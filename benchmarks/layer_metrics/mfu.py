"""Model step: model FLOP/s utilization in percent — the window's
`tokens_per_s_per_chip` (tokens a step over the median time between step
completions) x model FLOPs per token (`flops.py`, causal attention,
recomputation not counted) over the chip's bf16 peak (`peaks.json`). The rate
is the steps' own: neither saves nor host stalls are in it."""


def read(run):
    if not run["peaks"]:
        return None
    return (100.0 * run["window"]["tokens_per_s_per_chip"]
            * run["flops_per_token"] / run["peaks"]["bf16_flops_per_s"])
