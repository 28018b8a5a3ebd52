"""Serve: the window's forward FLOP/s utilization in percent — forward FLOPs
the model needs for the real tokens of the requests answered inside the
window (`flops.forward_flops`: each document's own causal triangle, padding
never counted) over the window's seconds, the chips and the chip's bf16 peak
(`peaks.json`). Host clock, whole window: idle stretches between arrivals,
padding and the host path are all in it."""


def read(run):
    window, peaks = run["window"], run["peaks"]
    if not peaks or not window["seconds"]:
        return None
    return (100.0 * window["forward_flops_answered"] / window["seconds"]
            / run["cell"]["chips"] / peaks["bf16_flops_per_s"])
