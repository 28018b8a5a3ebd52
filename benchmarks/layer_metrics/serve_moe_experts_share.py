"""Serve, model step: percent of the traced stretch's busy device time in
the routed experts themselves — the three grouped matmuls over the ragged
groups (`ragged-dot*`, found by name: they carry no scope) and what the
scope `moe_experts` of `models/moe.py` holds beside them (the SwiGLU
product), forward only, all programs of the stretch (`swa_work.of_run`, the
raw trace's name-stack paths) over `busy_s`."""

from benchmarks import swa_work


def read(run):
    reduced = run["trace"]
    seconds = swa_work.of_run(run)
    if not seconds or not reduced or reduced["busy_s"] <= 0:
        return None
    return (100.0 * (seconds["moe_experts"] + seconds["grouped_matmul"])
            / reduced["busy_s"])
