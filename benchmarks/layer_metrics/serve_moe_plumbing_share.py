"""Serve, model step: percent of the traced stretch's busy device time under
the scopes `moe_router`, `moe_dispatch` and `moe_combine` of
`models/moe.py` — the expert block without its experts: the router and the
choice, the sort by expert and the rows gathered into that order, the rows
summed back a token — all programs of the stretch (`moe_scopes.of_run`, the
raw trace's name-stack paths) over `busy_s`."""

from benchmarks import moe_scopes


def read(run):
    reduced = run.get("trace")
    seconds = moe_scopes.of_run(run)
    if not seconds or not reduced or reduced["busy_s"] <= 0:
        return None
    return 100.0 * sum(seconds.values()) / reduced["busy_s"]
