"""Kernels: device milliseconds a step in the gated delta rule
(`ops/delta_rule.py`, the scope `gdn_rule`) over all linear layers: forward,
the forward recomputed under remat, and backward."""

from benchmarks import hybrid_work


def read(run):
    seconds = hybrid_work.scope_seconds(run, ("gdn_rule",))
    return None if seconds is None else 1e3 * seconds
