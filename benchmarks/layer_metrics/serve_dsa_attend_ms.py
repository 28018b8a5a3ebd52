"""Serve, kernels: device milliseconds a bucket's program spends in
`dsa_attend_fwd`, the flash forward walk that carries a choice of keys
(`ops/attention.py`: every rectangle of scores masked by the indexer's
choice, tiles without a chosen key skipped), all layers, mean over the
programs of the traced stretch (`trace_reduce`'s table of operations, by the
name `pl.pallas_call` gave the kernel)."""

from benchmarks import dsa_work, trace_reduce


def read(run):
    reduced = run["trace"]
    if not reduced:
        return None
    return 1e3 * trace_reduce.op_seconds_per_step(
        reduced, dsa_work.ATTEND_KERNEL) or None
