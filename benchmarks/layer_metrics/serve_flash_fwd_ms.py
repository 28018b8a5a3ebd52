"""Serve, kernels: device milliseconds a bucket's program spends in
`flash_fwd`, the forward kernel the training cells run, mean over the
programs of the traced stretch. Not `flash_fwd_ms`'s reader: that one keeps
to the operations of one program id (a training cell's step), and a served
stretch runs one program a bucket; this one sums the kernel's own time over
every program between the first and the last start (`trace_reduce`'s table
of operations)."""

from benchmarks import trace_reduce


def read(run):
    reduced = run["trace"]
    if not reduced:
        return None
    return 1e3 * trace_reduce.op_seconds_per_step(
        reduced, r"^flash_fwd") or None
