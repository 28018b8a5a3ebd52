"""Checkpoint layer: seconds of the traced save in which the copy of a leaf
from the device to the host was in progress — the runtime's own
`np.asarray(jax.Array)` events inside the span `checkpoint::orbax_save`, on
the profiler's host lines. The rest of `ckpt_write_s` is serialisation and
the write."""

from benchmarks import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace["save_d2h_s"] if trace else None
