"""Kernels: device milliseconds a step in `flash_fwd`, the forward kernel,
its recomputed runs under remat included (`pl.pallas_call(name="flash_fwd")`
in `ops/attention.py`)."""

from benchmarks import program_trace


def read(run):
    return program_trace.kernel_ms(run, "flash_fwd")
