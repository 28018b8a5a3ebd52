"""Kernels: device milliseconds a step in `flash_bwd_dq`, the backward kernel
for dq (`pl.pallas_call(name="flash_bwd_dq")` in `ops/attention.py`)."""

from benchmarks import program_trace


def read(run):
    return program_trace.kernel_ms(run, "flash_bwd_dq")
