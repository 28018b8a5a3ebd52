"""Kernels: device milliseconds a step in `flash_bwd_dkv`, the backward
kernel for dk and dv (`pl.pallas_call(name="flash_bwd_dkv")` in
`ops/attention.py`)."""

from benchmarks import program_trace


def read(run):
    return program_trace.kernel_ms(run, "flash_bwd_dkv")
