"""Kernels: device milliseconds a step in `flash_bwd`, the backward kernel
that makes a rectangle's p and ds once for dq, dk and dv
(`pl.pallas_call(name="flash_bwd")` in `ops/attention.py`, PR 38). Nothing
where the step runs the pair `flash_bwd_dq` + `flash_bwd_dkv` in its place
(a row whose dq accumulator does not fit in VMEM, and every program before
PR 38)."""

from benchmarks import program_trace


def read(run):
    return program_trace.kernel_ms(run, "flash_bwd")
