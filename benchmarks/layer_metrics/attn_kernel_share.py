"""Kernels: the Pallas flash-attention calls' share of the step's device
time, in percent. The calls are the trace's kernels named `flash_*` by
`pl.pallas_call(name=...)` (`program_trace.kernels_seconds`): forward, the
forward recomputed under remat, and the backward — `flash_fwd_ms +
flash_bwd_ms` over `step_device_ms` in every cell since PR 38 (the prefix
reads the pair `flash_bwd_dq` / `flash_bwd_dkv` too, where a step runs it).
A step's other kernels (the grouped matmuls, the delta rule's pair) have
readers of their own."""

import statistics

from benchmarks import program_trace


def read(run):
    trace, took = run["trace"], program_trace.kernels_seconds(run, "flash_")
    if not trace or not took:
        return None
    return 100.0 * took / (statistics.median(trace["step_device_ms"]) / 1e3)
