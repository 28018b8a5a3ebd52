"""Kernels: the Pallas flash-attention calls' share of the step's device
time, in percent. The calls are the trace's custom calls (the step has no
other): forward, the forward recomputed under remat, dq and dkv."""

import statistics

from benchmarks import trace_reduce


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    step_s = statistics.median(trace["step_device_ms"]) / 1e3
    return 100.0 * trace_reduce.op_seconds_per_step(
        trace, trace_reduce.PALLAS_CALLS) / step_s
