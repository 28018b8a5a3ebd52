"""Kernels: the flash-attention calls' share of their roofline, in percent —
the least time the chip could take for causal attention over all layers of
one step (`flops.flash_attention_work`: the larger of FLOPs over the bf16
peak and bytes over the HBM peak; at these shapes compute bounds it) over the
device time the calls took, the forward recomputed under remat included."""

from benchmarks import flops, trace_reduce


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks:
        return None
    took = trace_reduce.op_seconds_per_step(
        trace, trace_reduce.PALLAS_CALLS)
    if took <= 0:
        return None
    cell = run["cell"]
    work = flops.flash_attention_work(
        cell["config"]["model"], cell["traffic"]["seq_len"],
        cell["config"]["batch_per_chip"])
    return 100.0 * flops.roofline_seconds(work, peaks)["seconds"] / took
