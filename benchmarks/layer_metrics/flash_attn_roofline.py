"""Kernels: the flash-attention calls' share of their roofline, in percent —
the least time the chip could take for causal attention over the layers of
one step that attend (`flops.flash_attention_work`, from the configuration's
own geometry: the larger of FLOPs over the bf16 peak and bytes over the HBM
peak; at these shapes compute bounds it) over the device time of the kernels
named `flash_*`, the forward recomputed under remat included."""

from benchmarks import flops, program_trace


def read(run):
    took, peaks = program_trace.kernels_seconds(run, "flash_"), run["peaks"]
    if not took or not peaks:
        return None
    cell = run["cell"]
    work = flops.flash_attention_work(
        cell["config"]["model"], cell["traffic"]["seq_len"],
        cell["config"]["batch_per_chip"])
    return 100.0 * flops.roofline_seconds(work, peaks)["seconds"] / took
