"""Kernels: the expert matmuls' share of their roofline, in percent — the
least time the chip could take for the routed experts' matmuls of one step
(`moe_work.expert_matmul_work`: the larger of FLOPs over the bf16 peak and
bytes over the HBM peak; at a deployment's tokens per expert compute bounds
it) over the device time of the grouped-matmul operations (`ragged-dot*`,
by name), those recomputed under remat included."""

from benchmarks import flops, moe_work


def read(run):
    trace, peaks = moe_work.of_run(run), run["peaks"]
    if not trace or not peaks or trace["expert_matmul_s_per_step"] <= 0:
        return None
    cell = run["cell"]
    work = moe_work.expert_matmul_work(
        cell["config"]["model"],
        cell["config"]["batch_per_chip"] * cell["traffic"]["seq_len"])
    return (100.0 * flops.roofline_seconds(work, peaks)["seconds"]
            / trace["expert_matmul_s_per_step"])
