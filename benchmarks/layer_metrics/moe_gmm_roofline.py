"""Kernels: the expert matmuls' share of their roofline, in percent — the
least time the chip could take for the matmuls of the experts held here in
one step (`moe_work.expert_matmul_work` at the (token, expert) pairs the
steps themselves reported, `moe_work.pairs_per_step`: the median over the
window; the larger of FLOPs over the bf16 peak and bytes over the HBM peak;
at a deployment's tokens per expert compute bounds it) over the device time
of the grouped-matmul operations (`ragged-dot*`, by name), those recomputed
under remat included."""

from benchmarks import flops, moe_work


def read(run):
    trace, peaks = moe_work.of_run(run), run["peaks"]
    pairs = moe_work.pairs_per_step(run["window"])
    if (not trace or not peaks or not pairs
            or trace["expert_matmul_s_per_step"] <= 0):
        return None
    work = moe_work.expert_matmul_work(run["cell"]["config"]["model"], pairs)
    return (100.0 * flops.roofline_seconds(work, peaks)["seconds"]
            / trace["expert_matmul_s_per_step"])
