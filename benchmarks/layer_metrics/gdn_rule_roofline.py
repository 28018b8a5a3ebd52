"""Kernels: the gated delta rule's share of its roofline, in percent — the
least time the chip could take for the rule over all linear layers of one
step (`hybrid_work.delta_rule_work`: the larger of FLOPs over the bf16 peak
and bytes over the HBM peak; on a v5e the bytes bound it) over the device
time under the scope `gdn_rule`, the forward recomputed under remat
included."""

from benchmarks import flops, hybrid_work


def read(run):
    took, peaks = hybrid_work.scope_seconds(run, ("gdn_rule",)), run["peaks"]
    if not took or not peaks:
        return None
    cell = run["cell"]
    work = hybrid_work.delta_rule_work(
        cell["config"]["model"],
        cell["config"]["batch_per_chip"] * cell["traffic"]["seq_len"])
    return 100.0 * flops.roofline_seconds(work, peaks)["seconds"] / took
