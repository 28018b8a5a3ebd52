"""Serve, kernels: the routed experts' grouped matmuls' share of their
roofline, forward only, in percent — the least time the chip could take for
three products a (token, expert) pair of the real tokens of the documents in
the traced stretch's device calls (`swa_work.expert_matmul_work`: 6 x d x f
a pair, every routed layer; the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, each call reading every expert's matrices once) over the
device time of the grouped-matmul operations (`ragged-dot*`, by name) in
those calls. Padded rows and tails pass through the matmuls and are not
counted."""

from benchmarks import swa_work


def read(run):
    return swa_work.grouped_matmul_roofline(run)
