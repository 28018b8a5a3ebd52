"""Serve, kernels: the indexer kernel's share of its roofline, in percent —
the least time the chip could take for the index scores of every causal pair
of each scored document (`dsa_work.index_work`, the real lengths of the
documents in the traced stretch's device calls; the larger of FLOPs over the
bf16 peak and bytes over the HBM peak; padded rows and tails, the cut's
passes and the keys after a query inside a tile are not work the requests
need) over the device time of the kernel `dsa_index` in those calls. The
score products are 64 deep, half of the matrix unit's 128: 50% is the most
they can read."""

from benchmarks import dsa_work


def read(run):
    return dsa_work.kernel_roofline(run, dsa_work.INDEX_KERNEL,
                                    dsa_work.index_work)
