"""Serve, kernels: the sparse attention kernel's share of its roofline, in
percent — the least time the chip could take for attention over the chosen
(query, key) pairs of each scored document (`dsa_work.attend_work`: sum over
t of min(t + 1, topk) pairs, two products each, the real lengths of the
documents in the traced stretch's device calls) over the device time of the
kernel `dsa_attend_fwd` in those calls. The rectangles walked under a mask
are not counted: where the choice is spread evenly (seeded weights) the
kernel walks the dense causal triangle and this reads what of it was
needed."""

from benchmarks import dsa_work


def read(run):
    return dsa_work.kernel_roofline(run, dsa_work.ATTEND_KERNEL,
                                    dsa_work.attend_work)
