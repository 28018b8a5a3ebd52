"""Serve, kernels: the windowed flash forward kernel's share of its
roofline, in percent — the least time the chip could take for attention over
the band of each scored document (`swa_work.window_work`: sum over t of
min(t + 1, window) pairs, two products each, the real lengths of the
documents in the traced stretch's device calls, all window layers) over the
device time of the kernel `flash_fwd_window` in those calls. The masked half
of an edge rectangle, padded rows and tails are not counted."""

from benchmarks import dsa_work, swa_work


def read(run):
    return dsa_work.kernel_roofline(run, swa_work.WINDOW_KERNEL,
                                    swa_work.window_work)
