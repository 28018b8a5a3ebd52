"""Serve, kernels: the flash forward kernel's share of its roofline in a
model that also has window layers, in percent — the least time the chip
could take for causal attention over each scored document's own triangle in
the full layers alone (`swa_work.full_work`, the real lengths of the
documents in the traced stretch's device calls) over the device time of the
kernel `flash_fwd` there (by name: `flash_fwd_window` is the window layers'
and is left out)."""

from benchmarks import dsa_work, swa_work


def read(run):
    return dsa_work.kernel_roofline(run, swa_work.FULL_KERNEL,
                                    swa_work.full_work)
