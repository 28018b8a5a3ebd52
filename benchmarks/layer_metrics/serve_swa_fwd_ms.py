"""Serve, kernels: device milliseconds a bucket's program spends in
`flash_fwd_window`, the flash forward walk of a window's band
(`ops/attention.py`: the rectangles older than a query's window are dead
beside those after the diagonal, and no copy is issued for a dead grid
step), all window layers, mean over the programs of the traced stretch
(`trace_reduce`'s table of operations, by the name `pl.pallas_call` gave the
kernel)."""

from benchmarks import swa_work


def read(run):
    return swa_work.kernel_ms(run, swa_work.WINDOW_KERNEL)
