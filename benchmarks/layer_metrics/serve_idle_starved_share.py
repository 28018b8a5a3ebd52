"""Serve, from inside: percent of the traced stretch's idle device time
during which the collector's thread sat in `rtpu:serve::batch_wait` —
blocked with no request to take: the arrivals starved the device
(`serve_trace`, host lines on the device's clock). What
`breakdown.idle_gaps` labels `batcher_collect` from outside, told apart."""

from benchmarks import serve_trace


def read(run):
    return serve_trace.idle_share(run, ("batch_wait",))
