"""Serve, from inside: percent of the traced stretch's idle device time
during which the collector's thread sat in `rtpu:serve::batch_fill` or
`rtpu:serve::batch_resolve` — it had a request and the device had nothing:
the most a collector that fills the next batch while one runs can win
(`serve_trace`, host lines on the device's clock). The rest of the idle time
is under `serve::batch_call` (the deployment's own host work), under
`serve::batch_wait` (`serve_idle_starved_share`) or under none."""

from benchmarks import serve_trace


def read(run):
    return serve_trace.idle_share(run, ("batch_fill", "batch_resolve"))
