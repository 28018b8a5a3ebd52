"""Serve, from inside: the longest `handle.remote()` of the job on the
caller's thread, in milliseconds — the upper edge of the highest non-empty
bucket of `rtpu_serve_handle_route_seconds` (span `serve::route`: the
replica list's refresh when due, the pick, the actor call's submission).
Over the job: warm-up, window and traced stretch (`serve_counters`). The
stall inside `serve_send_lag_ms`."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.top_edge_ms(run,
                                      "rtpu_serve_handle_route_seconds")
