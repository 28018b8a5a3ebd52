"""Serve, from inside: mean milliseconds of the handle's round trip to the
controller for the replica list (`get(get_replicas.remote(...))`), made once
a second on the thread of the caller whose request found the list stale
(`rtpu_serve_handle_refresh_seconds`, span `serve::refresh`). Over the job:
warm-up, window and traced stretch (`serve_counters`)."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.mean_ms(run, "rtpu_serve_handle_refresh_seconds")
