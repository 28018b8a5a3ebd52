"""Serve, from inside: median milliseconds from the collector's stamp
before it resolves a batch's futures to the member's pool thread back from
`fut.result()` — the futures ahead of its own, then the GIL
(`rtpu_serve_batch_wake_seconds`). Over the job: warm-up, window and traced
stretch (`serve_counters`). The replica's first part of `serve_reply_ms`."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.median_ms(run, "rtpu_serve_batch_wake_seconds")
