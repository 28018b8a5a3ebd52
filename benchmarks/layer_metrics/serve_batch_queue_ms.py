"""Serve, from inside: median milliseconds a request waited in the
`@serve.batch` queue, its `submit` -> its batch closed, on the replica's
monotonic clock (`rtpu_serve_batch_queue_seconds`, one record a member).
Over the job — warm-up, window and traced stretch — where `serve_queue_ms`,
its twin from outside, is the window's (`serve_counters`)."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.median_ms(run, "rtpu_serve_batch_queue_seconds")
