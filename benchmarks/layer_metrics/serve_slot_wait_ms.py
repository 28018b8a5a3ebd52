"""Serve, from inside: median milliseconds from the arrival of a request's
frame in the replica's worker (`worker._enqueue_execute`) to the start of its
execution on a pool thread: the wait for one of `max_concurrent_queries`
slots, on one process's monotonic clock
(`rtpu_serve_replica_slot_wait_seconds`). Over the job: warm-up, window and
traced stretch (`serve_counters`). The replica-local part of
`serve_ingress_ms`."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.median_ms(
        run, "rtpu_serve_replica_slot_wait_seconds")
