"""Serve, from inside: median milliseconds from the handle's stamp
(`handle._request_meta`, the caller's `time.time()`) to the start of the
request's execution on one of the replica's pool threads
(`rtpu_serve_queue_wait_digest_seconds`, recorded since PR 13 in
`replica._begin_request`). Two processes' wall clocks on one host. Over the
job — warm-up, window and traced stretch — where `serve_ingress_ms`, its twin
from outside, is the window's (`serve_counters`)."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.median_ms(
        run, "rtpu_serve_queue_wait_digest_seconds")
