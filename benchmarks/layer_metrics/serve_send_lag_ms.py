"""Serve, client: how late the load generator ran — milliseconds from a
request's due moment to its client thread's call, the 99th percentile over
the window's requests. It is inside every latency (a request is timed from
when it was due); large, the client pool and not the system was the queue."""


def read(run):
    return run["window"]["send_lag_p99_ms"]
