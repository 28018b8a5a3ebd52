"""Serve: the 99th percentile, in milliseconds, of return less due moment
over the window's requests (a request past `deadline_ms` counts at the
deadline) — recorded and not judged in a cell above the knee, where the
queue grows all through the run and the tail follows the smallest change."""


def read(run):
    return run["window"]["latency_p99_ms"]
