"""Data feed: median host span around `next(batches)` in the window (queue
actor round trip, object fetch, host-to-device copy)."""


def read(run):
    span = run["spans"].get("batch_wait")
    return span["median_ms"] if span else None
