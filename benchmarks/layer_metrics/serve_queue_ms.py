"""Serve: milliseconds from the arrival in the replica to the start of the
batch that took the request (`serve/batching.py`'s collector: the batch
before it, then the collection window), the median over the window's
answered requests. Host clock on one machine (`time.time()` in both
processes)."""


def read(run):
    return run["window"]["queue_ms"]
