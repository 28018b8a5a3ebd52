"""Serve, from inside: mean milliseconds a batch that `batching.py`'s
collector sat blocked with no request to take — the device starved of
arrivals (`rtpu_serve_batch_seconds{phase=wait}`, span `serve::batch_wait`).
A sum over the job: warm-up, window and traced stretch (`serve_counters`).
Part of what `bench:batcher_collect` labels from outside."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.mean_ms(run, serve_counters.BATCH_SECONDS,
                                  phase="wait")
