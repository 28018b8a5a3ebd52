"""Serve, from inside: mean milliseconds a batch from the collector's first
request taken to the batch closed — `max_batch_size` reached or
`batch_wait_timeout_s` run out (`rtpu_serve_batch_seconds{phase=fill}`, span
`serve::batch_fill`). A sum over the job: warm-up, window and traced stretch
(`serve_counters`). Part of what `bench:batcher_collect` labels from
outside."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.mean_ms(run, serve_counters.BATCH_SECONDS,
                                  phase="fill")
