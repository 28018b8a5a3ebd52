"""Serve, from inside: mean milliseconds a batch the collector spent on
`set_result` / `set_exception` of its members' futures, each of which wakes
a pool thread under the one GIL (`rtpu_serve_batch_seconds{phase=resolve}`,
span `serve::batch_resolve`). A sum over the job: warm-up, window and traced
stretch (`serve_counters`). Part of `bench:batcher_collect` and of
`serve_reply_ms`."""

from benchmarks import serve_counters


def read(run):
    return serve_counters.mean_ms(run, serve_counters.BATCH_SECONDS,
                                  phase="resolve")
