"""Checkpoint layer, worker side: gigabytes of pytree leaves a second of
`Checkpoint.from_pytree` — `rtpu_checkpoint_save_bytes_total` over the busy
seconds of `rtpu_checkpoint_save_seconds`, over the job's saves. What an
asynchronous or faster save (R5) must raise, whatever the state's size."""

from benchmarks import program_counters


def read(run):
    saved = program_counters.total("rtpu_checkpoint_save_bytes_total")
    seconds, _ = program_counters.sum_count("rtpu_checkpoint_save_seconds")
    return saved / seconds / 1e9 if saved and seconds else None
