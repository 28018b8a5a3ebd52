"""Data feed, from inside: mean milliseconds `iter_device_batches` spent
turning one batch into jax Arrays, `asarray` and `device_put`
(`rtpu_data_feed_to_device_seconds`, span `data::to_device`)."""

from benchmarks import program_counters


def read(run):
    seconds = program_counters.mean("rtpu_data_feed_to_device_seconds")
    return None if seconds is None else 1e3 * seconds
