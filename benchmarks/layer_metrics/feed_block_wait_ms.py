"""Data feed, from inside: milliseconds a device batch waited for its block
— the shard queue's `get` and the object's fetch, both stages of
`rtpu_data_feed_wait_seconds` (spans `data::block_wait`, `data::block_get`
in `ray_tpu/data/iterator.py`) — over the job's batches
(`rtpu_data_feed_batches_total`), the job's first wait included."""

from benchmarks import program_counters


def read(run):
    seconds, _ = program_counters.sum_count("rtpu_data_feed_wait_seconds")
    batches = program_counters.total("rtpu_data_feed_batches_total")
    return 1e3 * seconds / batches if batches else None
