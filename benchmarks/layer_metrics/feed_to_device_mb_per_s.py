"""Data feed, from inside: megabytes of device batches a second of
`iter_device_batches`' `asarray` + `device_put` —
`rtpu_data_feed_bytes_total` over the busy seconds of
`rtpu_data_feed_to_device_seconds`. Tells a feed whose batches grew from one
whose copies slowed, which `feed_to_device_ms` alone cannot."""

from benchmarks import program_counters


def read(run):
    fed = program_counters.total("rtpu_data_feed_bytes_total")
    seconds, _ = program_counters.sum_count(
        "rtpu_data_feed_to_device_seconds")
    return fed / seconds / 1e6 if fed and seconds else None
