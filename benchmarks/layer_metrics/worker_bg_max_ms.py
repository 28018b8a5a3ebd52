"""Device layer: the longest activation, in milliseconds, of a periodic
background thread in the worker that holds the chips — the upper edge of the
highest non-empty bucket of `rtpu_worker_background_seconds` (the telemetry
flusher's `sample_devices()` and `flush()`, each timed apart in
`telemetry._flush_loop`). Wall time of a thread that shares the GIL with the
training loop: an upper bound on what one activation can take from it. Falls
back to every worker's series if none is tagged with chips."""

from benchmarks import program_counters

NAME = "rtpu_worker_background_seconds"


def read(run):
    rows = program_counters.matching(NAME)
    granted = [r for r in rows if r["tags"].get("chips", "0") != "0"]
    edge = program_counters.top_edge(NAME, granted or rows)
    return None if edge is None else 1e3 * edge
