"""The serve layer's own series, as its per-layer readers want them.

`ray_tpu/serve` times itself (PERF.md section 3): the collector's phases
(`rtpu_serve_batch_seconds{phase}`), a member's waits into its batch and back
out of it, the replica's wait for a pool thread, the handle's routing and its
refresh. Every reader here goes through `program_counters` (the control
plane's merged table, read once per process), so the numbers are **sums over
the job** — warm-up, window and traced stretch; the control requests never
reach the batcher — and not the window's own: a window's reading waits for a
snapshot at its edges (ROADMAP S9). A program without a series gives None.

Prints one progress line, `{"kind": "serve_counters", ...}`: every
`rtpu_serve_*` series with count, sum and p50 / p99 (a digest) or top edge (a
histogram), and two checks that cost nothing: the batches the collector
called and the requests a batch, beside the window's own
`serve_batch_rows_mean`.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

from benchmarks import program_counters

PREFIX = "rtpu_serve_"
BATCH_SECONDS = "rtpu_serve_batch_seconds"
BATCH_QUEUE = "rtpu_serve_batch_queue_seconds"

_said = False


def say_once(run: Dict[str, Any]) -> None:
    global _said
    if _said:
        return
    _said = True
    series = []
    for r in program_counters.rows():
        if not r["name"].startswith(PREFIX):
            continue
        row = {k: r[k] for k in ("name", "tags", "value", "sum", "count",
                                 "top_edge", "max") if k in r}
        if r["kind"] == "digest":
            row.update(p50=r["quantiles"]["p50"], p99=r["quantiles"]["p99"])
        series.append(row)
    _, batches = program_counters.sum_count(BATCH_SECONDS, phase="call")
    _, members = program_counters.sum_count(BATCH_QUEUE)
    print(json.dumps({
        "kind": "serve_counters", "series": series, "batches": batches,
        "requests_a_batch": members / batches if batches else None,
        "window_batch_rows_mean":
            (run.get("window") or {}).get("batch_requests_mean")}),
        flush=True)


def mean_ms(run: Dict[str, Any], name: str, **tags: str) -> Optional[float]:
    """Mean milliseconds an observation of a histogram series: a batch in
    one `phase` of the collector, a refresh."""
    say_once(run)
    seconds = program_counters.mean(name, **tags)
    return None if seconds is None else 1e3 * seconds


def top_edge_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    say_once(run)
    edge = program_counters.top_edge(name)
    return None if edge is None else 1e3 * edge


def median_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """The median of a digest series, of the deployment that recorded most
    (a served cell runs one)."""
    say_once(run)
    found = [r for r in program_counters.matching(name)
             if r["kind"] == "digest" and r.get("count")]
    if not found:
        return None
    return 1e3 * max(found, key=lambda r: r["count"])["quantiles"]["p50"]
