"""The telemetry table of a run, as the per-layer readers want it.

The program counts its own layer boundaries (`ray_tpu/_private/telemetry.py`
series recorded by the train path: see PERF.md section 3). This module asks
for the table once per process — the control plane's merged table, which
holds the worker's series and, flushed first, the driver's own; with no
runtime connected, this process's `snapshot_local()` — and answers by name
and tags: a histogram's `sum`, `count` and the upper edge of its highest
non-empty bucket, a counter's value. A program that has no such series (the
parent of the PR that added them) gives None everywhere.

Prints one progress line, `{"kind": "program_counters", ...}`, with every
train-path series it found.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Tuple

PREFIXES = ("rtpu_train_", "rtpu_data_feed_", "rtpu_checkpoint_",
            "rtpu_worker_background_")

Row = Dict[str, Any]
_rows: Optional[List[Row]] = None


def shape(snapshot: Dict[str, Any]) -> List[Row]:
    """A telemetry snapshot (`snapshot_local()` or the control plane's) as
    the rows of `state.api.shape_metrics`, a histogram's with `top_edge`
    added: the upper edge of the highest bucket that holds an observation;
    for the overflow bucket the series' sum, which bounds any one of them."""
    from ray_tpu.state.api import shape_metrics
    rows = shape_metrics(snapshot)
    for row in rows:
        if row["kind"] == "histogram":
            below = 0
            row["top_edge"] = None
            for edge, cumulative in row["buckets"]:
                if cumulative > below:
                    row["top_edge"] = edge
                below = cumulative
            if row["count"] > below:
                row["top_edge"] = float(row["sum"])
    return rows


def _snapshot() -> Dict[str, Any]:
    from ray_tpu._private import context, telemetry
    if context.current_client is not None:
        try:
            return context.current_client.state_query("metrics", None) or {}
        except Exception:   # noqa: BLE001 — a reader never raises
            pass
    return telemetry.snapshot_local()


def rows() -> List[Row]:
    global _rows
    if _rows is None:
        try:
            _rows = shape(_snapshot())
        except Exception:   # noqa: BLE001 — no program, no table
            _rows = []
        print(json.dumps({"kind": "program_counters", "series": [
            {k: r[k] for k in ("name", "tags", "value", "sum", "count",
                               "top_edge") if k in r}
            for r in _rows if r["name"].startswith(PREFIXES)]}), flush=True)
    return _rows


def matching(name: str, table: Optional[Iterable[Row]] = None,
             **tags: str) -> List[Row]:
    return [r for r in (rows() if table is None else table)
            if r["name"] == name
            and all(r["tags"].get(k) == v for k, v in tags.items())]


def sum_count(name: str, table: Optional[Iterable[Row]] = None,
              **tags: str) -> Tuple[float, int]:
    found = matching(name, table, **tags)
    return (sum(r.get("sum", 0.0) for r in found),
            sum(r.get("count", 0) for r in found))


def mean(name: str, table: Optional[Iterable[Row]] = None,
         **tags: str) -> Optional[float]:
    """Busy seconds per activation of a histogram series."""
    total, count = sum_count(name, table, **tags)
    return total / count if count else None


def total(name: str, table: Optional[Iterable[Row]] = None,
          **tags: str) -> Optional[float]:
    found = [r for r in matching(name, table, **tags)
             if r["kind"] == "counter"]
    return sum(r["value"] for r in found) if found else None


def top_edge(name: str, table: Optional[Iterable[Row]] = None,
             **tags: str) -> Optional[float]:
    edges = [r["top_edge"] for r in matching(name, table, **tags)
             if r.get("top_edge") is not None]
    return max(edges) if edges else None


GANG_START = "rtpu_train_gang_start_seconds"
GANG_PHASES = ("spawn", "load", "run_wait")


def gang_phase_seconds(phases: Iterable[str] = GANG_PHASES,
                       table: Optional[Iterable[Row]] = None
                       ) -> Optional[float]:
    """Seconds of a train worker's start spent in `phases` (each observed
    once in every worker: the mean over the workers), None unless the
    program recorded every one of them."""
    table = rows() if table is None else list(table)
    means = [mean(GANG_START, table, phase=phase) for phase in phases]
    return None if None in means or not means else sum(means)
