"""The start of a run from inside the program: jax's compile path by stage
and function, and the worker's start by phase.

The program's telemetry counts every trace, lowering and backend compile jax
reports (`ray_tpu/_private/telemetry.py::install_jax_listeners`: the series
`rtpu_jax_compile_seconds{stage, fun, cache}`, each span's own time — its
duration less the jax spans nested inside it, so a stage's sum over every
function is wall time spent in that stage), and the worker process times its
own start (`ray_tpu/_private/worker.py`: `rtpu_worker_start_seconds{phase,
chips}` and `rtpu_worker_load_code_seconds{kind, name}`). This module asks
`program_counters` for those rows; `program_counters.PREFIXES` does not hold
them, so it prints a progress line of its own, `{"kind": "program_compile",
...}`: the seconds and events of each stage, the cache's verdicts, the ten
largest (fun, stage, cache, seconds) and the start's rows. A program without
the series (the parent of the PR that added them) gives None everywhere.
"""

from __future__ import annotations

import collections
import json
from typing import Iterable, List, Optional

from benchmarks import program_counters
from benchmarks.program_counters import Row

COMPILE = "rtpu_jax_compile_seconds"
CACHE_RETRIEVAL = "rtpu_jax_cache_retrieval_seconds"
WORKER_START = "rtpu_worker_start_seconds"
LOAD_CODE = "rtpu_worker_load_code_seconds"
STAGES = ("trace", "lower", "backend_compile")
START_PHASES = ("runtime", "first_task")
LARGEST = 10

_said = False


def _table(table: Optional[Iterable[Row]]) -> List[Row]:
    """The run's table; the first time it is the run's own, say what it
    holds of the start."""
    global _said
    if table is not None:
        return list(table)
    rows = program_counters.rows()
    if not _said:
        _said = True
        print(json.dumps(summary(rows)), flush=True)
    return rows


def summary(table: Iterable[Row]) -> dict:
    table = list(table)
    compiles = program_counters.matching(COMPILE, table)
    by_cache = collections.Counter()
    for r in compiles:
        if "cache" in r["tags"]:
            by_cache[r["tags"]["cache"]] += r["count"]
    return {
        "kind": "program_compile",
        "stages": {stage: {"seconds": stage_seconds(stage, table),
                           "events": sum(r["count"] for r in compiles
                                         if r["tags"].get("stage") == stage)}
                   for stage in STAGES} if compiles else None,
        "cache": dict(by_cache) or None,
        "cache_retrieval_s": program_counters.sum_count(
            CACHE_RETRIEVAL, table)[0],
        "largest": [
            {"fun": r["tags"].get("fun"), "stage": r["tags"].get("stage"),
             "cache": r["tags"].get("cache"), "seconds": r["sum"],
             "events": r["count"]}
            for r in sorted(compiles, key=lambda r: -r["sum"])[:LARGEST]],
        "start": [{"name": r["name"], "tags": r["tags"], "sum": r["sum"],
                   "count": r["count"]}
                  for r in table if r["name"] in (WORKER_START, LOAD_CODE)],
    }


def stage_seconds(stage: str, table: Optional[Iterable[Row]] = None
                  ) -> Optional[float]:
    """Wall seconds the run's processes spent in one stage of jax's compile
    path: the sum of the own times of every function's spans of that stage.
    None unless the program records the series."""
    table = _table(table)
    if not program_counters.matching(COMPILE, table):
        return None
    return program_counters.sum_count(COMPILE, table, stage=stage)[0]


def cache_misses(table: Optional[Iterable[Row]] = None) -> Optional[int]:
    """Backend compiles that XLA made and wrote to the persistent cache: 0
    on a start the cache carries whole. None unless the program records the
    series."""
    table = _table(table)
    if not program_counters.matching(COMPILE, table):
        return None
    return program_counters.sum_count(COMPILE, table, stage="backend_compile",
                                      cache="miss")[1]


def _granted(rows: List[Row]) -> List[Row]:
    """The rows of the processes started for a task that holds chips (the
    train workers of a granted gang); every row where none is."""
    return [r for r in rows if r["tags"].get("chips", "0") != "0"] or rows


def worker_register_seconds(table: Optional[Iterable[Row]] = None
                            ) -> Optional[float]:
    """Seconds from a train worker's `main()` to its first task's arrival,
    the mean over the workers: the two phases of
    `rtpu_worker_start_seconds`. None unless both were recorded."""
    table = _table(table)
    rows = _granted(program_counters.matching(WORKER_START, table))
    means = [program_counters.mean(WORKER_START, rows, phase=phase)
             for phase in START_PHASES]
    return None if None in means else sum(means)


def class_load_seconds(name: str, table: Optional[Iterable[Row]] = None
                       ) -> Optional[float]:
    """Seconds a worker spent unpickling the actor class `name` (span
    `worker::load_code`), the mean over the workers that loaded it."""
    # (`name` is `matching`'s own first parameter: that tag is matched here)
    rows = [r for r in program_counters.matching(LOAD_CODE, _table(table),
                                                 kind="actor_class")
            if r["tags"].get("name") == name]
    return program_counters.mean(LOAD_CODE, rows)
