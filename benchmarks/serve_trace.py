"""What the serve batcher was doing while the device idled, from inside.

`trace_reduce.py` labels an idle gap of a served cell's traced stretch with
the benchmark's own span, and between two batches that span is one:
`bench:batcher_collect`, from the batch function's exit to its next entry.
`ray_tpu/serve/batching.py` tiles its collector's thread with four spans of
its own (PERF.md section 3): `serve::batch_wait` (blocked, no request to
take: the device is starved), `serve::batch_fill` (first request taken ->
batch closed), `serve::batch_call` (the deployment's function: padding,
dispatch, the fetch) and `serve::batch_resolve` (the futures). This module
reads them as `program_trace.analyse` reads the train path's: the run's raw
`.xplane.pb`, the gaps between the device's operations from the first to the
last start of a `score_bucket` program, host lines moved onto the device's
clock (`program_trace.host_clock_offset`), and the idle nanoseconds under
each piece of `program_trace.innermost_segments` over the spans whose name
starts `rtpu:serve::`. The replica's pool threads each sit in an
`rtpu:actor_call::` span for the whole stretch; they are not counted.

Parsed once per process; prints one progress line, `{"kind": "serve_trace",
...}`. A program without the spans (the parent of the PR that added them)
gives no collector line, and every reader None.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmarks import program_trace, trace_reduce

STEP_MODULE = "score_bucket"    # as `loops/serve.py` names its programs
SPAN_PREFIX = program_trace.SPAN_PREFIX + "serve::"
PHASES = ("batch_wait", "batch_fill", "batch_call", "batch_resolve")


def analyse(planes: List[Dict[str, Any]], step_module: str = STEP_MODULE
            ) -> Optional[Dict[str, Any]]:
    """See the module's text. None if the trace holds no device plane with
    two executions of a bucket's program."""
    devices = sorted(
        (p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(trace_reduce.DEVICE_PLANE.match(
            p["name"]).group(2)))
    for plane in devices:
        by_name = {line["name"]: line["events"] for line in plane["lines"]}
        modules = by_name.get(trace_reduce.MODULES_LINE, ())
        starts = sorted(e[1] for e in modules if step_module in e[0])
        if len(starts) >= 2:
            break
    else:
        return None
    lo, hi = starts[0], starts[-1]
    ops = by_name.get(trace_reduce.OPS_LINE, [])
    busy = trace_reduce.clipped(
        trace_reduce.merge((s, s + d) for _, s, d, _ in ops), lo, hi)
    gaps = trace_reduce._gaps(busy, lo, hi)
    gap_ends = [b for _, b in gaps]
    idle_ns = sum(b - a for a, b in gaps)
    offset_ns, completion_lag_ms = program_trace.host_clock_offset(
        planes, modules)

    idle = dict.fromkeys(PHASES, 0.0)
    other: Dict[str, float] = {}    # serve:: spans that are no phase
    threads, spans_found = [], 0
    covered: List[Tuple[float, float]] = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans = [(e[0][len(SPAN_PREFIX):], e[1] - offset_ns, e[2], e[3])
                     for e in line["events"]
                     if e[0].startswith(SPAN_PREFIX)]
            if not any(name in idle for name, *_ in spans):
                continue        # no collector's line
            threads.append(f"{line['name']}/{line['id']}")
            spans_found += len(spans)
            for a, b, name in program_trace.innermost_segments(spans):
                under = program_trace._overlap([(a, b)], gaps, gap_ends)
                table = idle if name in idle else other
                table[name] = table.get(name, 0.0) + under / 1e9
            covered += [(s, s + d) for _, s, d, _ in spans]
    named_s = sum(idle.values()) + sum(other.values())
    in_stretch = trace_reduce.clipped(trace_reduce.merge(covered), lo, hi)
    return {
        "stretch_s": (hi - lo) / 1e9, "idle_s": idle_ns / 1e9,
        "idle_s_by_phase": idle, "idle_s_under_other_spans": other,
        "idle_s_under_none": idle_ns / 1e9 - named_s,
        # of the stretch, on the collector's line, under one of its spans
        "collector_cover_share":
            100.0 * sum(b - a for a, b in in_stretch) / (hi - lo)
            if spans_found else None,
        "collector_threads": threads, "collector_spans": spans_found,
        "host_clock_offset_s": offset_ns / 1e9,
        "completion_lag_ms_max": completion_lag_ms,
    }


_cache: Dict[str, Optional[Dict[str, Any]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The analysis of this run's raw trace, still under the run's storage
    while the readers run; None without one. Never raises."""
    name = run["cell"]["name"]
    if not run.get("trace"):
        return None
    if name not in _cache:
        t0 = time.perf_counter()
        result, error = None, None
        try:
            path = program_trace.trace_file(name)
            if path:
                with open(path, "rb") as f:
                    result = analyse(program_trace.read_xspace(f.read()))
        except Exception as e:      # noqa: BLE001 — a reader never raises
            error = repr(e)
        _cache[name] = result
        print(json.dumps({"kind": "serve_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def idle_share(run: Dict[str, Any], phases: Tuple[str, ...]
               ) -> Optional[float]:
    """Percent of the traced stretch's idle device time under the
    collector's spans `phases`; None unless the program opened them."""
    trace = of_run(run)
    if not trace or not trace["collector_spans"] or trace["idle_s"] <= 0:
        return None
    return 100.0 * sum(trace["idle_s_by_phase"][p]
                       for p in phases) / trace["idle_s"]
