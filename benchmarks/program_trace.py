"""What the program says about itself inside a profiler trace.

`trace_reduce.py` reads a trace from outside: device operations by name, idle
gaps labelled by the benchmark's `bench:` spans. This module reads what
`ray_tpu/` writes into the same file (PERF.md section 3):

- every device operation's scope and pass. The profiler keeps, in the
  metadata of each `XLA Ops` event, the stat `tf_op`: the operation's path
  through jax's name stack, `jit(train_step)/transpose(jvp())/while/body/
  closed_call/checkpoint/rematted_computation/mlp/.../dot_general:` (for a
  fusion, the path of its heaviest operation). The innermost
  `jax.named_scope` of `ray_tpu/models` in that path is the scope; `jvp(`,
  `transpose(` and `rematted_computation`, which jax writes itself, tell the
  forward, the backward and the recomputed forward apart. A fusion is
  filed whole under that one path, so a scope's total is robust (XLA fuses
  mostly within a block's stage) but the split by pass is approximate: a
  fusion that mixes recomputed and backward work counts under the heavier.
  Under "full" remat each scope's recomputed time should equal its forward
  time; where the table disagrees (`gpt2xl-fsdp4`: `mlp` 205 forward, 92
  recomputed) the difference was filed under backward, and
  `recompute_share` under-reads by about that much. `jax.profiler.
  ProfileData` does not hand out metadata stats, so the file is read with a
  small protobuf wire reader (`read_xspace`): no jax, no backend.
  A Pallas kernel is whatever `pl.pallas_call(name=...)` named it: its path
  ends `<name>/pallas_call:`, and the kernels of a trace are the names it
  holds. The TPU compiler's own grouped matmuls (`ragged-dot*`, what
  `lax.ragged_dot` becomes) carry that name in place of a path; they are
  filed under `mlp`, the only scope that issues them.
- every host thread's `rtpu:<span>` annotations (`util/tracing.start_span`),
  on the profiler's clock: idle device time is summed by the innermost open
  span of each thread.
- the runtime's own transfer events inside the save
  (`np.asarray(jax.Array)`: the device-to-host copy of one leaf).

Same window as `trace_reduce.reduce_trace`: first to last start of the step
program on the first device. Parsed once per process; prints one progress
line, `{"kind": "program_trace", ...}`.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import struct
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks import cells, trace_reduce

SCOPES = ("embed", "attn_qkv", "attn_kernel", "attn_out", "mlp",
          "head_loss", "optimizer")
UNSCOPED = "unscoped"
PASSES = ("forward", "recompute", "backward", "other")
# the grouped matmuls (forward, recomputed, both backward products): device
# operations the compiler names itself and strips of their path
GROUPED_MATMUL = re.compile(r"^ragged-dot")
SPAN_PREFIX = "rtpu:"
D2H_EVENT = "np.asarray(jax.Array)"
COMPLETION_EVENT = "CompleteCallbacks"     # the runtime's, with a `run_id`
SAVE_SPAN = SPAN_PREFIX + "checkpoint::orbax_save"

_TOKEN = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_KERNEL = re.compile(r"([A-Za-z_][A-Za-z0-9_]*)/pallas_call:?$")
_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


# ------------------------------------------------------ the file, by hand

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, bytes for a fixed one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, Any]:
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One XStat: (name, value). A `ref_value` names a stat metadata entry
    whose name is the string."""
    name, value = "", None
    for field, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, "")
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v
        elif field == 5:
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, "")
    return name, value


def read_xspace(data: bytes, wanted_stats: Sequence[str] = (
        "tf_op", "program_id", "run_id")
        ) -> List[Dict[str, Any]]:
    """The planes of a serialized XSpace: name and lines; a line has `name`,
    `id` and `events`, rows of (name, start ns, duration ns, stats), `stats`
    being those of the event and of its metadata that `wanted_stats` names
    (`tf_op` and `program_id` sit on the metadata of a device operation,
    `run_id` on the event of a program's execution). The schema is
    tsl's `xplane.proto`: XSpace.planes = 1; XPlane name 2, lines 3,
    event_metadata 4, stat_metadata 5; XLine id 1, name 2, timestamp_ns 3,
    events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3, stats 4;
    XEventMetadata name 2, display_name 4, stats 5; XStatMetadata name 2;
    XStat metadata_id 1 and one value field."""
    planes = []
    for field, plane_buf in _fields(memoryview(data)):
        if field != 1:
            continue
        name, lines, events_meta, stat_names = "", [], [], {}
        for f, v in _fields(plane_buf):
            if f == 2:
                name = _text(v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                events_meta.append(v)
            elif f == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (_text(x) for g, x in _fields(meta) if g == 2), "")
        metadata: Dict[int, Tuple[str, Dict[str, Any]]] = {}
        for entry in events_meta:
            key, meta = _map_entry(entry)
            event_name, display, stats = "", "", {}
            for g, x in _fields(meta):
                if g == 2:
                    event_name = _text(x)
                elif g == 4:
                    display = _text(x)
                elif g == 5:
                    stat_name, value = _stat(x, stat_names)
                    if stat_name in wanted_stats:
                        stats[stat_name] = value
            metadata[key] = (event_name or display, stats)
        out_lines = []
        for line_buf in lines:
            line_name, line_id, origin_ns, rows = "", 0, 0, []
            events = []
            for g, x in _fields(line_buf):
                if g == 2:
                    line_name = _text(x)
                elif g == 1:
                    line_id = x
                elif g == 3:
                    origin_ns = x
                elif g == 4:
                    events.append(x)
            for event_buf in events:
                meta_id = offset_ps = duration_ps = 0
                own: Dict[str, Any] = {}
                for g, x in _fields(event_buf):
                    if g == 1:
                        meta_id = x
                    elif g == 2:
                        offset_ps = x
                    elif g == 3:
                        duration_ps = x
                    elif g == 4:
                        stat_name, value = _stat(x, stat_names)
                        if stat_name in wanted_stats:
                            own[stat_name] = value
                event_name, stats = metadata.get(meta_id, ("", {}))
                if own:
                    stats = {**stats, **own}
                rows.append((event_name, origin_ns + offset_ps / 1e3,
                             duration_ps / 1e3, stats))
            out_lines.append({"name": line_name, "id": line_id,
                              "events": rows})
        planes.append({"name": name, "lines": out_lines})
    return planes


# -------------------------------------------------------- classification

def scope_of(path: str) -> str:
    """The innermost of the program's scopes in a name-stack path."""
    found = UNSCOPED
    for token in _TOKEN.findall(path or ""):
        if token in SCOPES:
            found = token
    return found


def pass_of(path: str) -> str:
    path = path or ""
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    if "jvp(" in path:
        return "forward"
    return "other"


def kernel_of(path: str) -> Optional[str]:
    """The `name=` of the `pl.pallas_call` a device operation is, if one."""
    found = _KERNEL.search(path or "")
    return found.group(1) if found else None


def innermost_segments(events: Sequence[Tuple[str, float, float, Any]]
                       ) -> List[Tuple[float, float, str]]:
    """The spans of one thread's line as disjoint (start, end, name) pieces,
    each named by the innermost span open in it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []     # (end, name)
    at = 0.0

    def close_until(limit: float) -> None:
        nonlocal at
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > at:
            out.append((at, start, stack[-1][1]))
        at = max(at, start) if stack else start
        stack.append((start + dur, name))
    close_until(float("inf"))
    return out


def _overlap(segments: Sequence[Tuple[float, float]],
             gaps: Sequence[Tuple[float, float]],
             gap_ends: Sequence[float]) -> float:
    """Nanoseconds of `gaps` (disjoint, sorted; `gap_ends` their ends)
    covered by `segments` (disjoint)."""
    total = 0.0
    for lo, hi in segments:
        k = bisect.bisect_right(gap_ends, lo)
        while k < len(gaps) and gaps[k][0] < hi:
            total += min(hi, gaps[k][1]) - max(lo, gaps[k][0])
            k += 1
    return total


def host_clock_offset(planes: Sequence[Dict[str, Any]],
                      modules: Sequence[Tuple[str, float, float, Any]]
                      ) -> Tuple[float, Optional[float]]:
    """Host lines and device lines of one trace do not share a clock to
    better than seconds (seen: the host 1.502 s ahead, steady to 0.2 ms over
    30 s). The runtime's completion callback for a program's execution
    (`run_id`) follows the end of that execution on the device at once, so
    the least (callback start - device end) over the trace's executions is
    the offset, to within the quickest callback. Returns (nanoseconds to
    take from a host time, the slowest callback's lag over the quickest in
    milliseconds); (0, None) for a trace without such events."""
    ends = {e[3]["run_id"]: e[1] + e[2] for e in modules
            if "run_id" in e[3]}
    lags = [e[1] - ends[e[3]["run_id"]]
            for plane in planes
            if not trace_reduce.DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"] for e in line["events"]
            if e[0] == COMPLETION_EVENT and e[3].get("run_id") in ends]
    if not lags:
        return 0.0, None
    return min(lags), (max(lags) - min(lags)) / 1e6


# ------------------------------------------------------------ the reading

def analyse(planes: List[Dict[str, Any]], step_module: str
            ) -> Optional[Dict[str, Any]]:
    """See the module's text. None if the trace holds no device plane with
    two executions of the step program."""
    devices = sorted(
        (p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(trace_reduce.DEVICE_PLANE.match(
            p["name"]).group(2)))
    for plane in devices:
        by_name = {line["name"]: line["events"] for line in plane["lines"]}
        steps = sorted((e for e in by_name.get(trace_reduce.MODULES_LINE, ())
                        if step_module in e[0]), key=lambda e: e[1])
        if len(steps) >= 2:
            break
    else:
        return None
    ops = by_name.get(trace_reduce.OPS_LINE, [])
    lo, hi, n_steps = steps[0][1], steps[-1][1], len(steps) - 1
    # the step's own operations carry its program's id; a checksum or a
    # copy that runs inside the window carries another
    program = _PROGRAM_ID.search(steps[0][0])
    program_id = int(program.group(1)) if program else None
    if program_id not in {e[3].get("program_id") for e in ops}:
        program_id = None

    # ---- device seconds a step, by scope x pass; the kernels, by name
    by_scope = {scope: dict.fromkeys(PASSES, 0.0)
                for scope in SCOPES + (UNSCOPED,)}
    kernels: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    other_programs = scoped_ops = 0
    for (name, start, _, stats), own in trace_reduce.self_times(ops):
        if not lo <= start < hi:
            continue
        if program_id is not None and stats.get("program_id") not in (
                None, program_id):
            other_programs += 1
            continue
        path = stats.get("tf_op") or ""
        short = trace_reduce.short_name(name)[0]
        scope = "mlp" if GROUPED_MATMUL.match(short) else scope_of(path)
        scoped_ops += scope != UNSCOPED
        by_scope[scope][pass_of(path)] += own / 1e9 / n_steps
        if scope == UNSCOPED:
            key = f"{short} {path.rstrip(':')[-60:]}"
            unscoped[key] = unscoped.get(key, 0.0) + own / 1e9 / n_steps
        kernel = kernel_of(path)
        if kernel:
            kernels[kernel] = kernels.get(kernel, 0.0) + own / 1e9 / n_steps
    step_s = sum(sum(row.values()) for row in by_scope.values())

    # ---- idle device time under the program's host spans, on the
    # device's clock
    offset_ns, completion_lag_ms = host_clock_offset(
        planes, by_name.get(trace_reduce.MODULES_LINE, ()))
    busy = trace_reduce.clipped(
        trace_reduce.merge((s, s + d) for _, s, d, _ in ops), lo, hi)
    gaps = trace_reduce._gaps(busy, lo, hi)
    gap_ends = [b for _, b in gaps]
    idle_ns = sum(b - a for a, b in gaps)
    idle_by_span: Dict[str, float] = {}
    covered: List[Tuple[float, float]] = []
    host_spans = 0
    d2h: List[Tuple[float, float]] = []
    saves: List[Tuple[float, float]] = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans = [(e[0], e[1] - offset_ns, e[2], e[3])
                     for e in line["events"]
                     if e[0].startswith(SPAN_PREFIX)]
            d2h += [(e[1] - offset_ns, e[1] - offset_ns + e[2])
                    for e in line["events"] if e[0] == D2H_EVENT]
            saves += [(e[1], e[1] + e[2]) for e in spans
                      if e[0] == SAVE_SPAN]
            if not spans:
                continue
            host_spans += len(spans)
            thread = line["name"] if str(line["id"]) in line["name"] \
                else f"{line['name']}/{line['id']}"
            for a, b, name in innermost_segments(spans):
                seconds = _overlap([(a, b)], gaps, gap_ends) / 1e9
                if seconds > 0:
                    key = f"{thread} {name[len(SPAN_PREFIX):]}"
                    idle_by_span[key] = idle_by_span.get(key, 0.0) + seconds
            covered += [(e[1], e[1] + e[2]) for e in spans]
    idle_program_s = _overlap(trace_reduce.merge(covered), gaps,
                              gap_ends) / 1e9

    # ---- the device-to-host copy inside the traced saves
    in_saves = [piece for lo_s, hi_s in saves
                for piece in trace_reduce.clipped(
                    trace_reduce.merge(d2h), lo_s, hi_s)]
    return {
        "n_steps": n_steps, "window_s": (hi - lo) / 1e9,
        "step_device_s": step_s,
        "device_s_per_step": {
            scope: {k: v for k, v in row.items() if v}
            for scope, row in by_scope.items() if any(row.values())},
        "kernels_s_per_step": kernels,
        "unscoped_top_s_per_step": dict(sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:5]),
        "scoped_ops": scoped_ops, "ops_of_other_programs": other_programs,
        "idle_s": idle_ns / 1e9, "idle_program_s": idle_program_s,
        "idle_s_by_thread_and_span": dict(sorted(
            idle_by_span.items(), key=lambda kv: -kv[1])[:12]),
        "host_spans": host_spans,
        "host_clock_offset_s": offset_ns / 1e9,
        "completion_lag_ms_max": completion_lag_ms,
        # seconds of a traced save in which a leaf's copy was in progress
        "saves": len(saves),
        "save_d2h_s": sum(b - a for a, b in in_saves) / 1e9 / len(saves)
        if saves and d2h else None,
    }


# --------------------------------------------------- the run's own trace

_cache: Dict[str, Optional[Dict[str, Any]]] = {}


def trace_file(cell_name: str, root: str = cells.ROOT) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        root, cells.RUNS_DIR, cell_name, "trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    return found[-1] if found else None


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The analysis of this run's raw trace, which is still under the run's
    storage while the readers run; None without one (no `--trace 1`, a run
    on the CPU: no device plane). Never raises."""
    reduced = run.get("trace")
    name = run["cell"]["name"]
    if not reduced:
        return None
    if name not in _cache:
        t0 = time.perf_counter()
        result, error = None, None
        try:
            path = trace_file(name)
            if path:
                with open(path, "rb") as f:
                    result = analyse(read_xspace(f.read()),
                                     reduced["step_module"])
        except Exception as e:      # noqa: BLE001 — a reader never raises
            error = repr(e)
        _cache[name] = result
        print(json.dumps({"kind": "program_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def scope_share(run: Dict[str, Any], scopes: Sequence[str] = (),
                passes: Sequence[str] = PASSES) -> Optional[float]:
    """Percent of the step's device time under `scopes` (all if empty) in
    `passes`. None unless the program put scopes on its operations."""
    trace = of_run(run)
    if not trace or not trace["scoped_ops"] or trace["step_device_s"] <= 0:
        return None
    table = trace["device_s_per_step"]
    seconds = sum(v for scope, row in table.items()
                  if not scopes or scope in scopes
                  for k, v in row.items() if k in passes)
    return 100.0 * seconds / trace["step_device_s"]


def kernel_ms(run: Dict[str, Any], kernel: str) -> Optional[float]:
    trace = of_run(run)
    if not trace or not trace["kernels_s_per_step"].get(kernel):
        return None
    return 1e3 * trace["kernels_s_per_step"][kernel]


def kernels_seconds(run: Dict[str, Any], prefix: str) -> Optional[float]:
    """Device seconds a step in the kernels whose name starts with `prefix`
    (`flash_`: forward, the forward recomputed under remat, dq and dkv);
    None if the trace holds none."""
    trace = of_run(run)
    if not trace:
        return None
    return sum(v for k, v in trace["kernels_s_per_step"].items()
               if k.startswith(prefix)) or None
