"""From a profiler trace (`*.xplane.pb`) to the numbers the metrics read.

The yardstick for every device-side number: device busy and idle over the
traced steady window, time per device operation, the step program's device
time, collective time that no compute hides, and the longest idle gaps
labelled with the benchmark's host span that was open. Read with nothing but
`jax.profiler.ProfileData`. Checked on a recorded trace in `tests/`.

What a TPU trace looks like (jax 0.9 on a v5e, looked at by hand first): one
plane per chip, `/device:TPU:<n>`, whose line `XLA Modules` has one event per
execution of a compiled program (`jit_train_step(<fingerprint>)`) and whose
line `XLA Ops` has one event per HLO operation, nested where an operation
contains others (`while` around a scanned layer's body). The core runs one
operation at a time, so on that line a collective's own time (for an
asynchronous pair, the `-start` issue and the `-done` wait) is time in which
no compute ran: exposed. Host threads are lines of `/host:CPU`; the
benchmark's spans appear there as `bench:<name>` on the same clock.

The traced window runs from the start of the first execution of the step
program to the start of the last one recorded: whole step periods, gaps
between steps included, whatever the loop did in between (a save, a report).
The loop therefore runs one step more than it wants measured.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|ragged-all-to-all)")
MAX_OPS = 400           # rows of the per-operation table handed on

Event = Tuple[str, float, float, str]     # name, start ns, duration ns, opcode
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def short_name(text: str) -> Tuple[str, str]:
    """An `XLA Ops` event is named by its whole HLO line, `%fusion.12 =
    f32[..]{..} fusion(%a, %b), kind=...`: the instruction's name and its
    opcode are what identify it."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    found = _OPCODE.search(" " + rest)
    return name.lstrip("%"), found.group(1) if found else ""


def load_xplane(path: str) -> Dict[str, Any]:
    """The planes this module reads, as plain lists: device planes whole,
    of the other planes only the `bench:` spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events: List[Event] = []
            for ev in line.events:
                name, opcode = ev.name, ""
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                if device and line.name == OPS_LINE:
                    name, opcode = short_name(name)
                events.append((name, float(ev.start_ns),
                               float(ev.duration_ns), opcode))
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _line(plane: Dict[str, Any], name: str) -> List[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clipped(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time: its duration less that of the events
    nested directly inside it on the same line."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in order]
    stack: List[int] = []
    for i, (_, start, dur, _) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(e, max(t, 0.0)) for e, t in zip(order, own)]


def _gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _label(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    best, best_cover = "none", 0.0
    for name, start, dur, _ in spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > best_cover:
            best, best_cover = name[len(SPAN_PREFIX):], cover
    return best


def reduce_trace(trace: Dict[str, Any], step_module: str,
                 top_gaps: int = 5) -> Optional[Dict[str, Any]]:
    """The reduction. `step_module` is a substring of the step program's
    name on the `XLA Modules` line. None when the trace holds no device
    plane or fewer than two executions of the step program."""
    devices = sorted((p for p in trace["planes"]
                      if DEVICE_PLANE.match(p["name"])),
                     key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(2)))
    spans = [e for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
             for line in p["lines"] for e in line["events"]]
    per_device = []
    for plane in devices:
        steps = sorted((e for e in _line(plane, MODULES_LINE)
                        if step_module in e[0]), key=lambda e: e[1])
        if len(steps) < 2:
            continue
        lo, hi = steps[0][1], steps[-1][1]
        ops = _line(plane, OPS_LINE)
        busy = clipped(merge((s, s + d) for _, s, d, _ in ops), lo, hi)
        per_device.append({
            "plane": plane["name"], "lo": lo, "hi": hi, "busy": busy,
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "steps": steps, "ops": ops})
    if not per_device:
        return None

    first = per_device[0]
    lo, hi = first["lo"], first["hi"]
    n_steps = len(first["steps"]) - 1
    table: Dict[str, List[Any]] = {}
    exposed = 0.0
    for (name, start, _, opcode), own in self_times(first["ops"]):
        if not lo <= start < hi:
            continue
        row = table.setdefault(name, [0.0, 0, opcode])
        row[0] += own / 1e9
        row[1] += 1
        if COLLECTIVE.match(name) or COLLECTIVE.match(opcode):
            exposed += own / 1e9
    rows = sorted(table.items(), key=lambda kv: -kv[1][0])
    kept, rest = rows[:MAX_OPS], rows[MAX_OPS:]
    idle_by_span: Dict[str, float] = {}
    labelled = []
    for gap in _gaps(first["busy"], lo, hi):
        label, seconds = _label(gap, spans), (gap[1] - gap[0]) / 1e9
        labelled.append([label, seconds])
        idle_by_span[label] = idle_by_span.get(label, 0.0) + seconds
    labelled.sort(key=lambda g: -g[1])
    return {
        "devices": len(per_device),
        "n_steps": n_steps,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "busy_s_per_device": [d["busy_s"] for d in per_device],
        "step_device_ms": [e[2] / 1e6 for e in first["steps"][:n_steps]],
        "step_module": first["steps"][0][0],
        # [name, own seconds in the window, executions, opcode]
        "ops": [[name, row[0], row[1], row[2]] for name, row in kept],
        "ops_not_listed_s": sum(row[0] for _, row in rest),
        "collective_exposed_s": exposed,
        "idle_gaps": labelled[:top_gaps],
        # all idle time in the window, summed by the label of its gap
        "idle_by_span": idle_by_span,
    }


def op_seconds_per_step(reduced: Dict[str, Any], pattern: str) -> float:
    """Own device seconds per step of the operations whose name or opcode
    matches `pattern` (a regular expression, case ignored)."""
    match = re.compile(pattern, re.I)
    total = sum(secs for name, secs, _, opcode in reduced["ops"]
                if match.search(name) or match.search(opcode or ""))
    return total / reduced["n_steps"]


def reduce_file(path: str, step_module: str) -> Optional[Dict[str, Any]]:
    return reduce_trace(load_xplane(path), step_module)


def breakdown(reduced: Dict[str, Any], top_ops: int = 10) -> Dict[str, Any]:
    """The contract's `breakdown`: the device operations with most time and
    the longest idle gaps by what the host was doing."""
    return {"device_ops": [[f"{name} [{opcode}]" if opcode else name, secs]
                           for name, secs, _, opcode in
                           reduced["ops"][:top_ops]],
            "idle_gaps": [list(g) for g in reduced["idle_gaps"]]}
