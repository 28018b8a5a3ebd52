"""The sparse-expert block's work and its share of a traced step.

Kept with the benchmark beside `flops.py`, and the work module of a
configuration whose layers are of one kind and hold all their experts
(`work.module`: model FLOPs a token are `flops.py`'s, which counts the top-k
experts a token is routed to; `work.routing_check`: `every_pair_routed`).
Here: what the expert matmuls need, from shapes and from the (token, expert)
pairs the steps reported, and a small reader of the raw trace for the four
scopes the program puts inside `mlp` (`ray_tpu/models/moe.py`): `moe_router`
(logits, softmax, top-k), `moe_dispatch` (sort, counts, the gather of rows
into expert order), `moe_experts` (the three grouped matmuls and the SwiGLU
product) and `moe_combine` (the gather back and the weighted sum over k).
`program_trace` files all four under `mlp`; this module reads the same
`tf_op` paths one level further in.

The grouped matmuls themselves carry no scope: the TPU compiler rewrites
`jax.lax.ragged_dot` into custom calls named `ragged-dot-none*` (and a small
`ragged-dot-metadata*` before each group of them) whose `tf_op` is that name
and not jax's name stack (seen in the first trace of `olmoe-steady`, PR 27).
They are found by name and counted with `moe_experts`; `program_trace` files
them under `mlp` by the same names (`program_trace.GROUPED_MATMUL`). A
kernel of the repo's own that replaces them gets a `name=` and is found as
every Pallas kernel is.

A program without these scopes, or a run without a device trace, reads as
nothing: every reader returns None and never raises.
"""

from __future__ import annotations

import json
import statistics
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

from benchmarks import flops, program_trace, trace_reduce

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")

model_flops_per_token = flops.model_flops_per_token


# ------------------------------------------------- from the step's reports

def _window_records(window: Mapping[str, Any]) -> List[Mapping[str, Any]]:
    records = window.get("step_records") or []
    return records[window.get("first_window_record", 0):]


def pairs_per_step(window: Mapping[str, Any]) -> Optional[float]:
    """(token, expert) pairs the experts held on this chip were given in one
    step, over all layers: the median over the window's steps of what the
    step itself reported — `moe_routed_here` [layers] where the layers hold
    a share of their experts, else `moe_expert_tokens` (all of them: tokens
    x top-k x layers). None where the steps report neither."""
    given = [r.get("moe_routed_here") or r.get("moe_expert_tokens")
             for r in _window_records(window)]
    values = [sum(g) for g in given if g]
    return statistics.median(values) if values else None


def pairs_per_token(window: Mapping[str, Any]) -> Optional[float]:
    """Median over the window's steps of the (token, expert) pairs routed to
    held experts over the step's tokens, a mean over the layers. None unless
    the steps report `moe_routed_here` (a share of the experts held)."""
    tokens = window.get("tokens_per_step")
    values = [statistics.fmean(r["moe_routed_here"]) / tokens
              for r in _window_records(window)
              if r.get("moe_routed_here") and tokens]
    return statistics.median(values) if values else None


def every_pair_routed(model: Mapping[str, Any], steps, checked, reference,
                      tokens_per_step: int) -> List[str]:
    """The routing check of a model that holds all its experts: every step
    of every report sent each of its tokens to top-k experts in every layer
    (`moe_expert_tokens` sums to tokens x top-k x layers: no token
    dropped)."""
    want = tokens_per_step * int(model["moe_top_k"]) * int(model["n_layers"])
    short = [s for s in steps
             if sum(s.get("moe_expert_tokens", ())) != want]
    if not steps:
        return ["no report carried the steps' metrics"]
    if short:
        return [f"{len(short)} of {len(steps)} reported steps routed other "
                f"than {want} (token, expert) pairs: first "
                f"{sum(short[0].get('moe_expert_tokens', ()))}"]
    return []


# ------------------------------------------------------------ from shapes

def expert_matmul_work(model: Mapping[str, Any], pairs: float,
                       act_bytes: int = 2) -> Dict[str, float]:
    """What the routed experts' matmuls need in one training step in which
    the experts held on this chip are given `pairs` (token, expert) pairs,
    over all layers (tokens x top-k x layers where every expert is held).
    FLOPs: three matmuls a SwiGLU expert, 2 FLOPs a multiply-add, forward
    once and backward twice (recomputation never counted): 18 x d x f a
    pair. Bytes, the least that must cross HBM: forward reads the rows once
    for up and gate, writes up and gate, reads the product and writes the
    output; backward reads each matmul's output cotangent and saved input
    and writes its input cotangent; every held expert's three matrices are
    read forward and backward and their gradients written once (`act_bytes`
    each: the program multiplies bf16 copies and the matmuls hand back bf16
    gradients)."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    held = int(model.get("moe_experts_held") or model["n_experts"])
    rows_d, rows_f = pairs * d, pairs * f
    forward = 2 * rows_d + 3 * rows_f
    backward = 3 * rows_d + 4 * rows_f
    weights = 3 * int(model["n_layers"]) * held * d * f
    return {
        "flops": 18.0 * pairs * d * f,
        "bytes": float(act_bytes) * (forward + backward + 3 * weights),
    }


def scope_of(path: str) -> Optional[str]:
    """The innermost of the four scopes in a name-stack path."""
    found = None
    for token in program_trace._TOKEN.findall(path or ""):
        if token in SCOPES:
            found = token
    return found


def analyse(planes: Sequence[Dict[str, Any]], step_module: str
            ) -> Optional[Dict[str, Any]]:
    """Device seconds a step by scope x pass for the four scopes and, by
    name, of the grouped matmuls, over the same window and the same program
    as `program_trace.analyse`. None for a trace without two executions of
    the step program or without any operation under one of the scopes."""
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        by_name = {line["name"]: line["events"] for line in plane["lines"]}
        steps = sorted((e for e in by_name.get(trace_reduce.MODULES_LINE, ())
                        if step_module in e[0]), key=lambda e: e[1])
        if len(steps) >= 2:
            break
    else:
        return None
    ops = by_name.get(trace_reduce.OPS_LINE, [])
    lo, hi, n_steps = steps[0][1], steps[-1][1], len(steps) - 1
    program = program_trace._PROGRAM_ID.search(steps[0][0])
    program_id = int(program.group(1)) if program else None
    if program_id not in {e[3].get("program_id") for e in ops}:
        program_id = None

    table = {scope: dict.fromkeys(program_trace.PASSES, 0.0)
             for scope in SCOPES}
    matmul_s = 0.0
    matmul_ops: Dict[str, float] = {}
    found = 0
    for (name, start, _, stats), own in trace_reduce.self_times(ops):
        if not lo <= start < hi:
            continue
        if program_id is not None and stats.get("program_id") not in (
                None, program_id):
            continue
        seconds = own / 1e9 / n_steps
        short = trace_reduce.short_name(name)[0]
        if program_trace.GROUPED_MATMUL.match(short):
            matmul_s += seconds
            matmul_ops[short] = matmul_ops.get(short, 0.0) + seconds
            continue
        path = stats.get("tf_op") or ""
        scope = scope_of(path)
        if scope is None:
            continue
        found += 1
        table[scope][program_trace.pass_of(path)] += seconds
    if not found:
        return None
    return {
        "n_steps": n_steps,
        "device_s_per_step": {scope: {k: v for k, v in row.items() if v}
                              for scope, row in table.items()},
        "expert_matmul_s_per_step": matmul_s,
        "expert_matmul_ops_s_per_step": dict(sorted(
            matmul_ops.items(), key=lambda kv: -kv[1])[:16]),
    }


_cache: Dict[str, Optional[Dict[str, Any]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The analysis of this run's raw trace (still under the run's storage
    while the readers run) with the step's device seconds beside it; None
    without a trace or without the scopes. Never raises. Prints one progress
    line, `{"kind": "moe_trace", ...}`."""
    reduced = run.get("trace")
    name = run["cell"]["name"]
    if not reduced:
        return None
    if name not in _cache:
        t0 = time.perf_counter()
        result, error = None, None
        try:
            path = program_trace.trace_file(name)
            whole = program_trace.of_run(run)
            if path and whole and whole["step_device_s"] > 0:
                with open(path, "rb") as f:
                    planes = program_trace.read_xspace(f.read())
                result = analyse(planes, reduced["step_module"])
                if result:
                    result["step_device_s"] = whole["step_device_s"]
        except Exception as e:      # noqa: BLE001 — a reader never raises
            error = repr(e)
        _cache[name] = result
        print(json.dumps({"kind": "moe_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def scope_share(run: Dict[str, Any], scopes: Sequence[str],
                grouped_matmuls: bool = False) -> Optional[float]:
    """Percent of the step's device time under `scopes`, all passes, with
    the grouped matmuls' if asked."""
    trace = of_run(run)
    if not trace:
        return None
    seconds = sum(sum(trace["device_s_per_step"][scope].values())
                  for scope in scopes)
    if grouped_matmuls:
        seconds += trace["expert_matmul_s_per_step"]
    return 100.0 * seconds / trace["step_device_s"]
