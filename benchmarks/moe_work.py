"""The sparse-expert block's work and its share of a traced step.

Kept with the benchmark beside `flops.py`: what the expert matmuls need (from
shapes alone) and a small reader of the raw trace for the four scopes the
program puts inside `mlp` (`ray_tpu/models/moe.py`): `moe_router` (logits,
softmax, top-k), `moe_dispatch` (sort, counts, the gather of rows into expert
order), `moe_experts` (the three grouped matmuls and the SwiGLU product) and
`moe_combine` (the gather back and the weighted sum over k). `program_trace`
files all four under `mlp` (its `SCOPES` is closed); this module reads the
same `tf_op` paths one level further in.

The grouped matmuls themselves carry no scope: the TPU compiler rewrites
`jax.lax.ragged_dot` into custom calls named `ragged-dot-none*` (and a small
`ragged-dot-metadata*` before each group of them) whose `tf_op` is that name
and not jax's name stack (seen in the first trace of `olmoe-steady`, PR 27).
They are found by name, as the flash kernels are, and counted with
`moe_experts`; `program_trace` files them under `unscoped`, so in a cell with
experts `mlp_share` lacks them and `unscoped_share` holds them. A kernel of
the repo's own that replaces them gets a `name=` and a line in
`GROUPED_MATMUL`.

A program without these scopes, or a run without a device trace, reads as
nothing: every reader returns None and never raises.
"""

from __future__ import annotations

import json
import re
import time
from typing import Any, Dict, Mapping, Optional, Sequence

from benchmarks import program_trace, trace_reduce

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
# the grouped matmuls' device operations, by instruction name: forward, the
# forward recomputed under remat, and both backward products
GROUPED_MATMUL = re.compile(r"^ragged-dot")


def expert_matmul_work(model: Mapping[str, Any], tokens: int,
                       act_bytes: int = 2) -> Dict[str, float]:
    """What the routed experts' matmuls need in one training step of
    `tokens` tokens on one chip, over all layers. FLOPs: three matmuls a
    SwiGLU expert, each token through top-k experts, 2 FLOPs a multiply-add,
    forward once and backward twice (recomputation never counted):
    18 x tokens x k x d x f a layer. Bytes, the least that must cross HBM:
    forward reads the rows once for up and gate, writes up and gate, reads
    the product and writes the output; backward reads each matmul's output
    cotangent and saved input and writes its input cotangent; every expert's
    three matrices are read forward and backward and their gradients written
    once (`act_bytes` each: the program multiplies bf16 copies and the
    matmuls hand back bf16 gradients)."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    layers, k = int(model["n_layers"]), int(model["moe_top_k"])
    experts = int(model["n_experts"])
    rows_d, rows_f = tokens * k * d, tokens * k * f
    forward = 2 * rows_d + 3 * rows_f
    backward = 3 * rows_d + 4 * rows_f
    weights = 3 * experts * d * f
    return {
        "flops": 18.0 * layers * tokens * k * d * f,
        "bytes": float(layers) * act_bytes * (forward + backward
                                              + 3 * weights),
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
        if GROUPED_MATMUL.match(short):
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
