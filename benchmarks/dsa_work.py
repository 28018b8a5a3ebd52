"""The work of a model whose layers attend a learned choice of keys: forward
FLOPs of scored documents, the indexer's and the sparse attention's least
operations and bytes, and a reader of the raw trace for the two scopes such
a layer adds (`dsa_index` inside `attn_qkv`, `dsa_attend` inside
`attn_kernel`).

Kept with the benchmark beside `flops.py`, `moe_work.py` and
`hybrid_work.py`; this file is the work module (`work.module`) of a served
configuration whose `layer_pattern` is ("sparse",): softmax attention over
the `sparse_topk` causal keys a query that an indexer of `index_heads` heads
of `index_head_dim` scores highest, every layer routed over `n_experts` of
which `moe_experts_held` live here.

Forward FLOPs a document of L tokens needs (2 a multiply-add; padding, the
scores a kernel makes of keys it then drops, and anything made twice are
work the program chose, never counted — so no share read from these can
pass 100%):

    every token, a layer   2 x (q, k, v, out projections; the indexer's
                           three projections; the router over all its
                           outputs; 3 x d x f a routed pair x the pairs a
                           token has on held experts at even routing)
    the indexer's scores   2 x index_heads x index_head_dim a causal pair
                           (s <= t: L (L + 1) / 2 of them), a layer
    attention              4 x heads x head width a chosen pair
                           (sum over t of min(t + 1, sparse_topk)), a layer
    head                   2 x d_model x vocabulary rows held, a token

Rooflines. The indexer (`index_work`): the score products over the causal
pairs; bytes, what must cross HBM at least once: its three operands read
and a chosen key's index written a chosen pair. Compute bounds it on a
v5e. The attention over the choice (`attend_work`): two products a chosen
pair; q read and o written once, k and v read once, the choice read once.
Compute bounds it too. Neither counts the rectangles a kernel walks under a
mask: under seeded weights the choice is spread evenly and every rectangle
holds chosen keys, so the walk is the dense one and the share says so.

The scope reader sums the own device time of every operation of the traced
stretch whose name-stack path holds one of `SCOPES`, whatever program ran
it (a served stretch runs one program a bucket). A program without the
scopes, or a run without a device trace, reads as nothing: every reader
returns None and never raises.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Iterable, Mapping, Optional

from benchmarks import program_trace, trace_reduce

SCOPES = ("dsa_index", "dsa_attend")
_ALL_SCOPES = program_trace.SCOPES + SCOPES + (
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine")
INDEX_KERNEL = r"^dsa_index"
ATTEND_KERNEL = r"^dsa_attend_fwd"


# ------------------------------------------------------------ from shapes

def _geometry(model: Mapping[str, Any]) -> Dict[str, int]:
    d, heads = int(model["d_model"]), int(model["n_heads"])
    return {
        "d": d, "layers": int(model["n_layers"]), "heads": heads,
        "kv_heads": int(model.get("n_kv_heads") or heads),
        "width": int(model.get("d_head") or d // heads),
        "topk": int(model["sparse_topk"]),
        "index_heads": int(model["index_heads"]),
        "index_dim": int(model["index_head_dim"])}


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def chosen_pairs(n: int, topk: int) -> float:
    """sum over t < n of min(t + 1, topk)."""
    full = min(n, topk)
    return causal_pairs(full) + (n - full) * float(topk)


def uniform_pairs_per_token(model: Mapping[str, Any]) -> float:
    routed = int(model["n_experts"])
    held = int(model.get("moe_experts_held") or routed)
    return int(model["moe_top_k"]) * held / routed


def params_per_token(model: Mapping[str, Any]) -> float:
    """Matmul parameters one token uses in one layer here."""
    g = _geometry(model)
    d = g["d"]
    attention = (2 * d * g["heads"] * g["width"]
                 + 2 * d * g["kv_heads"] * g["width"])
    indexer = d * (g["index_heads"] * g["index_dim"] + g["index_dim"]
                   + g["index_heads"])
    experts = (d * int(model["n_experts"])
               + uniform_pairs_per_token(model) * 3 * d * int(model["d_ff"]))
    return attention + indexer + experts


def forward_flops(model: Mapping[str, Any], lengths: Iterable[int]) -> float:
    """Forward-only FLOPs the model needs to score documents of these
    lengths, each alone (the module's text)."""
    g = _geometry(model)
    per_token = 2.0 * (g["layers"] * params_per_token(model)
                       + g["d"] * int(model["vocab_size"]))
    per_causal = 2.0 * g["layers"] * g["index_heads"] * g["index_dim"]
    per_chosen = 4.0 * g["layers"] * g["heads"] * g["width"]
    return sum(per_token * n + per_causal * causal_pairs(int(n))
               + per_chosen * chosen_pairs(int(n), g["topk"])
               for n in lengths)


def index_work(model: Mapping[str, Any], lengths: Iterable[int],
               act_bytes: int = 2) -> Dict[str, float]:
    """What the indexers of all layers need over documents of these lengths,
    each alone (the module's text)."""
    g = _geometry(model)
    total = {"flops": 0.0, "bytes": 0.0}
    for n in (int(n) for n in lengths):
        total["flops"] += (2.0 * g["layers"] * g["index_heads"]
                           * g["index_dim"] * causal_pairs(n))
        total["bytes"] += g["layers"] * (
            n * act_bytes * (g["index_heads"] * g["index_dim"]
                             + g["index_dim"] + g["index_heads"])
            + 4.0 * chosen_pairs(n, g["topk"]))
    return total


def attend_work(model: Mapping[str, Any], lengths: Iterable[int],
                act_bytes: int = 2) -> Dict[str, float]:
    """What attention over the chosen keys needs, all layers, over documents
    of these lengths, each alone (the module's text)."""
    g = _geometry(model)
    total = {"flops": 0.0, "bytes": 0.0}
    for n in (int(n) for n in lengths):
        chosen = chosen_pairs(n, g["topk"])
        total["flops"] += (4.0 * g["layers"] * g["heads"] * g["width"]
                           * chosen)
        total["bytes"] += g["layers"] * (
            2.0 * n * act_bytes * (g["heads"] + g["kv_heads"]) * g["width"]
            + 4.0 * chosen)
    return total


# the served loop's name for the attention kernel's forward work
flash_forward_work = attend_work


# ----------------------------------------------------- from the raw trace

def analyse(planes, step_module: str) -> Optional[Dict[str, Any]]:
    """Own device seconds in the traced stretch (first to last start of a
    program named `step_module` on the first device that ran two) under each
    of `SCOPES`, all programs together, and (`by_scope`, for the progress
    line and PERF.md's breakdown) under the innermost of every scope the
    model names. None without such a stretch or without any operation under
    one of `SCOPES`."""
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
    lo, hi = steps[0][1], steps[-1][1]
    seconds = dict.fromkeys(SCOPES, 0.0)
    by_scope: Dict[str, float] = {}
    found = 0
    for (_, start, _, stats), own in trace_reduce.self_times(
            by_name.get(trace_reduce.OPS_LINE, [])):
        if not lo <= start < hi:
            continue
        scope, innermost = None, program_trace.UNSCOPED
        for token in program_trace._TOKEN.findall(stats.get("tf_op") or ""):
            if token in SCOPES:
                scope = token
            if token in _ALL_SCOPES:
                innermost = token
        by_scope[innermost] = by_scope.get(innermost, 0.0) + own / 1e9
        if scope:
            found += 1
            seconds[scope] += own / 1e9
    if not found:
        return None
    return {"seconds": seconds, "by_scope": dict(sorted(
        by_scope.items(), key=lambda kv: -kv[1]))}


_cache: Dict[str, Optional[Dict[str, float]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds under each of `SCOPES` by the analysis of this run's raw
    trace; None without a trace or without the scopes. Never raises. Prints
    one progress line, `{"kind": "dsa_trace", ...}`."""
    reduced = run.get("trace")
    name = run["cell"]["name"]
    if not reduced:
        return None
    if name not in _cache:
        t0 = time.perf_counter()
        result, error = None, None
        try:
            path = program_trace.trace_file(name)
            if path:
                with open(path, "rb") as f:
                    result = analyse(program_trace.read_xspace(
                        f.read(), ("tf_op",)), reduced["step_module"])
        except Exception as e:      # noqa: BLE001 — a reader never raises
            error = repr(e)
        _cache[name] = result and result["seconds"]
        print(json.dumps({"kind": "dsa_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def traced_lengths(run: Dict[str, Any]) -> Optional[list]:
    """The real lengths of the documents in the traced stretch's device
    calls (those the reduction's steps cover), or None without them."""
    reduced, traced = run.get("trace"), run.get("traced")
    if not reduced or not traced:
        return None
    calls = [lengths for batch in traced["log"]["batches"]
             for _, _, lengths in batch["calls"]][:reduced["n_steps"]]
    if len(calls) < reduced["n_steps"]:
        return None
    return [n for call in calls for n in call]


def kernel_roofline(run: Dict[str, Any], pattern: str, work
                    ) -> Optional[float]:
    """Percent: the least time the chip could take for `work(model, the
    traced documents' lengths)` over the device time of the operations
    named `pattern` in the traced stretch."""
    from benchmarks import flops
    reduced, peaks = run.get("trace"), run.get("peaks")
    lengths = traced_lengths(run)
    if not reduced or not peaks or not lengths:
        return None
    per_call = trace_reduce.op_seconds_per_step(reduced, pattern)
    if not per_call:
        return None
    needed = work(run["cell"]["config"]["model"], lengths)
    return (100.0 * flops.roofline_seconds(needed, peaks)["seconds"]
            / (per_call * reduced["n_steps"]))
