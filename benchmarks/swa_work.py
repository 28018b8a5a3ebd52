"""The work of a model whose layers mix attention over a window of keys with
attention over all of them, routed experts beside a shared one after leading
dense layers: forward FLOPs of scored documents, the two attention kernels'
and the grouped matmuls' least operations and bytes, and a reader of the raw
trace for the scopes such a model adds (`attn_window` inside `attn_kernel`,
`moe_shared`, `lead`) and for its experts' (`moe_experts`, and the grouped
matmuls by name).

Kept with the benchmark beside `flops.py`, `moe_work.py`, `hybrid_work.py`
and `dsa_work.py`; this file is the work module (`work.module`) of a served
configuration whose `lead_layers` and `layer_pattern` hold "window" and
"full" layers: a window query attends its last `attn_window` keys, its own
included; a leading layer's FFN is dense at `lead_d_ff`, a period's layer
routes each token to `moe_top_k` of `n_experts` experts of `d_ff`, all held
here, beside a shared expert of `moe_shared_ff`.

Forward FLOPs a document of L tokens needs (2 a multiply-add; padding,
rectangles a kernel walks under a mask and anything made twice are work the
program chose, never counted — so no share read from these can pass 100%):

    every token, a layer   2 x (q and its gate, k, v, out projections; in a
                           leading layer 3 x d x lead_d_ff; in a period's
                           the router over all its outputs, 3 x d x d_ff a
                           routed pair x moe_top_k, 3 x d x moe_shared_ff)
    a window layer         4 x heads x head width a (query, key) pair of the
                           band: sum over t of min(t + 1, attn_window)
    a full layer           the same a causal pair: L (L + 1) / 2 of them
    head                   2 x d_model x vocabulary rows held, a token

Rooflines. Each attention kernel (`window_work`, `full_work`): two products
a pair; q read and o written once, k and v read once. The grouped matmuls
(`expert_matmul_work`): three products a (token, expert) pair of the real
tokens, 6 x d x d_ff; the rows read once for up and gate, both written, their
product read and the output written, and every expert's three matrices read
once a layer a call (a call's batch is what amortises them: the bytes are
counted a device call, `calls`). Compute bounds all three on a v5e at this
cell's sizes.

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

from benchmarks import dsa_work, program_trace, trace_reduce

SCOPES = ("attn_window", "moe_experts", "moe_shared", "lead")
_ALL_SCOPES = program_trace.SCOPES + SCOPES + (
    "moe_router", "moe_dispatch", "moe_combine")
WINDOW_KERNEL = r"^flash_fwd_window"
FULL_KERNEL = r"^flash_fwd(?!_window)"

causal_pairs = dsa_work.causal_pairs
band_pairs = dsa_work.chosen_pairs      # sum over t < n of min(t + 1, window)


# ------------------------------------------------------------ from shapes

def _geometry(model: Mapping[str, Any]) -> Dict[str, Any]:
    d, heads = int(model["d_model"]), int(model["n_heads"])
    lead = tuple(model.get("lead_layers") or ())
    pattern = tuple(model.get("layer_pattern") or ("full",))
    periods = (int(model["n_layers"]) - len(lead)) // len(pattern)
    return {
        "d": d, "heads": heads,
        "kv_heads": int(model.get("n_kv_heads") or heads),
        "width": int(model.get("d_head") or d // heads),
        "window": int(model.get("attn_window") or 0),
        "lead": len(lead), "routed": periods * len(pattern),
        "window_layers": lead.count("window")
        + periods * pattern.count("window"),
        "full_layers": lead.count("full") + periods * pattern.count("full")}


def params_per_token(model: Mapping[str, Any]) -> float:
    """Matmul parameters one token uses in all the layers here."""
    g = _geometry(model)
    d = g["d"]
    attention = ((3 if model.get("attn_gate") else 2) * d * g["heads"]
                 * g["width"] + 2 * d * g["kv_heads"] * g["width"])
    dense = 3 * d * int(model.get("lead_d_ff") or model["d_ff"])
    routed = (d * int(model["n_experts"])
              + int(model["moe_top_k"]) * 3 * d * int(model["d_ff"])
              + 3 * d * int(model.get("moe_shared_ff") or 0))
    return ((g["lead"] + g["routed"]) * attention + g["lead"] * dense
            + g["routed"] * routed)


def forward_flops(model: Mapping[str, Any], lengths: Iterable[int]) -> float:
    """Forward-only FLOPs the model needs to score documents of these
    lengths, each alone (the module's text)."""
    g = _geometry(model)
    per_token = 2.0 * (params_per_token(model)
                       + g["d"] * int(model["vocab_size"]))
    per_pair = 4.0 * g["heads"] * g["width"]
    return sum(per_token * n + per_pair * (
        g["window_layers"] * band_pairs(int(n), g["window"])
        + g["full_layers"] * causal_pairs(int(n))) for n in lengths)


def _attention_work(model, lengths, layers: int, pairs, act_bytes: int
                    ) -> Dict[str, float]:
    g = _geometry(model)
    total = {"flops": 0.0, "bytes": 0.0}
    for n in (int(n) for n in lengths):
        total["flops"] += 4.0 * layers * g["heads"] * g["width"] * pairs(n)
        total["bytes"] += (2.0 * layers * n * act_bytes
                           * (g["heads"] + g["kv_heads"]) * g["width"])
    return total


def window_work(model: Mapping[str, Any], lengths: Iterable[int],
                act_bytes: int = 2) -> Dict[str, float]:
    """What attention over the window needs, the "window" layers, over
    documents of these lengths, each alone (the module's text)."""
    g = _geometry(model)
    return _attention_work(model, lengths, g["window_layers"],
                           lambda n: band_pairs(n, g["window"]), act_bytes)


def full_work(model: Mapping[str, Any], lengths: Iterable[int],
              act_bytes: int = 2) -> Dict[str, float]:
    """What causal attention over every key needs, the "full" layers."""
    return _attention_work(model, lengths, _geometry(model)["full_layers"],
                           causal_pairs, act_bytes)


def flash_forward_work(model: Mapping[str, Any], lengths: Iterable[int],
                       act_bytes: int = 2) -> Dict[str, float]:
    """The served loop's name for the attention kernels' forward work: the
    band in the window layers and the triangle in the full ones."""
    lengths = list(lengths)
    window = window_work(model, lengths, act_bytes)
    full = full_work(model, lengths, act_bytes)
    return {k: window[k] + full[k] for k in window}


def expert_matmul_work(model: Mapping[str, Any], lengths: Iterable[int],
                       calls: int = 1, act_bytes: int = 2
                       ) -> Dict[str, float]:
    """What the routed experts' three grouped matmuls need, forward only,
    all routed layers, for documents of these lengths answered in `calls`
    device calls (the module's text)."""
    g = _geometry(model)
    d, f = g["d"], int(model["d_ff"])
    pairs = g["routed"] * int(model["moe_top_k"]) * float(sum(lengths))
    weights = 3.0 * g["routed"] * int(model["n_experts"]) * d * f
    return {"flops": 6.0 * pairs * d * f,
            "bytes": act_bytes * (pairs * (2 * d + 3 * f) + calls * weights)}


# ----------------------------------------------------- from the raw trace

def analyse(planes, step_module: str) -> Optional[Dict[str, Any]]:
    """Own device seconds in the traced stretch (first to last start of a
    program named `step_module` on the first device that ran two) under each
    of `SCOPES`, all programs together (an operation under `lead` counts
    there and under what it holds), of the grouped matmuls by name
    (`grouped_matmul`: they carry no scope) and (`by_scope`, for the progress
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
    seconds = dict.fromkeys(SCOPES + ("grouped_matmul",), 0.0)
    by_scope: Dict[str, float] = {}
    found = 0
    for (name, start, _, stats), own in trace_reduce.self_times(
            by_name.get(trace_reduce.OPS_LINE, [])):
        if not lo <= start < hi:
            continue
        tokens = program_trace._TOKEN.findall(stats.get("tf_op") or "")
        innermost = program_trace.UNSCOPED
        for token in tokens:
            if token in _ALL_SCOPES and token != "lead":
                innermost = token
        if program_trace.GROUPED_MATMUL.match(
                trace_reduce.short_name(name)[0]):
            seconds["grouped_matmul"] += own / 1e9
            innermost = "grouped_matmul"
        by_scope[innermost] = by_scope.get(innermost, 0.0) + own / 1e9
        for scope in SCOPES:
            if scope in tokens:
                found += 1
                seconds[scope] += own / 1e9
    if not found:
        return None
    return {"seconds": seconds, "by_scope": dict(sorted(
        by_scope.items(), key=lambda kv: -kv[1]))}


_cache: Dict[str, Optional[Dict[str, float]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds under each of `SCOPES` and of the grouped matmuls by the
    analysis of this run's raw trace; None without a trace or without the
    scopes. Never raises. Prints one progress line,
    `{"kind": "swa_trace", ...}`."""
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
        print(json.dumps({"kind": "swa_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def kernel_ms(run: Dict[str, Any], pattern: str) -> Optional[float]:
    """Device milliseconds a device call of the traced stretch spends in
    the operations named `pattern`, all layers, mean over its programs."""
    reduced = run.get("trace")
    if not reduced:
        return None
    return 1e3 * trace_reduce.op_seconds_per_step(reduced, pattern) or None


def grouped_matmul_roofline(run: Dict[str, Any]) -> Optional[float]:
    """Percent: the least time the chip could take for the routed experts'
    matmuls of the traced stretch's real tokens over the device time of the
    grouped matmuls there."""
    from benchmarks import flops
    reduced, peaks = run.get("trace"), run.get("peaks")
    lengths, seconds = dsa_work.traced_lengths(run), of_run(run)
    if not reduced or not peaks or not lengths or not seconds or (
            seconds["grouped_matmul"] <= 0):
        return None
    needed = expert_matmul_work(run["cell"]["config"]["model"], lengths,
                                calls=reduced["n_steps"])
    return (100.0 * flops.roofline_seconds(needed, peaks)["seconds"]
            / seconds["grouped_matmul"])
