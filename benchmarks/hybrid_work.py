"""A hybrid model's work: model FLOPs a token for a layer pattern, the gated
delta rule's least operations and bytes, and a reader of the raw trace for
the scopes a hybrid step has one level inside the ones `program_trace` knows.

Kept with the benchmark beside `flops.py` (one kind of layer, all experts
here) and `moe_work.py` (the routed experts' matmuls); this file is the work
module (`work.module`, with `work.routing_check`: `held_share_routed`) of a
configuration whose `layer_pattern` mixes softmax attention ("full") with
Gated DeltaNet layers ("linear"), whose head width is its own (`d_head`),
and whose layers hold a share of their routed experts beside a shared one.

Model FLOPs a token, forward + backward, recomputation never counted
(PERF.md section 2's definition, per kind of layer):

    full layer     6 x (q [x 2 with its gate], k, v, out projections)
                   + 6 x seq_len x heads x head width      causal attention
    linear layer   6 x (in_proj_qkvz, in_proj_ba, the convolution's taps,
                        out_proj)
                   + 18 x key width x value width x value heads
                     the delta rule at the recurrence's own count: the decay
                     and the read S^T k, the rank-one write, the read S^T q,
                     each 2 x keys x values forward and twice that backward.
                     The chunked form's extra products are work the program
                     chose, like recomputation
    every layer    6 x (router over all its outputs, the shared expert's
                        three matrices and its gate, 3 x d x f a routed pair
                        x the pairs a token has on held experts)
    head           6 x d_model x vocabulary rows held

The pairs a token has on held experts are what the run reported
(`moe_routed_here` over tokens, the median over the window, a mean over
layers); `uniform_pairs_per_token` (top-k x held / routed over) is printed
beside it.

The delta rule's roofline. FLOPs as above. Bytes, the least that must cross
HBM: forward reads q, k, v (activations), g and beta (float32) and writes o;
backward reads those and dO and writes dq, dk, dv, dg, dbeta. The state
never leaves the chip in the least-traffic algorithm. On a v5e the bytes
bound it.

The scope reader files each device operation of the traced steps under the
innermost of `SCOPES` in its name-stack path (`gdn_*` inside the mixers'
scopes, `moe_*` inside `mlp`), the grouped matmuls (`ragged-dot*` custom
calls, which carry no scope) by name. A program without these scopes, or a
run without a device trace, reads as nothing: every reader returns None and
never raises.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

from benchmarks import program_trace, trace_reduce

GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_out")
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
SCOPES = GDN_SCOPES + MOE_SCOPES


# ------------------------------------------------------------ from shapes

def _pattern(model: Mapping[str, Any]) -> Sequence[str]:
    return tuple(model.get("layer_pattern") or ("full",))


def _layers_of(model: Mapping[str, Any], kind: str) -> int:
    pattern = _pattern(model)
    return int(model["n_layers"]) // len(pattern) * pattern.count(kind)


def uniform_pairs_per_token(model: Mapping[str, Any]) -> float:
    """(token, expert) pairs a token has on held experts, a layer, if the
    router spread its choices evenly."""
    routed = int(model["n_experts"])
    held = int(model.get("moe_experts_held") or routed)
    return int(model["moe_top_k"]) * held / routed


def mixer_params(model: Mapping[str, Any], kind: str) -> int:
    """Matmul parameters one token uses in one mixer of `kind`."""
    d = int(model["d_model"])
    if kind == "full":
        heads = int(model["n_heads"])
        kv_heads = int(model.get("n_kv_heads") or heads)
        width = int(model.get("d_head") or d // heads)
        q = (2 if model.get("attn_gate") else 1) * d * heads * width
        return q + 2 * d * kv_heads * width + heads * width * d
    keys = int(model["linear_key_heads"]) * int(model["linear_key_dim"])
    values = int(model["linear_value_heads"]) * int(model["linear_value_dim"])
    return (d * (2 * keys + 2 * values)
            + d * 2 * int(model["linear_value_heads"])
            + int(model.get("linear_conv", 4)) * (2 * keys + values)
            + values * d)


def expert_block_params(model: Mapping[str, Any], pairs_per_token: float
                        ) -> float:
    d, f = int(model["d_model"]), int(model["d_ff"])
    shared = int(model.get("moe_shared_ff") or 0)
    return (d * int(model["n_experts"]) + (3 * d * shared + d if shared else 0)
            + pairs_per_token * 3 * d * f)


def delta_rule_flops_per_token(model: Mapping[str, Any]) -> float:
    """One linear layer's delta rule, forward + backward."""
    return (18.0 * int(model["linear_key_dim"])
            * int(model["linear_value_dim"])
            * int(model["linear_value_heads"]))


def flops_by_part(model: Mapping[str, Any], seq_len: int,
                  pairs_per_token: Optional[float] = None
                  ) -> Dict[str, float]:
    """Model FLOPs a token, forward + backward, by part (the module's text)."""
    if pairs_per_token is None:
        pairs_per_token = uniform_pairs_per_token(model)
    d, layers = int(model["d_model"]), int(model["n_layers"])
    full, linear = _layers_of(model, "full"), _layers_of(model, "linear")
    heads = int(model["n_heads"])
    width = int(model.get("d_head") or d // heads)
    return {
        "full_projections": 6.0 * full * mixer_params(model, "full"),
        "attention": 6.0 * full * seq_len * heads * width,
        "linear_projections": (6.0 * linear * mixer_params(model, "linear")
                               if linear else 0.0),
        "delta_rule": (linear * delta_rule_flops_per_token(model)
                       if linear else 0.0),
        "expert_blocks": 6.0 * layers * expert_block_params(
            model, pairs_per_token),
        "head": 6.0 * d * int(model["vocab_size"]),
    }


def model_flops_per_token(model: Mapping[str, Any], seq_len: int,
                          pairs_per_token: Optional[float] = None) -> float:
    return sum(flops_by_part(model, seq_len, pairs_per_token).values())


def delta_rule_work(model: Mapping[str, Any], tokens: int,
                    act_bytes: int = 2) -> Dict[str, float]:
    """What the delta rule needs in one training step of `tokens` tokens on
    one chip, over all linear layers (the module's text)."""
    layers = _layers_of(model, "linear")
    heads = int(model["linear_value_heads"])
    keys = int(model["linear_key_heads"]) * int(model["linear_key_dim"])
    values = heads * int(model["linear_value_dim"])
    forward = act_bytes * (2 * keys + 2 * values) + 4 * 2 * heads
    backward = act_bytes * (2 * (2 * keys + values) + values) + 4 * 4 * heads
    return {
        "flops": float(layers) * tokens * delta_rule_flops_per_token(model),
        "bytes": float(layers) * tokens * (forward + backward),
    }


# ------------------------------------------------- from the step's reports

def held_share_routed(model: Mapping[str, Any], steps, checked, reference,
                      tokens_per_step: int) -> List[str]:
    """The routing check of a model whose layers hold a share of their
    experts: no (token, expert) pair routed to a held expert was dropped.
    In every step of every report and for every layer, what the held experts
    were given (`moe_expert_tokens` [layers, held], the grouped matmuls' own
    group sizes) sums to the router's count of its own choices that fell on
    them (`moe_routed_here` [layers]); and on the first timed batch the timed
    step's own per-expert counts differ from the reference's by no more than
    the choices that disagree explain (each moves two counts by one: counts
    that are not the choices' fail this whatever the precision) and by no
    more than the configuration's `counts_differ_max`, a limit between what
    sound runs and a float8 path read."""
    layers = int(model["n_layers"])
    held = int(model.get("moe_experts_held") or model["n_experts"])
    if not steps:
        return ["no report carried the steps' metrics"]
    problems = []
    short = []
    for s in steps:
        given, routed = s.get("moe_expert_tokens"), s.get("moe_routed_here")
        if (not isinstance(given, list) or not isinstance(routed, list)
                or len(given) != layers or len(routed) != layers
                or any(not isinstance(g, list) or len(g) != held
                       for g in given)):
            return [f"a step reported no [{layers}, {held}] moe_expert_tokens"
                    f" beside [{layers}] moe_routed_here"]
        if any(sum(g) != r for g, r in zip(given, routed)):
            short.append(([sum(g) for g in given], routed))
    if short:
        problems.append(
            f"{len(short)} of {len(steps)} reported steps gave the held "
            f"experts other than the pairs routed to them: first "
            f"{short[0][0]} given, {short[0][1]} routed")
    if "counts_differ" in checked:
        disagree = round((1.0 - checked["choice_agreement"])
                         * checked["choices"])
        if checked["counts_differ"] > 2 * disagree:
            problems.append(
                f"per-expert counts differ from the reference's by "
                f"{checked['counts_differ']}, more than the {disagree} "
                f"choices that disagree explain")
        counts_differ_max = reference.get("counts_differ_max")
        if (counts_differ_max is not None
                and checked["counts_differ"] > counts_differ_max):
            problems.append(
                f"per-expert counts differ from the reference's by "
                f"{checked['counts_differ']}, over the configuration's "
                f"{counts_differ_max}")
    return problems


# ----------------------------------------------------- from the raw trace

def scope_of(path: str) -> Optional[str]:
    found = None
    for token in program_trace._TOKEN.findall(path or ""):
        if token in SCOPES:
            found = token
    return found


def analyse(planes: Sequence[Dict[str, Any]], step_module: str
            ) -> Optional[Dict[str, Any]]:
    """Device seconds a step by scope x pass for `SCOPES` and, by name, of
    the grouped matmuls, over the same window and program as
    `program_trace.analyse`. None for a trace without two executions of the
    step program or without any operation under one of the scopes."""
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
    matmul_s, found = 0.0, 0
    top: Dict[str, float] = {}
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
            continue
        path = stats.get("tf_op") or ""
        scope = scope_of(path)
        if scope is None:
            continue
        found += 1
        table[scope][program_trace.pass_of(path)] += seconds
        key = f"{scope} {short}"
        top[key] = top.get(key, 0.0) + seconds
    if not found:
        return None
    return {
        "n_steps": n_steps,
        "device_s_per_step": {scope: {k: v for k, v in row.items() if v}
                              for scope, row in table.items()},
        "expert_matmul_s_per_step": matmul_s,
        "top_ops_s_per_step": dict(sorted(
            top.items(), key=lambda kv: -kv[1])[:24]),
    }


_cache: Dict[str, Optional[Dict[str, Any]]] = {}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The analysis of this run's raw trace with the step's device seconds
    beside it; None without a trace or without the scopes. Never raises.
    Prints one progress line, `{"kind": "hybrid_trace", ...}`."""
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
        print(json.dumps({"kind": "hybrid_trace", "cell": name,
                          "parse_s": time.perf_counter() - t0,
                          "error": error, **(result or {})}), flush=True)
    return _cache[name]


def scope_seconds(run: Dict[str, Any], scopes: Sequence[str],
                  grouped_matmuls: bool = False) -> Optional[float]:
    """Device seconds a step under `scopes`, all passes, with the grouped
    matmuls' if asked; None if none of them has any."""
    trace = of_run(run)
    if not trace:
        return None
    seconds = sum(sum(trace["device_s_per_step"][scope].values())
                  for scope in scopes)
    if grouped_matmuls:
        seconds += trace["expert_matmul_s_per_step"]
    return seconds or None


def scope_share(run: Dict[str, Any], scopes: Sequence[str],
                grouped_matmuls: bool = False) -> Optional[float]:
    seconds = scope_seconds(run, scopes, grouped_matmuls)
    if seconds is None:
        return None
    return 100.0 * seconds / of_run(run)["step_device_s"]
