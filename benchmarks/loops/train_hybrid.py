"""Traffic kind `train_hybrid`: kind `train_ref`'s job for a model whose
layers are of more than one kind and hold a share of their routed experts.

The worker's side is `loops/train_ref.py`'s as it stands — `train_loop`
(`JaxTrainer.fit()` with one granted worker, the state from the seed, the
reference check at the real widths before the window, one compiled step, a
run-ahead of two, every step's metrics fetched with its loss, the traced
segment after the window) with its `_reference_check`, and from
`loops/train.py` `median_step_seconds` and the rest it imports — so
`tokens_per_s_per_chip` and `setup_s` mean what they mean in every training
cell. The driver's side, `run`, is written out again here because two things
in `train_ref.run` are fixed to a model of one kind of layer that holds all
its experts:

1. Model FLOPs a token come from `hybrid_work.model_flops_per_token`: a
   layer pattern, the head width the configuration states, the delta rule at
   the recurrence's count, the shared expert, and the routed experts by the
   (token, expert) pairs the steps reported as routed to the experts held
   here (`moe_routed_here`, median over the window; the count at uniform
   routing is printed beside it). `flops.model_flops_per_token` derives the
   head width from d_model / n_heads, knows one kind of layer and counts all
   top-k experts.
2. No pair dropped, for a share: in every step of every report and for every
   layer, what the held experts were given (`moe_expert_tokens`
   [layers, held], the grouped matmuls' own group sizes) sums to the router's
   count of its own choices that fell on them (`moe_routed_here` [layers]);
   and on the reference rows the system's per-expert counts differ from the
   reference's by no more than the choices that disagree explain (each moves
   two counts by one: counts that are not the choices' fail this whatever
   the precision) and by no more than the configuration's
   `counts_differ_max`, a limit between what sound runs and a float8 path
   read. `train_ref._routing_problems` wants tokens x top-k x layers pairs,
   which a share of the experts never has.

Everything else `correct` needs is `train_ref`'s: platform and chip count,
no backend in the driver, no compilation in the window, traffic not
exhausted, finite losses, the first cross-entropy within the file's
half-width of ln V + 0.02^2 d / 2, the fall of 0.5, Pallas calls in the
step, the evaluation cross-entropy within `loss_atol` of the reference's,
and the share of (token, expert) choices over all the router's outputs that
agree. No saves (`ckpt_every` must be 0).
"""

from __future__ import annotations

import math
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

from benchmarks import cells, hybrid_work, traffic_gen
from benchmarks.loops.train import (LOSS_FALL_MIN, RUNS_DIR, _model_config,
                                    _one)
from benchmarks.loops.train_ref import expected_first_loss, train_loop


def _first_loss_problems(ce: List[float], model: Dict[str, Any],
                         halfwidth: float) -> List[str]:
    """The first step's cross-entropy is what the seeded initialisation
    gives: a head or an embedding at another scale, or a loss over another
    slice of the vocabulary, moves it by more than the half-width."""
    centre = expected_first_loss(model)
    if ce and not abs(ce[0] - centre) <= halfwidth:
        return [f"first cross-entropy {ce[0]:.4f} is not within {halfwidth} "
                f"of ln V + 0.02^2 d / 2 = {centre:.4f}"]
    return []


def _share_problems(model: Dict[str, Any], reports, checked: Dict[str, Any],
                    counts_differ_max: Optional[int] = None) -> List[str]:
    """No (token, expert) pair routed to a held expert was dropped (the
    module's text, 2)."""
    layers = int(model["n_layers"])
    held = int(model.get("moe_experts_held") or model["n_experts"])
    steps = [s for r in reports if r.get("kind") == "losses"
             for s in r["steps"]]
    if not steps:
        return ["no report carried the steps' metrics"]
    problems = []
    short = []
    for s in steps:
        given, routed = s.get("moe_expert_tokens"), s.get("moe_routed_here")
        if (not isinstance(given, list) or not isinstance(routed, list)
                or len(given) != layers or len(routed) != layers
                or any(len(g) != held for g in given)):
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
        if (counts_differ_max is not None
                and checked["counts_differ"] > counts_differ_max):
            problems.append(
                f"per-expert counts differ from the reference's by "
                f"{checked['counts_differ']}, over the configuration's "
                f"{counts_differ_max}")
    return problems


def run(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
        process_start_wall: float, rehearsal: Optional[Dict[str, Any]],
        say) -> Dict[str, Any]:
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu._private.accelerators import jax_backend_initialized
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, traffic = cell.config, cell.traffic
    if int(traffic.get("ckpt_every") or 0):
        raise ValueError("kind train_hybrid makes no saves: a checkpointing "
                         "cell is kind train's")
    # a program that cannot describe the configuration's model (an older
    # one, a field it lacks) fails here, before any process is started
    _model_config(config)
    platform = "cpu" if rehearsal else "tpu"
    peaks_table = cells.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "peaks.json"))
    problems: List[str] = []

    t0 = time.perf_counter()
    if rehearsal:
        ray_tpu.init(num_cpus=4, num_tpus=rehearsal["num_tpus"])
    else:
        ray_tpu.init()          # the chips are detected, never declared
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        say(kind="cluster", tpu=advertised, cell=cell.name,
            init_s=time.perf_counter() - t0)
        if advertised < cell.chips:
            raise cells.NoResult(
                f"this machine offers {advertised} TPU chip(s), the cell "
                f"{cell.name} needs {cell.chips}")

        storage = os.path.join(cell.root, RUNS_DIR, cell.name)
        shutil.rmtree(storage, ignore_errors=True)
        os.makedirs(storage)

        # ---- traffic: packed rows from the seed, one block a global batch
        t1 = time.perf_counter()
        batch_rows = config["batch_per_chip"] * cell.chips
        n_blocks = int(traffic["blocks"])
        rows = traffic_gen.packed_rows(traffic, n_blocks * batch_rows,
                                       seed)["tokens"]
        dataset = rd.Dataset(block_refs=[
            ray_tpu.put({"tokens": rows[i * batch_rows:(i + 1) * batch_rows]})
            for i in range(n_blocks)])
        reference_rows = traffic_gen.packed_rows(
            traffic, max(2, cell.chips), seed + 1_000_003)["tokens"]
        say(kind="traffic", blocks=n_blocks, rows_per_block=batch_rows,
            make_s=time.perf_counter() - t1)

        loop_config = {
            "config": config, "traffic": traffic, "chips": cell.chips,
            "platform": platform, "seed": seed, "seconds": seconds,
            "trace": trace, "storage": storage, "root": cell.root,
            "paths": cell.paths,
            "reference_rows": reference_rows.tolist()}
        loop_config["fit_called_wall"] = time.time()
        result = JaxTrainer(
            train_loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            datasets={"train": dataset},
            run_config=RunConfig(name="fit", storage_path=storage)).fit()
        reports = result.metrics_history
        for r in reports:
            if r.get("kind") in ("worker", "trace", "done"):
                say(**{k: v for k, v in r.items() if k != "reduced"})

        # ---- what ran where
        worker = _one(reports, "worker") or {}
        device = {"platform": worker.get("platform"),
                  "kind": worker.get("device_kind"),
                  "count": worker.get("count", 0)}
        if worker.get("pid") == os.getpid():
            problems.append("the loop ran in the driver's process")
        if result.error is not None:
            problems.append(f"fit() failed: {result.error!r}")
        if device["platform"] != "tpu":
            problems.append(f"ran on {device['platform']!r}, not a TPU")
        peak = peaks_table.get(device["kind"])
        if peak is None:
            problems.append(f"no peaks on record for device kind "
                            f"{device['kind']!r} (peaks.json)")
        if device["count"] != cell.chips:
            problems.append(f"{device['count']} device(s), the cell has "
                            f"{cell.chips}")
        window = _one(reports, "window")
        if window is None:
            raise RuntimeError(f"the worker never closed its window: "
                               f"{problems}")

        # ---- the window
        say(kind="window", **{k: window[k] for k in (
            "seconds", "steps", "tokens_per_s_per_chip", "median_step_s",
            "steps_timed", "window_tokens_per_s_per_chip",
            "compiles_in_window", "phases", "state_bytes", "program_bytes",
            "memory_analysis", "peak_bytes_in_use", "pallas_custom_calls",
            "reference", "compile_seconds", "spans")})
        records = window["step_records"]
        model = config["model"]
        if window["compiles_in_window"]:
            problems.append(f"{window['compiles_in_window']} compilation(s) "
                            f"inside the window")
        if window["stream_exhausted"]:
            problems.append("the traffic file's blocks ran out before the "
                            "window closed")
        bad_losses = sum(1 for r in records if not math.isfinite(r["loss"]))
        ce = [r.get("ce_loss", r["ppl_log"]) for r in records]
        centre = expected_first_loss(model)
        problems.extend(_first_loss_problems(
            ce, model, config["reference"]["first_loss_halfwidth"]))
        last = statistics.fmean(ce[-10:]) if ce else float("nan")
        if not ce or not last < ce[0] - LOSS_FALL_MIN:
            problems.append(f"cross-entropy did not fall by {LOSS_FALL_MIN}: "
                            f"first {ce[:1]}, mean of last ten {last:.4f}")
        checked = window["reference"]
        tolerance = config["reference"]["loss_atol"]
        if not abs(checked["system_loss"] - checked["reference_loss"]
                   ) <= tolerance:
            problems.append(
                f"evaluation cross-entropy {checked['system_loss']:.6f} "
                f"differs from the reference's "
                f"{checked['reference_loss']:.6f} by more than {tolerance}")
        agreement = config["reference"].get("choice_agreement_min")
        if agreement is not None and not checked.get(
                "choice_agreement", 0.0) >= agreement:
            problems.append(
                f"{checked.get('choice_agreement')} of the (token, expert) "
                f"choices agree with the reference's, under {agreement}")
        problems.extend(_share_problems(
            model, reports, checked,
            config["reference"].get("counts_differ_max")))
        if platform == "tpu" and not window["pallas_custom_calls"]:
            problems.append("no tpu_custom_call in the step: attention did "
                            "not lower to the Pallas kernels")
        in_window = records[window["first_window_record"]:]
        say(kind="losses", first=records[:1], last_ten_ce_mean=last,
            n=len(records), expected_first_ce=centre,
            window_medians={
                k: statistics.median(r[k] for r in in_window)
                for k in (in_window[0] if in_window else {})
                if isinstance(in_window[0][k], float)},
            unigram_entropy=traffic_gen.unigram_entropy(traffic["tokens"]))

        # ---- metrics
        setup_s = window["window_start_wall"] - process_start_wall
        end_to_end = {
            "tokens_per_s_per_chip": window["tokens_per_s_per_chip"],
            "setup_s": setup_s}
        pairs = hybrid_work.pairs_per_token({"window": window})
        by_part = hybrid_work.flops_by_part(model, traffic["seq_len"], pairs)
        say(kind="model_flops", per_token=sum(by_part.values()),
            by_part=by_part, pairs_per_token=pairs,
            uniform_pairs_per_token=hybrid_work.uniform_pairs_per_token(
                model))
        run_facts = {
            "cell": {"name": cell.name, "chips": cell.chips,
                     "config": config, "traffic": traffic},
            "peaks": peak, "device": device,
            "flops_per_token": sum(by_part.values()),
            "worker": worker, "window": window, "spans": window["spans"],
            "saves": [], "setup_s": setup_s, "end_to_end": end_to_end,
            "trace": (_one(reports, "trace") or {}).get("reduced"),
        }
        wanted = cell.per_layer if trace else cell.end_to_end
        metrics: Dict[str, Dict[str, Any]] = {}
        for m in wanted:
            if trace:
                value = cells.layer_reader(cell, m["name"])(run_facts)
            else:
                value = end_to_end.get(m["name"])
                if value is None:
                    problems.append(f"no value for {m['name']}")
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        device["memory_peak_bytes"] = max(
            [window["program_bytes"]]
            + [b for b in window["peak_bytes_in_use"] if b])
        line: Dict[str, Any] = {
            "correct": not problems,
            "attempted": window["steps"], "failed": bad_losses,
            "metrics": metrics, "device": device}
        reduced = run_facts["trace"]
        if trace and reduced:
            from benchmarks import trace_reduce
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = trace_reduce.breakdown(reduced)
            say(kind="trace_reduced",
                **{k: v for k, v in reduced.items() if k != "ops"},
                ops=reduced["ops"][:40])
        elif trace:
            problems.append("the traced segment gave no device trace")
            line["correct"] = False
        if jax_backend_initialized():
            problems.append("the driver process opened a JAX backend")
            line["correct"] = False
        say(kind="verdict", problems=problems)
        return line
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(os.path.join(cell.root, RUNS_DIR, cell.name),
                      ignore_errors=True)
