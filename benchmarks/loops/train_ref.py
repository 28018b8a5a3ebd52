"""Traffic kind `train_ref`: kind `train`'s job for a model family that
brings its own plain reference.

The same job as `loops/train.py` — `JaxTrainer.fit()` with one granted
worker, one block a global batch in the object store before the window, the
benchmark's own loop in the worker: steps enqueued with a run-ahead of two,
each step's completion the moment its loss arrives, `tokens_per_s_per_chip`
from `median_step_seconds`, `setup_s` from process start to window start —
so both metrics mean what they mean in a `train` cell. What is imported from
`loops/train.py` is shared as it stands; the loop itself is written out
again here because `train.py`'s is one function around the GPT-2 reference.
Three differences:

1. The reference and its glue come from the configuration:
   `reference.module` (`loss_terms(tokens, top, layers, config) ->
   {"ce", ...}`, `config` being the configuration's file, which holds the
   published keys as run; optionally `counts` [L, E], `chosen` [L, T, k]) and
   `reference.glue` (`reference_weights(params, mesh, devices) -> (top,
   layers)`), both paths under the benchmark's directories. The reference is
   given one row at a time (a float32 4k x 4k score matrix a head is 1 GB a
   row). A new family is a configuration and these two files.
2. The first-loss check is on the cross-entropy alone (`ce_loss` of the
   step's metrics, `ppl_log` where a model reports no other term) and centred
   where a head initialised at 0.02 on unit-RMS inputs puts it:
   ln V + 0.02^2 d / 2, with the configuration's
   `reference.first_loss_halfwidth`.
3. Every metric of the step is fetched with its loss and reported, and where
   the model routes tokens to experts `correct` also needs
   `moe_expert_tokens` to sum to tokens x top-k x layers in every step of
   every report: no token dropped.

No saves: a checkpointing cell is kind `train`'s (`ckpt_every` must be 0).
"""

from __future__ import annotations

import collections
import glob
import importlib
import math
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

from benchmarks import cells, flops, spans as spans_mod, traffic_gen
from benchmarks.loops.train import (KEEP_TRACE_ENV, LOSS_FALL_MIN, RUNS_DIR,
                                    STEP_MODULE, _model_config, _one,
                                    median_step_seconds)

HEAD_INIT_STD = 0.02        # `GPT.init`: every matrix, the head among them


def expected_first_loss(model: Dict[str, Any]) -> float:
    """Cross-entropy at initialisation: logits of a head with entries of
    standard deviation 0.02 on inputs of unit RMS (the final norm's output)
    are normal with variance 0.02^2 d, and E[logsumexp] - E[logit of the
    target] = ln V + variance / 2."""
    return (math.log(model["vocab_size"])
            + HEAD_INIT_STD ** 2 * model["d_model"] / 2)


def _module(root: str, paths: List[str], relative: str):
    """A module of the benchmark by its path under one of `paths`."""
    path = cells._find(root, paths, *relative.split("/"))
    dotted = os.path.relpath(path, root)[:-len(".py")].replace(os.sep, ".")
    return importlib.import_module(dotted)


# -------------------------------------------------------------- worker side

def _reference_check(cfg, model, state, mesh, devices, tokens, sharding):
    """The system's evaluation against the plain reference's, on the same
    parameters and rows, at the run's real width: the cross-entropy, and
    where the model routes, the (token, expert) choices and the counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config = cfg["config"]
    reference = _module(cfg["root"], cfg["paths"],
                        config["reference"]["module"])
    glue = _module(cfg["root"], cfg["paths"], config["reference"]["glue"])

    def evaluate(params, batch):
        _, metrics = model.loss(params, batch)
        _, aux = model.forward_with_aux(params, batch["tokens"])
        return metrics, {k: aux[k] for k in ("moe_expert_tokens",
                                             "moe_expert_choice") if k in aux}

    tokens = jnp.asarray(tokens, jnp.int32)
    batch = {"tokens": jax.device_put(tokens, sharding)
             if sharding is not None else tokens}
    metrics, routing = jax.device_get(jax.jit(evaluate)(state.params, batch))
    out = {"system_loss": float(metrics.get("ce_loss", metrics["ppl_log"]))}

    top, layers = glue.reference_weights(state.params, mesh, devices)
    layers = list(layers)
    rows = [jax.device_get({k: v for k, v in reference.loss_terms(
        jax.device_put(tokens[i:i + 1], devices[0]), top, layers,
        config).items() if k != "logits"})
        for i in range(tokens.shape[0])]
    out["reference_loss"] = float(np.mean([r["ce"] for r in rows]))
    if routing and "chosen" in rows[0]:
        n_experts = routing["moe_expert_tokens"].shape[-1]
        chosen = np.concatenate([r["chosen"] for r in rows], axis=1)

        def mask(choice):       # [L, T, k] -> [L, T, E]
            return (choice[..., None] == np.arange(n_experts)).any(-2)

        agree = (mask(routing["moe_expert_choice"]) & mask(chosen)).sum()
        counts = np.sum([r["counts"] for r in rows], axis=0)
        out["choice_agreement"] = float(agree) / chosen.size
        out["counts_differ"] = int(np.abs(
            counts - routing["moe_expert_tokens"]).sum())
        out["choices"] = int(chosen.size)
    return out


def train_loop(cfg: Dict[str, Any]) -> None:
    first_line_wall = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.models import (GPT, init_train_state, make_optimizer,
                                make_train_step)
    from ray_tpu.models.training import batch_shardings, state_shardings
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from benchmarks import trace_reduce

    # cache every program, the small ones too: each run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event.endswith("backend_compile_duration") else None)

    devices = jax.devices()
    dev = devices[0]
    backend_init_s = time.time() - first_line_wall
    train.report({
        "kind": "worker", "pid": os.getpid(), "platform": dev.platform,
        "device_kind": dev.device_kind, "count": len(devices),
        "accelerator_ids":
            ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"],
        "gang_start_s": first_line_wall - cfg["fit_called_wall"],
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    if dev.platform != cfg["platform"] or len(devices) != cfg["chips"]:
        raise RuntimeError(
            f"the granted worker's JAX found {len(devices)} x "
            f"{dev.platform}, the cell needs {cfg['chips']} x "
            f"{cfg['platform']}")

    config, traffic = cfg["config"], cfg["traffic"]
    log = spans_mod.SpanLog()
    phases: Dict[str, float] = {"backend_init_s": backend_init_s}

    def phase(name: str, t0: float) -> float:
        phases[name] = time.perf_counter() - t0
        return time.perf_counter()

    # ---- state, on the device, in one jitted call from the seed (the key
    # is an argument: `loops/train.py` says why)
    t = time.perf_counter()
    mesh = build_mesh(MeshSpec(**config["mesh"])) if config["mesh"] else None
    model = GPT(_model_config(config), **({"mesh": mesh} if mesh else {}))
    optimizer = make_optimizer(**config["optimizer"])
    placement = (state_shardings(model, optimizer, mesh) if mesh is not None
                 else jax.sharding.SingleDeviceSharding(dev))
    state = jax.jit(lambda k: init_train_state(model, optimizer, k),
                    out_shardings=placement)(jax.random.PRNGKey(cfg["seed"]))
    jax.block_until_ready(state)
    state_bytes = sum(leaf.nbytes
                      for leaf in jax.tree_util.tree_leaves(state))
    t = phase("state_init_s", t)

    # ---- the reference check, at the published width, before the window
    sharding = batch_shardings(mesh) if mesh is not None else None
    checked = _reference_check(cfg, model, state, mesh, devices,
                               np.asarray(cfg["reference_rows"]), sharding)
    t = phase("reference_check_s", t)

    # ---- the one step shape: compile (or cache hit), then warm up
    batch_rows = config["batch_per_chip"] * cfg["chips"]
    tokens_per_step = batch_rows * traffic["seq_len"]
    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=batch_rows, dtype=jnp.int32, sharding=sharding)
    first = next(batches)
    lowered = make_train_step(model, optimizer, mesh=mesh).lower(state, first)
    hlo = lowered.as_text()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # as `loops/train.py` counts it: temporaries (which on the TPU span the
    # donated state) plus the arguments that are not donated
    program_bytes = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     - mem.alias_size_in_bytes)
    t = phase("step_compile_s", t)

    steps_done = 0
    pending: collections.deque = collections.deque()    # (step, metrics)
    records: List[Dict[str, Any]] = []  # every fetched step's metrics
    done: List[tuple] = []      # (segment, step, host clock at its loss)
    segment = 0                 # the window's start opens a new one
    unreported = 0
    in_flight = int(traffic["loop"]["max_in_flight"])
    report_every = int(traffic["report_every"])

    def fetch() -> None:
        """The oldest step in flight: wait for its loss. It arrives when
        the step's program ends, so that moment is the step's completion;
        the step's other metrics are outputs of the same program."""
        step, metrics = pending.popleft()
        loss = float(metrics["loss"])
        done.append((segment, step, time.perf_counter()))
        rest = jax.device_get({k: v for k, v in metrics.items()
                               if k != "loss"})
        records.append({"loss": loss, **{
            k: float(v) if v.ndim == 0 else v.tolist()
            for k, v in rest.items()}})

    def one_step() -> bool:
        nonlocal state, steps_done, unreported
        with log.span("batch_wait"):
            batch = next(batches, None)
        if batch is None:
            return False
        with log.span("step_enqueue"):
            state, metrics = compiled(state, batch)
        steps_done += 1
        pending.append((steps_done, metrics))
        if len(pending) > in_flight:
            with log.span("loss_fetch"):
                fetch()
            unreported += 1
        if steps_done % report_every == 0 and unreported:
            with log.span("report"):
                train.report({"kind": "losses",
                              "until_step": steps_done - len(pending),
                              "steps": records[-unreported:]})
            unreported = 0
        return True

    for _ in range(int(traffic["warmup_steps"])):
        one_step()
    while pending:
        fetch()
    jax.block_until_ready(state)
    t = phase("warmup_steps_s", t)
    compiles_before = len(compiles)

    # ---- the window
    window_wall = time.time()
    w0 = time.perf_counter()
    w0_ns = time.perf_counter_ns()
    first_window_step = steps_done
    segment += 1
    first_window_row = len(done)
    exhausted = False
    while time.perf_counter() - w0 < cfg["seconds"]:
        if not one_step():
            exhausted = True
            break
    with log.span("drain"):
        while pending:
            fetch()
        jax.block_until_ready(state)
    w1 = time.perf_counter()
    w1_ns = time.perf_counter_ns()
    window_steps = steps_done - first_window_step
    compiles_in_window = len(compiles) - compiles_before
    # the window's mean rate: every late wake-up of the host that drains the
    # device queue is in it
    window_rate = window_steps * tokens_per_step / (w1 - w0) / cfg["chips"]
    # the rate of the steps, the end-to-end metric: a median over the window
    window_done = done[first_window_row:]
    step_s = median_step_seconds(window_done)
    tokens_per_s_per_chip = (tokens_per_step / step_s / cfg["chips"]
                             if step_s else window_rate)
    memory = [d.memory_stats() or {} for d in devices]

    train.report({
        "kind": "window", "window_start_wall": window_wall,
        "seconds": w1 - w0, "steps": window_steps,
        "blocked_in_saves_s": 0.0, "tokens_per_step": tokens_per_step,
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "median_step_s": step_s, "steps_timed": len(window_done),
        "window_tokens_per_s_per_chip": window_rate,
        "goodput_tokens_per_s_per_chip": None,
        "compiles_in_window": compiles_in_window,
        "stream_exhausted": exhausted,
        "step_records": records, "first_window_record": first_window_row,
        "losses": [r["loss"] for r in records],
        "spans": log.summary(w0_ns, w1_ns),
        "phases": phases, "state_bytes": state_bytes,
        "program_bytes": program_bytes,
        "memory_analysis": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "code": mem.generated_code_size_in_bytes},
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in memory],
        "bytes_limit": [m.get("bytes_limit") for m in memory],
        "pallas_custom_calls": hlo.count("tpu_custom_call"),
        "reference": checked,
        "system_loss": checked["system_loss"],
        "reference_loss": checked["reference_loss"],
        "compile_seconds": compiles[:compiles_before]})

    # ---- the traced segment, after the window: trace_steps whole steps,
    # then one step more, whose start closes the traced window
    if cfg["trace"]:
        trace_dir = os.path.join(cfg["storage"], "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t_trace = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        log.annotate = True
        for _ in range(int(traffic["trace_steps"]) + 1):
            one_step()
        with log.span("drain"):
            jax.block_until_ready(state)
        log.annotate = False
        jax.profiler.stop_trace()
        t_reduce = time.perf_counter()
        found = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        reduced, error = None, None
        try:
            if found:
                reduced = trace_reduce.reduce_file(found[-1], STEP_MODULE)
        except Exception as e:      # noqa: BLE001 — reported, run goes on
            error = repr(e)
        keep = os.environ.get(KEEP_TRACE_ENV)
        if keep and found:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(found[-1], keep)
        train.report({"kind": "trace", "reduced": reduced, "error": error,
                      "xplane_bytes": os.path.getsize(found[-1])
                      if found else 0,
                      "trace_s": t_reduce - t_trace,
                      "reduce_s": time.perf_counter() - t_reduce})

    train.report({"kind": "done", "steps": steps_done, "saves": []})


# -------------------------------------------------------------- driver side

def _routing_problems(model: Dict[str, Any], reports, tokens_per_step: int
                      ) -> List[str]:
    """Where the model routes: every step of every report sent each of its
    tokens to top-k experts in every layer."""
    if not model.get("n_experts"):
        return []
    want = tokens_per_step * int(model["moe_top_k"]) * int(model["n_layers"])
    steps = [s for r in reports if r.get("kind") == "losses"
             for s in r["steps"]]
    short = [s for s in steps
             if sum(s.get("moe_expert_tokens", ())) != want]
    if not steps:
        return ["no report carried the steps' metrics"]
    if short:
        return [f"{len(short)} of {len(steps)} reported steps routed other "
                f"than {want} (token, expert) pairs: first "
                f"{sum(short[0].get('moe_expert_tokens', ()))}"]
    return []


def run(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
        process_start_wall: float, rehearsal: Optional[Dict[str, Any]],
        say) -> Dict[str, Any]:
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu._private.accelerators import jax_backend_initialized
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, traffic = cell.config, cell.traffic
    if int(traffic.get("ckpt_every") or 0):
        raise ValueError("kind train_ref makes no saves: a checkpointing "
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
        halfwidth = config["reference"]["first_loss_halfwidth"]
        if ce and not abs(ce[0] - centre) <= halfwidth:
            problems.append(f"first cross-entropy {ce[0]:.4f} is not within "
                            f"{halfwidth} of ln V + 0.02^2 d / 2 = "
                            f"{centre:.4f}")
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
        problems.extend(_routing_problems(model, reports,
                                          window["tokens_per_step"]))
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
        run_facts = {
            "cell": {"name": cell.name, "chips": cell.chips,
                     "config": config, "traffic": traffic},
            "peaks": peak, "device": device,
            "flops_per_token": flops.model_flops_per_token(
                model, traffic["seq_len"]),
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
