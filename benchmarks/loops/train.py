"""Traffic kind `train`: a training job through JaxTrainer + ray_tpu.data.

Two halves. `run()` is the driver: it starts the runtime without opening a
JAX backend, makes the packed rows from the seed, puts one block per global
batch into the object store, and calls `JaxTrainer.fit()` with
`ScalingConfig(num_workers=1, use_tpu=True)`. `train_loop()` is "user code",
the loop a competent user would write, and runs inside the `_TrainWorker`
the trainer starts — the only process that holds the chips. It reports
through `train.report`; the driver turns the reports into the result line.

The loop is the yardstick, so it lives here and not in `ray_tpu/`: `step()`
is enqueued without a per-step `block_until_ready`; the scalar loss of step
i-2 is fetched after step i is enqueued (run-ahead of two, the device queue
never drains), the step's other metrics with it (outputs of the same
program); the window is closed by one `block_until_ready`; every call into a
layer of the system sits in a host span (`spans.py`).

The rate of the steps is a median over the window: the moment each step's
loss arrives is the moment the step completed, and `tokens_per_s_per_chip`
is tokens a step over the median time between consecutive completions
(`median_step_seconds`). A host that wakes late once (seen: 0.05 to 0.8 s
on a quiet machine, seconds on a shared one) moves two readings of a hundred
and not the median; what such stalls cost the window is the per-layer metric
`window_idle_share`.

One loop runs every model. What differs between models is named by the
configuration's file, each a path under the benchmark's directories:

- `reference.module`: the plain reference, `training(config, operands) ->
  {"embed", "block", "head", "routes"}` (`config` being the configuration's
  file), the model in the pieces `reference/train_steps.py` differentiates,
  and `reference.glue`: `reference_weights(params, mesh, devices) -> (top,
  layers)`, a tree shaped like the program's parameters in the reference's
  layout. `reference.objective` and `reference.adamw` state the training
  objective's coefficients and the optimizer's step, `reference.steps` how
  many of the timed object's first steps are followed (3) and
  `reference.rows_per_pass` how many rows a device the reference takes at
  once.
- What `correct` compares (`step_check.py`): the warm-up IS the timed
  object's first steps — the compiled step the window times, from the seeded
  state, on the traffic's first blocks after the one it was lowered on,
  through the window's own call and feed. The loop keeps those steps'
  records and batches, every leaf's norm of the optimizer's first moment
  after one step and of the parameters' change after the last followed step
  (both through the glue, so leaf by leaf in the reference's layout). After
  the window has closed, the peak has been read, the trace taken and the
  trained state freed, the seeded parameters are made again and the
  reference follows the same steps on the same batches, all rows, in
  float32 at "highest" with AdamW written out. Held to `step_loss_atol`,
  `grad_norm_rtol`, `grad_leaf_rtol`, `change_leaf_rtol`; where the model
  routes, the first timed step's per-expert counts against the reference's
  on that batch (`counts_differ_max`) and the choices of the program's
  evaluation on that batch (`choice_agreement_min`).
- `reference.first_loss_halfwidth`: the first step's cross-entropy
  (`ce_loss` of the step's metrics; `ppl_log` where a model reports no other
  term) is what a head initialised at 0.02 on unit-RMS inputs gives,
  `expected_first_loss`, within it.
- `work.module`: `model_flops_per_token(model, seq_len[, pairs_per_token])`,
  the pairs being what the steps reported as routed to the experts held here
  (`moe_work.pairs_per_token`; left out where they report none), and
  optionally `work.routing_check`, a function of that module,
  `(model, steps, checked, reference, tokens_per_step) -> [problem, ...]`
  over every reported step's metrics and the reference check's result.

Saves are a traffic parameter: `ckpt_every` steps (0 = none), through the
system's checkpoint path, each watched from the driver's side until it is
durable and the last one read back. Set-up runs the save path's one-off work
(the checksum's program, orbax's import) and no whole save: `setup_s` swung
with the machine's disk while two stood there.

What a loop module must provide (see README.md): `run(cell, *, seed,
seconds, trace, process_start_wall, rehearsal, say) -> dict`, the result
line.
"""

from __future__ import annotations

import collections
import gc
import glob
import importlib
import json
import math
import os
import re
import shutil
import statistics
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from benchmarks import (cells, moe_work, spans as spans_mod, step_check,
                        traffic_gen)

WORKER_SAVES = "worker_saves"   # in a run's directory: where the loop writes
KEEP_TRACE_ENV = "BENCH_KEEP_TRACE_DIR"   # copy the raw trace here (debugging)
STEP_MODULE = "train_step"      # in the step program's name in the trace

# The cross-entropy must fall: by step 40 of the warm-up schedule every chip
# run of PR 23 had fallen by more than 1.5 nats toward the unigram entropy
# (6.46); 0.5 leaves room and still fails a model that does not learn.
LOSS_FALL_MIN = 0.5
HEAD_INIT_STD = 0.02        # `GPT.init`: every matrix, the head among them
# float32 sum of squares on the device against float64 on the host
CHECKSUM_RTOL = 1e-3


def median_step_seconds(done) -> Optional[float]:
    """The median time between the completions of consecutive steps.

    `done` holds one row per step, in order: (segment, step, seconds on the
    host clock when its loss arrived). A segment is a stretch of steps with
    no save in it; only neighbours within a segment are compared, so neither
    a save nor the refilling of the device queue after it is in any reading.
    While the loop runs ahead of the device a reading is the device's step;
    a loss that arrives late makes one reading long and the next short, and
    the median keeps to the step. None if no two steps were neighbours."""
    gaps = [t1 - t0 for (seg0, step0, t0), (seg1, step1, t1)
            in zip(done, done[1:]) if seg1 == seg0 and step1 == step0 + 1]
    return statistics.median(gaps) if gaps else None


def expected_first_loss(model: Dict[str, Any]) -> float:
    """Cross-entropy at initialisation: logits of a head with entries of
    standard deviation 0.02 on inputs of unit RMS (the final norm's output)
    are normal with variance 0.02^2 d, and E[logsumexp] - E[logit of the
    target] = ln V + variance / 2."""
    return (math.log(model["vocab_size"])
            + HEAD_INIT_STD ** 2 * model["d_model"] / 2)


def first_loss_problems(ce: List[float], model: Dict[str, Any],
                        halfwidth: float) -> List[str]:
    """The first step's cross-entropy is what the seeded initialisation
    gives: a head or an embedding at another scale, or a loss over another
    slice of the vocabulary, moves it by more than the half-width."""
    centre = expected_first_loss(model)
    if ce and not abs(ce[0] - centre) <= halfwidth:
        return [f"first cross-entropy {ce[0]:.4f} is not within {halfwidth} "
                f"of ln V + 0.02^2 d / 2 = {centre:.4f}"]
    return []


# ------------------------------------------------------------------ helpers


def _key(path) -> str:
    """A pytree key path as `a/b/0/c`, the same for jax's and orbax's
    spellings of it."""
    import jax
    return "/".join(re.findall(r"[A-Za-z_0-9]+",
                               jax.tree_util.keystr(path)))


def _dir_bytes(path: str) -> int:
    total = 0
    for base, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(base, name))
            except OSError:
                pass
    return total


def _durable_save(path: str) -> Optional[int]:
    """The index of the save that the directory `path` (a `checkpoint_*` of
    the experiment directory) holds completely, else None: it carries that
    save's report (`.rtpu_metrics.json`, which `train.report` writes into
    the checkpoint) and every byte the worker counted after writing it."""
    try:
        with open(os.path.join(path, ".rtpu_metrics.json")) as f:
            report = json.load(f)
    except (OSError, ValueError):
        return None
    if _dir_bytes(os.path.join(path, "pytree")) != report.get("bytes"):
        return None
    return report["save"]


def _dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _model_config(config: Dict[str, Any]):
    from ray_tpu.models.gpt import GPTConfig
    kw = dict(config["model"])
    kw["dtype"] = _dtype(kw["dtype"])
    kw["param_dtype"] = _dtype(kw["param_dtype"])
    return GPTConfig(**kw)


# -------------------------------------------------------------- worker side

def _first_moment(opt_state):
    """Adam's first moment inside an optax state: a tree shaped like the
    parameters."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _first_moment(part)
            if found is not None:
                return found
    return None


def _kept_sumsq(cfg, mesh, devices, seeded=None):
    """A jitted `(tree, key) -> {leaf: sum of squares}` over a tree shaped
    like the program's parameters, leaf by leaf in the reference's layout
    (the glue is traced, so nothing is copied out and the process's peak
    stays the step's); with `seeded`, of the tree less `seeded(key)`."""
    import jax
    import jax.numpy as jnp
    _, glue, train_steps = _reference_modules(cfg)

    def sums(tree, key):
        if seeded is not None:
            tree = jax.tree_util.tree_map(jnp.subtract, tree, seeded(key))
        return train_steps.traced_sumsq(
            *glue.reference_weights(tree, mesh, devices))

    def floats(tree, key):
        return {k: float(v) for k, v in
                jax.device_get(jitted(tree, key)).items()}

    jitted = jax.jit(sums)
    return floats


def _reference_modules(cfg):
    from benchmarks.reference import train_steps
    group = cfg["config"]["reference"]
    return (cells.module(cfg["root"], cfg["paths"], group["module"]),
            cells.module(cfg["root"], cfg["paths"], group["glue"]),
            train_steps)


def _choice_agreement(ours, theirs, n_experts: int) -> float:
    """The share of (token, expert) choices, [L, T, k] each, that two
    routings have in common, a layer at a time."""
    import numpy as np

    def mask(choices):          # [1, T, k] -> [1, T, E]
        return (choices[..., None] == np.arange(n_experts)).any(-2)

    agree = sum(int((mask(ours[i:i + 1]) & mask(theirs[i:i + 1])).sum())
                for i in range(theirs.shape[0]))
    return agree / theirs.size


def _routing_against(model, params, batch, first_record, followed):
    """Where the model routes: the program's evaluation of the first timed
    batch on the seeded parameters gives its (token, expert) choices; the
    counts are the timed first step's own. Both against the reference's on
    that batch."""
    import jax
    import numpy as np

    choice = jax.device_get(jax.jit(
        lambda p, tokens: model.forward_with_aux(p, tokens)[1][
            "moe_expert_choice"])(params, batch))       # [L, B*S, k]
    chosen, counts = followed["chosen"], followed["counts"]
    given = np.asarray(first_record["moe_expert_tokens"])
    return {"choice_agreement": _choice_agreement(choice, chosen,
                                                  counts.shape[-1]),
            "choices": int(chosen.size),
            "counts_differ": int(np.abs(
                _counts_as_reported(counts, given.ndim, model.config)
                - given).sum())}


def _counts_as_reported(counts, ndim: int, c):
    """The reference's per-expert counts [L, E] in the form the step reports
    its own: summed over the layers where every expert is held ([E]), else
    what the held experts were given ([L, held])."""
    if ndim == 1:
        return counts.sum(0)
    return counts[:, c.moe_first_expert:c.moe_first_expert + c.experts_held]


def _follow_reference(cfg, model, init_params, mesh, devices, sharding,
                      batches, first_record):
    """After the window: the plain reference through the same first steps,
    from the seeded parameters made again, on the batches the timed steps
    were given. Returns what `step_check.compare` and the routing check
    read."""
    import jax
    import jax.numpy as jnp

    reference, glue, train_steps = _reference_modules(cfg)
    key = jax.random.PRNGKey(cfg["seed"])

    def start():
        return glue.reference_weights(init_params(key), mesh, devices)

    followed = train_steps.follow(reference, cfg["config"], start, batches,
                                  devices)
    out = {k: followed[k] for k in ("steps", "grad_sumsq", "change_sumsq")}
    if "chosen" in followed:
        tokens = jnp.asarray(batches[0], jnp.int32)
        if sharding is not None:
            tokens = jax.device_put(tokens, sharding)
        out["routing"] = _routing_against(model, init_params(key), tokens,
                                          first_record, followed)
    return out


def train_loop(cfg: Dict[str, Any]) -> None:
    first_line_wall = time.time()
    if cfg.get("patch"):
        # tests only (`rehearsal`): break the program underneath, here in
        # the worker, before anything of it is imported by name
        module, _, name = cfg["patch"].partition(":")
        getattr(importlib.import_module(module), name)()
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

    # ---- state, on the device, in one jitted call from the seed
    t = time.perf_counter()
    mesh = build_mesh(MeshSpec(**config["mesh"])) if config["mesh"] else None
    model = GPT(_model_config(config), **({"mesh": mesh} if mesh else {}))
    optimizer = make_optimizer(**config["optimizer"])
    # The key is an argument (`init_train_state(mesh=...)` closes over it,
    # so every seed would be a new program and a 31 s compile on four chips);
    # one chip: placed explicitly, as a restored state is (PR 22's finding).
    placement = (state_shardings(model, optimizer, mesh) if mesh is not None
                 else jax.sharding.SingleDeviceSharding(dev))
    state = jax.jit(lambda k: init_train_state(model, optimizer, k),
                    out_shardings=placement)(jax.random.PRNGKey(cfg["seed"]))
    jax.block_until_ready(state)
    state_bytes = sum(leaf.nbytes
                      for leaf in jax.tree_util.tree_leaves(state))
    t = phase("state_init_s", t)

    sharding = batch_shardings(mesh) if mesh is not None else None
    init_params = jax.jit(model.init, out_shardings=(
        placement.params if mesh is not None else placement))
    followed_steps = int(config["reference"].get("steps", 3))
    kept_batches: List[Any] = []        # of the first followed steps
    system: Dict[str, Any] = {}         # what step_check.compare reads

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
    # On the TPU the donated state's buffers are reused, so the compiler's
    # "temporaries" already span them (arguments + temporaries would exceed
    # the chip); what comes on top is the arguments that are not donated.
    program_bytes = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     - mem.alias_size_in_bytes)
    t = phase("step_compile_s", t)

    checksum = jax.jit(lambda s: {
        _key(p): jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        for p, leaf in jax.tree_util.tree_flatten_with_path(s)[0]})

    steps_done = 0
    pending: collections.deque = collections.deque()    # (step, metrics)
    records: List[Dict[str, Any]] = []  # every fetched step's metrics
    done: List[tuple] = []      # (segment, step, host clock at its loss)
    segment = 0                 # a save or the window's start opens a new one
    unreported = 0
    saves: List[Dict[str, Any]] = []
    open_save: Optional[Dict[str, Any]] = None
    in_flight = int(traffic["loop"]["max_in_flight"])
    report_every = int(traffic["report_every"])
    ckpt_every = int(traffic.get("ckpt_every") or 0)
    save_root = os.path.join(cfg["storage"], WORKER_SAVES)

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
        nonlocal state, steps_done, unreported, open_save
        with log.span("batch_wait"):
            batch = next(batches, None)
        if batch is None:
            return False
        if steps_done < followed_steps:
            kept_batches.append(np.asarray(jax.device_get(batch["tokens"])))
        if open_save is not None:
            # the loop is moving again. The stall ends where the step is
            # handed over: the first call after a save takes 0.2 s, and the
            # device is already working through it.
            open_save["stall_s"] = time.perf_counter() - open_save.pop("_t0")
            open_save = None
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

    def save(traced: bool = False) -> None:
        """The whole TrainState through the system's checkpoint path."""
        nonlocal open_save, unreported, segment
        # The steps in flight first: their losses are due anyway, and any
        # save has to wait for the state they produce (the next step donates
        # it). So the stall below holds no device work of the loop's own.
        with log.span("loss_fetch"):
            while pending:
                fetch()
                unreported += 1
        segment += 1
        index = len(saves)
        sums = checksum(state)
        record = {"save": index, "step": steps_done, "traced": traced,
                  "start_wall": time.time(), "_t0": time.perf_counter()}
        with log.span("ckpt_write"):
            ckpt = train.Checkpoint.from_pytree(
                state, dir=os.path.join(save_root, f"save_{index:04d}"))
        record["write_s"] = time.perf_counter() - record["_t0"]
        record["return_t"] = time.perf_counter()
        record["bytes"] = _dir_bytes(os.path.join(ckpt.path, "pytree"))
        record["checksums"] = {k: float(v)
                               for k, v in jax.device_get(sums).items()}
        with log.span("report"):
            train.report({"kind": "save", **{k: v for k, v in record.items()
                                             if not k.startswith("_")}},
                         checkpoint=ckpt)
        saves.append(record)
        open_save = record

    # ---- the warm-up: the timed object's first steps, which the reference
    # follows after the window. After one step the optimizer's first moment
    # is (1 - b1) x the gradient it got; after the last followed step the
    # parameters' change from the seeded ones; each leaf by leaf through the
    # glue, before the next step donates the state.
    t_kept = 0.0
    seed_key = jax.random.PRNGKey(cfg["seed"])
    for i in range(1, max(int(traffic["warmup_steps"]), followed_steps) + 1):
        one_step()
        t0 = time.perf_counter()
        if i == 1:     # (1 - b1) x the gradient Adam got
            system["moment_sumsq"] = _kept_sumsq(cfg, mesh, devices)(
                _first_moment(state.opt_state), seed_key)
        if i == followed_steps:
            system["change_sumsq"] = _kept_sumsq(
                cfg, mesh, devices, model.init)(state.params, seed_key)
        t_kept += time.perf_counter() - t0
    while pending:
        fetch()
    jax.block_until_ready(state)
    system["records"] = records[:followed_steps]
    phases["first_steps_kept_s"] = t_kept
    t = phase("warmup_steps_s", t)
    if ckpt_every:
        # the save path's one-off costs: the checksum's program and the
        # import `Checkpoint.from_pytree` makes on its first call. No whole
        # save: each is the state twice through the machine's disk, and
        # set-up swung with that disk (PERF.md section 6, PR 44)
        jax.block_until_ready(checksum(state))
        t = phase("save_checksum_s", t)
        import orbax.checkpoint     # noqa: F401
        t = phase("save_import_s", t)
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
        if ckpt_every and (steps_done - first_window_step) % ckpt_every == 0:
            save()
    if open_save is not None and not exhausted:
        one_step()      # not counted: it only ends the last save's stall
    with log.span("drain"):
        while pending:
            fetch()
        jax.block_until_ready(state)
    w1 = time.perf_counter()
    w1_ns = time.perf_counter_ns()
    window_steps = steps_done - first_window_step
    compiles_in_window = len(compiles) - compiles_before
    # the window's mean rate: its steps over the window less the seconds the
    # loop was blocked in saves (none in a cell without saves). Every late
    # wake-up of the host that drains the device queue is in it.
    blocked_s = sum(s.get("stall_s", 0.0) for s in saves)
    window_rate = (window_steps * tokens_per_step
                   / (w1 - w0 - blocked_s) / cfg["chips"])
    # the rate of the steps, the end-to-end metric: a median over the window
    window_done = done[first_window_row:]
    step_s = median_step_seconds(window_done)
    tokens_per_s_per_chip = (tokens_per_step / step_s / cfg["chips"]
                             if step_s else window_rate)
    # goodput: whole save cycles only, each from one save's return to the
    # next, stalls and all
    goodput = None
    if saves:
        goodput = (len(saves) * ckpt_every * tokens_per_step
                   / (saves[-1]["return_t"] - w0) / cfg["chips"])
    memory = [d.memory_stats() or {} for d in devices]

    train.report({
        "kind": "window", "window_start_wall": window_wall,
        "seconds": w1 - w0, "steps": window_steps,
        "blocked_in_saves_s": blocked_s, "tokens_per_step": tokens_per_step,
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "median_step_s": step_s, "steps_timed": len(window_done),
        "window_tokens_per_s_per_chip": window_rate,
        "goodput_tokens_per_s_per_chip": goodput,
        "compiles_in_window": compiles_in_window,
        "stream_exhausted": exhausted,
        "step_records": records, "first_window_record": first_window_row,
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
        "first_steps": system,
        "compile_seconds": compiles[:compiles_before]})

    # ---- the traced segment, after the window: trace_steps whole steps
    # (in a checkpointing cell followed by one save), then one step more,
    # whose start closes the traced window
    if cfg["trace"]:
        trace_dir = os.path.join(cfg["storage"], "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t_trace = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        log.annotate = True
        for _ in range(int(traffic["trace_steps"])):
            one_step()
        if ckpt_every:
            save(traced=True)
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

    # ---- the reference, after the window: its peak has been read and the
    # trained state goes first
    # The step's executable and every cached program go with it: what a
    # loaded program holds on the chip is in no `bytes_in_use` (after one
    # follow olmoe-steady's readings process had 246 MB free of 15.75 GiB
    # with 57 MB in use).
    t_check = time.perf_counter()
    del state, compiled, lowered
    jax.clear_caches()
    gc.collect()
    followed = _follow_reference(cfg, model, init_params, mesh, devices,
                                 sharding, kept_batches, system["records"][0])
    train.report({"kind": "reference", **followed,
                  "reference_check_s": time.perf_counter() - t_check})

    train.report({"kind": "done", "steps": steps_done,
                  "saves": [{k: v for k, v in s.items()
                             if not k.startswith("_") and k != "checksums"}
                            for s in saves]})


# -------------------------------------------------------------- driver side

class _DurableWatcher(threading.Thread):
    """Watches the experiment directory from the benchmark's side: a save is
    durable when a `checkpoint_*` directory there carries that save's report
    (`.rtpu_metrics.json`, written by `train.report`) and all the bytes the
    worker counted. Keeps the newest `keep_last` durable ones, as a job's
    retention would (the trainer's own `num_to_keep` is not used: see
    PERF.md, Open questions), and removes the loop's own copy of a save once
    the driver's is whole — off the loop's thread, where deleting 4 GB took
    seconds."""

    def __init__(self, storage: str, keep_last: int):
        super().__init__(name="bench-durable-watcher", daemon=True)
        self.exp_dir = os.path.join(storage, "fit")
        self.worker_saves = os.path.join(storage, WORKER_SAVES)
        self.keep_last = keep_last
        self.durable: Dict[int, Dict[str, Any]] = {}    # save -> facts
        self._done: Dict[str, int] = {}                 # dir -> save
        self._stop_event = threading.Event()

    def run(self) -> None:
        while not self._stop_event.is_set():
            self.poll()
            self._stop_event.wait(0.05)
        self.poll()

    def stop(self) -> None:
        self._stop_event.set()
        self.join()

    def poll(self) -> None:
        for path in sorted(glob.glob(os.path.join(self.exp_dir,
                                                  "checkpoint_*"))):
            if path in self._done:
                continue
            index = _durable_save(path)
            if index is None:
                continue
            now = time.time()
            with open(os.path.join(path, ".rtpu_metrics.json")) as f:
                started = json.load(f)["start_wall"]
            self._done[path] = index
            self.durable[index] = {"path": path, "durable_wall": now,
                                   "durable_s": now - started}
            # the loop's own copy has served once the driver's is whole
            shutil.rmtree(os.path.join(self.worker_saves,
                                       f"save_{index:04d}"),
                          ignore_errors=True)
            for old in sorted(self._done, key=self._done.get
                              )[:-self.keep_last]:
                shutil.rmtree(old, ignore_errors=True)


def _read_back(path: str) -> Dict[str, Any]:
    """Runs as a CPU-pinned task: the checkpoint as numpy arrays, then the
    step number and the sum of squares of every leaf, in float64."""
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    target = os.path.join(path, "pytree")
    checkpointer = ocp.PyTreeCheckpointer()
    meta = checkpointer.metadata(target)
    tree = getattr(getattr(meta, "item_metadata", meta), "tree", None)
    if tree is None:
        tree = meta
    restored = checkpointer.restore(target, restore_args=jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree))

    def sum_of_squares(leaf) -> float:
        flat, total, chunk = np.asarray(leaf).ravel(), 0.0, 1 << 22
        for lo in range(0, flat.size, chunk):
            part = flat[lo:lo + chunk].astype(np.float64)
            total += float(part @ part)
        return total

    return {"platform": jax.default_backend(),
            "step": int(restored["step"]),
            "checksums": {
                _key(p): sum_of_squares(leaf) for p, leaf in
                jax.tree_util.tree_flatten_with_path(restored)[0]}}


def _one(reports: List[Dict[str, Any]], kind: str) -> Optional[Dict[str, Any]]:
    found = [r for r in reports if r.get("kind") == kind]
    return found[-1] if found else None


def run(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
        process_start_wall: float, rehearsal: Optional[Dict[str, Any]],
        say) -> Dict[str, Any]:
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu._private.accelerators import jax_backend_initialized
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, traffic = cell.config, cell.traffic
    work = cells.module(cell.root, cell.paths, config["work"]["module"])
    platform = "cpu" if rehearsal else "tpu"
    peaks_table = cells.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "peaks.json"))
    problems: List[str] = []
    compared: List[List[Any]] = []      # [name, what, value, limit]

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

        storage = os.path.join(cell.root, cells.RUNS_DIR, cell.name)
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
        say(kind="traffic", blocks=n_blocks, rows_per_block=batch_rows,
            make_s=time.perf_counter() - t1)

        loop_config = {
            "config": config, "traffic": traffic, "chips": cell.chips,
            "platform": platform, "seed": seed, "seconds": seconds,
            "trace": trace, "storage": storage, "root": cell.root,
            "paths": cell.paths,
            "patch": (rehearsal or {}).get("patch")}
        ckpt_every = int(traffic.get("ckpt_every") or 0)
        watcher = None
        if ckpt_every:
            watcher = _DurableWatcher(storage, int(traffic["keep_last"]))
            watcher.start()
        loop_config["fit_called_wall"] = time.time()
        result = JaxTrainer(
            train_loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            datasets={"train": dataset},
            run_config=RunConfig(name="fit", storage_path=storage)).fit()
        if watcher is not None:
            watcher.stop()
        reports = result.metrics_history
        for r in reports:
            if r.get("kind") in ("worker", "trace", "done", "reference"):
                say(**{k: v for k, v in r.items()
                       if k not in ("reduced", "grad_sumsq", "change_sumsq")})

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
        followed = _one(reports, "reference")
        if followed is not None:    # the check's seconds, after the window
            window["phases"]["reference_check_s"] = followed[
                "reference_check_s"]
        say(kind="window", **{k: window[k] for k in (
            "seconds", "steps", "blocked_in_saves_s",
            "tokens_per_s_per_chip", "median_step_s", "steps_timed",
            "window_tokens_per_s_per_chip", "goodput_tokens_per_s_per_chip",
            "compiles_in_window", "phases",
            "state_bytes", "program_bytes", "memory_analysis",
            "peak_bytes_in_use", "pallas_custom_calls",
            "compile_seconds", "spans")})
        records = window["step_records"]
        model = config["model"]
        reference = config["reference"]
        if window["compiles_in_window"]:
            problems.append(f"{window['compiles_in_window']} compilation(s) "
                            f"inside the window")
        if window["stream_exhausted"]:
            problems.append("the traffic file's blocks ran out before the "
                            "window closed")
        bad_losses = sum(1 for r in records if not math.isfinite(r["loss"]))
        # the cross-entropy alone, where the model adds other terms
        ce = [r.get("ce_loss", r["ppl_log"]) for r in records]
        centre = expected_first_loss(model)
        problems.extend(first_loss_problems(
            ce, model, reference["first_loss_halfwidth"]))
        last = statistics.fmean(ce[-10:]) if ce else float("nan")
        if not ce or not last < ce[0] - LOSS_FALL_MIN:
            problems.append(f"cross-entropy did not fall by {LOSS_FALL_MIN}: "
                            f"first {ce[:1]}, mean of last ten {last:.4f}")
        # the timed object's first steps against the reference's
        if followed is None:
            problems.append("the reference never followed the first steps")
            followed, step_rows = {}, []
        else:
            step_rows, step_problems = step_check.compare(
                window["first_steps"], followed, reference)
            problems.extend(step_problems)
        checked = followed.get("routing", {})
        agreement = reference.get("choice_agreement_min")
        if agreement is not None and not checked.get(
                "choice_agreement", 0.0) >= agreement:
            problems.append(
                f"{checked.get('choice_agreement')} of the (token, expert) "
                f"choices agree with the reference's, under {agreement}")
        if config["work"].get("routing_check"):
            problems.extend(getattr(work, config["work"]["routing_check"])(
                model, [step for r in reports if r.get("kind") == "losses"
                        for step in r["steps"]],
                checked, reference, window["tokens_per_step"]))
        if platform == "tpu" and not window["pallas_custom_calls"]:
            problems.append("no tpu_custom_call in the step: attention did "
                            "not lower to the Pallas kernels")
        # each number compared, beside its limit (what a model has none of
        # is left out)
        compared += [row for row in [
            ["first_ce_off_centre",
             "first cross-entropy less ln V + 0.02^2 d / 2",
             ce[0] - centre if ce else None,
             reference["first_loss_halfwidth"]],
            ["ce_fall", "first cross-entropy less the mean of the last ten "
             "(at least)", ce[0] - last if ce else None, LOSS_FALL_MIN],
            *step_rows,
            ["choice_agreement", "share of (token, expert) choices on the "
             "first timed batch that agree (at least)",
             checked.get("choice_agreement"), agreement],
            ["counts_differ", "per-expert counts of the first timed step "
             "that differ from the reference's",
             checked.get("counts_differ"),
             reference.get("counts_differ_max")]]
            if row[2] is not None and row[3] is not None]
        in_window = records[window["first_window_record"]:]
        say(kind="losses", first=records[:3], last_ten_ce_mean=last,
            n=len(records), expected_first_ce=centre,
            window_medians={
                k: statistics.median(r[k] for r in in_window)
                for k in (in_window[0] if in_window else {})
                if isinstance(in_window[0][k], float)},
            unigram_entropy=traffic_gen.unigram_entropy(traffic["tokens"]))

        # ---- the saves
        done = _one(reports, "done") or {"saves": []}
        saves = done["saves"]
        failed_saves = 0
        for s in saves:
            facts = watcher.durable.get(s["save"]) if watcher else None
            s["durable_s"] = facts["durable_s"] if facts else None
            if facts is None:
                failed_saves += 1
        if failed_saves:
            problems.append(f"{failed_saves} save(s) never became durable")
        if ckpt_every:
            say(kind="saves", saves=saves)
            if not [s for s in saves if not s["traced"]]:
                problems.append("no whole save cycle inside the window")
            read_back_problems, worst = _check_last_checkpoint(
                reports, watcher, say)
            problems.extend(read_back_problems)
            compared.append(["ckpt_leaf_error", "worst relative error of a "
                             "leaf's sum of squares, read back", worst,
                             CHECKSUM_RTOL])

        # ---- metrics
        setup_s = window["window_start_wall"] - process_start_wall
        timed = [s for s in saves if not s["traced"]]
        end_to_end = {
            "tokens_per_s_per_chip": window["tokens_per_s_per_chip"],
            "setup_s": setup_s}
        # where the steps report the pairs routed to the experts held here,
        # the model's work follows them
        pairs = moe_work.pairs_per_token(window)
        flops_per_token = work.model_flops_per_token(
            model, traffic["seq_len"], *(() if pairs is None else (pairs,)))
        say(kind="model_flops", per_token=flops_per_token,
            pairs_per_token=pairs)
        run_facts = {
            "cell": {"name": cell.name, "chips": cell.chips,
                     "config": config, "traffic": traffic},
            "peaks": peak, "device": device,
            "flops_per_token": flops_per_token,
            "worker": worker, "window": window, "spans": window["spans"],
            "saves": timed, "setup_s": setup_s, "end_to_end": end_to_end,
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
            "attempted": window["steps"] + len(timed),
            "failed": bad_losses + failed_saves,
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
        say(kind="verdict", problems=problems, compared=compared)
        # each number compared beside its limit, last in the line
        line["compared"] = {name: {"value": value, "limit": limit}
                            for name, _, value, limit in compared}
        return line
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(os.path.join(cell.root, cells.RUNS_DIR, cell.name),
                      ignore_errors=True)
        # each number compared beside its limit: the run's last lines on
        # standard error
        for name, what, value, limit in compared:
            print(f"compared: {name} = {value!r} (limit {limit!r}): {what}",
                  file=sys.stderr)
        for problem in problems:
            print(f"not correct: {problem}", file=sys.stderr)


def _check_last_checkpoint(reports, watcher, say):
    """The last durable checkpoint, read back on the host: it must carry the
    step number and the per-leaf checksums taken on the device at the save.
    Returns (problems, the worst leaf's relative error)."""
    import ray_tpu

    if not watcher.durable:
        return ["no durable checkpoint to read back"], None
    index = max(watcher.durable)
    report = next(r for r in reports
                  if r.get("kind") == "save" and r["save"] == index)
    t0 = time.perf_counter()
    back = ray_tpu.get(ray_tpu.remote(_read_back).remote(
        watcher.durable[index]["path"]))
    problems = []
    if back["platform"] != "cpu":
        problems.append(f"the read-back ran on {back['platform']!r}")
    if back["step"] != report["step"]:
        problems.append(f"checkpoint {index} holds step {back['step']}, "
                        f"saved at step {report['step']}")
    worst = 0.0
    for key, want in report["checksums"].items():
        got = back["checksums"].get(key)
        if got is None:
            problems.append(f"checkpoint {index} lacks leaf {key}")
            continue
        err = abs(got - want) / max(abs(want), 1e-30)
        worst = max(worst, err)
        if err > CHECKSUM_RTOL:
            problems.append(f"leaf {key} of checkpoint {index}: sum of "
                            f"squares {got!r}, saved {want!r}")
    say(kind="read_back", save=index, step=back["step"],
        leaves=len(report["checksums"]), worst_relative_error=worst,
        seconds=time.perf_counter() - t0)
    return problems, worst
