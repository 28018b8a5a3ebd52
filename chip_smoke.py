#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host: the sharded phase only

One chip: the driver starts a local cluster without opening a JAX backend;
`JaxTrainer` with `ScalingConfig(use_tpu=True)` runs the repo's flagship
(`gpt2_medium`, 24 layers, seq 1024, batch 12, bf16, "dots" remat) inside
the worker process that was granted the chip: 2 warm-up + 5 timed steps fed
from a `ray_tpu.data` Dataset, `train.report` each step, one orbax
checkpoint. A second `fit()` resumes from that checkpoint in a fresh worker
and takes 2 more steps — which needs the first gang to have released the
chip, the checkpoint round trip to work from device arrays, and the compile
cache to be where both processes look.

Four chips (`--chips 4`): one worker granted the whole host runs the same
model on an `fsdp=4` mesh at global batch 48, and its first loss is held
against four unsharded forward passes on device 0.

This is a smoke, not a benchmark: it prints no rate and claims nothing.
Every line on stdout is one JSON object; the LAST line is exactly
`{"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as the worker's JAX reported it, and the exit code is 0
only when every phase passed. There is no size or platform option: tests
reach the tiny CPU size by calling `run_phases` / `finish` directly.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import itertools
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
# Two computations of the same loss from the same parameters and tokens
# (sharded step vs unsharded forward passes; resumed step vs a forward pass
# before the save): bf16 activations, f32 loss, different fusion and
# reduction order only.
SAME_LOSS_ATOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Size:
    """What one chip runs. FULL is the width bench.py measures."""
    model: str
    batch: int          # sequences per chip per step
    seq: int


FULL = Size("gpt2_medium", batch=12, seq=1024)
TINY = Size("llama_tiny", batch=2, seq=64)      # CPU tests only


class SmokeFailure(Exception):
    pass


def _storage() -> str:
    """This driver's run directory under the checkout (git-ignored; removed
    when the phases end). Per driver, so that two runs do not collide."""
    return os.path.join(REPO, ".smoke_runs", f"driver-{os.getpid()}")


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def _model_config(name: str, seq: int):
    from ray_tpu import models
    if name == "gpt2_medium":
        return models.gpt2_medium(max_seq_len=seq, remat_policy="dots")
    if name == "llama_tiny":
        return models.llama_tiny()      # rope: any seq <= 256
    raise ValueError(f"unknown model {name!r}")


def _cache_entries() -> int:
    from ray_tpu._private.accelerators import compile_cache_dir
    return len(glob.glob(os.path.join(compile_cache_dir(), "*")))


# --------------------------------------------------------------- worker side
# The loops below run inside the _TrainWorker actor's process — the one
# process that may open the TPU backend. They report through train.report;
# the driver reads result.metrics_history.

def _report_worker(config) -> list:
    """First report of every loop: what this process's JAX found. Sent
    before anything is asserted, so a failure still says what was found."""
    import jax
    import ray_tpu
    from ray_tpu import train
    from ray_tpu._private.accelerators import compile_cache_dir

    devices = jax.devices()
    dev = devices[0]
    slots = ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"]
    train.report({
        "kind": "worker", "pid": os.getpid(), "platform": dev.platform,
        "device_kind": dev.device_kind, "count": len(devices),
        "accelerator_ids": slots,
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        "compile_cache_dir": compile_cache_dir(),
        "cache_entries_at_start": _cache_entries()})
    if len(slots) != config["chips"]:
        raise SmokeFailure(f"the worker holds TPU slots {slots}, not "
                           f"{config['chips']}: it was not granted the chips")
    if dev.platform != config["platform"]:
        raise SmokeFailure(f"the granted worker's JAX is on "
                           f"{dev.platform!r}, not {config['platform']!r}")
    if len(devices) != config["chips"]:
        raise SmokeFailure(f"the granted worker sees {len(devices)} "
                           f"device(s), not {config['chips']}")
    return devices


def _compile(step, state, batch, platform: str):
    """AOT-compile the train step; on the TPU the lowered program must
    hold the Pallas kernel, not the einsum reference or interpret mode."""
    import jax
    from ray_tpu import train

    cache_events = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.append(event.rsplit("/", 1)[-1])
        if "compilation_cache" in event else None)
    t0 = time.perf_counter()
    lowered = step.lower(state, batch)
    pallas = "tpu_custom_call" in lowered.as_text()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    train.report({"kind": "compile", "lower_s": t1 - t0,
                  "compile_s": time.perf_counter() - t1,
                  "pallas_custom_call": pallas,
                  "cache_events": sorted(set(cache_events)),
                  "cache_entries": _cache_entries()})
    if platform == "tpu" and not pallas:
        raise SmokeFailure("no tpu_custom_call in the lowered train step: "
                           "attention did not lower to the Pallas kernel")
    return compiled


def _run_steps(compiled, state, batches, first_step: int):
    import jax
    from ray_tpu import train

    for i, batch in enumerate(batches, start=first_step):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        train.report({"kind": "step", "step": i,
                      "loss": float(metrics["loss"]),
                      "step_s": time.perf_counter() - t0})
    return state


def _memory(devices) -> list:
    return [(d.memory_stats() or {}) for d in devices]


def _optimizer():
    """The repo's AdamW, with a warm-up of two steps instead of the default
    hundred and a rate at which gpt2_medium's loss falls steadily from the
    first update on the same sequences (at the default 3e-4 without a real
    warm-up it oscillates) — the smoke checks that it falls."""
    from ray_tpu.models import make_optimizer
    return make_optimizer(learning_rate=5e-5, warmup_steps=2, total_steps=64)


def train_loop(config):
    """One chip: init or resume, compile, step, checkpoint."""
    import jax
    import jax.numpy as jnp
    from ray_tpu import train
    from ray_tpu.models import GPT, init_train_state, make_train_step
    from ray_tpu.models.training import eval_step_fn

    devices = _report_worker(config)
    model = GPT(_model_config(config["model"], config["seq"]))
    opt = _optimizer()
    # placed explicitly, as a restored state is: a committed and an
    # uncommitted argument lower to different programs (sharding
    # annotations), and the resumed run is to find this run's in the cache
    state = jax.device_put(
        init_train_state(model, opt, jax.random.PRNGKey(SEED)), devices[0])
    if config.get("resume"):
        state = train.Checkpoint(config["resume"]).to_pytree(template=state)
    first_step = int(state.step)

    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=config["batch"], dtype=jnp.int32)
    first = next(batches)
    compiled = _compile(make_train_step(model, opt), state, first,
                        config["platform"])
    state = _run_steps(compiled, state, itertools.chain([first], batches),
                       first_step)

    ckpt = next_loss = save_s = None
    if config.get("save"):
        # what the next step will report (its loss is computed before its
        # update): the resumed run is held to this
        next_loss = float(eval_step_fn(model)(state.params, first)["loss"])
        t0 = time.perf_counter()
        ckpt = train.Checkpoint.from_pytree(
            state, dir=os.path.join(config["save"], "worker_checkpoint"))
        save_s = time.perf_counter() - t0
    mem = _memory(devices)[0]
    train.report({"kind": "done", "final_step": int(state.step),
                  "next_loss": next_loss,
                  "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
                  "bytes_limit": mem.get("bytes_limit"),
                  "checkpoint_save_s": save_s}, checkpoint=ckpt)


def sharded_loop(config):
    """Four chips, one process: fsdp=4 against unsharded forward passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import train
    from ray_tpu.models import GPT, init_train_state, make_train_step
    from ray_tpu.models.training import batch_shardings, eval_step_fn
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = _report_worker(config)
    n = len(devices)
    cfg = _model_config(config["model"], config["seq"])
    mesh = build_mesh(MeshSpec(fsdp=n))
    model = GPT(cfg, mesh=mesh)
    opt = _optimizer()
    state = init_train_state(model, opt, jax.random.PRNGKey(SEED), mesh=mesh)
    jax.block_until_ready(state)

    # where the state landed: code that has never seen two chips may put
    # everything on the first
    leaves = jax.tree_util.tree_leaves(state)
    total = sum(leaf.nbytes for leaf in leaves)
    held = {d.id: 0 for d in devices}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    spans = sorted({len({s.device.id for s in leaf.addressable_shards})
                    for leaf in jax.tree_util.tree_leaves(state.params)})
    in_use = [m.get("bytes_in_use") for m in _memory(devices)]
    train.report({"kind": "placement", "state_bytes": total,
                  "shard_bytes_per_device": list(held.values()),
                  "bytes_in_use_per_device": in_use,
                  "devices_per_param": spans,
                  "mesh": dict(mesh.shape)})
    if spans != [n]:
        raise SmokeFailure(f"a parameter's shards span {spans} devices, "
                           f"not {n}")
    per_device = list(held.values()) + [b for b in in_use if b is not None]
    if not all(0.2 * total <= b <= 0.4 * total for b in per_device):
        raise SmokeFailure(f"per-device bytes {per_device} are not about a "
                           f"quarter of the state's {total}")

    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=config["batch"], dtype=jnp.int32,
        sharding=batch_shardings(mesh))
    first = next(batches)

    # the reference: the same initial parameters gathered on device 0
    # (before the first step donates them), no mesh, one forward pass per
    # chip-sized slice of the same global batch
    params0 = jax.device_put(state.params, devices[0])
    eval_step = eval_step_fn(GPT(cfg))
    tokens = np.asarray(first["tokens"])
    per_chip = config["batch"] // n
    ref_losses = [
        float(eval_step(params0, {"tokens": jax.device_put(
            tokens[i * per_chip:(i + 1) * per_chip], devices[0])})["loss"])
        for i in range(n)]
    del params0
    train.report({"kind": "reference", "losses": ref_losses,
                  "mean": sum(ref_losses) / n})

    compiled = _compile(make_train_step(model, opt, mesh=mesh), state,
                        first, config["platform"])
    state = _run_steps(compiled, state, itertools.chain([first], batches), 0)
    train.report({"kind": "done", "final_step": int(state.step),
                  "peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                        for m in _memory(devices)]})


# --------------------------------------------------------------- driver side

def _fit(loop, config, name: str, n_steps: int, device: dict):
    """One JaxTrainer.fit() over a Dataset of n_steps blocks that all hold
    the same seeded sequences (so the loss has to fall). Notes in `device`
    what the worker's JAX found, then holds the run to what every fit must
    show. Returns (result, the worker's reports, the losses)."""
    import numpy as np
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    vocab = _model_config(config["model"], config["seq"]).vocab_size
    tokens = np.random.default_rng(SEED).integers(
        0, vocab, (config["batch"], config["seq"]), dtype=np.int32)
    dataset = rd.from_numpy({"tokens": np.tile(tokens, (n_steps, 1))},
                            num_blocks=n_steps)
    t0 = time.perf_counter()
    result = JaxTrainer(
        loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        datasets={"train": dataset},
        run_config=RunConfig(name=name, storage_path=_storage())).fit()
    reports = result.metrics_history
    for r in reports:
        say(fit=name, **r)
    say(fit=name, kind="fit", fit_s=time.perf_counter() - t0,
        error=repr(result.error) if result.error else None,
        checkpoint=result.checkpoint.path if result.checkpoint else None)

    for r in reports:       # first, so that a failed fit still says it
        if r.get("kind") == "worker":
            device.update(platform=r["platform"], kind=r["device_kind"],
                          count=r["count"])
            if r["pid"] == os.getpid():
                raise SmokeFailure("the loop ran in the driver's process")
    if result.error is not None:
        raise SmokeFailure(f"fit() failed: {result.error!r}")
    losses = [r["loss"] for r in reports if r.get("kind") == "step"]
    if len(losses) != n_steps:
        raise SmokeFailure(f"{len(losses)} steps reported, not {n_steps}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"loss is not finite: {losses}")
    if _one(reports, "compile")["cache_entries"] < 1:
        raise SmokeFailure("the compile cache directory holds no entry "
                           "after the train step was compiled")
    return result, reports, losses


def _one(reports, kind: str) -> dict:
    found = [r for r in reports if r.get("kind") == kind]
    if not found:
        raise SmokeFailure(f"the worker sent no {kind!r} report")
    return found[0]


def _check_fresh(losses, vocab: int) -> None:
    if abs(losses[0] - math.log(vocab)) > 0.5:
        raise SmokeFailure(f"first loss {losses[0]} is not near "
                           f"ln(vocab) = {math.log(vocab):.3f}")
    if losses[-1] >= losses[0]:
        raise SmokeFailure(f"loss did not fall: {losses}")


def phase_one_chip(size: Size, platform: str, device: dict) -> None:
    """Phases 2 and 3: train + checkpoint, then resume in a new worker."""
    vocab = _model_config(size.model, size.seq).vocab_size
    base = {"model": size.model, "batch": size.batch, "seq": size.seq,
            "platform": platform, "chips": 1}
    warmup, timed, more = 2, 5, 2

    result, first_reports, first_losses = _fit(
        train_loop, {**base, "save": _storage()}, "train", warmup + timed,
        device)
    _check_fresh(first_losses, vocab)
    if result.checkpoint is None:
        raise SmokeFailure("fit() returned no checkpoint")

    _, reports, losses = _fit(
        train_loop, {**base, "resume": result.checkpoint.path}, "resume",
        more, device)
    steps = [r["step"] for r in reports if r.get("kind") == "step"]
    if steps != list(range(warmup + timed, warmup + timed + more)):
        raise SmokeFailure(f"resume did not continue at step "
                           f"{warmup + timed}: {steps}")
    expected = _one(first_reports, "done")["next_loss"]
    if abs(losses[0] - expected) > SAME_LOSS_ATOL:
        raise SmokeFailure(f"the resumed step's loss {losses[0]} is not the "
                           f"{expected} the saved state gives: the "
                           f"checkpoint did not round-trip")
    if _one(reports, "worker")["pid"] == _one(first_reports, "worker")["pid"]:
        raise SmokeFailure("the second fit() reused the first worker")
    say(kind="summary", phase="one_chip", losses=first_losses + losses,
        resumed_loss_expected=expected,
        timed_step_s=[r["step_s"] for r in first_reports
                      if r.get("kind") == "step"][warmup:],
        compile_s_first=_one(first_reports, "compile")["compile_s"],
        compile_s_second=_one(reports, "compile")["compile_s"],
        peak_bytes_in_use=_one(first_reports, "done")["peak_bytes_in_use"],
        claim=None)


def phase_four_chips(size: Size, platform: str, chips: int,
                     device: dict) -> None:
    """Phase F: one worker, the whole host, fsdp over its chips."""
    warmup, timed = 2, 3
    config = {"model": size.model, "batch": size.batch * chips,
              "seq": size.seq, "platform": platform, "chips": chips}
    _, reports, losses = _fit(sharded_loop, config, "sharded",
                              warmup + timed, device)
    _check_fresh(losses, _model_config(size.model, size.seq).vocab_size)
    ref = _one(reports, "reference")["mean"]
    if abs(losses[0] - ref) > SAME_LOSS_ATOL:
        raise SmokeFailure(f"first sharded loss {losses[0]} differs from "
                           f"the unsharded mean {ref} by more than "
                           f"{SAME_LOSS_ATOL}")
    say(kind="summary", phase="four_chips", sharded_loss=losses[0],
        unsharded_mean=ref, atol=SAME_LOSS_ATOL, claim=None)


def run_phases(chips: int, platform: str, size: Size) -> dict:
    """Run the phases for `chips` on an initialised cluster. Never raises:
    returns the last line's object, `ok` false on any failure."""
    import ray_tpu
    from ray_tpu import state

    device = {"platform": None, "kind": None, "count": 0}
    shutil.rmtree(_storage(), ignore_errors=True)
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        say(kind="cluster", tpu=advertised, driver_pid=os.getpid())
        if advertised != chips:
            # checked here so that a machine without the chips fails in
            # seconds, not after the trainer's placement timeouts
            raise SmokeFailure(f"the cluster advertises {advertised} TPU, "
                               f"this run needs {chips}")
        if chips == 1:
            phase_one_chip(size, platform, device)
        else:
            phase_four_chips(size, platform, chips, device)
        say(kind="telemetry", hbm_gauges=[
            {"device": r["tags"].get("device"), "value": r.get("value")}
            for r in state.list_metrics(
                {"name": "rtpu_device_hbm_bytes_in_use"})])
        ok = True
    except Exception as e:   # noqa: BLE001 — every failure ends in ok=false
        traceback.print_exc()
        say(kind="failed", error=f"{type(e).__name__}: {e}")
        ok = False
    finally:
        shutil.rmtree(_storage(), ignore_errors=True)
    return {"ok": ok, "device": device}


def finish(summary: dict) -> int:
    """Stop the runtime, then print the last line. shutdown() drains the
    worker-log forwarder and stops it before it returns, so nothing can
    follow the summary onto stdout."""
    import ray_tpu
    from ray_tpu._private.accelerators import jax_backend_initialized

    if jax_backend_initialized():
        say(kind="failed", error="the driver process opened a JAX backend")
        summary["ok"] = False
    ray_tpu.shutdown()
    sys.stderr.flush()
    print(json.dumps(summary), flush=True)
    return 0 if summary["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    args = ap.parse_args()

    sys.path.insert(0, REPO)      # this checkout's package, nothing else
    import ray_tpu
    from ray_tpu._private import native

    so_at_start = os.path.exists(native._LIB_PATH)
    dev = sorted(p for p in glob.glob("/dev/*") + glob.glob("/dev/vfio/*")
                 if any(s in p for s in ("accel", "vfio", "tpu")))
    say(kind="host", dev=dev, detect_tpus=ray_tpu._detect_tpus(),
        cpus=os.cpu_count(),
        disk_free_gb=round(shutil.disk_usage(REPO).free / 1e9, 1),
        env={k: v for k, v in os.environ.items()
             if k.startswith(("JAX_", "TPU_", "XLA_", "LIBTPU"))})
    ray_tpu.init()                # no num_tpus: the chips are detected
    say(kind="native_arena", so_present_before_init=so_at_start,
        loaded=native.available())
    return finish(run_phases(args.chips, "tpu", FULL))


if __name__ == "__main__":
    sys.exit(main())
