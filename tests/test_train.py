"""JaxTrainer tests (reference model: ``python/ray/train/tests/`` —
trainer fit, session report, checkpointing, failure restart)."""

import os

import pytest

import ray_tpu
from ray_tpu import train as rt_train
from ray_tpu.train import (Checkpoint, CheckpointConfig, FailureConfig,
                           JaxTrainer, RunConfig, ScalingConfig)


def test_fit_reports_metrics(rtpu_init, tmp_path):
    def loop(config):
        from ray_tpu import train
        ctx = train.get_context()
        for i in range(3):
            train.report({"loss": 1.0 / (i + 1),
                          "rank": ctx.get_world_rank()})

    trainer = JaxTrainer(
        loop,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2,
                                     placement_strategy="PACK"),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] == pytest.approx(1.0 / 3)
    assert len(result.metrics_history) == 3
    assert result.metrics["rank"] == 0


def test_fit_persists_checkpoints(rtpu_init, tmp_path):
    def loop(config):
        from ray_tpu import train
        ctx = train.get_context()
        for i in range(2):
            ckpt = None
            if ctx.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"step": i})
            train.report({"step": i}, checkpoint=ckpt)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t2", storage_path=str(tmp_path),
                             checkpoint_config=CheckpointConfig(
                                 num_to_keep=1)))
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict() == {"step": 1}
    # num_to_keep=1: only one checkpoint dir remains
    dirs = [d for d in os.listdir(result.path)
            if d.startswith("checkpoint_")]
    assert len(dirs) == 1


def test_failure_restart_resumes_from_checkpoint(rtpu_init, tmp_path):
    def loop(config):
        from ray_tpu import train
        ctx = train.get_context()
        start = 0
        resume = train.get_checkpoint()
        if resume is not None:
            start = resume.to_dict()["step"] + 1
        for i in range(start, 4):
            ckpt = (Checkpoint.from_dict({"step": i})
                    if ctx.get_world_rank() == 0 else None)
            train.report({"step": i, "resumed": start > 0},
                         checkpoint=ckpt)
            if i == 1 and start == 0:
                raise RuntimeError("injected failure at step 1")

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t3", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    assert result.metrics["resumed"] is True


def test_failure_budget_exhausted(rtpu_init, tmp_path):
    def loop():
        raise ValueError("always fails")

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t4", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is not None


def test_jax_training_with_pytree_checkpoint(rtpu_init, tmp_path):
    def loop(config):
        import jax
        import numpy as np
        from ray_tpu import train
        from ray_tpu.models import (GPT, llama_tiny, init_train_state,
                                    make_optimizer, make_train_step)

        cfg = llama_tiny()
        model = GPT(cfg)
        opt = make_optimizer(total_steps=4)
        state = init_train_state(model, opt, jax.random.PRNGKey(0))
        step = make_train_step(model, opt)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    cfg.vocab_size)
        for i in range(2):
            state, metrics = step(state, {"tokens": tokens})
            ckpt = train.Checkpoint.from_pytree(
                {"params": state.params, "step": np.asarray(state.step)})
            train.report({"loss": float(metrics["loss"])}, checkpoint=ckpt)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t5", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] > 0
    restored = result.checkpoint.to_pytree()
    assert int(restored["step"]) == 2
    assert "tok_embed" in restored["params"]


def _granted_loop(config):
    """`steps` train steps at `llama_tiny` size in the worker that was granted
    the chips, from a fresh or a restored TrainState; with `save`, ends on
    the loss the next step is to report and a checkpoint of the state."""
    import jax
    import numpy as np
    from ray_tpu import train
    from ray_tpu.models import (GPT, llama_tiny, init_train_state,
                                make_optimizer, make_train_step)
    from ray_tpu.models.training import batch_shardings, eval_step_fn
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    chips = config["chips"]
    devices = jax.devices()[:chips]
    mesh = (build_mesh(MeshSpec(fsdp=chips), devices) if chips > 1
            else None)
    cfg = llama_tiny()
    model = GPT(cfg, mesh=mesh)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, total_steps=64)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    if mesh is None:
        state = jax.device_put(state, devices[0])   # placed, as a restored one
    if config.get("resume"):
        state = train.Checkpoint(config["resume"]).to_pytree(template=state)
    spans = sorted({len(leaf.sharding.device_set)
                    for leaf in jax.tree_util.tree_leaves(state.params)})
    train.report({
        "kind": "worker", "pid": os.getpid(), "params_span": spans,
        "slots": ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"]})

    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2 * chips, 64), dtype=np.int32)
    batch = {"tokens": jax.device_put(
        tokens, batch_shardings(mesh) if mesh else devices[0])}
    step = make_train_step(model, opt, mesh=mesh)
    for _ in range(config["steps"]):
        at = int(state.step)
        state, metrics = step(state, batch)
        train.report({"kind": "step", "step": at,
                      "loss": float(metrics["loss"])})
    if config.get("save"):
        # a step's loss is taken before its update: the resumed run's first
        # step is held to this forward pass
        next_loss = float(eval_step_fn(model, mesh=mesh)(
            state.params, batch)["loss"])
        train.report({"kind": "saved", "next_loss": next_loss},
                     checkpoint=train.Checkpoint.from_pytree(state))


@pytest.mark.parametrize("chips", [1, 4])
def test_granted_fit_resumes_from_a_pytree_checkpoint_in_a_fresh_worker(
        chips, tmp_path, monkeypatch):
    """Save, let the gang be killed, resume: the second `fit()` needs the
    first worker to have given its chips back, a new worker to be granted
    them, and the TrainState to round-trip through `Checkpoint.from_pytree`
    — sharded `fsdp=4` over four virtual devices when the grant is four."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    ray_tpu.init(num_cpus=4, num_tpus=chips)
    try:
        def fit(name, **config):
            result = JaxTrainer(
                _granted_loop,
                train_loop_config={"chips": chips, **config},
                scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
                run_config=RunConfig(name=name,
                                     storage_path=str(tmp_path))).fit()
            assert result.error is None
            by_kind = {}
            for r in result.metrics_history:
                by_kind.setdefault(r["kind"], []).append(r)
            (worker,) = by_kind["worker"]
            assert worker["slots"] == list(range(chips))
            assert worker["params_span"] == [chips]
            assert worker["pid"] != os.getpid()
            return result, worker, by_kind["step"], by_kind.get("saved")

        first, worker1, steps1, saved = fit("train", steps=3, save=True)
        assert [s["step"] for s in steps1] == [0, 1, 2]
        assert steps1[-1]["loss"] < steps1[0]["loss"]
        assert first.checkpoint is not None

        _, worker2, steps2, _ = fit("resume", steps=2,
                                    resume=first.checkpoint.path)
        assert worker2["pid"] != worker1["pid"]
        assert [s["step"] for s in steps2] == [3, 4]
        # two computations of one loss from the same parameters and tokens:
        # bf16 activations, another fusion and reduction order
        assert steps2[0]["loss"] == pytest.approx(saved[0]["next_loss"],
                                                  abs=2e-2)
    finally:
        ray_tpu.shutdown()
