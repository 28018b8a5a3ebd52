"""Headline benchmark: GPT pretraining throughput on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: training tokens/sec/chip on a ~350M-param GPT (gpt2-medium shape,
bf16 activations, remat, fused single-program train step). The
reference's north-star target (BASELINE.json) is >=35% MFU for GPT
pretraining on TPU; `vs_baseline` is achieved-MFU / 0.35, so 1.0 means
the north-star bar, higher is better.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

# bf16 peak FLOP/s of one chip, keyed by `jax.devices()[0].device_kind`
# (Google Cloud TPU documentation, per-generation system architecture
# pages). A device that is not in the table is an error, not a default.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,      # v6e
}

MFU_TARGET = 0.35  # BASELINE.json north star: >=35% MFU


def main():
    from ray_tpu._private.accelerators import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a chip; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    if dev.device_kind not in PEAK_FLOPS:
        raise SystemExit(f"no peak FLOP/s on record for device kind "
                         f"{dev.device_kind!r}; add it to PEAK_FLOPS "
                         f"with its source")
    from ray_tpu.models import (GPT, gpt2_medium, init_train_state,
                                make_optimizer, make_train_step)
    from ray_tpu.models.training import batch_shardings, flops_per_token
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    n_dev = len(jax.devices())
    # "dots" remat saves q, k, v, the MLP's up projection and the flash
    # kernel's output (recompute the out-projection and the elementwise);
    # b12/chip is the largest batch that fits HBM with the saved
    # activations (b16: "Used 17.42G of 15.75G hbm"). Batch scales with
    # device count so act_batch stays shardable over dp.
    cfg = gpt2_medium(max_seq_len=1024, remat_policy="dots")
    batch, seq, steps, warmup = 12 * n_dev, 1024, 20, 3

    mesh = None
    model_kwargs = {}
    if n_dev > 1:
        mesh = build_mesh(MeshSpec(dp=-1).resolve(n_dev))
        model_kwargs["mesh"] = mesh
    model = GPT(cfg, **model_kwargs)
    opt = make_optimizer(total_steps=steps + warmup)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    step = make_train_step(model, opt, mesh=mesh)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    batch_dict = {"tokens": tokens}
    if mesh is not None:
        batch_dict = {"tokens": jax.device_put(tokens,
                                               batch_shardings(mesh))}

    for _ in range(warmup):
        state, metrics = step(state, batch_dict)
    jax.block_until_ready((state, metrics))

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict)
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tokens_per_sec = steps * tokens_per_step / dt
    tokens_per_sec_chip = tokens_per_sec / n_dev
    flops_tok = flops_per_token(cfg)
    mfu = tokens_per_sec_chip * flops_tok / PEAK_FLOPS[dev.device_kind]

    print(json.dumps({
        "metric": "gpt_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / MFU_TARGET, 4),
        "detail": {
            "model": "gpt2_medium",
            "n_params": cfg.n_params,
            "batch": batch, "seq": seq, "steps": steps,
            "n_devices": n_dev,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "mfu": round(mfu, 4),
            "step_time_s": round(dt / steps, 4),
        },
    }))


if __name__ == "__main__":
    main()
