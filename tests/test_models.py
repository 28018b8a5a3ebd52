"""Model zoo tests: forward, training, sharded-equivalence.

Mirrors the reference's Train/RLlib model test style (SURVEY §4) but the
assertion that matters on TPU is *parallelism equivalence*: the same step
on a 1-device and an 8-device mesh (dp/fsdp/tp and sp/ring) must agree.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (GPT, GPTConfig, gpt2_medium, gpt2_small,
                            llama_tiny, init_train_state, make_optimizer,
                            make_train_step)
from ray_tpu.models.training import batch_shardings
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(cfg, b=4, s=64, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                                cfg.vocab_size)
    return {"tokens": tokens}


def test_forward_shapes_llama():
    cfg = llama_tiny()
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    logits = model.apply(params, _batch(cfg)["tokens"])
    assert logits.shape == (4, 64, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_forward_shapes_gpt2_family():
    cfg = GPTConfig(vocab_size=512, n_layers=2, d_model=128, n_heads=4,
                    max_seq_len=128, activation="gelu", norm="layernorm",
                    positions="learned", tie_embeddings=True)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "pos_embed" in params and "lm_head" not in params
    logits = model.apply(params, _batch(cfg, s=32)["tokens"])
    assert logits.shape == (4, 32, cfg.vocab_size)


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = llama_tiny(remat=False)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = _batch(cfg, b=1, s=32)["tokens"]
    logits1 = model.apply(params, toks)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
    logits2 = model.apply(params, toks2)
    np.testing.assert_allclose(logits1[0, :-1], logits2[0, :-1],
                               atol=1e-4, rtol=1e-3)


def test_train_step_reduces_loss():
    cfg = llama_tiny()
    model = GPT(cfg)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         total_steps=50)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt)
    batch = _batch(cfg, b=2, s=64)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 8


# From the commit before layer patterns (PR 31's tree), float32 on the CPU:
# sum |logits|, logits[1, 5, 7], the loss, the gradients' global norm and
# d loss / d wq[1, 3, 2, 1], each for `init(PRNGKey(0))` on
# randint(PRNGKey(1), (2, 48)).
_BEFORE_PATTERNS = {
    "gpt2": (dict(vocab_size=512, n_layers=2, d_model=128, n_heads=4,
                  max_seq_len=128, activation="gelu", norm="layernorm",
                  positions="learned", tie_embeddings=True),
             (8930.0478515625, -0.24748189747333527, 6.3406596183776855,
              3.042456865310669, 3.0980416340753436e-05)),
    "olmoe": (dict(vocab_size=256, n_layers=2, d_model=64, n_heads=4,
                   d_ff=32, max_seq_len=128, activation="swiglu",
                   norm="rmsnorm", positions="rope", tie_embeddings=False,
                   norm_eps=1e-5, qk_norm=True, n_experts=8, moe_top_k=2,
                   moe_norm_topk_prob=False, moe_aux_coeff=0.01,
                   moe_router_z_coeff=0.001, remat=False,
                   attention_impl="reference"),
              (3113.0576171875, 0.016855686902999878, 5.5984954833984375,
               1.429702877998352, -0.0014655494596809149)),
}


@pytest.mark.parametrize("family", list(_BEFORE_PATTERNS))
def test_one_kind_of_layer_is_the_period_full(family):
    """The layer pattern holds what exists: a model of one kind is the
    period ("full",), its weights stacked [L, ...] as before, and its
    logits and gradients are what the model gave before patterns."""
    import optax
    kw, want = _BEFORE_PATTERNS[family]
    cfg = GPTConfig(dtype=jnp.float32, **kw)
    assert cfg.layer_pattern == ("full",)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert params["blocks"]["wq"].shape[0] == cfg.n_layers
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                cfg.vocab_size)
    logits = model.apply(params, tokens)
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, {"tokens": tokens})
    got = (float(jnp.abs(logits).sum()), float(logits[1, 5, 7]),
           float(loss), float(optax.global_norm(grads)),
           float(grads["blocks"]["wq"][1, 3, 2, 1]))
    assert got == pytest.approx(want, rel=1e-5)


def test_layer_pattern_is_whole_periods_of_known_kinds():
    with pytest.raises(ValueError, match="whole periods"):
        GPTConfig(n_layers=6, layer_pattern=("linear", "linear", "linear",
                                             "full"))
    with pytest.raises(ValueError, match="period of"):
        GPTConfig(layer_pattern=("banded",))
    with pytest.raises(ValueError, match="attn_window"):
        GPTConfig(layer_pattern=("window",))
    # a JSON file hands the period over as a list
    assert GPTConfig(n_layers=4, layer_pattern=["linear", "full"]
                     ).layer_pattern == ("linear", "full")


def test_n_params_counts():
    cfg = gpt2_small()
    # GPT-2 small is ~124M params; our count excludes norms/bias.
    assert 1.1e8 < cfg.n_params < 1.4e8


def _json_model(path):
    """The `model` of a benchmark configuration as a `GPTConfig`."""
    with open(os.path.join(_ROOT, "benchmarks", path)) as f:
        kw = json.load(f)["model"]
    kw.update({k: getattr(jnp, kw[k]) for k in ("dtype", "param_dtype")})
    return GPTConfig(**kw)


_DECLARED = {
    "gpt2_small": gpt2_small, "gpt2_medium": gpt2_medium,
    "llama_tiny": llama_tiny,
    **{name: functools.partial(_json_model,
                               f"tests/fixtures/configs/{name}.json")
       for name in ("gpt2_tiny", "gpt2_tiny_fsdp4", "olmoe_tiny",
                    "qwen3_next_tiny")}}


@pytest.mark.parametrize("name,stages", [
    (name, stages) for name in _DECLARED for stages in (1, 2)
    # a pipeline takes neither experts nor a layer pattern
    if stages == 1 or name not in ("olmoe_tiny", "qwen3_next_tiny")])
def test_every_weight_is_declared_with_axes_of_its_rank(name, stages):
    """`init` and `param_logical_axes` walk one declaration: the trees have
    one structure and a weight's axes are as many as its dimensions, the
    stacking axes (layers; stages of layers; periods and layers of a kind)
    among them."""
    mesh = build_mesh(MeshSpec(pp=2), devices=jax.devices()[:2]
                      ) if stages == 2 else None
    model = GPT(_DECLARED[name](), mesh=mesh)
    assert model.pp_stages == stages
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_logical_axes()
    is_axes = lambda x: isinstance(x, tuple)    # noqa: E731
    assert (jax.tree_util.tree_structure(shapes)
            == jax.tree_util.tree_structure(axes, is_leaf=is_axes))
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        names = functools.reduce(lambda tree, key: tree[key.key], path, axes)
        assert len(names) == leaf.ndim, jax.tree_util.keystr(path)


@pytest.mark.parametrize("name,count", [
    ("gpt2_small", 123568128), ("gpt2_medium", 353501184),
    ("llama_tiny", 622592), ("configs/gpt2_xl.json", 1555046400),
    ("configs/olmoe_1b_7b.json", 625606656),
    ("configs/qwen3_next_80b_a3b.json", 625639424)])
def test_n_params_is_what_the_written_out_count_gave(name, count):
    """`n_params` is read off the weights' declaration; before PR 47 it was
    a formula beside it, and these are that formula's numbers."""
    cfg = _DECLARED[name]() if name in _DECLARED else _json_model(name)
    assert cfg.n_params == count


@functools.lru_cache(maxsize=None)
def _remat_grads(remat_policy):
    """(jaxpr text, gradients) of the loss of a two-block model whose
    attention is the flash kernel under the interpreter."""
    remat = dict(remat=False) if remat_policy is None else dict(
        remat=True, remat_policy=remat_policy)
    cfg = GPTConfig(vocab_size=128, n_layers=2, d_model=128, n_heads=2,
                    max_seq_len=128, tie_embeddings=True,
                    attention_impl="pallas_interpret", **remat)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, b=2, s=128)
    grad = jax.grad(lambda p: model.loss(p, batch)[0])
    return str(jax.make_jaxpr(grad)(params)), jax.jit(grad)(params)


@pytest.mark.parametrize("remat_policy,forward_calls",
                         [("dots", 1), ("full", 2), (None, 1)])
def test_remat_policy_decides_how_often_the_flash_forward_runs(
        remat_policy, forward_calls):
    """The blocks are one scan, so its body holds a block's calls: "dots"
    saves the kernel's output and log-sum-exp and runs the forward kernel
    once a block, as no remat does; "full" runs it again in the backward
    pass. What is saved changes no number."""
    text, grads = _remat_grads(remat_policy)
    assert text.count("name=flash_fwd") == forward_calls
    # one backward kernel a block (PR 38): the tiny row fits in VMEM, so
    # the pair `flash_bwd_dq` + `flash_bwd_dkv` is not picked
    assert len(re.findall(r"name=flash_bwd\b", text)) == 1
    assert "name=flash_bwd_d" not in text
    _, want = _remat_grads(None)
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _loss_and_grads(attention_impl, n_heads, mesh_spec):
    """Loss and parameter gradients of a two-block model with `n_heads`
    heads of 64, bf16 activations, through `GPT._attention`'s plain branch
    or, with a mesh, its shard_map branch."""
    cfg = GPTConfig(vocab_size=128, n_layers=2, d_model=64 * n_heads,
                    n_heads=n_heads, max_seq_len=128, remat=False,
                    attention_impl=attention_impl)
    batch = _batch(cfg, b=8, s=128)
    if mesh_spec is None:
        model = GPT(cfg)
    else:
        mesh = build_mesh(mesh_spec.resolve(8))
        model = GPT(cfg, mesh=mesh)
        batch = {"tokens": jax.device_put(batch["tokens"],
                                          batch_shardings(mesh))}
    params = model.init(jax.random.PRNGKey(0))
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch)[0]))(params)


@pytest.mark.parametrize("n_heads,mesh_spec", [
    (2, None), (3, None),                  # one block; a block and a half
    (4, MeshSpec(dp=2, fsdp=2, tp=2)),     # two heads a device
    (3, MeshSpec(dp=2, fsdp=4)),           # every head on every device
], ids=["plain_2", "plain_3", "mesh_tp_4", "mesh_fsdp_3"])
def test_flash_kernels_match_reference_through_the_model(n_heads, mesh_spec):
    """`_attention` hands q, k, v to the flash kernels as the projections
    wrote them, [B, S, H, 64], and transposes nothing: loss and gradients
    agree with the einsum attention to bf16 rounding."""
    if mesh_spec is not None and (not _HAS_SHARD_MAP
                                  or len(jax.devices()) < 8):
        pytest.skip("needs jax.shard_map and 8 virtual devices")
    want_loss, want = _loss_and_grads("reference", n_heads, mesh_spec)
    got_loss, got = _loss_and_grads("pallas_interpret", n_heads, mesh_spec)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-3)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2 * scale, err_msg=jax.tree_util.keystr(path))


# Feature probes for this box's jax (0.4.x): the sharded model paths
# use the jax>=0.5 top-level APIs (jax.shard_map / jax.set_mesh).
# skipif on the PROBE, not a version string, so the gate lifts itself
# the moment the runtime jax grows the API (ISSUE 15: tier-1 reads
# honestly green instead of carrying a known-red set).
_HAS_SHARD_MAP = hasattr(jax, "shard_map")
_needs_shard_map = pytest.mark.skipif(
    not _HAS_SHARD_MAP,
    reason=f"jax {jax.__version__} lacks top-level jax.shard_map "
           "(the sharded attention path requires it)")


@_needs_shard_map
@pytest.mark.parametrize("spec", [
    MeshSpec(dp=2, fsdp=2, tp=2),
    MeshSpec(dp=2, fsdp=1, sp=2, tp=2),
    MeshSpec(dp=1, fsdp=4, tp=2),
])
def test_sharded_training_matches_single_device(spec):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = llama_tiny()
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         total_steps=50)
    batch = _batch(cfg, b=4, s=64)

    # single-device reference
    ref_model = GPT(cfg)
    ref_state = init_train_state(ref_model, opt, jax.random.PRNGKey(0))
    ref_step = make_train_step(ref_model, opt, donate=False)
    ref_losses = []
    for _ in range(3):
        ref_state, m = ref_step(ref_state, batch)
        ref_losses.append(float(m["loss"]))

    mesh = build_mesh(spec.resolve(8))
    model = GPT(cfg, mesh=mesh)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    step = make_train_step(model, opt, mesh=mesh, donate=False)
    sharded = {"tokens": jax.device_put(batch["tokens"],
                                        batch_shardings(mesh))}
    losses = []
    for _ in range(3):
        state, m = step(state, sharded)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-2)
