"""MoE (expert parallel) and pipeline parallel model tests."""

import jax
import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.models import (GPT, GPTConfig, init_train_state, llama_tiny,
                            make_optimizer, make_train_step)
from ray_tpu.models.training import batch_shardings
from ray_tpu.parallel.mesh import MeshSpec, build_mesh


def _tokens(cfg, b=4, s=64):
    return jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)



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


def test_moe_forward_and_training():
    cfg = llama_tiny(n_experts=4, moe_top_k=2)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert params["blocks"]["w_up"].shape[1] == 4      # expert dim
    toks = _tokens(cfg, b=2)
    logits, aux = model.forward_with_aux(params, toks)
    assert logits.shape == (2, 64, cfg.vocab_size)
    # balanced-ish routing at init: the balance loss counts all k choices,
    # so it is near k = 2; dropless: every layer routes every (token, choice)
    assert 1.5 < float(aux["moe_aux_loss"]) < 3.0
    assert (np.asarray(aux["moe_expert_tokens"]).sum(-1) == 2 * 64 * 2).all()

    opt = make_optimizer(learning_rate=1e-3, total_steps=20)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt)
    losses = []
    for _ in range(6):
        state, m = step(state, {"tokens": toks})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@_needs_shard_map
def test_moe_ep_sharded():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = llama_tiny(n_experts=4)
    mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2).resolve(8))
    model = GPT(cfg, mesh=mesh)
    opt = make_optimizer(total_steps=10)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    assert "ep" in str(state.params["blocks"]["w_up"].sharding.spec)
    step = make_train_step(model, opt, mesh=mesh)
    toks = jax.device_put(_tokens(cfg, b=8), batch_shardings(mesh))
    state, m = step(state, {"tokens": toks})
    assert 0 < float(m["loss"]) < 20
    # still partitioned by `expert` after a step, and nothing dropped
    assert "ep" in str(state.params["blocks"]["w_up"].sharding.spec)
    assert int(m["moe_expert_tokens"].sum()) == (
        8 * 64 * cfg.moe_top_k * cfg.n_layers)


def _hybrid_tiny(**kw):
    """Test-scale Qwen3-Next shape: three Gated DeltaNet layers to one gated
    full-attention layer, a shared expert, half of the experts held."""
    base = dict(
        vocab_size=512, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        d_head=32, d_ff=32, max_seq_len=128,
        layer_pattern=("linear", "linear", "linear", "full"),
        activation="swiglu", norm="rmsnorm_1p", positions="rope",
        rope_fraction=0.25, tie_embeddings=False, qk_norm="head",
        attn_gate=True, linear_key_heads=2, linear_value_heads=4,
        linear_key_dim=16, linear_value_dim=16, n_experts=8, moe_top_k=2,
        moe_shared_ff=32, moe_first_expert=4, moe_experts_held=4,
        dtype=jnp.float32)
    base.update(kw)
    return GPTConfig(**base)


@_needs_shard_map
@pytest.mark.parametrize("mesh_axes,kw", [
    (dict(fsdp=4), {}),
    # the delta rule's kernels (under the interpreter) inside the mixer's
    # shard_map, a key head with its two value heads on each side of tp
    (dict(fsdp=2, tp=2), dict(attention_impl="pallas_interpret")),
    # one key head does not split over tp: the rule runs whole on both
    (dict(fsdp=2, tp=2), dict(linear_key_heads=1, linear_value_heads=2)),
], ids=["fsdp4", "fsdp2_tp2_kernels", "fsdp2_tp2_one_key_head"])
def test_hybrid_pattern_fsdp_sharded(mesh_axes, kw):
    """`param_logical_axes` covers every weight of both kinds of layer, and
    a step on a mesh gives the loss the unsharded model gives."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg = _hybrid_tiny(**kw)
    toks = _tokens(cfg, b=4, s=80)
    opt = make_optimizer(learning_rate=1e-3, total_steps=20)
    plain = GPT(cfg)
    params = plain.init(jax.random.PRNGKey(0))
    axes = plain.param_logical_axes()
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple)))
    for leaf, logical in zip(
            jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(
                axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert leaf.ndim == len(logical), (leaf.shape, logical)
    want, _ = plain.loss(params, {"tokens": toks})

    mesh = build_mesh(MeshSpec(**mesh_axes).resolve(4), devices=jax.devices()[:4])
    model = GPT(cfg, mesh=mesh)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    assert "fsdp" in str(
        state.params["blocks"]["linear"]["w_qkvz"].sharding.spec)
    step = make_train_step(model, opt, mesh=mesh)
    batch = {"tokens": jax.device_put(toks, batch_shardings(mesh))}
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[0] == pytest.approx(float(want), rel=1e-4)
    assert losses[-1] < losses[0]
    assert np.array_equal(np.asarray(m["moe_expert_tokens"]).sum(-1),
                          np.asarray(m["moe_routed_here"]))


def test_pipeline_refuses_a_layer_pattern():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = build_mesh(MeshSpec(pp=2, dp=-1).resolve(len(jax.devices())))
    with pytest.raises(NotImplementedError, match="layer pattern"):
        GPT(_hybrid_tiny(n_layers=8, n_experts=0), mesh=mesh)


def test_pipeline_matches_reference():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = llama_tiny()
    toks = _tokens(cfg, b=4)

    ref = GPT(cfg)
    ref_logits = ref.apply(ref.init(jax.random.PRNGKey(0)), toks)

    mesh = build_mesh(MeshSpec(dp=2, pp=2, tp=2).resolve(8))
    pp = GPT(cfg, mesh=mesh)
    pp_logits = pp.apply(pp.init(jax.random.PRNGKey(0)), toks)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), atol=2e-2)


def test_pipeline_train_step():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = llama_tiny(pp_microbatches=4)
    mesh = build_mesh(MeshSpec(dp=1, fsdp=2, pp=2, tp=2).resolve(8))
    model = GPT(cfg, mesh=mesh)
    opt = make_optimizer(learning_rate=1e-3, total_steps=20)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    step = make_train_step(model, opt, mesh=mesh)
    toks = jax.device_put(_tokens(cfg, b=8), batch_shardings(mesh))
    losses = []
    for _ in range(4):
        state, m = step(state, {"tokens": toks})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_pipeline_rejects_bad_config():
    mesh_like = build_mesh(MeshSpec(pp=2, dp=-1).resolve(
        len(jax.devices()))) if len(jax.devices()) >= 2 else None
    if mesh_like is None:
        pytest.skip("needs 2 devices")
    import dataclasses
    cfg3 = dataclasses.replace(llama_tiny(), n_layers=3)
    with pytest.raises(ValueError):
        GPT(cfg3, mesh=mesh_like)                     # 3 % 2 != 0
    with pytest.raises(NotImplementedError):
        GPT(llama_tiny(n_experts=2), mesh=mesh_like)  # EP+PP


@_needs_shard_map
def test_sharded_compile_no_involuntary_remat(capfd):
    """Regression pin for the r03/r04 remat fix (gpt.py embedding gather):
    compiling the sp/tp/fsdp train step must emit zero spmd_partitioner
    "involuntary full rematerialization" warnings. A sharding-rule
    regression would otherwise land silently."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = llama_tiny()
    mesh = build_mesh(MeshSpec(tp=2, sp=2, fsdp=2).resolve(8))
    model = GPT(cfg, mesh=mesh)
    opt = make_optimizer(total_steps=10)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh=mesh)
    step = make_train_step(model, opt, mesh=mesh)
    toks = jax.device_put(_tokens(cfg, b=8), batch_shardings(mesh))
    capfd.readouterr()  # drain anything emitted during init
    step.lower(state, {"tokens": toks}).compile()
    err = capfd.readouterr().err
    assert "rematerialization" not in err, err
    assert "spmd_partitioner" not in err, err
