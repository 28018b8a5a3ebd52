"""The Qwen3-Next-shaped model (`ray_tpu.models.GPT` with the layer pattern
three Gated DeltaNet layers to one gated full-attention layer, a shared
expert, and a held share of the routed experts) against the plain reference
`benchmarks/reference/qwen3_next.py`, in float32 on the CPU, on the same
seeded weights and rows: logits, the loss terms, every parameter's gradient,
every (token, expert) choice; the shares of a layer against the whole layer.
The operators alone (the chunked delta rule against the recurrence one
position at a time, the two passes around it) are in `test_qwen3_next_ops.py`
with the tolerances of their bfloat16 cases: two files, so that
`--dist loadfile` can give them to two workers.

Tolerances: both sides compute in float32 and differ in the order of their
sums (the program solves a chunk of 64 positions at once where the reference
takes one position at a time, sorts rows by expert where the reference masks),
so what is allowed is float32 rounding through eight layers (rows of 72: one
chunk and a ragged second; one case of 150): 2e-4 on logits of
size ~1, 2e-5 on the loss terms, and on gradients 5e-4 of each leaf's largest
entry. A path that dropped a pair, skipped the shared expert, rotated the
whole head or took a plain norm scale is off by 1e-2 or more.

The chip runs another branch of the held experts than float32 inputs take:
the walk and its backward pass in bf16. The held experts' bf16 test gives
that branch bf16 inputs and holds the result and every gradient to the
float32 reference within bf16's rounding: the largest difference read, as a
share of the reference's largest entry, was 0.013 (the seeds of 8 on which
no choice flipped); 0.03 is allowed. A backward rule that forgot a routing
weight is off by 0.5 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import qwen3_next, qwen3_next_glue   # noqa: E402
from ray_tpu.models import GPT                                  # noqa: E402
from ray_tpu.models.gpt import GPTConfig                        # noqa: E402
from ray_tpu.models.moe import moe_ffn                          # noqa: E402

AUX = 0.001
VOCAB = 192


def _config(**kw):
    base = dict(
        vocab_size=VOCAB, n_layers=8, d_model=48, n_heads=4, n_kv_heads=1,
        d_head=16, d_ff=24, max_seq_len=256,
        layer_pattern=("linear", "linear", "linear", "full"),
        activation="swiglu", norm="rmsnorm_1p", norm_eps=1e-6,
        positions="rope", rope_theta=1e7, rope_fraction=0.25,
        tie_embeddings=False, qk_norm="head", attn_gate=True,
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
        linear_value_dim=12, linear_conv=4, n_experts=16, moe_top_k=3,
        moe_norm_topk_prob=True, moe_aux_coeff=AUX, moe_shared_ff=24,
        z_loss=0.0, dtype=jnp.float32, remat=False,
        attention_impl="reference")
    base.update(kw)
    return GPTConfig(**base)


def _hparams(config):
    return {"num_attention_heads": config.n_heads,
            "num_key_value_heads": config.kv_heads,
            "head_dim": config.head_dim,
            "full_attention_interval": len(config.layer_pattern),
            "partial_rotary_factor": config.rope_fraction,
            "linear_num_key_heads": config.linear_key_heads,
            "linear_num_value_heads": config.linear_value_heads,
            "num_experts_per_tok": config.moe_top_k,
            "norm_topk_prob": config.moe_norm_topk_prob,
            "rms_norm_eps": config.norm_eps, "rope_theta": config.rope_theta,
            "first_expert_held": config.moe_first_expert}


def _params(model, seed):
    params = model.init(jax.random.PRNGKey(seed))
    # scales that are not their start, or a dropped (1 + w), gate or gated
    # norm would pass; a router wide enough that choices are not near-ties;
    # a convolution and experts large enough to matter
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(a, by=0.2):
        return a + by * jax.random.normal(next(keys), a.shape)

    for kind, stack in params["blocks"].items():
        for name in ("norm1", "norm2", "ws_open"):
            stack[name] = jitter(stack[name])
        stack["router"] = stack["router"] * 20.0
        for name in ("w_up", "w_gate", "w_down", "ws_up", "ws_gate",
                     "ws_down"):
            stack[name] = stack[name] * 10.0
        if kind == "full":
            for name in ("q_norm", "k_norm"):
                stack[name] = jitter(stack[name])
        else:
            for name in ("lin_norm", "dt_bias"):
                stack[name] = jitter(stack[name])
            stack["conv_w"] = stack["conv_w"] * 25.0
            stack["w_ba"] = stack["w_ba"] * 25.0
    params["norm_f"] = jitter(params["norm_f"])
    return params


def _reference(params, tokens, config):
    top, layers = qwen3_next_glue.reference_weights(params, None,
                                                    jax.devices())
    return qwen3_next.loss_terms(tokens, top, layers, _hparams(config))


def _reference_total(params, tokens, config):
    """(the loss the system minimises, the reference's terms)."""
    terms = _reference(params, tokens, config)
    return terms["ce"] + AUX * terms["load_balance"], terms


CASES = {
    # query heads a KV head 4, value heads a key head 2, rows of 72
    "all_experts_held": dict(n_experts=8),
    "a_share_held": dict(moe_first_expert=4, moe_experts_held=8),
    "two_chunks_and_a_ragged_third": dict(moe_first_expert=8,
                                          moe_experts_held=4, seq=150),
    "gqa_group_1": dict(n_heads=2, n_kv_heads=2, linear_key_heads=4,
                        moe_first_expert=12, moe_experts_held=4),
    # every kernel under the interpreter: the flash kernels, the delta
    # rule's pair and the Gated DeltaNet layer's two passes around it
    "through_the_kernels": dict(attention_impl="pallas_interpret",
                                moe_first_expert=4, moe_experts_held=8),
    # a router of a whole 128-lane tile: its top-k is `ops.router_topk`'s
    # kernel too (a narrower one keeps `lax.top_k`)
    "through_the_router_kernel": dict(attention_impl="pallas_interpret",
                                      n_experts=128, moe_top_k=6,
                                      moe_first_expert=40,
                                      moe_experts_held=32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_system_agrees_with_the_plain_reference(case):
    kw = dict(CASES[case])
    seq = kw.pop("seq", 72)
    config = _config(**kw)
    model = GPT(config)
    params = _params(model, seed=3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, seq), 0, VOCAB)
    def system(p):      # one program: the forward pass is shared
        return (jax.value_and_grad(model.loss, has_aux=True)(
            p, {"tokens": tokens}), model.forward_with_aux(p, tokens))

    with jax.default_matmul_precision("highest"):
        ((total, metrics), grads), (logits, aux) = jax.jit(system)(params)
        (ref_total, ref), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference_total(p, tokens, config),
            has_aux=True))(params)
    # routing: the same choices over all the router's outputs, and the held
    # experts were given every pair the router sent them
    assert np.array_equal(np.sort(np.asarray(aux["moe_expert_choice"]), -1),
                          np.sort(np.asarray(ref["chosen"]), -1))
    assert np.array_equal(np.asarray(aux["moe_expert_tokens"]),
                          np.asarray(ref["counts"]))
    first, held = config.moe_first_expert, config.experts_held
    if held < config.n_experts:
        given = np.asarray(metrics["moe_expert_tokens"])
        assert given.shape == (config.n_layers, held)
        assert np.array_equal(given.sum(-1),
                              np.asarray(metrics["moe_routed_here"]))
        assert np.array_equal(
            given, np.asarray(ref["counts"])[:, first:first + held])
        # and the step reports the rows the walk took for them, a layer
        walked = np.asarray(metrics["moe_rows_walked"])
        assert walked.shape == (config.n_layers,)
        assert (walked >= given.sum(-1)).all() and walked.sum() > 0
    # values
    assert float(jnp.max(jnp.abs(logits - ref["logits"]))) < 2e-4
    assert abs(float(metrics["ce_loss"]) - float(ref["ce"])) < 2e-5
    assert abs(float(metrics["moe_aux_loss"])
               - float(ref["load_balance"])) < 2e-5
    assert abs(float(total) - float(ref_total)) < 2e-5
    # every parameter's gradient
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat)
    for path, g in flat:
        want = ref_flat[path]
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - want))) <= 5e-4 * scale, (
            jax.tree_util.keystr(path))


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """Four shares of four experts, the shared expert counted once, give
    what the uncut reference gives for the layer with all sixteen."""
    config = _config(n_layers=4)
    params = _params(GPT(config), seed=7)
    top, layers = qwen3_next_glue.reference_weights(params, None,
                                                    jax.devices())
    w = next(iter(layers))
    stack = {k: v[0, 0] for k, v in params["blocks"]["linear"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 96, config.d_model))
    def share(h, first):
        return moe_ffn(
            h, stack["router"], *(stack[k][first:first + 4] for k in (
                "w_up", "w_gate", "w_down")),
            top_k=config.moe_top_k, norm_topk_prob=True, first_expert=first,
            dtype=jnp.float32)

    with jax.default_matmul_precision("highest"):
        whole, _ = qwen3_next.expert_block(x, w, _hparams(config))
        shared_alone = whole - qwen3_next.expert_block(
            x, w, _hparams(config), shared=False)[0]
        h = GPT(config)._norm(x, stack["norm2"], None)
        parts, given = [], []
        for first in range(0, 16, 4):
            out, aux = jax.jit(share, static_argnums=1)(h, first)
            parts.append(out)
            given.append(int(aux["moe_routed_here"]))
            assert int(aux["moe_expert_tokens"][first:first + 4].sum()
                       ) == given[-1]
    assert sum(given) == 2 * 96 * config.moe_top_k      # no pair lost
    assert float(jnp.max(jnp.abs(sum(parts) + shared_alone - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-2     # and not trivially


def test_a_hot_share_is_walked_in_more_than_one_chunk(monkeypatch):
    """However many pairs are routed here, none is dropped: with chunks of
    64 rows a share that receives hundreds takes several trips."""
    from ray_tpu.models import moe
    monkeypatch.setattr(moe, "_held_chunk_rows", lambda pairs, share: 64)
    config = _config(n_layers=4)
    stack = {k: v[0, 0]
             for k, v in _params(GPT(config), 9)["blocks"]["linear"].items()}
    # a router that sends every token to experts 0..2 first
    router = stack["router"].at[:, :3].add(50.0)
    h = 0.5 + jnp.abs(jax.random.normal(jax.random.PRNGKey(10),
                                        (2, 100, config.d_model)))

    def part(h, held):
        return moe_ffn(h, router, *(stack[k][held] for k in (
            "w_up", "w_gate", "w_down")), top_k=3, norm_topk_prob=True,
            first_expert=held.start, dtype=jnp.float32)

    with jax.default_matmul_precision("highest"):
        out, aux = part(h, slice(0, 4))
        whole, _ = part(h, slice(0, 16))
        grad = jax.grad(lambda h: part(h, slice(0, 4))[0].sum())(h)
        grad_whole = jax.grad(lambda h: part(h, slice(0, 16))[0].sum())(h)
    assert int(aux["moe_routed_here"]) >= 500 > 64
    assert int(aux["moe_expert_tokens"][:4].sum()) == int(
        aux["moe_routed_here"])
    # everything was routed to the share: it gives what all sixteen give
    assert int(aux["moe_routed_here"]) == 2 * 100 * 3
    assert int(aux["moe_rows_walked"]) == 10 * 64 >= 600
    assert float(jnp.max(jnp.abs(out - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(grad - grad_whole))) < 1e-4


def test_a_period_s_walks_lower_to_one_function_a_size_of_trip(monkeypatch):
    """What the walk costs a program's start: two periods of the Qwen3-Next
    pattern under "full" remat — eight expert layers, each walked forward,
    forward again where the checkpoint recomputes it and backward — lower to
    one private function a size of trip for the forward trip and one for the
    backward, not one a layer or a call site: the trips are jitted functions
    of their arrays (`moe._held_trip`, `moe._held_trip_bwd`), so the period's
    layers share a lowered function a size."""
    import collections
    import re
    from ray_tpu.models import moe
    monkeypatch.setattr(moe, "_held_trip_sizes",
                        lambda pairs, share: (96, 48))
    config = _config(moe_first_expert=4, moe_experts_held=4, remat=True)
    assert config.n_layers == 2 * len(config.layer_pattern) == 8
    model = GPT(config)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 96), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: model.loss(p, {"tokens": t}), has_aux=True)).lower(
            params, tokens).as_text()
    private = collections.Counter(
        re.sub(r"_\d+$", "", name) for name in re.findall(
            r"func\.func private @(\w+)\(", text))
    assert private["_held_trip"] == 2 and private["_held_trip_bwd"] == 2
    # each called from every layer's walk: a loop a size, the four layers of
    # the period written out inside the scan over periods
    assert len(re.findall(r"call @_held_trip(?:_\d+)?\(", text)) == 8
    assert len(re.findall(r"call @_held_trip_bwd(?:_\d+)?\(", text)) == 8


@pytest.mark.parametrize("seed", [1, 5])
def test_held_experts_in_bf16_match_the_float32_reference(seed):
    """The branch the chip runs of the held share (experts 4..7 of 16): the
    walk and its backward pass on bf16 rows and weights. The share's part of
    the result and the gradient of the input, the norm, the router and each
    of the three expert matrices against the reference's expert block in
    float32. The seeds are ones on which rounding the rows to bf16 flips no
    choice: a flipped choice is a different function, not a rounding."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    config = _config(n_layers=4, moe_first_expert=4, moe_experts_held=4)
    model = GPT(config)
    stack = _params(model, seed)["blocks"]["linear"]
    ws = {name: stack[name][0, 0]
          for name in ("norm2", "router", "w_up", "w_gate", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(100 + seed),
                          (2, 96, config.d_model))
    weight = jax.random.normal(jax.random.PRNGKey(200 + seed), x.shape)

    def system(x, ws):
        h = model._norm(x, ws["norm2"], None).astype(bf16)
        out, aux = moe_ffn(h, ws["router"], ws["w_up"], ws["w_gate"],
                           ws["w_down"], top_k=config.moe_top_k,
                           norm_topk_prob=True, first_expert=4, dtype=bf16)
        return (out.astype(f32) * weight).sum(), (out, aux)

    def reference(x, ws):
        out, facts = qwen3_next.expert_block(x, {
            "post_attention_layernorm": ws["norm2"], "mlp.gate": ws["router"],
            "mlp.experts.gate_proj": ws["w_gate"],
            "mlp.experts.up_proj": ws["w_up"],
            "mlp.experts.down_proj": ws["w_down"]}, _hparams(config),
            shared=False)
        return (out * weight).sum(), (out, facts)

    (_, (out, aux)), grads = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1), has_aux=True))(x, ws)
    with jax.default_matmul_precision("highest"):
        (_, (want, facts)), wants = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True))(x, ws)
    assert out.dtype == bf16 and int(aux["moe_routed_here"]) > 100
    assert np.array_equal(np.sort(np.asarray(aux["moe_expert_choice"]), -1),
                          np.sort(np.asarray(facts["chosen"]), -1))
    pairs = [("out", out, want), ("x", grads[0], wants[0])] + [
        (name, grads[1][name], wants[1][name]) for name in ws]
    for name, got, ref in pairs:
        assert float(jnp.max(jnp.abs(got.astype(f32) - ref))) <= 0.03 * float(
            jnp.max(jnp.abs(ref))), name


def test_partial_rope_and_the_norms_match_the_reference():
    """RoPE turns the first quarter of a head and leaves the other three
    untouched; the (1 + w) scale, the per-head q/k norm and the gated norm
    match the reference at non-zero w."""
    config = _config()
    model = GPT(config)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 4, 16))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    turned = model._rope(x, positions)
    assert np.array_equal(np.asarray(turned[..., 4:]), np.asarray(x[..., 4:]))
    assert float(jnp.max(jnp.abs(turned[:, 1:, :, :4] - x[:, 1:, :, :4]))
                 ) > 1e-2
    want = qwen3_next._rope(x.transpose(0, 2, 1, 3), config.rope_theta,
                            config.rope_fraction).transpose(0, 2, 1, 3)
    assert float(jnp.max(jnp.abs(turned - want))) < 1e-6
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    assert float(jnp.max(jnp.abs(
        model._norm(x, w, None) - qwen3_next._rms_norm(x, w, 1e-6)))) < 1e-6
    plain = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(model._norm(x, w, None) - plain * (1 + w)))
                 ) < 1e-6
