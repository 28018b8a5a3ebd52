"""The Qwen3-Next-shaped model (`ray_tpu.models.GPT` with the layer pattern
three Gated DeltaNet layers to one gated full-attention layer, a shared
expert, and a held share of the routed experts) against the plain reference
`benchmarks/reference/qwen3_next.py`, in float32 on the CPU, on the same
seeded weights and rows: logits, the loss terms, every parameter's gradient,
every (token, expert) choice; the chunked delta rule against the recurrence
one position at a time; the shares of a layer against the whole layer.

Tolerances: both sides compute in float32 and differ in the order of their
sums (the program solves a chunk of 64 positions at once where the reference
takes one position at a time, sorts rows by expert where the reference masks),
so what is allowed is float32 rounding through eight layers (rows of 72: one
chunk and a ragged second; one case of 150): 2e-4 on logits of
size ~1, 2e-5 on the loss terms, and on gradients 5e-4 of each leaf's largest
entry. A path that dropped a pair, skipped the shared expert, rotated the
whole head or took a plain norm scale is off by 1e-2 or more.

The chip runs another branch of the two operators than float32 inputs take:
bf16 operands with float32 accumulation, the delta rule's solve at
`Precision.HIGH`, the held experts' walk and its backward pass in bf16. The
delta rule's bfloat16 cases and the held experts' bf16 test give that branch
bf16 inputs and hold the result and every gradient to the float32 reference within bf16's rounding: the largest
difference read, as a share of the reference's largest entry, was 0.0097 for
the delta rule (8 seeds at each of three decays) and 0.013 for the held
experts (the seeds of 8 on which no choice flipped); 0.02 and 0.03 are
allowed. A backward rule with a transpose missing, or one that forgot a
routing weight, is off by 0.5 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import qwen3_next, qwen3_next_glue   # noqa: E402
from ray_tpu.models import GPT                                  # noqa: E402
from ray_tpu.models.gpt import GPTConfig                        # noqa: E402
from ray_tpu.models.moe import moe_ffn                          # noqa: E402
from ray_tpu.ops import gated_deltanet                          # noqa: E402
from ray_tpu.ops.delta_rule import gated_delta_rule             # noqa: E402

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
    assert float(jnp.max(jnp.abs(out - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(grad - grad_whole))) < 1e-4


def _recurrence(q, k, v, g, beta):
    """The delta rule one position at a time, as the reference writes it."""
    hv = v.shape[2]
    q, k = (jnp.repeat(x, hv // x.shape[2], 2) for x in (q, k))

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state,
                                                   k_t))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state = jnp.zeros((q.shape[0], hv, q.shape[-1], v.shape[-1]))
    _, out = lax.scan(position, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _delta_rule_inputs(seed, dtype, rate, b, s, hk, hv, d):
    """q and k normalised as `GPT._linear_mixer` hands them over, g and beta
    in float32, and a weight for the result's sum."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, s, hk, d)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
         ).astype(dtype)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = jax.random.normal(keys[2], (b, s, hv, d)).astype(dtype)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, hv)))
    weight = jax.random.normal(keys[5], (b, s, hv, d))
    return (q, k, v, g, beta), weight


def _rule_and_grads(impl, args, weight):
    def weighted(*a):
        return (gated_delta_rule(*a, impl=impl).astype(jnp.float32)
                * weight).sum()

    return (gated_delta_rule(*args, impl=impl),
            *jax.grad(weighted, argnums=range(5))(*args))


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rate", [1e-3, 1.0, 40.0],
                         ids=["decay_near_1", "decay_mid", "decay_near_0"])
def test_chunked_delta_rule_matches_the_recurrence(rate, dtype, impl):
    """Forward and every input's gradient, on a row of 150 (two chunks and
    a ragged third), two value heads a key head, against the recurrence in
    float32: the `jnp` form and the kernel pair under the interpreter. In
    float32 both sides differ by the order of their sums; in bfloat16 (the
    branch the chip runs: q, k, v in bf16, g and beta in float32 as
    `GPT._linear_mixer` hands them over) by bf16's rounding of the same
    inputs."""
    f32 = jnp.float32
    wide = dtype == "float32"
    args, weight = _delta_rule_inputs(0 if wide else 5, dtype, rate, 2, 150,
                                      2, 4, 16 if wide else 32)
    exact = tuple(a.astype(f32) for a in args)
    with jax.default_matmul_precision("highest" if wide else "default"):
        out, *grads = _rule_and_grads(impl, args, weight)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*exact)
        wants = jax.grad(lambda *a: (_recurrence(*a) * weight).sum(),
                         argnums=range(5))(*exact)
    assert out.dtype == dtype
    if wide:
        assert float(jnp.max(jnp.abs(out - want))) < 2e-6
    for name, got, ref in zip("o q k v g beta".split(), (out, *grads),
                              (want, *wants)):
        assert bool(jnp.all(jnp.isfinite(got))), name
        largest = float(jnp.max(jnp.abs(ref)))
        allowed = 2e-5 * max(1.0, largest) if wide else 0.02 * largest
        assert float(jnp.max(jnp.abs(got.astype(f32) - ref))) <= allowed, name


def test_delta_rule_kernels_carry_the_state_across_grid_steps():
    """At the head width the chip runs (128 keys, 128 values, bf16) on a
    row of 600: ten chunks, so three grid steps of the chunk axis, the last
    ragged. The kernels under the interpreter against the `jnp` form: the
    state the forward carries from step to step, dS the backward carries
    the other way, and dq, dk summed over a key head's two value heads."""
    f32 = jnp.float32
    args, weight = _delta_rule_inputs(7, "bfloat16", 1.0, 1, 600, 1, 2, 128)
    got = _rule_and_grads("pallas_interpret", args, weight)
    want = _rule_and_grads("reference", args, weight)
    for name, a, b in zip("o q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        largest = float(jnp.max(jnp.abs(b.astype(f32))))
        assert float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
                     ) <= 0.02 * largest, name


def _solve_inputs(c, rate, width, seed):
    """a^T as `_Chunk.heads` makes it of a chunk of c positions: strictly
    upper, -beta_t k_t.k_j e^{G_t - G_j} at [j, t]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(keys[0], (c, width))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    total = jnp.cumsum(-rate * jax.nn.softplus(jax.random.normal(keys[1],
                                                                 (c,))))
    beta = jax.nn.sigmoid(jax.random.normal(keys[2], (c,)))
    upper = jnp.arange(c)[:, None] < jnp.arange(c)[None, :]
    return jnp.where(upper, -beta[None, :] * (k @ k.T) * jnp.exp(
        jnp.where(upper, total[None, :] - total[:, None], 0.0)), 0.0)


def _three_dot_inverses_t(a_ts):
    """The solve as the kernels made it until PR 46, the yardstick of the
    bf16 branch: (I + a)(I + a^2)(I + a^4)..., each product three separate
    bf16 passes summed in float32."""
    def split(a):
        high = a.astype(jnp.bfloat16)
        return high, (a - high.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(a, b):
        (a0, a1), (b0, b1) = split(a), split(b)

        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)

        return dot(a0, b0) + (dot(a0, b1) + dot(a1, b0))

    inverses = []
    for a_t in a_ts:
        c = a_t.shape[0]
        inverse, power = jnp.eye(c) + a_t, a_t
        for _ in range(c.bit_length() - 2):
            power = mm(power, power)
            inverse = inverse + mm(power, inverse)
        inverses.append(inverse)
    return inverses


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c", [32, 64])
def test_delta_rule_solve_matches_the_inverse(c, dtype):
    """`_inverses_t`, the kernels' (I - a)^-1 transposed, of three
    chunks side by side (one of decays near 1 and narrow keys, so that T is
    far from I) against numpy's inverse in float64, as a share of its
    largest entry, at the kernels' chunk and at half of it. Beside float32
    inputs it is float32's; beside bf16 inputs (three bf16 passes a
    product, two of them summed along the matrix unit's depth) no further
    off than the three separate passes that it replaced, or than float32's
    limit where both are inside it: the two differ in the order of three
    sums, by 1.3% at most here, and a tenth is allowed. (A chunk of 128,
    the backward kernel's until PR 46, reads 1.8e-4 on the third input by
    the yardstick, and 8.4e-6 in float32: the squares up to a^64 lose what
    a chunk of 64 keeps.)"""
    from ray_tpu.ops.delta_rule import _inverses_t

    a_ts = [_solve_inputs(c, rate, width, seed)
            for seed, (rate, width) in enumerate([(1.0, 128), (0.1, 32),
                                                  (1e-3, 12)])]
    wants = [np.linalg.inv(np.eye(c) - np.asarray(a_t, np.float64))
             for a_t in a_ts]

    def errors(gots):
        return [float(np.max(np.abs(np.asarray(got, np.float64) - want))
                      / np.max(np.abs(want)))
                for got, want in zip(gots, wants)]

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        got = errors(_inverses_t(a_ts, dt=jnp.dtype(dtype)))
        was = errors(_three_dot_inverses_t(a_ts))
    assert np.max(np.abs(wants[2] - np.eye(c))) > 0.5      # far from I
    for new, old in zip(got, was):
        assert new <= (2e-6 if dtype == "float32"
                       else max(1.1 * old, 2e-6)), (got, was)


# The Gated DeltaNet layer's passes around the rule (`ops/gated_deltanet.py`),
# with `_ROWS` set to 64: (row length, key heads, key width, value heads,
# value width).
PASSES = {
    # two blocks and 22 positions of a third; v's columns start at no whole
    # block of its lanes, so the parts are sliced out and dx joined
    "two_blocks_and_a_ragged_third": (150, 2, 8, 4, 12),
    "shorter_than_a_block": (40, 2, 16, 4, 16),
    # whole blocks at the chip's head width: q, k, v read in place and the
    # three backward calls write one dx through its aliases
    "whole_blocks_read_in_place": (128, 1, 128, 2, 128),
}


def _held_to(name, got, want, dtype):
    """float32: the order of the sums; bfloat16: one rounding of the output
    (an 8-bit mantissa rounds by at most 2^-9 of the value; sums the
    kernels keep in float32 are held as float32)."""
    f32 = jnp.float32
    assert got.shape == want.shape, name
    largest = float(jnp.max(jnp.abs(want)))
    error = jnp.abs(got.astype(f32) - want)
    if dtype == "float32" or got.dtype == f32:
        assert got.dtype == f32, name
        assert float(jnp.max(error)) <= 2e-6 * max(1.0, largest), name
    else:
        assert got.dtype == dtype, name
        assert bool(jnp.all(error <= 2.0 ** -8 * jnp.abs(want)
                            + 1e-6 * largest)), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_convolution_pass_kernels_match_the_jnp_form(case, dtype,
                                                     monkeypatch):
    """`gdn_conv_fwd` / `gdn_conv_bwd` under the interpreter against the
    `jnp` form in float32 on the same inputs: q, k, v, d qkv and d conv_w.
    The first taps - 1 positions against a history of zeros and the
    positions around a block's edge against the positions before it,
    written out."""
    monkeypatch.setattr(gated_deltanet, "_ROWS", 64)
    f32 = jnp.float32
    s, kh, kd, vh, vd = PASSES[case]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (2, s, 2 * kh * kd + vh * vd)).astype(
        dtype)
    w = 0.5 * jax.random.normal(keys[1], (4, x.shape[-1]))
    # cotangents the outputs' dtype holds, so that both sides are given
    # the same ones
    weights = [jax.random.normal(key, (2, s, n)).astype(dtype).astype(f32)
               for key, n in zip(keys[2:], (kh * kd, kh * kd, vh * vd))]

    def run(impl, x):
        def weighted(x, w):
            outs = gated_deltanet.gdn_conv(
                x, w, key_heads=kh, key_dim=kd, value_dim=vd, eps=1e-6,
                impl=impl)
            return sum((o.astype(f32) * t).sum()
                       for o, t in zip(outs, weights)), outs

        (_, outs), grads = jax.value_and_grad(
            weighted, argnums=(0, 1), has_aux=True)(x, w)
        return (*outs, *grads)

    got = run("pallas_interpret", x)
    want = run("reference", x.astype(f32))
    for name, a, b in zip("q k v dqkv dconv_w".split(), got, want):
        _held_to(name, a, b, dtype)
    # v is SiLU of the taps' sum alone
    xv, wv = x.astype(f32)[..., 2 * kh * kd:], w[:, 2 * kh * kd:]
    for t in (0, 1, 2, 63, 64, 65, 66):
        if t >= s:
            continue
        pre = sum(wv[i] * xv[:, t - 3 + i] for i in range(4)
                  if t - 3 + i >= 0)
        _held_to(f"v at {t}", got[2][:, t], jax.nn.silu(pre), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_gated_norm_pass_kernels_match_the_jnp_form(case, dtype,
                                                    monkeypatch):
    """`gdn_norm_fwd` / `gdn_norm_bwd` under the interpreter against the
    `jnp` form in float32 on the same inputs: the result, do, dz and
    d lin_norm, at a scale that is not its start."""
    monkeypatch.setattr(gated_deltanet, "_ROWS", 64)
    f32 = jnp.float32
    s, _, _, vh, vd = PASSES[case]
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    o, z = (jax.random.normal(key, (2, s, vh * vd)).astype(dtype)
            for key in keys[:2])
    scale = 1.0 + 0.3 * jax.random.normal(keys[2], (vd,))
    weight = jax.random.normal(keys[3], o.shape).astype(dtype).astype(f32)

    def run(impl, o, z):
        def weighted(o, z, scale):
            y = gated_deltanet.gdn_gated_norm(o, z, scale, eps=1e-6,
                                              impl=impl)
            return (y.astype(f32) * weight).sum(), y

        (_, y), grads = jax.value_and_grad(
            weighted, argnums=(0, 1, 2), has_aux=True)(o, z, scale)
        return (y, *grads)

    got = run("pallas_interpret", o, z)
    want = run("reference", o.astype(f32), z.astype(f32))
    for name, a, b in zip("y do dz dlin_norm".split(), got, want):
        _held_to(name, a, b, dtype)


def test_the_passes_kernels_refuse_a_width_they_do_not_take():
    """As the rule's: named, the kernels refuse a head width that is no
    whole number of 128-lane tiles, and "auto" takes the `jnp` form by the
    shape (here by the backend too)."""
    x = jnp.ones((1, 16, 2 * 2 * 16 + 4 * 16), jnp.bfloat16)
    w = jnp.ones((4, x.shape[-1]))
    kw = dict(key_heads=2, key_dim=16, value_dim=16, eps=1e-6)
    with pytest.raises(ValueError, match="multiples of 128"):
        gated_deltanet.gdn_conv(x, w, impl="pallas", **kw)
    with pytest.raises(ValueError, match="unknown Gated DeltaNet impl"):
        gated_deltanet.gdn_conv(x, w, impl="mosaic", **kw)
    for a, b in zip(gated_deltanet.gdn_conv(x, w, impl="auto", **kw),
                    gated_deltanet.gdn_conv(x, w, impl="reference", **kw)):
        assert jnp.array_equal(a, b)
    o = jnp.ones((1, 16, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiples of 128"):
        gated_deltanet.gdn_gated_norm(o, o, jnp.ones((16,)), eps=1e-6,
                                      impl="pallas")


def test_delta_rule_kernels_refuse_a_width_they_do_not_take():
    """Asked for by name, the kernels refuse a head width that is no
    multiple of the 128 lanes; only "auto" falls to the `jnp` form by the
    shape (here by the backend too), so a measurement that named the
    kernels never reads the reference instead."""
    args, _ = _delta_rule_inputs(3, "bfloat16", 1.0, 1, 64, 1, 2, 16)
    with pytest.raises(ValueError, match="multiples of 128"):
        gated_delta_rule(*args, impl="pallas")
    with pytest.raises(ValueError, match="unknown delta rule impl"):
        gated_delta_rule(*args, impl="mosaic")
    auto = gated_delta_rule(*args, impl="auto")
    want = gated_delta_rule(*args, impl="reference")
    assert jnp.array_equal(auto, want)


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
