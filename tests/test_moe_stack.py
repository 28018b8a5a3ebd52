"""A layer's experts read in the stack they lie in (`moe.moe_ffn`'s `layer`,
PR 54) on the CPU at small sizes: the stack and the place against the sliced
weights, for a layer that holds all its experts and one that holds a share,
at each place of a stack of three, the place static or traced; the gradients
under weights in the compute dtype; and that a stack which is cast on its way
in is sliced into the cast and never merged. Then the model's half
(`GPT._through_blocks`): a replica's weights in the compute dtype are read in
place under the one-kind scan and under a written-out period, a derivative of
the same call is the sliced form's, and master weights in float32 never see
the stack.

Equal means bit for bit for what the router decides (the counts, the
choices) and to a rounding of the dtype for what the matmuls give: the CPU's
grouped matmul is one product contracted over (group, width), whose order of
summation follows the number of groups (float32 results differ in their last
bits, bfloat16 ones in one element of 16,384 by one step). On the chip the
kernel walks a group's rows against that group's matrix whichever stack it
lies in; `benchmarks/run.py`'s `correct` holds the served cells to the
plain reference there."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.gpt import GPT, GPTConfig

LAYERS, TOKENS, D, F, TOP_K = 3, 256, 64, 32, 4
# experts routed over, held, the first held: all of a layer's, or a share
SHARES = {"all_held": (16, 16, 0), "a_share_held": (32, 8, 8)}


def _inputs(share, dtype=jnp.bfloat16, seed=0):
    e, held, _ = SHARES[share]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (1, TOKENS, D)).astype(dtype)
    router = jax.random.normal(keys[1], (D, e))
    w_up, w_gate = (
        (jax.random.normal(k, (LAYERS, held, D, F)) * 0.1).astype(dtype)
        for k in keys[2:4])
    w_down = (jax.random.normal(keys[4], (LAYERS, held, F, D))
              * 0.1).astype(dtype)
    return x, router, (w_up, w_gate, w_down)


DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _ffn(share, x, router, weights, layer=None):
    return moe.moe_ffn(x, router, *weights, layer=layer, top_k=TOP_K,
                       first_expert=SHARES[share][2], dtype=x.dtype,
                       impl="reference")


def _merged(share, text, short="bf16"):
    """Whether the program holds a weight of all the stack's experts."""
    held = SHARES[share][1]
    return f"{short}[{LAYERS * held},{D},{F}]" in text


def _same(got, want):
    """Bit for bit for integers; for floats within 2 ** -7 of the largest
    entry in bfloat16 (a step) and 2 ** -20 in float32."""
    assert got.dtype == want.dtype and got.shape == want.shape
    if not jnp.issubdtype(got.dtype, jnp.floating):
        return np.array_equal(got, want)
    step = 2.0 ** (-7 if got.dtype == jnp.bfloat16 else -20)
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    return bool((np.abs(got - want) <= step * np.abs(want).max()).all())


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("layer", range(LAYERS),
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("share", SHARES)
def test_a_layer_read_in_its_stack_is_the_layer_sliced_out(share, layer,
                                                           traced, dtype):
    x, router, stacks = _inputs(share, DTYPES[dtype])
    want, facts = jax.jit(lambda *a: _ffn(share, *a))(
        x, router, tuple(w[layer] for w in stacks))
    if traced:
        fn = jax.jit(lambda x, r, w, at: _ffn(share, x, r, w, at))
        args = (x, router, stacks, jnp.int32(layer))
    else:
        fn = jax.jit(lambda x, r, w: _ffn(share, x, r, w, layer))
        args = (x, router, stacks)
    got, aux = fn(*args)
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 1e-3
    assert _same(got, want)
    assert aux.keys() == facts.keys()
    for name in aux:
        assert _same(aux[name], facts[name]), name
    # it was the stack that the grouped matmuls took, and no slice of it
    text = str(jax.make_jaxpr(fn)(*args))
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    assert _merged(share, text, short)
    assert f"{short}[{SHARES[share][1]},{D},{F}]" not in text


@pytest.mark.parametrize("share", SHARES)
def test_a_stack_that_is_cast_is_sliced_into_the_cast(share):
    """Master weights in float32 under a bfloat16 layer: the cast writes the
    layer's copy whatever it reads, so the slice goes in front of it and the
    program is the sliced form's, with no weight of the stack's size."""
    x, router, stacks = _inputs(share, dtype=jnp.float32)
    x = x.astype(jnp.bfloat16)
    at = jnp.int32(1)
    got, aux = jax.jit(lambda w, at: _ffn(share, x, router, w, at))(
        stacks, at)
    want, facts = jax.jit(lambda w: _ffn(share, x, router, w))(
        tuple(w[1] for w in stacks))
    assert np.array_equal(got, want)
    assert np.array_equal(aux["moe_expert_tokens"],
                          facts["moe_expert_tokens"])
    text = str(jax.make_jaxpr(lambda w, at: _ffn(share, x, router, w, at))(
        stacks, at))
    assert not _merged(share, text)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("share", SHARES)
def test_the_gradients_of_a_layer_read_in_its_stack_are_the_sliced_layers(
        share, traced, dtype):
    """Weights in the compute dtype, differentiated: the input's and the
    router's gradients are the sliced form's, the stacks' are the sliced
    weights' at the layer's place and zero at the others."""
    layer = 1
    x, router, stacks = _inputs(share, DTYPES[dtype])
    mix = jax.random.normal(jax.random.PRNGKey(9), (TOKENS, D))

    def part(x, router, weights, at):
        out, _ = _ffn(share, x, router, weights, at)
        return (out[0].astype(jnp.float32) * mix).sum()

    grad = jax.grad(part, argnums=(0, 1, 2))
    dx, dr, dw = (jax.jit(grad)(x, router, stacks, jnp.int32(layer)) if traced
                  else jax.jit(lambda *a: grad(*a, layer))(x, router, stacks))
    want_x, want_r, want_w = jax.jit(lambda *a: grad(*a, None))(
        x, router, tuple(w[layer] for w in stacks))
    assert float(jnp.abs(want_x.astype(jnp.float32)).max()) > 1e-3
    assert _same(dx, want_x)
    assert _same(dr, want_r)
    for got, want in zip(dw, want_w):
        assert got.dtype == want.dtype == DTYPES[dtype]
        assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0
        assert _same(got[layer], want)
        assert not np.asarray(got.astype(jnp.float32))[[0, 2]].any()


# ------------------------------------------------------- the model's half

MODELS = {
    # a scan over layers of one kind, each holding a share of its experts
    "one_kind": dict(n_layers=3, layer_pattern=("full",), n_experts=32,
                     moe_experts_held=8, moe_first_expert=8),
    # a leading dense layer, then periods of two kinds written out
    "a_period": dict(n_layers=7, layer_pattern=("window", "window", "full"),
                     lead_layers=("window",), lead_d_ff=96, attn_window=32,
                     n_experts=16),
}


WIDTHS = dict(
    vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32,
    max_seq_len=128, activation="swiglu", norm="rmsnorm", positions="rope",
    tie_embeddings=False, moe_top_k=4, dtype=jnp.bfloat16,
    param_dtype=jnp.float32, attention_impl="reference")


def _model(name):
    return GPT(GPTConfig(**WIDTHS, **MODELS[name], remat=False))


def _stacked_experts(text, model):
    """Whether the program holds a grouped matmul over a kind's whole stack
    of experts."""
    c = model.config
    layers = (c.n_layers - len(c.lead_layers)) // len(c.layer_pattern) * max(
        c.layer_pattern.count(kind) for kind in c.layer_pattern)
    return f"bf16[{layers * c.experts_held},{c.d_model},{c.d_ff}]" in text


@pytest.mark.parametrize("name", MODELS)
def test_a_replica_reads_its_experts_in_place_and_a_gradient_slices_them(
        name):
    model = _model(name)
    master = model.init(jax.random.PRNGKey(0))
    served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), master)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)

    def mean_logit(params):
        return model.apply(params, tokens).mean()

    # served: in place; the same weights differentiated, or master weights
    # in float32 whether differentiated or not: the sliced form
    assert _stacked_experts(str(jax.make_jaxpr(model.apply)(
        served, tokens)), model)
    for fn, params in ((jax.grad(mean_logit), served),
                       (jax.grad(mean_logit), master),
                       (mean_logit, master)):
        assert not _stacked_experts(str(jax.make_jaxpr(fn)(params)), model)

    # and the sliced form is what it was: the same model told that its
    # experts do not lie ready gives the same logits, facts and gradients
    plain = _model(name)
    plain._experts_lie_ready = lambda blocks: False
    got, facts = jax.jit(model.forward_with_aux)(served, tokens)
    want, wanted = jax.jit(plain.forward_with_aux)(served, tokens)
    assert float(jnp.abs(want).max()) > 1e-3
    assert _same(got, want)
    assert facts.keys() == wanted.keys()
    for fact in facts:
        assert _same(facts[fact], wanted[fact]), fact
    grads = jax.jit(jax.grad(mean_logit))(served)
    wants = jax.jit(jax.grad(
        lambda p: plain.apply(p, tokens).mean()))(served)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        np.array_equal, grads, wants)))


@pytest.mark.parametrize("kw,digest", [
    (dict(n_layers=2, n_experts=16, remat=True, remat_policy="dots"),
     "72d34371eb86637c"),
    (dict(n_layers=2, n_experts=32, moe_experts_held=8, moe_first_expert=8,
          remat=True, remat_policy="full"), "1da176f742ac743d"),
    (dict(n_layers=5, layer_pattern=("window", "window", "window", "full"),
          lead_layers=("window",), lead_d_ff=96, attn_window=32,
          n_experts=16, remat=False), "86712a8d54f0d652")],
    ids=["all_held_scanned", "a_share_held_scanned", "a_period_written_out"])
def test_master_weights_in_float32_trace_the_program_they_did(kw, digest):
    """The training cells' side of the choice: with float32 weights under a
    bfloat16 layer the jaxpr of the logits and of the loss's gradients is
    the one read off the tree named here, operation for operation, with the
    same jax — so a compiled step is what it was there. A layer that holds a
    share of its experts traces what it did at PR 53: PR 56 rewrote the
    block that holds all its experts (the two other cases, read again off
    that PR's tree) and this digest is the proof that the held share's walk,
    which shares `_swiglu_groups` with it, was left as it was."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were read off jax 0.9.0's printer")
    model = GPT(GPTConfig(**WIDTHS, **kw))
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    text = str(jax.make_jaxpr(lambda p: (
        model.apply(p, tokens),
        jax.grad(lambda p: model.loss(p, {"tokens": tokens})[0])(p)))(params))
    assert hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                          ).hexdigest()[:16] == digest
