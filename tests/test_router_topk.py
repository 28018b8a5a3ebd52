"""`ops.router_topk`: the kernel (under the interpreter) against `lax.top_k`,
bit for bit — values, indices, their order among equals, the gradient — and
the choice of form by width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops.router_topk import router_topk

WIDTHS = [(512, 10), (128, 6), (256, 8)]
IDS = [f"E{e}_K{k}" for e, k in WIDTHS]


def _probs(tokens, e, seed=0):
    return jax.nn.softmax(3.0 * jax.random.normal(
        jax.random.PRNGKey(seed), (tokens, e), jnp.float32))


def _same(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("e,k", WIDTHS, ids=IDS)
def test_kernel_equals_lax_top_k_on_random_rows(e, k):
    probs = _probs(256, e)
    _same(router_topk(probs, k, impl="pallas_interpret"),
          lax.top_k(probs, k))


@pytest.mark.parametrize("e,k", WIDTHS, ids=IDS)
def test_kernel_takes_the_lowest_index_among_equals(e, k):
    """Rows with repeated values: the maximum twice, a run of equals that
    straddles the K-th place, a row of one value throughout."""
    probs = np.array(_probs(128, e, seed=1))
    probs[:, 7] = probs.max(-1)                 # the maximum, once more
    order = np.argsort(-probs, -1)
    at = order[:, k - 2:k + 2]                  # four equals around place K
    probs[np.arange(128)[:, None], at] = probs[np.arange(128), order[:, k]][
        :, None]
    probs[5] = 1.0 / e
    probs = jnp.asarray(probs)
    _same(router_topk(probs, k, impl="pallas_interpret"),
          lax.top_k(probs, k))


@pytest.mark.parametrize("e,k", WIDTHS, ids=IDS)
def test_kernel_takes_a_token_count_that_is_no_multiple_of_the_tile(e, k):
    probs = _probs(300, e, seed=2)
    _same(router_topk(probs, k, impl="pallas_interpret"),
          lax.top_k(probs, k))


@pytest.mark.parametrize("e,k", WIDTHS, ids=IDS)
def test_gradient_equals_autodiff_of_lax_top_k(e, k):
    probs = _probs(200, e, seed=3)
    weight = jax.random.normal(jax.random.PRNGKey(4), (200, k))

    def loss(impl):
        def f(p):
            values, idx = router_topk(p, k, impl=impl)
            return (values * weight).sum() + (values ** 2).sum()
        return f

    _same([jax.grad(loss("pallas_interpret"))(probs)],
          [jax.grad(loss("reference"))(probs)])


@pytest.mark.parametrize("impl,kernel", [("auto", False), ("reference", False),
                                         ("pallas_interpret", True)])
def test_form_by_name(impl, kernel):
    """On this backend "auto" is `lax.top_k` at any width."""
    text = str(jax.make_jaxpr(lambda p: router_topk(p, 4, impl=impl))(
        _probs(128, 128)))
    assert ("pallas_call" in text) == kernel
    assert ("top_k" in text) != kernel


def test_a_width_of_no_whole_lane_tile_keeps_lax_top_k():
    """E = 64 (OLMoE's router): "auto" takes `lax.top_k`, "pallas" by name
    refuses the width, and the layer hands such a router to `lax.top_k`
    whatever kernels the model was told to run."""
    from ray_tpu.models.moe import moe_ffn
    probs = _probs(128, 64)
    _same(router_topk(probs, 8, impl="auto"), lax.top_k(probs, 8))
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        router_topk(probs, 8, impl="pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        router_topk(probs, 8, impl="mosaic")
    d, e, f = 32, 64, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    args = (jax.random.normal(keys[0], (1, 128, d), jnp.bfloat16),
            jax.random.normal(keys[1], (d, e)),
            jax.random.normal(keys[2], (e, d, f)),
            jax.random.normal(keys[3], (e, d, f)),
            jax.random.normal(keys[4], (e, f, d)))
    text = str(jax.make_jaxpr(
        lambda *a: moe_ffn(*a, top_k=8, impl="pallas")[0])(*args))
    assert "top_k" in text and "moe_topk" not in text


def test_the_router_products_are_float32_at_the_highest_precision():
    """What the kernel selects from is made as before (PR 41 left it): the
    logits' product, and its two transposes in the backward pass, take
    float32 operands at `HIGHEST` — bf16 activations cast up, every bit of
    the router's weights. On the chip the compiler itself leaves out the
    passes that multiply the cast's zero terms (PERF.md, PR 41); at the
    default precision it would round the weights to bf16."""
    from ray_tpu.models.moe import moe_ffn
    t, d, e, f = 128, 32, 128, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (1, t, d), jnp.bfloat16)
    weights = (jax.random.normal(keys[1], (d, e)),
               jax.random.normal(keys[2], (e, d, f)),
               jax.random.normal(keys[3], (e, d, f)),
               jax.random.normal(keys[4], (e, f, d)))

    def loss(x, router_w, *experts):
        out, aux = moe_ffn(x, router_w, *experts, top_k=6)
        return out.astype(jnp.float32).sum() + aux["moe_router_z"]

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, *weights).as_text()
    highest = [line for line in text.splitlines()
               if "dot_general" in line and "HIGHEST" in line]
    shapes = sorted(line.split(" : ")[-1] for line in highest)
    assert shapes == sorted([
        f"(tensor<{t}x{d}xf32>, tensor<{d}x{e}xf32>) -> tensor<{t}x{e}xf32>",
        f"(tensor<{t}x{e}xf32>, tensor<{d}x{e}xf32>) -> tensor<{t}x{d}xf32>",
        f"(tensor<{t}x{e}xf32>, tensor<{t}x{d}xf32>) -> tensor<{e}x{d}xf32>",
    ]), shapes
