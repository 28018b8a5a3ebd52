"""`ray_tpu.ops.swiglu`: the SwiGLU product of the joined up | gate rows
times a weight a row, and its backward pass (`models/moe.py::_weighted_down`
calls both).

The forward product against the formula written out; the backward pass — the
kernel `moe_swiglu_bwd` under the interpreter and the `jnp` form — against
autodiff of that formula and, for the product it makes again, against the
forward; for bf16 and float32 rows, at an F of one and of three 128-lane
tiles (the kernel walks 256 lanes at a time, or 128 where F is no multiple
of 256), at a row count of one block, of several with a ragged last one, and
of fewer than a tile. All sides compute in float32 and round once to the
rows' dtype, so float32 results agree to the order of a row's sum over F
(1e-6 of the largest entry) and bf16 ones to a rounding step.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (weighted_swiglu, weighted_swiglu_bwd,
                         weighted_swiglu_bwd_reference)


def _plain(up_gate, weights, act="silu"):
    f = up_gate.shape[-1] // 2
    up, gate = (a.astype(jnp.float32) for a in (up_gate[:, :f],
                                                up_gate[:, f:]))
    opened = (jnp.maximum(gate, 0.0) if act == "relu"
              else gate * jax.nn.sigmoid(gate))
    return opened * up * weights[:, None]


def _inputs(rows, f, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return ((2.0 * jax.random.normal(keys[0], (rows, 2 * f))).astype(dtype),
            jax.random.uniform(keys[1], (rows,), minval=0.05, maxval=1.5),
            jax.random.normal(keys[2], (rows, f)).astype(dtype))


def _close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6
    return bool((np.abs(got - want) <= step * np.abs(want).max()).all())


DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rows,f", [(256, 128), (600, 384), (5, 256)],
                         ids=["one_block", "a_ragged_last_block",
                              "under_a_tile"])
@pytest.mark.parametrize("impl", ["pallas_interpret", "reference"])
@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_product_and_its_backward_pass_are_the_formulas(act, impl, rows,
                                                            f, dtype):
    """Under either activation of the gate (a seeded normal draw has no
    gate of exactly 0, where ReLU's derivative is a convention)."""
    dtype = DTYPES[dtype]
    up_gate, weights, g = _inputs(rows, f, dtype)
    out = weighted_swiglu(up_gate, weights, act)
    want, pull = jax.vjp(functools.partial(_plain, act=act), up_gate,
                         weights)
    assert float(jnp.abs(want).max()) > 1.0
    assert _close(out, want.astype(dtype), dtype)
    d, d_w, again = weighted_swiglu_bwd(up_gate, weights, g, impl=impl,
                                        act=act)
    want_d, want_w = pull(g.astype(jnp.float32))
    assert _close(d, want_d, dtype)
    # a row's weight gradient is a float32 sum over F on both sides
    assert _close(d_w, want_w, jnp.float32)
    # the product made again is the product
    assert np.array_equal(again, out) or (impl == "pallas_interpret"
                                          and _close(again, out, dtype))
    # and the two forms of the pass are one pass
    for got, ref in zip((d, d_w, again),
                        weighted_swiglu_bwd_reference(up_gate, weights, g,
                                                      act)):
        assert _close(got, ref, jnp.float32 if got is d_w else dtype)


def test_an_unknown_activation_is_refused_by_name():
    up_gate, weights, g = _inputs(8, 128, jnp.float32)
    with pytest.raises(ValueError, match="moe_activation 'gelu'"):
        weighted_swiglu(up_gate, weights, "gelu")
    with pytest.raises(ValueError, match="moe_activation 'gelu'"):
        weighted_swiglu_bwd(up_gate, weights, g, impl="reference",
                            act="gelu")


def test_the_kernel_is_the_backward_pass_alone():
    up_gate, weights, g = _inputs(256, 128, jnp.bfloat16)
    assert "pallas_call" not in str(jax.make_jaxpr(weighted_swiglu)(
        up_gate, weights))
    assert "moe_swiglu_bwd" in str(jax.make_jaxpr(
        lambda *a: weighted_swiglu_bwd(*a, impl="pallas_interpret"))(
            up_gate, weights, g))
