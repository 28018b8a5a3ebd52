"""`impl` is resolved in one place, `ops/_impl.py`, for every operator: off
a TPU "auto" is the `jnp` form, a name that is none of the four is refused,
and where the kernels take only whole 128-lane tiles "pallas" by name
refuses another width while "auto" runs it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops import (dot_product_attention, gated_delta_rule, gdn_conv,
                         gdn_gated_norm, ring_attention, router_topk,
                         sorted_segment_sum, weighted_swiglu_bwd)
from ray_tpu.ops._impl import IMPLS, resolve_impl


def _normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _attention(impl):
    q = _normal(0, 1, 2, 16, 48)
    return dot_product_attention(q, q, q, impl=impl)


def _ring_attention(impl):
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    spec = P(None, None, "sp", None)
    q = _normal(1, 1, 2, 32, 48)
    return jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", True, None, impl),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False)(q, q, q)


def _delta_rule(impl):
    q, v = _normal(2, 1, 16, 1, 64), _normal(3, 1, 16, 2, 64)
    g = -jnp.abs(_normal(4, 1, 16, 2))
    return gated_delta_rule(q, q, v, g, jax.nn.sigmoid(g), impl=impl)


def _gdn_conv(impl):
    qkv = _normal(5, 1, 16, 2 * 2 * 48 + 4 * 48)
    return gdn_conv(qkv, _normal(6, 4, qkv.shape[-1]), key_heads=2,
                    key_dim=48, value_dim=48, eps=1e-6, impl=impl)


def _gdn_gated_norm(impl):
    o = _normal(7, 1, 16, 4 * 48)
    return gdn_gated_norm(o, o, jnp.ones((48,)), eps=1e-6, impl=impl)


def _segment_sum(impl):
    ids = jnp.sort(jax.random.randint(jax.random.PRNGKey(8), (40,), 0, 9))
    return sorted_segment_sum(_normal(9, 40, 64), ids, 8, impl=impl)


def _router_topk(impl):
    return router_topk(jax.nn.softmax(_normal(10, 24, 48)), 3, impl=impl)


def _weighted_swiglu_bwd(impl):
    return weighted_swiglu_bwd(_normal(11, 24, 96), _normal(12, 24),
                               _normal(13, 24, 48), impl=impl)


OPERATORS = {
    # the operator at a width of 48 or 64, and whether its kernels take
    # only whole 128-lane tiles
    "attention": (_attention, False),
    "ring_attention": (_ring_attention, False),
    "delta_rule": (_delta_rule, True),
    "gdn_conv": (_gdn_conv, True),
    "gdn_gated_norm": (_gdn_gated_norm, True),
    "segment_sum": (_segment_sum, True),
    "router_topk": (_router_topk, True),
    "weighted_swiglu_bwd": (_weighted_swiglu_bwd, True),
}


@pytest.mark.parametrize("name", list(OPERATORS))
def test_every_operator_resolves_impl_alike(name):
    run, tiles_only = OPERATORS[name]
    assert jax.default_backend() != "tpu"
    assert "pallas_call" not in str(jax.make_jaxpr(lambda: run("auto"))())
    for auto, want in zip(jax.tree_util.tree_leaves(run("auto")),
                          jax.tree_util.tree_leaves(run("reference"))):
        assert jnp.array_equal(auto, want)
    with pytest.raises(ValueError, match="unknown impl 'mosaic'"):
        run("mosaic")
    if tiles_only:
        with pytest.raises(ValueError, match="whole 128-lane tiles"):
            run("pallas")
    else:
        assert "pallas_call" in str(
            jax.make_jaxpr(lambda: run("pallas_interpret"))())


def test_auto_is_the_kernels_on_a_tpu_where_the_widths_allow(monkeypatch):
    """The arm no CPU run takes: with a TPU backend "auto" names the
    kernels, at whole tiles only; a name is returned as given."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl("auto", "op") == "pallas"
    assert resolve_impl("auto", "op", 128, 256) == "pallas"
    assert resolve_impl("auto", "op", 128, 64) == "reference"
    for impl in IMPLS:
        assert resolve_impl(impl, "op", 128) == impl
    with pytest.raises(ValueError, match="op: the kernels take widths"):
        resolve_impl("pallas", "op", 128, 64)
