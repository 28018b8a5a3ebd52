"""Faults planted in the learned sparse attention of the program, for
`test_keye_vl2.py` and for the builder's chip script (`keye_readings.py`):
each function breaks `ray_tpu` underneath a served cell, in the replica,
before the deployment is built (`loops/serve.py::Scorer` calls the one named
by `rehearsal["patch"]`, which no command line can set). Each wraps the
indexer as the model calls it (`ray_tpu.models.gpt.sparse_index`), so the
kernels and the `jnp` form are broken alike. Nothing here is reachable from
a run of the benchmark."""

from __future__ import annotations

import jax.numpy as jnp


def _wrap(change):
    """`change(real, q_idx, k_idx, w_idx, topk, impl) -> Selection` in the
    indexer's place."""
    from ray_tpu.models import gpt
    real = gpt.sparse_index

    def broken(q_idx, k_idx, w_idx, topk, *, impl="auto"):
        return change(real, q_idx, k_idx, w_idx, topk, impl)
    gpt.sparse_index = broken


def _with_mask(mask):
    """A `Selection` of a [B, keys, queries] choice, its counts summed
    again."""
    from ray_tpu.ops.sparse_index import _summary
    return _summary(jnp.swapaxes(mask != 0, 1, 2))


def selection_ignored():
    """Dense attention: every causal key is chosen, whatever the indexer
    says."""
    def dense(real, q_idx, k_idx, w_idx, topk, impl):
        selection = real(q_idx, k_idx, w_idx, topk, impl=impl)
        s = q_idx.shape[1]
        causal = jnp.arange(s)[:, None] <= jnp.arange(s)[None, :]
        return _with_mask(jnp.broadcast_to(causal, selection.mask.shape))
    _wrap(dense)


def topk_halved():
    """Half of `topk` keys a query."""
    _wrap(lambda real, q_idx, k_idx, w_idx, topk, impl: real(
        q_idx, k_idx, w_idx, topk // 2, impl=impl))


def relu_left_out():
    """The index score without its ReLU, sum_j w[t, j] (q[t, j] . k[s]): one
    product of the weighted sum m of a query's heads, which the real indexer
    makes as relu(m . k) - relu(-(m . k)), two heads weighted +1 and -1."""
    def linear(real, q_idx, k_idx, w_idx, topk, impl):
        merged = (q_idx * w_idx[..., None].astype(q_idx.dtype)).sum(
            2, keepdims=True)
        ones = jnp.ones_like(w_idx[..., :1])
        return real(jnp.concatenate([merged, -merged], 2), k_idx,
                    jnp.concatenate([ones, -ones], -1), topk, impl=impl)
    _wrap(linear)


def key_after_the_query():
    """A query also attends the key that follows it: the choice is no
    longer causal. The kernel takes a choice as causal by itself; the `jnp`
    form masks the triangle besides, so there the triangle's mask goes
    too."""
    def leaky(real, q_idx, k_idx, w_idx, topk, impl):
        selection = real(q_idx, k_idx, w_idx, topk, impl=impl)
        s = q_idx.shape[1]
        after = jnp.arange(s)[:, None] == jnp.arange(s)[None, :] + 1
        return _with_mask((selection.mask != 0) | after)
    _wrap(leaky)

    from ray_tpu.models import gpt
    from ray_tpu.ops._impl import resolve_impl
    attend = gpt.dot_product_attention

    def unmasked(q, k, v, causal=True, impl="auto", **kw):
        if (kw.get("selection") is not None
                and resolve_impl(impl, "attention") == "reference"):
            causal = False
        return attend(q, k, v, causal=causal, impl=impl, **kw)
    gpt.dot_product_attention = unmasked
