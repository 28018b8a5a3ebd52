"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference framework has no sequence/context parallelism at all
(SURVEY §5: no ring attention / Ulysses anywhere in the tree); here it is
first-class. Sequence is sharded over the mesh axis ``sp``; K/V blocks
circulate around the ring via `lax.ppermute` while each device keeps its
own Q shard, merging per-block softmax partials online (FlashAttention
accumulation across devices). Communication rides ICI neighbor links and
overlaps with the per-block attention compute.

Must be called *inside* `shard_map` (or an equivalently manual axis
context) with q/k/v already sharded over `axis_name` on the sequence
dimension. The backward pass runs the ring again, circulating dK/dV
accumulators along with the K/V blocks so a full cycle deposits them back
on their home shard.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ._impl import resolve_impl
from .attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, NEG_INF,
                        _bwd_pallas, _fwd_pallas)

_FULL = 0   # attend to every key in the block
_DIAG = 1   # intra-shard causal (the step-0 diagonal block)


def _repeat_kv(k, group):
    return jnp.repeat(k, group, axis=-3) if group > 1 else k


def _partial_fwd_reference(q, k, v, scale, diag):
    """Blockwise attention partial → (out_f32, lse) in plain jnp."""
    group = q.shape[-3] // k.shape[-3]
    k, v = _repeat_kv(k, group), _repeat_kv(v, group)
    s = jnp.einsum("...hqd,...hkd->...hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if diag:
        q_len, k_len = s.shape[-2], s.shape[-1]
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        kj = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(kj <= qi, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("...hqk,...hkd->...hqd", p, v.astype(jnp.float32))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return out / l_safe[..., None], m + jnp.log(l_safe)


def _partial_bwd_reference(q, k, v, do, lse, delta, scale, diag):
    """Blockwise gradients given the *global* lse/delta row statistics."""
    num_kv_heads = k.shape[-3]
    group = q.shape[-3] // num_kv_heads
    kr, vr = _repeat_kv(k, group), _repeat_kv(v, group)
    s = jnp.einsum("...hqd,...hkd->...hqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if diag:
        q_len, k_len = s.shape[-2], s.shape[-1]
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        kj = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(kj <= qi, s, NEG_INF)
    p = jnp.exp(s - lse[..., None])
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("...hqk,...hqd->...hkd", p, do32)
    dp = jnp.einsum("...hqd,...hkd->...hqk", do32, vr.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("...hqk,...hkd->...hqd", ds, kr.astype(jnp.float32))
    dk = jnp.einsum("...hqk,...hqd->...hkd", ds, q.astype(jnp.float32))
    if group > 1:
        b, h, klen, d = dk.shape
        dk = dk.reshape(b, num_kv_heads, group, klen, d).sum(axis=2)
        dv = dv.reshape(b, num_kv_heads, group, klen, d).sum(axis=2)
    return dq, dk, dv


def _partial_fwd_pallas(q, k, v, scale, diag, block_q, block_k, interpret):
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=diag,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return out.astype(jnp.float32), lse


def _partial_bwd_pallas(q, k, v, do, lse, delta, scale, diag, block_q,
                        block_k, interpret):
    return _bwd_pallas(q, k, v, lse, do, delta, scale=scale, causal=diag,
                       block_q=block_q, block_k=block_k, interpret=interpret,
                       keep_f32=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None, impl: str = "auto",
                   block_q: int = DEFAULT_BLOCK_Q,
                   block_k: int = DEFAULT_BLOCK_K):
    """Exact attention with sequence sharded over ``axis_name``.

    q: [B, H, S_local, D]; k/v: [B, Hk, S_local, D] (local shards).
    """
    out, _ = _ring_fwd(q, k, v, axis_name, causal, scale, impl, block_q,
                       block_k)
    return out


def _partial_fns(impl, scale, block_q, block_k):
    impl = resolve_impl(impl, "ring attention")
    if impl == "reference":
        fwd = lambda q, k, v, diag: _partial_fwd_reference(q, k, v, scale,
                                                           diag)
        bwd = lambda q, k, v, do, lse, dl, diag: _partial_bwd_reference(
            q, k, v, do, lse, dl, scale, diag)
        return fwd, bwd
    interp = impl == "pallas_interpret"
    fwd = lambda q, k, v, diag: _partial_fwd_pallas(
        q, k, v, scale, diag, block_q, block_k, interp)
    bwd = lambda q, k, v, do, lse, dl, diag: _partial_bwd_pallas(
        q, k, v, do, lse, dl, scale, diag, block_q, block_k, interp)
    return fwd, bwd


def _ring_fwd(q, k, v, axis_name, causal, scale, impl, block_q, block_k):
    size = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    fwd_fn, _ = _partial_fns(impl, scale_val, block_q, block_k)
    perm = [(i, (i + 1) % size) for i in range(size)]

    batch, heads, s_local, d = q.shape
    acc0 = jnp.zeros((batch, heads, s_local, d), jnp.float32)
    m0 = jnp.full((batch, heads, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, s_local), jnp.float32)

    def step(carry, s):
        k_cur, v_cur, acc, m, l = carry

        def skip(_):
            return jnp.zeros_like(acc), jnp.full_like(m, NEG_INF)

        def diag_blk(_):
            return fwd_fn(q, k_cur, v_cur, True)

        def full_blk(_):
            return fwd_fn(q, k_cur, v_cur, False)

        if causal:
            # Block at step s originated on shard (idx - s) mod size:
            # s == 0 → my own (diagonal causal); s <= idx → strictly
            # earlier shard (full); otherwise later shard (masked out).
            mode = jnp.where(s == 0, 1, jnp.where(s <= idx, 2, 0))
            o_s, lse_s = lax.switch(mode, [skip, diag_blk, full_blk], None)
        else:
            o_s, lse_s = full_blk(None)
        m_new = jnp.maximum(m, lse_s)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(lse_s - m_new)
        acc = acc * alpha[..., None] + o_s * beta[..., None]
        l = l * alpha + beta
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m_new, l), None

    (k_fin, v_fin, acc, m, l), _ = lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(size))
    del k_fin, v_fin  # back home after a full cycle
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, impl, block_q, block_k, res, g):
    q, k, v, out, lse = res
    size = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    _, bwd_fn = _partial_fns(impl, scale_val, block_q, block_k)
    perm = [(i, (i + 1) % size) for i in range(size)]

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def step(carry, s):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry

        def skip(_):
            return (jnp.zeros_like(dq0), jnp.zeros_like(dk0),
                    jnp.zeros_like(dv0))

        def diag_blk(_):
            return bwd_fn(q, k_cur, v_cur, g, lse, delta, True)

        def full_blk(_):
            return bwd_fn(q, k_cur, v_cur, g, lse, delta, False)

        if causal:
            mode = jnp.where(s == 0, 1, jnp.where(s <= idx, 2, 0))
            dq_s, dk_s, dv_s = lax.switch(mode, [skip, diag_blk, full_blk],
                                          None)
        else:
            dq_s, dk_s, dv_s = full_blk(None)
        dq_acc = dq_acc + dq_s
        dk_cur = dk_cur + dk_s
        dv_cur = dv_cur + dv_s
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur, axis_name, perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc), None

    (k_fin, v_fin, dk, dv, dq), _ = lax.scan(
        step, (k, v, dk0, dv0, dq0), jnp.arange(size))
    del k_fin, v_fin
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)
