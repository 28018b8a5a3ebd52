"""Causal multi-head attention: jnp reference + Pallas TPU flash kernel.

The reference framework has no attention kernel of its own (it defers to
torch); on TPU the attention inner loop is the single hottest op of the
flagship models, so it gets a first-class FlashAttention-2 style Pallas
kernel: blocked online softmax in VMEM, fp32 accumulators, GQA-aware
block mapping, causal block skipping, and a custom VJP whose backward is
two more Pallas kernels (dq and dk/dv) driven by the saved logsumexp.

Shapes follow [batch, num_heads, seq, head_dim] ("BHSD"). GQA is
expressed as num_q_heads = G * num_kv_heads; the kernels map q-head h to
kv-head h // G in BlockSpec index maps, so no K/V replication ever
materializes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # softmax running state is lane-replicated to this width

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


# ---------------------------------------------------------------------------
# Reference implementation (ground truth; CPU path)
# ---------------------------------------------------------------------------

def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """Plain jnp attention with GQA. q: [B, H, S, D]; k/v: [B, Hk, S, D]."""
    *_, num_q_heads, q_len, head_dim = q.shape
    num_kv_heads = k.shape[-3]
    k_len = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)
    if num_q_heads != num_kv_heads:
        group = num_q_heads // num_kv_heads
        k = jnp.repeat(k, group, axis=-3)
        v = jnp.repeat(v, group, axis=-3)
    s = jnp.einsum("...hqd,...hkd->...hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        # Aligned to the end: query i attends keys j <= i + (k_len - q_len).
        qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        kj = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(kj <= qi + (k_len - q_len), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("...hqk,...hkd->...hqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _zero_padded_rows(x, block_start, length):
    """Zero rows of a loaded block that lie beyond the logical length.
    Out-of-bounds block reads return unspecified padding (NaN under the
    interpreter) and 0 * NaN = NaN would leak through the matmuls."""
    rows = block_start + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < length, x, 0.0)


def _tile_mask(qb, kb, *, block_q, block_k, q_len, k_len, causal):
    """Validity mask for the (qb, kb) tile: in-bounds rows/cols, plus the
    end-aligned causal constraint kj <= qi + (k_len - q_len) — matching
    ``attention_reference`` for q_len != k_len (decode-style calls)."""
    qi = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kj = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (qi < q_len) & (kj < k_len)
    if causal:
        mask &= kj <= qi + (k_len - q_len)
    return mask


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                q_len, k_len):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # The (qb, kb) tile is dead under causal masking iff every key index
    # exceeds every (end-aligned) query index in it.
    live = (kb * block_k <= qb * block_q + block_q - 1 + k_len - q_len) \
        if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = _zero_padded_rows(k_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qb, kb, block_q=block_q, block_k=block_k,
                          q_len=q_len, k_len=k_len, causal=causal)
        s = jnp.where(mask, s, NEG_INF)
        # Running state is lane-replicated [block_q, _LANES].
        m_prev = m_ref[:]
        s_max = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_new = jnp.maximum(m_prev, s_max)                  # [bq, LANES]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                       # [bq, bk]
        # Fully-masked (padded) rows have m == NEG_INF and would exp to 1.
        p = jnp.where(mask, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        v = _zero_padded_rows(v_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + pv

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:]))
        lse_ref[0, 0] = lse.astype(jnp.float32)


def _fwd_pallas(q, k, v, *, scale, causal, block_q, block_k, interpret):
    batch, num_q_heads, q_len, head_dim = q.shape
    num_kv_heads, k_len = k.shape[1], k.shape[2]
    group = num_q_heads // num_kv_heads
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(k_len, block_k)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               q_len=q_len, k_len=k_len)
    out, lse = pl.pallas_call(
        kernel,
        grid=(batch, num_q_heads, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, num_q_heads, q_len, _LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style, lse + delta residuals)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, q_len,
                   k_len):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (kb * block_k <= qb * block_q + block_q - 1 + k_len - q_len) \
        if causal else True

    @pl.when(live)
    def _compute():
        q = _zero_padded_rows(q_ref[0, 0].astype(jnp.float32),
                              qb * block_q, q_len)
        k = _zero_padded_rows(k_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        v = _zero_padded_rows(v_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        do = _zero_padded_rows(do_ref[0, 0].astype(jnp.float32),
                               qb * block_q, q_len)
        lse = lse_ref[0, 0][:, :1]                          # [bq, 1]
        delta = _zero_padded_rows(delta_ref[0, 0], qb * block_q,
                                  q_len)[:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qb, kb, block_q=block_q, block_k=block_k,
                          q_len=q_len, k_len=k_len, causal=causal)
        # Padded rows carry garbage lse; zero their probabilities exactly.
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, q_len, k_len):
    kb = pl.program_id(2)
    qb = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (qb * block_q + block_q - 1 + k_len - q_len >= kb * block_k) \
        if causal else True

    @pl.when(live)
    def _compute():
        q = _zero_padded_rows(q_ref[0, 0].astype(jnp.float32),
                              qb * block_q, q_len)
        k = _zero_padded_rows(k_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        v = _zero_padded_rows(v_ref[0, 0].astype(jnp.float32),
                              kb * block_k, k_len)
        do = _zero_padded_rows(do_ref[0, 0].astype(jnp.float32),
                               qb * block_q, q_len)
        lse = lse_ref[0, 0][:, :1]
        delta = _zero_padded_rows(delta_ref[0, 0], qb * block_q,
                                  q_len)[:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qb, kb, block_q=block_q, block_k=block_k,
                          q_len=q_len, k_len=k_len, causal=causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, *, scale, causal, block_q, block_k,
                interpret, delta=None, keep_f32=False):
    batch, num_q_heads, q_len, head_dim = q.shape
    num_kv_heads, k_len = k.shape[1], k.shape[2]
    group = num_q_heads // num_kv_heads
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(k_len, block_k)

    if delta is None:
        # delta_i = rowsum(dO * O); cheap, fused by XLA.
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
    # Lane-replicate [B, H, S] row statistics to match the lse layout.
    delta = jnp.broadcast_to(delta[..., None],
                             (*delta.shape, _LANES))

    q_spec = pl.BlockSpec((1, 1, block_q, head_dim),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, head_dim),
                           lambda b, h, i, j: (b, h // group, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, _LANES),
                            lambda b, h, i, j: (b, h, i, 0))

    dq_dtype = jnp.float32 if keep_f32 else q.dtype
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=q_len, k_len=k_len),
        grid=(batch, num_q_heads, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: kv block is the outer grid axis, q blocks stream innermost.
    q_spec_i = pl.BlockSpec((1, 1, block_q, head_dim),
                            lambda b, h, j, i: (b, h, i, 0))
    kv_spec_i = pl.BlockSpec((1, 1, block_k, head_dim),
                             lambda b, h, j, i: (b, h // group, j, 0))
    row_spec_i = pl.BlockSpec((1, 1, block_q, _LANES),
                              lambda b, h, j, i: (b, h, i, 0))
    kv_out_spec = pl.BlockSpec((1, 1, block_k, head_dim),
                               lambda b, h, j, i: (b, h, j, 0))

    # Accumulated per q-head, then reduced over the GQA group outside.
    dkv_shape = (batch, num_q_heads, k_len, head_dim)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=q_len, k_len=k_len),
        grid=(batch, num_q_heads, nk, nq),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct(dkv_shape, jnp.float32),
            jax.ShapeDtypeStruct(dkv_shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, head_dim), jnp.float32),
                        pltpu.VMEM((block_k, head_dim), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk.reshape(batch, num_kv_heads, group, k_len, head_dim)
        dk = dk.sum(axis=2)
        dv = dv.reshape(batch, num_kv_heads, group, k_len, head_dim)
        dv = dv.sum(axis=2)
    if keep_f32:
        return dq, dk, dv
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public flash attention with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """FlashAttention-2 on TPU (Pallas). [B, H, S, D]; GQA via Hk | H."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _fwd_pallas(q, k, v, scale=scale_val, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _bwd_pallas(q, k, v, out, lse, g, scale=scale_val,
                             causal=causal, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def dot_product_attention(q, k, v, causal: bool = True,
                          scale: Optional[float] = None,
                          impl: str = "auto",
                          block_q: int = DEFAULT_BLOCK_Q,
                          block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Attention entry point used by models.

    impl: "auto" (pallas on TPU, reference elsewhere), "pallas",
    "pallas_interpret" (kernel under the interpreter — CPU tests),
    "reference".
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return attention_reference(q, k, v, causal=causal, scale=scale)
    if impl == "pallas":
        return flash_attention(q, k, v, causal, scale, block_q, block_k,
                               False)
    if impl == "pallas_interpret":
        return flash_attention(q, k, v, causal, scale, block_q, block_k,
                               True)
    raise ValueError(f"unknown attention impl {impl!r}")
