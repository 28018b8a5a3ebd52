"""Causal multi-head attention: jnp reference + Pallas TPU flash kernel.

The reference framework has no attention kernel of its own (it defers to
torch); on TPU the attention inner loop is the single hottest op of the
flagship models, so it gets a first-class FlashAttention-2 style Pallas
kernel: blocked online softmax, GQA-aware block mapping, and a custom VJP
whose backward is two more Pallas kernels (dq and dk/dv) driven by the
saved logsumexp.

Shapes follow [batch, num_heads, seq, head_dim] ("BHSD"). GQA is
expressed as num_q_heads = G * num_kv_heads; the kernels map q-head h to
kv-head h // G in BlockSpec index maps, so no K/V replication ever
materializes.

How the kernels spend their time (measured on the v5e, PERF.md PR 26):

* Tile classes. A grid step covers a large tile (by default the whole
  sequence up to 1024, because a grid step costs the same live or dead),
  and walks it in rectangles of scores. A rectangle that the causal mask
  empties is *dead* and not computed; one that the mask keeps whole is
  *interior* and runs no iota, compare or select; only an *edge*
  rectangle, which the diagonal or a ragged end crosses, builds a mask.
  `chunk_classes` counts them from the same bounds the loops use.
* Layout. Scores are held [keys, queries]: keys on sublanes, queries on
  lanes. The softmax statistics of a block of queries are then [1, n] rows
  and its accumulator [head_dim, n], small enough to ride a loop in
  registers, and lse and delta travel as [batch, heads, seq] rows instead
  of lane-replicated [.., seq, 128] slabs.
* Static walks. Where a tile's place relative to the diagonal is known at
  trace time (one tile spans the sequence, or tiles are aligned: on the
  diagonal or wholly below it) the walk unrolls into straight-line code
  that the compiler's scheduler overlaps; ragged or unaligned shapes take
  the same walk as loops over bounds computed from the program ids.
* Precision. Matmul operands are in the inputs' dtype (bf16 inputs feed
  the MXU bf16; p and ds are cast to it), always with float32
  accumulation. Scores, exp, the running maximum and sum, lse, delta and
  every accumulator are float32. float32 inputs run float32 matmuls. The
  softmax scale is folded into q (forward, dq) or k (dkv) once per block
  and into the dq / dk accumulators once at the end, not into each score.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Grid tile: the whole sequence up to this length (swept on the v5e at the
# widths the benchmark runs, PERF.md PR 26: one 1024 tile beats four 512s).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# Rectangles of the walk inside a tile, (sub, chunk), by kernel: `sub` is
# the block that stays put (queries in forward and dq, keys in dkv) and
# `chunk` what the loop steps over. Swept with the tile.
_FWD_RECT = (512, 512)
_DQ_RECT = (512, 512)
_DKV_RECT = (128, 128)
_ACC_VREGS = 32   # registers (1024 float32) an accumulator may ride a loop in


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


# ---------------------------------------------------------------------------
# Tile classes: which rectangles of the score square a kernel computes
# ---------------------------------------------------------------------------
#
# Everything here is relative to one (q tile, k tile) pair, described by
#   rel     = (tile's first query) + (k_len - q_len) - (tile's first key):
#             the last key the tile's first query may see, counted from the
#             tile's first key (None if not causal);
#   q_valid, k_valid = queries / keys of the tile that lie inside the
#             sequence (less than the tile only at a ragged end).
# Values are Python ints where the tile's place is static and traced
# scalars where it comes from the program ids; the same arithmetic serves
# both. A rectangle is dead (nothing kept: not computed), interior (all
# kept: no mask) or edge (the diagonal or a ragged end crosses it: masked).

def _static(*xs):
    return all(x is None or isinstance(x, int) for x in xs)


def _clip(x, lo, hi):
    """min(max(x, lo), hi): Python ints stay static."""
    if _static(x, lo, hi):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _k_chunk_bounds(r0, sub, rel, k_valid, *, chunk):
    """For the tile's queries [r0, r0 + sub): key chunks [0, interior_end)
    are interior, [interior_end, live_end) edge, the rest dead."""
    interior_end, live_end = k_valid // chunk, -(-k_valid // chunk)
    if rel is not None:
        interior_end = _clip((rel + r0 + 1) // chunk, 0, interior_end)
        live_end = _clip((rel + r0 + sub - 1) // chunk + 1, 0, live_end)
    return interior_end, live_end


def _q_chunk_bounds(r0, sub, rel, q_valid, k_valid, *, chunk):
    """For the tile's keys [r0, r0 + sub): query chunks [0, live_start) are
    dead, [live_start, interior_start) edge (the diagonal), [interior_start,
    interior_end) interior, [interior_end, live_end) edge (a ragged end)."""
    interior_end, live_end = q_valid // chunk, -(-q_valid // chunk)
    live_start = interior_start = 0
    if rel is not None:
        live_start = _clip((r0 - rel) // chunk, 0, live_end)
        interior_start = _clip(-(-(r0 + sub - 1 - rel) // chunk),
                               live_start, interior_end)
    # keys that the sequence's end crosses are masked in every chunk
    if _static(r0, k_valid, interior_start, interior_end):
        if r0 + sub > k_valid:
            interior_start = interior_end
    else:
        interior_start = jnp.where(r0 + sub > k_valid, interior_end,
                                   interior_start)
    return live_start, interior_start, interior_end, live_end


def _divisor(block, want):
    """The largest lane-aligned divisor of `block` that is at most `want`;
    the whole block if it has none."""
    for size in range(min(want, block) // 128 * 128, 0, -128):
        if block % size == 0:
            return size
    return block


def _rect(block_sub, block_chunk, head_dim, rect):
    """(sub, chunk) for one kernel: the swept sizes, cut so that they divide
    the tile and a [head_dim, sub] float32 accumulator stays in registers."""
    sub, chunk = rect
    sub = min(sub, max(128, _ACC_VREGS * 1024 // head_dim // 128 * 128))
    return _divisor(block_sub, sub), _divisor(block_chunk, chunk)


def chunk_classes(q_len, k_len, causal, tile=DEFAULT_BLOCK_K,
                  sub=_FWD_RECT[0], chunk=_FWD_RECT[1]):
    """Count the score square's [chunk keys, sub queries] rectangles by
    class, as the forward and dq kernels walk it under `tile`-sized grid
    steps: {"dead", "interior", "edge", "computed_share"}. A pure function
    of static shapes, on the bounds that the kernels' loops use."""
    block_q, block_k = min(tile, q_len), min(tile, k_len)
    sub, chunk = _divisor(block_q, sub), _divisor(block_k, chunk)
    n_chunks = -(-block_k // chunk)
    dead = interior = edge = 0
    for q0 in range(0, q_len, block_q):
        for k0 in range(0, k_len, block_k):
            rel = q0 + k_len - q_len - k0 if causal else None
            for r0 in range(0, block_q, sub):
                interior_end, live_end = _k_chunk_bounds(
                    r0, sub, rel, min(block_k, k_len - k0), chunk=chunk)
                if q0 + r0 >= q_len:
                    interior_end = live_end = 0
                interior += interior_end
                edge += live_end - interior_end
                dead += n_chunks - live_end
    return {"dead": dead, "interior": interior, "edge": edge,
            "computed_share": (interior + edge) / (dead + interior + edge)}


def _edge_mask(k0, q0, shape, rel, q_valid, k_valid):
    """Validity of an edge rectangle of scores laid out [keys, queries]
    whose first key and query are the tile's k0-th and q0-th, or None if
    nothing masks. The in-bounds halves exist only at a ragged end (a
    `*_valid` that is not None, known at trace time)."""
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = None if rel is None else kj <= qi + rel
    for idx, valid in ((kj, k_valid), (qi, q_valid)):
        if valid is not None:
            mask = idx < valid if mask is None else mask & (idx < valid)
    return mask


# ---------------------------------------------------------------------------
# What the three kernels share
# ---------------------------------------------------------------------------

def _dot_nt(a, b):
    """a [m, d] x b [n, d] -> [m, n], float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    """a [m, n] x b [n, d] -> [m, d], float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a [n, d] x b [n, m] -> [d, m], float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _rows(ref, start, size, valid=None):
    """Rows [start, start + size) of a [1, 1, rows, width] block. Rows from
    `valid` on are zeroed if it is given: an out-of-bounds block read
    returns unspecified padding (NaN under the interpreter), and 0 * NaN
    would leak through the matmuls."""
    if isinstance(start, int):
        x = ref[0, 0, start:start + size, :]
    else:
        x = ref[0, 0, pl.ds(pl.multiple_of(start, size), size), :]
    if valid is None:
        return x
    rows = start + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    return jnp.where(rows < valid, x, jnp.zeros_like(x))


def _scaled(x, scale):
    """x * scale in x's dtype: the softmax scale folded into a [rows,
    head_dim] operand once, not into every score."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _when(cond):
    """`pl.when`, decided at trace time where the condition is static."""
    if isinstance(cond, bool):
        return lambda f: f() if cond else None
    return pl.when(cond)


def _walk(bounds, body, carry):
    """Run `body(c, carry, edge)` over chunks [bounds[0], bounds[1]) with
    `edge` False, then [bounds[1], bounds[2]) with `edge` True. Static
    bounds unroll into straight-line code, which the scheduler overlaps;
    traced bounds become two loops."""
    lo, mid, hi = bounds
    if _static(*bounds):
        for c in range(lo, hi):
            carry = body(c, carry, c >= mid)
        return carry
    carry = jax.lax.fori_loop(lo, mid, lambda c, x: body(c, x, False), carry)
    return jax.lax.fori_loop(mid, hi, lambda c, x: body(c, x, True), carry)


class _Tiling:
    """Static facts about one call's grid, and the tile's place in it."""

    def __init__(self, q_len, k_len, block_q, block_k, causal):
        self.q_len, self.k_len, self.causal = q_len, k_len, causal
        self.block_q, self.block_k = min(block_q, q_len), min(block_k, k_len)
        self.nq = pl.cdiv(q_len, self.block_q)
        self.nk = pl.cdiv(k_len, self.block_k)
        self.off = k_len - q_len
        self.ragged_q = q_len % self.block_q != 0
        self.ragged_k = k_len % self.block_k != 0
        # every live tile lies on the diagonal or wholly below it
        self.aligned = (self.block_q == self.block_k
                        and self.off % self.block_k == 0)

    def ids(self, q_axis, k_axis):
        """(qb, kb): Python 0 along an axis that one tile spans."""
        return (0 if self.nq == 1 else pl.program_id(q_axis),
                0 if self.nk == 1 else pl.program_id(k_axis))

    def span(self, q_valid, k_valid):
        """(queries, keys) of the tile inside the sequence, as numbers."""
        return (self.block_q if q_valid is None else q_valid,
                self.block_k if k_valid is None else k_valid)

    def valid(self, qb, kb):
        """(q_valid, k_valid): how much of the tile lies inside the
        sequence; None where the length divides the block."""
        def inside(length, block, b):
            return jnp.minimum(length - b * block, block)
        return (inside(self.q_len, self.block_q, qb) if self.ragged_q
                else None,
                inside(self.k_len, self.block_k, kb) if self.ragged_k
                else None)

    def for_each_class(self, qb, kb, walk):
        """Call `walk(rel)` for the tile's class. A static place, or no
        mask at all, is one static walk. Aligned causal tiles are either
        on the diagonal or wholly interior, each a static walk under its
        `pl.when`; dead tiles run nothing. Ragged or unaligned shapes walk
        with the traced `rel`."""
        if not self.causal:
            return walk(None)
        rel = qb * self.block_q + self.off - kb * self.block_k
        if _static(rel):
            return walk(rel) if rel + self.block_q > 0 else None
        if self.aligned and not (self.ragged_q or self.ragged_k):
            pl.when(rel == 0)(lambda: walk(0))
            pl.when(rel >= self.block_k)(lambda: walk(self.block_k))
        else:
            pl.when(rel + self.block_q > 0)(lambda: walk(rel))

    def last_live_k(self, i):
        """The last k tile that q tile `i` computes: dead steps name its
        block again, so that no copy is issued for them."""
        if not self.causal:
            return self.nk - 1
        return jnp.clip((i * self.block_q + self.block_q - 1 + self.off)
                        // self.block_k, 0, self.nk - 1)

    def first_live_q(self, j):
        """The first q tile that k tile `j` computes; dkv's dead steps come
        before it and name its block."""
        if not self.causal:
            return 0
        return jnp.clip((j * self.block_k - self.off) // self.block_q,
                        0, self.nq - 1)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, t, sub, chunk, transposed_out):
    """One (q tile, k tile) step of the forward pass.

    Scores are laid out [keys, queries]. Each `sub` queries of the q tile
    walk their live `chunk`s of keys with online softmax; the running
    maximum and sum ([1, sub]) and the transposed accumulator ([head_dim,
    sub]) ride the walk in registers and rest in VMEM scratch between k
    tiles. Dead chunks are not visited, only edge chunks build a mask, and
    the select after the exp exists only where a query can have no key at
    all. q is scaled once per sub-block. Matmul operands are in the inputs'
    dtype (p cast to it); scores, exp, m, l, the accumulator and lse are
    float32. With `transposed_out` the output block is written as it was
    accumulated, [head_dim, queries].
    """
    qb, kb = t.ids(2, 3)
    q_valid, k_valid = t.valid(qb, kb)
    dtype = q_ref.dtype
    keyless = t.causal and t.off < 0      # queries before the first key

    @_when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def walk(rel):
        for r0 in range(0, t.block_q, sub):
            rs = slice(r0, r0 + sub)
            interior_end, live_end = _k_chunk_bounds(
                r0, sub, rel, t.span(q_valid, k_valid)[1], chunk=chunk)
            q = _scaled(q_ref[0, 0, rs, :], scale)

            def step(c, carry, edge):
                m_prev, l_prev, acc = carry
                pad = k_valid if edge else None
                st = _dot_nt(_rows(k_ref, c * chunk, chunk, pad), q)
                mask = _edge_mask(c * chunk, r0, st.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    st = jnp.where(mask, st, NEG_INF)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(st, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                pt = jnp.exp(st - m_new)
                if mask is not None and keyless:
                    pt = jnp.where(mask, pt, 0.0)   # m == NEG_INF: exp(0)
                pv = _dot_tn(_rows(v_ref, c * chunk, chunk, pad),
                             pt.astype(dtype))
                return (m_new,
                        l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True),
                        acc * alpha + pv)

            m_ref[:, rs], l_ref[:, rs], acc_ref[:, rs] = _walk(
                (0, interior_end, live_end), step,
                (m_ref[:, rs], l_ref[:, rs], acc_ref[:, rs]))

    t.for_each_class(qb, kb, walk)

    @_when(kb == t.nk - 1)
    def _finalize():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o = acc_ref[:] / l                  # [head_dim, block_q]
        o_ref[0, 0] = (o if transposed_out else o.T).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)


def _fwd_pallas(q, k, v, *, scale, causal, block_q, block_k, interpret,
                transposed_out=False):
    """Returns (out [B, H, S, D] in q's dtype, lse [B, H, S] float32); with
    `transposed_out`, out is [B, H, D, S]."""
    batch, num_q_heads, q_len, head_dim = q.shape
    num_kv_heads, k_len = k.shape[1], k.shape[2]
    group = num_q_heads // num_kv_heads
    t = _Tiling(q_len, k_len, block_q, block_k, causal)
    sub, chunk = _rect(t.block_q, t.block_k, head_dim, _FWD_RECT)

    q_spec = pl.BlockSpec((1, 1, t.block_q, head_dim),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, t.block_k, head_dim),
        lambda b, h, i, j: (b, h // group,
                            jnp.minimum(j, t.last_live_k(i)), 0))
    out_spec, out_shape = q_spec, q.shape
    if transposed_out:
        out_spec = pl.BlockSpec((1, 1, head_dim, t.block_q),
                                lambda b, h, i, j: (b, h, 0, i))
        out_shape = (batch, num_q_heads, head_dim, q_len)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, t=t, sub=sub,
                          chunk=chunk, transposed_out=transposed_out),
        grid=(batch, num_q_heads, t.nq, t.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            out_spec,
            pl.BlockSpec((1, 1, 1, t.block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, q.dtype),
            jax.ShapeDtypeStruct((batch, num_q_heads, 1, q_len),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((head_dim, t.block_q), jnp.float32),
            pltpu.VMEM((1, t.block_q), jnp.float32),
            pltpu.VMEM((1, t.block_q), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style, lse + delta residuals)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, t, sub, chunk):
    """One (q tile, k tile) step of dq, walked as the forward pass is:
    p = exp(s - lse) and ds = p * (dp - delta) per [chunk keys, sub
    queries] rectangle, dq^T ([head_dim, sub]) accumulated in registers
    and scaled once at the end. lse and delta are [1, sub] rows. Matmul
    operands in the inputs' dtype (ds cast to it), the rest float32."""
    qb, kb = t.ids(2, 3)
    q_valid, k_valid = t.valid(qb, kb)
    dtype = q_ref.dtype

    @_when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def walk(rel):
        for r0 in range(0, t.block_q, sub):
            rs = slice(r0, r0 + sub)
            interior_end, live_end = _k_chunk_bounds(
                r0, sub, rel, t.span(q_valid, k_valid)[1], chunk=chunk)
            q = _scaled(q_ref[0, 0, rs, :], scale)
            do = do_ref[0, 0, rs, :]
            lse = lse_ref[0, 0, :, rs]
            delta = delta_ref[0, 0, :, rs]

            def step(c, acc, edge):
                pad = k_valid if edge else None
                k = _rows(k_ref, c * chunk, chunk, pad)
                v = _rows(v_ref, c * chunk, chunk, pad)
                pt = jnp.exp(_dot_nt(k, q) - lse)
                mask = _edge_mask(c * chunk, r0, pt.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    # also: padded queries carry garbage lse
                    pt = jnp.where(mask, pt, 0.0)
                dst = pt * (_dot_nt(v, do) - delta)
                return acc + _dot_tn(k, dst.astype(dtype))

            acc_ref[:, rs] = _walk((0, interior_end, live_end), step,
                                   acc_ref[:, rs])

    t.for_each_class(qb, kb, walk)

    @_when(kb == t.nk - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[:] * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, t, sub, chunk):
    """One (k tile, q tile) step of dk and dv: each `sub` keys of the k
    tile walk their live `chunk`s of queries, scores again [keys, queries],
    dk and dv ([sub, head_dim]) accumulated in registers. k is scaled once
    per sub-block for the scores and dk once at the end. lse and delta
    arrive whole, as [chunks, chunk] rows, so that the walk indexes them on
    the sublane axis. Matmul operands in the inputs' dtype (p and ds cast
    to it), the rest float32."""
    qb, kb = t.ids(3, 2)
    q_valid, k_valid = t.valid(qb, kb)
    n_chunks = t.block_q // chunk
    dtype = q_ref.dtype

    @_when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def walk(rel):
        for r0 in range(0, t.block_k, sub):
            rs = slice(r0, r0 + sub)
            live_start, interior_start, interior_end, live_end = \
                _q_chunk_bounds(r0, sub, rel, *t.span(q_valid, k_valid),
                                chunk=chunk)
            k = _scaled(_rows(k_ref, r0, sub, k_valid), scale)
            v = _rows(v_ref, r0, sub, k_valid)

            def step(c, carry, edge):
                dk, dv = carry
                pad = q_valid if edge else None
                q = _rows(q_ref, c * chunk, chunk, pad)
                do = _rows(do_ref, c * chunk, chunk, pad)
                row = qb * n_chunks + c
                row = slice(row, row + 1) if _static(row) else pl.ds(row, 1)
                lse = lse_ref[0, 0, row, :]
                delta = delta_ref[0, 0, row, :]
                pt = jnp.exp(_dot_nt(k, q) - lse)
                mask = _edge_mask(r0, c * chunk, pt.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    pt = jnp.where(mask, pt, 0.0)
                dv = dv + _dot_nn(pt.astype(dtype), do)
                dst = pt * (_dot_nt(v, do) - delta)
                return dk + _dot_nn(dst.astype(dtype), q), dv

            # the diagonal's edge chunks come first, a ragged end's last
            carry = _walk((live_start, live_start, interior_start), step,
                          (dk_acc[rs], dv_acc[rs]))
            dk_acc[rs], dv_acc[rs] = _walk(
                (interior_start, interior_end, live_end), step, carry)

    t.for_each_class(qb, kb, walk)

    @_when(qb == t.nq - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, *, scale, causal, block_q, block_k,
                interpret, delta=None, keep_f32=False):
    """lse and delta (if given) are [B, H, S] float32."""
    batch, num_q_heads, q_len, head_dim = q.shape
    num_kv_heads, k_len = k.shape[1], k.shape[2]
    group = num_q_heads // num_kv_heads
    t = _Tiling(q_len, k_len, block_q, block_k, causal)

    if delta is None:
        # delta_i = rowsum(dO * O); cheap, fused by XLA.
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)

    q_spec = pl.BlockSpec((1, 1, t.block_q, head_dim),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, t.block_k, head_dim),
        lambda b, h, i, j: (b, h // group,
                            jnp.minimum(j, t.last_live_k(i)), 0))
    row_spec = pl.BlockSpec((1, 1, 1, t.block_q),
                            lambda b, h, i, j: (b, h, 0, i))

    sub, chunk = _rect(t.block_q, t.block_k, head_dim, _DQ_RECT)
    dq_dtype = jnp.float32 if keep_f32 else q.dtype
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, t=t, sub=sub,
                          chunk=chunk),
        grid=(batch, num_q_heads, t.nq, t.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, dq_dtype),
        scratch_shapes=[pltpu.VMEM((head_dim, t.block_q), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse[:, :, None], delta[:, :, None])

    # dk/dv: kv block is the outer grid axis, q blocks stream innermost.
    sub, chunk = _rect(t.block_k, t.block_q, head_dim, _DKV_RECT)
    rows = t.nq * t.block_q // chunk

    def chunked(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, rows * chunk - q_len)))
        return x.reshape(batch, num_q_heads, rows, chunk)

    q_spec_i = pl.BlockSpec(
        (1, 1, t.block_q, head_dim),
        lambda b, h, j, i: (b, h, jnp.maximum(i, t.first_live_q(j)), 0))
    kv_spec_i = pl.BlockSpec((1, 1, t.block_k, head_dim),
                             lambda b, h, j, i: (b, h // group, j, 0))
    row_spec_i = pl.BlockSpec((1, 1, rows, chunk),
                              lambda b, h, j, i: (b, h, 0, 0))
    kv_out_spec = pl.BlockSpec((1, 1, t.block_k, head_dim),
                               lambda b, h, j, i: (b, h, j, 0))

    # Accumulated per q-head, then reduced over the GQA group outside.
    dkv_shape = (batch, num_q_heads, k_len, head_dim)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, t=t, sub=sub,
                          chunk=chunk),
        grid=(batch, num_q_heads, t.nk, t.nq),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct(dkv_shape, jnp.float32),
            jax.ShapeDtypeStruct(dkv_shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((t.block_k, head_dim), jnp.float32),
                        pltpu.VMEM((t.block_k, head_dim), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, chunked(lse), chunked(delta))

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


# Names of the forward kernel's two results as `jax.checkpoint` sees them. A
# policy that lists them (`GPT`'s "dots") keeps them for the backward pass,
# which then does not run the forward kernel a second time; under any other
# policy, and outside `jax.checkpoint`, a name does nothing.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _narrow(q):
    """Head width that does not fill the 128 lanes of a tile."""
    return q.shape[-1] % 128 != 0


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """The residual that carries the output is lane-dense. An array whose
    minor dimension is narrower than the 128 lanes of a tile is stored
    padded to them (head width 64: twice its size, as a saved activation and
    in every copy of it), so at such a width the kernel writes the output as
    it accumulated it, [B, H, D, S], and that is what is kept. The primal
    result is derived from the named value: were it a side copy, a
    checkpoint that saves the name would still rerun the kernel for whoever
    reads the result."""
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    transposed = _narrow(q)
    out, lse = _fwd_pallas(q, k, v, scale=scale_val, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, transposed_out=transposed)
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    primal = jnp.swapaxes(out, 2, 3) if transposed else out
    return primal, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    delta = None
    if _narrow(q):
        # the kernels want the output for delta = rowsum(dO * O) alone: taken
        # from the transposed output it costs no copy back to [B, H, S, D]
        delta = jnp.sum(jnp.swapaxes(g, 2, 3).astype(jnp.float32)
                        * out.astype(jnp.float32), axis=2)
        out = None
    dq, dk, dv = _bwd_pallas(q, k, v, out, lse, g, scale=scale_val,
                             causal=causal, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             delta=delta)
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
