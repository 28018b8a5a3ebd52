"""Causal multi-head attention: jnp reference + Pallas TPU flash kernel.

The reference framework has no attention kernel of its own (it defers to
torch); on TPU the attention inner loop is the single hottest op of the
flagship models, so it gets a first-class FlashAttention-2 style Pallas
kernel: blocked online softmax, GQA-aware block mapping, and a custom VJP
whose backward is one more Pallas kernel, `flash_bwd`, driven by the saved
logsumexp: it makes a rectangle's p and ds once and feeds dq, dk and dv
from them. Rows whose float32 dq accumulator does not fit in VMEM take a
kernel pair instead (`flash_bwd_dq`, `flash_bwd_dkv`), which makes every
rectangle's p and ds twice.

Two layouts, one set of kernel bodies (`_Heads`). Models hand q, k, v over
as their projections wrote them, [batch, seq, num_heads, head_dim] (a free
reshape of [batch, seq, num_heads * head_dim]: `seq_major`), and get dq, dk,
dv back the same way. At a head width under the 128 lanes a grid step's
block is 128 lanes of that minor axis, two heads of 64, walked one after
the other, so nothing between a projection and a kernel is stored, copied
or multiplied at half-filled lanes; the forward output and dO are [batch,
num_heads, head_dim, seq], as the out-projection reads and writes them.
`flash_attention`'s own entry, ring attention and head widths that fill the
lanes keep [batch, num_heads, seq, head_dim] ("BHSD"), a block a head. GQA
is expressed as num_q_heads = G * num_kv_heads; the kernels map q-head h to
kv-head h // G in BlockSpec index maps, so no K/V replication ever
materializes.

`dsa_attend_fwd` is the forward walk under a learned choice of keys
(`ops/sparse_index.py`; forward only): the same rectangles, online softmax
and order of products, a KV head's whole group of query heads a grid step,
so that the choice's block is fetched and decoded once for the heads that
share it (`_attend_kernel`, PERF.md PR 50).

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
* The forward (PERF.md PR 45) walks rectangles of 128 queries x 128 keys
  at every width the models run (56% of the causal square at S = 1024,
  62.5% at 512, 75% at 256, one rectangle at 128). Its unrolled walk is one
  list of the rectangles of all the heads of a grid step, and k.q of the
  next `_FWD_AHEAD` rectangles is issued before a rectangle's softmax and
  p.v: the matrix units take their instructions in program order, and p.v,
  whose stationary operand is the softmax's last result, would hold the
  next scores behind it while the vector slots work. At a head width under
  the 128 lanes v^T of the k tile is written once a head to a VMEM scratch
  (as a product contracting v's rows it is remade a rectangle, streamed
  out of the matrix unit at half rate); at full lanes the unit keeps pace.
* Precision. Matmul operands are in the inputs' dtype (bf16 inputs feed
  the MXU bf16; p and ds are cast to it), always with float32
  accumulation. Scores, exp, the running maximum and sum, lse, delta and
  every accumulator are float32. float32 inputs run float32 matmuls. The
  softmax scale is folded into q (forward, dq) or k (dkv, the fused
  backward) once per block and into the dq / dk accumulators once at the
  end, not into each score.
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

from ._impl import resolve_impl

NEG_INF = -1e30

# Grid tile: the whole sequence up to this length (swept on the v5e at the
# widths the benchmark runs, PERF.md PR 26: one 1024 tile beats four 512s).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# Rectangles of the walk inside a tile, (sub, chunk), by kernel: `sub` is
# the block that stays put (queries in forward and dq, keys in dkv and in
# the fused backward kernel) and `chunk` what the loop steps over. Swept
# with the tile (PERF.md PR 26; the fused kernel's, PR 38; the forward's
# with its scores made ahead, PR 45: alone on the v5e [128, 128] twelve ahead
# beat [256, 128] and [256, 256] at widths 64, 128 and 256, rows of one to
# eight k tiles).
_FWD_RECT = (128, 128)
_FWD_AHEAD = 12   # rectangles of scores an unrolled forward walk makes ahead
_DQ_RECT = (512, 512)
_DKV_RECT = (128, 128)
_BWD_RECT = (256, 256)
_ACC_VREGS = 32   # registers (1024 float32) an accumulator may ride a loop in


# ---------------------------------------------------------------------------
# Reference implementation (ground truth; CPU path)
# ---------------------------------------------------------------------------

def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        mask: Optional[jax.Array] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain jnp attention with GQA. q: [B, H, S, D]; k/v: [B, Hk, S, D];
    `mask`, where given, [B, keys, queries]: the keys a query attends (a
    `Selection.mask`), every head alike; `window`, where given, the keys a
    causal query attends counted back from its own, itself included."""
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
        if window is not None:
            s = jnp.where(kj > qi + (k_len - q_len) - window, s, NEG_INF)
    if mask is not None:
        s = jnp.where(jnp.swapaxes(mask, -1, -2)[..., None, :, :] != 0, s,
                      NEG_INF)
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


def _k_band_bounds(r0, sub, rel, k_valid, window, *, chunk):
    """`_k_chunk_bounds` under a window of `window` keys a query, its own
    included (None: no window): of the key chunks [live_start, live_end)
    that are not dead — the earlier ones are older than the window of the
    tile's first query — those before `interior_start` are edge (the
    window's lower edge crosses them), those from `interior_end` on are edge
    as they were, and what lies between, if anything, is interior."""
    interior_end, live_end = _k_chunk_bounds(r0, sub, rel, k_valid,
                                             chunk=chunk)
    if window is None:
        return 0, 0, interior_end, live_end
    # query r keeps the keys above r + rel - window
    live_start = _clip((rel + r0 - window + 1) // chunk, 0, live_end)
    interior_start = _clip(-(-(rel + r0 + sub - window) // chunk),
                           live_start, live_end)
    return live_start, interior_start, interior_end, live_end


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
                  sub=_FWD_RECT[0], chunk=_FWD_RECT[1], window=None):
    """Count the score square's [chunk keys, sub queries] rectangles by
    class, as the forward and dq kernels walk it under `tile`-sized grid
    steps (the forward alone under a `window`, whose older rectangles are
    dead too): {"dead", "interior", "edge", "computed_share"}. A pure
    function of static shapes, on the bounds that the kernels' loops use."""
    block_q, block_k = min(tile, q_len), min(tile, k_len)
    sub, chunk = _divisor(block_q, sub), _divisor(block_k, chunk)
    n_chunks = -(-block_k // chunk)
    dead = interior = edge = 0
    for q0 in range(0, q_len, block_q):
        for k0 in range(0, k_len, block_k):
            rel = q0 + k_len - q_len - k0 if causal else None
            for r0 in range(0, block_q, sub):
                live_start, interior_start, interior_end, live_end = \
                    _k_band_bounds(r0, sub, rel, min(block_k, k_len - k0),
                                   window, chunk=chunk)
                if q0 + r0 >= q_len:
                    live_start = interior_start = interior_end = live_end = 0
                inside = max(interior_end - interior_start, 0)
                interior += inside
                edge += live_end - live_start - inside
                dead += n_chunks - (live_end - live_start)
    return {"dead": dead, "interior": interior, "edge": edge,
            "computed_share": (interior + edge) / (dead + interior + edge)}


def _edge_mask(k0, q0, shape, rel, q_valid, k_valid, low=None):
    """Validity of an edge rectangle of scores laid out [keys, queries]
    whose first key and query are the tile's k0-th and q0-th, or None if
    nothing masks. The in-bounds halves exist only at a ragged end (a
    `*_valid` that is not None, known at trace time); `low` is `rel` less a
    window's keys where the window's lower edge may cross the rectangle."""
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = None if rel is None else kj <= qi + rel
    if low is not None:
        mask = kj > qi + low if mask is None else mask & (kj > qi + low)
    for idx, valid in ((kj, k_valid), (qi, q_valid)):
        if valid is not None:
            mask = idx < valid if mask is None else mask & (idx < valid)
    return mask


# ---------------------------------------------------------------------------
# What the kernels share
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


def _span(start, size):
    """Index of [start, start + size), `start` a multiple of `size`: a slice
    where it is static, an aligned dynamic slice where it is traced."""
    if _static(start):
        return slice(start, start + size)
    return pl.ds(pl.multiple_of(start, size), size)


def _rows(ref, cols, start, size, valid=None):
    """Rows [start, start + size) of one head's columns `cols` of a [rows,
    lanes] block. Rows from `valid` on are zeroed if it is given: an
    out-of-bounds block read returns unspecified padding (NaN under the
    interpreter), and 0 * NaN would leak through the matmuls."""
    if isinstance(start, int):
        x = ref[start:start + size, cols]
    else:
        x = ref[pl.ds(pl.multiple_of(start, size), size), cols]
    if valid is None:
        return x
    rows = start + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    return jnp.where(rows < valid, x, jnp.zeros_like(x))


def _rows_t(ref, hh, start, size, valid=None):
    """The same rows of head `hh` of a [heads, head_dim, rows] block, as the
    [head_dim, size] tile they are stored as."""
    if isinstance(start, int):
        x = ref[hh, :, start:start + size]
    else:
        x = ref[hh, :, pl.ds(pl.multiple_of(start, size), size)]
    if valid is None:
        return x
    rows = start + jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)
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

    def __init__(self, q_len, k_len, block_q, block_k, causal, window=None):
        if window is not None and not (causal and window > 0):
            raise ValueError(f"a window of {window} keys: a causal query's "
                             "last keys, its own included, at least one")
        self.q_len, self.k_len, self.causal = q_len, k_len, causal
        # keys a query attends, counted back from its own (None: all)
        self.window = window
        self.block_q, self.block_k = min(block_q, q_len), min(block_k, k_len)
        self.nq = pl.cdiv(q_len, self.block_q)
        self.nk = pl.cdiv(k_len, self.block_k)
        self.off = k_len - q_len
        self.ragged_q = q_len % self.block_q != 0
        self.ragged_k = k_len % self.block_k != 0
        # every live tile lies on the diagonal or wholly below it
        self.aligned = (self.block_q == self.block_k
                        and self.off % self.block_k == 0)
        # grid steps along the keys of the forward pass: every k tile, or
        # under a window the most that a q tile's band holds
        self.steps_k = self.nk if window is None else max(
            self.last_live_k(i) - self.first_k(i) + 1
            for i in range(self.nq))

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

    def live(self, rel):
        """Whether the causal tile at `rel` computes anything: it is not
        above the diagonal, nor older than the window of its first query."""
        live = rel + self.block_q > 0
        if self.window is None:
            return live
        inside = rel < self.block_k + self.window - 1
        return live and inside if _static(rel) else live & inside

    def for_each_class(self, qb, kb, walk):
        """Call `walk(rel)` for the tile's class. A static place, or no
        mask at all, is one static walk. Aligned causal tiles are either
        on the diagonal or wholly interior, each a static walk under its
        `pl.when`, and under a window the one or two tiles that its lower
        edge crosses are a static walk each; dead tiles run nothing. Ragged
        or unaligned shapes walk with the traced `rel`."""
        if not self.causal:
            return walk(None)
        rel = qb * self.block_q + self.off - kb * self.block_k
        if _static(rel):
            return walk(rel) if self.live(rel) else None
        if not self.aligned or self.ragged_q or self.ragged_k:
            pl.when(self.live(rel))(lambda: walk(rel))
            return
        tile = self.block_k
        pl.when(rel == 0)(lambda: walk(0))
        if self.window is None:
            pl.when(rel >= tile)(lambda: walk(tile))
            return
        # the last query of a tile at `rel` keeps the keys above rel + tile
        # - 1 - window: whole tiles as far as `whole`, crossed ones beyond
        whole = (self.window - tile) // tile * tile
        if whole >= tile:
            pl.when((rel >= tile) & (rel <= whole))(lambda: walk(tile))
        for crossed in range(max(whole, 0) + tile, tile + self.window - 1,
                             tile):
            pl.when(rel == crossed)(functools.partial(walk, crossed))

    def last_live_k(self, i):
        """The last k tile that q tile `i` computes: dead steps after it
        name its block again, so that no copy is issued for them."""
        if not self.causal:
            return self.nk - 1
        return _clip((i * self.block_q + self.block_q - 1 + self.off)
                     // self.block_k, 0, self.nk - 1)

    def first_k(self, i):
        """The k tile of q tile `i`'s first forward grid step: the first, or
        under a window the first that is not older than the window of the
        tile's first query (the grid holds `steps_k` steps a q tile, not
        `nk`: a step before the band would cost what a live one costs to
        start, and most of a long row's would be such)."""
        if self.window is None:
            return 0
        return _clip((i * self.block_q + self.off - self.window + 1)
                     // self.block_k, 0, self.nk - 1)

    def live_k(self, i, j):
        """The k tile that forward step j of q tile `i` names: its own where
        the tile is live, else the last live one again."""
        if self.window is not None:
            j = self.first_k(i) + j
        return jnp.minimum(j, self.last_live_k(i))

    def first_live_q(self, j):
        """The first q tile that k tile `j` computes; dkv's dead steps come
        before it and name its block."""
        if not self.causal:
            return 0
        return jnp.clip((j * self.block_k - self.off) // self.block_q,
                        0, self.nq - 1)


class _Heads:
    """Where a call's heads lie in its arrays, and which of them a grid
    step holds. One set of kernel bodies serves the first two placements
    through it; the third is the walk under a choice of keys' own.

    Head-major, [B, H, S, Dh]: a grid step's block of q, k, v and their
    gradients is one head, [rows, Dh]. Sequence-major, [B, S, H * Dh] (the
    projections' own, at a head width under the 128 lanes): a block is
    [rows, 128 lanes], `per` = 128 // Dh heads side by side, and the bodies
    walk them one after the other, each over its own columns. Where H is not
    a multiple of `per` (gpt2_xl: 25 heads of 64) the last block holds fewer
    heads and the bodies skip the absent ones on the program id. Grouped
    (`grouped`, head-major arrays; `_attend_pallas`): a grid step is a KV
    head, its block of q and of the output the `per` = `group` query heads
    that attend it, [group, rows, Dh], against that head's one block of k
    and v — what the group shares is fetched once for it. The rows of
    statistics (lse, delta) and the transposed forward output are head-major
    in all three, `per` heads a block."""

    def __init__(self, q_shape, k_shape, seq_major, grouped=False):
        self.seq_major, self.grouped = seq_major, grouped
        if seq_major:
            self.batch, self.q_len, self.num, self.dim = q_shape
            self.k_len, self.num_kv = k_shape[1], k_shape[2]
        else:
            self.batch, self.num, self.q_len, self.dim = q_shape
            self.num_kv, self.k_len = k_shape[1], k_shape[2]
        self.group = self.num // self.num_kv
        self.lanes = self.dim
        if seq_major:
            assert seq_major_fits(q_shape, k_shape)
            self.lanes = min(128, self.num * self.dim)
        self.per = self.group if grouped else self.lanes // self.dim
        self.steps = pl.cdiv(self.num, self.per)

    def arrays(self, *xs):
        """The arrays as the BlockSpecs index them."""
        if not self.seq_major:
            return xs
        return tuple(x.reshape(*x.shape[:2], -1) for x in xs)

    def shape(self, length):
        """Of an array of `length` rows of every q head."""
        if self.seq_major:
            return (self.batch, length, self.num * self.dim)
        return (self.batch, self.num, length, self.dim)

    def spec(self, rows, row_block, kv=False):
        """`rows` rows of a grid step's heads of q, do, dq (or, with `kv`,
        of k and v under GQA); `row_block(i, j)` of the grid's last two
        indices is the block along the sequence."""
        if self.grouped:
            return pl.BlockSpec(
                (None, None if kv else self.per, rows, self.dim),
                lambda b, h, i, j, *_: (b, h, row_block(i, j), 0))
        head = (lambda h: h // self.group) if kv else (lambda h: h)
        if self.seq_major:
            return pl.BlockSpec(
                (None, rows, self.lanes),
                lambda b, h, i, j, *_: (b, row_block(i, j), head(h)))
        return pl.BlockSpec(
            (None, None, rows, self.dim),
            lambda b, h, i, j, *_: (b, head(h), row_block(i, j), 0))

    def stat_spec(self, rows, cols, index):
        """A [rows, cols] block a head of a head-major [B, H, *, *] array;
        `index(i, j)` gives the last two block indices."""
        return pl.BlockSpec((None, self.per, rows, cols),
                            lambda b, h, i, j, *_: (b, h, *index(i, j)))

    def cols(self, hh):
        """Columns of the block's `hh`-th head (and its rows of a
        transposed [per * Dh, n] accumulator)."""
        return slice(hh * self.dim, (hh + 1) * self.dim)

    def each(self, body):
        """A function that runs `body(hh)` for every head of the grid
        step's block, one after the other (`together`)."""
        def run(heads):
            for hh in heads:
                body(hh)
        return self.together(run)

    def together(self, body):
        """A function that runs `body(heads)` on the range of heads the
        grid step's block holds. Called at the kernel's top level: the
        program id is read there, not inside a conditional. Where the last
        block holds fewer heads, it and the full blocks are two
        straight-line regions (a conditional around each head would keep
        the scheduler from overlapping one head's code with the next's in
        every block)."""
        def run(heads=self.per):
            body(range(heads))

        rest = self.num % self.per
        if rest == 0:
            return run
        block, last = pl.program_id(1), self.steps - 1

        def split():
            pl.when(block < last)(run)
            pl.when(block == last)(functools.partial(run, rest))
        return split


def seq_major_fits(q_shape, k_shape):
    """Whether the kernels read [B, S, H, Dh] arrays as they are: where the
    head width divides the 128 lanes and there is no GQA group (two q heads
    of a block would want different columns of one k block). A width that
    fills the lanes is stored and multiplied at full lanes in either layout,
    and its [rows, 128] blocks of [B, S, H * Dh] are 256-byte pieces to
    fetch where a head-major block is one run: on the v5e the kernels were
    a quarter slower so ([5, 16, 4096, 128], PERF.md PR 31)."""
    dim = q_shape[-1]
    return dim < 128 and 128 % dim == 0 and q_shape[2] == k_shape[2]


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _scores(k_ref, cols, q, r0, c, chunk, rel, q_valid=None, k_valid=None,
            edge=False, low=None):
    """(st, mask): the float32 scores [keys, queries] of the tile's `c`-th
    chunk of keys against the scaled queries `q`, the tile's from `r0` on,
    NEG_INF where an edge rectangle's mask drops them, and that mask (None
    where nothing masks). `low`: `_edge_mask`'s."""
    st = _dot_nt(_rows(k_ref, cols, c * chunk, chunk,
                       k_valid if edge else None), q)
    mask = _edge_mask(c * chunk, r0, st.shape, rel, q_valid,
                      k_valid, low) if edge else None
    if mask is not None:
        st = jnp.where(mask, st, NEG_INF)
    return st, mask


@functools.partial(jax.jit, static_argnames="v_transposed")
def _online_softmax(carry, st, v, mask=None, v_transposed=False):
    """The running (m, l, acc) of a block of queries — [1, n] rows and the
    transposed [head_dim, n] accumulator — after one rectangle of scores
    `st` and its values `v` ([keys, head_dim], or [head_dim, keys] with
    `v_transposed`). p is cast to v's dtype for p.v, the rest is float32.
    `mask` is given only where a query can have no key at all: m is NEG_INF
    there and exp(st - m) is exp(0). Jitted for the trace's sake, as
    `_fwd_unrolled` is: a walk's first trace calls it once a rectangle, and
    as bare `jnp` calls that was half of that trace."""
    m_prev, l_prev, acc = carry
    m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pt = jnp.exp(st - m_new)
    if mask is not None:
        pt = jnp.where(mask, pt, 0.0)
    p = pt.astype(v.dtype)
    pv = _dot_nn(v, p) if v_transposed else _dot_tn(v, p)
    return (m_new, l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True),
            acc * alpha + pv)


@functools.partial(jax.jit, static_argnames=(
    "rel", "heads", "dim", "scale", "sub", "chunk", "keyless", "window"))
def _fwd_unrolled(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, vt_refs, *,
                  rel, heads, dim, scale, sub, chunk, keyless, window=None):
    """The forward walk of a whole tile whose place is static (`rel`), for
    the `heads` of the block, each `dim` wide: straight-line code.

    It is one list of the rectangles of all those heads, and the scores of
    the next `_FWD_AHEAD` rectangles are made before a rectangle's softmax
    and its p.v. The matrix units take their instructions in program order:
    p.v, whose stationary operand is the softmax's last result, otherwise
    holds the next scores behind it while the vector slots work, and the
    vector slots then wait for those scores (PERF.md PR 45).

    With `vt_refs` (a scratch a head of the block, [head_dim, block_k]) v^T
    of the k tile is written there once a head and p.v streams it from
    there; without, p.v contracts v's rows and the matrix unit makes v^T a
    rectangle (`_fwd_pallas` says where which).

    Under a `window` a block of queries starts its walk at the first chunk
    the window leaves it, and a rectangle's mask holds the half that crosses
    it: the diagonal's, the window's lower edge's, or both. A query that the
    lower edge leaves no key of its first rectangle keeps a maximum of
    NEG_INF there, and what exp(0) then adds to its sum and accumulator is
    multiplied by exp(NEG_INF - m), exactly 0, at its first kept key: its
    own key at the latest.

    Jitted, on the block's refs, for the trace's sake and not the
    program's: the walk is 36 rectangles a head at 1024 positions, a
    hundred a head of a long row's two tile classes, and every program that
    holds the kernel traces its body anew — the served cells' sixteen bucket
    programs paid 5.7 s of `setup_s` for that. The trace caches this
    function by the refs' shapes and the static place, so a process traces
    a walk once a tile shape; the kernel's lowering inlines it."""
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    # (scores' arguments, head, queries' slice, chunk, first, last)
    rects = []
    for hh in heads:
        cols = slice(hh * dim, (hh + 1) * dim)
        if vt_refs:
            vt_refs[hh][...] = v_ref[:, cols].T
        for r0 in range(0, block_q, sub):
            live_start, interior_start, interior_end, live_end = \
                _k_band_bounds(r0, sub, rel, block_k, window, chunk=chunk)
            q = _scaled(q_ref[r0:r0 + sub, cols], scale)
            for c in range(live_start, live_end):
                diagonal, lower = c >= interior_end, c < interior_start
                rects.append((
                    (k_ref, cols, q, r0, c, chunk, rel if diagonal else None,
                     None, None, diagonal or lower,
                     rel - window if lower else None),
                    hh, slice(r0, r0 + sub), c, c == live_start,
                    c == live_end - 1))

    made = [_scores(*args) for args, *_ in rects[:_FWD_AHEAD]]
    for i, ((_, cols, *_), hh, rs, c, first, last) in enumerate(rects):
        if i + _FWD_AHEAD < len(rects):
            made.append(_scores(*rects[i + _FWD_AHEAD][0]))
        row, keys = slice(hh, hh + 1), slice(c * chunk, (c + 1) * chunk)
        if first:
            carry = m_ref[row, rs], l_ref[row, rs], acc_ref[cols, rs]
        st, mask = made.pop(0)
        carry = _online_softmax(
            carry, st, vt_refs[hh][:, keys] if vt_refs else v_ref[keys, cols],
            mask if keyless else None, v_transposed=bool(vt_refs))
        if last:
            m_ref[row, rs], l_ref[row, rs], acc_ref[cols, rs] = carry


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *vt_refs, scale, t, heads, sub, chunk, transposed_out):
    """One (q tile, k tile) step of the forward pass, for the heads of the
    block.

    Scores are laid out [keys, queries]. Each `sub` queries of the q tile
    walk their live `chunk`s of keys with online softmax; the running
    maximum and sum ([1, sub]) and the transposed accumulator ([head_dim,
    sub]) ride the walk in registers and rest in VMEM scratch between k
    tiles. Dead chunks are not visited, only edge chunks build a mask, and
    the select after the exp exists only where a query can have no key at
    all. q is scaled once per sub-block. A tile whose place is static walks
    unrolled, its scores made ahead (`_fwd_unrolled`); a ragged or unaligned
    one by loops, a sub-block after the other, the products in place. Under
    a window (`t.window`) the chunks older than it are dead too, its lower
    edge is a second kind of edge, walked first, and a q tile's grid steps
    start at its band's first k tile (`_Tiling.first_k`).
    Matmul operands are in the inputs' dtype (p cast to it); scores, exp,
    m, l, the accumulator and lse are float32. With `transposed_out` the
    output block is written as it was accumulated, [head_dim, queries].
    """
    qb, step = t.ids(2, 3)      # the q tile, and its step along the keys
    kb = step if t.window is None else t.first_k(qb) + step
    q_valid, k_valid = t.valid(qb, kb)
    keyless = t.causal and t.off < 0      # queries before the first key

    @_when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def walk(rel, hhs):
        if _static(rel) and k_valid is None:
            return _fwd_unrolled(
                q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, vt_refs,
                rel=rel, heads=tuple(hhs), dim=heads.dim, scale=scale,
                sub=sub, chunk=chunk, keyless=keyless, window=t.window)
        low = None if t.window is None else rel - t.window
        for hh in hhs:
            cols, row = heads.cols(hh), slice(hh, hh + 1)
            if vt_refs:
                vt_refs[hh][...] = _rows(v_ref, cols, 0, t.block_k, k_valid).T
            for r0 in range(0, t.block_q, sub):
                rs = slice(r0, r0 + sub)
                live_start, interior_start, interior_end, live_end = \
                    _k_band_bounds(r0, sub, rel, t.span(q_valid, k_valid)[1],
                                   t.window, chunk=chunk)
                q = _scaled(q_ref[rs, cols], scale)

                def step(c, carry, edge):
                    st, mask = _scores(k_ref, cols, q, r0, c, chunk, rel,
                                       q_valid, k_valid, edge, low)
                    if vt_refs:
                        v = vt_refs[hh][:, _span(c * chunk, chunk)]
                    else:
                        v = _rows(v_ref, cols, c * chunk, chunk,
                                  k_valid if edge else None)
                    return _online_softmax(
                        carry, st, v, mask if keyless else None,
                        v_transposed=bool(vt_refs))

                carry = m_ref[row, rs], l_ref[row, rs], acc_ref[cols, rs]
                if t.window is not None:
                    # the window's lower edge comes first
                    carry = _walk((live_start, live_start, interior_start),
                                  step, carry)
                    interior_end = _clip(interior_end, interior_start,
                                         live_end)
                m_ref[row, rs], l_ref[row, rs], acc_ref[cols, rs] = _walk(
                    (interior_start, interior_end, live_end), step, carry)

    heads.together(lambda hhs: t.for_each_class(
        qb, kb, functools.partial(walk, hhs=hhs)))()

    def write(hh):
        cols, row = heads.cols(hh), slice(hh, hh + 1)
        l = jnp.where(l_ref[row] == 0.0, 1.0, l_ref[row])
        o = acc_ref[cols] / l                   # [head_dim, block_q]
        if transposed_out:
            o_ref[hh] = o.astype(o_ref.dtype)
        else:
            o_ref[:, cols] = o.T.astype(o_ref.dtype)
        lse_ref[hh] = m_ref[row] + jnp.log(l)

    _when(step == t.steps_k - 1)(heads.each(write))


def _fwd_pallas(q, k, v, *, scale, causal, block_q, block_k, interpret,
                transposed_out=False, seq_major=False, window=None):
    """q, k, v: [B, H, S, D] or, with `seq_major`, [B, S, H, D]. Returns
    (out in q's layout and dtype, lse [B, H, S] float32); with
    `transposed_out`, out is [B, H, D, S] in both layouts. With `window` a
    query attends its last `window` keys, its own included, and the call is
    named `flash_fwd_window`: the same body over the band's rectangles."""
    heads = _Heads(q.shape, k.shape, seq_major)
    t = _Tiling(heads.q_len, heads.k_len, block_q, block_k, causal, window)
    sub, chunk = _rect(t.block_q, t.block_k, heads.dim, _FWD_RECT)
    # v^T scratches, one a head of the block: a v narrower than the lanes
    # streams its transpose out of the matrix unit at half rate, 8 rows an
    # instruction, again for every rectangle; at full lanes the unit keeps
    # pace and the scratch costs more than it saves (PERF.md PR 45)
    staged = heads.per if heads.dim < 128 else 0

    q_spec = heads.spec(t.block_q, lambda i, j: i)
    kv_spec = heads.spec(t.block_k, t.live_k, kv=True)
    out_spec, out_shape = q_spec, heads.shape(t.q_len)
    if transposed_out:
        out_spec = heads.stat_spec(heads.dim, t.block_q, lambda i, j: (0, i))
        out_shape = (heads.batch, heads.num, heads.dim, t.q_len)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, t=t, heads=heads,
                          sub=sub, chunk=chunk,
                          transposed_out=transposed_out),
        grid=(heads.batch, heads.steps, t.nq, t.steps_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            out_spec,
            heads.stat_spec(1, t.block_q, lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, q.dtype),
            jax.ShapeDtypeStruct((heads.batch, heads.num, 1, t.q_len),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads.lanes, t.block_q), jnp.float32),
            pltpu.VMEM((heads.per, t.block_q), jnp.float32),
            pltpu.VMEM((heads.per, t.block_q), jnp.float32),
            *[pltpu.VMEM((heads.dim, t.block_k), q.dtype)] * staged,
        ],
        interpret=interpret,
        name="flash_fwd" if window is None else "flash_fwd_window",
    )(*heads.arrays(q, k, v))
    if not transposed_out:
        out = out.reshape(q.shape)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# The forward walk under a choice of keys (`dsa_attend_fwd`)
# ---------------------------------------------------------------------------

# Where a query's running maximum starts under a choice: far above the
# NEG_INF that a dropped key's score becomes, so that exp(score - maximum) of
# a dropped key is exactly 0 whether or not the query has a kept key yet, and
# far below any real score, so that the first kept key takes the maximum over.
_NO_KEY_YET = -1e20


def _live_tiles(selection, t):
    """[B x q tiles x k tiles] int32: the pairs a `Selection` chooses in
    each tile of the grid (all ones where its counts' tiles do not divide
    the grid's: nothing is skipped then)."""
    b, rows, s = selection.counts.shape
    per = s // rows
    if t.block_k % per or s % t.block_k or s % t.block_q:
        return jnp.ones((b * t.nq * t.nk,), jnp.int32)
    tiles = selection.counts.reshape(b, t.nk, t.block_k // per, t.nq,
                                     t.block_q).sum((2, 4))
    return jnp.swapaxes(tiles, 1, 2).reshape(-1)


@functools.partial(jax.jit, static_argnames=("rel", "scale", "sub", "chunk"))
def _attend_unrolled(hh, q_ref, k_ref, v_ref, bias_ref, acc_ref, m_ref, l_ref,
                     *, rel, scale, sub, chunk):
    """The walk of a whole tile whose place is static (`rel`) for the
    block's `hh`-th query head (traced: the heads are a loop around this,
    so that the straight-line code is one head's and not the group's):
    `_fwd_unrolled`'s order, the scores of the next `_FWD_AHEAD` rectangles
    made before a rectangle's softmax and its p.v. What the choice costs a
    rectangle is one addition a score: `bias_ref` holds 0 for a chosen key
    and NEG_INF for any other, and neither the scores nor p are selected on
    (`_NO_KEY_YET`). Jitted on the refs for the trace's sake, as
    `_fwd_unrolled` is."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[0]
    rects = []      # (scaled queries, their slice, chunk, the slice's last)
    for r0 in range(0, block_q, sub):
        _, live_end = _k_chunk_bounds(r0, sub, rel, block_k, chunk=chunk)
        q = _scaled(q_ref[hh, r0:r0 + sub, :], scale)
        rects += [(q, slice(r0, r0 + sub), c, c == live_end - 1)
                  for c in range(live_end)]

    def scores(q, rs, c, _last):
        keys = slice(c * chunk, (c + 1) * chunk)
        return _dot_nt(k_ref[keys, :], q) + bias_ref[keys, rs]

    made = [scores(*rect) for rect in rects[:_FWD_AHEAD]]
    for i, (_, rs, c, last) in enumerate(rects):
        if i + _FWD_AHEAD < len(rects):
            made.append(scores(*rects[i + _FWD_AHEAD]))
        if c == 0:
            carry = m_ref[hh, :, rs], l_ref[hh, :, rs], acc_ref[hh, :, rs]
        carry = _online_softmax(carry, made.pop(0),
                                v_ref[c * chunk:(c + 1) * chunk, :])
        if last:
            m_ref[hh, :, rs], l_ref[hh, :, rs], acc_ref[hh, :, rs] = carry


def _attend_kernel(live_ref, q_ref, k_ref, v_ref, sel_ref, o_ref, acc_ref,
                   m_ref, l_ref, bias_ref, *, scale, t, group, sub, chunk):
    """One (q tile, k tile) step of the forward pass under a choice of keys,
    for the `group` query heads of one KV head: q_ref and o_ref [group,
    block_q, head_dim], k_ref and v_ref that head's [block_k, head_dim],
    sel_ref the tile's [keys, queries] block of the `Selection.mask`, which
    every head of the group reads.

    The choice is decoded once a step for all of them: sel_ref's int8 becomes
    a float32 bias in VMEM, 0 where the key is chosen and NEG_INF where not,
    and a head's scores of a rectangle are k.q plus the bias's rectangle. A
    query's running maximum starts at `_NO_KEY_YET`, so what a dropped key
    adds to its sum and accumulator is exp of about NEG_INF, exactly 0, and
    a kept key's p is what the masked dense form gives; a query with no
    chosen key at all ends with l = 0 and an output of 0. The heads are a
    loop, a head's walk straight-line (`_attend_unrolled`); the statistics
    and the transposed accumulator, a head each, rest in VMEM scratch between
    k tiles as `_fwd_kernel`'s do. The choice is causal by itself and the
    tiles are whole and aligned, so a live tile is on the diagonal or wholly
    below it; a tile in which no key is chosen (`live_ref`, [batch x q tiles
    x k tiles] in SMEM: the pairs chosen a tile) is not walked."""
    qb, kb = t.ids(2, 3)
    chosen = live_ref[(pl.program_id(0) * t.nq + qb) * t.nk + kb]

    @_when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NO_KEY_YET)
        l_ref[:] = jnp.zeros_like(l_ref)

    def walk(rel):
        @pl.when(chosen > 0)
        def _walk_live():
            def decode(c, _):
                keys = _span(c * chunk, chunk)
                bias_ref[keys, :] = jnp.where(
                    sel_ref[keys, :].astype(jnp.int32) != 0, 0.0, NEG_INF)
                return 0

            jax.lax.fori_loop(0, t.block_k // chunk, decode, 0)

            def head(hh, _):
                _attend_unrolled(hh, q_ref, k_ref, v_ref, bias_ref, acc_ref,
                                 m_ref, l_ref, rel=rel, scale=scale, sub=sub,
                                 chunk=chunk)
                return 0

            jax.lax.fori_loop(0, group, head, 0)

    t.for_each_class(qb, kb, walk)

    @_when(kb == t.nk - 1)
    def _write():
        def head(hh, _):
            l = jnp.where(l_ref[hh] == 0.0, 1.0, l_ref[hh])
            o_ref[hh] = (acc_ref[hh] / l).T.astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, group, head, 0)


def _attend_pallas(q, k, v, selection, *, scale, causal, block_q, block_k,
                   interpret):
    """Causal attention of q [B, H, S, D] over the keys that `selection`
    (`ops.sparse_index.Selection`, queries and keys of one sequence of whole
    tiles, as many keys as queries a tile) chooses for each query, every
    head alike: the kernel `dsa_attend_fwd`. Forward only: it keeps no lse.

    The grid's head axis runs over the KV heads (`_Heads`' group placement):
    a step holds the query heads that share a k / v block and the choice's
    block, so both are fetched once a group and the choice is decoded once
    for it (`_attend_kernel`)."""
    heads = _Heads(q.shape, k.shape, False, grouped=True)
    t = _Tiling(heads.q_len, heads.k_len, block_q, block_k, causal)
    if not causal or t.off or t.ragged_q or t.ragged_k or not t.aligned:
        raise NotImplementedError(
            "attention over a choice of keys: one causal sequence of whole "
            f"tiles, as many keys as queries a tile, got {heads.q_len} "
            f"queries, {heads.k_len} keys under tiles of {t.block_q} x "
            f"{t.block_k}")
    sub, chunk = _rect(t.block_q, t.block_k, heads.dim, _FWD_RECT)
    live_k = lambda i, j: jnp.minimum(j, t.last_live_k(i))
    q_spec = heads.spec(t.block_q, lambda i, j: i)
    kv_spec = heads.spec(t.block_k, live_k, kv=True)
    stat = pltpu.VMEM((heads.group, 1, t.block_q), jnp.float32)
    # two buffers of every block; a head's accumulator and its two rows of
    # statistics (a row is stored as eight); the bias
    size = jnp.dtype(q.dtype).itemsize
    vmem = (2 * ((2 * heads.group * t.block_q + 2 * t.block_k) * heads.dim
                 * size + t.block_k * t.block_q)
            + heads.group * (heads.dim + 16) * t.block_q * 4
            + t.block_k * t.block_q * 4)
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, t=t, group=heads.group,
                          sub=sub, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads.batch, heads.steps, t.nq, t.nk),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                # the choice's block of a grid step, [keys, queries] as the
                # scores are laid out; dead steps name the row's last live
                # block again
                pl.BlockSpec((None, t.block_k, t.block_q),
                             lambda b, h, i, j, *_: (b, live_k(i, j), i)),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((heads.group, heads.dim, t.block_q), jnp.float32),
                stat, stat,
                pltpu.VMEM((t.block_k, t.block_q), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem + vmem // 4, _VMEM_UNASKED)),
        interpret=interpret,
        name="dsa_attend_fwd",
    )(_live_tiles(selection, t), q, k, v, selection.mask)


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style, lse + delta residuals)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, t, heads, sub, chunk, do_t):
    """One (q tile, k tile) step of dq, walked as the forward pass is:
    p = exp(s - lse) and ds = p * (dp - delta) per [chunk keys, sub
    queries] rectangle, dq^T ([head_dim, sub]) accumulated in registers
    and scaled once at the end, when the block's heads are written with one
    rounding. lse and delta are [1, sub] rows. Matmul operands in the
    inputs' dtype (ds cast to it), the rest float32."""
    qb, kb = t.ids(2, 3)
    q_valid, k_valid = t.valid(qb, kb)
    dtype = q_ref.dtype

    @_when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def walk(rel, hh):
        cols = heads.cols(hh)
        for r0 in range(0, t.block_q, sub):
            rs = slice(r0, r0 + sub)
            interior_end, live_end = _k_chunk_bounds(
                r0, sub, rel, t.span(q_valid, k_valid)[1], chunk=chunk)
            q = _scaled(q_ref[rs, cols], scale)
            do = do_ref[hh, :, rs] if do_t else do_ref[rs, cols]
            lse = lse_ref[hh, :, rs]
            delta = delta_ref[hh, :, rs]

            def step(c, acc, edge):
                pad = k_valid if edge else None
                k = _rows(k_ref, cols, c * chunk, chunk, pad)
                v = _rows(v_ref, cols, c * chunk, chunk, pad)
                pt = jnp.exp(_dot_nt(k, q) - lse)
                mask = _edge_mask(c * chunk, r0, pt.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    # also: padded queries carry garbage lse
                    pt = jnp.where(mask, pt, 0.0)
                dp = _dot_nn(v, do) if do_t else _dot_nt(v, do)
                dst = pt * (dp - delta)
                return acc + _dot_tn(k, dst.astype(dtype))

            acc_ref[cols, rs] = _walk((0, interior_end, live_end), step,
                                      acc_ref[cols, rs])

    heads.each(lambda hh: t.for_each_class(
        qb, kb, functools.partial(walk, hh=hh)))()

    @_when(kb == t.nk - 1)
    def _finalize():
        dq_ref[:] = (acc_ref[:] * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, t, heads, sub,
                    chunk, do_t):
    """One (k tile, q tile) step of dk and dv: each `sub` keys of the k
    tile walk their live `chunk`s of queries, scores again [keys, queries],
    dk and dv ([sub, head_dim]) accumulated in registers. k is scaled once
    per sub-block for the scores and dk once at the end, when the block's
    heads are written with one rounding. lse and delta arrive whole, as
    [chunks, chunk] rows, so that the walk indexes them on the sublane
    axis. Matmul operands in the inputs' dtype (p and ds cast to it), the
    rest float32. With `do_t`, dO comes [head_dim, queries] a head."""
    qb, kb = t.ids(3, 2)
    q_valid, k_valid = t.valid(qb, kb)
    n_chunks = t.block_q // chunk
    dtype = q_ref.dtype

    @_when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def walk(rel, hh):
        cols = heads.cols(hh)
        for r0 in range(0, t.block_k, sub):
            rs = slice(r0, r0 + sub)
            live_start, interior_start, interior_end, live_end = \
                _q_chunk_bounds(r0, sub, rel, *t.span(q_valid, k_valid),
                                chunk=chunk)
            k = _scaled(_rows(k_ref, cols, r0, sub, k_valid), scale)
            v = _rows(v_ref, cols, r0, sub, k_valid)

            def step(c, carry, edge):
                dk, dv = carry
                pad = q_valid if edge else None
                q = _rows(q_ref, cols, c * chunk, chunk, pad)
                if do_t:
                    do = _rows_t(do_ref, hh, c * chunk, chunk, pad)
                else:
                    do = _rows(do_ref, cols, c * chunk, chunk, pad)
                row = qb * n_chunks + c
                row = slice(row, row + 1) if _static(row) else pl.ds(row, 1)
                lse = lse_ref[hh, row, :]
                delta = delta_ref[hh, row, :]
                pt = jnp.exp(_dot_nt(k, q) - lse)
                mask = _edge_mask(r0, c * chunk, pt.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    pt = jnp.where(mask, pt, 0.0)
                p = pt.astype(dtype)
                dv = dv + (_dot_nt(p, do) if do_t else _dot_nn(p, do))
                dp = _dot_nn(v, do) if do_t else _dot_nt(v, do)
                dst = pt * (dp - delta)
                return dk + _dot_nn(dst.astype(dtype), q), dv

            # the diagonal's edge chunks come first, a ragged end's last
            carry = _walk((live_start, live_start, interior_start), step,
                          (dk_acc[rs, cols], dv_acc[rs, cols]))
            dk_acc[rs, cols], dv_acc[rs, cols] = _walk(
                (interior_start, interior_end, live_end), step, carry)

    heads.each(lambda hh: t.for_each_class(
        qb, kb, functools.partial(walk, hh=hh)))()

    @_when(qb == t.nq - 1)
    def _finalize():
        dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *kt_refs, scale, t,
                heads, sub, chunk, do_t):
    """One (k tile, q tile) step of all three gradients, walked as
    `_bwd_dkv_kernel` walks: each `sub` keys of the k tile walk their live
    `chunk`s of queries, and a rectangle's p = exp(s - lse) and ds = p *
    (dp - delta) are made once and feed three products. dk and dv ([sub,
    head_dim]) ride the walk; dq^T of the rectangle ([head_dim, chunk], from
    a k^T that is made once a sub-block and kept in VMEM, a scratch a head
    of the block, the same [head_dim, block_k] array in both layouts: the
    compiler would remake it a rectangle) is added into the row's float32
    accumulator, [q tiles, head_dim, block_q] in VMEM, which stays across
    the row's k tiles. In an unrolled walk that product is issued one
    rectangle late, after the next rectangle's own four: ds is its
    stationary operand, and placed right behind the products that make ds
    it stalls the matrix units (a third of the schedule, PERF.md PR 38).
    dq's output block is the whole row, a q tile of it written (scaled,
    one rounding) at the last k tile. Operands, scale folding, lse and
    delta rows and `do_t` as in the pair."""
    qb, kb = t.ids(3, 2)
    q_valid, k_valid = t.valid(qb, kb)
    n_chunks = t.block_q // chunk
    dtype = q_ref.dtype

    @_when(kb == 0)
    def _init_dq():
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    @_when(qb == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def walk(rel, hh):
        cols = heads.cols(hh)
        for r0 in range(0, t.block_k, sub):
            rs = slice(r0, r0 + sub)
            live_start, interior_start, interior_end, live_end = bounds = \
                _q_chunk_bounds(r0, sub, rel, *t.span(q_valid, k_valid),
                                chunk=chunk)
            late = _static(*bounds)
            k = _rows(k_ref, cols, r0, sub, k_valid)
            k_scaled = _scaled(k, scale)
            kt_refs[hh][:, rs] = k.T
            v = _rows(v_ref, cols, r0, sub, k_valid)

            def add_dq(dst, c):
                dq_acc[qb, cols, _span(c * chunk, chunk)] += _dot_nn(
                    kt_refs[hh][:, rs], dst)

            def step(c, carry, edge):
                dk, dv, pending = carry
                pad = q_valid if edge else None
                q = _rows(q_ref, cols, c * chunk, chunk, pad)
                if do_t:
                    do = _rows_t(do_ref, hh, c * chunk, chunk, pad)
                else:
                    do = _rows(do_ref, cols, c * chunk, chunk, pad)
                row = qb * n_chunks + c
                row = slice(row, row + 1) if _static(row) else pl.ds(row, 1)
                lse = lse_ref[hh, row, :]
                delta = delta_ref[hh, row, :]
                pt = jnp.exp(_dot_nt(k_scaled, q) - lse)
                mask = _edge_mask(r0, c * chunk, pt.shape, rel, q_valid,
                                  k_valid) if edge else None
                if mask is not None:
                    pt = jnp.where(mask, pt, 0.0)
                p = pt.astype(dtype)
                dv = dv + (_dot_nt(p, do) if do_t else _dot_nn(p, do))
                dp = _dot_nn(v, do) if do_t else _dot_nt(v, do)
                dst = (pt * (dp - delta)).astype(dtype)
                dk = dk + _dot_nn(dst, q)
                if pending is not None:
                    add_dq(*pending)
                if late:
                    return dk, dv, (dst, c)
                add_dq(dst, c)
                return dk, dv, None

            # the diagonal's edge chunks come first, a ragged end's last
            carry = _walk((live_start, live_start, interior_start), step,
                          (dk_acc[rs, cols], dv_acc[rs, cols], None))
            dk_acc[rs, cols], dv_acc[rs, cols], pending = _walk(
                (interior_start, interior_end, live_end), step, carry)
            if pending is not None:
                add_dq(*pending)

    heads.each(lambda hh: t.for_each_class(
        qb, kb, functools.partial(walk, hh=hh)))()

    @_when(kb == t.nk - 1)
    def _finalize_dq():
        dq_ref[_span(qb * t.block_q, t.block_q), :] = (
            dq_acc[qb] * scale).T.astype(dq_ref.dtype)

    @_when(qb == t.nq - 1)
    def _finalize_dkv():
        dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


# What the compiler gives a call's blocks and scratch where the call states no
# limit (the v5e's; a newer chip's is larger, and a need between the two that
# is stated there asks for nothing new).
_VMEM_UNASKED = 16 * 2 ** 20


def _vmem_capacity():
    """A core's VMEM on the device the call is traced for, by Pallas's table
    of chips (`get_tpu_info`: the mesh's described device, else the default
    one). Where the table knows neither (the interpreter; a compile for a
    described chip on a host without one) the v5e's, the chip this repo's
    compiles describe."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 128 * 2 ** 20


def _fused_vmem_bytes(t, heads, dtype, dq_dtype, dkv_dtype):
    """VMEM that `_bwd_kernel` needs at this shape: a block of every operand
    and result twice (the pipeline's two buffers), the three float32
    accumulators, dq's and its output block a whole row long, and the k
    tile's transpose."""
    size = lambda dt: jnp.dtype(dt).itemsize
    row = t.nq * t.block_q
    blocks = ((2 * t.block_q + 2 * t.block_k) * heads.lanes * size(dtype)
              + 2 * heads.per * row * 4                        # lse, delta
              + row * heads.lanes * size(dq_dtype)
              + 2 * t.block_k * heads.lanes * size(dkv_dtype))
    held = (2 * blocks + (row + 2 * t.block_k) * heads.lanes * 4
            + t.block_k * heads.lanes * size(dtype))
    return held + held // 8      # and room for the compiler's own spills


def _bwd_pallas(q, k, v, lse, do, delta, *, scale, causal, block_q, block_k,
                interpret, keep_f32=False, seq_major=False, do_t=False):
    """q, k, v, do: [B, H, S, D] or, with `seq_major`, [B, S, H, D]; with
    `do_t`, do is [B, H, D, S] in both, as the forward pass's transposed
    output is. lse and delta = rowsum(dO * O): [B, H, S] float32. dq, dk, dv
    come back in the inputs' layout, rounded once from the kernels' float32
    accumulators to the inputs' dtype (left float32 with `keep_f32`).

    One kernel, `flash_bwd`, wherever a row's dq accumulator fits in VMEM
    beside the blocks (`_fused_vmem_bytes`); the pair `flash_bwd_dq` +
    `flash_bwd_dkv`, which makes every rectangle's p and ds twice, for
    longer rows."""
    heads = _Heads(q.shape, k.shape, seq_major)
    t = _Tiling(heads.q_len, heads.k_len, block_q, block_k, causal)
    q3, k3, v3 = heads.arrays(q, k, v)
    do3 = do if do_t else heads.arrays(do)[0]
    # dk, dv are accumulated per q-head; a GQA group is reduced outside, in
    # float32
    summed = heads.group > 1
    dq_out = jax.ShapeDtypeStruct(
        q3.shape, jnp.float32 if keep_f32 else q.dtype)
    dkv_out = jax.ShapeDtypeStruct(
        heads.shape(t.k_len), jnp.float32 if keep_f32 or summed else k.dtype)
    vmem = _fused_vmem_bytes(t, heads, q.dtype, dq_out.dtype, dkv_out.dtype)
    # half of the core's: the model above is a count of buffers, not the
    # compiler's allocation, and a call that does not fit fails to compile
    fused = vmem <= _vmem_capacity() // 2
    body = dict(scale=scale, t=t, heads=heads, do_t=do_t)

    if not fused:
        q_spec = heads.spec(t.block_q, lambda i, j: i)
        kv_spec = heads.spec(
            t.block_k, lambda i, j: jnp.minimum(j, t.last_live_k(i)),
            kv=True)
        row_spec = heads.stat_spec(1, t.block_q, lambda i, j: (0, i))
        do_spec = q_spec
        if do_t:
            do_spec = heads.stat_spec(heads.dim, t.block_q,
                                      lambda i, j: (0, i))
        sub, chunk = _rect(t.block_q, t.block_k, heads.dim, _DQ_RECT)
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, sub=sub, chunk=chunk, **body),
            grid=(heads.batch, heads.steps, t.nq, t.nk),
            in_specs=[q_spec, kv_spec, kv_spec, do_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=dq_out,
            scratch_shapes=[pltpu.VMEM((heads.lanes, t.block_q),
                                       jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq",
        )(q3, k3, v3, do3, lse[:, :, None], delta[:, :, None])

    # dk/dv and the fused kernel: kv block is the outer grid axis, q blocks
    # stream innermost.
    sub, chunk = _rect(t.block_k, t.block_q, heads.dim,
                       _BWD_RECT if fused else _DKV_RECT)
    rows = t.nq * t.block_q // chunk

    def chunked(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, rows * chunk - t.q_len)))
        return x.reshape(heads.batch, heads.num, rows, chunk)

    live_q = lambda j, i: jnp.maximum(i, t.first_live_q(j))
    q_spec_i = do_spec_i = heads.spec(t.block_q, live_q)
    if do_t:
        do_spec_i = heads.stat_spec(heads.dim, t.block_q,
                                    lambda j, i: (0, live_q(j, i)))
    kv_spec_i = heads.spec(t.block_k, lambda j, i: j, kv=True)
    row_spec_i = heads.stat_spec(rows, chunk, lambda j, i: (0, 0))
    kv_out_spec = heads.spec(t.block_k, lambda j, i: j)
    kv_acc = pltpu.VMEM((t.block_k, heads.lanes), jnp.float32)
    call = dict(
        grid=(heads.batch, heads.steps, t.nk, t.nq),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, do_spec_i, row_spec_i,
                  row_spec_i],
        interpret=interpret)
    operands = (q3, k3, v3, do3, chunked(lse), chunked(delta))
    if fused:
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, sub=sub, chunk=chunk, **body),
            out_specs=[heads.spec(t.nq * t.block_q, lambda j, i: 0),
                       kv_out_spec, kv_out_spec],
            out_shape=[dq_out, dkv_out, dkv_out],
            scratch_shapes=[
                pltpu.VMEM((t.nq, heads.lanes, t.block_q), jnp.float32),
                kv_acc, kv_acc,
                *[pltpu.VMEM((heads.dim, t.block_k), q.dtype)] * heads.per],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem if vmem > _VMEM_UNASKED else None),
            name="flash_bwd", **call,
        )(*operands)
    else:
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, sub=sub, chunk=chunk, **body),
            out_specs=[kv_out_spec, kv_out_spec],
            out_shape=[dkv_out, dkv_out],
            scratch_shapes=[kv_acc, kv_acc],
            name="flash_bwd_dkv", **call,
        )(*operands)

    if summed:
        # [.., Hk, G, ..]: the group is the minor part of the head axis
        axis = 2 if seq_major else 1
        split = (*k.shape[:axis], heads.num_kv, heads.group,
                 *k.shape[axis + 1:])
        out_dtype = jnp.float32 if keep_f32 else k.dtype
        dk = dk.reshape(split).sum(axis=axis + 1).astype(out_dtype)
        dv = dv.reshape(split).sum(axis=axis + 1).astype(out_dtype)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# Public flash attention with custom VJP
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False, seq_major: bool = False,
                    window: Optional[int] = None):
    """FlashAttention-2 on TPU (Pallas). [B, H, S, D] or, with `seq_major`,
    [B, S, H, D] in and out; GQA via Hk | H. A sequence-major call whose
    heads the kernels do not read in place (`seq_major_fits`: a head width
    that fills the lanes, or does not divide them, or a GQA group) goes
    through the head-major kernels and XLA's transposes. With `window` a
    causal query attends its last `window` keys, its own included: forward
    only, the backward kernels know no window."""
    if seq_major and not seq_major_fits(q.shape, k.shape):
        return _via_head_major(
            lambda q, k, v: _flash(q, k, v, causal, scale, block_q, block_k,
                                   interpret, False, window), q, k, v)
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret,
                  seq_major, window)


def _via_head_major(fn, q, k, v):
    """`fn`, which takes and returns [B, H, S, D], on [B, S, H, D] arrays."""
    out = fn(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, seq_major,
           window=None):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      seq_major, window)[0]


# Why nothing differentiates through the kernels under a window: said where
# the gradient would be taken (`_flash_bwd`) and where a model would ask for
# it (`models.gpt.GPT.loss`).
WINDOW_HAS_NO_BACKWARD = (
    "attention under a window has no backward kernel: `flash_bwd` and the "
    "pair walk the whole causal triangle, and a full-attention gradient is "
    "not a window's. impl='reference' differentiates through the masked jnp "
    "form")

# Names of the forward kernel's two results as `jax.checkpoint` sees them. A
# policy that lists them (`GPT`'s "dots") keeps them for the backward pass,
# which then does not run the forward kernel a second time; under any other
# policy, and outside `jax.checkpoint`, a name does nothing.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _narrow(q):
    """Head width that does not fill the 128 lanes of a tile."""
    return q.shape[-1] % 128 != 0


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               seq_major, window=None):
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
                           interpret=interpret, transposed_out=transposed,
                           seq_major=seq_major, window=window)
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    primal = out
    if transposed:      # [B, H, D, S] -> the inputs' layout
        primal = jnp.transpose(out, (0, 3, 1, 2) if seq_major
                               else (0, 1, 3, 2))
    return primal, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, seq_major, window,
               res, g):
    if window is not None:
        raise NotImplementedError(WINDOW_HAS_NO_BACKWARD)
    q, k, v, out, lse = res
    scale_val = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # delta = rowsum(dO * O), [B, H, S]: all the kernels want of the output
    narrow = _narrow(q)
    if narrow:
        # dO in the kept output's layout, [B, H, D, S]: the out-projection's
        # backward matmul writes it so, delta costs no copy back to the
        # inputs' layout, and the kernels read it as it is
        g = jnp.transpose(g, (0, 2, 3, 1) if seq_major else (0, 1, 3, 2))
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=2 if narrow else -1)
    return _bwd_pallas(q, k, v, lse, g, delta, scale=scale_val,
                       causal=causal, block_q=block_q, block_k=block_k,
                       interpret=interpret, seq_major=seq_major,
                       do_t=narrow)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def dot_product_attention(q, k, v, causal: bool = True,
                          scale: Optional[float] = None,
                          impl: str = "auto",
                          block_q: int = DEFAULT_BLOCK_Q,
                          block_k: int = DEFAULT_BLOCK_K,
                          seq_major: bool = False,
                          selection=None,
                          window: Optional[int] = None) -> jax.Array:
    """Attention entry point used by models. [B, H, S, D] or, with
    `seq_major`, [B, S, H, D] (the projections' own layout) in and out.
    With `selection` (`ops.sparse_index.Selection`) each query attends its
    chosen keys alone, forward only: the kernel `dsa_attend_fwd`, which has
    no backward pass. With `window` a causal query attends its last `window`
    keys, its own included (t - window < s <= t): the forward kernel walks
    the band alone (`flash_fwd_window`), and only the `jnp` form
    differentiates.

    impl: as `ops._impl.resolve_impl` takes it; the kernels take any width.
    """
    impl = resolve_impl(impl, "attention")
    if selection is not None and window is not None:
        raise ValueError("attention over a choice of keys takes no window")
    if impl == "reference":
        reference = functools.partial(
            attention_reference, causal=causal, scale=scale,
            mask=None if selection is None else selection.mask,
            window=window)
        if seq_major:
            return _via_head_major(reference, q, k, v)
        return reference(q, k, v)
    if selection is None:
        return flash_attention(q, k, v, causal, scale, block_q, block_k,
                               impl == "pallas_interpret", seq_major, window)

    def attend(q, k, v):
        return _attend_pallas(
            q, k, v, selection, scale=scale or 1.0 / math.sqrt(q.shape[-1]),
            causal=causal, block_q=block_q, block_k=block_k,
            interpret=impl == "pallas_interpret")

    return _via_head_major(attend, q, k, v) if seq_major else attend(q, k, v)
