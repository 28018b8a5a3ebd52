"""A learned choice of keys for sparse attention: index scores, and for each
query the `topk` best of its causal keys.

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])        s <= t
    S_t     = the min(t + 1, topk) keys s <= t with the largest I[t, s],
              among equals the lowest index first (`lax.top_k`'s order)

(the lightning indexer of DeepSeek sparse attention: a few narrow heads
against one shared key head, a ReLU and a learned weight a head; positive
constant factors of the published form change no choice and are left out).
What comes back is the choice as the attention kernels read it
(`ops/attention.py`, `selection=`), a `Selection`:

    mask   [B, S keys, S queries] int8: 1 where the query attends the key.
           Keys on the second axis, as the kernels lay their scores out.
           Every head reads the same choice: `dsa_attend_fwd` fetches a
           [k tile, q tile] block of it once for the query heads of a KV
           head, turns it once into a float32 bias (0 chosen, -1e30 not)
           and adds that to each head's scores
    counts [B, S / tile, S queries] int32: how many of a query's chosen
           keys lie in each tile of `count_tile(S)` keys — the per-tile
           summary from which a caller finds the rectangles that hold no
           chosen key at all, and the pairs attended (their sum)

Two forms, chosen by `impl` (`ops/_impl.py`):

* `dsa_index`, a Pallas kernel. A grid step takes a tile of 128 queries (on
  the lanes) and makes their scores against the causal keys chunk by chunk
  on the matrix unit, a head at a time, into one VMEM scratch of [S, 128]
  — as integers whose order is the floats' order, keys after the query as
  the least integer. Nothing of [S, S] float32 ever reaches HBM. A query's
  cut is then found on the value, not by a sort: the threshold's 32 bits
  are settled from the highest down, each by one pass over the scratch
  that counts the scores at or above a candidate (compares and sums only;
  exact, whatever the distribution). Where more scores equal the threshold
  than the cut has room for (or a tile's queries have fewer keys than
  `topk`), a second search, on the key index, finds how many of the equals
  are kept, the lowest indices first. A last pass writes the chosen keys as
  int8 and their number a chunk. The indexer's heads are 64 wide: a product
  of that depth fills half of the matrix unit's 128, which caps the score
  products at half of the unit's peak.
* `jnp`: the scores as a loop over the heads and `lax.top_k` on them: the
  reference the kernel is held to, ties included, and the form of other
  backends and of lengths that are no whole 128s.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

_QUERIES = 128      # queries a grid step: the lanes of a tile
_CHUNK = 512        # keys a pass of the kernel's loops, and a tile of counts
_LEAST = -2 ** 31   # the key of a slot that holds no causal key


class Selection(NamedTuple):
    mask: jax.Array     # [B, S keys, S queries] int8
    counts: jax.Array   # [B, S / count_tile(S), S queries] int32


def count_tile(seq_len: int) -> int:
    """Keys a row of `Selection.counts` sums over."""
    for tile in (_CHUNK, _QUERIES):
        if seq_len % tile == 0:
            return tile
    return seq_len


def index_scores(q_idx, k_idx, w_idx):
    """I[b, t, s] float32 for every pair, causal or not. q_idx: [B, S, H,
    D]; k_idx: [B, S, D]; w_idx: [B, S, H]. Operands as they come (bf16
    operands are multiplied as bf16), sums in float32, a head at a time."""
    scores = jnp.zeros((*q_idx.shape[:2], k_idx.shape[1]), jnp.float32)
    for j in range(q_idx.shape[2]):
        qk = jnp.einsum("btd,bsd->bts", q_idx[:, :, j], k_idx,
                        preferred_element_type=jnp.float32)
        scores += (jnp.maximum(qk, 0.0)
                   * w_idx[:, :, j, None].astype(jnp.float32))
    # a sum of products with nothing is -0.0 or 0.0 by its terms' signs:
    # one zero, so that equals are equals
    return jnp.where(scores == 0.0, 0.0, scores)


def _summary(mask_tq):
    """`Selection` of a [B, queries, keys] boolean choice."""
    b, s, _ = mask_tq.shape
    mask = jnp.swapaxes(mask_tq, 1, 2)
    tile = count_tile(s)
    counts = mask.reshape(b, s // tile, tile, s).sum(2, dtype=jnp.int32)
    return Selection(mask.astype(jnp.int8), counts)


def sparse_index_reference(q_idx, k_idx, w_idx, topk: int) -> Selection:
    """`sparse_index` in `jnp`: `lax.top_k` of the causal scores."""
    b, s = q_idx.shape[:2]
    scores = index_scores(q_idx, k_idx, w_idx)
    t = lax.broadcasted_iota(jnp.int32, (s, s), 0)
    key = lax.broadcasted_iota(jnp.int32, (s, s), 1)
    scores = jnp.where(key <= t, scores, -jnp.inf)
    values, chosen = lax.top_k(scores, min(topk, s))
    mask = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], chosen
    ].set(values > -jnp.inf)
    return _summary(mask)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

def _ordered(x):
    """float32 -> int32 with the same order (no NaN; one zero)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _index_kernel(q_ref, k_ref, w_ref, mask_ref, counts_ref, keys_ref, *,
                  topk, heads, dim, chunk, index_bits):
    """One tile of `_QUERIES` queries against its causal keys. q_ref: [128,
    H * D]; k_ref: [S, D]; w_ref: [H, 128] float32; mask_ref: [S, 128] int8;
    counts_ref: [S / chunk, 128] int32; keys_ref: [S, 128] int32 scratch."""
    q0 = pl.program_id(1) * _QUERIES
    n_chunks = counts_ref.shape[0]
    live = (q0 + _QUERIES + chunk - 1) // chunk     # chunks with a causal key
    tile = (chunk, _QUERIES)
    query = q0 + lax.broadcasted_iota(jnp.int32, tile, 1)

    def key_index(c):
        return c * chunk + lax.broadcasted_iota(jnp.int32, tile, 0)

    def rows(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    # ---- the scores, as ordered integers
    w = w_ref[...]
    qs = [q_ref[:, j * dim:(j + 1) * dim] for j in range(heads)]

    def score(c, _):
        k = k_ref[rows(c), :]
        total = jnp.zeros(tile, jnp.float32)
        for j in range(heads):
            qk = lax.dot_general(k, qs[j], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            total += jnp.maximum(qk, 0.0) * w[j:j + 1, :]
        total = jnp.where(total == 0.0, 0.0, total)
        keys_ref[rows(c), :] = jnp.where(key_index(c) <= query,
                                         _ordered(total), _LEAST)
        return 0

    lax.fori_loop(0, live, score, 0)

    def count(test):
        """[1, 128]: a query's keys of the live chunks that pass `test(keys,
        chunk number)`."""
        def add(c, n):
            return n + jnp.sum(test(keys_ref[rows(c), :], c).astype(
                jnp.int32), axis=0, keepdims=True)
        return lax.fori_loop(0, live, add,
                             jnp.zeros((1, _QUERIES), jnp.int32))

    # ---- the cut: the largest threshold that `topk` scores reach, its bits
    # settled from the highest down (as unsigned: the sign bit flipped)
    def settle(step, found):
        trial = found | lax.shift_left(jnp.int32(1), 31 - step)
        at_least = count(lambda keys, c: keys >= (trial ^ _LEAST))
        return jnp.where(at_least >= topk, trial, found)

    cut = lax.fori_loop(0, 32, settle,
                        jnp.zeros((1, _QUERIES), jnp.int32)) ^ _LEAST
    above = count(lambda keys, c: keys > cut)
    equal = count(lambda keys, c: keys == cut)
    room = topk - above         # how many of the equals are kept (>= 1)

    # ---- among equals the lowest indices: the last index kept, searched
    # only where some query has more equals than room
    def settle_index(step, found):
        trial = found | lax.shift_left(jnp.int32(1), index_bits - 1 - step)
        before = count(lambda keys, c: (keys == cut) & (key_index(c) < trial))
        return jnp.where(before < room, trial, found)

    crowded = jnp.max(jnp.where((equal > room) & (cut != _LEAST), 1, 0)) > 0
    last = lax.cond(
        crowded,
        lambda: lax.fori_loop(0, index_bits, settle_index,
                              jnp.zeros((1, _QUERIES), jnp.int32)),
        lambda: jnp.full((1, _QUERIES), 2 ** index_bits - 1, jnp.int32))

    # ---- the choice, and its number a chunk
    def write(c, _):
        keys = keys_ref[rows(c), :]
        chosen = ((keys > cut) | ((keys == cut) & (key_index(c) <= last))
                  ) & (keys != _LEAST)
        chosen = chosen.astype(jnp.int32)
        mask_ref[rows(c), :] = chosen.astype(jnp.int8)
        counts_ref[pl.ds(c, 1), :] = jnp.sum(chosen, axis=0, keepdims=True)
        return 0

    def blank(c, _):
        mask_ref[rows(c), :] = jnp.zeros(tile, jnp.int8)
        counts_ref[pl.ds(c, 1), :] = jnp.zeros((1, _QUERIES), jnp.int32)
        return 0

    lax.fori_loop(0, live, write, 0)
    lax.fori_loop(live, n_chunks, blank, 0)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _index_pallas(q_idx, k_idx, w_idx, topk, interpret):
    b, s, heads, dim = q_idx.shape
    chunk = count_tile(s)
    size = jnp.dtype(q_idx.dtype).itemsize
    lanes = lambda n: -(-n // 128) * 128
    # the scratch, and two buffers of every block
    vmem = (s * _QUERIES * 4
            + 2 * (s * _QUERIES + s // chunk * _QUERIES * 4
                   + s * lanes(dim) * size
                   + _QUERIES * lanes(heads * dim) * size
                   + 8 * _QUERIES * 4 * -(-heads // 8)))
    mask, counts = pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, heads=heads, dim=dim,
                          chunk=chunk,
                          index_bits=max(1, (s - 1).bit_length())),
        grid=(b, s // _QUERIES),
        in_specs=[
            pl.BlockSpec((None, _QUERIES, heads * dim),
                         lambda i, t: (i, t, 0)),
            pl.BlockSpec((None, s, dim), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((None, heads, _QUERIES), lambda i, t: (i, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((None, s, _QUERIES), lambda i, t: (i, 0, t)),
            pl.BlockSpec((None, s // chunk, _QUERIES),
                         lambda i, t: (i, 0, t)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.int8),
                   jax.ShapeDtypeStruct((b, s // chunk, s), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((s, _QUERIES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem + vmem // 4, 16 * 2 ** 20)),
        interpret=interpret, name="dsa_index",
    )(q_idx.reshape(b, s, heads * dim), k_idx,
      jnp.swapaxes(w_idx.astype(jnp.float32), 1, 2))
    return Selection(mask, counts)


def sparse_index(q_idx: jax.Array, k_idx: jax.Array, w_idx: jax.Array,
                 topk: int, *, impl: str = "auto") -> Selection:
    """q_idx: [B, S, H, D]; k_idx: [B, S, D]; w_idx: [B, S, H]. Returns each
    query's `topk` best causal keys by index score (the module's text).

    impl: as `ops._impl.resolve_impl` takes it; the kernel takes a sequence
    of whole 128s, any other keeps the `jnp` form under "auto"."""
    impl = resolve_impl(impl, "sparse index", q_idx.shape[1])
    if impl == "reference":
        return sparse_index_reference(q_idx, k_idx, w_idx, topk)
    return _index_pallas(q_idx, k_idx, w_idx, topk,
                         impl == "pallas_interpret")
