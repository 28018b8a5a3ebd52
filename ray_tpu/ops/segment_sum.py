"""Rows summed into their segments when the rows come sorted by segment.

    out[t] = sum over the rows j with ids[j] == t of weights[j] * rows[j]

in float32, for ids that ascend: what a row scatter-add computes, without a
scatter. The rows of a segment are one contiguous run, so the sum is a
streaming pass: every row is read once, every segment written once. An id
of `num_segments` or more marks a row that belongs nowhere; sorted, such
rows are last. A segment may have no row (it reads zero) or any number.

Two forms, chosen by `impl` (`ops/_impl.py`; the kernel at a row width of
whole 128-lane tiles):

* `moe_segsum`, a Pallas kernel. The segments are cut into blocks of
  `_SEGMENTS` and the rows into chunks of `_ROWS`; a block's rows lie in a
  run of consecutive chunks, found outside the kernel by a `searchsorted`
  of the blocks' first ids. The grid is the list of (block, chunk) pairs
  that share a row, block by block — at most blocks + chunks of them,
  scalar-prefetched, so that the pipeline fetches each chunk and writes each
  block as the list names them (a chunk that straddles a block's edge is
  fetched for both; chunks of rows that belong nowhere are never fetched).
  A grid step adds its chunk into its block on the matrix unit: a
  [`_SEGMENTS`, `_ROWS`] selection matrix (row j's column holds weights[j]
  in the row of its segment, nothing elsewhere) times the chunk, accumulated
  in the block's float32 tile in VMEM. The selection matrix and a float32
  chunk are split into three bf16 terms each (8 + 8 + 8 bits of a float32's
  24), so that every product the unit forms is exact and the sum is a
  float32 sum: one pass for bf16 rows without weights, three with weights.
  Rows that belong nowhere are blanked in the chunk by their position (they
  are the last): nothing times a NaN is a NaN. `onto`, an array the sums
  are added to, is the output's own buffer, fetched a block at a time.
* `jax.ops.segment_sum` over sorted ids: the reference the kernel is held
  to, and what other backends and other widths run.

On a v5e (PERF.md, PR 36), [40,960, 2048] bf16 rows of which half belong
somewhere, into [32,768, 2048] float32: XLA's row scatter-add takes 4.7 ms in
the step (it sorts its indices itself), 3.0 ms alone when told they are
sorted; the kernel 0.7 ms without weights and 0.8 with (the output's 268 MB
and the real rows' 80 MB once across HBM are 0.43 ms), after the 1.4 ms row
gather that sorts the rows.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

# A grid step: the segments of an output block, the rows of a chunk and the
# lanes of both. A block's float32 tile and a chunk, each double-buffered,
# stay under 8 MB of VMEM; fewer segments a block or rows a chunk are more
# grid steps, more are more of the selection matrix that holds nothing.
_SEGMENTS = 256
_ROWS = 128
_LANES = 2048


def sorted_segment_sum_reference(rows, ids, num_segments, weights=None):
    """`sorted_segment_sum` in `jnp`."""
    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights.astype(jnp.float32)[:, None]
    return jax.ops.segment_sum(rows, ids, num_segments=num_segments,
                               indices_are_sorted=True)


def _bf16_terms(x):
    """x as a sum of bf16 terms: itself if it is bf16, else the three that
    hold a float32's 24 bits exactly."""
    if x.dtype == jnp.bfloat16:
        return [x]
    terms, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    return terms


def _segsum_kernel(block_ref, chunk_ref, n_ref, ids_ref, *refs, segments,
                   weighted, onto):
    """One (block, chunk) pair of the list. ids_ref, w_ref: [1, rows] of the
    chunk; rows_ref: [rows, lanes]; out_ref: [segments, lanes] float32, the
    block's tile, which stays in VMEM while the list stays on the block.
    With `onto`: the array the sums are added to, left in HBM (it is the
    output's own buffer), of which the kernel fetches a block as the list
    reaches it, unless n_ref[1] says it holds nothing yet. n_ref[0]: the
    list's length; n_ref[2]: the rows that belong somewhere."""
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    rows_ref = refs.pop(0)
    onto_ref = refs.pop(0) if onto else None
    out_ref = refs.pop(0)
    g, v = pl.program_id(0), pl.program_id(1)
    block = block_ref[v]
    reached = (v == 0) | (block_ref[jnp.maximum(v - 1, 0)] != block)

    @pl.when(reached & (n_ref[1] == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    if onto:
        @pl.when(reached & (n_ref[1] != 0))
        def _():
            fetch = pltpu.make_async_copy(
                onto_ref.at[pl.ds(block * segments, segments),
                            pl.ds(g * out_ref.shape[1], out_ref.shape[1])],
                out_ref, refs[0])
            fetch.start()
            fetch.wait()

    @pl.when(v < n_ref[0])
    def _():
        local = ids_ref[...] - block * segments               # [1, rows]
        hit = lax.broadcasted_iota(
            jnp.int32, (segments, local.shape[1]), 0) == local
        select = (jnp.where(hit, w_ref[...], 0.0) if weighted
                  else hit.astype(jnp.bfloat16))
        # a row that belongs nowhere may hold anything, and nothing times a
        # NaN is a NaN: sorted, such rows are the last, from row n_ref[2] on
        real = (chunk_ref[v] * rows_ref.shape[0] + lax.broadcasted_iota(
            jnp.int32, (rows_ref.shape[0], 1), 0)) < n_ref[2]
        rows = _bf16_terms(jnp.where(real, rows_ref[...], 0))
        # the terms' products, those under a float32's last bit left out
        out_ref[...] += functools.reduce(operator.add, [
            jnp.dot(s, r, preferred_element_type=jnp.float32)
            for i, s in enumerate(_bf16_terms(select)) for r in rows[:3 - i]])


def _schedule(ids, num_blocks, num_chunks, segments, rows):
    """The (block, chunk) pairs that share a row, block by block, every
    block at least once (an empty one is written as zeros): the block and
    the chunk of each of the list's blocks + chunks entries, how many of
    them are pairs (the entries after the last pair stay on it), and how
    many rows belong to a block at all."""
    first = jnp.searchsorted(
        ids, jnp.arange(num_blocks + 1, dtype=ids.dtype) * segments
    ).astype(jnp.int32)
    # a block's last chunk is its last row's; a block with no row stays on
    # the chunk the list is at
    hi = jnp.maximum((first[1:] - 1) // rows, 0)
    lo = jnp.minimum(first[:-1] // rows, hi)
    ends = jnp.cumsum(hi - lo + 1)
    at = jnp.arange(num_blocks + num_chunks, dtype=jnp.int32)
    block = jnp.minimum(jnp.searchsorted(ends, at, side="right"),
                        num_blocks - 1).astype(jnp.int32)
    chunk = jnp.minimum(lo[block] + at - (ends - (hi - lo + 1))[block],
                        hi[block])
    return (block, chunk.astype(jnp.int32), ends[-1:].astype(jnp.int32),
            first[-1:])


@functools.partial(jax.jit, static_argnums=(2, 5))
def _segsum_pallas(rows, ids, num_segments, weights, onto, interpret):
    n, d = rows.shape
    lanes = next(w for w in range(min(d, _LANES), 0, -128) if d % w == 0)
    segments = min(_SEGMENTS, -(-num_segments // 8) * 8)
    chunk_rows = min(_ROWS, -(-n // 128) * 128)
    num_blocks = -(-num_segments // segments)
    num_chunks = -(-n // chunk_rows)
    pad = num_chunks * chunk_rows - n
    # a row that belongs nowhere: past the last block's segments
    ids = jnp.pad(jnp.where(ids < num_segments, ids, num_blocks * segments
                            ).astype(jnp.int32),
                  (0, pad), constant_values=num_blocks * segments)
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    block, chunk, count, real = _schedule(ids, num_blocks, num_chunks,
                                          segments, chunk_rows)

    per_row = pl.BlockSpec((None, 1, chunk_rows),
                           lambda g, v, block, chunk, n: (chunk[v], 0, 0))
    operands = [ids.reshape(num_chunks, 1, chunk_rows)]
    in_specs = [per_row]
    if weights is not None:
        operands.append(jnp.pad(weights.astype(jnp.float32), (0, pad)
                                ).reshape(num_chunks, 1, chunk_rows))
        in_specs.append(per_row)
    operands.append(rows)
    in_specs.append(pl.BlockSpec(
        (chunk_rows, lanes), lambda g, v, block, chunk, n: (chunk[v], g)))
    holds = jnp.zeros((1,), jnp.int32)
    if onto is not None:
        onto, holds = onto
        operands.append(jnp.pad(onto.astype(jnp.float32), (
            (0, num_blocks * segments - num_segments), (0, 0))))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        holds = jnp.asarray(holds, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, segments=segments,
                          weighted=weights is not None,
                          onto=onto is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // lanes, num_blocks + num_chunks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (segments, lanes),
                lambda g, v, block, chunk, n: (block[v], g)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())] * (
                onto is not None)),
        out_shape=jax.ShapeDtypeStruct((num_blocks * segments, d),
                                       jnp.float32),
        input_output_aliases=({} if onto is None
                              else {len(operands) + 2: 0}),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="moe_segsum",
    )(block, chunk, jnp.concatenate([count, holds, real]), *operands)
    return out[:num_segments]


def sorted_segment_sum(rows: jax.Array, ids: jax.Array, num_segments: int,
                       weights: Optional[jax.Array] = None, *,
                       onto=None, impl: str = "auto") -> jax.Array:
    """rows: [N, D]; ids: [N] integers that ascend, `num_segments` or more
    for a row that belongs nowhere; weights: [N] float32, or None for ones.
    Returns [num_segments, D] float32, row t the weighted sum of the rows
    whose id is t (module docstring). `onto`: (an array of the result's
    shape that the sums are added to, a scalar that is false where that
    array is known to hold zeros — a loop's first trip — and is then not
    read). Not differentiable: its callers carry backward rules of their
    own."""
    impl = resolve_impl(impl, "segment sum", rows.shape[1])
    if impl == "reference":
        out = sorted_segment_sum_reference(rows, ids, num_segments, weights)
        return out if onto is None else out + jnp.where(onto[1], onto[0], 0)
    return _segsum_pallas(rows, ids, num_segments, weights, onto,
                          impl == "pallas_interpret")
