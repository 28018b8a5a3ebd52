"""Mixture-of-Experts FFN, dropless.

The reference has NO native MoE/EP (SURVEY §2.4: "absent — only via
external frameworks"); here it's first-class. Top-k routing with no
capacity and no dropped token, on scores that are a softmax over the experts
or a sigmoid of each (`score`), the choice made on the scores plus a bias
that is no part of the weights (`select_bias`), the chosen scores rescaled
to sum to 1 or not and multiplied by `route_scale`. The router is float32 in
truth: the logits are a `HIGHEST` product of the activations cast up and
every bit of the router's weights (where the activations are bf16 the compiler leaves out
the passes that would multiply the cast's zero terms: three of six on a
v5e, PERF.md, PR 41), the softmax is over all E, and the K largest
probabilities of a token are `ops.router_topk`'s — K rounds of a maximum in
one Pallas kernel at an E of whole 128-lane tiles on a TPU, `lax.top_k`
elsewhere, the same values, indices and order among equals either way. The
T x K (token, expert) assignments are sorted by expert (stable, so token
order is kept inside a group), the rows gathered into that order, and the
SwiGLU matmuls run as grouped matmuls over the E ragged groups
(`jax.lax.ragged_dot`: FLOPs and memory grow with T x K, with no factor of E
and no [T, E, C] tensor). The results return to token order through the
inverse permutation and are summed over K. An expert with no token is an
empty group; an expert with many times the mean is a long one.

Where a layer holds all its experts, everything that has to happen to a
(token, expert) pair happens while the pair's row is in expert order, so
that the T x K rows — [163,840, 2048] bf16, 0.67 GB a pass in OLMoE's step —
cross HBM as few times as the mathematics asks (PERF.md, PR 56). One sort
hands back the experts as sorted and the order; the counts are where each
expert's run begins in the sorted experts, not a scatter-add of T x K ones;
the pairs' routing weights come into that order as a sort's payload
(`_permuted`), not by a gather of single elements. **The routing weight is
applied inside the SwiGLU product**, in float32 ahead of the product's one
rounding: weight x ((silu(gate) x up) @ w_down) is (weight x silu(gate) x
up) @ w_down. **So the combine is the dispatch's transpose** and nothing
else: the dispatch makes K copies of a token's row in expert order
(`_take_rows`), the combine sums a token's K rows back (`_sum_rows`), and
each is the other's derivative — the combine's backward pass is one K-fold
gather of the [T, D] cotangent, no [T, K, D] product of cotangent and weight
is written, the rows gathered back are no residual, and the weights'
gradient is a sum over F inside the SwiGLU product's backward pass, which
holds up, gate and the cotangent already. **Up and gate are one grouped
matmul where the weights are cast on the way in** (float32 master weights
under a bf16 layer: training): the cast writes a copy of each weight
whatever it is handed, so it writes them side by side, [E, D, 2F]; the rows are read once for both, the transposes give the
rows' gradient in one matmul — not two and an addition of their [T x K, D]
results — and the joined weight's in one, and the product's backward pass
writes d up | d gate as one array (`ops.swiglu`: a kernel on a TPU, because
the compiler writes the halves out and joins them in a pass of its own) and
the product again beside them, for `w_down`'s gradient, so that it is
neither kept from the forward pass nor made in a pass of its own
(`_weighted_down`).
Weights that are in the compute dtype already (a served replica) are never
joined, which would be a copy: they are read where they lie, by three
matmuls. Which of the two it is the code reads off its input's dtype; there
is no argument for it. No pass of the block is a scatter-add, and nothing is
differentiated through a sort.

The weights are read where they lie: a layer may be
handed the stack its experts are one slice of ([L, E, D, F], as a parameter
tree holds the layers of a kind) and its place in it (`layer`), and the
grouped matmuls then run over the stack's L x E experts with the layer's E
group sizes set among zeros, the other layers' experts being (L - 1) x E more
empty groups — because a grouped matmul is a call the compiler fuses no slice
into, so that a layer's weights sliced from the stack were written out and
read back before every one of them, 0.5 GB a weight in a served Trinity-Mini
(PERF.md, PR 54). That holds where the stack is in the compute dtype already;
where it is cast on the way in, the cast's output is the layer's copy, the
slice is fused into it and the matmuls take that. Expert weights carry a
leading "expert" logical axis that the rules map to the ``ep`` mesh axis: an
annotation the partitioner is left to honour (no exchange of tokens between
chips is written here; nor is the joined weight written for it: the rules
map its last axis to ``tp``, so on such a mesh a chip may hold up's columns
and another gate's, and the partitioner moves what the product needs).

A layer may hold only a share of its experts (`first_expert` and as many as
the weights it is given: one chip's part of a layer that several share). It
then routes over all of them, and computes the part of the result that its
own experts give: the (token, expert) pairs routed to them, found as one
contiguous run of the sorted order. That run has no static length, so it is
walked in trips of a static number of rows by a loop whose trip count follows
the run's length: every pair of the run is gathered, multiplied and added to
its token's row, however many there are. A trip costs what its static rows
cost, real or not (XLA's gather takes every index it is handed, the grouped
matmuls every row), so a trip takes one of a short ladder of sizes
(`_held_trip_sizes`): whole trips are the chunk's — `HELD_CHUNK_SHARE` times
the run's length at balanced routing, which bounds the walk's memory
whatever the skew — and the trip that takes what is left is the smallest size
that holds it, chosen inside the trip (`_walk`). The one smaller size lies
midway between the balance and the chunk: a layer routed about its share,
a little under or a little over, so walks three quarters of the rows the
chunk alone would, whichever side of the balance it falls on, and a hot one
takes no more trips than before. The ladder is short because a size is a
body of its own, forward and backward, wherever a program holds the walk:
the bodies are jitted functions of their arrays (`_held_trip`,
`_held_trip_bwd`), lowered once a size a program however many layers call
them, but the compiler still builds each where it is called, and a program's
start pays for every kernel its executable holds (PERF.md, PR 52). Added how: a
chunk's rows come out of the grouped matmuls sorted by expert; one sort of
the chunk's token ids (made once a trip, outside the choice of its size)
and one row gather put them in token order, where a token's rows
(at most K, its choices being distinct experts) are a run, and the runs are
summed into their tokens in float32 as they stream past
(`ops.segment_sum.sorted_segment_sum`: a Pallas kernel on a TPU, a segment
sum over sorted ids elsewhere) — forward for the weighted results, backward
for the rows' gradients. There is no row scatter-add: on a TPU one costs
several row gathers (PERF.md, PRs 32 and 36). Rows routed elsewhere cost a
sort key and nothing more; what the absent experts would add is left out.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.router_topk import router_topk
from ..ops.segment_sum import sorted_segment_sum
from ..ops.swiglu import (gate_activation, weighted_swiglu,
                          weighted_swiglu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x: jax.Array, order: jax.Array, inverse: jax.Array,
               copies: int):
    """Row j of the result is row `order[j] // copies` of x, where `order`
    is a permutation of range(rows x copies) and `inverse` its inverse: with
    one copy a permutation of the rows, with K each token's row once per
    choice, in the sorted order. The cotangent comes back through `inverse`
    and is summed over the copies (`_sum_rows`): a gather and a reduction
    where autodiff would scatter-add."""
    return x[order // copies]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_rows(y: jax.Array, order: jax.Array, inverse: jax.Array,
              copies: int):
    """`_take_rows`' transpose: row t of the result is the sum, in float32,
    of the `copies` rows of y that `_take_rows` made of row t — rows
    `inverse[t * copies : (t + 1) * copies]`. Its cotangent is `_take_rows`
    of the result's, one gather of the [T, D] array: autodiff of the gather
    and the sum would first write the cotangent `copies` times over."""
    return y[inverse].reshape(-1, copies, y.shape[-1]).sum(
        1, dtype=jnp.float32).astype(y.dtype)


_take_rows.defvjp(
    lambda x, order, inverse, copies: (
        _take_rows(x, order, inverse, copies), (order, inverse)),
    lambda copies, at, g: (_sum_rows(g, *at, copies), None, None))
_sum_rows.defvjp(
    lambda y, order, inverse, copies: (
        _sum_rows(y, order, inverse, copies), (order, inverse)),
    lambda copies, at, g: (_take_rows(g, *at, copies), None, None))


@jax.custom_vjp
def _permuted(values: jax.Array, order: jax.Array, inverse: jax.Array):
    """values[order], a number an element, for a permutation `order` and
    its `inverse` — as the payload of a sort: sorted by `inverse`, element i
    lands at place inverse[i], which is where `order` reads it from. The
    cotangent comes back as the payload of `order`'s sort. On a TPU a
    gather of 163,840 single elements costs 1.17 ms and such a sort 0.16
    (PERF.md, PR 56)."""
    return lax.sort((inverse, values), num_keys=1)[1]


_permuted.defvjp(
    lambda values, order, inverse: (
        _permuted(values, order, inverse), (order, inverse)),
    lambda at, g: (_permuted(g, at[1], at[0]), None, None))


# Rows of the walk's largest trip, the chunk, as a multiple of the share's
# pairs at balanced routing (T x K x held / E): the static bound on the
# walk's memory (rows x d_model), not on how many pairs it takes. At 2 a
# share takes one trip unless it runs hotter than twice the balance. What a
# trip that is not full may take instead is `_held_trip_sizes`'s: one size
# midway between the balance and the chunk, and nothing finer — every size is
# another body, forward and backward, in each program's executable. Not the
# balance itself: layers routed about their share scatter around it, so a
# size there cuts through the middle of them, and which side a layer falls
# on, half the chunk's rows or all of them, changes with the seed — the
# step's time followed the routing (`qwen3next-steady` spread 1.1% over its
# seeds, `keye2-score-16k-over` 3.6%; PERF.md, PR 52: what each size bought
# and cost). Midway, a layer up to one and a half times its share takes the
# smaller trip, and one beyond that was never the usual case.
HELD_CHUNK_SHARE = 2.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_down(up_gate, weight, w_down, sizes, impl, act="silu"):
    """(weight x act(gate) x up) @ w_down over the groups, from the joined
    up | gate rows ([R, 2F]; `ops.swiglu`). Its backward pass keeps the
    product from nothing: the pass that differentiates the product holds
    up, gate and the weight, and writes the product again beside their
    gradients, for the gradient of `w_down`."""
    return lax.ragged_dot(weighted_swiglu(up_gate, weight, act), w_down,
                          sizes)


def _weighted_down_fwd(up_gate, weight, w_down, sizes, impl, act):
    return (_weighted_down(up_gate, weight, w_down, sizes, impl, act),
            (up_gate, weight, w_down, sizes))


def _weighted_down_bwd(impl, act, res, g):
    up_gate, weight, w_down, sizes = res
    d_act = lax.ragged_dot(g, w_down.swapaxes(1, 2), sizes)
    d_up_gate, d_weight, product = weighted_swiglu_bwd(
        up_gate, weight, d_act, impl=impl, act=act)
    d_w_down, = jax.vjp(lambda w: lax.ragged_dot(product, w, sizes),
                        w_down)[1](g)
    return d_up_gate, d_weight, d_w_down, None


_weighted_down.defvjp(_weighted_down_fwd, _weighted_down_bwd)


def _swiglu_groups(rows, w_up, w_gate, w_down, sizes, layer=None,
                   weight=None, impl="reference", act="silu"):
    """Rows sorted by expert through their experts ([E, ...] weights, [E]
    `sizes`): grouped matmuls, each weight cast to the rows' dtype where it
    is not in it. With `layer` the weights are stacks [L, E, ...] in that
    dtype, read where they lie: as [L x E, ...], which is the same bytes,
    under [L x E] sizes that are the layer's at `layer * E` and 0 elsewhere
    — the groups before a layer's hold no row, so its first still starts at
    row 0.

    With `weight` ([rows] float32: each row's routing weight) the result is
    that of the weighted rows' — weight x (silu(gate) x up) @ w_down is
    (weight x silu(gate) x up) @ w_down — the weight multiplied into the
    SwiGLU product in float32, ahead of the product's one rounding. Up and
    gate are then one grouped matmul where they are cast on the way in: the
    cast writes a copy of each weight whatever it is handed, so it writes
    the two side by side, [E, D, 2F]; the rows are read once for both, and
    the transposes give the rows' gradient in one matmul and the joined
    weight's in one (`ops.swiglu`, which `impl` is for). Weights that are in
    the rows' dtype already are never joined: that would be a copy.

    `act` names the gate's activation (`ops.swiglu.gate_activation`: "silu"
    in everything written here, or the model's "relu"), in all three
    branches."""
    opened = gate_activation(act)

    def of(w):
        w = w.astype(rows.dtype)
        return w if layer is None else w.reshape(-1, *w.shape[2:])

    with jax.named_scope("moe_experts"):
        if layer is not None:
            n, e = w_up.shape[:2]
            sizes = lax.dynamic_update_slice(
                jnp.zeros(n * e, sizes.dtype), sizes,
                (jnp.asarray(layer, jnp.int32) * e,))
        if weight is not None and w_up.dtype != rows.dtype:
            # each cast and then joined, not the join cast: a gradient
            # then comes back cut in the rows' dtype and cast half by half,
            # as two weights' are, where it would be cast whole first — a
            # pass of its own that writes what no one wrote before
            up_gate = lax.ragged_dot(
                rows, jnp.concatenate([of(w_up), of(w_gate)], -1), sizes)
            # (the product's backward kernel takes an F of whole lane tiles)
            return _weighted_down(
                up_gate, weight, of(w_down), sizes,
                impl if w_up.shape[-1] % 128 == 0 else "reference", act)
        up = lax.ragged_dot(rows, of(w_up), sizes)
        gate = lax.ragged_dot(rows, of(w_gate), sizes)
        if weight is None:
            return lax.ragged_dot(opened(gate) * up, of(w_down), sizes)
        f32 = jnp.float32
        product = opened(gate.astype(f32)) * up.astype(f32) * weight[:, None]
        return lax.ragged_dot(product.astype(rows.dtype), of(w_down), sizes)


class _Run(NamedTuple):
    """What every trip of a walk over the held experts' run reads and none
    changes: x [T, D]; gate_vals [T, K] float32; the weights of the H held
    experts; `order`, the (token, choice) pairs sorted by expert and followed
    by a chunk's length of padding, so that a trip is a slice of it wherever
    it starts; `start`, where the held experts' run begins in it; `counts`
    [H], how many pairs each held expert takes; `layer`, where the weights
    are stacks [L, H, ...], the layer whose experts these are
    (`_swiglu_groups`), else None."""
    x: jax.Array
    gate_vals: jax.Array
    w_up: jax.Array
    w_gate: jax.Array
    w_down: jax.Array
    order: jax.Array
    start: jax.Array
    counts: jax.Array
    layer: Optional[jax.Array] = None


def _held_chunk(lo, rows, run: _Run):
    """A trip of the walk: `rows` pairs from the run's `lo`-th on. Returns
    the pairs' ids and tokens, which of the trip's rows are real, the rows
    of x, and how many of the trip's rows each held expert takes."""
    with jax.named_scope("moe_dispatch"):
        ends = jnp.cumsum(run.counts)
        valid = lo + jnp.arange(rows, dtype=jnp.int32) < ends[-1]
        pair = lax.dynamic_slice(run.order, (run.start + lo,), (rows,))
        token = pair // run.gate_vals.shape[-1]
        sizes = jnp.clip(jnp.minimum(ends, lo + rows)
                         - jnp.maximum(ends - run.counts, lo), 0, None)
        return pair, token, valid, run.x[token], sizes.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "top_k", "n_tokens",
                                             "pairs"))
def _by_token(lo, order, start, total, *, chunk: int, top_k: int,
              n_tokens: int, pairs: bool):
    """A chunk's length of the run from its `lo`-th pair on, put in token
    order by one sort: each row's token (`n_tokens` for a row that is not
    real: those sort last, so the first rows of the result hold every real
    row of whatever size of trip takes what is left), its place in the
    slice (0 for a row that is not real) and, with `pairs`, its pair's id as
    the sort's payload. Made once a trip, outside the choice of its size:
    the sort is the largest piece of a trip's code and of its compile, and
    costs the device next to nothing (PERF.md, PR 52)."""
    pair = lax.dynamic_slice(order, (start + lo,), (chunk,))
    place = jnp.arange(chunk, dtype=jnp.int32)
    ids, at, *pair = lax.sort(
        (jnp.where(lo + place < total, pair // top_k, n_tokens), place,
         *((pair,) if pairs else ())), num_keys=1)
    return (ids, jnp.where(ids < n_tokens, at, 0), *pair)


def _sum_into_tokens(onto, lo, rows, by_token, weight=None, *, impl):
    """onto[t] plus the sum of weight x rows over the trip's real rows of
    token t, in float32: the rows of the trip that starts at the run's
    `lo`-th pair added to their tokens' rows. The trip comes sorted by
    expert; `by_token` (`_by_token`, cut to the trip's rows) and one row
    gather put it in token order, and there a token's rows are a run,
    summed by `ops.segment_sum` as they stream past — where
    `onto.at[token].add(rows)` is a scatter, which on a TPU costs several
    such gathers. `weight` is in token order already. The first trip does
    not read `onto`."""
    ids, at = by_token
    return sorted_segment_sum(rows[at], ids, onto.shape[0], weight,
                              onto=(onto, lo > 0), impl=impl)


def _held_chunk_rows(pairs: int, share: float) -> int:
    """Rows of the walk's largest trip: `HELD_CHUNK_SHARE` times the share's
    pairs at balance, in whole 1,024s, and no more than all the pairs."""
    rows = -(-int(HELD_CHUNK_SHARE * pairs * share) // 1024) * 1024
    return max(1, min(rows, pairs))


def _held_trip_sizes(pairs: int, share: float) -> Tuple[int, ...]:
    """The rows a trip of the walk may take, largest first (`_walk`): the
    chunk, and for a last trip the rows midway between the share's pairs at
    balance and the chunk, in whole 1,024s, where that is less."""
    chunk = _held_chunk_rows(pairs, share)
    midway = -(-int((1.0 + HELD_CHUNK_SHARE) / 2 * pairs * share)
               // 1024) * 1024
    return (chunk, midway) if 0 < midway < chunk else (chunk,)


def _walk(trip, carry, sums, run: _Run, *more, sizes, pairs, scope):
    """`carry` and `sums` through the trips that cover the run. `sizes` are
    the rows a trip may take, largest first: whole trips are the chunk's,
    `sizes[0]`, and the trip that takes what is left is the smallest size
    that holds it — chosen inside the trip, by a `lax.switch` on the pairs
    left, so the one loop holds one body a size.
    `trip(carry, lo, by_token, run, *more, rows=)` walks `rows` pairs from
    the run's `lo`-th on and returns the new carry and what it adds to
    `sums`. The adding is done here, outside the switch: a conditional's
    results are buffers of their own, and sums handed through it — the three
    float32 gradients of the held weights, 134 MB each in Qwen3-Next's step
    — were copied on every trip (read in the step compiled for a v5e:
    PERF.md, PR 52). The [T, D] rows do go through: `ops.segment_sum`
    writes them in place. The trip's order by token is made here too
    (`_by_token`, under `scope`; with the pairs' ids where `pairs`), once
    for whichever size takes it."""
    chunk, total = sizes[0], run.counts.sum()
    n_tokens, top_k = run.gate_vals.shape

    def body(c, state):
        carry, sums = state
        lo = c * chunk
        with jax.named_scope(scope):
            by_token = _by_token(lo, run.order, run.start, total, chunk=chunk,
                                 top_k=top_k, n_tokens=n_tokens, pairs=pairs)
        carry, addends = lax.switch(
            sum((total - lo <= rows).astype(jnp.int32) for rows in sizes[1:]),
            [functools.partial(trip, rows=rows) for rows in sizes],
            carry, lo, by_token, run, *more)
        return carry, jax.tree_util.tree_map(
            lambda a, b: a + b.astype(a.dtype), sums, addends)

    return lax.fori_loop(0, (total + chunk - 1) // chunk, body,
                         (carry, sums))


# The walk's two trips, forward and backward, are functions of their arrays
# that close over nothing, jitted with the trip's rows and `impl` static: a
# program that holds several walks of the same shapes (a period of layers
# written out inside the scan, each walked forward, forward again under the
# checkpoint and backward) lowers each trip once a size, and traces it once a
# size wherever jax's cache of traces reaches, which is the trace around it
# (a kind of block under `jax.checkpoint`, the backward pass).

@functools.partial(jax.jit, static_argnames=("rows", "impl", "act"))
def _held_trip(out, lo, by_token, run: _Run, *, rows: int, impl: str,
               act: str = "silu"):
    """`rows` pairs of the held experts' run from its `lo`-th on, forward:
    gathered, multiplied and added to `out`, the result so far in token
    order ([T, D] float32). Returns it with what the trip adds to the
    walk's counts: the rows each held expert was given, and the rows it
    took, real or not."""
    *_, taken, sizes = _held_chunk(lo, rows, run)
    y = _swiglu_groups(taken, run.w_up, run.w_gate, run.w_down, sizes,
                       run.layer, act=act)
    with jax.named_scope("moe_combine"):
        ids, at, pair = (a[:rows] for a in by_token)
        out = _sum_into_tokens(out, lo, y, (ids, at),
                               run.gate_vals.reshape(-1)[pair], impl=impl)
    return out, (sizes, jnp.int32(rows))


@functools.partial(jax.jit, static_argnames=("rows", "impl", "act"))
def _held_trip_bwd(carry, lo, by_token, run: _Run, d_out, *, rows: int,
                   impl: str, act: str = "silu"):
    """The same trip backward: its products made again and differentiated.
    `carry`: the gradients so far of x ([T, D] float32) and of the routing
    weights ([T x K]). Returns them with what the trip adds to the
    gradients of the three held weights."""
    f32 = jnp.float32
    dx, d_gate = carry
    pair, token, valid, taken, sizes = _held_chunk(lo, rows, run)
    y, pull = jax.vjp(
        lambda r, *w: _swiglu_groups(r, *w, sizes, act=act), taken, run.w_up,
        run.w_gate, run.w_down)
    with jax.named_scope("moe_combine"):
        dy = d_out[token]
        d_gate = d_gate.at[pair].add(jnp.where(
            valid, (dy * y.astype(f32)).sum(-1), 0.0))
        weight = run.gate_vals.reshape(-1)[pair]
        dy = jnp.where(valid[:, None], dy * weight[:, None],
                       0.0).astype(y.dtype)
    d_taken, *dw = pull(dy)
    with jax.named_scope("moe_dispatch"):
        dx = _sum_into_tokens(dx, lo, d_taken,
                              tuple(a[:rows] for a in by_token), impl=impl)
    return (dx, d_gate), tuple(dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _held_experts(run: _Run, trip_sizes: Tuple[int, ...], impl: str,
                  act: str = "silu"):
    """The held experts' part of the block's result: the result in token
    order ([T, D] float32), the rows each held expert was given ([H] int32,
    the grouped matmuls' own group sizes summed over the trips) and the
    rows the walk took for them (int32: each trip's rows, real or not,
    counted as it is taken). The loop's trip count follows the run, so
    autodiff cannot pass it: the backward pass is the same walk, each
    trip's products made again and differentiated."""
    out, (given, walked) = _walk(
        functools.partial(_held_trip, impl=impl, act=act),
        jnp.zeros(run.x.shape, jnp.float32),
        (jnp.zeros_like(run.counts), jnp.int32(0)), run,
        sizes=trip_sizes, pairs=True, scope="moe_combine")
    return out, given, walked


def _held_experts_fwd(run, trip_sizes, impl, act):
    return _held_experts(run, trip_sizes, impl, act), run


def _held_experts_bwd(trip_sizes, impl, act, run, cotangents):
    f32 = jnp.float32
    stacks = (run.w_up, run.w_gate, run.w_down)
    if run.layer is not None:
        # the backward walk takes the layer's own weights, sliced once a
        # pass: their gradients are the layer's size while they are summed
        # over the trips, and are set into the stacks' once, after
        run = run._replace(w_up=run.w_up[run.layer],
                           w_gate=run.w_gate[run.layer],
                           w_down=run.w_down[run.layer])
    weights = (run.w_up, run.w_gate, run.w_down)
    (dx, d_gate), d_weights = _walk(
        functools.partial(_held_trip_bwd, impl=impl, act=act),
        (jnp.zeros(run.x.shape, f32), jnp.zeros(run.gate_vals.size, f32)),
        tuple(jnp.zeros(w.shape, f32) for w in weights),
        run._replace(layer=None), cotangents[0],
        sizes=trip_sizes, pairs=False, scope="moe_dispatch")
    dx = dx.astype(run.x.dtype)
    d_gate = d_gate.reshape(run.gate_vals.shape).astype(run.gate_vals.dtype)
    d_weights = tuple(d.astype(w.dtype) for d, w in zip(d_weights, weights))
    if run.layer is not None:
        d_weights = tuple(jnp.zeros_like(w).at[run.layer].set(d)
                          for d, w in zip(d_weights, stacks))
    return (_Run(dx, d_gate, *d_weights, None, None, None, None),)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def shared_expert_ffn(x: jax.Array, w_up: jax.Array, w_gate: jax.Array,
                      w_down: jax.Array, gate_w: Optional[jax.Array] = None,
                      *, dtype=jnp.bfloat16, act: str = "silu") -> jax.Array:
    """The expert every token passes through, beside the routed ones: a
    SwiGLU MLP (`act` on its gate projection, as the routed experts'),
    times a sigmoid gate of its own where `gate_w` is given.
    x: [B, S, D]; w_up, w_gate: [D, F]; w_down: [F, D]; gate_w: [D]."""
    with jax.named_scope("moe_shared"):
        up = jnp.einsum("bsd,df->bsf", x, w_up.astype(dtype))
        gate = jnp.einsum("bsd,df->bsf", x, w_gate.astype(dtype))
        out = jnp.einsum("bsf,fd->bsd", gate_activation(act)(gate) * up,
                         w_down.astype(dtype))
        if gate_w is None:
            return out.astype(dtype)
        open_ = jax.nn.sigmoid(jnp.einsum(
            "bsd,d->bs", x.astype(jnp.float32), gate_w.astype(jnp.float32)))
        return (out * open_[..., None].astype(dtype)).astype(dtype)


def _route(logits, top_k, norm_topk_prob, score, select_bias, route_scale,
           impl):
    """The router from its float32 logits [T, E] on (`moe_ffn` says what
    each argument means): every expert's score [T, E], and the K chosen
    experts' weights and indices [T, K]."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score {score!r}: 'softmax' or 'sigmoid'")
    probs = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    chosen_by = probs
    if select_bias is not None:
        chosen_by = probs + lax.stop_gradient(
            select_bias.astype(jnp.float32))
    # the selection's kernel takes a router of whole 128-lane tiles; a
    # narrower one (OLMoE's 64) keeps `lax.top_k` whatever kernels the
    # model runs
    gate_vals, expert_idx = router_topk(                        # [T, K]
        chosen_by, top_k,
        impl=impl if logits.shape[-1] % 128 == 0 else "reference")
    if select_bias is not None:
        gate_vals = jnp.take_along_axis(probs, expert_idx, axis=-1)
    if norm_topk_prob:
        total = gate_vals.sum(-1, keepdims=True)
        gate_vals = gate_vals / (total + 1e-20 if score == "sigmoid"
                                 else total)
    if route_scale != 1.0:
        gate_vals = gate_vals * route_scale
    return probs, gate_vals, expert_idx


def _by_expert(expert_idx, e, method="scan"):
    """The T x K (token, choice) pairs sorted by expert, stable: their
    order ([T x K] int32) and where each of the `e` experts' runs begins in
    it ([e + 1] int32). The sort hands back its keys with the order — the
    experts as sorted, without a gather of T x K elements through the order
    — and the runs' starts are found in them (`jnp.searchsorted`'s
    `method`): the counts without a scatter-add of T x K ones into E
    bins."""
    by_expert, order = lax.sort(
        (expert_idx.reshape(-1), jnp.arange(expert_idx.size, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(
        by_expert, jnp.arange(e + 1, dtype=by_expert.dtype), method=method)
    return order, bounds.astype(jnp.int32)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_gate: jax.Array, w_down: jax.Array, *,
            top_k: int = 2, norm_topk_prob: bool = True,
            first_expert: int = 0, dtype=jnp.bfloat16,
            impl: str = "auto", score: str = "softmax",
            select_bias: Optional[jax.Array] = None,
            route_scale: float = 1.0, layer=None,
            router_x: Optional[jax.Array] = None, act: str = "silu"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D]; router_w: [D, E]; w_up/w_gate: [E, D, F];
    w_down: [E, F, D] → ([B, S, D], aux), aux holding the load-balancing
    loss over all K choices (E * sum_e fraction_e * mean prob_e: K at uniform
    routing), the router z-loss (mean squared logsumexp of the router
    logits), the tokens each expert received ([E] int32, summing to
    T x K: nothing is dropped) and each token's chosen experts ([T, K]).

    `router_x` ([B, S, D]), where given, is what the router reads in x's
    place — the logits, the choice and the weights are its, the rows the
    experts multiply are x's (a model whose router stands ahead of its
    attention: `GPTConfig.moe_router_input`). `act` is the activation on
    the experts' gate projection, "silu" or "relu", in every path below.

    `score`: "softmax" over the E logits, or "sigmoid" of each. Both losses
    are defined on a softmax's distribution over the experts; a sigmoid
    router has none, and under it `aux` holds neither (what balances such a
    router is `select_bias`'s update between steps, which is no loss).
    `select_bias` ([E]): added to the scores for the choice of the K experts
    alone — the weights are the chosen experts' scores without it — and no
    gradient reaches it. `norm_topk_prob` divides a token's K weights by
    their sum (plus 1e-20 under a sigmoid, whose scores may all be 0);
    `route_scale` multiplies them after.

    Given the weights of H < E experts, the layer holds experts
    `first_expert` .. `first_expert + H` of the E it routes over (the
    module's docstring): the result is their part alone, the routing weights
    and both losses are over all E, `moe_expert_tokens` is still [E] — for a
    held expert the rows its matmuls were given, for an absent one the
    router's count — and `moe_routed_here` is the router's own count of the
    choices that fell on held experts, which the held entries must sum to.
    `impl` picks the form of the router's top-k (`ops.router_topk`; an E
    that is no multiple of 128 keeps `lax.top_k` under any `impl`), of the
    sum that returns the held experts' rows to token order
    (`ops.segment_sum`) and of the joined SwiGLU product's backward pass
    (`ops.swiglu`; an F that is no multiple of 128 keeps the `jnp` form), as
    it picks the other kernels.

    Given all E experts (the module's docstring has the why): a pair's
    routing weight multiplies inside the SwiGLU product, in float32, while
    its row is in expert order, so the combine is the dispatch's transpose
    — a permutation and a sum over K, whose derivative is the dispatch's
    K-fold gather of the cotangent. Up and gate are one grouped matmul over
    [E, D, 2F] exactly where the weights' dtype is not `dtype`, which is
    where a cast writes a copy of them anyway; weights in `dtype` are three
    matmuls on what lies there.

    With `layer` (an int, or a traced one under a scan over layers), the
    three expert weights are stacks [L, ...] of which this layer's are
    `w[layer]`, and the result is that of `moe_ffn` on those slices (on a
    TPU bit for bit: PERF.md, PR 54). A stack in `dtype` is read where it
    lies (`_swiglu_groups`); one in another is sliced on its way through the
    cast, which writes the layer's copy whatever it is handed.
    """
    if layer is not None and not (
            w_up.dtype == w_gate.dtype == w_down.dtype == jnp.dtype(dtype)):
        w_up, w_gate, w_down = w_up[layer], w_gate[layer], w_down[layer]
        layer = None
    b, s, d = x.shape
    e = router_w.shape[-1]
    n_tokens = b * s
    xf = x.reshape(n_tokens, d)

    with jax.named_scope("moe_router"):
        # float32 in truth: on a TPU a float32 matmul at the default
        # precision rounds its operands to bf16. `HIGHEST` is six bf16
        # passes for two float32 operands; where one is bf16 cast up, as
        # `_norm`'s output is, the compiler leaves out the passes that
        # would multiply its zero terms (PERF.md, PR 41: this product and
        # its dW run at three passes' time, dx at six)
        routed_by = xf if router_x is None else router_x.reshape(n_tokens, d)
        logits = jnp.dot(routed_by.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)       # [T, E]
        probs, gate_vals, expert_idx = _route(
            logits, top_k, norm_topk_prob, score, select_bias, route_scale,
            impl)

    def losses(fraction):
        """The softmax router's two, given the share of the tokens each
        expert received (sums to K); none under a sigmoid."""
        if score == "sigmoid":
            return {}
        return {"moe_aux_loss": e * jnp.sum(fraction * probs.mean(0)),
                "moe_router_z": jnp.mean(
                    jax.nn.logsumexp(logits, axis=-1) ** 2)}

    held = w_up.shape[-3]
    if held < e:
        return _moe_ffn_held(x, e, losses, gate_vals, expert_idx, w_up,
                             w_gate, w_down, first_expert, dtype, impl, layer,
                             act)

    with jax.named_scope("moe_dispatch"):
        # (the runs' starts by one pass of compares, not a loop of E-wide
        # steps: the pairs are few enough here)
        order, bounds = _by_expert(expert_idx, e, method="compare_all")
        inverse = jnp.argsort(order)
        group_sizes = bounds[1:] - bounds[:-1]
        rows = _take_rows(xf.astype(dtype), order, inverse, top_k)  # [T*K, D]
        weight = _permuted(gate_vals.astype(jnp.float32).reshape(-1),
                           order, inverse)

    expert_out = _swiglu_groups(rows, w_up, w_gate, w_down, group_sizes,
                                layer, weight, impl, act)

    with jax.named_scope("moe_combine"):
        out = _sum_rows(expert_out, order, inverse, top_k)

    aux = {
        **losses(group_sizes.astype(jnp.float32) / n_tokens),  # sums to K
        "moe_expert_tokens": group_sizes,
        "moe_expert_choice": expert_idx,
    }
    return out.reshape(b, s, d).astype(dtype), aux


def _moe_ffn_held(x, e, losses, gate_vals, expert_idx, w_up, w_gate, w_down,
                  first_expert, dtype, impl, layer, act="silu"):
    """`moe_ffn` from the routing on, for a layer that holds experts
    `first_expert` .. `first_expert + H` of the `e` routed over; `losses`
    gives the router's loss terms from the experts' shares of the tokens."""
    b, s, d = x.shape
    n_tokens, held = b * s, w_up.shape[-3]
    top_k = expert_idx.shape[-1]
    here = slice(first_expert, first_expert + held)

    with jax.named_scope("moe_dispatch"):
        order, bounds = _by_expert(expert_idx, e)
        routed = bounds[1:] - bounds[:-1]
        start = bounds[first_expert]
        trip_sizes = _held_trip_sizes(n_tokens * top_k, held / e)
        order = jnp.pad(order, (0, trip_sizes[0]))
    # the walk gathers (dispatch), multiplies (experts) and adds back
    # (combine) chunk by chunk, each under its scope
    out, given, walked = _held_experts(_Run(
        x.reshape(n_tokens, d).astype(dtype), gate_vals,
        w_up.astype(dtype), w_gate.astype(dtype), w_down.astype(dtype),
        order, start, routed[here],
        None if layer is None else jnp.asarray(layer, jnp.int32)),
        trip_sizes, impl, act)

    in_share = (expert_idx >= first_expert) & (
        expert_idx < first_expert + held)
    aux = {
        **losses(routed.astype(jnp.float32) / n_tokens),
        "moe_expert_tokens": routed.at[here].set(given),
        "moe_expert_choice": expert_idx,
        "moe_routed_here": in_share.sum().astype(jnp.int32),
        "moe_rows_walked": walked,
    }
    return out.reshape(b, s, d).astype(dtype), aux
