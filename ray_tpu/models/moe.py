"""Mixture-of-Experts FFN, dropless.

The reference has NO native MoE/EP (SURVEY §2.4: "absent — only via
external frameworks"); here it's first-class. Softmax top-k routing with no
capacity and no dropped token. The router is float32 in truth: the logits
are a `HIGHEST` product of the activations cast up and every bit of the
router's weights (where the activations are bf16 the compiler leaves out
the passes that would multiply the cast's zero terms: three of six on a
v5e, PERF.md, PR 41), the softmax is over all E, and the K largest
probabilities of a token are `ops.router_topk`'s — K rounds of a maximum in
one Pallas kernel at an E of whole 128-lane tiles on a TPU, `lax.top_k`
elsewhere, the same values, indices and order among equals either way. The
T x K (token, expert) assignments are sorted by expert (stable, so token order is kept inside a group), the rows
gathered into that order, and the three SwiGLU matmuls run as grouped
matmuls over the E ragged groups (`jax.lax.ragged_dot`: FLOPs and memory
grow with T x K, with no factor of E and no [T, E, C] tensor). The weighted
results return to token order through the inverse permutation and are summed
over K. An expert with no token is an empty group; an expert with many times
the mean is a long one. Expert weights carry a leading "expert" logical axis
that the rules map to the ``ep`` mesh axis: an annotation the partitioner is
left to honour (no exchange of tokens between chips is written here).

A layer may hold only a share of its experts (`first_expert` and as many as
the weights it is given: one chip's part of a layer that several share). It
then routes over all of them, and computes the part of the result that its
own experts give: the (token, expert) pairs routed to them, found as one
contiguous run of the sorted order. That run has no static length, so it is
walked in chunks (`HELD_CHUNK_SHARE` times the run's length at balanced
routing) by a loop whose trip count is the run's length: every pair of the
run is gathered, multiplied and added to its token's row, however many there
are, and memory is one chunk's whatever the skew. Added how: a chunk's rows
come out of the grouped matmuls sorted by expert; one sort of the chunk's
token ids and one row gather put them in token order, where a token's rows
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
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.router_topk import router_topk
from ..ops.segment_sum import sorted_segment_sum


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x: jax.Array, order: jax.Array, inverse: jax.Array,
               copies: int):
    """Row j of the result is row `order[j] // copies` of x, where `order`
    is a permutation of range(rows x copies) and `inverse` its inverse: with
    one copy a permutation of the rows, with K each token's row once per
    choice, in the sorted order. The cotangent comes back through `inverse`
    and is summed over the copies: a gather and a reduction where autodiff
    would scatter-add."""
    return x[order // copies]


def _take_rows_fwd(x, order, inverse, copies):
    return x[order // copies], inverse


def _take_rows_bwd(copies, inverse, g):
    back = g[inverse]
    if copies > 1:
        back = back.reshape(-1, copies, g.shape[-1]).sum(
            1, dtype=jnp.float32).astype(g.dtype)
    return back, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# Pairs a trip of the held share's loop gathers, multiplies and adds back,
# as a multiple of the share's pairs at balanced routing (T x K x held / E):
# the static bound on its memory (rows x d_model), not on how many pairs it
# takes. At 2 a share takes one trip unless it runs hotter than twice the
# balance, so a step's time does not move with every swing of the routing.
HELD_CHUNK_SHARE = 2.0


def _swiglu_groups(rows, w_up, w_gate, w_down, sizes):
    """Rows sorted by expert through their experts: three grouped matmuls."""
    with jax.named_scope("moe_experts"):
        up = lax.ragged_dot(rows, w_up, sizes)
        gate = lax.ragged_dot(rows, w_gate, sizes)
        return lax.ragged_dot(jax.nn.silu(gate) * up, w_down, sizes)


def _held_chunk(c, x, gate_vals, order, start, counts, rows_per_chunk):
    """Trip `c` of the walk over the run of pairs routed to held experts
    (`counts` of them per held expert, the run starting at `start` of the
    sorted `order`, which is followed by a chunk's length of padding so that
    a chunk is a slice of it wherever it starts): the pairs' ids, tokens and
    routing weights, which of the chunk's rows are real, the rows of x, and
    how many of the chunk's rows each held expert takes."""
    with jax.named_scope("moe_dispatch"):
        top_k = gate_vals.shape[-1]
        ends = jnp.cumsum(counts)
        lo = c * rows_per_chunk
        valid = lo + jnp.arange(rows_per_chunk, dtype=jnp.int32) < ends[-1]
        pair = lax.dynamic_slice(order, (start + lo,), (rows_per_chunk,))
        token = pair // top_k
        weight = jnp.where(valid, gate_vals.reshape(-1)[pair], 0.0)
        sizes = jnp.clip(jnp.minimum(ends, lo + rows_per_chunk)
                         - jnp.maximum(ends - counts, lo), 0, None)
        return (pair, token, weight, valid, x[token],
                sizes.astype(jnp.int32))


def _sum_into_tokens(onto, c, rows, token, valid, weight=None, *, impl):
    """onto[t] plus the sum of weight x rows over the chunk's real rows of
    token t, in float32: trip `c`'s rows added to their tokens' rows. The
    chunk comes sorted by expert; one sort of its token ids (rows that are
    not real last) and one row gather put it in token order, and there a
    token's rows are a run, summed by `ops.segment_sum` as they stream past
    — where `onto.at[token].add(rows)` is a scatter, which on a TPU costs
    several such gathers. The first trip does not read `onto`."""
    ids, at, *weight = lax.sort(
        (jnp.where(valid, token, onto.shape[0]),
         jnp.arange(token.shape[0], dtype=jnp.int32),
         *(() if weight is None else (weight,))), num_keys=1)
    return sorted_segment_sum(rows[at], ids, onto.shape[0], *weight,
                              onto=(onto, c > 0), impl=impl)


def _held_chunk_rows(pairs: int, share: float) -> int:
    """Rows a trip of the walk: `HELD_CHUNK_SHARE` times the share's pairs
    at balance, in whole 1,024s, and no more than all the pairs."""
    rows = -(-int(HELD_CHUNK_SHARE * pairs * share) // 1024) * 1024
    return max(1, min(rows, pairs))


def _n_chunks(counts, rows_per_chunk):
    return (counts.sum() + rows_per_chunk - 1) // rows_per_chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _held_experts(x, gate_vals, w_up, w_gate, w_down, order, start, counts,
                  rows_per_chunk: int, impl: str):
    """The held experts' part of the block's result. x: [T, D]; gate_vals:
    [T, K] float32; the weights of the H held experts; `order` the (token,
    choice) pairs sorted by expert, `start` where the held experts' run
    begins in it and `counts` [H] how many pairs each takes. Returns the
    result in token order ([T, D] float32) and the rows each held expert was
    given ([H] int32, the grouped matmuls' own group sizes summed over the
    trips). The loop's trip count follows the run, so autodiff cannot pass
    it: the backward pass is the same walk, each chunk's products made again
    and differentiated."""
    def trip(c, carry):
        out, given = carry
        _, token, weight, valid, rows, sizes = _held_chunk(
            c, x, gate_vals, order, start, counts, rows_per_chunk)
        y = _swiglu_groups(rows, w_up, w_gate, w_down, sizes)
        with jax.named_scope("moe_combine"):
            return _sum_into_tokens(out, c, y, token, valid, weight,
                                    impl=impl), given + sizes

    return lax.fori_loop(
        0, _n_chunks(counts, rows_per_chunk), trip,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(counts)))


def _held_experts_fwd(x, gate_vals, w_up, w_gate, w_down, order, start,
                      counts, rows_per_chunk, impl):
    out = _held_experts(x, gate_vals, w_up, w_gate, w_down, order, start,
                        counts, rows_per_chunk, impl)
    return out, (x, gate_vals, w_up, w_gate, w_down, order, start, counts)


def _held_experts_bwd(rows_per_chunk, impl, res, cotangents):
    x, gate_vals, w_up, w_gate, w_down, order, start, counts = res
    d_out = cotangents[0]
    f32 = jnp.float32

    def trip(c, carry):
        dx, d_gate, d_weights = carry
        pair, token, weight, valid, rows, sizes = _held_chunk(
            c, x, gate_vals, order, start, counts, rows_per_chunk)
        y, pull = jax.vjp(
            lambda r, *w: _swiglu_groups(r, *w, sizes), rows, w_up, w_gate,
            w_down)
        with jax.named_scope("moe_combine"):
            dy = d_out[token]
            d_gate = d_gate.at[pair].add(jnp.where(
                valid, (dy * y.astype(f32)).sum(-1), 0.0))
            dy = jnp.where(valid[:, None], dy * weight[:, None],
                           0.0).astype(y.dtype)
        d_rows, *dw = pull(dy)
        with jax.named_scope("moe_dispatch"):
            dx = _sum_into_tokens(dx, c, d_rows, token, valid, impl=impl)
        return dx, d_gate, tuple(a + b.astype(f32)
                                 for a, b in zip(d_weights, dw))

    dx, d_gate, d_weights = lax.fori_loop(
        0, _n_chunks(counts, rows_per_chunk), trip,
        (jnp.zeros(x.shape, f32), jnp.zeros(gate_vals.size, f32),
         tuple(jnp.zeros(w.shape, f32) for w in (w_up, w_gate, w_down))))
    return (dx.astype(x.dtype), d_gate.reshape(gate_vals.shape).astype(
        gate_vals.dtype), *(d.astype(w.dtype) for d, w in zip(
            d_weights, (w_up, w_gate, w_down))), None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def shared_expert_ffn(x: jax.Array, w_up: jax.Array, w_gate: jax.Array,
                      w_down: jax.Array, gate_w: jax.Array, *,
                      dtype=jnp.bfloat16) -> jax.Array:
    """The expert every token passes through, beside the routed ones: a
    SwiGLU MLP times a sigmoid gate of its own. x: [B, S, D]; w_up, w_gate:
    [D, F]; w_down: [F, D]; gate_w: [D]."""
    with jax.named_scope("moe_shared"):
        up = jnp.einsum("bsd,df->bsf", x, w_up.astype(dtype))
        gate = jnp.einsum("bsd,df->bsf", x, w_gate.astype(dtype))
        out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                         w_down.astype(dtype))
        open_ = jax.nn.sigmoid(jnp.einsum(
            "bsd,d->bs", x.astype(jnp.float32), gate_w.astype(jnp.float32)))
        return (out * open_[..., None].astype(dtype)).astype(dtype)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_gate: jax.Array, w_down: jax.Array, *,
            top_k: int = 2, norm_topk_prob: bool = True,
            first_expert: int = 0, dtype=jnp.bfloat16,
            impl: str = "auto") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D]; router_w: [D, E]; w_up/w_gate: [E, D, F];
    w_down: [E, F, D] → ([B, S, D], aux), aux holding the load-balancing
    loss over all K choices (E * sum_e fraction_e * mean prob_e: K at uniform
    routing), the router z-loss (mean squared logsumexp of the router
    logits), the tokens each expert received ([E] int32, summing to
    T x K: nothing is dropped) and each token's chosen experts ([T, K]).

    Given the weights of H < E experts, the layer holds experts
    `first_expert` .. `first_expert + H` of the E it routes over (the
    module's docstring): the result is their part alone, the routing weights
    and both losses are over all E, `moe_expert_tokens` is still [E] — for a
    held expert the rows its matmuls were given, for an absent one the
    router's count — and `moe_routed_here` is the router's own count of the
    choices that fell on held experts, which the held entries must sum to.
    `impl` picks the form of the router's top-k (`ops.router_topk`; an E
    that is no multiple of 128 keeps `lax.top_k` under any `impl`) and of
    the sum that returns the held experts' rows to token order
    (`ops.segment_sum`), as it picks the other kernels.
    """
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
        logits = jnp.dot(xf.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)       # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        # the selection's kernel takes a router of whole 128-lane tiles; a
        # narrower one (OLMoE's 64) keeps `lax.top_k` whatever kernels the
        # model runs
        gate_vals, expert_idx = router_topk(                    # [T, K]
            probs, top_k, impl=impl if e % 128 == 0 else "reference")
        if norm_topk_prob:
            gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)

    held = w_up.shape[0]
    if held < e:
        return _moe_ffn_held(x, logits, probs, gate_vals, expert_idx, w_up,
                             w_gate, w_down, first_expert, dtype, impl)

    with jax.named_scope("moe_dispatch"):
        flat_expert = expert_idx.reshape(-1)                    # [T*K]
        order = jnp.argsort(flat_expert, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)
        rows = _take_rows(xf.astype(dtype), order, inverse, top_k)  # [T*K, D]

    with jax.named_scope("moe_experts"):
        up = lax.ragged_dot(rows, w_up.astype(dtype), group_sizes)
        gate = lax.ragged_dot(rows, w_gate.astype(dtype), group_sizes)
        act = jax.nn.silu(gate) * up
        expert_out = lax.ragged_dot(act, w_down.astype(dtype), group_sizes)

    with jax.named_scope("moe_combine"):
        back = _take_rows(expert_out, inverse, order, 1)
        out = jnp.einsum("tkd,tk->td", back.reshape(n_tokens, top_k, d),
                         gate_vals.astype(dtype))

    fraction = group_sizes.astype(jnp.float32) / n_tokens       # sums to K
    aux = {
        "moe_aux_loss": e * jnp.sum(fraction * probs.mean(0)),
        "moe_router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "moe_expert_tokens": group_sizes,
        "moe_expert_choice": expert_idx,
    }
    return out.reshape(b, s, d).astype(dtype), aux


def _moe_ffn_held(x, logits, probs, gate_vals, expert_idx, w_up, w_gate,
                  w_down, first_expert, dtype, impl):
    """`moe_ffn` from the routing on, for a layer that holds experts
    `first_expert` .. `first_expert + H` of the E routed over."""
    b, s, d = x.shape
    n_tokens, e, held = b * s, logits.shape[-1], w_up.shape[0]
    top_k = expert_idx.shape[-1]
    here = slice(first_expert, first_expert + held)

    with jax.named_scope("moe_dispatch"):
        # the sort hands back its keys with the order: the experts as
        # sorted, without a gather of T x K elements through the order
        by_expert, order = lax.sort(
            (expert_idx.reshape(-1),
             jnp.arange(n_tokens * top_k, dtype=jnp.int32)),
            num_keys=1, is_stable=True)
        # where each expert's run begins in the sorted order: the counts
        # without a scatter-add of T x K ones into E bins
        bounds = jnp.searchsorted(by_expert,
                                  jnp.arange(e + 1, dtype=by_expert.dtype)
                                  ).astype(jnp.int32)
        routed = bounds[1:] - bounds[:-1]
        start = bounds[first_expert]
        rows_per_chunk = _held_chunk_rows(n_tokens * top_k, held / e)
        order = jnp.pad(order, (0, rows_per_chunk))
    # the walk gathers (dispatch), multiplies (experts) and adds back
    # (combine) chunk by chunk, each under its scope
    out, given = _held_experts(
        x.reshape(n_tokens, d).astype(dtype), gate_vals,
        w_up.astype(dtype), w_gate.astype(dtype), w_down.astype(dtype),
        order, start, routed[here], rows_per_chunk, impl)

    fraction = routed.astype(jnp.float32) / n_tokens
    in_share = (expert_idx >= first_expert) & (
        expert_idx < first_expert + held)
    aux = {
        "moe_aux_loss": e * jnp.sum(fraction * probs.mean(0)),
        "moe_router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "moe_expert_tokens": routed.at[here].set(given),
        "moe_expert_choice": expert_idx,
        "moe_routed_here": in_share.sum().astype(jnp.int32),
    }
    return out.reshape(b, s, d).astype(dtype), aux
