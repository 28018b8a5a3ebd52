"""The gated delta rule over a sequence, chunked (Gated DeltaNet's mixer).

Per value head, with a state S of [keys, values] that starts at zero, a
decay a_t = exp(g_t) in (0, 1] and a write strength b_t in [0, 1]:

    S' = a_t S_{t-1}
    d_t = b_t (v_t - S'^T k_t)          what the state does not yet hold
    S_t = S' + k_t d_t^T
    o_t = S_t^T q_t

One position at a time that is S sequential rank-one updates; here the
sequence is cut into chunks of `CHUNK` positions and only the [keys, values]
state crosses a chunk's edge. With G_t the log-decay summed from the chunk's
start and S_0 the state entering the chunk, unrolling the recurrence gives

    (I + L) D = b (V - diag(e^G) K S_0)     L_tj = b_t e^{G_t - G_j} k_t.k_j, j < t
    O = diag(e^G) Q S_0 + tril(Q K^T e^{G_t - G_j}) D
    S_C = e^{G_C} S_0 + (e^{G_C - G} K)^T D

so a chunk is a handful of [C, C] and [C, d] products and one unit
lower-triangular solve. The solve is exact: with A = -L strictly lower,
A^C = 0 and (I - A)^-1 = (I + A)(I + A^2)(I + A^4)... in log2(C) factors,
kept in float32 (three bf16 passes a product beside bf16 inputs; the other
products take their operands in the inputs' dtype with float32 accumulation;
the state is float32). Every exponent is of a difference G_t - G_j with
t >= j, so nothing overflows at decays near 0, and at decays near 1 nothing
is divided by a small number. Rows whose length is no multiple of the chunk
are padded with positions that write nothing (b = 0, g = 0, zero q, k, v).

Two forms of that one algorithm, chosen by `impl` (`ops/_impl.py`):

* A Pallas kernel pair, `gdn_rule_fwd` and `gdn_rule_bwd`. A grid step is a
  batch row, `_BLOCK_KEY_HEADS` key heads with their value heads and
  `_BLOCK` positions of the row, the position axis innermost and
  sequential; the float32 states of those value heads live in VMEM scratch
  from the row's first chunk to its last, and a chunk's matrices (K K^T,
  Q K^T, the decay mask, the inverse, U, W, the delta) never leave VMEM.
  K K^T and Q K^T are made once for the value heads that share a key head.
  q, k, v and o are read and written as the projections hold them,
  [B, S, H * D]: a head is a block of lanes, so no transposed copy stands
  on either side. A step first makes what no state enters, for all its
  chunks and heads, the solves level by level side by side (six dependent
  levels of small products: alone, a solve leaves the matrix unit waiting),
  then walks the chunks from state to state. The matrix units take their
  products in program order, so every chain of dependent products is
  written across the heads, never head by head. The backward kernel takes
  the blocks last to first with dS in scratch: it reads the state that
  entered the block (the forward's residual, [B, Hv, S / `_BLOCK`, Dk, Dv]
  float32, a quarter of what a checkpointed scan of chunks keeps), walks
  the block's chunks forward again from it, then backward, and writes dq,
  dk, dv rounded once and dG, dbeta in float32; T = (I - A)^-1 has the
  closed derivative dA = T^T dT T^T. The `custom_vjp`'s primal is a forward
  that writes no states: under a block's rematerialisation the first
  forward pays nothing for residuals it drops.
* `gated_delta_rule_reference`: `jnp` chunks under one checkpointed
  `lax.scan`, the reference the kernels are held to and the form every
  other backend and every width that is no multiple of the 128 lanes runs.

On a v5e (PERF.md, PRs 33 and 46): the `jnp` form is bound by the count of
its small products and by the state crossing HBM three times a chunk; the
kernels by the matrix units' issue slots and, in the solves, by the spills.
"""

from __future__ import annotations

import functools
import itertools
import math
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

# A power of two (the solve squares its way up to it), two of which fit the
# matrix unit's depth; the `jnp` form's and both kernels' (on the v5e, PERF.md
# PR 46: ms a call at 64 / 128, forward 4.76 / 5.41, backward 9.60 / 9.47).
CHUNK = 64
# What a grid step of the kernels holds, as straight-line code for the
# scheduler to overlap: positions of the row (a chunk's solve waits for no
# state, so the solves of a step go level by level together) and key heads
# (their value heads' walks from state to state wait only for themselves).
_BLOCK = 256
_BLOCK_KEY_HEADS = 2


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, precision):
    """(I - a)^-1 for strictly lower-triangular a: [..., C, C] float32, C a
    power of two. Exact: a^C = 0, so the inverse is the product
    (I + a)(I + a^2)(I + a^4)... of log2(C) factors."""
    c = a.shape[-1]
    inverse = jnp.eye(c, dtype=a.dtype) + a
    power = a
    for _ in range(c.bit_length() - 2):
        power = jnp.matmul(power, power, precision=precision)
        inverse = inverse + jnp.matmul(inverse, power, precision=precision)
    return inverse


def _unit_lower_inverse_fwd(a, precision):
    inverse = _unit_lower_inverse(a, precision)
    return inverse, inverse


def _unit_lower_inverse_bwd(precision, inverse, d_inverse):
    # d(I - a)^-1 = T da T, so da = T^T dT T^T
    t = jnp.swapaxes(inverse, -1, -2)
    return (jnp.matmul(jnp.matmul(t, d_inverse, precision=precision), t,
                       precision=precision),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunk_step(state, xs, *, repeat: int):
    """One chunk of every (batch, value head). state: [B, H, Dk, Dv] f32;
    q, k: [B, H / repeat, C, Dk]; v: [B, H, C, Dv]; g, beta: [B, H, C]."""
    q, k, v, g, beta = xs
    dt = v.dtype
    f32 = jnp.float32
    # float32 inputs (the CPU tests) multiply in float32 throughout; the
    # solve is float32 either way, in three bf16 passes beside bf16 inputs
    prec = lax.Precision.HIGHEST if dt == f32 else None
    solve_prec = lax.Precision.HIGHEST if dt == f32 else lax.Precision.HIGH
    c = v.shape[-2]
    if repeat > 1:
        q, k = (jnp.repeat(x, repeat, axis=1) for x in (q, k))
    g, beta = g.astype(f32), beta.astype(f32)

    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(dt), b.astype(dt), precision=prec,
                          preferred_element_type=f32)

    total = jnp.cumsum(g, axis=-1)                              # G_t
    t_idx = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j_idx = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.exp(jnp.where(t_idx >= j_idx,
                              total[..., :, None] - total[..., None, :],
                              -jnp.inf))                        # 0 above diag
    e_total = jnp.exp(total)[..., None]

    solve = _unit_lower_inverse(
        jnp.where(t_idx > j_idx,
                  -beta[..., None] * mm("bhtd,bhjd->bhtj", k, k) * decay,
                  0.0), solve_prec)
    u = mm("bhtj,bhjd->bhtd", solve, beta[..., None] * v)
    w = mm("bhtj,bhjd->bhtd", solve, (beta[..., None] * e_total) * k)
    # W S and (e^G Q) S as one product: the state is read once for both
    from_state = mm("bhtk,bhkv->bhtv",
                    jnp.concatenate([w.astype(dt), (e_total * q).astype(dt)],
                                    axis=2), state)
    delta = u - from_state[:, :, :c]
    out = from_state[:, :, c:] + mm(
        "bhtj,bhjv->bhtv", mm("bhtd,bhjd->bhtj", q, k) * decay, delta)
    last = total[..., -1:]
    state = (jnp.exp(last)[..., None] * state
             + mm("bhtk,bhtv->bhkv", jnp.exp(last - total)[..., None] * k,
                  delta))
    return state, out.astype(dt)


def gated_delta_rule_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                               g: jax.Array, beta: jax.Array) -> jax.Array:
    """`gated_delta_rule` as `jnp` chunks under a checkpointed `lax.scan`:
    the backward pass keeps the state that entered each chunk and makes the
    chunk's products again."""
    b, s, hk, _ = q.shape
    hv = v.shape[2]
    pad = -s % CHUNK
    n = (s + pad) // CHUNK

    def chunks(x):      # [B, S, H, ...] -> [n, B, H, C, ...]
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, CHUNK, *x.shape[2:])
        return jnp.swapaxes(jnp.moveaxis(x, 1, 0), 2, 3)

    def step(state, xs):
        return _chunk_step(state, xs, repeat=hv // hk)

    state = jnp.zeros((b, hv, q.shape[-1], v.shape[-1]), jnp.float32)
    _, out = lax.scan(jax.checkpoint(step), state,
                      tuple(chunks(x) for x in (q, k, v, g, beta)))
    out = jnp.moveaxis(jnp.swapaxes(out, 2, 3), 0, 1)   # [B, n, C, Hv, Dv]
    return out.reshape(b, n * CHUNK, hv, -1)[:, :s]


# ---------------------------------------------------------------------------
# The Pallas kernels
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))      # a [m, n] x b [n, d] -> [m, d]
_NT = (((1,), (1,)), ((), ()))      # a [m, d] x b [n, d] -> [m, n]
_TN = (((0,), (0,)), ((), ()))      # a [n, m] x b [n, d] -> [m, d]


def _split(a):
    """The bf16 high and low parts of a float32 operand of the solve."""
    high = a.astype(jnp.bfloat16)
    return high, (a - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm_solve(a, b):
    """a x b of split operands, a [., C], in three bf16 passes, of which
    high x high and low x high are one product, [a_hi | a_lo] x [[b_hi],
    [b_hi]]: the matrix unit sums them along its depth of 128 in float32."""
    dot = functools.partial(lax.dot_general, dimension_numbers=_NN,
                            preferred_element_type=jnp.float32)
    return (dot(jnp.concatenate(a, axis=1), jnp.concatenate([b[0], b[0]]))
            + dot(a[0], b[1]))


class _Chunk:
    """What both kernels make of a block's chunks before any state enters:
    the products, in the precision of the module's docstring, and each value
    head's matrices. `dt` is the inputs' dtype. Matrices over two positions
    are [t, j] (row t, lane j) unless their name ends in `_t`."""

    def __init__(self, c, dt):
        self.c, self.dt, self.exact = c, dt, dt == jnp.float32
        row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        lane = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.lower, self.strict = row == lane, row >= lane, row > lane
        self.upper, self.strict_upper = row <= lane, row < lane
        self.left = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) < c
        self.split = (lambda a: (a,)) if self.exact else _split
        self.mm_solve = ((lambda a, b: self.mm(a[0], b[0])) if self.exact
                         else _mm_solve)

    def mm(self, a, b, dims=_NN):
        """Operands in the inputs' dtype, float32 accumulation."""
        precision = lax.Precision.HIGHEST if self.exact else None
        return lax.dot_general(a.astype(self.dt), b.astype(self.dt), dims,
                               precision=precision,
                               preferred_element_type=jnp.float32)

    def col(self, row):
        """[1, C] -> [C, 1] (positions from lanes to sublanes)."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, col):
        """[C, 1] -> [1, C]."""
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def heads(self, q_ref, k_ref, v_ref, g_ref, beta_ref, key_heads):
        """The block's chunks, first to last, each the list of its value
        heads: q, k: [m C, key_heads Dk] refs; v: [m C, Hv Dv]; g (G), beta:
        [Hv, m, C], Hv the value heads of the block's key heads."""
        f32, dt, c = jnp.float32, self.dt, self.c
        repeat = g_ref.shape[0] // key_heads
        dk, dv = q_ref.shape[1] // key_heads, v_ref.shape[1] // g_ref.shape[0]
        heads, chunks = [], range(q_ref.shape[0] // c)
        for i, j in itertools.product(chunks, range(key_heads)):
            rows, key_cols = slice(i * c, (i + 1) * c), slice(j * dk,
                                                              (j + 1) * dk)
            q, k = q_ref[rows, key_cols], k_ref[rows, key_cols]
            qf, kf = q.astype(f32), k.astype(f32)
            kk, qk = self.mm(k, k, _NT), self.mm(q, k, _NT)
            for r in range(j * repeat, (j + 1) * repeat):
                cols = slice(r * dv, (r + 1) * dv)
                heads.append(types.SimpleNamespace(
                    i=i, r=r, rows=rows, key_cols=key_cols, cols=cols,
                    last_of_key=r + 1 == (j + 1) * repeat,
                    q=q, k=k, qf=qf, kf=kf, kk=kk, qk=qk, **_matrices(
                        kk, qk, qf, kf, v_ref[rows, cols],
                        g_ref[r, i:i + 1, :], beta_ref[r, i:i + 1, :], dt=dt)))
        solves = _inverses_t([h.a_t for h in heads], dt=dt)
        for h, solve_t in zip(heads, solves):
            h.solve_t = solve_t
            uw = self.mm(solve_t, h.vk, _TN)
            h.u, h.w = uw[:, :dv], uw[:, dv:].astype(dt)
        return [[h for h in heads if h.i == i] for i in chunks]


@functools.partial(jax.jit, static_argnames="dt")
def _inverses_t(a_ts, *, dt):
    """((I - a)^-1)^T for each a^T of the list, as `_unit_lower_inverse`
    makes it, transposed: a power's square and the inverse's next factor
    share their left operand, so a level is one product against
    [inverse^T | power^T], and the list's matrices go level by level
    together: the scheduler fills one's waits with another's work. Jitted,
    as `_matrices`, for the trace's sake: the three kernels' traces share
    one of it, and an operation written out costs a busy worker 0.8 ms."""
    c = a_ts[0].shape[0]
    self = _Chunk(c, dt)
    powers = [self.split(a_t) for a_t in a_ts]
    powers = [self.mm_solve(p, p) for p in powers]
    both = [jnp.concatenate([jnp.where(self.eye, 1.0, a_t), p], axis=1)
            for a_t, p in zip(a_ts, powers)]
    for _ in range(c.bit_length() - 3):
        steps = [self.mm_solve(self.split(p), self.split(z))
                 for p, z in zip(powers, both)]
        both = [jnp.where(self.left, z + s, s) for z, s in zip(both, steps)]
        powers = [z[:, c:] for z in both]
    return [z[:, :c] + self.mm_solve(self.split(p), self.split(z[:, :c]))
            for p, z in zip(powers, both)]


@functools.partial(jax.jit, static_argnames="dt")
def _matrices(kk, qk, qf, kf, v, total, beta, *, dt):
    """A value head's matrices of one chunk; total (G), beta: [1, C]."""
    self = _Chunk(total.shape[1], dt)
    total_c, beta_c = self.col(total), self.col(beta)
    decay = jnp.exp(jnp.where(self.lower, total_c - total, -jnp.inf))
    decay_t = jnp.exp(jnp.where(self.upper, total - total_c, -jnp.inf))
    e_total = jnp.exp(total_c)                                  # [C, 1]
    k_gain = beta_c * e_total
    last = total_c[-1:]                                         # [1, 1]
    k_left = jnp.exp(last - total_c)                            # e^{G_C-G}
    return dict(
        v=v, beta_c=beta_c, decay=decay, e_total=e_total, k_gain=k_gain,
        k_left=k_left, e_last=jnp.exp(last),
        a_t=jnp.where(self.strict_upper, -beta * kk * decay_t, 0.0),
        # beta V and beta e^G K side by side: U, W are one product
        vk=jnp.concatenate([(beta_c * v.astype(jnp.float32)).astype(dt),
                            (k_gain * kf).astype(dt)], axis=1),
        qg=(e_total * qf).astype(dt), scores=qk * decay,
        k_decayed=(k_left * kf).astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                key_heads):
    """One batch row, `key_heads` key heads with their Hv value heads, `m`
    chunks of the row. q, k: [m C, key_heads Dk]; v, o: [m C, Hv Dv]; g (G,
    summed from each chunk's start), beta: [Hv, m, C]; states (the `fwd`
    rule's call only), scratch: [Hv, Dk, Dv], the state entering the block."""
    state_ref, states_ref = rest[-1], rest[0] if len(rest) > 1 else None
    ch = _Chunk(CHUNK, q_ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    if states_ref is not None:
        states_ref[...] = state_ref[...]
    for heads in ch.heads(q_ref, k_ref, v_ref, g_ref, beta_ref, key_heads):
        for h in heads:
            h.state = state_ref[h.r]
            # W S and (e^G Q) S as one product: the state is pushed once
            h.from_state = ch.mm(jnp.concatenate([h.w, h.qg], 0), h.state)
        for h in heads:
            h.delta = (h.u - h.from_state[:CHUNK]).astype(ch.dt)
            state_ref[h.r] = h.e_last * h.state + ch.mm(h.k_decayed, h.delta,
                                                        _TN)
        for h in heads:     # after what the next chunk waits for
            o_ref[h.rows, h.cols] = (h.from_state[CHUNK:] + ch.mm(
                h.scores, h.delta)).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref,
                walk_ref, *, key_heads):
    """The same blocks, taken last to first. do, dv: [m C, Hv Dv]; dq, dk:
    [m C, key_heads Dk], summed over a key head's value heads; dg (with
    respect to G), dbeta: [Hv, m, C] float32; scratch: dS [Hv, Dk, Dv]
    float32, of the state that leaves the chunk, and the states that entered
    the block's chunks, [Hv, m, Dk, Dv], walked again from the forward's."""
    f32, c, ch = jnp.float32, CHUNK, _Chunk(CHUNK, q_ref.dtype)
    mm, dt = ch.mm, ch.dt
    at_last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1

    def lanes(x):       # sum over a row's lanes, [C, d] -> [C, 1]
        return jnp.sum(x, axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    chunks = ch.heads(q_ref, k_ref, v_ref, g_ref, beta_ref, key_heads)
    heads = [h for chunk_heads in chunks for h in chunk_heads]
    walk_ref[:, 0] = states_ref[...]
    for chunk_heads in chunks:      # the forward's walk from the first state
        for h in chunk_heads:
            h.state = walk_ref[h.r, h.i]
            h.state_dt = h.state.astype(dt)
            h.delta = (h.u - mm(h.w, h.state_dt)).astype(dt)
        if chunk_heads is not chunks[-1]:
            for h in chunk_heads:
                walk_ref[h.r, h.i + 1] = h.e_last * h.state + mm(
                    h.k_decayed, h.delta, _TN)
    # what dS enters, last chunk first: O = (e^G Q) S + scores delta and
    # S_C = e^{G_C} S + k_decayed^T delta, with delta = U - W S
    for chunk_heads in reversed(chunks):
        for h in chunk_heads:
            h.do = do_ref[h.rows, h.cols]
            h.d_delta = mm(h.scores, h.do, _TN)
        for h in chunk_heads:
            h.dstate = dstate_ref[h.r]
            h.dstate_dt = h.dstate.astype(dt)
            h.d_delta = (h.d_delta + mm(h.k_decayed, h.dstate_dt)).astype(dt)
        for h in chunk_heads:
            dstate_ref[h.r] = h.e_last * h.dstate + mm(
                jnp.concatenate([h.qg, h.w], 0),
                jnp.concatenate([h.do, -h.d_delta], 0), _TN)
        for h in chunk_heads:       # off the chain
            h.d_k_decayed = mm(h.delta, h.dstate_dt, _NT)
            h.d_e_last = jnp.sum(lanes(h.dstate * walk_ref[h.r, h.i]), axis=0,
                                 keepdims=True)
    # the rest waits for no other chunk
    for h in heads:
        h.d_scores = mm(h.do, h.delta, _NT)
        h.d_qg = mm(h.do, h.state_dt, _NT)
        d_w = -mm(h.d_delta, h.state_dt, _NT)
        h.d_uw = jnp.concatenate([h.d_delta, d_w.astype(dt)], axis=1)
    # U, W = T [beta V, beta e^G K]; dA = T^T dT T^T
    for h in heads:
        h.d_vk = mm(h.solve_t, h.d_uw)
        h.d_solve = ch.split(mm(h.d_uw, h.vk, _NT))
        h.solve_split = ch.split(h.solve_t)
    d_as = [ch.split(ch.mm_solve(h.solve_split, h.d_solve)) for h in heads]
    d_as = [ch.mm_solve(d_a, h.solve_split) for d_a, h in zip(d_as, heads)]
    @jax.jit    # sixteen calls, one trace
    def rest(h, d_a, dq, dk):
        h = types.SimpleNamespace(**h)
        qf, kf, d_scores, d_qg, d_vk = h.qf, h.kf, h.d_scores, h.d_qg, h.d_vk
        left = lanes(h.d_k_decayed * h.k_left * kf)     # d(G_C - G_t)
        d_last = h.e_last * h.d_e_last + jnp.sum(left, axis=0, keepdims=True)
        dv = h.v.shape[1]
        d_vb, d_kb = d_vk[:, :dv], d_vk[:, dv:]
        # A = -beta K K^T decay below the diagonal
        through = jnp.where(ch.strict, d_a, 0.0) * h.decay
        d_kk = -h.beta_c * through
        d_qk = d_scores * h.decay
        # every exponent G_t - G_j: + its row's sum to t, - its column's to j
        exponents = d_kk * h.kk + d_scores * h.scores
        d_total_c = (lanes(exponents) - left + lanes(d_kb * h.k_gain * kf)
                     + lanes(d_qg * h.e_total * qf))
        d_total = (ch.row(d_total_c)
                   - jnp.sum(exponents, axis=0, keepdims=True)
                   + jnp.where(at_last, d_last, 0.0))
        d_beta_c = (lanes(d_vb * h.v.astype(f32))
                    + lanes(d_kb * kf) * h.e_total - lanes(through * h.kk))
        dq = dq + h.e_total * d_qg + mm(d_qk, h.k)
        dk = (dk + h.k_left * h.d_k_decayed + h.k_gain * d_kb
              + mm(d_kk, h.k) + mm(d_kk, h.k, _TN) + mm(d_qk, h.q, _TN))
        return (dq, dk, (h.beta_c * d_vb).astype(dv_ref.dtype), d_total,
                ch.row(d_beta_c))

    dq = dk = jnp.zeros(heads[0].q.shape, f32)
    for h, d_a in zip(heads, d_as):
        held = {n: x for n, x in vars(h).items() if isinstance(x, jax.Array)}
        (dq, dk, dv_ref[h.rows, h.cols], dg_ref[h.r, h.i:h.i + 1, :],
         dbeta_ref[h.r, h.i:h.i + 1, :]) = rest(held, d_a, dq, dk)
        if h.last_of_key:
            dq_ref[h.rows, h.key_cols] = dq.astype(dq_ref.dtype)
            dk_ref[h.rows, h.key_cols] = dk.astype(dk_ref.dtype)
            dq = dk = jnp.zeros_like(dq)


def _specs(q, v, reverse):
    """One call's tiling: the grid (batch, block of key heads, block of m
    chunks), the key heads a step, block specs by kind of array — rows of
    keys [B, S, Hk Dk], rows of values [B, S, Hv Dv], `gates` (g and beta)
    [B, Hv, S / block, m, C], `states` [B, Hv, S / block, Dk, Dv] — and
    the shapes of the gates, of the states and of one step's states.
    `reverse` walks the blocks last to first."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    chunk, m = CHUNK, min(_BLOCK, s) // CHUNK
    steps = s // (m * chunk)
    key_heads = math.gcd(_BLOCK_KEY_HEADS, hk)
    here = key_heads * hv // hk         # value heads a step

    def at(c):
        return steps - 1 - c if reverse else c

    specs = {
        "keys": pl.BlockSpec((None, m * chunk, key_heads * dk),
                             lambda i, j, c: (i, at(c), j)),
        "values": pl.BlockSpec((None, m * chunk, here * dv),
                               lambda i, j, c: (i, at(c), j)),
        "gates": pl.BlockSpec((None, here, None, m, chunk),
                              lambda i, j, c: (i, j, at(c), 0, 0)),
        "states": pl.BlockSpec((None, here, None, dk, dv),
                               lambda i, j, c: (i, j, at(c), 0, 0)),
    }
    return types.SimpleNamespace(
        specs=specs, grid=(b, hk // key_heads, steps), key_heads=key_heads,
        gates=(b, hv, steps, m, chunk), states=(b, hv, steps, dk, dv),
        held=(here, dk, dv))


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# The kernels' bodies are some thousands of operations written out: under
# `jax.jit` a model's layers, its loss and its evaluation trace them once.
@functools.partial(jax.jit, static_argnames=("interpret", "with_states"))
def _fwd_pallas(q, k, v, total, beta, *, interpret, with_states):
    """q, k: [B, S, Hk, Dk]; v: [B, S, Hv, Dv]; total, beta: [B, Hv, S]
    float32; S a multiple of the block. Returns o [B, S, Hv, Dv] and, with
    `with_states`, the states that entered the blocks."""
    t = _specs(q, v, False)
    specs = t.specs
    b, s = q.shape[:2]
    out_specs = [specs["values"]]
    out_shape = [jax.ShapeDtypeStruct((b, s, v.shape[2] * v.shape[3]),
                                      v.dtype)]
    if with_states:
        out_specs.append(specs["states"])
        out_shape.append(jax.ShapeDtypeStruct(t.states, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, key_heads=t.key_heads),
        grid=t.grid,
        in_specs=[specs["keys"], specs["keys"], specs["values"],
                  specs["gates"], specs["gates"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(t.held, jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="gdn_rule_fwd",
    )(q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1),
      total.reshape(t.gates), beta.reshape(t.gates))
    return (out[0].reshape(v.shape), *out[1:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_pallas(q, k, v, total, beta, states, do, *, interpret):
    """The gradients of q, k, v (their dtypes) and of total and beta
    (float32), from the states the forward kept and dO [B, S, Hv, Dv]."""
    t = _specs(q, v, True)
    specs = t.specs
    b, s = q.shape[:2]
    keys = jax.ShapeDtypeStruct((b, s, q.shape[2] * q.shape[3]), q.dtype)
    gate = jax.ShapeDtypeStruct(t.gates, jnp.float32)
    dq, dk, dv, dtotal, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, key_heads=t.key_heads),
        grid=t.grid,
        in_specs=[specs["keys"], specs["keys"], specs["values"],
                  specs["gates"], specs["gates"], specs["states"],
                  specs["values"]],
        out_specs=[specs["keys"], specs["keys"], specs["values"],
                   specs["gates"], specs["gates"]],
        out_shape=[keys, keys,
                   jax.ShapeDtypeStruct((b, s, v.shape[2] * v.shape[3]),
                                        v.dtype), gate, gate],
        scratch_shapes=[pltpu.VMEM(t.held, jnp.float32),
                        pltpu.VMEM((t.held[0], t.gates[3], *t.held[1:]),
                                   jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="gdn_rule_bwd",
    )(q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1),
      total.reshape(t.gates), beta.reshape(t.gates), states,
      do.reshape(b, s, -1))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dtotal.reshape(total.shape), dbeta.reshape(beta.shape))


def _scoped(fn):
    """The scopes the trace files the rule under (`GPT._linear_mixer`'s),
    kept inside the `custom_vjp`'s rules as well."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("attn_kernel"), jax.named_scope("gdn_rule"):
            return fn(*args, **kwargs)
    return scoped


def _chunk_sums(g, reverse=False):
    """g [B, Hv, S] summed from each chunk's start to each position (G) or,
    with `reverse`, from each position to its chunk's end (G's gradient)."""
    chunks = g.reshape(*g.shape[:2], -1, CHUNK)
    if reverse:
        chunks = jnp.flip(chunks, -1)
    sums = jnp.cumsum(chunks, axis=-1)
    return (jnp.flip(sums, -1) if reverse else sums).reshape(g.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    """q, k, v: [B, S, H, D]; g, beta: [B, Hv, S] float32; S whole blocks."""
    return _fwd_pallas(q, k, v, _chunk_sums(g), beta, interpret=interpret,
                       with_states=False)[0]


@_scoped
def _rule_fwd(q, k, v, g, beta, interpret):
    total = _chunk_sums(g)
    out, states = _fwd_pallas(q, k, v, total, beta, interpret=interpret,
                              with_states=True)
    return out, (q, k, v, total, beta, states)


@_scoped
def _rule_bwd(interpret, residuals, do):
    *grads, d_total, d_beta = _bwd_pallas(*residuals, do, interpret=interpret)
    return (*grads, _chunk_sums(d_total, reverse=True), d_beta)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _gated_delta_rule_pallas(q, k, v, g, beta, *, interpret):
    """Rows padded to whole blocks, g and beta as [B, Hv, S] float32 rows."""
    s = q.shape[1]
    pad = -s % CHUNK
    pad += -(s + pad) % min(_BLOCK, s + pad)

    def padded(x):
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))

    def head_rows(x):   # [B, S, Hv] -> [B, Hv, S] float32
        return jnp.swapaxes(padded(x.astype(jnp.float32)), 1, 2)

    out = _rule(padded(q), padded(k), padded(v), head_rows(g),
                head_rows(beta), interpret)
    return out[:, :s]


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, impl: str = "auto") -> jax.Array:
    """q, k: [B, S, Hk, Dk] (scaled and normalised by the caller); v:
    [B, S, Hv, Dv], key head j serving value heads j * Hv / Hk onwards;
    g (log of the decay, <= 0) and beta: [B, S, Hv]. Returns o:
    [B, S, Hv, Dv] in v's dtype.

    impl: as `ops._impl.resolve_impl` takes it; the kernels take key and
    value widths of whole 128-lane tiles."""
    assert v.shape[2] % q.shape[2] == 0, (q.shape, v.shape)
    impl = resolve_impl(impl, "delta rule", q.shape[-1], v.shape[-1])
    if impl == "reference":
        return gated_delta_rule_reference(q, k, v, g, beta)
    return _gated_delta_rule_pallas(q, k, v, g, beta,
                                    interpret=impl == "pallas_interpret")
