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
is divided by a small number.

The chunks run under one `lax.scan` whose body is checkpointed: the backward
pass keeps the state that entered each chunk (S / C states, not S) and makes
the chunk's products again. The solve has a backward rule of its own (with
T = (I - A)^-1, dA = T^T dT T^T: two products where autodiff would walk the
factors back). Rows whose length is no multiple of the chunk are padded with
positions that write nothing (b = 0, g = 0, zero q, k, v).

What bounds it on a v5e (PERF.md, PR 32): `jnp` products of [64, 64] and
[64, 128] matrices, a dozen microseconds each whatever their FLOPs, and the
float32 state crossing HBM three times a chunk; a Pallas kernel that keeps
the state and the chunk's matrices in VMEM is the next step, and what ships
sits under the scope `gdn_rule` for the trace to find.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64      # a power of two: the solve squares its way up to it


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


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array) -> jax.Array:
    """q, k: [B, S, Hk, Dk] (scaled and normalised by the caller); v:
    [B, S, Hv, Dv], key head j serving value heads j * Hv / Hk onwards;
    g (log of the decay, <= 0) and beta: [B, S, Hv]. Returns o:
    [B, S, Hv, Dv] in v's dtype."""
    b, s, hk, _ = q.shape
    hv = v.shape[2]
    assert hv % hk == 0, (hk, hv)
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
