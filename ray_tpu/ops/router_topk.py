"""The K largest of each row of a router's probabilities, as `lax.top_k`
gives them: values in descending order, and among equals the lowest index
first.

    values[t, j], idx[t, j] = the j-th largest of probs[t, :] and where it is

On a TPU `lax.top_k` of [32,768, 512] is a full sort of every row (PERF.md,
PR 32), and its backward pass a scatter of [T, K] values into T x E
elements. K is small beside E, so K rounds of (the row's maximum, the lowest
index that holds it, that entry blanked) read the rows once and do K passes
over a tile that stays in VMEM.

Two forms, chosen by `impl` (`ops/_impl.py`; the kernel at a row width of
whole 128-lane tiles, `lax.top_k` as the other form):

* `moe_topk_rounds`, a Pallas kernel. A grid step takes `_TOKENS` rows of all E
  probabilities and turns the tile token-minor, [E, tokens]: a row's
  maximum is then an elementwise maximum down the tile's vector registers
  and one reduction over a register's eight sublanes, where in the rows' own
  layout it would be a reduction across lanes for every row; and values and
  indices leave as [K, tokens] blocks, lane-dense, where [tokens, K] blocks
  would fill a tenth of their lanes. Blanked entries are -inf: the rows
  must not hold it (probabilities do not). The backward rule is `jnp`: K
  compares and selects an element, d probs[t, e] = g[t, j] where
  idx[t, j] == e, which the compiler fuses into the pass that reads it (the
  softmax's backward) — no scatter.
* `lax.top_k` with its own derivative: the reference the kernel is held to
  bit for bit, and what other backends, other widths and a mesh run (a
  Mosaic call is not partitioned automatically).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._impl import resolve_impl

# Rows a grid step. The tile is straight-line code over its vector
# registers (E x tokens / 1,024 of them a pass, 3 passes a round): fewer
# tokens are more grid steps, more are a longer program to compile.
_TOKENS = 256


def _topk_kernel(p_ref, v_ref, i_ref, *, k):
    """p_ref: [tokens, E]; v_ref, i_ref: [K rounded up to 8, tokens]."""
    p = p_ref[...].T                                        # [E, tokens]
    e = p.shape[0]
    expert = lax.broadcasted_iota(jnp.int32, p.shape, 0)
    for j in range(k):
        top = jnp.max(p, axis=0, keepdims=True)             # [1, tokens]
        # (a row that holds a NaN equals its maximum nowhere: the last index)
        at = jnp.min(jnp.where(p == top, expert, e - 1), axis=0,
                     keepdims=True)
        v_ref[j:j + 1, :] = top
        i_ref[j:j + 1, :] = at
        p = jnp.where(expert == at, -jnp.inf, p)
    if k < v_ref.shape[0]:
        v_ref[k:, :] = jnp.zeros_like(v_ref[k:, :])
        i_ref[k:, :] = jnp.zeros_like(i_ref[k:, :])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _topk_pallas(probs, k, interpret):
    n, e = probs.shape
    tokens = min(_TOKENS, -(-n // 128) * 128)
    steps = -(-n // tokens)
    rows = -(-k // 8) * 8
    out = pl.BlockSpec((rows, tokens), lambda t: (0, t))
    values, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(steps,),
        in_specs=[pl.BlockSpec((tokens, e), lambda t: (t, 0))],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((rows, steps * tokens), probs.dtype),
            jax.ShapeDtypeStruct((rows, steps * tokens), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="moe_topk_rounds",
    )(jnp.pad(probs, ((0, steps * tokens - n), (0, 0))))
    return values[:k, :n].T, idx[:k, :n].T


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _topk(probs, k, interpret):
    return _topk_pallas(probs, k, interpret)


def _topk_fwd(probs, k, interpret):
    values, idx = _topk_pallas(probs, k, interpret)
    # the row of expert numbers the backward rule compares with: a constant,
    # and what tells the rule E
    return (values, idx), (idx, lax.broadcasted_iota(
        jnp.int32, (1, probs.shape[1]), 1))


def _topk_bwd(k, interpret, res, cotangents):
    idx, expert = res
    g = cotangents[0]
    # a row's K indices are distinct: each element takes at most one
    d = jnp.zeros((idx.shape[0], expert.shape[1]), g.dtype)
    for j in range(k):
        d = jnp.where(expert == idx[:, j:j + 1], g[:, j:j + 1], d)
    return (d,)


_topk.defvjp(_topk_fwd, _topk_bwd)


def router_topk(probs: jax.Array, k: int, *, impl: str = "auto"
                ) -> Tuple[jax.Array, jax.Array]:
    """probs: [T, E] floats above -inf. Returns the K largest of each row
    ([T, K], descending) and their indices ([T, K] int32; among equals the
    lowest first), as `lax.top_k(probs, k)` does (module docstring).

    impl: as `ops._impl.resolve_impl` takes it, "reference" being
    `lax.top_k`; the kernel takes an E of whole 128-lane tiles."""
    impl = resolve_impl(impl, "router top-k", probs.shape[1])
    if impl == "reference":
        return lax.top_k(probs, k)
    return _topk(probs, k, impl == "pallas_interpret")
