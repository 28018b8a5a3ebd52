"""Mixture-of-Experts FFN, dropless, with expert parallelism.

The reference has NO native MoE/EP (SURVEY §2.4: "absent — only via
external frameworks"); here it's first-class. Softmax top-k routing with no
capacity and no dropped token: the T x K (token, expert) assignments are
sorted by expert (stable, so token order is kept inside a group), the rows
gathered into that order, and the three SwiGLU matmuls run as grouped
matmuls over the E ragged groups (`jax.lax.ragged_dot`: FLOPs and memory
grow with T x K, with no factor of E and no [T, E, C] tensor). The weighted
results return to token order through the inverse permutation and are summed
over K. An expert with no token is an empty group; an expert with many times
the mean is a long one. Expert weights carry a leading "expert" logical axis
sharded over the ``ep`` mesh axis.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


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


def moe_ffn(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_gate: jax.Array, w_down: jax.Array, *,
            top_k: int = 2, norm_topk_prob: bool = True,
            dtype=jnp.bfloat16) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D]; router_w: [D, E]; w_up/w_gate: [E, D, F];
    w_down: [E, F, D] → ([B, S, D], aux), aux holding the load-balancing
    loss over all K choices (E * sum_e fraction_e * mean prob_e: K at uniform
    routing), the router z-loss (mean squared logsumexp of the router
    logits), the tokens each expert received ([E] int32, summing to
    T x K: nothing is dropped) and each token's chosen experts ([T, K]).
    """
    b, s, d = x.shape
    e = router_w.shape[-1]
    n_tokens = b * s
    xf = x.reshape(n_tokens, d)

    with jax.named_scope("moe_router"):
        # float32 in truth: on a TPU a float32 matmul at the default
        # precision rounds its operands to bf16
        logits = jnp.dot(xf.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)       # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = lax.top_k(probs, top_k)         # [T, K]
        if norm_topk_prob:
            gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)

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
