"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT` with
periods of one "full" layer and then the "window" layers, routed experts in
every layer) in the layout `reference/smallthinker.py` takes. It only picks
a layer out of its stack, reshapes and renames — the program already stores
every matrix as [in, out] — one layer at a time on device 0, so nothing here
can hide a difference between the two models."""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

_NAMES = {
    "input_layernorm": "norm1", "post_attention_layernorm": "norm2",
    "block_sparse_moe.primary_router": "router",
    "block_sparse_moe.experts.gate": "w_gate",
    "block_sparse_moe.experts.up": "w_up",
    "block_sparse_moe.experts.down": "w_down"}


def _renamed(w):
    d = w["wq"].shape[0]
    return {
        **{name: w[ours] for name, ours in _NAMES.items()},
        "self_attn.q_proj": w["wq"].reshape(d, -1),
        "self_attn.k_proj": w["wk"].reshape(d, -1),
        "self_attn.v_proj": w["wv"].reshape(d, -1),
        "self_attn.o_proj": w["wo"].reshape(-1, d)}


def reference_weights(params, mesh, devices):
    """(top, an iterator over the layers' dicts): each period's layers in
    the published order — the order of the kinds' stacks says nothing of
    it, so it is read off the stacks' sizes as the program lays a period
    out: the "full" layer, then the "window" ones."""
    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
    take = jax.jit(
        lambda stack, period, i: _renamed(
            jax.tree_util.tree_map(lambda a: a[period, i], stack)),
        out_shardings=replicated)
    blocks = params["blocks"]
    periods, fulls = blocks["full"]["wq"].shape[:2]
    windows = blocks["window"]["wq"].shape[1]

    def layers():
        for period in range(periods):
            for kind, count in (("full", fulls), ("window", windows)):
                for i in range(count):
                    yield jax.device_put(take(blocks[kind], period, i),
                                         devices[0])

    top = jax.device_put(
        {"embed_tokens": params["tok_embed"], "norm": params["norm_f"],
         "lm_head": params["lm_head"]}, devices[0])
    return top, layers()
