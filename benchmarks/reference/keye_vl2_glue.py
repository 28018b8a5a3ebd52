"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT` with
the layer pattern ("sparse",)) in the layout `reference/keye_vl2.py` takes.
It only picks a layer out of the stack, reshapes and renames — the program
already stores every matrix as [in, out] — one layer at a time on device 0,
so nothing here can hide a difference between the two models."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

_SAME = {
    "input_layernorm": "norm1", "post_attention_layernorm": "norm2",
    "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
    "self_attn.indexer.wk": "wk_idx",
    "self_attn.indexer.k_norm.weight": "k_idx_norm",
    "self_attn.indexer.k_norm.bias": "k_idx_bias",
    "self_attn.indexer.weights_proj": "w_idx",
    "mlp.gate": "router", "mlp.experts.gate_proj": "w_gate",
    "mlp.experts.up_proj": "w_up", "mlp.experts.down_proj": "w_down"}


def reference_weights(params, mesh, devices):
    """(top, an iterator over the layers' dicts)."""

    def layer(blocks, i):
        w = {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
             for k, v in blocks.items()}
        d = w["wq"].shape[0]
        return {
            **{name: w[ours] for name, ours in _SAME.items()},
            "self_attn.q_proj": w["wq"].reshape(d, -1),
            "self_attn.k_proj": w["wk"].reshape(d, -1),
            "self_attn.v_proj": w["wv"].reshape(d, -1),
            "self_attn.o_proj": w["wo"].reshape(-1, d),
            "self_attn.indexer.wq": w["wq_idx"].reshape(d, -1)}

    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
    take = jax.jit(layer, out_shardings=replicated)
    n_layers = params["blocks"]["wq"].shape[0]
    top = jax.device_put(
        {"embed_tokens": params["tok_embed"], "norm": params["norm_f"],
         "lm_head": params["lm_head"]}, devices[0])
    layers = (jax.device_put(take(params["blocks"], jnp.int32(i)),
                             devices[0]) for i in range(n_layers))
    return top, layers
