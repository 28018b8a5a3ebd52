"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT` with
experts and QK-norm) in the layout `reference/olmoe.py` takes. It only
reshapes and renames — the program already stores every matrix as
[in, out] — one layer at a time on device 0, so nothing here can hide a
difference between the two models."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec


def reference_weights(params, mesh, devices):
    """(top, an iterator over the layers' dicts)."""

    def layer(blocks, i):
        w = {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
             for k, v in blocks.items()}
        d = w["wq"].shape[0]
        return {
            "input_layernorm": w["norm1"],
            "q_proj": w["wq"].reshape(d, -1),
            "k_proj": w["wk"].reshape(d, -1),
            "v_proj": w["wv"].reshape(d, -1),
            "q_norm": w["q_norm"].reshape(-1),
            "k_norm": w["k_norm"].reshape(-1),
            "o_proj": w["wo"].reshape(-1, d),
            "post_attention_layernorm": w["norm2"],
            "gate": w["router"],
            "experts.gate_proj": w["w_gate"],
            "experts.up_proj": w["w_up"],
            "experts.down_proj": w["w_down"],
        }

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
