"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT`,
dense, learned positions, tied head) in the layout `reference/gpt2.py` takes
(the checkpoints'). It only reshapes and renames, one layer at a time on
device 0, so nothing here can hide a difference between the two models."""

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
            "ln_1.g": w["norm1"], "ln_1.b": w["bias1"],
            "attn.c_attn.w": jnp.concatenate(
                [w[k].reshape(d, -1) for k in ("wq", "wk", "wv")], axis=1),
            "attn.c_proj.w": w["wo"].reshape(-1, d),
            "ln_2.g": w["norm2"], "ln_2.b": w["bias2"],
            "mlp.c_fc.w": w["w_up"], "mlp.c_proj.w": w["w_down"],
        }

    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
    take = jax.jit(layer, out_shardings=replicated)
    n_layers = params["blocks"]["wq"].shape[0]
    top = jax.device_put(
        {"wte": params["tok_embed"], "wpe": params["pos_embed"],
         "ln_f.g": params["norm_f"], "ln_f.b": params["bias_f"]},
        devices[0])
    layers = (jax.device_put(take(params["blocks"], jnp.int32(i)),
                             devices[0]) for i in range(n_layers))
    return top, layers
