"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT` with
the layer pattern ("linear", "linear", "linear", "full")) in the layout
`reference/qwen3_next.py` takes. It only picks each layer's weights out of
its kind's stack, reshapes and renames — the program already stores every
matrix as [in, out], its projections' columns side by side as the reference
reads them — one layer at a time on device 0, so nothing here can hide a
difference between the two models."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

_SHARED = {
    "input_layernorm": "norm1", "post_attention_layernorm": "norm2",
    "mlp.gate": "router", "mlp.experts.gate_proj": "w_gate",
    "mlp.experts.up_proj": "w_up", "mlp.experts.down_proj": "w_down",
    "mlp.shared_expert.gate_proj": "ws_gate",
    "mlp.shared_expert.up_proj": "ws_up",
    "mlp.shared_expert.down_proj": "ws_down",
    "mlp.shared_expert_gate": "ws_open"}
_LINEAR = {
    "linear_attn.in_proj_qkvz": "w_qkvz", "linear_attn.in_proj_ba": "w_ba",
    "linear_attn.conv1d": "conv_w", "linear_attn.A_log": "A_log",
    "linear_attn.dt_bias": "dt_bias", "linear_attn.norm": "lin_norm",
    "linear_attn.out_proj": "w_lin_out"}


def reference_weights(params, mesh, devices):
    """(top, an iterator over the layers' dicts, in the model's order)."""

    def layer(stack, period, within):
        w = {k: lax.dynamic_index_in_dim(
            lax.dynamic_index_in_dim(v, period, 0, keepdims=False),
            within, 0, keepdims=False) for k, v in stack.items()}
        out = {name: w[ours] for name, ours in _SHARED.items()}
        if "wq" in w:
            d = w["wq"].shape[0]
            out.update({
                "self_attn.q_proj": w["wq"].reshape(d, -1),
                "self_attn.k_proj": w["wk"].reshape(d, -1),
                "self_attn.v_proj": w["wv"].reshape(d, -1),
                "self_attn.q_norm": w["q_norm"],
                "self_attn.k_norm": w["k_norm"],
                "self_attn.o_proj": w["wo"].reshape(-1, d)})
        else:
            out.update({name: w[ours] for name, ours in _LINEAR.items()})
        return out

    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
    take = jax.jit(layer, out_shardings=replicated)
    blocks = params["blocks"]
    per_period = {kind: next(iter(stack.values())).shape[1]
                  for kind, stack in blocks.items()}
    periods = next(iter(blocks["full"].values())).shape[0]
    # the published order: the linear layers of a period, then its full one
    order = ["linear"] * per_period["linear"] + ["full"] * per_period["full"]
    top = jax.device_put(
        {"embed_tokens": params["tok_embed"], "norm": params["norm_f"],
         "lm_head": params["lm_head"]}, devices[0])

    def layers():
        for period in range(periods):
            seen = dict.fromkeys(per_period, 0)
            for kind in order:
                yield jax.device_put(
                    take(blocks[kind], jnp.int32(period),
                         jnp.int32(seen[kind])), devices[0])
                seen[kind] += 1

    return top, layers()
