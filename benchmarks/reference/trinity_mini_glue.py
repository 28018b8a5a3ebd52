"""Glue, not reference: the program's parameters (`ray_tpu.models.GPT` with
leading layers before periods of "window" and "full" layers, post-norms, an
output gate in `wq`, a selection bias and an ungated shared expert) in the
layout `reference/trinity_mini.py` takes. It only picks a layer out of its
stack, slices, reshapes and renames — the program already stores every
matrix as [in, out] — one layer at a time on device 0, so nothing here can
hide a difference between the two models. The program lays a head's gate
beside its query, the second half of `wq`'s last axis; the reference takes
the two projections apart."""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

_NORMS = {
    "input_layernorm": "norm1", "post_attention_layernorm": "norm1_post",
    "pre_mlp_layernorm": "norm2", "post_mlp_layernorm": "norm2_post",
    "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm"}
_DENSE = {"mlp.gate_proj": "w_gate", "mlp.up_proj": "w_up",
          "mlp.down_proj": "w_down"}
_ROUTED = {
    "mlp.router.gate": "router", "mlp.expert_bias": "router_bias",
    "mlp.experts.gate_proj": "w_gate", "mlp.experts.up_proj": "w_up",
    "mlp.experts.down_proj": "w_down",
    "mlp.shared_experts.gate_proj": "ws_gate",
    "mlp.shared_experts.up_proj": "ws_up",
    "mlp.shared_experts.down_proj": "ws_down"}


def _renamed(w):
    d, _, both = w["wq"].shape
    ffn = _ROUTED if "router" in w else _DENSE
    return {
        **{name: w[ours] for name, ours in {**_NORMS, **ffn}.items()},
        "self_attn.q_proj": w["wq"][:, :, :both // 2].reshape(d, -1),
        "self_attn.gate_proj": w["wq"][:, :, both // 2:].reshape(d, -1),
        "self_attn.k_proj": w["wk"].reshape(d, -1),
        "self_attn.v_proj": w["wv"].reshape(d, -1),
        "self_attn.o_proj": w["wo"].reshape(-1, d)}


def reference_weights(params, mesh, devices):
    """(top, an iterator over the layers' dicts): the leading layers, then
    each period's in the pattern's order — the order of the kinds' stacks
    says nothing of it, so the pattern is read off the stacks' sizes as the
    program lays a period out: the "window" layers, then the "full" one."""
    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
    take = jax.jit(
        lambda stack, period, i: _renamed(
            jax.tree_util.tree_map(lambda a: a[period, i], stack)),
        out_shardings=replicated)
    rename = jax.jit(_renamed, out_shardings=replicated)
    blocks = params["blocks"]
    periods, windows = blocks["window"]["wq"].shape[:2]
    fulls = blocks["full"]["wq"].shape[1]

    def layers():
        for w in params.get("lead", ()):
            yield jax.device_put(rename(w), devices[0])
        for period in range(periods):
            for kind, count in (("window", windows), ("full", fulls)):
                for i in range(count):
                    yield jax.device_put(take(blocks[kind], period, i),
                                         devices[0])

    top = jax.device_put(
        {"embed_tokens": params["tok_embed"], "norm": params["norm_f"],
         "lm_head": params["lm_head"]}, devices[0])
    return top, layers()
