"""Model FLOPs per token, and the flash kernels' operations and bytes.

The one definition of "model FLOPs" the benchmark uses (PERF.md section 2):
forward + backward of the matrix multiplications the model's equations need,
per token, for a dense decoder:

    6 x (matmul parameters of the blocks actually used)   qkv, out, up, down
  + 6 x d_model x vocab                                   the output head
  + 6 x n_layers x d_model x seq_len                      causal attention

Attention counts half the square (a query at position i sees i + 1 keys, so
S / 2 on average): 2 matmuls forward, 4 backward, 2 FLOPs a multiply-add,
d_model x S / 2 multiply-adds each -> 6 x d x S a layer. `seq_len` is the
run's row length, not the configuration's maximum. Recomputation (remat, the
flash kernels' re-made scores) is work the program chose, not work the model
needs: it is never counted. Embedding look-ups, norms, biases and the softmax
are left out (well under 1%). With sparse experts only the experts a token is
routed to count.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping


def _ff_dim(model: Mapping[str, Any]) -> int:
    if model.get("d_ff"):
        return int(model["d_ff"])
    d = int(model["d_model"])
    if model.get("activation", "gelu") == "swiglu":
        return (int(8 * d / 3) + 255) // 256 * 256
    return 4 * d


def matmul_params_per_layer(model: Mapping[str, Any]) -> int:
    """Parameters of one block's matrix multiplications that one token uses."""
    d = int(model["d_model"])
    heads = int(model["n_heads"])
    kv_heads = int(model.get("n_kv_heads") or heads)
    head_dim = d // heads
    attn = 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim
    mats = 3 if model.get("activation", "gelu") == "swiglu" else 2
    mlp = mats * d * _ff_dim(model)
    experts = int(model.get("n_experts") or 0)
    if experts:
        mlp = int(model.get("moe_top_k", 2)) * mlp + d * experts
    return attn + mlp


def model_flops_per_token(model: Mapping[str, Any], seq_len: int) -> float:
    """Forward + backward FLOPs the model needs for one token of a row of
    `seq_len` tokens (see the module docstring)."""
    d, layers = int(model["d_model"]), int(model["n_layers"])
    blocks = 6.0 * layers * matmul_params_per_layer(model)
    head = 6.0 * d * int(model["vocab_size"])
    attention = 6.0 * layers * d * seq_len
    return blocks + head + attention


def flash_attention_work(model: Mapping[str, Any], seq_len: int,
                         sequences: int, act_bytes: int = 2
                         ) -> Dict[str, float]:
    """What causal attention needs in one training step of `sequences` rows
    on one chip, over the layers that attend, from the configuration's own
    geometry: the "full" layers of its `layer_pattern` (every layer where it
    states none), `n_heads` query heads and `n_kv_heads` key and value heads
    of the head width it states (`d_head`, else d_model / n_heads). FLOPs:
    2 matmuls forward, 4 backward, half the square. Bytes, what must cross
    HBM at least once: forward reads q, k, v and writes o; backward reads q,
    k, v, o, do and writes dq, dk, dv (`act_bytes` each)."""
    pattern = tuple(model.get("layer_pattern") or ("full",))
    layers = int(model["n_layers"]) // len(pattern) * pattern.count("full")
    heads = int(model["n_heads"])
    kv_heads = int(model.get("n_kv_heads") or heads)
    width = int(model.get("d_head") or int(model["d_model"]) // heads)
    tokens = sequences * seq_len
    return {
        "flops": 6.0 * layers * heads * width * seq_len * tokens,
        "bytes": 6.0 * layers * (heads + kv_heads) * width * tokens
        * act_bytes,
    }


def forward_flops(model: Mapping[str, Any], lengths: Iterable[int]) -> float:
    """Forward-only FLOPs the model needs to score documents of these
    lengths, each alone (a served request: `loops/serve.py`): a third of the
    training count for every matmul (2 FLOPs a multiply-add in place of 6),
    attention over each document's own causal triangle —

        2 x (matmul parameters of the blocks + d_model x vocab) x L
      + 2 x n_layers x d_model x L^2

    Rows and row tails that a deployment pads are work it chose, not work
    the request needs: never counted, so `serve_mfu` and the flash kernel's
    served roofline read the same work whatever batches, buckets or kernels
    implement it."""
    d, layers = int(model["d_model"]), int(model["n_layers"])
    per_token = 2.0 * (layers * matmul_params_per_layer(model)
                       + d * int(model["vocab_size"]))
    return sum(per_token * n + 2.0 * layers * d * float(n) * n
               for n in lengths)


def flash_forward_work(model: Mapping[str, Any], lengths: Iterable[int],
                       act_bytes: int = 2) -> Dict[str, float]:
    """What causal attention needs, forward only, over documents of these
    lengths, each alone: the forward third of `flash_attention_work` (2 of
    its 6 matmuls; q, k, v read and o written once)."""
    total = {"flops": 0.0, "bytes": 0.0}
    for n in lengths:
        whole = flash_attention_work(model, int(n), 1, act_bytes)
        total["flops"] += whole["flops"] / 3.0
        total["bytes"] += whole["bytes"] / 3.0
    return total


def roofline_seconds(work: Mapping[str, float], peak: Mapping[str, float]
                     ) -> Dict[str, Any]:
    """The least time the chip could take for `work`, and which bound sets it."""
    compute = work["flops"] / peak["bf16_flops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory",
            "compute_s": compute, "memory_s": memory}
