"""OLMoE, plain: forward pass and the three loss terms in float32 `jax.numpy`.

Follows the equations of Hugging Face's `olmoe` modelling code
(`OlmoeModel`, `OlmoeAttention`, `OlmoeSparseMoeBlock`,
`load_balancing_loss_func`) for the published `config.json` of
`allenai/OLMoE-1B-7B-0125-Instruct`: token embedding; pre-norm blocks of
RMSNorm (float32, `rms_norm_eps`, learned scale), causal multi-head attention
with an RMSNorm over the whole projected q and over the whole projected k
(all heads together) before rotary position embedding (`rope_theta`, the
rotate-half convention, scale 1/sqrt(head_dim)), and a sparse block: router
logits, softmax over all experts, the `num_experts_per_tok` largest weights
kept as the softmax gave them unless `norm_topk_prob`, every chosen expert a
SwiGLU MLP (down(silu(gate(x)) * up(x))), the results weighted and summed; a
final RMSNorm; an untied head. The experts are a Python loop over all
`num_experts` with a mask: every expert sees every token and the unchosen are
multiplied by zero — no sort, no gather, no kernel, no cache, no remat,
nothing from `ray_tpu/`. Matmuls run at
`jax.default_matmul_precision("highest")`, or a TPU would quietly do them in
bf16.

The loss terms, separately: `ce`, the mean cross-entropy of token t+1 given
tokens <= t over positions 0..S-2 of every row; `load_balance`, per layer
num_experts * sum_e f_e * P_e with f_e the share of the T x k (token, choice)
pairs of that layer that went to expert e times k (so it is k at uniform
routing) and P_e the mean router probability of e; `router_z`, per layer the
mean over tokens of logsumexp(router logits)^2 (Zoph et al. 2022, the
coefficient 0.001 is OLMoE's training recipe). Both are averaged over layers.

Departures from the modelling code, each noted because the system under test
makes it or the code has no such term:
- no linear layer has a bias (`attention_bias: false`; the experts and the
  router have none in the modelling code either), and `clip_qkv` is null;
- the vocabulary's padding rows (the config pads it to 50,304, a few more
  than the tokenizer has) are ordinary rows of the head here: they take part
  in the softmax, and the traffic never draws them as inputs or targets;
- `load_balancing_loss_func` concatenates the layers' router logits before
  taking its two means; here each layer's term is computed on its own and the
  layers are averaged, as the OLMoE paper's equation reads. With one layer
  (the benchmark's cell) the two are the same number;
- the router z-loss is not in the modelling code (it is in the paper's
  training recipe);
- weights are handed over as [in, out] (y = x @ W), the transpose of how
  `torch.nn.Linear` stores them, and the experts' stacked: [E, in, out].

One layer's weights, a dict:
    input_layernorm [d]   q_proj [d, H*hd]   k_proj v_proj [d, Hkv*hd]
    q_norm [H*hd]   k_norm [Hkv*hd]   o_proj [H*hd, d]
    post_attention_layernorm [d]   gate [d, E]   (the router)
    experts.gate_proj experts.up_proj [E, d, f]   experts.down_proj [E, f, d]
and `top`: `embed_tokens` [V, d], `norm` [d], `lm_head` [d, V].
`hparams` holds the published config's own keys: `num_attention_heads`,
`num_key_value_heads`, `num_experts_per_tok`, `norm_topk_prob`,
`rms_norm_eps`, `rope_theta`. Layers are taken one at a time so that a caller
can hand them over one at a time.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Mapping

import jax
import jax.numpy as jnp

_PRECISION = "highest"


def _rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight * (x / jnp.sqrt(variance + eps))


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: [B, H, S, hd]; positions 0..S-1."""
    s, hd = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [S, hd]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


@jax.jit
def embed(tokens, embed_tokens):
    """tokens [B, S] int -> [B, S, d] float32."""
    return embed_tokens.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv_head", "eps",
                                             "theta"))
def attention(x, w: Dict[str, Any], *, n_head: int, n_kv_head: int,
              eps: float, theta: float):
    """The attention half of a block, residual included. x: [B, S, d]."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        hd = w["q_proj"].shape[1] // n_head
        h = _rms_norm(x, w["input_layernorm"], eps)
        q = _rms_norm(h @ w["q_proj"], w["q_norm"], eps)
        k = _rms_norm(h @ w["k_proj"], w["k_norm"], eps)
        v = h @ w["v_proj"]
        q = _rope(q.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3), theta)
        k = _rope(k.reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3),
                  theta)
        v = v.reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3)
        k = jnp.repeat(k, n_head // n_kv_head, axis=1)
        v = jnp.repeat(v, n_head // n_kv_head, axis=1)
        scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        out = jax.nn.softmax(scores, axis=-1) @ v
        out = out.transpose(0, 2, 1, 3).reshape(b, s, n_head * hd)
        return x + out @ w["o_proj"]


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk_prob",
                                             "eps"))
def route(x, post_attention_layernorm, gate, *, top_k: int,
          norm_topk_prob: bool, eps: float):
    """The sparse block's input and routing, over the T = B*S tokens: the
    normed hidden states [T, d], the router logits [T, E], the chosen
    experts [T, k], and as dense [T, E] matrices the routing weights (zero
    where an expert was not chosen) and the mask of what was chosen."""
    with jax.default_matmul_precision(_PRECISION):
        d = x.shape[-1]
        h = _rms_norm(x, post_attention_layernorm.astype(jnp.float32),
                      eps).reshape(-1, d)
        logits = h @ gate.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, top_k)
        if norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        one_hot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=jnp.float32)
        dense = (one_hot * weights[..., None]).sum(1)
        return h, logits, chosen, dense, one_hot.sum(1) > 0


@jax.jit
def expert(h, gate_proj, up_proj, down_proj, weight, mask):
    """One expert on every token, times the token's weight for it, and zero
    for a token that did not choose it. h: [T, d]; weight, mask: [T]."""
    with jax.default_matmul_precision(_PRECISION):
        gate_proj, up_proj, down_proj = (
            m.astype(jnp.float32) for m in (gate_proj, up_proj, down_proj))
        out = (jax.nn.silu(h @ gate_proj) * (h @ up_proj)) @ down_proj
        return jnp.where(mask[:, None], out * weight[:, None], 0.0)


def block(x, w: Mapping[str, Any], hparams: Mapping[str, Any]):
    """One block. Returns (x, the layer's routing facts): `load_balance`
    and `router_z` (scalars), `counts` [E] and `chosen` [T, k]."""
    eps = float(hparams["rms_norm_eps"])
    top_k = int(hparams["num_experts_per_tok"])
    x = attention(x, {k: w[k] for k in (
        "input_layernorm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
        "o_proj")}, n_head=int(hparams["num_attention_heads"]),
        n_kv_head=int(hparams["num_key_value_heads"]), eps=eps,
        theta=float(hparams["rope_theta"]))
    h, logits, chosen, dense, mask = route(
        x, w["post_attention_layernorm"], w["gate"], top_k=top_k,
        norm_topk_prob=bool(hparams["norm_topk_prob"]), eps=eps)
    n_experts = logits.shape[-1]
    out = jnp.zeros_like(h)
    for e in range(n_experts):
        out = out + expert(h, w["experts.gate_proj"][e],
                           w["experts.up_proj"][e],
                           w["experts.down_proj"][e], dense[:, e],
                           mask[:, e])
    counts = mask.sum(0)
    probs = jax.nn.softmax(logits, axis=-1)
    # tokens_per_expert of the modelling code, summed over the k choices
    fraction = counts.astype(jnp.float32) / mask.shape[0]
    facts = {
        "load_balance": n_experts * jnp.sum(fraction * probs.mean(0)),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "counts": counts, "chosen": chosen}
    return x + out.reshape(x.shape), facts


@functools.partial(jax.jit, static_argnames=("eps",))
def head_loss(x, tokens, norm, lm_head, *, eps: float):
    """Final RMSNorm, untied head, and the mean next-token cross-entropy
    (nats) over positions 0..S-2 of every row. Returns (loss, logits)."""
    with jax.default_matmul_precision(_PRECISION):
        x = _rms_norm(x, norm.astype(jnp.float32), eps)
        logits = x @ lm_head.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return nll.mean(), logits


def loss_terms(tokens, top: Mapping[str, Any],
               layers: Iterable[Mapping[str, Any]],
               hparams: Mapping[str, Any]) -> Dict[str, Any]:
    """The whole model. Returns `ce`, `load_balance`, `router_z` (the three
    terms, unweighted), `logits`, and per layer `counts` ([L, E]) and
    `chosen` ([L, T, k])."""
    x = embed(tokens, top["embed_tokens"])
    facts = []
    for w in layers:
        x, layer_facts = block(x, w, hparams)
        facts.append(layer_facts)
    ce, logits = head_loss(x, tokens, top["norm"], top["lm_head"],
                           eps=float(hparams["rms_norm_eps"]))
    return {
        "ce": ce, "logits": logits,
        "load_balance": jnp.mean(jnp.stack(
            [f["load_balance"] for f in facts])),
        "router_z": jnp.mean(jnp.stack([f["router_z"] for f in facts])),
        "counts": jnp.stack([f["counts"] for f in facts]),
        "chosen": jnp.stack([f["chosen"] for f in facts])}
