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

The training objective (`training`, for `reference/train_steps.py`): `ce`
plus the output z-loss, z * mean(logsumexp(logits)^2) over the same
positions (the repo's form, `GPTConfig.z_loss`), plus the configuration's
coefficients times `load_balance` and `router_z`; the three coefficients are
the configuration's `reference.objective`. Over a batch in several passes the
share f_e is the whole batch's (`fraction`, given; it has no gradient), so
each pass adds its rows' part of E * sum_e f_e * P_e. For the backward pass
the experts are a `lax.scan` over the stacked weights, each expert's forward
run again when its gradient is taken (`jax.checkpoint`): the same sums in the
same order as the loop, less memory. `operands`, where given, is the type
every matmul's two operands are rounded to before they are multiplied in
float32 (bfloat16, float8_e4m3fn): the control of a path of lower precision,
never the reference.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Mapping

import jax
import jax.numpy as jnp

from benchmarks.reference.operands import mm as _mm

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


def _attention(x, w: Dict[str, Any], *, n_head: int, n_kv_head: int,
               eps: float, theta: float, operands=None):
    """The attention half of a block, residual included. x: [B, S, d]."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        hd = w["q_proj"].shape[1] // n_head
        h = _rms_norm(x, w["input_layernorm"], eps)
        q = _rms_norm(_mm(h, w["q_proj"], operands), w["q_norm"], eps)
        k = _rms_norm(_mm(h, w["k_proj"], operands), w["k_norm"], eps)
        v = _mm(h, w["v_proj"], operands)
        q = _rope(q.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3), theta)
        k = _rope(k.reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3),
                  theta)
        v = v.reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3)
        k = jnp.repeat(k, n_head // n_kv_head, axis=1)
        v = jnp.repeat(v, n_head // n_kv_head, axis=1)
        scores = _mm(q, k.transpose(0, 1, 3, 2), operands) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        out = _mm(jax.nn.softmax(scores, axis=-1), v, operands)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, n_head * hd)
        return x + _mm(out, w["o_proj"], operands)


attention = jax.jit(_attention, static_argnames=(
    "n_head", "n_kv_head", "eps", "theta", "operands"))


def _route(x, post_attention_layernorm, gate, *, top_k: int,
           norm_topk_prob: bool, eps: float, operands=None):
    """The sparse block's input and routing, over the T = B*S tokens: the
    normed hidden states [T, d], the router logits [T, E], the chosen
    experts [T, k], and as dense [T, E] matrices the routing weights (zero
    where an expert was not chosen) and the mask of what was chosen."""
    with jax.default_matmul_precision(_PRECISION):
        d = x.shape[-1]
        h = _rms_norm(x, post_attention_layernorm.astype(jnp.float32),
                      eps).reshape(-1, d)
        logits = _mm(h, gate.astype(jnp.float32), operands)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, top_k)
        if norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        one_hot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=jnp.float32)
        dense = (one_hot * weights[..., None]).sum(1)
        return h, logits, chosen, dense, one_hot.sum(1) > 0


route = jax.jit(_route, static_argnames=("top_k", "norm_topk_prob", "eps",
                                         "operands"))


def _expert(h, gate_proj, up_proj, down_proj, weight, mask, operands=None):
    """One expert on every token, times the token's weight for it, and zero
    for a token that did not choose it. h: [T, d]; weight, mask: [T]."""
    with jax.default_matmul_precision(_PRECISION):
        gate_proj, up_proj, down_proj = (
            m.astype(jnp.float32) for m in (gate_proj, up_proj, down_proj))
        out = _mm(jax.nn.silu(_mm(h, gate_proj, operands))
                  * _mm(h, up_proj, operands), down_proj, operands)
        return jnp.where(mask[:, None], out * weight[:, None], 0.0)


expert = jax.jit(_expert, static_argnames=("operands",))


def _block(x, w: Mapping[str, Any], hparams: Mapping[str, Any],
           fraction=None, operands=None):
    """`block`, traced as one: the experts a scan over the stacked weights,
    in the loop's order. `fraction` [E], where given, stands for this call's
    own share of the pairs in `load_balance` (a batch in several passes)."""
    eps = float(hparams["rms_norm_eps"])
    top_k = int(hparams["num_experts_per_tok"])
    x = _attention(x, {k: w[k] for k in (
        "input_layernorm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
        "o_proj")}, n_head=int(hparams["num_attention_heads"]),
        n_kv_head=int(hparams["num_key_value_heads"]), eps=eps,
        theta=float(hparams["rope_theta"]), operands=operands)
    h, logits, chosen, dense, mask = _route(
        x, w["post_attention_layernorm"], w["gate"], top_k=top_k,
        norm_topk_prob=bool(hparams["norm_topk_prob"]), eps=eps,
        operands=operands)
    n_experts = logits.shape[-1]
    one = jax.checkpoint(functools.partial(_expert, operands=operands))

    def add_expert(out, e):
        gate_proj, up_proj, down_proj, weight, chose = e
        return out + one(h, gate_proj, up_proj, down_proj, weight, chose), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        w["experts.gate_proj"], w["experts.up_proj"], w["experts.down_proj"],
        dense.T, mask.T))
    counts = mask.sum(0)
    probs = jax.nn.softmax(logits, axis=-1)
    # tokens_per_expert of the modelling code, summed over the k choices
    if fraction is None:
        fraction = counts.astype(jnp.float32) / mask.shape[0]
    facts = {
        "load_balance": n_experts * jnp.sum(fraction * probs.mean(0)),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "counts": counts, "chosen": chosen}
    return x + out.reshape(x.shape), facts


_HPARAMS = ("num_attention_heads", "num_key_value_heads",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
            "rope_theta")


@functools.partial(jax.jit, static_argnames=("hparams", "operands"))
def _block_jit(x, w, fraction, *, hparams, operands):
    return _block(x, w, dict(hparams), fraction, operands)


def block(x, w: Mapping[str, Any], hparams: Mapping[str, Any],
          fraction=None, operands=None):
    """One block. Returns (x, the layer's routing facts): `load_balance`
    and `router_z` (scalars), `counts` [E] and `chosen` [T, k]."""
    return _block_jit(x, dict(w), fraction, hparams=tuple(
        (k, hparams[k]) for k in _HPARAMS), operands=operands)


def _head_terms(x, tokens, norm, lm_head, *, eps: float, operands=None):
    """Final RMSNorm, untied head; over positions 0..S-2 of every row the
    mean next-token cross-entropy (nats) and the mean squared log-sum-exp of
    the logits (what the output z-loss multiplies). Returns (ce, lse2,
    logits)."""
    with jax.default_matmul_precision(_PRECISION):
        x = _rms_norm(x, norm.astype(jnp.float32), eps)
        logits = _mm(x, lm_head.astype(jnp.float32), operands)
        lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        target = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                     axis=-1)[..., 0]
        return (lse - target).mean(), (lse ** 2).mean(), logits


@functools.partial(jax.jit, static_argnames=("eps",))
def head_loss(x, tokens, norm, lm_head, *, eps: float):
    """The mean next-token cross-entropy and the logits."""
    ce, _, logits = _head_terms(x, tokens, norm, lm_head, eps=eps)
    return ce, logits


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


def training(config: Mapping[str, Any], operands=None) -> Dict[str, Any]:
    """The model in the pieces `reference/train_steps.py` differentiates one
    at a time: `embed(top, tokens)`, `block(index)(w, x, fraction) -> (x,
    terms, facts)` and `head(top, x, tokens) -> (ce, lse2)`. `config` is the
    configuration's file, whose top level holds the published keys."""
    hparams = {k: config[k] for k in _HPARAMS}

    def one_block(w, x, fraction):
        x, facts = _block(x, w, hparams, fraction, operands)
        return (x, {k: facts[k] for k in ("load_balance", "router_z")},
                {k: facts[k] for k in ("counts", "chosen")})

    def head(top, x, tokens):
        return _head_terms(x, tokens, top["norm"], top["lm_head"],
                           eps=float(hparams["rms_norm_eps"]),
                           operands=operands)[:2]

    return {"embed": lambda top, tokens: embed(tokens, top["embed_tokens"]),
            "block": lambda index: one_block, "head": head, "routes": True}
