"""Qwen3-Next, plain: forward pass and the loss terms in float32 `jax.numpy`.

Follows the equations of Hugging Face's `qwen3_next` modelling code
(`Qwen3NextModel`, `Qwen3NextAttention`, `Qwen3NextGatedDeltaNet` with
`torch_recurrent_gated_delta_rule`, `Qwen3NextSparseMoeBlock`,
`Qwen3NextRMSNorm`, `Qwen3NextRMSNormGated`) for the published `config.json`
of `Qwen/Qwen3-Next-80B-A3B-Instruct`. Every layer is
`x <- x + mixer(norm(x)); x <- x + moe(norm(x))`; layer i (from 0) is full
attention where (i + 1) % full_attention_interval == 0 and Gated DeltaNet
otherwise. RMSNorm is `x / sqrt(mean(x^2) + eps) * (1 + w)` for the layer
norms, the final norm and the q / k head norms (`rms_norm_eps`).

Gated full attention: `q_proj` gives, per head, `head_dim` of query then
`head_dim` of gate; q and k take an RMSNorm over each head's width (one
scale for all query heads, one for the KV heads); rotary embedding
(`rope_theta`, rotate-half) on the first `partial_rotary_factor` of the
width, the rest passes; a masked [S, S] score matrix a head, scale
1 / sqrt(head_dim), each KV head serving num_attention_heads /
num_key_value_heads query heads; the result times sigmoid(gate), then
`o_proj`.

Gated DeltaNet: `in_proj_qkvz` gives q~ | k~ | v~ | z side by side and
`in_proj_ba` gives b | a. (q~, k~, v~) pass a causal depthwise convolution
over time, `linear_conv_kernel_dim` taps, zeros before the row's start,
written as one shifted product a tap, then SiLU. `linear_num_key_heads` query
and key heads of `linear_key_head_dim`, `linear_num_value_heads` value heads
of `linear_value_head_dim`, key head j serving value heads
j * (value heads / key heads) onwards. q <- q / sqrt(sum q^2 + 1e-6) /
sqrt(key width), k <- k / sqrt(sum k^2 + 1e-6). Per value head, with
beta_t = sigmoid(b_t), g_t = -exp(A_log) * softplus(a_t + dt_bias) and a
state S of [keys, values] that starts at zero, ONE POSITION AT A TIME:

    S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;   o_t = S_t^T q_t

then `RMSNorm(o_t; scale u, not zero-centred) * SiLU(z_t)` per head and
`out_proj`.

Expert block: softmax of the router's logits over all its outputs in
float32, the `num_experts_per_tok` largest rescaled to sum to 1
(`norm_topk_prob`), every chosen expert a SwiGLU MLP, weighted and summed;
plus sigmoid(h . shared_expert_gate) * shared SwiGLU expert. Every token goes
through every expert that is given, times zero where it was not chosen — no
sort, no gather, no chunking, no kernel, nothing from `ray_tpu/`. Matmuls run
at `jax.default_matmul_precision("highest")`.

A share of the experts. The router's width is that of `mlp.gate`; the expert
weights given may be fewer: experts `first_expert_held` .. + H of the
router's outputs (a chip's part of a layer that several chips share). The
routing, its weights and the balance term are over all of the router's
outputs; the sum runs over the chosen experts that are among the H given;
what the absent experts would add is left out. `loss_terms` takes the first
held expert from `hparams["first_expert_held"]` (0 where absent).

The loss terms: `ce`, the mean cross-entropy of token t+1 given tokens <= t
over positions 0..S-2 of every row; `load_balance`, per layer E * sum_e f_e
P_e with f_e the share of the T x k (token, choice) pairs that went to expert
e times k and P_e the mean router probability of e, averaged over layers.

Departures from the modelling code and the checkpoints, each noted:
- no multi-token-prediction module (the published `config.json` has no key
  for it);
- `in_proj_qkvz` and `in_proj_ba` hold their outputs side by side (q~ | k~ |
  v~ | z and b | a), where the checkpoints interleave them per key head: a
  permutation of columns;
- the convolution's weight is [taps, channels], tap i multiplying the input
  `taps - 1 - i` positions back (torch's conv1d weight [channels, 1, taps],
  transposed);
- the vocabulary's rows are those given (a slice of the published ones);
- document boundaries are ignored: attention and the state cross them;
- `load_balancing_loss_func` concatenates the layers before its means; here
  each layer's term is computed alone and the layers are averaged;
- weights are [in, out] (y = x @ W), the experts' stacked [H, in, out].

The training objective (`training`, for `reference/train_steps.py`): `ce`
plus the output z-loss, z * mean(logsumexp(logits)^2) over the same
positions (the repo's form, `GPTConfig.z_loss`), plus the configuration's
coefficient times `load_balance`; both coefficients are the configuration's
`reference.objective`. Over a batch in several passes the share f_e is the
whole batch's (`fraction`, given; it has no gradient). What is done so that
a backward pass fits beside 10 GB of parameters, gradient and moments, none
of which changes a sum or its order: the recurrence is a scan over segments
of `_SEGMENT` positions, each segment's positions run again when its gradient
is taken (`jax.checkpoint`; the plain scan would keep a 2 MB state a
position, 17 GB a row of 8,192); a head's score matrix is made again for its
gradient; the experts are a `lax.scan` over the stacked weights, each
expert's forward run again. `operands`, where given, is the type every
matmul's two operands are rounded to before they are multiplied in float32
(bfloat16, float8_e4m3fn; the recurrence's own sums stay float32): the
control of a path of lower precision, never the reference.

One layer's weights, a dict. Both kinds:
    input_layernorm [d]   post_attention_layernorm [d]   mlp.gate [d, E]
    mlp.experts.gate_proj mlp.experts.up_proj [H, d, f]
    mlp.experts.down_proj [H, f, d]
    mlp.shared_expert.gate_proj mlp.shared_expert.up_proj [d, fs]
    mlp.shared_expert.down_proj [fs, d]   mlp.shared_expert_gate [d]
full attention:
    self_attn.q_proj [d, heads * 2 * hd]   self_attn.k_proj v_proj [d, kv * hd]
    self_attn.q_norm self_attn.k_norm [hd]   self_attn.o_proj [heads * hd, d]
Gated DeltaNet:
    linear_attn.in_proj_qkvz [d, 2 * keys + 2 * values]
    linear_attn.in_proj_ba [d, 2 * value heads]
    linear_attn.conv1d [taps, 2 * keys + values]
    linear_attn.A_log linear_attn.dt_bias [value heads]
    linear_attn.norm [value width]   linear_attn.out_proj [values, d]
and `top`: `embed_tokens` [V, d], `norm` [d], `lm_head` [d, V]. `hparams`
holds the published config's own keys.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Mapping

import jax
import jax.numpy as jnp

from benchmarks.reference.operands import mm as _mm

_PRECISION = "highest"
_L2_EPS = 1e-6
_SEGMENT = 64       # positions of the recurrence run again as one piece


def _rms_norm(x, weight, eps):
    """The model's own: the scale is 1 + weight."""
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return (1.0 + weight) * (x / jnp.sqrt(variance + eps))


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta, fraction):
    """x: [B, H, S, hd]; positions 0..S-1; the first `fraction` of hd
    turns, the rest passes."""
    s, hd = x.shape[-2], x.shape[-1]
    rot = int(hd * fraction)
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [S, rot]
    turned = x[..., :rot] * jnp.cos(emb) + _rotate_half(
        x[..., :rot]) * jnp.sin(emb)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


@jax.jit
def embed(tokens, embed_tokens):
    """tokens [B, S] int -> [B, S, d] float32."""
    return embed_tokens.astype(jnp.float32)[tokens]


def _full_attention(x, w: Dict[str, Any], *, n_head: int, n_kv_head: int,
                    eps: float, theta: float, fraction: float,
                    operands=None):
    """The gated softmax-attention half of a block, residual included."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        hd = w["self_attn.q_norm"].shape[0]
        h = _rms_norm(x, w["input_layernorm"], eps)
        q_gate = _mm(h, w["self_attn.q_proj"], operands).reshape(
            b, s, n_head, 2 * hd)
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        k = _mm(h, w["self_attn.k_proj"], operands).reshape(
            b, s, n_kv_head, hd)
        v = _mm(h, w["self_attn.v_proj"], operands).reshape(
            b, s, n_kv_head, hd)
        q = _rms_norm(q, w["self_attn.q_norm"], eps).transpose(0, 2, 1, 3)
        k = _rms_norm(k, w["self_attn.k_norm"], eps).transpose(0, 2, 1, 3)
        q, k = _rope(q, theta, fraction), _rope(k, theta, fraction)
        v = v.transpose(0, 2, 1, 3)
        k = jnp.repeat(k, n_head // n_kv_head, axis=1)
        v = jnp.repeat(v, n_head // n_kv_head, axis=1)
        causal = jnp.tril(jnp.ones((s, s), bool))

        @jax.checkpoint
        def one_head(qkv):      # a float32 [S, S] score matrix at a time
            qh, kh, vh = qkv                                # [B, S, hd]
            scores = _mm(qh, kh.transpose(0, 2, 1), operands) / math.sqrt(hd)
            scores = jnp.where(causal, scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), vh, operands)

        out = jax.lax.map(one_head, tuple(
            t.transpose(1, 0, 2, 3) for t in (q, k, v)))    # [H, B, S, hd]
        out = out.transpose(1, 2, 0, 3) * jax.nn.sigmoid(gate)
        return x + _mm(out.reshape(b, s, n_head * hd),
                       w["self_attn.o_proj"], operands)


full_attention = jax.jit(_full_attention, static_argnames=(
    "n_head", "n_kv_head", "eps", "theta", "fraction", "operands"))


def _gated_delta_net(x, w: Dict[str, Any], *, n_key: int, n_value: int,
                     eps: float, operands=None):
    """The Gated DeltaNet half of a block, residual included: the
    recurrence one position at a time."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        dv = w["linear_attn.norm"].shape[0]
        taps, channels = w["linear_attn.conv1d"].shape
        dk = (channels - n_value * dv) // (2 * n_key)
        h = _rms_norm(x, w["input_layernorm"], eps)
        qkvz = _mm(h, w["linear_attn.in_proj_qkvz"], operands)
        ba = _mm(h, w["linear_attn.in_proj_ba"], operands)
        mixed, z = qkvz[..., :channels], qkvz[..., channels:]
        padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = jnp.zeros_like(mixed)
        for i in range(taps):
            conv = conv + padded[:, i:i + s] * w["linear_attn.conv1d"][i]
        conv = jax.nn.silu(conv)
        q = conv[..., :n_key * dk].reshape(b, s, n_key, dk)
        k = conv[..., n_key * dk:2 * n_key * dk].reshape(b, s, n_key, dk)
        v = conv[..., 2 * n_key * dk:].reshape(b, s, n_value, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS)
        q = q / math.sqrt(dk)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
        q = jnp.repeat(q, n_value // n_key, axis=2)
        k = jnp.repeat(k, n_value // n_key, axis=2)
        beta = jax.nn.sigmoid(ba[..., :n_value])
        g = -jnp.exp(w["linear_attn.A_log"]) * jax.nn.softplus(
            ba[..., n_value:] + w["linear_attn.dt_bias"])

        def position(state, at):                # state: [B, Hv, dk, dv]
            q_t, k_t, v_t, g_t, beta_t = at     # [B, Hv, ...]
            state = jnp.exp(g_t)[..., None, None] * state
            held = jnp.einsum("bhkv,bhk->bhv", state, k_t)
            delta = beta_t[..., None] * (v_t - held)
            state = state + k_t[..., :, None] * delta[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        # one position at a time, in segments so that a gradient can run a
        # segment again and not keep every position's state
        seg = _SEGMENT if s % _SEGMENT == 0 else 1

        @jax.checkpoint
        def segment(state, at):
            return jax.lax.scan(position, state, at)

        _, o = jax.lax.scan(
            segment, jnp.zeros((b, n_value, dk, dv), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0).reshape(
                (s // seg, seg) + t.shape[:1] + t.shape[2:])
                for t in (q, k, v, g, beta)))
        o = jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)   # [B,S,Hv,dv]
        variance = jnp.mean(o * o, axis=-1, keepdims=True)
        o = w["linear_attn.norm"] * (o / jnp.sqrt(variance + eps))
        o = o.reshape(b, s, n_value * dv) * jax.nn.silu(z)
        return x + _mm(o, w["linear_attn.out_proj"], operands)


gated_delta_net = jax.jit(_gated_delta_net, static_argnames=(
    "n_key", "n_value", "eps", "operands"))


def _route(x, post_attention_layernorm, gate, *, top_k: int,
           norm_topk_prob: bool, eps: float, operands=None):
    """The expert block's input and routing over the T = B*S tokens: the
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


def _shared_expert(h, gate_proj, up_proj, down_proj, shared_gate,
                   operands=None):
    """The expert every token passes through, times its sigmoid gate."""
    with jax.default_matmul_precision(_PRECISION):
        gate_proj, up_proj, down_proj, shared_gate = (
            m.astype(jnp.float32)
            for m in (gate_proj, up_proj, down_proj, shared_gate))
        out = _mm(jax.nn.silu(_mm(h, gate_proj, operands))
                  * _mm(h, up_proj, operands), down_proj, operands)
        return jax.nn.sigmoid(_mm(h, shared_gate, operands))[:, None] * out


shared_expert = jax.jit(_shared_expert, static_argnames=("operands",))


def _expert_block(x, w: Mapping[str, Any], hparams: Mapping[str, Any],
                  shared: bool = True, fraction=None, operands=None):
    """`expert_block`, traced as one: the given experts a scan over the
    stacked weights, in their order. `fraction` [E], where given, stands for
    this call's own share of the pairs in `load_balance` (a batch in
    several passes)."""
    h, logits, chosen, dense, mask = _route(
        x, w["post_attention_layernorm"], w["mlp.gate"],
        top_k=int(hparams["num_experts_per_tok"]),
        norm_topk_prob=bool(hparams["norm_topk_prob"]),
        eps=float(hparams["rms_norm_eps"]), operands=operands)
    n_experts = logits.shape[-1]
    first = int(hparams.get("first_expert_held", 0))
    held = w["mlp.experts.gate_proj"].shape[0]
    one = jax.checkpoint(functools.partial(_expert, operands=operands))

    def add_expert(out, e):
        gate_proj, up_proj, down_proj, weight, chose = e
        return out + one(h, gate_proj, up_proj, down_proj, weight, chose), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], dense[:, first:first + held].T,
        mask[:, first:first + held].T))
    if shared:
        out = out + _shared_expert(
            h, w["mlp.shared_expert.gate_proj"],
            w["mlp.shared_expert.up_proj"], w["mlp.shared_expert.down_proj"],
            w["mlp.shared_expert_gate"], operands)
    counts = mask.sum(0)
    probs = jax.nn.softmax(logits, axis=-1)
    if fraction is None:
        fraction = counts.astype(jnp.float32) / mask.shape[0]
    facts = {
        "load_balance": n_experts * jnp.sum(fraction * probs.mean(0)),
        "counts": counts, "chosen": chosen}
    return out.reshape(x.shape), facts


_HPARAMS = ("full_attention_interval", "num_attention_heads",
            "num_key_value_heads", "rope_theta", "partial_rotary_factor",
            "linear_num_key_heads", "linear_num_value_heads",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")


def _static(hparams: Mapping[str, Any]):
    return tuple((k, hparams[k]) for k in _HPARAMS) + (
        ("first_expert_held", int(hparams.get("first_expert_held", 0))),)


@functools.partial(jax.jit, static_argnames=("hparams", "shared"))
def _expert_block_jit(x, w, *, hparams, shared):
    return _expert_block(x, w, dict(hparams), shared)


def expert_block(x, w: Mapping[str, Any], hparams: Mapping[str, Any],
                 shared: bool = True):
    """The expert half of a block WITHOUT its residual: what the given
    experts (and, if asked, the shared one) add, and the routing facts."""
    return _expert_block_jit(
        x, {k: v for k, v in w.items() if k.startswith(
            ("mlp.", "post_attention_layernorm"))},
        hparams=_static(hparams), shared=shared)


def _block(x, w: Mapping[str, Any], hparams: Mapping[str, Any], index: int,
           fraction=None, operands=None):
    """Layer `index` (from 0). Returns (x, the layer's routing facts)."""
    eps = float(hparams["rms_norm_eps"])
    names = [k for k in w if k.startswith(("self_attn.", "linear_attn."))]
    mixer = {k: w[k] for k in names + ["input_layernorm"]}
    if (index + 1) % int(hparams["full_attention_interval"]) == 0:
        x = _full_attention(
            x, mixer, n_head=int(hparams["num_attention_heads"]),
            n_kv_head=int(hparams["num_key_value_heads"]), eps=eps,
            theta=float(hparams["rope_theta"]),
            fraction=float(hparams["partial_rotary_factor"]),
            operands=operands)
    else:
        x = _gated_delta_net(
            x, mixer, n_key=int(hparams["linear_num_key_heads"]),
            n_value=int(hparams["linear_num_value_heads"]), eps=eps,
            operands=operands)
    out, facts = _expert_block(x, w, hparams, True, fraction, operands)
    return x + out, facts


@functools.partial(jax.jit, static_argnames=("hparams", "index"))
def _block_jit(x, w, *, hparams, index):
    return _block(x, w, dict(hparams), index)


def block(x, w: Mapping[str, Any], hparams: Mapping[str, Any], index: int):
    """Layer `index` (from 0). Returns (x, the layer's routing facts)."""
    full = (index + 1) % int(hparams["full_attention_interval"]) == 0
    # one program a kind of layer, not one a layer
    return _block_jit(x, dict(w), hparams=_static(hparams),
                      index=int(hparams["full_attention_interval"]) - 1
                      if full else 0)


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
    """The whole model. Returns `ce` and `load_balance` (unweighted),
    `logits`, and per layer `counts` ([L, E], the router's choices over all
    its outputs) and `chosen` ([L, T, k])."""
    x = embed(tokens, top["embed_tokens"])
    facts = []
    for index, w in enumerate(layers):
        x, layer_facts = block(x, w, hparams, index)
        facts.append(layer_facts)
    ce, logits = head_loss(x, tokens, top["norm"], top["lm_head"],
                           eps=float(hparams["rms_norm_eps"]))
    return {
        "ce": ce, "logits": logits,
        "load_balance": jnp.mean(jnp.stack(
            [f["load_balance"] for f in facts])),
        "counts": jnp.stack([f["counts"] for f in facts]),
        "chosen": jnp.stack([f["chosen"] for f in facts])}


def training(config: Mapping[str, Any], operands=None) -> Dict[str, Any]:
    """The model in the pieces `reference/train_steps.py` differentiates one
    at a time: `embed(top, tokens)`, `block(index)(w, x, fraction) -> (x,
    terms, facts)` (one function a kind of layer) and `head(top, x, tokens)
    -> (ce, lse2)`. `config` is the configuration's file, whose top level
    holds the published keys."""
    hparams = dict(_static(config))
    interval = int(hparams["full_attention_interval"])

    def kind(index):
        def one_block(w, x, fraction):
            x, facts = _block(x, w, hparams, index, fraction, operands)
            return (x, {"load_balance": facts["load_balance"]},
                    {k: facts[k] for k in ("counts", "chosen")})
        return one_block

    linear, full = kind(0), kind(interval - 1)

    def head(top, x, tokens):
        return _head_terms(x, tokens, top["norm"], top["lm_head"],
                           eps=float(hparams["rms_norm_eps"]),
                           operands=operands)[:2]

    return {"embed": lambda top, tokens: embed(tokens, top["embed_tokens"]),
            "block": lambda index: full if (index + 1) % interval == 0
            else linear, "head": head, "routes": True}
