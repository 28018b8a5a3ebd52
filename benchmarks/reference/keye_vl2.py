"""Keye-VL-2.0's language model, plain: the forward pass in float32
`jax.numpy`, and the score of a document.

Follows the published `config.json` of `Kwai-Keye/Keye-VL-2.0-30B-A3B`
(`model_type` KeyeVL2) and the published description of DeepSeek sparse
attention for its `sa_config`. One layer (all are alike), x [S, d]:

    h  = RMSNorm(x)                                        eps rms_norm_eps
    q  = h Wq -> [S, heads, hd]    k, v = h Wk, h Wv -> [S, kv heads, hd]
    q, k <- RMSNorm over each head's hd, one scale for q and one for k
            (the Qwen3 family's; the config has no key for it), then RoPE
            over all hd, theta rope_theta (text only: the three M-RoPE
            positions of a token are equal, so `mrope_section` is RoPE)
    indexer, from the same h:
      qI = h WqI -> [S, indexer heads, indexer hd]
      kI = LayerNorm(h WkI) -> [S, indexer hd]       one key head for all
      wI = h Ww  -> [S, indexer heads]
      RoPE on qI and kI, the layer's positions and theta, over all of hd
      I[t, s] = heads^-1/2 hd^-1/2 sum_j wI[t, j] relu(qI[t, j] . kI[s])
    S_t = the min(t + 1, topk) keys s <= t of largest I[t, s], among equals
          the lowest s (`lax.top_k`)
    o[t, head] = sum over s in S_t of softmax_{S_t}(q[t, head] . k[s, head
                 // group] / sqrt(hd)) v[s, head // group]
    x <- x + concat(o) Wo
    h2 = RMSNorm(x);  p = softmax(h2 Wr) over all router outputs;  the top
    num_experts_per_tok, rescaled to sum to 1 (`norm_topk_prob`);
    x <- x + sum over the chosen experts HELD HERE of p~_e (silu(h2 Wg_e) *
         (h2 Wu_e)) Wd_e
then the final RMSNorm and the untied head; a token's score is the
log-softmax of the logits before it at its id. `q_chunk_size` and
`kv_chunk_size` of `sa_config` are read as the tiling in which an
implementation makes the index scores: they change no result.

No kernels, no cache, no batching, nothing of `ray_tpu/`. Matmuls run at
`jax.default_matmul_precision("highest")`. So that a 16,384-token document
fits one chip beside the weights, a layer's attention is made in blocks of
`_QUERIES` queries (index scores, `lax.top_k`, a mask of the chosen keys on
the dense float32 scores of every head): the result is the whole matrix's.
So that a run's sample compiles a handful of shapes and not one a length,
`token_logprobs` follows a row with zeros up to a multiple of `_ROW`
tokens: the causal choice and mask keep every real position from them.

A chip's share (the configuration's `deployment`): the router scores all
its outputs and the layer adds what the experts given here add
(`first_expert_held` and as many as the weights hold), the rest left out;
the vocabulary is the slice the weights hold. `operands`, where given, is
the type every matmul's two operands are rounded to (the indexer's too):
the control, never the reference.

Weights, one dict a layer, every matrix [in, out]:
    input_layernorm post_attention_layernorm [d]
    self_attn.q_proj [d, heads * hd]   self_attn.k_proj self_attn.v_proj
    [d, kv heads * hd]   self_attn.q_norm self_attn.k_norm [hd]
    self_attn.o_proj [heads * hd, d]
    self_attn.indexer.wq [d, indexer heads * indexer hd]
    self_attn.indexer.wk [d, indexer hd]
    self_attn.indexer.k_norm.weight self_attn.indexer.k_norm.bias
    [indexer hd]   self_attn.indexer.weights_proj [d, indexer heads]
    mlp.gate [d, router outputs]
    mlp.experts.gate_proj mlp.experts.up_proj [held, d, f]
    mlp.experts.down_proj [held, f, d]
and `top`: `embed_tokens` [V, d], `norm` [d], `lm_head` [d, V].
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Mapping

import jax
import jax.numpy as jnp

from benchmarks.reference.operands import mm as _mm

_PRECISION = "highest"
_LN_EPS = 1e-6          # the indexer's key LayerNorm (`assumed`)
_QUERIES = 512          # queries a block of a layer's attention
_ROW = 1024             # a row is computed at a multiple of this length


def _rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight * (x / jnp.sqrt(variance + eps))


def _layer_norm(x, weight, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _LN_EPS) * weight + bias


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: [S, heads, hd]; positions 0..S-1; all of hd turns."""
    s, hd = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def index_scores(qI, kI, wI, operands=None):
    """I[t, s] for the queries of qI [Q, heads, hd] and wI [Q, heads]
    against every key of kI [S, hd], causal or not."""
    heads, hd = qI.shape[1:]
    per_head = jax.nn.relu(_mm(qI.transpose(1, 0, 2), kI.T, operands))
    return (per_head * wI.T[:, :, None]).sum(0) / math.sqrt(heads * hd)


def chosen_keys(scores, first_query, topk: int):
    """[Q, S] bool: for query `first_query + i` the min(t + 1, topk) keys
    s <= t of largest scores[i, s], among equals the lowest s."""
    n, s = scores.shape
    t = first_query + jnp.arange(n)[:, None]
    causal = jnp.arange(s)[None, :] <= t
    # (one zero: a sum of nothing is -0.0 or 0.0 by its terms' signs)
    scores = jnp.where(causal, jnp.where(scores == 0.0, 0.0, scores),
                       -jnp.inf)
    values, keys = jax.lax.top_k(scores, min(topk, s))
    chosen = jnp.zeros((n, s), bool).at[jnp.arange(n)[:, None], keys].set(
        values > -jnp.inf)
    return chosen


def _sparse_attention(x, w: Dict[str, Any], hparams, operands=None):
    """The attention half of a block for one row x [S, d], residual
    included."""
    n_head = int(hparams["num_attention_heads"])
    n_kv = int(hparams["num_key_value_heads"])
    eps, theta = float(hparams["rms_norm_eps"]), float(hparams["rope_theta"])
    sa = hparams["sa_config"]
    i_head, i_dim = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    topk = int(sa["topk"])
    s, d = x.shape
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = _mm(h, w["self_attn.q_proj"], operands).reshape(s, n_head, -1)
    k = _mm(h, w["self_attn.k_proj"], operands).reshape(s, n_kv, -1)
    v = _mm(h, w["self_attn.v_proj"], operands).reshape(s, n_kv, -1)
    hd = q.shape[-1]
    q = _rope(_rms_norm(q, w["self_attn.q_norm"], eps), theta)
    k = _rope(_rms_norm(k, w["self_attn.k_norm"], eps), theta)
    qI = _mm(h, w["self_attn.indexer.wq"], operands).reshape(s, i_head, i_dim)
    kI = _layer_norm(_mm(h, w["self_attn.indexer.wk"], operands),
                     w["self_attn.indexer.k_norm.weight"],
                     w["self_attn.indexer.k_norm.bias"])
    wI = _mm(h, w["self_attn.indexer.weights_proj"], operands)
    qI, kI = _rope(qI, theta), _rope(kI[:, None], theta)[:, 0]
    # [heads, S, hd]: a key and value head serves its group of query heads
    kt = jnp.repeat(k, n_head // n_kv, axis=1).transpose(1, 2, 0)
    v = jnp.repeat(v, n_head // n_kv, axis=1).transpose(1, 0, 2)
    block_q = min(_QUERIES, s)
    assert s % block_q == 0, (s, block_q)

    def block(first):
        def rows(t):
            return jax.lax.dynamic_slice_in_dim(t, first, block_q, 0)

        chosen = chosen_keys(
            index_scores(rows(qI), kI, rows(wI), operands), first, topk)
        dense = _mm(rows(q).transpose(1, 0, 2), kt, operands) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(chosen[None], dense, -jnp.inf), axis=-1)
        return _mm(p, v, operands).transpose(1, 0, 2)       # [Q, heads, hd]

    out = jax.lax.map(block, jnp.arange(0, s, block_q))
    return x + _mm(out.reshape(s, -1), w["self_attn.o_proj"], operands)


def _expert(h, gate_proj, up_proj, down_proj, weight, mask, operands=None):
    """One expert on every token, times the token's weight for it, and zero
    for a token that did not choose it. h: [T, d]; weight, mask: [T]."""
    out = _mm(jax.nn.silu(_mm(h, gate_proj, operands))
              * _mm(h, up_proj, operands), down_proj, operands)
    return jnp.where(mask[:, None], out * weight[:, None], 0.0)


def _expert_block(x, w: Mapping[str, Any], hparams, operands=None):
    """The expert half of a block, residual included: the part of the
    result that the experts given here add."""
    h = _rms_norm(x, w["post_attention_layernorm"],
                  float(hparams["rms_norm_eps"]))
    probs = jax.nn.softmax(_mm(h, w["mlp.gate"], operands), axis=-1)
    weights, chosen = jax.lax.top_k(probs, int(hparams["num_experts_per_tok"]))
    if hparams["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32)
    dense = (one_hot * weights[..., None]).sum(1)           # [T, E]
    mask = one_hot.sum(1) > 0
    first = int(hparams.get("first_expert_held", 0))
    held = w["mlp.experts.gate_proj"].shape[0]

    def add_expert(out, e):
        gate_proj, up_proj, down_proj, weight, chose = e
        return out + _expert(h, gate_proj, up_proj, down_proj, weight,
                             chose, operands), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], dense[:, first:first + held].T,
        mask[:, first:first + held].T))
    return x + out


class _Frozen(dict):
    """The configuration's published keys as a static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _hparams(config: Mapping[str, Any]) -> _Frozen:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "num_experts_per_tok", "norm_topk_prob")
    return _Frozen({**{k: config[k] for k in keys},
                    "sa_config": _Frozen(config["sa_config"]),
                    "first_expert_held": int(config.get(
                        "first_expert_held", 0))})


@functools.partial(jax.jit, static_argnames=("hparams", "operands"))
def block(x, w, *, hparams, operands=None):
    """One layer on one row. x: [S, d] float32."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        x = _sparse_attention(x, w, hparams, operands)
        return _expert_block(x, w, hparams, operands)


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def head_scores(x, tokens, norm, lm_head, *, eps, operands=None):
    """Final RMSNorm, untied head; for positions 0..S-2 of a row the
    log-probability of the token that follows."""
    with jax.default_matmul_precision(_PRECISION):
        x = _rms_norm(x, norm.astype(jnp.float32), eps)
        logits = _mm(x[:-1], lm_head.astype(jnp.float32), operands)
        target = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
        return target - jax.nn.logsumexp(logits, axis=-1)


def token_logprobs(tokens, top: Mapping[str, Any],
                   layers: Iterable[Dict[str, Any]],
                   config: Mapping[str, Any], operands=None):
    """Forward only, what a scoring request is answered with
    (`loops/serve.py`): tokens [B, S] int32 -> [B, S-1] float32, the
    log-probability of each token 1..S-1 given the tokens before it, a row
    at a time. Nothing here knows of batches, buckets or padding.
    `operands` is the control."""
    hparams = _hparams(config)
    n = tokens.shape[1]
    width = -(-n // _ROW) * _ROW if n > _QUERIES else n
    tokens = jnp.pad(tokens, ((0, 0), (0, width - n)))
    embed = top["embed_tokens"].astype(jnp.float32)
    xs = [embed[row] for row in tokens]
    for w in layers:
        xs = [block(x, w, hparams=hparams, operands=operands) for x in xs]
    return jnp.stack([
        head_scores(x, row, top["norm"], top["lm_head"],
                    eps=float(config["rms_norm_eps"]), operands=operands)
        for x, row in zip(xs, tokens)])[:, :n - 1]
