"""SmallThinker-21BA3B-Instruct (`model_name` smallthinker_21b_instruct),
plain: the forward pass in float32 `jax.numpy`, and the score of a document.

Follows the published `config.json` of
`PowerInfer/SmallThinker-21BA3B-Instruct`; what that file has no key for is
marked (+) here and listed under `assumed` in
`configs/smallthinker_21b_a3b.json`. Layer l of the published model attends
everything before it where `sliding_window_layout[l]` is 0 and a window of
`sliding_window_size` keys where it is 1, and RoPE turns its q and k where
`rope_layout[l]` is 1 (the window layers; the full layers attend without
positions). x [S, d]:

    x0 = E[token]                                         no multiplier (+)
    h1 = RMSNorm(x)                    w * x / sqrt(mean(x^2) + eps)   (+)
    r  = h1 Wr -> [S, experts]         THE ROUTER READS h1, the normed input
                                       of attention, ahead of attention (+)
    q  = h1 Wq -> [S, heads, hd]   k, v = h1 Wk, h1 Wv -> [S, kv heads, hd]
                                       no bias, no QK-norm, no gate      (+)
    rope_layout[l] == 1: RoPE on q and k over all hd, theta rope_theta,
        rotate-half                                                     (+)
    scores q . k / sqrt(hd), query head j on key head j // group;
        a window query t keeps keys t - sliding_window_size < s <= t (its
        own key included) (+), a full query every s <= t
    x' = x + (softmax(scores) v) Wo
    h2 = RMSNorm(x')
    C  = the moe_num_active_primary_experts largest of r (`lax.top_k`:
         among equals the lowest index);  p = softmax(r[C])
         (`moe_primary_router_apply_softmax`; the weights sum to 1, so
         `norm_topk_prob` divides by 1)
    y  = sum over e in C of p_e (relu(h2 Wg_e) x (h2 Wu_e)) Wd_e
         ReLU on the gate projection, not on the up projection          (+)
         no shared expert, no secondary experts                         (+)
    x <- x' + y
then the final RMSNorm and the untied head; a token's score is the
log-softmax of the logits before it at its id, over the rows of the
vocabulary the weights hold.

No kernels, no cache, no batching, nothing of `ray_tpu/`. Matmuls run at
`jax.default_matmul_precision("highest")`; masks are `jnp.where` on iotas.
Departures from the equations as written, none of which changes a result:
so that a 16,384-token document fits one chip beside the weights, a layer's
attention is made in blocks of `_QUERIES` queries against every key (dense
float32 scores of every head, masked); an expert multiplies the tokens
routed to it and no other — their indices by `jnp.nonzero` at a static
capacity, the largest count of the layer read on the host and rounded up
to `_CAPACITY` times a power of two (`capacity`; rows beyond an expert's
count multiply zeros and are read by no token), where the sum over the
chosen, written out, would pass every token through every expert. So that a
run's sample compiles a handful of shapes and not one a length,
`token_logprobs` follows a row with zeros up to `_ROW` tokens times a power
of two: the causal masks keep every real position from them.

A chip's share (the configuration's `deployment`): `layers_held` names the
published indices of the layers the weights hold, in order, and a layer's
kind is the published model's at that index; the vocabulary is the slice
the weights hold. `operands`, where given, is the type every matmul's two
operands are rounded to (the router's too): the control, never the
reference.

Weights, one dict a layer, every matrix [in, out]:
    input_layernorm post_attention_layernorm [d]
    self_attn.q_proj [d, heads * hd]   self_attn.k_proj self_attn.v_proj
    [d, kv heads * hd]   self_attn.o_proj [heads * hd, d]
    block_sparse_moe.primary_router [d, E]
    block_sparse_moe.experts.gate block_sparse_moe.experts.up [E, d, f]
    block_sparse_moe.experts.down [E, f, d]
and `top`: `embed_tokens` [V, d], `norm` [d], `lm_head` [d, V].
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.operands import mm as _mm

_PRECISION = "highest"
_QUERIES = 512          # queries a block of a layer's attention
_ROW = 1024             # a row is computed at this length x a power of two
_CAPACITY = 256         # an expert's rows are this x a power of two


def _doubled(unit: int, n: int) -> int:
    """`unit` times the least power of two that reaches `n`."""
    size = unit
    while size < n:
        size *= 2
    return size


def _rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight * (x / jnp.sqrt(variance + eps))


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


def _reglu(h, gate_proj, up_proj, down_proj, operands=None):
    return _mm(jax.nn.relu(_mm(h, gate_proj, operands))
               * _mm(h, up_proj, operands), down_proj, operands)


def _attention(x, h, w: Mapping[str, Any], hparams, sliding: bool,
               turned: bool, operands=None):
    """The attention half of a block for one row x [S, d] and its normed
    form h, residual included."""
    n_head = int(hparams["num_attention_heads"])
    n_kv = int(hparams["num_key_value_heads"])
    theta = float(hparams["rope_theta"])
    window = int(hparams["sliding_window_size"])
    s, _ = x.shape
    q = _mm(h, w["self_attn.q_proj"], operands).reshape(s, n_head, -1)
    k = _mm(h, w["self_attn.k_proj"], operands).reshape(s, n_kv, -1)
    v = _mm(h, w["self_attn.v_proj"], operands).reshape(s, n_kv, -1)
    hd = q.shape[-1]
    if turned:
        q, k = _rope(q, theta), _rope(k, theta)
    # [heads, hd, S] and [heads, S, hd]: a key and value head serves its
    # group of query heads
    kt = jnp.repeat(k, n_head // n_kv, axis=1).transpose(1, 2, 0)
    v = jnp.repeat(v, n_head // n_kv, axis=1).transpose(1, 0, 2)
    block_q = min(_QUERIES, s)
    assert s % block_q == 0, (s, block_q)

    def block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block_q, 0)
        t = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 0)
        key = jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 1)
        keep = key <= t
        if sliding:
            keep = keep & (key > t - window)
        dense = _mm(rows.transpose(1, 0, 2), kt, operands) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep[None], dense, -jnp.inf), axis=-1)
        return _mm(p, v, operands).transpose(1, 0, 2)       # [Q, heads, hd]

    out = jax.lax.map(block, jnp.arange(0, s, block_q)).reshape(s, -1)
    return x + _mm(out, w["self_attn.o_proj"], operands)


def _routing(h1, w: Mapping[str, Any], hparams, operands=None):
    """([T, E] float32, [T, E] bool): each token's weight for every expert
    (0 for one it did not choose) and which it chose — from h1, the normed
    input of the layer's attention."""
    logits = _mm(h1, w["block_sparse_moe.primary_router"], operands)
    if not hparams["moe_primary_router_apply_softmax"]:
        raise NotImplementedError("a router without its softmax")
    top, chosen = jax.lax.top_k(
        logits, int(hparams["moe_num_active_primary_experts"]))
    weights = jax.nn.softmax(top, axis=-1)
    if hparams["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=jnp.float32)
    return (one_hot * weights[..., None]).sum(1), one_hot.sum(1) > 0


def _routed_experts(h, weights, chose, w: Mapping[str, Any], capacity: int,
                    operands=None):
    """sum over a token's chosen experts of its weight x the expert, every
    expert on the tokens that chose it: at most `capacity` of them."""
    n, d = h.shape
    h_and_zero = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)])

    def add_expert(out, e):
        gate_proj, up_proj, down_proj, weight, chose_e = e
        # the expert's tokens in order (then the row of zeros), through the
        # expert, and each token's row read back from its place among them:
        # gathers both ways, no scatter
        (tokens,) = jnp.nonzero(chose_e, size=capacity, fill_value=n)
        y = _reglu(h_and_zero[tokens], gate_proj, up_proj, down_proj,
                   operands)
        place = jnp.clip(jnp.cumsum(chose_e) - 1, 0, capacity - 1)
        return out + jnp.where(chose_e[:, None],
                               y[place] * weight[:, None], 0.0), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        w["block_sparse_moe.experts.gate"], w["block_sparse_moe.experts.up"],
        w["block_sparse_moe.experts.down"], weights.T, chose.T))
    return out


class _Frozen(dict):
    """The configuration's published keys as a static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _hparams(config: Mapping[str, Any]) -> _Frozen:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "sliding_window_size",
            "moe_num_active_primary_experts",
            "moe_primary_router_apply_softmax", "norm_topk_prob")
    hparams = _Frozen({k: config[k] for k in keys})
    if config.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling")
    return hparams


def layer_kinds(config: Mapping[str, Any]):
    """(sliding, turned by RoPE) of each layer held, by its published
    index."""
    held = config.get("layers_held") or range(config["num_hidden_layers"])
    return [(config["sliding_window_layout"][l] == 1,
             config["rope_layout"][l] == 1) for l in held]


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("hparams", "sliding", "turned",
                                             "operands"))
def attend(x, w, *, hparams, sliding, turned, operands=None):
    """A layer on one row x [S, d] float32 as far as its experts' input: the
    routing, read off h1 ahead of attention, the stream after attention, and
    (h2, each token's weight for every expert, which it chose)."""
    with jax.default_matmul_precision(_PRECISION):
        w = _f32(w)
        eps = float(hparams["rms_norm_eps"])
        h1 = _rms_norm(x, w["input_layernorm"], eps)
        weights, chose = _routing(h1, w, hparams, operands)
        x = _attention(x, h1, w, hparams, sliding, turned, operands)
        h2 = _rms_norm(x, w["post_attention_layernorm"], eps)
        return x, (h2, weights, chose)


@functools.partial(jax.jit, static_argnames=("capacity", "operands"))
def experts(x, routed, w, *, capacity, operands=None):
    """The layer's FFN on one row, from `attend`'s routing on."""
    with jax.default_matmul_precision(_PRECISION):
        h2, weights, chose = routed
        return x + _routed_experts(h2, weights, chose, _f32(w), capacity,
                                   operands)


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
                   config: Mapping[str, Any], operands=None,
                   capacity: Optional[int] = None):
    """Forward only, what a scoring request is answered with
    (`loops/serve.py`): tokens [B, S] int32 -> [B, S-1] float32, the
    log-probability of each token 1..S-1 given the tokens before it, a row
    at a time. Nothing here knows of batches, buckets or padding.
    `operands` is the control. `capacity`: the rows an expert's pass takes
    (None: a layer's largest count, read on the host; a caller that
    differentiates gives the row's length, which holds any routing)."""
    hparams = _hparams(config)
    n = tokens.shape[1]
    width = _doubled(_ROW, n) if n > _QUERIES else n
    tokens = jnp.pad(tokens, ((0, 0), (0, width - n)))
    embed = top["embed_tokens"].astype(jnp.float32)
    xs = [embed[row] for row in tokens]
    for w, (sliding, turned) in zip(layers, layer_kinds(config),
                                    strict=True):
        for i, x in enumerate(xs):
            x, routed = attend(x, w, hparams=hparams, sliding=sliding,
                               turned=turned, operands=operands)
            rows = capacity
            if rows is None:
                most = int(routed[2].sum(0).max())
                rows = min(_doubled(_CAPACITY, most), width)
            xs[i] = experts(x, routed, w, capacity=rows, operands=operands)
    return jnp.stack([
        head_scores(x, row, top["norm"], top["lm_head"],
                    eps=float(config["rms_norm_eps"]), operands=operands)
        for x, row in zip(xs, tokens)])[:, :n - 1]
