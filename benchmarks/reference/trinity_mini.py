"""Trinity-Mini (`model_type` afmoe), plain: the forward pass in float32
`jax.numpy`, and the score of a document.

Follows the published `config.json` of `arcee-ai/Trinity-Mini`; what that
file has no key for is the public `afmoe` modelling code's as the
configuration's writer knows it, marked (+) here and listed under `assumed`
in `configs/trinity_mini.json`. Layer l of the published model attends a
window if `layer_types[l]` is "sliding_attention" and everything before it
if "full_attention" (every `global_attn_every_n_layers`-th), and its FFN is
dense for l < `num_dense_layers`, routed after. x [S, d]:

    x0 = E[token] x sqrt(hidden_size)                     (+) `mup_enabled`
    h  = RMSNorm(x)                                       eps rms_norm_eps
    q  = h Wq -> [S, heads, hd]   k, v = h Wk, h Wv -> [S, kv heads, hd]
    g  = h Wg -> [S, heads x hd]                          (+) no bias anywhere
    q, k <- RMSNorm over each head's hd, one scale for q, one for k    (+)
    window layers only: RoPE on q and k over all hd, theta rope_theta,
        rotate-half; full layers: no positions at all                  (+)
    scores q . k / sqrt(hd), query head j on key head j // group;
        a window query t keeps keys t - sliding_window < s <= t (its own
        key included), a full query every s <= t
    o = softmax(scores) v;  o <- o x sigmoid(g)                        (+)
    x <- x + RMSNorm_post(o Wo)                  (+) a norm on the branch
    h2 = RMSNorm(x)
    dense:  f = (silu(h2 Wg) x (h2 Wu)) Wd           width intermediate_size
    routed: s = sigmoid(h2 Wr) over num_experts;           `score_func`
        the num_experts_per_tok largest of s + b, b a bias an expert   (+)
        that is in the choice alone (`lax.top_k`: among equals the lowest);
        w = s[chosen] / (sum of s[chosen] + 1e-20) x route_scale
        (`route_norm`; b is not in w);
        f = sum over the chosen of w_e (silu(h2 Wg_e) x (h2 Wu_e)) Wd_e
            + the shared expert, the same SwiGLU at moe_intermediate_size x
              num_shared_experts, with no gate of its own              (+)
        (`n_group`, `topk_group` 1: the choice is over all experts)
    x <- x + RMSNorm_post(f)                                           (+)
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
count multiply zeros and are read by no token), where the sum
over the chosen, written out, would pass every token through every expert.
So that a run's sample compiles a handful of shapes and not one a length
(a shape is five compiles here, and sixteen documents of sixteen lengths
took ten minutes of them), `token_logprobs` follows a row with zeros up to
`_ROW` tokens times a power of two: the causal masks keep every real
position from them.

A chip's share (the configuration's `deployment`): `layers_held` names the
published indices of the layers the weights hold, in order, and a layer's
kind and FFN are the published model's at that index; the vocabulary is the
slice the weights hold. `operands`, where given, is the type every matmul's
two operands are rounded to (the router's too): the control, never the
reference.

Weights, one dict a layer, every matrix [in, out]:
    input_layernorm post_attention_layernorm pre_mlp_layernorm
    post_mlp_layernorm [d]
    self_attn.q_proj self_attn.gate_proj [d, heads * hd]
    self_attn.k_proj self_attn.v_proj [d, kv heads * hd]
    self_attn.q_norm self_attn.k_norm [hd]   self_attn.o_proj [heads * hd, d]
    dense:  mlp.gate_proj mlp.up_proj [d, f]   mlp.down_proj [f, d]
    routed: mlp.router.gate [d, E]   mlp.expert_bias [E]
            mlp.experts.gate_proj mlp.experts.up_proj [E, d, f]
            mlp.experts.down_proj [E, f, d]
            mlp.shared_experts.gate_proj mlp.shared_experts.up_proj [d, fs]
            mlp.shared_experts.down_proj [fs, d]
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


def _swiglu(h, gate_proj, up_proj, down_proj, operands=None):
    return _mm(jax.nn.silu(_mm(h, gate_proj, operands))
               * _mm(h, up_proj, operands), down_proj, operands)


def _attention(x, w: Mapping[str, Any], hparams, sliding: bool,
               operands=None):
    """The attention half of a block for one row x [S, d], residual
    included."""
    n_head = int(hparams["num_attention_heads"])
    n_kv = int(hparams["num_key_value_heads"])
    eps, theta = float(hparams["rms_norm_eps"]), float(hparams["rope_theta"])
    window = int(hparams["sliding_window"])
    s, _ = x.shape
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = _mm(h, w["self_attn.q_proj"], operands).reshape(s, n_head, -1)
    k = _mm(h, w["self_attn.k_proj"], operands).reshape(s, n_kv, -1)
    v = _mm(h, w["self_attn.v_proj"], operands).reshape(s, n_kv, -1)
    gate = _mm(h, w["self_attn.gate_proj"], operands)
    hd = q.shape[-1]
    q = _rms_norm(q, w["self_attn.q_norm"], eps)
    k = _rms_norm(k, w["self_attn.k_norm"], eps)
    if sliding:
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
    out = out * jax.nn.sigmoid(gate)
    return x + _rms_norm(_mm(out, w["self_attn.o_proj"], operands),
                         w["post_attention_layernorm"], eps)


def _routing(h, w: Mapping[str, Any], hparams, operands=None):
    """([T, E] float32, [T, E] bool): each token's weight for every expert
    (0 for one it did not choose) and which it chose."""
    scores = _mm(h, w["mlp.router.gate"], operands)
    if hparams["score_func"] != "sigmoid":
        raise NotImplementedError(hparams["score_func"])
    scores = jax.nn.sigmoid(scores)
    _, chosen = jax.lax.top_k(scores + w["mlp.expert_bias"],
                              int(hparams["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if hparams["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * float(hparams["route_scale"])
    one_hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
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
        y = _swiglu(h_and_zero[tokens], gate_proj, up_proj, down_proj,
                    operands)
        place = jnp.clip(jnp.cumsum(chose_e) - 1, 0, capacity - 1)
        return out + jnp.where(chose_e[:, None],
                               y[place] * weight[:, None], 0.0), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], weights.T, chose.T))
    return out


class _Frozen(dict):
    """The configuration's published keys as a static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _hparams(config: Mapping[str, Any]) -> _Frozen:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "sliding_window", "num_experts_per_tok",
            "route_norm", "route_scale", "score_func", "num_shared_experts",
            "n_group", "topk_group")
    hparams = _Frozen({k: config[k] for k in keys})
    if (hparams["n_group"], hparams["topk_group"]) != (1, 1):
        raise NotImplementedError("a limit on the groups of experts")
    return hparams


def layer_kinds(config: Mapping[str, Any]):
    """(sliding, dense) of each layer held, by its published index."""
    held = config.get("layers_held") or range(config["num_hidden_layers"])
    return [(config["layer_types"][l] == "sliding_attention",
             l < config["num_dense_layers"]) for l in held]


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("hparams", "sliding", "dense",
                                             "operands"))
def attend(x, w, *, hparams, sliding, dense, operands=None):
    """A layer on one row x [S, d] float32 as far as its FFN's input: the
    stream after attention, and for a dense layer after the MLP too; for a
    routed one also (h2, each token's weight for every expert, which it
    chose)."""
    with jax.default_matmul_precision(_PRECISION):
        w = _f32(w)
        eps = float(hparams["rms_norm_eps"])
        x = _attention(x, w, hparams, sliding, operands)
        h = _rms_norm(x, w["pre_mlp_layernorm"], eps)
        if dense:
            f = _swiglu(h, w["mlp.gate_proj"], w["mlp.up_proj"],
                        w["mlp.down_proj"], operands)
            return x + _rms_norm(f, w["post_mlp_layernorm"], eps), None
        return x, (h, *_routing(h, w, hparams, operands))


@functools.partial(jax.jit, static_argnames=("hparams", "capacity",
                                             "operands"))
def experts(x, routed, w, *, hparams, capacity, operands=None):
    """The routed layer's FFN on one row, from `attend`'s routing on."""
    with jax.default_matmul_precision(_PRECISION):
        w = _f32(w)
        h, weights, chose = routed
        f = _routed_experts(h, weights, chose, w, capacity, operands)
        if int(hparams["num_shared_experts"]):
            f = f + _swiglu(h, w["mlp.shared_experts.gate_proj"],
                            w["mlp.shared_experts.up_proj"],
                            w["mlp.shared_experts.down_proj"], operands)
        return x + _rms_norm(f, w["post_mlp_layernorm"],
                             float(hparams["rms_norm_eps"]))


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
    scale = math.sqrt(config["hidden_size"]) if config["mup_enabled"] else 1.0
    xs = [embed[row] * scale for row in tokens]
    for w, (sliding, dense) in zip(layers, layer_kinds(config), strict=True):
        for i, x in enumerate(xs):
            x, routed = attend(x, w, hparams=hparams, sliding=sliding,
                               dense=dense, operands=operands)
            if routed is not None:
                rows = capacity
                if rows is None:
                    most = int(routed[2].sum(0).max())
                    rows = min(_doubled(_CAPACITY, most), width)
                x = experts(x, routed, w, hparams=hparams, capacity=rows,
                            operands=operands)
            xs[i] = x
    return jnp.stack([
        head_scores(x, row, top["norm"], top["lm_head"],
                    eps=float(config["rms_norm_eps"]), operands=operands)
        for x, row in zip(xs, tokens)])[:, :n - 1]
