"""GPT-2, plain: forward pass and next-token loss in float32 `jax.numpy`.

Follows Radford et al. 2019 / the `openai-community/gpt2*` checkpoints'
equations: learned token and position embeddings; pre-LayerNorm blocks
(eps 1e-5) of causal multi-head attention (scale 1/sqrt(head_dim)) and a
4x MLP with the tanh-approximated GELU ("gelu_new"); a final LayerNorm; the
output head tied to the token embedding; mean cross-entropy of token t+1
given tokens <= t. No kernels, no cache, no remat, no sharding, nothing from
`ray_tpu/`. Matmuls run at `jax.default_matmul_precision("highest")`, or a
TPU would quietly do them in bf16.

Departure from the checkpoints, noted because the system under test makes it:
its blocks have no biases on the four linear layers (LayerNorm keeps its
bias). Biases are therefore optional here; a missing one is zero.

Weights come in the checkpoints' layout, one dict per layer:
    ln_1.g ln_1.b [d]   attn.c_attn.w [d, 3d] (q | k | v)   attn.c_proj.w [d, d]
    ln_2.g ln_2.b [d]   mlp.c_fc.w [d, 4d]                   mlp.c_proj.w [4d, d]
    (+ optional .b beside each .w)
and `wte` [V, d], `wpe` [n_positions, d], `ln_f.g`, `ln_f.b`.
The block is a function of one layer's weights so that a caller whose model
does not fit one device can hand the layers over one at a time.

The training objective (`training`, for `reference/train_steps.py`): the
cross-entropy plus the output z-loss, z * mean(logsumexp(logits)^2) over the
same positions — the repo's form (`GPTConfig.z_loss`), stated under the
configuration's `reference.objective`. `operands`, where given, is the type
every matmul's two operands are rounded to before they are multiplied in
float32 (bfloat16, float8_e4m3fn): the control of a path of lower precision,
never the reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Mapping

import jax
import jax.numpy as jnp

from benchmarks.reference.operands import mm as _mm

_PRECISION = "highest"
_LN_EPS = 1e-5


def _layer_norm(x, g, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _LN_EPS) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _linear(x, w: Mapping[str, Any], name: str, operands=None):
    y = _mm(x, w[name + ".w"], operands)
    return y + w[name + ".b"] if name + ".b" in w else y


@jax.jit
def embed(tokens, wte, wpe):
    """tokens [B, S] int -> [B, S, d] float32."""
    return (wte.astype(jnp.float32)[tokens]
            + wpe.astype(jnp.float32)[: tokens.shape[1]][None])


def _block(x, w: Dict[str, Any], *, n_head: int, operands=None):
    """One pre-LayerNorm GPT-2 block. x: [B, S, d] float32."""
    with jax.default_matmul_precision(_PRECISION):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        h = _layer_norm(x, w["ln_1.g"], w["ln_1.b"])
        q, k, v = jnp.split(_linear(h, w, "attn.c_attn", operands), 3,
                            axis=-1)

        def heads(t):
            return t.reshape(b, s, n_head, d // n_head).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        scores = _mm(q, k.transpose(0, 1, 3, 2), operands) / math.sqrt(
            d // n_head)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        out = _mm(jax.nn.softmax(scores, axis=-1), v, operands)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + _linear(out, w, "attn.c_proj", operands)
        h = _layer_norm(x, w["ln_2.g"], w["ln_2.b"])
        h = _gelu_new(_linear(h, w, "mlp.c_fc", operands))
        return x + _linear(h, w, "mlp.c_proj", operands)


block = jax.jit(_block, static_argnames=("n_head", "operands"))


def _head_terms(x, tokens, ln_f_g, ln_f_b, wte, operands=None):
    """Final LayerNorm, tied head; over positions 0..S-2 of every row the
    mean next-token cross-entropy (nats) and the mean squared log-sum-exp of
    the logits (what the output z-loss multiplies). Returns (ce, lse2,
    logits)."""
    with jax.default_matmul_precision(_PRECISION):
        x = _layer_norm(x, ln_f_g.astype(jnp.float32),
                        ln_f_b.astype(jnp.float32))
        logits = _mm(x, wte.astype(jnp.float32).T, operands)
        lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        target = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                     axis=-1)[..., 0]
        return (lse - target).mean(), (lse ** 2).mean(), logits


@jax.jit
def head_loss(x, tokens, ln_f_g, ln_f_b, wte):
    """The mean next-token cross-entropy and the logits."""
    ce, _, logits = _head_terms(x, tokens, ln_f_g, ln_f_b, wte)
    return ce, logits


def loss(tokens, top: Mapping[str, Any], layers: Iterable[Dict[str, Any]],
         *, n_head: int):
    """The whole model: `top` holds wte, wpe, ln_f.g, ln_f.b; `layers`
    yields one layer's weights at a time. Returns (loss, logits)."""
    x = embed(tokens, top["wte"], top["wpe"])
    for w in layers:
        x = block(x, w, n_head=n_head)
    return head_loss(x, tokens, top["ln_f.g"], top["ln_f.b"], top["wte"])


def loss_terms(tokens, top: Mapping[str, Any],
               layers: Iterable[Dict[str, Any]],
               config: Mapping[str, Any]) -> Dict[str, Any]:
    """What the benchmark's loop asks of every reference (`loops/train.py`):
    the cross-entropy `ce` and the `logits`, the number of heads from the
    configuration's file."""
    ce, logits = loss(tokens, top, layers,
                      n_head=int(config["model"]["n_heads"]))
    return {"ce": ce, "logits": logits}


def _head_scores(x, tokens, ln_f_g, ln_f_b, wte, operands=None):
    """Final LayerNorm, tied head; for positions 0..S-2 of every row the
    log-probability of the token that follows."""
    with jax.default_matmul_precision(_PRECISION):
        x = _layer_norm(x, ln_f_g.astype(jnp.float32),
                        ln_f_b.astype(jnp.float32))
        logits = _mm(x[:, :-1], wte.astype(jnp.float32).T, operands)
        target = jnp.take_along_axis(logits, tokens[:, 1:, None],
                                     axis=-1)[..., 0]
        return target - jax.nn.logsumexp(logits, axis=-1)


head_scores = jax.jit(_head_scores, static_argnames=("operands",))


def token_logprobs(tokens, top: Mapping[str, Any],
                   layers: Iterable[Dict[str, Any]],
                   config: Mapping[str, Any], operands=None):
    """Forward only, what a scoring request is answered with
    (`loops/serve.py`): tokens [B, S] int32 -> [B, S-1] float32, the
    log-probability of each token 1..S-1 given the tokens before it. Nothing
    here knows of batches, buckets or padding. `operands` is the control."""
    n_head = int(config["model"]["n_heads"])
    x = embed(tokens, top["wte"], top["wpe"])
    for w in layers:
        x = block(x, w, n_head=n_head, operands=operands)
    return head_scores(x, tokens, top["ln_f.g"], top["ln_f.b"], top["wte"],
                       operands=operands)


def training(config: Mapping[str, Any], operands=None) -> Dict[str, Any]:
    """The model in the pieces `reference/train_steps.py` differentiates one
    at a time: `embed(top, tokens)`, `block(index)(w, x, fraction)` and
    `head(top, x, tokens) -> (ce, lse2)`. GPT-2 routes nothing: a block has
    no terms of its own and no facts."""
    n_head = int(config["model"]["n_heads"])

    def one_block(w, x, fraction):
        return _block(x, w, n_head=n_head, operands=operands), {}, {}

    def head(top, x, tokens):
        return _head_terms(x, tokens, top["ln_f.g"], top["ln_f.b"],
                           top["wte"], operands)[:2]

    return {"embed": lambda top, tokens: embed(tokens, top["wte"],
                                               top["wpe"]),
            "block": lambda index: one_block, "head": head, "routes": False}
