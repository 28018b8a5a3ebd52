"""Decoder-only transformer, TPU-first.

One implementation covers the GPT-2 family (learned positions, GELU MLP,
LayerNorm), the Llama family (RoPE, SwiGLU, RMSNorm, GQA), OLMoE's
sparse-expert block (QK-norm, dropless top-k experts, `models/moe.py`),
Qwen3-Next's hybrid (three Gated DeltaNet layers to one gated softmax
attention layer, a shared expert beside a held share of the routed ones),
learned sparse attention (an indexer's choice of keys a query, forward
only) and window and full attention mixed (Trinity-Mini's layers: three
"window" layers with RoPE to one "full" layer without positions, gated
attention under a norm on each branch's output, a leading dense layer
before the periods of routed ones, sigmoid scores with a selection-only
bias and a scale, an ungated shared expert; served only: the kernels'
backward pass knows no window) through `GPTConfig` fields — the reference
ships these as external torch models driven by Ray Train
(`release/train_tests`, SURVEY §6 north-star configs); here the model itself
is framework-native.

The layers' kinds are data: `GPTConfig.layer_pattern` is one period of them
("full": softmax attention, "linear": the gated delta rule of
`ops/delta_rule.py`, "sparse": softmax attention over the keys that the
indexer of `ops/sparse_index.py` chooses, "window": softmax attention over
a query's last `attn_window` keys), every layer is `x + mixer(norm(x))`
then `x + mlp(norm(x))` — with `post_norm`, `x + norm(mixer(norm(x)))` —
and the scan runs over periods. `lead_layers` names the kinds of the layers
before the first period, written out one by one under their own scope; their
FFN is dense (`lead_d_ff`) whatever the periods' is. Which kinds RoPE turns
is `rope_layers` (`GPT._turned`, the one place that decides). A model of one
kind is the period ("full",): its weights are stacked [L, ...] under
`params["blocks"]`; with a longer period each kind's weights are stacked
[periods, layers of that kind in a period, ...] under
`params["blocks"][kind]`; the leading layers' are a list, a layer each,
under `params["lead"]`.

A kind's weights are declared once, below `GPTConfig`: `_MIXERS` and
`_FFNS` give each kind of mixer and of FFN the function of the config that
lists its weights (shape, logical axes, how each starts) beside the method
of `GPT` that uses them; `_layer_weights` adds the layer's norms and
`_ffn_of` says which FFN a layer has by where it stands. `GPT.init`,
`GPT.param_logical_axes` and `GPTConfig.n_params` are walks over those
lists, `GPT._block` looks its two halves up in the same tables, and
`LAYER_KINDS` is the mixers' keys.

TPU-first choices:
  * scan-over-periods with stacked params — one compiled body a period,
    compile time O(1) in depth, and GSPMD gathers FSDP-sharded weights
    one layer at a time (ZeRO-3 semantics for free).
  * logical-axis names on every param/activation dim; the mesh mapping
    lives in `ray_tpu.parallel.sharding.ShardingRules`.
  * attention dispatch: Pallas flash kernel on one sequence shard,
    ring attention (`ops/ring_attention.py`) over the `sp` mesh axis when
    the sequence is context-parallel — both wrapped in `shard_map` so the
    kernel sees local blocks; everything else is GSPMD.
  * bf16 activations, f32 params/optimizer (cast at use).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ..ops._impl import resolve_impl
from ..ops.attention import (FLASH_RESIDUAL_NAMES, WINDOW_HAS_NO_BACKWARD,
                             chunk_classes, dot_product_attention)
from ..ops.delta_rule import gated_delta_rule
from ..ops.gated_deltanet import gdn_conv, gdn_gated_norm
from ..ops.ring_attention import ring_attention
from ..ops.sparse_index import sparse_index
from ..ops.swiglu import gate_activation
from ..parallel.sharding import (DEFAULT_RULES, ShardingRules,
                                 with_logical_constraint)

Params = Dict[str, Any]

# What `remat_policy="dots"` keeps of a block for the backward pass
# (`GPTConfig.remat_policy`): the names `_block` gives its projections, and
# the flash kernel's output and log-sum-exp.
_DOTS_SAVED_NAMES = ("attn_q", "attn_k", "attn_v", "mlp_up", "mlp_gate",
                     *FLASH_RESIDUAL_NAMES)

# the std of a weight's seeded normal draw
_STD = 0.02


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    n_kv_heads: Optional[int] = None  # None -> n_heads (MHA); < n_heads -> GQA
    d_ff: Optional[int] = None        # None -> 4*d_model (gelu) / 8/3*d (swiglu)
    max_seq_len: int = 1024
    # one period of the layers' kinds, each of `LAYER_KINDS`: layer i is
    # layer_pattern[i % len(layer_pattern)], and n_layers is whole periods
    layer_pattern: Tuple[str, ...] = ("full",)
    # width of an attention head; None -> d_model // n_heads
    d_head: Optional[int] = None
    # family switches
    activation: str = "gelu"          # "gelu" | "swiglu"
    # "rmsnorm_1p": RMSNorm whose scale is 1 + w, w starting at zero
    norm: str = "layernorm"           # "layernorm" | "rmsnorm" | "rmsnorm_1p"
    positions: str = "learned"        # "learned" | "rope"
    rope_theta: float = 10000.0
    # the leading share of a head's width that RoPE turns; the rest passes
    rope_fraction: float = 1.0
    tie_embeddings: bool = True
    # None -> the family's usual epsilon (1e-6 rmsnorm, 1e-5 layernorm)
    norm_eps: Optional[float] = None
    # a norm on the projected q and k before RoPE. True: RMSNorm with a
    # learned scale over all heads of the projection together (OLMoE /
    # OLMo-2). "head": the model's own RMSNorm over each head's width, one
    # scale of that width for all heads of q and one for k (Qwen3)
    qk_norm: Union[bool, str] = False
    # the q projection is twice as wide, each head's second half a gate:
    # the kernel's output is multiplied by its sigmoid before the
    # out-projection
    attn_gate: bool = False
    # "linear" layers (Gated DeltaNet): key heads (queries have as many) and
    # value heads, each key head serving value_heads / key_heads value
    # heads; their widths; the causal depthwise convolution's taps
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    # "sparse" layers: softmax attention over the `sparse_topk` causal keys
    # a query that an indexer scores highest (0: the model has none) — the
    # indexer's heads and their width, against one key head they share
    sparse_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # "window" layers: softmax attention over a causal query's last
    # `attn_window` keys, its own included (0: the model has none)
    attn_window: int = 0
    # under positions="rope", the kinds of layer whose q and k RoPE turns
    # (None: every kind that attends); a kind left out attends without
    # positions
    rope_layers: Optional[Tuple[str, ...]] = None
    # a second learned norm of the model's kind on each branch's output,
    # before it joins the stream: x + norm(f(norm(x)))
    post_norm: bool = False
    # the kinds of the layers before the scanned periods, in order (n_layers
    # counts them). Their FFN is dense, `lead_d_ff` wide (None: d_ff),
    # whatever the periods' is
    lead_layers: Tuple[str, ...] = ()
    lead_d_ff: Optional[int] = None
    # what a token's embedding is multiplied by as it enters the stream
    embed_scale: float = 1.0
    # pipeline parallelism: microbatches per global batch (0 -> = pp).
    # Stages come from the mesh's pp axis; GSPMD-style schedule (scan
    # over steps, stage-sharded rolling buffer -> collective-permute).
    pp_microbatches: int = 0
    # mixture-of-experts (0 = dense; EP is absent from the reference,
    # SURVEY §2.4 — first-class here)
    n_experts: int = 0
    moe_top_k: int = 2
    # rescale a token's top-k router weights to sum to 1 (Mixtral) or leave
    # them as the softmax gave them (OLMoE: `norm_topk_prob: false`)
    moe_norm_topk_prob: bool = True
    moe_aux_coeff: float = 0.01       # load-balancing loss, all k choices
    moe_router_z_coeff: float = 0.0   # mean squared logsumexp of the router
    # the router's scores: a "softmax" over the experts' logits or a
    # "sigmoid" of each (which has neither of the two losses above)
    moe_score: str = "softmax"
    # a bias an expert, added to the scores for the choice of the top k
    # alone (the weights are the chosen scores without it). No gradient's
    # weight: what moves it between steps is not written here
    moe_select_bias: bool = False
    # what a token's routing weights are multiplied by, after the rescaling
    moe_route_scale: float = 1.0
    # width of a SwiGLU expert that every token passes through beside the
    # routed ones (0: none), and whether it has a sigmoid gate of its own
    moe_shared_ff: int = 0
    moe_shared_gate: bool = True
    # the share of each layer's experts that lives here: experts
    # moe_first_expert .. + moe_experts_held of the n_experts routed over
    # (None: all of them). The layer computes their part of the result
    moe_first_expert: int = 0
    moe_experts_held: Optional[int] = None
    # what the router reads: "ffn", the FFN's own normed input (norm2 of the
    # stream the mixer hands on), or "mixer", the mixer's (norm1 of the
    # layer's input: the choice of experts is known before attention runs)
    moe_router_input: str = "ffn"
    # the activation on an expert's gate projection, routed or shared:
    # "silu" (SwiGLU) or "relu"
    moe_activation: str = "silu"
    # the seeded start of the token embedding: the std of its normal draw
    # (every other draw is `_STD`). At 0.02 a token's row is a fortieth of
    # what a pre-normed branch writes back, so a few layers in the stream is
    # what the context's mean left there and every token routes alike; at 1
    # the stream is the token's, as a trained model's is (for an untied
    # head: a tied one is this matrix, and its logits' scale)
    embed_std: float = _STD
    # numerics
    dtype: Any = jnp.bfloat16         # activation dtype
    param_dtype: Any = jnp.float32
    # training
    remat: bool = True
    # what the per-block checkpoint (`remat=True`) saves for the backward
    # pass. "full" saves the block's input alone and runs the whole block
    # again, flash forward kernel included (lowest memory, ~4/3x flops).
    # "dots" saves what costs more to recompute than to keep: q, k, v, the
    # MLP's up (and gate) projection, and the flash kernel's output and
    # log-sum-exp (B*S*D*2 bytes a layer, lane-dense), so a block runs three
    # kernel calls and not four. It recomputes the out-projection (its
    # output is as large as the kernel's and a d x d matmul is cheaper than
    # a second forward kernel; both do not fit beside 12 x 1024 rows of
    # GPT-2-medium on a v5e) and the elementwise rest: norms, RoPE,
    # activations, casts. The batched and grouped matmuls of an expert
    # block are not saved. `remat=False` saves whatever autodiff needs and
    # recomputes nothing.
    remat_policy: str = "full"
    z_loss: float = 1e-4
    # attention kernel: "auto" | "pallas" | "pallas_interpret" | "reference"
    attention_impl: str = "auto"

    def __post_init__(self):
        pattern = tuple(self.layer_pattern)     # a JSON file gives a list
        lead = tuple(self.lead_layers)
        object.__setattr__(self, "layer_pattern", pattern)
        object.__setattr__(self, "lead_layers", lead)
        if self.rope_layers is not None:
            object.__setattr__(self, "rope_layers", tuple(self.rope_layers))
        if not pattern or set(pattern) - set(LAYER_KINDS):
            raise ValueError(f"layer_pattern {pattern!r}: a period of "
                             f"{LAYER_KINDS}")
        if set(lead) - set(LAYER_KINDS):
            raise ValueError(f"lead_layers {lead!r}: of {LAYER_KINDS}")
        if self.n_layers < len(lead) or (
                self.n_layers - len(lead)) % len(pattern):
            raise ValueError(
                f"n_layers={self.n_layers} is not {len(lead)} leading "
                f"layers and whole periods of {pattern!r}")
        if "window" in self.kinds and self.attn_window <= 0:
            raise ValueError('a "window" layer needs attn_window')
        if "sparse" in self.kinds and not (self.sparse_topk > 0
                                        and self.index_heads > 0
                                        and self.index_head_dim > 0):
            raise ValueError('a "sparse" layer needs sparse_topk, '
                             "index_heads and index_head_dim")
        if self.moe_router_input not in ("ffn", "mixer"):
            raise ValueError(f"moe_router_input {self.moe_router_input!r}: "
                             "'ffn' or 'mixer'")
        if self.moe_router_input == "mixer" and (
                self.n_experts <= 0 or "linear" in pattern):
            raise ValueError(
                'moe_router_input="mixer" needs experts (n_experts) to route '
                "and, in every layer of a period, a mixer that hands its "
                'normed input on: not "linear"')
        gate_activation(self.moe_activation)    # an unknown one: by name

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def eps(self) -> float:
        if self.norm_eps is not None:
            return self.norm_eps
        return 1e-5 if self.norm == "layernorm" else 1e-6

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kinds(self) -> frozenset:
        """The kinds of layer the model has, leading or in a period."""
        return frozenset(self.lead_layers + self.layer_pattern)

    @property
    def periods(self) -> int:
        """Whole periods of `layer_pattern` after the leading layers."""
        return (self.n_layers - len(self.lead_layers)) // len(
            self.layer_pattern)

    @property
    def experts_held(self) -> int:
        return (self.n_experts if self.moe_experts_held is None
                else self.moe_experts_held)

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation == "swiglu":
            # llama convention: 8/3 * d, rounded up to a multiple of 256
            raw = int(8 * self.d_model / 3)
            return (raw + 255) // 256 * 256
        return 4 * self.d_model

    @property
    def n_params(self) -> int:
        """Approximate parameter count here: the weights that start as a
        normal draw (the matrices; norms, biases and decay rates are left
        out), bar the learned positions."""
        def drawn(weights):
            return sum(math.prod(w.shape) for name, w in weights.items()
                       if isinstance(w.start, float) and name != "pos_embed")

        return (drawn(_model_weights(self))
                + sum(drawn(_layer_weights(self, kind, lead=True))
                      for kind in self.lead_layers)
                + self.periods * sum(drawn(_layer_weights(self, kind))
                                     for kind in self.layer_pattern))


# --- presets ---------------------------------------------------------------

def gpt2_small(**kw) -> GPTConfig:
    return GPTConfig(n_layers=12, d_model=768, n_heads=12, **kw)


def gpt2_medium(**kw) -> GPTConfig:
    return GPTConfig(n_layers=24, d_model=1024, n_heads=16, **kw)


def _llama(**kw) -> GPTConfig:
    base = dict(activation="swiglu", norm="rmsnorm", positions="rope",
                tie_embeddings=False, vocab_size=32000, max_seq_len=2048)
    base.update(kw)
    return GPTConfig(**base)


def llama_tiny(**kw) -> GPTConfig:
    """Test-scale llama-style config (CPU-friendly)."""
    return _llama(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                  vocab_size=512, max_seq_len=256, **kw)


# --- the kinds of layer: their weights, declared once -----------------------

# `init` splits its key in twelve for a kind's layers (7..9 are the model's
# own) and the twelfth in eight more: a weight's `key` counts through both
_EXTRA = 12
# what `init` folds into its key for the leading layers' draws, from here up
# (a pattern's kinds fold in 1, 2, ...)
_LEAD_FOLD = 1 << 16


@dataclasses.dataclass(frozen=True)
class _Weight:
    """One weight of a layer (or of the model), without the stacking axes."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # the logical axis of each dimension
    # "ones" | "zeros" | "decay" | the std of a normal draw
    start: Union[str, float]
    key: Optional[int] = None           # what a draw is drawn from

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), self
        assert (self.key is None) == (self.start in ("ones", "zeros")), self

    def make(self, key_of, lead: Tuple[int, ...], dtype) -> jax.Array:
        shape = lead + self.shape
        if self.start in ("ones", "zeros"):
            return getattr(jnp, self.start)(shape, dtype)
        if self.start == "decay":
            # the public implementation's start: decay rates exp(A_log)
            # uniform in (0, 16)
            return jnp.log(jax.random.uniform(
                key_of(self.key), shape, jnp.float32, 1e-3, 16.0)
            ).astype(dtype)
        return (jax.random.normal(key_of(self.key), shape, jnp.float32)
                * self.start).astype(dtype)


def _resid_std(c: GPTConfig) -> float:
    return _STD / math.sqrt(2 * c.n_layers)


def _unit(c: GPTConfig) -> str:
    """A norm's scale of 1: "rmsnorm_1p" stores what it adds to 1."""
    return "zeros" if c.norm == "rmsnorm_1p" else "ones"


def _full_weights(c: GPTConfig) -> Dict[str, _Weight]:
    d, hd, h, hk = c.d_model, c.head_dim, c.n_heads, c.kv_heads
    weights = dict(
        wq=_Weight((d, h, (2 if c.attn_gate else 1) * hd),
                   ("embed", "heads", "head_dim"), _STD, 0),
        wk=_Weight((d, hk, hd), ("embed", "kv_heads", "head_dim"), _STD, 1),
        wv=_Weight((d, hk, hd), ("embed", "kv_heads", "head_dim"), _STD, 2),
        wo=_Weight((h, hd, d), ("heads", "head_dim", "embed"),
                   _resid_std(c), 3))
    if c.qk_norm == "head":
        weights.update(q_norm=_Weight((hd,), ("head_dim",), _unit(c)),
                       k_norm=_Weight((hd,), ("head_dim",), _unit(c)))
    elif c.qk_norm:
        weights.update(
            q_norm=_Weight((h, hd), ("heads", "head_dim"), "ones"),
            k_norm=_Weight((hk, hd), ("kv_heads", "head_dim"), "ones"))
    return weights


def _sparse_weights(c: GPTConfig) -> Dict[str, _Weight]:
    """A "full" layer's, and the indexer's: its heads' queries, the one key
    head they share with its LayerNorm, and a weight a head."""
    d, hi, di = c.d_model, c.index_heads, c.index_head_dim
    return dict(
        _full_weights(c),
        wq_idx=_Weight((d, hi, di), ("embed", None, None), _STD, _EXTRA + 4),
        wk_idx=_Weight((d, di), ("embed", None), _STD, _EXTRA + 5),
        w_idx=_Weight((d, hi), ("embed", None), _STD, _EXTRA + 6),
        k_idx_norm=_Weight((di,), (None,), "ones"),
        k_idx_bias=_Weight((di,), (None,), "zeros"))


def _linear_weights(c: GPTConfig) -> Dict[str, _Weight]:
    d, nv = c.d_model, c.linear_value_heads
    keys_w = c.linear_key_heads * c.linear_key_dim
    values_w = nv * c.linear_value_dim
    # the projections' columns are q, k, v and z side by side and the
    # convolution runs along them: not split over tp
    return dict(
        w_qkvz=_Weight((d, 2 * keys_w + 2 * values_w), ("embed", None),
                       _STD, _EXTRA + 4),
        w_ba=_Weight((d, 2 * nv), ("embed", None), _STD, _EXTRA + 5),
        conv_w=_Weight((c.linear_conv, 2 * keys_w + values_w), (None, None),
                       _STD, _EXTRA + 6),
        A_log=_Weight((nv,), (None,), "decay", _EXTRA + 7),
        dt_bias=_Weight((nv,), (None,), "ones"),       # the step's bias at 1
        lin_norm=_Weight((c.linear_value_dim,), (None,), "ones"),
        w_lin_out=_Weight((values_w, d), (None, "embed"), _resid_std(c),
                          _EXTRA + 3))


def _dense_weights(c: GPTConfig, lead: bool = False) -> Dict[str, _Weight]:
    d = c.d_model
    f = c.lead_d_ff if lead and c.lead_d_ff is not None else c.ff_dim
    weights = dict(
        w_up=_Weight((d, f), ("embed", "mlp"), _STD, 4),
        w_down=_Weight((f, d), ("mlp", "embed"), _resid_std(c), 5))
    if c.activation == "swiglu":
        weights["w_gate"] = _Weight((d, f), ("embed", "mlp"), _STD, 6)
    return weights


def _expert_weights(c: GPTConfig, lead: bool = False) -> Dict[str, _Weight]:
    d, f, held = c.d_model, c.ff_dim, c.experts_held
    weights = dict(
        router=_Weight((d, c.n_experts), ("embed", None), _STD, 4),
        w_up=_Weight((held, d, f), ("expert", "embed", "mlp"), _STD, 5),
        w_gate=_Weight((held, d, f), ("expert", "embed", "mlp"), _STD, 6),
        w_down=_Weight((held, f, d), ("expert", "mlp", "embed"),
                       _resid_std(c), 10))
    if c.moe_select_bias:
        weights["router_bias"] = _Weight((c.n_experts,), (None,), "zeros")
    if c.moe_shared_ff:
        fs = c.moe_shared_ff
        weights.update(
            ws_up=_Weight((d, fs), ("embed", "mlp"), _STD, _EXTRA + 0),
            ws_gate=_Weight((d, fs), ("embed", "mlp"), _STD, _EXTRA + 1),
            ws_down=_Weight((fs, d), ("mlp", "embed"), _resid_std(c),
                            _EXTRA + 2))
        if c.moe_shared_gate:
            weights["ws_open"] = _Weight((d,), (None,), "zeros")
    return weights


# the experts' weights that the grouped matmuls take: a custom call's
# operand, into which no slice of a stack is fused (`moe.moe_ffn`'s `layer`)
_EXPERT_STACKS = ("w_up", "w_gate", "w_down")


def _experts_apart(stacked: Params, lead: int) -> Tuple[Params, Params]:
    """The stacked weights of a kind's layers ([*stack, ...], `lead` stacking
    axes) in two: what a scan over the layers slices — every weight but the
    experts' three, and each layer's place among the kind's, `experts_at` —
    and the three as [L, E, ...], which is the same bytes."""
    stack = stacked[_EXPERT_STACKS[0]].shape[:lead]
    scanned = {name: w for name, w in stacked.items()
               if name not in _EXPERT_STACKS}
    scanned["experts_at"] = jnp.arange(
        math.prod(stack), dtype=jnp.int32).reshape(stack)
    return scanned, {name: stacked[name].reshape(
        -1, *stacked[name].shape[lead:]) for name in _EXPERT_STACKS}


def _unless_differentiated(fast, plain):
    """`fast`, a function of arrays whose values are `plain`'s, with
    `plain`'s derivatives: jax takes the rule below wherever the call is
    differentiated, forward or backward, and `fast` as it stands elsewhere.
    `fast` is jitted: traced under `custom_jvp` as it stands, a served
    bucket's program took 0.7 s longer to trace on the chip's host, a sixth
    of a replica's start over its six programs; jitted, what the parent
    took (PERF.md, PR 54)."""
    fn = jax.custom_jvp(jax.jit(fast))
    fn.defjvp(lambda primals, tangents: jax.jvp(plain, primals, tangents))
    return fn


class _Half(NamedTuple):
    """A kind of mixer or of FFN: what lists its weights (of the config; an
    FFN's also of whether the layer is a leading one), and what applies them
    (of a `GPT`: the model, the layer's input, positions, weights), giving
    its output and its facts — a mixer also the normed input it worked on
    (None from one that does not hand it on), which an FFN is given after
    its own where the model's router reads it (`moe_router_input`)."""
    weights: Callable[[GPTConfig], Dict[str, _Weight]]
    apply: Callable[..., Any]


_MIXERS = {
    "full": _Half(_full_weights,
                  lambda m, x, positions, w: m._full_mixer(x, positions, w)),
    "linear": _Half(_linear_weights,
                    lambda m, x, positions, w: (m._linear_mixer(x, w), {},
                                                None)),
    "sparse": _Half(_sparse_weights,
                    lambda m, x, positions, w: m._full_mixer(
                        x, positions, w, kind="sparse")),
    "window": _Half(_full_weights,
                    lambda m, x, positions, w: m._full_mixer(
                        x, positions, w, kind="window")),
}
_FFNS = {
    "dense": _Half(_dense_weights, lambda m, h, w, tap: m._dense_ffn(h, w)),
    "experts": _Half(_expert_weights,
                     lambda m, h, w, tap: m._expert_ffn(h, w, tap)),
}
LAYER_KINDS = tuple(_MIXERS)


def _ffn_of(c: GPTConfig, lead: bool = False) -> _Half:
    """The FFN of a layer by where it stands: dense in a leading layer,
    else the model's."""
    return _FFNS["experts" if c.n_experts > 0 and not lead else "dense"]


def _layer_weights(c: GPTConfig, kind: str, lead: bool = False
                   ) -> Dict[str, _Weight]:
    """The weights of one layer of `kind`, a leading one or one of a period:
    its norms', its mixer's and its FFN's."""
    d = c.d_model
    norms = ("1", "2") + (("1_post", "2_post") if c.post_norm else ())
    weights = dict(**{"norm" + n: _Weight((d,), (None,), _unit(c))
                      for n in norms},
                   **_MIXERS[kind].weights(c),
                   **_ffn_of(c, lead).weights(c, lead))
    if c.norm == "layernorm":
        weights.update({"bias" + n: _Weight((d,), (None,), "zeros")
                        for n in norms})
    return weights


def _model_weights(c: GPTConfig) -> Dict[str, _Weight]:
    """The model's weights outside its layers."""
    d = c.d_model
    weights = dict(
        tok_embed=_Weight((c.vocab_size, d), ("vocab", "embed"), c.embed_std,
                          7),
        norm_f=_Weight((d,), (None,), _unit(c)))
    if c.positions == "learned":
        weights["pos_embed"] = _Weight((c.max_seq_len, d), (None, "embed"),
                                       _STD, 8)
    if c.norm == "layernorm":
        weights["bias_f"] = _Weight((d,), (None,), "zeros")
    if not c.tie_embeddings:
        weights["lm_head"] = _Weight((d, c.vocab_size), ("embed", "vocab"),
                                     _STD, 9)
    return weights


class GPT:
    """Functional model: `init` → params pytree, `apply` → logits.

    Parallelism is injected at construction: a `Mesh` + `ShardingRules`.
    With no mesh (unit tests, single device) everything degrades to plain
    single-device JAX.
    """

    def __init__(self, config: GPTConfig, mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None):
        self.config = config
        self.mesh = mesh
        self.rules = rules if rules is not None else DEFAULT_RULES
        if self.pp_stages > 1:
            if config.n_layers % self.pp_stages:
                raise ValueError(
                    f"n_layers={config.n_layers} must divide into "
                    f"pp={self.pp_stages} stages")
            if config.n_experts > 0:
                raise NotImplementedError(
                    "EP+PP combined (MoE aux-loss masking across pipeline "
                    "bubbles) is not supported yet")
            if len(config.layer_pattern) > 1 or config.lead_layers:
                raise NotImplementedError(
                    f"pipeline stages of a layer pattern "
                    f"{config.lead_layers + config.layer_pattern!r} (stages "
                    "of unequal layer kinds) are not supported yet: pp "
                    'needs the period ("full",) and no leading layers')

    def _mesh_size(self, logical: str) -> int:
        """Size of the one mesh axis `logical` maps to (1 with no mesh)."""
        if self.mesh is None:
            return 1
        ax = self.rules.mesh_axes(logical)
        if isinstance(ax, str) and ax in self.mesh.shape:
            return self.mesh.shape[ax]
        return 1

    @property
    def pp_stages(self) -> int:
        return self._mesh_size("stage")

    @property
    def _kinds(self) -> Dict[str, int]:
        """Each kind of the pattern with its layers a period, in order of
        first appearance."""
        pattern = self.config.layer_pattern
        return {kind: pattern.count(kind) for kind in dict.fromkeys(pattern)}

    # -- parameters --------------------------------------------------------

    def _init_layers(self, kind: str, stack: Tuple[int, ...], keys,
                     lead: bool = False) -> Params:
        """The stacked weights of the layers of one kind, `stack` the
        stacking axes, `keys` the twelve."""
        extra = jax.random.split(keys[_EXTRA - 1], 8)

        def key_of(i):
            return keys[i] if i < _EXTRA else extra[i - _EXTRA]

        return {name: w.make(key_of, stack, self.config.param_dtype)
                for name, w in _layer_weights(self.config, kind,
                                              lead).items()}

    def init(self, rng: jax.Array) -> Params:
        c = self.config
        L = c.n_layers - len(c.lead_layers)
        keys = jax.random.split(rng, _EXTRA)
        if len(c.layer_pattern) == 1:
            blocks = self._init_layers(c.layer_pattern[0], (L,), keys)
        else:
            blocks = {
                kind: self._init_layers(
                    kind, (c.periods, n),
                    jax.random.split(jax.random.fold_in(rng, i + 1), _EXTRA))
                for i, (kind, n) in enumerate(self._kinds.items())}
        P = self.pp_stages
        if P > 1:
            # stage-stack: [L, ...] -> [P, L/P, ...]; the stage axis is
            # sharded over pp so each stage holds only its layers
            blocks = jax.tree_util.tree_map(
                lambda a: a.reshape((P, L // P) + a.shape[1:]), blocks)
        params = {"blocks": blocks,
                  **{name: w.make(lambda i: keys[i], (), c.param_dtype)
                     for name, w in _model_weights(c).items()}}
        if c.lead_layers:
            # a list, a layer each, unstacked: they need not be of one kind
            params["lead"] = [
                self._init_layers(
                    kind, (), jax.random.split(
                        jax.random.fold_in(rng, _LEAD_FOLD + i), _EXTRA),
                    lead=True)
                for i, kind in enumerate(c.lead_layers)]
        return params

    def param_logical_axes(self) -> Params:
        """Pytree matching `init` output: tuples of logical axis names."""
        c = self.config

        def axes(kind, stack, lead=False):
            return {name: stack + w.axes
                    for name, w in _layer_weights(c, kind, lead).items()}

        if len(c.layer_pattern) == 1:
            blocks: Params = axes(
                c.layer_pattern[0],
                ("stage", "layers") if self.pp_stages > 1 else ("layers",))
        else:
            blocks = {kind: axes(kind, ("layers", None))
                      for kind in self._kinds}
        lead = ({"lead": [axes(kind, (), lead=True)
                          for kind in c.lead_layers]}
                if c.lead_layers else {})
        return {**{name: w.axes for name, w in _model_weights(c).items()},
                "blocks": blocks, **lead}

    # -- building blocks ---------------------------------------------------

    def _norm(self, x, scale, bias):
        c = self.config
        xf = x.astype(jnp.float32)
        if c.norm in ("rmsnorm", "rmsnorm_1p"):
            xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + c.eps)
            scale = scale.astype(jnp.float32)
            if c.norm == "rmsnorm_1p":
                scale = 1.0 + scale
            return (xf * scale).astype(c.dtype)
        mean = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        xf = (xf - mean) * lax.rsqrt(var + c.eps)
        out = xf * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(c.dtype)

    def _qk_norm(self, x, scale):
        """RMSNorm over all heads of a projection together. x: [B, S, H, Dh],
        scale: [H, Dh]."""
        c = self.config
        xf = x.astype(jnp.float32)
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, (-2, -1), keepdims=True)
                            + c.eps)
        return (xf * scale.astype(jnp.float32)).astype(c.dtype)

    def _rope(self, x, positions):
        """x: [B, S, H, D_h]; positions: [B, S]. Turns the first
        `rope_fraction` of each head's width (rotate-half over that part)
        and passes the rest."""
        c = self.config
        hd = int(x.shape[-1] * c.rope_fraction)
        if hd < x.shape[-1]:
            turned = self._rope_whole(x[..., :hd], positions)
            return jnp.concatenate([turned, x[..., hd:]], -1)
        return self._rope_whole(x, positions)

    def _rope_whole(self, x, positions):
        c = self.config
        hd = x.shape[-1]
        half = hd // 2
        freqs = c.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                                 / half)
        angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
        return out.astype(x.dtype)

    def _attention(self, q, k, v, selection=None, window=None):
        """q: [B, S, H, Dh], k/v: [B, S, Hk, Dh] → [B, S, H, Dh]; with
        `selection` (`ops.sparse_index.Selection`) over each query's chosen
        keys alone, on one device; with `window` over each query's last
        `window` keys, its own included.

        The flash kernels read q, k, v as the projections wrote them and
        write their gradients the same way (`ops/attention.py`,
        `seq_major`): nothing is transposed on either side of them. Ring
        attention keeps [B, H, S, Dh] and wants the sequence axis *locally*
        sharded, so both pallas paths run under shard_map with specs derived
        from the mesh.
        """
        c = self.config
        if selection is not None:
            if self.mesh is not None:
                raise NotImplementedError(
                    "a \"sparse\" layer on a mesh: the indexer's choice of "
                    "keys is not partitioned (a Mosaic call is not, and no "
                    "shard_map carries it yet)")
            return dot_product_attention(
                q, k, v, causal=True, impl=c.attention_impl, seq_major=True,
                selection=selection)
        if self.pp_stages > 1:
            # pipeline mode runs blocks under vmap over the stage axis;
            # shard_map can't nest there, so use the einsum attention and
            # let GSPMD partition it (pallas-in-pipeline: future work)
            return dot_product_attention(q, k, v, causal=True,
                                         impl="reference", seq_major=True,
                                         window=window)
        if self._mesh_size("act_seq") > 1:      # sequence parallelism
            if window is not None:
                raise NotImplementedError(
                    "a \"window\" layer under sequence parallelism: ring "
                    "attention passes every block of keys around the ring, "
                    "and knows no window")
            # Specs derive from the rules table like every other sharding
            # decision; the ring axis is whatever act_seq maps to.
            spec_q = self.rules.spec("act_batch", "act_heads", "act_seq",
                                     "head_dim")
            spec_kv = self.rules.spec("act_batch", "act_kv_heads",
                                      "act_seq", "head_dim")
            seq_axis = self.rules.mesh_axes("act_seq")
            assert isinstance(seq_axis, str), (
                "ring attention needs act_seq mapped to a single mesh axis")

            def local(qb, kb, vb):
                return ring_attention(qb, kb, vb, seq_axis, True, None,
                                      c.attention_impl)

            ot = jax.shard_map(local, mesh=self.mesh,
                               in_specs=(spec_q, spec_kv, spec_kv),
                               out_specs=spec_q, check_vma=False)(
                *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
            return jnp.swapaxes(ot, 1, 2)

        def local(qb, kb, vb):
            return dot_product_attention(qb, kb, vb, causal=True,
                                         impl=c.attention_impl,
                                         seq_major=True, window=window)

        if self.mesh is None:
            return local(q, k, v)

        # across shard_map as [B, S, H * Dh]: the reshapes on either side
        # of the boundary then cancel against the projections' and the
        # kernels' own, where a [.., H, 64] array crossing it would be
        # copied to a padded layout and back
        def flat(x):
            return x.reshape(*x.shape[:2], -1)

        def local_flat(*qkv):
            return flat(local(*(x.reshape(*x.shape[:2], -1, q.shape[-1])
                                for x in qkv)))

        spec_q = self.rules.spec("act_batch", None, "act_heads")
        spec_kv = self.rules.spec("act_batch", None, "act_kv_heads")
        out = jax.shard_map(local_flat, mesh=self.mesh,
                            in_specs=(spec_q, spec_kv, spec_kv),
                            out_specs=spec_q, check_vma=False)(
            flat(q), flat(k), flat(v))
        return out.reshape(q.shape)

    def _delta_rule(self, q, k, v, g, beta):
        """q, k: [B, S, Hk, Dk], v: [B, S, Hv, Dv], g, beta: [B, S, Hv] →
        [B, S, Hv, Dv].

        On a mesh the rule runs under shard_map, as the flash kernels do (a
        Mosaic call is not partitioned automatically): rows over the batch
        axes, whole key heads with their value heads over the heads' axis
        where it divides them, the sequence whole on every device (the
        state runs along it). q, k, v and o cross the boundary as
        [B, S, H * D], for `_attention`'s reason. A pipeline never comes
        here: `GPT` refuses a layer pattern on one."""
        c = self.config

        def local(*xs):
            return gated_delta_rule(*xs, impl=c.attention_impl)

        if self.mesh is None:
            return local(q, k, v, g, beta)

        def flat(x):
            return x.reshape(*x.shape[:2], -1)

        def local_flat(qb, kb, vb, gb, bb):
            return flat(local(*(x.reshape(*x.shape[:2], -1, whole.shape[-1])
                                for x, whole in ((qb, q), (kb, k), (vb, v))),
                              gb, bb))

        heads = self.rules.mesh_axes("act_heads") or ()
        over = math.prod(self.mesh.shape.get(a, 1) for a in (
            (heads,) if isinstance(heads, str) else heads))
        spec = self.rules.spec("act_batch", None,
                               "act_heads" if q.shape[2] % over == 0
                               else None)
        out = jax.shard_map(local_flat, mesh=self.mesh, in_specs=(spec,) * 5,
                            out_specs=spec, check_vma=False)(
            flat(q), flat(k), flat(v), g, beta)
        return out.reshape(v.shape)

    def _constrain(self, x, *logical):
        return with_logical_constraint(x, *logical, rules=self.rules,
                                       mesh=self.mesh)

    def _index(self, h, positions, w):
        """The indexer of a "sparse" layer on the normed input h: its heads'
        queries, the key head they share (LayerNorm, then RoPE over the
        whole width as on the queries) and a weight a head, and the choice
        of `sparse_topk` keys a query they make."""
        c = self.config
        dt = c.dtype
        wq = w["wq_idx"].astype(dt)
        q = jnp.einsum("bsd,de->bse", h, wq.reshape(wq.shape[0], -1))
        k = jnp.einsum("bsd,de->bse", h, w["wk_idx"].astype(dt))
        weight = jnp.einsum("bsd,dh->bsh", h, w["w_idx"].astype(dt))
        kf = k.astype(jnp.float32)
        kf = kf - jnp.mean(kf, -1, keepdims=True)
        kf = kf * lax.rsqrt(jnp.mean(kf * kf, -1, keepdims=True) + c.eps)
        k = (kf * w["k_idx_norm"].astype(jnp.float32)
             + w["k_idx_bias"].astype(jnp.float32)).astype(dt)
        q = self._rope_whole(q.reshape(*q.shape[:2], *wq.shape[1:]),
                             positions)
        k = self._rope_whole(k[:, :, None], positions)[:, :, 0]
        return sparse_index(q, k, weight, c.sparse_topk,
                            impl=c.attention_impl)

    def _turned(self, kind: str) -> bool:
        """Whether RoPE turns the q and k of a layer of `kind`: the one place
        that says which layers attend with positions."""
        c = self.config
        return c.positions == "rope" and (c.rope_layers is None
                                          or kind in c.rope_layers)

    def _join(self, x, branch, w, post: str):
        """The stream plus a branch's output, through the branch's own norm
        first where the model has one (`post_norm`; `post` names it)."""
        if self.config.post_norm:
            branch = self._norm(branch, w["norm" + post], w.get("bias" + post))
        return x + self._constrain(branch, "act_batch", "act_seq",
                                   "act_embed")

    def _full_mixer(self, x, positions, w, kind="full"):
        """Softmax attention on the normed input, residual included, the
        layer's facts and that normed input: in a "sparse" layer over the
        keys the layer's indexer
        chooses, and how many (query, key) pairs that was; in a "window"
        layer over each query's last `attn_window` keys, and how many
        rectangles of scores the forward kernel's walk of that band holds."""
        c = self.config
        dt = c.dtype
        sparse = kind == "sparse"
        window = c.attn_window if kind == "window" else None
        selection, facts = None, {}
        # the scopes are metadata on the ops (the profiler's trace and the
        # HLO carry them), the program is the same with or without
        with jax.named_scope("attn_qkv"):
            h = self._norm(x, w["norm1"], w.get("bias1"))
            # [B, S, H * Dh] against the weight as [D, H * Dh], and saved so
            # under "dots": a 64-wide minor axis is stored, copied and
            # multiplied at half of the 128 lanes, and a reshape to it from
            # a kernel's full-lane operand is a copy. The heads are split
            # out only for what works on them (QK-norm, RoPE, the kernels'
            # own block maps).
            def project(name, saved):
                wt = w[name].astype(dt)
                y = jnp.einsum("bsd,de->bse", h,
                               wt.reshape(wt.shape[0], -1))
                y = checkpoint_name(y, saved)
                return y.reshape(*y.shape[:2], *wt.shape[1:])

            q = project("wq", "attn_q")
            k = project("wk", "attn_k")
            v = project("wv", "attn_v")
            if c.attn_gate:
                q, gate = jnp.split(q, 2, axis=-1)
            if c.qk_norm == "head":
                q = self._norm(q, w["q_norm"], None)
                k = self._norm(k, w["k_norm"], None)
            elif c.qk_norm:
                q = self._qk_norm(q, w["q_norm"])
                k = self._qk_norm(k, w["k_norm"])
            if self._turned(kind):
                q = self._rope(q, positions)
                k = self._rope(k, positions)
            q = self._constrain(q, "act_batch", "act_seq", "act_heads",
                                "head_dim")
            k = self._constrain(k, "act_batch", "act_seq", "act_kv_heads",
                                "head_dim")
            if sparse:
                with jax.named_scope("dsa_index"):
                    selection = self._index(h, positions, w)
                    facts["dsa_selected_pairs"] = selection.counts.sum()
        if window is not None:
            live = chunk_classes(x.shape[1], x.shape[1], True, window=window)
            facts["attn_window_rects"] = jnp.int32(
                x.shape[0] * c.n_heads * (live["interior"] + live["edge"]))
        with jax.named_scope("attn_kernel"), (
                jax.named_scope("dsa_attend") if sparse
                else jax.named_scope("attn_window") if window is not None
                else contextlib.nullcontext()):
            attn = self._attention(q, k, v, selection, window)
        with jax.named_scope("attn_out"):
            if c.attn_gate:
                attn = attn * jax.nn.sigmoid(gate)
            wo = w["wo"].astype(dt)
            attn = jnp.einsum("bse,ed->bsd",
                              attn.reshape(*attn.shape[:2], -1),
                              wo.reshape(-1, wo.shape[-1]))
            return self._join(x, attn, w, "1_post"), facts, h

    def _over_rows(self, fn, arrays, weights):
        """fn(*arrays, *weights) for [B, S, ...] arrays: on a mesh under
        shard_map, rows over the batch axes and everything else whole on
        every device (the Gated DeltaNet layer outside its rule is not split
        over tp: `_linear_weights`), for `_delta_rule`'s reason."""
        if self.mesh is None:
            return fn(*arrays, *weights)
        rows = self.rules.spec("act_batch", None, None)
        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(rows,) * len(arrays) + (P(),) * len(weights),
            out_specs=rows, check_vma=False)(*arrays, *weights)

    def _linear_mixer(self, x, w):
        """Gated DeltaNet on the normed input, residual included: the
        projections (q~ k~ v~ | z | b a), the convolution pass (a short
        causal convolution, SiLU and the per-head q / k normalisation:
        `ops.gated_deltanet.gdn_conv`), the gated delta rule per value head,
        the gated-norm pass (`gdn_gated_norm`) and the out-projection. q, k,
        v, o and z stay [B, S, H * D] from the projection to the
        out-projection; `attention_impl` picks the kernels or the `jnp` form
        of the two passes as it does the rule's."""
        c = self.config
        dt = c.dtype
        f32 = jnp.float32
        nk, nv = c.linear_key_heads, c.linear_value_heads
        dk, dv = c.linear_key_dim, c.linear_value_dim
        mixed = 2 * nk * dk + nv * dv
        with jax.named_scope("attn_qkv"):
            h = self._norm(x, w["norm1"], w.get("bias1"))
            with jax.named_scope("gdn_proj"):
                # q~ k~ v~ and z as two products: each pass then reads and
                # differentiates an array of its own, where columns of one
                # would be sliced out and their gradients joined again
                w_qkvz = w["w_qkvz"].astype(dt)
                qkv = jnp.einsum("bsd,de->bse", h, w_qkvz[:, :mixed])
                z = jnp.einsum("bsd,de->bse", h, w_qkvz[:, mixed:])
                ba = jnp.einsum("bsd,de->bse", h, w["w_ba"].astype(dt),
                                preferred_element_type=f32)
            with jax.named_scope("gdn_conv"):
                q, k, v = self._over_rows(
                    functools.partial(
                        gdn_conv, key_heads=nk, key_dim=dk, value_dim=dv,
                        eps=c.eps, impl=c.attention_impl),
                    (qkv,), (w["conv_w"],))
                q, k, v = (part.reshape(*part.shape[:2], heads, -1)
                           for part, heads in ((q, nk), (k, nk), (v, nv)))
                beta = jax.nn.sigmoid(ba[..., :nv])
                g = -jnp.exp(w["A_log"].astype(f32)) * jax.nn.softplus(
                    ba[..., nv:] + w["dt_bias"].astype(f32))
        with jax.named_scope("attn_kernel"), jax.named_scope("gdn_rule"):
            o = self._delta_rule(q, k, v, g, beta)
        with jax.named_scope("attn_out"), jax.named_scope("gdn_out"):
            o = self._over_rows(
                functools.partial(gdn_gated_norm, eps=c.eps,
                                  impl=c.attention_impl),
                (o.reshape(*o.shape[:2], -1), z), (w["lin_norm"],))
            out = jnp.einsum("bse,ed->bsd", o, w["w_lin_out"].astype(dt))
            return self._join(x, out, w, "1_post")

    def _dense_ffn(self, h, w):
        """The MLP on the normed input h: (its output, no facts)."""
        c = self.config
        dt = c.dtype
        up = jnp.einsum("bsd,df->bsf", h, w["w_up"].astype(dt))
        up = checkpoint_name(up, "mlp_up")
        if c.activation == "swiglu":
            gate = jnp.einsum("bsd,df->bsf", h, w["w_gate"].astype(dt))
            gate = checkpoint_name(gate, "mlp_gate")
            act = jax.nn.silu(gate) * up
        else:
            act = jax.nn.gelu(up, approximate=True)
        act = self._constrain(act, "act_batch", "act_seq", "act_mlp")
        return jnp.einsum("bsf,fd->bsd", act, w["w_down"].astype(dt)), {}

    def _expert_ffn(self, h, w, tap=None):
        """The routed experts held here, and the shared one where the model
        has it, on the normed input h, routed by `tap` where given (the
        mixer's normed input) and by h else: (their output, the router's
        facts)."""
        from .moe import moe_ffn, shared_expert_ffn
        c = self.config
        down, aux = moe_ffn(
            h, w["router"], w["w_up"], w["w_gate"], w["w_down"],
            layer=w.get("experts_at"), router_x=tap, act=c.moe_activation,
            top_k=c.moe_top_k, norm_topk_prob=c.moe_norm_topk_prob,
            first_expert=c.moe_first_expert, dtype=c.dtype,
            score=c.moe_score, select_bias=w.get("router_bias"),
            route_scale=c.moe_route_scale,
            # a Mosaic call is not partitioned automatically: on a mesh the
            # router's top-k is `lax.top_k` and the held experts' rows are
            # summed in `jnp`
            impl=c.attention_impl if self.mesh is None else "reference")
        if c.moe_shared_ff:
            down = down + shared_expert_ffn(
                h, w["ws_up"], w["ws_gate"], w["ws_down"], w.get("ws_open"),
                dtype=c.dtype, act=c.moe_activation)
        return down, aux

    def _block(self, x, positions, w, kind="full", lead=False):
        """One block of the given kind, a leading one or one of a period.
        x: [B, S, D] bf16."""
        c = self.config
        x, facts, tap = _MIXERS[kind].apply(self, x, positions, w)
        # a layer without an indexer chose none, one without a window walked
        # none: the facts of every layer of a period are stacked
        if "sparse" in c.kinds:
            facts.setdefault("dsa_selected_pairs", jnp.int32(0))
        if "window" in c.kinds:
            facts.setdefault("attn_window_rects", jnp.int32(0))
        with jax.named_scope("mlp"):
            h = self._norm(x, w["norm2"], w.get("bias2"))
            down, aux = _ffn_of(c, lead).apply(
                self, h, w, tap if c.moe_router_input == "mixer" else None)
            x = self._join(x, down, w, "2_post")
        return x, {**facts, **aux}

    # -- forward -----------------------------------------------------------

    def apply(self, params: Params, tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens: [B, S] int32 → logits [B, S, V] (f32)."""
        return self.forward_with_aux(params, tokens, positions)[0]

    def forward_with_aux(self, params: Params, tokens: jax.Array,
                         positions: Optional[jax.Array] = None):
        """Returns (logits, aux): with experts, a softmax router's two
        losses as means over the layers and, per layer, `moe_expert_tokens`
        [n_layers, n_experts], `moe_expert_choice` [n_layers, tokens,
        top_k] and, where the layers hold a share of their experts,
        `moe_routed_here` and `moe_rows_walked` [n_layers]; with "sparse"
        layers, `dsa_selected_pairs` [n_layers], the (query, key) pairs
        each layer's indexer chose; with "window" layers,
        `attn_window_rects` [n_layers], the rectangles of scores the band
        holds under the forward kernel's tiling (0 for a layer of another
        kind); with none of these, an empty dict. A fact's layers are those
        that have it, in order: the router's leave the leading layers
        out."""
        c = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32),
                tokens.shape)
        # Embedding lookup with an EXPLICIT all-gather of the
        # (vocab/tp, embed/fsdp)-sharded table and (batch, seq)-sharded
        # indices: left to inference, the partitioner shards the gather
        # output on tp and then falls back to "involuntary full
        # rematerialization" resharding it to (batch, seq) — the
        # spmd_partitioner.cc warning. Replicated
        # operand + sharded indices computes the gather directly in the
        # activation sharding.
        with jax.named_scope("embed"):
            tbl = self._constrain(params["tok_embed"].astype(c.dtype),
                                  None, None)
            tokens = self._constrain(tokens, "act_batch", "act_seq")
            x = tbl[tokens]
            if c.positions == "learned":
                pos_tbl = self._constrain(
                    params["pos_embed"].astype(c.dtype), None, None)
                x = x + pos_tbl[positions]
            if c.embed_scale != 1.0:
                x = x * jnp.asarray(c.embed_scale, c.dtype)
            x = self._constrain(x, "act_batch", "act_seq", "act_embed")

        block_fns = {kind: functools.partial(self._block, kind=kind)
                     for kind in self._kinds}
        block_fns.update({
            ("lead", kind): functools.partial(self._block, kind=kind,
                                              lead=True)
            for kind in c.lead_layers})
        if c.remat:
            cp = jax.checkpoint_policies
            policies = {
                "full": cp.nothing_saveable,
                "dots": cp.save_only_these_names(*_DOTS_SAVED_NAMES),
            }
            if c.remat_policy not in policies:
                raise ValueError(
                    f"remat_policy must be one of {sorted(policies)}, "
                    f"got {c.remat_policy!r} (use remat=False to disable "
                    "rematerialization entirely)")
            block_fns = {kind: jax.checkpoint(
                fn, policy=policies[c.remat_policy])
                for kind, fn in block_fns.items()}

        lead_facts = []
        for kind, layer_w in zip(c.lead_layers, params.get("lead", ())):
            # a scope of their own: a trace tells them from the periods'
            with jax.named_scope("lead"):
                x, facts = block_fns["lead", kind](x, positions, layer_w)
            lead_facts.append(facts)

        if self.pp_stages > 1:
            x = self._pipeline_blocks(block_fns["full"], params["blocks"], x,
                                      positions)
            aux_per_layer = {}
        else:
            through = functools.partial(self._through_blocks, block_fns)
            if self._experts_lie_ready(params["blocks"]):
                # read where they lie, unless the weights are differentiated:
                # then the stack closed over would be handed a cotangent of
                # its own size a layer, where a scanned slice's is the
                # layer's, so a derivative is that of the sliced form
                through = _unless_differentiated(
                    functools.partial(through, in_place=True), through)
            x, aux_per_layer = through(x, positions, params["blocks"])
        for facts in reversed(lead_facts):
            for name, fact in facts.items():
                aux_per_layer[name] = (
                    jnp.concatenate([fact[None], aux_per_layer[name]])
                    if name in aux_per_layer else fact[None])
        # one scope, `head_loss`, for the final norm and the logits here
        # and for the cross-entropy in `loss`
        with jax.named_scope("head_loss"):
            x = self._norm(x, params["norm_f"], params.get("bias_f"))
            if c.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x,
                                    params["tok_embed"].astype(c.dtype))
            else:
                logits = jnp.einsum("bsd,dv->bsv", x,
                                    params["lm_head"].astype(c.dtype))
            logits = self._constrain(logits, "act_batch", "act_seq",
                                     "act_vocab")
            logits = logits.astype(jnp.float32)
        # the scan stacked each layer's facts: a loss term is [L] now
        aux = {k: v.mean() if k in ("moe_aux_loss", "moe_router_z") else v
               for k, v in aux_per_layer.items()}
        return logits, aux

    def _experts_lie_ready(self, blocks: Params) -> bool:
        """Whether the layers' grouped matmuls can read the experts' weights
        in the stacks `blocks` holds them in (`moe.moe_ffn`'s `layer`): the
        FFN is the experts', the stacks are in the compute dtype already (a
        served replica's; master weights in float32 are cast a layer, and
        the cast writes the layer's copy whatever it reads), and no mesh
        shards them (merging the layers' axis with the experts' would move a
        sharded axis)."""
        c = self.config
        if c.n_experts <= 0 or self.mesh is not None:
            return False
        kinds = [blocks] if len(c.layer_pattern) == 1 else blocks.values()
        return all(w[name].dtype == jnp.dtype(c.dtype)
                   for w in kinds for name in _EXPERT_STACKS)

    def _through_blocks(self, block_fns, x, positions, blocks: Params,
                        in_place: bool = False):
        """x through the layers after the leading ones: a scan over the
        layers of the one kind, or over the periods with a period's layers
        written out. Returns x and the layers' facts, [L, ...] each.

        `in_place` (`_experts_lie_ready`): the experts' three stacks are not
        scanned but closed over, as [L, E, ...], and a layer is handed them
        with its place in them, `experts_at`, which is scanned with the
        other weights' slices."""
        c = self.config
        if len(c.layer_pattern) == 1:
            block_fn = block_fns[c.layer_pattern[0]]
            stacks = {}
            if in_place:
                blocks, stacks = _experts_apart(blocks, lead=1)

            def scan_body(x, layer_w):
                return block_fn(x, positions, {**layer_w, **stacks})

            return lax.scan(scan_body, x, blocks)
        stacks = dict.fromkeys(blocks, {})
        if in_place:
            apart = {kind: _experts_apart(w, lead=2)
                     for kind, w in blocks.items()}
            blocks, stacks = ({kind: halves[i] for kind, halves
                               in apart.items()} for i in (0, 1))

        def period_body(x, period_w):
            seen = dict.fromkeys(period_w, 0)
            facts = []
            for kind in c.layer_pattern:
                layer_w = jax.tree_util.tree_map(
                    lambda a: a[seen[kind]], period_w[kind])
                seen[kind] += 1
                x, aux = block_fns[kind](x, positions,
                                         {**layer_w, **stacks[kind]})
                facts.append(aux)
            return x, jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *facts)

        x, aux_per_period = lax.scan(period_body, x, blocks)
        # [periods, layers a period, ...] -> [L, ...]
        return x, jax.tree_util.tree_map(
            lambda a: a.reshape(-1, *a.shape[2:]), aux_per_period)

    def _pipeline_blocks(self, block_fn, blocks: Params, x: jax.Array,
                         positions: jax.Array) -> jax.Array:
        """GPipe schedule, GSPMD formulation (reference has no native PP,
        SURVEY §2.4 — Alpa-on-Ray only). Stage-stacked params [P, L/P, …]
        shard over pp; a [P, b, S, D] rolling buffer carries each
        microbatch through the stages; `jnp.roll` on the stage-sharded
        axis lowers to collective-permute over ICI. M microbatches take
        M + P - 1 steps (the usual bubble)."""
        c = self.config
        P = self.pp_stages
        B, S, D = x.shape
        M = c.pp_microbatches or P
        if B % M:
            raise ValueError(f"batch {B} must divide into {M} microbatches")
        mb = B // M
        x_mb = x.reshape(M, mb, S, D)
        x_mb = self._constrain(x_mb, None, "act_batch", "act_seq",
                               "act_embed")
        pos_mb = positions.reshape(M, mb, S)[0]

        def stage_step(carry, t):
            state, outs = carry
            # shift: stage s hands its activation to stage s+1
            state = jnp.roll(state, shift=1, axis=0)
            # feed the next microbatch into stage 0
            inp = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            state = state.at[0].set(jnp.where(t < M, inp, state[0]))
            state = self._constrain(state, "stage", "act_batch",
                                    "act_seq", "act_embed")

            # every stage applies its L/P layers (vmap over stages;
            # per-stage scan over layers)
            def one_stage(stage_params, xs):
                def body(h, layer_w):
                    h, _ = block_fn(h, pos_mb, layer_w)
                    return h, None
                out, _ = lax.scan(body, xs, stage_params)
                return out

            state = jax.vmap(one_stage)(blocks, state)
            state = self._constrain(state, "stage", "act_batch",
                                    "act_seq", "act_embed")
            # collect the last stage's output once the fill drains
            out_idx = jnp.clip(t - (P - 1), 0, M - 1)
            outs = lax.cond(
                t >= P - 1,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, state[P - 1], out_idx, axis=0),
                lambda o: o, outs)
            return (state, outs), None

        state0 = jnp.zeros((P, mb, S, D), c.dtype)
        outs0 = jnp.zeros((M, mb, S, D), c.dtype)
        (_, outs), _ = lax.scan(stage_step, (state0, outs0),
                                jnp.arange(M + P - 1))
        return outs.reshape(B, S, D)

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token cross entropy (+ z-loss) with an optional loss mask.

        batch: {"tokens": [B, S] int32, optional "loss_mask": [B, S]}.
        Targets are tokens shifted left; the final position is masked.
        """
        c = self.config
        if "sparse" in c.kinds:
            raise NotImplementedError(
                'a model with a "sparse" layer is not trained: the objective '
                "that trains the indexer (a divergence to the main "
                "attention's distribution, its schedule and coefficient) is "
                "no part of the configuration, and the layer has no backward "
                "pass")
        if "window" in c.kinds and resolve_impl(
                c.attention_impl, "attention") != "reference":
            raise NotImplementedError(
                'a model with a "window" layer is not trained through the '
                "kernels: " + WINDOW_HAS_NO_BACKWARD)
        tokens = batch["tokens"]
        logits, aux = self.forward_with_aux(params, tokens)  # [B,S,V] f32
        with jax.named_scope("head_loss"):
            targets = jnp.concatenate(
                [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
            mask = jnp.concatenate(
                [jnp.ones_like(tokens[:, 1:], jnp.float32),
                 jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
            if "loss_mask" in batch:
                mask = mask * batch["loss_mask"].astype(jnp.float32)

            lse = jax.nn.logsumexp(logits, axis=-1)            # [B, S]
            true_logit = jnp.take_along_axis(
                logits, targets[..., None], axis=-1)[..., 0]   # [B, S]
            nll = lse - true_logit
            total = jnp.maximum(mask.sum(), 1.0)
            loss = (nll * mask).sum() / total
            if c.z_loss:
                loss = loss + c.z_loss * (lse ** 2 * mask).sum() / total
        metrics = {
            "loss": loss,
            "ppl_log": (nll * mask).sum() / total,
            "tokens": mask.sum(),
        }
        if c.n_experts > 0:
            if "moe_aux_loss" in aux:   # a softmax router's: `moe.moe_ffn`
                loss = (loss + c.moe_aux_coeff * aux["moe_aux_loss"]
                        + c.moe_router_z_coeff * aux["moe_router_z"])
                metrics.update(moe_aux_loss=aux["moe_aux_loss"],
                               moe_router_z=aux["moe_router_z"])
            counts = aux["moe_expert_tokens"].astype(jnp.float32)   # [L, E]
            metrics.update(
                loss=loss, ce_loss=metrics["ppl_log"],
                # over the layers: each layer's sum is tokens x top-k
                moe_expert_tokens=aux["moe_expert_tokens"].sum(0),
                moe_load_max_over_mean=(counts.max(-1)
                                        / counts.mean(-1)).mean())
            if "moe_routed_here" in aux:
                # a share of the experts: per layer, what each held expert
                # was given beside the router's count of what it sent here,
                # and the rows the walk took for them, real or not
                first = c.moe_first_expert
                metrics.update(
                    moe_expert_tokens=aux["moe_expert_tokens"][
                        :, first:first + c.experts_held],
                    moe_routed_here=aux["moe_routed_here"],
                    moe_rows_walked=aux["moe_rows_walked"])
        return loss, metrics
