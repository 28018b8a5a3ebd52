"""Decoder-only transformer, TPU-first.

One implementation covers the GPT-2 family (learned positions, GELU MLP,
LayerNorm), the Llama family (RoPE, SwiGLU, RMSNorm, GQA) and OLMoE's
sparse-expert block (QK-norm, dropless top-k experts, `models/moe.py`)
through `GPTConfig` switches — the reference ships these as external torch
models driven by Ray Train (`release/train_tests`, SURVEY §6 north-star
configs); here the model itself is framework-native.

TPU-first choices:
  * scan-over-layers with stacked params — one compiled block body,
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

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ..ops.attention import FLASH_RESIDUAL_NAMES, dot_product_attention
from ..ops.ring_attention import ring_attention
from ..parallel.sharding import (DEFAULT_RULES, ShardingRules,
                                 with_logical_constraint)

Params = Dict[str, Any]

# What `remat_policy="dots"` keeps of a block for the backward pass
# (`GPTConfig.remat_policy`): the names `_block` gives its projections, and
# the flash kernel's output and log-sum-exp.
_DOTS_SAVED_NAMES = ("attn_q", "attn_k", "attn_v", "mlp_up", "mlp_gate",
                     *FLASH_RESIDUAL_NAMES)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    n_kv_heads: Optional[int] = None  # None -> n_heads (MHA); < n_heads -> GQA
    d_ff: Optional[int] = None        # None -> 4*d_model (gelu) / 8/3*d (swiglu)
    max_seq_len: int = 1024
    # family switches
    activation: str = "gelu"          # "gelu" | "swiglu"
    norm: str = "layernorm"           # "layernorm" | "rmsnorm"
    positions: str = "learned"        # "learned" | "rope"
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    # None -> the family's usual epsilon (1e-6 rmsnorm, 1e-5 layernorm)
    norm_eps: Optional[float] = None
    # RMSNorm with a learned scale on the whole projected q and k (all heads
    # together), before RoPE — OLMoE / OLMo-2
    qk_norm: bool = False
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

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def eps(self) -> float:
        if self.norm_eps is not None:
            return self.norm_eps
        return 1e-6 if self.norm == "rmsnorm" else 1e-5

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

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
        """Approximate parameter count (excludes norms/bias)."""
        d, f, v = self.d_model, self.ff_dim, self.vocab_size
        hd, h, hk = self.head_dim, self.n_heads, self.kv_heads
        attn = d * h * hd + 2 * d * hk * hd + h * hd * d
        if self.n_experts > 0:
            mlp = self.n_experts * 3 * d * f + d * self.n_experts
        else:
            mlp = (3 if self.activation == "swiglu" else 2) * d * f
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + mlp) + emb


# --- presets ---------------------------------------------------------------

def gpt2_small(**kw) -> GPTConfig:
    return GPTConfig(n_layers=12, d_model=768, n_heads=12, **kw)


def gpt2_medium(**kw) -> GPTConfig:
    return GPTConfig(n_layers=24, d_model=1024, n_heads=16, **kw)


def gpt2_large(**kw) -> GPTConfig:
    return GPTConfig(n_layers=36, d_model=1280, n_heads=20, **kw)


def _llama(**kw) -> GPTConfig:
    base = dict(activation="swiglu", norm="rmsnorm", positions="rope",
                tie_embeddings=False, vocab_size=32000, max_seq_len=2048)
    base.update(kw)
    return GPTConfig(**base)


def llama_tiny(**kw) -> GPTConfig:
    """Test-scale llama-style config (CPU-friendly)."""
    return _llama(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                  vocab_size=512, max_seq_len=256, **kw)


def llama_1b(**kw) -> GPTConfig:
    return _llama(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=8, **kw)


def llama_7b(**kw) -> GPTConfig:
    return _llama(n_layers=32, d_model=4096, n_heads=32, d_ff=11008,
                  max_seq_len=4096, **kw)


# --- init ------------------------------------------------------------------

def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


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

    @property
    def pp_stages(self) -> int:
        if self.mesh is None:
            return 1
        ax = self.rules.mesh_axes("stage")
        if isinstance(ax, str) and ax in self.mesh.shape:
            return self.mesh.shape[ax]
        return 1

    # -- parameters --------------------------------------------------------

    def init(self, rng: jax.Array) -> Params:
        c = self.config
        pd = c.param_dtype
        d, f, hd = c.d_model, c.ff_dim, c.head_dim
        h, hk, L = c.n_heads, c.kv_heads, c.n_layers
        std = 0.02
        resid_std = std / math.sqrt(2 * L)
        keys = jax.random.split(rng, 12)

        def ones(shape):
            return jnp.ones(shape, pd)

        blocks = {
            "norm1": ones((L, d)),
            "norm2": ones((L, d)),
            "wq": _normal(keys[0], (L, d, h, hd), std, pd),
            "wk": _normal(keys[1], (L, d, hk, hd), std, pd),
            "wv": _normal(keys[2], (L, d, hk, hd), std, pd),
            "wo": _normal(keys[3], (L, h, hd, d), resid_std, pd),
        }
        if c.qk_norm:
            blocks["q_norm"] = ones((L, h, hd))
            blocks["k_norm"] = ones((L, hk, hd))
        if c.n_experts > 0:
            E = c.n_experts
            blocks["router"] = _normal(keys[4], (L, d, E), std, pd)
            blocks["w_up"] = _normal(keys[5], (L, E, d, f), std, pd)
            blocks["w_gate"] = _normal(keys[6], (L, E, d, f), std, pd)
            blocks["w_down"] = _normal(keys[10], (L, E, f, d), resid_std,
                                       pd)
        else:
            blocks["w_up"] = _normal(keys[4], (L, d, f), std, pd)
            blocks["w_down"] = _normal(keys[5], (L, f, d), resid_std, pd)
            if c.activation == "swiglu":
                blocks["w_gate"] = _normal(keys[6], (L, d, f), std, pd)
        if c.norm == "layernorm":
            blocks["bias1"] = jnp.zeros((L, d), pd)
            blocks["bias2"] = jnp.zeros((L, d), pd)
        params: Params = {
            "tok_embed": _normal(keys[7], (c.vocab_size, d), std, pd),
            "blocks": blocks,
            "norm_f": ones((d,)),
        }
        if c.positions == "learned":
            params["pos_embed"] = _normal(keys[8], (c.max_seq_len, d), std,
                                          pd)
        if c.norm == "layernorm":
            params["bias_f"] = jnp.zeros((d,), pd)
        if not c.tie_embeddings:
            params["lm_head"] = _normal(keys[9], (d, c.vocab_size), std, pd)
        P = self.pp_stages
        if P > 1:
            # stage-stack: [L, ...] -> [P, L/P, ...]; the stage axis is
            # sharded over pp so each stage holds only its layers
            params["blocks"] = jax.tree_util.tree_map(
                lambda a: a.reshape((P, L // P) + a.shape[1:]),
                params["blocks"])
        return params

    def param_logical_axes(self) -> Params:
        """Pytree matching `init` output: tuples of logical axis names."""
        c = self.config
        blocks = {
            "norm1": ("layers", None),
            "norm2": ("layers", None),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
        }
        if c.qk_norm:
            blocks["q_norm"] = ("layers", "heads", "head_dim")
            blocks["k_norm"] = ("layers", "kv_heads", "head_dim")
        if c.n_experts > 0:
            blocks["router"] = ("layers", "embed", None)
            blocks["w_up"] = ("layers", "expert", "embed", "mlp")
            blocks["w_gate"] = ("layers", "expert", "embed", "mlp")
            blocks["w_down"] = ("layers", "expert", "mlp", "embed")
        else:
            blocks["w_up"] = ("layers", "embed", "mlp")
            blocks["w_down"] = ("layers", "mlp", "embed")
            if c.activation == "swiglu":
                blocks["w_gate"] = ("layers", "embed", "mlp")
        if c.norm == "layernorm":
            blocks["bias1"] = ("layers", None)
            blocks["bias2"] = ("layers", None)
        if self.pp_stages > 1:
            blocks = {k: ("stage",) + v for k, v in blocks.items()}
        axes: Params = {
            "tok_embed": ("vocab", "embed"),
            "blocks": blocks,
            "norm_f": (None,),
        }
        if c.positions == "learned":
            axes["pos_embed"] = (None, "embed")
        if c.norm == "layernorm":
            axes["bias_f"] = (None,)
        if not c.tie_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # -- building blocks ---------------------------------------------------

    def _norm(self, x, scale, bias):
        c = self.config
        xf = x.astype(jnp.float32)
        if c.norm == "rmsnorm":
            xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + c.eps)
            return (xf * scale.astype(jnp.float32)).astype(c.dtype)
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
        """x: [B, S, H, D_h]; positions: [B, S]."""
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

    def _sp_size(self) -> int:
        """Size of the mesh axis act_seq maps to (sequence parallelism)."""
        if self.mesh is None:
            return 1
        ax = self.rules.mesh_axes("act_seq")
        if isinstance(ax, str) and ax in self.mesh.shape:
            return self.mesh.shape[ax]
        return 1

    def _attention(self, q, k, v):
        """q: [B, S, H, Dh], k/v: [B, S, Hk, Dh] → [B, S, H, Dh].

        The flash kernels read q, k, v as the projections wrote them and
        write their gradients the same way (`ops/attention.py`,
        `seq_major`): nothing is transposed on either side of them. Ring
        attention keeps [B, H, S, Dh] and wants the sequence axis *locally*
        sharded, so both pallas paths run under shard_map with specs derived
        from the mesh.
        """
        c = self.config
        sp = self._sp_size()
        if getattr(self, "_in_pipeline", False):
            # pipeline mode runs blocks under vmap over the stage axis;
            # shard_map can't nest there, so use the einsum attention and
            # let GSPMD partition it (pallas-in-pipeline: future work)
            return dot_product_attention(q, k, v, causal=True,
                                         impl="reference", seq_major=True)
        if sp > 1:
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
                                         seq_major=True)

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

    def _constrain(self, x, *logical):
        return with_logical_constraint(x, *logical, rules=self.rules,
                                       mesh=self.mesh)

    def _block(self, x, positions, w):
        """One transformer block. x: [B, S, D] bf16."""
        c = self.config
        dt = c.dtype

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
            if c.qk_norm:
                q = self._qk_norm(q, w["q_norm"])
                k = self._qk_norm(k, w["k_norm"])
            if c.positions == "rope":
                q = self._rope(q, positions)
                k = self._rope(k, positions)
            q = self._constrain(q, "act_batch", "act_seq", "act_heads",
                                "head_dim")
            k = self._constrain(k, "act_batch", "act_seq", "act_kv_heads",
                                "head_dim")
        with jax.named_scope("attn_kernel"):
            attn = self._attention(q, k, v)
        with jax.named_scope("attn_out"):
            wo = w["wo"].astype(dt)
            attn = jnp.einsum("bse,ed->bsd",
                              attn.reshape(*attn.shape[:2], -1),
                              wo.reshape(-1, wo.shape[-1]))
            x = x + self._constrain(attn, "act_batch", "act_seq",
                                    "act_embed")

        with jax.named_scope("mlp"):
            h = self._norm(x, w["norm2"], w.get("bias2"))
            aux = {}
            if c.n_experts > 0:
                from .moe import moe_ffn
                down, aux = moe_ffn(
                    h, w["router"], w["w_up"], w["w_gate"], w["w_down"],
                    top_k=c.moe_top_k,
                    norm_topk_prob=c.moe_norm_topk_prob, dtype=dt)
            else:
                up = jnp.einsum("bsd,df->bsf", h, w["w_up"].astype(dt))
                up = checkpoint_name(up, "mlp_up")
                if c.activation == "swiglu":
                    gate = jnp.einsum("bsd,df->bsf", h,
                                      w["w_gate"].astype(dt))
                    gate = checkpoint_name(gate, "mlp_gate")
                    act = jax.nn.silu(gate) * up
                else:
                    act = jax.nn.gelu(up, approximate=True)
                act = self._constrain(act, "act_batch", "act_seq",
                                      "act_mlp")
                down = jnp.einsum("bsf,fd->bsd", act,
                                  w["w_down"].astype(dt))
            x = x + self._constrain(down, "act_batch", "act_seq",
                                    "act_embed")
        return x, aux

    # -- forward -----------------------------------------------------------

    def apply(self, params: Params, tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens: [B, S] int32 → logits [B, S, V] (f32)."""
        return self.forward_with_aux(params, tokens, positions)[0]

    def forward_with_aux(self, params: Params, tokens: jax.Array,
                         positions: Optional[jax.Array] = None):
        """Returns (logits, aux): with experts, the router's two losses as
        means over the layers and, per layer, `moe_expert_tokens`
        [n_layers, n_experts] and `moe_expert_choice` [n_layers, tokens,
        top_k]; without, an empty dict."""
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
            x = self._constrain(x, "act_batch", "act_seq", "act_embed")

        block_fn = self._block
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
            block_fn = jax.checkpoint(block_fn,
                                      policy=policies[c.remat_policy])

        if self.pp_stages > 1:
            x = self._pipeline_blocks(block_fn, params["blocks"], x,
                                      positions)
            aux_per_layer = {}
        else:
            def scan_body(x, layer_w):
                x, aux = block_fn(x, positions, layer_w)
                return x, aux

            x, aux_per_layer = lax.scan(scan_body, x, params["blocks"])
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
        aux = {k: v.mean() if v.ndim == 1 else v
               for k, v in aux_per_layer.items()}
        return logits, aux

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

        self._in_pipeline = True
        try:
            def stage_step(carry, t):
                state, outs = carry
                # shift: stage s hands its activation to stage s+1
                state = jnp.roll(state, shift=1, axis=0)
                # feed the next microbatch into stage 0
                inp = lax.dynamic_index_in_dim(
                    x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
                state = state.at[0].set(
                    jnp.where(t < M, inp, state[0]))
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
        finally:
            self._in_pipeline = False
        return outs.reshape(B, S, D)

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token cross entropy (+ z-loss) with an optional loss mask.

        batch: {"tokens": [B, S] int32, optional "loss_mask": [B, S]}.
        Targets are tokens shifted left; the final position is masked.
        """
        c = self.config
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
            loss = (loss + c.moe_aux_coeff * aux["moe_aux_loss"]
                    + c.moe_router_z_coeff * aux["moe_router_z"])
            counts = aux["moe_expert_tokens"].astype(jnp.float32)   # [L, E]
            metrics.update(
                loss=loss, ce_loss=metrics["ppl_log"],
                moe_aux_loss=aux["moe_aux_loss"],
                moe_router_z=aux["moe_router_z"],
                # over the layers: each layer's sum is tokens x top-k
                moe_expert_tokens=aux["moe_expert_tokens"].sum(0),
                moe_load_max_over_mean=(counts.max(-1)
                                        / counts.mean(-1)).mean())
        return loss, metrics
