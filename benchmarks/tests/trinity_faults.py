"""Faults planted in the mechanisms a model of window and full layers adds to
the program, for `tests/test_trinity_mini.py`, `test_trinity_mini.py` here
and the builder's chip script (`trinity_readings.py`): each function breaks
`ray_tpu` underneath a served cell, in the replica, before the deployment is
built (`loops/serve.py::Scorer` calls the one named by `rehearsal["patch"]`,
which no command line can set). Each wraps the program's own code at the
place the model calls it, so the kernels and the `jnp` form are broken alike.
`seeded_bias` is no fault: it gives the selection bias, zero at a seeded
start, a seeded non-zero value in the program and, through the same
parameters, in the reference — without it `bias_in_the_weights` changes
nothing. `restore()` undoes all of them. Nothing here is reachable from a run
of the benchmark."""

from __future__ import annotations

import dataclasses

_saved = {}


def _replace(owner, name, new):
    _saved.setdefault((owner, name), getattr(owner, name))
    setattr(owner, name, new)


def restore():
    for (owner, name), real in _saved.items():
        setattr(owner, name, real)
    _saved.clear()


def _as(model, **fields):
    """The same model under a changed configuration."""
    from ray_tpu.models.gpt import GPT
    return GPT(dataclasses.replace(model.config, **fields), model.mesh,
               model.rules)


def window_layers_full():
    """A "window" layer attends every causal key."""
    from ray_tpu.models import gpt
    attend = gpt.dot_product_attention

    def full(q, k, v, **kw):
        kw.pop("window", None)
        return attend(q, k, v, **kw)
    _replace(gpt, "dot_product_attention", full)


def rope_on_full_layers():
    """RoPE turns the q and k of every layer, the "full" ones too."""
    from ray_tpu.models.gpt import GPT
    _replace(GPT, "_turned",
             lambda self, kind: self.config.positions == "rope")


def gate_left_out():
    """The attention's output is not multiplied by its gate."""
    from ray_tpu.models.gpt import GPT
    real = GPT._full_mixer

    def ungated(self, x, positions, w, kind="full"):
        width = w["wq"].shape[-1] // 2
        return real(_as(self, attn_gate=False), x, positions,
                    {**w, "wq": w["wq"][..., :width]}, kind)
    _replace(GPT, "_full_mixer", ungated)


def post_norm_left_out():
    """A branch's output joins the stream as it is."""
    from ray_tpu.models.gpt import GPT
    real = GPT._join
    _replace(GPT, "_join", lambda self, x, branch, w, post: real(
        _as(self, post_norm=False), x, branch, w, post))


def bias_in_the_weights():
    """The routing weights are the chosen experts' scores with the
    selection bias in them."""
    import jax
    from jax import lax
    from ray_tpu.models import moe

    def biased(logits, top_k, norm_topk_prob, score, select_bias,
               route_scale, impl):
        assert score == "sigmoid" and norm_topk_prob
        probs = jax.nn.sigmoid(logits)
        vals, idx = lax.top_k(probs + select_bias.astype(probs.dtype), top_k)
        return probs, vals / (vals.sum(-1, keepdims=True)
                              + 1e-20) * route_scale, idx
    _replace(moe, "_route", biased)


def shared_expert_gated():
    """The shared expert passes a gate that stands at a half."""
    from ray_tpu.models import moe
    real = moe.shared_expert_ffn
    _replace(moe, "shared_expert_ffn",
             lambda *a, **kw: (0.5 * real(*a, **kw)).astype(kw["dtype"]))


def seeded_bias(std: float = 0.25):
    """Not a fault: every routed layer's selection bias a normal draw of
    `std` from the seed of the weights (a quarter: about the spread of a
    token's sigmoid scores at seeded weights, so that the bias decides about
    half of a token's choices and, misplaced in the weights, moves them by
    as much as they differ)."""
    import jax
    from ray_tpu.models.gpt import GPT
    real = GPT.init

    def init(self, rng):
        params = real(self, rng)
        kinds = params["blocks"]
        for i, kind in enumerate(sorted(kinds)):
            bias = kinds[kind]["router_bias"]
            kinds[kind]["router_bias"] = (std * jax.random.normal(
                jax.random.fold_in(rng, 77 + i), bias.shape)
            ).astype(bias.dtype)
        return params
    _replace(GPT, "init", init)


def seeded_bias_in_the_weights():
    seeded_bias()
    bias_in_the_weights()


FAULTS = ("window_layers_full", "rope_on_full_layers", "gate_left_out",
          "post_norm_left_out", "shared_expert_gated",
          "seeded_bias_in_the_weights")
