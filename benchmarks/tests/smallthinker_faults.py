"""Faults planted in the mechanisms SmallThinker's layer adds to the program
(a router that reads the mixer's normed input, ReLU-gated experts, a softmax
over the chosen logits, a period that opens with a full layer without
positions before window layers), for `tests/test_smallthinker.py`,
`test_smallthinker.py` here and the builder's chip script
(`smallthinker_readings.py`): each function breaks `ray_tpu` underneath a
served cell, in the replica, before the deployment is built
(`loops/serve.py::Scorer` calls the one named by `rehearsal["patch"]`, which
no command line can set). Each wraps the program's own code at the place the
model calls it, so the kernels and the `jnp` form are broken alike.
`restore()` undoes all of them. Nothing here is reachable from a run of the
benchmark."""

from __future__ import annotations

from benchmarks.tests.trinity_faults import (          # noqa: F401
    _as, _replace, restore, rope_on_full_layers, window_layers_full)


def router_reads_after_attention():
    """The router reads norm2 of the stream after attention, as every other
    model's does, and not norm1 of the layer's input."""
    from ray_tpu.models.gpt import GPT
    real = GPT._expert_ffn
    _replace(GPT, "_expert_ffn",
             lambda self, h, w, tap=None: real(self, h, w, None))


def silu_for_relu():
    """The experts' gate projection passes SiLU, the repo's other models'
    activation, and not ReLU."""
    from ray_tpu.models.gpt import GPT
    real = GPT._expert_ffn
    _replace(GPT, "_expert_ffn", lambda self, h, w, tap=None: real(
        _as(self, moe_activation="silu"), h, w, tap))


def window_halved():
    """A "window" layer attends half the keys the model's window holds."""
    from ray_tpu.models import gpt
    attend = gpt.dot_product_attention

    def halved(q, k, v, **kw):
        if kw.get("window") is not None:
            kw["window"] = kw["window"] // 2
        return attend(q, k, v, **kw)
    _replace(gpt, "dot_product_attention", halved)


def weights_not_rescaled():
    """A token's six weights are the full softmax's over all the experts,
    not rescaled to sum to 1."""
    from ray_tpu.models.gpt import GPT
    real = GPT._expert_ffn
    _replace(GPT, "_expert_ffn", lambda self, h, w, tap=None: real(
        _as(self, moe_norm_topk_prob=False), h, w, tap))


FAULTS = ("router_reads_after_attention", "silu_for_relu",
          "window_layers_full", "rope_on_full_layers", "window_halved",
          "weights_not_rescaled")
