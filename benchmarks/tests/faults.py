"""Faults planted in the program for `test_first_steps.py`: each function
breaks `ray_tpu` underneath a CPU rehearsal of a cell, in the worker, before
the step is built (`loops/train.py` calls the one named by
`rehearsal["patch"]`, which no command line can set). Nothing here is
reachable from a run of the benchmark."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def state_unchanged():
    """A step that returns its state as it was given."""
    import ray_tpu.models as models
    from ray_tpu.models import training
    real = training.make_train_step

    def make(model, optimizer, mesh=None, donate=True):
        step = real(model, optimizer, mesh=mesh, donate=False)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return jax.jit(unchanged)

    models.make_train_step = training.make_train_step = make


def _loss_over_first_rows(share: int):
    """The loss (and so the gradient) of the first 1 / share of the rows,
    the mean taken over them; the shapes stay, so a mesh still divides."""
    from ray_tpu.models.gpt import GPT
    real = GPT.loss

    def loss(self, params, batch):
        tokens = batch["tokens"]
        kept = jnp.arange(tokens.shape[0]) < tokens.shape[0] // share
        return real(self, params, dict(
            batch, loss_mask=jnp.broadcast_to(kept[:, None], tokens.shape)))
    GPT.loss = loss


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    _loss_over_first_rows(2)


def no_exchange():
    """The exchange between four chips left out: the gradient is what one
    chip's rows give."""
    _loss_over_first_rows(4)


@jax.custom_vjp
def _times_1_05_backward(x):
    return x


_times_1_05_backward.defvjp(lambda x: (x, None),
                            lambda _, g: (1.05 * g,))


def gradient_group_scaled():
    """The gradient of one group (the MLPs' up-projection) times 1.05."""
    from ray_tpu.models.gpt import GPT
    real = GPT.loss

    def loss(self, params, batch):
        blocks = dict(params["blocks"])
        blocks["w_up"] = _times_1_05_backward(blocks["w_up"])
        return real(self, dict(params, blocks=blocks), batch)
    GPT.loss = loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _drop_dk(k, seq_axis):
    return k


def _drop_dk_bwd(seq_axis, _, g):
    # one rectangle of the backward: head 0's keys of the first half
    half = g.shape[seq_axis] // 2
    index = [slice(None)] * g.ndim
    index[seq_axis] = slice(0, half)
    index[3 - seq_axis] = slice(0, 1)       # [B, H, S, D] or [B, S, H, D]
    return (g.at[tuple(index)].set(0.0),)


_drop_dk.defvjp(lambda k, seq_axis: (k, None), _drop_dk_bwd)


def flash_dk_dropped():
    """One rectangle's dk dropped from the attention's backward pass."""
    from ray_tpu.models import gpt
    real = gpt.dot_product_attention

    def attention(q, k, v, *args, seq_major=False, **kw):
        return real(q, _drop_dk(k, 1 if seq_major else 2), v, *args,
                    seq_major=seq_major, **kw)
    gpt.dot_product_attention = attention


def no_bias_correction():
    """AdamW without the first moment's 1 / (1 - b1^t)."""
    import optax
    import ray_tpu.models as models
    from ray_tpu.models import training

    def adam_without(b1, b2, eps=1e-8):
        def init(params):
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            return optax.ScaleByAdamState(
                count=jnp.zeros((), jnp.int32), mu=zeros,
                nu=jax.tree_util.tree_map(jnp.zeros_like, params))

        def update(grads, state, params=None):
            count = state.count + 1
            mu = jax.tree_util.tree_map(
                lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
            nu = jax.tree_util.tree_map(
                lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, grads)
            c2 = 1 - b2 ** count.astype(jnp.float32)
            updates = jax.tree_util.tree_map(
                lambda m, v: m / (jnp.sqrt(v / c2) + eps), mu, nu)
            return updates, optax.ScaleByAdamState(count=count, mu=mu, nu=nu)
        return optax.GradientTransformation(init, update)

    def make(learning_rate=3e-4, warmup_steps=100, total_steps=10000,
             weight_decay=0.1, b1=0.9, b2=0.95, grad_clip=1.0, **_):
        lr = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps,
            max(total_steps, warmup_steps + 1), learning_rate * 0.1)
        return optax.chain(
            optax.clip_by_global_norm(grad_clip), adam_without(b1, b2),
            optax.add_decayed_weights(weight_decay),
            optax.scale_by_learning_rate(lr))

    models.make_optimizer = training.make_optimizer = make
