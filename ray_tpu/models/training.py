"""Training step factory: optimizer, TrainState, sharded jit train step.

The reference's training loop is user code orchestrated by Ray Train
(`train/data_parallel_trainer.py:484`, DDP wrap `train_loop_utils.py:74`);
gradient sync is NCCL allreduce hidden inside torch. TPU-native: the whole
step — forward, backward, optimizer — is ONE jitted SPMD program over the
mesh; GSPMD inserts the psums/all-gathers implied by the param/batch
shardings (dp gradient reduction, fsdp ZeRO gathering, tp partials).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import DEFAULT_RULES, ShardingRules
from .gpt import GPT

Params = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Params
    opt_state: Any


def make_optimizer(learning_rate: float = 3e-4,
                   warmup_steps: int = 100,
                   total_steps: int = 10000,
                   weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95,
                   grad_clip: float = 1.0,
                   schedule: str = "cosine") -> optax.GradientTransformation:
    if schedule == "cosine":
        lr = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps,
            max(total_steps, warmup_steps + 1), learning_rate * 0.1)
    else:
        lr = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(lr, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def state_logical_axes(model: GPT, optimizer: optax.GradientTransformation,
                       sample_params: Optional[Params] = None) -> Any:
    """Logical-axis pytree for a whole TrainState.

    Optimizer state (adam mu/nu) shards like the params it mirrors —
    subtrees of the optimizer state whose structure equals the param tree
    get the param axes; everything else (counts, schedule scalars) is
    replicated. Structure is discovered via `eval_shape` (no allocation).
    """
    param_axes = model.param_logical_axes()
    if sample_params is None:
        sample_params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
    param_treedef = jax.tree_util.tree_structure(sample_params)

    def _axes_like(node):
        if jax.tree_util.tree_structure(node) == param_treedef:
            return param_axes
        if isinstance(node, dict):
            return {k: _axes_like(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            children = [_axes_like(c) for c in node]
            if hasattr(node, "_fields"):      # namedtuple (optax states)
                return type(node)(*children)
            return type(node)(children)
        shape = getattr(node, "shape", ())
        return tuple([None] * len(shape))

    opt_shape = jax.eval_shape(optimizer.init, sample_params)
    return TrainState(step=(), params=param_axes,
                      opt_state=_axes_like(opt_shape))


def _is_axes(x):
    return x is None or (isinstance(x, tuple)
                         and all(a is None or isinstance(a, str) for a in x))


def state_shardings(model: GPT, optimizer: optax.GradientTransformation,
                    mesh: Mesh,
                    rules: Optional[ShardingRules] = None) -> Any:
    rules = rules if rules is not None else model.rules
    axes = state_logical_axes(model, optimizer)
    return jax.tree_util.tree_map(
        lambda logical: NamedSharding(mesh, rules.spec(*logical))
        if logical != () else NamedSharding(mesh, P()),
        axes, is_leaf=_is_axes)


def init_train_state(model: GPT, optimizer: optax.GradientTransformation,
                     rng: jax.Array,
                     mesh: Optional[Mesh] = None) -> TrainState:
    """Initialize params + optimizer state, sharded from birth.

    With a mesh, init runs under jit with out_shardings so large models
    never materialize unsharded on one device.
    """

    def _init():
        params = model.init(rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    if mesh is None:
        return _init()
    shardings = state_shardings(model, optimizer, mesh)
    return jax.jit(_init, out_shardings=shardings)()


def batch_shardings(mesh: Mesh,
                    rules: Optional[ShardingRules] = None) -> Any:
    rules = rules if rules is not None else DEFAULT_RULES
    return NamedSharding(mesh, rules.spec("act_batch", "act_seq"))


def make_train_step(model: GPT, optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None,
                    donate: bool = True
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted SPMD train step.

    Returns step(state, batch) -> (state, metrics). batch arrays are
    expected sharded over ("act_batch", "act_seq") — use
    `batch_shardings(mesh)` + `jax.device_put`.
    """

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        grad_fn = jax.value_and_grad(model.loss, has_aux=True)
        (loss, metrics), grads = grad_fn(state.params, batch)
        # scope names are metadata: the trace's device ops carry them
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
        return new_state, metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,) if donate else ())

    shardings = state_shardings(model, optimizer, mesh)
    return jax.jit(
        train_step,
        in_shardings=(shardings, None),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def eval_step_fn(model: GPT, mesh: Optional[Mesh] = None):
    def eval_step(params, batch):
        _, metrics = model.loss(params, batch)
        return metrics

    if mesh is None:
        return jax.jit(eval_step)
    rules = model.rules
    param_shardings = jax.tree_util.tree_map(
        lambda logical: NamedSharding(mesh, rules.spec(*logical)),
        model.param_logical_axes(), is_leaf=_is_axes)
    return jax.jit(eval_step, in_shardings=(param_shardings, None))

