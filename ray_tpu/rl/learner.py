"""Learner / LearnerGroup: SGD as one jitted program.

Reference: ``rllib/core/learner/learner.py:229`` (update :1230),
``learner_group.py:61``. The reference data-parallelizes learners with
torch DDP over NCCL; here the update is a single `jax.jit` call on the
learner's default device: the class builds no mesh, so the update is not
sharded over chips. The LearnerGroup actor form exists for placement
(run the learner on a TPU host while rollouts run elsewhere); with
several learners it averages their weights on the host.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..api import remote
from . import sample_batch as SB
from .module import DiscretePolicyModule


class Learner:
    """PPO-style clipped surrogate learner (the loss fn is pluggable)."""

    def __init__(self, module: DiscretePolicyModule,
                 *, lr: float = 3e-4, clip: float = 0.2,
                 vf_coeff: float = 0.5, entropy_coeff: float = 0.0,
                 grad_clip: float = 0.5, seed: int = 0,
                 gamma: float = 0.99,
                 rho_clip: float = 1.0, c_clip: float = 1.0,
                 loss: str = "ppo",
                 loss_fn: Optional[Callable] = None):
        self.module = module
        self.clip = clip
        self.vf_coeff = vf_coeff
        self.entropy_coeff = entropy_coeff
        self.gamma = gamma
        self.rho_clip = rho_clip
        self.c_clip = c_clip
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(grad_clip), optax.adam(lr))
        self.params = module.init(jax.random.PRNGKey(seed))
        self.opt_state = self.optimizer.init(self.params)
        # `loss` is a picklable name so LearnerGroup actors can build the
        # same learner remotely; `loss_fn` overrides with a callable
        builtin = {"ppo": self._ppo_loss, "vtrace": self._vtrace_loss}
        self._loss_fn = loss_fn or builtin[loss]
        self._update = jax.jit(self._update_impl)

    # --------------------------------------------------------------- losses
    def _ppo_loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        logits, values = self.module.forward(params, batch[SB.OBS])
        logp_all = jax.nn.log_softmax(logits)
        actions = batch[SB.ACTIONS]
        logp = logp_all[jnp.arange(actions.shape[0]), actions]
        ratio = jnp.exp(logp - batch[SB.LOGP])
        adv = batch[SB.ADVANTAGES]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        pg1 = ratio * adv
        pg2 = jnp.clip(ratio, 1 - self.clip, 1 + self.clip) * adv
        pg_loss = -jnp.minimum(pg1, pg2).mean()
        vf_loss = 0.5 * ((values - batch[SB.VALUE_TARGETS]) ** 2).mean()
        entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
        loss = (pg_loss + self.vf_coeff * vf_loss
                - self.entropy_coeff * entropy)
        stats = {"pg_loss": pg_loss, "vf_loss": vf_loss,
                 "entropy": entropy, "total_loss": loss,
                 "approx_kl": (batch[SB.LOGP] - logp).mean()}
        return loss, stats

    def _vtrace_loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        """IMPALA's V-trace off-policy actor-critic loss over time-major
        fragments (reference: ``rllib/algorithms/impala`` + the V-trace
        targets of Espeholt et al. 2018). Batch layout: obs (B,T,D),
        actions/rewards/dones/action_logp (B,T), bootstrap_obs (B,D).
        The backward recursion is a ``lax.scan`` over time — one compiled
        program, no Python loop."""
        obs = batch[SB.OBS]
        bsz, horizon = obs.shape[0], obs.shape[1]
        logits, values = self.module.forward(
            params, obs.reshape(bsz * horizon, -1))
        logits = logits.reshape(bsz, horizon, -1)
        values = values.reshape(bsz, horizon)
        logp_all = jax.nn.log_softmax(logits)
        actions = batch[SB.ACTIONS]
        tlogp = jnp.take_along_axis(logp_all, actions[..., None],
                                    axis=-1)[..., 0]
        rho = jnp.exp(tlogp - batch[SB.LOGP])
        rho_c = jnp.minimum(rho, self.rho_clip)
        cs = jnp.minimum(rho, self.c_clip)
        _, bootstrap = self.module.forward(params, batch["bootstrap_obs"])
        discounts = self.gamma * (1.0 - batch[SB.DONES].astype(jnp.float32))
        values_tp1 = jnp.concatenate(
            [values[:, 1:], bootstrap[:, None]], axis=1)
        rewards = batch[SB.REWARDS]
        deltas = rho_c * (rewards + discounts * values_tp1 - values)

        def backward(acc, xs):
            delta_t, disc_t, c_t = xs
            acc = delta_t + disc_t * c_t * acc
            return acc, acc

        _, acc_rev = jax.lax.scan(
            backward, jnp.zeros(bsz),
            (deltas.T[::-1], discounts.T[::-1], cs.T[::-1]))
        vs = values + acc_rev[::-1].T                       # (B,T)
        vs_tp1 = jnp.concatenate([vs[:, 1:], bootstrap[:, None]], axis=1)
        pg_adv = jax.lax.stop_gradient(
            rho_c * (rewards + discounts * vs_tp1 - values))
        pg_loss = -(tlogp * pg_adv).mean()
        vf_loss = 0.5 * ((jax.lax.stop_gradient(vs) - values) ** 2).mean()
        entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
        loss = (pg_loss + self.vf_coeff * vf_loss
                - self.entropy_coeff * entropy)
        stats = {"pg_loss": pg_loss, "vf_loss": vf_loss,
                 "entropy": entropy, "total_loss": loss,
                 "mean_rho": rho.mean()}
        return loss, stats

    # --------------------------------------------------------------- update
    def _update_impl(self, params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(params, batch)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, stats

    def update(self, batch: SB.SampleBatch) -> Dict[str, float]:
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        self.params, self.opt_state, stats = self._update(
            self.params, self.opt_state, jbatch)
        return {k: float(v) for k, v in stats.items()}

    def get_weights(self):
        return jax.tree_util.tree_map(np.asarray, self.params)

    def set_weights(self, weights) -> None:
        self.params = jax.tree_util.tree_map(jnp.asarray, weights)


@remote
class _LearnerActor:
    def __init__(self, module_config: dict, learner_kwargs: dict):
        module = DiscretePolicyModule(**module_config)
        self.learner = Learner(module, **learner_kwargs)

    def update(self, batch) -> Dict[str, float]:
        return self.learner.update(SB.SampleBatch(batch))

    def get_weights(self):
        return self.learner.get_weights()

    def set_weights(self, weights) -> None:
        self.learner.set_weights(weights)


class LearnerGroup:
    """Placement wrapper: run the learner on its own (TPU-host) actor.

    num_learners>1 splits each batch and averages weights after update —
    only useful multi-host.
    """

    def __init__(self, module: DiscretePolicyModule, *,
                 num_learners: int = 1,
                 resources_per_learner: Optional[dict] = None,
                 **learner_kwargs):
        opts = {}
        if resources_per_learner:
            res = dict(resources_per_learner)
            if "CPU" in res:
                opts["num_cpus"] = res.pop("CPU")
            if res:
                opts["resources"] = res
        cfg = {"observation_size": module.observation_size,
               "action_size": module.action_size,
               "hidden": module.hidden}
        self._actors = [
            _LearnerActor.options(**opts).remote(cfg, learner_kwargs)
            for _ in range(num_learners)]

    def update(self, batch: SB.SampleBatch) -> Dict[str, float]:
        from .. import get
        b = len(batch)
        # never hand a learner an empty slice: with fewer rows than
        # learners (async algorithms often deliver a single fragment)
        # only the first len(batch) actors participate this round
        parts = self._actors[:max(1, min(len(self._actors), b))]
        n = len(parts)
        if n == 1:
            stats = [get(parts[0].update.remote(dict(batch)))]
        else:
            size = b // n
            refs = []
            for i, a in enumerate(parts):
                hi = b if i == n - 1 else (i + 1) * size
                refs.append(a.update.remote(dict(batch.slice(i * size,
                                                             hi))))
            stats = get(refs)
        if len(self._actors) > 1:
            # data-parallel consensus over the participants, broadcast
            # to everyone (non-participants hold pre-update weights)
            weights = get([a.get_weights.remote() for a in parts])
            mean_w = jax.tree_util.tree_map(
                lambda *ws: np.mean(np.stack(ws), axis=0), *weights)
            get([a.set_weights.remote(mean_w) for a in self._actors])
        return {k: float(np.mean([s[k] for s in stats]))
                for k in stats[0]}

    def get_weights(self):
        from .. import get
        return get(self._actors[0].get_weights.remote())

    def shutdown(self) -> None:
        from .. import kill
        for a in self._actors:
            try:
                kill(a)
            except Exception:
                pass
