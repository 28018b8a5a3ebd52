"""RL tests (reference model: ``rllib/tests`` + per-algorithm tests —
GAE math, module shapes, learner update, PPO CartPole learning)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import (CartPoleEnv, DiscretePolicyModule, Impala,
                        ImpalaConfig, Learner,
                        LearnerGroup, PPO, PPOConfig, RandomEnv,
                        SampleBatch)
from ray_tpu.rl import sample_batch as SB
from ray_tpu.rl.sample_batch import compute_gae, concat_batches


def test_cartpole_dynamics():
    env = CartPoleEnv(seed=0)
    obs, _ = env.reset()
    assert obs.shape == (4,)
    total = 0
    for _ in range(600):
        obs, r, term, trunc, _ = env.step(1)
        total += r
        if term or trunc:
            break
    assert term            # constant action falls over quickly
    assert total < 100


def test_gae_single_step_matches_td():
    batch = SampleBatch({
        SB.REWARDS: np.array([1.0, 1.0], np.float32),
        SB.VF_PREDS: np.array([0.5, 0.4], np.float32),
        SB.DONES: np.array([False, True]),
    })
    out = compute_gae(batch, gamma=0.9, lam=1.0, last_value=0.0)
    # terminal step: delta = r - v = 0.6
    assert out[SB.ADVANTAGES][1] == pytest.approx(0.6)
    # step 0: delta0 + gamma*adv1 = (1 + .9*.4 - .5) + .9*.6
    assert out[SB.ADVANTAGES][0] == pytest.approx(0.86 + 0.54, abs=1e-5)


def test_module_shapes():
    import jax
    m = DiscretePolicyModule(4, 2, hidden=(8,))
    params = m.init(jax.random.PRNGKey(0))
    obs = np.zeros((3, 4), np.float32)
    logits, value = m.forward(params, obs)
    assert logits.shape == (3, 2) and value.shape == (3,)
    a, logp, v = m.action_dist(params, obs, jax.random.PRNGKey(1))
    assert a.shape == (3,) and logp.shape == (3,)


def test_learner_reduces_loss():
    m = DiscretePolicyModule(4, 2, hidden=(16,))
    learner = Learner(m, lr=1e-2)
    rng = np.random.default_rng(0)
    n = 64
    batch = SampleBatch({
        SB.OBS: rng.normal(size=(n, 4)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 2, n).astype(np.int32),
        SB.LOGP: np.full(n, -0.69, np.float32),
        SB.ADVANTAGES: rng.normal(size=n).astype(np.float32),
        SB.VALUE_TARGETS: rng.normal(size=n).astype(np.float32),
    })
    first = learner.update(batch)
    for _ in range(20):
        last = learner.update(batch)
    assert last["vf_loss"] < first["vf_loss"]


def test_ppo_smoke_random_env(rtpu_init):
    algo = (PPOConfig()
            .environment(lambda: RandomEnv(episode_len=20))
            .rollouts(num_rollout_workers=1, rollout_fragment_length=64)
            .training(num_sgd_iter=2, sgd_minibatch_size=32)
            .build())
    result = algo.train()
    assert result["num_env_steps_sampled"] == 64
    assert "learner/total_loss" in result
    algo.stop()


def test_ppo_learns_cartpole(rtpu_init):
    algo = (PPOConfig()
            .environment(CartPoleEnv)
            .rollouts(num_rollout_workers=2, rollout_fragment_length=512)
            .training(num_sgd_iter=10, sgd_minibatch_size=256, lr=1e-3,
                      entropy_coeff=0.01)
            .build())
    first_reward = None
    best = -np.inf
    for i in range(40):
        result = algo.train()
        r = result["episode_reward_mean"]
        if not np.isnan(r):
            if first_reward is None:
                first_reward = r
            best = max(best, r)
        if best >= 80:
            break
    algo.stop()
    assert first_reward is not None
    assert best >= 80, (
        f"PPO failed to learn: first={first_reward}, best={best}")


def test_learner_group_multi(rtpu_init):
    m = DiscretePolicyModule(4, 2, hidden=(8,))
    group = LearnerGroup(m, num_learners=2, lr=1e-3)
    rng = np.random.default_rng(0)
    n = 64
    batch = SampleBatch({
        SB.OBS: rng.normal(size=(n, 4)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 2, n).astype(np.int32),
        SB.LOGP: np.full(n, -0.69, np.float32),
        SB.ADVANTAGES: rng.normal(size=n).astype(np.float32),
        SB.VALUE_TARGETS: rng.normal(size=n).astype(np.float32),
    })
    stats = group.update(batch)
    assert "total_loss" in stats
    w = group.get_weights()
    assert "pi" in w
    group.shutdown()


def test_vtrace_matches_onpolicy_returns():
    """With rho = c = 1 (behavior == target policy), V-trace targets are
    the lambda=1 GAE targets — verify the scan against the numpy GAE."""
    import jax

    from ray_tpu.rl.learner import Learner

    m = DiscretePolicyModule(4, 2, hidden=(8,))
    learner = Learner(m, loss="vtrace", gamma=0.9, entropy_coeff=0.0,
                      vf_coeff=1.0)
    rng = np.random.default_rng(0)
    T = 16
    obs = rng.normal(size=(1, T, 4)).astype(np.float32)
    actions = rng.integers(0, 2, (1, T)).astype(np.int32)
    rewards = rng.normal(size=(1, T)).astype(np.float32)
    dones = np.zeros((1, T), bool)
    dones[0, 7] = True
    bootstrap_obs = rng.normal(size=(1, 4)).astype(np.float32)

    # on-policy behavior logp: exactly the current policy's
    logits, values = m.forward(learner.params, obs[0])
    logp_all = np.asarray(jax.nn.log_softmax(logits))
    blogp = logp_all[np.arange(T), actions[0]][None, :].astype(np.float32)

    batch = {SB.OBS: obs, SB.ACTIONS: actions, SB.REWARDS: rewards,
             SB.DONES: dones, SB.LOGP: blogp,
             "bootstrap_obs": bootstrap_obs}
    import jax.numpy as jnp
    loss, stats = learner._vtrace_loss(
        jax.tree_util.tree_map(jnp.asarray, learner.params),
        {k: jnp.asarray(v) for k, v in batch.items()})
    assert float(stats["mean_rho"]) == pytest.approx(1.0, abs=1e-5)

    # numpy reference: vs == lambda=1 returns == GAE(lam=1) + V
    _, bv = m.forward(learner.params, bootstrap_obs)
    gae_batch = SampleBatch({
        SB.REWARDS: rewards[0], SB.VF_PREDS: np.asarray(values),
        SB.DONES: dones[0],
    })
    out = compute_gae(gae_batch, gamma=0.9, lam=1.0,
                      last_value=float(bv[0]))
    vs_expected = out[SB.VALUE_TARGETS]
    vf_loss = float(stats["vf_loss"])
    vf_expected = 0.5 * np.mean((vs_expected - np.asarray(values)) ** 2)
    assert vf_loss == pytest.approx(vf_expected, rel=1e-4)


def test_impala_smoke_random_env(rtpu_init):
    algo = (ImpalaConfig()
            .environment(lambda: RandomEnv(episode_len=20))
            .rollouts(num_rollout_workers=2, rollout_fragment_length=32)
            .build())
    result = algo.train()
    assert result["num_env_steps_sampled"] >= 32
    assert "learner/total_loss" in result
    algo.stop()


def test_impala_learns_cartpole(rtpu_init):
    algo = (ImpalaConfig()
            .environment(CartPoleEnv)
            .rollouts(num_rollout_workers=2, rollout_fragment_length=256)
            .training(lr=2e-3, entropy_coeff=0.02, num_sgd_iter=6)
            .build())
    best = -np.inf
    for _ in range(200):
        result = algo.train()
        r = result["episode_reward_mean"]
        if not np.isnan(r):
            best = max(best, r)
        if best >= 80:
            break
    algo.stop()
    assert best >= 80, f"IMPALA failed to learn CartPole: best={best}"


def test_impala_multi_learner(rtpu_init):
    algo = (ImpalaConfig()
            .environment(lambda: RandomEnv(episode_len=20))
            .rollouts(num_rollout_workers=2, rollout_fragment_length=32)
            .learners(2)
            .build())
    result = algo.train()
    assert "learner/total_loss" in result
    algo.stop()


def test_dqn_learner_update_smoke():
    """DQNLearner._loss is jitted on first update
    (past learning_starts); a missing import inside the trace raised
    NameError there. Runs enough updates to cross a target sync."""
    from ray_tpu.rl.dqn import NEXT_OBS, DQNLearner
    from ray_tpu.rl.module import QNetworkModule

    rng = np.random.default_rng(0)
    learner = DQNLearner(QNetworkModule(4, 2), target_update_freq=2)
    batch = SampleBatch({
        SB.OBS: rng.standard_normal((32, 4)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 2, 32).astype(np.int32),
        SB.REWARDS: rng.standard_normal(32).astype(np.float32),
        NEXT_OBS: rng.standard_normal((32, 4)).astype(np.float32),
        SB.DONES: (rng.random(32) < 0.1),
    })
    losses = [learner.update(batch)["loss"] for _ in range(5)]
    assert all(np.isfinite(l) for l in losses)


def test_dqn_trains_past_learning_starts(rtpu_init):
    from ray_tpu.rl import DQNConfig

    algo = (DQNConfig()
            .environment(lambda: RandomEnv(episode_len=20))
            .rollouts(num_rollout_workers=1, rollout_fragment_length=64)
            .training(learning_starts=64, train_batch_size=32,
                      updates_per_iter=4, target_update_freq=4)
            .build())
    saw_update = False
    for _ in range(4):
        result = algo.train()
        if result["num_updates"] > 0:
            assert np.isfinite(result["loss"])
            saw_update = True
    algo.stop()
    assert saw_update, "DQN never ran a learner update"


def test_vector_env_autoreset_and_shapes():
    from ray_tpu.rl import VectorEnv

    venv = VectorEnv(lambda: RandomEnv(episode_len=3), 4)
    obs = venv.reset_all()
    assert obs.shape == (4, 4) and obs.dtype == np.float32
    for step in range(3):
        obs, rew, terms, truncs, final = venv.step(np.zeros(4, np.int32))
        assert obs.shape == (4, 4) and rew.shape == (4,)
    assert truncs.all()            # episode_len=3 hit simultaneously
    # after auto-reset the envs keep stepping
    obs, _, terms, truncs, _ = venv.step(np.zeros(4, np.int32))
    assert not (terms | truncs).any()


def test_ppo_vectorized_learns_cartpole(rtpu_init):
    algo = (PPOConfig()
            .environment(CartPoleEnv)
            .rollouts(num_rollout_workers=1, num_envs_per_worker=4,
                      rollout_fragment_length=256)
            .training(num_sgd_iter=10, sgd_minibatch_size=256, lr=1e-3,
                      entropy_coeff=0.01)
            .build())
    best = 0.0
    for _ in range(40):
        result = algo.train()
        assert result["num_env_steps_sampled"] == 4 * 256
        r = result["episode_reward_mean"]
        if not np.isnan(r):
            best = max(best, r)
        if best >= 80:
            break
    algo.stop()
    assert best >= 80, f"vectorized PPO failed to learn: best={best}"


def test_impala_vectorized_smoke(rtpu_init):
    algo = (ImpalaConfig()
            .environment(lambda: RandomEnv(episode_len=16))
            .rollouts(num_rollout_workers=2, num_envs_per_worker=3,
                      rollout_fragment_length=32)
            .build())
    result = algo.train()
    assert "learner/total_loss" in result
    assert result["num_env_steps_sampled"] % (3 * 32) == 0
    algo.stop()


def test_dqn_vectorized_smoke(rtpu_init):
    from ray_tpu.rl import DQNConfig

    algo = (DQNConfig()
            .environment(lambda: RandomEnv(episode_len=10))
            .rollouts(num_rollout_workers=1, num_envs_per_worker=4,
                      rollout_fragment_length=32)
            .training(learning_starts=64, train_batch_size=32,
                      updates_per_iter=2)
            .build())
    upd = 0
    for _ in range(3):
        result = algo.train()
        upd += result["num_updates"]
    algo.stop()
    assert upd > 0


def test_replay_buffers_uniform_and_prioritized():
    """Replay-buffer library (reference: rllib/utils/replay_buffers):
    ring semantics, proportional prioritized sampling, importance
    weights, priority updates."""
    from ray_tpu.rl import PrioritizedReplayBuffer, UniformReplayBuffer

    buf = UniformReplayBuffer(capacity=5, seed=0)
    for i in range(8):
        buf.add(i)
    assert len(buf) == 5 and buf.num_added == 8
    assert set(buf.sample(50)) <= {3, 4, 5, 6, 7}   # oldest evicted

    pb = PrioritizedReplayBuffer(capacity=100, alpha=1.0, seed=0)
    for i in range(100):
        pb.add(i, priority=0.05)
    pb.update_priorities(np.asarray([7]), np.asarray([20.0]))
    items, idx, weights = pb.sample(1000, beta=1.0)
    arr = np.asarray(items)
    counts = np.bincount(arr, minlength=100)
    # item 7 holds ~80% of the priority mass -> dominates sampling
    assert counts[7] > 600
    assert counts.sum() - counts[7] > 50      # others still appear
    assert weights.max() == pytest.approx(1.0)
    # the frequently-sampled item carries a much smaller importance
    # weight than the rare ones (normalized by the sampled max)
    assert weights[arr == 7].max() < 0.05 * weights[arr != 7].max()


def test_offline_dqn_from_dataset(rtpu_init):
    """Offline RL: collect transitions into a Dataset with a random
    behavior policy, then train DQN purely from the logs (reference:
    rllib/offline DatasetReader)."""
    from ray_tpu.rl import CartPoleEnv, OfflineDQN, collect_to_dataset

    ds = collect_to_dataset(CartPoleEnv, num_steps=256, num_envs=2,
                            epsilon=1.0, seed=0)
    assert ds.count() == 512
    algo = OfflineDQN(ds, observation_size=4, action_size=2,
                      train_batch_size=32, seed=0)
    r1 = algo.train(num_updates=8)
    r2 = algo.train(num_updates=8)
    assert r2["num_updates"] == 16
    assert np.isfinite(r1["loss"]) and np.isfinite(r2["loss"])
    w = algo.get_weights()
    assert "q" in w or len(w) > 0
