"""RLlib-equivalent tests.

Modeled on the reference's test strategy (SURVEY.md §4): pure-logic unit
tests for math components (V-trace, GAE, replay priorities — like
`rllib/algorithms/impala/tests/test_vtrace.py`), plus short
learning-regression runs with reward thresholds (the reference's
`tuned_examples/*.yaml` regression oracles, rllib/BUILD:152-162)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.rllib.core.distributions import Categorical, DiagGaussian
from ray_tpu.rllib.env.jax_env import CartPole, EagerJaxEnv, Pendulum
from ray_tpu.rllib.replay_buffers import (
    PrioritizedReplayBuffer, ReplayBuffer)
from ray_tpu.rllib.sample_batch import SampleBatch, compute_gae, concat_samples


# ---------------------------------------------------------------------------
# Math units
# ---------------------------------------------------------------------------


def test_sample_batch_ops():
    b1 = SampleBatch({"obs": np.ones((4, 3)), "rewards": np.arange(4.0)})
    b2 = SampleBatch({"obs": np.zeros((2, 3)), "rewards": np.arange(2.0)})
    cat = concat_samples([b1, b2])
    assert cat.count == 6
    mbs = list(cat.minibatches(2))
    assert len(mbs) == 3 and all(m.count == 2 for m in mbs)


def test_gae_matches_manual():
    r = np.array([1.0, 1.0, 1.0], np.float32)
    v = np.array([0.5, 0.4, 0.3], np.float32)
    d = np.array([False, False, True])
    out = compute_gae(r, v, d, last_value=9.9, gamma=0.9, lam=0.8)
    # terminal step: delta = 1 - 0.3
    a2 = 0.7
    a1 = (1 + 0.9 * 0.3 - 0.4) + 0.9 * 0.8 * a2
    a0 = (1 + 0.9 * 0.4 - 0.5) + 0.9 * 0.8 * a1
    np.testing.assert_allclose(out["advantages"], [a0, a1, a2], rtol=1e-5)


def test_vtrace_on_policy_reduces_to_returns():
    """With target==behaviour (rho=1) and lambda=1, vs is the n-step
    bootstrapped return (V-trace paper, remark 1)."""
    from ray_tpu.rllib.algorithms.impala import vtrace
    T = 5
    logp = jnp.zeros(T)
    rewards = jnp.ones(T)
    values = jnp.asarray(np.linspace(0.2, 1.0, T), jnp.float32)
    dones = jnp.zeros(T, bool)
    last_v = jnp.asarray(2.0)
    vs, pg = vtrace(logp, logp, rewards, values, dones, last_v,
                    gamma=0.9, lambda_=1.0, clip_rho=1.0, clip_pg_rho=1.0)
    # manual n-step return
    expect = []
    acc = float(last_v)
    for t in reversed(range(T)):
        acc = 1.0 + 0.9 * acc
        expect.append(acc)
    np.testing.assert_allclose(np.asarray(vs), expect[::-1], rtol=1e-5)


def test_categorical_dist():
    logits = jnp.asarray([[2.0, 0.0, -1.0]])
    dist = Categorical(logits)
    p = np.exp(np.asarray(jax.nn.log_softmax(logits)))[0]
    np.testing.assert_allclose(
        float(dist.entropy()[0]), -(p * np.log(p)).sum(), rtol=1e-5)
    np.testing.assert_allclose(
        float(dist.logp(jnp.asarray([0]))[0]), np.log(p[0]), rtol=1e-5)
    assert int(dist.deterministic()[0]) == 0


def test_gaussian_dist():
    dist = DiagGaussian(jnp.zeros((1, 2)), jnp.zeros((1, 2)))
    lp = float(dist.logp(jnp.zeros((1, 2)))[0])
    np.testing.assert_allclose(lp, -np.log(2 * np.pi), rtol=1e-5)
    kl = float(dist.kl(DiagGaussian(jnp.ones((1, 2)),
                                    jnp.zeros((1, 2))))[0])
    np.testing.assert_allclose(kl, 1.0, rtol=1e-5)   # 2 dims * 0.5


def test_replay_buffer_ring():
    buf = ReplayBuffer(capacity=10)
    buf.add_batch({"x": np.arange(8.0)})
    assert len(buf) == 8
    buf.add_batch({"x": np.arange(8.0, 16.0)})
    assert len(buf) == 10
    s = buf.sample(32)
    assert s["x"].shape == (32,)
    assert s["x"].max() >= 10      # new data present after wraparound


def test_prioritized_buffer_biases_sampling():
    buf = PrioritizedReplayBuffer(capacity=128, alpha=1.0, seed=0)
    buf.add_batch({"x": np.arange(100.0)})
    # give item 7 overwhelming priority
    buf.update_priorities(np.arange(100), np.full(100, 1e-3))
    buf.update_priorities(np.array([7]), np.array([100.0]))
    s = buf.sample(256)
    frac = (s["x"] == 7.0).mean()
    assert frac > 0.9
    assert "weights" in s and s["weights"].min() > 0


# ---------------------------------------------------------------------------
# Environments
# ---------------------------------------------------------------------------


def test_cartpole_pd_controller_survives():
    env = EagerJaxEnv(CartPole({}), seed=0)
    obs = env.reset()
    total = 0
    for _ in range(500):
        obs, r, done, _ = env.step(int(obs[2] + 0.5 * obs[3] > 0))
        total += r
        if done:
            break
    assert total > 400


def test_pendulum_shapes():
    env = Pendulum({})
    state, obs = env.reset(jax.random.PRNGKey(0))
    assert obs.shape == (3,)
    state, obs, r, done, _ = env.step(
        state, jnp.asarray([0.5]), jax.random.PRNGKey(1))
    assert float(r) <= 0          # pendulum cost is negative reward


# ---------------------------------------------------------------------------
# Learning regressions (reward thresholds, short budgets)
# ---------------------------------------------------------------------------


def test_ppo_cartpole_learns():
    from ray_tpu.rllib.algorithms.ppo import PPOConfig
    algo = (PPOConfig().environment("CartPole-v1")
            .training(num_sgd_iter=4, sgd_minibatch_size=256)
            .rollouts(num_envs_per_worker=8, rollout_fragment_length=64)
            .debugging(seed=0)
            .build())
    best = 0.0
    for _ in range(30):
        r = algo.train()
        rew = r.get("episode_reward_mean")
        if rew == rew:      # not NaN
            best = max(best, rew)
    assert best > 60, best


def test_dqn_cartpole_learns():
    from ray_tpu.rllib.algorithms.dqn import DQNConfig
    algo = (DQNConfig().environment("CartPole-v1")
            .training(epsilon_timesteps=15_000)
            .debugging(seed=0)
            .build())
    best = 0.0
    for _ in range(120):
        r = algo.train()
        rew = r.get("episode_reward_mean")
        if rew == rew:
            best = max(best, rew)
    assert best > 60, best


def test_dqn_prioritized_replay_runs():
    from ray_tpu.rllib.algorithms.dqn import DQNConfig
    algo = (DQNConfig().environment("CartPole-v1")
            .training(prioritized_replay=True, learning_starts=200,
                      n_updates_per_iter=4)
            .build())
    for _ in range(5):
        r = algo.train()
    assert r["buffer_size"] > 0


def test_ppo_pendulum_continuous_runs():
    from ray_tpu.rllib.algorithms.ppo import PPOConfig
    algo = (PPOConfig().environment("Pendulum-v1")
            .training(num_sgd_iter=2, sgd_minibatch_size=128)
            .rollouts(num_envs_per_worker=4, rollout_fragment_length=32)
            .build())
    r = algo.train()
    assert np.isfinite(r["policy_loss"])


def test_algorithm_checkpoint_roundtrip(tmp_path, monkeypatch):
    from ray_tpu.rllib.algorithms.ppo import PPOConfig
    # a Trainable built without a trial_dir saves under the working
    # directory: `checkpoint_000001/` belongs in tmp_path, not the checkout
    monkeypatch.chdir(tmp_path)
    algo = (PPOConfig().environment("CartPole-v1")
            .rollouts(num_envs_per_worker=2, rollout_fragment_length=16)
            .build())
    algo.train()
    ckpt = algo.save()
    algo2 = (PPOConfig().environment("CartPole-v1")
             .rollouts(num_envs_per_worker=2, rollout_fragment_length=16)
             .build())
    algo2.restore(ckpt)
    a = jax.tree.leaves(algo.params)
    b = jax.tree.leaves(algo2.params)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Distributed paths (shared cluster fixture)
# ---------------------------------------------------------------------------


def test_ppo_workerset_path(ray_session):
    """PPO with remote rollout actors (the reference's default shape)."""
    from ray_tpu.rllib.algorithms.ppo import PPOConfig
    algo = (PPOConfig().environment("CartPole-v1")
            .rollouts(num_rollout_workers=2, rollout_fragment_length=64)
            .training(num_sgd_iter=2, sgd_minibatch_size=64)
            .build())
    try:
        r1 = algo.train()
        r2 = algo.train()
        assert np.isfinite(r2["policy_loss"])
        assert r2["num_env_steps_sampled_this_iter"] == 128
    finally:
        algo.cleanup()


def test_impala_learns(ray_session):
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig
    algo = (IMPALAConfig().environment("CartPole-v1")
            .rollouts(num_rollout_workers=2, rollout_fragment_length=128)
            .build())
    best = 0.0
    try:
        for _ in range(40):
            r = algo.train()
            rew = r.get("episode_reward_mean")
            if rew == rew:
                best = max(best, rew)
    finally:
        algo.cleanup()
    assert best > 40, best


def test_tune_over_algorithm(ray_session, tmp_path):
    """tune.run(PPO, ...) — Algorithm as Trainable (reference:
    algorithm.py:191 Algorithm IS-A Trainable)."""
    from ray_tpu import tune
    from ray_tpu.rllib.algorithms.ppo import PPO

    grid = tune.run(
        PPO,
        config={"env": "CartPole-v1", "num_envs_per_worker": 4,
                "rollout_fragment_length": 32, "num_sgd_iter": 2,
                "sgd_minibatch_size": 64,
                "lr": tune.grid_search([3e-4, 1e-3])},
        stop={"training_iteration": 2},
        storage_path=str(tmp_path), name="rl_tune")
    assert len(grid) == 2
    assert not grid.errors
    for r in grid:
        assert r.metrics["training_iteration"] == 2
