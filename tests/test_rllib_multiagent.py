"""Multi-agent env + MAPPO, DDPG/TD3, and the tuned-example regression
harness (reference: rllib/env/multi_agent_env.py tests, td3 tests,
rllib/tests/run_regression_tests.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.rllib.env.multi_agent import CoopMatch
from ray_tpu.rllib.train import (
    list_tuned_examples,
    run_experiment,
    run_tuned_example,
)


def test_multi_agent_env_contract():
    env = CoopMatch({"n_agents": 3, "n_tokens": 4, "episode_len": 5})
    assert env.agent_ids == ("agent_0", "agent_1", "agent_2")
    key = jax.random.PRNGKey(0)
    state, obs = env.reset(key)
    assert set(obs) == set(env.agent_ids)
    assert obs["agent_0"].shape == (4,)
    acts = {aid: jnp.argmax(obs[aid]) for aid in env.agent_ids}
    state, obs2, rew, done, _ = env.step(state, acts, key)
    # all actions matched their tokens -> shared reward 1.0 for everyone
    for aid in env.agent_ids:
        assert float(rew[aid]) == pytest.approx(1.0)
    assert not bool(done)

    # vmap over a batch of envs (the in-graph vector path)
    keys = jax.random.split(key, 4)
    bstate, bobs = jax.vmap(env.reset)(keys)
    assert bobs["agent_1"].shape == (4, 4)
    bacts = {aid: jnp.zeros(4, jnp.int32) for aid in env.agent_ids}
    _, _, brew, bdone, _ = jax.vmap(env.step)(bstate, bacts, keys)
    assert brew["agent_2"].shape == (4,)


def test_mappo_learns_cooperative_toy():
    """Shared-reward coordination: MAPPO with per-agent policies reaches
    >=12 of the optimal 16 episode reward."""
    result = run_tuned_example(
        [p for p in list_tuned_examples() if "coopmatch-mappo" in p][0],
        verbose=False)
    assert result["passed"], result
    assert result["best_reward"] >= 12, result


def test_mappo_per_agent_policies():
    from ray_tpu.rllib.algorithms.ma_ppo import MAPPOConfig
    algo = (MAPPOConfig().environment("CoopMatch")
            .training(model={"fcnet_hiddens": (16, 16)})
            .rollouts(num_envs_per_worker=8, rollout_fragment_length=16)
            .debugging(seed=1)
            .multi_agent(policies={"p0", "p1"},
                         policy_mapping_fn=lambda aid: "p" + aid[-1])
            .build())
    r = algo.train()
    assert "p0/policy_loss" in r and "p1/policy_loss" in r
    # distinct parameter trees per policy
    assert set(algo.params) == {"p0", "p1"}
    acts = algo.compute_actions(
        {"agent_0": np.eye(3)[0], "agent_1": np.eye(3)[2]})
    assert set(acts) == {"agent_0", "agent_1"}
    # checkpoint roundtrip
    state = algo.get_state()
    algo.set_state(state)


@pytest.mark.slow
def test_td3_pendulum_improves():
    """TD3 clearly improves from the ~-1400 random-policy floor within a
    small budget (full -900 threshold lives in pendulum-td3.yaml)."""
    from ray_tpu.rllib.algorithms.ddpg import TD3Config
    algo = (TD3Config().environment("Pendulum-v1")
            .training(n_updates_per_iter=256, learning_starts=500,
                      train_batch_size=128, no_done_at_end=True,
                      exploration_noise=0.15,
                      model={"fcnet_hiddens": (64, 64)})
            .rollouts(num_envs_per_worker=32, rollout_fragment_length=8)
            .debugging(seed=0)
            .build())
    best = -1e9
    for _ in range(55):
        r = algo.train()
        rew = r.get("episode_reward_mean")
        if rew == rew:
            best = max(best, rew)
        if best > -950:
            break
    assert best > -950, best


def test_ddpg_td3_config_flags():
    from ray_tpu.rllib.algorithms.ddpg import DDPGConfig, TD3Config
    d, t = DDPGConfig(), TD3Config()
    assert not d.twin_q and d.policy_delay == 1 and d.target_noise == 0.0
    assert t.twin_q and t.policy_delay == 2 and t.target_noise == 0.2


def test_tuned_examples_parse_and_resolve():
    """Every shipped YAML names a registered algorithm and an env that
    make_env can resolve, and carries a reward-threshold stop."""
    import yaml

    from ray_tpu.rllib.algorithms import get_algorithm_class
    from ray_tpu.rllib.env.jax_env import _ENV_REGISTRY

    paths = list_tuned_examples()
    assert len(paths) >= 4
    for p in paths:
        with open(p) as f:
            spec = yaml.safe_load(f)
        _, body = next(iter(spec.items()))
        assert get_algorithm_class(body["run"]) is not None
        assert body["env"] in _ENV_REGISTRY
        assert "episode_reward_mean" in body["stop"]


def test_cli_runs_without_reward_target(capsys):
    from ray_tpu.rllib.train import main
    rc = main(["--algo", "A2C", "--env", "CartPole-v1",
               "--stop-iters", "2",
               "--config", '{"num_envs_per_worker": 4, '
                           '"rollout_fragment_length": 16}'])
    assert rc == 0
    out = capsys.readouterr().out
    assert '"passed": true' in out


def test_run_experiment_reports_failure():
    out = run_experiment(
        "A2C", "CartPole-v1",
        config={"num_envs_per_worker": 4, "rollout_fragment_length": 16},
        stop={"episode_reward_mean": 1e9, "training_iteration": 2},
        verbose=False)
    assert not out["passed"]
    assert out["iterations"] == 2
