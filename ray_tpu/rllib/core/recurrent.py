"""Recurrent policy support: LSTM Q-module + stateful in-graph sampler.

Nothing in rollout.py carries policy state; this module does.
TPU-first design: the recurrent state is just
another pytree in the scan carry — the whole rollout (env vmap + LSTM
step + epsilon-greedy) stays one compiled `lax.scan`, and the sampler
emits fixed-length fragments WITH the state snapshot at fragment start.
That is exactly R2D2's "stored state" strategy (Kapturowski et al. 2019),
which the reference implements eagerly in
`rllib/algorithms/r2d2/r2d2.py` + `policy/rnn_sequencing.py`; here the
storage format falls out of the scan naturally.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.core.rl_module import build_torso
from ray_tpu.rllib.env.spaces import Box, Discrete


class _RecurrentQNet(nn.Module):
    """obs -> torso -> LSTMCell -> Q(a). Single-step; time handled by
    the caller's scan so rollout (step) and training (unroll) share the
    exact same cell."""
    num_actions: int
    obs_shape: tuple
    cfg: dict

    @nn.compact
    def __call__(self, obs, state):
        hidden = self.cfg.get("lstm_cell_size", 64)
        torso = build_torso(self.obs_shape, self.cfg, "relu", "torso")
        x = torso(obs)
        cell = nn.OptimizedLSTMCell(features=hidden)
        (c, h), out = cell((state[0], state[1]), x)
        q = nn.Dense(self.num_actions)(out)
        return q, (c, h)


class RecurrentQModule:
    """Q-network with LSTM state for R2D2-style algorithms.

    API mirrors QModule but every method threads `state` (a (c, h)
    tuple, both [B, hidden]):
      - initial_state(n)           -> zero state
      - q_step(params, obs, state) -> (q [B, A], state')
      - q_unroll(params, obs [T,B,...], dones [T,B], state0)
                                   -> (q [T,B,A], stateT)
        (state resets to zeros where done, so stored sequences may cross
        episode boundaries like the reference's rnn_sequencing)
      - compute_actions(params, obs, state, key, epsilon)
                                   -> (actions, q_sel, state')
    """

    def __init__(self, observation_space: Box, action_space: Discrete,
                 model_config: dict | None = None):
        if not isinstance(action_space, Discrete):
            raise ValueError(
                "RecurrentQModule requires a Discrete action space")
        cfg = dict(model_config or {})
        self.observation_space = observation_space
        self.action_space = action_space
        self.num_actions = action_space.n
        self.hidden = int(cfg.get("lstm_cell_size", 64))
        self._obs_shape = tuple(observation_space.shape)
        self.net = _RecurrentQNet(self.num_actions, self._obs_shape, cfg)

    def initial_state(self, n: int):
        return (jnp.zeros((n, self.hidden)), jnp.zeros((n, self.hidden)))

    def init(self, key) -> dict:
        dummy = jnp.zeros((1, *self._obs_shape))
        return self.net.init(key, dummy, self.initial_state(1))["params"]

    def q_step(self, params, obs, state):
        return self.net.apply({"params": params}, obs, state)

    def q_unroll(self, params, obs_seq, dones_seq, state0):
        def step(state, xs):
            obs, done = xs
            q, new_state = self.q_step(params, obs, state)
            # reset where the episode ended AFTER this step: the next
            # step's state must not leak across the boundary
            mask = (1.0 - done.astype(jnp.float32))[:, None]
            new_state = (new_state[0] * mask, new_state[1] * mask)
            return new_state, q
        stateT, q = jax.lax.scan(step, state0, (obs_seq, dones_seq))
        return q, stateT

    def compute_actions(self, params, obs, state, key, epsilon=0.0):
        q, new_state = self.q_step(params, obs, state)
        greedy = jnp.argmax(q, axis=-1)
        k1, k2 = jax.random.split(key)
        rand_actions = jax.random.randint(
            k1, greedy.shape, 0, self.num_actions)
        explore = jax.random.uniform(k2, greedy.shape) < epsilon
        actions = jnp.where(explore, rand_actions, greedy)
        q_sel = jnp.take_along_axis(q, actions[..., None], axis=-1)[..., 0]
        return actions, q_sel, new_state


class RecurrentInGraphSampler:
    """Compiled vectorized rollout that carries policy state and emits
    the fragment-start state alongside each fixed-length fragment —
    the sequence + stored-state format R2D2's replay wants, produced
    directly by the scan (no host-side rnn_sequencing pass)."""

    def __init__(self, env, module: RecurrentQModule, num_envs: int,
                 rollout_length: int):
        self.env = env
        self.module = module
        self.num_envs = num_envs
        self.rollout_length = rollout_length
        self._unroll = jax.jit(self._unroll_impl)

    def init_state(self, key):
        keys = jax.random.split(key, self.num_envs)
        state, obs = jax.vmap(self.env.reset)(keys)
        return {"env_state": state, "obs": obs,
                "policy_state": self.module.initial_state(self.num_envs),
                "ep_ret": jnp.zeros(self.num_envs),
                "ep_len": jnp.zeros(self.num_envs, jnp.int32)}

    def _unroll_impl(self, params, carry, key, epsilon):
        state0 = carry["policy_state"]

        def one_step(carry, step_key):
            k_act, k_env = jax.random.split(step_key)
            obs = carry["obs"]
            actions, q_sel, pol_state = self.module.compute_actions(
                params, obs, carry["policy_state"], k_act, epsilon)
            env_keys = jax.random.split(k_env, self.num_envs)
            state, next_obs, reward, done, _ = jax.vmap(self.env.step)(
                carry["env_state"], actions, env_keys)
            # zero the policy state where the episode ended — the auto-
            # reset env starts fresh, so must the memory
            mask = (1.0 - done.astype(jnp.float32))[:, None]
            pol_state = (pol_state[0] * mask, pol_state[1] * mask)
            ep_ret = carry["ep_ret"] + reward
            ep_len = carry["ep_len"] + 1
            finished_ret = jnp.where(done, ep_ret, jnp.nan)
            finished_len = jnp.where(done, ep_len, -1)
            new_carry = {
                "env_state": state,
                "obs": next_obs,
                "policy_state": pol_state,
                "ep_ret": jnp.where(done, 0.0, ep_ret),
                "ep_len": jnp.where(done, 0, ep_len),
            }
            out = {sb.OBS: obs, sb.ACTIONS: actions, sb.REWARDS: reward,
                   sb.DONES: done,
                   "episode_return": finished_ret,
                   "episode_len": finished_len}
            return new_carry, out

        step_keys = jax.random.split(key, self.rollout_length)
        carry, traj = jax.lax.scan(one_step, carry, step_keys)
        return carry, traj, state0

    def sample(self, params, carry, key, epsilon):
        """-> (new_carry, traj [T, num_envs, ...], fragment-start policy
        state (c, h) each [num_envs, hidden])."""
        return self._unroll(params, carry, key, epsilon)
