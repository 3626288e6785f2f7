"""PPO — proximal policy optimization.

Counterpart of the reference's `rllib/algorithms/ppo/` (ppo.py:420
training_step; loss `ppo_torch_policy.py`: clipped surrogate + vf loss +
entropy; GAE `rllib/evaluation/postprocessing.py`). TPU-first shape:

- JaxEnv path: rollout (vmap+scan), GAE (reverse scan), and the full
  num_sgd_iter × minibatch SGD loop are ONE jitted function — the whole
  PPO iteration is a single XLA program; Python only reads metrics.
- Python-env path: WorkerSet actors sample; GAE on host; the same jitted
  update consumes the concatenated batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithms.algorithm import (
    Algorithm, AlgorithmConfig, register_algorithm)
from ray_tpu.rllib.env.jax_env import is_jax_env
from ray_tpu.rllib.rollout import InGraphSampler, episode_stats
from ray_tpu.rllib.sample_batch import SampleBatch, compute_gae, concat_samples
from ray_tpu.rllib.worker_set import WorkerSet, merge_episode_stats


class PPOConfig(AlgorithmConfig):
    def __init__(self, algo_class=None):
        super().__init__(algo_class or PPO)
        self.lr = 3e-4
        self.gamma = 0.99
        self.lambda_ = 0.95
        self.clip_param = 0.2
        self.vf_clip_param = 10.0
        self.vf_loss_coeff = 0.5
        self.entropy_coeff = 0.0
        self.num_sgd_iter = 8
        self.sgd_minibatch_size = 512
        self.rollout_fragment_length = 128
        self.num_envs_per_worker = 16
        self.grad_clip = 0.5
        # >1: shard the WHOLE fused iteration (rollout + GAE + SGD) over
        # a data-axis device mesh via shard_map — env batch split across
        # devices, gradients pmean'd over ICI. The TPU-native analogue of
        # the reference's multi-GPU learner stack
        # (rllib/execution/multi_gpu_learner_thread.py), except sampling
        # shards too, not just the SGD pass.
        self.num_learner_devices = 0


def _ppo_loss(module, params, batch, clip_param, vf_clip_param,
              vf_loss_coeff, entropy_coeff):
    dist, value = module.forward(params, batch[sb.OBS])
    logp = dist.logp(batch[sb.ACTIONS])
    ratio = jnp.exp(logp - batch[sb.ACTION_LOGP])
    adv = batch[sb.ADVANTAGES]
    surr = jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - clip_param, 1 + clip_param) * adv)
    policy_loss = -jnp.mean(surr)
    # Clipped value loss (reference: ppo_torch_policy.py vf_clip_param).
    vf_err = jnp.square(value - batch[sb.VALUE_TARGETS])
    vf_loss = jnp.mean(jnp.clip(vf_err, 0.0, vf_clip_param ** 2))
    entropy = jnp.mean(dist.entropy())
    total = policy_loss + vf_loss_coeff * vf_loss - entropy_coeff * entropy
    stats = {"policy_loss": policy_loss, "vf_loss": vf_loss,
             "entropy": entropy,
             "approx_kl": jnp.mean(batch[sb.ACTION_LOGP] - logp)}
    return total, stats


def _gae_scan(rewards, values, dones, last_value, gamma, lam):
    """In-graph GAE: reverse lax.scan over time. rewards/values/dones are
    [T, B]; last_value [B]."""

    def back(carry, xs):
        r, v, d, next_v = xs
        nonterm = 1.0 - d.astype(jnp.float32)
        delta = r + gamma * next_v * nonterm - v
        adv = delta + gamma * lam * nonterm * carry
        return adv, adv

    next_values = jnp.concatenate([values[1:], last_value[None]], axis=0)
    _, advs = jax.lax.scan(back, jnp.zeros_like(last_value),
                           (rewards, values, dones, next_values),
                           reverse=True)
    return advs


class PPO(Algorithm):
    _config_class = PPOConfig

    def build_learner(self) -> None:
        cfg = self.algo_config
        chain = []
        if cfg.grad_clip:
            chain.append(optax.clip_by_global_norm(cfg.grad_clip))
        chain.append(optax.adam(cfg.lr))
        self.optimizer = optax.chain(*chain)
        self.opt_state = self.optimizer.init(self.params)
        self.workers = None
        self._in_graph = is_jax_env(self.env)
        self._axis_name = None
        if self._in_graph and cfg.num_rollout_workers == 0:
            self.sampler = InGraphSampler(
                self.env, self.module, cfg.num_envs_per_worker,
                cfg.rollout_fragment_length)
            self._carry = self.sampler.init_state(self.next_key())
            n = int(cfg.num_learner_devices or 0)
            if n > 1:
                from jax.sharding import Mesh, PartitionSpec as P

                from ray_tpu.parallel.sharding import shard_map
                if cfg.num_envs_per_worker % n:
                    raise ValueError(
                        f"num_envs_per_worker={cfg.num_envs_per_worker} "
                        f"must divide over num_learner_devices={n}")
                devices = np.array(jax.devices()[:n])
                if len(devices) < n:
                    raise ValueError(
                        f"num_learner_devices={n} but only "
                        f"{len(devices)} devices visible")
                self._mesh = Mesh(devices, ("data",))
                self._axis_name = "data"
                fn = shard_map(
                    self._fused_iteration, mesh=self._mesh,
                    in_specs=(P(), P(), P("data"), P()),
                    out_specs=(P(), P(), P("data"), P(),
                               P(None, "data")),
                    check_vma=False)
                self._train_fn = jax.jit(fn)
            else:
                self._train_fn = jax.jit(self._fused_iteration)
        else:
            env_spec, env_cfg = cfg.env, dict(cfg.env_config)
            model_cfg = dict(cfg.model)
            from ray_tpu.rllib.core.rl_module import RLModule
            from ray_tpu.rllib.env.jax_env import make_env

            def env_creator(worker_index, _spec=env_spec, _cfg=env_cfg):
                return make_env(_spec, _cfg)

            def module_creator(env, _mc=model_cfg):
                return RLModule(env.observation_space, env.action_space, _mc)

            self.workers = WorkerSet(
                max(1, cfg.num_rollout_workers), env_creator,
                module_creator, cfg.rollout_fragment_length,
                seed=cfg.seed,
                num_cpus_per_worker=cfg.num_cpus_per_worker,
                connectors=cfg.connector_dict())
            self._update_fn = jax.jit(self._sgd_epochs)

    # -- fully-compiled iteration (JaxEnv path) ---------------------------

    def _fused_iteration(self, params, opt_state, carry, key):
        cfg = self.algo_config
        if self._axis_name:
            # distinct sampling/shuffle streams per shard; params stay
            # replicated because gradients are pmean'd before the update
            key = jax.random.fold_in(
                key, jax.lax.axis_index(self._axis_name))
        k_sample, k_sgd = jax.random.split(key)
        carry, traj, last_value = self.sampler._unroll_impl(
            params, carry, k_sample)
        advs = _gae_scan(traj[sb.REWARDS], traj[sb.VF_PREDS],
                         traj[sb.DONES], last_value, cfg.gamma, cfg.lambda_)
        targets = advs + traj[sb.VF_PREDS]
        flat = {k: v.reshape((-1,) + v.shape[2:])
                for k, v in traj.items()
                if k not in ("episode_return", "episode_len")}
        flat[sb.ADVANTAGES] = advs.reshape(-1)
        flat[sb.VALUE_TARGETS] = targets.reshape(-1)
        params, opt_state, stats = self._sgd_epochs(
            params, opt_state, flat, k_sgd)
        ep = {"episode_return": traj["episode_return"],
              "episode_len": traj["episode_len"]}
        return params, opt_state, carry, stats, ep

    def _sgd_epochs(self, params, opt_state, flat, key):
        """num_sgd_iter epochs of shuffled minibatch SGD as nested scans."""
        cfg = self.algo_config
        n = flat[sb.ADVANTAGES].shape[0]
        mb = min(cfg.sgd_minibatch_size, n)
        num_mb = max(n // mb, 1)
        # advantage standardization (reference: postprocessing.py) —
        # with GLOBAL moments when sharded over the learner mesh
        adv = flat[sb.ADVANTAGES]
        flat = dict(flat)
        if self._axis_name:
            mean = jax.lax.pmean(adv.mean(), self._axis_name)
            var = jax.lax.pmean(jnp.square(adv - mean).mean(),
                                self._axis_name)
            std = jnp.sqrt(var)
        else:
            mean, std = adv.mean(), adv.std()
        flat[sb.ADVANTAGES] = (adv - mean) / (std + 1e-8)

        loss_fn = functools.partial(
            _ppo_loss, self.module,
            clip_param=cfg.clip_param, vf_clip_param=cfg.vf_clip_param,
            vf_loss_coeff=cfg.vf_loss_coeff,
            entropy_coeff=cfg.entropy_coeff)

        def one_minibatch(state, batch):
            params, opt_state = state
            (_, stats), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch), has_aux=True)(params)
            if self._axis_name:
                # DP gradient sync: one pmean over the mesh's data axis
                # (ICI collective on real chips — SURVEY.md §2.3 mapping)
                grads = jax.lax.pmean(grads, self._axis_name)
                stats = jax.lax.pmean(stats, self._axis_name)
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), stats

        def one_epoch(state, epoch_key):
            perm = jax.random.permutation(epoch_key, n)
            shuffled = jax.tree.map(
                lambda v: v[perm][:num_mb * mb].reshape(
                    (num_mb, mb) + v.shape[1:]), flat)
            state, stats = jax.lax.scan(one_minibatch, state, shuffled)
            return state, jax.tree.map(jnp.mean, stats)

        epoch_keys = jax.random.split(key, cfg.num_sgd_iter)
        (params, opt_state), stats = jax.lax.scan(
            one_epoch, (params, opt_state), epoch_keys)
        return params, opt_state, jax.tree.map(jnp.mean, stats)

    # -- training step ----------------------------------------------------

    def training_step(self) -> dict:
        if self.workers is None:
            self.params, self.opt_state, self._carry, stats, ep = \
                self._train_fn(self.params, self.opt_state, self._carry,
                               self.next_key())
            metrics = episode_stats(ep)
        else:
            batches, last_values, stats_list = self.workers.sample_all(
                self.params)
            cfg = self.algo_config
            processed = []
            for batch, last_v in zip(batches, last_values):
                batch.update(compute_gae(
                    batch[sb.REWARDS], batch[sb.VF_PREDS],
                    batch[sb.DONES], last_v, cfg.gamma, cfg.lambda_))
                processed.append(batch)
            train_batch = concat_samples(processed)
            device_batch = {k: jnp.asarray(v)
                            for k, v in train_batch.items()}
            self.params, self.opt_state, stats = self._update_fn(
                self.params, self.opt_state, device_batch, self.next_key())
            metrics = merge_episode_stats(stats_list)
        metrics.update({k: float(np.asarray(v))
                        for k, v in stats.items()})
        metrics["num_env_steps_sampled_this_iter"] = (
            self.algo_config.rollout_fragment_length
            * max(self.algo_config.num_envs_per_worker, 1)
            if self.workers is None else
            self.algo_config.rollout_fragment_length
            * max(self.algo_config.num_rollout_workers, 1))
        return metrics

    def get_state(self) -> dict:
        return {"params": self.params, "opt_state": self.opt_state}

    def set_state(self, state: dict) -> None:
        self.params = state["params"]
        self.opt_state = state["opt_state"]


register_algorithm("PPO", PPO)
