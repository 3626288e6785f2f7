"""SPMD train-state/step factory — the jit-compiled training hot path.

The reference's equivalent seam is `prepare_model` wrapping torch modules in
DDP/FSDP (`train/torch/train_loop_utils.py:75-101`) plus NCCL process-group
setup (`train/torch/config.py:113`). TPU-native, the whole thing collapses
into shardings: parameters/optimizer state carry NamedShardings derived from
logical axes, the batch shards over the data-like mesh axes, and jit inserts
every collective (gradient psum, FSDP all-gather/reduce-scatter, TP
collectives) from the sharding lattice. There is no wrapper object.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.models.family import HEAD, OPTIMIZER
from ray_tpu.parallel.sharding import (
    logical_to_spec,
    replicated,
    tree_shardings,
)


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10_000,
                      b1: float = 0.9, b2: float = 0.95,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip — the standard LLM recipe."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def opt_state_shardings(optimizer: optax.GradientTransformation, params,
                        param_shardings, mesh: Mesh):
    """Shardings of `optimizer.init(params)`: moments take their
    parameter's sharding, everything else (step counts) sits replicated
    on the mesh. `params` may be arrays or shapes."""
    return optax.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params), param_shardings,
        transform_non_params=lambda _: replicated(mesh))


def _trainable(tree, frozen: Callable | None):
    """`tree` (parameters, their gradients or their shardings) as the
    optimizer sees it: None, a node without leaves, where `frozen(tree)`
    is true."""
    if frozen is None:
        return tree
    return jax.tree.map(lambda leaf, off: None if off else leaf, tree,
                        frozen(tree))


def create_sharded_state(init_fn: Callable[[jax.Array], Any],
                         param_logical_axes,
                         mesh: Mesh,
                         rng,
                         optimizer: optax.GradientTransformation,
                         rules: dict | None = None,
                         frozen: Callable | None = None
                         ) -> tuple[TrainState, Any]:
    """Initialize params + optimizer state directly into their shardings.

    Params and optimizer state are materialized *sharded* (jit with
    out_shardings), so a model too big for one device's HBM never exists
    unsharded anywhere. `frozen(tree)` -> a tree of bools by the leaves'
    places: leaves that are no part of the optimizer's state
    (`make_train_step`).
    """
    param_shardings = tree_shardings(mesh, param_logical_axes, rules)
    params = jax.jit(init_fn, out_shardings=param_shardings)(rng)
    seen = _trainable(params, frozen)
    # The optimizer state is placed as the train step returns it. Left
    # to propagation it comes back replicated (or, on a one-device mesh,
    # uncommitted and off the mesh), and jax keys a trace on each
    # argument's mesh and an executable on its sharding: the step would
    # compile once for the first call and again for every later one.
    opt_state = jax.jit(optimizer.init, out_shardings=opt_state_shardings(
        optimizer, seen, _trainable(param_shardings, frozen), mesh))(seen)
    step = jax.device_put(jnp.zeros((), jnp.int32), replicated(mesh))
    return TrainState(params, opt_state, step), param_shardings


def make_train_step(loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh,
                    donate: bool = True,
                    accum: int = 1,
                    rules: dict | None = None,
                    jit: bool = True,
                    aux_update: Callable | None = None,
                    frozen: Callable | None = None):
    """Build the jitted (state, batch) -> (state, metrics) step.

    loss_fn(params, batch) -> scalar loss. The batch is a pytree of global
    arrays sharded over the data-like axes; gradient synchronization is
    implicit (jit sees replicated params + sharded batch and inserts the
    reduce). Donation reuses param/opt-state HBM buffers in place.

    accum=k splits the batch's leading axis into k microbatches and
    `lax.scan`s value_and_grad over them, keeping a running f32 mean of
    loss and grads, then applies ONE optimizer update — peak activation
    memory is that of a single microbatch, so effective batch sizes grow
    k-fold beyond what fits in HBM at once. Each microbatch keeps the
    batch sharding over the data-like mesh axes (the leading k axis is
    the scan axis, unsharded). accum=k matches accum=1 on the same batch
    up to summation-order float error (~1e-6 f32); with a padding mask
    the per-microbatch normalization means exact parity only holds when
    mask counts are equal across microbatches.

    With `aux_update`, loss_fn returns (loss, aux) and the step ends with
    `aux_update(params, aux) -> (params, metrics)` on the optimizer's new
    parameters: the part of a model that moves by what the step counted
    and not by a gradient (a router's balancing bias). Its metrics join
    the step's. `frozen(params)` -> a tree of bools, true at the leaves
    the optimizer neither sees nor moves nor keeps state for.
    """
    accum = int(accum)
    if accum < 1:
        raise ValueError(f"accum must be >= 1, got {accum}")
    if aux_update is not None and accum != 1:
        raise ValueError("aux_update wants accum=1: a step's counts are "
                         "not accumulated over microbatches")
    micro_spec = logical_to_spec(("batch",), rules, mesh)

    def split_micro(batch):
        def rs(a):
            if a.shape[0] % accum:
                raise ValueError(
                    f"batch dim {a.shape[0]} not divisible by "
                    f"accum={accum}")
            a = a.reshape(accum, a.shape[0] // accum, *a.shape[1:])
            spec = PartitionSpec(
                None, *(list(micro_spec) + [None] * (a.ndim - 2)))
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec))
        return jax.tree.map(rs, batch)

    def value_and_mean_grad(params, batch):
        if accum == 1:
            return jax.value_and_grad(
                loss_fn, has_aux=aux_update is not None)(params, batch)

        def micro_step(carry, mb):
            i, loss_mean, gmean = carry
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            # running mean in f32 regardless of param/grad dtype: the
            # k-th increment is (x_k - mean)/k, so bf16 grads never
            # accumulate in their own (3-bit-mantissa-per-step) dtype
            with jax.named_scope(OPTIMIZER):
                inv = 1.0 / (i + 1.0)
                loss_mean = loss_mean + (loss.astype(jnp.float32)
                                         - loss_mean) * inv
                gmean = jax.tree.map(
                    lambda m, x: m + (x.astype(jnp.float32) - m) * inv,
                    gmean, g)
            return (i + 1.0, loss_mean, gmean), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (_, loss, gmean), _ = jax.lax.scan(
            micro_step, (jnp.zeros(()), jnp.zeros(()), zeros),
            split_micro(batch))
        with jax.named_scope(OPTIMIZER):
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                 gmean, params)
        return loss, grads

    def step(state: TrainState, batch):
        loss, grads = value_and_mean_grad(state.params, batch)
        more = {}
        if aux_update is not None:
            loss, aux = loss
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = optimizer.update(
                _trainable(grads, frozen), state.opt_state,
                _trainable(state.params, frozen))
            if frozen is None:
                params = optax.apply_updates(state.params, updates)
            else:
                params = jax.tree.map(
                    lambda p, u: (p if u is None
                                  else (p + u).astype(p.dtype)),
                    state.params, updates, is_leaf=lambda x: x is None)
            if aux_update is not None:
                params, more = aux_update(params, aux)
            gnorm = optax.global_norm(grads)
            new_state = TrainState(params, opt_state, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "step": new_state.step, **more}

    if not jit:
        return step
    kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(step, **kwargs)


# ---------------------------------------------------------------------------
# GPT-specific assembly (the flagship train path used by bench / graft entry)
# ---------------------------------------------------------------------------

def softmax_xent(logits, targets):
    """Dense cross entropy: ``gather - logsumexp`` touches the [B, T, V]
    logits twice instead of log_softmax's materialize-then-gather (the
    logits tensor is the biggest array in an LM step — at GPT-2 bench
    shape it is 1.6 GB f32, so every avoided pass is ~2 ms of HBM).
    cfg.loss_impl="fused" (ops/fused_xent.py) goes further and never
    materializes the logits at all — gpt_loss_fn routes between the
    two."""
    logits = logits.astype(jnp.float32)   # no-op for f32; bf16 logits
    #                                       upcast before the logsumexp
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.scipy.special.logsumexp(logits, axis=-1) - tgt


def _mean_nll(nll, mask):
    """The loss of per-token `nll`, over the mask's tokens where given."""
    with jax.named_scope(HEAD):
        if mask is None:
            return jnp.mean(nll)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def gpt_loss_fn(params, batch, cfg, mesh: Mesh | None = None):
    """Cross entropy over pre-shifted inputs/targets [B, T].

    Unlike `models.gpt.loss_fn` (which slices tokens[:, :-1] and breaks
    seq-axis divisibility), inputs/targets are shifted on the host so the
    in-graph T stays divisible by the `seq` mesh axis for ring attention.
    """
    from ray_tpu.models import gpt

    if gpt.check_loss_impl(cfg) == "fused":
        from ray_tpu.ops.fused_xent import fused_softmax_xent
        x = gpt.forward_features(params, batch["inputs"], cfg, mesh)
        with jax.named_scope(HEAD):
            nll = fused_softmax_xent(
                x, params["embed"].astype(cfg.activation_dtype()),
                batch["targets"], vocab_chunk=cfg.loss_chunk, mesh=mesh)
    else:
        logits = gpt.forward(params, batch["inputs"], cfg, mesh)
        with jax.named_scope(HEAD):
            nll = softmax_xent(logits, batch["targets"])
    return _mean_nll(nll, batch.get("mask"))


def make_gpt_trainer(cfg, mesh: Mesh, rng=None,
                     optimizer: optax.GradientTransformation | None = None,
                     rules: dict | None = None, accum: int = 1,
                     init_state: bool = True):
    """One-call assembly: sharded state + jitted step + batch sharding.

    Returns (state, step_fn, batch_sharding_fn). batch_sharding_fn places a
    host batch {"inputs","targets"} [B,T] onto the mesh sharded
    (batch→data/fsdp, length→seq). accum=k makes the step accumulate
    gradients over k microbatches (see make_train_step).

    init_state=False skips parameter/optimizer initialization and returns
    state=None — the elastic-resume path (train/ft.restore_resharded)
    already holds the state and shouldn't pay to materialize one it is
    about to throw away.
    """
    from ray_tpu.models import gpt

    return _make_lm_trainer(
        lambda key: gpt.init_params(key, cfg), gpt.param_logical_axes(cfg),
        partial(gpt_loss_fn, cfg=cfg, mesh=mesh), mesh, rng, optimizer,
        rules, accum=accum, init_state=init_state)


def moe_loss_fn(params, batch, cfg, mesh: Mesh | None = None):
    """MoE counterpart of gpt_loss_fn (pre-shifted inputs/targets, same
    optional padding mask) adding the router load-balance auxiliary loss."""
    from ray_tpu.models import moe

    logits, aux = moe.forward(params, batch["inputs"], cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(
        logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        ce = -jnp.mean(ll)
    return ce + cfg.aux_loss_coeff * aux


def _make_lm_trainer(init_fn, logical_axes, loss_fn, mesh: Mesh, rng,
                     optimizer, rules, accum: int = 1,
                     init_state: bool = True,
                     aux_update: Callable | None = None,
                     frozen: Callable | None = None):
    """Shared assembly behind make_gpt_trainer / make_moe_trainer /
    make_features_trainer."""
    rng = jax.random.key(0) if rng is None else rng
    optimizer = optimizer or default_optimizer()
    state = None
    if init_state:
        state, _ = create_sharded_state(
            init_fn, logical_axes, mesh, rng, optimizer, rules, frozen)
    step_fn = make_train_step(loss_fn, optimizer, mesh, accum=accum,
                              rules=rules, aux_update=aux_update,
                              frozen=frozen)

    tok_spec = logical_to_spec(("batch", "length"), rules, mesh)
    tok_sharding = NamedSharding(mesh, tok_spec)

    def shard_tokens(batch):
        return jax.tree.map(
            lambda a: jax.device_put(a, tok_sharding), batch)

    return state, step_fn, shard_tokens


def make_gpt_pipeline_trainer(cfg, mesh: Mesh, num_microbatches: int = 2,
                              rng=None,
                              optimizer: optax.GradientTransformation | None
                              = None,
                              rules: dict | None = None):
    """GPipe-staged GPT trainer: the layer stack splits into
    mesh["pipe"] contiguous stages, activations stream between neighbor
    stages via ppermute (parallel/pipeline.py), combinable with the data
    axis (each pipe rank streams its own data shard). The reference has no
    pipeline parallelism at all (SURVEY.md §2.4); this is the TPU-native
    member of the same trainer family as make_gpt_trainer."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models import gpt
    from ray_tpu.models.blocks import rms_norm
    from ray_tpu.parallel.pipeline import pipeline_apply

    s_count = max(mesh.shape.get("pipe", 1), 1)
    if cfg.n_layers % s_count:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pipe={s_count}")
    per = cfg.n_layers // s_count

    def loss_fn(params, batch):
        adt = cfg.activation_dtype()
        tokens = batch["inputs"]
        t = tokens.shape[1]
        x = params["embed"].astype(adt)[tokens]
        x = x + params["pos_embed"].astype(adt)[:t][None]
        per_stage = [
            jax.tree.map(lambda p: p[i * per:(i + 1) * per],
                         params["layers"])
            for i in range(s_count)
        ]

        def stage_fn(sp, xm):
            def body(h, lp):
                # mesh=None: attention stays local to the stage shard (no
                # nested seq-axis collectives inside the pipe shard_map)
                return gpt.block(h, lp, cfg, None), None
            out, _ = jax.lax.scan(body, xm, sp)
            return out

        x = pipeline_apply(stage_fn, per_stage, x, mesh=mesh,
                           num_microbatches=num_microbatches,
                           batch_spec=P(None, ("data", "fsdp")))
        x = rms_norm(x, params["final_ln_scale"])
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(adt),
                            preferred_element_type=jnp.float32)
        return jnp.mean(softmax_xent(logits, batch["targets"]))

    return _make_lm_trainer(
        lambda key: gpt.init_params(key, cfg), gpt.param_logical_axes(cfg),
        loss_fn, mesh, rng, optimizer, rules)


def make_moe_trainer(cfg, mesh: Mesh, rng=None,
                     optimizer: optax.GradientTransformation | None = None,
                     rules: dict | None = None, accum: int = 1,
                     init_state: bool = True):
    """MoE assembly: expert weights shard over the mesh's `expert` axis,
    so the dispatch/combine einsums lower to all-to-alls over ICI."""
    from ray_tpu.models import moe

    return _make_lm_trainer(
        lambda key: moe.init_params(key, cfg), moe.param_logical_axes(cfg),
        partial(moe_loss_fn, cfg=cfg, mesh=mesh), mesh, rng, optimizer,
        rules, accum=accum, init_state=init_state)


def features_loss_fn(params, batch, cfg, mesh: Mesh | None = None,
                     with_counts: bool = False):
    """Mean negative log-likelihood of pre-shifted inputs/targets [B, T]
    under the family `cfg.training` (`models.family.TrainingFamily`):
    its features against its output matrix, over the rows of the
    vocabulary held, through `fused_softmax_xent`; no auxiliary loss. With
    `with_counts`, (loss, what the forward counted)."""
    from ray_tpu.ops.fused_xent import fused_softmax_xent

    family = cfg.training
    x, counts = family.forward_features(params, batch["inputs"], cfg, mesh)
    with jax.named_scope(HEAD):
        nll = fused_softmax_xent(
            x, params[family.head].astype(cfg.activation_dtype()),
            batch["targets"], mesh=mesh)
    loss = _mean_nll(nll, batch.get("mask"))
    return (loss, counts) if with_counts else loss


def make_features_trainer(
        cfg, mesh: Mesh, rng=None,
        optimizer: optax.GradientTransformation | None = None,
        rules: dict | None = None, init_state: bool = True):
    """`make_gpt_trainer`'s assembly for the family `cfg.training`: its
    parameters and their axes, `features_loss_fn`, and the two hooks of
    `make_train_step`: `frozen` leaves, no part of the optimizer's state,
    where the family has them, and the `aux_update` that ends the step on
    the forward's counts and adds its metrics to the step's
    (`models.latent_sparse_moe`: the router bias moves by the step's
    expert loads; `models.window_moe_train`: the expert metrics alone)."""
    family = cfg.training
    return _make_lm_trainer(
        lambda key: family.init_params(key, cfg),
        family.param_logical_axes(cfg),
        partial(features_loss_fn, cfg=cfg, mesh=mesh, with_counts=True),
        mesh, rng, optimizer, rules, init_state=init_state,
        aux_update=partial(family.aux_update, cfg=cfg),
        frozen=family.frozen)


# The names `benchmarks/configs/kanana-2-30b-a3b.json` and
# `mellum2-12b-a2.5b.json` call by `program.entry` (ROADMAP D18).
latent_moe_loss_fn = window_moe_loss_fn = features_loss_fn
make_latent_moe_trainer = make_window_moe_trainer = make_features_trainer
