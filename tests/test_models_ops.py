"""Model + kernel tests on the CPU mesh."""

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import MeshSpec, reference_attention, tree_shardings


@pytest.fixture(scope="module")
def small_cfg():
    return gpt.small(dtype="float32", attn_impl="xla")


@pytest.fixture(scope="module")
def small_params(small_cfg):
    return gpt.init_params(jax.random.PRNGKey(0), small_cfg)


def test_gpt_forward_shape(small_cfg, small_params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt.forward(small_params, tokens, small_cfg)
    assert logits.shape == (2, 16, small_cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt_loss_decreases_with_training(small_cfg, small_params):
    import optax
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, small_cfg.vocab_size, (4, 32)),
                         jnp.int32)
    opt = optax.adam(1e-3)
    params = small_params
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(gpt.loss_fn)(
            params, {"tokens": tokens}, small_cfg)
        updates, state = opt.update(grads, state)
        return optax.apply_updates(params, updates), state, loss

    first = None
    for i in range(10):
        params, state, loss = step(params, state)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_gpt_attention_impls_agree(small_cfg, small_params):
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, small_cfg.vocab_size, (2, 128)),
        jnp.int32)
    import dataclasses
    logits_xla = gpt.forward(small_params, tokens, small_cfg)
    cfg_flash = dataclasses.replace(small_cfg, attn_impl="flash")
    logits_flash = gpt.forward(small_params, tokens, cfg_flash)
    np.testing.assert_allclose(np.asarray(logits_xla),
                               np.asarray(logits_flash), atol=2e-4,
                               rtol=2e-4)


def test_gpt_sharded_matches_single(small_cfg, small_params):
    """The same params/tokens give the same loss on a dp x tensor mesh."""
    mesh = MeshSpec(data=2, tensor=4).build()
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, small_cfg.vocab_size, (4, 32)),
        jnp.int32)
    base = float(gpt.loss_fn(small_params, {"tokens": tokens}, small_cfg))

    shardings = tree_shardings(mesh, gpt.param_logical_axes(small_cfg))
    sharded_params = jax.device_put(small_params, shardings)
    sharded = float(jax.jit(
        lambda p, b: gpt.loss_fn(p, b, small_cfg))(
            sharded_params, {"tokens": tokens}))
    assert abs(base - sharded) < 1e-4


def test_flash_attention_matches_reference():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 2, 32)),
                           jnp.float32) for _ in range(3))
    for causal in (False, True):
        out = flash_attention(q, k, v, causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_flash_attention_grad():
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 2, 16)),
                           jnp.float32) for _ in range(3))
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, True) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(
        reference_attention(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4,
                               rtol=1e-4)


def test_flash_attention_all_grads():
    """dq, dk, dv all flow through the Pallas backward kernels."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 2, 16)),
                           jnp.float32) for _ in range(3))

    def tot(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    gf = jax.grad(tot(lambda q, k, v: flash_attention(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(tot(lambda q, k, v: reference_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_attention_ragged_seq_stays_on_kernel():
    """T=384 is not a multiple of the default block; the planner shrinks
    blocks to a divisor instead of falling back to XLA, and walks them
    in sub-blocks that divide them."""
    from ray_tpu.ops.flash_attention import _plan_blocks

    def blocks(t):
        plan = _plan_blocks(t, 1024, 1024)
        assert plan.block_q % plan.sub_q == plan.block_kv % plan.sub_kv == 0
        return plan.block_q, plan.block_kv

    assert blocks(384) == (384, 384)
    assert blocks(1536) == (768, 768)
    assert blocks(1280) == (640, 640)
    assert blocks(1152) == (384, 384)
    assert _plan_blocks(8191, 1024, 1024) is None   # prime: XLA fallback

    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 384, 1, 16)),
                           jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# --- the walk over sub-blocks inside the resident blocks -------------------

def default_plan(t):
    """The plan `flash_attention` runs at when its caller names no blocks."""
    from ray_tpu.ops.flash_attention import _plan_blocks

    blocks = inspect.signature(flash_attention).parameters
    return _plan_blocks(t, blocks["block_q"].default,
                        blocks["block_kv"].default)


# (output, gradient) elementwise atol = rtol, and the error's norm as a
# share of the reference's. The float32 pairs are the file's own. A
# bfloat16 result carries its own rounding, an ulp of 2**-5 on a gradient
# of 4 to 8, which the elementwise limit has to let through; the norm
# averages that out (2**-9 / sqrt(3) = 0.0011 for the rounding alone) and
# is what a p or dS rounded lower would move. The largest these kernels
# show over the cases below: 0.0026 and 0.027 elementwise (causal, d64,
# T128, dk), 0.0035 of the norm (dk; out 0.0023, dq 0.0034, dv 0.0017).
WALK_TOL = {"float32": (2e-5, 1e-4, 1e-5), "bfloat16": (6e-3, 4e-2, 6e-3)}


@functools.lru_cache(maxsize=None)
def walk_case(causal, head_dim, t, dtype, bounds=()):
    """(flash, reference) as (out, dq, dk, dv) float32 arrays, B x H = 1:
    the reference runs in float32 on the same rounded operands. `bounds`:
    the caller's (block_q, block_kv), else the defaults."""
    rng = np.random.default_rng(t + head_dim)
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, 1, head_dim)), dtype)
               for _ in range(3))

    def both(attn, cast):
        def tot(q, k, v):
            out = attn(cast(q), cast(k), cast(v))
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        grads, out = jax.grad(tot, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(x, np.float32)[0, :, 0] for x in (out, *grads)]

    return (both(lambda q, k, v: flash_attention(q, k, v, causal, *bounds),
                 lambda x: x),
            both(lambda q, k, v: reference_attention(q, k, v, causal=causal),
                 lambda x: x.astype(jnp.float32)))


WALK_CASES = [(c, d, t, dt) for c in (True, False) for d in (64, 128)
              for t in (128, 384, 1024, 1536, 2048)
              for dt in ("float32", "bfloat16")]


@pytest.mark.parametrize(
    "causal,head_dim,t,dtype", WALK_CASES,
    ids=[f"{'causal' if c else 'full'}-d{d}-T{t}-{dt}"
         for c, d, t, dt in WALK_CASES])
def test_flash_walk_matches_reference(causal, head_dim, t, dtype):
    """Forward and all three gradients, at every rung of the block ladder
    and both head sizes of the benchmark's cells."""
    plan = default_plan(t)
    if t == 2048:       # the walk has more than one step to get wrong
        assert plan.sub_q < plan.block_q and plan.sub_kv < plan.block_kv
    got, want = walk_case(causal, head_dim, t, dtype)
    out_tol, grad_tol, norm_tol = WALK_TOL[dtype]
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want,
                               (out_tol, grad_tol, grad_tol, grad_tol)):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol, err_msg=name)
        assert np.linalg.norm(a - b) < norm_tol * np.linalg.norm(b), name


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("bounds", [(1024, 1024), (1024, 2048), (2048, 1024)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_flash_walk_across_grid_blocks(bounds, causal):
    """T above the caller's bounds (T = 4096 at the defaults): the walk's
    spans count from the resident block's start, and the running
    statistics and accumulators carry over the sequential grid axis."""
    from ray_tpu.ops.flash_attention import _plan_blocks

    plan = _plan_blocks(2048, *bounds)
    assert (plan.block_q, plan.block_kv) == bounds
    assert plan.sub_q < plan.block_q and plan.sub_kv < plan.block_kv
    got, want = walk_case(causal, 64, 2048, "float32", bounds)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_walk_at_the_diagonal_sub_blocks_edges(head_dim):
    """The first and last row of every sub-block the diagonal crosses:
    a trip count one short loses the last row's newest keys, one long or
    a missing mask lets the first row see ahead."""
    t = 2048
    plan = default_plan(t)
    got, want = walk_case(True, head_dim, t, "float32")
    q_edges = [r for a in range(t // plan.sub_q)
               for r in (a * plan.sub_q, (a + 1) * plan.sub_q - 1)]
    kv_edges = [r for b in range(t // plan.sub_kv)
                for r in (b * plan.sub_kv, (b + 1) * plan.sub_kv - 1)]
    for name, a, b, rows in zip(("out", "dq", "dk", "dv"), got, want,
                                (q_edges, q_edges, kv_edges, kv_edges)):
        np.testing.assert_allclose(a[rows], b[rows], atol=1e-4, rtol=1e-4,
                                   err_msg=name)
    # row 0 attends to key 0 alone
    np.testing.assert_allclose(got[0][0], want[0][0], atol=1e-6)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_executed_share_of_the_square(head_dim):
    """A causal pass needs half the square; the plan's tiles on the
    diagonal are computed whole. `executed_share` counts with the kv
    walk's span over one whole-T block; split into grid blocks, and seen
    from the dK/dV side, the walks visit as many tiles."""
    from ray_tpu.ops.flash_attention import (
        _Plan, _crossed, executed_share)

    t = 2048
    plan = default_plan(t)
    share = executed_share(plan, t, True)
    assert 0.5 < share <= 0.625
    assert executed_share(_Plan(1024, 1024, 1024, 1024), t, True) == 0.75
    assert executed_share(plan, t, False) == 1.0

    for plan in (plan, _Plan(1024, 1024, 512, 512), _Plan(512, 1024, 128, 256),
                 _Plan(2048, 1024, 512, 256)):
        tile = plan.sub_q * plan.sub_kv
        n_q, n_kv = (plan.block_q // plan.sub_q, plan.block_kv // plan.sub_kv)
        by_q = sum(int(_crossed(q0, plan.sub_q, plan.sub_kv, kb, n_kv)[1])
                   for q0 in range(0, t, plan.sub_q)
                   for kb in range(t // plan.block_kv))
        by_kv = sum(n_q - int(_crossed(k0, plan.sub_kv, plan.sub_q, qb, n_q)[0])
                    for k0 in range(0, t, plan.sub_kv)
                    for qb in range(t // plan.block_q))
        assert (by_q * tile == by_kv * tile
                == executed_share(plan, t, True) * t * t), plan


def pallas_calls(jaxpr):
    """{kernel name: (operand avals, result avals)} of every `pallas_call`
    in a jaxpr and the jaxprs inside it."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            assert name not in found, f"{name} runs twice"
            found[name] = tuple(
                [v.aval.str_short(short_dtypes=True) for v in vs]
                for vs in (eqn.invars, eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(pallas_calls(sub))
    return found


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_kernels_keep_their_operand_shapes(head_dim):
    """`benchmarks/layer_metrics/flash_roofline.py` finds the kernels'
    seconds by these shapes: one call of each a layer, q, k, v(, dO) as
    bf16[BH,T,D] and then lse, delta as f32[BH,T,128]."""
    qkv = jax.ShapeDtypeStruct((2, 2048, 4, head_dim), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2)))(qkv, qkv, qkv)
    x, row = f"bf16[8,2048,{head_dim}]", "f32[8,2048,128]"
    assert pallas_calls(jaxpr.jaxpr) == {
        "flash_fwd": ([x] * 3, [x, row]),
        "flash_dq": ([x] * 4 + [row] * 2, [x]),
        "flash_dkv": ([x] * 4 + [row] * 2, [x, x]),
    }


# --- what the layer checkpoint saves of the flash forward ------------------

FWD_RUNS_PER_LAYER = {"nothing": 2, "dots": 1, "attn_out": 1}
# 65 tokens -> T=64 in the model: small(): L=2, B*H=16, so the forward's
# stacked lse is f32[2,16,64] compact and f32[2,16,64,128] as the kernel
# writes it; no activation of the model has either shape.
FLASH_TOKENS = np.random.default_rng(11).integers(0, 512, (4, 65))


def flash_cfg(**kw):
    return gpt.small(dtype="float32", attn_impl="flash", **kw)


def flash_grad(cfg, mesh=None):
    batch = {"tokens": jnp.asarray(FLASH_TOKENS, jnp.int32)}
    return jax.grad(lambda p: gpt.loss_fn(p, batch, cfg, mesh))


@pytest.fixture(scope="module")
def flash_params():
    return gpt.init_params(jax.random.PRNGKey(0), flash_cfg())


@pytest.fixture(scope="module")
def grads_saving_nothing(flash_params):
    return jax.jit(flash_grad(flash_cfg(remat_policy="nothing")))(
        flash_params)


@pytest.mark.parametrize("mesh_spec", [None, dict(fsdp=4)],
                         ids=["one-device", "fsdp4"])
@pytest.mark.parametrize("policy", sorted(FWD_RUNS_PER_LAYER))
def test_flash_forward_runs_per_layer(flash_params, policy, mesh_spec):
    """Forward kernels per dQ kernel in the traced backward: "dots" and
    "attn_out" keep the forward's output and lse, so each layer's backward
    goes straight to dQ and dK/dV; "nothing" runs the forward again. The
    policy sees inside the mesh's shard_map."""
    mesh = mesh_spec and MeshSpec(**mesh_spec).build(jax.devices()[:4])
    text = str(jax.make_jaxpr(
        flash_grad(flash_cfg(remat_policy=policy), mesh))(flash_params))
    assert text.count("name=flash_dq") == text.count("name=flash_dkv") == 1
    assert text.count("name=flash_fwd") == FWD_RUNS_PER_LAYER[policy]


@pytest.mark.parametrize("kw", [
    dict(remat_policy="dots"), dict(remat_policy="attn_out"),
    dict(remat=False)], ids=["dots", "attn_out", "no-remat"])
def test_saved_lse_is_one_lane(flash_params, kw):
    """What crosses from the forward scan to the backward scan holds the
    lse as [L, BH, T], never as the kernel's lane-broadcast
    [L, BH, T, 128]."""
    text = str(jax.make_jaxpr(flash_grad(flash_cfg(**kw)))(flash_params))
    assert "f32[2,16,64,128]" not in text
    assert "f32[2,16,64]" in text


@pytest.mark.parametrize("policy", ["dots", "attn_out"])
def test_remat_policies_give_the_same_gradients(
        flash_params, grads_saving_nothing, policy):
    """Same kernels on the same operands, one run fewer."""
    grads = jax.jit(flash_grad(flash_cfg(remat_policy=policy)))(
        flash_params)
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(grads_saving_nothing)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)


def test_flash_fallback_differentiates_under_a_saving_policy():
    """T=130: no block divides it, the forward rule returns no lse and
    names nothing, and the backward is the XLA path's own."""
    from ray_tpu.ops.flash_attention import SAVED_NAMES, _plan_blocks

    assert _plan_blocks(130, 1024, 1024) is None
    rng = np.random.default_rng(13)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 130, 2, 16)),
                           jnp.float32) for _ in range(3))

    def tot(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    saved = jax.checkpoint(
        lambda q, k, v: flash_attention(q, k, v, True),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))
    got = jax.grad(tot(saved), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(tot(lambda q, k, v: reference_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_resnet18_forward_and_grad():
    from ray_tpu.models.resnet import resnet18
    model = resnet18(num_classes=10, dtype="float32")
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)

    def loss(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return jnp.mean(out ** 2)

    g = jax.grad(loss)(variables["params"])
    assert jax.tree.all(jax.tree.map(lambda a: bool(jnp.all(jnp.isfinite(a))), g))
