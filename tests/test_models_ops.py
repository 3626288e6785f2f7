"""Model + kernel tests on the CPU mesh."""

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
    """T=384 is not a multiple of the 1024 default block; the planner
    shrinks blocks to a divisor instead of falling back to XLA."""
    from ray_tpu.ops.flash_attention import _plan_blocks

    assert _plan_blocks(384, 1024, 1024) == (384, 384)
    assert _plan_blocks(1536, 1024, 1024) == (768, 768)
    assert _plan_blocks(1280, 1024, 1024) == (640, 640)
    assert _plan_blocks(1152, 1024, 1024) == (384, 384)
    assert _plan_blocks(8191, 1024, 1024) is None   # prime: XLA fallback

    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 384, 1, 16)),
                           jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


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
