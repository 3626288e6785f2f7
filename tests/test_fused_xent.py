"""Fused chunked cross-entropy (ops/fused_xent.py): parity with the
dense path, odd shapes, vocab-sharded TP composition, and the no-logits
memory claim."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops import fused_xent
from ray_tpu.ops.fused_xent import fused_softmax_xent
from ray_tpu.parallel import MeshSpec, tree_shardings
from ray_tpu.train import spmd


def _dense_nll(x, emb, tgt):
    logits = jnp.einsum("btd,vd->btv", x, emb,
                        preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jax.scipy.special.logsumexp(logits, -1) - picked


def _rand(v, shape=(2, 16, 64), seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(k[0], shape, jnp.float32)
    emb = jax.random.normal(k[1], (v, shape[-1]), jnp.float32) * 0.1
    tgt = jax.random.randint(k[2], shape[:2], 0, v)
    return x, emb, tgt


@pytest.mark.parametrize("v", [512, 517, 130, 96])
def test_fused_matches_dense_any_vocab(v):
    """Value <= 1e-4 and grads <= 1e-3 vs dense, including vocab sizes
    not divisible by (or smaller than) the chunk."""
    x, emb, tgt = _rand(v)
    ref = _dense_nll(x, emb, tgt)
    out = fused_softmax_xent(x, emb, tgt, vocab_chunk=128, impl="scan")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)
    gd = jax.grad(lambda x, e: _dense_nll(x, e, tgt).mean(),
                  argnums=(0, 1))(x, emb)
    gf = jax.grad(
        lambda x, e: fused_softmax_xent(
            x, e, tgt, vocab_chunk=128, impl="scan").mean(),
        argnums=(0, 1))(x, emb)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3)


def test_pallas_kernels_match_dense():
    """The TPU kernels (forward + both backward kernels), via interpret
    mode on CPU."""
    x, emb, tgt = _rand(512)
    ref = _dense_nll(x, emb, tgt)
    out = fused_softmax_xent(x, emb, tgt, vocab_chunk=128, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)
    gd = jax.grad(lambda x, e: _dense_nll(x, e, tgt).mean(),
                  argnums=(0, 1))(x, emb)
    gp = jax.grad(
        lambda x, e: fused_softmax_xent(
            x, e, tgt, vocab_chunk=128, impl="pallas").mean(),
        argnums=(0, 1))(x, emb)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3)


@pytest.mark.parametrize("mesh_kw", [dict(tensor=8),
                                     dict(data=2, tensor=4),
                                     dict(data=2, fsdp=2, tensor=2)])
def test_vocab_sharded_tp_matches_dense(mesh_kw):
    """Vocab-sharded embed: per-shard partial logsumexp psum'd over the
    tensor axis reproduces the unsharded loss AND both grads — dembed's
    batch reduction and dx's vocab reduction each cross different mesh
    axes, so every composition here exercises a distinct collective."""
    mesh = MeshSpec(**mesh_kw).build()
    x, emb, tgt = _rand(512, shape=(4, 16, 64), seed=1)
    ref = _dense_nll(x, emb, tgt)
    gd = jax.grad(lambda x, e: _dense_nll(x, e, tgt).mean(),
                  argnums=(0, 1))(x, emb)
    out = jax.jit(lambda x, e: fused_softmax_xent(
        x, e, tgt, vocab_chunk=128, mesh=mesh))(x, emb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)
    gf = jax.jit(jax.grad(
        lambda x, e: fused_softmax_xent(
            x, e, tgt, vocab_chunk=128, mesh=mesh).mean(),
        argnums=(0, 1)))(x, emb)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3)


@pytest.mark.parametrize("dtype,val_tol,grad_tol", [
    ("float32", 1e-4, 1e-3),
    # bf16 activations: the fused path keeps f32 accumulators while the
    # dense path quantizes grads through the bf16 logits cotangent, so
    # they differ by ~one bf16 ulp (2^-9 relative)
    ("bfloat16", 1e-4, 4e-3),
])
def test_gpt_loss_impl_parity(dtype, val_tol, grad_tol):
    """cfg.loss_impl="fused" reproduces the dense GPT loss and all
    parameter gradients, for f32 and bf16 activation configs."""
    cfg_d = gpt.small(dtype=dtype, attn_impl="xla")
    cfg_f = dataclasses.replace(cfg_d, loss_impl="fused")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg_d)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg_d.vocab_size, (2, 33)),
        jnp.int32)
    ld, gd = jax.value_and_grad(gpt.loss_fn)(
        params, {"tokens": tokens}, cfg_d)
    lf, gf = jax.value_and_grad(gpt.loss_fn)(
        params, {"tokens": tokens}, cfg_f)
    assert abs(float(ld) - float(lf)) < val_tol
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=grad_tol)


def test_gpt_trainer_fused_tp_matches_dense():
    """make_gpt_trainer with loss_impl="fused" on a dp x tensor mesh:
    first-step loss and grad norm match the dense trainer."""
    mesh = MeshSpec(data=2, tensor=4).build()
    cfg_d = gpt.small(dtype="float32", attn_impl="xla")
    cfg_f = dataclasses.replace(cfg_d, loss_impl="fused")
    tok = np.random.default_rng(4).integers(
        0, cfg_d.vocab_size, (4, 33), np.int32)
    out = {}
    for name, cfg in [("dense", cfg_d), ("fused", cfg_f)]:
        state, step_fn, shard_tokens = spmd.make_gpt_trainer(cfg, mesh)
        batch = shard_tokens({"inputs": tok[:, :-1].copy(),
                              "targets": tok[:, 1:].copy()})
        _, metrics = step_fn(state, batch)
        out[name] = (float(metrics["loss"]), float(metrics["grad_norm"]))
    assert abs(out["fused"][0] - out["dense"][0]) < 1e-4
    assert abs(out["fused"][1] - out["dense"][1]) < 1e-3


def test_fused_loss_never_materializes_logits():
    """The memory claim: the fused forward+backward graph contains no
    [B, T, vocab] tensor — peak loss activation is O(B*T*chunk). Checked
    on the lowered HLO of value_and_grad (the dense graph is the
    positive control for the shape probe)."""
    # vocab 768 so the [B, T, V] probe can't collide with the MLP hidden
    # [B, T, d_ff=512]; chunk < vocab, or the single "chunk" IS the
    # logits tensor
    cfg_d = gpt.small(dtype="float32", attn_impl="xla", vocab_size=768)
    cfg_f = dataclasses.replace(cfg_d, loss_impl="fused", loss_chunk=128)
    tokens = jnp.zeros((2, 33), jnp.int32)
    b, t, v = 2, 32, cfg_d.vocab_size
    logits_shape = f"{b}x{t}x{v}"

    def lowered(cfg):
        f = jax.jit(lambda p, b: jax.value_and_grad(gpt.loss_fn)(
            p, b, cfg))
        params = jax.eval_shape(
            lambda: gpt.init_params(jax.random.PRNGKey(0), cfg))
        return f.lower(params, {"tokens": tokens}).as_text()

    assert logits_shape in lowered(cfg_d)        # probe sanity
    assert logits_shape not in lowered(cfg_f)


def test_gpt_trainer_fused_keeps_donation():
    """Buffer donation on the train step survives the fused loss: the
    pre-step param buffer is invalidated and the compiled module aliases
    inputs to outputs."""
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    cfg = gpt.small(dtype="float32", attn_impl="xla", loss_impl="fused")
    state, step_fn, shard_tokens = spmd.make_gpt_trainer(cfg, mesh)
    tok = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (2, 33), np.int32)
    batch = shard_tokens({"inputs": tok[:, :-1].copy(),
                          "targets": tok[:, 1:].copy()})
    assert "input_output_alias" in step_fn.lower(
        state, batch).compile().as_text()
    old_embed = state.params["embed"]
    state, _ = step_fn(state, batch)
    assert old_embed.is_deleted()


def test_loss_impl_validated_at_trace_time():
    cfg = gpt.small(attn_impl="xla", loss_impl="dense_v2")
    params = jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="loss_impl"):
        gpt.loss_fn(params, {"tokens": jnp.zeros((2, 9), jnp.int32)}, cfg)
    with pytest.raises(ValueError, match="loss_impl"):
        spmd.gpt_loss_fn(
            params, {"inputs": jnp.zeros((2, 8), jnp.int32),
                     "targets": jnp.zeros((2, 8), jnp.int32)}, cfg)
    with pytest.raises(ValueError, match="impl"):
        x, emb, tgt = _rand(512)
        fused_softmax_xent(x, emb, tgt, impl="tensorcore")


# ---------------------------------------------------------------------------
# the kernels' step plan (PR 29): a pure function of the shapes, the
# element sizes and the VMEM it may use
# ---------------------------------------------------------------------------

MIB = 1 << 20
V5E_VMEM = 128 * MIB
# (rows, vocab rows, d_model) a chip of the benchmark's two cells, and the
# blocks the isolated sweep ranked first there (PERF.md, section 6, PR 29)
CELLS = {
    "datadecide-300m.pretrain-2k": ((16384, 50304, 1024),
                                    fused_xent._Plan(512, 384, 512, None)),
    "olmo-1b.pretrain-2k-fsdp4": ((8192, 50304, 2048),
                                  fused_xent._Plan(1024, 384, 512, 48 * MIB)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_at_the_cells_shapes(cell):
    """What can silently disengage the mechanism is a plan that quietly
    shrinks: the blocks both cells run are pinned here."""
    (n, v, d), want = CELLS[cell]
    plan = fused_xent._plan(n, v, d, 2, 2, V5E_VMEM)
    assert plan == want
    assert n % plan.block_n == 0 and v % plan.block_v == 0
    assert plan.block_n % plan.sub_n == 0
    assert (plan.vmem_limit or 0) < V5E_VMEM
    # off a TPU the plan is made for the v5e the cells run on
    assert fused_xent._plan(n, v, d) == want


@pytest.mark.parametrize("d", [1024, 2048])
@pytest.mark.parametrize("n", [256, 1536, 8192, 16384])
def test_plan_keeps_the_widest_vocab_block_of_50304(n, d):
    """50304 = 2^7 x 3 x 131: its lane-aligned divisors under 16768 are
    128 and 384, and a 128-row block costs every kernel 1-4 ms a call.
    No row count or width plans it while VMEM allows 384, nor a row
    block under 512 where 512 divides."""
    plan = fused_xent._plan(n, 50304, d, 2, 2, V5E_VMEM)
    assert plan.block_v == 384
    assert n % plan.block_n == 0 and plan.block_n >= min(n, 512)


@pytest.mark.parametrize("d,vmem,block_n,asks", [
    (1024, 128 * MIB, 512, False),      # fits the default scope: no ask
    (1024, 16 * MIB, 512, False),
    (2048, 128 * MIB, 1024, True),      # nothing fits it: half the VMEM
    (2048, 64 * MIB, 512, True),
    (2048, 32 * MIB, 256, True),        # the smallest, whatever it needs
    (4096, 128 * MIB, 512, True),
])
def test_plan_follows_the_vmem_it_may_use(d, vmem, block_n, asks):
    """The largest row block whose working set the compiler's default
    scope holds asks for nothing; where none does, the largest in half
    the chip's VMEM, with a limit computed from the plan."""
    plan = fused_xent._plan(8192, 50304, d, 2, 2, vmem)
    assert (plan.block_n, plan.block_v) == (block_n, 384)
    need = fused_xent._working_set(plan.block_n, plan.block_v, plan.sub_n,
                                   d, 2, 2)
    if asks:
        assert 16 * MIB < need < plan.vmem_limit <= need * 5 // 4 + MIB
    else:
        assert plan.vmem_limit is None and need <= 16 * MIB


@pytest.mark.parametrize("n,v,blocks", [
    (1024, 1536, (1024, 512, 512)),     # both divide
    (264, 512, (88, 512, 88)),          # ragged rows: one tile a block
    (24, 640, (24, 128, 24)),
    (32, 512, (32, 512, 32)),
    (30, 512, None),                    # no sublane-aligned divisor of n
    (32, 517, None),                    # no lane-aligned divisor of v
    (32, 130, None),
    (32, 96, None),                     # vocab under one lane tile
    (16384, 25152, None),               # `tensor=2` of 50304: 2^6 x 3 x 131
])
def test_plan_over_ragged_shapes(n, v, blocks):
    """A divisor exists: a plan that divides. None: `auto` takes the
    scan path, `pallas` says so, exactly as before the plan."""
    plan = fused_xent._plan(n, v, 64, 4, 4, V5E_VMEM)
    x = jax.ShapeDtypeStruct((1, n, 64), jnp.float32)
    emb = jax.ShapeDtypeStruct((v, 64), jnp.float32)
    if blocks is None:
        assert plan is None
        assert fused_xent._resolve_impl("auto", x, emb, 128) == ("scan", 128)
        with pytest.raises(ValueError, match="no pallas block plan"):
            fused_xent._resolve_impl("pallas", x, emb, 128)
    else:
        assert plan[:3] == blocks
        assert plan.vmem_limit is None      # the default scope holds it
        assert fused_xent._resolve_impl("pallas", x, emb, 128) == (
            "pallas", plan)


def _rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# V = 1536 is three vocab blocks of 512; N = 1024 one row block walked
# as two tiles of 512. Rows 0 and 1 aim at the first and the last vocab
# block's edge columns; row 2's logits are small but for column 700,
# whose lane (700 % 128 = 60) holds the row's maximum alone.
@pytest.fixture(scope="module")
def edge_case():
    x, emb, tgt = _rand(1536, shape=(2, 512, 64), seed=2)
    tgt = tgt.at[0, 0].set(0).at[0, 1].set(1535).at[0, 2].set(700)
    emb = emb.at[700].set(x[0, 2] * 0.5)
    assert fused_xent._plan(1024, 1536, 64, 4, 4)[:3] == (1024, 512, 512)
    return x, emb, tgt


@pytest.mark.parametrize("ref_impl", ["scan", "dense"])
@pytest.mark.parametrize("dtype,val_tol,grad_tol", [
    ("float32", 1e-4, 1e-5),
    # bf16 operands: the kernels hand the MXU dlogits in the operand
    # type (one pass, as the f32 ones were truncated to before); scan
    # and dense keep them f32
    ("bfloat16", 1e-4, 1e-2),
])
def test_kernels_match_scan_and_dense(edge_case, dtype, val_tol, grad_tol,
                                      ref_impl):
    """Loss, dX and dE of the three kernels (interpret mode) against the
    scan path and against dense logits, on the same stored operands."""
    x, emb, tgt = edge_case
    x, emb = x.astype(dtype), emb.astype(dtype)

    def ref(x, e):
        if ref_impl == "scan":
            return fused_softmax_xent(x, e, tgt, vocab_chunk=512,
                                      impl="scan")
        return _dense_nll(x, e, tgt)

    def pal(x, e):
        return fused_softmax_xent(x, e, tgt, impl="pallas")

    want, got = ref(x, emb), pal(x, emb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=val_tol)
    # the edge rows themselves, not only the norm
    for row in range(3):
        assert abs(float(got[0, row] - want[0, row])) < val_tol
    assert float(got[0, 2]) < 0.5       # column 700 carries the row
    gw = jax.grad(lambda x, e: ref(x, e).mean(), argnums=(0, 1))(x, emb)
    gg = jax.grad(lambda x, e: pal(x, e).mean(), argnums=(0, 1))(x, emb)
    for a, b in zip(gg, gw):
        assert a.dtype == b.dtype
        assert _rel(a, b) < grad_tol
    # dE's rows 0, 1535 and 700 carry the -1/N of their targets
    for col in (0, 1535, 700):
        assert _rel(gg[1][col], gw[1][col]) < grad_tol


def test_kernels_take_mixed_operand_types(edge_case):
    """bf16 activations against an f32 embedding: the score matmul runs
    in the wider of the two, each gradient comes back in its own type."""
    x, emb, tgt = edge_case
    xb = x.astype(jnp.bfloat16)
    want = _dense_nll(xb, emb, tgt)
    got = fused_softmax_xent(xb, emb, tgt, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    gx, ge = jax.grad(lambda x, e: fused_softmax_xent(
        x, e, tgt, impl="pallas").mean(), argnums=(0, 1))(xb, emb)
    assert gx.dtype == jnp.bfloat16 and ge.dtype == jnp.float32
