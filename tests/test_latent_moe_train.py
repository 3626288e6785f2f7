"""The latent / routed-expert family on the training path
(`models/latent_sparse_moe.py`'s `forward_features`,
`train.spmd.make_latent_moe_trainer`, the backward kernels of
`ops/grouped_experts.py`, `ops/flash_attention.py` with keys and values
of unequal width) against its plain reference
(`benchmarks/refs/latent_moe_train.py`) at a tiny size on the CPU, seeded
random weights, float32: logits, gradients leaf by leaf, the router bias's
own rule, and the two kernels' backward passes in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import latent_moe_train as ref
from ray_tpu.models import blocks
from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.ops import grouped_experts
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import MeshSpec
from ray_tpu.parallel.ring_attention import reference_attention
from ray_tpu.train import loop, spmd

# `deepseek_v3`'s keys at a tiny size: no indexer, no query bottleneck, two
# shared experts, 4 of the router's 16 experts held (experts 4-7), 4 a token
TINY = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
    layers_from=0, intermediate_size=128, moe_intermediate_size=32,
    n_shared_experts=2, published={"n_routed_experts": 16},
    num_experts_per_tok=4, experts_held_from=4, n_routed_experts=4,
    routed_scaling_factor=2.448, norm_topk_prob=True, rope_theta=1e6,
    rms_norm_eps=1e-6, max_position_embeddings=128, vocab_size=512)
TOL = 2e-4      # float32 both sides


def config(**over):
    return lsm.from_published(**TINY, dtype="float32", flash_block_q=128,
                              flash_block_kv=128,
                              **over)


def batch_of(b=2, t=128, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (b, t + 1)).astype(np.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


@pytest.fixture(scope="module")
def params():
    p = lsm.init_params(jax.random.key(0), config())
    # a bias that matters to the choice, as a trained one does
    for i, lp in enumerate(p["layers"]):
        if "router" in lp:
            lp["router_bias"] = 0.05 * jax.random.normal(
                jax.random.key(10 + i), lp["router_bias"].shape)
    return p


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_tree_is_the_reference_s(params):
    """No bottleneck and no indexer: `w_q` in place of three leaves, the
    shared part twice an expert's width, the router its published width,
    the experts held a share of it."""
    dense, sparse = params["layers"][0], params["layers"][1]
    assert "w_q" in dense and "wq_a" not in dense and "wi_q" not in dense
    assert dense["w_q"].shape == (64, 4 * 24)
    assert "w_gate" in dense and "router" not in dense
    assert sparse["router"].shape == (64, 16)
    assert sparse["we_gate"].shape == (4, 32, 64)
    assert sparse["ws_gate"].shape == (64, 64)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))


def test_training_forward_matches_the_reference_s_logits(params):
    cfg = config()
    tokens = batch_of()["inputs"]
    x, counts = jax.jit(
        lambda p, t: lsm.forward_features(p, t, cfg))(params, tokens)
    got = jnp.einsum("btd,vd->btv", x, params["head"])
    want = ref.logits(params, tokens, TINY)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    # and the whole-sequence forward the serving tests use takes the
    # same layers
    np.testing.assert_allclose(lsm.forward(params, tokens, cfg), want,
                               rtol=0, atol=TOL)
    assert counts.shape == (2, 2 + 16)
    assert np.all(np.asarray(counts[:, 1]) == 2 * 128 * 4)


@pytest.mark.parametrize("sparse_impl", ["jax", "pallas"])
def test_gradients_match_the_reference_s_leaf_by_leaf(params, sparse_impl):
    """On the plain path of the routed experts and through their three
    kernels (interpret mode), in the whole model."""
    cfg = config(sparse_impl=sparse_impl)
    batch = batch_of(seed=1)
    loss, got = jax.jit(jax.value_and_grad(
        lambda p: spmd.latent_moe_loss_fn(p, batch, cfg)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, batch["inputs"], batch["targets"], TINY)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    seen = set()
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        name = jax.tree_util.keystr(path)
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(flat[path], w, rtol=0, atol=2e-3 * scale,
                                   err_msg=name)
        seen.add(name.rsplit("'", 2)[-2])
    assert {"router", "wkv_b", "we_gate", "we_down", "ws_up", "w_q",
            "head", "embed"} <= seen
    # the bias enters a choice, not a value: no gradient
    for lp in got["layers"]:
        if "router_bias" in lp:
            assert not np.any(np.asarray(lp["router_bias"]))
            assert np.any(np.asarray(lp["router"]))


def routed_here(params, tokens, cfg):
    """Pairs the reference's router sends to the held experts, and every
    expert's load, for each sparse layer: by hand, from the layers'
    inputs."""
    from benchmarks.refs import latent_sparse_moe as serving_ref
    here, loads = [], []
    eps = TINY["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[1])
    xs = jnp.asarray(params["embed"])[tokens]
    for lp in params["layers"]:
        nxt = []
        for x in xs:
            x = x + ref.attention(
                serving_ref.rms_norm(x, lp["attn_norm_scale"], eps), lp, pos,
                TINY)
            h2 = serving_ref.rms_norm(x, lp["ffn_norm_scale"], eps)
            if "router" in lp:
                chosen, _ = serving_ref.routing(h2, lp, TINY)
                nxt.append((x + serving_ref.feed_forward(h2, lp, TINY),
                            np.bincount(np.asarray(chosen).ravel(),
                                        minlength=16)))
            else:
                nxt.append((x + serving_ref.feed_forward(h2, lp, TINY),
                            None))
        xs = [x for x, _ in nxt]
        if nxt[0][1] is not None:
            load = sum(c for _, c in nxt)
            loads.append(load)
            here.append(int(load[cfg.held_from:
                                 cfg.held_from + cfg.held_count].sum()))
    return here, loads


def test_a_step_is_dropless_and_moves_the_bias_by_its_own_counts(params):
    """The optimizer's state has nothing for `router_bias`; a step leaves
    it to `update_router_bias`, which moves each expert's bias by gamma
    towards the mean load; every pair routed to a held expert is counted
    (and computed: the gradient test above compares with the reference,
    which drops nothing)."""
    cfg = config()
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, shard = spmd.make_latent_moe_trainer(
        cfg, mesh, rng=jax.random.key(0),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    n_params = len(jax.tree.leaves(state.params))
    moments = [a for a in jax.tree.leaves(state.opt_state) if a.ndim]
    assert len(moments) == 2 * (n_params - 2)       # two sparse layers
    assert not any(a.shape == (16,) for a in moments)
    state = spmd.TrainState(
        jax.tree.map(jnp.copy, params), state.opt_state, state.step)
    history = []
    for seed in (3, 4):
        batch = batch_of(seed=seed)
        before = jax.tree.map(np.asarray, state.params)
        here, loads = routed_here(before, batch["inputs"], cfg)
        state, metrics = step_fn(state, shard(batch))
        history.append(jax.tree.map(np.asarray, metrics))
        assert int(metrics["expert_pairs_here"]) == sum(here)
        assert int(metrics["expert_pairs_routed"]) == 2 * 2 * 128 * 4
        held = np.stack([ld[4:8] for ld in loads])
        assert int(metrics["expert_load_max"]) == held.max()
        assert float(metrics["expert_load_mean"]) == pytest.approx(
            held.mean())
        sparse = [(b, a) for b, a in zip(before["layers"],
                                         state.params["layers"])
                  if "router" in b]
        for (b, a), load in zip(sparse, loads):
            want = b["router_bias"] + 0.001 * np.sign(load.mean() - load)
            np.testing.assert_allclose(a["router_bias"], want, atol=1e-7)
            assert np.any(np.asarray(a["router"]) != b["router"])
        assert float(metrics["router_bias_abs_max"]) == pytest.approx(
            max(float(np.abs(a["router_bias"]).max()) for _, a in sparse))
    totals = loop.step_totals(history)
    assert totals["expert_pairs_here"] == sum(
        int(m["expert_pairs_here"]) for m in history)
    assert totals["expert_load_max"] == sum(
        int(m["expert_load_max"]) for m in history)
    assert totals["first_step"]["expert_load_mean"] == pytest.approx(
        float(history[0]["expert_load_mean"]))
    assert totals["last_step"]["router_bias_abs_max"] == pytest.approx(
        float(history[-1]["router_bias_abs_max"]))
    assert "loss" not in totals and "step" not in totals["first_step"]
    # a trainer whose steps carry nothing more: nothing more in `stats()`
    assert loop.step_totals([{"loss": np.float32(1)}]) == {}
    assert loop.step_totals([]) == {}


def test_the_fused_dispatch_carries_the_counters_into_stats():
    cfg = config()
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, _ = spmd.make_latent_moe_trainer(
        cfg, mesh, rng=jax.random.key(1),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    train = loop.TrainLoop(step_fn, unroll=2, metrics_interval=2)
    batches = loop.DevicePrefetcher(
        (batch_of(seed=s) for s in range(4)),
        loop.make_placer(mesh, stacked=True), depth=2, group=2)
    state, metrics = train.run(state, batches)
    stats = train.stats()
    assert len(metrics) == 4 and stats["dispatch_traces"] == 1
    assert stats["expert_pairs_routed"] == 4 * 2 * 2 * 128 * 4
    assert stats["expert_pairs_here"] == sum(
        int(m["expert_pairs_here"]) for m in metrics)
    assert stats["expert_load_max"] >= stats["expert_load_mean"] > 0
    assert stats["first_step"]["router_bias_abs_max"] == pytest.approx(0.001)
    assert stats["last_step"]["router_bias_abs_max"] == pytest.approx(0.004)


def test_a_layer_without_an_indexer_has_a_pool_of_latent_rows_alone(params):
    """Served since the dense latent decode exists
    (`tests/test_linear_latent.py` streams it through the engine): its
    pool holds no index keys."""
    cfg = config()
    assert set(lsm.init_pool(cfg, 8, 16)) == {"latent"}
    with pytest.raises(ValueError, match="do not mix"):
        lsm.LatentSparseMoEConfig(indexer_types=("full", "none", "none"))
    with pytest.raises(ValueError, match="unknown expert_round"):
        config(expert_round="int4")


def test_the_control_s_rounding_moves_the_loss(params):
    batch = batch_of(seed=2)
    sound, rounded = (float(spmd.latent_moe_loss_fn(
        params, batch, config(expert_round=r)))
        for r in ("none", "float8_e4m3fn"))
    assert abs(sound - rounded) > 1e-4


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_control_s_rounding_is_the_float8_grid_by_arithmetic(dtype):
    """`rounded` works on the bits (the TPU compiler drops a cast to
    float8 and back): in the type's normal range it is the cast's value,
    ties to even, and the gradient passes as through a cast."""
    cfg = config(expert_round="float8_e4m3fn")
    a = (3 * jax.random.normal(jax.random.key(5), (4096,))).astype(dtype)
    got = np.asarray(blocks.rounded(a, cfg.expert_round), np.float32)
    want = np.asarray(a.astype(jnp.float8_e4m3fn).astype(dtype), np.float32)
    normal = np.abs(np.asarray(a, np.float32)) >= 2.0 ** -6
    assert normal.sum() > 4000 and np.all(got[normal] == want[normal])
    assert len(np.unique(np.abs(got[normal]))) < 80
    grad = jax.grad(lambda x: jnp.sum(blocks.rounded(x, cfg.expert_round)
                                      .astype(jnp.float32)))(a)
    assert np.all(np.asarray(grad, np.float32) == 1.0)
    assert blocks.rounded(a, config().expert_round) is a


# -- the two kernels' backward passes ---------------------------------------

def test_experts_grouped_backward_kernels_against_the_plain_path():
    """`experts_grouped_dx` and `experts_grouped_dw` in interpret mode:
    one held expert gets no token, one gets most, one token chooses no
    held expert at all; 128-row tiles (300 x 4 pairs)."""
    n, k, d, f, held = 300, 4, 64, 96, 4
    keys = jax.random.split(jax.random.key(1), 6)
    x = jax.random.normal(keys[0], (n, d))
    ws = [jax.random.normal(kk, (held, f, d)) * s
          for kk, s in zip(keys[1:4], (d ** -0.5, d ** -0.5, f ** -0.5))]
    rng = np.random.default_rng(0)
    others = [0, 3, 4, 5, 6, 7]                 # never expert 1
    chosen = np.stack([rng.permutation(others + [2])[:k] for _ in range(n)])
    for i in range(250):                        # expert 2 gets most
        chosen[i] = [2] + list(rng.permutation(others)[:k - 1])
    chosen[-1] = [4, 5, 6, 7]                   # nothing held
    chosen = jnp.asarray(chosen, jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(keys[4], (n, k)))

    def loss(impl):
        def fn(x, weights, wg, wu, wd):
            y, load = grouped_experts.experts_grouped(
                x, chosen, weights, wg, wu, wd, held_from=0, impl=impl,
                name=grouped_experts.EXPERTS_GROUPED_TRAIN)
            return jnp.sum(jnp.sin(y)), load
        return jax.value_and_grad(fn, (0, 1, 2, 3, 4), has_aux=True)

    (got_loss, load), got = loss("pallas")(x, weights, *ws)
    (want_loss, load2), want = loss("jax")(x, weights, *ws)
    assert list(np.asarray(load)) == list(np.asarray(load2))
    assert int(load[1]) == 0 and int(load[2]) > 250
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4)
    assert not np.any(np.asarray(got[2])[1])    # the empty expert: zeros
    assert not np.any(np.asarray(got[0])[-1])   # the token held nowhere


def test_experts_grouped_backward_with_no_token_here():
    x = jnp.ones((256, 64))
    ws = [jnp.ones((2, 32, 64)) * 0.1] * 3
    grads = jax.grad(
        lambda x, *ws: jnp.sum(grouped_experts.experts_grouped(
            x, jnp.full((256, 4), 7), jnp.ones((256, 4)), *ws, held_from=0,
            impl="pallas")[0]), (0, 1, 2, 3))(x, *ws)
    assert all(np.all(np.asarray(g) == 0) for g in grads)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_with_keys_wider_than_values(causal):
    """d_qk 24, d_v 16, two 128-row blocks: forward, dQ, dK and dV
    against the plain attention; the scale is d_qk^-1/2 and the output
    d_v wide."""
    keys = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, d))
               for kk, d in zip(keys, (24, 24, 16)))

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), (0, 1, 2))

    out = flash_attention(q, k, v, causal, 128, 128)
    assert out.shape == (2, 256, 2, 16)
    np.testing.assert_allclose(
        out, reference_attention(q, k, v, causal=causal), rtol=0, atol=1e-5)
    got_loss, got = through(
        lambda q, k, v: flash_attention(q, k, v, causal, 128, 128))(q, k, v)
    want_loss, want = through(
        lambda q, k, v: reference_attention(q, k, v, causal=causal))(q, k, v)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-4)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5)
