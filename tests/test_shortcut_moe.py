"""The shortcut family (`models/shortcut_moe.py`: two latent-attention
blocks and two dense MLPs a layer, the routed experts on a shortcut beside
them, a softmax router whose last outputs are identity experts) against
its plain reference (`benchmarks/refs/shortcut_moe.py`) at a tiny size on
the CPU, seeded random weights, float32: the whole-sequence forward,
chunked prefill and decode through the engine (logprobs, not tokens),
what padding and idle rows leave of the pool, the share test with the
identity part counted once, a row that chooses identity experts alone,
and where in the layer the experts' result joins the stream."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import shortcut_moe as ref
from ray_tpu.models import blocks, shortcut_moe
from ray_tpu.ops import grouped_experts
from ray_tpu.serve.engine import InferenceEngine

# the published keys at a tiny size: two layers (four attention blocks),
# experts 0-3 of 8 held, a router 12 wide whose last 4 outputs are
# identity experts, 5 a token
TINY = dict(
    attention_bias=False, attention_method="MLA", vocab_size=512,
    hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
    num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=16,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=4, published={"n_routed_experts": 8},
    zero_expert_num=4, zero_expert_type="identity", moe_topk=5,
    max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=1e7,
    layers_from=0, experts_held_from=0,
    draws={"embed_scale": 1.0, "attention_out_gain": 0.25,
           "router_gain": 2.0, "router_bias": 0.02})
NOT_ARGUMENTS = ("draws",)
# float32 both sides at the highest matmul precision; measured 4e-6 on
# logprobs (the absorbed form sums in another order than the expanded
# heads). A wrong scale, mask, norm or place of the shortcut moves a logit
# by 1e-1 and up
TOL = 1e-4
BS = 16


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k not in NOT_ARGUMENTS}
    return shortcut_moe.from_published(
        **{**keys, **over}, dtype="float32", sparse_impl=impl)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        ref.init_params(jax.random.key(0), TINY))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def make_engine(params, cfg=None, **kw):
    kw = {"slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": 32,
          "prefill_buckets": [16, 32], "prefix_cache": False, **kw}
    return InferenceEngine(params, cfg or config(), **kw)


def stream(eng, rid):
    return [(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]


# -- (a) the model against the reference -----------------------------------

def test_the_configuration_is_the_published_one():
    cfg = config()
    assert (cfg.router_width, cfg.identity_from, cfg.held_count,
            cfg.experts_per_token) == (12, 8, 4, 5)
    assert cfg.q_scale == 2.0 and cfg.kv_scale == 2.0 ** 0.5
    assert cfg.experts.score_func == "softmax" \
        and cfg.experts.identity_from == 8 and not cfg.experts.norm_topk
    assert cfg.family.state_blocks == 0 and cfg.family.paged \
        and cfg.family.verify is None and cfg.training is None
    assert shortcut_moe.init_pool(cfg, 7, BS)["latent"].shape == (
        4, 7, BS, 1, cfg.row_words)
    with pytest.raises(ValueError, match="not sharded"):
        shortcut_moe.init_pool(cfg, 7, BS, mesh=object())
    with pytest.raises(ValueError, match="zero-computation"):
        config(zero_expert_type="zero")


def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(50, 1), prompt(50, 2)]))
    np.testing.assert_allclose(
        np.asarray(shortcut_moe.forward(params, toks, config())),
        np.asarray(ref.logits(params, toks, TINY)), rtol=0, atol=TOL)


def test_the_program_s_own_weights_have_the_reference_s_tree(params):
    own = shortcut_moe.init_params(jax.random.key(3), config())
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_engine_streams_the_reference_s_logprobs(params, impl):
    """Prompts of one chunk, several chunks and a padded last chunk, five
    requests on three slots; with `impl="pallas"` the latent and the
    expert kernels in interpret mode."""
    eng = make_engine(params, config(impl))
    prompts = [prompt(n, 10 + i) for i, n in enumerate((5, 37, 32, 70, 9))]
    rids = [eng.submit(p, max_new_tokens=8 + i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        lp = np.asarray(ref.token_logprobs(
            params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]
        np.testing.assert_allclose([x for _, x in got], lp, atol=TOL)
    eng.check_invariants()
    stats = eng.stats()
    # every live row chose 5 outputs, with an expert or without
    rows = stats["decode_rows_live"] + stats["chunk_rows_live"]
    assert rows == sum(len(p) + 8 + i - 1 for i, p in enumerate(prompts))
    assert stats["expert_tokens_routed"] + stats["identity_tokens"] \
        == 5 * 2 * rows
    assert 0 < stats["expert_tokens_here"] < stats["expert_tokens_routed"]
    assert stats["rows_few_experts"] > 0 and stats["decode_traces"] == 1


def test_spec_is_refused(params):
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")


# -- (b) the pool ------------------------------------------------------------

def test_padding_and_idle_rows_leave_the_pages(params):
    """A chunk of 13 live positions in buckets of 16 and 32: every block's
    rows bit for bit the same and nothing written past them; a decode step
    whose rows are all idle rewrites the trash page and nothing else."""
    cfg = config()
    table = jnp.asarray([3, 4, 0, 0, 0, 0], jnp.int32)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype),
                            shortcut_moe.init_pool(cfg, 6, BS))
        logits, pool, counts = shortcut_moe.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        assert [int(c) for c in counts[:5]] == [
            4 * 13 * 14 // 2, 0, 0, 13, bucket - 13]
        assert int(counts[6]) + int(counts[7]) == 2 * 5 * 13
        pools.append((logits, pool["latent"]))
    np.testing.assert_array_equal(np.asarray(pools[0][1]),
                                  np.asarray(pools[1][1]))
    np.testing.assert_array_equal(np.asarray(pools[0][0]),
                                  np.asarray(pools[1][0]))
    latent = np.asarray(pools[0][1])
    assert (latent[:, 3, :13, 0, :16] != 1).any(-1).all()
    assert (latent[:, 3, 13:] == 1).all() \
        and (latent[:, [1, 2, 4, 5]] == 1).all()
    before = {"latent": pools[0][1]}
    _, after, counts = shortcut_moe.decode(
        params, jnp.zeros((2,), jnp.int32), before, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), cfg)
    assert [int(c) for c in counts[:10]] == [0, 0, 2, 0, 0, 0, 0, 0, 0, 0]
    np.testing.assert_array_equal(np.asarray(before["latent"][:, 1:]),
                                  np.asarray(after["latent"][:, 1:]))


def test_rows_on_the_int8_grid_move_the_logprobs(params):
    """The benchmark's control: every cache row on the int8 grid of its
    own largest magnitude moves what a request streams by far more than
    the forms differ."""
    streams = {}
    for r in ("none", "int8"):
        eng = make_engine(params, config(cache_round=r))
        streams[r] = stream(eng, eng.submit(prompt(60, 80),
                                            max_new_tokens=20))
    moved = max(abs(a - b) for (_, a), (_, b) in
                zip(streams["none"], streams["int8"]))
    assert moved > 10 * TOL


def test_load_hands_back_a_served_tree_as_it_is(params):
    """Float32 masters into a bfloat16 program: every leaf in the type the
    steps read, the router and its bias in float32; the reference's draw
    is such a tree and the engine then runs nothing."""
    cfg = dataclasses.replace(config(), dtype="bfloat16")
    served = shortcut_moe.load(params, cfg)
    lp = served["layers"][1]
    assert lp["router"].dtype == lp["router_bias"].dtype == jnp.float32
    assert lp["we_gate"].dtype == lp["attn"][1]["wkv_b"].dtype \
        == lp["mlp"][0]["w_down"].dtype == served["embed"].dtype \
        == jnp.bfloat16
    drawn = ref.init_params(jax.random.key(0), TINY)
    assert jax.tree.map(lambda a: a.dtype, drawn) == \
        jax.tree.map(lambda a: a.dtype, served)
    again = shortcut_moe.load(drawn, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(drawn),
                                      jax.tree.leaves(again)))
    assert make_engine(drawn, cfg).stats()["load_traces"] == 0


# -- (c) the experts on their shortcut ---------------------------------------

def _whole():
    """The uncut layer's configuration: all 8 experts of the router."""
    whole = {**TINY, "n_routed_experts": 8}
    whole.pop("published")
    return whole


@pytest.mark.parametrize("cuts", [(0, 4, 8), (0, 2, 4, 6, 8), (0, 1, 5, 8)],
                         ids=["2x4", "4x2", "ragged"])
def test_shares_add_up_to_the_uncut_layer(cuts):
    """The guide's share test: the chips' routed parts, with the identity
    part (which every chip computes alike for its own rows) counted once,
    add up to what the reference gives for the expert layer with every
    expert; and with the dense path to the reference's whole layer."""
    whole = _whole()
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), whole)["layers"][1])
    n1 = jax.random.normal(jax.random.key(8), (48, 64))
    want = ref.moe(n1, lp, whole)
    total, here = 0.0, 0
    for lo, hi in zip(cuts, cuts[1:]):
        cfg = config(experts_held_from=lo, n_routed_experts=hi - lo)
        mine = {**lp, **{k: lp[k][lo:hi]
                         for k in ("we_gate", "we_up", "we_down")}}
        routed, shared, identity, counts = blocks.expert_layer(
            n1, mine, cfg.experts, jnp.float32, jnp.ones((48,), bool),
            grouped_experts.EXPERTS_GROUPED)
        assert shared is None
        total, here = total + routed, here + int(counts[0])
        # what one share gives is the reference's share of it
        share = {**TINY, "experts_held_from": lo, "n_routed_experts": hi - lo}
        chosen, weights = ref.routing(n1, mine, share)
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(ref.routed_part(
                n1, mine, chosen, weights, share)), rtol=0, atol=TOL)
        np.testing.assert_allclose(
            np.asarray(identity), np.asarray(ref.identity_part(
                n1, chosen, weights, share)), rtol=0, atol=TOL)
    # every pair to an expert reached exactly one share
    assert here == int(counts[1]) and 0 < here < 48 * 5
    np.testing.assert_allclose(np.asarray(total + identity),
                               np.asarray(want), rtol=0, atol=TOL)
    # the whole layer: one chip that holds every expert runs the program's
    # layer, which is the reference's
    cfg = config(n_routed_experts=8, published=None)
    x = jax.random.normal(jax.random.key(9), (24, 64))
    pos = jnp.arange(24)
    causal = pos[None, :] <= pos[:, None]

    def attend(n, ap, latent, block):
        q_nope, q_rope, row = shortcut_moe._project(n, ap, pos, cfg)
        return shortcut_moe.lsm.attend_full(
            q_nope, q_rope, row, causal, ap, cfg).reshape(24, -1), latent

    got = shortcut_moe._layer(x, lp, None, 0, cfg, jnp.ones((24,), bool),
                              grouped_experts.EXPERTS_GROUPED, attend)[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.layer(x, lp, pos, whole)),
                               rtol=0, atol=TOL)


def test_a_row_of_identity_experts_alone_gets_its_input_back():
    """A router whose bias lifts the four identity outputs and one expert
    over every other: a row chooses those five, and where the one expert
    is not held here the layer's shortcut is `6 * sum(p) * n` exactly."""
    cfg = config(experts_held_from=0, n_routed_experts=4)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), TINY)["layers"][0])
    bias = np.zeros(12, np.float32)
    bias[[7, 8, 9, 10, 11]] = 10.0          # expert 7 is another chip's
    lp = {**lp, "router_bias": jnp.asarray(bias)}
    n1 = jax.random.normal(jax.random.key(8), (16, 64))
    routed, _, identity, counts = blocks.expert_layer(
        n1, lp, cfg.experts, jnp.float32, jnp.ones((16,), bool))
    p = jax.nn.softmax(n1 @ lp["router"], -1)
    assert not np.asarray(routed).any() and int(counts[0]) == 0 \
        and int(counts[1]) == 16
    # (the four weights summed in the order they were chosen: float32's
    # last bit)
    np.testing.assert_allclose(
        np.asarray(identity),
        np.asarray(jnp.sum(p[:, 8:] * 6.0, -1, keepdims=True) * n1),
        rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(identity),
                               np.asarray(ref.moe(n1, lp, TINY)), rtol=0,
                               atol=1e-6)
    # a dead row has no identity part either
    _, _, identity, counts = blocks.expert_layer(
        n1, lp, cfg.experts, jnp.float32, jnp.arange(16) < 9)
    assert not np.asarray(identity[9:]).any() and int(counts[1]) == 9


def test_the_experts_join_the_stream_at_the_layer_s_end(params):
    """Where `s` is added decides what the second attention block and the
    second MLP read: a reference that adds it to `h1` (a chain, as every
    other family's layer is) differs from the program by far more than
    the forms differ, and the published order does not."""
    cfg = config()
    toks = jnp.asarray(prompt(40, 3))[None]
    got = np.asarray(shortcut_moe.forward(params, toks, cfg))[0]

    def chained(x, lp, pos, config):
        eps = config["rms_norm_eps"]
        (a0, a1), (m0, m1) = lp["attn"], lp["mlp"]
        x = x + ref.attention(ref.rms_norm(x, a0["attn_norm_scale"], eps),
                              a0, pos, config)
        n1 = ref.rms_norm(x, m0["ffn_norm_scale"], eps)
        x = x + ref.mlp(n1, m0) + ref.moe(n1, lp, config)     # too early
        x = x + ref.attention(ref.rms_norm(x, a1["attn_norm_scale"], eps),
                              a1, pos, config)
        return x + ref.mlp(ref.rms_norm(x, m1["ffn_norm_scale"], eps), m1)

    def logits(layer):
        x = params["embed"][toks[0]]
        for lp in params["layers"]:
            x = layer(x, lp, jnp.arange(40), TINY)
        return np.asarray(ref.rms_norm(x, params["final_ln_scale"], 1e-5)
                          @ params["head"].T)

    np.testing.assert_allclose(got, logits(ref.layer), rtol=0, atol=TOL)
    assert np.abs(got - logits(chained)).max() > 1000 * TOL


def test_the_scopes_name_the_shortcut_and_what_it_stands_beside(params):
    """`shortcut_experts` and `shortcut_dense` are in the decode step's
    and the chunk's programs, each under a part of `family.PARTS`."""
    cfg = config()
    pool = shortcut_moe.init_pool(cfg, 6, BS)
    table = jnp.zeros((6,), jnp.int32)
    step = jax.jit(lambda p, c: shortcut_moe.decode(
        p, jnp.zeros((2,), jnp.int32), c, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), cfg)).lower(params, pool)
    chunk = jax.jit(lambda p, c: shortcut_moe.prefill(
        p, jnp.zeros((1, 16), jnp.int32), c, cfg, block_table=table,
        start=0, length=16)).lower(params, pool)
    for lowered in (step, chunk):
        text = lowered.as_text(debug_info=True)
        for scope in ("ffn/shortcut_experts", "ffn/shortcut_dense",
                      "mixer/shortcut_dense"):
            assert scope in text, scope
