"""The short-convolution-and-routed-experts family
(`models/shortconv_moe.py`, `blocks.expert_layer` without a shared
expert, `ops/decode_attention.py`'s grouped heads) against its plain
reference (`benchmarks/refs/shortconv_moe.py`) at a tiny size on the CPU,
seeded random weights, float32: the whole-sequence forward, chunked
prefill and decode through the engine's pool (logprobs, not tokens),
chunk boundaries inside the convolution's reach, what a request of two
kinds of block asks of the engine, padding and idle rows, a state block
handed on, the load-time function, the control, and the share test with
no shared expert."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import shortconv_moe as ref
from ray_tpu.models import blocks, shortconv_moe
from ray_tpu.ops import grouped_experts
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: both dense layers, then sparse layers
# under convolution and under attention mixers; every expert of a router
# of 8 held
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
TINY = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=8, layer_types=TYPES,
    conv_L_cache=3, conv_bias=False, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=96, num_dense_layers=2,
    moe_intermediate_size=48, num_experts=8, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    norm_eps=1e-5, rope_theta=1000000, max_position_embeddings=128,
    layers_from=0, experts_held_from=0,
    draws={"embed_scale": 0.25, "dense_gain": 8.0, "conv_out_gain": 0.5,
           "expert_down_gain": 4.0, "router_bias": 0.01})
# float32 both sides at the highest matmul precision. A wrong tail, reset,
# rotary, group or mask moves a logit by 1e-1 and up
TOL = 1e-4
BS = 16


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k != "draws"}
    return shortconv_moe.from_published(
        **{**keys, **over}, dtype="float32", attn_impl=impl,
        sparse_impl=impl)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        ref.init_params(jax.random.key(0), TINY))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def make_engine(params, cfg=None, **kw):
    kw = {"slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": 32,
          "prefill_buckets": (16, 32), "prefix_cache": False, **kw}
    return InferenceEngine(params, cfg or config(), **kw)


def stream(eng, rid):
    return [(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]


def reference_logprobs(params, p, got):
    seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
    return np.asarray(ref.token_logprobs(
        params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]


# -- (a) the model against the reference -----------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(50, 1), prompt(50, 2)]))
    cfg = config()
    # both dense layers and every kind of sparse layer are present
    assert cfg.kinds == (
        ("conv", "dense"), ("conv", "dense"), ("attention", "sparse"),
        ("conv", "sparse"), ("conv", "sparse"), ("conv", "sparse"),
        ("attention", "sparse"), ("conv", "sparse"))
    assert ref.layer_kinds(TINY) == [
        ({"conv": "conv", "attention": "full_attention"}[m], f)
        for m, f in cfg.kinds]
    np.testing.assert_allclose(
        np.asarray(shortconv_moe.forward(params, toks, cfg)),
        np.asarray(ref.logits(params, toks, TINY)), rtol=0, atol=TOL)


def test_the_program_s_own_weights_have_the_reference_s_tree(params):
    own = shortconv_moe.init_params(jax.random.key(1), config())
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)
    with pytest.raises(ValueError, match="no bias"):
        config(conv_bias=True)
    with pytest.raises(ValueError, match="name every layer"):
        config(layer_types=TYPES[:5])


@pytest.mark.parametrize("n", [5, 16, 17, 20, 33, 34, 37, 70],
                         ids=["under_a_bucket", "one_bucket",
                              "one_past_a_bucket", "ends_inside_a_bucket",
                              "a_chunk_of_one", "a_chunk_of_two",
                              "two_chunks", "three_chunks"])
def test_engine_streams_the_reference_s_logprobs(params, n):
    """Prompts of one, two and three chunks in both bucket sizes: prefill
    through the pool, then decode, against the reference's full forward
    pass. A last chunk of one position (33) and of two (34) reads one
    and both of its taps' earlier positions from the tail the chunk
    before it kept, and the first decode step reads the last chunk's."""
    eng = make_engine(params)
    p = prompt(n, 10 + n)
    got = stream(eng, eng.submit(p, max_new_tokens=10))
    np.testing.assert_allclose([x for _, x in got],
                               reference_logprobs(params, p, got), atol=TOL)
    eng.check_invariants()


def test_engine_streams_through_the_kernels(params):
    """Five requests on three slots with the expert kernel and the
    grouped-head kernel in interpret mode."""
    eng = make_engine(params, config("pallas"))
    prompts = [prompt(n, 20 + i) for i, n in enumerate((5, 37, 20, 50, 9))]
    rids = [eng.submit(p, max_new_tokens=8 + i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        np.testing.assert_allclose(
            [x for _, x in got], reference_logprobs(params, p, got),
            atol=TOL)
    eng.check_invariants()


def test_a_head_of_64_through_the_pool():
    """Heads of 64 lie two to a row of a page: the family's writes and
    the kernel's reads (interpret mode) agree with the reference."""
    tiny = {**TINY, "hidden_size": 128, "num_attention_heads": 2,
            "num_key_value_heads": 2, "num_hidden_layers": 3,
            "layer_types": TYPES[:3], "intermediate_size": 64,
            "moe_intermediate_size": 32}
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           ref.init_params(jax.random.key(3), tiny))
    keys = {k: v for k, v in tiny.items() if k != "draws"}
    for impl in ("jax", "pallas"):
        cfg = shortconv_moe.from_published(
            **keys, dtype="float32", attn_impl=impl, sparse_impl="jax")
        assert (cfg.head_dim, cfg.kv_pack) == (64, 2)
        eng = make_engine(weights, cfg, slots=2)
        assert eng.cache["k"].shape == (1, eng.cache["k"].shape[1], 1, BS,
                                        128)
        p = prompt(37, 4)
        got = stream(eng, eng.submit(p, max_new_tokens=6))
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        want = np.asarray(ref.token_logprobs(
            weights, jnp.asarray(seq)[None], tiny)[0])[len(p) - 1:]
        np.testing.assert_allclose([x for _, x in got], want, atol=TOL)


def test_a_slot_handed_on_starts_from_reset_tails(params):
    """One slot, three requests one after another: each takes the state
    block and the pages the one before it left, and streams what the
    reference gives for it alone."""
    eng = make_engine(params, slots=1)
    prompts = [prompt(n, 40 + i) for i, n in enumerate((37, 9, 50))]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        np.testing.assert_allclose(
            [x for _, x in got], reference_logprobs(params, p, got),
            atol=TOL)
    s = eng.stats()
    assert s["state_resets"] == 3 and s["state_blocks"] == 1
    assert float(jnp.abs(eng.cache["tail"][:, 1]).max()) > 0
    eng.check_invariants()


# -- (b) two kinds of block under one allocator -------------------------------

def test_what_the_engine_holds_for_the_family(params):
    fam = shortconv_moe.FAMILY
    assert (fam.state_blocks, fam.paged, fam.state_keys, fam.verify) == \
        (1, True, ("tail",), None)
    eng = make_engine(params)
    # 3 slots x 1 state block + 3 x 96 / 16 pages; a table is the state
    # block and six pages
    assert (eng.max_blocks, eng.cache_blocks) == (7, 3 + 18)
    pool = eng.cache
    assert pool["tail"].shape == (6, 4, 2, 64)
    assert pool["k"].shape == pool["v"].shape == (2, 19, 2, BS, 16)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")


def test_a_request_holds_a_state_block_and_its_pages(params):
    eng = make_engine(params)
    lens = [20 + 9 * i for i in range(5)]
    rids = [eng.submit(prompt(n, 30 + i), max_new_tokens=4 + i)
            for i, n in enumerate(lens)]
    it = eng.tokens_for(rids[0])
    next(it)
    s = eng.stats()
    held = sum(eng._blocks_for(lens[i], 4 + i) for i in range(3))
    assert (s["state_blocks"], s["state_blocks_in_use"]) == (3, 3)
    assert s["blocks_in_use"] == held
    assert sorted(sl.table[0] for sl in eng._slots) == [1, 2, 3]
    list(it)
    eng.run_until_idle()
    assert all(len(stream(eng, r)) == 4 + i
               for i, r in enumerate(rids) if i)
    s = eng.stats()
    assert s["decode_traces"] == 1 and s["retraces_unexpected"] == 0
    assert s["preemptions"] == 0 and s["load_traces"] == 0
    assert s["blocks_in_use"] == s["state_blocks_in_use"] == 0
    # counts: the family's, through `counts`; six convolution layers and
    # six sparse layers a token, every expert held
    tokens = s["prefill_tokens"] + s["decode_tokens"]
    assert s["state_resets"] == 5
    assert s["conv_rows_live"] == 6 * tokens
    idle = s["decode_steps"] * 3 - s["decode_tokens"]
    padded = sum(eng._chunk_bucket_for(n % 32) - n % 32 for n in lens
                 if n % 32)
    assert s["conv_rows_padded"] == 6 * (idle + padded)
    assert s["attention_rows_read"] > 2 * tokens
    assert s["expert_tokens_here"] == s["expert_tokens_routed"] \
        == 3 * 6 * tokens
    assert s["expert_load_max_over_mean"] >= 1.0
    eng.reset_stats()
    assert eng.stats()["state_resets"] == 0
    eng.check_invariants()


def test_row_tiles_an_expert_reached_come_through_stats(params):
    """No group of the tiny programs passes its tile (a step's 9 pairs and
    a chunk's 48 or 96 over 8 experts: tiles of 16, 16 and 32, and a token
    gives an expert one pair), so every expert a call reached was one
    tile, one read of its matrices."""
    eng = make_engine(params)
    assert eng.stats()["row_tiles_per_expert_reached"] == 0.0
    rids = [eng.submit(prompt(n, 60 + n), max_new_tokens=5)
            for n in (20, 37, 9, 50)]
    for rid in rids:
        stream(eng, rid)
    s = eng.stats()
    calls = 6 * (s["decode_steps"] + s["prefill_chunks"])
    assert 0 < s["experts_reached"] <= 8 * calls
    assert s["expert_row_tiles"] == s["experts_reached"]
    assert s["row_tiles_per_expert_reached"] == 1.0


def test_a_group_past_its_tile_counts_a_second_row_tile(params):
    """A router that sends every row to expert 0: a step of 20 rows x 3
    over 8 experts lays out in tiles of 16, so expert 0's 20 pairs are
    two tiles in each of the six sparse layers and every other reached
    expert's are one."""
    cfg = config()
    b = 20
    assert grouped_experts.row_tile(b * 3, cfg.held_count) == 16
    skewed = dict(params, layers=[
        dict(lp, router_bias=lp["router_bias"].at[0].add(100.0))
        if "router_bias" in lp else lp for lp in params["layers"]])
    pool = shortconv_moe.init_pool(cfg, 1 + b, BS, state_blocks=1 + b)
    tables = jnp.stack([jnp.arange(1, b + 1)] * 2, axis=1)
    counts = np.asarray(shortconv_moe.decode(
        skewed, jnp.asarray(prompt(b, 7)), pool,
        jnp.zeros((b,), jnp.int32), tables, cfg)[2])
    s = shortconv_moe.FAMILY.counts(cfg, counts)
    assert s["expert_tokens_here"] == 6 * b * 3
    assert s["expert_row_tiles"] == s["experts_reached"] + 6
    assert 1.0 < s["row_tiles_per_expert_reached"] <= 1.0 + 6 / (6 * 3)


@pytest.mark.parametrize("at", [2, 5])
def test_preempt_and_resume(params, at):
    """Both kinds of block go back, the resume re-prefills prompt and
    emitted tokens from the first token into a state block it resets and
    pages it rewrites, and the stream is what an unpreempted one is."""
    base_eng = make_engine(params)
    base = stream(base_eng, base_eng.submit(prompt(40, 50),
                                            max_new_tokens=8))
    faults.install(faults.FaultPlan(seed=3).fail("engine.preempt", at=at,
                                                 times=1))
    eng = make_engine(params)
    rid = eng.submit(prompt(40, 50), max_new_tokens=8)
    eng.run_until_idle()
    s = eng.stats()
    assert s["preemptions"] == 1 and s["state_resets"] == 2
    assert s["blocks_in_use"] == 0
    got = stream(eng, rid)
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([x for _, x in got], [x for _, x in base],
                               rtol=0, atol=1e-4)
    eng.check_invariants()


def test_padding_and_idle_rows_leave_tails_and_pages(params):
    """A chunk of 13 live positions in buckets of 16 and 32 over a pool
    of ones: the tails are the last two live positions' and the page's
    rows the 13 written, the same to the last place in both buckets (a
    position's values do not depend on the rows beside it here but
    through the attention's sums, which the CPU backend orders by the
    shape); a first chunk reads the block's stale tails as zeros; a decode
    step whose rows are all idle rewrites the trash blocks and nothing
    else."""
    cfg = config()
    table = jnp.asarray([2, 3, 4, 0, 0, 0, 0], jnp.int32)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = shortconv_moe.init_pool(cfg, 6, BS, state_blocks=4)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), pool)
        _, pool, counts = shortconv_moe.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        assert [int(c) for c in counts[:3]] == [6 * 13, 6 * (bucket - 13), 1]
        pools.append(pool)
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pools[0][key][0]),
                                      np.asarray(pools[1][key][0]))
        np.testing.assert_allclose(np.asarray(pools[0][key][1]),
                                   np.asarray(pools[1][key][1]), rtol=0,
                                   atol=2e-6)
    # the two convolution layers before the first attention layer: bit
    # for bit; the rest read what the attention's sums made
    np.testing.assert_array_equal(np.asarray(pools[0]["tail"][:2]),
                                  np.asarray(pools[1]["tail"][:2]))
    np.testing.assert_allclose(np.asarray(pools[0]["tail"]),
                               np.asarray(pools[1]["tail"]), rtol=0,
                               atol=1e-5)
    # only block 2's tails were written, and not with the ones it held
    tail = np.asarray(pools[0]["tail"])
    assert (tail[:, [0, 1, 3]] == 1).all() and (tail[:, 2] != 1).all()
    # the chunk wrote its 13 rows and nothing past them
    assert float(jnp.abs(pools[0]["k"][0, 3, :, :13] - 1).min()) > 0
    np.testing.assert_array_equal(np.asarray(pools[0]["k"][0, 3, :, 13:]), 1)
    # the tails are g of positions 11 and 12: what the whole-sequence
    # form makes of them in the first layer
    lp = params["layers"][0]
    x = params["embed"][prompt(13, 5)]
    g, _ = shortconv_moe._gated(
        blocks.rms_norm(x, lp["operator_norm_scale"], cfg.eps), lp, cfg)
    np.testing.assert_allclose(tail[0, 2], np.asarray(g[11:13]), rtol=0,
                               atol=1e-6)
    before = pools[0]
    _, after, counts = shortconv_moe.decode(
        params, jnp.zeros((2,), jnp.int32), before,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 7), jnp.int32), cfg)
    assert [int(c) for c in counts[:6]] == [0, 12, 0, 0, 0, 0]
    for key in before:
        np.testing.assert_array_equal(np.asarray(before[key][:, 1:]),
                                      np.asarray(after[key][:, 1:]))


@pytest.mark.parametrize("start,length,impl", [
    (0, 16, "jax"), (0, 13, "jax"), (16, 16, "jax"), (16, 13, "jax"),
    (16, 13, "pallas")],
    ids=["first-full", "first-short", "later-full", "later-short",
         "later-short-kernels"])
def test_tick_is_prefill_then_decode_in_one_program(params, start, length,
                                                    impl):
    """`tick` against `prefill`, then `decode`, on the same pool and
    inputs: a chunk of 16 that starts its sequence or follows its first
    chunk, whole or 13 live positions long, beside a step of four rows of
    which two decode (after prompts of 9 and 21) and two are idle, over
    dense and sparse layers under both mixers. Both logits and every
    array of the pool agree; the counts are the two programs' summed,
    but the row tiles and the experts reached, which are the one call's
    a layer that `tick` makes where the two programs make two."""
    cfg = config(impl)
    pool = shortconv_moe.init_pool(cfg, 12, BS, state_blocks=5)
    # state block -> (the sequence's tokens, its pages, what this tick
    # finds of it in the pool)
    seqs = {1: (prompt(9, 1), [2], 9), 2: (prompt(21, 2), [3, 4], 21),
            3: (prompt(32, 3), [5, 6, 7], start)}
    tables = {b: jnp.asarray([b] + pages + [0] * (6 - len(pages)), jnp.int32)
              for b, (_, pages, _) in seqs.items()}

    def chunk_of(tokens):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :len(tokens)] = tokens
        return jnp.asarray(padded)

    for b, (tokens, _, fed) in seqs.items():
        for at in range(0, fed, 16):
            n = min(16, fed - at)
            _, pool, _ = shortconv_moe.prefill(
                params, chunk_of(tokens[at:at + n]), pool, cfg,
                block_table=tables[b], start=at, length=n)
    chunk = chunk_of(seqs[3][0][start:start + length])
    step_tokens = jnp.asarray([7, 0, 11, 0], jnp.int32)
    pos = jnp.asarray([9, 0, 21, 0], jnp.int32)
    rows = jnp.stack([tables[1], jnp.zeros(7, jnp.int32), tables[2],
                      jnp.zeros(7, jnp.int32)])
    want_chunk, after, chunk_counts = shortconv_moe.prefill(
        params, chunk, pool, cfg, block_table=tables[3], start=start,
        length=length)
    want_step, after, step_counts = shortconv_moe.decode(
        params, step_tokens, after, pos, rows, cfg)
    got_chunk, got_step, got, counts = shortconv_moe.tick(
        params, chunk, step_tokens, pool, pos, rows, cfg,
        block_table=tables[3], start=start, length=length)
    np.testing.assert_allclose(np.asarray(got_chunk), np.asarray(want_chunk),
                               rtol=0, atol=TOL)
    np.testing.assert_allclose(np.asarray(got_step), np.asarray(want_step),
                               rtol=0, atol=TOL)
    assert set(got) == set(after)
    for key in after:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(after[key]), rtol=0, atol=TOL,
                                   err_msg=key)
    counts, both = np.asarray(counts), np.asarray(chunk_counts + step_counts)
    tiles, reached = (shortconv_moe.COUNTS.index(n) for n in (
        "expert_row_tiles", "experts_reached"))
    rows_counted = [i for i in range(len(both)) if i not in (tiles, reached)]
    np.testing.assert_array_equal(counts[rows_counted], both[rows_counted])
    assert max(chunk_counts[reached], step_counts[reached]) \
        <= counts[reached] <= both[reached]
    assert counts[reached] <= counts[tiles] <= both[tiles]


def test_load_hands_back_a_served_tree_as_it_is(params):
    """Float32 masters into a bfloat16 program: every leaf in the type
    the steps read, the router, its bias and the taps in float32; a tree
    already there comes back leaf for leaf (the engine then runs
    nothing)."""
    cfg = dataclasses.replace(config(), dtype="bfloat16")
    served = shortconv_moe.load(params, cfg)
    sparse = served["layers"][2]
    assert sparse["router"].dtype == sparse["router_bias"].dtype \
        == served["layers"][0]["conv_w"].dtype == jnp.float32
    assert sparse["we_gate"].dtype == served["embed"].dtype == jnp.bfloat16
    again = shortconv_moe.load(served, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))
    # the reference's draw is such a tree
    drawn = ref.init_params(jax.random.key(0), TINY)
    assert jax.tree.map(lambda a: a.dtype, drawn) == \
        jax.tree.map(lambda a: a.dtype, served)
    eng = make_engine(drawn, cfg)
    assert eng.stats()["load_traces"] == 0


def test_experts_on_the_float8_grid_move_the_logprobs(params):
    """The benchmark's control: the routed experts' inputs and matrices
    on the float8_e4m3fn grid move what a request streams by far more
    than the forms differ."""
    streams = {}
    for r in ("none", "float8_e4m3fn"):
        eng = make_engine(params, config(expert_round=r))
        streams[r] = stream(eng, eng.submit(prompt(60, 80),
                                            max_new_tokens=20))
    moved = max(abs(a - b) for (_, a), (_, b) in
                zip(streams["none"], streams["float8_e4m3fn"]))
    assert moved > 10 * TOL
    with pytest.raises(ValueError, match="unknown expert_round"):
        config(expert_round="int8")


# -- (c) routed experts with no shared one -------------------------------------

def test_the_layer_without_a_shared_expert_costs_nothing_for_it(params):
    """`blocks.expert_layer` over parameters with no `ws_gate`: nothing
    shared, and the routing's 1e-6 beside the sum; a layer that has a
    shared expert still gets it, and an `Experts` without the field
    divides by the sum alone."""
    lp = params["layers"][2]
    f = jax.random.normal(jax.random.key(5), (24, 64))
    cfg = config()
    routed, shared, _, counts = blocks.expert_layer(
        f, lp, cfg.experts, jnp.float32, None)
    assert shared is None and int(counts[0]) == int(counts[1]) == 24 * 3
    np.testing.assert_allclose(np.asarray(routed),
                               np.asarray(ref.expert_layer(f, lp, TINY)),
                               rtol=0, atol=TOL)
    _, weights = blocks.routing(f, lp, cfg.experts)
    _, plain = blocks.routing(f, lp, cfg.experts._replace(norm_eps=0.0))
    assert blocks.Experts(8, 3, True, 0).norm_eps == 0.0
    np.testing.assert_allclose(np.asarray(jnp.sum(plain, -1)), 1.0,
                               atol=1e-6)
    total = np.asarray(jnp.sum(weights, -1))
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()
    with_shared = {**lp, "ws_gate": lp["we_gate"][0].T,
                   "ws_up": lp["we_up"][0].T, "ws_down": lp["we_down"][0]}
    assert blocks.expert_layer(f, with_shared, cfg.experts, jnp.float32,
                               None)[1].shape == (24, 64)


@pytest.mark.parametrize("cuts", [(0, 8, 16, 24, 32), (0, 5, 16, 29, 32)],
                         ids=["four_shares_of_8", "a_ragged_cut"])
def test_shares_add_up_to_the_uncut_layer(cuts):
    """The guide's share test with no shared expert: chips that each hold
    a run of the 32 experts, the router whole on every one; each computes
    its own experts' part, and the parts add up to what the reference
    gives for the whole layer with all 32."""
    whole = {**TINY, "num_experts": 32, "num_experts_per_tok": 4}
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), whole)["layers"][2])
    f = jax.random.normal(jax.random.key(8), (48, 64))
    want = ref.expert_layer(f, lp, whole)
    live = jnp.ones((48,), bool)
    total, routed_here = 0.0, 0
    for lo, hi in zip(cuts, cuts[1:]):
        cfg = config(num_experts=hi - lo, num_experts_per_tok=4,
                     published={"num_experts": 32}, experts_held_from=lo)
        assert (cfg.router_width, cfg.held_count) == (32, hi - lo)
        mine = {**lp, **{k: lp[k][lo:hi]
                         for k in ("we_gate", "we_up", "we_down")}}
        routed, shared, _, counts = blocks.expert_layer(
            f, mine, cfg.experts, jnp.float32, live,
            grouped_experts.EXPERTS_GROUPED)
        assert shared is None and int(counts[1]) == 48 * 4
        routed_here += int(counts[0])
        # what one share gives is the reference's share of it
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(ref.expert_layer(
                f, mine, {**whole, "num_experts": hi - lo,
                          "published": {"num_experts": 32},
                          "experts_held_from": lo})), rtol=0, atol=TOL)
        total = total + routed
    assert routed_here == 48 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=TOL)
