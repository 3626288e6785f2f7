"""The state-space-and-latent-experts family (`models/mamba_moe.py`,
`ops/mamba2.py`, the ungated form of `ops/grouped_experts.py`) against
its plain reference (`benchmarks/refs/mamba_moe.py`) at a tiny size on
the CPU, seeded random weights, float32: the whole-sequence forward,
chunked prefill and decode through the engine (logprobs, not tokens),
what a request of two kinds of block asks of the engine (one state block
and growing pages under one allocator: footprint, counters, preemption,
a slot handed on), padding and idle rows, the control, the ungated
expert kernel in interpret mode and the share test in the latent."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import mamba_moe as ref
from ray_tpu.models import blocks, mamba_moe
from ray_tpu.ops import grouped_experts, mamba2
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: the pattern's first 11 layers keep
# five state layers, five expert layers and the attention layer; experts
# 0-3 of a 16-wide router are held
PATTERN = "MEMEMEM*EMEMEMEM*EME"
TINY = dict(
    hidden_size=64, num_hidden_layers=11, hybrid_override_pattern=PATTERN,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    n_routed_experts=4, published={"n_routed_experts": 16},
    num_experts_per_tok=6, routed_scaling_factor=5, norm_topk_prob=True,
    layer_norm_epsilon=1e-5, max_position_embeddings=128, layers_from=0,
    experts_held_from=0, vocab_size=512, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4,
    draws={"a_range": [1.0, 2.0], "d_skip": 0.1, "conv_bias": 0.1,
           "embed_scale": 1.0, "mamba_out_gain": 5.0,
           "latent_up_gain": 0.35})
WEIGHTS = ("draws", "chunk_size", "time_step_min", "time_step_max",
           "time_step_floor")
# float32 both sides at the highest matmul precision; the chunk form sums
# in another order than the reference's scan. A wrong decay, reset, tail,
# group or mask moves a logit by 1e-1 and up
TOL = 1e-4
BS = 16


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k not in WEIGHTS}
    return mamba_moe.from_published(
        **{**keys, **over}, dtype="float32", mamba_impl=impl,
        attn_impl=impl, sparse_impl=impl)


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
    assert cfg.kinds.count("mamba") == cfg.kinds.count("experts") == 5
    assert cfg.kinds[7] == "attention" and len(cfg.kinds) == 11
    np.testing.assert_allclose(
        np.asarray(mamba_moe.forward(params, toks, cfg)),
        np.asarray(ref.logits(params, toks, TINY)), rtol=0, atol=TOL)


def test_the_program_s_own_weights_have_the_reference_s_tree(params):
    own = mamba_moe.init_params(jax.random.key(1), config())
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)


@pytest.mark.parametrize("n", [5, 16, 20, 37, 70],
                         ids=["under_a_bucket", "one_bucket",
                              "ends_inside_a_bucket", "two_chunks",
                              "three_chunks"])
def test_engine_streams_the_reference_s_logprobs(params, n):
    """A prompt shorter than the small bucket, one that fills it, one
    that ends inside the large one, and prompts of two and of three
    chunks: prefill through the pool, then decode, against the
    reference's full forward pass."""
    eng = make_engine(params)
    p = prompt(n, 10 + n)
    got = stream(eng, eng.submit(p, max_new_tokens=10))
    np.testing.assert_allclose([x for _, x in got],
                               reference_logprobs(params, p, got), atol=TOL)
    eng.check_invariants()


def test_engine_streams_through_the_expert_kernel(params):
    """Five requests on three slots with the ungated expert kernel in
    interpret mode (the tiny widths have no plan for the recurrence's or
    the attention's kernels: `tests/test_mamba2.py` holds those)."""
    eng = make_engine(params, dataclasses.replace(config(),
                                                  sparse_impl="pallas"))
    prompts = [prompt(n, 20 + i) for i, n in enumerate((5, 37, 20, 50, 9))]
    rids = [eng.submit(p, max_new_tokens=8 + i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        np.testing.assert_allclose(
            [x for _, x in got], reference_logprobs(params, p, got),
            atol=TOL)
    eng.check_invariants()


def test_a_slot_handed_on_starts_from_a_reset_state(params):
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
    eng.check_invariants()


@pytest.mark.parametrize("slots", [1, 2], ids=["one_slot", "two_slots"])
def test_a_block_freed_mid_ring_is_taken_by_a_new_sequence(params, slots):
    """Requests that decode past a fold and end part of the way into a
    ring, one after another on the same state blocks: the next sequence's
    first chunk leaves the block's rings empty, so each streams what the
    reference gives for it alone; on two slots the rows' rings fill in
    different steps. `state_folds` counts the rows whose rings went into
    their states: one every `RING` decode tokens of a request."""
    eng = make_engine(params, slots=slots)
    ring = mamba2.RING
    news = (ring + 4, 2 * ring + 3, ring + 2, 5)
    prompts = [prompt(n, 60 + i) for i, n in enumerate((20, 9, 37, 12))]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        np.testing.assert_allclose(
            [x for _, x in got], reference_logprobs(params, p, got),
            atol=TOL)
    s = eng.stats()
    # a request's first token is its prefill's
    assert s["state_folds"] == sum((n - 1) // ring for n in news)
    assert 0 <= s["decode_tokens"] / ring - s["state_folds"] < len(news)
    assert not np.asarray(eng.cache["held"])[0, 0]
    eng.check_invariants()


# -- (b) two kinds of block under one allocator -------------------------------

def test_what_the_engine_holds_for_the_family(params):
    fam = mamba_moe.FAMILY
    assert (fam.state_blocks, fam.paged, fam.state_keys, fam.verify) == \
        (1, True, ("state", "conv", "ring", "held"),
         None)
    eng = make_engine(params)
    # 3 slots x 1 state block + 3 x 96 / 16 pages; a table is the state
    # block and six pages
    assert (eng.max_blocks, eng.cache_blocks) == (7, 3 + 18)
    pool = eng.cache
    assert pool["state"].shape == (5, 4, 4, 16, 16)
    assert pool["conv"].shape == (5, 4, 3, 64 + 2 * 2 * 16)
    # the rings beside the states: an entry a token's d x (64 numbers:
    # a row of lanes), its B (2 x 16: a row) and its running log-decay a
    # head on the lanes (2 rows), in whole sublane tiles; one count a
    # block
    assert pool["ring"].shape == (5, 4, mamba2.RING, 8, 128)
    assert pool["held"].shape == (1, 4) and pool["held"].dtype == jnp.int32
    assert pool["k"].shape == pool["v"].shape == (1, 19, 2, BS, 16)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")
    with pytest.raises(ValueError, match="pattern"):
        config(hybrid_override_pattern="MEM")
    with pytest.raises(ValueError, match="pairs"):
        config(mamba_num_heads=6)


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
    assert s["preemptions"] == 0
    assert s["blocks_in_use"] == s["state_blocks_in_use"] == 0
    assert s["pool_bytes"] == sum(a.nbytes for a in eng.cache.values())
    # counts: the family's, through `counts`; five state layers a token
    tokens = s["prefill_tokens"] + s["decode_tokens"]
    assert s["state_resets"] == 5
    assert s["mamba_tokens_live"] == 5 * tokens
    idle = s["decode_steps"] * 3 - s["decode_tokens"]
    padded = sum(eng._chunk_bucket_for(n % 32) - n % 32 for n in lens
                 if n % 32)
    assert s["mamba_tokens_padded"] == 5 * (idle + padded)
    assert s["attention_rows_read"] > tokens
    assert 0 < s["expert_tokens_here"] < s["expert_tokens_routed"] \
        == 6 * 5 * tokens
    assert s["expert_load_max_over_mean"] >= 1.0
    eng.reset_stats()
    assert eng.stats()["state_resets"] == 0
    eng.check_invariants()


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


def test_padding_and_idle_rows_leave_state_tails_and_pages(params):
    """A chunk of 13 live positions in buckets of 16 and 32: state, tail
    and the page's rows bit for bit the same, and the block's rings left
    empty whatever they held; a decode step whose rows are all idle
    rewrites the trash blocks' tails and pages and nothing else: no
    state, no ring entry, no count of either kind of block."""
    cfg = config()
    table = jnp.asarray([2, 3, 4, 0, 0, 0, 0], jnp.int32)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = mamba_moe.init_pool(cfg, 6, BS, state_blocks=4)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), pool)
        _, pool, counts = mamba_moe.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        assert [int(c) for c in counts[:3]] == [5 * 13, 5 * (bucket - 13), 1]
        assert [int(h) for h in pool["held"][0]] == [1, 1, 0, 1]
        pools.append(pool)
    # bit for bit: the pages, and the four state layers before the
    # attention layer. The fifth reads what the plain attention path
    # made of 16 and of 32 query rows, whose sums the CPU backend orders
    # by the shape (the kernels' pair is `tests/test_mamba2.py`'s)
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pools[0][key]),
                                      np.asarray(pools[1][key]))
    for key in ("state", "conv"):
        np.testing.assert_array_equal(np.asarray(pools[0][key][:4]),
                                      np.asarray(pools[1][key][:4]))
        np.testing.assert_allclose(np.asarray(pools[0][key][4]),
                                   np.asarray(pools[1][key][4]), rtol=0,
                                   atol=2e-6)
    # the chunk wrote its 13 rows and nothing past them
    assert float(jnp.abs(pools[0]["k"][0, 3, :, :13] - 1).min()) > 0
    np.testing.assert_array_equal(np.asarray(pools[0]["k"][0, 3, :, 13:]), 1)
    before = pools[0]
    _, after, counts = mamba_moe.decode(
        params, jnp.zeros((2,), jnp.int32), before,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 7), jnp.int32), cfg)
    assert [int(c) for c in counts[:7]] == [0, 10, 0, 0, 0, 0, 0]
    for key in before:
        np.testing.assert_array_equal(np.asarray(before[key][:, 1:]),
                                      np.asarray(after[key][:, 1:]))
    for key in ("state", "held"):
        np.testing.assert_array_equal(np.asarray(before[key]),
                                      np.asarray(after[key]))
    # what the trash block's ring holds of tokens (not an entry's padding)
    shape = (cfg.mamba_heads, cfg.n_groups, cfg.mamba_head_dim,
             cfg.state_size)
    for was, now in zip(mamba2._unpacked(before["ring"], *shape),
                        mamba2._unpacked(after["ring"], *shape)):
        np.testing.assert_array_equal(np.asarray(was), np.asarray(now))


def test_a_rounded_state_moves_the_logprobs(params):
    """The benchmark's control: the state rounded to bfloat16 at every
    write moves what a request streams by far more than the forms differ."""
    streams = {}
    for r in ("none", "bfloat16"):
        eng = make_engine(params, config(state_round=r))
        streams[r] = stream(eng, eng.submit(prompt(60, 80),
                                            max_new_tokens=20))
    moved = max(abs(a - b) for (_, a), (_, b) in
                zip(streams["none"], streams["bfloat16"]))
    assert moved > 10 * TOL
    with pytest.raises(ValueError, match="unknown state_round"):
        config(state_round="int8")


# -- (c) experts without a gate matrix, in a latent ---------------------------

def expert_inputs(n, d, f, held, k, width, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (n, d)).astype(dtype)
    chosen = jnp.argsort(jax.random.uniform(ks[1], (n, width)), -1)[:, :k]
    weights = jax.random.uniform(ks[2], (n, k), jnp.float32, 0.1, 1.0)
    w_up = (jax.random.normal(ks[3], (held, f, d)) * d ** -0.5).astype(dtype)
    w_down = (jax.random.normal(ks[4], (held, f, d)) * f ** -0.5).astype(
        dtype)
    return x, chosen.astype(jnp.int32), weights, w_up, w_down


@pytest.mark.parametrize("n,f", [(24, 48), (160, 384), (9, 96)],
                         ids=["width_48", "width_384_tile_128", "width_96"])
def test_ungated_experts_match_their_plain_loop(n, f):
    """relu^2 of one projection, no gate matrix, at widths that are no
    multiple of 256, with a decode step's few pairs (16 an expert: row
    tiles of 32) and a chunk's many (128); by hand for one token
    besides."""
    x, chosen, weights, w_up, w_down = expert_inputs(n, 32, f, 4, 7, 16)
    got, load = grouped_experts.experts_grouped(
        x, chosen, weights, None, w_up, w_down, held_from=4, impl="pallas")
    want, want_load = grouped_experts.experts_grouped(
        x, chosen, weights, None, w_up, w_down, held_from=4, impl="jax")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    by_hand = sum(
        float(weights[0, j]) * (jnp.square(jax.nn.relu(
            x[0] @ w_up[int(e) - 4].T)) @ w_down[int(e) - 4])
        for j, e in enumerate(chosen[0]) if 4 <= int(e) < 8)
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(by_hand),
                               rtol=0, atol=1e-4)


def test_the_gated_form_is_unchanged_by_the_ungated_one():
    x, chosen, weights, w_up, w_down = expert_inputs(24, 32, 48, 4, 7, 16)
    w_gate = jnp.flip(w_up, 1)
    got, _ = grouped_experts.experts_grouped(
        x, chosen, weights, w_gate, w_up, w_down, held_from=4,
        impl="pallas")
    by_hand = sum(
        float(weights[0, j]) * ((jax.nn.silu(x[0] @ w_gate[int(e) - 4].T)
                                 * (x[0] @ w_up[int(e) - 4].T))
                                @ w_down[int(e) - 4])
        for j, e in enumerate(chosen[0]) if 4 <= int(e) < 8)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(by_hand),
                               rtol=0, atol=1e-4)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda x: jnp.sum(grouped_experts.experts_grouped(
            x, chosen, weights, None, w_up, w_down, held_from=4,
            impl="pallas")[0]))(x)


@pytest.mark.parametrize("width,want", [(768, 256), (2048, 256), (4096, 256),
                                        (2688, 384), (48, 48), (96, 96)])
def test_width_slices_are_whole_lane_tiles_where_the_width_is(width, want):
    assert grouped_experts._width_slice(width) == (want, width // want)


def test_four_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test in the latent: four chips each hold four
    experts of a 16-wide router; each computes its own experts' part in
    the latent and takes it through W_up (what the program does: W_up is
    linear, so the sum of the four results is W_up of the summed latent
    parts); with the shared expert counted once they add up to what the
    reference gives for the whole layer with all 16 experts."""
    whole = {**TINY, "n_routed_experts": 16}
    whole.pop("published")
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), whole)["layers"][1])
    n = jax.random.normal(jax.random.key(8), (48, 64))
    want = ref.expert_layer(n, lp, whole)
    live = jnp.ones((48,), bool)
    shared = mamba_moe._relu2_mlp(n, lp["ws_up"], lp["ws_down"], jnp.float32)
    total = 0.0
    for share in range(4):
        cfg = config(experts_held_from=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4]
                         for k in ("we_up", "we_down")}}
        out, counts = mamba_moe._experts(n, mine, cfg, live,
                                         grouped_experts.EXPERTS_GROUPED)
        total = total + (out - shared)
        # what one share gives is the reference's share of it
        np.testing.assert_allclose(
            np.asarray(out - shared), np.asarray(ref.routed_latent(
                n, mine, {**TINY, "experts_held_from": 4 * share})
                @ lp["latent_up"]), rtol=0, atol=TOL)
        assert int(counts[1]) == 48 * 6
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=0, atol=TOL)
    # the mixers and attention are every chip's alike: nothing of them is
    # a share (the reference's layer is the routed sum and the shared one)
    chosen, _ = blocks.routing(n, lp, config().experts)
    assert int(jnp.max(chosen)) > 3
