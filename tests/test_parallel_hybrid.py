"""The parallel-hybrid family (`models/parallel_hybrid.py`: a Mamba-2 state
branch and a grouped-head attention branch under one norm in every layer,
then a gated MLP, with the published multipliers) against its plain
reference (`benchmarks/refs/parallel_hybrid.py`) at a tiny size on the
CPU, seeded random weights, float32: the whole-sequence forward, chunked
prefill and decode through the engine (logprobs, not tokens), what a
request that holds a state block and growing pages in every layer asks
of the engine, padding and idle rows, a slot handed on, the control, and
every multiplier moved from its published value."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import parallel_hybrid as ref
from ray_tpu.models import mamba_moe, parallel_hybrid
from ray_tpu.ops import mamba2
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: a group of 5 query heads a key-value
# head, two state groups, every multiplier as published
MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381, ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845, attention_in_multiplier=1,
    key_multiplier=0.011048543456039804, attention_out_multiplier=0.0375,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    lm_head_multiplier=0.0078125)
TINY = dict(
    hidden_size=64, num_hidden_layers=3, mamba_n_heads=4, mamba_d_head=16,
    mamba_n_groups=2, mamba_d_state=16, mamba_d_conv=4,
    num_attention_heads=10, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, rope_theta=100000000000, rms_norm_eps=1e-5,
    max_position_embeddings=128, vocab_size=512, **MULTIPLIERS,
    draws={"time_step": [0.001, 0.1], "a_range": [1.0, 2.0], "d_skip": 0.1,
           "conv_bias": 0.1, "embed_scale": 1.0, "score_gain": 2.0,
           "mamba_out_gain": 3.0, "attention_out_gain": 2.0,
           "mlp_out_gain": 1.0})
# float32 both sides at the highest matmul precision; the chunk form sums
# in another order than the reference's scan. A wrong decay, reset, tail,
# group, mask, position or multiplier moves a logit by 1e-1 and up
TOL = 1e-4
BS = 16
LAYERS = TINY["num_hidden_layers"]


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k != "draws"}
    return parallel_hybrid.from_published(
        **{**keys, **over}, dtype="float32", mamba_impl=impl, attn_impl=impl)


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


def reference_logprobs(params, p, got, data=TINY):
    seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
    return np.asarray(ref.token_logprobs(
        params, jnp.asarray(seq)[None], data)[0])[len(p) - 1:]


# -- (a) the model against the reference -----------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(50, 1), prompt(50, 2)]))
    want = np.asarray(ref.logits(params, toks, TINY))
    np.testing.assert_allclose(
        np.asarray(parallel_hybrid.forward(params, toks, config())), want,
        rtol=0, atol=TOL)
    # the logits carry weight: the draws undo what the multipliers scale
    assert want.std() > 0.5


def test_the_reference_s_blocked_logprobs_are_its_logits(params):
    """`token_logprobs` makes the logits a block of positions by a block
    of vocabulary rows at a time; the same numbers as the whole rows."""
    toks = jnp.asarray(np.stack([prompt(40, 3), prompt(40, 4)]))
    whole = jax.nn.log_softmax(ref.logits(params, toks, TINY), -1)
    want = jnp.take_along_axis(whole[:, :-1], toks[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(
        np.asarray(ref.token_logprobs(params, toks, TINY)),
        np.asarray(want), rtol=0, atol=1e-5)


def test_the_program_s_own_weights_have_the_reference_s_tree(params):
    own = parallel_hybrid.init_params(jax.random.key(1), config())
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)


@pytest.mark.parametrize("n", [5, 16, 20, 37, 70],
                         ids=["under_a_bucket", "one_bucket",
                              "ends_inside_a_bucket", "two_chunks",
                              "three_chunks"])
def test_engine_streams_the_reference_s_logprobs(params, n):
    """A prompt shorter than the small bucket, one that fills it, one
    that ends inside the large one, and prompts of two and of three
    chunks: prefill through state and pages, then decode through both,
    against the reference's full forward pass."""
    eng = make_engine(params)
    p = prompt(n, 10 + n)
    got = stream(eng, eng.submit(p, max_new_tokens=10))
    np.testing.assert_allclose([x for _, x in got],
                               reference_logprobs(params, p, got), atol=TOL)
    eng.check_invariants()


def test_engine_streams_through_the_attention_kernel(params):
    """Four requests on three slots with `gqa_full_*` in interpret mode:
    a group of 5, padded to a sublane tile in the decode step (the tiny
    widths have no plan for the recurrence's kernels:
    `tests/test_mamba2.py` holds those at 128 x 256)."""
    eng = make_engine(params, dataclasses.replace(config(),
                                                  attn_impl="pallas"))
    prompts = [prompt(n, 20 + i) for i, n in enumerate((5, 37, 20, 9))]
    rids = [eng.submit(p, max_new_tokens=4 + i)
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
    their states, once whatever the layers: one every `RING` decode
    tokens of a request."""
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


# -- (b) a state block and pages in every layer -------------------------------

def test_what_the_engine_holds_for_the_family(params):
    fam = parallel_hybrid.FAMILY
    assert (fam.state_blocks, fam.paged, fam.state_keys, fam.verify,
            fam.load) == (1, True, ("state", "conv", "ring", "held"), None,
                         None)
    eng = make_engine(params)
    # 3 slots x 1 state block + 3 x 96 / 16 pages; a table is the state
    # block and six pages
    assert (eng.max_blocks, eng.cache_blocks) == (7, 3 + 18)
    pool = eng.cache
    # every array has a layer of the model a layer
    assert pool["state"].shape == (LAYERS, 4, 2, 16, 32)
    assert pool["conv"].shape == (LAYERS, 4, 3, 64 + 2 * 2 * 16)
    assert pool["ring"].shape == (LAYERS, 4, mamba2.RING, 8, 128)
    assert pool["held"].shape == (1, 4) and pool["held"].dtype == jnp.int32
    assert pool["k"].shape == pool["v"].shape == (LAYERS, 19, 2, BS, 16)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")
    with pytest.raises(ValueError, match="lane tiles"):
        config(mamba_n_heads=6)
    with pytest.raises(ValueError, match="key-value heads"):
        config(num_attention_heads=9)
    with pytest.raises(ValueError, match="five"):
        config(ssm_multipliers=[1.0, 1.0])


def test_a_head_that_fills_the_lanes_is_stored_alone():
    cfg = config(mamba_n_heads=2, mamba_d_head=128, mamba_d_state=256)
    pool = parallel_hybrid.init_pool(cfg, 3, BS, state_blocks=2)
    assert pool["state"].shape == (LAYERS, 2, 2, 256, 128)
    assert pool["conv"].shape == (LAYERS, 2, 3, 256 + 2 * 2 * 256)
    # an entry: a head a row of d x, a group's B two rows, a head a
    # lane of one row of log-decays
    assert pool["ring"].shape == (LAYERS, 2, mamba2.RING, 8, 128)


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
    # counts: the family's, through `counts`; a state layer a layer
    tokens = s["prefill_tokens"] + s["decode_tokens"]
    assert s["state_resets"] == 5
    assert s["mamba_tokens_live"] == LAYERS * tokens
    idle = s["decode_steps"] * 3 - s["decode_tokens"]
    padded = sum(eng._chunk_bucket_for(n % 32) - n % 32 for n in lens
                 if n % 32)
    assert s["mamba_tokens_padded"] == LAYERS * (idle + padded)
    # every position a token's query reads, its own among them, a layer:
    # a prompt's triangle, then a row more a decoded token
    triangle = sum(n * (n + 1) // 2 for n in lens)
    steps = sum(sum(range(n + 1, n + 4 + i))
                for i, n in enumerate(lens))
    assert s["decode_rows_read_a_layer"] == steps
    assert s["attention_rows_read"] == LAYERS * (triangle + steps)
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
    """A chunk of 13 live positions in buckets of 16 and 32: the first
    layer's state, tail and pages bit for bit the same (a later layer
    reads what the plain attention path made of 16 and of 32 query rows,
    whose sums the CPU backend orders by the shape; the kernels' pair is
    `tests/test_mamba2.py`'s), and the block's rings left empty whatever
    they held; a decode step whose rows are all idle rewrites the trash
    blocks' tails and pages and nothing else: no state, no ring entry, no
    count of either."""
    cfg = config()
    table = jnp.asarray([2, 3, 4, 0, 0, 0, 0], jnp.int32)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = parallel_hybrid.init_pool(cfg, 6, BS, state_blocks=4)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), pool)
        _, pool, counts = parallel_hybrid.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        assert [int(c) for c in counts] == [
            LAYERS * 13, LAYERS * (bucket - 13), 1, LAYERS * 13 * 14 // 2, 0,
            0]
        assert [int(h) for h in pool["held"][0]] == [1, 1, 0, 1]
        pools.append(pool)
    for key in ("state", "conv", "k", "v"):
        np.testing.assert_array_equal(np.asarray(pools[0][key][0]),
                                      np.asarray(pools[1][key][0]))
        np.testing.assert_allclose(np.asarray(pools[0][key]),
                                   np.asarray(pools[1][key]), rtol=0,
                                   atol=5e-6)
    # the chunk wrote its 13 rows, in every layer, and nothing past them
    assert float(jnp.abs(pools[0]["k"][:, 3, :, :13] - 1).min()) > 0
    np.testing.assert_array_equal(np.asarray(pools[0]["k"][:, 3, :, 13:]), 1)
    before = pools[0]
    _, after, counts = parallel_hybrid.decode(
        params, jnp.zeros((2,), jnp.int32), before,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 7), jnp.int32), cfg)
    assert [int(c) for c in counts] == [0, 2 * LAYERS, 0, 0, 0, 0]
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


# -- (c) the multipliers ---------------------------------------------------

def moved(name, index=None):
    """The published value of one multiplier, half again as large."""
    value = MULTIPLIERS[name]
    if index is None:
        return {name: value * 1.5}
    value = list(value)
    value[index] *= 1.5
    return {name: value}


CASES = [("embedding_multiplier", None), ("ssm_in_multiplier", None),
         ("ssm_multipliers", 0), ("ssm_multipliers", 1),
         ("ssm_multipliers", 2), ("ssm_multipliers", 3),
         ("ssm_multipliers", 4), ("ssm_out_multiplier", None),
         ("attention_in_multiplier", None), ("key_multiplier", None),
         ("attention_out_multiplier", None), ("mlp_multipliers", 0),
         ("mlp_multipliers", 1), ("lm_head_multiplier", None)]


@pytest.mark.parametrize("name,index", CASES,
                         ids=[n if i is None else f"{n}_{i}"
                              for n, i in CASES])
def test_no_multiplier_is_dead(params, name, index):
    """Each of the fourteen moved from its published value changes the
    logits, in the program and in the reference alike: none is left out
    and each sits in the same place in both."""
    toks = jnp.asarray(prompt(40, 7))[None]
    over = moved(name, index)
    base = np.asarray(parallel_hybrid.forward(params, toks, config()))
    got = np.asarray(parallel_hybrid.forward(params, toks, config(**over)))
    want = np.asarray(ref.logits(params, toks, {**TINY, **over}))
    assert np.abs(got - base).max() > 100 * TOL
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def test_the_state_branch_is_mamba_moe_s_by_import():
    """`mup` is the one argument the shared functions gained, and without
    it they are what `models/mamba_moe.py` ran before."""
    import inspect
    for fn in (mamba_moe.mamba_whole, mamba_moe.mamba_chunk,
               mamba_moe.mamba_step, mamba_moe._in_proj):
        assert inspect.signature(fn).parameters["mup"].default is None
    source = inspect.getsource(parallel_hybrid)
    for shared in ("mamba_moe.mamba_whole", "mamba_moe.mamba_chunk",
                   "mamba_moe.mamba_step", "mamba_moe.state_arrays"):
        assert shared in source
    # the recurrence through them alone
    assert "mamba2.mamba2" not in source


def test_a_chunk_s_step_columns_are_a_product_of_their_own(params):
    """Where a chunk's rows are whole lane tiles and W_in's width is not
    (H columns of dt after z | xBC), `_in_proj` makes dt in a second
    product: the same numbers as the one product (to the order in which
    a backend sums a row), and `mup` lands on the same columns."""
    cfg = config()
    lp = params["layers"][0]
    assert lp["w_in"].shape[1] % 128
    mup = parallel_hybrid._mup(cfg)
    n = jax.random.normal(jax.random.key(3), (128, cfg.d_model), jnp.float32)
    split = mamba_moe._in_proj(n, lp, cfg, mup)
    # 96 rows are no whole lane tile: the one product
    whole = [jnp.concatenate([a, b]) for a, b in zip(
        mamba_moe._in_proj(n[:96], lp, cfg, mup),
        mamba_moe._in_proj(n[96:], lp, cfg, mup))]
    for got, want in zip(split, whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-5)
    assert [a.shape[1] for a in split] == [cfg.inner, cfg.conv_channels,
                                           cfg.mamba_heads]
