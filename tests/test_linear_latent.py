"""The KDA-and-latent family (`models/linear_latent.py`, `ops/kda.py`,
`ops/sparse_latent.latent_decode`) against its plain reference
(`benchmarks/refs/linear_latent.py`) at a tiny size on the CPU, seeded
random weights, float32: the whole-sequence forward, chunked prefill and
decode through the engine (logprobs, not tokens), what a request of two
kinds of block asks of the engine (one state block and growing pages
under one allocator: footprint, counters, preemption, cancel, hand-off),
the dense latent decode kernel in interpret mode, group-limited routing
and the share test with groups, and the latent family's layer without an
indexer (kanana-2's shape) served by the same code."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import linear_latent as ref
from ray_tpu.models import blocks, gpt, latent_sparse_moe as lsm, \
    linear_latent, retention
from ray_tpu.ops import kda, sparse_latent
from ray_tpu.serve.engine import BlockAllocator, InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: layers 1-7 of a period of six keep a
# dense layer, five KDA layers around one latent layer; group 0 of four
# groups of a 16-wide router is held
TINY = dict(
    hidden_size=64, num_hidden_layers=7, num_attention_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_experts=4,
    published={"num_experts": 16}, num_experts_per_tok=4, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=6e6, max_position_embeddings=128,
    first_k_dense_replace=1, layer_group_size=6, short_conv_kernel_size=4,
    kda_lower_bound=-5, layers_from=1, experts_held_from=0, vocab_size=512,
    gate_bias=[-4.0, -9.5], gate_log_scale=[-0.2, 0.2], embed_scale=1.0,
    kda_out_gain=3.74)
WEIGHTS = ("gate_bias", "gate_log_scale", "embed_scale", "kda_out_gain",
           "moe_shared_expert_intermediate_size")
# float32 both sides at the highest matmul precision; measured 3e-6 on
# logprobs (the chunk form solves where the reference scans). A wrong
# mask, decay, reset, tail or group moves a logit by 1e-1 and up
TOL = 1e-4
BS = 16


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k not in WEIGHTS}
    return linear_latent.from_published(
        **{**keys, **over}, dtype="float32", kda_impl=impl,
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
    kw = {"slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": 16,
          "prefix_cache": False, **kw}
    return InferenceEngine(params, cfg or config(), **kw)


def stream(eng, rid):
    return [(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]


def same_stream(got, base):
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in base], rtol=0, atol=1e-4)


# -- (a) the model against the reference -----------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(50, 1), prompt(50, 2)]))
    assert config().mixers == ("kda", "kda", "kda", "kda", "latent", "kda",
                               "kda")
    np.testing.assert_allclose(
        np.asarray(linear_latent.forward(params, toks, config())),
        np.asarray(ref.logits(params, toks, TINY)), rtol=0, atol=TOL)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_engine_streams_the_reference_s_logprobs(params, impl):
    """Prompts of one chunk, several chunks and a padded last chunk, five
    requests on three slots; with `impl="pallas"` the latent kernels in
    interpret mode (head_dim 16 has no KDA plan: that pair is
    `tests/test_kda.py`'s)."""
    eng = make_engine(params, config(impl) if impl == "jax" else
                      dataclasses.replace(config(), sparse_impl="pallas"))
    prompts = [prompt(n, 10 + i) for i, n in enumerate((5, 37, 20, 50, 9))]
    rids = [eng.submit(p, max_new_tokens=10 + i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        lp = np.asarray(ref.token_logprobs(
            params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]
        np.testing.assert_allclose([x for _, x in got], lp, atol=TOL)
    eng.check_invariants()


# -- (b) two kinds of block under one allocator -------------------------------

def test_one_footprint_arithmetic_for_every_family(params):
    """(state blocks, paged) is (0, yes) for the dense and the latent
    family, (1, no) for retention, (1, yes) here; a request's blocks are
    its state blocks and the pages of its tokens."""
    fams = {"gpt": gpt.GPTConfig().family, "latent": lsm.FAMILY,
            "retention": retention.FAMILY, "hybrid": linear_latent.FAMILY}
    assert {n: (f.state_blocks, f.paged, f.state_keys)
            for n, f in fams.items()} == {
        "gpt": (0, True, ()), "latent": (0, True, ()),
        "retention": (1, False, ("s", "z", "ring", "held")),
        "hybrid": (1, True, ("state", "conv", "ring", "held"))}
    eng = make_engine(params)
    # 3 slots x 1 state block + 3 x 96 / 16 pages; a table is the state
    # block and six pages
    assert (eng.max_blocks, eng.cache_blocks) == (7, 3 + 18)
    assert eng._blocks_for(5, 3) == 1 + 1 and eng._blocks_for(60, 30) == 1 + 6
    assert eng._written_blocks(16) == 2 and eng._written_blocks(17) == 3
    pool = eng.cache
    assert pool["state"].shape[:2] == pool["conv"].shape[:2] == (6, 4)
    # the rings beside the states: an entry a token's k, u and running G
    # (4 heads x 16 numbers: a row of lanes each, in a sublane tile of
    # its own); one count a block
    assert pool["ring"].shape == (6, 4, kda.RING, 24, 128)
    assert pool["held"].shape == (1, 4) and pool["held"].dtype == jnp.int32
    assert pool["latent"].shape[:3] == (1, 19, BS)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")
    with pytest.raises(ValueError, match="exceeds cache"):
        make_engine(params, cache_blocks=3).submit(prompt(40), 30)


def test_the_allocator_keeps_two_free_lists_in_one_space_of_ids():
    a = BlockAllocator(8, n_state=2)
    assert (a.free_state, a.free, a.used) == (2, 5, 0)
    s, p = a.alloc(kind="state"), a.alloc()
    assert (s, p) == (1, 3) and a.used == 2
    a.alloc(kind="state")
    with pytest.raises(RuntimeError, match="out of"):
        a.alloc(kind="state")
    a.decref(s)
    a.decref(p)
    assert (a.free_state, a.free) == (1, 5)
    with pytest.raises(RuntimeError, match="double free"):
        a.decref(s)
    a.check()


def test_a_request_holds_a_state_block_and_its_pages(params):
    eng = make_engine(params)
    lens = [20 + 9 * i for i in range(5)]
    rids = [eng.submit(prompt(n, 30 + i), max_new_tokens=4 + i)
            for i, n in enumerate(lens)]
    it = eng.tokens_for(rids[0])
    next(it)
    s = eng.stats()
    # three slots: three state blocks, and each request's pages
    held = sum(eng._blocks_for(lens[i], 4 + i) for i in range(3))
    assert (s["state_blocks"], s["state_blocks_in_use"]) == (3, 3)
    assert s["blocks_in_use"] == held
    tables = [sl.table for sl in eng._slots]
    assert sorted(t[0] for t in tables) == [1, 2, 3]
    assert all((t[1:][t[1:] > 0] <= 18).all() for t in tables)
    list(it)
    eng.run_until_idle()
    assert all(len(stream(eng, r)) == 4 + i
               for i, r in enumerate(rids) if i)
    s = eng.stats()
    assert s["decode_traces"] == 1 and s["retraces_unexpected"] == 0
    assert s["prefix_cache"] is False and s["preemptions"] == 0
    assert s["blocks_in_use"] == s["state_blocks_in_use"] == 0
    assert s["pool_bytes"] == sum(a.nbytes for a in eng.cache.values())
    # a page's row (128 words of 4 B: 40 values in whole lane tiles) a
    # token, and a sequence's state, tails, rings (whole sublane tiles
    # of lanes at this width: 24 rows an entry) and count over its 96
    # positions
    state = 6 * (4 * 16 * 16 + 3 * 3 * 4 * 16 + kda.RING * 24 * 128) * 4 + 4
    assert s["kv_bytes_per_token"] == pytest.approx(128 * 4 + state / 96)
    # counts: the family's, through `counts`
    assert s["state_resets"] == 5
    assert s["kda_tokens_live"] == s["prefill_tokens"] + s["decode_tokens"]
    tails = [n % 16 for n in lens]
    idle = s["decode_steps"] * 3 - s["decode_tokens"]
    assert s["kda_tokens_padded"] == idle + sum(
        eng._chunk_bucket_for(n) - n for n in tails if n)
    assert s["latent_rows_read"] > s["kda_tokens_live"]
    assert 0 < s["expert_tokens_here"] < s["expert_tokens_routed"] \
        == 4 * 6 * s["kda_tokens_live"]
    # two of four groups kept a token a sparse layer; group 0 is held
    assert 0 < s["expert_groups_kept_here"] <= 6 * s["kda_tokens_live"]
    assert s["expert_load_max_over_mean"] >= 1.0
    eng.reset_stats()
    assert eng.stats()["state_resets"] == 0
    eng.check_invariants()


@pytest.mark.parametrize("at", [2, 4, 6])
def test_preempt_and_resume(params, at):
    """Preempted after its first token, in the middle of its steps and
    before its last, each time with tokens waiting in its rings: both
    kinds of block go back, the resume re-prefills prompt and emitted
    tokens from the first token into a state block it resets (its rings
    left empty) and pages it rewrites, and the stream is what an
    unpreempted one is."""
    base_eng = make_engine(params)
    base = stream(base_eng, base_eng.submit(prompt(40, 50),
                                            max_new_tokens=9))
    faults.install(faults.FaultPlan(seed=3).fail("engine.preempt", at=at,
                                                 times=1))
    eng = make_engine(params)
    rid = eng.submit(prompt(40, 50), max_new_tokens=9)
    eng.run_until_idle()
    s = eng.stats()
    assert s["preemptions"] == 1 and s["state_resets"] == 2
    assert s["blocks_in_use"] == 0 and s["cached_prefix_blocks"] == 0
    same_stream(stream(eng, rid), base)
    eng.check_invariants()


def test_handoff_carries_the_state_block_and_the_pages(params):
    """`serve/disagg.py`'s hand-off: a prefill engine exports the state
    block and the prompt's pages, each with its own kind's arrays, a
    decode engine imports them and streams what one engine streams."""
    p = prompt(37, 60)
    one = make_engine(params)
    base = stream(one, one.submit(p, max_new_tokens=6))
    pre = make_engine(params, role="prefill")
    rid = pre.submit(p, max_new_tokens=6)
    blob = pre.handoff_for(rid)
    assert blob["n_blocks"] == len(blob["payload"]) == 1 + 3
    assert set(blob["payload"][0]) == {"state", "conv", "ring", "held"}
    assert blob["payload"][0]["state"].shape == (6, 4, 16, 16)
    assert blob["payload"][0]["ring"].shape == (6, kda.RING, 24, 128)
    assert [set(b) for b in blob["payload"][1:]] == [{"latent"}] * 3
    assert blob["payload"][1]["latent"].shape == (1, BS, 1, 128)
    assert pre.stats()["blocks_in_use"] == 0
    dec = make_engine(params, role="decode")
    same_stream(stream(dec, dec.import_handoff(blob)), base)
    assert dec.stats()["blocks_in_use"] == 0
    dec.check_invariants()
    pre.check_invariants()


@pytest.mark.parametrize("slots", [1, 2], ids=["one_slot", "two_slots"])
def test_a_block_freed_mid_ring_is_taken_by_a_new_sequence(params, slots):
    """Requests that decode past a fold and end part of the way into a
    ring, one after another on the same state blocks: the next sequence's
    first chunk leaves the block's rings empty, so each streams what the
    reference gives for it alone; on two slots the rows' rings fill in
    different steps. `state_folds` counts the rows whose rings went into
    their states: one every `RING` decode tokens of a request."""
    eng = make_engine(params, slots=slots)
    ring = kda.RING
    news = (ring + 4, 2 * ring + 3, ring + 2, 5)
    prompts = [prompt(n, 60 + i) for i, n in enumerate((20, 9, 37, 12))]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        lp = np.asarray(ref.token_logprobs(
            params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]
        np.testing.assert_allclose([x for _, x in got], lp, atol=TOL)
    s = eng.stats()
    # a request's first token is its prefill's
    assert s["state_folds"] == sum((n - 1) // ring for n in news)
    assert 0 <= s["decode_tokens"] / ring - s["state_folds"] < len(news)
    assert not np.asarray(eng.cache["held"])[0, 0]
    eng.check_invariants()


def test_the_engine_counts_a_fold_a_ring_of_decode_tokens(params):
    """`state_folds` beside `decode_tokens`: over a run of requests that
    start in different steps the share is near `1 / RING` (short of it by
    the requests that end part of the way into a ring), and 1 under the
    control, whose every step folds its one token."""
    news = (41, 26, 33, 50, 19)
    for state_round, share in (("none", 1 / kda.RING), ("bfloat16", 1.0)):
        eng = make_engine(params, config(state_round=state_round))
        for i, n in enumerate(news):
            eng.submit(prompt(7 + 5 * i, 90 + i), max_new_tokens=n)
        eng.run_until_idle()
        s = eng.stats()
        assert s["decode_tokens"] == sum(news) - len(news)
        if state_round == "none":
            assert s["state_folds"] == sum((n - 1) // kda.RING for n in news)
            assert 0.11 <= s["state_folds"] / s["decode_tokens"] <= share
        else:
            assert s["state_folds"] == s["decode_tokens"]
        eng.check_invariants()


def test_a_handed_off_block_carries_a_half_full_ring(params):
    """A state block exported in the middle of a ring (`gather_block`'s
    arrays: the ring's entries and its count beside state and tail) and
    imported into another block of another pool goes on as it was."""
    cfg = config()
    fam = linear_latent.FAMILY
    table = jnp.asarray([[2, 3, 4, 0, 0, 0, 0]], jnp.int32)
    toks = prompt(20 + 5, 95)
    pool = linear_latent.init_pool(cfg, 6, BS, state_blocks=4)
    for start, n in ((0, 16), (16, 4)):
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = toks[start:start + n]
        _, pool, _ = linear_latent.prefill(
            params, jnp.asarray(chunk), pool, cfg, block_table=table[0],
            start=start, length=n)

    def steps(pool, table, lo, hi):
        logits = []
        for i in range(lo, hi):
            out, pool, _ = linear_latent.decode(
                params, jnp.asarray(toks[i:i + 1]), pool,
                jnp.asarray([i], jnp.int32), table, cfg)
            logits.append(out)
        return jnp.concatenate(logits), pool

    _, pool = steps(pool, table, 20, 23)
    assert int(pool["held"][0, 2]) == 3
    want, _ = steps(pool, table, 23, 25)
    # the state block into block 1 of a pool whose block 2 holds garbage
    other = jax.tree.map(lambda a: a + jnp.ones((), a.dtype),
                         linear_latent.init_pool(cfg, 6, BS, state_blocks=4))
    mine = {key: pool[key] for key in fam.state_keys}
    moved = {**fam.scatter_block({key: other[key] for key in fam.state_keys},
                                 fam.gather_block(mine, 2), 1),
             "latent": pool["latent"]}
    assert int(moved["held"][0, 1]) == 3
    got, _ = steps(moved, table.at[0, 0].set(1), 23, 25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=TOL)


def test_a_cancelled_request_frees_both_kinds_of_block(params):
    eng = make_engine(params, slots=2)
    rid = eng.submit(prompt(30, 70), max_new_tokens=20)
    it = eng.tokens_for(rid)
    next(it)
    s = eng.stats()
    assert (s["state_blocks_in_use"], s["blocks_in_use"]) == (1, 1 + 4)
    it.close()
    s = eng.stats()
    assert s["blocks_in_use"] == s["state_blocks_in_use"] == 0
    assert s["cancelled"] == 1
    eng.check_invariants()


def test_padding_and_idle_rows_leave_state_tails_and_pages(params):
    """A chunk of 13 live positions in buckets of 16 and 32: state, tail
    and the page's rows bit for bit the same; a decode step whose rows
    are all idle rewrites the trash blocks and nothing else."""
    cfg = config()
    table = jnp.asarray([2, 3, 4, 0, 0, 0, 0], jnp.int32)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = linear_latent.init_pool(cfg, 6, BS, state_blocks=4)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), pool)
        _, pool, counts = linear_latent.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        assert [int(c) for c in counts[:3]] == [13, bucket - 13, 1]
        pools.append(pool)
    for key in ("state", "conv"):
        np.testing.assert_array_equal(np.asarray(pools[0][key]),
                                      np.asarray(pools[1][key]))
    np.testing.assert_array_equal(np.asarray(pools[0]["latent"][0, 3, :13]),
                                  np.asarray(pools[1]["latent"][0, 3, :13]))
    before = pools[0]
    _, after, counts = linear_latent.decode(
        params, jnp.zeros((2,), jnp.int32), before, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 7), jnp.int32), cfg)
    assert [int(c) for c in counts[:7]] == [0, 2, 0, 0, 0, 0, 0]
    for key in before:
        np.testing.assert_array_equal(np.asarray(before[key][:, 1:]),
                                      np.asarray(after[key][:, 1:]))


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


# -- (c) the dense latent decode ----------------------------------------------

def _latent_case(dtype, h, bs, values, seed=3):
    """Six streams over layer 1 of a pool of two layers in pages of `bs`
    rows: contexts of 1 row (an idle slot), of 255, 256 and 257 rows (a
    chunk less one, whole, and one more), of a ragged last page, and of
    every page of its table; the shorter tables are padded with the trash
    block. -> (rows [2, n_blocks, bs, values], pool, q, tables, count)."""
    dt = jnp.dtype(dtype)
    words = sparse_latent.row_words(values, dt)
    count = np.asarray([1, 255, 256, 257, 259 + bs // 2, 384], np.int32)
    mb = 384 // bs
    ks = jax.random.split(jax.random.key(seed), 2)
    rows = jax.random.normal(ks[0], (2, 6 * mb + 1, bs, values)).astype(dt)
    pool = sparse_latent.pack_rows(rows, words)[:, :, :, None, :]
    q = sparse_latent.split_query(
        (2 * values ** -0.5 * jax.random.normal(ks[1], (6, h, values))
         ).astype(dt), words)
    tables = np.random.default_rng(seed).permutation(
        np.arange(1, 6 * mb + 1)).reshape(6, mb).astype(np.int32)
    tables[np.arange(mb)[None, :] * bs >= count[:, None]] = 0
    return rows, pool, q, jnp.asarray(tables), jnp.asarray(count)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("h,values", [(4, 40), (32, 576), (64, 576)])
def test_latent_decode_kernel_streams_a_stream_s_pages(dtype, bs, h, values):
    """The kernel (interpreted here) against the plain path and against
    softmax attention by hand, at ling's and longcat's heads and row."""
    rows, pool, q, tables, count = _latent_case(dtype, h, bs, values)
    got = {impl: sparse_latent.join_parts(sparse_latent.latent_decode(
        q, pool, 1, tables, count, dtype=jnp.dtype(dtype), impl=impl), values)
        for impl in ("jax", "pallas")}
    np.testing.assert_allclose(np.asarray(got["pallas"]),
                               np.asarray(got["jax"]), rtol=0, atol=2e-2
                               if dtype == "bfloat16" else 1e-5)
    qf = sparse_latent.join_parts(q, values).astype(jnp.float32)
    for i in range(len(count)):
        ctx = rows[1, tables[i]].reshape(-1, values)[:int(count[i])].astype(
            jnp.float32)
        p = jax.nn.softmax(qf[i] @ ctx.T, -1)
        np.testing.assert_allclose(np.asarray(got["jax"][i]),
                                   np.asarray(p @ ctx), rtol=0, atol=1e-4)


def test_latent_decode_reads_only_the_tiles_that_hold_values():
    """longcat's row, 512 + 64 values in 768 stored: with `values` and
    `kv_rank` given, the last lane tile of a row's second part is never
    read (NaN there reaches nothing), scores stop at `values`, and the
    output's first `kv_rank` values are those of the whole-row call."""
    values, kv_rank = 576, 512
    rows, pool, q, tables, count = _latent_case("bfloat16", 64, 128, values)
    whole = sparse_latent.join_parts(sparse_latent.latent_decode(
        q, pool, 1, tables, count, dtype=jnp.bfloat16, impl="jax"), kv_rank)
    nan = jnp.uint32(0x7FC00000)    # a bfloat16 NaN in a word's high half
    poisoned = pool.at[..., 256:].set(pool[..., 256:] & 0xFFFF | nan)
    for impl, tol in (("jax", 1e-5), ("pallas", 2e-2)):
        out = sparse_latent.latent_decode(
            q, poisoned, 1, tables, count, dtype=jnp.bfloat16, values=values,
            kv_rank=kv_rank, impl=impl)
        got = np.asarray(sparse_latent.join_parts(out, kv_rank))
        assert np.isfinite(got).all(), impl
        assert not np.asarray(sparse_latent.join_parts(out, 768))[
            ..., kv_rank:].any(), impl
        np.testing.assert_allclose(got, np.asarray(whole), rtol=0, atol=tol)


def test_a_layer_without_an_indexer_is_served(params):
    """kanana-2-30b-a3b's shape at a small size (no indexer, no query
    bottleneck, keys 24 and values 16 wide, a dense layer and two sparse
    ones) through the engine: prefill in chunks, then `latent_decode`
    over the pages, against the family's whole-sequence forward; the pool
    has no index keys."""
    cfg = lsm.LatentSparseMoEConfig(
        q_rank=None, index_topk=None, indexer_types=("none",) * 3,
        mlp_types=("dense", "sparse", "sparse"), dtype="float32",
        sparse_impl="jax", max_seq_len=96)
    weights = lsm.init_params(jax.random.key(4), cfg)
    for impl in ("jax", "pallas"):
        eng = InferenceEngine(weights, dataclasses.replace(
            cfg, sparse_impl=impl), slots=2, max_len=96, block_size=BS,
            prefill_chunk=16)
        assert set(eng.cache) == {"latent"}
        p = prompt(37, 90)
        got = stream(eng, eng.submit(p, max_new_tokens=12))
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        want = jax.nn.log_softmax(lsm.forward(
            weights, jnp.asarray(seq)[None], cfg)[0], -1)
        np.testing.assert_allclose(
            [x for _, x in got],
            [float(want[len(p) - 1 + i, t]) for i, (t, _) in enumerate(got)],
            atol=TOL)
        eng.check_invariants()


# -- (d) routing by groups -------------------------------------------------

def router_inputs(cfg, n=64, seed=6):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (n, cfg.d_model)),
            {"router": jax.random.normal(ks[1], (cfg.d_model,
                                                 cfg.router_width)) * 0.3,
             "router_bias": jax.random.normal(ks[2], (cfg.router_width,))
             * 0.01})


def test_one_group_routes_as_before():
    """`n_group` 1 (glm-5.2, kanana-2): the k largest of score + bias over
    the whole width, weights from the scores alone."""
    cfg = lsm.LatentSparseMoEConfig(router_width=16, experts_per_token=4)
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    h2, lp = router_inputs(cfg)
    chosen, weights = blocks.routing(h2, lp, cfg.experts)
    g = jax.nn.sigmoid(h2 @ lp["router"])
    _, want = jax.lax.top_k(g + lp["router_bias"], 4)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    w = jnp.take_along_axis(g, want, -1)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(
        w / w.sum(-1, keepdims=True) * 2.5), rtol=1e-6)
    with pytest.raises(ValueError, match="groups over a router"):
        lsm.LatentSparseMoEConfig(router_width=16, n_group=3)


def test_groups_limit_the_choice_as_the_reference_s_do():
    cfg = config()
    h2, lp = router_inputs(cfg)
    chosen, weights = blocks.routing(h2, lp, cfg.experts)
    want, want_w = ref.routing(h2, lp, TINY)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(want), -1))
    np.testing.assert_allclose(np.sort(np.asarray(weights), -1),
                               np.sort(np.asarray(want_w), -1), rtol=1e-5)
    # every token's experts lie in two of the four groups of four
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(chosen))
    free = dataclasses.replace(cfg, n_group=1, topk_group=1)
    assert (np.sort(np.asarray(blocks.routing(h2, lp, free.experts)[0]), -1)
            != np.sort(np.asarray(chosen), -1)).any()


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test with groups: four chips each hold one group
    of four experts of a 16-wide router in 4 groups (2 kept); their
    routed parts and the shared expert, counted once, add up to what the
    reference gives for the whole layer with all 16 experts."""
    whole = {**TINY, "num_experts": 16}
    whole.pop("published")
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), whole)["layers"][1])
    h2 = jax.random.normal(jax.random.key(8), (48, 64))
    want = ref.feed_forward(h2, lp, whole)
    total = 0.0
    for share in range(4):
        cfg = config(experts_held_from=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4]
                         for k in ("we_gate", "we_up", "we_down")}}
        routed, shared, _, counts = blocks.expert_layer(
            h2, mine, cfg.experts, cfg.activation_dtype())
        total = total + routed
        # what one share gives is the reference's share of it
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(ref.routed_part(
                h2, mine, {**TINY, "experts_held_from": 4 * share})),
            rtol=0, atol=TOL)
        assert int(counts[1]) == 48 * 4
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=0, atol=TOL)
