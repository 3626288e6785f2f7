"""The window-and-full family (`models/window_moe.py`, the `gqa_*`
kernels of `ops/decode_attention.py`) against its plain reference
(`benchmarks/refs/window_moe.py`) at a tiny size on the CPU, seeded random
weights, float32: the whole-sequence forward, chunked prefill and decode
through the engine (logprobs, not tokens) for requests under the window
and past it by several windows, what a request of two kinds of page asks
of the engine (pages that grow and a ring of pages that do not, under one
allocator: footprint, counters, preemption, cancel, hand-off, a fuzz),
padding, the benchmark's two controls, and the eight shares of the expert
layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import window_moe as ref
from ray_tpu.models import blocks, gpt, latent_sparse_moe as lsm, \
    linear_latent, retention, window_moe
from ray_tpu.ops import decode_attention as da
from ray_tpu.serve.engine import BlockAllocator, InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: one period (window x 3, full), 8 query
# heads over 2 key-value heads, a window of 32 positions = 4 pages of 8,
# experts 0-3 of a 16-wide router held
TINY = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=32,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    intermediate_size=32, num_shared_experts=4, num_experts=4,
    published={"num_experts": 16}, num_experts_per_tok=4,
    norm_topk_prob=True, layer_norm_eps=1e-5, rope_theta=50000,
    max_position_embeddings=256, logit_scale=1, layers_from=0,
    experts_held_from=0, vocab_size=512, attn_logit_std=2.4,
    attn_out_gain=5.66, embed_scale=0.02, final_norm_gain=2.0)
WEIGHTS = ("attn_logit_std", "attn_out_gain", "embed_scale",
           "final_norm_gain")
# float32 both sides at the highest matmul precision; measured 2e-5 on
# logits. A wrong mask, window, ring page, rotary or group moves a logit
# by 1e-1 and up
TOL = 1e-4
BS, WINDOW, CHUNK = 8, 32, 16
RING = 7        # ceil((32 + 16 - 2) / 8) + 1 pages: the window and a chunk


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k not in WEIGHTS}
    return window_moe.from_published(
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
    kw = {"slots": 3, "max_len": 256, "block_size": BS,
          "prefill_chunk": CHUNK, "prefill_buckets": (8, 16),
          "prefix_cache": False, **kw}
    return InferenceEngine(params, cfg or config(), **kw)


def stream(eng, rid):
    return [(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]


def same_stream(got, base):
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in base], rtol=0, atol=1e-4)


def reference_logprobs(params, p, got):
    seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
    return np.asarray(ref.token_logprobs(
        params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]


# -- (a) the model against the reference -----------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(100, 1), prompt(100, 2)]))
    assert config().kinds == ("window", "window", "window", "full")
    assert ref.layer_kinds(TINY) == list(config().kinds)
    np.testing.assert_allclose(
        np.asarray(window_moe.forward(params, toks, config())),
        np.asarray(ref.logits(params, toks, TINY)), rtol=0, atol=TOL)


def test_the_head_is_not_saturated(params):
    """The weight scales leave a greedy stream something to compare: its
    tokens differ and their logprobs are not 0 (a tied head over a
    residual that the embedding dominates repeats one token at logprob
    0)."""
    eng = make_engine(params)
    got = stream(eng, eng.submit(prompt(40, 3), max_new_tokens=24))
    assert len({t for t, _ in got}) > 12
    assert np.mean([lp for _, lp in got]) < -0.5


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_engine_streams_the_reference_s_logprobs(params, impl):
    """Five requests on three slots: a prompt under the window in one
    chunk, one that ends at the window's edge, one whose third chunk
    straddles it (positions 32..36), and two past it by four and six
    windows, whose window pages are a ring written again in place; chunk
    buckets of 8 and 16, padded last chunks. With `impl="pallas"` the four
    kernels in interpret mode."""
    eng = make_engine(params, config(impl))
    lens = (5, 32, 37, 150, 200)
    prompts = [prompt(n, 10 + i) for i, n in enumerate(lens)]
    rids = [eng.submit(p, max_new_tokens=10 + 5 * i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        np.testing.assert_allclose([x for _, x in got],
                                   reference_logprobs(params, p, got),
                                   atol=TOL)
    s = eng.stats()
    assert s["bounded_pages_reused"] > 0 and s["preemptions"] == 0
    eng.check_invariants()


# -- (b) two kinds of page under one allocator -----------------------------

def test_one_footprint_arithmetic_for_every_family(params):
    """What a request may hold: state blocks, pages that grow, pages that
    grow up to a bound. (state blocks, paged, bound) by family, and this
    family's footprint: the pages of its tokens and as many again up to a
    ring."""
    fams = {"gpt": gpt.GPTConfig().family, "latent": lsm.FAMILY,
            "retention": retention.FAMILY, "hybrid": linear_latent.FAMILY,
            "window": config().family}
    assert {n: (f.state_blocks, f.paged, f.bounded_tokens, f.bounded_keys)
            for n, f in fams.items()} == {
        "gpt": (0, True, 0, ()), "latent": (0, True, 0, ()),
        "retention": (1, False, 0, ()), "hybrid": (1, True, 0, ()),
        "window": (0, True, WINDOW, ("kw", "vw"))}
    eng = make_engine(params)
    # 3 slots x 256 / 8 pages and 3 rings; a table is 32 columns of full
    # pages, then 32 of window pages
    assert eng._ring == RING
    assert (eng.max_blocks, eng.cache_blocks, eng.bounded_blocks) == (
        64, 3 * 32 + 3 * RING, 3 * RING)
    assert eng._footprint(5) == (0, 1, 1)
    assert eng._footprint(56) == (0, 7, 7) and eng._footprint(57) == (0, 7, 8)
    assert eng._written_blocks(57) == 15 and eng._footprint(256) == (0, 7, 32)
    assert eng._blocks_for(5, 3) == 2 and eng._blocks_for(200, 30) == 29 + 7
    pool = eng.cache
    assert pool["k"].shape == pool["v"].shape == (1, 97, 2, BS, 16)
    assert pool["kw"].shape == pool["vw"].shape == (3, 22, 2, BS, 16)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec="ngram")
    with pytest.raises(ValueError, match="holds no request's ring"):
        make_engine(params, bounded_blocks=RING - 1)
    with pytest.raises(ValueError, match="exceeds cache"):
        make_engine(params, cache_blocks=3).submit(prompt(40), 30)


def test_the_allocator_keeps_three_free_lists_in_one_space_of_ids():
    a = BlockAllocator(12, n_state=2, n_bounded=3)
    assert (a.free_state, a.free_bounded, a.free, a.used) == (2, 3, 6, 0)
    s, w, p = a.alloc(kind="state"), a.alloc(kind="bounded"), a.alloc()
    assert (s, w, p) == (1, 3, 6) and a.used == 3
    assert [a.kind_of(b) for b in (s, w, p)] == ["state", "bounded", "page"]
    assert [a.index_of(b) for b in (s, w, p)] == [1, 1, 1]
    a.alloc(kind="bounded")
    a.alloc(kind="bounded")
    with pytest.raises(RuntimeError, match="out of"):
        a.alloc(kind="bounded")
    for b in (s, w, p):
        a.decref(b)
    assert (a.free_state, a.free_bounded, a.free) == (2, 1, 6)
    with pytest.raises(RuntimeError, match="double free"):
        a.decref(w)
    a.check()


def test_a_request_holds_its_pages_and_a_ring(params):
    eng = make_engine(params)
    lens = [20, 90, 37, 130]
    rids = [eng.submit(prompt(n, 30 + i), max_new_tokens=4 + i)
            for i, n in enumerate(lens)]
    it = eng.tokens_for(rids[0])
    next(it)
    s = eng.stats()
    held = [eng._footprint(lens[i] + 3 + i) for i in range(3)]
    assert (s["bounded_blocks"], s["bounded_ring"]) == (3 * RING, RING)
    assert s["bounded_blocks_in_use"] == sum(h[1] for h in held) == 3 + 7 + 6
    assert s["blocks_in_use"] == sum(sum(h) for h in held)
    for sl, h in zip(eng._slots, held):
        full, ring = sl.table[:32], sl.table[32:]
        assert (full > 0).sum() == h[2] and (full <= 96).all()
        # a ring's columns walk its pages, a column a logical page
        assert (ring > 0).sum() == h[2] and len(set(ring[ring > 0])) == h[1]
        assert (ring[:h[2]] == np.resize(ring[:h[1]], h[2])).all()
    list(it)
    eng.run_until_idle()
    assert all(len(stream(eng, r)) == 4 + i for i, r in enumerate(rids) if i)
    s = eng.stats()
    assert s["decode_traces"] == 1 and s["retraces_unexpected"] == 0
    assert s["prefix_cache"] is False and s["preemptions"] == 0
    assert s["blocks_in_use"] == s["bounded_blocks_in_use"] == 0
    assert s["pool_bytes"] == sum(a.nbytes for a in eng.cache.values())
    # counts: the family's, through `counts`
    live = s["prefill_tokens"] + s["decode_tokens"]
    assert 0 < s["window_rows_read"] < 3 * s["full_rows_read"]
    # 90 and 130 positions: logical pages 7.. of 8 rows lie past a ring
    assert s["bounded_pages_reused"] == sum(
        (n + 2 + i) // BS + 1 - RING for i, n in enumerate(lens)
        if (n + 2 + i) // BS + 1 > RING)
    assert 0 < s["expert_tokens_here"] < s["expert_tokens_routed"] \
        == 4 * 4 * live
    assert s["expert_load_max_over_mean"] >= 1.0
    eng.reset_stats()
    s = eng.stats()
    assert s["window_rows_read"] == s["bounded_pages_reused"] == 0
    eng.check_invariants()


@pytest.mark.parametrize("at", [2, 4, 6])
def test_preempt_and_resume(params, at):
    """Preempted after its first token, in the middle of its steps and
    before its last: both kinds of page go back, the resume re-prefills
    prompt and emitted tokens from the first token (a ring's page names
    no lasting range of tokens to keep), and the stream is what an
    unpreempted one is."""
    base_eng = make_engine(params)
    base = stream(base_eng, base_eng.submit(prompt(90, 50),
                                            max_new_tokens=9))
    faults.install(faults.FaultPlan(seed=3).fail("engine.preempt", at=at,
                                                 times=1))
    eng = make_engine(params)
    rid = eng.submit(prompt(90, 50), max_new_tokens=9)
    eng.run_until_idle()
    s = eng.stats()
    assert s["preemptions"] == 1
    assert s["blocks_in_use"] == s["bounded_blocks_in_use"] == 0
    assert s["cached_prefix_blocks"] == 0
    same_stream(stream(eng, rid), base)
    eng.check_invariants()


@pytest.mark.parametrize("n", [37, 90])
def test_handoff_carries_the_ring_and_the_pages(params, n):
    """`serve/disagg.py`'s hand-off: a prefill engine exports the ring's
    pages and the prompt's pages, each with its own kind's arrays, a
    decode engine imports them into a ring of its own and streams what
    one engine streams; under the bound and past it."""
    p = prompt(n, 60)
    one = make_engine(params)
    base = stream(one, one.submit(p, max_new_tokens=6))
    pre = make_engine(params, role="prefill")
    rid = pre.submit(p, max_new_tokens=6)
    blob = pre.handoff_for(rid)
    _, ring, pages = pre._footprint(n)
    assert blob["n_blocks"] == len(blob["payload"]) == ring + pages
    assert [set(b) for b in blob["payload"]] == \
        [{"kw", "vw"}] * ring + [{"k", "v"}] * pages
    assert blob["payload"][0]["kw"].shape == (3, 2, BS, 16)
    assert blob["payload"][-1]["k"].shape == (1, 2, BS, 16)
    assert pre.stats()["blocks_in_use"] == 0
    dec = make_engine(params, role="decode")
    same_stream(stream(dec, dec.import_handoff(blob)), base)
    assert dec.stats()["blocks_in_use"] == 0
    dec.check_invariants()
    pre.check_invariants()


def test_a_cancelled_request_frees_both_kinds_of_page(params):
    eng = make_engine(params, slots=2)
    rid = eng.submit(prompt(70, 70), max_new_tokens=20)
    it = eng.tokens_for(rid)
    next(it)
    s = eng.stats()
    assert (s["bounded_blocks_in_use"], s["blocks_in_use"]) == (7, 7 + 12)
    it.close()
    s = eng.stats()
    assert s["blocks_in_use"] == s["bounded_blocks_in_use"] == 0
    assert s["cancelled"] == 1
    eng.check_invariants()


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_with_the_bounded_kind(params, seed):
    """Random submit / cancel / drain / step with a preemption thrown in,
    over a pool too small for every slot's longest request: a request's
    holding of the bounded kind never passes its ring
    (`check_invariants`), used + free of each kind is conserved after
    every operation, and everything goes back."""
    faults.install(faults.FaultPlan(seed=seed).fail(
        "engine.preempt", at=5, times=2))
    eng = make_engine(params, cache_blocks=40, bounded_blocks=2 * RING + 3)
    alloc = eng._alloc
    rng = np.random.default_rng(seed)
    live = []

    def conserved():
        eng.check_invariants()
        held = [b for s in eng._slots for b in s.blocks]
        for kind, free, total in (("bounded", alloc.free_bounded,
                                   2 * RING + 3), ("page", alloc.free, 40)):
            used = sum(alloc.kind_of(b) == kind for b in held)
            assert used + free == total, kind

    for _ in range(50):
        op = rng.integers(0, 10)
        if op < 4:
            try:
                live.append(eng.submit(
                    prompt(int(rng.integers(1, 120)), int(rng.integers(99))),
                    max_new_tokens=int(rng.integers(1, 12))))
            except ValueError:
                pass        # footprint exceeds the pool
        elif op < 5 and live:
            eng.cancel(live.pop(int(rng.integers(0, len(live)))))
        elif op < 6 and live:
            for _ in eng.tokens_for(live.pop(0)):
                pass
        else:
            eng.step()
        conserved()
    for rid in live:
        eng.cancel(rid)
    eng.run_until_idle()
    conserved()
    s = eng.stats()
    assert s["active"] == 0 and s["pending"] == 0
    assert s["blocks_in_use"] == s["bounded_blocks_in_use"] == 0
    assert s["decode_tokens"] > 0


def test_padding_and_idle_rows_leave_the_pool(params):
    """A chunk of 13 live positions in buckets of 16 and 32: every array
    of the pool bit for bit the same, so the padding was written nowhere;
    a decode step whose rows are all idle rewrites the trash pages and
    nothing else."""
    cfg = config()
    table = np.zeros((64,), np.int32)
    table[:3], table[32:35] = (2, 3, 4), (1, 2, 3)
    pools = []
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt(13, 5)
        pool = window_moe.init_pool(cfg, 6, BS, bounded_blocks=5)
        pool = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), pool)
        _, pool, counts = window_moe.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=0, length=13)
        # rows 1 + .. + 13 a layer
        assert [int(c) for c in counts[:2]] == [3 * 91, 91]
        pools.append(pool)
    for key in pools[0]:
        np.testing.assert_array_equal(np.asarray(pools[0][key]),
                                      np.asarray(pools[1][key]))
    assert float(pools[0]["k"][0, 3, 0, 4, 0]) != 1.0     # position 12
    assert float(pools[0]["k"][0, 3, 0, 5, 0]) == 1.0     # position 13
    before = pools[0]
    _, after, counts = window_moe.decode(
        params, jnp.zeros((2,), jnp.int32), before,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 64), jnp.int32), cfg)
    assert not np.asarray(counts).any()
    for key in before:
        np.testing.assert_array_equal(np.asarray(before[key][:, 1:]),
                                      np.asarray(after[key][:, 1:]))


def test_span_attributes_under_a_profiler_session(params, tmp_path):
    """What the benchmark's reducer reads from the trace: a decode step's
    `engine/decode_dispatch` carries `bounded_rows`, the sum over its
    decoding streams of min(context, window), and a chunk's
    `engine/prefill_chunk` its `start`; the sum is made only while a
    session is on."""
    import glob
    from jax.profiler import ProfileData
    eng = make_engine(params)
    list(eng.tokens_for(eng.submit(prompt(40, 1), max_new_tokens=3)))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        got = stream(eng, eng.submit(prompt(30, 2), max_new_tokens=5))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines if plane.name == "/host:CPU" else ():
            for ev in line.events:
                spans.setdefault(ev.name, []).append(
                    (ev.start_ns, dict(ev.stats)))
    # contexts 31 .. 34 at the four steps, over a window of 32
    steps = [a["bounded_rows"] for _, a in sorted(
        spans["engine/decode_dispatch"], key=lambda e: e[0])]
    assert len(got) == 5 and steps == [31, 32, 32, 32]
    assert sorted(a["start"] for _, a in spans["engine/prefill_chunk"]) \
        == [0, 16]


# -- (c) the benchmark's controls ------------------------------------------

@pytest.mark.parametrize("control", [{"cache_round": "int8"},
                                     {"full_window": WINDOW},
                                     {"expert_round": "float8_e4m3fn"}])
def test_a_control_moves_the_logprobs(params, control):
    """Cache rows rounded to the int8 grid and the full layer cut at the
    window (the cell's two controls), and the routed experts on the
    float8 grid (a probe: in float32 it shows, beside bfloat16 with an
    eighth of the experts held it does not): each moves what a request
    past the window streams by far more than the forms differ; the cut
    leaves a request under the window alone."""
    streams = {}
    for name, cfg in (("sound", config()), ("control", config(**control))):
        eng = make_engine(params, cfg)
        streams[name] = [stream(eng, eng.submit(prompt(n, 80),
                                                max_new_tokens=8))
                         for n in (20, 120)]
    moved = [max(abs(a - b) for (_, a), (_, b) in zip(x, y))
             for x, y in zip(streams["sound"], streams["control"])]
    assert moved[1] > 10 * TOL
    if "full_window" in control:
        assert moved[0] < TOL
    with pytest.raises(ValueError, match="unknown cache_round"):
        config(cache_round="int4")
    with pytest.raises(ValueError, match="unknown expert_round"):
        config(expert_round="int4")


# -- (d) the chip's share of the expert layer ------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: eight chips each hold two experts of a
    16-wide router; their routed parts, with attention and the shared
    experts (which every chip computes alike) counted once, add up to what
    the reference gives for the whole layer with all 16 experts, in a
    window layer and in a full layer."""
    whole = {**TINY, "num_experts": 16}
    whole.pop("published")
    layers = jax.tree.map(lambda a: a.astype(jnp.float32), ref.init_params(
        jax.random.key(7), whole)["layers"])
    x = jax.random.normal(jax.random.key(8), (48, 64)) * 0.3
    pos = jnp.arange(48, dtype=jnp.int32)
    for lp, kind in ((layers[0], "window"), (layers[3], "full")):
        want = ref.layer(x, lp, kind, whole)
        cfg = config(num_experts=2)
        n = blocks.layer_norm(x, lp["norm_scale"], cfg.eps)
        q, k, v = window_moe._qkv(n, lp, kind, pos, cfg)
        att = da.reference_gqa_attention(
            q[None], k[None], v[None], jnp.zeros((1,), jnp.int32),
            WINDOW if kind == "window" else None)[0]
        total = x + att.reshape(48, -1) @ lp["w_out"]
        for share in range(8):
            cfg = config(num_experts=2, experts_held_from=2 * share)
            mine = {**lp, **{key: lp[key][2 * share:2 * share + 2]
                             for key in ("we_gate", "we_up", "we_down")}}
            routed, shared, _, counts = blocks.expert_layer(
                n, mine, cfg.experts, cfg.activation_dtype())
            total = total + routed
            np.testing.assert_allclose(
                np.asarray(routed), np.asarray(ref.routed_part(
                    n, mine, {**TINY, "experts_held_from": 2 * share})),
                rtol=0, atol=TOL)
            assert int(counts[1]) == 48 * 4
        # the four shared experts side by side are one gated MLP: their
        # mean is a quarter of it
        np.testing.assert_allclose(
            np.asarray(shared / 4), np.asarray(ref.shared_part(n, lp, TINY)),
            rtol=0, atol=TOL)
        np.testing.assert_allclose(np.asarray(total + shared / 4),
                                   np.asarray(want), rtol=0, atol=TOL)


def test_the_config_maps_the_published_keys():
    cfg = config()
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.window) == (
        8, 2, 16, 32)
    assert (cfg.router_width, cfg.held_count, cfg.experts_per_token,
            cfg.shared_experts) == (16, 4, 4, 4)
    assert dataclasses.replace(cfg, first_layer=1).kinds == (
        "window", "window", "full")
    with pytest.raises(ValueError, match="window or a full layer"):
        window_moe.WindowMoEConfig(layer_types=("window", "chunked") * 2)
