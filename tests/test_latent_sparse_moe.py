"""The latent / sparse-attention / routed-expert family
(`models/latent_sparse_moe.py`, `ops/sparse_latent.py`,
`ops/grouped_experts.py`) against its plain reference
(`benchmarks/refs/latent_sparse_moe.py`) at a tiny size on the CPU, seeded
random weights, float32: the whole-sequence forward, chunked prefill and
decode through the paged latent and index pools (logits, not tokens), the
selected sets, the share test, the engine's invariants with the new
family, and the `gpt` family through the engine's model interface."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import latent_sparse_moe as ref
from ray_tpu.models import blocks, gpt
from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.ops import grouped_experts, sparse_latent
from ray_tpu.serve.engine import (FROM_CHUNK, FROM_STEP, InferenceEngine,
                                  pack_chunk, pack_rows, unpack_chunk,
                                  unpack_rows)
from ray_tpu.util import faults

# the published keys at a tiny size: two layers own an indexer, the top-k
# (12) is smaller than every prompt below, 4 of the router's 8 experts held
TINY = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=16, index_head_dim=16,
    index_topk=12, indexer_types=["full", "shared", "full", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"], layers_from=0,
    intermediate_size=128, moe_intermediate_size=32, n_shared_experts=1,
    published={"n_routed_experts": 8}, num_experts_per_tok=2,
    experts_held_from=2, n_routed_experts=4, routed_scaling_factor=2.5,
    norm_topk_prob=True, rope_theta=8e6, rms_norm_eps=1e-5,
    max_position_embeddings=128, vocab_size=512)
BS = 16
TOL = 2e-4      # float32 both sides; measured 3e-6 on logits of spread 4


def config(impl="jax", **over):
    return lsm.from_published(**{**TINY, **over}, dtype="float32",
                              sparse_impl=impl)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(jax.random.key(0), TINY)


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


def paged_logits(params, cfg, seq, n_prompt, chunk=16, select=None,
                 table_blocks=8):
    """seq's logits through the pool: the first `n_prompt` tokens by
    chunked prefill (the logits of each chunk's last position), the rest
    one decode step each. -> {position: logits [V]}."""
    n_blocks = -(-len(seq) // BS)
    cache = lsm.init_pool(cfg, n_blocks + 2, BS)
    table = np.zeros(table_blocks, np.int32)
    table[:n_blocks] = 2 + np.arange(n_blocks)[::-1]    # not in order
    out = {}
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = seq[start:start + n]
        lg, cache, _ = lsm.prefill(params, jnp.asarray(toks), cache, cfg,
                                   block_table=table, start=start, length=n)
        out[start + n - 1] = np.asarray(lg[0])
    for pos in range(n_prompt, len(seq)):
        lg, cache, _ = lsm.decode(
            params, jnp.asarray(seq[pos:pos + 1]), cache,
            jnp.asarray([pos], jnp.int32), jnp.asarray(table[None]), cfg,
            selections=select)
        out[pos] = np.asarray(lg[0])
    return out


# -- (a) the whole-sequence forward ------------------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(40, 1), prompt(40, 2)]))
    want = ref.logits(params, toks, TINY)
    got = lsm.forward(params, toks, config())
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


# -- (b) prefill in chunks, then decode, through the pool --------------------

@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_paged_prefill_and_decode_match_the_reference(params, impl):
    """Logits, not tokens: with random weights the largest logit turns
    on rounding. The prompt (37) is longer than the top-k (12), chunks
    of 16 leave a ragged tail, and the block table is out of order."""
    seq = prompt(45, 3)
    want = np.asarray(ref.logits(params, jnp.asarray(seq[None]), TINY))[0]
    got = paged_logits(params, config(impl), seq, n_prompt=37)
    assert sorted(got) == [15, 31, 36] + list(range(37, 45))
    for pos, lg in got.items():
        np.testing.assert_allclose(lg, want[pos], rtol=0, atol=TOL,
                                   err_msg=f"position {pos}")


def test_engine_streams_the_reference_s_logprobs(params):
    """Through `InferenceEngine`: three slots, prompts longer than the
    top-k, prefill a chunk a tick beside decoding streams."""
    cfg = config()
    eng = InferenceEngine(params, cfg, slots=3, max_len=128,
                          cache_blocks=40, prefill_chunk=16)
    prompts = [prompt(n, 10 + n) for n in (37, 50, 21, 64)]
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    for rid, p in zip(rids, prompts):
        got = [(int(t), t.logprob) for t in eng.tokens_for(rid)]
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        want = np.asarray(ref.token_logprobs(
            params, jnp.asarray(seq[None]), TINY))[0, len(p) - 1:]
        np.testing.assert_allclose([lp for _, lp in got], want, rtol=0,
                                   atol=TOL)
    assert eng.stats()["decode_traces"] == 1
    eng.check_invariants()


# -- (c) the selected sets ---------------------------------------------------

def test_selected_sets_are_the_reference_s_exact_top_k(params):
    """A decode step's selection (positions, from `jax.lax.top_k` of the
    kernel's scores) and the whole-sequence forward's (a mask) are the
    reference's S_t; a "shared" layer computes none of its own."""
    seq = prompt(44, 4)
    want = []
    ref.features(params, jnp.asarray(seq), TINY, selections=want)
    assert len(want) == 2                       # the two "full" layers
    k = TINY["index_topk"]
    assert all(int(m[-1].sum()) == k for m in want)
    mine = []
    lsm.forward(params, jnp.asarray(seq[None]), config(), selections=mine)
    assert len(mine) == 2
    for a, b in zip(mine, want):
        assert bool(jnp.all(a == b))
    picked = []
    paged_logits(params, config("pallas"), seq, n_prompt=40, select=picked)
    assert len(picked) == 2 * 4                 # 2 "full" layers x 4 steps
    for step, pos in enumerate(range(40, 44)):
        for layer in range(2):
            idx = np.asarray(picked[2 * step + layer][0])
            assert sorted(idx) == list(np.flatnonzero(
                np.asarray(want[layer][pos])))


def test_decode_selects_across_row_chunks_and_index_steps():
    """A context of 620 and a top-k of 300 at the tiny widths: a decode
    step's `index_scores` walks two grid steps (the second partly live),
    `sparse_latent_decode` two chunks of rows (the second partly live),
    as the cell's do at 2048 of 6,000; logits and selected sets against
    the reference."""
    over = {"index_topk": 300, "max_position_embeddings": 1024}
    tiny = {**TINY, **over}
    params = ref.init_params(jax.random.key(1), tiny)
    seq = prompt(623, 6)
    want_sets = []
    ref.features(params, jnp.asarray(seq), tiny, selections=want_sets)
    want = np.asarray(ref.logits(params, jnp.asarray(seq[None]), tiny))[0]
    picked = []
    got = paged_logits(params, config("pallas", **over), seq, n_prompt=620,
                       chunk=128, select=picked, table_blocks=48)
    for pos, lg in got.items():
        np.testing.assert_allclose(lg, want[pos], rtol=0, atol=TOL,
                                   err_msg=f"position {pos}")
    for step, pos in enumerate(range(620, 623)):
        for layer in range(2):
            idx = np.asarray(picked[2 * step + layer][0])
            assert sorted(idx) == list(np.flatnonzero(
                np.asarray(want_sets[layer][pos])))


def test_selection_takes_every_position_while_there_are_no_more(params):
    seq = prompt(9, 5)                          # shorter than the top-k
    want = np.asarray(ref.logits(params, jnp.asarray(seq[None]), TINY))[0]
    got = paged_logits(params, config("pallas"), seq, n_prompt=5, chunk=8)
    for pos, lg in got.items():
        np.testing.assert_allclose(lg, want[pos], rtol=0, atol=TOL)


def _rows(case):
    """-> (scores [N, S] f32 with -inf where not live, live bool [N, S], k,
    block, n_blocks) of one case of the counted selection."""
    rng = np.random.default_rng(11)
    n, s, k, block, n_blocks = 6, 96, 20, None, 1
    x = rng.normal(size=(n, s)).astype(np.float32)
    live = np.ones((n, s), bool)
    if case == "signs_and_zeros":
        # positives, negatives and both zeros, the zeros across the k-th
        # place: +0.0 is taken before -0.0 wherever either lies
        x[:, ::3] = np.where(rng.random((n, s // 3)) < 0.5, 0.0, -0.0)
        x[0] = -np.abs(x[0])
        x[1] = np.abs(x[1])
        x[2, 12:] = -0.0
        x[2, 40:50] = 0.0
    elif case == "ties_across_the_kth_place":
        x = np.round(x * 2) / 2 + 0.0           # runs of equal scores
        x[0] = 1.0                              # a row of one value
        x[1, :15], x[1, 15:] = 3.0, 2.0         # 15 above, 81 tied for 5
        x[2, -k:] = 9.0                         # exactly k above the rest
    elif case == "fewer_live_than_k":
        live = np.arange(s)[None, :] <= np.array(
            [0, 3, 18, 19, 20, 50])[:, None]
        x[3, 5] = -np.inf                       # a live score of -inf
    elif case == "rows_of_minus_infinity":
        live[1] = live[4] = False
        live[2, 7:] = False
        x[5] = -np.inf                          # live, and every score -inf
    elif case == "ragged_live_width":
        # a mask of three blocks of 16; the live width (43) ends inside
        # the third
        block, n_blocks = 16, 3
        live = np.arange(s)[None, :] <= np.array(
            [30, 36, 40, 41, 42, 42])[:, None]
    elif case == "blocks_with_ties":
        block, n_blocks, k = 32, 3, 40
        x = np.round(x) + 0.0                   # no -0.0
    else:
        raise ValueError(case)
    x = np.where(live, x, -np.inf).astype(np.float32)
    return x, live, k, block, n_blocks


@pytest.mark.parametrize("case", [
    "signs_and_zeros", "ties_across_the_kth_place", "fewer_live_than_k",
    "rows_of_minus_infinity", "ragged_live_width", "blocks_with_ties"])
def test_counted_selection_is_top_k_s_set(case):
    """`_select_dense` finds its threshold by counting; the set it takes is
    the one `jax.lax.top_k`'s indices name (the earliest positions of equal
    scores), bit for bit, on the rows a sort orders by more than `<`."""
    x, live, k, block, n_blocks = _rows(case)
    n, s = x.shape
    _, idx = jax.lax.top_k(jnp.asarray(x), k)
    want = np.zeros((n, s), bool)
    want[np.arange(n)[:, None], np.asarray(idx)] = True
    want &= live
    assert block is None or not live[:, n_blocks * block:].any()
    got = jax.jit(lsm._select_dense, static_argnums=(2, 3))(
        jnp.asarray(x), jnp.asarray(live), k, block, n_blocks)
    assert want.sum() > 0 and np.array_equal(np.asarray(got), want)
    # the reference's mask is the same set wherever no -0.0 meets a +0.0
    # at the threshold (it compares floats, and takes them as equal)
    if case != "signs_and_zeros":
        assert np.array_equal(
            np.asarray(ref.top_k_mask(jnp.asarray(x), k)) & live, want)


CROSSING = {"index_topk": 24}       # chunks of 16: under, across, past


def test_chunks_across_the_top_k_select_what_forward_selects(params,
                                                             monkeypatch):
    """A prompt of 48 in chunks of 16 under a top-k of 24: the first chunk
    lies wholly under it (every live position, no threshold), the second
    straddles it, the third is past it; each "full" layer's mask is the
    whole-sequence forward's rows, and nothing past the chunk is set."""
    cfg = config(**CROSSING)
    seq = prompt(48, 8)
    want = []
    lsm.forward(params, jnp.asarray(seq[None]), cfg, selections=want)
    taken = []
    real = lsm._prefill_select

    def kept(*args):
        taken.append(real(*args))
        return taken[-1]

    monkeypatch.setattr(lsm, "_prefill_select", kept)
    got = paged_logits(params, cfg, seq, n_prompt=48)
    assert len(taken) == 2 * 3
    for chunk in range(3):
        rows = slice(16 * chunk, 16 * chunk + 16)
        for layer in range(2):
            mask = np.asarray(taken[2 * chunk + layer])
            assert np.array_equal(mask[:, :48], np.asarray(want[layer][rows]))
            assert not mask[:, 48:].any()
    assert int(want[0][15].sum()) == 16 and int(want[0][40].sum()) == 24
    tiny = {**TINY, **CROSSING}
    ref_logits = np.asarray(
        ref.logits(params, jnp.asarray(seq[None]), tiny))[0]
    for pos, lg in got.items():
        np.testing.assert_allclose(lg, ref_logits[pos], rtol=0, atol=TOL)


def test_stats_count_the_chunks_that_took_a_threshold(params):
    """`index_chunk_selections`: the "full" layers of every prefill chunk;
    `index_chunk_thresholds`: of the chunks whose context is past the
    top-k; a decode step adds to neither."""
    eng = InferenceEngine(params, config(**CROSSING), slots=2, max_len=128,
                          cache_blocks=40, prefill_chunk=16)
    eng.submit(prompt(48, 8), max_new_tokens=1)      # last: 15, 31, 47
    eng.submit(prompt(20, 9), max_new_tokens=1)      # last: 15, 19
    eng.run_until_idle()
    s = eng.stats()
    assert s["prefill_chunks"] == 5
    assert s["index_chunk_selections"] == 2 * 5
    assert s["index_chunk_thresholds"] == 2 * 2
    rid = eng.submit(prompt(10, 10), max_new_tokens=6)
    eng.run_until_idle()
    assert len(list(eng.tokens_for(rid))) == 6
    s = eng.stats()
    assert s["decode_steps"] >= 5 and s["prefill_chunks"] == 6
    assert s["index_chunk_selections"] == 2 * 6
    assert s["index_chunk_thresholds"] == 2 * 2
    assert s["index_layer_runs"] == 2 * (s["decode_steps"] + 6)


# -- (d) the share test ------------------------------------------------------

@pytest.mark.parametrize("n_shared", [1, 2])
def test_the_shares_add_up_to_the_uncut_layer(n_shared):
    """Every share's routed part, plus the shared experts counted once
    (two of them are one gated MLP of twice the width), is the uncut
    reference's whole expert layer: 8 experts in shares of 4 + 4 and of
    2 + 6 (a ragged cut)."""
    uncut = {**TINY, "n_routed_experts": 8, "experts_held_from": 0,
             "n_shared_experts": n_shared}
    lp = ref.init_params(jax.random.key(7), uncut)["layers"][1]
    h2 = jax.random.normal(jax.random.key(8), (48, TINY["hidden_size"]))
    want = ref.feed_forward(h2, lp, uncut)
    for cuts in ([0, 4, 8], [0, 2, 8]):
        total = ref.shared_part(h2, lp)
        for lo, hi in zip(cuts, cuts[1:]):
            share = {**lp, **{n: lp[n][lo:hi]
                              for n in ("we_gate", "we_up", "we_down")}}
            cfg = config("pallas", n_routed_experts=hi - lo,
                         experts_held_from=lo, n_shared_experts=n_shared)
            assert share["ws_gate"].shape[1] == 32 * n_shared
            routed, shared, _, counts = blocks.expert_layer(
                h2, share, cfg.experts, cfg.activation_dtype())
            np.testing.assert_allclose(shared, ref.shared_part(h2, lp),
                                       rtol=0, atol=TOL)
            assert int(counts[1]) == 48 * 2     # every pair is routed
            total = total + routed
        np.testing.assert_allclose(total, want, rtol=0, atol=TOL)


# -- the three kernels against their plain paths ----------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_pack_and_unpack(dtype):
    x = jax.random.normal(jax.random.key(1), (5, 40)).astype(dtype)
    words = sparse_latent.row_words(40, dtype)
    assert words == 128
    packed = sparse_latent.pack_rows(x, words)
    assert packed.shape == (5, words) and packed.dtype == jnp.uint32
    assert bool(jnp.all(sparse_latent.unpack_rows(packed, 40, dtype) == x))
    q = sparse_latent.split_query(x, words)
    assert bool(jnp.all(sparse_latent.join_parts(q, 40) == x))


# one chunk of rows, and the cell's 2048 (eight chunks of `ROW_CHUNK`, two
# buffers in turn, the running maximum rescaled) with ragged counts
@pytest.mark.parametrize("k,counts", [(24, (24, 7, 1)),
                                      (2048, (2048, 700, 257))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_latent_decode_kernel(dtype, k, counts):
    b, h, values, n_rows = 3, 4, 40, 2 * k + 48
    assert k < sparse_latent.ROW_CHUNK or k >= 3 * sparse_latent.ROW_CHUNK
    words = sparse_latent.row_words(values, dtype)
    keys = jax.random.split(jax.random.key(2), 3)
    data = jax.random.normal(keys[0], (n_rows, values)).astype(dtype)
    pool = sparse_latent.pack_rows(data, words)[:, None, :]
    # scores of spread 3: the maximum moves from chunk to chunk
    q = sparse_latent.split_query((0.5 * jax.random.normal(
        keys[1], (b, h, values))).astype(dtype), words)
    rows = jax.random.randint(keys[2], (b, k), 1, n_rows)
    count = jnp.asarray(counts, jnp.int32)
    got, want = (sparse_latent.join_parts(sparse_latent.sparse_latent_decode(
        q, pool, rows, count, dtype=dtype, impl=impl), values)
        for impl in ("pallas", "jax"))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 if dtype == jnp.float32 else 5e-3)
    # by hand, stream 1: softmax over its live rows
    picked = data[rows[1, :counts[1]]].astype(jnp.float32)
    p = jax.nn.softmax(jnp.einsum(
        "hv,kv->hk", sparse_latent.join_parts(q, values)[1].astype(
            jnp.float32), picked), -1)
    np.testing.assert_allclose(want[1], p @ picked, rtol=0, atol=5e-3)


# one grid step, and three of `INDEX_STEP_TOKENS` with a stream that ends
# three positions into the third, one inside the second and one at 0
@pytest.mark.parametrize("mb,positions", [(4, (63, 20, 0)),
                                          (96, (1027, 700, 0))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_index_scores_kernel(dtype, mb, positions):
    b, j, di, nb = 3, 8, 16, 3 * mb
    assert mb * BS < sparse_latent.INDEX_STEP_TOKENS or \
        max(positions) >= 2 * sparse_latent.INDEX_STEP_TOKENS + 3
    keys = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(keys[0], (b, j, di))
    w = jax.random.normal(keys[1], (b, j))
    pool = jax.random.normal(keys[2], (nb, BS, di)).astype(dtype)
    tables = jax.random.randint(keys[3], (b, mb), 1, nb)
    pos = jnp.asarray(positions, jnp.int32)
    got, want = (sparse_latent.index_scores(q, w, pool, tables, pos,
                                            impl=impl)
                 for impl in ("pallas", "jax"))
    live = np.arange(mb * BS)[None, :] <= np.asarray(pos)[:, None]
    assert np.all(np.isneginf(np.asarray(got)[~live]))
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=0, atol=1e-4)
    # the order the top-k reads is the plain path's
    for a, c, n in zip(np.asarray(got), np.asarray(want), positions):
        assert list(np.argsort(-a[:n + 1], kind="stable")[:16]) == \
            list(np.argsort(-c[:n + 1], kind="stable")[:16])


@pytest.mark.parametrize("n", [6, 160])      # row tiles of 16 and of 128
def test_experts_grouped_kernel(n):
    d, f, held, width, k = 64, 32, 4, 8, 2
    keys = jax.random.split(jax.random.key(4), 6)
    x = jax.random.normal(keys[0], (n, d))
    chosen = jnp.argsort(jax.random.uniform(keys[1], (n, width)))[:, :k]
    chosen = chosen.at[0].set(-1)               # a row that counts nothing
    weights = jax.random.uniform(keys[2], (n, k))
    ws = [jax.random.normal(kk, (held, f, d)) * d ** -0.5
          for kk in keys[3:]]
    (got, load), (want, load2) = (grouped_experts.experts_grouped(
        x, chosen, weights, *ws, held_from=2, impl=impl)
        for impl in ("pallas", "jax"))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    mine = (np.asarray(chosen) >= 2) & (np.asarray(chosen) < 6)
    assert int(load.sum()) == int(load2.sum()) == int(mine.sum())
    assert np.all(np.asarray(got)[0] == 0)


def test_experts_grouped_with_no_token_here():
    x = jnp.ones((4, 64))
    ws = [jnp.ones((2, 32, 64))] * 3
    got, load = grouped_experts.experts_grouped(
        x, jnp.full((4, 2), 7), jnp.ones((4, 2)), *ws, held_from=0,
        impl="pallas")
    assert int(load.sum()) == 0 and not np.any(np.asarray(got))


# rows, experts a token, held experts -> row tile, at every expert call of
# the benchmark's cells (`tests/test_aot_tpu_compile.py:EXPERT_SHAPES`'
# seven, nemotron's step, lfm2's step and both its chunk buckets, mellum2's
# training chunk). Only lfm2, which holds every expert of its router, hands
# an expert more than 8 pairs of a call under 1,024
ROW_TILES = {
    "glm-5.2.decode": (16, 8, 16, 16),
    "glm-5.2.prefill": (512, 8, 16, 128),
    "kanana-2-30b-a3b.train": (16384, 6, 16, 128),
    "ling-3.0-flash-vl.decode": (64, 8, 128, 16),
    "ling-3.0-flash-vl.prefill": (512, 8, 128, 128),
    "command-a-plus.decode": (16, 8, 16, 16),
    "command-a-plus.prefill": (512, 8, 16, 128),
    "nemotron-3-super.decode": (64, 22, 128, 128),
    "mellum2-12b-a2.5b.train": (8192, 8, 16, 128),
    "lfm2-8b-a1b.decode": (128, 4, 32, 32),
    "lfm2-8b-a1b.prefill_512": (512, 4, 32, 128),
    "lfm2-8b-a1b.prefill_128": (128, 4, 32, 32),
}


@pytest.mark.parametrize("case", sorted(ROW_TILES))
def test_row_tile_at_the_benchmark_s_shapes(case):
    """The tile is what it was (16 under 1,024 pairs, 128 from there) in
    every call but lfm2's two of 512 pairs over 32 experts, where 32 holds
    the 16 pairs an expert expects twice over."""
    n, k, held, tile = ROW_TILES[case]
    assert grouped_experts.row_tile(n * k, held) == tile
    if n * k < 1024:
        assert tile * held >= 2 * n * k
        assert tile == 16 or (tile // 2) * held < 2 * n * k


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_experts_grouped_at_a_tile_of_32(gated):
    """64 rows x 2 over 8 held experts plans tiles of 32. One expert gets
    no pair, one exactly a tile, one 37 (a second tile, which must be as
    right as the first), the rest what is left; unheld ids beside them."""
    n, d, f, held, k = 64, 64, 32, 8, 2
    assert grouped_experts.row_tile(n * k, held) == 32
    # held experts 3 .. 10 of a router of 12: expert 3 none, 4 a tile, 5 37
    ids = np.concatenate([np.full(32, 4), np.full(37, 5),
                          np.resize([6, 7, 8, 9, 10, 0, 11], 59)])
    chosen = jnp.asarray(np.random.default_rng(0).permutation(ids)
                         .reshape(n, k).astype(np.int32))
    keys = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(keys[0], (n, d))
    weights = jax.random.uniform(keys[1], (n, k))
    w_gate, w_up, w_down = (jax.random.normal(kk, (held, f, d)) * d ** -0.5
                            for kk in keys[2:])
    if not gated:
        w_gate = None
    got, load = grouped_experts.experts_grouped(
        x, chosen, weights, w_gate, w_up, w_down, held_from=3, impl="pallas")
    want = grouped_experts.reference_experts_grouped(
        x, chosen, weights, w_gate, w_up, w_down, held_from=3)
    assert list(np.asarray(load)[:3]) == [0, 32, 37]
    layout = grouped_experts.group_layout(chosen, 3, held, 32)
    # expert 5's two tiles follow expert 4's one: a tile more than the
    # seven experts that got a pair
    assert list(np.asarray(layout[2])[:3]) == [1, 2, 2]
    assert int(layout[4]) == 1 + int(np.sum(np.asarray(load) > 0)) == 8
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


# -- (e) the engine's invariants with the new family -------------------------

def make_engine(params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 128)
    kw.setdefault("prefill_chunk", 16)
    return InferenceEngine(params, config(), **kw)


def stream(eng, rid):
    return [(int(t), t.logprob) for t in eng.tokens_for(rid)]


def same_stream(got, base):
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in base], rtol=0, atol=1e-4)


def test_one_decode_program_and_the_family_s_counters(params):
    eng = make_engine(params, cache_blocks=48)
    rids = [eng.submit(prompt(20 + 7 * i, 30 + i), max_new_tokens=5 + i)
            for i in range(7)]
    eng.run_until_idle()
    assert all(len(stream(eng, r)) == 5 + i for i, r in enumerate(rids))
    s = eng.stats()
    assert s["decode_traces"] == 1 and s["retraces_unexpected"] == 0
    # latent rows of 40 float32 values in 128 words, 4 layers; index keys
    # of 16 values, 2 layers
    assert s["kv_bytes_per_token"] == 4 * 128 * 4 + 2 * 16 * 4
    assert s["pool_bytes"] == sum(a.nbytes for a in eng.cache.values())
    calls = s["decode_steps"] + s["prefill_chunks"]
    assert s["index_layer_runs"] == 2 * calls
    assert s["index_layer_reuses"] == 2 * calls
    assert 0 < s["index_selected_tokens"] < s["index_scanned_tokens"]
    tokens = s["prefill_tokens"] + s["decode_tokens"]
    assert s["expert_tokens_routed"] == tokens * 2 * 3      # k x layers
    assert 0.3 < s["expert_tokens_here"] / s["expert_tokens_routed"] < 0.7
    assert s["expert_load_max_over_mean"] >= 1.0
    eng.reset_stats()
    assert eng.stats()["expert_tokens_routed"] == 0
    eng.check_invariants()


def test_a_shared_prefix_hits_the_radix_tree_and_copies_a_block(params):
    """Both kinds of state ride the block moves: a prompt that shares two
    blocks and half of a third with an earlier one prefills only its own
    part, copies the partly matched block, and streams what it streams
    alone."""
    a = prompt(60, 40)
    b = np.concatenate([a[:40], prompt(15, 41)])
    alone = make_engine(params, cache_blocks=48)
    base = stream(alone, alone.submit(b, max_new_tokens=6))
    eng = make_engine(params, cache_blocks=48)
    stream(eng, eng.submit(a, max_new_tokens=4))
    got = stream(eng, eng.submit(b, max_new_tokens=6))
    s = eng.stats()
    assert s["prefix_hit_tokens"] == 40 and s["cow_copies"] == 1
    same_stream(got, base)
    eng.check_invariants()


def test_preempt_and_resume(params):
    base_eng = make_engine(params, cache_blocks=48)
    base = stream(base_eng, base_eng.submit(prompt(30, 50),
                                            max_new_tokens=8))
    faults.install(faults.FaultPlan(seed=3).fail("engine.preempt", at=4,
                                                 times=1))
    eng = make_engine(params, cache_blocks=48)
    rid = eng.submit(prompt(30, 50), max_new_tokens=8)
    eng.run_until_idle()
    assert eng.stats()["preemptions"] == 1
    same_stream(stream(eng, rid), base)
    eng.check_invariants()


def test_handoff_carries_both_kinds_of_state(params):
    """`serve/disagg.py`'s hand-off: a prefill engine exports the
    prompt's blocks of every array of the pool, a decode engine imports
    them and streams what one engine streams."""
    p = prompt(37, 60)
    one = make_engine(params, cache_blocks=48)
    base = stream(one, one.submit(p, max_new_tokens=6))
    pre = make_engine(params, cache_blocks=48, role="prefill")
    rid = pre.submit(p, max_new_tokens=6)
    blob = pre.handoff_for(rid)
    assert set(blob["payload"][0]) == {"latent", "index"}
    dec = make_engine(params, cache_blocks=48, role="decode")
    same_stream(stream(dec, dec.import_handoff(blob)), base)
    dec.check_invariants()


# -- (f) speculative decoding is refused -------------------------------------

@pytest.mark.parametrize("spec", ["ngram", "draft"])
def test_spec_is_refused_for_a_family_without_a_verify_step(params, spec):
    with pytest.raises(ValueError, match="no verify step"):
        InferenceEngine(params, config(), slots=2, max_len=64, spec=spec,
                        draft_params=params, draft_cfg=config())


def test_the_family_has_no_int8_pool(params):
    """`kv_dtype` is `models/gpt.py`'s, where it shrinks the pool; here
    nothing would shrink, so the name is refused. What the benchmark's
    control rounds rows with is `cache_round`, and it moves the logits."""
    with pytest.raises(TypeError, match="kv_dtype"):
        config(kv_dtype="int8")
    with pytest.raises(ValueError, match="cache_round"):
        config(cache_round="int4")
    seq = prompt(45, 3)
    sound = paged_logits(params, config(), seq, n_prompt=37)
    rounded = paged_logits(params, config(cache_round="int8"), seq,
                           n_prompt=37)
    assert max(float(np.max(np.abs(rounded[p] - sound[p])))
               for p in sound) > 50 * TOL


# -- (g) the gpt family through the interface --------------------------------

def _sha(fn, *args):
    return hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest()


def test_gpt_lowers_to_the_programs_it_lowered_to_before():
    """The engine's prefill and decode programs for `models/gpt.py`,
    built through `GPTConfig.family`, against the same two functions
    written the way the engine wrote them before the interface (calling
    `gpt.prefill_paged` / `gpt.decode_step_paged` by name): the same
    lowered text, to the byte."""
    cfg = gpt.small(dtype="float32")
    p = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(p, cfg, slots=4, max_len=64, cache_blocks=24)

    def _sample(logits, temps, key, step):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, step)
        safe = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.random.categorical(
            k, logits.astype(jnp.float32) / safe[:, None]
        ).astype(jnp.int32)
        tok = jnp.where(temps > 0, sampled, greedy)
        nat = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = jnp.take_along_axis(nat, tok[:, None], axis=-1)[:, 0]
        return tok, logp

    def _prefill(params, inputs, cache, key):
        tokens, table, start, length, temp, step = unpack_chunk(
            inputs, eng.max_blocks)
        logits, cache = gpt.prefill_paged(
            params, tokens, cache, cfg, None, block_table=table,
            start=start, length=length)
        tok, logp = _sample(logits, temp[None], key, step)
        return tok[0], logp[0], cache

    def _decode(params, cache, inputs, key, prev, chunk_tok):
        tokens, pos, temps, tables, step = unpack_rows(inputs, 4)
        tokens = jnp.where(
            tokens == FROM_STEP, prev,
            jnp.where(tokens == FROM_CHUNK, chunk_tok, tokens))
        logits, cache = gpt.decode_step_paged(
            params, tokens, cache, pos, tables, cfg, None)
        tok, logp = _sample(logits, temps, key, step)
        return tok, logp, cache

    i32, f32 = np.int32, np.float32
    key = jax.random.PRNGKey(0)
    # each program's host-built input is one packed int32 array
    # (tests/test_packed_inputs.py holds the layouts to the bit)
    decode_args = (p, eng.cache, pack_rows(
        np.zeros(4, i32), np.zeros(4, i32), np.zeros(4, f32),
        np.zeros((4, eng.max_blocks), i32), 0), key, np.zeros(4, i32),
        i32(0))
    assert _sha(eng._decode_fn, *decode_args) == _sha(
        jax.jit(_decode, donate_argnums=(1,)), *decode_args)
    prefill_args = (p, pack_chunk(
        np.zeros(16, i32), 16, np.zeros(eng.max_blocks, i32), 0, 0.0, 0),
        eng.cache, key)
    assert _sha(eng._prefill_fn, *prefill_args) == _sha(
        jax.jit(_prefill, donate_argnums=(2,)), *prefill_args)
    # and its streams do not change: the family adds nothing to stats()
    assert "expert_tokens_routed" not in eng.stats()
