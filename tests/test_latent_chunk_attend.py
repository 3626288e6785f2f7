"""`ops/sparse_latent.latent_chunk_attend`, the latent family's prefill
chunk over its cached context: the kernel in interpret mode against
`reference_latent_chunk_attend` and against a softmax written out by
hand, at the two cells' head shapes and tiny otherwise; the model's
`_prefill_attend` around it against expanded heads; a chunked prefill
whose context spans two blocks against the plain reference's logits; and
the family's `chunk_attend_blocks` counted by hand."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import latent_sparse_moe as ref
from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.ops import sparse_latent
from ray_tpu.serve.engine import InferenceEngine

from test_latent_sparse_moe import TINY, TOL, config, prompt

# the two cells' head shapes (nope, rope, v, rows a page), the latent
# rank and the heads tiny
SHAPES = {"glm-5.2": (192, 64, 256, 16), "ling-3.0-flash-vl": (128, 64, 128,
                                                               128)}
KV_RANK, HEADS, LAYERS = 32, 2, 3
TABLE = 3072            # positions of a table: three context blocks


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def case(shape: str, mask: str, dtype, c: int, start: int, length: int):
    """A chunk of `c` queries, `length` of them live from `start` on, over
    a context whose pages lie scattered through layer 1 of a pool; the
    pages of the blocks past the chunk's last position, and every page no
    table names, hold NaN. -> (the model's config, q_nope, q_rope, pool,
    table, positions, valid, selected, the layer's weights, the context's
    rows [TABLE, R])."""
    nope, rope, v, bs = SHAPES[shape]
    cfg = lsm.LatentSparseMoEConfig(
        n_heads=HEADS, kv_rank=KV_RANK, nope_dim=nope, rope_dim=rope,
        v_dim=v, n_layers=LAYERS, index_topk=40,
        indexer_types=("full",) * LAYERS, mlp_types=("dense",) * LAYERS,
        dtype=jnp.dtype(dtype).name)
    keys = jax.random.split(jax.random.key(5), 6)
    mb = TABLE // bs
    sb = sparse_latent.context_block(TABLE, bs)
    assert sb == 1024
    positions = start + jnp.arange(c, dtype=jnp.int32)
    valid = jnp.arange(c) < length
    last = start + length - 1
    live_pages = (last // sb + 1) * sb // bs
    table = np.zeros(mb, np.int32)
    table[:live_pages] = 1 + np.asarray(jax.random.permutation(
        keys[0], mb + 7))[:live_pages]
    table[live_pages:] = mb + 8 + np.arange(mb - live_pages)    # poisoned
    rows = jax.random.normal(keys[1], (TABLE, cfg.row_values)).astype(dtype)
    packed = sparse_latent.pack_rows(rows, cfg.row_words).reshape(
        mb, bs, 1, -1)
    pool = np.full((LAYERS, 2 * mb + 8, bs, 1, cfg.row_words), 0x7FC07FC0,
                   np.uint32)                   # NaN in either row format
    pool[1, table[:live_pages]] = np.asarray(packed[:live_pages])
    q_nope = (0.3 * jax.random.normal(keys[2], (c, HEADS, nope))
              ).astype(dtype)
    q_rope = (0.3 * jax.random.normal(keys[3], (c, HEADS, rope))
              ).astype(dtype)
    lp = {"wkv_b": jax.random.normal(
        keys[4], (KV_RANK, HEADS * (nope + v))) * KV_RANK ** -0.5}
    live = lsm.every_earlier(positions, valid, TABLE)
    if mask == "selected":      # each live query's top 40 of random scores
        scores = jnp.where(live, jax.random.normal(keys[5], (c, TABLE)),
                           -jnp.inf)
        selected = lsm._select_dense(scores, live, cfg.index_topk)
        assert int(jnp.sum(selected[0])) == 40
    else:
        selected = live
    return (cfg, q_nope, q_rope, jnp.asarray(pool), jnp.asarray(table),
            positions, valid, selected, lp, rows)


# a chunk that starts past 0 and is not full (its padded queries select
# nothing), and whose last position, 1500, ends inside the second of three
# blocks: the third block's pages are poisoned. One chunk inside the first
# block. In bfloat16 and in float32 rows
CASES = [(shape, mask, dtype, c, start, length)
         for shape in SHAPES for mask in ("selected", "every_earlier")
         for dtype, c, start, length in [(jnp.float32, 16, 1488, 13),
                                         (jnp.bfloat16, 32, 1470, 31),
                                         (jnp.float32, 8, 200, 8)]]


@pytest.mark.parametrize("shape,mask,dtype,c,start,length", CASES)
def test_the_kernel_is_the_reference_and_the_softmax_by_hand(
        shape, mask, dtype, c, start, length):
    cfg, q_nope, q_rope, pool, table, positions, valid, selected, lp, rows \
        = case(shape, mask, dtype, c, start, length)
    got, want = (np.asarray(lsm.prefill_attend(
        q_nope, q_rope, pool, 1, table, positions, valid, selected, lp,
        dataclasses.replace(cfg, sparse_impl=impl)), np.float32)
        for impl in ("pallas", "jax"))
    assert got.shape == (c, HEADS * cfg.v_dim)
    assert np.all(np.isfinite(got))             # padded queries as well
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    # by hand, expanded heads (`attend_full`'s form), the live queries
    up = lsm._kv_up(lp, cfg, jnp.float32)
    kv = jnp.einsum("sc,chd->shd", rows[:, :KV_RANK].astype(jnp.float32),
                    up)
    s = (jnp.einsum("qhd,shd->hqs", q_nope.astype(jnp.float32),
                    kv[..., :cfg.nope_dim])
         + jnp.einsum("qhd,sd->hqs", q_rope.astype(jnp.float32),
                      rows[:, KV_RANK:].astype(jnp.float32)))
    p = jax.nn.softmax(jnp.where(selected[None], s * lsm._sm_scale(cfg),
                                 -1e30), -1)
    hand = jnp.einsum("hqs,shd->qhd", p, kv[..., cfg.nope_dim:]).reshape(
        c, -1)
    assert float(jnp.max(jnp.abs(hand[:length]))) > 0.1
    np.testing.assert_allclose(got[:length], hand[:length], rtol=0,
                               atol=1e-4 if dtype == jnp.float32 else 5e-2)


def test_the_op_walks_only_as_far_as_the_last_position():
    """The op itself, a layer and a table of its own: the kernel's and the
    reference's mixes of rows are equal to float32's rounding, whatever
    lies in the blocks past `last`."""
    _, _, q_rope, pool, table, _, _, selected, _, _ = case(
        "glm-5.2", "selected", jnp.float32, 16, 1010, 16)
    q = jnp.concatenate([jnp.zeros((HEADS, 16, KV_RANK)),
                         q_rope.transpose(1, 0, 2)], -1)
    outs = [sparse_latent.latent_chunk_attend(
        q, pool, 1, table, selected, jnp.int32(1025), mixed=KV_RANK,
        dtype=jnp.float32, impl=impl) for impl in ("pallas", "jax")]
    assert outs[0].shape == (HEADS, 16, KV_RANK)
    np.testing.assert_allclose(*map(np.asarray, outs), rtol=0, atol=1e-5)
    assert np.all(np.isfinite(np.asarray(outs[0])))


def test_prefill_over_two_context_blocks_gives_the_reference_s_logits():
    """A prompt of 700 in chunks of 64 through a table of 1,280 positions
    (two context blocks of 640, the pages out of order): each chunk's
    logits through the kernel are the plain reference's and the
    `jax.numpy` path's."""
    params = ref.init_params(jax.random.key(0), TINY)
    seq = prompt(700, 11)
    tiny = {**TINY, "max_position_embeddings": 1280}
    want = np.asarray(ref.logits(params, jnp.asarray(seq[None]), tiny))[0]
    got = {}
    for impl in ("pallas", "jax"):
        cfg = config(impl, max_position_embeddings=1280)
        cache = lsm.init_pool(cfg, 82, 16)
        table = np.zeros(80, np.int32)
        table[:44] = 2 + np.arange(44)[::-1]
        assert sparse_latent.context_block(1280, 16) == 640
        step = jax.jit(lambda toks, cache, start, n: lsm.prefill(
            params, toks, cache, cfg, block_table=table, start=start,
            length=n))
        for start in range(0, 700, 64):
            n = min(64, 700 - start)
            toks = np.zeros((1, 64), np.int32)
            toks[0, :n] = seq[start:start + n]
            lg, cache, counts = step(jnp.asarray(toks), cache, start, n)
            got[impl, start + n - 1] = np.asarray(lg[0])
            walked = int(counts[lsm.COUNTS.index("chunk_attend_blocks")])
            assert walked == 4 * ((start + n - 1) // 640 + 1)
    for (impl, pos), lg in got.items():
        np.testing.assert_allclose(lg, want[pos], rtol=0, atol=TOL,
                                   err_msg=f"{impl}, position {pos}")


def test_chunk_attend_blocks_reaches_stats_counted_by_hand():
    """Two chunks by hand: a table of 2,048 positions is two context
    blocks of 1,024; a chunk that ends at position 1,023 walks one block
    in each of the four layers and the next chunk two. Through the engine
    every chunk of a short prompt walks one block a layer; a decode step
    adds nothing."""
    params = ref.init_params(jax.random.key(0), TINY)
    cfg = config(max_position_embeddings=2048)
    at = lsm.COUNTS.index("chunk_attend_blocks")
    cache = lsm.init_pool(cfg, 130, 16)
    table = jnp.arange(1, 129, dtype=jnp.int32)
    toks = jnp.asarray(prompt(16, 3)[None])
    for start, blocks in ((1008, 1), (1024, 2)):
        _, cache, counts = lsm.prefill(params, toks, cache, cfg,
                                       block_table=table, start=start)
        assert int(counts[at]) == 4 * blocks
    eng = InferenceEngine(params, config(), slots=2, max_len=128,
                          cache_blocks=40, prefill_chunk=16)
    eng.submit(prompt(40, 8), max_new_tokens=3)
    eng.run_until_idle()
    s = eng.stats()
    assert s["prefill_chunks"] == 3 and s["decode_steps"] >= 2
    assert s["chunk_attend_blocks"] == 4 * 3
