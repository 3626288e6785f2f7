"""The paged kernel body with grouped key-value heads and a window
(`ops/decode_attention.py`): the decode form and the chunk form against
their plain `jax.numpy` references, over query heads a key-value head in
{1, 5, 16} (5: a group that is no whole sublane tile), window on and
off, and tables whose live pages do not start at column 0 (a window
layer's ring: the columns the window has left name pages that hold later
positions, or the trash page)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import decode_attention as da

BS, D, HKV, NB = 8, 16, 2, 40


def _pools(key, hkv=HKV, layers=2):
    kk, kv = jax.random.split(key)
    shape = (layers, NB, hkv, BS, D)
    return (jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


def _ring_tables(rng, pos_last, window, mb, ring):
    """A table a stream: column `j` names ring page `j % ring` of the
    stream's own pages for the columns that can still be live, 0 (the
    trash page) for those the window has left whole and past the end."""
    b = len(pos_last)
    tables = np.zeros((b, mb), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for i, last in enumerate(pos_last):
        pages = [free.pop() for _ in range(min(ring, last // BS + 1))]
        lo = 0 if window is None else max(0, last - window - BS) // BS
        for j in range(lo, last // BS + 1):
            tables[i, j] = pages[j % len(pages)]
    return jnp.asarray(tables)


def _dense(q, k_pool, v_pool, tables, pos, window, layer):
    """By the definition, from the pages the table names, position by
    position: nothing shared with the code under test but the pool."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, k_pool, v_pool))
    tables, pos = np.asarray(tables), np.asarray(pos)
    b, w, hq, d = q.shape
    g = hq // kp.shape[2]
    out = np.zeros_like(q)
    for i in range(b):
        for t in range(w):
            at = pos[i] + t
            lo = 0 if window is None else max(0, at - window + 1)
            js = np.arange(lo, at + 1)
            for h in range(hq):
                k = kp[layer, tables[i, js // BS], h // g, js % BS]
                v = vp[layer, tables[i, js // BS], h // g, js % BS]
                s = k @ q[i, t, h] * d ** -0.5
                p = np.exp(s - s.max())
                out[i, t, h] = (p / p.sum()) @ v
    return out


# a group of 5 without a window: no configuration pairs the two
GROUPS = pytest.mark.parametrize(
    "g,window", [(1, None), (1, 20), (5, None), (16, None), (16, 20)])


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@GROUPS
def test_decode_form_matches_the_definition(g, window, impl):
    rng = np.random.default_rng(3)
    pos = np.array([0, 5, 37, 75, 130], np.int32)
    mb = 20
    k_pool, v_pool = _pools(jax.random.key(1))
    # a ring of 5 pages holds a window of 20 and a page more
    tables = _ring_tables(rng, pos, window, mb, 5 if window else mb)
    # the positions a ring no longer holds are not in the pool at all:
    # what the table's earlier columns name is later data or trash
    q = jax.random.normal(jax.random.key(2), (len(pos), HKV * g, D))
    got = da.gqa_decode_attention(q, k_pool, v_pool, tables, jnp.asarray(pos),
                                  layer=1, window=window, impl=impl)
    want = _dense(q[:, None], k_pool, v_pool, tables, pos, window, 1)[:, 0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@GROUPS
@pytest.mark.parametrize("start,c", [(0, 16), (13, 16), (64, 8), (91, 16)])
def test_chunk_form_matches_the_definition(start, c, g, window, impl):
    """A chunk's queries each keep their own window: the first pages are
    cut for the later ones inside the mask, a chunk that straddles the
    window's edge (start 13, window 20) among them."""
    rng = np.random.default_rng(4)
    mb = 20
    k_pool, v_pool = _pools(jax.random.key(5))
    last = start + c - 1
    ring = (20 + c) // BS + 2 if window else mb
    table = _ring_tables(rng, [last], window and window + c, mb, ring)[0]
    q = jax.random.normal(jax.random.key(6), (c, HKV * g, D))
    got = da.gqa_chunk_attention(q, k_pool, v_pool, table, jnp.int32(start),
                                 layer=0, window=window, impl=impl)
    want = _dense(q[None], k_pool, v_pool, table[None],
                  np.array([start]), window, 0)[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("start", [0, 37])
def test_a_long_chunk_of_a_group_of_five_goes_in_whole_row_tiles(start):
    """More queries than one program scores (1,024 rows / 5 heads): the
    tile is cut to 200 queries so that its rows are whole sublane tiles,
    and the chunk's 208 go to two programs."""
    assert da._gqa_query_tile(208, 5) == 200
    assert da._gqa_query_tile(512, 16) == da._query_tile(512, 16) == 64
    assert da._gqa_query_tile(1, 5) == 1
    c, g = 208, 5
    rng = np.random.default_rng(7)
    mb = (start + c) // BS + 1
    k_pool, v_pool = _pools(jax.random.key(12), layers=1)
    table = _ring_tables(rng, [start + c - 1], None, mb, mb)[0]
    q = jax.random.normal(jax.random.key(13), (c, HKV * g, D))
    got = da.gqa_chunk_attention(q, k_pool, v_pool, table, jnp.int32(start),
                                 layer=0, impl="pallas")
    want = da.reference_gqa_paged_attention(
        q[None], k_pool[0], v_pool[0], table[None], jnp.array([start]))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_a_window_layer_never_reads_a_page_the_window_has_left():
    """NaNs in every page before the window's first: the kernel's output
    is finite, so none of them was multiplied in."""
    window, pos = 16, np.array([100], np.int32)
    k_pool, v_pool = _pools(jax.random.key(10), layers=1)
    tables = jnp.arange(1, 21, dtype=jnp.int32)[None]
    first_live = (100 - window + 1) // BS
    dead = np.asarray(tables[0, :first_live])
    k_pool = k_pool.at[0, dead].set(jnp.nan)
    v_pool = v_pool.at[0, dead].set(jnp.nan)
    q = jax.random.normal(jax.random.key(11), (1, HKV * 16, D))
    got = da.gqa_decode_attention(q, k_pool, v_pool, tables,
                                  jnp.asarray(pos), layer=0, window=window,
                                  impl="pallas")
    assert np.isfinite(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# a head of half a lane tile: two key-value heads side by side in a page
# ---------------------------------------------------------------------------

D64, HKV64, G64 = 64, 8, 4      # LFM2's: 32 query heads over 8 of 64


def _paired_pools(key, layers=2):
    """(pools as a family of `gqa_pack` 2 stores them `[L, NB, 4, BS,
    128]`, the same numbers head by head `[L, NB, 8, BS, 64]`)."""
    k_pool, v_pool = (jax.random.normal(k, (layers, NB, HKV64, BS, D64),
                                        jnp.float32)
                      for k in jax.random.split(key))
    pair = lambda p: p.reshape(layers, NB, HKV64 // 2, 2, BS, D64).transpose(
        0, 1, 2, 4, 3, 5).reshape(layers, NB, HKV64 // 2, BS, 2 * D64)
    return (pair(k_pool), pair(v_pool)), (k_pool, v_pool)


def test_a_head_of_64_lies_two_to_a_lane_tile():
    assert da.gqa_pack(8, 64) == 2 and da.gqa_pack(8, 128) == 1
    assert da.gqa_pack(2, 32) == 1 and da.gqa_pack(8, 256) == 1
    rows = jnp.arange(3 * 8 * 64).reshape(3, 8, 64)
    side = da.heads_side_by_side(rows, 2)
    assert side.shape == (3, 4, 128)
    assert (side[:, 1, 64:] == rows[:, 3]).all()
    # the plan the cell's shapes run at: whole tiles, the default scope
    for w in (1, 128):
        assert da._gqa_plan(128, 8, 128, jnp.bfloat16, w) is not None
    assert da._gqa_plan(128, 4, 64, jnp.bfloat16, 1) is None


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("pos", [
    (0, 5, 37, 75, 130),        # ragged, a last page part full
    (7, 15, 16, 159, 0)])       # a last page just full, one just begun
def test_decode_at_a_head_of_64_matches_the_definition(pos, impl):
    rng = np.random.default_rng(21)
    pos = np.array(pos, np.int32)
    paired, apart = _paired_pools(jax.random.key(20))
    tables = _ring_tables(rng, pos, None, 20, 20)
    q = jax.random.normal(jax.random.key(22), (len(pos), HKV64 * G64, D64))
    got = da.gqa_decode_attention(q, *paired, tables, jnp.asarray(pos),
                                  layer=1, impl=impl)
    want = _dense(q[:, None], *apart, tables, pos, None, 1)[:, 0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    ref = da.reference_gqa_paged_attention(
        q[:, None], paired[0][1], paired[1][1], tables, jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(ref[:, 0]), want, atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("start,c", [(0, 16), (13, 16), (64, 8), (91, 16),
                                     (8, 136)])
def test_chunk_at_a_head_of_64_matches_the_definition(start, c, impl):
    """(8, 136): 136 queries of the 8 heads that read a pair are more
    than one program's 1,024 score rows: two programs, the second
    padded."""
    assert da._gqa_query_tile(136, 2 * G64) == 128
    rng = np.random.default_rng(23)
    paired, apart = _paired_pools(jax.random.key(24))
    table = _ring_tables(rng, [start + c - 1], None, 20, 20)[0]
    q = jax.random.normal(jax.random.key(25), (c, HKV64 * G64, D64))
    got = da.gqa_chunk_attention(q, *paired, table, jnp.int32(start),
                                 layer=0, impl=impl)
    want = _dense(q[None], *apart, table[None], np.array([start]), None, 0)[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
