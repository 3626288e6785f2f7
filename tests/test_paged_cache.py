"""Paged KV cache tests: block-table attention parity, the paged model
path vs. full forward, BlockAllocator and RadixTree invariants, engine
prefix sharing (shared system prompt prefilled exactly once, COW on
mid-block divergence), chunked-admission stall bounds, cancellation and
abandoned-stream cleanup, eviction under pool pressure, and a seeded
admit/cancel/retire fuzz (small here; the big variant is `slow`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import quant
from ray_tpu.serve.engine import BlockAllocator, InferenceEngine, RadixTree


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32"), **kw})


def rollout_reference(params, prompt, cfg, steps):
    """Greedy generation via repeated FULL forward passes."""
    toks = list(prompt)
    for _ in range(steps):
        logits = gpt.forward(params, jnp.asarray([toks]), cfg)[0, -1]
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("block_size", 8)
    return InferenceEngine(params, cfg, **kw)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

# (heads, head size, block size): the cells' own, `datadecide-300m`'s
# head size, and one whose heads fill a single row of lanes
KERNEL_SHAPES = [(16, 128, 16), (16, 64, 16), (4, 32, 8)]


def edge_batch(h, d, bs, dtype, seed=0, w=None):
    """Six rows against one scrambled pool, `turn` the kernel's pages a
    turn: an idle row (pos 0, a table of zeros), one ending on a block's
    last position, one on a block's first, one filling the whole table
    (2 turns and 3 pages), one a page past a turn, one of exactly a
    turn. Entries past a row's length point at other rows' blocks. With
    `w`, rows of `w` queries (a verify step's: query `i` at `pos + i`,
    its page the row's own where the table reaches it).
    -> (q, k_pool, v_pool, tables, pos)."""
    turn = 128 // bs
    mb = 2 * turn + 3
    pos = np.array([0, 3 * bs - 1, 3 * bs, mb * bs - 1,
                    (turn + 1) * bs - 5, turn * bs - 1], np.int32)
    rng = np.random.default_rng(seed)
    live = np.minimum((pos + (w or 1) - 1) // bs + 1, mb)
    live[0] = 0
    nb = 1 + int(live.sum())
    blocks = iter(rng.permutation(nb - 1) + 1)
    tables = rng.integers(1, nb, size=(len(pos), mb)).astype(np.int32)
    tables[0] = 0
    for i, n in enumerate(live):
        tables[i, :n] = [next(blocks) for _ in range(n)]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = lambda key: jax.random.normal(key, (nb, bs, h, d)).astype(dtype)
    rows = (len(pos), h, d) if w is None else (len(pos), w, h, d)
    q = jax.random.normal(kq, rows).astype(dtype)
    return q, pool(kk), pool(kv), jnp.asarray(tables), jnp.asarray(pos)


def dead_blocks(nb, bs, tables, pos, w=1):
    """Blocks that no decoding row (row 0 is idle) reads at its pos, or
    for its `w` queries from there on."""
    tables, pos = np.asarray(tables), np.asarray(pos)
    read = {int(b) for i in range(1, len(pos))
            for b in tables[i, :(pos[i] + w - 1) // bs + 1]}
    return jnp.asarray(sorted(set(range(nb)) - read))


def with_dead_nan(kp, vp, ksc, vsc, dead):
    """The pools with NaN in every `dead` block: in the payload, or for
    an int8 pool (which cannot hold one) in the scales, over a payload
    of garbage."""
    if ksc is None:
        return kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan), {}
    return kp.at[dead].set(127), vp.at[dead].set(-128), {
        "k_scale": ksc.at[dead].set(jnp.nan),
        "v_scale": vsc.at[dead].set(jnp.nan)}


class TestPagedAttention:
    def _paged(self, b, s, h, d, bs, seed=0):
        """Random contiguous K/V scattered into a scrambled block pool;
        returns (q, k, v, pools, tables, pos)."""
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        mb = s // bs
        rng = np.random.default_rng(seed)
        # one shared pool; each sequence owns a disjoint scrambled set
        perm = rng.permutation(b * mb) + 1      # keep block 0 unused
        tables = perm.reshape(b, mb).astype(np.int32)
        kp = np.zeros((b * mb + 1, bs, h, d), np.float32)
        vp = np.zeros_like(kp)
        for i in range(b):
            for j in range(mb):
                kp[tables[i, j]] = np.asarray(k[i, j * bs:(j + 1) * bs])
                vp[tables[i, j]] = np.asarray(v[i, j * bs:(j + 1) * bs])
        pos = jnp.array([s - 1, 3][:b], jnp.int32)
        return q, k, v, jnp.asarray(kp), jnp.asarray(vp), \
            jnp.asarray(tables), pos

    def test_gather_reassembles_contiguous_kv(self):
        q, k, v, kp, vp, tables, pos = self._paged(2, 32, 2, 8, 8)
        got = da.gather_kv_pages(kp, tables)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(k))

    def test_paged_matches_unpaged(self):
        """Attention through a scrambled block table == attention over
        the contiguous cache it encodes."""
        q, k, v, kp, vp, tables, pos = self._paged(2, 32, 2, 8, 8)
        ref = da.reference_decode_attention(q, k, v, pos)
        out = da.paged_decode_attention(q, kp, vp, tables, pos,
                                        impl="jax")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_reference_and_auto_agree(self):
        q, k, v, kp, vp, tables, pos = self._paged(2, 64, 2, 16, 16,
                                                   seed=3)
        ref = da.reference_paged_decode_attention(q, kp, vp, tables,
                                                  pos)
        out = da.paged_decode_attention(q, kp, vp, tables, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_masks_beyond_pos(self):
        """Blocks past pos — including live blocks of OTHER sequences
        in the shared pool — must not leak in."""
        q, k, v, kp, vp, tables, pos = self._paged(2, 32, 2, 8, 8)
        # corrupt everything strictly past each row's pos
        kp2, vp2 = np.array(kp), np.array(vp)
        for i in range(2):
            p = int(pos[i])
            for j in range((p // 8), 4):
                off = p + 1 - j * 8
                if off < 8:
                    kp2[tables[i, j], max(off, 0):] = 1e4
                    vp2[tables[i, j], max(off, 0):] = -1e4
        out = da.paged_decode_attention(q, kp, vp, tables, pos,
                                        impl="jax")
        out2 = da.paged_decode_attention(q, jnp.asarray(kp2),
                                         jnp.asarray(vp2), tables, pos,
                                         impl="jax")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("h,d,bs", KERNEL_SHAPES)
    def test_kernel_matches_reference_on_the_edges(self, h, d, bs, dtype):
        """`paged_decode` (interpret mode) against the gather-then-attend
        reference on `edge_batch`'s rows, whole pages and ragged ones."""
        q, kp, vp, tables, pos = edge_batch(h, d, bs, dtype)
        ref = da.reference_paged_decode_attention(q, kp, vp, tables, pos)
        out = da.paged_decode_attention(q, kp, vp, tables, pos,
                                        impl="pallas")
        assert out.dtype == q.dtype and out.shape == q.shape
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("h,d,bs", KERNEL_SHAPES)
    def test_kernel_never_reads_a_dead_block(self, h, d, bs):
        """NaN in every block no decoding row's live table entry names
        (the trash block and the blocks that entries past a length point
        at among them): the decoding rows get the clean pool's answer."""
        q, kp, vp, tables, pos = edge_batch(h, d, bs, "float32")
        ref = da.reference_paged_decode_attention(q, kp, vp, tables, pos)
        dead = dead_blocks(kp.shape[0], bs, tables, pos)
        out = da.paged_decode_attention(
            q, kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan),
            tables, pos, impl="pallas")
        np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("shape,kw,want", [
        ((16, 16, 128, "bfloat16"), {}, (1, 8, None)),      # the cells'
        ((16, 16, 64, "bfloat16"), {}, (2, 8, None)),       # two heads a row
        ((8, 4, 32, "float32"), {}, (4, 16, None)),
        ((16, 16, 128, "int8"), {"quantized": True}, (1, 8, None)),
        ((16, 12, 96, "bfloat16"), {}, None),       # no row of 128 lanes
        ((16, 2, 128, "float32"), {}, None),        # heads under a tile
        ((8, 4, 32, "int8"), {"quantized": True}, None),    # scale rows
        ((256, 64, 128, "bfloat16"), {}, (1, 1, 53 << 20)),  # asks VMEM
        ((256, 64, 128, "bfloat16"), {"vmem": 32 << 20}, None),
        # `paged_mq`: the cells' chunk of 64 (1024 score rows a program),
        # a verify step of 5, a chunk at two heads a row, an f32 pool
        ((16, 16, 128, "bfloat16"), {"w": 64}, (1, 4, None)),
        ((16, 16, 128, "bfloat16"), {"w": 5}, (1, 8, None)),
        ((16, 16, 128, "int8"), {"w": 64, "quantized": True}, (1, 4, None)),
        ((16, 16, 64, "bfloat16"), {"w": 64}, (2, 8, None)),
        ((16, 16, 128, "float32"), {"w": 64}, (1, 2, None)),
    ])
    def test_the_plan_comes_from_the_shapes(self, shape, kw, want):
        bs, h, d, dtype = shape
        kw = {"quantized": False, "w": 1, **kw}
        plan = da._decode_plan(bs, h, d, jnp.dtype(dtype), **kw)
        assert plan == (want and da._DecodePlan(*want))


# ---------------------------------------------------------------------------
# paged model path
# ---------------------------------------------------------------------------

class TestPagedModelPath:
    def test_chunked_prefill_then_decode_matches_full_forward(self,
                                                              setup):
        """Prefill in 2 chunks through a scrambled table, then decode
        greedily — token-for-token equal to full-forward rollout."""
        cfg, params = setup
        bs, chunks = 8, (8, 4)
        prompt = list(np.random.default_rng(0).integers(
            0, cfg.vocab_size, 12))
        pool = gpt.init_kv_pool(cfg, 8, bs)
        table = np.array([5, 2, 7, 1], np.int32)
        start = 0
        for clen in chunks:
            toks = np.zeros((1, 8), np.int32)
            toks[0, :clen] = prompt[start:start + clen]
            logits, pool = gpt.prefill_paged(
                params, jnp.asarray(toks), pool, cfg,
                block_table=jnp.asarray(table), start=start,
                length=jnp.int32(clen))
            start += clen
        toks_out, cur = [], int(jnp.argmax(logits[0]))
        tables = jnp.asarray(table)[None]
        for t in range(len(prompt), len(prompt) + 6):
            toks_out.append(cur)
            logits, pool = gpt.decode_step_paged(
                params, jnp.asarray([cur], jnp.int32), pool,
                jnp.asarray([t], jnp.int32), tables, cfg)
            cur = int(jnp.argmax(logits[0]))
        assert toks_out == rollout_reference(params, prompt, cfg, 6)

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-4),
                                            ("bfloat16", 5e-2)])
    def test_prefill_and_decode_logits_match_full_forward(self, dtype,
                                                          atol):
        """Chunked prefill, then one decode step a token: the logits at
        every position are the full forward's, in f32 and in bf16."""
        cfg = tiny_cfg(dtype=dtype)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        bs, T, P = 8, 14, 10
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (T,), 0, cfg.vocab_size))
        full = np.asarray(gpt.forward(params, jnp.asarray(toks[None]),
                                      cfg)[0], np.float32)   # [T, V]
        pool = gpt.init_kv_pool(cfg, 8, bs)
        table = jnp.asarray([5, 2, 7, 1], jnp.int32)
        for start, clen in ((0, 8), (8, P - 8)):
            chunk = np.zeros((1, 8), np.int32)
            chunk[0, :clen] = toks[start:start + clen]
            logits, pool = gpt.prefill_paged(
                params, jnp.asarray(chunk), pool, cfg, block_table=table,
                start=start, length=jnp.int32(clen))
            np.testing.assert_allclose(
                np.asarray(logits[0]), full[start + clen - 1],
                atol=atol, rtol=atol)
        for t in range(P, T):
            logits, pool = gpt.decode_step_paged(
                params, jnp.asarray(toks[t:t + 1]), pool,
                jnp.asarray([t], jnp.int32), table[None], cfg)
            np.testing.assert_allclose(np.asarray(logits[0]), full[t],
                                       atol=atol, rtol=atol)

    def test_ragged_length_returns_last_real_positions_logits(self,
                                                              setup):
        """`length` < C: the logits are position `start + length - 1`'s,
        whatever the padded tail holds, and the tail writes nothing."""
        cfg, params = setup
        bs = 8
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (1, 8), 1, cfg.vocab_size))
        full = gpt.forward(params, jnp.asarray(toks[:, :5]), cfg)
        table = jnp.asarray([3, 1], jnp.int32)

        def run(tail):
            chunk = toks.copy()
            chunk[0, 5:] = tail
            return gpt.prefill_paged(
                params, jnp.asarray(chunk), gpt.init_kv_pool(cfg, 4, bs),
                cfg, block_table=table, start=0, length=jnp.int32(5))

        logits, pool = run(0)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full[0, 4]),
                                   atol=1e-5, rtol=1e-5)
        logits2, _ = run(7)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(logits2))
        k = np.asarray(pool["k"])               # [L, nb, bs, H, Dh]
        assert np.any(k[:, 3, :5] != 0) and not np.any(k[:, 3, 5:])
        assert not np.any(k[:, [0, 1, 2]])

    def test_prefill_rejects_a_batch_and_a_missing_start(self, setup):
        cfg, params = setup
        pool = gpt.init_kv_pool(cfg, 4, 8)
        table = jnp.asarray([1, 2], jnp.int32)
        with pytest.raises(ValueError, match="tokens \\[1, C\\]"):
            gpt.prefill_paged(params, jnp.zeros((2, 8), jnp.int32), pool,
                              cfg, block_table=table, start=0)
        with pytest.raises(ValueError, match="needs start"):
            gpt.prefill_paged(params, jnp.zeros((1, 8), jnp.int32), pool,
                              cfg, block_table=table, start=None)

    def test_writes_past_the_table_drop(self, setup):
        """A position past `max_blocks * block_size` (a speculative step
        near max_len) writes nothing: clamping would land it inside the
        slot's own last block. The pool is whatever size the caller
        asks; nothing ties it to `max_seq_len`."""
        cfg, params = setup
        bs = 8
        pool = gpt.init_kv_pool(cfg, cfg.max_seq_len // bs + 3, bs)
        assert pool["k"].shape[1:3] == (cfg.max_seq_len // bs + 3, bs)
        tables = jnp.asarray([[1, 2]], jnp.int32)      # reach: 16
        tok = jnp.asarray([5], jnp.int32)
        _, inside = gpt.decode_step_paged(
            params, tok, pool, jnp.asarray([15], jnp.int32), tables, cfg)
        assert np.any(np.asarray(inside["k"][:, 2, 7]))
        for write in (
            lambda pos: gpt.decode_step_paged(
                params, tok, pool, jnp.asarray([pos], jnp.int32), tables,
                cfg),
            lambda pos: gpt.verify_step_paged(
                params, jnp.asarray([[5, 6]], jnp.int32), pool,
                jnp.asarray([pos], jnp.int32), tables, cfg),
        ):
            _, past = write(16)
            for name in pool:
                assert not np.any(np.asarray(past[name])), name

    def test_decode_step_paged_pool_donation(self, setup):
        """Under jit(donate_argnums=pool) the compiled step aliases the
        pool's input to its output (an in-place update in HBM), the
        donated buffers are consumed, and a second step with other
        positions and tables does not trace again."""
        cfg, params = setup
        pool = gpt.init_kv_pool(cfg, 6, 8)
        toks = jnp.array([3, 5], jnp.int32)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        traces = []

        def fn(p, t, c, pos, tbl):
            traces.append(1)
            return gpt.decode_step_paged(p, t, c, pos, tbl, cfg)

        step = jax.jit(fn, donate_argnums=(2,))
        pos = jnp.array([0, 0], jnp.int32)
        hlo = step.lower(params, toks, pool, pos, tables).compile().as_text()
        assert "input_output_alias" in hlo
        _, new_pool = step(params, toks, pool, pos, tables)
        assert pool["k"].is_deleted() and pool["v"].is_deleted()
        assert not new_pool["k"].is_deleted()
        step(params, toks, new_pool, pos + 1, tables[::-1])
        assert len(traces) == 1         # the lowering's; the calls reuse it

    def test_copy_block(self, setup):
        cfg, params = setup
        pool = gpt.init_kv_pool(cfg, 4, 8)
        pool = {k: v + jnp.arange(4, dtype=v.dtype)[None, :, None,
                                                    None, None]
                for k, v in pool.items()}
        out = gpt.copy_block(pool, 3, 1)
        np.testing.assert_array_equal(np.asarray(out["k"][:, 1]),
                                      np.asarray(out["k"][:, 3]))
        np.testing.assert_array_equal(np.asarray(out["v"][:, 2]),
                                      2 * np.ones_like(
                                          np.asarray(out["v"][:, 2])))

    def test_pool_sharding_specs(self, setup):
        from ray_tpu.parallel import MeshSpec
        from ray_tpu.parallel.sharding import kv_pool_specs
        cfg, _ = setup
        mesh = MeshSpec(data=-1).build(jax.devices())
        specs = kv_pool_specs(mesh)
        assert set(specs) == {"k", "v"}
        pool = gpt.init_kv_pool(tiny_cfg(n_layers=1), 4, 8, mesh=mesh)
        assert pool["k"].sharding.spec == specs["k"]


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_free_cycle(self):
        a = BlockAllocator(5)        # blocks 1..4 usable
        got = [a.alloc() for _ in range(4)]
        assert sorted(got) == [1, 2, 3, 4]
        assert a.free == 0 and a.used == 4
        with pytest.raises(RuntimeError, match="out of"):
            a.alloc()
        for b in got:
            a.decref(b)
        assert a.free == 4 and a.used == 0
        a.check()

    def test_refcounts(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.ref(b)
        assert a.refcount(b) == 2
        a.decref(b)
        assert a.used == 1           # still held once
        a.decref(b)
        assert a.used == 0

    def test_double_free_raises(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.decref(b)
        with pytest.raises(RuntimeError, match="double free"):
            a.decref(b)
        with pytest.raises(RuntimeError, match="ref of free"):
            a.ref(b)
        with pytest.raises(RuntimeError):
            a.decref(0)              # trash block is never freeable
        a.check()

    def test_too_small(self):
        with pytest.raises(ValueError):
            BlockAllocator(1)


# ---------------------------------------------------------------------------
# radix tree
# ---------------------------------------------------------------------------

class TestRadixTree:
    def _tree(self, bs=4, n=32):
        a = BlockAllocator(n)
        return RadixTree(bs, a), a

    def test_insert_match_aligned(self):
        t, a = self._tree()
        x = list(range(8))
        bx = [a.alloc(), a.alloc()]
        t.insert(x, bx)
        assert t.match(x) == (bx, 8)
        assert t.match(x[:4]) == (bx[:1], 4)
        assert t.match(x + [99]) == (bx, 8)
        assert t.match([99]) == ([], 0)
        assert a.refcount(bx[0]) == 2    # ours + the tree's

    def test_partial_block_match(self):
        t, a = self._tree()
        x = list(range(8))
        bx = [a.alloc(), a.alloc()]
        t.insert(x, bx)
        blocks, m = t.match([0, 1, 2, 3, 4, 5, 77])
        assert m == 6                    # diverges inside block 2
        assert blocks == bx              # last block shared partially

    def test_split_on_divergence(self):
        t, a = self._tree()
        x = list(range(8))
        bx = [a.alloc(), a.alloc()]
        t.insert(x, bx)
        y = x[:4] + [9, 9, 9, 9]
        c = a.alloc()
        t.insert(y, [bx[0], c])          # engine passes shared + own
        assert t.n_nodes() == 3          # split: upper + two tails
        assert t.match(x) == (bx, 8)
        assert t.match(y) == ([bx[0], c], 8)
        assert a.refcount(bx[0]) == 2    # shared head ref'd ONCE by tree
        assert a.refcount(c) == 2

    def test_insert_existing_is_noop(self):
        t, a = self._tree()
        x = list(range(8))
        bx = [a.alloc(), a.alloc()]
        t.insert(x, bx)
        t.insert(x, bx)
        assert t.n_nodes() == 1
        assert a.refcount(bx[0]) == 2

    def test_evict_lru_zero_ref_leaves(self):
        t, a = self._tree()
        x = list(range(8))
        bx = [a.alloc(), a.alloc()]
        t.insert(x, bx)
        y = x[:4] + [9, 9, 9, 9]
        c = a.alloc()
        t.insert(y, [bx[0], c])
        for b in (*bx, c):               # drop our refs: tree-only now
            a.decref(b)
        t.match(y)                       # y's path is most recent
        assert t.evict(1) == 1           # LRU victim: x's tail [bx[1]]
        assert t.match(x) == ([bx[0]], 4)
        assert t.match(y) == ([bx[0], c], 8)
        # referenced blocks are never evicted
        a.ref(c)
        assert t.evict(10) == 0
        a.decref(c)
        t.clear()
        assert t.n_blocks() == 0 and a.used == 0


# ---------------------------------------------------------------------------
# engine: prefix sharing
# ---------------------------------------------------------------------------

class TestPrefixSharing:
    def test_shared_system_prompt_prefilled_once(self, setup):
        """The acceptance criterion: two requests sharing a 16-token
        system prompt prefill it exactly once — asserted via the
        engine's prefill-token counter — and both still decode exactly
        what a cold engine decodes."""
        cfg, params = setup
        rng = np.random.default_rng(7)
        sys_p = list(rng.integers(0, cfg.vocab_size, 16))
        a = sys_p + list(rng.integers(0, cfg.vocab_size, 4))
        b = sys_p + list(rng.integers(0, cfg.vocab_size, 4))

        eng = make_engine(cfg, params)
        ra = eng.submit(a, max_new_tokens=4)
        rb = eng.submit(b, max_new_tokens=4)
        eng.run_until_idle()
        s = eng.stats()
        # a: 20 prefilled; b: only its 4-token suffix
        assert s["prefill_tokens"] == len(a) + 4
        assert s["prefix_hit_tokens"] == 16
        assert s["prefix_hit_rate"] == pytest.approx(16 / 40)
        got_a = [eng._out[ra].popleft() for _ in range(4)]
        got_b = [eng._out[rb].popleft() for _ in range(4)]
        assert got_a == rollout_reference(params, a, cfg, 4)
        assert got_b == rollout_reference(params, b, cfg, 4)
        eng.check_invariants()

    def test_cow_on_mid_block_divergence(self, setup):
        """A prefix that diverges inside a cached block is shared
        copy-on-write: one device block copy, identical tokens."""
        cfg, params = setup
        rng = np.random.default_rng(11)
        x = list(rng.integers(0, cfg.vocab_size, 16))
        y = x[:12] + list(rng.integers(0, cfg.vocab_size, 4))
        eng = make_engine(cfg, params)
        got_x = eng.generate(x, max_new_tokens=3)
        got_y = eng.generate(y, max_new_tokens=3)
        s = eng.stats()
        assert s["cow_copies"] == 1
        assert s["prefix_hit_tokens"] == 12
        assert got_x == rollout_reference(params, x, cfg, 3)
        assert got_y == rollout_reference(params, y, cfg, 3)
        eng.check_invariants()

    def test_decode_compiles_once_with_sharing(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(5)
        sys_p = list(rng.integers(0, cfg.vocab_size, 8))
        eng = make_engine(cfg, params)
        for i in range(4):
            tail = list(rng.integers(0, cfg.vocab_size, 2 + i))
            eng.generate(sys_p + tail, max_new_tokens=3)
        assert eng.decode_traces == 1
        assert eng.stats()["prefix_hit_tokens"] > 0

    def test_prefix_cache_off(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, prefix_cache=False)
        p = list(range(1, 17))
        g1 = eng.generate(p, max_new_tokens=3)
        g2 = eng.generate(p, max_new_tokens=3)
        assert g1 == g2
        s = eng.stats()
        assert s["prefix_hit_tokens"] == 0
        assert s["prefill_tokens"] == 32
        # nothing cached → pool drains completely between requests
        assert s["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# engine: chunked prefill
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_admission_never_stalls_decode_more_than_one_chunk(
            self, setup):
        """While a long prompt is being admitted, every scheduler tick
        still advances the in-flight stream by one token and runs at
        most ONE prefill chunk. (A tick reads the decode step the tick
        before enqueued, so the first tick's is counted by the second.)"""
        cfg, params = setup
        eng = make_engine(cfg, params, prefill_chunk=8,
                          prefix_cache=False)
        eng.submit(list(range(1, 5)), max_new_tokens=24)
        eng.step()                      # admit + drain tiny prefill
        assert eng.stats()["decode_steps"] == 0 and eng._flight is not None
        # now a 24-token prompt arrives: 3 chunks of 8
        eng.submit(list(range(40, 64)), max_new_tokens=2)
        for tick in range(1, 4):
            before = eng.stats()
            eng.step()
            s = eng.stats()
            assert s["prefill_chunks"] - before["prefill_chunks"] == 1
            assert s["decode_steps"] - before["decode_steps"] == 1
            assert s["decode_tokens"] - before["decode_tokens"] == 1
        assert s["prefill_chunks"] == 4     # 1 warm + 3 chunked
        assert s["max_admission_stall_ms"] > 0.0
        eng.run_until_idle()
        eng.check_invariants()

    def test_idle_engine_drains_prefill_freely(self, setup):
        """With nothing decoding there is nobody to stall: one tick
        absorbs every pending chunk."""
        cfg, params = setup
        eng = make_engine(cfg, params, prefill_chunk=8,
                          prefix_cache=False)
        eng.submit(list(range(1, 25)), max_new_tokens=2)
        eng.step()
        s = eng.stats()
        assert s["prefill_chunks"] == 3
        assert s["prefill_tokens"] == 24

    def test_long_prompt_beyond_buckets_decodes_correctly(self, setup):
        """Chunking removed the bucket-length admission limit: a prompt
        longer than the largest prefill bucket works and matches the
        full-forward rollout."""
        cfg, params = setup
        prompt = list(np.random.default_rng(3).integers(
            0, cfg.vocab_size, 26))
        eng = make_engine(cfg, params, prefill_chunk=8)
        assert eng.generate(prompt, max_new_tokens=4) == \
            rollout_reference(params, prompt, cfg, 4)


# ---------------------------------------------------------------------------
# engine: cancellation and cleanup
# ---------------------------------------------------------------------------

class TestCancel:
    def test_cancel_pending(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params)
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        assert eng.cancel(rid)
        assert not eng.cancel(rid)      # idempotent
        s = eng.stats()
        assert s["pending"] == 0 and s["cancelled"] == 1
        eng.check_invariants()

    def test_cancel_mid_decode_releases_blocks(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, prefix_cache=False)
        rid = eng.submit(list(range(1, 10)), max_new_tokens=20)
        for _ in range(3):
            eng.step()
        assert eng.stats()["blocks_in_use"] > 0
        assert eng.cancel(rid)
        s = eng.stats()
        assert s["blocks_in_use"] == 0 and s["active"] == 0
        assert rid not in eng._out
        eng.check_invariants()

    def test_cancel_finished_undrained(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params)
        rid = eng.submit([4, 5, 6], max_new_tokens=3)
        eng.run_until_idle()
        assert len(eng._out[rid]) == 3
        assert eng.cancel(rid)
        assert rid not in eng._out and rid not in eng._done

    def test_abandoned_stream_releases_request(self, setup):
        """Breaking out of `tokens_for` (generator finalization) must
        cancel the request and free its blocks — the leak named in the
        issue."""
        cfg, params = setup
        eng = make_engine(cfg, params, prefix_cache=False)
        rid = eng.submit(list(range(1, 9)), max_new_tokens=20)
        it = eng.tokens_for(rid)
        next(it)
        assert eng.stats()["active"] == 1
        it.close()                      # walk away mid-stream
        s = eng.stats()
        assert s["active"] == 0 and s["blocks_in_use"] == 0
        assert s["cancelled"] == 1 and rid not in eng._out
        eng.check_invariants()

    def test_engine_continues_after_cancel(self, setup):
        """Cancelling one stream must not disturb a co-resident one."""
        cfg, params = setup
        p = list(range(20, 28))
        eng = make_engine(cfg, params, prefix_cache=False)
        keep = eng.submit(p, max_new_tokens=6)
        kill = eng.submit(list(range(1, 9)), max_new_tokens=6)
        eng.step()
        eng.cancel(kill)
        eng.run_until_idle()
        got = [eng._out[keep].popleft() for _ in range(6)]
        assert got == rollout_reference(params, p, cfg, 6)

    def test_cancel_mid_spec_frees_draft_blocks(self, setup):
        """Cancel during a mid-flight speculative run (draft backend)
        must free BOTH pools' blocks and roll the slot back cleanly."""
        cfg, params = setup
        eng = make_engine(cfg, params, spec="draft", spec_k=3,
                          draft_params=params, draft_cfg=cfg)
        keep = eng.submit(list(range(20, 29)), max_new_tokens=12)
        kill = eng.submit(list(range(1, 8)), max_new_tokens=12)
        it = eng.tokens_for(keep)
        for _ in range(3):       # both slots are decoding speculatively
            next(it)
        assert eng._draft_alloc.used > 0
        assert eng.cancel(kill)
        eng.check_invariants()   # covers the draft allocator too
        rest = list(it)
        assert len(rest) == 12 - 3
        eng.run_until_idle()
        eng.check_invariants()
        assert eng._draft_alloc.used == 0
        assert eng.stats()["blocks_in_use"] == 0 or \
            eng.stats()["cached_prefix_blocks"] > 0

    def test_abandoned_stream_mid_spec(self, setup):
        """Generator abandonment mid-speculation releases draft blocks
        (the spec-path extension of the abandoned-stream regression)."""
        cfg, params = setup
        eng = make_engine(cfg, params, prefix_cache=False, spec="draft",
                          spec_k=2, draft_params=params, draft_cfg=cfg)
        rid = eng.submit(list(range(1, 9)), max_new_tokens=20)
        it = eng.tokens_for(rid)
        next(it)
        assert eng._draft_alloc.used > 0
        it.close()
        eng.check_invariants()
        s = eng.stats()
        assert s["active"] == 0 and s["blocks_in_use"] == 0
        assert eng._draft_alloc.used == 0 and s["cancelled"] == 1

    def test_cancel_mid_spec_ngram(self, setup):
        """Cancel mid-speculation on the n-gram backend: no draft pool
        involved, slot and main blocks roll back cleanly."""
        cfg, params = setup
        motif = [3, 7, 11, 13]
        eng = make_engine(cfg, params, prefix_cache=False, spec="ngram",
                          spec_k=4)
        rid = eng.submit(motif * 3, max_new_tokens=16)
        it = eng.tokens_for(rid)
        for _ in range(2):
            next(it)
        it.close()
        eng.check_invariants()
        s = eng.stats()
        assert s["active"] == 0 and s["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# engine: eviction under pressure
# ---------------------------------------------------------------------------

class TestEviction:
    def test_cached_prefix_evicted_under_pressure(self, setup):
        """A pool too small for two cached prompts evicts the zero-ref
        prefix instead of failing admission."""
        cfg, params = setup
        rng = np.random.default_rng(13)
        a = list(rng.integers(0, cfg.vocab_size, 16))
        b = list(rng.integers(0, cfg.vocab_size, 16))
        eng = make_engine(cfg, params, slots=1, cache_blocks=3)
        got_a = eng.generate(a, max_new_tokens=2)
        assert eng.stats()["blocks_in_use"] == 2   # a's prefix cached
        got_b = eng.generate(b, max_new_tokens=2)
        s = eng.stats()
        assert s["evicted_blocks"] >= 2
        assert got_a == rollout_reference(params, a, cfg, 2)
        assert got_b == rollout_reference(params, b, cfg, 2)
        eng.check_invariants()

    def test_admission_waits_when_pool_fully_referenced(self, setup):
        """When live requests hold every block, a newcomer stays
        pending (no eviction possible) and admits once one retires."""
        cfg, params = setup
        eng = make_engine(cfg, params, slots=2, cache_blocks=3,
                          prefix_cache=False)
        r1 = eng.submit(list(range(1, 17)), max_new_tokens=6)  # 3 blocks
        eng.step()
        r2 = eng.submit(list(range(30, 46)), max_new_tokens=6)
        eng.step()
        assert eng.stats()["pending"] == 1      # pool exhausted by r1
        eng.run_until_idle()
        assert len(eng._out[r1]) == 6 and len(eng._out[r2]) == 6
        eng.check_invariants()


# ---------------------------------------------------------------------------
# fuzz: admit / cancel / retire
# ---------------------------------------------------------------------------

def _fuzz(setup, ops, seed, **engine_kw):
    """Random submit/cancel/step/drain storm over a small-alphabet
    token space (to force radix collisions, splits, COW and eviction),
    checking allocator/tree/slot invariants after every operation."""
    cfg, params = setup
    eng = make_engine(cfg, params, slots=3, cache_blocks=9,
                      **engine_kw)
    rng = np.random.default_rng(seed)
    live = []
    for _ in range(ops):
        op = rng.integers(0, 10)
        if op < 4:      # submit (small alphabet → shared prefixes)
            p = list(rng.integers(1, 5, int(rng.integers(1, 25))))
            mnt = int(rng.integers(1, 6))
            try:
                live.append(eng.submit(p, max_new_tokens=mnt))
            except ValueError:
                pass    # footprint exceeds the pool — fine
        elif op < 6 and live:   # cancel a random request
            eng.cancel(live.pop(int(rng.integers(0, len(live)))))
        elif op < 7 and live:   # drain one finished stream
            rid = live.pop(0)
            for _ in eng.tokens_for(rid):
                pass
        else:
            eng.step()
        eng.check_invariants()
    for rid in live:
        eng.cancel(rid)
    eng.run_until_idle()
    eng.check_invariants()
    s = eng.stats()
    assert s["active"] == 0 and s["pending"] == 0
    # every block still allocated is held by the prefix cache only
    assert s["blocks_in_use"] == s["cached_prefix_blocks"]
    if eng._tree is not None:
        eng._tree.clear()
    assert eng.stats()["blocks_in_use"] == 0
    eng.check_invariants()
    return s


def test_fuzz_small(setup):
    s = _fuzz(setup, ops=40, seed=0)
    assert s["decode_tokens"] > 0


def test_fuzz_small_no_prefix_cache(setup):
    _fuzz(setup, ops=30, seed=1, prefix_cache=False)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [2, 3, 4])
def test_fuzz_large(setup, seed):
    _fuzz(setup, ops=300, seed=seed)


# ---------------------------------------------------------------------------
# quantized KV (int8 payload, per-row scales)
# ---------------------------------------------------------------------------

def _peaked(params):
    """Sharpen the tiny random-init model's logits: they are near-uniform
    (greedy argmax gaps below int8 noise), so token-identity tests scale
    the embedding to restore a decisive winner at every step."""
    return {**params, "embed": params["embed"] * 8}


@pytest.fixture(scope="module")
def setup_q(setup):
    """kv_dtype="int8" config + peaked params (shapes are independent of
    kv_dtype, so the module fixture's params are reusable)."""
    return tiny_cfg(kv_dtype="int8"), _peaked(setup[1])


class TestQuantizedPagedAttention:
    def _quantized(self, b, s, h, d, bs, seed=0):
        q, k, v, kp, vp, tables, pos = TestPagedAttention()._paged(
            b, s, h, d, bs, seed=seed)
        kq, ksc = quant.quantize_rows(kp)
        vq, vsc = quant.quantize_rows(vp)
        return q, kp, vp, kq, ksc, vq, vsc, tables, pos

    def test_kernel_matches_reference(self):
        """Pallas (interpret on CPU) dequant-in-VMEM == gather-then-
        dequant reference on an int8 pool."""
        q, _, _, kq, ksc, vq, vsc, tables, pos = self._quantized(
            2, 64, 2, 16, 16, seed=5)
        ref = da.reference_paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc)
        out = da.paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc,
            impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("h,d,bs", KERNEL_SHAPES)
    def test_kernel_matches_reference_on_the_edges(self, h, d, bs):
        """`edge_batch` on an int8 pool: payload and scales page by page,
        same rounding as the gather-then-dequantize reference."""
        q, kp, vp, tables, pos = edge_batch(h, d, bs, "float32", seed=2)
        kq, ksc = quant.quantize_rows(kp)
        vq, vsc = quant.quantize_rows(vp)
        ref = da.reference_paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc)
        out = da.paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc,
            impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_kernel_never_reads_a_dead_scale(self):
        """An int8 payload cannot hold a NaN, its scales can: NaN scales
        in every block no decoding row reads, garbage payload there."""
        h, d, bs = KERNEL_SHAPES[0]
        q, kp, vp, tables, pos = edge_batch(h, d, bs, "float32", seed=3)
        kq, ksc = quant.quantize_rows(kp)
        vq, vsc = quant.quantize_rows(vp)
        ref = da.reference_paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc)
        dead = dead_blocks(kp.shape[0], bs, tables, pos)
        out = da.paged_decode_attention(
            q, kq.at[dead].set(127), vq.at[dead].set(-128), tables, pos,
            k_scale=ksc.at[dead].set(jnp.nan),
            v_scale=vsc.at[dead].set(jnp.nan), impl="pallas")
        np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                                   atol=2e-5, rtol=2e-5)

    def test_quantized_close_to_f32(self):
        """Int8+scale attention lands within quantization noise of the
        f32 pool it was built from."""
        q, kp, vp, kq, ksc, vq, vsc, tables, pos = self._quantized(
            2, 32, 2, 8, 8, seed=1)
        f32 = da.paged_decode_attention(q, kp, vp, tables, pos,
                                        impl="jax")
        i8 = da.paged_decode_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc,
            impl="jax")
        np.testing.assert_allclose(np.asarray(i8), np.asarray(f32),
                                   atol=0.1, rtol=0.1)

    def test_roundtrip_is_deterministic(self):
        """Same f32 rows -> byte-identical int8 payload and scales on
        every call — the property that keeps batched verify bit-equal
        to sequential decode on a quantized pool."""
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 4, 8))
        q1, s1 = quant.quantize_rows(x)
        q2, s2 = quant.quantize_rows(x)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        # zero rows must dequantize to exact zero, not NaN
        qz, sz = quant.quantize_rows(jnp.zeros((2, 3, 8)))
        assert not np.isnan(np.asarray(sz)).any()
        np.testing.assert_array_equal(
            np.asarray(quant.dequantize_rows(qz, sz)), 0.0)

    def test_scale_validation(self):
        """k_scale/v_scale are both-or-neither on every paged wrapper."""
        q, _, _, kq, ksc, vq, vsc, tables, pos = self._quantized(
            2, 32, 2, 8, 8)
        with pytest.raises(ValueError, match="both k_scale and v_scale"):
            da.paged_decode_attention(q, kq, vq, tables, pos,
                                      k_scale=ksc)
        with pytest.raises(ValueError):
            da.paged_decode_attention(
                q, kq, vq, tables, pos, k_scale=ksc[:, :4],
                v_scale=vsc)


class TestFusedPrefill:
    def _seq(self, s, h, d, bs, seed=0, quantize=False):
        """One sequence's K/V scattered into a scrambled single-table
        pool, plus its full query stack."""
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (s, h, d))
        k = jax.random.normal(ks[1], (s, h, d))
        v = jax.random.normal(ks[2], (s, h, d))
        mb = s // bs
        table = (np.random.default_rng(seed).permutation(mb) + 1) \
            .astype(np.int32)
        kp = np.zeros((mb + 1, bs, h, d), np.float32)
        vp = np.zeros_like(kp)
        for j in range(mb):
            kp[table[j]] = np.asarray(k[j * bs:(j + 1) * bs])
            vp[table[j]] = np.asarray(v[j * bs:(j + 1) * bs])
        kp, vp = jnp.asarray(kp), jnp.asarray(vp)
        if not quantize:
            return q, kp, vp, None, None, jnp.asarray(table)
        kq, ksc = quant.quantize_rows(kp)
        vq, vsc = quant.quantize_rows(vp)
        return q, kq, vq, ksc, vsc, jnp.asarray(table)

    @pytest.mark.parametrize("start,c", [(0, 32), (8, 8), (16, 5)])
    def test_pallas_matches_jax(self, start, c):
        """The fused (mq-kernel) path == the legacy dense gather+einsum,
        including a ragged tail chunk (c=5, padded rows discarded)."""
        q, kp, vp, _, _, table = self._seq(32, 2, 16, 8, seed=4)
        ref = da.paged_prefill_attention(q[start:start + c], kp, vp,
                                         table, start, impl="jax")
        pal = da.paged_prefill_attention(q[start:start + c], kp, vp,
                                         table, start, impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("start,c", [(0, 16), (8, 5)])
    def test_pallas_matches_jax_quantized(self, start, c):
        q, kq, vq, ksc, vsc, table = self._seq(16, 2, 16, 8, seed=7,
                                               quantize=True)
        ref = da.paged_prefill_attention(
            q[start:start + c], kq, vq, table, start,
            k_scale=ksc, v_scale=vsc, impl="jax")
        pal = da.paged_prefill_attention(
            q[start:start + c], kq, vq, table, start,
            k_scale=ksc, v_scale=vsc, impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dead", [False, True],
                             ids=["clean", "dead-pages-nan"])
    @pytest.mark.parametrize("kv", ["f32", "int8"])
    @pytest.mark.parametrize("start,c", [(0, 24), (19, 17), (40, 80)],
                             ids=["from-0", "ends-mid-page", "C80"])
    @pytest.mark.parametrize("h,d,bs", KERNEL_SHAPES)
    def test_kernel_reads_the_live_pages_alone(self, h, d, bs, start, c,
                                               kv, dead):
        """`paged_mq` (interpret mode) as a prefill chunk on the kernel
        shapes (16 heads: 80 queries are two programs, the second padded)
        against the dense reference: a chunk from position 0, one whose
        last query sits mid-page, and with `dead` NaN in every block past
        the chunk's last page, those that table entries past the length
        name among them (an int8 pool: NaN scales)."""
        mb = (start + c - 1) // bs + 3
        q, kp, vp, ksc, vsc, table = self._seq(
            mb * bs, h, d, bs, seed=5, quantize=kv == "int8")
        q = q[start:start + c]
        ref = da.reference_paged_prefill_attention(
            q, kp, vp, table, start, k_scale=ksc, v_scale=vsc)
        scales = {} if ksc is None else {"k_scale": ksc, "v_scale": vsc}
        if dead:
            live = np.asarray(table)[:(start + c - 1) // bs + 1]
            gone = jnp.asarray(sorted(set(range(mb + 1)) - set(live.tolist())))
            kp, vp, scales = with_dead_nan(kp, vp, ksc, vsc, gone)
        out = da.paged_prefill_attention(q, kp, vp, table, start,
                                         impl="pallas", **scales)
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_rejects_bad_rank(self):
        with pytest.raises(ValueError, match="paged_prefill_attention"):
            da.paged_prefill_attention(
                jnp.zeros((4, 16)), jnp.zeros((4, 8, 2, 16)),
                jnp.zeros((4, 8, 2, 16)), jnp.zeros((4,), jnp.int32), 0)


class TestVerifyKernel:
    @pytest.mark.parametrize("dead", [False, True],
                             ids=["clean", "dead-pages-nan"])
    @pytest.mark.parametrize("kv", ["f32", "int8"])
    @pytest.mark.parametrize("h,d,bs", KERNEL_SHAPES)
    def test_kernel_reads_the_live_pages_alone(self, h, d, bs, kv, dead):
        """`paged_mq` (interpret mode) as a verify step of 5 on the decode
        kernel's shapes and `edge_batch`'s rows (one runs past the
        table's reach) against the gather-then-attend reference; with
        `dead`, NaN in every block no row's five queries reach (an int8
        pool: NaN scales over garbage)."""
        w = 5
        q, kp, vp, tables, pos = edge_batch(h, d, bs, "float32", seed=4,
                                            w=w)
        ksc = vsc = None
        if kv == "int8":
            kp, ksc = quant.quantize_rows(kp)
            vp, vsc = quant.quantize_rows(vp)
        scales = {} if ksc is None else {"k_scale": ksc, "v_scale": vsc}
        ref = da.reference_paged_verify_attention(q, kp, vp, tables, pos,
                                                  **scales)
        if dead:
            kp, vp, scales = with_dead_nan(
                kp, vp, ksc, vsc, dead_blocks(kp.shape[0], bs, tables, pos,
                                              w))
        out = da.paged_verify_attention(q, kp, vp, tables, pos,
                                        impl="pallas", **scales)
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                                   atol=2e-5, rtol=2e-5)


class TestQuantizedModelPath:
    def test_pool_layout(self, setup_q):
        cfg, _ = setup_q
        pool = gpt.init_kv_pool(cfg, 6, 8)
        assert set(pool) == {"k", "v", "k_scale", "v_scale"}
        assert pool["k"].dtype == jnp.int8
        assert pool["k_scale"].dtype == jnp.float32
        assert pool["k_scale"].shape == pool["k"].shape[:-1]

    def test_f32_pool_unchanged(self, setup):
        """kv_dtype="f32" (the default) keeps the legacy two-array pool
        — no scale arrays, no dtype change."""
        cfg, _ = setup
        pool = gpt.init_kv_pool(cfg, 6, 8)
        assert set(pool) == {"k", "v"}
        assert pool["k"].dtype == jnp.dtype(cfg.dtype)

    def test_bad_kv_dtype_rejected(self, setup):
        with pytest.raises(ValueError, match="kv_dtype"):
            gpt.init_kv_pool(tiny_cfg(kv_dtype="int4"), 6, 8)

    def test_copy_block_carries_scales(self, setup_q):
        """COW block copies move the scale rows with the payload."""
        cfg, _ = setup_q
        pool = gpt.init_kv_pool(cfg, 4, 8)
        pool = {name: arr + jnp.arange(4, dtype=arr.dtype).reshape(
                    (1, 4) + (1,) * (arr.ndim - 2))
                for name, arr in pool.items()}
        out = gpt.copy_block(pool, 3, 1)
        for name in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(out[name][:, 1]),
                                          np.asarray(out[name][:, 3]))
        np.testing.assert_array_equal(
            np.asarray(out["k_scale"][:, 2]),
            2 * np.ones_like(np.asarray(out["k_scale"][:, 2])))

    def test_pool_sharding_specs_quantized(self):
        from ray_tpu.parallel import MeshSpec
        from ray_tpu.parallel.sharding import kv_pool_specs
        mesh = MeshSpec(data=-1).build(jax.devices())
        specs = kv_pool_specs(mesh, quantized=True)
        assert set(specs) == {"k", "v", "k_scale", "v_scale"}
        pool = gpt.init_kv_pool(tiny_cfg(n_layers=1, kv_dtype="int8"),
                                4, 8, mesh=mesh)
        assert pool["k_scale"].sharding.spec == specs["k_scale"]

    def test_prefill_decode_greedy_matches_f32(self, setup):
        """The tentpole criterion at the model-path level: chunked
        prefill + greedy decode through an int8 pool emits the exact
        tokens of the f32 pool AND the full-forward rollout."""
        params = _peaked(setup[1])
        prompt = list(np.random.default_rng(0).integers(
            0, 128, 12))

        def run(cfg):
            pool = gpt.init_kv_pool(cfg, 8, 8)
            table = np.array([5, 2, 7, 1], np.int32)
            start = 0
            for clen in (8, 4):
                toks = np.zeros((1, 8), np.int32)
                toks[0, :clen] = prompt[start:start + clen]
                logits, pool = gpt.prefill_paged(
                    params, jnp.asarray(toks), pool, cfg,
                    block_table=jnp.asarray(table), start=start,
                    length=jnp.int32(clen))
                start += clen
            out, cur = [], int(jnp.argmax(logits[0]))
            tables = jnp.asarray(table)[None]
            for t in range(len(prompt), len(prompt) + 6):
                out.append(cur)
                logits, pool = gpt.decode_step_paged(
                    params, jnp.asarray([cur], jnp.int32), pool,
                    jnp.asarray([t], jnp.int32), tables, cfg)
                cur = int(jnp.argmax(logits[0]))
            return out

        got_q = run(tiny_cfg(kv_dtype="int8"))
        got_f = run(tiny_cfg())
        assert got_q == got_f == rollout_reference(
            params, prompt, tiny_cfg(), 6)

    def test_quantize_params_layout(self, setup):
        """Weight-only int8: every matmul weight gains a per-output-
        channel scale sibling; norms/embeddings stay f32 masters."""
        _, params = setup
        qp = gpt.quantize_params(params)
        for name in gpt.QUANTIZED_WEIGHTS:
            w = qp["layers"][name]
            s = qp["layers"][name + "_scale"]
            assert w.dtype == jnp.int8
            assert s.shape == w.shape[:-2] + w.shape[-1:]
        assert qp["embed"].dtype == params["embed"].dtype
        assert qp["layers"]["ln1_scale"].dtype == jnp.float32


class TestQuantizedEngine:
    def test_greedy_token_identical_to_f32(self, setup, setup_q):
        """Engine-level tentpole criterion: int8-KV greedy decode is
        token-identical to the f32 engine across a shared aligned
        prefix AND a mid-block COW divergence."""
        cfg_q, params = setup_q
        cfg_f = tiny_cfg()
        rng = np.random.default_rng(21)
        x = list(rng.integers(0, 128, 16))
        y = x[:12] + list(rng.integers(0, 128, 4))   # COW split
        z = x + list(rng.integers(0, 128, 4))        # aligned extend

        def run(cfg):
            eng = make_engine(cfg, params)
            outs = [eng.generate(p, max_new_tokens=6) for p in
                    (x, y, z)]
            eng.check_invariants()
            return outs, eng.stats()

        got_q, sq = run(cfg_q)
        got_f, sf = run(cfg_f)
        assert got_q == got_f
        assert got_q[0] == rollout_reference(params, x, cfg_f, 6)
        assert sq["cow_copies"] >= 1 and sq["prefix_hit_tokens"] > 0
        assert sq["decode_traces"] == 1

    def test_weight_int8_quality_and_swap(self, setup):
        """Weight-only int8: greedy logprobs stay tight-allclose to the
        f32 engine (the pinned quality bound), the quantize executable
        compiles exactly once, and a same-shape update_params reuses it
        (RL-flywheel swap path, zero retraces)."""
        cfg_f = tiny_cfg()
        cfg_w = tiny_cfg(weight_dtype="int8")
        params = _peaked(setup[1])
        prompt = list(np.random.default_rng(23).integers(0, 128, 10))
        eng_w = make_engine(cfg_w, params)
        eng_f = make_engine(cfg_f, params)
        a = eng_w.generate(prompt, max_new_tokens=8)
        b = eng_f.generate(prompt, max_new_tokens=8)
        assert list(a) == list(b)           # peaked logits: same argmax
        deltas = [abs(x.logprob - y.logprob) for x, y in zip(a, b)]
        assert max(deltas) < 0.05
        assert eng_w.load_traces == 1
        assert eng_w.stats()["load_traces"] == 1
        eng_w.update_params(params)         # same shapes: no retrace
        assert eng_w.load_traces == 1
        assert list(eng_w.generate(prompt, max_new_tokens=8)) == list(b)
        eng_w.check_invariants()

    def test_pool_gauges(self, setup, setup_q):
        """`pool_bytes`/`kv_bytes_per_token` report the int8 shrink:
        payload bytes per token drop from 4 per element to 1 + the
        amortized scale column."""
        cfg_q, params = setup_q
        sq = make_engine(cfg_q, params).stats()
        sf = make_engine(tiny_cfg(), params).stats()
        assert 0 < sq["pool_bytes"] < sf["pool_bytes"]
        hd = cfg_q.head_dim
        assert sf["kv_bytes_per_token"] / sq["kv_bytes_per_token"] == \
            pytest.approx(4 * hd / (hd + 4))
        # engine invariants audit the scale arrays alongside payloads
        eng = make_engine(cfg_q, params)
        eng.generate([1, 2, 3, 4], max_new_tokens=3)
        eng.check_invariants()


def test_fuzz_small_quantized(setup):
    """The admit/cancel/retire storm on an int8 pool: COW, eviction and
    abandonment with `check_invariants` auditing scale arrays after
    every operation."""
    s = _fuzz((tiny_cfg(kv_dtype="int8"), setup[1]), ops=40, seed=0)
    assert s["decode_tokens"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("seed", [5, 6])
def test_fuzz_large_quantized(setup, seed):
    _fuzz((tiny_cfg(kv_dtype="int8"), setup[1]), ops=300, seed=seed)
