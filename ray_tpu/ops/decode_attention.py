"""Decode attention over a paged KV pool — Pallas TPU kernels plus
pure-JAX fallbacks with identical math.

The autoregressive hot path: one new query per sequence attends over that
sequence's cached keys/values. There is no O(T^2) score matrix here — per
(batch, head) the work is a [1, D] x [D, S] matvec — so the op is purely
HBM-bandwidth-bound (arithmetic intensity ~1 flop/byte). What the kernels
buy over the XLA fallback is the same thing flash_attention buys the
training path: the masked scores, softmax statistics and weighted sum all
live in VMEM while K/V blocks stream through, so the [B, H, S] score
tensor is never written to HBM and the per-position mask costs no extra
pass.

**Paged decode** (`paged_decode_attention`): K/V live in a shared block
pool ``[n_blocks, block_size, H, D]`` and each sequence names its blocks
through an int32 block table ``[B, max_blocks]`` (logical block j of
sequence b is physical block ``tables[b, j]``). The kernel
(`paged_decode`) runs ``grid = (B, 1)``: a program is one stream, and its
loop's trip count is the stream's own, ``pos[b] // block_size + 1`` live
pages walked in turns of several pages. The pools stay in HBM in the
layout the model writes, and where it keeps them: the model's whole
stacked cache ``[L, n_blocks, block_size, H, D]`` may be handed over with
the layer to read (a pool of one layer is a stack of one). The layer,
the block table and positions arrive as scalar prefetch, a page is one
DMA of a block named by the layer and the table, and a turn's pages land
in one of two buffers while the turn before is computed, so what is read
follows what is live and nothing else does: a layer scan never slices
the pool. A turn scores
every head of its pages at once with two plain matmuls over the page as
stored and a mask that keeps each head its own rows; running (m, l, acc)
softmax statistics live in VMEM scratch and the output is written once.
`_decode_plan` chooses pages a turn and the scoped VMEM from the shapes,
the pool's dtype and the chip's VMEM; `_paged_kernel` says what a
dead page costs (nothing is fetched for it; the V rows it leaves in a
buffer are zeroed). Two things follow from decode:

- **position masking**: each sequence attends to logical positions
  ``<= pos[b]`` (its current token's position — the caller writes the new
  K/V at ``pos`` *before* attending), so stale data in partially-filled
  tail blocks never contributes.
- **idle rows are nearly free**: a row with ``pos = 0`` and a table of
  zeros (how the engine marks a slot that is not decoding) is one page
  and one turn, whatever the table's width.

**Verify and the fused prefill** (`paged_mq`) are the same kernel body
with W query rows a head where decode has one: the queries go in as the
model made them, ``[W * H, D]`` (row ``w * H + h``), a row of scores
keeps its own head's columns at or before its own position (the
staircase ``col <= pos + w``), and the trip count is the last query's,
``(pos + W - 1) // block_size + 1`` pages. A verify step is ``grid =
(B, 1)`` with W = ``spec_k + 1``; a prefill chunk is one stream with W
the chunk's bucket, in programs of `_PROGRAM_ROWS` score rows along the
grid's second axis where one does not hold them (64 queries at 16
heads). Neither copies, slices or transposes a layer: the same DMAs
from the same stacked pool.

The JAX fallback gathers ``pool[tables]`` and attends with
`reference_decode_attention`, the same masking and f32 accumulation.

**Int8 pools** (`ops/quant.py`): every paged op takes optional
``k_scale`` / ``v_scale`` arrays ``[n_blocks, bs, H]`` f32 — one scale
per (position, head) row of an int8 pool. Dequantization happens
*inside* the kernels (a page's scales are fetched through the same
table entry as its payload) and inside the fallbacks (gathered
through the same `gather_kv_pages`), so HBM reads stay int8 and the
block-table machinery above never sees the dtype. Scales absent ==
full-precision pool, bit-for-bit the pre-quantization math.

**Fused paged prefill** (`paged_prefill_attention`): chunked-prefill
attention for one sequence over the same paged pool — the dense-math
JAX path is exactly the gather+einsum that used to live inline in
`models.gpt.prefill_paged`, and the Pallas path is the multi-query
kernel above (the prefill staircase ``col <= start + row`` IS the
verify mask with ``pos = start``), so the [C, S] score matrix stays in
VMEM, a turn of pages at a time, instead of round-tripping through HBM.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend

NEG_INF = -1e30

# Kernel names in the compiled program and the profiler's trace
# (`%paged_decode.N = ... custom-call`); PERF.md, section 3, lists them.
# Each call sits in a `named_scope` of its own name: see flash_attention.py.
# One kernel body (`_paged_kernel`) under two names: the decode step's one
# query a head is `paged_decode`; verify and the fused prefill share
# `paged_mq`.
PAGED_DECODE, PAGED_MQ = "paged_decode", "paged_mq"
# The same body over pages that hold one key-value head each
# (`gqa_decode_attention`, `gqa_chunk_attention`): a window layer's call
# and a full layer's, the decode step's and a prompt chunk's, so that a
# trace parts the four.
GQA_WINDOW_DECODE, GQA_FULL_DECODE = "gqa_window_decode", "gqa_full_decode"
GQA_WINDOW_CHUNK, GQA_FULL_CHUNK = "gqa_window_chunk", "gqa_full_chunk"
# a full layer's two calls inside a program that holds a step and a chunk
# (`ServingFamily.tick`): names of their own, since a reader divides a
# name's seconds over the whole trace by the runs of one program
GQA_FULL_DECODE_TICK = "gqa_full_decode_tick"
GQA_FULL_CHUNK_TICK = "gqa_full_chunk_tick"


# ---------------------------------------------------------------------------
# pure-JAX fallback (the everywhere-correct path; CPU/CI default)
# ---------------------------------------------------------------------------

def reference_decode_attention(q, k, v, pos):
    """q [B, H, D]; k, v [B, S, H, D]; pos [B] i32. Attends to cache
    positions <= pos[b] and returns [B, H, D] in q.dtype. Accumulation is
    f32 regardless of input dtype (same contract as the kernel)."""
    b, s, h, d = k.shape
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    live = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= \
        pos.astype(jnp.int32)[:, None, None]
    scores = jnp.where(live, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# K/V behind a block table
# ---------------------------------------------------------------------------

def gather_kv_pages(pool, tables):
    """Materialize per-sequence K or V from a block pool:
    ``pool [n_blocks, bs, H, D]`` gathered through ``tables
    [B, max_blocks]`` -> ``[B, max_blocks * bs, H, D]`` where row b's
    logical position ``p`` lives at ``(tables[b, p // bs], p % bs)``.
    The JAX fallback path and the chunked-prefill context read share
    this one gather."""
    b, mb = tables.shape
    nb, bs = pool.shape[0], pool.shape[1]
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    idx = (tables.astype(jnp.int32)[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
        b, mb * bs)
    return flat[idx]


def _gather_dequant(pool, scale, tables):
    """Gather a (possibly int8) pool through block tables; with a
    per-row ``scale [n_blocks, bs, H]`` the gathered sequence is
    dequantized to f32 (`ops.quant` row convention), otherwise it is
    returned untouched — the full-precision path stays bit-identical."""
    seq = gather_kv_pages(pool, tables)
    if scale is None:
        return seq
    return seq.astype(jnp.float32) * \
        gather_kv_pages(scale, tables).astype(jnp.float32)[..., None]


def reference_paged_decode_attention(q, k_pool, v_pool, tables, pos, *,
                                     k_scale=None, v_scale=None):
    """q [B, H, D]; k_pool, v_pool [n_blocks, bs, H, D]; tables
    [B, max_blocks] i32; pos [B] i32. Gather-then-attend fallback with
    the exact masking/accumulation math of the paged kernel. With
    ``k_scale`` / ``v_scale`` [n_blocks, bs, H] f32 the pools are int8
    and dequantized after the gather (same math the kernel applies
    in VMEM)."""
    k_seq = _gather_dequant(k_pool, k_scale, tables)
    v_seq = _gather_dequant(v_pool, v_scale, tables)
    return reference_decode_attention(q, k_seq, v_seq, pos)


# ---------------------------------------------------------------------------
# the paged kernel: a program a stream (and a tile of its query rows) over
# its own pages
# ---------------------------------------------------------------------------

_TURN_TOKENS = 128              # cached positions a turn, where they fit
_PROGRAM_ROWS = 1024            # score rows (queries x heads) a program
_FAR = 1 << 30                  # a position no query ever reaches


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


def _query_tile(w: int, h: int) -> int:
    """Queries a head that one program of the kernel scores: all `w` of a
    stream where their `w * h` score rows are `_PROGRAM_ROWS` or fewer
    (the decode step's one, a verify step's few, a 64-token chunk at 16
    heads), else as many as fill `_PROGRAM_ROWS`, the rest in further
    programs of the grid's second axis."""
    return min(w, max(1, _PROGRAM_ROWS // h))


def _gqa_query_tile(w: int, g: int) -> int:
    """`_query_tile` for a key-value head's group of `g` query heads:
    where the queries go to several programs, as many of them as make a
    program's `wt * g` score rows whole sublane tiles (a group of 5: 200
    queries, not 204; a group of 16: `_query_tile`'s 64)."""
    wt = _query_tile(w, g)
    return wt if wt == w else max(wt - wt % (8 // math.gcd(g, 8)), 1)


class _DecodePlan(NamedTuple):
    """What the paged kernel runs at (`_decode_plan` chooses it). `pack`:
    heads side by side in a row of lanes, 1 where the pool goes to the
    kernel as the model stores it. `pages`: pages a turn of a stream's
    loop. `vmem_limit`: what the working set asks of
    `CompilerParams(vmem_limit_bytes=)`, None where the compiler's
    default scope holds it."""
    pack: int
    pages: int
    vmem_limit: int | None


def _decode_plan(bs: int, h: int, d: int, dtype, quantized: bool, w: int,
                 vmem: int | None = None) -> _DecodePlan | None:
    """The plan for pools `[n_blocks, bs, h, d]` of `dtype` and `w`
    queries a head a program (1: the decode step), from the shapes, the
    element size and the chip's VMEM alone.

    A page reaches VMEM by one DMA, and Mosaic moves by DMA only slices
    whose lanes fill whole 128-lane tiles. A head size that is a multiple
    of 128 does as stored: `pack` 1, no operand touched. A smaller one
    that divides 128 XLA stores in a layout of its own, so the wrapper
    lays `pack = 128 // d` heads side by side (`[n_blocks, bs * h / pack,
    128]`, one copy of the layer read). Pages a turn: `_TURN_TOKENS`
    positions, halved until two turns of K and V, what the body makes of
    one, its `w * h` rows of score tiles and of running state fit the
    default scope; one page that does not fit asks for what it needs,
    up to half the VMEM. None where no row of lanes can be made (`d`
    neither a multiple nor a divisor of 128, heads that do not fill
    rows or sublanes) or an int8 pool's scale rows are not whole lane
    tiles: the caller takes the JAX path."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item                             # sublanes of a tile
    pack = 1 if d % 128 == 0 else 128 // d
    rows = bs * h // pack                           # score columns a page
    if pack == 1:
        ok = h % 8 == 0
        page = bs * _up(h, sub) * d * item          # as VMEM holds it
    else:
        ok = pack * d == 128 and h % pack == 0 and rows % sub == 0
        page = rows * 128 * item
    if not ok or (quantized and rows % 128):
        return None
    qrows, lanes = _up(w * h, 8), max(d, 128)
    # q and the output as they come and go (two buffers each, in the
    # activations' dtype: the pool's, or bf16 over an int8 pool), q as it
    # is scaled, acc, m and l
    state = qrows * (lanes * (4 * max(item, 2) + 4 + 4) + 128 * 4 * 2)

    def working_set(pages):
        cols = pages * rows
        bufs = 2 * 2 * pages * page                 # K and V, two turns
        if quantized:
            bufs += 2 * 2 * 8 * cols * 4            # their scale rows
        operands = 2 * cols * lanes * 4             # K, V as the MXU gets them
        # the mask, s and p: the compiler keeps two and a half of them
        # alive (AOT for a v5e, 1024 rows: 5.4 / 7.3 / 13.6 / 22.3 MB of
        # scoped VMEM used at 256 / 512 / 1024 / 2048 columns; this
        # estimate 6.0 / 9.0 / 15.0 / 27.0)
        scores = 5 * qrows * cols * 2
        if quantized and pack > 1:      # the scales' rows tiled over the
            scores += qrows * cols * 4  # queries' (pack 1: a broadcast)
        return bufs + operands + scores + state

    pages = max(1, _TURN_TOKENS // bs)
    while pages > 1 and working_set(pages) > backend.SCOPED_VMEM_DEFAULT:
        pages //= 2
    need = working_set(pages)
    if need <= backend.SCOPED_VMEM_DEFAULT:
        return _DecodePlan(pack, pages, None)
    if need > (vmem or backend.vmem_capacity()) // 2:
        return None
    # the estimate and a quarter for what it cannot see
    return _DecodePlan(pack, pages, _up(need + need // 4, 1 << 20))


def reads_pool_where_it_lies(bs: int, h: int, d: int, dtype,
                             quantized: bool) -> bool:
    """Whether the paged kernel takes pools `[L, n_blocks, bs, h, d]` of
    `dtype` as the model stores them (`_decode_plan`'s `pack` 1: a head
    fills its 128 lanes), so that a layer loop can keep the stacked pool
    in one buffer and hand it over whole. At a smaller head size XLA
    stores the pool in a layout of its own and every reader and writer
    of rows works on a lay-out of it: a layer loop should then take one
    layer out at a time, or that lay-out is the whole pool's. (`pack`
    follows the head size alone, whatever the queries a program.)"""
    plan = _decode_plan(bs, h, d, dtype, quantized, 1)
    return plan is None or plan.pack == 1


def _paged_kernel(tbl_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                  sm_scale: float, pack: int, pages: int, block_size: int,
                  heads: int, queries: int, quantized: bool,
                  window: int | None = None, head_major: bool = False):
    """One stream's queries, `wt` of them a head a program (all `queries`
    of them in one where the grid's second axis is 1): the stream's live
    pages in turns of `pages`, online softmax over a turn's every head
    at once. The decode step is `queries` 1; a verify step and a prefill
    chunk (`paged_mq`) are the same body with more rows. The pools are
    stacked, `[L, n_blocks, ...]`, and every page's DMA reads layer
    `layer_ref[0]` of them: the layer is one more number in the copy's
    index, never a slice of the pool.

    A turn's K is `[cols, lanes]`: row `c` holds position `c // hr` of
    the turn and the `pack` heads from `c % hr * pack` on, `hr = H /
    pack` rows a position (with `pack` 1, a page as the model wrote it:
    `[bs, H, D]` is `[bs * H, D]`). The queries are `[wt * H, lanes]`,
    as the model made them: row `r` is query `r // H` of head `r % H`,
    at position `pos + r // H`. `q . K^T` scores every row against every
    column, `[wt * H, cols]`; a row of scores keeps the columns that
    hold its own head and are at or before its own position (`ahead`:
    the staircase `col <= pos + row` of a chunk, one step of it a
    query), the rest are masked like a dead position, and `p . V` then
    sums a head's own rows only. The MXU does `hr` times the useful
    multiplications on 128 x 128 tiles it would otherwise leave idle; no
    head is ever moved out of the layout it was stored in.

    Dead pages: a turn issues and awaits DMAs for its live pages only
    (page `j` is live while `j <= last // bs`, `last` the position of
    the program's last query; an idle row, `pos` 0, is one page, one
    turn), so no table entry past the length is read and no byte of a
    dead page is moved. The last turn's dead pages are whatever the
    buffer held: their scores are masked by `ahead`, and their V rows
    (and V scales) are zeroed before `p . V`, because zero times a NaN
    is a NaN. What is read beyond the live positions is the rest of each
    stream's last live page: `bs - 1 - last % bs` positions, under one
    page of K and one of V a stream a layer. A query row past `queries`
    (the last program's padding) scores what the real ones fetched and
    is cut away by the wrapper.

    `window`: a query at position `i` keeps the positions `i - window <
    j <= i` only. The pages wholly before the window of the program's
    first query are not fetched: the loop starts at the page that holds
    position `first - window + 1`, whatever column of the table that is,
    and the mask cuts the rest of that page and, for the later queries
    of a chunk, what has left their own window. A row that a turn masks
    whole before any of its scores was kept adds `exp(0)` a column to
    sums that the first kept score's correction, `exp(NEG_INF - m)`,
    turns to exactly zero; every row keeps its own position at least.

    `head_major`: the pools are `[L, n_blocks, Hkv, bs, D]`, a page of
    one key-value head a contiguous `[bs, D]`, and the grid is `(B, Hkv,
    tiles)`: a program is a stream, one key-value head and `wt` queries
    of each of the `heads` query heads that read it (`heads` is then the
    group, 16 of Command A+'s 128 over 8). Its page is fetched once for
    all of them and every column holds its rows' own head, so no
    multiplication is masked away; q and K go to the MXU in the type
    they are stored in and the scale is applied to the float32 scores
    (the products of two bfloat16 values are exact in float32, so this
    is the float32 matmul's result at the bfloat16 rate)."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    bs, mb = block_size, tbl_ref.shape[1]
    layer = layer_ref[0]
    qrows, lanes = q_ref.shape[-2:]
    h = heads
    wt = qrows // h                     # queries a head here
    # rows of lanes a cached position: a page of one head has one
    hr = 1 if head_major else h // pack
    rows = bs * hr                      # score columns a page
    cols = pages * rows
    size = kbuf.shape[1] // pages       # a page along the buffer's rows
    t = pl.program_id(2 if head_major else 1)
    first = pos_ref[b] + t * wt         # this program's first query's position
    last = pos_ref[b] + jnp.minimum((t + 1) * wt, queries) - 1  # last real
    n_pages = jnp.minimum(last // bs + 1, mb)
    if window is None:
        n_turns = (n_pages + pages - 1) // pages

        def page_of(c, i=None):         # page `i` of turn `c`
            return c * pages if i is None else c * pages + i
    else:
        page0 = jnp.maximum(first - (window - 1), 0) // bs
        n_turns = (n_pages - page0 + pages - 1) // pages

        def page_of(c, i=0):
            return page0 + c * pages + i

    kv_head = pl.program_id(1) if head_major else None

    def at(pool, blk):
        """Block `blk` of the layer, as one DMA reads it."""
        if head_major:
            return pool.at[layer, blk, kv_head]
        return pool.at[layer, blk]

    def copies(blk, slot, i):
        """Page `i` of a turn: block `blk` into buffer `slot`."""
        page, scales = pl.ds(i * size, size), pl.ds(i * rows, rows)
        pairs = [(at(k_hbm, blk), kbuf.at[slot, page]),
                 (at(v_hbm, blk), vbuf.at[slot, page])]
        if quantized:       # the scales come laid out, one layer's
            pairs += [(ks_hbm.at[blk], ksbuf.at[slot, :, scales]),
                      (vs_hbm.at[blk], vsbuf.at[slot, :, scales])]
        return [pltpu.make_async_copy(src, dst, sem.at[slot])
                for src, dst in pairs]

    def issue(c, slot):
        for i in range(pages):
            @pl.when(page_of(c, i) < n_pages)
            def _start():
                for cp in copies(tbl_ref[b, page_of(c, i)], slot, i):
                    cp.start()

    def land(c, slot):
        for i in range(pages):
            live = page_of(c, i) < n_pages

            @pl.when(live)
            def _wait():        # the semaphore counts bytes, not blocks
                for cp in copies(0, slot, i):
                    cp.wait()

            @pl.when(jnp.logical_not(live))
            def _zero():
                vbuf[slot, pl.ds(i * size, size)] = jnp.zeros(
                    (size,) + vbuf.shape[2:], vbuf.dtype)
                if quantized:
                    vsbuf[slot, :, pl.ds(i * rows, rows)] = jnp.zeros(
                        (pack, rows), jnp.float32)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    if head_major:
        q = q_ref[0, 0]                                     # [wt * g, lanes]
    else:
        q = q_ref[0].astype(jnp.float32) * sm_scale         # [wt * H, lanes]
    row = jax.lax.broadcasted_iota(jnp.int32, (qrows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (qrows, cols), 1)
    head, query = row % h, row // h
    # how far a column's position within the turn lies ahead of the row's
    # query where it holds the row's head, past every query where not. A
    # query past the table's reach (a draft step near the longest length)
    # sees what the table's last position sees: no page holds more
    query = jnp.minimum(query, mb * bs - 1 - first)
    if head_major:      # every column holds the rows' own key-value head
        ahead = col - query
    else:
        ahead = jnp.where(col % hr == head // pack, col // hr - query, _FAR)
    issue(0, 0)

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_turns)
        def _next():
            issue(c + 1, 1 - slot)

        land(c, slot)
        if head_major:
            k = kbuf[slot]
            both = jnp.promote_types(q.dtype, k.dtype)
            q_, k = q.astype(both), k.astype(both)
        else:
            q_, k = q, kbuf[slot].astype(jnp.float32).reshape(cols, lanes)
        v = vbuf[slot]
        if quantized:
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(
            q_, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [wt * H, cols]
        if head_major:
            s = s * sm_scale
        if quantized:   # row r takes the scales of head r % H: r % pack here
            s = s * jnp.tile(ksbuf[slot], (qrows // pack, 1))
        reach = first - page_of(c) * bs     # of the program's first query
        keep = ahead <= reach
        if window is not None:
            keep = keep & (ahead > reach - window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=1,
                                                     keepdims=True)
        m_scr[:, :1] = m_new
        if quantized:
            p = p * jnp.tile(vsbuf[slot], (qrows // pack, 1))
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v.reshape(cols, lanes),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [wt * H, lanes]
        return _

    jax.lax.fori_loop(0, n_turns, step, 0)
    out = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
    if head_major:
        o_ref[0, 0] = out
    else:
        o_ref[0] = out


def _paged_call(name: str, q, k_pool, v_pool, tables, pos, layer, *,
                plan: _DecodePlan, block_size: int, heads: int, queries: int,
                sm_scale: float, interpret: bool, ks=None, vs=None,
                window: int | None = None, head_major: bool = False):
    """q [B, tiles * wt * H, lanes], a stream's queries as the model
    made them (`_paged_kernel` has the order; `queries` of the `tiles *
    wt` are real); k_pool, v_pool in HBM, stacked: `[L, n_blocks, bs, H,
    D]` as stored (`plan.pack` 1) or `[1, n_blocks, bs * H / pack,
    128]`; tables [B, max_blocks], pos [B] and layer [1] i32,
    scalar-prefetched -> q's shape. `grid = (B, tiles)`: a program is a
    stream (and `wt` of its queries a head), its loop's trip count its
    own live pages, each page one DMA named by the layer and the table,
    a turn's pages landing while the turn before is computed.
    ``ks``/``vs`` `[n_blocks, pack, bs * H / pack]` f32, one layer's,
    mark int8 pools: row `j` holds the scales of heads `j, j + pack, ..`
    in the order of a page's rows, fetched page by page beside the
    payload and applied, a row of them to the score columns and to the
    probabilities (``q . (k_j * s_j) == (q . k_j) * s_j``, ``p @ (v * s)
    == (p * s) @ v``: no `[cols, D]` multiply and no relayout).

    `head_major`: pools `[L, n_blocks, Hkv, bs, D]`, q `[B, Hkv, tiles *
    wt * g, lanes]` (a key-value head's `heads` = g query heads side by
    side, row `w * g + j`), `grid = (B, Hkv, tiles)`; `window` as
    `_paged_kernel` says."""
    b, total, lanes = q.shape[0], q.shape[-2], q.shape[-1]
    qrows = (_gqa_query_tile if head_major else _query_tile)(
        queries, heads) * heads
    quantized = ks is not None
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if head_major:
        grid = (b, q.shape[1], total // qrows)
        row = pl.BlockSpec((1, 1, qrows, lanes),
                           lambda i, j, t, tbl, ps, ly: (i, j, t, 0))
        turn = (2, plan.pages * block_size, lanes)
    else:
        grid = (b, total // qrows)
        row = pl.BlockSpec((1, qrows, lanes),
                           lambda i, t, tbl, ps, ly: (i, t, 0))
        turn = (2, plan.pages * k_pool.shape[2]) + k_pool.shape[3:]
    more = {} if window is None and not head_major else {
        "window": window, "head_major": head_major}
    operands = [tables, pos, layer, q, k_pool, v_pool]
    scratch = [pltpu.VMEM(turn, k_pool.dtype)] * 2
    if quantized:
        operands += [ks, vs]
        scratch += [pltpu.VMEM((2, plan.pack, plan.pages * ks.shape[2]),
                               jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((qrows, 128), jnp.float32),    # m (column 0 used)
                pltpu.VMEM((qrows, 128), jnp.float32),    # l
                pltpu.VMEM((qrows, lanes), jnp.float32)]  # acc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid,
        in_specs=[row] + [hbm] * (len(operands) - 4),
        out_specs=row, scratch_shapes=scratch)
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(_paged_kernel, sm_scale=sm_scale,
                              pack=plan.pack, pages=plan.pages,
                              block_size=block_size, heads=heads,
                              queries=queries, quantized=quantized, **more),
            name=name,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                # streams, and a stream's tiles of queries, share nothing
                dimension_semantics=("parallel",) * len(grid),
                vmem_limit_bytes=plan.vmem_limit),
            interpret=interpret,
        )(*operands)


def reference_paged_verify_attention(q, k_pool, v_pool, tables, pos, *,
                                     k_scale=None, v_scale=None):
    """Multi-query verify attention, gather-then-attend fallback.

    q [B, W, H, D]: W query tokens per sequence, token i of row b sits at
    logical position ``pos[b] + i`` and attends to cache positions
    ``<= pos[b] + i`` (the caller writes all W tokens' K/V *before*
    attending, so draft token i sees drafts 0..i-1 — in-cache causal).
    k_pool, v_pool [n_blocks, bs, H, D]; tables [B, max_blocks] i32;
    pos [B] i32. Returns [B, W, H, D] in q.dtype. ``k_scale``/``v_scale``
    [n_blocks, bs, H] f32 mark int8 pools (dequantized after the
    gather)."""
    k_seq = _gather_dequant(k_pool, k_scale, tables)
    v_seq = _gather_dequant(v_pool, v_scale, tables)
    b, s, h, d = k_seq.shape
    w = q.shape[1]
    scores = jnp.einsum("bwhd,bshd->bhws", q.astype(jnp.float32),
                        k_seq.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    limit = pos.astype(jnp.int32)[:, None] + jnp.arange(w, dtype=jnp.int32)
    live = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= \
        limit[:, :, None]                                # [B, W, S]
    scores = jnp.where(live[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhws,bshd->bwhd", p, v_seq.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _layer_of(pool, layer):
    """Layer `layer` (may be traced), `[n_blocks, ...]`, of a stacked
    pool `[L, n_blocks, ...]`: one layer's bytes, for a reader that
    copies or gathers what it reads anyway. A pool given without a layer
    (or no pool at all: absent scales) is returned as it came."""
    if pool is None or layer is None:
        return pool
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def _check_pools(op: str, q, q_form: str, k_pool, tables, tables_form: str,
                 layer):
    """Rank check shared by the paged wrappers: a pool is one layer's
    `[n_blocks, bs, H, D]`, or stacked `[L, n_blocks, bs, H, D]` with
    the `layer` to read."""
    stacked = layer is not None
    if (q.ndim != len(q_form.split(",")) or k_pool.ndim != 4 + stacked
            or tables.ndim != len(tables_form.split(","))):
        raise ValueError(
            f"{op} wants q [{q_form}], pools [n_blocks, bs, H, D] (or "
            f"[L, n_blocks, bs, H, D] with layer=) and tables "
            f"[{tables_form}]; got {q.shape}, {k_pool.shape}, "
            f"{tables.shape}, layer "
            f"{'given' if stacked else None}")


def _check_scales(k_scale, v_scale, k_pool, op: str):
    """Both-or-neither scale validation shared by the paged wrappers;
    returns True when the pool is quantized."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            f"{op} wants both k_scale and v_scale or neither; got "
            f"k_scale={'set' if k_scale is not None else None}, "
            f"v_scale={'set' if v_scale is not None else None}")
    if k_scale is None:
        return False
    if k_scale.shape != k_pool.shape[:-1]:
        raise ValueError(
            f"{op} scale shape {k_scale.shape} != pool row shape "
            f"{k_pool.shape[:-1]} ([n_blocks, bs, H], stacked like the "
            "pool)")
    return True


def _kernel_plan(op: str, impl: str, q, k_pool, quantized: bool):
    """Resolve a paged wrapper's ``impl`` for queries ``q [B, W, H, D]``:
    the plan its kernel runs at, or None for the JAX path. ``"auto"`` is
    the kernel on a TPU where `_decode_plan` has a plan (a shape without
    one is recorded), the JAX path elsewhere."""
    _, w, h, d = q.shape
    bs = k_pool.shape[-3]
    plan = _decode_plan(bs, h, d, k_pool.dtype, quantized,
                        _query_tile(w, h))
    shape = (f"block_size {bs}, {w} queries of {h} heads of {d}, "
             f"{k_pool.dtype} pool")
    if impl == "auto":
        if plan is None:
            backend.note_fallback(op, shape)
        impl = "pallas" if backend.on_tpu() and plan is not None else "jax"
    if impl == "jax":
        return None
    if impl != "pallas":
        raise ValueError(
            f"unknown {op} impl {impl!r} (expected 'auto' | 'pallas' | "
            "'jax')")
    if plan is None:
        if not backend.interpret():
            raise ValueError(
                f"no paged kernel plan for {shape}; use impl='jax'")
        # the interpreter has no tiles to align: any shape, as stored
        plan = _DecodePlan(1, max(1, _TURN_TOKENS // bs), None)
    return plan


def _paged_attend(name: str, q, k_pool, v_pool, tables, pos, k_scale,
                  v_scale, layer, plan: _DecodePlan):
    """The kernel path of the three wrappers: ``q [B, W, H, D]``, query
    `i` of stream `b` at position ``pos[b] + i``, against the pools where
    and as they are stored -> ``[B, W, H, D]``. The queries go to the
    kernel in the order the model made them (``[B, W * H, D]`` is a free
    reshape), padded to whole programs where one does not hold them all.

    A head size that fills its lanes (`plan.pack` 1) leaves the pools
    untouched, stacked or not: the layer is a number in each page's DMA.
    At a smaller one XLA stores the pool padded to 128 lanes, and the
    one layer read is sliced out and laid out for the kernel here, one
    copy of a layer a call; an int8 pool's scales are laid out the same
    way, a layer's at a time (2 MB at the cells' shapes)."""
    b, w, h, d = q.shape
    nb, bs = k_pool.shape[-4:-2]
    pack = plan.pack
    wt = _query_tile(w, h)
    if w % wt:
        q = jnp.pad(q, ((0, 0), (0, -w % wt), (0, 0), (0, 0)))
    q = q.reshape(b, -1, d)                         # [B, tiles * wt * H, D]
    ks = vs = None
    if k_scale is not None:     # laid out for the kernel, one layer's scales
        lay = lambda sc: _layer_of(sc, layer).reshape(
            nb, bs, h // pack, pack).transpose(0, 3, 1, 2).reshape(
            nb, pack, bs * h // pack)
        ks, vs = lay(k_scale), lay(v_scale)
    if pack > 1:
        # `pack` heads side by side in a row of 128 lanes; a row of the
        # queries and of the output keeps the lanes of its own head
        # (row r is head r % H, and `pack` divides H)
        own = (jnp.arange(q.shape[1])[:, None] % pack
               == jnp.arange(pack * d)[None, :] // d)       # [rows, lanes]
        q = jnp.where(own, jnp.tile(q, (1, 1, pack)), 0)
        # the lay-out is a copy, so it is a layer's: slice, then reshape
        # (a reshape of the stacked pool would copy every layer, and in a
        # layer scan do so once a layer)
        k_pool, v_pool = (
            _layer_of(pool, layer).reshape(nb, bs * h // pack, pack * d)
            for pool in (k_pool, v_pool))
        layer = None
    if layer is None:       # one layer is a stack of one: a free reshape
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    out = _paged_call(name, q, k_pool, v_pool, tables.astype(jnp.int32),
                      pos.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32).reshape(1), plan=plan,
                      block_size=bs, heads=h, queries=w, sm_scale=d ** -0.5,
                      interpret=backend.interpret(), ks=ks, vs=vs)
    if pack > 1:
        out = jnp.sum(jnp.where(own, out, 0).reshape(b, -1, pack, d), axis=2)
    return out.reshape(b, -1, h, d)[:, :w]


def paged_verify_attention(q, k_pool, v_pool, tables, pos, *,
                           k_scale=None, v_scale=None, layer=None,
                           impl: str = "auto"):
    """Masked multi-query attention through the paged cache — the verify
    half of speculative decoding. ``q [B, W, H, D]`` holds W query tokens
    per sequence (current token + W-1 speculated continuations); token i
    of row b sits at logical position ``pos[b] + i`` and attends to cache
    positions ``<= pos[b] + i``. Pools/tables as in
    `paged_decode_attention`, including the int8 ``k_scale``/``v_scale``
    contract and the stacked form with ``layer``. Returns
    ``[B, W, H, D]`` in q.dtype.

    impl: "auto" (pallas on a TPU where `_decode_plan` has a plan, else
    jax) | "pallas" | "jax"; the paths share masking/accumulation math.
    The kernel (`paged_mq`) is the decode step's with W rows a head: it
    reads each stream's live pages of the layer where the pool lies
    (`_paged_attend`); the jax path gathers one layer's slice."""
    _check_pools("paged_verify_attention", q, "B, W, H, D", k_pool,
                 tables, "B, max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_verify_attention")
    plan = _kernel_plan("paged_verify_attention", impl, q, k_pool,
                        quantized)
    if plan is None:
        k, v, ks, vs = (_layer_of(a, layer)
                        for a in (k_pool, v_pool, k_scale, v_scale))
        return reference_paged_verify_attention(q, k, v, tables, pos,
                                                k_scale=ks, v_scale=vs)
    return _paged_attend(PAGED_MQ, q, k_pool, v_pool, tables, pos,
                         k_scale, v_scale, layer, plan)


def paged_decode_attention(q, k_pool, v_pool, tables, pos, *,
                           k_scale=None, v_scale=None, layer=None,
                           impl: str = "auto"):
    """Decode-step attention through a paged KV cache: ``q [B, H, D]``
    against a block pool ``k_pool, v_pool [n_blocks, block_size, H, D]``
    indexed by ``tables [B, max_blocks]`` i32 (logical block j of row b
    is physical block ``tables[b, j]``; entries past the allocated
    length may be any valid block — the kernel never reads them, the
    JAX path masks them). Attends to logical positions ``<= pos[b]`` and
    returns ``[B, H, D]`` in q.dtype.

    The pools may be the model's whole stacked cache ``[L, n_blocks,
    block_size, H, D]`` with ``layer`` (a scalar, traced in a layer
    scan) the layer to read: the form a layer loop wants, because the
    kernel then reads the layer where it lies and the loop never slices
    the pool.

    With ``k_scale``/``v_scale`` ``[n_blocks, bs, H]`` f32 (stacked like
    the pools) the pools hold int8 payloads (`ops.quant.quantize_rows`
    convention, one scale per position-head row); both impls dequantize
    at read — in VMEM for pallas, post-gather for jax — so HBM traffic
    stays int8.

    impl: "auto" (pallas on a TPU where `_decode_plan` has a plan, else
    jax) | "pallas" | "jax". The two paths share the same
    masking/accumulation math and agree to f32 tolerance. The kernel
    (`paged_decode`) is `_paged_attend`'s with one query a head: what it
    takes as stored and what it lays out is said there."""
    _check_pools("paged_decode_attention", q, "B, H, D", k_pool, tables,
                 "B, max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_decode_attention")
    rows = q[:, None]                   # one query a head: W = 1
    plan = _kernel_plan("paged_decode_attention", impl, rows, k_pool,
                        quantized)
    if plan is None:
        k, v, ks, vs = (_layer_of(a, layer)
                        for a in (k_pool, v_pool, k_scale, v_scale))
        return reference_paged_decode_attention(q, k, v, tables, pos,
                                                k_scale=ks, v_scale=vs)
    return _paged_attend(PAGED_DECODE, rows, k_pool, v_pool, tables, pos,
                         k_scale, v_scale, layer, plan)[:, 0]


# ---------------------------------------------------------------------------
# fused paged prefill: chunked-prefill attention over the pool
# ---------------------------------------------------------------------------

def reference_paged_prefill_attention(q, k_pool, v_pool, table, start, *,
                                      k_scale=None, v_scale=None):
    """Dense-math chunked-prefill attention for ONE sequence — exactly
    the gather+einsum that lived inline in `models.gpt.prefill_paged`
    (bit-for-bit on full-precision pools), factored out so the fused
    kernel has a reference to agree with.

    q [C, H, D]: the chunk's queries, token t at absolute position
    ``start + t``; the caller has already scattered the chunk's K/V into
    the pool, so token t attends to gathered positions ``<= start + t``
    (whole-prefix causal). k_pool, v_pool [n_blocks, bs, H, D]; table
    [max_blocks] i32; start scalar i32. Returns [C, H, D] in q.dtype.
    ``k_scale``/``v_scale`` [n_blocks, bs, H] mark int8 pools."""
    c, h, d = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    table = table.astype(jnp.int32)
    kctx = _gather_dequant(k_pool, k_scale, table[None])[0]
    vctx = _gather_dequant(v_pool, v_scale, table[None])[0]
    positions = jnp.asarray(start, jnp.int32) + \
        jnp.arange(c, dtype=jnp.int32)
    scores = jnp.einsum(
        "thd,shd->hts", q.astype(jnp.float32), kctx.astype(jnp.float32),
        preferred_element_type=jnp.float32) * (d ** -0.5)
    cols = jnp.arange(kctx.shape[0], dtype=jnp.int32)
    live = cols[None, None, :] <= positions[None, :, None]
    scores = jnp.where(live, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hts,shd->thd", p, vctx.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return att.astype(q.dtype)


def paged_prefill_attention(q, k_pool, v_pool, table, start, *,
                            k_scale=None, v_scale=None, layer=None,
                            impl: str = "auto"):
    """Chunked-prefill attention for one sequence through the paged
    pool: ``q [C, H, D]`` (chunk token t at absolute position
    ``start + t``) attends over the sequence's whole gathered prefix —
    the caller scatters the chunk's K/V into the pool FIRST, exactly as
    `models.gpt.prefill_paged` always has.

    The pallas path is the verify kernel's (`paged_mq`): the prefill
    staircase (token t sees positions ``<= start + t``) is the verify
    mask with ``pos = start``, ``W = C`` and one stream, so the [C, S]
    score matrix lives a turn of pages at a time in VMEM instead of
    round-tripping through HBM, and only the pages up to ``start + C -
    1`` are fetched, from the pool where it lies (`_paged_attend`).
    The jax path is the legacy dense gather+einsum
    (`reference_paged_prefill_attention`) — bit-identical to the
    pre-fused inline math, which keeps ``impl="jax"`` the bitwise
    default on CPU. ``k_scale``/``v_scale`` [n_blocks, bs, H] mark int8
    pools, dequantized at read on both paths. Pools and scales may be
    stacked ``[L, n_blocks, ...]`` with ``layer`` the one to read, as in
    `paged_decode_attention`: the kernel reads that layer's live pages
    in place, the jax path gathers from its slice.

    impl: "auto" (pallas on a TPU where `_decode_plan` has a plan, else
    jax) | "pallas" | "jax". Returns ``[C, H, D]`` in q.dtype."""
    _check_pools("paged_prefill_attention", q, "C, H, D", k_pool, table,
                 "max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_prefill_attention")
    plan = _kernel_plan("paged_prefill_attention", impl, q[None], k_pool,
                        quantized)
    if plan is None:
        k, v, ks, vs = (_layer_of(a, layer)
                        for a in (k_pool, v_pool, k_scale, v_scale))
        return reference_paged_prefill_attention(q, k, v, table, start,
                                                 k_scale=ks, v_scale=vs)
    # one sequence is one stream of the kernel: B = 1, W = C, pos = start
    return _paged_attend(PAGED_MQ, q[None], k_pool, v_pool, table[None],
                         jnp.asarray(start).reshape(1), k_scale, v_scale,
                         layer, plan)[0]


# ---------------------------------------------------------------------------
# grouped key-value heads over head-major pages, with or without a window
# ---------------------------------------------------------------------------

def reference_gqa_attention(q, k, v, pos, window=None):
    """q [B, W, Hq, D], query `i` of row `b` at position ``pos[b] + i``;
    k, v [B, S, Hkv, D]; query head `h` reads key-value head ``h // (Hq
    // Hkv)`` at the positions ``j <= pos[b] + i`` and, with `window`,
    ``j > pos[b] + i - window``. -> [B, W, Hq, D] in q.dtype, float32
    inside: the definition the kernels below are held to."""
    b, w, hq, d = q.shape
    s, hkv = k.shape[1:3]
    qg = q.astype(jnp.float32).reshape(b, w, hkv, hq // hkv, d)
    scores = jnp.einsum("bwkgd,bskd->bkgws", qg, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * d ** -0.5
    at = (pos.astype(jnp.int32)[:, None]
          + jnp.arange(w, dtype=jnp.int32))[:, :, None]       # [B, W, 1]
    cols = jnp.arange(s, dtype=jnp.int32)
    live = cols <= at
    if window is not None:
        live &= cols > at - window
    p = jax.nn.softmax(jnp.where(live[:, None, None], scores, NEG_INF), -1)
    out = jnp.einsum("bkgws,bskd->bwkgd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, w, hq, d).astype(q.dtype)


def gather_head_major(pool, tables):
    """`pool [n_blocks, Hkv, bs, D]` through `tables [B, max_blocks]` ->
    `[B, max_blocks * bs, Hkv, D]`: logical position `p` of row `b` is
    `pool[tables[b, p // bs], :, p % bs]`."""
    b, mb = tables.shape
    _, hkv, bs, d = pool.shape
    return pool[tables.astype(jnp.int32)].transpose(0, 1, 3, 2, 4).reshape(
        b, mb * bs, hkv, d)


def gqa_pack(hkv: int, d: int) -> int:
    """Key-value heads that lie side by side in a row of a head-major
    page: 1 where a head fills whole lane tiles, `128 // d` where it is a
    part of one that divides it and the heads make whole rows (8 heads of
    64: 2, a page `[bs, 128]` of a pair). A family lays its pool out as
    `[L, n_blocks, Hkv / pack, bs, pack * d]` and writes its rows
    `[N, Hkv, d]` as `[N, Hkv / pack, pack * d]`, which is the same bytes
    in the same order (`heads_side_by_side`)."""
    pack = 128 // d if d < 128 and 128 % d == 0 else 1
    return pack if hkv % pack == 0 else 1


def heads_side_by_side(rows, pack: int):
    """Key or value rows `[N, Hkv, d]` as a pool of `gqa_pack` = `pack`
    stores them, `[N, Hkv / pack, pack * d]`: a free reshape."""
    n, hkv, d = rows.shape
    return rows.reshape(n, hkv // pack, pack * d)


def _heads_apart(pool, pack: int):
    """One layer's pool `[n_blocks, Hkv / pack, bs, pack * d]` as
    `[n_blocks, Hkv, bs, d]`: a copy, for the gather path alone."""
    if pack == 1:
        return pool
    nb, rows, bs, lanes = pool.shape
    return pool.reshape(nb, rows, bs, pack, lanes // pack).transpose(
        0, 1, 3, 2, 4).reshape(nb, rows * pack, bs, lanes // pack)


def reference_gqa_paged_attention(q, k_pool, v_pool, tables, pos,
                                  window=None):
    """`reference_gqa_attention` over one layer's head-major pools
    `[n_blocks, Hkv, bs, D]` gathered through `tables`; pools whose rows
    hold several heads side by side (`gqa_pack`: the last axis a multiple
    of q's) are taken apart first."""
    pack = k_pool.shape[-1] // q.shape[-1]
    return reference_gqa_attention(
        q, gather_head_major(_heads_apart(k_pool, pack), tables),
        gather_head_major(_heads_apart(v_pool, pack), tables), pos, window)


_DECODE_TURN, _CHUNK_TURN = 1024, 512   # cached positions a turn, at most


def _gqa_plan(bs: int, g: int, d: int, dtype, w: int,
              vmem: int | None = None) -> _DecodePlan | None:
    """`_decode_plan` for head-major pools `[.., Hkv, bs, d]` (`d` a
    page's row as stored: `gqa_pack` heads of 64 side by side are one
    row of 128) and `w`
    queries of each of `g` heads a program: pages a turn from `_DECODE_TURN`
    (one query a head: the turn is all DMA) or `_CHUNK_TURN` positions,
    halved until two turns of K and V, the score tiles and the running
    state fit the default scope. None where a page or a program's `w * g`
    score rows are not whole tiles (`gqa_attention` pads a group and
    `_gqa_query_tile` cuts a tile so that the rows are)."""
    item = jnp.dtype(dtype).itemsize
    if d % 128 or bs % (8 * 4 // item) or (w * g) % 8:
        return None
    qrows = w * g
    state = qrows * (d * (4 * max(item, 2) + 4) + 128 * 4 * 2)

    def working_set(pages):
        cols = pages * bs
        return (2 * 2 * cols * d * item             # K and V, two turns
                + 5 * qrows * cols * 2 + state)     # mask, s, p; see above

    pages = max(1, (_DECODE_TURN if w == 1 else _CHUNK_TURN) // bs)
    while pages > 1 and working_set(pages) > backend.SCOPED_VMEM_DEFAULT:
        pages //= 2
    need = working_set(pages)
    if need <= backend.SCOPED_VMEM_DEFAULT:
        return _DecodePlan(1, pages, None)
    if need > (vmem or backend.vmem_capacity()) // 2:
        return None
    return _DecodePlan(1, pages, _up(need + need // 4, 1 << 20))


def gqa_attention(name: str, q, k_pool, v_pool, tables, pos, *, layer,
                  window: int | None = None, impl: str = "auto"):
    """Attention of ``q [B, W, Hq, D]`` (query `i` of stream `b` at
    position ``pos[b] + i``, written to the pool before this call) over
    stacked head-major pools ``[L, n_blocks, Hkv, bs, D]``, layer
    `layer` of them, through ``tables [B, max_blocks]`` whose column `j`
    names the page of positions ``j * bs ..`` wherever the engine keeps
    it (a window layer's pages may be a ring: a column the window has
    left names a page that holds later positions by now, and is never
    read). Query head `h` reads key-value head ``h // (Hq // Hkv)``;
    with `window` the positions ``pos[b] + i - window < j <= pos[b] + i``.
    -> ``[B, W, Hq, D]`` in q.dtype.

    The kernel is `_paged_kernel` under `name`, `head_major`: a program
    is a stream, a key-value head and a tile of queries; its pages are
    fetched once for the head's whole group and, with a window, from the
    first page the window reaches. The jax path gathers the layer
    through the whole table (`reference_gqa_paged_attention`).

    A head smaller than a lane tile (`gqa_pack`: 64, two a tile) is read
    where it lies too, from pools ``[L, n_blocks, Hkv / pack, bs, pack *
    D]``: a page is `pack` key-value heads side by side, one DMA of whole
    tiles, and a program is a stream, such a row of heads and the ``pack
    * Hq / Hkv`` query heads that read them. A query row goes to the
    kernel `pack * D` lanes wide with zeros outside its own head's lanes,
    so its scores are its own head's; ``p . V`` then fills every lane and
    the row's own are kept. The MXU multiplies `pack` times what is
    needed, on tiles it would fill no better, and the bytes read are the
    pool's own."""
    b, w, hq, d = q.shape
    pack = k_pool.shape[-1] // d if k_pool.ndim == 5 else 0
    if (not pack or tables.ndim != 2 or k_pool.shape[4] != pack * d
            or hq % (k_pool.shape[2] * pack)):
        raise ValueError(
            f"gqa_attention wants q [B, W, Hq, D], pools [L, n_blocks, "
            f"Hkv / pack, bs, pack * D] and tables [B, max_blocks]; got "
            f"{q.shape}, {k_pool.shape}, {tables.shape}")
    # `hkv`: rows of heads a page holds; `g`: the query heads that read one
    hkv, bs = k_pool.shape[2:4]
    g, lanes = hq // hkv, pack * d
    wt = _gqa_query_tile(w, g)
    # rows a key-value head a program are whole sublane tiles: where the
    # queries of one program are not (the decode step's one query of a
    # group of 5), the group is padded with heads of zeros, whose rows
    # score what the real ones fetched and are cut away
    gp = g if (wt * g) % 8 == 0 else _up(g, 8)
    if gp != g:
        wt = _gqa_query_tile(w, gp)
    plan = _gqa_plan(bs, gp, lanes, k_pool.dtype, wt)
    if impl == "auto":
        if plan is None:
            backend.note_fallback(name, f"block_size {bs}, {w} queries of "
                                  f"{hq} heads over {hkv} of {lanes}")
        impl = "pallas" if backend.on_tpu() and plan is not None else "jax"
    if impl == "jax":
        return reference_gqa_paged_attention(
            q, _layer_of(k_pool, layer), _layer_of(v_pool, layer), tables,
            pos, window)
    if impl != "pallas":
        raise ValueError(f"unknown {name} impl {impl!r}")
    if plan is None:
        if not backend.interpret():
            raise ValueError(f"no kernel plan for {name} at {q.shape} over "
                             f"{k_pool.shape}; use impl='jax'")
        plan = _DecodePlan(1, max(1, _TURN_TOKENS // bs), None)
    if w % wt:
        q = jnp.pad(q, ((0, 0), (0, -w % wt), (0, 0), (0, 0)))
    # a key-value head's g query heads side by side: [B, Hkv, W * g, D]
    rows = q.reshape(b, -1, hkv, g, d)
    if pack > 1:
        # query head j of a row of heads reads the head at lanes j // (g /
        # pack) * d ..: its own lanes hold it, the others zeros
        own = (jnp.arange(g)[:, None] // (g // pack)
               == jnp.arange(lanes)[None, :] // d)              # [g, lanes]
        rows = jnp.where(own, jnp.tile(rows, (1, 1, 1, 1, pack)), 0)
    if gp != g:
        rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, gp - g), (0, 0)))
    rows = rows.transpose(0, 2, 1, 3, 4).reshape(b, hkv, -1, lanes)
    out = _paged_call(name, rows, k_pool, v_pool, tables.astype(jnp.int32),
                      pos.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32).reshape(1), plan=plan,
                      block_size=bs, heads=gp, queries=w, sm_scale=d ** -0.5,
                      interpret=backend.interpret(), window=window,
                      head_major=True)
    out = out.reshape(b, hkv, -1, gp, lanes)[:, :, :, :g]
    if pack > 1:
        out = jnp.sum(jnp.where(own, out, 0).reshape(
            out.shape[:-1] + (pack, d)), axis=-2)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, -1, hq, d)[:, :w]


def gqa_decode_attention(q, k_pool, v_pool, tables, pos, *, layer,
                         window: int | None = None, impl: str = "auto"):
    """The decode step's form: ``q [B, Hq, D]`` at ``pos [B]``
    (`gqa_window_decode` with a window, `gqa_full_decode` without)."""
    name = GQA_FULL_DECODE if window is None else GQA_WINDOW_DECODE
    return gqa_attention(name, q[:, None], k_pool, v_pool, tables, pos,
                         layer=layer, window=window, impl=impl)[:, 0]


def gqa_chunk_attention(q, k_pool, v_pool, table, start, *, layer,
                        window: int | None = None, impl: str = "auto"):
    """A prompt chunk's form: ``q [C, Hq, D]`` of one sequence, token `t`
    at position ``start + t``, ``table [max_blocks]`` (`gqa_window_chunk`
    with a window, `gqa_full_chunk` without). A window layer's call
    fetches the pages that intersect ``[start - window + 1, start + C)``."""
    name = GQA_FULL_CHUNK if window is None else GQA_WINDOW_CHUNK
    return gqa_attention(name, q[None], k_pool, v_pool, table[None],
                         jnp.asarray(start).reshape(1), layer=layer,
                         window=window, impl=impl)[0]
