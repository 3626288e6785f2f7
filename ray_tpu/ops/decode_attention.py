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
(`paged_decode`) runs ``grid = (B,)``: a program is one stream, and its
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
the pool's dtype and the chip's VMEM; `_paged_decode_kernel` says what a
dead page costs (nothing is fetched for it; the V rows it leaves in a
buffer are zeroed). Two things follow from decode:

- **position masking**: each sequence attends to logical positions
  ``<= pos[b]`` (its current token's position — the caller writes the new
  K/V at ``pos`` *before* attending), so stale data in partially-filled
  tail blocks never contributes.
- **idle rows are nearly free**: a row with ``pos = 0`` and a table of
  zeros (how the engine marks a slot that is not decoding) is one page
  and one turn, whatever the table's width.

Verify and the fused prefill (`paged_mq`) keep the older structure:
grid (B*H, max_blocks) over a head-major copy of the pool, one
``(block_size, D)`` tile a step through BlockSpec index maps that
dereference the table, blocks past the last query's horizon predicated
away with ``pl.when``. Given the stacked cache and a layer they slice
that layer out inside the copy they make anyway.

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
`models.gpt.prefill_paged`, and the Pallas path reuses the multi-query
verify kernel (the prefill staircase ``col <= start + row`` IS the
verify mask with ``pos = start``), so the [C, S] score matrix stays in
VMEM instead of round-tripping through HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.ops.flash_attention import _head_pad_target, _pad_heads

NEG_INF = -1e30

# Kernel names in the compiled program and the profiler's trace
# (`%paged_decode.N = ... custom-call`); PERF.md, section 3, lists them.
# Each call sits in a `named_scope` of its own name: see flash_attention.py.
# Verify and the fused prefill share `paged_mq`.
PAGED_DECODE, PAGED_MQ = "paged_decode", "paged_mq"


def _auto_impl(op: str, has_plan: bool, why: str) -> str:
    """Resolve ``impl="auto"``: the kernel on a TPU backend, the
    pure-JAX path elsewhere. A TPU shape with no plan is recorded."""
    if not has_plan:
        backend.note_fallback(op, why)
    return "pallas" if backend.on_tpu() and has_plan else "jax"


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


def _scale_spec(n_heads: int, bs: int):
    """BlockSpec of an int8 pool's scales, head-major [n_blocks, H, bs]:
    every head's row of one physical block, fetched through the same
    table dereference as the payload. Mosaic wants a block's last two
    dims (8, 128)-divisible or equal to the array's, which (H, bs) is
    and a single head's (1, bs) row is not."""
    return pl.BlockSpec((1, n_heads, bs),
                        lambda i, j, tbl, ps: (tbl[i // n_heads, j], 0, 0))


def _scale_row(scale_ref, head):
    """This head's [1, bs] scale row of a `_scale_spec` block. A row,
    not a column: a per-position K scale multiplies the score columns
    (``q . (k_j * s_j) == (q . k_j) * s_j``) and a V scale the
    probabilities (``p @ (v * s) == (p * s) @ v``), so dequantization
    needs no lane-to-sublane relayout and no [bs, D] multiply."""
    return scale_ref[0, pl.ds(head, 1), :].astype(jnp.float32)


# ---------------------------------------------------------------------------
# paged decode: a program a stream over its own pages
# ---------------------------------------------------------------------------

_TURN_TOKENS = 128              # cached positions a turn, where they fit


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


class _DecodePlan(NamedTuple):
    """What `paged_decode` runs at (`_decode_plan` chooses it). `pack`:
    heads side by side in a row of lanes, 1 where the pool goes to the
    kernel as the model stores it. `pages`: pages a turn of a stream's
    loop. `vmem_limit`: what the working set asks of
    `CompilerParams(vmem_limit_bytes=)`, None where the compiler's
    default scope holds it."""
    pack: int
    pages: int
    vmem_limit: int | None


def _decode_plan(bs: int, h: int, d: int, dtype, quantized: bool,
                 vmem: int | None = None) -> _DecodePlan | None:
    """The plan for pools `[n_blocks, bs, h, d]` of `dtype`, from the
    shapes, the element size and the chip's VMEM alone.

    A page reaches VMEM by one DMA, and Mosaic moves by DMA only slices
    whose lanes fill whole 128-lane tiles. A head size that is a multiple
    of 128 does as stored: `pack` 1, no operand touched. A smaller one
    that divides 128 XLA stores in a layout of its own, so the wrapper
    lays `pack = 128 // d` heads side by side (`[n_blocks, bs * h / pack,
    128]`, one copy of the layer read). Pages a turn: `_TURN_TOKENS`
    positions, halved until two turns of K and V, what the body makes of
    one and its score tiles fit the default scope; one page that does not fit asks for what it needs,
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

    def working_set(pages):
        cols = pages * rows
        bufs = 2 * 2 * pages * page                 # K and V, two turns
        if quantized:
            bufs += 2 * 2 * 8 * cols * 4            # their scale rows
        operands = 2 * cols * max(d, 128) * 4       # K, V as the MXU gets them
        scores = 4 * _up(h, 8) * cols * 4           # the mask, s, p, a spare
        return bufs + operands + scores

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
    """Whether `paged_decode` takes pools `[L, n_blocks, bs, h, d]` of
    `dtype` as the model stores them (`_decode_plan`'s `pack` 1: a head
    fills its 128 lanes), so that a layer loop can keep the stacked pool
    in one buffer and hand it over whole. At a smaller head size XLA
    stores the pool in a layout of its own and every reader and writer
    of rows works on a lay-out of it: a layer loop should then take one
    layer out at a time, or that lay-out is the whole pool's."""
    plan = _decode_plan(bs, h, d, dtype, quantized)
    return plan is None or plan.pack == 1


def _paged_decode_kernel(tbl_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         *rest, sm_scale: float, pack: int, pages: int,
                         block_size: int, quantized: bool):
    """One stream: its live pages in turns of `pages`, online softmax
    over a turn's every head at once. The pools are stacked, `[L,
    n_blocks, ...]`, and every page's DMA reads layer `layer_ref[0]` of
    them: the layer is one more number in the copy's index, never a
    slice of the pool.

    A turn's K is `[cols, lanes]`: row `c` holds position `c // hr` of
    the turn and the `pack` heads from `c % hr * pack` on, `hr = H /
    pack` rows a position (with `pack` 1, a page as the model wrote it:
    `[bs, H, D]` is `[bs * H, D]`). `q [H, lanes] . K^T` scores every
    head against every row, `[H, cols]`; a row of scores keeps the
    columns that hold its own head and are at or before `pos` (`ahead`),
    the rest are masked like a dead position, and `p . V` then sums a
    head's own rows only. The MXU does `hr` times the useful
    multiplications on 128 x 128 tiles it would otherwise leave idle; no
    head is ever moved out of the layout it was stored in.

    Dead pages: a turn issues and awaits DMAs for its live pages only
    (page `j` is live while `j <= pos // bs`; an idle row, `pos` 0, is
    one page, one turn), so no table entry past the length is read and
    no byte of a dead page is moved. The last turn's dead pages are
    whatever the buffer held: their scores are masked by `ahead`, and
    their V rows (and V scales) are zeroed before `p . V`, because zero
    times a NaN is a NaN. What is read beyond the live positions is the
    rest of each stream's last live page: `bs - 1 - pos % bs` positions,
    under one page of K and one of V a stream a layer."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    bs, mb = block_size, tbl_ref.shape[1]
    layer = layer_ref[0]
    _, h, lanes = q_ref.shape
    hr = h // pack                      # rows of lanes a cached position
    rows = bs * hr                      # score columns a page
    cols = pages * rows
    size = kbuf.shape[1] // pages       # a page along the buffer's rows
    pos = pos_ref[b]
    n_pages = jnp.minimum(pos // bs + 1, mb)
    n_turns = (n_pages + pages - 1) // pages

    def copies(blk, slot, i):
        """Page `i` of a turn: block `blk` into buffer `slot`."""
        page, scales = pl.ds(i * size, size), pl.ds(i * rows, rows)
        pairs = [(k_hbm.at[layer, blk], kbuf.at[slot, page]),
                 (v_hbm.at[layer, blk], vbuf.at[slot, page])]
        if quantized:       # the scales come laid out, one layer's
            pairs += [(ks_hbm.at[blk], ksbuf.at[slot, :, scales]),
                      (vs_hbm.at[blk], vsbuf.at[slot, :, scales])]
        return [pltpu.make_async_copy(src, dst, sem.at[slot])
                for src, dst in pairs]

    def issue(c, slot):
        for i in range(pages):
            @pl.when(c * pages + i < n_pages)
            def _start():
                for cp in copies(tbl_ref[b, c * pages + i], slot, i):
                    cp.start()

    def land(c, slot):
        for i in range(pages):
            live = c * pages + i < n_pages

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
    q = q_ref[0].astype(jnp.float32) * sm_scale             # [H, lanes]
    row = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
    # a column's position within the turn where it holds the row's head,
    # past every position where it does not
    ahead = jnp.where(col % hr == row // pack, col // hr, mb * bs)
    issue(0, 0)

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_turns)
        def _next():
            issue(c + 1, 1 - slot)

        land(c, slot)
        k = kbuf[slot].astype(jnp.float32).reshape(cols, lanes)
        v = vbuf[slot]
        if quantized:
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, cols]
        if quantized:   # row h takes the scales of head h: h % pack here
            s = s * jnp.tile(ksbuf[slot], (hr, 1))
        s = jnp.where(ahead <= pos - c * pages * bs, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=1,
                                                     keepdims=True)
        m_scr[:, :1] = m_new
        if quantized:
            p = p * jnp.tile(vsbuf[slot], (hr, 1))
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v.reshape(cols, lanes),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, lanes]
        return _

    jax.lax.fori_loop(0, n_turns, step, 0)
    o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _paged_decode(q, k_pool, v_pool, tables, pos, layer, *,
                  plan: _DecodePlan, block_size: int, sm_scale: float,
                  interpret: bool, ks=None, vs=None):
    """q [B, H, lanes]; k_pool, v_pool in HBM, stacked: `[L, n_blocks,
    bs, H, D]` as stored (`plan.pack` 1) or `[1, n_blocks, bs * H / pack,
    128]`; tables [B, max_blocks], pos [B] and layer [1] i32,
    scalar-prefetched -> [B, H, lanes]. `grid = (B,)`: a program is a
    stream, its loop's trip count the stream's own live pages, each page
    one DMA named by the layer and the table, a turn's pages landing
    while the turn before is computed. ``ks``/``vs`` `[n_blocks, pack,
    bs * H / pack]` f32, one layer's, mark int8 pools: row `j` holds the
    scales of heads `j, j + pack, ..` in the order of a page's rows,
    fetched page by page beside the payload and applied to the scores
    and the probabilities (`_scale_row` says why there)."""
    b, h, lanes = q.shape
    quantized = ks is not None
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row = pl.BlockSpec((1, h, lanes), lambda i, tbl, ps, ly: (i, 0, 0))
    operands = [tables, pos, layer, q, k_pool, v_pool]
    turn = (2, plan.pages * k_pool.shape[2]) + k_pool.shape[3:]
    scratch = [pltpu.VMEM(turn, k_pool.dtype)] * 2
    if quantized:
        operands += [ks, vs]
        scratch += [pltpu.VMEM((2, plan.pack, plan.pages * ks.shape[2]),
                               jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, 128), jnp.float32),    # m (column 0 used)
                pltpu.VMEM((h, 128), jnp.float32),    # l
                pltpu.VMEM((h, lanes), jnp.float32)]  # acc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b,),
        in_specs=[row] + [hbm] * (len(operands) - 4),
        out_specs=row, scratch_shapes=scratch)
    with jax.named_scope(PAGED_DECODE):
        return pl.pallas_call(
            functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                              pack=plan.pack, pages=plan.pages,
                              block_size=block_size, quantized=quantized),
            name=PAGED_DECODE,
            out_shape=jax.ShapeDtypeStruct((b, h, lanes), q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),   # streams share nothing
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


def _paged_mq_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                     sm_scale: float, block_size: int, n_heads: int,
                     w_real: int, quantized: bool):
    """W query rows of one (b, h) against one `(block_size, D)` tile of
    that head a grid step: stock online softmax with per-row statistics,
    the staircase mask ``col <= pos + row``, and blocks past the LAST
    query row's horizon (``pos + w_real - 1``) skipped at run time."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0) // n_heads]
    head = pl.program_id(0) % n_heads
    k_start = ji * block_size

    @pl.when(k_start <= pos + w_real - 1)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [Wp, D]
        k = k_ref[0, 0].astype(jnp.float32)         # [bs, D]
        s = jax.lax.dot_general(
            q * sm_scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [Wp, bs]
        if quantized:
            s = s * _scale_row(ks_ref, head)
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Padded q rows (>= w_real) reuse the last real row's mask so
        # they keep >= 1 live column (l stays nonzero); their output is
        # sliced away by the wrapper.
        row = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), w_real - 1)
        s = jnp.where(col <= pos + row, s, NEG_INF)
        m_prev = m_scr[:, :1]                       # [Wp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                      # [Wp, bs]
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(
            p, axis=1, keepdims=True)
        m_scr[:, :1] = m_new
        v = v_ref[0, 0]
        if quantized:
            p = p * _scale_row(vs_ref, head)
            v = v.astype(jnp.float32)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [Wp, D]
        acc_scr[:] = acc_scr[:] * corr + pv

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _paged_mq_bhsd(q, k, v, tables, pos, *, sm_scale: float,
                   n_heads: int, w_real: int, interpret: bool,
                   ks=None, vs=None):
    """q [BH, Wp, D] (Wp = W padded to a sublane multiple); k, v
    [n_blocks, H, bs, D] head-major pool; tables [B, max_blocks]; pos
    [B] i32 -> [BH, Wp, D]. Grid walks (row, logical block); the
    physical block index comes out of the scalar-prefetched table inside
    the BlockSpec index maps, so paging lives in the DMA schedule.
    ``ks``/``vs`` [n_blocks, H, bs] mark int8 pools (dequantized in
    VMEM)."""
    bh, wp, d = q.shape
    mb = tables.shape[1]
    bs = k.shape[2]
    h = n_heads
    quantized = ks is not None

    pool_spec = pl.BlockSpec((1, 1, bs, d),
                             lambda i, j, tbl, ps: (tbl[i // h, j],
                                                    i % h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, wp, d), lambda i, j, tbl, ps: (i, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [tables, pos, q, k, v]
    if quantized:
        in_specs += [_scale_spec(h, bs)] * 2
        operands += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, wp, d),
                               lambda i, j, tbl, ps: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((wp, 128), jnp.float32),   # m (col 0 used)
            pltpu.VMEM((wp, 128), jnp.float32),   # l
            pltpu.VMEM((wp, d), jnp.float32),     # acc
        ],
    )
    with jax.named_scope(PAGED_MQ):
        return pl.pallas_call(
            functools.partial(_paged_mq_kernel, sm_scale=sm_scale,
                              block_size=bs, n_heads=n_heads, w_real=w_real,
                              quantized=quantized),
            name=PAGED_MQ,
            out_shape=jax.ShapeDtypeStruct((bh, wp, d), q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(*operands)


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

    impl: "auto" (pallas on TPU-friendly shapes, else jax) | "pallas" |
    "jax"; the paths share masking/accumulation math. Both read one
    layer's copy of the pool (the kernel a head-major one, the jax path
    a gather), so a stacked pool's layer is sliced inside that copy."""
    _check_pools("paged_verify_attention", q, "B, W, H, D", k_pool,
                 tables, "B, max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_verify_attention")
    k_pool, v_pool, k_scale, v_scale = (
        _layer_of(a, layer) for a in (k_pool, v_pool, k_scale, v_scale))
    b, w, h, d = q.shape
    bs = k_pool.shape[1]
    if impl == "auto":
        impl = _auto_impl("paged_verify_attention", bs % 8 == 0,
                          f"block_size {bs}")
    if impl == "jax":
        return reference_paged_verify_attention(
            q, k_pool, v_pool, tables, pos,
            k_scale=k_scale, v_scale=v_scale)
    if impl != "pallas":
        raise ValueError(
            f"unknown paged_verify_attention impl {impl!r} "
            "(expected 'auto' | 'pallas' | 'jax')")
    if bs % 8 != 0:
        raise ValueError(
            f"block_size {bs} is not a multiple of 8; use impl='jax'")
    interpret = backend.interpret()
    d_pad = _head_pad_target(d)
    wp = max(8, ((w + 7) // 8) * 8)
    kt = _pad_heads(k_pool, d_pad).transpose(0, 2, 1, 3)
    vt = _pad_heads(v_pool, d_pad).transpose(0, 2, 1, 3)
    qt = _pad_heads(q, d_pad).transpose(0, 2, 1, 3).reshape(
        b * h, w, d_pad)
    qt = jnp.pad(qt, ((0, 0), (0, wp - w), (0, 0)))
    ks = vs = None
    if quantized:
        ks = k_scale.transpose(0, 2, 1)      # head-major [nb, H, bs]
        vs = v_scale.transpose(0, 2, 1)
    out = _paged_mq_bhsd(qt, kt, vt, tables.astype(jnp.int32),
                         pos.astype(jnp.int32), sm_scale=d ** -0.5,
                         n_heads=h, w_real=w, interpret=interpret,
                         ks=ks, vs=vs)
    return out.reshape(b, h, wp, d_pad)[:, :, :w, :d].transpose(
        0, 2, 1, 3)


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
    takes a pool whose head size is a multiple of 128 where and as it is
    stored, stacked or not: the layer is a number in each page's DMA. At
    a smaller head size XLA stores the pool padded to 128 lanes, and the
    one layer read is sliced out and laid out for the kernel here, one
    copy of a layer a call; an int8 pool's scales are laid out the same
    way, a layer's at a time."""
    _check_pools("paged_decode_attention", q, "B, H, D", k_pool, tables,
                 "B, max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_decode_attention")
    b, h, d = q.shape
    nb, bs = k_pool.shape[-4:-2]
    plan = _decode_plan(bs, h, d, k_pool.dtype, quantized)
    if impl == "auto":
        impl = _auto_impl("paged_decode_attention", plan is not None,
                          f"block_size {bs}, {h} heads of {d}, "
                          f"{k_pool.dtype} pool")
    if impl == "jax":
        k, v, ks, vs = (_layer_of(a, layer)
                        for a in (k_pool, v_pool, k_scale, v_scale))
        return reference_paged_decode_attention(q, k, v, tables, pos,
                                                k_scale=ks, v_scale=vs)
    if impl != "pallas":
        raise ValueError(
            f"unknown paged_decode_attention impl {impl!r} "
            "(expected 'auto' | 'pallas' | 'jax')")
    interpret = backend.interpret()
    if plan is None:
        if not interpret:
            raise ValueError(
                f"no paged_decode plan for block_size {bs}, {h} heads of "
                f"{d}, {k_pool.dtype} pool; use impl='jax'")
        # the interpreter has no tiles to align: any shape, as stored
        plan = _DecodePlan(1, max(1, _TURN_TOKENS // bs), None)
    pack = plan.pack
    ks = vs = None
    if quantized:           # laid out for the kernel, one layer's scales
        lay = lambda sc: _layer_of(sc, layer).reshape(
            nb, bs, h // pack, pack).transpose(0, 3, 1, 2).reshape(
            nb, pack, bs * h // pack)
        ks, vs = lay(k_scale), lay(v_scale)
    if pack > 1:
        # `pack` heads side by side in a row of 128 lanes; row h of the
        # query and of the output keeps the lanes of its own head
        own = (jnp.arange(h)[:, None] % pack
               == jnp.arange(pack * d)[None, :] // d)       # [H, lanes]
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
    out = _paged_decode(q, k_pool, v_pool, tables.astype(jnp.int32),
                        pos.astype(jnp.int32),
                        jnp.asarray(layer, jnp.int32).reshape(1), plan=plan,
                        block_size=bs, sm_scale=d ** -0.5,
                        interpret=interpret, ks=ks, vs=vs)
    if pack > 1:
        out = jnp.sum(jnp.where(own, out, 0).reshape(b, h, pack, d), axis=2)
    return out


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

    The pallas path reuses the multi-query verify kernel: the prefill
    staircase (token t sees positions ``<= start + t``) is the verify
    mask with ``pos = start`` and ``W = C``, so the [C, S] score matrix
    lives blockwise in VMEM instead of round-tripping through HBM, and
    the runtime block skip prunes pool blocks past ``start + C - 1``.
    The jax path is the legacy dense gather+einsum
    (`reference_paged_prefill_attention`) — bit-identical to the
    pre-fused inline math, which keeps ``impl="jax"`` the bitwise
    default on CPU. ``k_scale``/``v_scale`` [n_blocks, bs, H] mark int8
    pools, dequantized at read on both paths. Pools and scales may be
    stacked ``[L, n_blocks, ...]`` with ``layer`` the one to read, as in
    `paged_decode_attention`; both paths copy what they read, so the
    layer is sliced inside that copy.

    impl: "auto" (pallas on TPU-friendly shapes, else jax) | "pallas" |
    "jax". Returns ``[C, H, D]`` in q.dtype."""
    _check_pools("paged_prefill_attention", q, "C, H, D", k_pool, table,
                 "max_blocks", layer)
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_prefill_attention")
    k_pool, v_pool, k_scale, v_scale = (
        _layer_of(a, layer) for a in (k_pool, v_pool, k_scale, v_scale))
    c, h, d = q.shape
    bs = k_pool.shape[1]
    if impl == "auto":
        impl = _auto_impl("paged_prefill_attention", bs % 8 == 0,
                          f"block_size {bs}")
    if impl == "jax":
        return reference_paged_prefill_attention(
            q, k_pool, v_pool, table, start,
            k_scale=k_scale, v_scale=v_scale)
    if impl != "pallas":
        raise ValueError(
            f"unknown paged_prefill_attention impl {impl!r} "
            "(expected 'auto' | 'pallas' | 'jax')")
    if bs % 8 != 0:
        raise ValueError(
            f"block_size {bs} is not a multiple of 8; use impl='jax'")
    interpret = backend.interpret()
    d_pad = _head_pad_target(d)
    wp = max(8, ((c + 7) // 8) * 8)
    kt = _pad_heads(k_pool, d_pad).transpose(0, 2, 1, 3)
    vt = _pad_heads(v_pool, d_pad).transpose(0, 2, 1, 3)
    # One sequence == one batch row of the mq kernel: B=1, W=C,
    # pos=start. Padded q rows (>= C) compute a discarded garbage row —
    # the same thing the dense path's padded chunk tail does.
    qt = q.transpose(1, 0, 2)                      # [H, C, D]
    qt = _pad_heads(qt, d_pad)
    qt = jnp.pad(qt, ((0, 0), (0, wp - c), (0, 0)))
    ks = vs = None
    if quantized:
        ks = k_scale.transpose(0, 2, 1)
        vs = v_scale.transpose(0, 2, 1)
    tables = table.astype(jnp.int32)[None]
    pos = jnp.asarray(start, jnp.int32).reshape(1)
    out = _paged_mq_bhsd(qt, kt, vt, tables, pos, sm_scale=d ** -0.5,
                         n_heads=h, w_real=c, interpret=interpret,
                         ks=ks, vs=vs)
    return out[:, :c, :d].transpose(1, 0, 2)
