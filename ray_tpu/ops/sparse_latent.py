"""Sparse attention over a paged latent cache: the decode kernels, a
prefill chunk's kernel and the row format they read, with pure-JAX paths
of identical math.

A latent-attention model caches one row a token a layer: the compressed
key-value vector and the shared rotary key (`[c_kv | k_rope]`). A learned
indexer scores every cached position of a stream, the `top_k` best are
selected, and attention reads those rows only. Both steps run over the
engine's block pool through its block tables (block 0 the trash block,
`ops/decode_attention.py`'s conventions).

**The row format.** A selected row is fetched from HBM by a DMA of its
own. A DMA out of an array tiled (8, 128) moves eight rows at the least,
so the latent pool is an array of uint32 words with a unit axis before the
words, `[n_blocks, block_size, 1, words]`, `words` a multiple of 128: the
compiler tiles it (1, 128), a row is `words * 4` contiguous bytes, and
one DMA moves exactly one. XLA's own gather and scatter would first copy
the whole pool into the (8, 128) tiling, so on a TPU rows are also written
by DMA, HBM to HBM (`write_rows`), and a prefill chunk's kernel fetches
whole pages. A row of 16-bit values is split into a first and a second
half, and word j holds first[j] in its low and second[j] in its high 16
bits (`pack_rows`; the kernel unpacks with a shift and a mask, and a
bfloat16 widened to float32 is those 16 bits shifted left); a row of
float32 values is its words as they are. Either way a row is `parts`
arrays of `words` lanes, and a decode kernel's query is laid out the same
way (`split_query`); the chunk's kernel joins the parts back into the
row's own order and takes its query in that order.

**`index_scores`** (decode): for every stream the indexer's score of each
cached position, `I[b, s] = sum_j w[b, j] ReLU(q[b, j] . k[s])` in
float32 (a float32 query against 16-bit keys goes as a high and a low
part), over the paged index keys `[n_blocks, block_size, Di]` of the stream's whole
context; positions past `pos[b]` come out as -inf. The grid walks
(stream, group of blocks); a step fetches its blocks through the table
with one DMA a block and is skipped past the stream's position.

**`sparse_latent_decode`**: absorbed latent attention of one query a
stream over its selected rows, `rows[b, :count[b]]` (physical row numbers,
the live ones first): scores against the whole row (the no-position part
of the query already multiplied through the key up-projection), online
softmax, and the weighted sum of the rows themselves, whose leading
`kv_rank` values the caller takes through the value up-projection. The
rows come in chunks through a ring of `CHUNK_SLOTS` buffers, one DMA a
row, the next chunks in flight while one is scored; chunks past
`count[b]` are never fetched, so the bytes scale with min(context, top_k)
and never with the context.

**`latent_decode`**: the same attention over every cached row of a
stream's pages, the first `count[b]` live: a chunk is `ROW_CHUNK` rows of
whole pages, a page one DMA where it lies, through the same ring (on a
v5e a chunk's fetch runs at 520 GB/s with one chunk in flight and bounds
the kernel; with two, at 680: `PERF.md`, PR 66).

**A chunk's step** (`_attend_chunk`, both decode kernels). The chunk
buffer keeps the pool's tiling, `[chunk, 1, words]` tiled (1, 128), so
that a row or a page lands by a plain DMA; it is never reshaped. The
same bytes seen as `[chunk * words / 128, 128]` are tiled (8, 128), and a
load with a sublane stride of `words / 128` takes one lane tile of eight
rows as one vreg (a reshape of the loaded value costs about eighty vector
operations a vreg instead: gathers, rotates, selects and repacks). Each
part is shifted or masked into float32 and packed to the compute type
once. In both products the cached rows are the MXU's stationary operand,
a tile of 128 x 128 latched once and the heads streamed past it (`q
[H, lanes] . part^T` for the scores, `p [H, chunk] . part` for the sum);
the softmax runs along the lanes of `[H, chunk]`. With the row's `values`
and `kv_rank` given, only the lane tiles that hold some of them are
loaded, unpacked and multiplied: of a row of 512 + 64 values stored as
two parts of 384 lanes, five tiles of six for the scores and four for
the sum, whose other output tiles stay zero.

**`latent_chunk_attend`** (prefill): the same absorbed attention of a
chunk's queries, every head, over the cached context up to the chunk's
last position, masked to each query's selection. Dense: every live page
is fetched once a group of heads and every key scored, the score tile
made, masked, exponentiated and multiplied into the accumulator in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend

NEG_INF = -1e30

# Kernel names in the compiled program and the profiler's trace; PERF.md,
# section 3, lists them. Each call sits in a `named_scope` of its own
# name: see flash_attention.py.
SPARSE_LATENT_DECODE, INDEX_SCORES = "sparse_latent_decode", "index_scores"
LATENT_DECODE, LATENT_CHUNK_ATTEND = "latent_decode", "latent_chunk_attend"

LANES = 128
ROW_CHUNK = 256         # cached rows a chunk of the two decode kernels
CHUNK_SLOTS = 3         # their chunk buffers: one scored, the rest in flight
INDEX_STEP_TOKENS = 512  # cached positions a grid step of `index_scores`
CONTEXT_BLOCK = 1024    # cached positions a step of a prefill chunk's loops
CHUNK_VMEM_LIMIT = 100 << 20    # `latent_chunk_attend`'s scoped VMEM
CHUNK_VMEM_BUDGET = 80 << 20    # what its plan of heads a grid step fills


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


def resolve_impl(impl: str) -> str:
    if impl not in ("auto", "pallas", "jax"):
        raise ValueError(f"unknown impl {impl!r} (auto | pallas | jax)")
    if impl == "auto":
        return "pallas" if backend.on_tpu() else "jax"
    return impl


# ---------------------------------------------------------------------------
# the row format
# ---------------------------------------------------------------------------

def row_words(values: int, dtype) -> int:
    """uint32 words of a pool row that holds `values` numbers of `dtype`."""
    if jnp.dtype(dtype).itemsize == 2:
        return _up(-(-values // 2), LANES)
    return _up(values, LANES)


def row_parts(dtype) -> int:
    return 2 if jnp.dtype(dtype).itemsize == 2 else 1


def pack_rows(x, words: int):
    """x [..., R] (bfloat16 or float32) -> uint32 [..., words]."""
    parts = row_parts(x.dtype)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                + [(0, parts * words - x.shape[-1])])
    if parts == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return bits[..., :words] | (bits[..., words:] << 16)


def unpack_rows(words_arr, values: int, dtype):
    """uint32 [..., words] -> [..., values] of `dtype`."""
    return jnp.concatenate(_parts_of(words_arr, dtype), -1)[
        ..., :values].astype(dtype)


def _parts_of(w, dtype):
    """The row's part arrays, float32 [..., words] each (in a kernel or
    out of one)."""
    if row_parts(dtype) == 1:
        return [jax.lax.bitcast_convert_type(w, jnp.float32)]
    return [jax.lax.bitcast_convert_type(w << 16, jnp.float32),
            jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                         jnp.float32)]


def split_query(q, words: int):
    """q [..., R] in the row's dtype -> [parts, ..., words], laid out as
    `pack_rows` lays out a row."""
    parts = row_parts(q.dtype)
    q = jnp.pad(q, [(0, 0)] * (q.ndim - 1)
                + [(0, parts * words - q.shape[-1])])
    return jnp.stack([q[..., i * words:(i + 1) * words]
                      for i in range(parts)])


def join_parts(o, values: int):
    """[parts, ..., words] -> [..., values]: the weighted sum of rows back
    in the row's own order."""
    return jnp.concatenate(list(o), -1)[..., :values]


# ---------------------------------------------------------------------------
# rows in and out of the pool
# ---------------------------------------------------------------------------

ROW_WRITE = "latent_row_write"


def _row_copies(n: int, copy):
    """Start `n` row DMAs on one semaphore, then wait for them all."""
    def start(i, _):
        copy(i).start()
        return _

    def wait(i, _):
        copy(i).wait()
        return _

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def _write_kernel(at_ref, rows_ref, pool_ref, o_ref, sem, *, n: int,
                  n_rows: int):
    del pool_ref                # the same buffer as o_ref

    def copy(i):
        # a dropped row is written to row 0 of the trash block instead
        at = at_ref[i]
        return pltpu.make_async_copy(
            rows_ref.at[i], o_ref.at[jnp.where(at < n_rows, at, 0)], sem)

    _row_copies(n, copy)


def write_rows(pool, rows, at, *, impl: str = "auto"):
    """rows [N, words] uint32 into pool [n_rows, 1, words] at row numbers
    at [N] i32 (n_rows and beyond: dropped), in place when the pool is
    donated. Row 0 belongs to the trash block: on a TPU a dropped row
    lands there."""
    n_rows, _, words = pool.shape
    if resolve_impl(impl) != "pallas":
        return pool.at[at, 0].set(rows, mode="drop")
    n = rows.shape[0]
    with jax.named_scope(ROW_WRITE):
        return pl.pallas_call(
            functools.partial(_write_kernel, n=n, n_rows=n_rows),
            name=ROW_WRITE,
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
            input_output_aliases={2: 0},
            interpret=backend.interpret(),
        )(at.astype(jnp.int32), rows[:, None, :], pool)


# ---------------------------------------------------------------------------
# index scores (decode)
# ---------------------------------------------------------------------------

def query_parts(q, key_dtype):
    """A float32 query as the parts that multiply keys of `key_dtype`
    without losing its low bits: itself against float32 keys; against
    16-bit keys a high and a low 16-bit part (`q = hi + lo` to 2^-17),
    whose dots add up. [..., J, Di] -> [..., parts * J, Di]."""
    q = q.astype(jnp.float32)
    if jnp.dtype(key_dtype).itemsize == 4:
        return q
    hi = q.astype(key_dtype)
    lo = (q - hi.astype(jnp.float32)).astype(key_dtype)
    return jnp.concatenate([hi, lo], axis=-2)


def index_dots(q, keys, eq: str):
    """The indexer's q . k per head in float32 (`eq` an einsum over
    q [..., J, Di] and keys [..., Di], heads on the output's axis -2):
    the selection is a discrete choice, so the query keeps its float32
    bits whatever type the cached keys have."""
    j = q.shape[-2]
    dots = jnp.einsum(eq, query_parts(q, keys.dtype), keys,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    return dots if dots.shape[-2] == j else \
        dots[..., :j, :] + dots[..., j:, :]


def reference_index_scores(q, w, pool, tables, pos):
    """q [B, J, Di] f32, w [B, J] f32, pool [n_blocks, bs, Di], tables
    [B, max_blocks], pos [B] -> f32 [B, max_blocks * bs], -inf past pos."""
    from ray_tpu.ops.decode_attention import gather_kv_pages
    keys = gather_kv_pages(pool, tables)                    # [B, S, Di]
    s = index_dots(q, keys, "bjd,bsd->bjs")
    scores = jnp.einsum("bj,bjs->bs", w.astype(jnp.float32),
                        jax.nn.relu(s))
    live = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :] \
        <= pos.astype(jnp.int32)[:, None]
    return jnp.where(live, scores, -jnp.inf)


def _index_kernel(tbl_ref, pos_ref, q_ref, w_ref, pool_ref, o_ref, buf, sem,
                  *, group: int, block_size: int, heads: int):
    b, g = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    base = g * group * block_size

    @pl.when(base > pos)
    def _skip():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    @pl.when(base <= pos)
    def _body():
        def copy(i):
            return pltpu.make_async_copy(
                pool_ref.at[tbl_ref[b, g * group + i]],
                buf.at[pl.ds(i * block_size, block_size)], sem)

        for i in range(group):
            copy(i).start()
        for i in range(group):
            copy(i).wait()
        s = jax.lax.dot_general(
            q_ref[0], buf[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [parts * J, tokens]
        if s.shape[0] != heads:         # the query's high and low parts
            s = s[:heads] + s[heads:]
        scores = jnp.sum(jax.nn.relu(s) * w_ref[0], axis=0, keepdims=True)
        col = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        o_ref[0] = jnp.where(col <= pos, scores, -jnp.inf)


def _index_scores_pallas(q, w, pool, tables, pos):
    heads = q.shape[1]
    q = query_parts(q, pool.dtype)
    b, j, di = q.shape
    nb, bs, _ = pool.shape
    mb = tables.shape[1]
    group = max(1, min(mb, INDEX_STEP_TOKENS // bs))
    while mb % group:
        group -= 1
    step = group * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb // group),
        in_specs=[
            pl.BlockSpec((1, j, di), lambda i, g, tbl, ps: (i, 0, 0)),
            pl.BlockSpec((1, heads, 1), lambda i, g, tbl, ps: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, step), lambda i, g, tbl, ps: (i, 0, g)),
        scratch_shapes=[pltpu.VMEM((step, di), pool.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    with jax.named_scope(INDEX_SCORES):
        out = pl.pallas_call(
            functools.partial(_index_kernel, group=group, block_size=bs,
                              heads=heads),
            name=INDEX_SCORES,
            out_shape=jax.ShapeDtypeStruct((b, 1, mb * bs), jnp.float32),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=backend.interpret(),
        )(tables.astype(jnp.int32), pos.astype(jnp.int32),
          q, w.astype(jnp.float32)[..., None], pool)
    return out[:, 0]


def index_scores(q, w, pool, tables, pos, *, impl: str = "auto"):
    """The indexer's scores of every cached position of every stream:
    q [B, J, Di] f32 (`query_parts` keeps its bits against 16-bit keys),
    w [B, J], pool [n_blocks, bs, Di] (the paged index keys), tables
    [B, max_blocks] i32, pos [B] i32 -> f32 [B, max_blocks * bs], -inf at
    positions past pos[b]."""
    if resolve_impl(impl) == "pallas":
        return _index_scores_pallas(q, w, pool, tables, pos)
    return reference_index_scores(q, w, pool, tables, pos)


# ---------------------------------------------------------------------------
# sparse latent decode
# ---------------------------------------------------------------------------

def reference_sparse_latent_decode(q, pool, rows, count, dtype, values=None,
                                   kv_rank=None):
    """q [parts, B, H, words] (the scale folded in), pool
    [n_rows, 1, words] uint32, rows [B, K] i32, count [B] i32 ->
    f32 [parts, B, H, words]. Scores over a row's first `values` values
    and the sum of its first `kv_rank` (None: the whole row)."""
    k = rows.shape[1]
    picked = jnp.stack(_parts_of(pool[rows, 0], dtype))  # [parts,B,K,words]

    def first(n):   # a row's value i * words + j is part i's lane j
        if n is None:
            return picked
        at = jnp.arange(picked.shape[0] * picked.shape[-1])
        return jnp.where(at.reshape(-1, 1, 1, picked.shape[-1]) < n, picked,
                         0.0)

    s = jnp.einsum("pbhw,pbkw->bhk", q.astype(jnp.float32), first(values),
                   preferred_element_type=jnp.float32)
    live = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,pbkw->pbhw", p, first(kv_rank),
                      preferred_element_type=jnp.float32)


def _live_tiles(n, words: int, parts: int):
    """Lane tiles of each part that hold some of a row's first `n` values
    (None: the whole row)."""
    full = words // LANES
    if n is None:
        return (full,) * parts
    return tuple(min(full, max(0, -(-(n - i * words) // LANES)))
                 for i in range(parts))


def _attend_chunk(q_ref, rows_ref, c, n, m_scr, l_scr, acc_scr, dtype, tiles):
    """One chunk of cached rows (`rows_ref` uint32 [chunk, 1, words] as the
    DMAs left them, the c-th chunk of a stream's `n` live rows) into the
    online softmax that both decode kernels keep: scores of every head
    against the rows, the running maximum and sum, and the weighted sum of
    the rows. `tiles`: the lane tiles of each part that are scored and
    that are summed (`_live_tiles`); no other is loaded, unpacked,
    multiplied or accumulated.

    The buffer is tiled (1, 128) like the pool, a row's lane tile a
    sublane of its own. Seen as `[chunk * words / 128, 128]` it is the
    same bytes tiled (8, 128), and a load with a sublane stride of
    `words / 128` takes one lane tile of eight rows as the vreg the
    products read: no row is moved after it has landed."""
    chunk, _, words = rows_ref.shape
    stride = words // LANES
    compute = jnp.bfloat16 if row_parts(dtype) == 2 else jnp.float32
    scored, summed = tiles
    lanes = rows_ref.reshape(chunk * stride, LANES)
    loaded = jnp.concatenate([lanes[pl.ds(t, chunk, stride=stride), :]
                              for t in range(max(scored + summed))], axis=1)
    rows = [_parts_of(loaded[:, :max(k, v) * LANES], dtype)[i].astype(compute)
            for i, (k, v) in enumerate(zip(scored, summed))]
    s = sum(jax.lax.dot_general(
        q_ref[i, 0, :, :k * LANES].astype(compute), part[:, :k * LANES],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
        for i, (part, k) in enumerate(zip(rows, scored)) if k)  # [H, chunk]
    col = c * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n, s, NEG_INF)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_scr[:, :1] = m_new
    p = p.astype(compute)
    for i, (part, v) in enumerate(zip(rows, summed)):
        if v:
            acc_scr[i, :, :v * LANES] = acc_scr[i, :, :v * LANES] * corr \
                + jax.lax.dot_general(
                    p, part[:, :v * LANES],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [H, lanes]


def _online_decode(n, chunk, issue, wait, q_ref, o_ref, buf, m_scr, l_scr,
                   acc_scr, dtype, tiles):
    """The loop both decode kernels run for one stream: its `n` live rows
    in chunks of `chunk` through a ring of `CHUNK_SLOTS` buffers
    (`issue(c, slot)` starts chunk c's DMAs into `buf[slot]`,
    `wait(slot)` waits for them; the chunks after the one being scored
    are in flight), through the online softmax, then the weighted sum of
    rows out."""
    n_chunks = (n + chunk - 1) // chunk
    ahead = CHUNK_SLOTS - 1
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    for c in range(ahead):
        @pl.when(c < n_chunks)
        def _first():
            issue(c, c)

    def step(c, _):
        slot = c % CHUNK_SLOTS

        @pl.when(c + ahead < n_chunks)
        def _next():
            issue(c + ahead, (c + ahead) % CHUNK_SLOTS)

        wait(slot)
        _attend_chunk(q_ref, buf.at[slot], c, n, m_scr, l_scr, acc_scr, dtype,
                      tiles)
        return _

    jax.lax.fori_loop(0, n_chunks, step, 0)
    for i in range(acc_scr.shape[0]):
        o_ref[i, 0] = acc_scr[i] / jnp.maximum(l_scr[:, :1], 1e-30)


def _sparse_kernel(rows_ref, count_ref, q_ref, pool_ref, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, chunk: int, dtype, tiles):
    b = pl.program_id(0)

    def issue(c, slot):
        def eight(g, _):
            for i in range(8):
                at = g * 8 + i
                pltpu.make_async_copy(
                    pool_ref.at[rows_ref[b, c * chunk + at]],
                    buf.at[slot, at], sem.at[slot]).start()
            return _
        jax.lax.fori_loop(0, chunk // 8, eight, 0)

    def wait(slot):
        # the semaphore counts bytes: one wait for the chunk's rows
        pltpu.make_async_copy(pool_ref.at[pl.ds(0, chunk)], buf.at[slot],
                              sem.at[slot]).wait()

    _online_decode(count_ref[b], chunk, issue, wait, q_ref, o_ref, buf,
                   m_scr, l_scr, acc_scr, dtype, tiles)


def _decode_specs(parts: int, h: int, words: int, chunk: int):
    """(the query's and the output's BlockSpec, the scratch) both decode
    kernels take, after two prefetched scalars."""
    block = pl.BlockSpec((parts, 1, h, words), lambda i, *_: (0, i, 0, 0))
    return block, [
        pltpu.VMEM((CHUNK_SLOTS, chunk, 1, words), jnp.uint32),
        pltpu.SemaphoreType.DMA((CHUNK_SLOTS,)),
        pltpu.VMEM((h, LANES), jnp.float32),          # m (col 0 used)
        pltpu.VMEM((h, LANES), jnp.float32),          # l
        pltpu.VMEM((parts, h, words), jnp.float32),   # acc
    ]


def _sparse_latent_decode_pallas(q, pool, rows, count, dtype):
    parts, b, h, words = q.shape
    k = rows.shape[1]
    chunk = min(ROW_CHUNK, _up(k, 8))
    if k % chunk:           # whole chunks: the tail points at row 0
        rows = jnp.pad(rows, ((0, 0), (0, _up(k, chunk) - k)))
    block, scratch = _decode_specs(parts, h, words, chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block, scratch_shapes=scratch)
    with jax.named_scope(SPARSE_LATENT_DECODE):
        return pl.pallas_call(
            functools.partial(_sparse_kernel, chunk=chunk, dtype=dtype,
                              tiles=(_live_tiles(None, words, parts),) * 2),
            name=SPARSE_LATENT_DECODE,
            out_shape=jax.ShapeDtypeStruct((parts, b, h, words),
                                           jnp.float32),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=backend.interpret(),
        )(rows.astype(jnp.int32), count.astype(jnp.int32), q, pool)


def sparse_latent_decode(q, pool, rows, count, *, dtype,
                         impl: str = "auto"):
    """One query a stream over its selected rows of the latent pool.

    q [parts, B, H, words]: `split_query` of the absorbed query, the
    softmax scale folded in. pool [n_rows, 1, words] uint32: the latent
    rows (`pack_rows` of values of `dtype`), layers and blocks flattened.
    rows [B, K] i32: physical row numbers, the `count[b]` live ones first.
    -> f32 [parts, B, H, words]: softmax-weighted sum of the rows
    (`join_parts` puts it back in the row's order)."""
    if resolve_impl(impl) == "pallas":
        return _sparse_latent_decode_pallas(q, pool, rows, count, dtype)
    return reference_sparse_latent_decode(q, pool, rows, count, dtype)


# ---------------------------------------------------------------------------
# dense latent decode
# ---------------------------------------------------------------------------

def reference_latent_decode(q, pool, layer: int, tables, count, dtype,
                            values=None, kv_rank=None):
    """q [parts, B, H, words], pool [L, n_blocks, bs, 1, words] uint32,
    tables [B, max_blocks] i32, count [B] i32 -> f32 [parts, B, H, words]:
    `reference_sparse_latent_decode` over every row of the stream's
    pages, the first `count[b]` live."""
    bs = pool.shape[2]
    at = jnp.arange(tables.shape[1] * bs, dtype=jnp.int32)
    rows = jnp.take(tables, at // bs, axis=1) * bs + at % bs
    return reference_sparse_latent_decode(
        q, pool[layer].reshape(-1, 1, pool.shape[-1]), rows, count, dtype,
        values, kv_rank)


def _latent_kernel(tbl_ref, count_ref, q_ref, pool_ref, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, layer: int, pages: int,
                   block_size: int, dtype, tiles):
    b = pl.program_id(0)

    def copy(c, slot, i):
        # a page where it lies: its rows are contiguous, one DMA
        return pltpu.make_async_copy(
            pool_ref.at[layer, tbl_ref[b, c * pages + i]],
            buf.at[slot, pl.ds(i * block_size, block_size)], sem.at[slot])

    def issue(c, slot):
        for i in range(pages):
            copy(c, slot, i).start()

    def wait(slot):
        for i in range(pages):
            copy(0, slot, i).wait()

    _online_decode(count_ref[b], pages * block_size, issue, wait, q_ref,
                   o_ref, buf, m_scr, l_scr, acc_scr, dtype, tiles)


def _latent_decode_pallas(q, pool, layer: int, tables, count, dtype, values,
                          kv_rank):
    parts, b, h, words = q.shape
    bs = pool.shape[2]
    pages = max(1, ROW_CHUNK // bs)
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)))
    block, scratch = _decode_specs(parts, h, words, pages * bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block, scratch_shapes=scratch)
    with jax.named_scope(LATENT_DECODE):
        return pl.pallas_call(
            functools.partial(_latent_kernel, layer=layer, pages=pages,
                              block_size=bs, dtype=dtype,
                              tiles=(_live_tiles(values, words, parts),
                                     _live_tiles(kv_rank, words, parts))),
            name=LATENT_DECODE,
            out_shape=jax.ShapeDtypeStruct((parts, b, h, words),
                                           jnp.float32),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=backend.interpret(),
        )(tables.astype(jnp.int32), count.astype(jnp.int32), q, pool)


def latent_decode(q, pool, layer: int, tables, count, *, dtype,
                  values: int | None = None, kv_rank: int | None = None,
                  impl: str = "auto"):
    """One query a stream over every cached row of its context: dense
    absorbed latent attention.

    q [parts, B, H, words]: `split_query` of the absorbed query, the
    softmax scale folded in. pool [L, n_blocks, bs, 1, words] uint32: the
    latent pool where it lies; `layer` (static) the layer read. tables
    [B, max_blocks] i32: each stream's pages in order (0: the trash
    block). count [B] i32: the stream's live rows, its first `count[b]`
    positions. A stream's live pages are streamed whole by DMA, two chunks
    of pages ahead of the one being scored, so the bytes scale with its
    context; pages past it are never fetched. `values` / `kv_rank`
    (static; None: the whole row): the scores read a row's first `values`
    values and the sum its first `kv_rank`, in whole lane tiles of each
    part; what lies in the pool's other tiles is never loaded, and the
    output's other tiles are zero.
    -> f32 [parts, B, H, words] (`join_parts` puts it back in the row's
    order)."""
    if resolve_impl(impl) == "pallas":
        return _latent_decode_pallas(q, pool, layer, tables, count, dtype,
                                     values, kv_rank)
    return reference_latent_decode(q, pool, layer, tables, count, dtype,
                                   values, kv_rank)


# ---------------------------------------------------------------------------
# a prefill chunk over its cached context
# ---------------------------------------------------------------------------

def context_block(s: int, bs: int) -> int:
    """Cached positions a step of a prefill chunk's loops over a table of
    `s` positions in pages of `bs`: whole pages, a divisor of `s`,
    `CONTEXT_BLOCK` at the most."""
    b = max(bs, min(s, CONTEXT_BLOCK) // bs * bs)
    while s % b:
        b -= bs
    return b


def reference_latent_chunk_attend(q, pool, layer: int, table, selected, last,
                                  mixed: int, dtype):
    """`latent_chunk_attend` in plain `jax.numpy`: the context's rows
    gathered through the table a block of positions at a time, only as far
    as position `last`, through an online softmax."""
    h, c, values = q.shape
    _, nb, bs, _, words = pool.shape
    sb = context_block(table.shape[0] * bs, bs)
    flat = pool.reshape(-1, words)

    def block(j, carry):
        m, l, acc = carry
        at = j * sb + jnp.arange(sb, dtype=jnp.int32)
        rows = unpack_rows(
            flat[(table[at // bs] + layer * nb) * bs + at % bs], values,
            dtype)
        sc = jnp.einsum("hqr,sr->hqs", q, rows,
                        preferred_element_type=jnp.float32)
        live = jax.lax.dynamic_slice_in_dim(selected, j * sb, sb, axis=1)
        sc = jnp.where(live[None], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, -1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        acc = acc * corr[..., None] + jnp.einsum(
            "hqs,sm->hqm", p.astype(dtype), rows[:, :mixed],
            preferred_element_type=jnp.float32)
        return m_new, l * corr + jnp.sum(p, -1), acc

    m, l, acc = jax.lax.fori_loop(
        0, last // sb + 1, block,
        (jnp.full((h, c), NEG_INF, jnp.float32),
         jnp.zeros((h, c), jnp.float32),
         jnp.zeros((h, c, mixed), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _chunk_kernel(tbl_ref, last_ref, q_ref, mask_ref, pool_ref, o_ref, buf,
                  mbuf, sem, k_scr, bias_scr, m_scr, l_scr, acc_scr, *,
                  layer: int, pages: int, block_size: int, dtype):
    heads, c, _ = q_ref.shape
    sb, width = k_scr.shape
    words = buf.shape[-1]
    n_blocks = last_ref[0] // sb + 1

    def page(j, slot, i):
        # a page where it lies: its rows are contiguous, one DMA
        return pltpu.make_async_copy(
            pool_ref.at[layer, tbl_ref[j * pages + i]],
            buf.at[slot, pl.ds(i * block_size, block_size)], sem.at[0, slot])

    def mask(j, slot):
        return pltpu.make_async_copy(
            mask_ref.at[:, pl.ds(pl.multiple_of(j * sb, sb), sb)],
            mbuf.at[slot], sem.at[1, slot])

    def issue(j, slot):
        mask(j, slot).start()

        def start(i, _):
            page(j, slot, i).start()
            return _
        jax.lax.fori_loop(0, pages, start, 0)

    def wait(slot):
        mask(0, slot).wait()

        def one(i, _):
            page(0, slot, i).wait()
            return _
        jax.lax.fori_loop(0, pages, one, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    issue(0, 0)

    def block(j, _):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            issue(j + 1, 1 - slot)

        wait(slot)
        # the block's keys in the rows' own order, and its mask as what is
        # added to a score: made once, read by every head of the group
        parts = _parts_of(buf[slot].reshape(sb, words), dtype)
        for i, part in enumerate(parts):
            lo, hi = i * words, min((i + 1) * words, width)
            if lo < hi:
                k_scr[:, lo:hi] = part[:, :hi - lo].astype(k_scr.dtype)
        bias_scr[...] = jnp.where(mbuf[slot].astype(jnp.int32) != 0, 0.0,
                                  NEG_INF)

        def head(h, _):
            s = jax.lax.dot_general(
                q_ref[h], k_scr[...],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + bias_scr[...]
            m_prev = m_scr[h]                               # [C, LANES]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * corr[:, :1] + jax.lax.dot_general(
                p.astype(k_scr.dtype), k_scr[:, :acc_scr.shape[-1]],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return _

        jax.lax.fori_loop(0, heads, head, 0)
        return _

    jax.lax.fori_loop(0, n_blocks, block, 0)

    def norm(h, _):
        o_ref[h] = (acc_scr[h] * (1.0 / jnp.maximum(l_scr[h], 1e-30))[:, :1]
                    ).astype(o_ref.dtype)
        return _
    jax.lax.fori_loop(0, heads, norm, 0)


def _chunk_heads(h: int, c: int, sb: int, width: int, out: int, words: int,
                 itemsize: int) -> int:
    """Heads a grid step of `latent_chunk_attend`: the most that divide
    `h` and keep the step's VMEM under `CHUNK_VMEM_BUDGET`. A head costs
    its queries and its output (both double-buffered), its accumulator and
    its running maximum and sum; the step, the two page and mask buffers,
    the keys, the mask's addends and a score tile's temporaries."""
    head = (2 * c * (width + out) * itemsize + c * out * 4
            + 2 * c * LANES * 4)
    step = (2 * sb * words * 4 + 2 * c * sb + sb * width * itemsize
            + 4 * c * sb * 4)
    fit = max(1, (CHUNK_VMEM_BUDGET - step) // head)
    return max(g for g in range(1, h + 1) if h % g == 0 and g <= fit)


def _latent_chunk_attend_pallas(q, pool, layer: int, table, selected, last,
                                mixed: int, dtype):
    h, c, values = q.shape
    bs, words = pool.shape[2], pool.shape[-1]
    sb = context_block(table.shape[0] * bs, bs)
    compute = jnp.bfloat16 if row_parts(dtype) == 2 else jnp.float32
    width, out = _up(values, LANES), _up(mixed, LANES)
    padded = jnp.pad(q.astype(compute),
                     ((0, 0), (0, 0), (0, width - values)))
    heads = _chunk_heads(h, c, sb, width, out, words,
                         jnp.dtype(compute).itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(h // heads,),
        in_specs=[pl.BlockSpec((heads, c, width), lambda g, *_: (g, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((heads, c, out), lambda g, *_: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, sb, 1, words), jnp.uint32),
            pltpu.VMEM((2, c, sb), jnp.int8),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((sb, width), compute),           # the block's keys
            pltpu.VMEM((c, sb), jnp.float32),           # 0 or NEG_INF
            pltpu.VMEM((heads, c, LANES), jnp.float32),     # m
            pltpu.VMEM((heads, c, LANES), jnp.float32),     # l
            pltpu.VMEM((heads, c, out), jnp.float32),       # acc
        ])
    with jax.named_scope(LATENT_CHUNK_ATTEND):
        return pl.pallas_call(
            functools.partial(_chunk_kernel, layer=layer, pages=sb // bs,
                              block_size=bs, dtype=dtype),
            name=LATENT_CHUNK_ATTEND,
            out_shape=jax.ShapeDtypeStruct((h, c, out), q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=CHUNK_VMEM_LIMIT),
            interpret=backend.interpret(),
        )(table.astype(jnp.int32), jnp.reshape(last, (1,)).astype(jnp.int32),
          padded, selected.astype(jnp.int8), pool)[..., :mixed]


def latent_chunk_attend(q, pool, layer: int, table, selected, last, *,
                        mixed: int, dtype, impl: str = "auto"):
    """A chunk's queries, every head, over the cached context of their
    sequence: dense absorbed latent attention under a mask.

    q [H, C, values]: the absorbed query in a row's own order
    (`[q_nope W_uk | q_rope]`), the softmax scale folded in. pool
    [L, n_blocks, bs, 1, words] uint32: the latent pool where it lies;
    `layer` (static) the layer read. table [max_blocks] i32: the
    sequence's pages in order (0: the trash block). selected bool
    [C, max_blocks * bs]: the positions each query attends to. last i32:
    the chunk's last position; the context is walked in blocks of
    `context_block` positions only as far as `last`'s, and pages past it
    are never fetched.
    -> [H, C, mixed] in q's type, accumulated in float32: the
    softmax-weighted sum of the leading `mixed` values of the selected
    rows (a query that selects nothing: finite)."""
    if resolve_impl(impl) == "pallas":
        return _latent_chunk_attend_pallas(q, pool, layer, table, selected,
                                           last, mixed, dtype)
    return reference_latent_chunk_attend(q, pool, layer, table, selected,
                                         last, mixed, dtype)
