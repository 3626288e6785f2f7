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
sequence b is physical block ``tables[b, j]``). The structure mirrors
`ops/flash_attention.py`: grid (B*H, max_blocks) with the kv dimension
innermost/sequential walking *logical* blocks, per-row running
(m, l, acc) softmax statistics in VMEM scratch, finalize on the last kv
block. The block table and positions arrive as scalar prefetch
(`pltpu.PrefetchScalarGridSpec`), so the K/V BlockSpec index maps
dereference the table and the DMA engine fetches exactly the blocks the
sequence owns — the pool is never materialized per sequence. Two
decode-specific twists:

- **position masking**: each sequence attends to logical positions
  ``<= pos[b]`` (its current token's position — the caller writes the new
  K/V at ``pos`` *before* attending), so stale data in partially-filled
  tail blocks never contributes.
- **data-dependent block skip**: kv blocks strictly past ``pos`` are
  predicated away with ``pl.when(k_start <= pos)`` — a *runtime* branch,
  unlike flash's static causal predicate — so short sequences behind a
  long table don't pay for the empty tail.

The JAX fallback gathers ``pool[tables]`` and attends with
`reference_decode_attention`, the same masking and f32 accumulation.

**Int8 pools** (`ops/quant.py`): every paged op takes optional
``k_scale`` / ``v_scale`` arrays ``[n_blocks, bs, H]`` f32 — one scale
per (position, head) row of an int8 pool. Dequantization happens
*inside* the kernels (the scale tile rides the same table-dereferenced
DMA schedule as its payload block) and inside the fallbacks (gathered
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


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  sm_scale: float, block_size: int, n_heads: int,
                  quantized: bool):
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
    k_start = ji * block_size     # LOGICAL position of this kv block --
    # the BlockSpec index maps already dereferenced tbl_ref, so k_ref
    # holds the right physical block; masking stays in logical space.

    @pl.when(k_start <= pos)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [1, D]
        k = k_ref[0, 0].astype(jnp.float32)         # [bs, D]
        s = jax.lax.dot_general(
            q * sm_scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [1, bs]
        if quantized:
            s = s * _scale_row(ks_ref, head)
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_prev = m_scr[:1, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:1, :1] = l_scr[:1, :1] * corr + jnp.sum(
            p, axis=1, keepdims=True)
        m_scr[:1, :1] = m_new
        v = v_ref[0, 0]
        if quantized:
            p = p * _scale_row(vs_ref, head)
            v = v.astype(jnp.float32)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [1, D]
        acc_scr[:1] = acc_scr[:1] * corr + pv

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:1] / l_scr[:1, :1]).astype(o_ref.dtype)


def _paged_bhsd(q, k, v, tables, pos, *, sm_scale: float, n_heads: int,
                interpret: bool, ks=None, vs=None):
    """q [BH, 1, D]; k, v [n_blocks, H, bs, D] head-major pool; tables
    [B, max_blocks]; pos [B] i32 -> [BH, 1, D]. Grid walks (row, logical
    block); the physical block index comes out of the scalar-prefetched
    table inside the BlockSpec index maps — paging lives entirely in the
    DMA schedule, the kernel body is the stock online softmax. With
    ``ks``/``vs`` [n_blocks, H, bs] (head-major per-row scales) the
    pools are int8 and dequantized in VMEM."""
    bh, _, d = q.shape
    mb = tables.shape[1]
    bs = k.shape[2]
    grid = (bh, mb)
    h = n_heads
    quantized = ks is not None

    pool_spec = pl.BlockSpec((1, 1, bs, d),
                             lambda i, j, tbl, ps: (tbl[i // h, j],
                                                    i % h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, d), lambda i, j, tbl, ps: (i, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [tables, pos, q, k, v]
    if quantized:
        in_specs += [_scale_spec(h, bs)] * 2
        operands += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d), lambda i, j, tbl, ps: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),    # m (cell [0, 0] used)
            pltpu.VMEM((8, 128), jnp.float32),    # l
            pltpu.VMEM((8, d), jnp.float32),      # acc (row 0 used)
        ],
    )
    with jax.named_scope(PAGED_DECODE):
        return pl.pallas_call(
            functools.partial(_paged_kernel, sm_scale=sm_scale,
                              block_size=bs, n_heads=n_heads,
                              quantized=quantized),
            name=PAGED_DECODE,
            out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
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
    """`_paged_kernel` generalized to W query rows per (b, h): the online
    softmax statistics become per-row vectors, the mask becomes the
    staircase ``col <= pos + row``, and the runtime block skip widens to
    the LAST query row's horizon (``pos + w_real - 1``)."""
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
    [B] i32 -> [BH, Wp, D]. Same DMA schedule as `_paged_bhsd` — only
    the q/o tile grows from one row to Wp. ``ks``/``vs``
    [n_blocks, H, bs] mark int8 pools (dequantized in VMEM)."""
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
    if k_scale.shape != k_pool.shape[:3]:
        raise ValueError(
            f"{op} scale shape {k_scale.shape} != pool row shape "
            f"{k_pool.shape[:3]} ([n_blocks, bs, H])")
    return True


def paged_verify_attention(q, k_pool, v_pool, tables, pos, *,
                           k_scale=None, v_scale=None,
                           impl: str = "auto"):
    """Masked multi-query attention through the paged cache — the verify
    half of speculative decoding. ``q [B, W, H, D]`` holds W query tokens
    per sequence (current token + W-1 speculated continuations); token i
    of row b sits at logical position ``pos[b] + i`` and attends to cache
    positions ``<= pos[b] + i``. Pools/tables as in
    `paged_decode_attention`, including the int8 ``k_scale``/``v_scale``
    contract. Returns ``[B, W, H, D]`` in q.dtype.

    impl: "auto" (pallas on TPU-friendly shapes, else jax) | "pallas" |
    "jax"; the paths share masking/accumulation math."""
    if q.ndim != 4 or k_pool.ndim != 4 or tables.ndim != 2:
        raise ValueError(
            "paged_verify_attention wants q [B, W, H, D], pools "
            f"[n_blocks, bs, H, D] and tables [B, max_blocks]; got "
            f"{q.shape}, {k_pool.shape}, {tables.shape}")
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_verify_attention")
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
                           k_scale=None, v_scale=None,
                           impl: str = "auto"):
    """Decode-step attention through a paged KV cache: ``q [B, H, D]``
    against a block pool ``k_pool, v_pool [n_blocks, block_size, H, D]``
    indexed by ``tables [B, max_blocks]`` i32 (logical block j of row b
    is physical block ``tables[b, j]``; entries past the allocated
    length may be any valid block — they are masked). Attends to logical
    positions ``<= pos[b]`` and returns ``[B, H, D]`` in q.dtype.

    With ``k_scale``/``v_scale`` ``[n_blocks, bs, H]`` f32 the pools
    hold int8 payloads (`ops.quant.quantize_rows` convention, one scale
    per position-head row); both impls dequantize at read — in VMEM for
    pallas, post-gather for jax — so HBM traffic stays int8.

    impl: "auto" (pallas on TPU-friendly shapes, else jax) | "pallas" |
    "jax". The two paths share the same masking/accumulation math and
    agree to f32 tolerance."""
    if q.ndim != 3 or k_pool.ndim != 4 or tables.ndim != 2:
        raise ValueError(
            "paged_decode_attention wants q [B, H, D], pools "
            f"[n_blocks, bs, H, D] and tables [B, max_blocks]; got "
            f"{q.shape}, {k_pool.shape}, {tables.shape}")
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_decode_attention")
    b, h, d = q.shape
    bs = k_pool.shape[1]
    if impl == "auto":
        impl = _auto_impl("paged_decode_attention", bs % 8 == 0,
                          f"block_size {bs}")
    if impl == "jax":
        return reference_paged_decode_attention(
            q, k_pool, v_pool, tables, pos,
            k_scale=k_scale, v_scale=v_scale)
    if impl != "pallas":
        raise ValueError(
            f"unknown paged_decode_attention impl {impl!r} "
            "(expected 'auto' | 'pallas' | 'jax')")
    if bs % 8 != 0:
        raise ValueError(
            f"block_size {bs} is not a multiple of 8; use impl='jax'")
    interpret = backend.interpret()
    d_pad = _head_pad_target(d)
    # [n_blocks, bs, H, D] -> head-major [n_blocks, H, bs, D]: the
    # kernel's per-(row, block) tile is (bs, D) for one head.
    kt = _pad_heads(k_pool, d_pad).transpose(0, 2, 1, 3)
    vt = _pad_heads(v_pool, d_pad).transpose(0, 2, 1, 3)
    qt = _pad_heads(q, d_pad).reshape(b * h, 1, d_pad)
    ks = vs = None
    if quantized:
        ks = k_scale.transpose(0, 2, 1)
        vs = v_scale.transpose(0, 2, 1)
    out = _paged_bhsd(qt, kt, vt, tables.astype(jnp.int32),
                      pos.astype(jnp.int32), sm_scale=d ** -0.5,
                      n_heads=h, interpret=interpret, ks=ks, vs=vs)
    return out.reshape(b, h, d_pad)[..., :d]


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
                            k_scale=None, v_scale=None,
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
    pools, dequantized at read on both paths.

    impl: "auto" (pallas on TPU-friendly shapes, else jax) | "pallas" |
    "jax". Returns ``[C, H, D]`` in q.dtype."""
    if q.ndim != 3 or k_pool.ndim != 4 or table.ndim != 1:
        raise ValueError(
            "paged_prefill_attention wants q [C, H, D], pools "
            f"[n_blocks, bs, H, D] and table [max_blocks]; got "
            f"{q.shape}, {k_pool.shape}, {table.shape}")
    quantized = _check_scales(k_scale, v_scale, k_pool,
                              "paged_prefill_attention")
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
