"""Flash attention as Pallas TPU kernels — forward AND backward.

The reference has no custom kernels of its own (its GPU fast paths live in
torch/NCCL); on TPU the memory-bound op worth hand-scheduling is attention:
O(T^2) scores never touch HBM — K/V blocks stream through VMEM while
per-row running softmax statistics live in VMEM scratch across the
sequential kv grid dimension.

Layout: [B, T, H, D] public API (matching `ray_tpu.parallel.ring_attention`
so models switch impls freely). Internally [B*H, T, D], grid
(BH, T/block_q, T/block_kv) with the kv dimension innermost/sequential and
batch/query dimensions parallel. At the default blocks a head's whole K
and V (up to T = 2048) are one block: fetched once a head and resident
while its q blocks go by.

Inside a grid step the kernel body walks its blocks in `sub_q` x `sub_kv`
tiles of the score matrix (`_Plan`), q sub-blocks outermost, with
`lax.fori_loop`s over `pl.ds` slices of the resident refs. Under causal
masking the tile is the unit of skipping and of masking: for each q
sub-block the loop's trip count, computed from `program_id`, ends at the
last kv tile the rows' causal bound reaches, so a tile above the diagonal
is never visited; tiles wholly below the diagonal go through with no
mask, and only the tiles the diagonal crosses pay the iota, compare and
select of `_causal_mask`. `executed_share` counts what that leaves of the
T x T square: 0.625 at T = 2048 with 512-wide tiles, against the half a
causal pass needs. A non-causal call walks every tile unmasked. A block
of one tile (T <= 512 at the defaults) has no loop: the tile is indexed
statically and its causal bound is a predicate around it.

Backward pass: two more Pallas kernels (FlashAttention-2 style).  The
forward saves the per-row logsumexp; backward precomputes
``delta = rowsum(dO * O)`` in XLA (bandwidth-trivial), then

- the **dQ kernel** walks kv tiles for each q sub-block, accumulating
  ``dq += ds @ k`` in VMEM scratch, and
- the **dKV kernel** walks q tiles from the diagonal down for each kv
  sub-block, accumulating ``dv += p^T @ dO`` and ``dk += ds^T @ q``,

so the O(T^2) probability matrix is rebuilt tile by tile in VMEM and
never written to HBM in either direction.

The running max and sum of the forward live in [rows, 128] scratch with
every lane alike, as lse and delta arrive in the backward: a [rows, 1]
column costs a vreg per 8 rows all the same, and each use of it a lane
broadcast. The forward's cost per tile is mostly these per-row
statistics, not the tile's elements.

The forward-only (inference) path compiles a kernel variant with no lse
output, so serving never pays the lse write; the lse variant runs only
under autodiff.  The kernels read and write lse/delta as [BH, T, 128] f32 —
broadcast across the 128-lane tile — because Mosaic requires output block
last dims of 128 (a [BH, T] row vector with (1, block_q) blocks fails its
tiling check); the stock JAX TPU flash kernel stores its lse the same way.
Between the forward and the backward the lse is kept as [BH, T].
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.parallel.ring_attention import reference_attention

NEG_INF = -1e30

# Kernel names: what each `pallas_call` is called in the compiled program
# (`%flash_fwd.N = ... custom-call`) and so in a profiler trace's `XLA Ops`
# line. Readers learn them from PERF.md, section 3. The instruction is
# named after the innermost scope around the call, and a transform wraps
# the first scope it meets (`jvp(xent_fwd)` -> `%jvp_xent_fwd_.N`), so
# every call sits in a `named_scope` of its own name that takes the wrap.
FLASH_FWD, FLASH_DQ, FLASH_DKV = "flash_fwd", "flash_dq", "flash_dkv"

# `checkpoint_name`s of the two things only the forward kernel can make:
# its output and its per-row logsumexp. A `jax.checkpoint` policy that
# saves both (`models/gpt.py`: "dots", "attn_out") goes straight to the
# dQ and dK/dV kernels in its backward; one that saves neither runs the
# forward kernel a second time to get them back.
SAVED_NAMES = ("attn_out", "attn_lse")


class _Plan(NamedTuple):
    """What a call's three kernels run at. `block_q` / `block_kv`: the q
    and kv rows one grid step holds in VMEM (forward and dQ step over q
    blocks with a kv block resident, dK/dV the other way round).
    `sub_q` x `sub_kv`: the tile of the score matrix a kernel body
    builds at a time, walking its blocks; the unit of causal skipping
    and masking."""
    block_q: int
    block_kv: int
    sub_q: int
    sub_kv: int


def _causal_mask(s, q_start, k_start):
    # row - col is a constant of the tile shape; the tile's place on the
    # diagonal is one scalar.
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where(ahead >= k_start - q_start, s, NEG_INF)


def _lanes(x, n: int):
    """[rows, 128] with every lane alike -> [rows, n] of the same."""
    if n <= 128:
        return x[:, :n]
    reps = -(-n // 128)
    return pltpu.repeat(x, reps, axis=1)[:, :n]


def _sub(i, size: int):
    """Rows [i * size, (i + 1) * size) of a resident block."""
    if isinstance(i, int):
        return pl.ds(i * size, size)
    return pl.ds(pl.multiple_of(i * size, size), size)


def _crossed(start, width, sub, block, n):
    """Rows (or columns) [start, start + width) of one axis against the
    resident block `block` of the other, which holds `n` sub-blocks of
    `sub`: the diagonal crosses sub-blocks [lo, hi), counted from the
    block's start. For q rows the kv sub-blocks under `lo` lie wholly
    below the diagonal and those from `hi` on wholly above it; for kv
    columns the q sub-blocks under `lo` lie above, those from `hi` on
    below."""
    first = block * n
    lo = start // sub - first
    hi = (start + width + sub - 1) // sub - first
    return jnp.clip(lo, 0, n), jnp.clip(hi, 0, n)


def executed_share(plan: _Plan, t: int, causal: bool) -> float:
    """Score elements the kernels compute, as a share of the T x T
    square. A causal pass needs half; tiles the diagonal crosses are
    computed whole. Counted with the walks' own spans: for each q
    sub-block, the kv tiles up to where `_walk_kv` stops."""
    if not causal:
        return 1.0
    tiles = sum(int(_crossed(q0, plan.sub_q, plan.sub_kv, 0,
                             t // plan.sub_kv)[1])
                for q0 in range(0, t, plan.sub_q))
    return tiles * plan.sub_q * plan.sub_kv / (t * t)


def _walk(lo, hi, n, tile, *args):
    """`tile(i, *args)` for i in [lo, hi), of a block's `n` sub-blocks:
    a loop whose trip count the causal bound sets, so a tile above the
    diagonal costs nothing. A block of one sub-block is indexed
    statically: its rows need not be a multiple of the 8 sublanes
    (T <= 128 runs at T), and Mosaic refuses a dynamic start it cannot
    prove aligned."""
    if n == 1:
        pl.when(lo < hi)(lambda: tile(0, *args))
    else:
        jax.lax.fori_loop(lo, hi, lambda i, _: tile(i, *args), None)


def _walk_kv(tile, q_start, kv_block, *, causal: bool, plan: _Plan):
    """Walk the resident kv block's sub-blocks for one sub-block of q
    rows: unmasked below the diagonal, masked where it crosses."""
    n = plan.block_kv // plan.sub_kv
    if not causal:
        return _walk(0, n, n, tile, False)
    lo, hi = _crossed(q_start, plan.sub_q, plan.sub_kv, kv_block, n)
    _walk(0, lo, n, tile, False)
    _walk(lo, hi, n, tile, True)


def _walk_q(tile, k_start, q_block, *, causal: bool, plan: _Plan):
    """The same for one sub-block of kv rows over the resident q block:
    from the diagonal down."""
    n = plan.block_q // plan.sub_q
    if not causal:
        return _walk(0, n, n, tile, False)
    lo, hi = _crossed(k_start, plan.sub_kv, plan.sub_q, q_block, n)
    _walk(lo, hi, n, tile, True)
    _walk(hi, n, n, tile, False)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                  causal: bool, plan: _Plan, with_lse: bool):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def q_rows(r):
        rows = _sub(r, plan.sub_q)
        q_start = qi * plan.block_q + r * plan.sub_q
        q = q_ref[0, rows, :].astype(jnp.float32) * sm_scale   # [sq, D]

        def tile(j, masked):
            cols = _sub(j, plan.sub_kv)
            k = k_ref[0, cols, :].astype(jnp.float32)          # [skv, D]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [sq, skv]
            if masked:
                s = _causal_mask(
                    s, q_start, ki * plan.block_kv + j * plan.sub_kv)
            m_prev = m_scr[rows, :]                            # [sq, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))         # [sq, skv]
            l_scr[rows, :] = l_scr[rows, :] * corr + jnp.sum(
                p, axis=1, keepdims=True)
            m_scr[rows, :] = m_new
            v = v_ref[0, cols, :]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [sq, D]
            acc_scr[rows, :] = (acc_scr[rows, :]
                                * _lanes(corr, pv.shape[1]) + pv)

        _walk_kv(tile, q_start, ki, causal=causal, plan=plan)

    n_q = plan.block_q // plan.sub_q
    _walk(0, n_q, n_q, q_rows)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / _lanes(l_scr[:], acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _flash_bhtd(q, k, v, *, sm_scale: float, causal: bool, plan: _Plan,
                interpret: bool, with_lse: bool):
    """q, k: [BH, T, D], v: [BH, T, Dv] with T divisible by both block
    sizes.

    Returns (out [BH, T, Dv], lse) where lse is [BH, T, 128] f32 (per-row
    logsumexp broadcast across the lane tile) when with_lse, else None."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    block_q, block_kv = plan.block_q, plan.block_kv
    grid = (bh, t // block_q, t // block_kv)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, plan=plan,
        with_lse=with_lse)
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, t, 128), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)))
    with jax.named_scope(FLASH_FWD):
        res = pl.pallas_call(
            kernel,
            name=FLASH_FWD,
            out_shape=tuple(out_shape),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_kv, dv), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),   # m, lanes alike
                pltpu.VMEM((block_q, 128), jnp.float32),   # l
                pltpu.VMEM((block_q, dv), jnp.float32),    # acc
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p_ds(q, k, v, do, lse, delta, q_start, k_start, *,
                    sm_scale: float, masked: bool):
    """Rebuild one tile of the probabilities and of dS from saved
    lse/delta — the shared core of both backward kernels, so a
    masking/scaling change can never diverge between dQ and dK/dV.
    q, do: [sq, D] f32; k, v: [skv, D] f32; lse, delta: [sq, 128], every
    lane alike, as they arrive.
    `masked` is static: the walk knows which tiles the diagonal
    crosses."""
    s = jax.lax.dot_general(
        q * sm_scale, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [sq, skv]
    if masked:
        s = _causal_mask(s, q_start, k_start)
    p = jnp.exp(s - _lanes(lse, s.shape[1]))    # [sq, skv]
    dp = jax.lax.dot_general(
        do, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [sq, skv]
    ds = p * (dp - _lanes(delta, s.shape[1]))   # [sq, skv]
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, causal: bool, plan: _Plan):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def q_rows(r):
        rows = _sub(r, plan.sub_q)
        q_start = qi * plan.block_q + r * plan.sub_q
        q = q_ref[0, rows, :].astype(jnp.float32)
        do = do_ref[0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, rows, :]
        delta = delta_ref[0, rows, :]

        def tile(j, masked):
            cols = _sub(j, plan.sub_kv)
            k = k_ref[0, cols, :].astype(jnp.float32)
            v = v_ref[0, cols, :].astype(jnp.float32)
            _, ds = _recompute_p_ds(
                q, k, v, do, lse, delta, q_start,
                ki * plan.block_kv + j * plan.sub_kv,
                sm_scale=sm_scale, masked=masked)
            dq_scr[rows, :] += sm_scale * jax.lax.dot_general(
                ds, k,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [sq, D]

        _walk_kv(tile, q_start, ki, causal=causal, plan=plan)

    n_q = plan.block_q // plan.sub_q
    _walk(0, n_q, n_q, q_rows)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                causal: bool, plan: _Plan):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def kv_rows(c):
        cols = _sub(c, plan.sub_kv)
        k_start = ki * plan.block_kv + c * plan.sub_kv
        k = k_ref[0, cols, :].astype(jnp.float32)
        v = v_ref[0, cols, :].astype(jnp.float32)

        def tile(i, masked):
            rows = _sub(i, plan.sub_q)
            q = q_ref[0, rows, :].astype(jnp.float32)
            do = do_ref[0, rows, :].astype(jnp.float32)
            p, ds = _recompute_p_ds(
                q, k, v, do, lse_ref[0, rows, :], delta_ref[0, rows, :],
                qi * plan.block_q + i * plan.sub_q, k_start,
                sm_scale=sm_scale, masked=masked)
            dv_scr[cols, :] += jax.lax.dot_general(
                p, do,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [skv, D]
            dk_scr[cols, :] += sm_scale * jax.lax.dot_general(
                ds, q,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [skv, D]

        _walk_q(tile, k_start, qi, causal=causal, plan=plan)

    n_kv = plan.block_kv // plan.sub_kv
    _walk(0, n_kv, n_kv, kv_rows)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, do, lse, delta, *, sm_scale: float,
                    causal: bool, plan: _Plan, interpret: bool):
    """q, k [BH, T, D]; v, do [BH, T, Dv] (lse/delta [BH, T, 128] f32)
    -> (dq, dk, dv)."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    block_q, block_kv = plan.block_q, plan.block_kv
    common = dict(sm_scale=sm_scale, causal=causal, plan=plan)

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0))
    vspec = pl.BlockSpec((1, block_kv, dv), lambda b, i, j: (b, j, 0))
    dospec = pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0))
    rowq = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    with jax.named_scope(FLASH_DQ):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common),
            name=FLASH_DQ,
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            grid=(bh, t // block_q, t // block_kv),
            in_specs=[qspec, kspec, vspec, dospec, rowq, rowq],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    # dKV grid: kv blocks parallel, q blocks innermost/sequential.
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    vspec2 = pl.BlockSpec((1, block_kv, dv), lambda b, j, i: (b, j, 0))
    dospec2 = pl.BlockSpec((1, block_q, dv), lambda b, j, i: (b, i, 0))
    rowq2 = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    with jax.named_scope(FLASH_DKV):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **common),
            name=FLASH_DKV,
            out_shape=(jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, t, dv), v.dtype)),
            grid=(bh, t // block_kv, t // block_q),
            in_specs=[qspec2, kspec2, vspec2, dospec2, rowq2, rowq2],
            out_specs=(kspec2, vspec2),
            scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                            pltpu.VMEM((block_kv, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pick_block(t: int, pref: int) -> int | None:
    """Largest lane-aligned block <= pref that divides t, so raising the
    preferred block size never silently drops a shape the kernel handled
    at a smaller block (e.g. T=1536 runs at 768, not the XLA fallback)."""
    if t <= 128:
        return t
    b = min(pref, t) // 128 * 128
    while b >= 128:
        if t % b == 0:
            return b
        b -= 128
    return None


# The widest tile that still lets a causal pass at T = 2048 skip part of
# the square; the sweep in `flash_attention`'s docstring chose it.
_SUB = 512


def _plan_blocks(t: int, block_q: int, block_kv: int) -> _Plan | None:
    """Blocks: the largest divisors of T under the caller's bounds. Tiles:
    the largest divisors of the blocks up to `_SUB`, so T <= 512 is one
    tile and a ragged T (1536, 384) walks tiles that divide its block.
    None: T has no lane-aligned divisor and the call takes the XLA path."""
    bq, bkv = _pick_block(t, block_q), _pick_block(t, block_kv)
    if bq is None or bkv is None:
        return None
    return _Plan(bq, bkv, _pick_block(bq, _SUB), _pick_block(bkv, _SUB))


def _pad_heads(x, d_pad):
    d = x.shape[-1]
    if d_pad == d:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


def _head_pad_target(d: int) -> int:
    """Mosaic accepts a last block dim equal to the full array dim, so any
    multiple of the 8-sublane tile works unpadded (64 for GPT heads); only
    ragged head dims pad up to the next 8-sublane multiple."""
    return d if d % 8 == 0 else -(-d // 8) * 8


def _bhtd(x):
    """[B, T, H, D] -> [B * H, T, D padded to whole sublanes]."""
    b, t, h, d = x.shape
    d_pad = _head_pad_target(d)
    return _pad_heads(x, d_pad).transpose(0, 2, 1, 3).reshape(b * h, t, d_pad)


def _unbhtd(x, b: int, h: int, d: int):
    """`_bhtd` undone: [B * H, T, D padded] -> [B, T, H, d]."""
    return x.reshape(b, h, *x.shape[1:]).transpose(0, 2, 1, 3)[..., :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 2048,
                    block_kv: int = 2048):
    """[B, T, H, D] attention; falls back to the XLA path on
    TPU-unfriendly shapes. Fully differentiable: both directions are
    Pallas kernels (backward = dQ + dKV kernels over saved lse). v may
    have another width than q and k (latent attention: 192 against 128);
    the scale is that of q's width, the output has v's, and neither is
    padded to the other.

    `block_q` / `block_kv` are upper bounds: blocks shrink to the largest
    divisor of T, so ragged sequence lengths stay on the kernel path. The
    kernels cannot be partitioned by the compiler: on a mesh of more than
    one device the caller wraps this in a shard_map
    (`models.gpt._attention` does).

    The plan was swept on one TPU v5e at the benchmark's two shapes,
    causal, bf16, each kernel alone, device ms a call from a profiler
    trace (PERF.md, PR 27). (BH, T, D) = (128, 2048, 64) / (64, 2048, 128):

        blocks, tile          forward        dQ             dK/dV
        1024, whole (PR 26)   1.850 / 0.924  2.100 / 1.085  2.785 / 1.415
        2048, 1024 x 1024     1.582 / 0.791  refused: scoped VMEM
        2048,  512 x 1024     1.589 / 0.795  1.816 / 0.907  2.395 / 1.191
        2048,  512 x  512     1.327 / 0.665  1.621 / 0.809  2.076 / 1.032
        1024,  512 x  512     1.589 / 0.793  2.076 / 1.074  2.483 / 1.260
        2048,  256 x  512     1.479 / 0.739  1.792 / 0.895  2.541 / 1.263
        2048,  256 x  256     2.457 / 1.228  2.340 / 1.169  2.913 / 1.447

    Both head sizes pick the same plan. A 256-wide tile executes less of
    the square (0.5625 against 0.625) and loses more to what every tile
    costs whatever its size: per-row statistics, accumulator read-modify-
    writes, MXU weight loads. Whole-T blocks beat 1024 because a grid
    step above the diagonal still fetches its K and V.

    Keys wider than values (latent attention), swept the same way
    (`benchmarks/tools/flash_sweep.py`, PERF.md, PR 38) at (BH, T, d_qk,
    d_v) = (64, 8192, 192, 128), block_q x block_kv, tile:

        blocks, tile             forward   dQ        dK/dV
        2048 x 2048, 512 x 512   refused: scoped VMEM (a 192-wide block
                                 is stored 256 lanes wide)
        2048 x 1024, 512 x 512   12.672    19.464    24.292
        1024 x 2048, 512 x 512   13.640    19.846    22.576
        1024 x 1024, 512 x 512   14.147    20.813    25.272
        1024 x  512, 512 x 512   14.689    22.349    30.206
         512 x 1024, 512 x 512   16.609    23.214    25.905
         512 x  512, 512 x 512   17.504    25.039    31.481
        1024 x 1024, 256 x 256   23.166    23.859    32.286

    The caller passes the bounds (`models/latent_sparse_moe.py`: 1024 x
    2048, 56.1 ms the three together against 56.4 and 60.2); the default
    bounds and the (d, d) plan above are as they were."""
    out, _ = _flash_forward_impl(q, k, v, causal, block_q, block_kv,
                                 with_lse=False)
    return out


def _flash_forward_impl(q, k, v, causal, block_q, block_kv, with_lse):
    """Returns (out, lse|None). lse is None on the XLA fallback path or
    when with_lse=False (the inference variant, which skips the lse
    write entirely)."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    plan = _plan_blocks(t, block_q, block_kv)
    if plan is None:
        backend.note_fallback("flash_attention", f"T={t}")
        return reference_attention(q, k, v, causal=causal), None
    interpret = backend.interpret()
    out, lse = _flash_bhtd(_bhtd(q), _bhtd(k), _bhtd(v), sm_scale=d ** -0.5,
                           causal=causal, plan=plan, interpret=interpret,
                           with_lse=with_lse)
    return _unbhtd(out, b, h, dv), lse


def _flash_fwd(q, k, v, causal, block_q, block_kv):
    out, lse = _flash_forward_impl(q, k, v, causal, block_q, block_kv,
                                   with_lse=True)
    if lse is None:
        return out, (q, k, v, None, None)
    # The kernel writes its lse across a 128-lane tile; one lane of it is
    # kept ([BH, T] f32, 1/128 of the bytes), so a policy can afford to
    # save it for every layer. `_flash_bwd` broadcasts it back, as it
    # does delta.
    out = checkpoint_name(out, SAVED_NAMES[0])
    lse = checkpoint_name(lse[..., 0], SAVED_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, res, g):
    q, k, v, out, lse = res
    if lse is None:   # XLA fallback path (static shape decision)
        _, vjp = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=causal),
            q, k, v)
        return vjp(g)

    b, t, h, d = q.shape
    dv = v.shape[-1]
    plan = _plan_blocks(t, block_q, block_kv)
    interpret = backend.interpret()
    # delta_i = rowsum(dO_i * O_i) — O(T*D) traffic, fine in XLA.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                          # [B, T, H]
    delta = delta.transpose(0, 2, 1).reshape(b * h, t)
    delta = jnp.broadcast_to(delta[..., None], (b * h, t, 128))
    lse = jnp.broadcast_to(lse[..., None], (b * h, t, 128))
    dq, dk, dv_ = _flash_bwd_bhtd(
        _bhtd(q), _bhtd(k), _bhtd(v), _bhtd(g), lse, delta,
        sm_scale=d ** -0.5, causal=causal, plan=plan, interpret=interpret)
    return (_unbhtd(dq, b, h, d), _unbhtd(dk, b, h, d),
            _unbhtd(dv_, b, h, dv))


flash_attention.defvjp(_flash_fwd, _flash_bwd)
