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
# A banded call's three (`window` given and under T), so that a trace
# tells a window layer from a full one.
FLASH_FWD_BAND, FLASH_DQ_BAND, FLASH_DKV_BAND = (
    "flash_fwd_band", "flash_dq_band", "flash_dkv_band")

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


def _band_mask(s, q_start, k_start, window: int):
    # the band's lower edge: row i keeps the columns j > i - window
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where(ahead < window + k_start - q_start, s, NEG_INF)


# What a walk tells a tile of its place: wholly inside (no mask), on the
# diagonal, on the band's lower edge, or on both (a window narrower than
# two tiles).
_FREE, _DIAG, _EDGE, _BOTH = False, True, "edge", "both"


def _masked(s, kind, q_start, k_start, window):
    if kind in (_DIAG, _BOTH):
        s = _causal_mask(s, q_start, k_start)
    if kind in (_EDGE, _BOTH):
        s = _band_mask(s, q_start, k_start, window)
    return s


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


def _under_band(q_start, plan: _Plan, window: int, block, n):
    """A q sub-block's rows against the resident kv block's `n`
    sub-blocks and the band's lower edge (row i reads j > i - window):
    the sub-blocks under `lo` lie wholly under the band and are skipped,
    [lo, hi) are crossed by its edge."""
    first = block * n
    lo = (q_start - window + 1) // plan.sub_kv - first
    hi = (q_start + plan.sub_q - 1 - window) // plan.sub_kv + 1 - first
    return jnp.clip(lo, 0, n), jnp.clip(hi, 0, n)


def _past_band(k_start, plan: _Plan, window: int, block, n, t: int):
    """The same for a kv sub-block's columns against the resident q
    block: the band's edge crosses q sub-blocks [lo, hi), and those from
    `hi` on read none of these columns (or lie past the sequence's `t`
    rows: a grid step held on the last q block)."""
    first = block * n
    lo = (k_start + window) // plan.sub_q - first
    hi = jnp.minimum(
        (k_start + plan.sub_kv + window + plan.sub_q - 2) // plan.sub_q,
        t // plan.sub_q) - first
    return jnp.clip(lo, 0, n), jnp.clip(hi, 0, n)


def executed_share(plan: _Plan, t: int, causal: bool,
                   window: int | None = None) -> float:
    """Score elements the kernels compute, as a share of the T x T
    square. A causal pass needs half; tiles the diagonal crosses are
    computed whole, and with a `window` those its lower edge crosses,
    while the tiles under the band are not visited. Counted with the
    walks' own spans: for each q sub-block, the kv tiles from where
    `_walk_kv` starts to where it stops."""
    if not causal:
        return 1.0
    n = t // plan.sub_kv
    tiles = 0
    for q0 in range(0, t, plan.sub_q):
        tiles += int(_crossed(q0, plan.sub_q, plan.sub_kv, 0, n)[1])
        if window is not None:
            tiles -= int(_under_band(q0, plan, window, 0, n)[0])
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


def _on_diagonal(plan: _Plan, window: int | None):
    """What the diagonal's tiles are told: the band's edge can cross one
    of them only where the window is narrower than two tiles."""
    if window is not None and window < plan.sub_q + plan.sub_kv - 1:
        return _BOTH
    return _DIAG


def _walk_kv(tile, q_start, kv_block, *, causal: bool, plan: _Plan,
             window: int | None = None):
    """Walk the resident kv block's sub-blocks for one sub-block of q
    rows: unmasked below the diagonal, masked where it crosses. With a
    `window` the walk starts at the first tile the band reaches and masks
    those its lower edge crosses."""
    n = plan.block_kv // plan.sub_kv
    if not causal:
        return _walk(0, n, n, tile, _FREE)
    lo, hi = _crossed(q_start, plan.sub_q, plan.sub_kv, kv_block, n)
    if window is None:
        _walk(0, lo, n, tile, _FREE)
        _walk(lo, hi, n, tile, _DIAG)
        return
    first, inside = _under_band(q_start, plan, window, kv_block, n)
    inside = jnp.minimum(inside, lo)
    _walk(first, inside, n, tile, _EDGE)
    _walk(inside, lo, n, tile, _FREE)
    _walk(lo, hi, n, tile, _on_diagonal(plan, window))


def _walk_q(tile, k_start, q_block, *, causal: bool, plan: _Plan,
            window: int | None = None, t: int = 0):
    """The same for one sub-block of kv rows over the resident q block:
    from the diagonal down, and with a `window` no further than the last
    q tile that reads these columns."""
    n = plan.block_q // plan.sub_q
    if not causal:
        return _walk(0, n, n, tile, _FREE)
    lo, hi = _crossed(k_start, plan.sub_kv, plan.sub_q, q_block, n)
    if window is None:
        _walk(lo, hi, n, tile, _DIAG)
        _walk(hi, n, n, tile, _FREE)
        return
    edge, last = _past_band(k_start, plan, window, q_block, n, t)
    last = jnp.maximum(last, hi)
    edge = jnp.clip(edge, hi, last)
    _walk(lo, hi, n, tile, _on_diagonal(plan, window))
    _walk(hi, edge, n, tile, _FREE)
    _walk(edge, last, n, tile, _EDGE)


class _Span(NamedTuple):
    """Which blocks of the sequential grid axis a step of the parallel
    axes can need, as functions of that step's block on the other axis:
    `first(i)` .. `last(i)`, and `steps`, how many the grid walks. The
    kernel's block at step `s` is `first(i) + s`; its index map holds a
    step past `last(i)` (or, counting from 0, before `first(i)`) on the
    nearest block it does need, so that the step moves no bytes
    (`grouped_experts._held_slice` does the same), and its body's walks
    are empty there. `None` in place of a `_Span`: the axis is walked
    whole, one block at a time, as a call of one block or a non-causal
    one is."""
    first: object
    last: object
    steps: int
    offset: bool        # the grid's step 0 is `first(i)`, not block 0


def _kv_span(t: int, plan: _Plan, causal: bool, window: int | None):
    """The kv blocks a q block needs (forward and dQ)."""
    n_q, n_kv = t // plan.block_q, t // plan.block_kv
    if not causal or n_kv == 1:
        return None
    bq, bkv = plan.block_q, plan.block_kv

    def last(i):
        return ((i + 1) * bq - 1) // bkv

    if window is None:
        return _Span(lambda i: 0, last, n_kv, False)

    def first(i):
        return jnp.maximum(i * bq - window + 1, 0) // bkv

    steps = max(((i + 1) * bq - 1) // bkv - max(i * bq - window + 1, 0) // bkv
                for i in range(n_q)) + 1
    return _Span(first, last, steps, True)


def _q_span(t: int, plan: _Plan, causal: bool, window: int | None):
    """The q blocks a kv block needs (dK/dV)."""
    n_q, n_kv = t // plan.block_q, t // plan.block_kv
    if not causal or n_q == 1:
        return None
    bq, bkv = plan.block_q, plan.block_kv

    def first(j):
        return (j * bkv) // bq

    if window is None:
        return _Span(first, lambda j: n_q - 1, n_q, False)

    def last(j):
        return jnp.minimum(((j + 1) * bkv + window - 2) // bq, n_q - 1)

    steps = max(min(((j + 1) * bkv + window - 2) // bq, n_q - 1)
                - (j * bkv) // bq for j in range(n_kv)) + 1
    return _Span(first, last, steps, True)


def _held(span: _Span | None, i, s):
    """(the block the body of step `s` works on, the block its index map
    names)."""
    if span is None:
        return s, s
    at = span.first(i) + s if span.offset else s
    return at, jnp.clip(at, span.first(i), span.last(i))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                  causal: bool, plan: _Plan, with_lse: bool,
                  window: int | None = None, span: _Span | None = None):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = _held(span, qi, step)[0]

    @pl.when(step == 0)
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
            # a row whose columns of this tile are all masked adds
            # garbage at the running maximum NEG_INF, which the first
            # live column's correction exp(NEG_INF - m) = 0 wipes; every
            # row has one, its own position
            if masked:
                s = _masked(s, masked, q_start,
                            ki * plan.block_kv + j * plan.sub_kv, window)
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

        _walk_kv(tile, q_start, ki, causal=causal, plan=plan, window=window)

    n_q = plan.block_q // plan.sub_q
    _walk(0, n_q, n_q, q_rows)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / _lanes(l_scr[:], acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _q_side_specs(plan: _Plan, group: int, span: _Span | None, d: int,
                  dv: int):
    """Block specs of the grids that hold a q block while kv blocks go by
    (forward, dQ): (a q block of width w, K, V). Query head `b` reads
    key-value head `b // group`: K and V are never repeated in HBM."""
    def q_rows(w):
        return pl.BlockSpec((1, plan.block_q, w), lambda b, i, j: (b, i, 0))

    if group == 1 and span is None:
        def kv_at(b, i, j):
            return b, j, 0
    else:
        def kv_at(b, i, j):
            return b // group, _held(span, i, j)[1], 0
    return (q_rows, pl.BlockSpec((1, plan.block_kv, d), kv_at),
            pl.BlockSpec((1, plan.block_kv, dv), kv_at))


def _flash_bhtd(q, k, v, *, sm_scale: float, causal: bool, plan: _Plan,
                interpret: bool, with_lse: bool, window: int | None = None):
    """q [BHq, T, D]; k [BHkv, T, D], v [BHkv, T, Dv], BHq a multiple of
    BHkv, with T divisible by both block sizes.

    Returns (out [BHq, T, Dv], lse) where lse is [BHq, T, 128] f32
    (per-row logsumexp broadcast across the lane tile) when with_lse,
    else None."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    block_q = plan.block_q
    span = _kv_span(t, plan, causal, window)
    grid = (bh, t // block_q, span.steps if span else t // plan.block_kv)
    name = FLASH_FWD if window is None else FLASH_FWD_BAND

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, plan=plan,
        with_lse=with_lse, window=window, span=span)
    q_rows, kspec, vspec = _q_side_specs(plan, bh // k.shape[0], span, d, dv)
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), q.dtype)]
    out_specs = [q_rows(dv)]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, t, 128), jnp.float32))
        out_specs.append(q_rows(128))
    with jax.named_scope(name):
        res = pl.pallas_call(
            kernel,
            name=name,
            out_shape=tuple(out_shape),
            grid=grid,
            in_specs=[q_rows(d), kspec, vspec],
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
                    sm_scale: float, masked, window: int | None = None):
    """Rebuild one tile of the probabilities and of dS from saved
    lse/delta — the shared core of both backward kernels, so a
    masking/scaling change can never diverge between dQ and dK/dV.
    q, do: [sq, D] f32; k, v: [skv, D] f32; lse, delta: [sq, 128], every
    lane alike, as they arrive.
    `masked` is static: the walk knows which tiles the diagonal and the
    band's edge cross."""
    s = jax.lax.dot_general(
        q * sm_scale, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [sq, skv]
    s = _masked(s, masked, q_start, k_start, window)
    p = jnp.exp(s - _lanes(lse, s.shape[1]))    # [sq, skv]
    dp = jax.lax.dot_general(
        do, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [sq, skv]
    ds = p * (dp - _lanes(delta, s.shape[1]))   # [sq, skv]
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, causal: bool, plan: _Plan,
               window: int | None = None, span: _Span | None = None):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = _held(span, qi, step)[0]

    @pl.when(step == 0)
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
                sm_scale=sm_scale, masked=masked, window=window)
            dq_scr[rows, :] += sm_scale * jax.lax.dot_general(
                ds, k,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [sq, D]

        _walk_kv(tile, q_start, ki, causal=causal, plan=plan, window=window)

    n_q = plan.block_q // plan.sub_q
    _walk(0, n_q, n_q, q_rows)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                causal: bool, plan: _Plan, window: int | None = None,
                span: _Span | None = None, steps: int | None = None,
                t: int = 0):
    """The innermost grid axis walks, for each query head of the key-value
    head's group in turn, the `steps` q blocks the kv block needs; dK and
    dV are summed over all of them in VMEM and written once."""
    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = _held(span, ki, step if steps is None else step % steps)[0]

    @pl.when(step == 0)
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
                sm_scale=sm_scale, masked=masked, window=window)
            dv_scr[cols, :] += jax.lax.dot_general(
                p, do,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [skv, D]
            dk_scr[cols, :] += sm_scale * jax.lax.dot_general(
                ds, q,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [skv, D]

        _walk_q(tile, k_start, qi, causal=causal, plan=plan, window=window,
                t=t)

    n_kv = plan.block_kv // plan.sub_kv
    _walk(0, n_kv, n_kv, kv_rows)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, do, lse, delta, *, sm_scale: float,
                    causal: bool, plan: _Plan, interpret: bool,
                    window: int | None = None):
    """q [BHq, T, D], do [BHq, T, Dv]; k [BHkv, T, D], v [BHkv, T, Dv]
    (lse/delta [BHq, T, 128] f32) -> (dq [BHq, T, D], dk, dv as k, v:
    summed over a key-value head's group of query heads)."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]
    block_q, block_kv = plan.block_q, plan.block_kv
    common = dict(sm_scale=sm_scale, causal=causal, plan=plan, window=window)
    banded = window is not None
    name_dq, name_dkv = ((FLASH_DQ_BAND, FLASH_DKV_BAND) if banded
                         else (FLASH_DQ, FLASH_DKV))

    span = _kv_span(t, plan, causal, window)
    q_rows, kspec, vspec = _q_side_specs(plan, group, span, d, dv)
    with jax.named_scope(name_dq):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common, span=span),
            name=name_dq,
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            grid=(bh, t // block_q, span.steps if span else t // block_kv),
            in_specs=[q_rows(d), kspec, vspec, q_rows(dv), q_rows(128),
                      q_rows(128)],
            out_specs=q_rows(d),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    # dKV grid: kv blocks parallel, q blocks innermost/sequential, and
    # with grouped heads the group's query heads around them.
    span = _q_span(t, plan, causal, window)
    steps = span.steps if span else t // block_q
    if group == 1 and span is None:
        steps = None            # the axis is the q blocks and no more

        def q_at(b, j, i):
            return b, i, 0
    else:
        def q_at(b, j, s):
            return b * group + s // steps, _held(span, j, s % steps)[1], 0

    def q_rows2(w):
        return pl.BlockSpec((1, block_q, w), q_at)

    kspec2 = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    vspec2 = pl.BlockSpec((1, block_kv, dv), lambda b, j, i: (b, j, 0))
    with jax.named_scope(name_dkv):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **common, span=span, steps=steps,
                              t=t),
            name=name_dkv,
            out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)),
            grid=(k.shape[0], t // block_kv,
                  group * steps if steps else t // block_q),
            in_specs=[q_rows2(d), kspec2, vspec2, q_rows2(dv), q_rows2(128),
                      q_rows2(128)],
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 2048,
                    block_kv: int = 2048, window: int | None = None):
    """q [B, T, Hq, D], k, v [B, T, Hkv, D] attention; falls back to the
    XLA path on TPU-unfriendly shapes. Fully differentiable: both
    directions are Pallas kernels (backward = dQ + dKV kernels over saved
    lse). v may have another width than q and k (latent attention: 192
    against 128); the scale is that of q's width, the output has v's, and
    neither is padded to the other.

    Grouped heads: Hq a multiple of Hkv, query head `h` reads key-value
    head `h // (Hq // Hkv)` through the kernels' index maps. K and V are
    never repeated in HBM, and dK, dV are summed over a group's query
    heads inside the dK/dV kernel, whose innermost grid axis walks them.

    `window` (causal calls): position i reads `i - window < j <= i`. The
    tile walks get a lower bound from the band as they have an upper one
    from the diagonal, the tiles its lower edge crosses are masked, and
    the grid's sequential axis is only as long as the blocks one step's
    band can touch; the three kernels then run under the `_band` names. A
    window of T or more is no band and runs the plain kernels. Where a
    causal call's grid has more than one block on the sequential axis, a
    step outside the diagonal (or the band) stays on the nearest block
    it needs and fetches nothing.

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
    bounds and the (d, d) plan above are as they were.

    Grouped heads at a long sequence, banded and full, swept the same way
    (`benchmarks/tools/flash_group_sweep.py`, PERF.md, PR 57) at (Hq,
    Hkv, T, D) = (32, 4, 32768, 128), B = 1, device ms a call, block_q x
    block_kv, tile; `share`: the part of the square the walks compute
    (the band itself is 0.0308 of it, the triangle 0.5000):

        window 1,024             share    forward   dQ        dK/dV
        2048 x 2048, 512 x 512   0.0461    6.326     8.133     9.953
        1024 x 2048, 512 x 512   0.0461    6.469     9.440    10.180
        1024 x 1024, 512 x 512   0.0461    6.419     8.158    10.047
         512 x 1024, 512 x 512   0.0461    6.573     8.367    10.796
        1024 x 2048, 256 x 256   0.0385   11.031    12.116    13.262
        1024 x 1024, 256 x 256   0.0385   10.987    10.879    13.071
         512 x  512, 256 x 256   0.0385   11.562    11.729    13.995
        no window
        2048 x 2048, 512 x 512   0.5078   61.254    85.864   104.446
        1024 x 2048, 512 x 512   0.5078   62.033    86.805   107.286
        2048 x 1024, 512 x 512   0.5078   63.811    91.794   105.578
        1024 x 1024, 512 x 512   0.5078   65.216    93.567   109.554
        1024 x 2048, 256 x 256   0.5039  133.197   135.951   166.268

    At D = 128, 2048 x 2048 fits and is the quickest for both, so
    `models/window_moe_train.py` passes those bounds: a window layer's
    three kernels take 24.4 ms where a full layer's take 251.6. Under a
    window of 1,024 a 512-row tile reads 1,536 columns (the edge's tile,
    a whole one, the diagonal's) for the 1,024 it needs; a 256-wide tile
    reads 1,280 and loses more than that to the cost of a tile. dK and dV
    are summed over a group's 8 query heads in the kernel's scratch, one
    write a key-value head: eight partial gradients of [32, T, 128]
    summed outside would add 0.5 GB of traffic a layer and were not
    built."""
    out, _ = _flash_forward_impl(q, k, v, causal, block_q, block_kv, window,
                                 with_lse=False)
    return out


def _band(window, causal: bool, t: int):
    """The call's window as the kernels take it: None where it cuts
    nothing off."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("a window is a causal call's, of one position "
                         "or more")
    return None if window >= t else int(window)


def _flash_forward_impl(q, k, v, causal, block_q, block_kv, window,
                        with_lse):
    """Returns (out, lse|None). lse is None on the XLA fallback path or
    when with_lse=False (the inference variant, which skips the lse
    write entirely)."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h} query heads do not divide over "
                         f"{k.shape[2]} key and {v.shape[2]} value heads")
    window = _band(window, causal, t)
    plan = _plan_blocks(t, block_q, block_kv)
    if plan is None:
        backend.note_fallback("flash_attention", f"T={t}")
        return reference_attention(q, k, v, causal=causal,
                                   window=window), None
    interpret = backend.interpret()
    out, lse = _flash_bhtd(_bhtd(q), _bhtd(k), _bhtd(v), sm_scale=d ** -0.5,
                           causal=causal, plan=plan, interpret=interpret,
                           with_lse=with_lse, window=window)
    return _unbhtd(out, b, h, dv), lse


def _flash_fwd(q, k, v, causal, block_q, block_kv, window):
    out, lse = _flash_forward_impl(q, k, v, causal, block_q, block_kv,
                                   window, with_lse=True)
    if lse is None:
        return out, (q, k, v, None, None)
    # The kernel writes its lse across a 128-lane tile; one lane of it is
    # kept ([BH, T] f32, 1/128 of the bytes), so a policy can afford to
    # save it for every layer. `_flash_bwd` broadcasts it back, as it
    # does delta.
    out = checkpoint_name(out, SAVED_NAMES[0])
    lse = checkpoint_name(lse[..., 0], SAVED_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, window, res, g):
    q, k, v, out, lse = res
    window = _band(window, causal, q.shape[1])
    if lse is None:   # XLA fallback path (static shape decision)
        _, vjp = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=causal,
                                                window=window),
            q, k, v)
        return vjp(g)

    b, t, h, d = q.shape
    hkv = k.shape[2]
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
        sm_scale=d ** -0.5, causal=causal, plan=plan, interpret=interpret,
        window=window)
    return (_unbhtd(dq, b, h, d), _unbhtd(dk, b, hkv, d),
            _unbhtd(dv_, b, hkv, dv))


flash_attention.defvjp(_flash_fwd, _flash_bwd)
