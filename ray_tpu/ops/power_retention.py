"""Power retention (power 2) over a state of fixed size a sequence.

One key-value head j of one layer weighs every earlier position s of its
sequence for a query head i of its group (Manifest AI, arXiv:2507.04239):

    a[t, s] = (q_t . k_s)^2 / d  x  exp(sum_{r = s+1 .. t} log g_r),  s <= t
    o_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

`phi(x)` is the symmetric second power of x: `phi(q) . phi(k) = (q . k)^2`
exactly, so the same numbers come from a state that does not grow:

    S_t = g_t S_{t-1} + v_t phi(k_t)^T        [d, D]
    z_t = g_t z_{t-1} + phi(k_t)              [D]
    o_t = S_t phi(q_t) / (z_t . phi(q_t) + d eps)

**The layout of phi.** The d dims are cut into tiles of 16; the features
are the 16 x 16 products of every pair of tiles (A <= B), pairs in row
order, a pair's 256 features as `x[A*16 + a] * x[B*16 + b]` at
`a * 16 + b`, times sqrt 2 where A < B. d = 128: 36 pairs, D = 9216 (the
exact symmetric power has 8256; the 960 more are the lower halves of the
eight diagonal pairs, each with weight 1 where the exact form has one of
weight sqrt 2: the same dot product). A pair is a whole number of lane
tiles, and sixteen features of it are one row of x against one tile of x:
a broadcast, which is how the chunk kernel makes phi in VMEM and never
reads it from HBM.

**The state as stored**: `s [L, blocks, Hkv, d, D]` and `z [L, blocks,
Hkv, 1, D]`, float32, the features on the lanes; a block is one
sequence's state and block 0 the engine's trash block. Both kernels take
the whole pool, are told layer and block through scalar prefetch, and
write the block in place (`input_output_aliases`).

`retention_chunk` is prefill's: C positions of one sequence; inside the
chunk the masked square, across chunks the state. Rows at and past
`length` (a chunk bucket's padding) weigh nothing and leave the state as
it was; `first` (the sequence's first chunk) reads the block as zeros,
whatever a freed block still holds. `retention_step` is decode's: one
position of each of B sequences, each against its own block; idle rows
name block 0. Matmul operands are bfloat16 with float32 accumulation; the
state is updated in float32 and read as a high and a low bfloat16 part.

Each has a plain `jax.numpy` path behind `impl`, which the CPU tests
compare with the kernel in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.ops.sparse_latent import resolve_impl

# Kernel names in the compiled program and the profiler's trace; PERF.md,
# section 3, lists them. Each call sits in a `named_scope` of its name.
RETENTION_CHUNK = "retention_chunk"
RETENTION_STEP = "retention_step"

TILE = 16                   # dims a tile of the feature layout
LANES = 128
PAD_ROWS = 8                # z's row and seven of zeros under a state tile
VMEM_LIMIT = 96 * 1024 * 1024
CHUNK_PHI_BYTES = 6 << 20   # phi(q) of a chunk's feature tile, in VMEM
STEP_TILE_BYTES = 5 << 20   # one block's state tile, in VMEM
MM_DTYPE = jnp.bfloat16     # what both kernels feed the MXU
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the feature map
# ---------------------------------------------------------------------------

def tile_pairs(d: int):
    """(A [P], B [P]) int32: the tile pairs A <= B of d dims, row order."""
    nt = d // TILE
    pairs = [(a, b) for a in range(nt) for b in range(a, nt)]
    return (np.asarray([p[0] for p in pairs], np.int32),
            np.asarray([p[1] for p in pairs], np.int32))


def feature_dim(d: int) -> int:
    if d % TILE:
        raise ValueError(f"head_dim {d} is not a multiple of {TILE}")
    nt = d // TILE
    return nt * (nt + 1) // 2 * TILE * TILE


def phi(x):
    """x [..., d] -> float32 [..., D], the layout above."""
    d = x.shape[-1]
    pa, pb = tile_pairs(d)
    xt = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // TILE, TILE))
    coef = jnp.asarray(np.where(pa == pb, 1.0, math.sqrt(2.0)), jnp.float32)
    out = (xt[..., pa, :, None] * coef[:, None, None]) * xt[..., pb, None, :]
    return out.reshape(x.shape[:-1] + (feature_dim(d),))


def _rounded(x, state_round: str):
    """A state as it is kept (`state_round`: the benchmark's control
    rounds it to bfloat16 at every write, and keeps float32 bytes)."""
    if state_round == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


# ---------------------------------------------------------------------------
# plain paths
# ---------------------------------------------------------------------------

def retention_quadratic(q, k, v, logg, *, eps: float):
    """The definition, with no state: q [T, Hq, d], k, v [T, Hkv, d],
    logg [T, Hkv] -> o [T, Hq, d] float32."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.astype(jnp.float32).reshape(t, hkv, hq // hkv, d)
    cum = jnp.cumsum(logg.astype(jnp.float32), axis=0)
    scores = jnp.einsum("tjgd,sjd->jgts", qg, k.astype(jnp.float32),
                        precision=_HIGHEST) ** 2
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    expo = jnp.where(causal[None], cum.T[:, :, None] - cum.T[:, None, :],
                     -jnp.inf)
    a = scores * jnp.exp(expo)[:, None]
    num = jnp.einsum("jgts,sjd->tjgd", a, v.astype(jnp.float32),
                     precision=_HIGHEST)
    den = jnp.sum(a, -1).transpose(2, 0, 1)[..., None]
    return (num / (den + d * eps)).reshape(t, hq, d)


def _chunk_parts(logg, length):
    """-> (cum [C, Hkv]: the chunk's running log decay, 0 steps past
    `length`; w [C, Hkv]: what a position's key still weighs at the
    chunk's end, 0 past `length`; total [Hkv]: the decay of the state
    the chunk found)."""
    c = logg.shape[0]
    live = jnp.arange(c) < length
    cum = jnp.cumsum(jnp.where(live[:, None], logg.astype(jnp.float32), 0.0),
                     axis=0)
    w = jnp.where(live[:, None], jnp.exp(cum[-1][None] - cum), 0.0)
    return cum, w, jnp.exp(cum[-1])


def _chunk_plain(q, k, v, logg, s, z, first, length, *, eps, state_round):
    """One chunk against one block's state s [Hkv, d, D], z [Hkv, 1, D]."""
    c, hq, d = q.shape
    hkv = k.shape[1]
    cum, w, total = _chunk_parts(logg, length)
    s = jnp.where(first, 0.0, s)
    z = jnp.where(first, 0.0, z)[:, 0]
    qg = q.astype(jnp.float32).reshape(c, hkv, hq // hkv, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("tjgd,sjd->jgts", qg, kf, precision=_HIGHEST) ** 2
    seen = ((jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])
            & (jnp.arange(c) < length)[None, :])
    expo = jnp.where(seen[None], cum.T[:, :, None] - cum.T[:, None, :],
                     -jnp.inf)
    a = scores * jnp.exp(expo)[:, None]
    fq, fk = phi(qg), phi(kf)
    carried = jnp.exp(cum)[:, :, None]                       # [C, Hkv, 1]
    num = (jnp.einsum("jgts,sjd->tjgd", a, vf, precision=_HIGHEST)
           + carried[..., None] * jnp.einsum(
               "tjgf,jdf->tjgd", fq, s, precision=_HIGHEST))
    den = (jnp.sum(a, -1).transpose(2, 0, 1)
           + carried * jnp.einsum("tjgf,jf->tjg", fq, z,
                                  precision=_HIGHEST))
    o = (num / (den[..., None] + d * eps)).reshape(c, hq, d)
    s_new = total[:, None, None] * s + jnp.einsum(
        "sjd,sjf,sj->jdf", vf, fk, w, precision=_HIGHEST)
    z_new = total[:, None] * z + jnp.einsum("sjf,sj->jf", fk, w,
                                            precision=_HIGHEST)
    return (o, _rounded(s_new, state_round),
            _rounded(z_new, state_round)[:, None])


def _step_plain(q, k, v, logg, s, z, *, eps, state_round):
    """One position of B sequences against their states s [B, Hkv, d, D],
    z [B, Hkv, 1, D]."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    g = jnp.exp(logg.astype(jnp.float32))[..., None, None]
    fk = phi(k)                                              # [B, Hkv, D]
    s_new = _rounded(g * s + v.astype(jnp.float32)[..., None]
                     * fk[:, :, None, :], state_round)
    z_new = _rounded(g * z + fk[:, :, None, :], state_round)
    fq = phi(q.reshape(b, hkv, hq // hkv, d))
    num = jnp.einsum("bjgf,bjdf->bjgd", fq, s_new, precision=_HIGHEST)
    den = jnp.einsum("bjgf,bjf->bjg", fq, z_new[:, :, 0],
                     precision=_HIGHEST)
    return (num / (den[..., None] + d * eps)).reshape(b, hq, d), s_new, z_new


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

def chunk_plan(c: int, hq: int, hkv: int, d: int):
    """Tile pairs a grid step of the chunk kernel, or the reason there is
    no plan. The chunk's positions are lanes, so whole lane tiles of
    them; phi(q) of one feature tile has to fit its VMEM budget."""
    if d % TILE:
        return None, f"head_dim {d} is not a multiple of {TILE}"
    if c % LANES:
        return None, f"a chunk of {c} positions is not whole lane tiles"
    if hq % hkv:
        return None, f"{hq} query heads over {hkv} key-value heads"
    n_pairs = len(tile_pairs(d)[0])
    cols = hq // hkv * c
    fits = [p for p in range(1, n_pairs + 1) if n_pairs % p == 0
            and p * TILE * TILE * cols * 2 <= CHUNK_PHI_BYTES]
    if not fits:
        return None, (f"phi(q) of one tile pair over {cols} columns is "
                      f"over {CHUNK_PHI_BYTES} B of VMEM")
    return max(fits), ""


def step_plan(d: int):
    """Feature tiles a block of the step kernel, or the reason there is
    no plan: the fewest whose state tile fits its VMEM budget."""
    if d % TILE:
        return None, f"head_dim {d} is not a multiple of {TILE}"
    big = feature_dim(d)
    for n in range(1, big // LANES + 1):
        if big % (n * LANES) == 0 and d * (big // n) * 4 <= STEP_TILE_BYTES:
            return n, ""
    return None, f"no feature tile of head_dim {d} fits VMEM"


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------

def _make_phi(pa_ref, pb_ref, first_pair, per_step: int, x_ref, out_ref):
    """Rows of phi(x)^T for `per_step` tile pairs from `first_pair` into
    out_ref [per_step * 256, N]; x_ref [1, d, N] float32, dims on the
    sublanes. Sixteen rows at a time: one row of x against one tile."""
    for p in range(per_step):
        ta, tb = pa_ref[first_pair + p], pb_ref[first_pair + p]
        coef = jnp.where(ta == tb, 1.0, math.sqrt(2.0)).astype(jnp.float32)
        tile_b = x_ref[0, pl.ds(pl.multiple_of(tb * TILE, TILE), TILE), :]
        for a in range(TILE):
            row = x_ref[0, pl.ds(ta * TILE + a, 1), :] * coef
            at = p * TILE * TILE + a * TILE
            out_ref[at:at + TILE, :] = (row * tile_b).astype(out_ref.dtype)


def _chunk_kernel(pa_ref, pb_ref, meta_ref, qt_ref, kt_ref, k_ref, vt_ref,
                  vwt_ref, grow_ref, gcol_ref, total_ref, s_ref, z_ref,
                  o_ref, s_out, z_out, phiq, phik, *, per_step: int,
                  group: int, state_round: str):
    dt = pl.program_id(1)
    c = k_ref.shape[1]
    d = s_ref.shape[3]

    @pl.when(dt == 0)
    def _inside_the_chunk():
        length = meta_ref[3]
        s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        seen = (s_idx <= t_idx) & (s_idx < length)
        gcol = gcol_ref[0]                                   # [C, 1]
        for g in range(group):
            cols = slice(g * c, (g + 1) * c)
            kq = jnp.dot(k_ref[0], qt_ref[0, :, cols].astype(k_ref.dtype),
                         preferred_element_type=jnp.float32)  # [s, t]
            expo = jnp.where(seen, grow_ref[0, :, cols] - gcol, -jnp.inf)
            a = (kq * kq * jnp.exp(expo)).astype(vt_ref.dtype)
            o_ref[0, :, cols] = jnp.dot(
                vt_ref[0], a, preferred_element_type=jnp.float32)

    _make_phi(pa_ref, pb_ref, dt * per_step, per_step, qt_ref, phiq)
    _make_phi(pa_ref, pb_ref, dt * per_step, per_step, kt_ref, phik)

    state = jnp.concatenate(
        [s_ref[0, 0, 0], z_ref[0, 0, 0],
         jnp.zeros((PAD_ROWS - 1, s_ref.shape[4]), jnp.float32)], axis=0)
    state = jnp.where(meta_ref[2] > 0, 0.0, state)           # a first chunk
    high = state.astype(MM_DTYPE)
    low = (state - high.astype(jnp.float32)).astype(MM_DTYPE)
    carried = (jnp.dot(high, phiq[...], preferred_element_type=jnp.float32)
               + jnp.dot(low, phiq[...],
                         preferred_element_type=jnp.float32))
    o_ref[0] += carried * jnp.exp(grow_ref[0])
    new = total_ref[0][:, 0:1] * state + jax.lax.dot_general(
        vwt_ref[0], phik[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    new = _rounded(new, state_round)
    s_out[0, 0, 0] = new[:d]
    z_out[0, 0, 0] = new[d:d + 1]


def _chunk_pallas(q, k, v, logg, pool_s, pool_z, layer, block, first,
                  length, *, eps, state_round, per_step):
    c, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    big = pool_s.shape[-1]
    dt_size = per_step * TILE * TILE
    mm = MM_DTYPE
    cum, w, total = _chunk_parts(logg, length)
    pa, pb = tile_pairs(d)
    # dims on the sublanes, positions on the lanes; a group's heads side
    # by side: column g * C + t
    qt = q.astype(jnp.float32).reshape(c, hkv, group, d).transpose(
        1, 3, 2, 0).reshape(hkv, d, group * c)
    kt = k.astype(jnp.float32).transpose(1, 2, 0)            # [Hkv, d, C]
    vt = v.astype(jnp.float32).transpose(1, 2, 0)
    pad = jnp.zeros((hkv, PAD_ROWS - 1, c), jnp.float32)
    ones = jnp.ones((hkv, 1, c), jnp.float32)
    wt = w.T[:, None, :]                                     # [Hkv, 1, C]
    vt_aug = jnp.concatenate([vt, ones, pad], 1).astype(mm)
    vwt_aug = jnp.concatenate([vt * wt, wt, pad], 1).astype(mm)
    grow = jnp.tile(cum.T[:, None, :], (1, 1, group))        # [Hkv, 1, GC]
    gcol = cum.T[:, :, None]                                 # [Hkv, C, 1]
    total_b = jnp.broadcast_to(total[:, None, None], (hkv, 1, LANES))
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(block, jnp.int32),
                      jnp.asarray(first, jnp.int32),
                      jnp.asarray(length, jnp.int32)])

    def head(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda j, t, *_: (j,) + (0,) * len(shape))

    def pool(rows):
        return pl.BlockSpec(
            (1, 1, 1, rows, dt_size),
            lambda j, t, pa, pb, meta: (meta[0], meta[1], j, 0, t))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hkv, big // dt_size),
        in_specs=[head(d, group * c), head(d, c), head(c, d),
                  head(d + PAD_ROWS, c), head(d + PAD_ROWS, c),
                  head(1, group * c), head(c, 1), head(1, LANES),
                  pool(d), pool(1)],
        out_specs=[head(d + PAD_ROWS, group * c), pool(d), pool(1)],
        scratch_shapes=[pltpu.VMEM((dt_size, group * c), mm),
                        pltpu.VMEM((dt_size, c), mm)],
    )
    with jax.named_scope(RETENTION_CHUNK):
        out, pool_s, pool_z = pl.pallas_call(
            functools.partial(_chunk_kernel, per_step=per_step, group=group,
                              state_round=state_round),
            name=RETENTION_CHUNK,
            out_shape=[
                jax.ShapeDtypeStruct((hkv, d + PAD_ROWS, group * c),
                                     jnp.float32),
                jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype)],
            grid_spec=grid_spec,
            # operands count the three prefetched ones
            input_output_aliases={11: 1, 12: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(jnp.asarray(pa), jnp.asarray(pb), meta, qt, kt, k.transpose(
            1, 0, 2).astype(mm), vt_aug, vwt_aug, grow, gcol, total_b,
          pool_s, pool_z)
    num = out[:, :d].reshape(hkv, d, group, c)
    den = out[:, d].reshape(hkv, 1, group, c)
    o = num / (den + d * eps)
    return o.transpose(3, 0, 2, 1).reshape(c, hq, d), pool_s, pool_z


def retention_chunk(q, k, v, logg, pool_s, pool_z, layer, block, first,
                    length, *, eps: float, state_round: str = "none",
                    impl: str = "auto"):
    """A prefill chunk of one sequence through one layer's retention.

    q [C, Hq, d]; k, v [C, Hkv, d]; logg [C, Hkv] float32 (<= 0); pool_s
    [L, blocks, Hkv, d, D], pool_z [L, blocks, Hkv, 1, D] float32; layer,
    block: which state; first: the sequence's first chunk (the block is
    read as zeros); length: the chunk's live positions.
    -> (o [C, Hq, d] float32, pool_s, pool_z)."""
    c, hq, d = q.shape
    if resolve_impl(impl) == "pallas":
        per_step, why = chunk_plan(c, hq, k.shape[1], d)
        if per_step is not None:
            return _chunk_pallas(q, k, v, logg, pool_s, pool_z, layer, block,
                                 first, length, eps=eps,
                                 state_round=state_round, per_step=per_step)
        backend.note_fallback(RETENTION_CHUNK, why)
    o, s, z = _chunk_plain(q, k, v, logg, pool_s[layer, block],
                           pool_z[layer, block], first, length, eps=eps,
                           state_round=state_round)
    return (o, pool_s.at[layer, block].set(s),
            pool_z.at[layer, block].set(z))


# ---------------------------------------------------------------------------
# the step kernel
# ---------------------------------------------------------------------------

def _step_kernel(blocks_ref, meta_ref, fq_ref, fk_ref, v_ref, g_ref, s_ref,
                 z_ref, o_ref, den_ref, s_out, z_out, *, state_round: str):
    dt = pl.program_id(2)
    g = g_ref[0, 0][:, 0:1]                                  # [1, 1]
    fk = fk_ref[0, 0]                                        # [1, Dt]
    s_new = _rounded(g * s_ref[0, 0, 0] + v_ref[0, 0] * fk, state_round)
    z_new = _rounded(g * z_ref[0, 0, 0] + fk, state_round)
    s_out[0, 0, 0] = s_new
    z_out[0, 0, 0] = z_new
    fq = fq_ref[0, 0].astype(MM_DTYPE)                       # [8, Dt]
    high = s_new.astype(MM_DTYPE)
    low = (s_new - high.astype(jnp.float32)).astype(MM_DTYPE)
    nt = (((1,), (1,)), ((), ()))
    num = (jax.lax.dot_general(fq, high, nt,
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(fq, low, nt,
                                 preferred_element_type=jnp.float32))
    den = jnp.sum(fq.astype(jnp.float32) * z_new, axis=-1, keepdims=True)

    @pl.when(dt == 0)
    def _first_tile():
        o_ref[...] = jnp.zeros_like(o_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    o_ref[0, 0] += num
    den_ref[0, 0] += jnp.broadcast_to(den, den_ref.shape[2:])


def _step_pallas(q, k, v, logg, pool_s, pool_z, layer, blocks, *, eps,
                 state_round, tiles):
    b, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    rows = -(-group // 8) * 8
    big = pool_s.shape[-1]
    dt_size = big // tiles
    fq = phi(q.reshape(b, hkv, group, d))
    fq = jnp.pad(fq, ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    fk = phi(k)[:, :, None, :]                               # [B, Hkv, 1, D]
    vcol = v.astype(jnp.float32)[..., None]                  # [B, Hkv, d, 1]
    gb = jnp.broadcast_to(jnp.exp(logg.astype(jnp.float32))[..., None, None],
                          (b, hkv, 1, LANES))
    meta = jnp.asarray(layer, jnp.int32)[None]

    def row(r, w, tiled=False):
        return pl.BlockSpec(
            (1, 1, r, w),
            lambda i, j, t, *_: (i, j, 0, t if tiled else 0))

    def pool(r):
        return pl.BlockSpec(
            (1, 1, 1, r, dt_size),
            lambda i, j, t, blocks, meta: (meta[0], blocks[i], j, 0, t))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, tiles),
        in_specs=[row(rows, dt_size, True), row(1, dt_size, True),
                  row(d, 1), row(1, LANES), pool(d), pool(1)],
        out_specs=[row(rows, d), row(rows, LANES), pool(d), pool(1)],
    )
    with jax.named_scope(RETENTION_STEP):
        num, den, pool_s, pool_z = pl.pallas_call(
            functools.partial(_step_kernel, state_round=state_round),
            name=RETENTION_STEP,
            out_shape=[
                jax.ShapeDtypeStruct((b, hkv, rows, d), jnp.float32),
                jax.ShapeDtypeStruct((b, hkv, rows, LANES), jnp.float32),
                jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype)],
            grid_spec=grid_spec,
            # operands count the two prefetched ones
            input_output_aliases={6: 2, 7: 3},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(jnp.asarray(blocks, jnp.int32), meta, fq, fk, vcol, gb,
          pool_s, pool_z)
    o = num[:, :, :group] / (den[:, :, :group, 0:1] + d * eps)
    return o.reshape(b, hq, d), pool_s, pool_z


def retention_step(q, k, v, logg, pool_s, pool_z, layer, blocks, *,
                   eps: float, state_round: str = "none",
                   impl: str = "auto"):
    """One decode position of B sequences through one layer's retention.

    q [B, Hq, d]; k, v [B, Hkv, d]; logg [B, Hkv] float32; the pools as
    `retention_chunk` takes them; blocks [B] int32: each row's state (idle
    rows: 0, the trash block, which they rewrite among themselves).
    -> (o [B, Hq, d] float32, pool_s, pool_z)."""
    d = q.shape[-1]
    if resolve_impl(impl) == "pallas":
        tiles, why = step_plan(d)
        if tiles is not None:
            return _step_pallas(q, k, v, logg, pool_s, pool_z, layer, blocks,
                                eps=eps, state_round=state_round,
                                tiles=tiles)
        backend.note_fallback(RETENTION_STEP, why)
    o, s, z = _step_plain(q, k, v, logg, pool_s[layer, blocks],
                          pool_z[layer, blocks], eps=eps,
                          state_round=state_round)
    return (o, pool_s.at[layer, blocks].set(s),
            pool_z.at[layer, blocks].set(z))
