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
sequence's state and block 0 the engine's trash block. Beside them `ring
[L, blocks, Hkv, 3, RING, d]`, float32: the `k` (after norm and rotary),
`v` and `log g` (across its row's lanes) of the decode tokens that are not
in `s` and `z` yet, oldest first, and per block how many of them it holds
(`held`, kept by the caller: all layers step together). Both kernels take
the whole pool, are told layer and block through scalar prefetch, and
write in place (`input_output_aliases`).

`retention_chunk` is prefill's: C positions of one sequence; inside the
chunk the masked square, across chunks the state. Rows at and past
`length` (a chunk bucket's padding) weigh nothing and leave the state as
it was; `first` (the sequence's first chunk) reads the block as zeros,
whatever a freed block still holds. A chunk reads no ring: its caller
runs it on a sequence that is not decoding and leaves the block's `held`
at 0. Matmul operands are bfloat16 with float32 accumulation; the state is
updated in float32 and read as a high and a low bfloat16 part.

`retention_step` is decode's: one position of each of B sequences, each
against its own block. The step's token goes into its ring first. With
`S_b`, `z_b` the stored state, complete up to position b - 1, and the
ring holding positions b .. t:

    c_i = exp(sum_{r = i+1 .. t} log g_r),   G = exp(sum_{r = b .. t} log g_r)
    o_t = (G S_b phi(q_t) + sum_i c_i (q_t . k_i)^2 v_i)
          / (G z_b . phi(q_t) + sum_i c_i (q_t . k_i)^2 + d eps)

which is the per-token form term for term; the ring's scores are float32
dot products. A row whose ring is full with this token **folds**: `S <- G
S_b + sum_i c_i v_i phi(k_i)^T`, `z` alike, phi made in VMEM from the
ring's keys as the chunk makes it, the update one pass of the MXU over
the operands' three bfloat16 parts each, the six products that float32
keeps side by side along the contraction (nothing is lost against the
per-token multiply-add), and its ring is empty after. So a step reads
every decoding row's state once and writes only the folding rows': the
kernel holds the pool in HBM (`pl.ANY`), a live row's tile comes by a DMA
started two live tiles earlier, and goes back by a DMA under
`pl.when(fold)`. An idle row (block 0) moves nothing, of the trash block
either. Rows fold when their own
ring is full, so the traffic's staggered positions put about B / RING
folds in every step and every step is the same length.

`RING` = 16. By bytes a step moves 1 + 1 / RING states where the
read-modify-write moved 2 (1.25, 1.125, 1.06 at 4, 8, 16), and bytes are
what the kernel costs: timed alone on the chip at
brumby-14b.docgen-closed24's shapes, sixteen rows folding in turn, eight
layers, a step took 9.95, 8.98, 8.52 ms at 4, 8, 16 between 7.98 where
no row folds and 15.97 where every row does (the read-modify-write's
15.98), on one line in the rows that fold (PERF.md, PR 53). The ring's
own work (RING float32 dot products a head on the vector unit, 24 KB of
ring a head at 16) hides under a tile's read at all three. 16 is also
the most whose six products of parts fit the 128 lanes of one pass (6 x
RING <= 128); past it a fold is two passes for a sixteenth of a state
less. Under `state_round` (the benchmark's control: the state rounded at
every write) a ring holds one token and every step folds, so that the
control rounds at every token as it did.

Outside the kernel the step makes phi(q) alone, by `phi_selected` (two
selections on the MXU and one product, in the kernel's operand dtype);
phi(k) is made in the kernel, and only where a row folds.

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
STEP_SLOTS = 3              # buffers of the step kernel's state tiles
RING = 16                   # decode tokens a ring takes before it folds
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


def phi_selected(x, dtype=jnp.float32):
    """`phi(x)` in `dtype`, made where a compiled step makes it outside a
    kernel: each feature's two factors by a one-hot `[d, D]` matrix on the
    MXU (a selection copies: exact for bfloat16 rows at one pass, for
    float32 rows at the highest precision), then the one product. XLA
    lays `phi`'s `[..., 16, 16]` products out anew for the D lanes, at
    several times what this costs (PERF.md, PR 53)."""
    d = x.shape[-1]
    pa, pb = tile_pairs(d)
    at = np.arange(TILE)
    # feature (pair, a, b) is x[A * 16 + a] x[B * 16 + b]
    first = np.repeat(pa[:, None] * TILE + at, TILE, axis=1).reshape(-1)
    second = np.tile(pb[:, None] * TILE + at, (1, TILE)).reshape(-1)
    coef = np.repeat(np.where(pa == pb, 1.0, math.sqrt(2.0)),
                     TILE * TILE).astype(np.float32)
    dims = jnp.arange(d)[:, None]
    exact = None if x.dtype == jnp.bfloat16 else _HIGHEST

    def selected(index):
        return jnp.einsum("...d,df->...f", x,
                          (dims == index[None]).astype(x.dtype),
                          precision=exact, preferred_element_type=x.dtype)

    return ((selected(first).astype(jnp.float32) * coef)
            * selected(second).astype(jnp.float32)).astype(dtype)


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


def _step_plain(q, k, v, logg, s, z, ring, held, fold, live, *, eps,
                entries, state_round):
    """One position of B sequences against their states s [B, Hkv, d, D],
    z [B, Hkv, 1, D] and rings [B, Hkv, 3, RING, d]; held, fold, live [B]
    as `ring_after` has them."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    f32 = jnp.float32
    at = jnp.arange(ring.shape[3])
    put = ((at == held[:, None]) & live[:, None])[:, None, :, None]
    kr = jnp.where(put, k.astype(f32)[:, :, None], ring[:, :, 0])
    vr = jnp.where(put, v.astype(f32)[:, :, None], ring[:, :, 1])
    gr = jnp.where(put, logg.astype(f32)[:, :, None, None], ring[:, :, 2])
    ring = jnp.stack([kr, vr, gr], axis=2)
    kr, vr, gr = kr[:, :, :entries], vr[:, :, :entries], gr[:, :, :entries]
    # an entry's decay from its position to this one; 0 past the last held
    holds = (at[:entries] <= held[:, None])[:, None]         # [B, 1, R]
    logs = jnp.where(holds, gr[..., 0], 0.0)
    total = jnp.sum(logs, -1, keepdims=True)
    decay = jnp.where(holds, jnp.exp(total - jnp.cumsum(logs, -1)), 0.0)
    carried = jnp.exp(total)[..., None]                      # G [B, Hkv, 1, 1]
    qg = q.astype(f32).reshape(b, hkv, hq // hkv, d)
    w = jnp.einsum("bjgd,bjrd->bjgr", qg, kr, precision=_HIGHEST) ** 2 \
        * decay[:, :, None]
    fq = phi(qg)
    num = (carried * jnp.einsum("bjgf,bjdf->bjgd", fq, s, precision=_HIGHEST)
           + jnp.einsum("bjgr,bjrd->bjgd", w, vr, precision=_HIGHEST))
    den = (carried[..., 0] * jnp.einsum("bjgf,bjf->bjg", fq, z[:, :, 0],
                                        precision=_HIGHEST)
           + jnp.sum(w, -1))
    o = (num / (den[..., None] + d * eps)).reshape(b, hq, d)
    fk = phi(kr)                                             # [B, Hkv, R, D]
    s_new = _rounded(carried * s + jnp.einsum(
        "bjr,bjrd,bjrf->bjdf", decay, vr, fk, precision=_HIGHEST),
        state_round)
    z_new = _rounded(carried * z + jnp.einsum(
        "bjr,bjrf->bjf", decay, fk, precision=_HIGHEST)[:, :, None],
        state_round)
    folds = fold[:, None, None, None]
    return (jnp.where(live[:, None, None], o, 0.0),
            jnp.where(folds, s_new, s), jnp.where(folds, z_new, z), ring)


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
    no plan: the fewest, of whole tile pairs and whole multiples of d
    features each, whose state tile fits its VMEM budget."""
    if d % TILE:
        return None, f"head_dim {d} is not a multiple of {TILE}"
    if len(_PARTS) * RING > LANES:
        return None, f"a ring of {RING} does not fold in one pass"
    big, n_pairs = feature_dim(d), len(tile_pairs(d)[0])
    for n in range(1, n_pairs + 1):
        if n_pairs % n == 0 and big // n % d == 0 \
                and d * (big // n) * 4 <= STEP_TILE_BYTES:
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

# rows of the step kernel's per-row scalars (scalar prefetch, [5, B])
_BLOCK, _HELD, _FOLD, _NEXT, _ORD = range(5)
# a float32 is three bfloat16 parts (0 high, 1 middle, 2 low); the
# products of parts that a float32 product keeps, (part of a, part of b)
_PARTS = ((0, 0), (0, 1), (1, 0), (0, 2), (2, 0), (1, 1))


def _step_kernel(row_ref, meta_ref, pa_ref, pb_ref, fq_ref, q_ref, now_ref,
                 ring_ref, s_hbm, z_hbm, o_ref, den_ref, ring_out, s_out,
                 z_out, sbuf, zbuf, tr, kt, phik, rsem, wsem, unsent, *,
                 tiles: int, entries: int, state_round: str, mm):
    """Grid (B, Hkv, tiles), in order. A live row's state tile comes by
    DMAs that the live step two before it started (`STEP_SLOTS` buffers,
    so two tiles are on their way while one is read), and goes back by
    DMAs only where the row folds, waited for when its buffer is next
    wanted; an idle row moves nothing. `entries`: how many ring entries
    a row can hold."""
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hkv = pl.num_programs(1)
    d, dt = sbuf.shape[1:]
    layer = meta_ref[0]
    live = row_ref[_BLOCK, i] != 0

    def tile(pool, row, head, at):
        cols = pl.ds(pl.multiple_of(at * dt, LANES), dt)
        return pool.at[layer, row_ref[_BLOCK, row], head, :, cols]

    def copies(row, head, at, slot, back=False):
        """A tile's two DMAs: in from the pool as it came, or back into
        the pool as it leaves (the same buffers on the chip)."""
        if back:
            pairs = [(sbuf.at[slot], tile(s_out, row, head, at)),
                     (zbuf.at[slot], tile(z_out, row, head, at))]
        else:
            pairs = [(tile(s_hbm, row, head, at), sbuf.at[slot]),
                     (tile(z_hbm, row, head, at), zbuf.at[slot])]
        sem = (wsem if back else rsem).at[slot]
        return [pltpu.make_async_copy(src, dst, sem) for src, dst in pairs]

    def following(row, head, at):
        """The live step after a live step (row -1: none): the row's next
        tile, its next head, or the next live row's first."""
        head_n = jnp.where(at + 1 == tiles, head + 1, head)
        at_n = jnp.where(at + 1 == tiles, 0, at + 1)
        row_n = jnp.where(head_n == hkv,
                          row_ref[_NEXT, jnp.maximum(row, 0)], row)
        return (jnp.where(row < 0, -1, row_n),
                jnp.where(head_n == hkv, 0, head_n), at_n)

    def sent(slot):
        """A buffer is free once the tile a fold sent back from it has
        landed."""
        @pl.when(unsent[slot] != 0)
        def _land():
            for cp in copies(i, j, t, slot, back=True):
                cp.wait()
            unsent[slot] = 0

    @pl.when(jnp.logical_not(live))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        ring_out[...] = ring_ref[...]

    @pl.when(live)
    def _live():
        turn = (row_ref[_ORD, i] * hkv + j) * tiles + t
        slot = turn % STEP_SLOTS
        ahead = [(i, j, t)]
        for _ in range(STEP_SLOTS - 1):
            ahead.append(following(*ahead[-1]))

        @pl.when(turn == 0)
        def _first():
            for n in range(STEP_SLOTS):
                unsent[n] = 0
            for n, (row, head, at) in enumerate(ahead[:-1]):
                @pl.when(row >= 0)
                def _start():
                    for cp in copies(row, head, at, n):
                        cp.start()

        row_n, head_n, at_n = ahead[-1]

        @pl.when(row_n >= 0)
        def _prefetch():
            into = (turn + STEP_SLOTS - 1) % STEP_SLOTS
            sent(into)
            for cp in copies(row_n, head_n, at_n, into):
                cp.start()

        # the ring with this step's token in it, and each entry's decay
        # from its position to this one
        held = row_ref[_HELD, i]
        put = jax.lax.broadcasted_iota(jnp.int32, ring_ref.shape[-2:],
                                       0) == held
        now = now_ref[0, 0]
        kr = jnp.where(put, now[0:1], ring_ref[0, 0, 0, 0])
        vr = jnp.where(put, now[1:2], ring_ref[0, 0, 0, 1])
        gr = jnp.where(put, now[2:3], ring_ref[0, 0, 0, 2])
        ring_out[0, 0, 0, 0] = kr
        ring_out[0, 0, 0, 1] = vr
        ring_out[0, 0, 0, 2] = gr
        after = jnp.zeros((1, d), jnp.float32)
        decay = [None] * entries
        for r in reversed(range(entries)):
            decay[r] = jnp.where(r <= held, jnp.exp(after), 0.0)
            after = after + jnp.where(r <= held, gr[r:r + 1], 0.0)
        carried = jnp.exp(after)                             # G, [1, d]

        q = q_ref[0, 0]                                      # [rows, d]
        num = jnp.zeros(q.shape, jnp.float32)
        den = jnp.zeros((q.shape[0], 1), jnp.float32)
        for r in range(entries):
            score = jnp.sum(q * kr[r:r + 1], axis=-1, keepdims=True)
            w = score * score * decay[r][:, 0:1]
            num = num + w * vr[r:r + 1]
            den = den + w

        @pl.when(t == 0)
        def _first_tile():
            o_ref[0, 0] = num
            den_ref[0, 0] = jnp.broadcast_to(den, den_ref.shape[2:])

        for cp in copies(i, j, t, slot):
            cp.wait()
        s, z = sbuf[slot], zbuf[slot]
        fq = fq_ref[0, 0]                                    # [rows, Dt], mm
        high = s.astype(mm)
        low = (s - high.astype(jnp.float32)).astype(mm)
        nt = (((1,), (1,)), ((), ()))
        read = (jax.lax.dot_general(fq, high, nt,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(fq, low, nt,
                                      preferred_element_type=jnp.float32))
        o_ref[0, 0] += carried * read
        den_ref[0, 0] += jnp.broadcast_to(
            carried[:, 0:1] * jnp.sum(fq.astype(jnp.float32) * z, axis=-1,
                                      keepdims=True), den_ref.shape[2:])

        @pl.when(row_ref[_FOLD, i] != 0)
        def _fold():
            # S <- G S + sum_r (c_r v_r) phi(k_r)^T as one pass of the
            # MXU that loses nothing against float32: each operand is
            # the sum of three bfloat16 parts, and the six products of
            # parts that float32 can tell apart lie side by side along
            # the contraction, the ring's entries six times on the lanes
            def on_lanes(x):
                """x [entries, d] -> [d, LANES]: x's rows on the lanes,
                `_PARTS` times side by side, zeros past them."""
                tr[...] = jnp.zeros_like(tr)
                for n in range(len(_PARTS)):
                    tr[n * entries:(n + 1) * entries] = x
                return tr[...].T

            def parts(x, which):
                """f32 x [N, LANES] -> bfloat16: in lane group n its high
                (0), middle (1) or low (2) part, as `which[n]` says."""
                high = x.astype(jnp.bfloat16)
                rest = x - high.astype(jnp.float32)
                mid = rest.astype(jnp.bfloat16)
                low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
                group = jax.lax.broadcasted_iota(
                    jnp.int32, (1, LANES), 1) // entries
                pick = lambda p: functools.reduce(
                    jnp.logical_or, [group == n for n in range(len(which))
                                     if which[n] == p])
                return jnp.where(pick(0), high, jnp.where(pick(1), mid, low))

            at = jax.lax.broadcasted_iota(jnp.int32, (entries, d), 0)
            weight = jnp.zeros((entries, d), jnp.float32)
            for r in range(entries):
                weight = jnp.where(at == r, decay[r], weight)
            kt[0] = on_lanes(kr[:entries])
            per_tile, pair = dt // (TILE * TILE), TILE * TILE

            def phi_of_pair(p, _):      # rolled: a tile's pairs are many
                rows = pl.ds(pl.multiple_of(p * pair, pair), pair)
                _make_phi(pa_ref, pb_ref, t * per_tile + p, 1, kt,
                          phik.at[rows])
                return _

            jax.lax.fori_loop(0, per_tile, phi_of_pair, 0)
            # the values' rows, then the normaliser's (weights alone)
            grown = jax.lax.dot_general(
                parts(jnp.concatenate([on_lanes(weight * vr[:entries]),
                                       on_lanes(weight)[:PAD_ROWS]], axis=0),
                      [p for p, _ in _PARTS]),
                parts(phik[...], [p for _, p in _PARTS]), nt,
                preferred_element_type=jnp.float32)
            kept = jnp.concatenate([carried] * (dt // d), axis=1)
            sbuf[slot] = _rounded(kept * s + grown[:d], state_round)
            zbuf[slot] = _rounded(kept * z + grown[d:d + 1], state_round)
            for cp in copies(i, j, t, slot, back=True):
                cp.start()
            unsent[slot] = 1

        @pl.when(ahead[1][0] < 0)
        def _last():
            for n in range(STEP_SLOTS):
                sent(n)


@functools.partial(jax.jit, static_argnames=(
    "eps", "entries", "state_round", "tiles", "mm", "interpret"))
def _step_pallas(q, k, v, logg, pool_s, pool_z, pool_ring, layer, blocks,
                 held, fold, *, eps, entries, state_round, tiles, mm,
                 interpret):
    """Jitted, and the layer an argument of it: a decode program's layers
    are one trace and one lowering of the kernel, not one each (a second
    and a half a layer on every start of a process otherwise)."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    rows = -(-group // 8) * 8
    big = pool_s.shape[-1]
    dt_size = big // tiles
    f32 = jnp.float32
    qg = jnp.pad(q.reshape(b, hkv, group, d),
                 ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    fq, qg = phi_selected(qg, mm), qg.astype(f32)
    now = jnp.stack([k.astype(f32), v.astype(f32),
                     jnp.broadcast_to(logg.astype(f32)[..., None],
                                      (b, hkv, d))], axis=2)
    now = jnp.pad(now, ((0, 0), (0, 0), (0, PAD_ROWS - 3), (0, 0)))
    live = blocks != 0
    at = jnp.arange(b, dtype=jnp.int32)
    # the next live row after each, -1 after the last
    later = jnp.concatenate([jnp.where(live, at, b)[1:],
                             jnp.full((1,), b, jnp.int32)])
    nxt = jax.lax.cummin(later, reverse=True)
    per_row = jnp.stack([
        blocks, held, fold.astype(jnp.int32), jnp.where(nxt < b, nxt, -1),
        jnp.cumsum(live) - live]).astype(jnp.int32)
    meta = jnp.asarray(layer, jnp.int32)[None]
    pa, pb = tile_pairs(d)

    def row(r, w, tiled=False):
        return pl.BlockSpec(
            (1, 1, r, w),
            lambda i, j, t, *_: (i, j, 0, t if tiled else 0))

    ring = pl.BlockSpec(
        (1, 1, 1) + pool_ring.shape[3:],
        lambda i, j, t, per_row, meta, *_: (meta[0], per_row[_BLOCK, i], j,
                                            0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, tiles),
        in_specs=[row(rows, dt_size, True), row(rows, d), row(PAD_ROWS, d),
                  ring, hbm, hbm],
        out_specs=[row(rows, d), row(rows, LANES), ring, hbm, hbm],
        scratch_shapes=[pltpu.VMEM((STEP_SLOTS, d, dt_size), f32),
                        pltpu.VMEM((STEP_SLOTS, 1, dt_size), f32),
                        pltpu.VMEM((LANES, d), f32),
                        pltpu.VMEM((1, d, LANES), f32),
                        pltpu.VMEM((dt_size, LANES), f32),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SMEM((STEP_SLOTS,), jnp.int32)],
    )
    with jax.named_scope(RETENTION_STEP):
        num, den, pool_ring, pool_s, pool_z = pl.pallas_call(
            functools.partial(_step_kernel, tiles=tiles, entries=entries,
                              state_round=state_round, mm=mm),
            name=RETENTION_STEP,
            out_shape=[
                jax.ShapeDtypeStruct((b, hkv, rows, d), f32),
                jax.ShapeDtypeStruct((b, hkv, rows, LANES), f32),
                jax.ShapeDtypeStruct(pool_ring.shape, pool_ring.dtype),
                jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype)],
            grid_spec=grid_spec,
            # operands count the four prefetched ones
            input_output_aliases={7: 2, 8: 3, 9: 4},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(per_row, meta, jnp.asarray(pa), jnp.asarray(pb), fq, qg, now,
          pool_ring, pool_s, pool_z)
    o = num[:, :, :group] / (den[:, :, :group, 0:1] + d * eps)
    return o.reshape(b, hq, d), pool_s, pool_z, pool_ring


def ring_entries(state_round: str) -> int:
    """Tokens a row's ring takes before it folds: `RING`, and one under
    the benchmark's control, whose state is rounded at every token."""
    return RING if state_round == "none" else 1


def ring_after(blocks, held, state_round: str = "none"):
    """What a decode step does to its rows' rings: blocks [B] (0: an idle
    row), held [B] the entries each ring holds before the step -> (fold
    [B] bool: the row's ring is full with this step's token and goes into
    its state, held [B] after the step)."""
    live = blocks != 0
    fold = live & (held + 1 >= ring_entries(state_round))
    return fold, jnp.where(live, jnp.where(fold, 0, held + 1), held)


def retention_step(q, k, v, logg, pool_s, pool_z, pool_ring, layer, blocks,
                   held, *, eps: float, state_round: str = "none",
                   impl: str = "auto"):
    """One decode position of B sequences through one layer's retention.

    q [B, Hq, d]; k, v [B, Hkv, d]; logg [B, Hkv] float32; pool_s, pool_z
    as `retention_chunk` takes them; pool_ring [L, blocks, Hkv, 3, RING,
    d] float32; blocks [B] int32: each row's state (idle rows: 0, the
    trash block, of which they move nothing); held [B] int32: the entries
    each row's ring holds before this step (`ring_after` says which rows
    fold and what they hold after it).
    -> (o [B, Hq, d] float32, pool_s, pool_z, pool_ring)."""
    d = q.shape[-1]
    blocks = jnp.asarray(blocks, jnp.int32)
    held = jnp.asarray(held, jnp.int32)
    fold, _ = ring_after(blocks, held, state_round)
    entries = ring_entries(state_round)
    if resolve_impl(impl) == "pallas":
        tiles, why = step_plan(d)
        if tiles is not None:
            return _step_pallas(q, k, v, logg, pool_s, pool_z, pool_ring,
                                layer, blocks, held, fold, eps=eps,
                                entries=entries, state_round=state_round,
                                tiles=tiles, mm=MM_DTYPE,
                                interpret=backend.interpret())
        backend.note_fallback(RETENTION_STEP, why)
    o, s, z, ring = _step_plain(
        q, k, v, logg, pool_s[layer, blocks], pool_z[layer, blocks],
        pool_ring[layer, blocks], held, fold, blocks != 0, eps=eps,
        entries=entries, state_round=state_round)
    return (o, pool_s.at[layer, blocks].set(s),
            pool_z.at[layer, blocks].set(z),
            pool_ring.at[layer, blocks].set(ring))
