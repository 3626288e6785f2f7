"""The selective state-space recurrence of Mamba-2 (state-space duality,
arXiv:2405.21060) over a state of fixed size a sequence: a scalar decay a
head, no delta rule, `B` and `C` shared by the heads of a group.

One head of one layer keeps `S [P, N]` (P the head's channels, N the
state size), float32, and a token moves it

    a_t = exp(d_t A_h)            d_t > 0 the step (softplus), A_h < 0
    S_t = a_t S_{t-1} + d_t x_t B_t^T
    y_t = S_t C_t

with `x_t [P]` the head's input and `B_t`, `C_t [N]` those of the head's
group (head `h` reads group `h // (H / G)`). The skip `D_h x_t`, the
gate and the grouped norm are the caller's.

**The state as stored**: `[L, blocks, H / t, N, t P]` float32, a block
one sequence's state and block 0 the engine's trash block
(`ops/power_retention.py`'s conventions). `t = tile_heads(P)` heads of
one group lie side by side on the lanes, each transposed: `pool[l, b, i,
n, j * P + p] = S_{t i + j}[p, n]`; two below a lane tile (a pair: at P =
64 one lane tile), one where a head fills the lanes alone (P = 128). So a
token's `x` of a tile's heads is one row as the projection left it, `B`
and `C` are columns the tile shares, `y` comes out as a row, and every
matmul of the chunk form is 128 wide; N counts the tile's rows (one
lane tile square at 128, two tiles tall at 256). Both kernels take the
whole pool, are told layer and block through scalar prefetch, and write
the block in place (`input_output_aliases`). They have plans for P x N
of 64 x 128 and 128 x 256 (`plan`).

`mamba2_step` is decode's: one position of each of B sequences, each
against its own block, float32 on the vector unit (a step is bound by the
state's bytes: it is read once and written once). A program is one
sequence's one group; idle rows name block 0 and rewrite it among
themselves.

`mamba2_chunk` is prefill's: C positions of one sequence in sub-blocks of
`SUB` (128, the published `chunk_size`). With `c_t` the running sum of
`d A` inside a sub-block (inclusive), the same sum reordered:

    Y = exp(c) * (C S_0)  +  (L * (C B^T)) (d x),   L[t, s] = exp(c_t - c_s), s <= t
    S_n = exp(c_n) S_0 + B^T (exp(c_n - c) * d x)

A program is one lane tile of heads; `C B^T` is made once a group and
kept in VMEM while the group's tiles follow one another. Every exponent is a
difference taken forward in time, so no factor passes 1 however fast a
head forgets. Matmul operands are bfloat16 with float32 accumulation; the
state is read as a high and a low bfloat16 part and updated in float32.
Rows at and past `length` (a chunk bucket's padding) carry d = 0: they
decay nothing, add nothing and leave the state bit for bit; `first`
reads the block as zeros, whatever a freed block still holds.

Each has a plain `jax.numpy` path behind `impl` (float32 at the highest
matmul precision), which the CPU tests compare with the kernel in
interpret mode and with the token-by-token definition
(`mamba2_recurrent`). Why a file of its own and not `ops/kda.py` widened:
nothing a plan depends on is shared (a scalar decay where KDA has one a
channel, no triangular solve, grouped `B` / `C`, a state of P x N that is
not square, sub-blocks of 128 where KDA's ratio bound holds it to 16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.ops.sparse_latent import resolve_impl

# Kernel names in the compiled program and the profiler's trace; PERF.md,
# section 3, lists them. Each call sits in a `named_scope` of its name.
MAMBA2_STEP, MAMBA2_CHUNK = "mamba2_step", "mamba2_chunk"

SUB = 128                   # positions a sub-block (`chunk_size`)
LANES = 128
ROWS = 8                    # sublanes of a float32 tile
VMEM_LIMIT = 64 * 1024 * 1024
MM_DTYPE = jnp.bfloat16     # what the chunk kernel feeds the MXU
NEVER = -1e30               # an exponent that reads as a factor of 0
# head widths P x state sizes N the kernels have plans for: a pair of
# heads a lane tile over one tile of rows, one head a lane tile over two
PLANNED = ((64, 128), (128, 256))
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # [a, c] x [b, c] -> [a, b]
_TN = (((0,), (0,)), ((), ()))      # [c, a] x [c, b] -> [a, b]


def _rounded(x, state_round: str):
    """A state as it is kept (`state_round`: the benchmark's control
    rounds it to bfloat16 at every write, and keeps float32 bytes)."""
    if state_round == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def tile_heads(p: int) -> int:
    """Heads side by side on a row of lanes: a pair, or one where a head
    fills the lanes alone."""
    return 1 if p % LANES == 0 else 2


def to_pairs(s):
    """States by head `[..., H, P, N]` -> as stored `[..., H / t, N, t P]`,
    `t = tile_heads(P)`."""
    *lead, h, p, n = s.shape
    t = tile_heads(p)
    return jnp.moveaxis(s.reshape(*lead, h // t, t, p, n), -1, -3).reshape(
        *lead, h // t, n, t * p)


def to_heads(s, p: int):
    """As stored `[..., H / t, N, t P]` -> by head `[..., H, P, N]`, given
    the head's P: the stored shape alone does not tell a pair of 64 from
    one head of 128."""
    *lead, ht, n, tp = s.shape
    t = tile_heads(p)
    return jnp.moveaxis(s.reshape(*lead, ht, n, t, tp // t), -3, -1).reshape(
        *lead, t * ht, tp // t, n)


def _by_head(a, heads: int):
    """A group's `B` or `C` `[..., G, N]` -> one a head `[..., H, N]`."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# plain paths
# ---------------------------------------------------------------------------

def mamba2_recurrent(x, dt, a, b, c, s0=None):
    """The definition, token by token: x [T, H, P]; dt [T, H] (> 0);
    a [H] (< 0); b, c [T, G, N] -> (y [T, H, P] float32, S [H, P, N])."""
    f32 = jnp.float32
    if s0 is None:
        s0 = jnp.zeros(x.shape[1:] + b.shape[-1:], f32)

    def step(s, row):
        y, s = _step_plain(*(r[None] for r in row), a, s[None],
                           state_round="none")
        return s[0], y[0]

    s, y = jax.lax.scan(step, s0.astype(f32), (
        x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32)))
    return y, s


def _step_plain(x, dt, b, c, a, s, *, state_round):
    """One position of B sequences against their states s [B, H, P, N]
    (by head): -> (y [B, H, P], s)."""
    f32 = jnp.float32
    h = x.shape[1]
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))[..., None, None]
    xd = x.astype(f32) * dt[..., None]
    s = _rounded(decay * s + xd[..., None]
                 * _by_head(b.astype(f32), h)[..., None, :], state_round)
    return jnp.einsum("bhpn,bhn->bhp", s, _by_head(c.astype(f32), h),
                      precision=_HIGHEST), s


def _chunk_plain(x, dt, a, b, c, s, *, state_round):
    """The sub-blocks in order against one block's state s [H, P, N] (by
    head), float32 throughout; x [C, H, P], dt [C, H] (0 on padding),
    b, c [C, G, N], C a multiple of `SUB`: -> (y [C, H, P], s)."""
    f32 = jnp.float32
    n_c, h, p = x.shape
    sub, n = SUB, n_c // SUB
    la = (dt * a).reshape(n, sub, h)
    cum = jnp.cumsum(la, axis=1)                             # [n, T, H]
    xd = (x.astype(f32) * dt[..., None]).reshape(n, sub, h, p)
    bh = _by_head(b.astype(f32), h).reshape(n, sub, h, -1)
    ch = _by_head(c.astype(f32), h).reshape(n, sub, h, -1)
    tri = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]

    def block(s, part):
        cum, xd, bh, ch = part
        lower = jnp.exp(jnp.where(
            tri[..., None], cum[:, None] - cum[None, :], NEVER))  # [T, S, H]
        scores = jnp.einsum("thn,shn->tsh", ch, bh, precision=_HIGHEST)
        y = (jnp.exp(cum)[..., None] * jnp.einsum(
            "thn,hpn->thp", ch, s, precision=_HIGHEST)
            + jnp.einsum("tsh,shp->thp", lower * scores, xd,
                         precision=_HIGHEST))
        total = cum[-1]                                      # [H]
        s = (jnp.exp(total)[:, None, None] * s + jnp.einsum(
            "shp,shn->hpn", xd * jnp.exp(total - cum)[..., None], bh,
            precision=_HIGHEST))
        return s, y

    s, y = jax.lax.scan(block, s, (cum, xd, bh, ch))
    return y.reshape(n_c, h, p), _rounded(s, state_round)


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

def plan(heads: int, groups: int, p: int, n: int, c: int = SUB):
    """"" where the kernels have a plan for these widths (and, for the
    chunk kernel, a chunk of `c` positions), else why not."""
    if (p, n) not in PLANNED:
        return (f"a head's state of {p} x {n} is none of those the kernels "
                f"have plans for ({', '.join(f'{a} x {b}' for a, b in PLANNED)}"
                f": whole lane tiles)")
    if heads % groups or (heads // groups) % tile_heads(p):
        return f"{heads} heads do not lie in pairs inside {groups} groups"
    if c % SUB:
        return f"a chunk of {c} positions is not whole sub-blocks of {SUB}"
    return ""


# ---------------------------------------------------------------------------
# the step kernel
# ---------------------------------------------------------------------------

def _step_kernel(blocks_ref, meta_ref, rows_ref, bc_ref, s_ref, y_ref, s_out,
                 *, pairs: int, state_round: str):
    del blocks_ref, meta_ref
    bc = bc_ref[0, 0]                                        # [ROWS, N]
    # B and C as columns: one square transpose a lane tile of N, a group
    cols = jnp.concatenate([
        jnp.concatenate([bc[:, i:i + LANES],
                         jnp.zeros((LANES - ROWS, LANES), jnp.float32)],
                        axis=0).T
        for i in range(0, bc.shape[1], LANES)], axis=0)
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for i in range(pairs):
        xd = rows_ref[0, 0, pl.ds(i, 1), :]                  # [1, LANES]
        decay = rows_ref[0, 0, pl.ds(pairs + i, 1), :]
        s = _rounded(s_ref[0, 0, i] * decay + b_col * xd, state_round)
        s_out[0, 0, i] = s
        y_ref[0, 0, pl.ds(i, 1), :] = jnp.sum(s * c_col, axis=0,
                                              keepdims=True)


def _step_pallas(x, dt, a, b, c, pool, layer, blocks, *, state_round):
    nb, h, p = x.shape
    g, n = b.shape[1:]
    pairs = h // g // tile_heads(p)     # lane tiles of heads a group
    f32 = jnp.float32
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(nb, g, pairs, LANES)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (nb, h, p)).reshape(nb, g, pairs, LANES)
    rows = jnp.concatenate([xd, decay], axis=2)              # [B, G, 2 pairs, LANES]
    bc = jnp.pad(jnp.stack([b.astype(f32), c.astype(f32)], axis=2),
                 ((0, 0), (0, 0), (0, ROWS - 2), (0, 0)))    # [B, G, ROWS, N]

    def state():
        return pl.BlockSpec(
            (1, 1, pairs, n, LANES),
            lambda i, j, blocks, meta: (meta[0], blocks[i], j, 0, 0))

    def mine(rows_, lanes=LANES):
        return pl.BlockSpec((1, 1, rows_, lanes),
                            lambda i, j, *_: (i, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, g),
        in_specs=[mine(2 * pairs), mine(ROWS, n), state()],
        out_specs=[mine(pairs), state()],
    )
    with jax.named_scope(MAMBA2_STEP):
        y, pool = pl.pallas_call(
            functools.partial(_step_kernel, pairs=pairs,
                              state_round=state_round),
            name=MAMBA2_STEP,
            out_shape=[jax.ShapeDtypeStruct((nb, g, pairs, LANES), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the two prefetched ones
            input_output_aliases={4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(jnp.asarray(blocks, jnp.int32), jnp.asarray(layer, jnp.int32)[None],
          rows, bc, pool)
    return y.reshape(nb, h, p), pool


def mamba2_step(x, dt, a, b, c, pool, layer, blocks, *,
                state_round: str = "none", impl: str = "auto"):
    """One decode position of B sequences through one layer's recurrence.

    x [B, H, P]; dt [B, H] float32 (> 0); a [H] float32 (< 0); b, c
    [B, G, N]; pool [L, blocks, H / t, N, t P] float32 (`to_pairs`);
    blocks [B] int32: each row's state (idle rows: 0, the trash block).
    -> (y [B, H, P] float32, pool)."""
    h, p = x.shape[1:]
    if resolve_impl(impl) == "pallas":
        why = plan(h, b.shape[1], p, b.shape[2])
        if not why:
            return _step_pallas(x, dt, a, b, c, pool, layer, blocks,
                                state_round=state_round)
        backend.note_fallback(MAMBA2_STEP, why)
    y, s = _step_plain(x, dt, b, c, a,
                       to_heads(pool[layer, blocks], p),
                       state_round=state_round)
    return y, pool.at[layer, blocks].set(to_pairs(s))


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------

def _chunk_kernel(meta_ref, b_ref, c_ref, xd_ref, cum_ref, row_ref, s_ref,
                  y_ref, s_out, cb, *, subs: int, state_round: str):
    f32 = jnp.float32
    heads = row_ref.shape[1]            # of this program's lane tile
    half = LANES // heads

    @pl.when(pl.program_id(1) == 0)
    def _scores():                      # C B^T of the group's sub-blocks
        for j in range(subs):
            rows = slice(j * SUB, (j + 1) * SUB)
            cb[j] = jax.lax.dot_general(c_ref[0, rows, :], b_ref[0, rows, :],
                                        _NT, preferred_element_type=f32)

    s = jnp.where(meta_ref[2] > 0, 0.0, s_ref[0, 0, 0])     # a first chunk
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    for j in range(subs):
        rows = slice(j * SUB, (j + 1) * SUB)
        cum = cum_ref[0, rows, :]                            # [SUB, LANES]
        total = cum[SUB - 1:SUB, :]
        xd = xd_ref[0, rows, :].astype(f32)
        high = s.astype(MM_DTYPE)
        low = (s - high.astype(f32)).astype(MM_DTYPE)
        cj = c_ref[0, rows, :]
        y = jnp.exp(cum) * (jnp.dot(cj, high, preferred_element_type=f32)
                            + jnp.dot(cj, low, preferred_element_type=f32))
        for head in range(heads):
            col = cum[:, head * half:head * half + 1]        # [SUB, 1]
            row = row_ref[0, pl.ds(head, 1), rows]           # [1, SUB]
            lower = jnp.exp(jnp.where(t_idx >= s_idx, col - row, NEVER))
            mine = (lane >= half) if head else (lane < half)
            y += jnp.dot((lower * cb[j]).astype(MM_DTYPE),
                         (xd if heads == 1 else jnp.where(mine, xd, 0.0)
                          ).astype(MM_DTYPE), preferred_element_type=f32)
        y_ref[0, rows, :] = y
        s = jnp.exp(total) * s + jax.lax.dot_general(
            b_ref[0, rows, :], (xd * jnp.exp(total - cum)).astype(MM_DTYPE),
            _TN, preferred_element_type=f32)
    s_out[0, 0, 0] = _rounded(s, state_round)


def _chunk_pallas(x, dt, a, b, c, pool, layer, block, first, *, state_round):
    n_c, h, p = x.shape
    g, n = b.shape[1:]
    t = tile_heads(p)
    pairs = h // g // t                 # lane tiles of heads a group
    subs = n_c // SUB
    mm, f32 = MM_DTYPE, jnp.float32

    def by_pair(v):                     # [C, H, P] -> [H / t, C, LANES]
        return v.reshape(n_c, h // t, LANES).swapaxes(0, 1)

    cum = jnp.cumsum((dt * a).reshape(subs, SUB, h), axis=1).reshape(n_c, h)
    xd = by_pair(x.astype(f32) * dt[..., None]).astype(mm)
    cum_lanes = by_pair(jnp.broadcast_to(cum[..., None], (n_c, h, p)))
    cum_rows = cum.T.reshape(h // t, t, n_c)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(block, jnp.int32),
                      jnp.asarray(first, jnp.int32)])

    def group(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, j, meta: (i,) + (0,) * len(shape))

    def pair(*shape):
        return pl.BlockSpec(
            (1,) + shape,
            lambda i, j, meta: (i * pairs + j,) + (0,) * len(shape))

    def state():
        return pl.BlockSpec(
            (1, 1, 1, n, LANES),
            lambda i, j, meta: (meta[0], meta[1], i * pairs + j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, pairs),
        in_specs=[group(n_c, n), group(n_c, n), pair(n_c, LANES),
                  pair(n_c, LANES), pair(t, n_c), state()],
        out_specs=[pair(n_c, LANES), state()],
        scratch_shapes=[pltpu.VMEM((subs, SUB, SUB), f32)],
    )
    with jax.named_scope(MAMBA2_CHUNK):
        y, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, subs=subs,
                              state_round=state_round),
            name=MAMBA2_CHUNK,
            out_shape=[jax.ShapeDtypeStruct((h // t, n_c, LANES), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the prefetched one
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(meta, b.swapaxes(0, 1).astype(mm), c.swapaxes(0, 1).astype(mm),
          xd, cum_lanes, cum_rows, pool)
    return y.swapaxes(0, 1).reshape(n_c, h, p), pool


def mamba2_chunk(x, dt, a, b, c, pool, layer, block, first, length, *,
                 state_round: str = "none", impl: str = "auto"):
    """A prefill chunk of one sequence through one layer's recurrence.

    x [C, H, P]; dt [C, H] float32 (> 0); a [H] float32 (< 0); b, c
    [C, G, N]; pool [L, blocks, H / t, N, t P] float32 (`to_pairs`);
    layer, block: which state; first: the sequence's first chunk (the block is read as
    zeros); length: the chunk's live positions.
    -> (y [C, H, P] float32, pool)."""
    n_c, h, p = x.shape
    f32 = jnp.float32
    # padding takes no step: it decays nothing and adds nothing
    dt = jnp.where((jnp.arange(n_c) < length)[:, None], dt.astype(f32), 0.0)
    a = a.astype(f32)
    if resolve_impl(impl) == "pallas":
        why = plan(h, b.shape[1], p, b.shape[2], n_c)
        if not why:
            return _chunk_pallas(x, dt, a, b, c, pool, layer, block, first,
                                 state_round=state_round)
        backend.note_fallback(MAMBA2_CHUNK, why)
    pad = -n_c % SUB
    if pad:                 # whole sub-blocks, so that every bucket sums a
        # live position in one order; the tail is padding
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
    y, s = _chunk_plain(
        x, dt, a, b, c,
        jnp.where(first, 0.0, to_heads(pool[layer, block], p)),
        state_round=state_round)
    return y[:n_c], pool.at[layer, block].set(to_pairs(s))
