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

**The state as stored**: `[L, blocks, H / 2, N, 2 P]` float32, a block
one sequence's state and block 0 the engine's trash block
(`ops/power_retention.py`'s conventions). Two heads of one group lie
side by side on the lanes, each transposed: `pool[l, b, i, n, j * P + p]
= S_{2 i + j}[p, n]`. At P = 64 that is one lane tile, so a token's
`x` of a head pair is one row as the projection left it, `B` and `C` are
columns shared by the pair, `y` comes out as a row, and every matmul of
the chunk form is 128 wide. Both kernels take the whole pool, are told
layer and block through scalar prefetch, and write the block in place
(`input_output_aliases`).

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

A program is one head pair; `C B^T` is made once a group and kept in
VMEM while the group's pairs follow one another. Every exponent is a
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
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # [a, c] x [b, c] -> [a, b]
_TN = (((0,), (0,)), ((), ()))      # [c, a] x [c, b] -> [a, b]


def _rounded(x, state_round: str):
    """A state as it is kept (`state_round`: the benchmark's control
    rounds it to bfloat16 at every write, and keeps float32 bytes)."""
    if state_round == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def to_pairs(s):
    """States by head `[..., H, P, N]` -> as stored `[..., H / 2, N, 2 P]`."""
    *lead, h, p, n = s.shape
    return jnp.moveaxis(s.reshape(*lead, h // 2, 2, p, n), -1, -3).reshape(
        *lead, h // 2, n, 2 * p)


def to_heads(s):
    """As stored `[..., H / 2, N, 2 P]` -> by head `[..., H, P, N]`."""
    *lead, hp, n, pp = s.shape
    return jnp.moveaxis(s.reshape(*lead, hp, n, 2, pp // 2), -3, -1).reshape(
        *lead, 2 * hp, pp // 2, n)


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
    if 2 * p != LANES or n != LANES:
        return (f"a head pair's state of {n} x {2 * p} is not one lane tile "
                f"square ({LANES} x {LANES})")
    if heads % groups or (heads // groups) % 2:
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
    n = bc.shape[1]
    # B and C as columns: one square transpose a group
    cols = jnp.concatenate(
        [bc, jnp.zeros((n - ROWS, n), jnp.float32)], axis=0).T
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for i in range(pairs):
        xd = rows_ref[0, 0, pl.ds(i, 1), :]                  # [1, 2 P]
        decay = rows_ref[0, 0, pl.ds(pairs + i, 1), :]
        s = _rounded(s_ref[0, 0, i] * decay + b_col * xd, state_round)
        s_out[0, 0, i] = s
        y_ref[0, 0, pl.ds(i, 1), :] = jnp.sum(s * c_col, axis=0,
                                              keepdims=True)


def _step_pallas(x, dt, a, b, c, pool, layer, blocks, *, state_round):
    nb, h, p = x.shape
    g, n = b.shape[1:]
    pairs = h // g // 2                 # head pairs a group
    f32 = jnp.float32
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(nb, g, pairs, 2 * p)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (nb, h, p)).reshape(nb, g, pairs, 2 * p)
    rows = jnp.concatenate([xd, decay], axis=2)              # [B, G, 2 pairs, 2P]
    bc = jnp.pad(jnp.stack([b.astype(f32), c.astype(f32)], axis=2),
                 ((0, 0), (0, 0), (0, ROWS - 2), (0, 0)))    # [B, G, ROWS, N]

    def state():
        return pl.BlockSpec(
            (1, 1, pairs, n, 2 * p),
            lambda i, j, blocks, meta: (meta[0], blocks[i], j, 0, 0))

    def mine(rows_):
        return pl.BlockSpec((1, 1, rows_, LANES),
                            lambda i, j, *_: (i, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, g),
        in_specs=[mine(2 * pairs), mine(ROWS), state()],
        out_specs=[mine(pairs), state()],
    )
    with jax.named_scope(MAMBA2_STEP):
        y, pool = pl.pallas_call(
            functools.partial(_step_kernel, pairs=pairs,
                              state_round=state_round),
            name=MAMBA2_STEP,
            out_shape=[jax.ShapeDtypeStruct((nb, g, pairs, 2 * p), f32),
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
    [B, G, N]; pool [L, blocks, H / 2, N, 2 P] float32; blocks [B] int32:
    each row's state (idle rows: 0, the trash block).
    -> (y [B, H, P] float32, pool)."""
    h, p = x.shape[1:]
    if resolve_impl(impl) == "pallas":
        why = plan(h, b.shape[1], p, b.shape[2])
        if not why:
            return _step_pallas(x, dt, a, b, c, pool, layer, blocks,
                                state_round=state_round)
        backend.note_fallback(MAMBA2_STEP, why)
    y, s = _step_plain(x, dt, b, c, a, to_heads(pool[layer, blocks]),
                       state_round=state_round)
    return y, pool.at[layer, blocks].set(to_pairs(s))


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------

def _chunk_kernel(meta_ref, b_ref, c_ref, xd_ref, cum_ref, row_ref, s_ref,
                  y_ref, s_out, cb, *, subs: int, state_round: str):
    f32 = jnp.float32
    half = LANES // 2

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
        cum = cum_ref[0, rows, :]                            # [SUB, 2 P]
        total = cum[SUB - 1:SUB, :]
        xd = xd_ref[0, rows, :].astype(f32)
        high = s.astype(MM_DTYPE)
        low = (s - high.astype(f32)).astype(MM_DTYPE)
        cj = c_ref[0, rows, :]
        y = jnp.exp(cum) * (jnp.dot(cj, high, preferred_element_type=f32)
                            + jnp.dot(cj, low, preferred_element_type=f32))
        for head in range(2):
            col = cum[:, head * half:head * half + 1]        # [SUB, 1]
            row = row_ref[0, pl.ds(head, 1), rows]           # [1, SUB]
            lower = jnp.exp(jnp.where(t_idx >= s_idx, col - row, NEVER))
            mine = (lane >= half) if head else (lane < half)
            y += jnp.dot((lower * cb[j]).astype(MM_DTYPE),
                         jnp.where(mine, xd, 0.0).astype(MM_DTYPE),
                         preferred_element_type=f32)
        y_ref[0, rows, :] = y
        s = jnp.exp(total) * s + jax.lax.dot_general(
            b_ref[0, rows, :], (xd * jnp.exp(total - cum)).astype(MM_DTYPE),
            _TN, preferred_element_type=f32)
    s_out[0, 0, 0] = _rounded(s, state_round)


def _chunk_pallas(x, dt, a, b, c, pool, layer, block, first, *, state_round):
    n_c, h, p = x.shape
    g, n = b.shape[1:]
    pairs = h // g // 2
    subs = n_c // SUB
    mm, f32 = MM_DTYPE, jnp.float32

    def by_pair(v):                     # [C, H, P] -> [H / 2, C, 2 P]
        return v.reshape(n_c, h // 2, 2 * p).swapaxes(0, 1)

    cum = jnp.cumsum((dt * a).reshape(subs, SUB, h), axis=1).reshape(n_c, h)
    xd = by_pair(x.astype(f32) * dt[..., None]).astype(mm)
    cum_lanes = by_pair(jnp.broadcast_to(cum[..., None], (n_c, h, p)))
    cum_rows = cum.T.reshape(h // 2, 2, n_c)
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
            (1, 1, 1, n, 2 * p),
            lambda i, j, meta: (meta[0], meta[1], i * pairs + j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, pairs),
        in_specs=[group(n_c, n), group(n_c, n), pair(n_c, 2 * p),
                  pair(n_c, 2 * p), pair(2, n_c), state()],
        out_specs=[pair(n_c, 2 * p), state()],
        scratch_shapes=[pltpu.VMEM((subs, SUB, SUB), f32)],
    )
    with jax.named_scope(MAMBA2_CHUNK):
        y, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, subs=subs,
                              state_round=state_round),
            name=MAMBA2_CHUNK,
            out_shape=[jax.ShapeDtypeStruct((h // 2, n_c, 2 * p), f32),
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
    [C, G, N]; pool [L, blocks, H / 2, N, 2 P] float32; layer, block:
    which state; first: the sequence's first chunk (the block is read as
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
        jnp.where(first, 0.0, to_heads(pool[layer, block])),
        state_round=state_round)
    return y[:n_c], pool.at[layer, block].set(to_pairs(s))
