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

**The ring beside the state** (`ring_array`; a block's, as the state):
the decode tokens that are not in the state yet, oldest first, `RING` of
them at most: `ring [L, blocks, RING, rows, 128]` float32, an entry one
token's `d x` (a lane tile of heads a row, as the state's rows), its `B`
(a group after a group) and the running log-decay `l_s = sum d A` of each
head since the last fold (a head a lane, a tile's first heads then its
second: `_to_lanes`), each from a row of its own (`_entry_rows`); and how
many a block's ring holds (`held`, kept by the caller: all layers step
together). One array, not three: what is small enough the compiler
carries into VMEM around every call and back (AOT for a v5e, PR 56).
With `t0` the last fold and the ring holding tokens `t0 + 1 .. t`:

    S_t = exp(l_t) S_t0 + sum_s exp(l_t - l_s) (d_s x_s) B_s^T
    y_t = exp(l_t) (S_t0 C_t) + sum_s exp(l_t - l_s) (B_s . C_t) (d_s x_s)

the same sum reordered, every exponent a difference taken forward in
time. A row whose ring is full with this token **folds**: `S_t0 <- S_t`,
its ring is empty after and `l` starts again at 0.

`mamba2_step` is decode's: one position of each of B sequences, each
against its own block, float32 on the vector unit. The step's token goes
into its ring first. A step is bound by the state's bytes, so it reads
every decoding row's state once and writes only the folding rows': the
kernel holds the pool in HBM (`pl.ANY`), a program is one sequence, whose
state comes by a DMA that the live row `STEP_SLOTS - 1` before it
started, and goes back by a DMA only where the row folds, waited for when
its buffer is next wanted. The ring comes as a block and the step's
token goes back through an out block of its own (one entry, not the
ring). An idle row (block 0) moves nothing of the state and leaves the
trash block's ring as it was. Rows fold when their own
ring is full, so the traffic's staggered positions put about B / RING
folds in every step and every step is the same length. `RING` = 8: by
bytes a step moves `1 + 1 / RING + RING x entry / state` states where the
read-modify-write moved 2 (1.20 at 8 and 1.21 at 16 for 128 heads of 64 x
128, 1.16 and 1.13 for 32 heads of 128 x 256: level), 8 rows of float32
are one tile of sublanes, and the rings of 16 would not fit beside
`falcon-h1-34b`'s pool (PERF.md, PR 56). Under `state_round` (the
benchmark's control: the state rounded at every write) a ring holds one
token and every step folds, so that the control rounds at every token as
it did.

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
RING = 8                    # decode tokens a ring takes before it folds
STEP_SLOTS = 3              # buffers of the step kernel's states
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


def _tile_lanes(heads: int, p: int) -> int:
    """Whole lane tiles that hold one value a lane tile of heads."""
    return -(-(heads // tile_heads(p)) // LANES) * LANES


def _to_lanes(v, p: int):
    """One value a head `[..., H]` -> a head a lane as a ring's entry
    keeps its log-decays `[..., t x lanes]`: lane `j x lanes + i` is head
    `t i + j`, so that the tiles' j-th heads lie side by side (zeros past
    them)."""
    *lead, h = v.shape
    t, lanes = tile_heads(p), _tile_lanes(h, p)
    v = jnp.swapaxes(v.reshape(*lead, h // t, t), -1, -2)
    return jnp.pad(v, [(0, 0)] * (len(lead) + 1) + [(0, lanes - h // t)]
                   ).reshape(*lead, t * lanes)


def _from_lanes(v, heads: int, p: int):
    """`_to_lanes`' inverse: `[..., t x lanes]` -> `[..., H]`."""
    *lead, _ = v.shape
    t = tile_heads(p)
    v = v.reshape(*lead, t, -1)[..., :heads // t]
    return jnp.swapaxes(v, -1, -2).reshape(*lead, heads)


def _entry_rows(heads: int, groups: int, p: int, n: int):
    """Where a ring entry `[rows, LANES]` keeps a token: -> (the rows of
    its `d x`, of its `B`, of its log-decays, all of them: whole sublane
    tiles). Each part starts a row."""
    x, b = -(-heads * p // LANES), -(-groups * n // LANES)
    logs = tile_heads(p) * _tile_lanes(heads, p) // LANES
    return x, b, logs, -(-(x + b + logs) // ROWS) * ROWS


def _packed(xd, b, logs, p: int):
    """A token's `d x` [..., H, P], `B` [..., G, N] and log-decays
    [..., H] -> an entry [..., rows, LANES]."""
    lead = xd.shape[:-2]
    (h, _), (g, n) = xd.shape[-2:], b.shape[-2:]
    x_rows, b_rows, _, rows = _entry_rows(h, g, p, n)
    parts = [xd.reshape(*lead, -1), b.reshape(*lead, -1), _to_lanes(logs, p)]
    flat = jnp.concatenate([
        jnp.pad(v, [(0, 0)] * len(lead) + [(0, r * LANES - v.shape[-1])])
        for v, r in zip(parts, (x_rows, b_rows, rows - x_rows - b_rows))],
        axis=-1)
    return flat.reshape(*lead, rows, LANES)


def _unpacked(entry, heads: int, groups: int, p: int, n: int):
    """`_packed`'s inverse: entries [..., rows, LANES] -> (`d x` [..., H,
    P], `B` [..., G, N], log-decays [..., H])."""
    lead = entry.shape[:-2]
    x, b, logs, _ = _entry_rows(heads, groups, p, n)
    flat = entry.reshape(*lead, -1)
    return (flat[..., :heads * p].reshape(*lead, heads, p),
            flat[..., x * LANES:x * LANES + groups * n].reshape(
                *lead, groups, n),
            _from_lanes(flat[..., (x + b) * LANES:(x + b + logs) * LANES],
                        heads, p))


def ring_array(layers: int, blocks: int, heads: int, groups: int, p: int,
               n: int):
    """The rings beside `layers x blocks` states, empty: float32 [L,
    blocks, RING, rows, LANES], blocks on axis 1."""
    return jnp.zeros((layers, blocks, RING,
                      _entry_rows(heads, groups, p, n)[3], LANES),
                     jnp.float32)


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


# ---------------------------------------------------------------------------
# plain paths
# ---------------------------------------------------------------------------

def mamba2_recurrent(x, dt, a, b, c, s0=None):
    """The definition, token by token: x [T, H, P]; dt [T, H] (> 0);
    a [H] (< 0); b, c [T, G, N] -> (y [T, H, P] float32, S [H, P, N])."""
    f32 = jnp.float32
    h = x.shape[1]
    if s0 is None:
        s0 = jnp.zeros(x.shape[1:] + b.shape[-1:], f32)

    def step(s, row):
        x, dt, b, c = row
        s = jnp.exp(dt * a.astype(f32))[:, None, None] * s \
            + (x * dt[:, None])[..., None] * _by_head(b, h)[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, _by_head(c, h),
                             precision=_HIGHEST)

    s, y = jax.lax.scan(step, s0.astype(f32), (
        x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32)))
    return y, s


def _step_plain(x, dt, b, c, a, s, rx, rb, rl, held, fold, live, *, entries,
                state_round):
    """One position of B sequences against their states s [B, H, P, N] and
    rings rx [B, R, H, P], rb [B, R, G, N], rl [B, R, H] (`_unpacked`);
    held, fold, live [B] as `ring_after` has them: -> (y [B, H, P], s,
    rx, rb, rl)."""
    f32 = jnp.float32
    h = x.shape[1]
    at = jnp.arange(rx.shape[1])
    dt = dt.astype(f32)
    put = (at == held[:, None]) & live[:, None]              # [B, R]
    last = jnp.take_along_axis(
        rl, jnp.maximum(held - 1, 0)[:, None, None], axis=1)[:, 0]
    lt = jnp.where((held > 0)[:, None], last, 0.0) + dt * a.astype(f32)
    rx = jnp.where(put[..., None, None],
                   (x.astype(f32) * dt[..., None])[:, None], rx)
    rb = jnp.where(put[..., None, None], b.astype(f32)[:, None], rb)
    rl = jnp.where(put[..., None], lt[:, None], rl)
    # an entry's decay from its position to this one; 0 past the last held
    holds = (at[:entries] <= held[:, None])[..., None]       # [B, E, 1]
    z = jnp.exp(jnp.where(holds, lt[:, None] - rl[:, :entries], NEVER)
                )[..., None] * rx[:, :entries]               # [B, E, H, P]
    carried = jnp.exp(lt)[..., None]                         # [B, H, 1]
    bh = _by_head(rb[:, :entries], h)                        # [B, E, H, N]
    ch = _by_head(c.astype(f32), h)
    kept = carried * jnp.einsum("bhpn,bhn->bhp", s, ch, precision=_HIGHEST) \
        + jnp.einsum("behn,bhn,behp->bhp", bh, ch, z, precision=_HIGHEST)
    folded = _rounded(carried[..., None] * s + jnp.einsum(
        "behp,behn->bhpn", z, bh, precision=_HIGHEST), state_round)
    y = jnp.where(fold[:, None, None],
                  jnp.einsum("bhpn,bhn->bhp", folded, ch,
                             precision=_HIGHEST), kept)
    return (jnp.where(live[:, None, None], y, 0.0),
            jnp.where(fold[:, None, None, None], folded, s), rx, rb, rl)


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

# rows of the step kernel's per-row scalars (scalar prefetch, [5, B])
_BLOCK, _HELD, _FOLD, _NEXT, _ORD = range(5)


def _step_kernel(row_ref, meta_ref, now_ref, side_ref, ring_ref, s_hbm,
                 y_ref, entry_out, s_out, sbuf, zs, carried, ringy, c_cols,
                 b_cols, tr, rsem, wsem, unsent, *, pairs: int, tile: int,
                 entries: int, state_round: str):
    """Grid (B,), in order: a program is one sequence. A live row's state
    comes by a DMA that the live row `STEP_SLOTS - 1` before it started
    and goes back by a DMA only where the row folds, waited for when its
    buffer is next wanted; an idle row moves nothing. `now_ref`: the
    step's `d x` as an entry keeps it; `side_ref`: its `B`, its `d A` (a
    head a lane) and its `C`, in rows; `entries`: how many ring entries a
    row can hold."""
    f32 = jnp.float32
    i = pl.program_id(0)
    layer = meta_ref[0]
    ring, rows = ring_ref.shape[2:4]    # entries a ring; rows an entry
    tiles, n = sbuf.shape[1:3]          # lane tiles of heads; state size
    groups, nk = tiles // pairs, n // LANES
    nb = groups * nk                    # rows of B, and of C
    nl = tile * (-(-tiles // LANES))    # rows of log-decays, a head a lane
    lo_b, lo_l = tiles, tiles + nb      # where an entry keeps them
    held = row_ref[_HELD, i]
    live = row_ref[_BLOCK, i] != 0

    def copy(row, slot, back=False):
        """A row's state: in from the pool as it came, or back into the
        pool as it leaves (the same buffer on the chip)."""
        block = row_ref[_BLOCK, jnp.maximum(row, 0)]
        if back:
            return pltpu.make_async_copy(
                sbuf.at[slot], s_out.at[layer, block], wsem.at[slot])
        return pltpu.make_async_copy(
            s_hbm.at[layer, block], sbuf.at[slot], rsem.at[slot])

    def sent(slot):
        """A buffer is free once the state a fold sent back from it has
        landed."""
        @pl.when(unsent[slot] != 0)
        def _land():
            copy(i, slot, back=True).wait()
            unsent[slot] = 0

    @pl.when(jnp.logical_not(live))
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)
        entry_out[0, 0, 0] = ring_ref[0, 0, held]

    @pl.when(live)
    def _live():
        turn = row_ref[_ORD, i]
        slot = turn % STEP_SLOTS
        ahead = [i]                     # this live row and those after it
        for _ in range(STEP_SLOTS - 1):
            ahead.append(jnp.where(
                ahead[-1] < 0, -1,
                row_ref[_NEXT, jnp.maximum(ahead[-1], 0)]))

        @pl.when(turn == 0)
        def _first():
            for k in range(STEP_SLOTS):
                unsent[k] = 0
            for k, row in enumerate(ahead[:-1]):
                @pl.when(row >= 0)
                def _start():
                    copy(row, k).start()

        @pl.when(ahead[-1] >= 0)
        def _prefetch():
            into = (turn + STEP_SLOTS - 1) % STEP_SLOTS
            sent(into)
            copy(ahead[-1], into).start()

        # this step's token into its ring: `d x` and `B` as they came, the
        # running log-decay where the step's `d A` came
        logs = slice(lo_l, lo_l + nl)
        lt = side_ref[0, nb:nb + nl, :] + jnp.where(
            held > 0, ring_ref[0, 0, jnp.maximum(held - 1, 0), logs, :], 0.0)
        entry_out[0, 0, 0, 0:tiles, :] = now_ref[0]
        entry_out[0, 0, 0, lo_b:lo_l, :] = side_ref[0, 0:nb, :]
        entry_out[0, 0, 0, logs, :] = lt
        if lo_l + nl < rows:            # an entry's padding
            entry_out[0, 0, 0, lo_l + nl:, :] = jnp.zeros(
                (rows - lo_l - nl, LANES), f32)
        # each entry's decay from its position to this one, then what the
        # state itself carries over, exp(l_t): rows of `tr`, a head a
        # lane, made columns (a head a sublane) by a square transpose
        for r in range(entries):
            since = jnp.where(held == r, lt, ring_ref[0, 0, r, logs, :])
            decay = jnp.exp(jnp.where(held >= r, lt - since, NEVER))
            for j in range(nl):
                tr[j, r:r + 1, :] = decay[j:j + 1]
        for j in range(nl):
            tr[j, ring:ring + 1, :] = jnp.exp(lt[j:j + 1])
        per = nl // tile                # rows of log-decays a head of a tile
        by_head = [jnp.concatenate(
            [tr[j * per + m].T for m in range(per)], axis=0)[:tiles]
            for j in range(tile)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (tiles, LANES), 1)

        def on_lanes(col: int):
            """Column `col` across each head's own lanes: [tiles, LANES]."""
            wide = [jnp.broadcast_to(v[:, col:col + 1], (tiles, LANES))
                    for v in by_head]
            return wide[0] if tile == 1 else jnp.where(
                lane < LANES // tile, wide[0], wide[1])

        carried[...] = on_lanes(ring)
        for r in range(entries):
            zs[r] = on_lanes(r) * jnp.where(
                held == r, now_ref[0], ring_ref[0, 0, r, 0:tiles, :])

        copy(i, slot).wait()

        def group(g, _, folds: bool):
            """A group's tiles: `y`, and where the row folds its states."""
            first = pl.multiple_of(g * pairs, pairs)
            mine = pl.ds(first, pairs)

            def b_row(r: int, k: int):
                return jnp.where(                            # [1, LANES]
                    held == r, side_ref[0, pl.ds(g * nk + k, 1), :],
                    ring_ref[0, 0, r, pl.ds(lo_b + g * nk + k, 1), :])

            c_rows = [side_ref[0, pl.ds(nb + nl + g * nk + k, 1), :]
                      for k in range(nk)]
            # C, and for a fold the ring's B, as columns
            for k in range(nk):
                tr[k, ring:ring + 1, :] = c_rows[k]
                if folds:
                    for r in range(entries):
                        tr[k, r:r + 1, :] = b_row(r, k)
            cols = jnp.concatenate([tr[k].T for k in range(nk)], axis=0)
            c_cols[...] = jnp.broadcast_to(cols[:, ring:ring + 1], (n, LANES))
            if folds:
                for r in range(entries):
                    b_cols[r] = jnp.broadcast_to(cols[:, r:r + 1],
                                                 (n, LANES))
            else:
                acc = jnp.zeros((pairs, LANES), f32)
                for r in range(entries):
                    score = sum(jnp.sum(b_row(r, k) * c_rows[k], axis=1,
                                        keepdims=True) for k in range(nk))
                    acc = acc + score * zs[r, mine, :]
                ringy[mine, :] = acc

            def one(p, _):
                row = pl.ds(p, 1)
                s = sbuf[slot, p]
                if folds:
                    s = jax.lax.fori_loop(
                        0, entries,
                        lambda r, s: s + b_cols[r] * zs[r, row, :],
                        s * carried[row, :])
                    s = _rounded(s, state_round)
                    sbuf[slot, p] = s
                read = jnp.sum(s * c_cols[...], axis=0, keepdims=True)
                y_ref[0, row, :] = read if folds else \
                    carried[row, :] * read + ringy[row, :]
                return _

            return jax.lax.fori_loop(first, first + pairs, one, _)

        fold = row_ref[_FOLD, i] != 0

        @pl.when(jnp.logical_not(fold))
        def _read():
            jax.lax.fori_loop(0, groups,
                              functools.partial(group, folds=False), 0)

        @pl.when(fold)
        def _fold():
            jax.lax.fori_loop(0, groups,
                              functools.partial(group, folds=True), 0)
            copy(i, slot, back=True).start()
            unsent[slot] = 1

        @pl.when(ahead[1] < 0)
        def _last():
            for k in range(STEP_SLOTS):
                sent(k)


@functools.partial(jax.jit, static_argnames=(
    "entries", "state_round", "interpret"))
def _step_pallas(x, dt, a, b, c, pool, ring, layer, blocks, held, fold, *,
                 entries, state_round, interpret):
    """Jitted, and the layer an argument of it: a decode program's layers
    are one trace and one lowering of the kernel, not one each."""
    nb, h, p = x.shape
    g, n = b.shape[1:]
    t = tile_heads(p)
    tiles, pairs = h // t, h // g // t  # lane tiles of heads: all, a group
    held_most, rows = ring.shape[2:4]   # entries a ring; rows an entry
    f32 = jnp.float32
    dt = dt.astype(f32)
    now = (x.astype(f32) * dt[..., None]).reshape(nb, tiles, LANES)
    # the step's B, its d A a head a lane, and its C, in rows
    side = jnp.concatenate([
        v.reshape(nb, -1, LANES) for v in (
            b.astype(f32), _to_lanes(dt * a.astype(f32), p), c.astype(f32))],
        axis=1)
    live = blocks != 0
    at = jnp.arange(nb, dtype=jnp.int32)
    # the next live row after each, -1 after the last
    later = jnp.concatenate([jnp.where(live, at, nb)[1:],
                             jnp.full((1,), nb, jnp.int32)])
    nxt = jax.lax.cummin(later, reverse=True)
    per_row = jnp.stack([
        blocks, held, fold.astype(jnp.int32), jnp.where(nxt < nb, nxt, -1),
        jnp.cumsum(live) - live]).astype(jnp.int32)

    def mine(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, *_: (i,) + (0,) * len(shape))

    def entries_of(count: int):
        """A row's block of the ring: all its entries, or the one it
        writes."""
        return pl.BlockSpec(
            (1, 1, count, rows, LANES),
            lambda i, per_row, meta: (
                meta[0], per_row[_BLOCK, i],
                0 if count > 1 else per_row[_HELD, i], 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[mine(tiles, LANES), mine(side.shape[1], LANES),
                  entries_of(held_most), hbm],
        out_specs=[mine(tiles, LANES), entries_of(1), hbm],
        scratch_shapes=[pltpu.VMEM((STEP_SLOTS,) + pool.shape[2:], f32),
                        pltpu.VMEM((held_most, tiles, LANES), f32),
                        pltpu.VMEM((tiles, LANES), f32),
                        pltpu.VMEM((tiles, LANES), f32),
                        pltpu.VMEM((n, LANES), f32),
                        pltpu.VMEM((held_most, n, LANES), f32),
                        pltpu.VMEM((max(n // LANES,
                                        _entry_rows(h, g, p, n)[2]),
                                    LANES, LANES), f32),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SMEM((STEP_SLOTS,), jnp.int32)],
    )
    with jax.named_scope(MAMBA2_STEP):
        y, ring, pool = pl.pallas_call(
            functools.partial(_step_kernel, pairs=pairs, tile=t,
                              entries=entries, state_round=state_round),
            name=MAMBA2_STEP,
            out_shape=[jax.ShapeDtypeStruct((nb, tiles, LANES), f32),
                       jax.ShapeDtypeStruct(ring.shape, ring.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the two prefetched ones
            input_output_aliases={4: 1, 5: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(per_row, jnp.asarray(layer, jnp.int32)[None], now, side, ring,
          pool)
    return y.reshape(nb, h, p), pool, ring


def mamba2_step(x, dt, a, b, c, pool, ring, layer, blocks, held, *,
                state_round: str = "none", impl: str = "auto"):
    """One decode position of B sequences through one layer's recurrence.

    x [B, H, P]; dt [B, H] float32 (> 0); a [H] float32 (< 0); b, c
    [B, G, N]; pool [L, blocks, H / t, N, t P] float32 (`to_pairs`);
    ring [L, blocks, RING, rows, LANES] float32 (`ring_array`); blocks
    [B] int32: each row's state (idle rows: 0, the trash block, of which
    they move nothing); held [B] int32: the entries each row's ring holds
    before this step (`ring_after` says which rows fold and what they
    hold after it). -> (y [B, H, P] float32, pool, ring)."""
    h, p = x.shape[1:]
    g, n = b.shape[1:]
    blocks = jnp.asarray(blocks, jnp.int32)
    held = jnp.asarray(held, jnp.int32)
    fold, _ = ring_after(blocks, held, state_round)
    entries = ring_entries(state_round)
    if resolve_impl(impl) == "pallas":
        why = plan(h, g, p, n)
        if not why:
            return _step_pallas(
                x, dt, a, b, c, pool, ring, layer, blocks, held, fold,
                entries=entries, state_round=state_round,
                interpret=backend.interpret())
        backend.note_fallback(MAMBA2_STEP, why)
    y, s, *rings = _step_plain(
        x, dt, b, c, a, to_heads(pool[layer, blocks], p),
        *_unpacked(ring[layer, blocks], h, g, p, n), held, fold, blocks != 0,
        entries=entries, state_round=state_round)
    return (y, pool.at[layer, blocks].set(to_pairs(s)),
            ring.at[layer, blocks].set(_packed(*rings, p)))


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
