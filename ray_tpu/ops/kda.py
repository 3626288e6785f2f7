"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692) over a state
of fixed size a sequence: the delta rule with a decay a channel.

One head of one layer keeps `S [dk, dv]`, float32, and a token moves it

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with `g_t [dk]` in `[floor, 0)` (the published lower bound, -5) and
`beta_t` in (0, 1). Written as a decay and one rank-one correction:

    S' = diag(exp g_t) S_{t-1};   u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T

**The state as stored**: `[L, blocks, H, dk, dv]` float32, a block one
sequence's state and block 0 the engine's trash block
(`ops/power_retention.py`'s conventions). Both kernels take the whole
pool, are told layer and block through scalar prefetch, and write the
block in place (`input_output_aliases`).

**The ring beside the state** (`ring_array`; a block's, as the state):
the decode tokens that are not in the state yet, oldest first, `RING` of
them at most: `ring [L, blocks, RING, rows, 128]` float32, an entry one
token's `k`, its corrected value `u` and the running sum `G` of `g`
since the last fold (a channel, <= 0), a head a row of 128 and each of
the three from a sublane tile of its own (`_entry_rows`: 1.5 KB a head
against a state's 64 KB); and how many a block's ring holds (`held`,
kept by the caller: all layers step together). One array, not three:
what is small enough the compiler carries into VMEM around every call
and back (`ops/mamba2.py`, PR 56). With `t0` the last fold and the ring
holding tokens `t0 + 1 .. t`:

    S_t = diag(exp G_t) S_t0 + sum_s diag(exp(G_t - G_s)) k_s u_s^T
    u_t = beta_t (v_t - S_t0^T (k_t exp G_t)
                  - sum_{s<t} ((k_t exp(G_t - G_s)) . k_s) u_s)
    o_t = S_t0^T (q_t exp G_t) + sum_{s<=t} ((q_t exp(G_t - G_s)) . k_s) u_s

the same sum reordered, every exponent a difference taken forward in
time: with `g >= -5` and a ring of 8, `exp G_t >= e^-40`, inside
float32. Once `u_t` is known it never changes, so the delta rule's
dependence on the state costs two products of a row with `S_t0` a token
(`k exp G` and `q exp G`) and nothing else. A row whose ring is full with
this token **folds**: `S_t0 <- S_t`, its ring is empty after and `G`
starts again at 0.

`kda_step` is decode's: one position of each of B sequences, each against
its own block, float32 on the vector unit but for a fold's sum. A step is
bound by the state's bytes, so it reads every decoding row's state once
and writes only the folding rows': the kernel holds the pool in HBM (`pl.ANY`), a program is
one sequence, whose 32 states of a layer come by one DMA that the live
row `STEP_SLOTS - 1` before it started, and go back by one DMA only
where the row folds, waited for when its buffer is next wanted. The ring
comes as a block and the step's entry goes back through an out block of
its own (one entry, not the ring). Everything a token needs but the two
products is made eight heads at a time, a head a sublane (the ring's
scores, `u`, `o`); the products' operands become columns by one square
transpose a group of eight heads, where the read-modify-write made one a
head (the products on the MXU, a head's state the stationary operand,
were slower on a v5e: PERF.md, PR 64); only a fold decays and rewrites
the state, a head at a time, its sum over the entries one pass of the
MXU over the six products of three bfloat16 parts an operand that
float32 keeps. An idle row (block 0) moves nothing of the state and
leaves the trash block's ring as it was. Rows fold when their own ring is full, so the
traffic's staggered positions put about B / RING folds in every step.
`RING` = 8: by bytes a step moves `1 + 1 / RING + RING x entry / state`
states where the read-modify-write moved 2 (1.34 at 4, 1.31 at 8, 1.44
at 16: an entry is 0.023 of a state, so a ring of 16 reads more of its
entries than it saves of its folds), and 8 rows of float32 are one tile
of sublanes (PERF.md, PR 64, has the three readings). Under
`state_round` (the benchmark's control: the state rounded at every
write) a ring holds one token and every step folds, so that the control
rounds at every token as it did; a folding row's `o` is read from the
state it writes.

`kda_chunk` is prefill's: C positions of one sequence in sub-chunks of
`SUB` (16). It reads and writes the folded state: a prompt's last chunk
leaves the ring empty (the caller's `held` 0), and a chunk after decode
steps of the same sequence does not occur. Inside a sub-chunk the
corrected values obey a unit lower-triangular system,

    (I + diag(beta) A) U = diag(beta) (V - K~ S_0),
    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])   (i < t)

(`G` the running sum of `g` inside the sub-chunk, `K~ = k exp(G)`), so
`U = W_v - W_k S_0` with `W_v = T beta V`, `W_k = T beta K~`,
`T = (I + diag(beta) A)^-1`: everything but `S_0` is known before the
state is. `_chunk_parts` makes those pieces in float32 (the solve
included); the kernel then walks the sub-chunks with three matmuls
against the state each:

    U = W_v - W_k S;   O = Q~ S + B U;   S = diag(exp G_n) S + K^^T U

(`Q~ = q exp(G)`, `B[t, i] = sum_c q_t k_i exp(G_t - G_i)` for i <= t,
`K^ = k exp(G_n - G)`). The lower bound is what makes this safe: inside
16 positions a decay ratio stays within e^80, inside float32, and the
exponents are taken relative to the sub-chunk's eighth position, so no
factor passes e^40 and no ratio is formed across more than a sub-chunk.
Matmul operands are bfloat16 with float32 accumulation; the state is read
as a high and a low bfloat16 part and updated in float32. Rows at and
past `length` (a chunk bucket's padding) carry k = 0, beta = 0, g = 0:
they weigh nothing and leave the state bit for bit; `first` reads the
block as zeros, whatever a freed block still holds.

Each has a plain `jax.numpy` path behind `impl` (float32 at the highest
matmul precision), which the CPU tests compare with the kernel in
interpret mode and with the token-by-token definition.
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
KDA_STEP, KDA_CHUNK = "kda_step", "kda_chunk"

SUB = 16                    # positions a sub-chunk
LANES = 128
ROWS = 8                    # sublanes of a float32 tile
VMEM_LIMIT = 64 * 1024 * 1024
RING = 8                    # decode tokens a ring takes before it folds
STEP_SLOTS = 3              # buffers of the step kernel's states
MM_DTYPE = jnp.bfloat16     # what the chunk kernel feeds the MXU
NEVER = -1e30               # an exponent that reads as a factor of 0
_HIGHEST = jax.lax.Precision.HIGHEST
_TN = (((0,), (0,)), ((), ()))


def _rounded(x, state_round: str):
    """A state as it is kept (`state_round`: the benchmark's control
    rounds it to bfloat16 at every write, and keeps float32 bytes)."""
    if state_round == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def _entry_rows(heads: int, dk: int, dv: int):
    """Where a ring entry `[rows, LANES]` keeps a token: -> (the rows of
    its `k`, and of its `G`; of its `u`; all of them). Each part starts a
    sublane tile; at a head of 128 a head is a row."""
    def tiles(n):
        return -(-heads * n // (ROWS * LANES)) * ROWS
    return tiles(dk), tiles(dv), 2 * tiles(dk) + tiles(dv)


def _packed(k, u, big):
    """A token's `k` [..., H, dk], `u` [..., H, dv] and `G` [..., H, dk]
    -> an entry [..., rows, LANES]: `k`, then `u`, then `G`."""
    lead = k.shape[:-2]
    (h, dk), dv = k.shape[-2:], u.shape[-1]
    k_rows, u_rows, rows = _entry_rows(h, dk, dv)
    flat = jnp.concatenate([
        jnp.pad(v.reshape(*lead, -1),
                [(0, 0)] * len(lead) + [(0, r * LANES - v.shape[-1] * h)])
        for v, r in ((k, k_rows), (u, u_rows), (big, k_rows))], axis=-1)
    return flat.reshape(*lead, rows, LANES)


def _unpacked(entry, heads: int, dk: int, dv: int):
    """`_packed`'s inverse: entries [..., rows, LANES] -> (`k` [..., H,
    dk], `u` [..., H, dv], `G` [..., H, dk])."""
    lead = entry.shape[:-2]
    k_rows, u_rows, _ = _entry_rows(heads, dk, dv)
    flat = entry.reshape(*lead, -1)

    def part(row, n):
        return flat[..., row * LANES:row * LANES + heads * n].reshape(
            *lead, heads, n)

    return part(0, dk), part(k_rows, dv), part(k_rows + u_rows, dk)


def ring_array(layers: int, blocks: int, heads: int, dk: int,
               dv: int | None = None):
    """The rings beside `layers x blocks` states, empty: float32 [L,
    blocks, RING, rows, LANES], blocks on axis 1."""
    return jnp.zeros((layers, blocks, RING,
                      _entry_rows(heads, dk, dk if dv is None else dv)[2],
                      LANES), jnp.float32)


def ring_entries(state_round: str) -> int:
    """Tokens a row's ring takes before it folds: `RING`, and one under
    the benchmark's control, whose state is rounded at every token."""
    return RING if state_round == "none" else 1


def ring_after(blocks, held, state_round: str = "none"):
    """What a decode step does to its rows' rings (`mamba2.ring_after`'s
    rule): blocks [B] (0: an idle row), held [B] the entries each ring
    holds before the step -> (fold [B] bool: the row's ring is full with
    this step's token and goes into its state, held [B] after the step)."""
    live = blocks != 0
    fold = live & (held + 1 >= ring_entries(state_round))
    return fold, jnp.where(live, jnp.where(fold, 0, held + 1), held)


# ---------------------------------------------------------------------------
# plain paths
# ---------------------------------------------------------------------------

def kda_recurrent(q, k, v, g, beta, s0=None):
    """The definition, token by token: q, k [T, H, dk], v [T, H, dv],
    g [T, H, dk], beta [T, H] -> (o [T, H, dv] float32, S [H, dk, dv])."""
    f32 = jnp.float32
    t, h, dk = q.shape
    if s0 is None:
        s0 = jnp.zeros((h, dk, v.shape[-1]), f32)

    def step(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[..., None]
        u = beta[..., None] * (v - jnp.einsum("hc,hcv->hv", k, s,
                                              precision=_HIGHEST))
        s = s + k[..., None] * u[..., None, :]
        return s, jnp.einsum("hc,hcv->hv", q, s, precision=_HIGHEST)

    s, o = jax.lax.scan(step, s0.astype(f32), (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32)))
    return o, s


def _step_plain(q, k, v, g, beta, s, rk, ru, rg, held, fold, live, *,
                entries, state_round):
    """One position of B sequences against their states s [B, H, dk, dv]
    and rings rk, rg [B, R, H, dk], ru [B, R, H, dv] (`_unpacked`); held,
    fold, live [B] as `ring_after` has them: -> (o [B, H, dv], s, rk, ru,
    rg)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    at = jnp.arange(rk.shape[1])
    put = ((at == held[:, None]) & live[:, None])[..., None, None]
    last = jnp.take_along_axis(
        rg, jnp.maximum(held - 1, 0)[:, None, None, None], axis=1)[:, 0]
    big = jnp.where((held > 0)[:, None, None], last, 0.0) + g
    rk = jnp.where(put, k[:, None], rk)
    rg = jnp.where(put, big[:, None], rg)
    # each entry's key as this position sees it: decayed from its
    # position to this one, 0 past the last held (this token's own is k)
    holds = (at[:entries] <= held[:, None])[..., None, None]  # [B, E, 1, 1]
    moved = rk[:, :entries] * jnp.exp(
        jnp.where(holds, big[:, None] - rg[:, :entries], NEVER))
    earlier = (at[:entries] < held[:, None])[..., None]       # [B, E, 1]
    carried = jnp.exp(big)                                    # [B, H, dk]
    u = beta[..., None] * (
        v - jnp.einsum("bhc,bhcv->bhv", k * carried, s, precision=_HIGHEST)
        - jnp.einsum("beh,behv->bhv", jnp.where(earlier, jnp.einsum(
            "bhc,behc->beh", k, moved, precision=_HIGHEST), 0.0),
            ru[:, :entries], precision=_HIGHEST))
    ru = jnp.where(put, u[:, None], ru)
    kept = jnp.einsum("bhc,bhcv->bhv", q * carried, s, precision=_HIGHEST) \
        + jnp.einsum("beh,behv->bhv", jnp.einsum(
            "bhc,behc->beh", q, moved, precision=_HIGHEST), ru[:, :entries],
            precision=_HIGHEST)
    folded = _rounded(carried[..., None] * s + jnp.einsum(
        "behc,behv->bhcv", moved, ru[:, :entries], precision=_HIGHEST),
        state_round)
    o = kept
    if state_round != "none":   # the control's, from the state as written
        o = jnp.where(fold[:, None, None], jnp.einsum(
            "bhc,bhcv->bhv", q, folded, precision=_HIGHEST), kept)
    return (jnp.where(live[:, None, None], o, 0.0),
            jnp.where(fold[:, None, None, None], folded, s), rk, ru, rg)


def _chunk_parts(q, k, v, g, beta, length):
    """Everything of a chunk that does not read the state, float32, heads
    first and sub-chunks second: -> (w_k, q_t, k_hat [H, N, SUB, dk],
    w_v [H, N, SUB, dv], b [H, N, SUB, SUB], gamma [H, N, dk])."""
    f32 = jnp.float32
    c, h, dk = q.shape
    n = c // SUB
    live = (jnp.arange(c) < length)[:, None]

    def heads_first(a):
        return a.reshape((n, SUB) + a.shape[1:]).swapaxes(1, 2).swapaxes(0, 1)

    q = heads_first(q.astype(f32))                           # [H, N, S, dk]
    k = heads_first(jnp.where(live[..., None], k.astype(f32), 0.0))
    v = heads_first(v.astype(f32))
    beta = heads_first(jnp.where(live, beta.astype(f32), 0.0))[..., None]
    big = jnp.cumsum(heads_first(jnp.where(live[..., None], g.astype(f32),
                                           0.0)), axis=2)
    # exponents relative to the eighth position: none passes SUB/2 steps
    rel = big - big[:, :, SUB // 2 - 1:SUB // 2]
    up, down = jnp.exp(rel), jnp.exp(-rel)
    t_idx = jnp.arange(SUB)[:, None]
    i_idx = jnp.arange(SUB)[None, :]
    k_down = k * down
    a = jnp.where(i_idx < t_idx, jnp.einsum(
        "hntc,hnic->hnti", k * up, k_down, precision=_HIGHEST), 0.0)
    b = jnp.where(i_idx <= t_idx, jnp.einsum(
        "hntc,hnic->hnti", q * up, k_down, precision=_HIGHEST), 0.0)
    decay = jnp.exp(big)
    rhs = jnp.concatenate([beta * v, beta * k * decay], -1)
    solved = jax.scipy.linalg.solve_triangular(
        jnp.eye(SUB, dtype=f32) + beta * a, rhs, lower=True,
        unit_diagonal=True)
    dv = v.shape[-1]
    total = big[:, :, -1:]
    return (solved[..., dv:], q * decay, k * jnp.exp(total - big),
            solved[..., :dv], b, jnp.exp(total[:, :, 0]))


def _chunk_plain(parts, s, *, state_round):
    """The sub-chunks in order against one block's state s [H, dk, dv],
    float32 throughout: -> (o [H, N, SUB, dv], s)."""
    w_k, q_t, k_hat, w_v, b, gamma = parts

    def sub(s, x):
        w_k, q_t, k_hat, w_v, b, gamma = x
        u = w_v - jnp.einsum("htc,hcv->htv", w_k, s, precision=_HIGHEST)
        o = (jnp.einsum("htc,hcv->htv", q_t, s, precision=_HIGHEST)
             + jnp.einsum("hti,hiv->htv", b, u, precision=_HIGHEST))
        s = gamma[..., None] * s + jnp.einsum("htc,htv->hcv", k_hat, u,
                                              precision=_HIGHEST)
        return s, o

    s, o = jax.lax.scan(sub, s, tuple(a.swapaxes(0, 1) for a in parts))
    return o.swapaxes(0, 1), _rounded(s, state_round)


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

def plan(dk: int, dv: int, c: int = SUB):
    """"" where the kernels have a plan for these widths (and, for the
    chunk kernel, a chunk of `c` positions), else why not."""
    if dk != LANES or dv != LANES:
        return (f"a state of {dk} x {dv} is not one lane tile square "
                f"({LANES} x {LANES})")
    if -(-c // SUB) > LANES:    # exp G_n of every sub-chunk is one tile
        return f"a chunk of {c} positions is over {LANES} sub-chunks"
    return ""


# ---------------------------------------------------------------------------
# the step kernel
# ---------------------------------------------------------------------------

def _parts(x):
    """x float32 as three bfloat16 parts that sum to it, in float32."""
    f32 = jnp.float32
    high = x.astype(MM_DTYPE).astype(f32)
    mid = (x - high).astype(MM_DTYPE).astype(f32)
    return high, mid, (x - high - mid).astype(MM_DTYPE).astype(f32)


# rows of the step kernel's per-row scalars (scalar prefetch, [5, B])
_BLOCK, _HELD, _FOLD, _NEXT, _ORD = range(5)
# planes of its operand x [B, 5, heads, LANES]: a head a row of each
_Q, _K, _G, _V, _BETA = range(5)


def _step_kernel(row_ref, meta_ref, x_ref, ring_ref, s_hbm, o_ref,
                 entry_out, s_out, sbuf, mine, reads, scores, moved, tr,
                 cols, fold_in, rsem, wsem, unsent, *, heads: int,
                 entries: int, state_round: str):
    """Grid (B,), in order: a program is one sequence. A live row's
    states come by a DMA that the live row `STEP_SLOTS - 1` before it
    started and go back by a DMA only where the row folds, waited for
    when its buffer is next wanted; an idle row moves nothing. `x_ref`:
    the step's q, k, g, v and beta, a head a row; `entries`: how many
    ring entries a row can hold."""
    f32 = jnp.float32
    i = pl.program_id(0)
    layer = meta_ref[0]
    hp = x_ref.shape[2]                 # heads, in whole sublane tiles
    lo_u, lo_g = hp, 2 * hp             # where an entry keeps u and G
    held = row_ref[_HELD, i]
    live = row_ref[_BLOCK, i] != 0

    def copy(row, slot, back=False):
        """A row's states: in from the pool as they came, or back into
        the pool as they leave (the same buffer on the chip)."""
        block = row_ref[_BLOCK, jnp.maximum(row, 0)]
        if back:
            return pltpu.make_async_copy(
                sbuf.at[slot], s_out.at[layer, block], wsem.at[slot])
        return pltpu.make_async_copy(
            s_hbm.at[layer, block], sbuf.at[slot], rsem.at[slot])

    def sent(slot):
        """A buffer is free once the states a fold sent back from it
        have landed."""
        @pl.when(unsent[slot] != 0)
        def _land():
            copy(i, slot, back=True).wait()
            unsent[slot] = 0

    def tile(group, lo=0):
        """The sublane tile of a group of eight heads, `lo` rows down."""
        return pl.ds(pl.multiple_of(lo + group * ROWS, ROWS), ROWS)

    def each_head(group, one):
        """`one(h, j)` for head `h`, the `j`-th of a group: unrolled, so
        that eight heads' chains of loads, products and stores overlap;
        a last tile's rows past the heads are skipped."""
        for j in range(ROWS):
            h = group * ROWS + j
            if heads % ROWS:
                pl.when(h < heads)(functools.partial(one, h, j))
            else:
                one(h, j)

    @pl.when(jnp.logical_not(live))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)
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

        if heads % ROWS:                # the last tile's rows past the heads
            reads[...] = jnp.zeros_like(reads)

        def prelude(group, _):
            """Eight heads' token into its ring (k and the running G;
            u follows the state), the rows the state is read by, and each
            entry's key as this position sees it with its two scores."""
            rows = tile(group)
            q, k = x_ref[0, _Q, rows, :], x_ref[0, _K, rows, :]
            big = x_ref[0, _G, rows, :] + jnp.where(
                held > 0,
                ring_ref[0, 0, jnp.maximum(held - 1, 0), tile(group, lo_g), :],
                0.0)
            carried = jnp.exp(big)
            mine[0, rows, :] = q * carried
            mine[1, rows, :] = k * carried
            mine[2, rows, :] = carried
            entry_out[0, 0, 0, rows, :] = k
            entry_out[0, 0, 0, tile(group, lo_g), :] = big
            for r in range(entries):
                since = jnp.where(held == r, big,
                                  ring_ref[0, 0, r, tile(group, lo_g), :])
                seen = jnp.where(held == r, k, ring_ref[0, 0, r, rows, :]) \
                    * jnp.exp(jnp.where(held >= r, big - since, NEVER))
                moved[r, rows, :] = seen
                scores[0, r, rows, :] = jnp.broadcast_to(jnp.where(
                    held > r, jnp.sum(k * seen, axis=1, keepdims=True), 0.0),
                    (ROWS, LANES))
                scores[1, r, rows, :] = jnp.broadcast_to(
                    jnp.sum(q * seen, axis=1, keepdims=True), (ROWS, LANES))
            return _

        jax.lax.fori_loop(0, hp // ROWS, prelude, 0)
        copy(i, slot).wait()

        def read(group, _):
            """S^T (q exp G) and S^T (k exp G) of eight heads: their
            sixteen rows, and exp G for a fold, made columns by one square
            transpose."""
            rows = tile(group)
            for part in range(3):
                tr[part * ROWS:(part + 1) * ROWS, :] = mine[part, rows, :]
            cols[group] = tr[...].T

            def one(h, j: int):
                s = sbuf[slot, h]
                for part in range(2):
                    at = part * ROWS + j
                    reads[part, pl.ds(h, 1), :] = jnp.sum(
                        s * cols[group, :, at:at + 1], axis=0, keepdims=True)

            each_head(group, one)
            return _

        jax.lax.fori_loop(0, hp // ROWS, read, 0)

        def finish(group, _):
            """Eight heads' u and o from what the state gave and the
            ring's entries."""
            rows, u_rows = tile(group), tile(group, lo_u)
            taken = reads[1, rows, :]
            for r in range(entries):
                taken = taken + scores[0, r, rows, :] \
                    * ring_ref[0, 0, r, u_rows, :]
            u = x_ref[0, _BETA, rows, :] * (x_ref[0, _V, rows, :] - taken)
            o = reads[0, rows, :]
            for r in range(entries):
                o = o + scores[1, r, rows, :] * jnp.where(
                    held == r, u, ring_ref[0, 0, r, u_rows, :])
            o_ref[0, rows, :] = o
            entry_out[0, 0, 0, u_rows, :] = u
            return _

        jax.lax.fori_loop(0, hp // ROWS, finish, 0)

        @pl.when(row_ref[_FOLD, i] != 0)
        def _fold():
            er = -(-entries // ROWS) * ROWS     # the entries, in whole tiles

            def one(group, h, j: int):
                """S <- diag(exp G) S + sum_s (k_s exp(G - G_s)) u_s^T of
                head `h`, the `j`-th of a group: the entries' decayed keys
                against their u as one pass of the MXU over the six
                products of their parts that float32 keeps, the decay by
                the column `read` made of exp G."""
                row, mine_at = pl.ds(h, 1), 2 * er * j
                for r in range(entries):
                    fold_in[mine_at + r:mine_at + r + 1, :] = moved[r, row, :]
                    fold_in[mine_at + er + r:mine_at + er + r + 1, :] = \
                        jnp.where(
                            held == r,
                            entry_out[0, 0, 0, pl.ds(lo_u + h, 1), :],
                            ring_ref[0, 0, r, pl.ds(lo_u + h, 1), :])
                k1, k2, k3 = _parts(fold_in[mine_at:mine_at + er, :])
                u1, u2, u3 = _parts(fold_in[mine_at + er:mine_at + 2 * er, :])
                added = jax.lax.dot_general(
                    jnp.concatenate([k1, k1, k1, k2, k2, k3], axis=0).astype(
                        MM_DTYPE),
                    jnp.concatenate([u1, u2, u3, u1, u2, u1], axis=0).astype(
                        MM_DTYPE), _TN, preferred_element_type=f32)
                at = 2 * ROWS + j
                s = _rounded(sbuf[slot, h] * cols[group, :, at:at + 1] + added,
                             state_round)
                sbuf[slot, h] = s
                if state_round != "none":
                    # the control's o, read from the state as it is written
                    tr[0:1, :] = x_ref[0, _Q, row, :]
                    o_ref[0, row, :] = jnp.dot(
                        tr[0:ROWS, :], s, precision=_HIGHEST,
                        preferred_element_type=f32)[0:1]

            def fold_group(group, _):
                each_head(group, functools.partial(one, group))
                return _

            if entries % ROWS:      # the rows past a ring's entries
                fold_in[...] = jnp.zeros_like(fold_in)
            jax.lax.fori_loop(0, hp // ROWS, fold_group, 0)
            copy(i, slot, back=True).start()
            unsent[slot] = 1

        @pl.when(ahead[1] < 0)
        def _last():
            for k in range(STEP_SLOTS):
                sent(k)


@functools.partial(jax.jit, static_argnames=(
    "entries", "state_round", "interpret"))
def _step_pallas(q, k, v, g, beta, pool, ring, layer, blocks, held, fold, *,
                 entries, state_round, interpret):
    """Jitted, and the layer an argument of it: a decode program's layers
    are one trace and one lowering of the kernel, not one each."""
    nb, h, dk = q.shape
    dv = v.shape[-1]
    held_most, rows = ring.shape[2:4]   # entries a ring; rows an entry
    hp = rows // 3                      # heads, in whole sublane tiles
    f32 = jnp.float32
    x = jnp.stack([q.astype(f32), k.astype(f32), g.astype(f32),
                   v.astype(f32),
                   jnp.broadcast_to(beta.astype(f32)[..., None], (nb, h, dk))],
                  axis=1)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, hp - h), (0, 0)))
    live = blocks != 0
    at = jnp.arange(nb, dtype=jnp.int32)
    # the next live row after each, -1 after the last
    later = jnp.concatenate([jnp.where(live, at, nb)[1:],
                             jnp.full((1,), nb, jnp.int32)])
    nxt = jax.lax.cummin(later, reverse=True)
    per_row = jnp.stack([
        blocks, held, fold.astype(jnp.int32), jnp.where(nxt < nb, nxt, -1),
        jnp.cumsum(live) - live]).astype(jnp.int32)

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
        in_specs=[pl.BlockSpec((1, 5, hp, LANES), lambda i, *_: (i, 0, 0, 0)),
                  entries_of(held_most), hbm],
        out_specs=[pl.BlockSpec((1, hp, LANES), lambda i, *_: (i, 0, 0)),
                   entries_of(1), hbm],
        scratch_shapes=[pltpu.VMEM((STEP_SLOTS,) + pool.shape[2:], f32),
                        pltpu.VMEM((3, hp, LANES), f32),
                        pltpu.VMEM((2, hp, LANES), f32),
                        pltpu.VMEM((2, held_most, hp, LANES), f32),
                        pltpu.VMEM((held_most, hp, LANES), f32),
                        pltpu.VMEM((LANES, LANES), f32),
                        pltpu.VMEM((hp // ROWS, LANES, LANES), f32),
                        pltpu.VMEM((2 * ROWS * -(-held_most // ROWS) * ROWS,
                                    LANES), f32),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SemaphoreType.DMA((STEP_SLOTS,)),
                        pltpu.SMEM((STEP_SLOTS,), jnp.int32)],
    )
    with jax.named_scope(KDA_STEP):
        o, ring, pool = pl.pallas_call(
            functools.partial(_step_kernel, heads=h, entries=entries,
                              state_round=state_round),
            name=KDA_STEP,
            out_shape=[jax.ShapeDtypeStruct((nb, hp, dv), f32),
                       jax.ShapeDtypeStruct(ring.shape, ring.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the two prefetched ones
            input_output_aliases={3: 1, 4: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(per_row, jnp.asarray(layer, jnp.int32)[None], x, ring, pool)
    return o[:, :h], pool, ring


def kda_step(q, k, v, g, beta, pool, ring, layer, blocks, held, *,
             state_round: str = "none", impl: str = "auto"):
    """One decode position of B sequences through one layer's KDA.

    q, k [B, H, dk] (normed, q scaled); v [B, H, dv]; g [B, H, dk] float32
    (< 0); beta [B, H]; pool [L, blocks, H, dk, dv] float32; ring [L,
    blocks, RING, rows, LANES] float32 (`ring_array`); blocks [B] int32:
    each row's state (idle rows: 0, the trash block, of which they move
    nothing); held [B] int32: the entries each row's ring holds before
    this step (`ring_after` says which rows fold and what they hold after
    it). -> (o [B, H, dv] float32, pool, ring)."""
    h, dk = q.shape[1:]
    dv = v.shape[-1]
    blocks = jnp.asarray(blocks, jnp.int32)
    held = jnp.asarray(held, jnp.int32)
    fold, _ = ring_after(blocks, held, state_round)
    entries = ring_entries(state_round)
    if resolve_impl(impl) == "pallas":
        why = plan(dk, dv)
        if not why:
            return _step_pallas(
                q, k, v, g, beta, pool, ring, layer, blocks, held, fold,
                entries=entries, state_round=state_round,
                interpret=backend.interpret())
        backend.note_fallback(KDA_STEP, why)
    o, s, *rings = _step_plain(
        q, k, v, g, beta, pool[layer, blocks],
        *_unpacked(ring[layer, blocks], h, dk, dv), held, fold, blocks != 0,
        entries=entries, state_round=state_round)
    return (o, pool.at[layer, blocks].set(s),
            ring.at[layer, blocks].set(_packed(*rings)))


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------

def _chunk_kernel(meta_ref, wq_ref, kh_ref, wv_ref, b_ref, gam_ref, s_ref,
                  o_ref, s_out, *, subs: int, state_round: str):
    f32 = jnp.float32
    # exp G_n of sub-chunk j as column j
    gamma = gam_ref[0].T                                     # [dk, 128]
    s = jnp.where(meta_ref[2] > 0, 0.0, s_ref[0, 0, 0])     # a first chunk
    for j in range(subs):
        rows = slice(j * SUB, (j + 1) * SUB)
        high = s.astype(MM_DTYPE)
        low = (s - high.astype(f32)).astype(MM_DTYPE)
        wq = wq_ref[0, j]                                    # [2 SUB, dk]
        read = (jnp.dot(wq, high, preferred_element_type=f32)
                + jnp.dot(wq, low, preferred_element_type=f32))
        u = wv_ref[0, rows, :] - read[:SUB]
        um = u.astype(MM_DTYPE)
        o_ref[0, rows, :] = read[SUB:] + jnp.dot(
            b_ref[0, rows, :].astype(MM_DTYPE), um,
            preferred_element_type=f32)
        s = gamma[:, j:j + 1] * s + jax.lax.dot_general(
            kh_ref[0, rows, :], um, _TN, preferred_element_type=f32)
    s_out[0, 0, 0] = _rounded(s, state_round)


def _chunk_pallas(parts, pool, layer, block, first, *, state_round):
    w_k, q_t, k_hat, w_v, b, gamma = parts
    h, n, _, dk = w_k.shape
    dv = w_v.shape[-1]
    c = n * SUB
    mm, f32 = MM_DTYPE, jnp.float32
    # a sub-chunk's two state reads as one operand: W_k over Q~
    wq = jnp.concatenate([w_k, q_t], axis=2).astype(mm)      # [H, N, 2S, dk]
    gamma = jnp.pad(gamma, ((0, 0), (0, LANES - n), (0, 0)))  # [H, 128, dk]
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(block, jnp.int32),
                      jnp.asarray(first, jnp.int32)])

    def head(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, meta: (i,) + (0,) * len(shape))

    def state():
        return pl.BlockSpec((1, 1, 1, dk, dv),
                            lambda i, meta: (meta[0], meta[1], i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h,),
        in_specs=[head(n, 2 * SUB, dk), head(c, dk), head(c, dv),
                  head(c, SUB), head(LANES, dk), state()],
        out_specs=[head(c, dv), state()],
    )
    with jax.named_scope(KDA_CHUNK):
        o, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, subs=n,
                              state_round=state_round),
            name=KDA_CHUNK,
            out_shape=[jax.ShapeDtypeStruct((h, c, dv), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the prefetched one
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(meta, wq, k_hat.reshape(h, c, dk).astype(mm),
          w_v.reshape(h, c, dv), b.reshape(h, c, SUB), gamma, pool)
    return o, pool


def kda_chunk(q, k, v, g, beta, pool, layer, block, first, length, *,
              state_round: str = "none", impl: str = "auto"):
    """A prefill chunk of one sequence through one layer's KDA.

    q, k [C, H, dk]; v [C, H, dv]; g [C, H, dk] float32 (in [floor, 0));
    beta [C, H]; pool [L, blocks, H, dk, dv] float32; layer, block: which
    state; first: the sequence's first chunk (the block is read as
    zeros); length: the chunk's live positions.
    -> (o [C, H, dv] float32, pool)."""
    c, h, dk = q.shape
    dv = v.shape[-1]
    pad = -c % SUB
    if pad:                 # whole sub-chunks; the tail is padding
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    parts = _chunk_parts(q, k, v, g, beta, length)
    if resolve_impl(impl) == "pallas":
        why = plan(dk, dv, c)
        if not why:
            o, pool = _chunk_pallas(parts, pool, layer, block, first,
                                    state_round=state_round)
            return o.swapaxes(0, 1)[:c], pool
        backend.note_fallback(KDA_CHUNK, why)
    o, s = _chunk_plain(parts, jnp.where(first, 0.0, pool[layer, block]),
                        state_round=state_round)
    return (o.reshape(h, c + pad, dv).swapaxes(0, 1)[:c],
            pool.at[layer, block].set(s))
