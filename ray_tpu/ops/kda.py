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

`kda_step` is decode's: one position of each of B sequences, each against
its own block, the whole update in float32 on the vector unit (a step is
bound by the state's bytes: it is read once and written once). Idle rows
name block 0 and rewrite it among themselves.

`kda_chunk` is prefill's: C positions of one sequence in sub-chunks of
`SUB` (16). Inside a sub-chunk the corrected values obey a unit
lower-triangular system,

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
ROWS = 8                    # a step's vectors a head: q, k, exp g, v, beta
VMEM_LIMIT = 64 * 1024 * 1024
MM_DTYPE = jnp.bfloat16     # what the chunk kernel feeds the MXU
_HIGHEST = jax.lax.Precision.HIGHEST
_TN = (((0,), (0,)), ((), ()))


def _rounded(x, state_round: str):
    """A state as it is kept (`state_round`: the benchmark's control
    rounds it to bfloat16 at every write, and keeps float32 bytes)."""
    if state_round == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


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
        o, s = _step_plain(*(a[None] for a in x), s[None],
                           state_round="none")
        return s[0], o[0]

    s, o = jax.lax.scan(step, s0.astype(f32), (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32)))
    return o, s


def _step_plain(q, k, v, g, beta, s, *, state_round):
    """One position of B sequences against their states s [B, H, dk, dv]:
    -> (o [B, H, dv], s)."""
    f32 = jnp.float32
    kf = k.astype(f32)
    s = s * jnp.exp(g.astype(f32))[..., None]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum(
        "bhc,bhcv->bhv", kf, s, precision=_HIGHEST))
    s = _rounded(s + kf[..., None] * u[..., None, :], state_round)
    return jnp.einsum("bhc,bhcv->bhv", q.astype(f32), s,
                      precision=_HIGHEST), s


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

def _step_kernel(blocks_ref, meta_ref, x_ref, s_ref, o_ref, s_out, *,
                 heads: int, state_round: str):
    del blocks_ref, meta_ref
    dk = s_ref.shape[3]

    def head(h, _):
        x = x_ref[0, h]                                      # [ROWS, dk]
        # q, k and exp g as columns: one square transpose a head
        cols = jnp.concatenate(
            [x, jnp.zeros((dk - ROWS, dk), jnp.float32)], axis=0).T
        q, k, decay = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        v, beta = x[3:4], x[4:5]
        s = s_ref[0, 0, h] * decay
        u = beta * (v - jnp.sum(s * k, axis=0, keepdims=True))
        s = _rounded(s + k * u, state_round)
        s_out[0, 0, h] = s
        o_ref[0, pl.ds(h, 1), :] = jnp.sum(s * q, axis=0, keepdims=True)
        return _

    jax.lax.fori_loop(0, heads, head, 0)


def _step_pallas(q, k, v, g, beta, pool, layer, blocks, *, state_round):
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    x = jnp.stack([q.astype(f32), k.astype(f32), jnp.exp(g.astype(f32)),
                   v.astype(f32),
                   jnp.broadcast_to(beta.astype(f32)[..., None], (b, h, dk))],
                  axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, ROWS - x.shape[2]), (0, 0)))

    def state():
        return pl.BlockSpec(
            (1, 1, h, dk, dv),
            lambda i, blocks, meta: (meta[0], blocks[i], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, ROWS, dk), lambda i, *_: (i, 0, 0, 0)),
                  state()],
        out_specs=[pl.BlockSpec((1, h, dv), lambda i, *_: (i, 0, 0)),
                   state()],
    )
    with jax.named_scope(KDA_STEP):
        o, pool = pl.pallas_call(
            functools.partial(_step_kernel, heads=h,
                              state_round=state_round),
            name=KDA_STEP,
            out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            grid_spec=grid_spec,
            # operands count the two prefetched ones
            input_output_aliases={3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(jnp.asarray(blocks, jnp.int32), jnp.asarray(layer, jnp.int32)[None],
          x, pool)
    return o, pool


def kda_step(q, k, v, g, beta, pool, layer, blocks, *,
             state_round: str = "none", impl: str = "auto"):
    """One decode position of B sequences through one layer's KDA.

    q, k [B, H, dk] (normed, q scaled); v [B, H, dv]; g [B, H, dk] float32
    (< 0); beta [B, H]; pool [L, blocks, H, dk, dv] float32; blocks [B]
    int32: each row's state (idle rows: 0, the trash block).
    -> (o [B, H, dv] float32, pool)."""
    if resolve_impl(impl) == "pallas":
        why = plan(q.shape[-1], v.shape[-1])
        if not why:
            return _step_pallas(q, k, v, g, beta, pool, layer, blocks,
                                state_round=state_round)
        backend.note_fallback(KDA_STEP, why)
    o, s = _step_plain(q, k, v, g, beta, pool[layer, blocks],
                       state_round=state_round)
    return o, pool.at[layer, blocks].set(s)


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
