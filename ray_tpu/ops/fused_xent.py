"""Fused chunked cross-entropy over a tied embedding — the LM loss
without the logits tensor.

The dense LM loss materializes logits ``[B, T, V]`` (the single biggest
activation in a GPT step: 1.6 GB f32 at the bench shape) just to reduce
it straight back down to one scalar per token. This op consumes the
pre-unembed activations ``x [B, T, d_model]`` and the tied embedding
``embed [V, d_model]`` instead, streaming the unembed matmul in vocab
chunks with an online (running max / log-sum-exp) accumulator — the
FlashAttention trick applied to the softmax over the vocabulary. Peak
live activation for the loss becomes O(B*T*chunk) instead of
O(B*T*V).

The backward is a `custom_vjp` that recomputes each chunk's logits from
the saved per-token logsumexp, so the residuals are just (x, embed,
targets, lse) — again no ``[B, T, V]`` anywhere:

    dlogits_c = g * (softmax_c - onehot_c)
    dx       += dlogits_c @ embed_c          (accumulated over chunks)
    dembed_c  = dlogits_c^T @ x              (one chunk per scan step)

Two implementations share that math:

- **pallas**: three TPU kernels (`xent_fwd`, `xent_dx`, `xent_de`),
  each a grid of row blocks x vocab blocks over one MXU pass per score
  tile, bf16 operands as stored, f32 everything else. What a grid step
  holds is `_plan`'s: forward and dX keep a block of `x` rows (and, for
  dX, its f32 result block, which is its own accumulator) while the
  whole embedding streams past 384 rows at a time at V = 50304; dE
  keeps 384 embedding rows and their f32 result while `x` streams past.
  A body walks its row block in tiles of `sub_n` rows with a static
  loop. Both shapes the benchmark runs sat at the HBM ridge with the
  blocks a rounded-down `vocab_chunk` gave them (256 rows x 384 or 128
  vocab rows: the embedding read once per 256 rows); the table under
  `fused_softmax_xent` is the sweep the plan was written from.
- **scan**: a pure-JAX `lax.scan` over vocab chunks of `vocab_chunk`
  rows — the everywhere-correct path, and what `impl="auto"` picks off
  a TPU or for a shape with no plan.

**Row statistics are lane-dense.** The forward keeps its running max,
sum and target logit as `[rows, 128]` with one value *per lane*: lane c
of a row owns the vocab columns = c (mod 128), so a score tile is
folded in slab by slab (128 columns) with elementwise `max`, `exp`,
add and select only. Nothing crosses lanes until `_finalize`, which
reduces 128 lanes once a row block. A `[rows, 1]` column costs a vreg
per 8 rows all the same, each use of it a lane broadcast, and each tile
two cross-lane reductions and a third for the target: at these narrow
tiles that, not the MXU, was most of the forward. The backward's lse,
targets and cotangents arrive `[N, 128]` with every lane alike and are
used whole against each slab.

On a mesh of more than one device the op runs under `shard_map` (the
compiler cannot partition a Pallas kernel): tokens split over the batch
axes, and a vocab-sharded (tensor-parallel) embedding stays sharded —
each shard reduces its *local* vocab rows to a partial logsumexp and
partial target logit, then one psum over the vocab mesh axis combines
them (`parallel/sharding.fused_xent_specs` derives the specs from the
rule table). The collective moves two ``[B, T]`` f32 arrays — vs. the
dense path's vocab-sharded logits gather/reduction over ``[B, T, V]``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend

_NEG = -1e30   # finite -inf stand-in: exp(_NEG - m) underflows to 0

# Kernel names in the compiled program and the profiler's trace
# (`%xent_fwd.N = ... custom-call`); PERF.md, section 3, lists them.
# Each call sits in a `named_scope` of its own name: see flash_attention.py.
XENT_FWD, XENT_DX, XENT_DE = "xent_fwd", "xent_dx", "xent_de"


# ---------------------------------------------------------------------------
# scan implementation (the everywhere-correct fallback)
# ---------------------------------------------------------------------------

def _chunked_embed(embed, chunk):
    """[V, D] -> ([nc, chunk, D], padded_v). Zero-padded rows are masked
    by callers via their column index (col < V)."""
    v, d = embed.shape
    nc = -(-v // chunk)
    vpad = nc * chunk
    if vpad != v:
        embed = jnp.pad(embed, ((0, vpad - v), (0, 0)))
    return embed.reshape(nc, chunk, d), vpad


def _lse_tgt_scan(x, embed, targets, chunk):
    """Partial stats over `embed`'s rows: per-token logsumexp [B, T] and
    raw target logit [B, T] (0 when the target id is outside [0, V) —
    the tensor-parallel shard case)."""
    v = embed.shape[0]
    chunk = min(chunk, v)
    emb, _ = _chunked_embed(embed, chunk)
    bt = x.shape[:-1]
    init = (jnp.full(bt, _NEG, jnp.float32),       # running max m
            jnp.zeros(bt, jnp.float32),            # sumexp at m
            jnp.zeros(bt, jnp.float32))            # target logit

    def body(carry, inp):
        m, l, tg = carry
        idx, e_c = inp
        s = jnp.einsum("btd,cd->btc", x, e_c,
                       preferred_element_type=jnp.float32)
        col = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = col < v
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l = (l * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(s - m_new[..., None]), axis=-1))
        hit = (col == targets[..., None]) & valid
        tg = tg + jnp.sum(jnp.where(hit, s, 0.0), axis=-1)
        return (m_new, l, tg), None

    nc = emb.shape[0]
    (m, l, tg), _ = jax.lax.scan(body, init,
                                 (jnp.arange(nc, dtype=jnp.int32), emb))
    return m + jnp.log(l), tg


def _bwd_scan(x, embed, targets, lse, c_lse, c_tgt, chunk):
    """Recompute per-chunk logits from the saved lse and emit f32
    (dx [B, T, D], dembed [V, D]). c_lse/c_tgt are the cotangents of the
    partial (lse, target-logit) pair — (g, -g) for the plain nll."""
    v, d = embed.shape
    chunk = min(chunk, v)
    emb, vpad = _chunked_embed(embed, chunk)

    def body(dx, inp):
        idx, e_c = inp
        s = jnp.einsum("btd,cd->btc", x, e_c,
                       preferred_element_type=jnp.float32)
        col = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = col < v
        p = jnp.where(valid, jnp.exp(s - lse[..., None]), 0.0)
        hit = ((col == targets[..., None]) & valid).astype(jnp.float32)
        dlog = c_lse[..., None] * p + c_tgt[..., None] * hit
        dx = dx + jnp.einsum("btc,cd->btd", dlog, e_c,
                             preferred_element_type=jnp.float32)
        de_c = jnp.einsum("btc,btd->cd", dlog, x,
                          preferred_element_type=jnp.float32)
        return dx, de_c

    nc = emb.shape[0]
    dx, de = jax.lax.scan(body, jnp.zeros(x.shape, jnp.float32),
                          (jnp.arange(nc, dtype=jnp.int32), emb))
    return dx, de.reshape(vpad, d)[:v]


# ---------------------------------------------------------------------------
# pallas kernels (TPU)
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    """What a call's three kernels run at (`_plan` chooses it).
    `block_n`: the rows of `x` a grid step of `xent_fwd` / `xent_dx`
    keeps in VMEM while the whole embedding streams past in blocks of
    `block_v` rows; `xent_de` turns that round: `block_v` embedding
    rows and their f32 dE block stay while `x` streams past in blocks
    of `block_n` rows. `sub_n`: the rows of the score tile
    `[sub_n, block_v]` a kernel body builds at a time, walking its row
    block. `vmem_limit`: what the hungriest of the three working sets
    asks of `CompilerParams(vmem_limit_bytes=)`, None where the
    compiler's default scope holds it."""
    block_n: int
    block_v: int
    sub_n: int
    vmem_limit: int | None


def _walk(plan: _Plan, rows):
    """`rows(slice)` for each `sub_n` rows of the resident row block:
    a static loop, so every start is a constant and the scheduler may
    run one tile's matmul under its neighbour's exp (as a
    `lax.fori_loop` the same walk cost 1.4-1.5 ms a kernel: the table
    under `fused_softmax_xent`)."""
    for r in range(plan.block_n // plan.sub_n):
        rows(pl.ds(r * plan.sub_n, plan.sub_n))


def _slabs(s):
    """[rows, block_v] -> its 128-column slabs, one lane tile each."""
    return [s[:, k * 128:(k + 1) * 128] for k in range(s.shape[1] // 128)]


def _scores(x, e):
    """One score tile on the MXU, the operands as stored (one type, or
    the wider of two): [sn, D] x [bv, D] -> f32 [sn, bv]."""
    ct = jnp.promote_types(x.dtype, e.dtype)
    return jax.lax.dot_general(
        x.astype(ct), e.astype(ct),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, e_ref, t_ref, lse_ref, tgt_ref,
                m_scr, l_scr, t_scr, *, plan: _Plan):
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    e = e_ref[...]                                      # [bv, D]
    lane = jax.lax.broadcasted_iota(jnp.int32, (plan.sub_n, 128), 1)

    def rows(sl):
        slabs = _slabs(_scores(x_ref[sl, :], e))        # f32 [sn, 128] each
        # lane c of a row keeps the running max, sum and target logit
        # of the vocab columns = c (mod 128): elementwise work only
        m_prev = m_scr[sl, :]
        m_new = functools.reduce(jnp.maximum, slabs, m_prev)
        l = l_scr[sl, :] * jnp.exp(m_prev - m_new)
        tg = t_scr[sl, :]
        tloc = t_ref[sl, :] - ji * plan.block_v         # lanes alike
        for k, sk in enumerate(slabs):
            l = l + jnp.exp(sk - m_new)
            tg = tg + jnp.where(lane + k * 128 == tloc, sk, 0.0)
        m_scr[sl, :] = m_new
        l_scr[sl, :] = l
        t_scr[sl, :] = tg

    _walk(plan, rows)

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finalize():
        # the one cross-lane pass of a row block: a row's 128 maxima,
        # sums and target terms become one, broadcast across the
        # 128-lane tile (TPU min tile width)
        m = m_scr[:]
        row_m = jnp.max(m, axis=1, keepdims=True)
        row_l = jnp.sum(l_scr[:] * jnp.exp(m - row_m), axis=1,
                        keepdims=True)
        lse_ref[...] = jnp.broadcast_to(row_m + jnp.log(row_l),
                                        lse_ref.shape)
        tgt_ref[...] = jnp.broadcast_to(
            jnp.sum(t_scr[:], axis=1, keepdims=True), tgt_ref.shape)


def _dlog(x, e, t, lse, cl, ct, v_start):
    """Rebuild one score tile from the saved lse and form dlogits in
    f32 — shared by the dx and dembed kernels so the masking/softmax
    math can never diverge between them
    (flash_attention._recompute_p_ds idiom). `t`, `lse`, `cl`, `ct`:
    [sn, 128] with every lane alike, as they arrive, used whole against
    each 128-column slab."""
    tloc = t - v_start
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    parts = []
    for k, sk in enumerate(_slabs(_scores(x, e))):
        p = cl * jnp.exp(sk - lse)
        parts.append(jnp.where(lane + k * 128 == tloc, p + ct, p))
    return jnp.concatenate(parts, axis=1)


def _dx_kernel(x_ref, e_ref, t_ref, lse_ref, cl_ref, ct_ref, dx_ref, *,
               plan: _Plan):
    ji = pl.program_id(1)

    # the f32 dx block stays while the vocab blocks go by: it is its
    # own accumulator
    @pl.when(ji == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    e = e_ref[...]

    def rows(sl):
        dlog = _dlog(x_ref[sl, :], e, t_ref[sl, :], lse_ref[sl, :],
                     cl_ref[sl, :], ct_ref[sl, :], ji * plan.block_v)
        dx_ref[sl, :] += jax.lax.dot_general(
            dlog.astype(e.dtype), e,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [sn, D]

    _walk(plan, rows)


def _de_kernel(x_ref, e_ref, t_ref, lse_ref, cl_ref, ct_ref, de_ref, *,
               plan: _Plan):
    # grid is (vocab blocks, row blocks): rows are the inner sequential
    # dim, so the f32 dembed block accumulates across them in place
    @pl.when(pl.program_id(1) == 0)
    def _init():
        de_ref[...] = jnp.zeros_like(de_ref)

    e = e_ref[...]
    v_start = pl.program_id(0) * plan.block_v

    def rows(sl):
        x = x_ref[sl, :]
        dlog = _dlog(x, e, t_ref[sl, :], lse_ref[sl, :], cl_ref[sl, :],
                     ct_ref[sl, :], v_start)
        de_ref[...] += jax.lax.dot_general(
            dlog.astype(x.dtype), x,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bv, D]

    _walk(plan, rows)


def _rows128(a, n):
    """[B, T] -> [N, 128] f32/int32 broadcast across the lane tile."""
    return jnp.broadcast_to(a.reshape(n, 1), (n, 128))


def _params(plan: _Plan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit)


def _lse_tgt_pallas(x, embed, targets, plan: _Plan, interpret):
    b, t, d = x.shape
    n = b * t
    v = embed.shape[0]
    bn, bv = plan.block_n, plan.block_v
    row_spec = pl.BlockSpec((bn, 128), lambda i, j: (i, 0))
    with jax.named_scope(XENT_FWD):
        lse2, tgt2 = pl.pallas_call(
            functools.partial(_fwd_kernel, plan=plan),
            name=XENT_FWD,
            out_shape=(jax.ShapeDtypeStruct((n, 128), jnp.float32),) * 2,
            grid=(n // bn, v // bv),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
                row_spec,
            ],
            out_specs=(row_spec, row_spec),
            # running max, sum and target logit, one of each per lane
            scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32)] * 3,
            compiler_params=_params(plan),
            interpret=interpret,
        )(x.reshape(n, d), embed, _rows128(targets.astype(jnp.int32), n))
    return lse2[:, 0].reshape(b, t), tgt2[:, 0].reshape(b, t)


def _bwd_pallas(x, embed, targets, lse, c_lse, c_tgt, plan: _Plan,
                interpret):
    b, t, d = x.shape
    n = b * t
    v = embed.shape[0]
    bn, bv = plan.block_n, plan.block_v
    x2 = x.reshape(n, d)
    t2 = _rows128(targets.astype(jnp.int32), n)
    lse2 = _rows128(lse.astype(jnp.float32), n)
    cl2 = _rows128(c_lse.astype(jnp.float32), n)
    ct2 = _rows128(c_tgt.astype(jnp.float32), n)
    row_spec = pl.BlockSpec((bn, 128), lambda i, j: (i, 0))

    with jax.named_scope(XENT_DX):
        dx = pl.pallas_call(
            functools.partial(_dx_kernel, plan=plan),
            name=XENT_DX,
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            grid=(n // bn, v // bv),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
                row_spec, row_spec, row_spec, row_spec,
            ],
            out_specs=pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            compiler_params=_params(plan),
            interpret=interpret,
        )(x2, embed, t2, lse2, cl2, ct2)

    # swapped grid: each vocab block streams every row block through its
    # accumulator
    row_spec_t = pl.BlockSpec((bn, 128), lambda j, i: (i, 0))
    with jax.named_scope(XENT_DE):
        de = pl.pallas_call(
            functools.partial(_de_kernel, plan=plan),
            name=XENT_DE,
            out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
            grid=(v // bv, n // bn),
            in_specs=[
                pl.BlockSpec((bn, d), lambda j, i: (i, 0)),
                pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
                row_spec_t, row_spec_t, row_spec_t, row_spec_t,
            ],
            out_specs=pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
            compiler_params=_params(plan),
            interpret=interpret,
        )(x2, embed, t2, lse2, cl2, ct2)
    return dx.reshape(b, t, d), de


# ---------------------------------------------------------------------------
# the step plan and the implementation dispatch
# ---------------------------------------------------------------------------

_MIB = 1 << 20
_ROW_BLOCKS = (2048, 1024, 512, 256)    # resident row blocks looked for
_BLOCK_V = 512                  # the widest vocab block looked for
_SUB_N = 512                    # rows of a score tile, where they divide


def _pick(t: int, pref: int, step: int) -> int | None:
    """Largest step-aligned block <= pref that divides t (the
    flash_attention._pick_block divisor search; step=128 for the lane
    dim, 8 for the sublane dim)."""
    b = min(pref, t) // step * step
    while b >= step:
        if t % b == 0:
            return b
        b -= step
    return None


def _working_set(bn: int, bv: int, sn: int, d: int, xb: int,
                 eb: int) -> int:
    """Bytes of VMEM the hungriest of the three kernels holds in a grid
    step: every operand and result block twice (the pipeline's two
    buffers), scratch once, and the body's temporaries (four of the
    score tile for it and what is made of it, and the second matmul's
    result on its way to the accumulator)."""
    rows = bn * 128 * 4                         # one [bn, 128] f32 block
    blocks = 2 * (bn * d * xb + bv * d * eb)    # x and embedding blocks
    tile = 4 * sn * bv * 4
    fwd = blocks + 2 * 3 * rows + 3 * rows + tile
    dx = blocks + 2 * (4 * rows + bn * d * 4) + tile + sn * d * 4
    de = blocks + 2 * (4 * rows + bv * d * 4) + tile + bv * d * 4
    return max(fwd, dx, de)


def _plan(n: int, v: int, d: int, x_bytes: int = 2, e_bytes: int = 2,
          vmem: int | None = None) -> _Plan | None:
    """The step plan for `n` rows against `v` embedding rows of width
    `d`, from the shapes, the element sizes and the chip's VMEM alone:
    the widest lane-aligned vocab block that divides `v`, and of the
    row blocks of `_ROW_BLOCKS` that divide `n` the largest whose
    working set fits the compiler's default scope, which asks nothing;
    where none does, the largest that fits half the VMEM, and the ask.
    (An ask is not free outside the kernels: XLA keeps buffers of its
    own in VMEM, and with any `vmem_limit_bytes` on these three calls
    it moved 134 MB of the one-chip cell's temporaries to HBM.) A row
    count none of them divides runs one tile a block. None where `n`
    has no sublane-aligned divisor or `v` no lane-aligned one: the
    caller takes the scan path."""
    bv = _pick(v, _BLOCK_V, 128)
    ragged = _pick(n, _ROW_BLOCKS[-1], 8)
    if bv is None or ragged is None:
        return None
    fits = []                   # (working set, block_n, sub_n), largest first
    for bn in (*(b for b in _ROW_BLOCKS if n % b == 0), ragged):
        sn = _SUB_N if bn % _SUB_N == 0 else bn
        fits.append((_working_set(bn, bv, sn, d, x_bytes, e_bytes), bn, sn))

    def largest_in(limit):
        return next((f for f in fits if f[0] <= limit), None)

    fit = largest_in(backend.SCOPED_VMEM_DEFAULT)
    if fit is not None:
        return _Plan(fit[1], bv, fit[2], None)
    need, bn, sn = (largest_in((vmem or backend.vmem_capacity()) // 2)
                    or fits[-1])
    # the estimate and a quarter for what it cannot see
    return _Plan(bn, bv, sn, -(-(need + need // 4) // _MIB) * _MIB)


def _resolve_impl(impl: str, x, embed, chunk: int):
    """-> ("scan", chunk) | ("pallas", _Plan). `chunk` is the scan
    path's; the kernels' blocks come from the shapes."""
    b, t, d = x.shape
    n, v = b * t, embed.shape[0]
    plan = _plan(n, v, d, x.dtype.itemsize, embed.dtype.itemsize)
    if impl == "auto":
        if plan is None:
            backend.note_fallback("fused_softmax_xent",
                                  f"rows={n}, vocab rows={v}")
        impl = "pallas" if backend.on_tpu() and plan is not None \
            else "scan"
    if impl == "scan":
        return "scan", chunk
    if impl == "pallas":
        if plan is None:
            raise ValueError(
                f"loss shape (rows={n}, vocab={v}) has no pallas block "
                "plan; use impl='scan'")
        return "pallas", plan
    raise ValueError(
        f"unknown fused-xent impl {impl!r} (expected 'auto' | 'pallas' "
        "| 'scan')")


def _lse_tgt_impl(x, embed, targets, chunk, impl):
    kind, arg = _resolve_impl(impl, x, embed, chunk)
    if kind == "scan":
        return _lse_tgt_scan(x, embed, targets, arg)
    return _lse_tgt_pallas(x, embed, targets, arg,
                           interpret=backend.interpret())


def _bwd_impl(x, embed, targets, lse, c_lse, c_tgt, chunk, impl):
    """f32 (dx, dembed); callers cast at the custom_vjp boundary (and
    the TP path psums in f32 first)."""
    kind, arg = _resolve_impl(impl, x, embed, chunk)
    if kind == "scan":
        return _bwd_scan(x, embed, targets, lse, c_lse, c_tgt, arg)
    return _bwd_pallas(x, embed, targets, lse, c_lse, c_tgt, arg,
                       interpret=backend.interpret())


def _int_zero(targets):
    return np.zeros(targets.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------------
# single-shard op: custom_vjp over (partial lse, partial target logit)
# ---------------------------------------------------------------------------
# Exposing the PAIR (not the nll) keeps one vjp serving both the local
# loss (nll = lse - tgt, cotangents (g, -g)) and any composition that
# reduces partials across shards first.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _lse_and_target(x, embed, targets, chunk, impl):
    return _lse_tgt_impl(x, embed, targets, chunk, impl)


def _lse_and_target_fwd(x, embed, targets, chunk, impl):
    lse, tgt = _lse_tgt_impl(x, embed, targets, chunk, impl)
    return (lse, tgt), (x, embed, targets, lse)


def _lse_and_target_bwd(chunk, impl, res, cts):
    x, embed, targets, lse = res
    c_lse, c_tgt = cts
    dx, de = _bwd_impl(x, embed, targets, lse, c_lse, c_tgt, chunk, impl)
    return dx.astype(x.dtype), de.astype(embed.dtype), _int_zero(targets)


_lse_and_target.defvjp(_lse_and_target_fwd, _lse_and_target_bwd)


# ---------------------------------------------------------------------------
# sharded composition: any mesh of more than one device
# ---------------------------------------------------------------------------
# The compiler cannot partition a Pallas kernel, so on a mesh the loss
# runs under shard_map whatever the axes: tokens split over the batch
# axes, and the embedding over the vocab axes when the rules shard it
# (tensor parallelism), with one psum of the partial terms.

def _flat_axes(spec):
    out = []
    for entry in spec:
        if entry is None:
            continue
        out.extend((entry,) if isinstance(entry, str) else tuple(entry))
    return tuple(out)


def _local_targets(ts, es, vocab_axes):
    """Target ids relative to this shard's vocab rows (out of range on
    every shard but the owner, which the kernels treat as no hit)."""
    if not vocab_axes:
        return ts
    return ts - jax.lax.axis_index(vocab_axes) * es.shape[0]


def _sharded_nll_and_lse(x, embed, targets, mesh, specs, chunk, impl):
    from ray_tpu.parallel.sharding import shard_map
    x_spec, e_spec, t_spec = specs
    vocab_axes = _flat_axes(e_spec[:1])

    def fwd(xs, es, ts):
        lse, tgt = _lse_tgt_impl(
            xs, es, _local_targets(ts, es, vocab_axes), chunk, impl)
        if vocab_axes:
            # psum of the partial log-sum-exp terms over the vocab
            # axes, max-shifted for stability; the partial target logit
            # is nonzero on exactly the shard owning the id, so a plain
            # psum recovers it
            mg = jax.lax.pmax(lse, vocab_axes)
            lse = mg + jnp.log(
                jax.lax.psum(jnp.exp(lse - mg), vocab_axes))
            tgt = jax.lax.psum(tgt, vocab_axes)
        return lse - tgt, lse

    f = shard_map(fwd, mesh=mesh, in_specs=(x_spec, e_spec, t_spec),
                  out_specs=(t_spec, t_spec), check_vma=False)
    return f(x, embed, targets)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_xent_sharded(x, embed, targets, mesh, specs, chunk, impl):
    nll, _ = _sharded_nll_and_lse(x, embed, targets, mesh, specs, chunk,
                                  impl)
    return nll


def _fused_xent_sharded_fwd(x, embed, targets, mesh, specs, chunk, impl):
    nll, lse = _sharded_nll_and_lse(x, embed, targets, mesh, specs,
                                    chunk, impl)
    return nll, (x, embed, targets, lse)


def _fused_xent_sharded_bwd(mesh, specs, chunk, impl, res, g):
    from ray_tpu.parallel.sharding import shard_map
    x, embed, targets, lse = res
    x_spec, e_spec, t_spec = specs
    vocab_axes = _flat_axes(e_spec[:1])
    # dembed sums over every axis that shards tokens (its batch
    # reduction); dx sums the per-vocab-shard partials
    batch_axes = _flat_axes(t_spec)

    def bwd(xs, es, ts, lse_s, gs):
        dx, de = _bwd_impl(xs, es, _local_targets(ts, es, vocab_axes),
                           lse_s, gs, -gs, chunk, impl)
        if vocab_axes:
            dx = jax.lax.psum(dx, vocab_axes)
        if batch_axes:
            de = jax.lax.psum(de, batch_axes)
        return dx.astype(xs.dtype), de.astype(es.dtype)

    f = shard_map(
        bwd, mesh=mesh,
        in_specs=(x_spec, e_spec, t_spec, t_spec, t_spec),
        out_specs=(x_spec, e_spec), check_vma=False)
    dx, de = f(x, embed, targets, lse, g)
    return dx, de, _int_zero(targets)


_fused_xent_sharded.defvjp(_fused_xent_sharded_fwd, _fused_xent_sharded_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def fused_softmax_xent(x, embed, targets, *, vocab_chunk: int = 512,
                       impl: str = "auto", mesh=None,
                       rules: dict | None = None):
    """Per-token nll [B, T] from pre-unembed activations, without ever
    materializing [B, T, V] logits (forward or backward).

    Same contract as ``spmd.softmax_xent(logits, targets)`` with the
    unembed matmul folded in: ``x [B, T, d_model]`` are the final-norm
    activations, ``embed [V, d_model]`` the tied embedding, and the
    implied logits are ``x @ embed.T`` accumulated in f32.

    With a `mesh` of more than one device the op runs under shard_map
    (`parallel.sharding.fused_xent_specs`): tokens split over the batch
    axes, and where the rules shard the vocab (default: over ``tensor``)
    each shard reduces its local rows and one psum of the partial
    log-sum-exp / target-logit terms combines them.

    `vocab_chunk` is the scan path's chunk. The kernels' blocks are
    `_plan(N, V, D, element sizes, VMEM)`'s, written from this sweep
    (one v5e, bf16, each kernel alone in its own jit, device ms a call
    by kernel name from a profiler trace, 5 calls; `(N, V, D)` =
    `(16384, 50304, 1024)` / `(8192, 50304, 2048)`, the benchmark's two
    cells a chip; one logits matmul is 8.57 ms at the MXU's peak, the
    forward does one, dX and dE two each):

    ====================================  ===========  ===========  ===========
    rows x vocab rows a step; body        xent_fwd     xent_dx      xent_de
    ====================================  ===========  ===========  ===========
    256 x 384 / 256 x 128; f32 upcast,    14.53/17.59  18.78/19.66  18.75/23.08
    `[rows, 1]` statistics (before)
    the same body, 1024 x 384             12.67/10.63  17.68/17.45  18.13/18.02
    the same body, 1024 x 128             20.81/14.71  18.51/17.86  22.35/19.17
    lanes alike, 1024 x 384                9.31/ 8.95  as below     as below
    per lane, 256 x 384                   11.23/ 9.98  18.64/18.01  18.68/18.27
    per lane, 512 x 384                    9.37/ 8.99  17.88/17.58  18.00/17.68
    per lane, 1024 x 384                   8.98/ 8.79  17.54/17.40  17.56/17.44
    per lane, 1024 x 128                   9.77/ 9.18  18.30/17.82  21.72/20.14
    per lane, 2048 x 384                   8.80/ 8.70  17.38/17.33  17.39/17.36
    per lane, 1024 x 384, `fori_loop`     10.45/ 9.79  18.92/18.35  19.02/18.45
    per lane, 1024 x 384, f32 upcast       8.98/ 8.79  17.55/17.40  17.59/17.47
    ====================================  ===========  ===========  ===========

    (Per-lane rows: tiles of 256 or 512 rows walked with a static loop;
    128-row tiles cost dX 0.2-0.4 ms, one tile of the whole block dE
    0.4.) Row blocks past 512 need more than the compiler's default 16
    MiB of scoped VMEM. The plan asks for it only where no row block
    fits the default, as at D = 2048 (1024 rows, 48 MiB asked), and
    takes 512 rows at D = 1024: an ask on these calls made XLA move
    134 MB of the one-chip cell's temporaries out of VMEM into HBM,
    for the 1.5 ms between the 512 and 2048 rows of the table.
    Operands as stored against an f32 upcast measured nothing; the
    stored type is what the MXU ran either way.
    """
    if x.ndim != 3 or embed.ndim != 2:
        raise ValueError(
            f"fused_softmax_xent wants x [B, T, D] and embed [V, D]; got "
            f"{x.shape} and {embed.shape}")
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import (
            fused_xent_specs,
            valid_spec_for,
        )
        # a dim its mesh axes do not divide stays whole on every shard
        specs = tuple(
            valid_spec_for(mesh, spec, a.shape) for spec, a in zip(
                fused_xent_specs(mesh, rules), (x, embed, targets)))
        return _fused_xent_sharded(x, embed, targets, mesh, specs,
                                   vocab_chunk, impl)
    lse, tgt = _lse_and_target(x, embed, targets, vocab_chunk, impl)
    return lse - tgt
