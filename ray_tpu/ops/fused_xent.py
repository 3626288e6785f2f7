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

- **pallas**: TPU forward + backward kernels (grid = rows x vocab
  blocks, per-row m/l/target-logit accumulators in VMEM scratch),
  mirroring flash_attention.py's structure.
- **scan**: a pure-JAX `lax.scan` over vocab chunks — the
  everywhere-correct path, and what `impl="auto"` picks off a TPU.

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

def _fwd_kernel(x_ref, e_ref, t_ref, lse_ref, tgt_ref,
                m_scr, l_scr, t_scr, *, block_v):
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    x = x_ref[...].astype(jnp.float32)              # [bn, D]
    e = e_ref[...].astype(jnp.float32)              # [bv, D]
    s = jax.lax.dot_general(
        x, e, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # [bn, bv]
    col = ji * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_scr[:, :1] = (l_scr[:, :1] * jnp.exp(m_prev - m_new)
                    + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_scr[:, :1] = m_new
    hit = col == t_ref[:, :1]
    t_scr[:, :1] += jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finalize():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])
        # broadcast across the 128-lane tile (TPU min tile width)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        tgt_ref[...] = jnp.broadcast_to(t_scr[:, :1], tgt_ref.shape)


def _recompute_dlog(x_ref, e_ref, t_ref, lse_ref, cl_ref, ct_ref,
                    v_start):
    """Rebuild one logits block from the saved lse and form dlogits —
    shared by the dx and dembed kernels so the masking/softmax math can
    never diverge between them (flash_attention._recompute_p_ds idiom)."""
    x = x_ref[...].astype(jnp.float32)              # [bn, D]
    e = e_ref[...].astype(jnp.float32)              # [bv, D]
    s = jax.lax.dot_general(
        x, e, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # [bn, bv]
    col = v_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.exp(s - lse_ref[:, :1])
    hit = (col == t_ref[:, :1]).astype(jnp.float32)
    dlog = cl_ref[:, :1] * p + ct_ref[:, :1] * hit  # [bn, bv]
    return x, e, dlog


def _dx_kernel(x_ref, e_ref, t_ref, lse_ref, cl_ref, ct_ref, dx_ref,
               dx_scr, *, block_v):
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    _, e, dlog = _recompute_dlog(x_ref, e_ref, t_ref, lse_ref, cl_ref,
                                 ct_ref, ji * block_v)
    dx_scr[:] += jax.lax.dot_general(
        dlog, e, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [bn, D]

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finalize():
        dx_ref[...] = dx_scr[:]


def _de_kernel(x_ref, e_ref, t_ref, lse_ref, cl_ref, ct_ref, de_ref,
               de_scr, *, block_v):
    # grid is (vocab blocks, row blocks): rows are the inner sequential
    # dim so the dembed accumulator lives in scratch across them
    ii = pl.program_id(1)

    @pl.when(ii == 0)
    def _init():
        de_scr[:] = jnp.zeros_like(de_scr)

    x, _, dlog = _recompute_dlog(x_ref, e_ref, t_ref, lse_ref, cl_ref,
                                 ct_ref, pl.program_id(0) * block_v)
    de_scr[:] += jax.lax.dot_general(
        dlog, x, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [bv, D]

    @pl.when(ii == pl.num_programs(1) - 1)
    def _finalize():
        de_ref[...] = de_scr[:]


def _rows128(a, n):
    """[B, T] -> [N, 128] f32/int32 broadcast across the lane tile."""
    return jnp.broadcast_to(a.reshape(n, 1), (n, 128))


def _lse_tgt_pallas(x, embed, targets, block_n, block_v, interpret):
    b, t, d = x.shape
    n = b * t
    v = embed.shape[0]
    grid = (n // block_n, v // block_v)
    row_spec = pl.BlockSpec((block_n, 128), lambda i, j: (i, 0))
    with jax.named_scope(XENT_FWD):
        lse2, tgt2 = pl.pallas_call(
            functools.partial(_fwd_kernel, block_v=block_v),
            name=XENT_FWD,
            out_shape=(jax.ShapeDtypeStruct((n, 128), jnp.float32),) * 2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
                pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
                row_spec,
            ],
            out_specs=(row_spec, row_spec),
            scratch_shapes=[pltpu.VMEM((block_n, 128), jnp.float32)] * 3,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(x.reshape(n, d), embed, _rows128(targets.astype(jnp.int32), n))
    return lse2[:, 0].reshape(b, t), tgt2[:, 0].reshape(b, t)


def _bwd_pallas(x, embed, targets, lse, c_lse, c_tgt, block_n, block_v,
                interpret):
    b, t, d = x.shape
    n = b * t
    v = embed.shape[0]
    x2 = x.reshape(n, d)
    t2 = _rows128(targets.astype(jnp.int32), n)
    lse2 = _rows128(lse.astype(jnp.float32), n)
    cl2 = _rows128(c_lse.astype(jnp.float32), n)
    ct2 = _rows128(c_tgt.astype(jnp.float32), n)
    row_spec = pl.BlockSpec((block_n, 128), lambda i, j: (i, 0))

    with jax.named_scope(XENT_DX):
        dx = pl.pallas_call(
            functools.partial(_dx_kernel, block_v=block_v),
            name=XENT_DX,
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            grid=(n // block_n, v // block_v),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
                pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
                row_spec, row_spec, row_spec, row_spec,
            ],
            out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(x2, embed, t2, lse2, cl2, ct2)

    # swapped grid: each vocab block streams every row block through its
    # accumulator
    row_spec_t = pl.BlockSpec((block_n, 128), lambda j, i: (i, 0))
    with jax.named_scope(XENT_DE):
        de = pl.pallas_call(
            functools.partial(_de_kernel, block_v=block_v),
            name=XENT_DE,
            out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
            grid=(v // block_v, n // block_n),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
                pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
                row_spec_t, row_spec_t, row_spec_t, row_spec_t,
            ],
            out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            scratch_shapes=[pltpu.VMEM((block_v, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(x2, embed, t2, lse2, cl2, ct2)
    return dx.reshape(b, t, d), de


# ---------------------------------------------------------------------------
# implementation dispatch
# ---------------------------------------------------------------------------

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


def _plan(n: int, v: int, block_n: int, block_v: int):
    bn, bv = _pick(n, block_n, 8), _pick(v, block_v, 128)
    return (bn, bv) if bn and bv else None


def _resolve_impl(impl: str, n: int, v: int, chunk: int):
    """-> ("scan", chunk) | ("pallas", (block_n, block_v)). `chunk`
    doubles as the preferred pallas vocab block."""
    plan = _plan(n, v, block_n=256, block_v=max(chunk, 128))
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
    b, t, _ = x.shape
    kind, arg = _resolve_impl(impl, b * t, embed.shape[0], chunk)
    if kind == "scan":
        return _lse_tgt_scan(x, embed, targets, arg)
    return _lse_tgt_pallas(x, embed, targets, *arg,
                           interpret=backend.interpret())


def _bwd_impl(x, embed, targets, lse, c_lse, c_tgt, chunk, impl):
    """f32 (dx, dembed); callers cast at the custom_vjp boundary (and
    the TP path psums in f32 first)."""
    b, t, _ = x.shape
    kind, arg = _resolve_impl(impl, b * t, embed.shape[0], chunk)
    if kind == "scan":
        return _bwd_scan(x, embed, targets, lse, c_lse, c_tgt, arg)
    return _bwd_pallas(x, embed, targets, lse, c_lse, c_tgt, *arg,
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
