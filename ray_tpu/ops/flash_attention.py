"""Flash attention as Pallas TPU kernels — forward AND backward.

The reference has no custom kernels of its own (its GPU fast paths live in
torch/NCCL); on TPU the memory-bound op worth hand-scheduling is attention:
O(T^2) scores never touch HBM — K/V blocks stream through VMEM while
per-row running softmax statistics live in VMEM scratch across the
sequential kv grid dimension.

Layout: [B, T, H, D] public API (matching `ray_tpu.parallel.ring_attention`
so models switch impls freely). Internally [B*H, T, D], grid
(BH, T/block_q, T/block_kv) with the kv dimension innermost/sequential and
batch/query dimensions parallel.

Backward pass: two more Pallas kernels (FlashAttention-2 style).  The
forward saves the per-row logsumexp; backward precomputes
``delta = rowsum(dO * O)`` in XLA (bandwidth-trivial), then

- the **dQ kernel** iterates kv blocks innermost, accumulating
  ``dq += ds @ k`` in VMEM scratch, and
- the **dKV kernel** iterates q blocks innermost, accumulating
  ``dv += p^T @ dO`` and ``dk += ds^T @ q``,

so the O(T^2) probability matrix is rebuilt block-by-block in VMEM and
never written to HBM in either direction.  Under causal masking, blocks
strictly above the diagonal are predicated away in all three kernels.

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

# `checkpoint_name`s of the two things only the forward kernel can make:
# its output and its per-row logsumexp. A `jax.checkpoint` policy that
# saves both (`models/gpt.py`: "dots", "attn_out") goes straight to the
# dQ and dK/dV kernels in its backward; one that saves neither runs the
# forward kernel a second time to get them back.
SAVED_NAMES = ("attn_out", "attn_lse")


def _causal_mask(s, q_start, k_start, block_q, block_kv):
    qpos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                  causal: bool, block_q: int, block_kv: int,
                  with_lse: bool):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv
    # Under causal masking, kv blocks strictly above the diagonal band
    # contribute nothing; predicate the whole body away.
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)           # [bq, D]
        k = k_ref[0].astype(jnp.float32)           # [bkv, D]
        s = jax.lax.dot_general(
            q * sm_scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bkv]
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_kv)
        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [bq, bkv]
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=1,
                                                     keepdims=True)
        m_scr[:, :1] = m_new
        v = v_ref[0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, D]
        acc_scr[:] = acc_scr[:] * corr + pv

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        if with_lse:
            # lse broadcast across the 128-lane tile (TPU min tile width).
            lse_ref[0] = jnp.broadcast_to(
                m_scr[:, :1] + jnp.log(l_scr[:, :1]), lse_ref.shape[1:])


def _flash_bhtd(q, k, v, *, sm_scale: float, causal: bool, block_q: int,
                block_kv: int, interpret: bool, with_lse: bool):
    """q,k,v: [BH, T, D] with T divisible by both block sizes.

    Returns (out [BH, T, D], lse) where lse is [BH, T, 128] f32 (per-row
    logsumexp broadcast across the lane tile) when with_lse, else None."""
    bh, t, d = q.shape
    grid = (bh, t // block_q, t // block_kv)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, with_lse=with_lse)
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, t, 128), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)))
    with jax.named_scope(FLASH_FWD):
        res = pl.pallas_call(
            kernel,
            name=FLASH_FWD,
            out_shape=tuple(out_shape),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),   # m (col 0 used)
                pltpu.VMEM((block_q, 128), jnp.float32),   # l
                pltpu.VMEM((block_q, d), jnp.float32),     # acc
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    q_start, k_start, *, sm_scale: float, causal: bool,
                    block_q: int, block_kv: int):
    """Rebuild the probability block and dS from saved lse/delta — the
    shared core of both backward kernels, so a masking/scaling change can
    never diverge between dQ and dK/dV."""
    q = q_ref[0].astype(jnp.float32)            # [bq, D]
    k = k_ref[0].astype(jnp.float32)            # [bkv, D]
    v = v_ref[0].astype(jnp.float32)            # [bkv, D]
    do = do_ref[0].astype(jnp.float32)          # [bq, D]
    lse = lse_ref[0][:, :1]                     # [bq, 1]
    delta = delta_ref[0][:, :1]                 # [bq, 1]
    s = jax.lax.dot_general(
        q * sm_scale, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [bq, bkv]
    if causal:
        s = _causal_mask(s, q_start, k_start, block_q, block_kv)
    p = jnp.exp(s - lse)                        # [bq, bkv]
    dp = jax.lax.dot_general(
        do, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [bq, bkv]
    ds = p * (dp - delta)                       # [bq, bkv]
    return q, k, do, p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, causal: bool,
               block_q: int, block_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_kv
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        _, k, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv)
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds, k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [bq, D]

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                causal: bool, block_q: int, block_kv: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_kv
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _body():
        q, _, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv)
        dv_scr[:] += jax.lax.dot_general(
            p, do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [bkv, D]
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [bkv, D]

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, do, lse, delta, *, sm_scale: float,
                    causal: bool, block_q: int, block_kv: int,
                    interpret: bool):
    """All inputs [BH, T, D] (lse/delta [BH, T, 128] f32) -> (dq, dk, dv)."""
    bh, t, d = q.shape
    common = dict(sm_scale=sm_scale, causal=causal,
                  block_q=block_q, block_kv=block_kv)

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0))
    rowq = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    with jax.named_scope(FLASH_DQ):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common),
            name=FLASH_DQ,
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            grid=(bh, t // block_q, t // block_kv),
            in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    # dKV grid: kv blocks parallel, q blocks innermost/sequential.
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    rowq2 = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    with jax.named_scope(FLASH_DKV):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **common),
            name=FLASH_DKV,
            out_shape=(jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, t, d), v.dtype)),
            grid=(bh, t // block_kv, t // block_q),
            in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
            out_specs=(kspec2, kspec2),
            scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                            pltpu.VMEM((block_kv, d), jnp.float32)],
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


def _plan_blocks(t: int, block_q: int, block_kv: int):
    bq, bkv = _pick_block(t, block_q), _pick_block(t, block_kv)
    if bq is None or bkv is None:
        return None
    return bq, bkv


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 1024,
                    block_kv: int = 1024):
    """[B, T, H, D] attention; falls back to the XLA path on
    TPU-unfriendly shapes. Fully differentiable: both directions are
    Pallas kernels (backward = dQ + dKV kernels over saved lse).

    The default blocks have not been measured on this round's chip.
    Blocks shrink to the largest divisor of T, so ragged sequence
    lengths stay on the kernel path. The kernels cannot be partitioned
    by the compiler: on a mesh of more than one device the caller wraps
    this in a shard_map (`models.gpt._attention` does)."""
    out, _ = _flash_forward_impl(q, k, v, causal, block_q, block_kv,
                                 with_lse=False)
    return out


def _flash_forward_impl(q, k, v, causal, block_q, block_kv, with_lse):
    """Returns (out, lse|None). lse is None on the XLA fallback path or
    when with_lse=False (the inference variant, which skips the lse
    write entirely)."""
    b, t, h, d = q.shape
    plan = _plan_blocks(t, block_q, block_kv)
    if plan is None:
        backend.note_fallback("flash_attention", f"T={t}")
        return reference_attention(q, k, v, causal=causal), None
    block_q, block_kv = plan
    interpret = backend.interpret()
    d_pad = _head_pad_target(d)
    bhtd = lambda x: (_pad_heads(x, d_pad)
                      .transpose(0, 2, 1, 3).reshape(b * h, t, d_pad))
    out, lse = _flash_bhtd(bhtd(q), bhtd(k), bhtd(v), sm_scale=d ** -0.5,
                           causal=causal, block_q=block_q,
                           block_kv=block_kv, interpret=interpret,
                           with_lse=with_lse)
    out = out.reshape(b, h, t, d_pad).transpose(0, 2, 1, 3)
    return out[..., :d], lse


def _flash_fwd(q, k, v, causal, block_q, block_kv):
    out, lse = _flash_forward_impl(q, k, v, causal, block_q, block_kv,
                                   with_lse=True)
    if lse is None:
        return out, (q, k, v, None, None)
    # The kernel writes its lse across a 128-lane tile; one lane of it is
    # kept ([BH, T] f32, 1/128 of the bytes), so a policy can afford to
    # save it for every layer. `_flash_bwd` broadcasts it back, as it
    # does delta.
    out = checkpoint_name(out, SAVED_NAMES[0])
    lse = checkpoint_name(lse[..., 0], SAVED_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, res, g):
    q, k, v, out, lse = res
    if lse is None:   # XLA fallback path (static shape decision)
        _, vjp = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=causal),
            q, k, v)
        return vjp(g)

    b, t, h, d = q.shape
    block_q, block_kv = _plan_blocks(t, block_q, block_kv)
    interpret = backend.interpret()
    d_pad = _head_pad_target(d)
    # delta_i = rowsum(dO_i * O_i) — O(T*D) traffic, fine in XLA.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                          # [B, T, H]
    delta = delta.transpose(0, 2, 1).reshape(b * h, t)
    delta = jnp.broadcast_to(delta[..., None], (b * h, t, 128))
    lse = jnp.broadcast_to(lse[..., None], (b * h, t, 128))
    bhtd = lambda x: (_pad_heads(x, d_pad)
                      .transpose(0, 2, 1, 3).reshape(b * h, t, d_pad))
    dq, dk, dv = _flash_bwd_bhtd(
        bhtd(q), bhtd(k), bhtd(v), bhtd(g), lse, delta,
        sm_scale=d ** -0.5, causal=causal, block_q=block_q,
        block_kv=block_kv, interpret=interpret)
    unbhtd = lambda x: (x.reshape(b, h, t, d_pad)
                        .transpose(0, 2, 1, 3)[..., :d])
    return unbhtd(dq), unbhtd(dk), unbhtd(dv)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
