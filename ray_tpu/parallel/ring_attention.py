"""Ring attention: sequence/context parallelism over an ICI ring.

Net-new capability relative to the reference, which has no sequence
parallelism at all (SURVEY.md §5.7) — it scales sequence *count*, not
length. Here long sequences shard over the mesh's ``seq`` axis; K/V blocks
rotate around the ring via `jax.lax.ppermute` while each device accumulates
flash-attention-style running softmax statistics, so peak memory per device
is O(T/n) and communication overlaps compute on ICI.

Algorithm (Liu et al., Ring Attention; blockwise softmax from
Rabe & Staats / FlashAttention):

    for step in 0..n-1:
        score  = q_local @ k_ring.T          # [B,H,Tq,Tk] on MXU
        m_new  = max(m, rowmax(score))
        o      = o * exp(m - m_new) + exp(score - m_new) @ v_ring
        l      = l * exp(m - m_new) + rowsum(exp(score - m_new))
        (k_ring, v_ring) <- ppermute(+1 on the ring)

Causal masking uses global positions reconstructed from the ring step, so
the result is exactly equal to full attention on the gathered sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import shard_map

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, q_off, k_off, causal):
    """One blockwise-softmax accumulation step. q:[B,Tq,H,D] k/v:[B,Tk,H,D]
    m,l:[B,H,Tq] o:[B,Tq,H,D]; offsets are global token positions."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scale = qf.shape[-1] ** -0.5
    # [B,H,Tq,Tk]
    score = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, kf)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_off + jnp.arange(tq)[:, None]        # [Tq,1]
        kpos = k_off + jnp.arange(tk)[None, :]        # [1,Tk]
        score = jnp.where(qpos >= kpos, score, NEG_INF)
    m_new = jnp.maximum(m, score.max(axis=-1))        # [B,H,Tq]
    # exp moves: correction for previous accumulator, probs for this block
    corr = jnp.exp(m - m_new)                         # [B,H,Tq]
    p = jnp.exp(score - m_new[..., None])             # [B,H,Tq,Tk]
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_attn_sharded(q, k, v, axis_name: str, causal: bool):
    """Runs inside shard_map: q,k,v are the local sequence shards
    [B, T_local, H, D]."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    m0 = jnp.full((b, h, t_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    q_off = idx * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        k_blk, v_blk, m, l, o = carry
        # K/V block currently held came from rank (idx - s) mod n.
        src = (idx - s) % n
        k_off = src * t_local
        m, l, o = _block_attn(q, k_blk, v_blk, m, l, o, q_off, k_off,
                              causal)
        # Rotate AFTER compute; XLA overlaps the ppermute with the next
        # iteration's einsum when possible.
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    (k_fin, v_fin, m, l, o), _ = jax.lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n))
    del k_fin, v_fin
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                   axis_name: str = "seq"):
    """Sequence-parallel attention over `axis_name` of `mesh`.

    Args are global arrays [B, T, H, D] (sharded or not — shard_map
    partitions by the specs). Returns [B, T, H, D] sharded the same way.
    """
    if mesh.shape.get(axis_name, 1) == 1:
        # No ring: plain (still blockwise-stable) attention.
        m0 = jnp.full(
            (q.shape[0], q.shape[2], q.shape[1]), NEG_INF, jnp.float32)
        l0 = jnp.zeros_like(m0)
        o0 = jnp.zeros(q.shape, jnp.float32)
        m, l, o = _block_attn(q, k, v, m0, l0, o0, 0, 0, causal)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ring_attn_sharded, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = False,
                        window: int | None = None):
    """O(T^2)-memory reference for tests. k and v may have fewer heads
    than q (query head `h` reads head `h // (Hq // Hkv)`); with `window`,
    position i reads `i - window < j` only."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    if kf.shape[2] != qf.shape[2]:
        kf, vf = (jnp.repeat(x, qf.shape[2] // x.shape[2], axis=2)
                  for x in (kf, vf))
    scale = qf.shape[-1] ** -0.5
    score = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, kf)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t, t), bool), -window)
        score = jnp.where(mask, score, NEG_INF)
    p = jax.nn.softmax(score, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)
