"""Dropless grouped matmul over the experts a chip holds.

A routed expert layer hands every token to `k` of the router's experts;
this chip holds `held` of them (`held_from` .. `held_from + held - 1`) and
computes their part of the result for the tokens routed to them, however
many or few those are. `experts_grouped` sorts the (token, expert) pairs
by expert, lays each held expert's tokens out as a group of whole row
tiles, and runs one gated MLP (gate, up, down) a group:

    y[t] = sum over the held experts e that t chose of
           weight[t, e] * (silu(x[t] Wg_e^T) * (x[t] Wu_e^T)) Wd_e

The layout has a static size, the worst case (every pair held, every group
with a ragged tile): `N * k + held * tile` rows. The kernel's grid walks
(row tile, slice of the expert width); tiles past the last group are
skipped and re-use the last live tile's blocks, so they move no bytes. An
expert's three matrices are stored `[held, F, D]`, width first, so that a
slice of the width is whole rows of D.

The pure-JAX path is the plain loop over the held experts, each over every
token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.ops.sparse_latent import resolve_impl

# Kernel names in the compiled program and the profiler's trace; PERF.md,
# section 3, lists them. The call sits in a `named_scope` of the same
# name. A prefill chunk's call takes the second, so that a trace tells
# the decode step's few tokens an expert from the chunk's many.
EXPERTS_GROUPED = "experts_grouped"
EXPERTS_GROUPED_PREFILL = "experts_grouped_prefill"

WIDTH_SLICE = 256           # rows of the expert width a grid step
VMEM_LIMIT = 64 * 1024 * 1024


def reference_experts_grouped(x, chosen, weights, w_gate, w_up, w_down,
                              held_from: int):
    """x [N, D]; chosen [N, k] i32 expert ids; weights [N, k] f32;
    w_gate, w_up, w_down [held, F, D] -> [N, D] f32."""
    held = w_gate.shape[0]

    def expert(y, e):
        i, wg, wu, wd = e
        mine = jnp.sum(jnp.where(chosen == held_from + i, weights, 0.0), -1)
        gate = jnp.einsum("nd,fd->nf", x, wg.astype(x.dtype),
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("nd,fd->nf", x, wu.astype(x.dtype),
                        preferred_element_type=jnp.float32)
        out = jnp.einsum("nf,fd->nd",
                         (jax.nn.silu(gate) * up).astype(x.dtype),
                         wd.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        return y + mine[:, None] * out, None

    return jax.lax.scan(
        expert, jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(held), w_gate, w_up, w_down))[0]


def held_pairs(chosen, held_from: int, held: int):
    """-> (local [N * k] i32: each pair's expert among the held ones, or
    `held` where it is not held; load [held] i32: pairs an expert)."""
    local = chosen.astype(jnp.int32).reshape(-1) - held_from
    local = jnp.where((local >= 0) & (local < held), local, held)
    return local, jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]


def group_layout(chosen, held_from: int, held: int, tile: int):
    """Where each (token, choice) pair goes when the pairs are sorted by
    held expert and every group starts on a tile.

    -> (dest [N, k] i32: the pair's row in the layout, -1 if its expert
        is not held; src [M] i32: the token of each row (0 for padding);
        tile_expert [T] i32, tile_block [T] i32: for each tile its expert
        and the tile whose blocks it reads (itself while live, the last
        live one after); n_live i32; load [held] i32: pairs an expert)."""
    n, k = chosen.shape
    m = n * k + held * tile
    local, load = held_pairs(chosen, held_from, held)
    padded = -(-load // tile) * tile
    starts = jnp.cumsum(padded) - padded                    # group's row 0
    order = jnp.argsort(local, stable=True)
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    first = jnp.cumsum(load) - load                         # in sorted order
    safe = jnp.minimum(local, held - 1)
    dest = jnp.where(local < held,
                     starts[safe] + rank - first[safe], -1)
    src = jnp.zeros((m,), jnp.int32).at[
        jnp.where(dest >= 0, dest, m)].set(
            jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    n_live = jnp.sum(padded) // tile
    tiles = m // tile
    t = jnp.arange(tiles, dtype=jnp.int32)
    ends = jnp.cumsum(padded) // tile                       # [held]
    expert_of = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1), held - 1)
    last = jnp.maximum(n_live - 1, 0)
    tile_block = jnp.minimum(t, last).astype(jnp.int32)
    tile_expert = expert_of[tile_block].astype(jnp.int32)
    return (dest.reshape(n, k), src, tile_expert, tile_block,
            n_live.astype(jnp.int32), load)


def _experts_kernel(expert_ref, block_ref, live_ref, x_ref, wg_ref, wu_ref,
                    wd_ref, o_ref, acc):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < live_ref[0])
    def _body():
        @pl.when(f == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        nt = (((1,), (1,)), ((), ()))
        gate = jax.lax.dot_general(x, wg_ref[0], nt,
                                   preferred_element_type=jnp.float32)
        up = jax.lax.dot_general(x, wu_ref[0], nt,
                                 preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)   # [tile, slice]
        acc[...] += jax.lax.dot_general(
            hidden, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _store():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def _grouped_pallas(xs, tile_expert, tile_block, n_live, w_gate, w_up,
                    w_down, tile: int, name: str):
    m, d = xs.shape
    _, width, _ = w_gate.shape
    fs = min(WIDTH_SLICE, width)
    while width % fs:
        fs -= 1
    nf = width // fs

    def weight_map(t, f, ex, blk, live):
        # past the last group: stay on the block the last live step read
        on = (t < live[0]).astype(jnp.int32)
        return ex[t], f * on + (nf - 1) * (1 - on), 0

    rows = pl.BlockSpec((tile, d), lambda t, f, ex, blk, live: (blk[t], 0))
    weight = pl.BlockSpec((1, fs, d), weight_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // tile, nf),
        in_specs=[rows, weight, weight, weight],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
    )
    with jax.named_scope(name):
        return pl.pallas_call(
            _experts_kernel, name=name,
            out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=backend.interpret(),
        )(tile_expert, tile_block, n_live[None], xs, w_gate, w_up, w_down)


def row_tile(n_pairs: int) -> int:
    """Rows a tile: 128 where a chunk of a prompt is routed, the 16 of a
    packed sublane tile for a decode step's few pairs."""
    return 128 if n_pairs >= 1024 else 16


def experts_grouped(x, chosen, weights, w_gate, w_up, w_down, *,
                    held_from: int, impl: str = "auto",
                    name: str = EXPERTS_GROUPED):
    """The held experts' part of a routed layer's result.

    x [N, D] normed activations; chosen [N, k] i32: each token's experts
    (ids over the router's whole width); weights [N, k] f32: their
    weights; w_gate, w_up, w_down [held, F, D]: experts `held_from` ..
    `held_from + held - 1`; name: the kernel's. -> ([N, D] f32, load
    [held] i32: the pairs each held expert got)."""
    held = w_gate.shape[0]
    if resolve_impl(impl) != "pallas":
        return reference_experts_grouped(
            x, chosen, weights, w_gate, w_up, w_down,
            held_from), held_pairs(chosen, held_from, held)[1]
    n, k = chosen.shape
    tile = row_tile(n * k)
    dest, src, tile_expert, tile_block, n_live, load = group_layout(
        chosen, held_from, held, tile)
    ys = _grouped_pallas(x[src], tile_expert, tile_block, n_live,
                         w_gate.astype(x.dtype), w_up.astype(x.dtype),
                         w_down.astype(x.dtype), tile, name)
    picked = ys[jnp.maximum(dest, 0)].astype(jnp.float32)   # [N, k, D]
    picked = jnp.where((dest >= 0)[..., None], picked, 0.0)
    return jnp.einsum("nk,nkd->nd", weights.astype(jnp.float32),
                      picked), load
