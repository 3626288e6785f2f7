"""Dropless grouped matmul over the experts a chip holds.

A routed expert layer hands every token to `k` of the router's experts;
this chip holds `held` of them (`held_from` .. `held_from + held - 1`) and
computes their part of the result for the tokens routed to them, however
many or few those are. `experts_grouped` sorts the (token, expert) pairs
by expert, lays each held expert's tokens out as a group of whole row
tiles, and runs one MLP a group, in one of two forms of an expert:

    gated (w_gate given; gate, up, down):
    y[t] = sum over the held experts e that t chose of
           weight[t, e] * (silu(x[t] Wg_e^T) * (x[t] Wu_e^T)) Wd_e
    ungated relu^2 (w_gate None; up, down):
    y[t] = sum ... of weight[t, e] * relu(x[t] Wu_e^T)^2 Wd_e

`glm-5.2`, `kanana-2-30b-a3b`, `ling-3.0-flash-vl`, `command-a-plus`,
`mellum2-12b-a2.5b` and `lfm2-8b-a1b` run the gated form at the model's
own width (`models/blocks.py:expert_layer`; `lfm2-8b-a1b` alone holds
every expert of its router); `nemotron-3-super` runs the ungated one in
its 1,024-wide latent (`models/mamba_moe.py`), forward only. Both are one
kernel body under the same names, so the same metrics read them.

The layout has a static size, the worst case (every pair held, every group
with a ragged tile): `N * k + held * tile` rows. The kernel's grid walks
(row tile, slice of the expert width), so each row tile of a group fetches
its expert's matrices again. `row_tile` therefore sizes the tile by what
the call can see, `N * k` pairs over `held` experts: about twice the pairs
an expert can expect, so that a group is one tile and its matrices are
read once a call; a larger tile costs `held * tile` rows of padding and
nothing else. Tiles past the last group are skipped and re-use the last
live tile's blocks, so they move no bytes. An expert's matrices (three, or
two) are stored `[held, F, D]`, width first, so that a slice of the width
is whole rows of D.

The pure-JAX path is the plain loop over the held experts, each over every
token; it differentiates as it is, and is what the kernels are compared
with.

The kernel path differentiates through a `custom_vjp` over the same group
layout (training: `EXPERTS_GROUPED_TRAIN` names the forward). Nothing of
the forward is kept but its inputs. `experts_grouped_dx` walks the row
tiles as the forward does, makes gate and up again, and gives each row's
dX, the gradient to the row's routing weight, and the three [tile, slice]
factors the weights' gradients are made of; `experts_grouped_dw` walks
the tiles once a slice of the width and sums each group's rows into its
expert's dW_gate, dW_up and dW_down, one reduction a group. Tiles past
the last group cost nothing in either, as in the forward; an expert that
got no token gets zeros.
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
# section 3, lists them. The call sits in a `named_scope` of the same
# name. A prefill chunk's call takes the second, so that a trace tells
# the decode step's few tokens an expert from the chunk's many; a call on
# the rows of both (`ServingFamily.tick`) the third, so that the readers
# of the first two, which divide a name's seconds by one program's runs,
# keep reading the programs they name.
EXPERTS_GROUPED = "experts_grouped"
EXPERTS_GROUPED_PREFILL = "experts_grouped_prefill"
EXPERTS_GROUPED_TICK = "experts_grouped_tick"
EXPERTS_GROUPED_TRAIN = "experts_grouped_train"
EXPERTS_GROUPED_DX = "experts_grouped_dx"
EXPERTS_GROUPED_DW = "experts_grouped_dw"

WIDTH_SLICE = 256           # rows of the expert width a grid step
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024

# `dot_general` dimension numbers
_NT = (((1,), (1,)), ((), ()))      # [a, c] x [b, c] -> [a, b]
_NN = (((1,), (0,)), ((), ()))      # [a, c] x [c, b] -> [a, b]
_TN = (((0,), (0,)), ((), ()))      # [c, a] x [c, b] -> [a, b]


def reference_experts_grouped(x, chosen, weights, w_gate, w_up, w_down,
                              held_from: int):
    """x [N, D]; chosen [N, k] i32 expert ids; weights [N, k] f32;
    w_gate (None: the ungated relu^2 form), w_up, w_down [held, F, D]
    -> [N, D] f32."""
    held = w_up.shape[0]

    def expert(y, e):
        i, wg, wu, wd = e
        mine = jnp.sum(jnp.where(chosen == held_from + i, weights, 0.0), -1)
        if wg is not None:
            gate = jnp.einsum("nd,fd->nf", x, wg.astype(x.dtype),
                              preferred_element_type=jnp.float32)
        up = jnp.einsum("nd,fd->nf", x, wu.astype(x.dtype),
                        preferred_element_type=jnp.float32)
        hidden = (jnp.square(jax.nn.relu(up)) if wg is None
                  else jax.nn.silu(gate) * up)
        out = jnp.einsum("nf,fd->nd", hidden.astype(x.dtype),
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


def _experts_kernel(expert_ref, block_ref, live_ref, x_ref, *refs):
    """One row tile against one slice of its expert's width. `refs`: the
    expert's matrices (gate, up, down; or up, down: the ungated relu^2
    form), the result's tile and the accumulator."""
    *w_in, wd_ref, o_ref, acc = refs
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < live_ref[0])
    def _body():
        @pl.when(f == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        if len(w_in) == 2:
            gate = jax.lax.dot_general(x, w_in[0][0], _NT,
                                       preferred_element_type=jnp.float32)
        up = jax.lax.dot_general(x, w_in[-1][0], _NT,
                                 preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up if len(w_in) == 2
                  else jnp.square(jax.nn.relu(up))).astype(x.dtype)
        acc[...] += jax.lax.dot_general(                    # [tile, slice]
            hidden, wd_ref[0], _NN, preferred_element_type=jnp.float32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _store():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def _width_slice(width: int) -> tuple[int, int]:
    """(rows of the expert width a grid step, steps): the largest divisor
    of the width up to `WIDTH_SLICE`; where that is not whole lane tiles
    and the width is (2,688 = 21 x 128 walks down to 224), the next
    divisor above that is (384)."""
    fs = min(WIDTH_SLICE, width)
    while width % fs:
        fs -= 1
    if fs % LANES and width % LANES == 0:
        fs = WIDTH_SLICE + LANES
        while width % fs:
            fs += LANES
    return fs, width // fs


def _held_slice(nf: int):
    """Index map of a [held, F, D] matrix's block for grid step (t, f):
    past the last group it stays on the block the last live step read."""
    def weight_map(t, f, ex, blk, live):
        on = (t < live[0]).astype(jnp.int32)
        return ex[t], f * on + (nf - 1) * (1 - on), 0
    return weight_map


def _grouped_pallas(xs, tile_expert, tile_block, n_live, w_gate, w_up,
                    w_down, tile: int, name: str):
    m, d = xs.shape
    fs, nf = _width_slice(w_up.shape[1])
    matrices = [w for w in (w_gate, w_up, w_down) if w is not None]
    rows = pl.BlockSpec((tile, d), lambda t, f, ex, blk, live: (blk[t], 0))
    weight = pl.BlockSpec((1, fs, d), _held_slice(nf))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // tile, nf),
        in_specs=[rows] + [weight] * len(matrices),
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
        )(tile_expert, tile_block, n_live[None], xs, *matrices)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dx_kernel(expert_ref, block_ref, live_ref, x_ref, dy_ref, rw_ref,
               wg_ref, wu_ref, wd_ref, dx_ref, drw_ref, dg_ref, du_ref,
               hs_ref, dx_acc, drw_acc):
    """One row tile against one slice of its expert's width: gate and up
    made again, then the slice's part of dX and of the rows' weight
    gradient, and its [tile, slice] factors of the three dW."""
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < live_ref[0])
    def _body():
        @pl.when(f == 0)
        def _init():
            dx_acc[...] = jnp.zeros_like(dx_acc)
            drw_acc[...] = jnp.zeros_like(drw_acc)

        x, dy = x_ref[...], dy_ref[...]
        f32 = jnp.float32
        gate = jax.lax.dot_general(x, wg_ref[0], _NT,
                                   preferred_element_type=f32)
        up = jax.lax.dot_general(x, wu_ref[0], _NT,
                                 preferred_element_type=f32)
        sig = jax.nn.sigmoid(gate)
        silu = gate * sig
        hidden = silu * up                                   # [tile, slice]
        # d hidden of the unweighted row; the row's weight scales it
        dh = jax.lax.dot_general(dy, wd_ref[0], _NT,
                                 preferred_element_type=f32)
        drw_acc[...] += jnp.sum(hidden * dh, axis=1, keepdims=True)
        rw = rw_ref[...][:, :1]                              # [tile, 1]
        dh = dh * rw
        d_up = (dh * silu).astype(x.dtype)
        d_gate = (dh * up * sig * (1.0 + gate * (1.0 - sig))).astype(x.dtype)
        dg_ref[...] = d_gate
        du_ref[...] = d_up
        hs_ref[...] = (hidden * rw).astype(x.dtype)
        dx_acc[...] += (
            jax.lax.dot_general(d_gate, wg_ref[0], _NN,
                                preferred_element_type=f32)
            + jax.lax.dot_general(d_up, wu_ref[0], _NN,
                                  preferred_element_type=f32))

        @pl.when(f == pl.num_programs(1) - 1)
        def _store():
            dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)
            drw_ref[...] = drw_acc[...]


def _dw_kernel(expert_ref, block_ref, live_ref, x_ref, dy_ref, dg_ref,
               du_ref, hs_ref, dwg_ref, dwu_ref, dwd_ref):
    """One slice of the width against one row tile: the tile's rows summed
    into its expert's three gradients, which stay in VMEM while the walk
    is inside the group."""
    t = pl.program_id(1)

    @pl.when(t < live_ref[0])
    def _body():
        first = (t == 0) | (expert_ref[jnp.maximum(t - 1, 0)]
                            != expert_ref[t])

        @pl.when(first)
        def _init():
            dwg_ref[...] = jnp.zeros_like(dwg_ref)
            dwu_ref[...] = jnp.zeros_like(dwu_ref)
            dwd_ref[...] = jnp.zeros_like(dwd_ref)

        f32 = jnp.float32
        x, dy = x_ref[...], dy_ref[...]
        dwg_ref[0] += jax.lax.dot_general(dg_ref[...], x, _TN,
                                          preferred_element_type=f32)
        dwu_ref[0] += jax.lax.dot_general(du_ref[...], x, _TN,
                                          preferred_element_type=f32)
        dwd_ref[0] += jax.lax.dot_general(hs_ref[...], dy, _TN,
                                          preferred_element_type=f32)


def _grouped_backward(xs, dys, row_w, tile_expert, tile_block, n_live,
                      w_gate, w_up, w_down, tile: int):
    """xs, dys [M, D]: each row's input and the gradient of its token's
    result (unweighted); row_w [M] f32: the row's routing weight, 0 on a
    padding row -> (dxs [M, D], d row_w [M] f32, dW_gate, dW_up, dW_down
    [held, F, D] f32; garbage where no tile is live: rows past the last
    group, experts without a token)."""
    m, d = xs.shape
    held, width, _ = w_gate.shape
    fs, nf = _width_slice(width)
    lanes = jnp.broadcast_to(row_w[:, None], (m, 128))
    scalars = (tile_expert, tile_block, n_live[None])
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)

    held_slice = _held_slice(nf)
    rows = pl.BlockSpec((tile, d), lambda t, f, ex, blk, live: (blk[t], 0))
    row_lanes = pl.BlockSpec((tile, 128),
                             lambda t, f, ex, blk, live: (blk[t], 0))
    factor = pl.BlockSpec(
        (tile, fs),
        lambda t, f, ex, blk, live: (blk[t], held_slice(t, f, ex, blk,
                                                        live)[1]))
    weight = pl.BlockSpec((1, fs, d), held_slice)
    factor_shape = jax.ShapeDtypeStruct((m, width), xs.dtype)
    with jax.named_scope(EXPERTS_GROUPED_DX):
        dxs, drw, d_gate, d_up, hidden = pl.pallas_call(
            _dx_kernel, name=EXPERTS_GROUPED_DX,
            out_shape=(jax.ShapeDtypeStruct((m, d), xs.dtype),
                       jax.ShapeDtypeStruct((m, 128), jnp.float32),
                       factor_shape, factor_shape, factor_shape),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(m // tile, nf),
                in_specs=[rows, rows, row_lanes, weight, weight, weight],
                out_specs=(rows, row_lanes, factor, factor, factor),
                scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32),
                                pltpu.VMEM((tile, 128), jnp.float32)]),
            compiler_params=params, interpret=backend.interpret(),
        )(*scalars, xs, dys, lanes, w_gate, w_up, w_down)

    # the width outermost, so that a group's tiles follow one another and
    # its expert's block of the result is written once
    rows2 = pl.BlockSpec((tile, d), lambda f, t, ex, blk, live: (blk[t], 0))
    factor2 = pl.BlockSpec((tile, fs),
                           lambda f, t, ex, blk, live: (blk[t], f))
    weight2 = pl.BlockSpec((1, fs, d),
                           lambda f, t, ex, blk, live: (ex[t], f, 0))
    grad = jax.ShapeDtypeStruct((held, width, d), jnp.float32)
    with jax.named_scope(EXPERTS_GROUPED_DW):
        dwg, dwu, dwd = pl.pallas_call(
            _dw_kernel, name=EXPERTS_GROUPED_DW,
            out_shape=(grad, grad, grad),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(nf, m // tile),
                in_specs=[rows2, rows2, factor2, factor2, factor2],
                out_specs=(weight2, weight2, weight2)),
            compiler_params=params, interpret=backend.interpret(),
        )(*scalars, xs, dys, d_gate, d_up, hidden)
    return dxs, drw[:, 0], dwg, dwu, dwd


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

ROW_TILES = (16, 32, 64, 128)     # whole packed sublane tiles of bfloat16


def row_tile(n_pairs: int, held: int) -> int:
    """Rows a tile, from the call's static shape: 128 where a chunk of a
    prompt is routed (1,024 pairs and more); under that the smallest of
    `ROW_TILES` with room for twice the pairs a held expert can expect.

    Under uniform routing that is `n_pairs / held` at most (where every
    expert of the router is held; less where a share is, since the other
    experts' pairs never reach the layout). A group that passes its tile
    has its expert's matrices fetched a second time, and the expectation
    itself is passed by nearly half the groups: at 512 pairs over 32
    experts (16 an expert) a tile of 16 is 1.434 tiles an expert reached,
    and 32, four standard deviations over, is 1.0001."""
    if n_pairs >= 1024:
        return ROW_TILES[-1]
    return next((t for t in ROW_TILES if t * held >= 2 * n_pairs),
                ROW_TILES[-1])


def _pick_rows(rows, dest):
    """rows [M, ...] -> [N, k, ...]: each pair's row, zeros where its
    expert is not held."""
    picked = rows[jnp.maximum(dest, 0)]
    return jnp.where((dest >= 0).reshape(dest.shape + (1,) * (rows.ndim - 1)),
                     picked, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped(name: str, tile: int, x, weights, w_gate, w_up, w_down, layout):
    """The held experts' part on the kernel path; layout = (dest, src,
    tile_expert, tile_block, n_live, load) of `group_layout`."""
    dest, src, tile_expert, tile_block, n_live, _ = layout
    ys = _grouped_pallas(x[src], tile_expert, tile_block, n_live,
                         None if w_gate is None else w_gate.astype(x.dtype),
                         w_up.astype(x.dtype), w_down.astype(x.dtype), tile,
                         name)
    # op for op the forward as it was before it had a backward: the
    # serving programs lower to what they were
    picked = ys[jnp.maximum(dest, 0)].astype(jnp.float32)   # [N, k, D]
    picked = jnp.where((dest >= 0)[..., None], picked, 0.0)
    return jnp.einsum("nk,nkd->nd", weights.astype(jnp.float32), picked)


def _grouped_fwd(name, tile, x, weights, w_gate, w_up, w_down, layout):
    """The forward where a backward follows (training; the gated form
    alone has one). The same values
    as `_grouped`; the rows are picked in their own type and widened
    inside the sum, which at 16,384 tokens x 6 keeps an [N, k, D] float32
    array out of HBM (8 ms a step of `kanana-2-30b-a3b.pretrain-8k`:
    PERF.md, PR 38)."""
    if w_gate is None:
        raise NotImplementedError("the ungated relu^2 form of "
                                  "experts_grouped has no backward pass")
    dest, src, tile_expert, tile_block, n_live, _ = layout
    ys = _grouped_pallas(x[src], tile_expert, tile_block, n_live,
                         w_gate.astype(x.dtype), w_up.astype(x.dtype),
                         w_down.astype(x.dtype), tile, name)
    out = jnp.einsum("nk,nkd->nd", weights.astype(jnp.float32),
                     _pick_rows(ys, dest).astype(jnp.float32))
    return out, (x, weights, w_gate, w_up, w_down, layout)


def _grouped_bwd(name, tile, res, dy):
    x, weights, w_gate, w_up, w_down, layout = res
    dest, src, tile_expert, tile_block, n_live, load = layout
    n, k = dest.shape
    m = src.shape[0]
    # each row's routing weight, by the row (0 on padding rows)
    row_w = jnp.zeros((m,), jnp.float32).at[
        jnp.where(dest >= 0, dest, m).reshape(-1)].set(
            weights.astype(jnp.float32).reshape(-1), mode="drop")
    dxs, drw, dwg, dwu, dwd = _grouped_backward(
        x[src], dy.astype(x.dtype)[src], row_w, tile_expert, tile_block,
        n_live, w_gate.astype(x.dtype), w_up.astype(x.dtype),
        w_down.astype(x.dtype), tile)
    dx = jnp.sum(_pick_rows(dxs, dest).astype(jnp.float32), 1)
    got = (load > 0)[:, None, None]
    return (dx.astype(x.dtype), _pick_rows(drw, dest).astype(weights.dtype),
            jnp.where(got, dwg, 0.0).astype(w_gate.dtype),
            jnp.where(got, dwu, 0.0).astype(w_up.dtype),
            jnp.where(got, dwd, 0.0).astype(w_down.dtype), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def experts_grouped(x, chosen, weights, w_gate, w_up, w_down, *,
                    held_from: int, impl: str = "auto",
                    name: str = EXPERTS_GROUPED):
    """The held experts' part of a routed layer's result; the gated form
    differentiable in x, weights and the three matrices on either path.

    x [N, D] normed activations; chosen [N, k] i32: each token's experts
    (ids over the router's whole width); weights [N, k] f32: their
    weights; w_gate, w_up, w_down [held, F, D]: experts `held_from` ..
    `held_from + held - 1`, `w_gate` None for experts without a gate
    matrix (relu^2 of the one projection); name: the forward kernel's.
    -> ([N, D] f32, load [held] i32: the pairs each held expert got)."""
    held = w_up.shape[0]
    if resolve_impl(impl) != "pallas":
        return reference_experts_grouped(
            x, chosen, weights, w_gate, w_up, w_down,
            held_from), held_pairs(chosen, held_from, held)[1]
    n, k = chosen.shape
    tile = row_tile(n * k, held)
    layout = group_layout(chosen, held_from, held, tile)
    return _grouped(name, tile, x, weights, w_gate, w_up, w_down,
                    layout), layout[-1]
