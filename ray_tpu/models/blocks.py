"""The layer below the model families: what two or more of them compute
the same way, each once. A projection, the norms, the two rotaries, the
unembedding, the gated MLP, the pool's writes and block moves, the routed
feed-forward and the counting that goes with it. It imports `jax`,
`ray_tpu.ops` and nothing of `ray_tpu/models/`; every family imports it,
and none reads another family's private name for any of this
(`tests/test_models_layering.py`). What only one family computes stays in
that family.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_experts


# projections, norms, rotaries

def mm(x, w, adt):
    return jnp.einsum("...d,df->...f", x, w.astype(adt),
                      preferred_element_type=jnp.float32).astype(adt)


def rms_norm(x, scale, eps: float = 1e-6):
    """x / rms(x) over the last axis, times `scale` in x's type."""
    scale = scale.astype(x.dtype)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x, scale, eps: float, bias=None):
    """Mean subtracted, float32 inside; `bias` where the norm has one."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_halves(x, pos, theta: float):
    """Rotary embedding on the last axis of x [N, H, d] at positions pos
    [N], in halves: (x[i], x[i + d/2]) turned by pos * theta^(-2i/d);
    float32 inside."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def rope_pairs(x, pos, theta: float):
    """Rotary embedding on the last axis of x [N, ..., d] at positions
    pos [N], interleaved pairs: (x[2i], x[2i + 1]) turned together;
    float32 inside."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def unembed(x, w, adt):
    """Final-normed x [..., D] over the output matrix w [V, D] -> float32
    logits [..., V]. A family that scales its logits or ties its table
    does so at the call."""
    return jnp.einsum("...d,vd->...v", x, w.astype(adt),
                      preferred_element_type=jnp.float32)


def weight(lp, name, adt):
    """Resolve one per-layer matmul weight: dequantize (f32 scale per
    output channel, then cast to the activation dtype) when the layer
    dict carries a ``"<name>_scale"`` sibling, plain cast otherwise —
    a static dict-key check, so f32 configs trace byte-identical code."""
    w = lp[name]
    s = lp.get(name + "_scale")
    if s is None:
        return w.astype(adt)
    return (w.astype(jnp.float32) * s[..., None, :]).astype(adt)


def cast_leaves(params, adt, float32_leaves=()):
    """A family's `load` where its steps read plain leaves: every
    floating leaf in the activations' type `adt`, those named in
    `float32_leaves` in float32. A leaf already there is returned as it
    is."""
    def cast(path, leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        name = path[-1].key if hasattr(path[-1], "key") else None
        want = jnp.float32 if name in float32_leaves else adt
        return leaf if leaf.dtype == want else leaf.astype(want)

    return jax.tree_util.tree_map_with_path(cast, params)


def gated_mlp(h, lp, adt, pet):
    """SwiGLU feed-forward on normed activations h [..., D] over `lp`'s
    `w_up`, `w_gate` and `w_down`, einsums emitting `pet`:
    -> (out [..., D], None)."""
    up = jnp.einsum("...d,df->...f", h, weight(lp, "w_up", adt),
                    preferred_element_type=pet).astype(adt)
    gate = jnp.einsum("...d,df->...f", h, weight(lp, "w_gate", adt),
                      preferred_element_type=pet).astype(adt)
    ff = jax.nn.silu(gate) * up
    return jnp.einsum("...f,fd->...d", ff, weight(lp, "w_down", adt),
                      preferred_element_type=pet).astype(adt), None


# the pool: block moves over every array, writes into head-major pages

def copy_block(cache, src, dst):
    """Copy physical block `src` onto `dst` in every entry of the pool —
    the device half of copy-on-write prefix sharing. Iterates the cache
    dict, so an int8 pool's scale rows travel with their payload and COW
    semantics never depend on the dtype (the block axis is axis 1 for
    payloads and scales alike). src/dst may be traced scalars, so one
    jit (with the cache donated) serves every copy the engine ever
    issues."""
    out = {}
    for name in cache:
        blk = jax.lax.dynamic_slice_in_dim(cache[name], src, 1, axis=1)
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], blk, dst, axis=1)
    return out


def gather_block(cache, idx):
    """Read physical block `idx` out of every entry of the pool — the
    device half of KV-block export for disaggregated prefill/decode
    serving. Returns a dict of [L, block_size, H, Dh] payload rows (and
    [L, block_size, H] scale rows for an int8 pool — iterating the
    cache dict means scales always travel with their payload, exactly
    like `copy_block`). `idx` may be a traced scalar, so one jit serves
    every block a prefill engine ever exports; the cache is NOT donated
    (the pool must survive the read)."""
    return {name: jax.lax.dynamic_index_in_dim(
                cache[name], idx, axis=1, keepdims=False)
            for name in cache}


def scatter_block(cache, block, idx):
    """Write one exported block's rows (the dict `gather_block`
    returned, re-hosted on the importing engine) onto physical block
    `idx` of this pool — the device half of KV-block import. Payload
    and scale entries land through the same index, so an int8 pool's
    quantized rows re-install byte-identical and the decode engine's
    attention dequantizes exactly what the prefill engine wrote. `idx`
    may be a traced scalar; donate the cache at jit time so imports
    update the pool in place."""
    return {name: jax.lax.dynamic_update_slice_in_dim(
                cache[name], block[name][:, None], idx, axis=1)
            for name in cache}


def row_index(pages, pos, pool):
    """Where a decode step's rows go in a head-major pool `[L, n_blocks,
    Hkv, bs, d]`: the flat position `page * bs + offset` of pos [B]
    through each row's pages [B, columns]; past the table's reach,
    `n_blocks * bs` (dropped by `write_rows`)."""
    n_blocks, bs = pool.shape[1], pool.shape[3]
    cols = pages.shape[1]
    page = jnp.minimum(pos // bs, cols - 1)[:, None]
    return jnp.where(
        pos < cols * bs,
        jnp.take_along_axis(pages, page, 1)[:, 0] * bs + pos % bs,
        n_blocks * bs)


def write_rows(pool, layer: int, rows, widx):
    """A decode step's rows [B, Hkv, d], one position a stream, into layer
    `layer` of a head-major pool at the flat positions widx [B] (`page *
    bs + offset`; `n_blocks * bs` and beyond: dropped). The pool is seen
    as rows of d, `(page * Hkv + head) * bs + offset`, so that what is
    scattered is whole contiguous rows: a window of (head, d), which a
    page does not hold side by side, makes XLA relayout the whole pool
    around the scatter."""
    layers, n_blocks, hkv, bs, d = pool.shape
    heads = jnp.arange(hkv, dtype=jnp.int32)
    at = ((widx // bs)[:, None] * hkv + heads) * bs + (widx % bs)[:, None]
    at = jnp.where((widx < n_blocks * bs)[:, None], at, n_blocks * hkv * bs)
    flat = pool.reshape(layers, n_blocks * hkv * bs, d)
    flat = flat.at[layer, at.reshape(-1)].set(
        rows.astype(pool.dtype).reshape(-1, d), mode="drop")
    return flat.reshape(pool.shape)


def write_chunk(pool, layer: int, rows, table, start, length):
    """A chunk's rows [C, Hkv, d] at positions start .. start + length -
    1 into layer `layer` of a head-major pool, a page at a time: each of
    the pages the chunk can touch is read, its rows that the chunk holds
    are replaced, and it is written back where it lies (a slice update in
    place; a page the chunk does not reach is written back as it was).
    The bucket's padding past `length` is written nowhere."""
    c, hkv, d = rows.shape
    bs, cols = pool.shape[3], table.shape[0]
    padded = jnp.pad(rows.astype(pool.dtype), ((bs, bs), (0, 0), (0, 0)))
    offs = jnp.arange(bs, dtype=jnp.int32)
    first = start // bs
    for i in range(-(-c // bs) + 1):
        page = first + i
        # the page's row r is the chunk's row page * bs + r - start
        lo = page * bs - start
        mine = jax.lax.dynamic_slice_in_dim(padded, lo + bs, bs)
        live = (lo + offs >= 0) & (lo + offs < length) & (page < cols)
        blk = table[jnp.minimum(page, cols - 1)]
        old = jax.lax.dynamic_slice(
            pool, (layer, blk, 0, 0, 0), (1, 1, hkv, bs, d))
        new = jnp.where(live[None, None, None, :, None],
                        mine.swapaxes(0, 1)[None, None], old)
        pool = jax.lax.dynamic_update_slice(pool, new, (layer, blk, 0, 0, 0))
    return pool


# routed experts, this chip's share

class Experts(NamedTuple):
    """What `routing` and `expert_layer` read of a family: its
    configuration builds one (`cfg.experts`). The chip holds experts
    `held_from ..` of a router `router_width` wide."""
    router_width: int
    experts_per_token: int
    norm_topk: bool
    held_from: int
    n_group: int = 1                 # `kept_groups`; 1: no groups
    topk_group: int = 1
    routed_scale: float = 1.0
    expert_round: str = "none"       # none | float8_e4m3fn (`rounded`)
    impl: str = "auto"               # auto | pallas | jax
    norm_eps: float = 0.0            # added to the chosen scores' sum
    score_func: str = "sigmoid"      # sigmoid | softmax (the whole width)
    # outputs from here up have no expert behind them: each returns its
    # input (a zero-computation expert); None: every output has weights
    identity_from: int | None = None


def kept_groups(biased, n_group: int, topk_group: int):
    """The group-limited choice's first half: biased scores [N, E] ->
    bool [N, n_group], true at the `topk_group` groups whose two largest
    scores sum highest."""
    n = biased.shape[0]
    best = jax.lax.top_k(biased.reshape(n, n_group, -1), 2)[0]
    _, keep = jax.lax.top_k(jnp.sum(best, -1), topk_group)
    return jnp.any(keep[..., None] == jnp.arange(n_group), axis=1)


def router_scores(h2, lp, score_func: str = "sigmoid"):
    """-> (scores [N, E]: a sigmoid an output, or a softmax over the
    router's whole width; scores + the expert bias where the layer has
    one), float32."""
    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[score_func]
    g = score(jnp.einsum(
        "nd,de->ne", h2.astype(jnp.float32),
        lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if "router_bias" not in lp:
        return g, g
    return g, g + lp["router_bias"].astype(jnp.float32)


def routing(h2, lp, experts: Experts):
    """A router's choice: -> (chosen [N, k] i32, weights [N, k] f32), in
    float32: a choice between two near-equal scores should not turn on
    the activations' rounding more than it must. Outputs without an
    expert (`identity_from`) are chosen like any other."""
    g, biased = router_scores(h2, lp, experts.score_func)
    if experts.n_group > 1:
        biased = jnp.where(jnp.repeat(
            kept_groups(biased, experts.n_group, experts.topk_group),
            experts.router_width // experts.n_group, 1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, experts.experts_per_token)
    weights = jnp.take_along_axis(g, chosen, -1)
    if experts.norm_topk:
        total = jnp.sum(weights, -1, keepdims=True)
        if experts.norm_eps:        # 0.0: the program it was, not one op more
            total = total + experts.norm_eps
        weights = weights / total
    return chosen.astype(jnp.int32), weights * experts.routed_scale


def rounded(a, grid: str):
    """`a` on `grid` ("float8_e4m3fn": three bits of mantissa, at most
    448; ties to even, the small exponents' coarser steps left out; "none":
    as it is), in a's own type; the gradient passes as through a cast. By
    arithmetic on the bits and not by a cast there and back: the TPU
    compiler drops a round trip through a type its chip has no unit for,
    and the control then rounds nothing (PERF.md, PR 38)."""
    if grid == "none":
        return a
    drop = 23 - 3                       # float32 mantissa bits to lose
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32((1 << (drop - 1)) - 1)
            + ((bits >> drop) & jnp.uint32(1))) & jnp.uint32(
                ~((1 << drop) - 1) & 0xFFFFFFFF)
    on_grid = jnp.clip(jax.lax.bitcast_convert_type(bits, jnp.float32),
                       -448.0, 448.0).astype(a.dtype)
    return a + jax.lax.stop_gradient(on_grid - a)


def expert_layer(h2, lp, experts: Experts, adt, live=None,
                 kernel: str = grouped_experts.EXPERTS_GROUPED,
                 every_load: bool = False):
    """A sparse layer's parts on normed h2 [N, D]: -> (routed: what
    the held experts add, shared: the shared expert's, or None where the
    layer's parameters hold no `ws_gate`, identity: `h2` times the sum of
    a row's weights for outputs without an expert, which every chip adds
    for its own rows, or None where `experts.identity_from` is, counts
    i32: pairs routed here, pairs routed to an expert anywhere, then the
    pairs each held expert got, or with `every_load` each output of the
    router's whole width, held or not; rows where `live` is false count
    nothing)."""
    chosen, weights = routing(h2, lp, experts)
    if live is not None:
        chosen = jnp.where(live[:, None], chosen, -1)
    identity = None
    if experts.identity_from is not None:
        free = chosen >= experts.identity_from
        identity = (jnp.sum(jnp.where(free, weights, 0.0), -1, keepdims=True)
                    * h2.astype(jnp.float32)).astype(adt)
    grid = experts.expert_round
    routed, load = grouped_experts.experts_grouped(
        rounded(h2, grid), chosen, weights, rounded(lp["we_gate"], grid),
        rounded(lp["we_up"], grid), rounded(lp["we_down"], grid),
        held_from=experts.held_from, impl=experts.impl, name=kernel)
    shared = None
    if "ws_gate" in lp:
        shared, _ = gated_mlp(
            h2, {"w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
                 "w_down": lp["ws_down"]}, adt, jnp.float32)
    here = jnp.sum(load)
    if every_load:
        load = jnp.sum(chosen[..., None] == jnp.arange(experts.router_width),
                       (0, 1), dtype=jnp.int32)
    to_expert = chosen >= 0
    if identity is not None:
        to_expert &= ~free
    counts = jnp.concatenate([
        jnp.stack([here, jnp.sum(to_expert, dtype=jnp.int32)]), load])
    return routed.astype(adt), shared, identity, counts


# what the programs count

def expert_totals(expert_counts, width: int):
    """The expert layers' counts summed, or `width` zeros where the
    layers that ran have no experts."""
    return sum(expert_counts) if expert_counts else jnp.zeros(
        (width,), jnp.int32)


def summarize(names, totals, held_count: int = 0) -> dict:
    """A program's counts `names`, and after them the `held_count` held
    experts' loads, summed over a window (None: nothing ran yet) -> the
    engine's `stats()` entries."""
    if totals is None:
        totals = [0] * (len(names) + held_count)
    out = {name: int(totals[i]) for i, name in enumerate(names)}
    if held_count:
        load = [int(v) for v in totals[len(names):]]
        mean = sum(load) / max(len(load), 1)
        out["expert_load_max_over_mean"] = max(load) / mean if mean else 0.0
    return out
