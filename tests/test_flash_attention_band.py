"""`ops.flash_attention` with a window and with grouped heads, in
interpret mode on the CPU: forward and all three gradients against
`reference_attention` under the same mask, over tiles that put the band's
edge inside a tile, on a tile's boundary and past T; what the banded walks
visit (`executed_share`, the grid's length); and a call without either
lowers to what it did before."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import _Plan, executed_share, flash_attention
from ray_tpu.parallel.ring_attention import reference_attention


def case(t, hq, hkv, window, bq, bkv, tile, d=32, dtype=jnp.float32, seed=0):
    """(flash, reference) as (out, dq, dk, dv), B = 1, with the tile
    `_SUB` set for the call."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, h, d)), dtype)
               for h in (hq, hkv, hkv))

    def both(attn):
        def tot(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        grads, out = jax.grad(tot, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *grads)]

    was, fa._SUB = fa._SUB, tile
    try:
        got = both(lambda q, k, v: flash_attention(q, k, v, True, bq, bkv,
                                                   window))
    finally:
        fa._SUB = was
    return got, both(lambda q, k, v: reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True, window=window))


# (T, Hq, Hkv, window, block_q, block_kv, tile): the band's edge inside a
# tile (100, 130, 200), on a tile's boundary (128, 256), narrower than a
# tile (40, 1), wider than T (700), no window at grouped heads; blocks
# that are one grid step, several on either axis, and unequal
CASES = [
    (512, 4, 2, None, 256, 256, 128),
    (512, 8, 1, None, 512, 128, 128),
    (512, 4, 1, 100, 256, 256, 128),
    (512, 4, 2, 128, 128, 256, 128),
    (512, 2, 1, 200, 256, 128, 128),
    (1024, 2, 1, 130, 256, 512, 128),
    (1024, 8, 1, 256, 512, 256, 128),
    (512, 2, 2, 40, 512, 512, 128),
    (512, 2, 1, 700, 256, 256, 128),
    (512, 2, 1, 1, 256, 256, 128),
    (384, 3, 1, 129, 384, 384, 128),
    (512, 2, 1, 300, 128, 128, 128),
    (100, 4, 2, 30, 2048, 2048, 512),
]


@pytest.mark.parametrize(
    "t,hq,hkv,window,bq,bkv,tile", CASES,
    ids=[f"T{t}-{hq}over{hkv}-w{w}-{bq}x{bkv}x{tile}"
         for t, hq, hkv, w, bq, bkv, tile in CASES])
def test_band_and_groups_match_the_reference(t, hq, hkv, window, bq, bkv,
                                             tile):
    got, want = case(t, hq, hkv, window, bq, bkv, tile)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


def test_band_in_bfloat16_at_the_cell_s_group():
    """32 query heads over 4, head 128, bfloat16 operands: the norm of
    the error as `tests/test_models_ops.py` holds the plain kernels'."""
    got, want = case(512, 32, 4, 200, 256, 512, 128, d=128,
                     dtype=jnp.bfloat16)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.linalg.norm(a - b) < 8e-3 * np.linalg.norm(b), name


def test_the_band_s_edge_rows_and_columns():
    """The first and last row of every q tile and column of every kv
    tile: a walk one tile short loses a row's oldest keys, one long or a
    missing mask lets it see past the window."""
    t, w, tile = 1024, 300, 128
    got, want = case(t, 2, 1, w, 512, 256, tile)
    edges = [r for a in range(t // tile) for r in (a * tile,
                                                   (a + 1) * tile - 1)]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a[0, edges], b[0, edges], atol=1e-4,
                                   rtol=1e-4, err_msg=name)
    # a key further back than the window gets no gradient from the row
    q, k, v = (jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, t, 1, 32)), jnp.float32) for _ in range(3))
    dk = jax.grad(lambda k: jnp.sum(flash_attention(
        q, k, v, True, 512, 256, w)[0, w + 10]))(k)
    assert np.all(np.asarray(dk[0, :11]) == 0) and np.any(
        np.asarray(dk[0, 11]) != 0)


@pytest.mark.parametrize("plan,window", [
    (_Plan(1024, 2048, 512, 512), 1024),
    (_Plan(1024, 2048, 256, 256), 1024),
    (_Plan(1024, 2048, 512, 256), 1000),
    (_Plan(2048, 1024, 256, 512), 700),
    (_Plan(1024, 2048, 512, 512), 32768),
])
def test_executed_share_with_a_window(plan, window):
    """The walks visit the tiles that hold a pair of the band and no
    other: at T = 32,768 under a window of 1,024 a row block of 512
    visits three tiles of 512 (the edge's, a whole one, the diagonal's)
    where the triangle has up to 64; the dK/dV side visits as many."""
    t = 32768
    sq, skv = plan.sub_q, plan.sub_kv
    live = sum(b * skv <= (a + 1) * sq - 1 and (b + 1) * skv - 1 > a * sq
               - window for a in range(t // sq) for b in range(t // skv))
    share = executed_share(plan, t, True, window)
    assert share * t * t == live * sq * skv
    if window >= t:
        assert share == executed_share(plan, t, True)
    else:
        assert share < executed_share(plan, t, True) / 8
    if (sq, skv, window) == (512, 512, 1024):
        assert live == 3 * 64 - 3
    n_q = plan.block_q // sq
    by_kv = 0
    for k0 in range(0, t, skv):
        for qb in range(t // plan.block_q):
            lo, _ = fa._crossed(k0, skv, sq, qb, n_q)
            _, last = fa._past_band(k0, plan, window, qb, n_q, t)
            by_kv += max(int(last) - int(lo), 0)
    assert by_kv == live


def pallas_grids(fn, *args):
    """{kernel name: grid} of the `pallas_call`s in `fn`'s jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def grad_of(window, bq, bkv):
    return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, bq, bkv, window).astype(jnp.float32)), (0, 1, 2))


def test_a_banded_call_s_kernels_names_and_grids():
    """At the cell's shape a q block of 1,024 rows touches at most two kv
    blocks of 2,048 and a kv block at most three q blocks, eight query
    heads of a group one after the other; K and V keep their 4 heads."""
    shape = jax.ShapeDtypeStruct
    args = (shape((1, 32768, 32, 128), jnp.bfloat16),
            shape((1, 32768, 4, 128), jnp.bfloat16),
            shape((1, 32768, 4, 128), jnp.bfloat16))
    assert pallas_grids(grad_of(1024, 1024, 2048), *args) == {
        "flash_fwd_band": (32, 32, 2), "flash_dq_band": (32, 32, 2),
        "flash_dkv_band": (4, 16, 8 * 3)}
    assert pallas_grids(grad_of(None, 1024, 2048), *args) == {
        "flash_fwd": (32, 32, 16), "flash_dq": (32, 32, 16),
        "flash_dkv": (4, 16, 8 * 32)}
    text = str(jax.make_jaxpr(grad_of(1024, 1024, 2048))(*args))
    assert "bf16[1,32768,32,128]" in text           # q, and nothing of
    assert not re.search(r"= (broadcast_in_dim|concatenate)\[[^\]]*\] [a-z]+"
                         r":bf16\[1,32768,4,", text)
    # a window of T or more is no band
    assert set(pallas_grids(grad_of(32768, 1024, 2048), *args)) == {
        "flash_fwd", "flash_dq", "flash_dkv"}


# sha256 (first 16 hex digits) of the jaxpr of the causal gradient at the
# two accepted cells whose calls are one block on the sequential axis,
# taken on the parent commit of PR 57 (e0983a6), before the kernels knew
# a window or a group: (B, T, H, D) bfloat16. `kanana-2-30b-a3b`'s call
# (1024 x 2048 blocks at T 8192) is not held: its steps above the
# diagonal now stay on the last block they need.
UNCHANGED = {
    "datadecide-300m": ((8, 2048, 16, 64), "29e8c6cf808413b4"),
    "olmo-1b": ((4, 2048, 16, 128), "15bb81af17b95747"),
}
DIGESTS_JAX = "0.9.0"


@pytest.mark.parametrize("cell", sorted(UNCHANGED))
def test_a_plain_call_traces_to_what_it_did(cell, monkeypatch):
    if jax.__version__ != DIGESTS_JAX:
        pytest.skip(f"digests taken under jax {DIGESTS_JAX}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape, want = UNCHANGED[cell]
    fn = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True).astype(jnp.float32)), (0, 1, 2))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


def test_a_window_wants_a_causal_call():
    q = jnp.zeros((1, 128, 2, 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, 128, 128, 64)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q[:, :, :1], q, True)
