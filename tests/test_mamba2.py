"""`ops/mamba2.py`: the step and chunk kernels (interpret mode) and their
plain paths against the token-by-token recurrence; the ring of decode
tokens beside a state (what it holds between folds, rows that fold in
different steps, idle rows, stale rings, the control, the fold against
float64)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mamba2

F32 = jnp.float32
H, G, P, N = 4, 2, 64, 128          # the kernels' widths, few heads
L, NB = 2, 5
# a pair of heads a lane tile over one tile of rows; a head a lane tile
# over two
WIDTHS = pytest.mark.parametrize("p,n", [(64, 128), (128, 256)],
                                 ids=["64x128", "128x256"])


def draws(key, t, h=H, g=G, p=P, n=N, dtype=F32):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (t, h, p), F32).astype(dtype)
    # steps from a thousandth to a few: heads that keep thousands of
    # positions beside heads that forget in one
    dt = jnp.exp(jax.random.uniform(ks[1], (t, h), F32, -7.0, 1.0))
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), F32, 0.0, 2.7))
    b = jax.random.normal(ks[3], (t, g, n), F32).astype(dtype)
    c = jax.random.normal(ks[4], (t, g, n), F32).astype(dtype)
    return x, dt, a, b, c


def pool_with(key, h=H, p=P, n=N):
    t = mamba2.tile_heads(p)
    return jax.random.normal(key, (L, NB, h // t, n, t * p), F32)


def rings_with(key=None, h=H, g=G, p=P, n=N):
    """Empty rings, or with `key` stale ones: whatever a freed block's
    last sequence left in them."""
    ring = mamba2.ring_array(L, NB, h, g, p, n)
    return ring if key is None else jax.random.normal(key, ring.shape, F32)


def state_with_its_ring(pool, ring, layer, block, held, h, p):
    """What a block's state would be with its ring's `held` tokens folded
    in, by head [H, P, N]: exp(l_t) S_t0 + sum_s exp(l_t - l_s) (d_s x_s)
    B_s^T, in float32 from the arrays as stored."""
    s = mamba2.to_heads(pool[layer, block], p)
    if not held:
        return s
    xd, b, logs = mamba2._unpacked(ring[layer, block, :held], h, G, p,
                                   s.shape[-1])
    b = mamba2._by_head(b, h)                                # [held, H, N]
    w = jnp.exp(logs[-1][None] - logs)                       # [held, H]
    return jnp.exp(logs[-1])[:, None, None] * s + jnp.einsum(
        "sh,shp,shn->hpn", w, xd, b, precision=jax.lax.Precision.HIGHEST)


def test_pairs_and_heads_are_inverse():
    s = jax.random.normal(jax.random.key(0), (3, H, P, N), F32)
    stored = mamba2.to_pairs(s)
    assert stored.shape == (3, H // 2, N, 2 * P)
    np.testing.assert_array_equal(mamba2.to_heads(stored, P), s)
    # pool[i, n, j * P + p] = S_{2 i + j}[p, n]
    np.testing.assert_array_equal(stored[1, 1, 5, P + 3], s[1, 3, 3, 5])


def test_a_head_that_fills_the_lanes_is_stored_alone():
    s = jax.random.normal(jax.random.key(0), (3, H, 128, 256), F32)
    stored = mamba2.to_pairs(s)
    assert stored.shape == (3, H, 256, 128)
    np.testing.assert_array_equal(mamba2.to_heads(stored, 128), s)
    np.testing.assert_array_equal(stored[1, 3, 200, 7], s[1, 3, 7, 200])


# y is a float32 sum over N: at 256 it is twice as long as at 128, and so
# is the room for its order
@pytest.mark.parametrize("p,n,atol", [(64, 128, 4e-5), (128, 256, 8e-5)],
                         ids=["64x128", "128x256"])
@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_steps_match_the_recurrence_token_by_token(impl, p, n, atol):
    """2 RING + 3 decode steps of two sequences, the second of which
    starts three steps after the first (its row idle until then: block
    0), so the rows' rings fill in different steps, over rings that hold
    a freed block's stale tokens: `y` at every step; the state after
    every fold; at a step that does not fold the state as it was and,
    with its ring's tokens, the recurrence's. The idle row moves
    nothing, of the trash block either; no other layer and no other
    block is touched."""
    ring, late = mamba2.RING, 3
    steps = 2 * ring + 3
    to_heads = functools.partial(mamba2.to_heads, p=p)
    x, dt, a, b, c = draws(jax.random.key(1), 3 * steps, p=p, n=n)
    x, dt, b, c = (v.reshape((steps, 3) + v.shape[1:]) for v in (x, dt, b, c))
    pool = first = pool_with(jax.random.key(2), p=p, n=n)
    rings = stale = rings_with(jax.random.key(3), p=p, n=n)
    held = jnp.zeros((3,), jnp.int32)
    want = {}
    for row, blk, since in ((0, 2, 0), (2, 4, late)):
        want[row] = mamba2.mamba2_recurrent(
            x[since:, row], dt[since:, row], a, b[since:, row],
            c[since:, row], to_heads(pool[1, blk]))
    # the recurrence's state after each of a row's tokens
    states = {row: [mamba2.mamba2_recurrent(
        x[since:t + 1, row], dt[since:t + 1, row], a, b[since:t + 1, row],
        c[since:t + 1, row], to_heads(first[1, blk]))[1]
        for t in range(since, steps)]
        for row, blk, since in ((0, 2, 0), (2, 4, late))}
    folds = 0
    for t in range(steps):
        blocks = jnp.array([2, 0, 4 if t >= late else 0], jnp.int32)
        fold, after = mamba2.ring_after(blocks, held)
        y, new, rings = mamba2.mamba2_step(
            x[t], dt[t], a, b[t], c[t], pool, rings, 1, blocks, held,
            impl=impl)
        for row, blk, since in ((0, 2, 0), (2, 4, late)):
            if t < since:
                continue
            np.testing.assert_allclose(y[row], want[row][0][t - since],
                                       rtol=2e-5, atol=atol)
            scale = float(jnp.max(jnp.abs(states[row][t - since])))
            if bool(fold[row]):
                folds += 1
                np.testing.assert_allclose(
                    to_heads(new[1, blk]), states[row][t - since],
                    rtol=1e-5, atol=1e-6 * scale)
            else:
                np.testing.assert_array_equal(new[1, blk], pool[1, blk])
                np.testing.assert_allclose(
                    state_with_its_ring(new, rings, 1, blk, int(after[row]),
                                        H, p),
                    states[row][t - since], rtol=1e-5, atol=1e-6 * scale)
        assert int(after[1]) == 0 and not bool(fold[1])
        pool, held = new, after
    assert folds == (steps // ring) + ((steps - late) // ring)
    # rows of one batch folded in different steps
    assert [int(h) for h in held] == [steps % ring, 0, (steps - late) % ring]
    # the idle row, the other layer and the other blocks: as they were
    np.testing.assert_array_equal(pool[0], first[0])
    for blk in (0, 1, 3):
        np.testing.assert_array_equal(pool[1, blk], first[1, blk])
    np.testing.assert_array_equal(rings[0], stale[0])
    for blk in (1, 3):
        np.testing.assert_array_equal(rings[1, blk], stale[1, blk])
    # the trash block's: what an entry holds of a token (the plain path
    # packs an idle row's entries anew, padding and all)
    for got, was in zip(mamba2._unpacked(rings[1, 0], H, G, p, n),
                        mamba2._unpacked(stale[1, 0], H, G, p, n)):
        np.testing.assert_array_equal(got, was)


@WIDTHS
@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_a_first_chunk_then_steps_over_a_stale_ring(impl, p, n):
    """A freed block's state and rings hold its last sequence's numbers;
    a first chunk reads the state as zeros and its caller leaves the
    block's rings empty (`held` 0), so the steps after it read as the
    recurrence does over the whole sequence."""
    t, steps = 128, mamba2.RING + 2
    x, dt, a, b, c = draws(jax.random.key(11), t + steps, p=p, n=n)
    pool = pool_with(jax.random.key(12), p=p, n=n)
    rings = rings_with(jax.random.key(13), p=p, n=n)
    want_y, want_s = mamba2.mamba2_recurrent(x, dt, a, b, c)
    _, pool = mamba2.mamba2_chunk(x[:t], dt[:t], a, b[:t], c[:t], pool, 0, 3,
                                  True, t, impl=impl)
    blocks = jnp.array([3], jnp.int32)
    held = jnp.zeros((1,), jnp.int32)       # what a chunk leaves
    tol = 2e-4 if impl == "jax" else 6e-2   # the chunk kernel's bfloat16
    scale = float(jnp.max(jnp.abs(want_y)))
    for i in range(t, t + steps):
        y, pool, rings = mamba2.mamba2_step(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], pool, rings,
            0, blocks, held, impl=impl)
        _, held = mamba2.ring_after(blocks, held)
        np.testing.assert_allclose(y[0], want_y[i], atol=tol * scale)
    np.testing.assert_allclose(
        state_with_its_ring(pool, rings, 0, 3, int(held[0]), H, p), want_s,
        atol=tol * float(jnp.max(jnp.abs(want_s))))


@WIDTHS
def test_the_fold_against_float64(p, n):
    """A ring's tokens folded into a state by the kernel, beside the
    per-token float32 multiply-add, both against the same sum in float64:
    the reordered sum loses nothing (errors of one order)."""
    ring = mamba2.RING
    x, dt, a, b, c = draws(jax.random.key(21), ring, p=p, n=n)
    pool = pool_with(jax.random.key(22), p=p, n=n)
    s64 = np.asarray(mamba2.to_heads(pool[0, 1], p), np.float64)
    a64 = np.asarray(a, np.float64)
    for i in range(ring):
        d = np.asarray(dt[i], np.float64)
        bh = np.repeat(np.asarray(b[i], np.float64), H // G, axis=0)
        s64 = np.exp(d * a64)[:, None, None] * s64 + (
            np.asarray(x[i], np.float64) * d[:, None])[..., None] \
            * bh[:, None, :]
    plain = mamba2.mamba2_recurrent(x, dt, a, b, c,
                                    mamba2.to_heads(pool[0, 1], p))[1]
    rings, blocks = rings_with(p=p, n=n), jnp.array([1], jnp.int32)
    held = jnp.zeros((1,), jnp.int32)
    for i in range(ring):
        _, pool, rings = mamba2.mamba2_step(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], pool, rings,
            0, blocks, held, impl="pallas")
        _, held = mamba2.ring_after(blocks, held)
    assert int(held[0]) == 0                # folded with the last token
    errs = [float(np.max(np.abs(np.asarray(v, np.float64) - s64)))
            for v in (mamba2.to_heads(pool[0, 1], p), plain)]
    print(f"fold against float64 at {p} x {n}: kernel {errs[0]:.3g}, "
          f"per-token float32 {errs[1]:.3g}, on states of "
          f"{np.max(np.abs(s64)):.3g}")
    assert errs[0] < 4 * errs[1] + 1e-6


@WIDTHS
@pytest.mark.parametrize("impl,tol", [("jax", 2e-4), ("pallas", 6e-2)])
@pytest.mark.parametrize("first", [True, False], ids=["first", "carried"])
def test_chunk_matches_the_recurrence(impl, tol, first, p, n):
    """From zero (`first`, whatever the block holds) and from a carried
    state; the kernel feeds the MXU bfloat16, the plain path float32."""
    t = 256
    x, dt, a, b, c = draws(jax.random.key(3), t, p=p, n=n)
    pool = pool_with(jax.random.key(4), p=p, n=n)
    to_heads = functools.partial(mamba2.to_heads, p=p)
    y, new = mamba2.mamba2_chunk(x, dt, a, b, c, pool, 0, 3, first, t,
                                 impl=impl)
    s0 = None if first else to_heads(pool[0, 3])
    want_y, want_s = mamba2.mamba2_recurrent(x, dt, a, b, c, s0)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(y, want_y, atol=tol * scale)
    np.testing.assert_allclose(
        to_heads(new[0, 3]), want_s,
        atol=tol * float(jnp.max(jnp.abs(want_s))))
    np.testing.assert_array_equal(new[1], pool[1])
    np.testing.assert_array_equal(new[0, 2], pool[0, 2])


@WIDTHS
@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_chunk_padding_leaves_the_state_bit_identical(impl, p, n):
    """A chunk of 100 live positions in a bucket of 128 and in one of
    384: the same state bit for bit, the same live outputs."""
    x, dt, a, b, c = draws(jax.random.key(5), 384, p=p, n=n)
    pool = pool_with(jax.random.key(6), p=p, n=n)
    to_heads = functools.partial(mamba2.to_heads, p=p)
    small = mamba2.mamba2_chunk(x[:128], dt[:128], a, b[:128], c[:128],
                                pool, 1, 2, False, 100, impl=impl)
    large = mamba2.mamba2_chunk(x, dt, a, b, c, pool, 1, 2, False, 100,
                                impl=impl)
    np.testing.assert_array_equal(small[1], large[1])
    np.testing.assert_array_equal(small[0][:100], large[0][:100])
    live = mamba2.mamba2_recurrent(x[:100], dt[:100], a, b[:100], c[:100],
                                   to_heads(pool[1, 2]))[1]
    np.testing.assert_allclose(
        to_heads(small[1][1, 2]), live,
        atol=(2e-4 if impl == "jax" else 6e-2)
        * float(jnp.max(jnp.abs(live))))


def test_chunks_then_steps_carry_one_state():
    """Two chunks and three steps through the plain paths against one
    pass of the recurrence: the state a chunk leaves is the one the next
    chunk and the steps start from."""
    t = 40
    x, dt, a, b, c = draws(jax.random.key(7), t, h=4, g=2, p=8, n=16)
    pool = jnp.zeros((1, 3, 2, 16, 16), F32)
    ys = []
    y, pool = mamba2.mamba2_chunk(x[:16], dt[:16], a, b[:16], c[:16], pool,
                                  0, 1, True, 16, impl="jax")
    ys.append(y)
    y, pool = mamba2.mamba2_chunk(x[16:37], dt[16:37], a, b[16:37], c[16:37],
                                  pool, 0, 1, False, 21, impl="jax")
    ys.append(y)
    rings = mamba2.ring_array(1, 3, 4, 2, 8, 16)
    blocks, held = jnp.array([1], jnp.int32), jnp.zeros((1,), jnp.int32)
    for i in range(37, t):
        y, pool, rings = mamba2.mamba2_step(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], pool, rings,
            0, blocks, held, impl="jax")
        _, held = mamba2.ring_after(blocks, held)
        ys.append(y)
    want_y, want_s = mamba2.mamba2_recurrent(x, dt, a, b, c)
    np.testing.assert_allclose(jnp.concatenate(ys), want_y, rtol=2e-4,
                               atol=2e-4)
    # the three tokens wait in the ring
    assert int(held[0]) == 3
    np.testing.assert_allclose(
        state_with_its_ring(pool, rings, 0, 1, 3, 4, 8), want_s, rtol=2e-4,
        atol=2e-4)
    # an entry keeps a token's d x, its B and its log-decays, each from
    # a row of its own
    assert mamba2._entry_rows(4, 2, 8, 16) == (1, 1, 2, 8)
    assert mamba2._entry_rows(128, 8, 64, 128) == (64, 8, 2, 80)
    assert mamba2._entry_rows(32, 2, 128, 256) == (32, 4, 1, 40)


@WIDTHS
@pytest.mark.parametrize("form", ["step", "chunk"])
def test_state_round_rounds_every_write(form, p, n):
    """The control's field: the state that is written holds bfloat16
    numbers in float32 bytes, and differs from the sound one. A step
    under it folds its one token at once, whatever `RING` is, into the
    state the read-modify-write gave: `bfloat16(exp(d A) S + d x B^T)`;
    the sound step writes no state, its token waits in the ring."""
    x, dt, a, b, c = draws(jax.random.key(8), 128, p=p, n=n)
    pool = pool_with(jax.random.key(9), p=p, n=n)
    if form == "step":
        blocks = jnp.array([1, 2], jnp.int32)
        held = jnp.zeros((2,), jnp.int32)
        assert mamba2.ring_entries("bfloat16") == 1 < mamba2.ring_entries(
            "none") == mamba2.RING
        fold, after = mamba2.ring_after(blocks, held, "bfloat16")
        assert bool(fold.all()) and not bool(after.any())

        def call(rnd):
            return mamba2.mamba2_step(
                x[:2], dt[:2], a, b[:2], c[:2], pool, rings_with(p=p, n=n),
                0, blocks, held, state_round=rnd, impl="pallas")[1][0, 1:3]

        np.testing.assert_array_equal(call("none"), pool[0, 1:3])
        s = mamba2.to_heads(pool[0, 1:3], p)
        moved = jnp.exp(dt[:2] * a)[..., None, None] * s + (
            x[:2] * dt[:2, :, None])[..., None] * mamba2._by_head(
                b[:2], H)[:, :, None, :]
        gave = mamba2.to_pairs(moved.astype(jnp.bfloat16).astype(F32))
        # to the bit but where the two sums' last float32 bit lies on
        # either side of a bfloat16 rounding
        same = np.asarray(call("bfloat16") == gave)
        assert same.mean() > 0.99
        np.testing.assert_allclose(call("bfloat16"), gave, rtol=2 ** -7,
                                   atol=1e-6)
    else:
        def call(rnd):
            return mamba2.mamba2_chunk(
                x, dt, a, b, c, pool, 0, 1, False, 128, state_round=rnd,
                impl="pallas")[1][0, 1]
    sound, rounded = call("none"), call("bfloat16")
    assert rounded.dtype == F32
    np.testing.assert_array_equal(
        rounded, rounded.astype(jnp.bfloat16).astype(F32))
    assert float(jnp.max(jnp.abs(sound - rounded))) > 0


def test_plan_names_what_the_kernels_cannot_take():
    assert mamba2.plan(128, 8, 64, 128, 512) == ""
    assert mamba2.plan(32, 2, 128, 256, 512) == ""
    why = mamba2.plan(4, 2, 8, 16)
    assert "64 x 128" in why and "128 x 256" in why and "8 x 16" in why
    assert "pairs" in mamba2.plan(6, 2, 64, 128)
    assert mamba2.plan(6, 2, 128, 256) == ""       # a head a lane tile
    assert "sub-blocks" in mamba2.plan(4, 2, 64, 128, 96)
