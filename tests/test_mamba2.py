"""`ops/mamba2.py`: the step and chunk kernels (interpret mode) and their
plain paths against the token-by-token recurrence."""

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
@pytest.mark.parametrize("p,n,atol", [(64, 128, 2e-5), (128, 256, 4e-5)],
                         ids=["64x128", "128x256"])
@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_step_matches_the_recurrence(impl, p, n, atol):
    nb = 3
    x, dt, a, b, c = draws(jax.random.key(1), nb, p=p, n=n)
    pool = pool_with(jax.random.key(2), p=p, n=n)
    to_heads = functools.partial(mamba2.to_heads, p=p)
    blocks = jnp.array([2, 0, 4], jnp.int32)
    y, new = mamba2.mamba2_step(x, dt, a, b, c, pool, 1, blocks, impl=impl)
    for i, blk in enumerate([2, 0, 4]):
        if blk == 0:
            continue
        want_y, want_s = mamba2.mamba2_recurrent(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1],
            to_heads(pool[1, blk]))
        np.testing.assert_allclose(y[i], want_y[0], rtol=2e-5, atol=atol)
        np.testing.assert_allclose(to_heads(new[1, blk]), want_s,
                                   rtol=1e-6, atol=1e-6)
    # no other layer and no other live block is touched
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, 1], pool[1, 1])
    np.testing.assert_array_equal(new[1, 3], pool[1, 3])


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
    for i in range(37, t):
        y, pool = mamba2.mamba2_step(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                                     c[i:i + 1], pool, 0,
                                     jnp.array([1], jnp.int32), impl="jax")
        ys.append(y)
    want_y, want_s = mamba2.mamba2_recurrent(x, dt, a, b, c)
    np.testing.assert_allclose(jnp.concatenate(ys), want_y, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(mamba2.to_heads(pool[0, 1], 8), want_s,
                               rtol=2e-4, atol=2e-4)


@WIDTHS
@pytest.mark.parametrize("form", ["step", "chunk"])
def test_state_round_rounds_every_write(form, p, n):
    """The control's field: the state that is written holds bfloat16
    numbers in float32 bytes, and differs from the sound one."""
    x, dt, a, b, c = draws(jax.random.key(8), 128, p=p, n=n)
    pool = pool_with(jax.random.key(9), p=p, n=n)
    if form == "step":
        def call(rnd):
            return mamba2.mamba2_step(
                x[:2], dt[:2], a, b[:2], c[:2], pool, 0,
                jnp.array([1, 2], jnp.int32), state_round=rnd,
                impl="pallas")[1][0, 1:3]
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
