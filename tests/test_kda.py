"""`ops/kda.py` at small sizes on the CPU: the chunk form and the step
form, each on its plain path and as a kernel in interpret mode, against
the token-by-token definition (`kda_recurrent`) in float32; a chunk
boundary inside a sequence, a gate at its lower bound for a whole
sub-chunk, padding that leaves the state bit-identical, the control's
rounding, and what a shape with no plan does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import backend, kda

H, D = 2, 128               # the kernels' plan is one lane tile square
FLOOR = -5.0
# the plain paths are float32 at the highest precision against a float32
# scan; the kernels feed the MXU bfloat16 operands (2^-9 a value)
TOL = {"jax": 2e-5, "pallas": 2e-2}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(t, seed=0, h=H, d=D):
    """q, k normed as the layer norms them; g in (FLOOR, 0) over slow and
    fast channels; positions 16-31 at the floor itself."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (t, h, d)))
    v = jax.random.normal(ks[2], (t, h, d))
    g = FLOOR * jax.nn.sigmoid(
        jax.random.normal(ks[3], (t, h, d)) + jnp.linspace(-9.0, 1.0, d))
    g = g.at[16:32].set(FLOOR)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    pool = jax.random.normal(ks[5], (2, 4, h, d, d))
    return q, k, v, g, beta, pool


def close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=tol)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("chunk", [48, 32, 20])
def test_chunk_form_is_the_definition_across_chunk_boundaries(impl, chunk):
    """48 positions in chunks of 48, 32 + 16 and 20 + 20 + 8 (a boundary
    inside a sub-chunk's worth of positions), the first chunk resetting a
    block that held garbage."""
    q, k, v, g, beta, pool = inputs(48)
    want, s_want = kda.kda_recurrent(q, k, v, g, beta)
    outs = []
    for start in range(0, 48, chunk):
        cut = slice(start, min(start + chunk, 48))
        o, pool = kda.kda_chunk(q[cut], k[cut], v[cut], g[cut], beta[cut],
                                pool, 1, 2, start == 0, cut.stop - start,
                                impl=impl)
        outs.append(o)
    close(jnp.concatenate(outs), want, TOL[impl])
    close(pool[1, 2], s_want, 10 * TOL[impl])


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_a_gate_at_its_floor_for_a_whole_sub_chunk_stays_finite(impl):
    q, k, v, g, beta, pool = inputs(32)
    g = jnp.full_like(g, FLOOR)
    want, s_want = kda.kda_recurrent(q, k, v, g, beta, pool[0, 1])
    o, new = kda.kda_chunk(q, k, v, g, beta, pool, 0, 1, False, 32,
                           impl=impl)
    assert bool(jnp.isfinite(o).all() & jnp.isfinite(new).all())
    close(o, want, TOL[impl])
    close(new[0, 1], s_want, TOL[impl])


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_padding_leaves_the_state_bit_identical(impl):
    """20 live positions in a bucket of 48: the state is the one 20
    positions in a bucket of 32 leave, bit for bit, whatever the padding
    rows hold; a chunk with no live position leaves the block as it was."""
    q, k, v, g, beta, pool = inputs(48)
    o48, p48 = kda.kda_chunk(q, k, v, g, beta, pool, 1, 2, True, 20,
                             impl=impl)
    o32, p32 = kda.kda_chunk(q[:32], k[:32], v[:32].at[20:].multiply(7.0),
                             g[:32], beta[:32], pool, 1, 2, True, 20,
                             impl=impl)
    assert bool((p48 == p32.at[1, 2].set(p48[1, 2])).all())
    np.testing.assert_array_equal(np.asarray(p48[1, 2]),
                                  np.asarray(p32[1, 2]))
    want, s_want = kda.kda_recurrent(q[:20], k[:20], v[:20], g[:20],
                                     beta[:20])
    close(o48[:20], want, TOL[impl])
    close(p48[1, 2], s_want, 10 * TOL[impl])
    _, same = kda.kda_chunk(q, k, v, g, beta, pool, 1, 2, False, 0,
                            impl=impl)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(pool))


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_step_form_is_the_definition_and_touches_its_blocks_only(impl):
    """Three rows, one of them idle (block 0), four steps each: every row
    follows its own sequence's recurrence, and blocks nobody names keep
    their bits."""
    q, k, v, g, beta, pool = inputs(12, seed=1)
    blocks = jnp.asarray([2, 0, 1], jnp.int32)
    rows = [slice(0, 4), slice(4, 8), slice(8, 12)]
    start = pool
    outs = []
    for t in range(4):
        pick = jnp.asarray([r.start + t for r in rows])
        o, pool = kda.kda_step(q[pick], k[pick], v[pick], g[pick],
                               beta[pick], pool, 1, blocks, impl=impl)
        outs.append(o)
    outs = jnp.stack(outs, 1)                               # [row, t, H, D]
    for i, r in enumerate(rows):
        want, s_want = kda.kda_recurrent(q[r], k[r], v[r], g[r], beta[r],
                                         start[1, blocks[i]])
        close(outs[i], want, 1e-5)
        close(pool[1, blocks[i]], s_want, 1e-5)
    np.testing.assert_array_equal(np.asarray(pool[0]), np.asarray(start[0]))
    np.testing.assert_array_equal(np.asarray(pool[1, 3]),
                                  np.asarray(start[1, 3]))


def test_the_kernels_round_the_state_as_the_plain_paths_do():
    q, k, v, g, beta, pool = inputs(16, seed=2)
    blocks = jnp.asarray([1, 2], jnp.int32)
    for impl in ("jax", "pallas"):
        _, stepped = kda.kda_step(q[:2], k[:2], v[:2], g[:2], beta[:2], pool,
                                  0, blocks, state_round="bfloat16",
                                  impl=impl)
        _, chunked = kda.kda_chunk(q, k, v, g, beta, pool, 0, 3, False, 16,
                                   state_round="bfloat16", impl=impl)
        for s in (stepped[0, 1], stepped[0, 2], chunked[0, 3]):
            assert bool((s == s.astype(jnp.bfloat16).astype(jnp.float32))
                        .all())
        assert bool((stepped[0, 3] == pool[0, 3]).all())
    _, sound = kda.kda_chunk(q, k, v, g, beta, pool, 0, 3, False, 16)
    assert float(jnp.abs(sound[0, 3] - chunked[0, 3]).max()) > 1e-4


def test_a_shape_with_no_plan_takes_the_plain_path_and_says_so(monkeypatch):
    """head_dim 16 has no kernel plan: on the CPU the plain path is the
    normal one and nothing is said; told it is on a TPU, the op says so
    once a trace and gives the plain path's numbers."""
    said = []
    monkeypatch.setattr(backend, "note_fallback",
                        lambda op, why: said.append((op, why)))
    q, k, v, g, beta, pool = inputs(16, d=16)
    want, _ = kda.kda_recurrent(q, k, v, g, beta)
    o, _ = kda.kda_chunk(q, k, v, g, beta, pool, 0, 1, True, 16,
                         impl="pallas")
    close(o, want, 2e-5)
    kda.kda_step(q[:2], k[:2], v[:2], g[:2], beta[:2], pool, 0,
                 jnp.asarray([1, 2]), impl="pallas")
    assert [op for op, _ in said] == [kda.KDA_CHUNK, kda.KDA_STEP]
    assert "lane tile" in said[0][1]
    assert kda.plan(128, 128) == "" and kda.plan(128, 128, 512) == ""
    assert "sub-chunks" in kda.plan(128, 128, 128 * 16 + 1)
