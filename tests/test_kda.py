"""`ops/kda.py` at small sizes on the CPU: the chunk form and the step
form, each on its plain path and as a kernel in interpret mode, against
the token-by-token definition (`kda_recurrent`) in float32; a chunk
boundary inside a sequence, a gate at its lower bound for a whole
sub-chunk, padding that leaves the state bit-identical, the control's
rounding, and what a shape with no plan does. The step form keeps its
last tokens in a ring beside the state: rows that fold in different
steps over stale rings with an idle row beside them, a gate at its floor
for a whole ring, the fold against float64, the control's one entry, and
kernel against plain path entry by entry."""

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


# -- the step form and its ring -----------------------------------------------

RING = kda.RING


def rings_with(key=None, layers=2, blocks=4, h=H, d=D):
    """Rings as a freed block leaves them: another sequence's numbers."""
    ring = kda.ring_array(layers, blocks, h, d)
    if key is None:
        return ring
    return jax.random.normal(key, ring.shape)


def state_with_its_ring(pool, ring, layer, block, held, h=H, d=D):
    """The state a block stands for: its folded state moved by the `held`
    entries that wait in its ring, S_t = diag(exp G_t) S_t0 + sum_s
    diag(exp(G_t - G_s)) k_s u_s^T, in float64."""
    s = np.asarray(pool[layer, block], np.float64)
    if not held:
        return s
    k, u, big = (np.asarray(a, np.float64)[:held] for a in kda._unpacked(
        ring[layer, block], h, d, d))
    last = big[-1]
    return np.exp(last)[..., None] * s + np.einsum(
        "shc,shv->hcv", np.exp(last[None] - big) * k, u)


def run_steps(impl, x, pool, rings, plan_of, layer=1, state_round="none"):
    """`kda_step` over the steps of `plan_of(t)` -> blocks [B]; x's arrays
    [T, B, ...] a row a column: -> (o [T, B, H, D], the pools, rings,
    folds and `held` after every step)."""
    q, k, v, g, beta = x
    held = jnp.zeros((q.shape[1],), jnp.int32)
    outs, trail = [], []
    for t in range(q.shape[0]):
        blocks = plan_of(t)
        fold, after = kda.ring_after(blocks, held, state_round)
        o, pool, rings = kda.kda_step(
            q[t], k[t], v[t], g[t], beta[t], pool, rings, layer, blocks,
            held, state_round=state_round, impl=impl)
        held = after
        outs.append(o)
        trail.append((pool, rings, fold, held))
    return jnp.stack(outs), trail


def by_row(t, rows, seed=1, **kw):
    """Inputs of `rows` sequences of `t` tokens: arrays [T, B, ...]."""
    *x, pool = inputs(t * rows, seed=seed, **kw)
    return tuple(a.reshape((rows, t) + a.shape[1:]).swapaxes(0, 1)
                 for a in x), pool


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_step_form_is_the_definition_and_touches_its_blocks_only(impl):
    """Three rows, one of them idle (block 0), four steps each: every row
    follows its own sequence's recurrence, and blocks nobody names keep
    their bits; four tokens wait in each ring and the states are as they
    were."""
    x, start = by_row(4, 3)
    blocks = jnp.asarray([2, 0, 1], jnp.int32)
    stale = rings_with(jax.random.key(7))
    outs, trail = run_steps(impl, x, start, stale, lambda t: blocks)
    pool, rings, _, held = trail[-1]
    assert [int(n) for n in held] == [4, 0, 4]
    for i in (0, 2):
        want, s_want = kda.kda_recurrent(*(a[:, i] for a in x),
                                         start[1, blocks[i]])
        close(outs[:, i], want, 1e-5)
        close(state_with_its_ring(pool, rings, 1, int(blocks[i]), 4), s_want,
              1e-5)
    np.testing.assert_array_equal(np.asarray(pool), np.asarray(start))
    np.testing.assert_array_equal(np.asarray(rings[0]), np.asarray(stale[0]))
    np.testing.assert_array_equal(np.asarray(rings[1, 3]),
                                  np.asarray(stale[1, 3]))


@pytest.mark.parametrize("heads", [2, 16], ids=["heads2", "heads16"])
@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_rows_fold_in_different_steps_over_stale_rings(impl, heads):
    """Two sequences, the second three steps late, an idle row between
    them, rings that hold another sequence's numbers: every token's `o`
    is the recurrence's; a row's state is rewritten only in the step that
    fills its ring, and with its ring it is the recurrence's state after
    every token; the idle row leaves block 0's state and ring as they
    were. Sixteen heads are two whole sublane tiles, two a part of one."""
    late, steps = 3, 2 * RING + 3 if heads == 2 else RING + 3
    x, first = by_row(steps, 3, h=heads)
    stale = rings_with(jax.random.key(8), h=heads)
    plan_of = lambda t: jnp.asarray([2, 0, 3 if t >= late else 0], jnp.int32)
    outs, trail = run_steps(impl, x, first, stale, plan_of)
    folds = 0
    for row, blk, since in ((0, 2, 0), (2, 3, late)):
        mine = [a[since:, row] for a in x]
        want, _ = kda.kda_recurrent(*mine, first[1, blk])
        close(outs[since:, row], want, 1e-5)
        before = first
        for t in range(since, steps):
            pool, rings, fold, held = trail[t]
            _, s_want = kda.kda_recurrent(*(a[:t - since + 1] for a in mine),
                                          first[1, blk])
            if bool(fold[row]):
                folds += 1
                assert int(held[row]) == 0
                close(pool[1, blk], s_want, 1e-5)
            else:
                np.testing.assert_array_equal(np.asarray(pool[1, blk]),
                                              np.asarray(before[1, blk]))
                close(state_with_its_ring(pool, rings, 1, blk,
                                          int(held[row]), heads), s_want,
                      1e-5)
            before = pool
    assert folds == steps // RING + (steps - late) // RING
    pool, rings, _, held = trail[-1]
    assert [int(n) for n in held] == [steps % RING, 0, (steps - late) % RING]
    assert float(jnp.abs(outs[:, 1]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(pool[0]), np.asarray(first[0]))
    for blk in (0, 1):
        np.testing.assert_array_equal(np.asarray(pool[1, blk]),
                                      np.asarray(first[1, blk]))
    np.testing.assert_array_equal(np.asarray(rings[0]), np.asarray(stale[0]))
    np.testing.assert_array_equal(np.asarray(rings[1, 1]),
                                  np.asarray(stale[1, 1]))
    # the trash block's: what an entry holds of a token (the plain path
    # packs an idle row's entries anew, padding and all)
    for got, was in zip(kda._unpacked(rings[1, 0], heads, D, D),
                        kda._unpacked(stale[1, 0], heads, D, D)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(was))


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_a_gate_at_its_floor_for_a_whole_ring_stays_finite(impl):
    """Every channel at the lower bound for two rings: `exp G` falls to
    e^-40 before a fold, inside float32, and the step stays the
    recurrence's."""
    steps = 2 * RING
    x, pool = by_row(steps, 1, seed=3)
    x = x[:3] + (jnp.full_like(x[3], FLOOR),) + x[4:]
    blocks = jnp.asarray([1], jnp.int32)
    outs, trail = run_steps(impl, x, pool, rings_with(), lambda t: blocks, 0)
    want, s_want = kda.kda_recurrent(*(a[:, 0] for a in x), pool[0, 1])
    new, rings = trail[-1][:2]
    assert bool(jnp.isfinite(outs).all() & jnp.isfinite(new).all()
                & jnp.isfinite(rings).all())
    close(outs[:, 0], want, 1e-5)
    close(new[0, 1], s_want, 1e-5)
    # the running sum of a full ring's gates, as its last entry keeps it
    big = kda._unpacked(trail[-2][1][0, 1], H, D, D)[2]
    close(big[RING - 2], jnp.full((H, D), FLOOR * (RING - 1)), 1e-4)


CASES = {
    # (rows' blocks by step, steps, state_round, the gate)
    "folds_in_turn": (lambda t: [2, 0, 3 if t >= 3 else 0], RING + 4, "none",
                      None),
    "every_row_live": (lambda t: [1, 2, 3], RING + 1, "none", None),
    "at_the_floor": (lambda t: [3, 1, 0], RING + 1, "none", FLOOR),
    "control": (lambda t: [2, 0, 3], 3, "bfloat16", None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_steps_as_the_plain_path_does(case):
    """Kernel in interpret mode against `_step_plain`, step by step and
    entry by entry: `o`, the states and what the rings hold of each
    token."""
    rows_of, steps, state_round, gate = CASES[case]
    x, pool = by_row(steps, 3, seed=4)
    if gate is not None:
        x = x[:3] + (jnp.full_like(x[3], gate),) + x[4:]
    stale = rings_with(jax.random.key(9))
    plan_of = lambda t: jnp.asarray(rows_of(t), jnp.int32)
    got, want = (run_steps(impl, x, pool, stale, plan_of,
                           state_round=state_round)
                 for impl in ("pallas", "jax"))
    # the control's `o` is read from the rounded state
    close(got[0], want[0], 1e-5 if state_round == "none" else 1e-3)
    for (p_got, r_got, *_), (p_want, r_want, *_) in zip(got[1], want[1]):
        if state_round == "none":
            close(p_got, p_want, 1e-5)
        else:   # to the bit but where a sum's last float32 bit lies on
            # either side of a bfloat16 rounding
            assert np.asarray(p_got == p_want).mean() > 0.99
            close(p_got, p_want, 2 ** -6)
        for a, b in zip(kda._unpacked(r_got[1], H, D, D),
                        kda._unpacked(r_want[1], H, D, D)):
            close(a, b, 1e-4 if state_round == "bfloat16" else 1e-5)


def test_the_fold_against_float64():
    """A ring's tokens folded into a state by the kernel, beside the
    per-token float32 update, both against the same recurrence in
    float64: the reordered sum loses nothing (errors of one order)."""
    x, pool = by_row(RING, 1, seed=5)
    s64 = np.asarray(pool[0, 1], np.float64)
    for t in range(RING):
        q, k, v, g, beta = (np.asarray(a[t, 0], np.float64) for a in x)
        s64 = s64 * np.exp(g)[..., None]
        u = beta[:, None] * (v - np.einsum("hc,hcv->hv", k, s64))
        s64 = s64 + k[..., None] * u[:, None, :]
    plain = kda.kda_recurrent(*(a[:, 0] for a in x), pool[0, 1])[1]
    blocks = jnp.asarray([1], jnp.int32)
    _, trail = run_steps("pallas", x, pool, rings_with(), lambda t: blocks, 0)
    new, _, fold, held = trail[-1]
    assert bool(fold[0]) and int(held[0]) == 0      # folded with the last
    errs = [float(np.max(np.abs(np.asarray(s, np.float64) - s64)))
            for s in (new[0, 1], plain)]
    print(f"fold against float64: kernel {errs[0]:.3g}, per-token float32 "
          f"{errs[1]:.3g}, on states of {np.max(np.abs(s64)):.3g}")
    assert errs[0] < 4 * errs[1] + 1e-6


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_the_control_folds_every_token_and_rounds_as_it_did(impl):
    """Under `state_round` a ring holds one token and every step folds:
    the state written is the read-modify-write's, `bfloat16(diag(exp g) S
    + k u^T)`, float32 bytes; the sound step writes no state, its token
    waits in the ring."""
    assert kda.ring_entries("bfloat16") == 1 < kda.ring_entries("none") \
        == RING
    x, pool = by_row(1, 2, seed=6)
    blocks = jnp.asarray([1, 2], jnp.int32)
    fold, after = kda.ring_after(blocks, jnp.zeros((2,), jnp.int32),
                                 "bfloat16")
    assert bool(fold.all()) and not bool(after.any())

    def call(rnd):
        return run_steps(impl, x, pool, rings_with(), lambda t: blocks, 0,
                         state_round=rnd)

    np.testing.assert_array_equal(np.asarray(call("none")[1][0][0]),
                                  np.asarray(pool))
    outs, trail = call("bfloat16")
    q, k, v, g, beta = (a[0].astype(jnp.float32) for a in x)
    s = pool[0, 1:3] * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhc,bhcv->bhv", k, s))
    gave = (s + k[..., None] * u[..., None, :]).astype(jnp.bfloat16).astype(
        jnp.float32)
    new = trail[0][0][0, 1:3]
    np.testing.assert_array_equal(
        np.asarray(new), np.asarray(new.astype(jnp.bfloat16).astype(
            jnp.float32)))
    assert np.asarray(new == gave).mean() > 0.99
    close(new, gave, 2 ** -6)
    # the step's output is read from the state as it is written
    close(outs[0], jnp.einsum("bhc,bhcv->bhv", q, new), 1e-5)
    np.testing.assert_array_equal(np.asarray(trail[0][0][0, 3]),
                                  np.asarray(pool[0, 3]))


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_a_first_chunk_then_steps_over_a_stale_ring(impl):
    """A freed block's state and rings hold its last sequence's numbers;
    a first chunk reads the state as zeros and its caller leaves the
    block's rings empty (`held` 0), so the steps after it read as the
    recurrence does over the whole sequence."""
    t, steps = 32, RING + 2
    q, k, v, g, beta, pool = inputs(t + steps, seed=7)
    want, s_want = kda.kda_recurrent(q, k, v, g, beta)
    _, pool = kda.kda_chunk(q[:t], k[:t], v[:t], g[:t], beta[:t], pool, 0, 3,
                            True, t, impl=impl)
    x = tuple(a[t:, None] for a in (q, k, v, g, beta))
    blocks = jnp.asarray([3], jnp.int32)
    outs, trail = run_steps(impl, x, pool, rings_with(jax.random.key(10)),
                            lambda i: blocks, 0)
    close(outs[:, 0], want[t:], TOL[impl])
    pool, rings, _, held = trail[-1]
    close(state_with_its_ring(pool, rings, 0, 3, int(held[0])), s_want,
          10 * TOL[impl])


def test_the_kernels_round_the_state_as_the_plain_paths_do():
    q, k, v, g, beta, pool = inputs(16, seed=2)
    blocks = jnp.asarray([1, 2], jnp.int32)
    held = jnp.zeros((2,), jnp.int32)
    for impl in ("jax", "pallas"):
        _, stepped, _ = kda.kda_step(
            q[:2], k[:2], v[:2], g[:2], beta[:2], pool, rings_with(), 0,
            blocks, held, state_round="bfloat16", impl=impl)
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
    kda.kda_step(q[:2], k[:2], v[:2], g[:2], beta[:2], pool,
                 rings_with(d=16), 0, jnp.asarray([1, 2]),
                 jnp.zeros((2,), jnp.int32), impl="pallas")
    assert [op for op, _ in said] == [kda.KDA_CHUNK, kda.KDA_STEP]
    assert "lane tile" in said[0][1]
    assert kda.plan(128, 128) == "" and kda.plan(128, 128, 512) == ""
    assert "sub-chunks" in kda.plan(128, 128, 128 * 16 + 1)


def test_an_entry_keeps_a_head_a_row_of_each_part():
    """An entry's `k`, `u` and `G`, each from a sublane tile of its own:
    a head a row at the kernel's width, whole rows of lanes at a
    narrower one; packing and unpacking are inverses."""
    assert kda._entry_rows(32, 128, 128) == (32, 32, 96)
    assert kda._entry_rows(2, 128, 128) == (8, 8, 24)
    assert kda._entry_rows(4, 16, 16) == (8, 8, 24)
    assert kda.ring_array(6, 65, 32, 128).shape == (6, 65, RING, 96, 128)
    k, u, big = (jax.random.normal(jax.random.key(i), (3, 4, 16))
                 for i in range(3))
    for got, want in zip(kda._unpacked(kda._packed(k, u, big), 4, 16, 16),
                         (k, u, big)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    entry = kda._packed(*(jnp.ones((32, 128)) * i for i in (1, 2, 3)))
    np.testing.assert_array_equal(
        np.asarray(entry[:, 0]), np.repeat([1.0, 2.0, 3.0], 32))
