"""The paged layer scan carries the stacked pool instead of scanning over
it (`gpt._paged_layers`): same rows written, same rows read, so on the
CPU `jax` path the three serving programs give bitwise the logits and
the pool of the formulation they replace, which is kept here. Both
forms of the loop are held to it: the pool written and read where it
lies (a head fills its lanes), and a layer taken out at a time (a head
size under 128, where XLA stores the pool in a layout of its own).

The trap the change walks past: a dropped row's index is `n_blocks *
bs`, past one layer's rows. In one flat view of the stacked pool that is
row 0 of block 0 of the next layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import blocks, gpt
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import quant

NB, BS, MB, SLOTS, CHUNK, W = 7, 4, 3, 4, 8, 3


def scanned_pool_layers(params, x, cache, cfg, widx, attend):
    """`gpt._paged_layers` as it was: the pool a scanned input and a
    stacked output, a layer's slice written at flat `widx`."""
    def scatter(lc, k, v):
        rows = {"k": k.reshape((-1,) + lc["k"].shape[2:]),
                "v": v.reshape((-1,) + lc["v"].shape[2:])}
        if "k_scale" in lc:
            rows["k"], rows["k_scale"] = quant.quantize_rows(rows["k"])
            rows["v"], rows["v_scale"] = quant.quantize_rows(rows["v"])
        return {n: a.reshape((-1,) + a.shape[2:]).at[widx].set(
            rows[n].astype(a.dtype), mode="drop").reshape(a.shape)
            for n, a in lc.items()}

    def body(x, layer):
        lp, lc = layer

        def write_then_attend(q, k, v):
            written = scatter(lc, k, v)
            stack_of_one = {n: a[None] for n, a in written.items()}
            return attend(q, stack_of_one, 0), written

        x, lc, _ = gpt.layer(x, lp, cfg, gpt._matmul_out(cfg),
                             write_then_attend)
        return x, lc

    x, cache = jax.lax.scan(body, x, (params["layers"], cache))
    scale = params["final_ln_scale"].astype(cfg.activation_dtype())
    return blocks.rms_norm(x, scale), cache


@functools.lru_cache(maxsize=None)
def model(dtype):
    cfg = gpt.small(vocab_size=128, d_model=32, n_heads=2, d_ff=64,
                    max_seq_len=32, dtype="bfloat16" if dtype == "bf16"
                    else "float32", kv_dtype="int8" if dtype == "int8"
                    else "f32", decode_attn_impl="jax",
                    prefill_attn_impl="jax")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    pool = gpt.init_kv_pool(cfg, NB, BS)
    keys = jax.random.split(jax.random.PRNGKey(1), len(pool))
    for key, (name, a) in zip(keys, sorted(pool.items())):  # no zero rows
        r = jax.random.normal(key, a.shape, jnp.float32)
        pool[name] = (jnp.abs(r) + 0.5 if "scale" in name
                      else r * 40 if a.dtype == jnp.int8 else r).astype(
                          a.dtype)
    return cfg, params, pool


def tokens(*shape):
    return jax.random.randint(jax.random.PRNGKey(2), shape, 0, 128)


def run(program, cfg, params, pool, drop_all=False):
    """One step of `program` on a copy of `pool`: a prefill chunk with a
    padded tail; a decode step with an idle row on the trash block, two
    rows that collide nowhere and one past the table's reach, which must
    drop; a verify window that runs off the table's end. `drop_all`:
    every row of the step drops."""
    pool = jax.tree.map(jnp.copy, pool)
    past = MB * BS                       # the first position off a table
    tables = jnp.asarray([[0, 0, 0], [2, 5, 1], [3, 6, 4], [1, 2, 3]])
    if program == "prefill":
        return jax.jit(lambda p, t, c, n: gpt.prefill_paged(
            p, t, c, cfg, block_table=tables[1], start=3, length=n))(
            params, tokens(1, CHUNK), pool, 0 if drop_all else CHUNK - 3)
    pos = jnp.asarray([past, past + 1, past + 5, past] if drop_all
                      else [0, 5, past - 2, past + 1])
    if program == "decode":
        return jax.jit(lambda p, t, c, ps: gpt.decode_step_paged(
            p, t, c, ps, tables, cfg))(params, tokens(SLOTS), pool, pos)
    return jax.jit(lambda p, t, c, ps: gpt.verify_step_paged(
        p, t, c, ps, tables, cfg))(params, tokens(SLOTS, W), pool, pos)


def same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))


LOOPS = {"where-it-lies": True, "a-layer-at-a-time": False}


@pytest.mark.parametrize("loop", sorted(LOOPS))
@pytest.mark.parametrize("dtype", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("program", ["prefill", "decode", "verify"])
def test_carried_pool_is_bitwise_the_scanned_pool(monkeypatch, program,
                                                  dtype, loop):
    cfg, params, pool = model(dtype)
    monkeypatch.setattr(da, "reads_pool_where_it_lies",
                        lambda *a: LOOPS[loop])
    logits, written = run(program, cfg, params, pool)
    monkeypatch.setattr(gpt, "_paged_layers", scanned_pool_layers)
    want_logits, want = run(program, cfg, params, pool)
    same(logits, want_logits)
    same(written, want)
    # the step wrote something, and what dropped did not land
    assert any((np.asarray(written[n]) != np.asarray(pool[n])).any()
               for n in pool)


@pytest.mark.parametrize("loop", sorted(LOOPS))
@pytest.mark.parametrize("program", ["prefill", "decode", "verify"])
def test_dropped_rows_drop_in_every_layer(monkeypatch, program, loop):
    """A step whose rows all drop leaves the pool as it was, and by name
    row 0 of block 0 of every layer, payload and scales: where a flat
    index of `n_blocks * bs` into the stacked pool would land."""
    cfg, params, pool = model("int8")
    monkeypatch.setattr(da, "reads_pool_where_it_lies",
                        lambda *a: LOOPS[loop])
    _, written = run(program, cfg, params, pool, drop_all=True)
    for name in pool:
        np.testing.assert_array_equal(np.asarray(written[name][:, 0, 0]),
                                      np.asarray(pool[name][:, 0, 0]))
    same(written, pool)


# (op, heads, head size, pool): a head that fills its lanes is read where
# it lies, four heads to a row of lanes are laid out a layer at a time
STACKED = [("decode", 8, 128, "f32"), ("decode", 4, 32, "int8"),
           ("decode", 4, 32, "f32"), ("verify", 8, 128, "int8"),
           ("prefill", 4, 32, "f32"),
           # `paged_mq` with the decode kernel's operands: two heads to
           # a row of lanes, and an int8 pool's scales a layer at a time
           ("prefill", 16, 64, "f32"), ("prefill", 16, 64, "int8"),
           ("prefill", 8, 128, "int8"), ("verify", 16, 64, "f32"),
           ("verify", 16, 64, "int8"), ("verify", 8, 128, "f32")]


@pytest.mark.parametrize("op,h,d,kv", STACKED,
                         ids=["-".join(map(str, c)) for c in STACKED])
def test_kernels_read_a_layer_of_the_stacked_pool(op, h, d, kv):
    """A paged op, given the stacked pool and a layer's number, on the
    kernel path (interpreted here) against its `jax` path on that
    layer's slice."""
    layers, nb, bs, mb, b = 3, 6, 32, 2, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    k_pool, v_pool = (jax.random.normal(k, (layers, nb, bs, h, d))
                      for k in ks[:2])
    scales = {}
    if kv == "int8":
        k_pool, scales["k_scale"] = quant.quantize_rows(k_pool)
        v_pool, scales["v_scale"] = quant.quantize_rows(v_pool)
    tables = jax.random.randint(ks[2], (b, mb), 1, nb)
    pos = jnp.asarray([0, bs + 2, mb * bs - W])
    sliced = {n: a[1] for n, a in scales.items()}
    if op == "decode":
        q = jax.random.normal(ks[3], (b, h, d))
        call, args = da.paged_decode_attention, (tables, pos)
    elif op == "verify":
        q = jax.random.normal(ks[3], (b, W, h, d))
        call, args = da.paged_verify_attention, (tables, pos)
    else:
        q = jax.random.normal(ks[3], (CHUNK, h, d))
        call, args = da.paged_prefill_attention, (tables[1], pos[1])
    got = jax.jit(lambda q, k, v, sc, layer: call(
        q, k, v, *args, layer=layer, impl="pallas", **sc))(
        q, k_pool, v_pool, scales, jnp.asarray(1))
    want = call(q, k_pool[1], v_pool[1], *args, impl="jax", **sliced)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
