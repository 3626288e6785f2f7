"""The power-retention family (`models/retention.py`,
`ops/power_retention.py`) against its plain reference
(`benchmarks/refs/retention_decoder.py`) at a tiny size on the CPU, seeded
random weights, float32: the whole-sequence forward, the three forms of one
sequence's retention (quadratic, chunked, stepped), chunked prefill and
decode through the state pool and through the engine (logits and
logprobs, not tokens), what a state block asks of the engine (one block a
request, no prefix tree, preemption, hand-off), and both kernels in
interpret mode against their plain paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import retention_decoder as ref
from ray_tpu.models import retention
from ray_tpu.models.family import ServingFamily
from ray_tpu.ops import power_retention as pr
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults

# the published keys at a tiny size: head_dim 32 is two tiles, so the
# feature layout has two diagonal pairs and an off-diagonal one (D = 768)
TINY = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, intermediate_size=128,
    rope_theta=1e6, rms_norm_eps=1e-6, retention_eps=1e-6,
    gate_bias=[4.0, 8.0], max_position_embeddings=128, vocab_size=512)
# float32 both sides at the highest matmul precision; measured 4e-6 on
# logits of spread 4. A squared score doubles a relative error and the
# state form sums 768 signed terms where the square sums 32, so the forms
# differ by more than two dense forwards do (1e-6), and by far less than a
# wrong mask, decay or reset moves a logit (1e-1 and up)
TOL = 2e-4
EPS = 1e-6


def config(impl="jax", **over):
    keys = {k: v for k, v in TINY.items() if k != "gate_bias"}     # a weight
    return retention.from_published(**{**keys, **over}, dtype="float32",
                                    retention_impl=impl)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(jax.random.key(0), TINY)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).astype(np.int32)


def make_engine(params, **kw):
    kw = {"slots": 3, "max_len": 96, "prefill_chunk": 16,
          "prefix_cache": False, **kw}
    return InferenceEngine(params, config(), **kw)


def stream(eng, rid):
    return [(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]


def same_stream(got, base):
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in base], rtol=0, atol=1e-4)


def pooled_logits(params, cfg, seq, n_prompt, chunk, pool=None, block=2,
                  bucket=None):
    """seq's logits through a state block: the first `n_prompt` tokens by
    chunked prefill (each chunk padded to `bucket`), the rest a decode
    step each beside an idle row. -> ({position: logits [V]}, pool)."""
    bucket = bucket or chunk
    pool = retention.init_pool(cfg, 4, 16) if pool is None else pool
    table = jnp.asarray([block], jnp.int32)
    out = {}
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq[start:start + n]
        lg, pool, counts = retention.prefill(
            params, jnp.asarray(toks), pool, cfg, block_table=table,
            start=start, length=n)
        assert [int(c) for c in counts] == [n, bucket - n, int(start == 0),
                                            0]
        out[start + n - 1] = np.asarray(lg[0])
        assert int(pool["held"][0, block]) == 0
    ring = pr.ring_entries(cfg.state_round)
    for t in range(n_prompt, len(seq)):
        lg, pool, counts = retention.decode(
            params, jnp.asarray([seq[t], 0], jnp.int32), pool,
            jnp.asarray([t, 0], jnp.int32),
            jnp.asarray([[block], [0]], jnp.int32), cfg)
        done = t - n_prompt + 1
        assert [int(c) for c in counts] == [0, 0, 0, int(done % ring == 0)]
        assert int(pool["held"][0, block]) == done % ring
        out[t] = np.asarray(lg[0])
    return out, pool


# -- (a) the layer and its three forms ----------------------------------------

def test_forward_matches_the_reference(params):
    toks = jnp.asarray(np.stack([prompt(96, 1), prompt(96, 2)]))
    want = ref.logits(params, toks, TINY)
    got = retention.forward(params, toks, config())
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    lp = ref.token_logprobs(params, toks, TINY)
    full = jnp.take_along_axis(jax.nn.log_softmax(want, -1)[:, :-1],
                               toks[:, 1:, None], -1)[..., 0]
    assert float(jnp.max(jnp.abs(lp - full))) < 1e-5


@pytest.mark.parametrize("d", [16, 32, 128])
def test_the_stored_layout_keeps_the_squared_dot_product(d):
    q, k = jax.random.normal(jax.random.key(d), (2, 7, d))
    assert pr.phi(q).shape == (7, pr.feature_dim(d))
    want = jnp.sum(q * k, -1) ** 2
    got = jnp.sum(pr.phi(q) * pr.phi(k), -1)
    # a sum of D signed products of size up to |q|^2 |k|^2 / d
    scale = float(jnp.max(jnp.sum(q * q, -1) * jnp.sum(k * k, -1)))
    np.testing.assert_allclose(got, want, atol=2e-6 * scale)
    # 128: the published head, 36 tile pairs of 256
    assert pr.feature_dim(128) == 9216 and len(pr.tile_pairs(128)[0]) == 36
    # the step's XLA side makes the same numbers by selection, to the bit,
    # from float32 rows and from the bfloat16 rows a deployment has
    for rows in (q, q.astype(jnp.bfloat16)):
        assert np.array_equal(np.asarray(pr.phi_selected(rows)),
                              np.asarray(pr.phi(rows)))
    assert pr.phi_selected(q, jnp.bfloat16).dtype == jnp.bfloat16


def _one_sequence(t=50, hq=4, hkv=2, d=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (t, hq, d))
    k = jax.random.normal(ks[1], (t, hkv, d))
    v = jax.random.normal(ks[2], (t, hkv, d))
    logg = jax.nn.log_sigmoid(jax.random.normal(ks[3], (t, hkv)) + 3.0)
    return q, k, v, logg


def _folded(s, z, ring, held):
    """What block 1 of layer 0 holds once its ring's `held` entries are
    in its state: the step form's fold, by its definition."""
    kr, vr, gr = (np.asarray(ring[0, 1, :, i, :held], np.float64)
                  for i in range(3))
    logs = gr[..., 0]                                        # [Hkv, held]
    after = logs.sum(-1, keepdims=True) - np.cumsum(logs, -1)
    w = np.exp(after)
    fk = np.asarray(pr.phi(jnp.asarray(kr, jnp.float32)), np.float64)
    total = np.exp(logs.sum(-1))[:, None, None]
    return (total * np.asarray(s[0, 1]) + np.einsum("jr,jrd,jrf->jdf", w,
                                                    vr, fk),
            total * np.asarray(z[0, 1]) + np.einsum("jr,jrf->jf", w,
                                                    fk)[:, None])


# decode steps after the prompt: every position; then two folds and a last
# step that finds the ring empty, holding one, one short of full, and that
# fills it (the row folds on that step)
STEPS = [50, 2 * pr.RING + 1, 2 * pr.RING + 2, 3 * pr.RING - 1, 3 * pr.RING]


@pytest.mark.parametrize("steps", STEPS)
@pytest.mark.parametrize("chunk", [50, 16, 10, 7])
def test_step_form_is_chunk_form_is_quadratic_form(chunk, steps):
    """One sequence of 50 positions: the definition; chunks that do and do
    not divide it, each padded to a bucket of 16 where it is shorter; the
    last `steps` positions a step each after the same chunks, through a
    ring that folds every `RING` steps. The same outputs and, chunked or
    stepped, the same final state once the ring's entries are counted
    in. Unnormed random q and k: a position whose scores are all
    small divides a sum of 768 signed terms by a small normaliser, which
    the square does not (5e-3 here; a wrong mask or decay moves an output
    by its own size, 1)."""
    tol = 5e-3
    q, k, v, logg = _one_sequence()
    t, _, d = q.shape
    want = pr.retention_quadratic(q, k, v, logg, eps=EPS)
    big = pr.feature_dim(d)
    zeros = (jnp.zeros((1, 2, 2, d, big)), jnp.zeros((1, 2, 2, 1, big)))

    def chunked(upto):
        s, z = zeros
        outs = []
        for start in range(0, upto, chunk):
            n = min(chunk, upto - start)
            cap = max(chunk, 16)
            pad = lambda a: jnp.pad(
                a[start:start + n],
                ((0, cap - n),) + ((0, 0),) * (a.ndim - 1))
            o, s, z = pr.retention_chunk(pad(q), pad(k), pad(v), pad(logg),
                                         s, z, 0, 1, start == 0, n, eps=EPS,
                                         impl="jax")
            outs.append(o[:n])
        return outs, s, z

    outs, s, z = chunked(t)
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=tol)
    outs, s2, z2 = chunked(t - steps)
    ring = jnp.zeros((1, 2, 2, 3, pr.RING, d))
    held, block = jnp.zeros((1,), jnp.int32), jnp.asarray([1])
    folds = 0
    for i in range(t - steps, t):
        o, s2, z2, ring = pr.retention_step(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], logg[i:i + 1], s2, z2, ring,
            0, block, held, eps=EPS, impl="jax")
        fold, held = pr.ring_after(block, held)
        folds += int(fold[0])
        outs.append(o)
    assert (folds, int(held[0])) == divmod(steps, pr.RING)
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=tol)
    s2, z2 = _folded(s2, z2, ring, int(held[0]))
    np.testing.assert_allclose(s[0, 1], s2, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(z[0, 1], z2, rtol=1e-4, atol=1e-3)
    for a in (s, z, ring):
        assert not np.any(np.asarray(a[0, 0]))


# -- (b) prefill and decode through the pool ----------------------------------

@pytest.mark.parametrize("chunk,bucket", [(16, 16), (13, 16), (45, 64)])
def test_pooled_prefill_and_decode_match_the_reference(params, chunk,
                                                       bucket):
    seq = prompt(60, 5)
    want = np.asarray(ref.logits(params, jnp.asarray(seq)[None], TINY)[0])
    got, _ = pooled_logits(params, config(), seq, 45, chunk, bucket=bucket)
    assert sorted(got)[-15:] == list(range(45, 60))
    for pos, lg in got.items():
        assert float(np.max(np.abs(lg - want[pos]))) < TOL, pos


def test_a_padded_bucket_leaves_the_state_bit_identical(params):
    """The same 21 tokens as one chunk in a bucket of 32 and in a bucket of
    64: the padding's rows weigh nothing, so block and logits are the
    same to the bit; the other blocks stay zero."""
    seq = prompt(21, 6)
    a, pool_a = pooled_logits(params, config(), seq, 21, 21, bucket=32)
    b, pool_b = pooled_logits(params, config(), seq, 21, 21, bucket=64)
    for name in ("s", "z"):
        assert np.array_equal(np.asarray(pool_a[name]),
                              np.asarray(pool_b[name]))
        assert not np.any(np.asarray(pool_a[name][:, [0, 1, 3]]))
        assert np.any(np.asarray(pool_a[name][:, 2]))
    # a chunk reads no ring and leaves the one it was given empty
    assert not np.any(np.asarray(pool_a["ring"]))
    assert not np.any(np.asarray(pool_a["held"]))
    np.testing.assert_allclose(a[20], b[20], atol=1e-6)


def test_a_reused_block_gives_what_a_fresh_pool_gives(params):
    """A first chunk resets the block it is given: a second sequence in
    the block a first one left its state in reads what it reads in a
    fresh pool."""
    first, second = prompt(40, 7), prompt(33, 8)
    _, used = pooled_logits(params, config(), first, 30, 16)
    assert np.any(np.asarray(used["s"][:, 2]))
    # the first left ten tokens behind its prompt: a fold, and a ring
    # that holds two, which the second must not inherit
    assert int(used["held"][0, 2]) == (40 - 30) % pr.RING != 0
    fresh, pool_f = pooled_logits(params, config(), second, 25, 16)
    again, pool_a = pooled_logits(params, config(), second, 25, 16,
                                  pool=used)
    for pos in fresh:
        assert np.array_equal(fresh[pos], again[pos]), pos
    for name in ("s", "held"):
        assert np.array_equal(np.asarray(pool_f[name][:, 2]),
                              np.asarray(pool_a[name][:, 2]))


def test_idle_rows_touch_not_even_the_trash_block(params):
    cfg = config()
    seq = prompt(20, 9)
    _, pool = pooled_logits(params, cfg, seq, 20, 16, block=3)
    # a trash block that holds something, to be seen untouched
    pool = {n: a.at[:, 0].set(a[:, 3] if n != "held" else 5)
            for n, a in pool.items()}
    before = {n: np.asarray(a) for n, a in pool.items()}
    # row 0 decodes into block 3; rows 1 and 2 are idle (table 0)
    _, after, counts = retention.decode(
        params, jnp.asarray([5, 0, 0], jnp.int32), pool,
        jnp.asarray([20, 0, 0], jnp.int32),
        jnp.asarray([[3], [0], [0]], jnp.int32), cfg)
    assert [int(c) for c in counts] == [0, 0, 0, 0]
    for name in before:
        got = np.asarray(after[name])
        assert np.array_equal(got[:, [0, 1, 2]], before[name][:, [0, 1, 2]])
    # the token went into block 3's rings and not yet into its state
    assert not np.array_equal(np.asarray(after["ring"])[:, 3],
                              before["ring"][:, 3])
    assert int(after["held"][0, 3]) == 1
    for name in ("s", "z"):
        assert np.array_equal(np.asarray(after[name]), before[name])


def test_a_block_moved_with_a_part_filled_ring_goes_on_as_it_was(params):
    """The three block moves the engine makes (`gpt.copy_block`,
    `gather_block` / `scatter_block`: copy-on-write, the hand-off) carry
    rings and count with the state: a sequence three tokens into a ring
    goes on from a copy in another block, and from a block scattered into
    another pool, as it does where it is."""
    from ray_tpu.models import gpt
    cfg = config()
    seq = prompt(45, 12)
    base, _ = pooled_logits(params, cfg, seq, 20, 16, block=2)
    _, pool = pooled_logits(params, cfg, seq[:23], 20, 16, block=2)
    assert int(pool["held"][0, 2]) == 3
    copied = gpt.copy_block(pool, 2, 1)
    other = gpt.scatter_block(retention.init_pool(cfg, 4, 16),
                              gpt.gather_block(pool, 2), 3)
    for moved, block in ((copied, 1), (other, 3)):
        for t in range(23, len(seq)):
            lg, moved, _ = retention.decode(
                params, jnp.asarray([seq[t]], jnp.int32), moved,
                jnp.asarray([t], jnp.int32),
                jnp.asarray([[block]], jnp.int32), cfg)
            np.testing.assert_allclose(np.asarray(lg[0]), base[t],
                                       atol=1e-5)


# -- (c) through the engine ----------------------------------------------------

def test_engine_streams_the_reference_s_logprobs(params):
    """Five requests over three slots (so blocks are reused), chunked
    prefill then decode: every streamed token is the reference's argmax
    and its logprob the reference's."""
    eng = make_engine(params)
    prompts = [prompt(n, 10 + i) for i, n in enumerate((5, 37, 20, 50, 9))]
    rids = [eng.submit(p, max_new_tokens=10 + i)
            for i, p in enumerate(prompts)]
    for p, rid in zip(prompts, rids):
        got = stream(eng, rid)
        seq = np.concatenate([p, [t for t, _ in got]]).astype(np.int32)
        lp = np.asarray(ref.token_logprobs(
            params, jnp.asarray(seq)[None], TINY)[0])[len(p) - 1:]
        np.testing.assert_allclose([x for _, x in got], lp, atol=TOL)
        lg = np.asarray(ref.logits(params, jnp.asarray(seq)[None], TINY)[0])
        assert [t for t, _ in got] == list(
            np.argmax(lg[len(p) - 1:-1], -1))
    eng.check_invariants()


def test_a_request_holds_one_block_and_the_counters_say_so(params):
    eng = make_engine(params)
    assert (eng.max_blocks, eng.cache_blocks) == (1, 3)
    assert eng._blocks_for(5, 3) == eng._blocks_for(60, 30) == 1
    rids = [eng.submit(prompt(20 + 9 * i, 30 + i), max_new_tokens=4 + i)
            for i in range(5)]
    eng.run_until_idle()
    assert all(len(stream(eng, r)) == 4 + i for i, r in enumerate(rids))
    s = eng.stats()
    assert s["decode_traces"] == 1 and s["retraces_unexpected"] == 0
    assert s["prefix_cache"] is False and s["cached_prefix_blocks"] == 0
    assert s["prefix_hit_tokens"] == 0 and s["preemptions"] == 0
    assert s["pool_bytes"] == sum(a.nbytes for a in eng.cache.values())
    # 2 layers x 2 heads x ((32 + 1) x 768 of state and 3 x RING x 32 of
    # rings) float32 and the rings' int32 count, a block of 96 tokens
    assert s["kv_bytes_per_token"] == (
        2 * 2 * (33 * 768 + 3 * pr.RING * 32) * 4 + 4) / 96
    assert s["cache_blocks"] == 3 and s["blocks_in_use"] == 0
    assert 0 < s["cache_block_utilization"] <= 1
    assert s["state_resets"] == 5 and s["state_folds"] == 0     # short
    assert s["retention_tokens_live"] == s["prefill_tokens"] == sum(
        20 + 9 * i for i in range(5))
    # a prompt's last chunk is padded to the smallest bucket that holds it
    tails = [(20 + 9 * i) % 16 for i in range(5)]
    assert s["retention_tokens_padded"] == sum(
        eng._chunk_bucket_for(n) - n for n in tails if n)
    from benchmarks.harness import serve_replica
    assert set(serve_replica.ENGINE_STATS) <= set(s)
    eng.reset_stats()
    assert eng.stats()["state_resets"] == 0
    eng.check_invariants()


def test_the_engine_counts_a_fold_a_ring_of_decode_tokens(params):
    """`state_folds` beside `decode_tokens`: a request folds once every
    `RING` decode steps, so the two differ by under one ring a request."""
    eng = make_engine(params)
    news = (30, 21, 40, 17)
    rids = [eng.submit(prompt(10 + 7 * i, 40 + i), max_new_tokens=n)
            for i, n in enumerate(news)]
    eng.run_until_idle()
    assert [len(stream(eng, r)) for r in rids] == list(news)
    s = eng.stats()
    # a request's first token is its prefill's
    assert s["decode_tokens"] == sum(news) - len(news)
    assert s["state_folds"] == sum((n - 1) // pr.RING for n in news)
    assert 0 <= s["decode_tokens"] / pr.RING - s["state_folds"] < len(news)
    eng.check_invariants()


def test_only_a_family_of_state_blocks_says_so():
    from ray_tpu.models import gpt, latent_sparse_moe
    fam = retention.FAMILY
    assert (fam.state_blocks, fam.paged, fam.state_keys) == (
        1, False, ("s", "z", "ring", "held"))
    for other in (gpt.GPTConfig().family, latent_sparse_moe.FAMILY):
        assert (other.state_blocks, other.paged, other.state_keys) == (
            0, True, ())
    assert ServingFamily._fields[-5:] == (
        "state_blocks", "paged", "state_keys", "bounded_keys",
        "bounded_tokens")
    assert (fam.bounded_keys, fam.bounded_tokens) == ((), 0)


def test_a_prefix_cache_is_refused(params):
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        InferenceEngine(params, config(), slots=2, max_len=64,
                        prefix_cache=True)


@pytest.mark.parametrize("spec", ["ngram", "draft"])
def test_spec_is_refused_for_a_family_without_a_verify_step(params, spec):
    with pytest.raises(ValueError, match="no verify step"):
        make_engine(params, spec=spec, draft_params=params,
                    draft_cfg=config())


@pytest.mark.parametrize("at", [2, 4, 6])
def test_preempt_and_resume(params, at):
    """Preempted after its first token, in the middle of its steps and
    before its last: nothing is published, the resume re-prefills prompt and
    emitted tokens from the first token into a block it resets, and the
    stream is what an unpreempted one is."""
    base_eng = make_engine(params)
    base = stream(base_eng, base_eng.submit(prompt(40, 50),
                                            max_new_tokens=9))
    faults.install(faults.FaultPlan(seed=3).fail("engine.preempt", at=at,
                                                 times=1))
    eng = make_engine(params)
    rid = eng.submit(prompt(40, 50), max_new_tokens=9)
    eng.run_until_idle()
    s = eng.stats()
    assert s["preemptions"] == 1 and s["state_resets"] == 2
    assert s["reprefill_blocks"] == 1 and s["cached_prefix_blocks"] == 0
    same_stream(stream(eng, rid), base)
    eng.check_invariants()


def test_handoff_carries_the_state(params):
    """`serve/disagg.py`'s hand-off: a prefill engine exports the
    request's one block, a decode engine imports it and streams what one
    engine streams."""
    p = prompt(37, 60)
    one = make_engine(params)
    base = stream(one, one.submit(p, max_new_tokens=6))
    pre = make_engine(params, role="prefill")
    rid = pre.submit(p, max_new_tokens=6)
    blob = pre.handoff_for(rid)
    assert blob["n_blocks"] == len(blob["payload"]) == 1
    assert set(blob["payload"][0]) == {"s", "z", "ring", "held"}
    assert blob["payload"][0]["s"].shape == (2, 2, 32, 768)
    assert blob["payload"][0]["ring"].shape == (2, 2, 3, pr.RING, 32)
    assert blob["payload"][0]["held"].tolist() == [0]
    # the decode engine's block was another sequence's, three tokens into
    # its ring: the import brings the count with the state
    dec = make_engine(params, role="decode", slots=1)
    dec.cache = {**dec.cache, "held": dec.cache["held"].at[0, 1].set(3)}
    same_stream(stream(dec, dec.import_handoff(blob)), base)
    dec.check_invariants()
    pre.check_invariants()


def test_a_cancelled_request_frees_its_block(params):
    eng = make_engine(params, slots=2)
    rid = eng.submit(prompt(30, 70), max_new_tokens=20)
    it = eng.tokens_for(rid)
    next(it)
    assert eng.stats()["blocks_in_use"] == 1
    for _ in range(4):      # cancelled part of the way into a ring
        next(it)
    it.close()
    assert eng.stats()["blocks_in_use"] == 0 and eng.stats()["cancelled"] == 1
    assert np.any(np.asarray(eng.cache["held"]))
    # whoever takes the block next streams what a fresh engine streams
    fresh = make_engine(params, slots=2)
    base = stream(fresh, fresh.submit(prompt(21, 71), max_new_tokens=12))
    rids = [eng.submit(prompt(21, 71), max_new_tokens=12) for _ in range(2)]
    for rid in rids:        # both blocks: one of them is the cancelled one
        same_stream(stream(eng, rid), base)
    eng.check_invariants()


# -- (d) the control -----------------------------------------------------------

def test_a_rounded_state_moves_the_logits(params):
    """`state_round`, the benchmark's control: the state rounded to
    bfloat16 at every write moves the logits by far more than the forms
    differ among themselves."""
    with pytest.raises(ValueError, match="state_round"):
        config(state_round="int8")
    seq = prompt(90, 3)
    sound, _ = pooled_logits(params, config(), seq, 60, 16)
    rounded, _ = pooled_logits(params, config(state_round="bfloat16"), seq,
                               60, 16)
    assert max(float(np.max(np.abs(rounded[p] - sound[p])))
               for p in sound) > 25 * TOL


# -- (e) the kernels, interpreted ----------------------------------------------

def _pool(key, layers=2, blocks=3, hkv=2, d=32):
    """A pool whose every block holds the state a sequence of 128 random
    positions left (a made-up state has a normaliser that is no sum of
    squares, and dividing by it says nothing of a kernel)."""
    big = pr.feature_dim(d)
    s = jnp.zeros((layers, blocks, hkv, d, big))
    z = jnp.zeros((layers, blocks, hkv, 1, big))
    for layer in range(layers):
        for block in range(blocks):
            _, k, v, logg = _one_sequence(
                t=128, hkv=hkv, d=d, seed=int(jax.random.randint(
                    jax.random.fold_in(key, layer * blocks + block), (),
                    0, 1 << 30)))
            _, s, z = pr.retention_chunk(
                jnp.zeros((128, hkv, d)), k, v, logg, s, z, layer, block, 1,
                128, eps=EPS, impl="jax")
    return s, z


# (outputs, states). float32 operands: the kernel's own arithmetic, to
# rounding. bfloat16 operands, as the chip runs it: 2^-9 an operand;
# measured 0.010 on outputs of size 1-2.5 and 0.10 on states of size 26-36
OPERANDS = {"float32": (jnp.float32, 1e-4, 5e-4),
            "bfloat16": (jnp.bfloat16, 0.03, 0.3)}


@pytest.mark.parametrize("operands", sorted(OPERANDS))
@pytest.mark.parametrize("first,length", [(0, 128), (0, 77), (1, 128),
                                          (1, 5)])
def test_retention_chunk_kernel(monkeypatch, operands, first, length):
    dtype, tol, state_tol = OPERANDS[operands]
    monkeypatch.setattr(pr, "MM_DTYPE", dtype)
    q, k, v, logg = _one_sequence(t=128, seed=4)
    s, z = _pool(jax.random.key(5))
    args = (q, k, v, logg, s, z, 1, 2, first, length)
    want = pr.retention_chunk(*args, eps=EPS, impl="jax")
    got = pr.retention_chunk(*args, eps=EPS, impl="pallas")
    np.testing.assert_allclose(got[0][:length], want[0][:length], atol=tol)
    for a, b, old in zip(got[1:], want[1:], (s, z)):
        np.testing.assert_allclose(a, b, atol=state_tol)
        # in place: every other block and layer is what it was
        assert np.array_equal(np.asarray(a[0]), np.asarray(old[0]))
        assert np.array_equal(np.asarray(a[1, :2]), np.asarray(old[1, :2]))


def _rings(key, layers=2, blocks=4, hkv=2, d=32):
    """Rings full of entries of a sequence's kind: what a ring's count
    says it holds, and past that what some step left there."""
    k, v, g = jax.random.normal(key, (3, layers, blocks, hkv, pr.RING, d))
    g = jnp.broadcast_to(jax.nn.log_sigmoid(g[..., :1] + 3.0), g.shape)
    return jnp.stack([k, v, g], axis=3)


@pytest.mark.parametrize("operands", sorted(OPERANDS))
def test_retention_step_kernel(monkeypatch, operands):
    """Five rows in one call: one whose ring is empty, one part of the
    way, two idle (one between live rows, one last) and one that folds.
    The kernel gives what the plain path gives; a row that does not fold
    leaves its state, and every row every other block and layer, to the
    bit what went in; idle rows leave the trash block so."""
    dtype, tol, state_tol = OPERANDS[operands]
    monkeypatch.setattr(pr, "MM_DTYPE", dtype)
    q, k, v, logg = _one_sequence(t=5, seed=6)
    s, z = _pool(jax.random.key(7), blocks=4)
    blocks = jnp.asarray([2, 0, 1, 3, 0], jnp.int32)
    held = jnp.asarray([0, 4, pr.RING - 1, 3, 0], jnp.int32)
    ring = _rings(jax.random.key(8))
    fold, after = pr.ring_after(blocks, held)
    assert fold.tolist() == [False, False, True, False, False]
    assert after.tolist() == [1, 4, 0, 4, 0]
    args = (q, k, v, logg, s, z, ring, 1, blocks, held)
    want = pr.retention_step(*args, eps=EPS, impl="jax")
    got = pr.retention_step(*args, eps=EPS, impl="pallas")
    np.testing.assert_allclose(got[0], want[0], atol=tol)
    assert not np.any(np.asarray(got[0])[[1, 4]])
    for a, b, old in zip(got[1:], want[1:], (s, z, ring)):
        # the fold is float32 either way; bfloat16 rounds phi(q) alone
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)
        assert np.array_equal(np.asarray(a[0]), np.asarray(old[0]))
        assert np.array_equal(np.asarray(a[1, 0]), np.asarray(old[1, 0]))
    for a, old in zip(got[1:3], (s, z)):
        assert np.array_equal(np.asarray(a[1, [2, 3]]),
                              np.asarray(old[1, [2, 3]]))
        assert not np.array_equal(np.asarray(a[1, 1]), np.asarray(old[1, 1]))
    # a live row's ring took the token at its own slot and nothing else
    new = np.asarray(got[3][1])
    for block, at in ((2, 0), (3, 3)):
        assert not np.array_equal(new[block, :, :, at],
                                  np.asarray(ring[1, block, :, :, at]))
        rest = [i for i in range(pr.RING) if i != at]
        assert np.array_equal(new[block][:, :, rest],
                              np.asarray(ring[1, block])[:, :, rest])


def test_the_step_kernel_with_no_live_row_moves_nothing():
    q, k, v, logg = _one_sequence(t=2, seed=12)
    s, z = _pool(jax.random.key(13), layers=1, blocks=2)
    ring = _rings(jax.random.key(14), layers=1, blocks=2)
    got = pr.retention_step(q, k, v, logg, s, z, ring, 0, jnp.zeros(2, int),
                            jnp.zeros(2, int), eps=EPS, impl="pallas")
    assert not np.any(np.asarray(got[0]))
    for a, old in zip(got[1:], (s, z, ring)):
        assert np.array_equal(np.asarray(a), np.asarray(old))


def test_the_kernels_round_the_state_as_the_plain_paths_do():
    q, k, v, logg = _one_sequence(t=128, seed=8)
    s, z = _pool(jax.random.key(9))
    got = pr.retention_chunk(q, k, v, logg, s, z, 0, 1, 0, 128, eps=EPS,
                             state_round="bfloat16", impl="pallas")
    block = np.asarray(got[1][0, 1])
    assert np.array_equal(
        block, np.asarray(block.astype(jnp.bfloat16).astype(np.float32)))
    # a rounded state is rounded at every token: a ring of one, whatever
    # the pool's rings would hold
    ring = jnp.zeros((2, 3, 2, 3, pr.RING, 32))
    blocks, held = jnp.asarray([1, 2]), jnp.zeros(2, jnp.int32)
    assert pr.ring_entries("bfloat16") == 1 < pr.ring_entries("none")
    fold, after = pr.ring_after(blocks, held, "bfloat16")
    assert fold.tolist() == [True, True] and after.tolist() == [0, 0]
    args = (q[:2], k[:2], v[:2], logg[:2], s, z, ring, 0, blocks, held)
    got = pr.retention_step(*args, eps=EPS, state_round="bfloat16",
                            impl="pallas")
    want = pr.retention_step(*args, eps=EPS, state_round="bfloat16",
                             impl="jax")
    for a, b, old in zip(got[1:3], want[1:3], (s, z)):
        block = np.asarray(a[0, 2])
        assert np.array_equal(
            block, np.asarray(block.astype(jnp.bfloat16).astype(np.float32)))
        assert not np.array_equal(block, np.asarray(old[0, 2]))
        # the same float32 sum on either path, rounded once: an ulp of
        # bfloat16 where the two sums straddle a rounding boundary
        np.testing.assert_allclose(a, b, rtol=2 ** -7)


def test_a_shape_with_no_plan_takes_the_plain_path_and_says_so(monkeypatch,
                                                               caplog):
    """A chunk of 100 positions is not whole lane tiles: `impl="auto"` on
    a TPU backend logs one fallback on `ray_tpu.ops` and computes what the
    plain path computes."""
    from ray_tpu.ops import backend
    assert pr.chunk_plan(512, 40, 8, 128) == (4, "")
    assert pr.chunk_plan(128, 40, 8, 128)[0] == 18
    assert pr.step_plan(128) == (1, "")
    assert pr.chunk_plan(100, 4, 2, 32)[0] is None
    q, k, v, logg = _one_sequence(t=100, seed=10)
    s, z = _pool(jax.random.key(11))
    want = pr.retention_chunk(q, k, v, logg, s, z, 0, 1, 1, 100, eps=EPS,
                              impl="jax")
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with caplog.at_level("WARNING", logger="ray_tpu.ops"):
        got = pr.retention_chunk(q, k, v, logg, s, z, 0, 1, 1, 100, eps=EPS)
    assert [r for r in caplog.records if "retention_chunk" in r.message]
    np.testing.assert_allclose(got[0], want[0], atol=1e-6)
