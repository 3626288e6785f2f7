"""Speculative decoding tests: masked multi-query verify attention
(pallas-interpret vs jax parity, single-query equivalence), the batched
`verify_step_paged` forward vs W sequential decode steps (the same
logits AND cache, to float rounding), greedy token-parity with speculation on vs off for
both backends (n-gram lookahead and draft model, incl. shared-prefix /
COW prompts and mid-flight joins), the compile-exactly-once guarantee
(`decode_traces`/`verify_traces`), the temperature accept path, and the
acceptance/tokens-per-step stats contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops import decode_attention as da
from ray_tpu.serve.engine import InferenceEngine


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32"), **kw})


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("block_size", 8)
    return InferenceEngine(params, cfg, **kw)


def rollout_reference(params, prompt, cfg, steps):
    toks = list(prompt)
    for _ in range(steps):
        logits = gpt.forward(params, jnp.asarray([toks]), cfg)[0, -1]
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


# Verify traces [B, W, D] matmuls where W sequential decode steps trace
# [B, D]: the same equations, summed in another order. Measured here:
# 1.0e-7 (f32 pool) and 1.6e-7 (int8 pool) on logits of magnitude 0.1,
# 8e-7 on K/V rows of magnitude 1. 1e-5 is above that by a factor of
# ten to sixty and far under what a wrong mask or a misplaced row does.
ROUNDING = dict(rtol=0, atol=1e-5)


def assert_same_pool(cache_a, cache_b):
    """Two pools written by two programs from the same tokens. Float
    entries (payloads of an f32 pool, scales of an int8 one) agree to
    `ROUNDING`. int8 payloads are `ops.quant`'s deterministic round of
    rows that agree to `ROUNDING`, so a cell can differ only where its
    value sits on a rounding boundary: by one unit, and rarely."""
    assert set(cache_a) == set(cache_b)
    for name in cache_a:
        a, b = np.asarray(cache_a[name]), np.asarray(cache_b[name])
        if a.dtype == np.int8:
            off = np.abs(a.astype(np.int32) - b.astype(np.int32))
            assert off.max() <= 1 and (off != 0).mean() < 0.01, name
        else:
            np.testing.assert_allclose(a, b, err_msg=name, **ROUNDING)


def motif_prompt(rng, vocab, n, motif_len=4):
    motif = rng.integers(1, vocab, motif_len)
    return np.tile(motif, -(-n // motif_len))[:n].astype(np.int32)


# ---------------------------------------------------------------------------
# verify attention kernel
# ---------------------------------------------------------------------------

class TestVerifyAttention:
    def _paged(self, b, s, h, d, bs, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        mb = s // bs
        rng = np.random.default_rng(seed)
        perm = rng.permutation(b * mb) + 1
        tables = perm.reshape(b, mb).astype(np.int32)
        kp = np.zeros((b * mb + 1, bs, h, d), np.float32)
        vp = np.zeros_like(kp)
        for i in range(b):
            for j in range(mb):
                kp[tables[i, j]] = np.asarray(k[i, j * bs:(j + 1) * bs])
                vp[tables[i, j]] = np.asarray(v[i, j * bs:(j + 1) * bs])
        return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)

    @pytest.mark.parametrize("w", [2, 5, 8])
    def test_pallas_matches_jax(self, w):
        b, s, h, d, bs = 3, 48, 2, 16, 8
        kp, vp, tables = self._paged(b, s, h, d, bs)
        q = jax.random.normal(jax.random.PRNGKey(7), (b, w, h, d))
        pos = jnp.asarray([5, 17, 40 - w], jnp.int32)
        ref = da.paged_verify_attention(q, kp, vp, tables, pos,
                                        impl="jax")
        pal = da.paged_verify_attention(q, kp, vp, tables, pos,
                                        impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_rows_match_single_query_decode(self):
        """Row i of the W-query verify must equal a plain decode-step
        attention issued at pos + i — same mask, same math."""
        b, s, h, d, bs, w = 2, 32, 2, 16, 8, 4
        kp, vp, tables = self._paged(b, s, h, d, bs, seed=3)
        q = jax.random.normal(jax.random.PRNGKey(9), (b, w, h, d))
        pos = jnp.asarray([6, 20], jnp.int32)
        out = da.paged_verify_attention(q, kp, vp, tables, pos,
                                        impl="jax")
        for i in range(w):
            single = da.paged_decode_attention(
                q[:, i], kp, vp, tables, pos + i, impl="jax")
            np.testing.assert_allclose(
                np.asarray(out[:, i]), np.asarray(single),
                atol=1e-5, rtol=1e-5)

    def test_rejects_bad_rank(self):
        with pytest.raises(ValueError):
            da.paged_verify_attention(
                jnp.zeros((2, 2, 16)), jnp.zeros((4, 8, 2, 16)),
                jnp.zeros((4, 8, 2, 16)), jnp.zeros((2, 4), jnp.int32),
                jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# verify_step_paged vs sequential decode steps
# ---------------------------------------------------------------------------

class TestVerifyStepPaged:
    def test_matches_sequential_decode(self, setup):
        """One W-token verify forward == W sequential single-token
        decode steps: logits AND the updated cache, to `ROUNDING`."""
        cfg, params = setup
        bs, max_blocks, w = 8, 4, 4
        pool_blocks = 2 * max_blocks + 1
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
        window = rng.integers(1, cfg.vocab_size, (2, w)) \
            .astype(np.int32)
        tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        pos = np.asarray([prompt.size, prompt.size], np.int32)

        def prefilled():
            cache = gpt.init_kv_pool(cfg, pool_blocks, bs)
            for row in range(2):
                _, cache = gpt.prefill_paged(
                    params, jnp.asarray(prompt[None]), cache, cfg,
                    block_table=jnp.asarray(tables[row]),
                    start=0, length=prompt.size)
            return cache

        # path A: batched verify
        va, cache_a = gpt.verify_step_paged(
            params, jnp.asarray(window), prefilled(),
            jnp.asarray(pos), jnp.asarray(tables), cfg)
        # path B: W sequential decode steps
        cache_b = prefilled()
        seq_logits = []
        for j in range(w):
            lg, cache_b = gpt.decode_step_paged(
                params, jnp.asarray(window[:, j]), cache_b,
                jnp.asarray(pos + j), jnp.asarray(tables), cfg)
            seq_logits.append(np.asarray(lg))
        vb = np.stack(seq_logits, axis=1)
        np.testing.assert_allclose(np.asarray(va), vb, **ROUNDING)
        assert_same_pool(cache_a, cache_b)


# ---------------------------------------------------------------------------
# engine: greedy parity + compile-exactly-once
# ---------------------------------------------------------------------------

class TestSpecParity:
    def _run(self, cfg, params, prompts, new, ekw):
        eng = make_engine(cfg, params, **ekw)
        outs = [eng.generate(p, max_new_tokens=new) for p in prompts]
        eng.check_invariants()
        return outs, eng.stats()

    def test_greedy_token_identical_both_backends(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(0)
        prompts = [motif_prompt(rng, cfg.vocab_size, 12),
                   motif_prompt(rng, cfg.vocab_size, 9),
                   rng.integers(1, cfg.vocab_size, 10).astype(np.int32)]
        base, bs = self._run(cfg, params, prompts, 12, {})
        ng, ns = self._run(cfg, params, prompts, 12,
                           dict(spec="ngram", spec_k=4))
        dr, ds = self._run(cfg, params, prompts, 12,
                           dict(spec="draft", spec_k=3,
                                draft_params=params, draft_cfg=cfg))
        assert base == ng == dr
        assert bs["decode_traces"] == 1 and bs["verify_traces"] == 0
        assert ns["verify_traces"] == 1 and ns["decode_traces"] <= 1
        assert ds["verify_traces"] == 1 and ds["draft_traces"] == 1
        # ...and they match the ground-truth full-forward rollout.
        assert base[2] == rollout_reference(params, prompts[2], cfg, 12)

    def test_shared_prefix_cow_parity(self, setup):
        """Two prompts diverging mid-block: the second admits through
        the radix tree with a COW copy; speculation must not perturb
        either stream."""
        cfg, params = setup
        rng = np.random.default_rng(1)
        shared = rng.integers(1, cfg.vocab_size, 19)
        p_a = np.concatenate([shared, rng.integers(1, 128, 6)]) \
            .astype(np.int32)
        p_b = np.concatenate([shared, rng.integers(1, 128, 3)]) \
            .astype(np.int32)
        base, bs = self._run(cfg, params, [p_a, p_b], 7, {})
        ng, ns = self._run(cfg, params, [p_a, p_b], 7,
                           dict(spec="ngram", spec_k=4))
        dr, ds = self._run(cfg, params, [p_a, p_b], 7,
                           dict(spec="draft", spec_k=3,
                                draft_params=params, draft_cfg=cfg))
        assert base == ng == dr
        for s in (bs, ns, ds):
            assert s["cow_copies"] >= 1
        assert ns["verify_traces"] == 1 and ds["verify_traces"] == 1

    @pytest.mark.parametrize("k", [1, 3, 6])
    def test_mixed_k_compiles_once(self, setup, k):
        """Each spec_k is a distinct static verify shape — but within
        one engine the verify executable compiles exactly once no
        matter how ragged the accepted spans get."""
        cfg, params = setup
        rng = np.random.default_rng(2)
        prompts = [motif_prompt(rng, cfg.vocab_size, 11),
                   rng.integers(1, cfg.vocab_size, 7).astype(np.int32),
                   motif_prompt(rng, cfg.vocab_size, 13, motif_len=3)]
        base, _ = self._run(cfg, params, prompts, 10, {})
        got, s = self._run(cfg, params, prompts, 10,
                           dict(spec="ngram", spec_k=k))
        assert got == base
        assert s["verify_traces"] == 1 and s["decode_traces"] <= 1

    def test_mid_flight_join(self, setup):
        """A request admitted while another is mid-speculation joins
        the verify batch without recompiles or cross-talk."""
        cfg, params = setup
        rng = np.random.default_rng(3)
        p1 = motif_prompt(rng, cfg.vocab_size, 12)
        p2 = motif_prompt(rng, cfg.vocab_size, 9)
        p3 = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
        eng = make_engine(cfg, params, spec="ngram", spec_k=4)
        r1 = eng.submit(p1, max_new_tokens=14)
        it = eng.tokens_for(r1)
        got1 = [next(it) for _ in range(4)]     # r1 is decoding
        r2 = eng.submit(p2, max_new_tokens=10)  # joins mid-flight
        got1 += [next(it) for _ in range(4)]
        r3 = eng.submit(p3, max_new_tokens=6)
        got1 += list(it)
        eng.run_until_idle()
        got2 = list(eng._out[r2])
        got3 = list(eng._out[r3])
        assert got1 == rollout_reference(params, p1, cfg, 14)
        assert got2 == rollout_reference(params, p2, cfg, 10)
        assert got3 == rollout_reference(params, p3, cfg, 6)
        s = eng.stats()
        assert s["verify_traces"] == 1 and s["decode_traces"] <= 1
        assert s["prefill_traces"] <= len(eng.chunk_buckets)
        eng.check_invariants()

    def test_temperature_path_runs(self, setup):
        """Rejection-sampling accept: sampled runs terminate with valid
        tokens on both backends (distributional exactness is argued in
        the engine docstring; this pins the plumbing)."""
        cfg, params = setup
        rng = np.random.default_rng(4)
        p = motif_prompt(rng, cfg.vocab_size, 12)
        for ekw in (dict(spec="ngram", spec_k=4),
                    dict(spec="draft", spec_k=3,
                         draft_params=params, draft_cfg=cfg)):
            eng = make_engine(cfg, params, **ekw)
            out = eng.generate(p, max_new_tokens=10, temperature=0.7)
            assert len(out) == 10
            assert all(0 <= t < cfg.vocab_size for t in out)
            eng.check_invariants()


# ---------------------------------------------------------------------------
# engine: stats contract
# ---------------------------------------------------------------------------

class TestSpecStats:
    def test_acceptance_and_tokens_per_step(self, setup):
        """Self-drafting (draft == target) accepts everything under
        greedy: tokens_per_step approaches k+1."""
        cfg, params = setup
        eng = make_engine(cfg, params, spec="draft", spec_k=3,
                          draft_params=params, draft_cfg=cfg)
        rng = np.random.default_rng(5)
        eng.generate(rng.integers(1, cfg.vocab_size, 10),
                     max_new_tokens=13)
        s = eng.stats()
        assert s["acceptance_rate"] > 0.9
        assert s["tokens_per_step"] > 2.0
        assert s["spec_steps"] > 0 and s["spec"] == "draft"

    def test_spec_off_tokens_per_step_is_one(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params)
        eng.generate([1, 2, 3, 4], max_new_tokens=6)
        s = eng.stats()
        assert s["tokens_per_step"] == 1.0
        assert s["acceptance_rate"] == 0.0 and s["spec"] == ""

    def test_windowed_load_stats_and_reset(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, spec="ngram", spec_k=2)
        rng = np.random.default_rng(6)
        eng.generate(motif_prompt(rng, cfg.vocab_size, 10),
                     max_new_tokens=8)
        s = eng.stats()
        assert s["decode_tok_s"] > 0
        assert s["queue_wait_ms_p50"] > 0
        assert s["queue_wait_ms_p99"] >= s["queue_wait_ms_p50"]
        assert s["queue_depth"] == 0
        eng.reset_stats()
        s = eng.stats()
        # every satellite stat zeroes; the trace counters do NOT
        assert s["decode_tok_s"] == 0.0 and s["tokens_per_step"] == 0.0
        assert s["queue_wait_ms_p50"] == 0.0
        assert s["acceptance_rate"] == 0.0 and s["spec_steps"] == 0
        assert s["verify_traces"] == 1

    def test_queue_depth_counts_pending(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1)
        for _ in range(3):
            eng.submit([1, 2, 3], max_new_tokens=4)
        eng.step()   # admits one, two stay queued
        assert eng.stats()["queue_depth"] == 2
        eng.run_until_idle()
        assert eng.stats()["queue_depth"] == 0

    def test_ngram_propose_unit(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, spec="ngram", spec_k=3,
                          ngram_max=3, ngram_min=1)
        from ray_tpu.serve.engine import _Slot
        s = _Slot(history=[5, 6, 7, 9, 5, 6, 7])
        # suffix [5,6,7] recurs at position 0; continuation is [9,5,6]
        assert eng._ngram_propose(s) == [9, 5, 6]
        s = _Slot(history=[1, 2, 3, 4])     # no repeat -> no proposal
        assert eng._ngram_propose(s) is None

    def test_bad_spec_config_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            make_engine(cfg, params, spec="bogus")
        with pytest.raises(ValueError):
            make_engine(cfg, params, spec="draft")   # no draft model
        with pytest.raises(ValueError):
            make_engine(cfg, params, spec="ngram", spec_k=0)


# ---------------------------------------------------------------------------
# quantized cache (int8 KV) through the speculative path
# ---------------------------------------------------------------------------

class TestQuantizedSpec:
    def test_verify_kernel_quantized_parity(self):
        """The W-query verify kernel's in-VMEM dequant == the gather-
        then-dequant jax path on an int8 pool."""
        from ray_tpu.ops import quant
        b, s, h, d, bs, w = 3, 48, 2, 16, 8, 5
        kp, vp, tables = TestVerifyAttention()._paged(b, s, h, d, bs)
        kq, ksc = quant.quantize_rows(kp)
        vq, vsc = quant.quantize_rows(vp)
        q = jax.random.normal(jax.random.PRNGKey(7), (b, w, h, d))
        pos = jnp.asarray([5, 17, 40 - w], jnp.int32)
        ref = da.paged_verify_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc,
            impl="jax")
        pal = da.paged_verify_attention(
            q, kq, vq, tables, pos, k_scale=ksc, v_scale=vsc,
            impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_verify_step_quantized_matches_sequential(self, setup):
        """Batched verify on an int8 pool == W sequential decode steps:
        logits AND cache INCLUDING scale arrays, to `ROUNDING`. Both
        paths quantize each token's K/V row once at write through the
        same deterministic round-trip, so the quantization adds nothing
        to the difference between the two programs."""
        _, params = setup
        cfg = tiny_cfg(kv_dtype="int8")
        bs, w = 8, 4
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
        window = rng.integers(1, cfg.vocab_size, (2, w)) \
            .astype(np.int32)
        tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        pos = np.asarray([prompt.size, prompt.size], np.int32)

        def prefilled():
            cache = gpt.init_kv_pool(cfg, 9, bs)
            for row in range(2):
                _, cache = gpt.prefill_paged(
                    params, jnp.asarray(prompt[None]), cache, cfg,
                    block_table=jnp.asarray(tables[row]),
                    start=0, length=prompt.size)
            return cache

        va, cache_a = gpt.verify_step_paged(
            params, jnp.asarray(window), prefilled(),
            jnp.asarray(pos), jnp.asarray(tables), cfg)
        cache_b = prefilled()
        seq_logits = []
        for j in range(w):
            lg, cache_b = gpt.decode_step_paged(
                params, jnp.asarray(window[:, j]), cache_b,
                jnp.asarray(pos + j), jnp.asarray(tables), cfg)
            seq_logits.append(np.asarray(lg))
        np.testing.assert_allclose(np.asarray(va),
                                   np.stack(seq_logits, axis=1),
                                   **ROUNDING)
        assert set(cache_a) == {"k", "v", "k_scale", "v_scale"}
        assert_same_pool(cache_a, cache_b)

    def test_greedy_spec_parity_quantized(self, setup):
        """Speculation on/off over an int8 cache: token-identical to
        each other AND to the f32 no-spec engine (peaked params keep
        the argmax gaps above quantization noise)."""
        _, base_params = setup
        params = {**base_params, "embed": base_params["embed"] * 8}
        cfg_q = tiny_cfg(kv_dtype="int8")
        rng = np.random.default_rng(8)
        prompts = [motif_prompt(rng, cfg_q.vocab_size, 12),
                   rng.integers(1, cfg_q.vocab_size, 9)
                   .astype(np.int32)]

        def run(cfg, **ekw):
            eng = make_engine(cfg, params, **ekw)
            outs = [eng.generate(p, max_new_tokens=10) for p in prompts]
            eng.check_invariants()
            return outs, eng.stats()

        f32, _ = run(tiny_cfg())
        base, bs = run(cfg_q)
        ng, ns = run(cfg_q, spec="ngram", spec_k=4)
        dr, ds = run(cfg_q, spec="draft", spec_k=3,
                     draft_params=params, draft_cfg=cfg_q)
        assert f32 == base == ng == dr
        assert ns["verify_traces"] == 1 and ds["verify_traces"] == 1
        assert bs["decode_traces"] == 1
