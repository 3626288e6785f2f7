"""Inference benchmark: autoregressive decode throughput through the
continuous-batching engine (ray_tpu/serve/engine.py).

Prints ONE JSON line. Headline fields follow bench.py's contract
({"metric", "value", "unit", "vs_baseline"}); the inference-specific
extras ride alongside:

  prefill_tokens_per_sec   prompt tokens absorbed per second (chunked
                           prefill, cache write included)
  decode_tokens_per_sec    generated tokens per second across all slots
                           (the headline `value`)
  p50_token_latency_ms     per-decode-step wall latency percentiles —
  p99_token_latency_ms     each step emits one token per resident slot,
                           so this IS per-token latency for a stream
  slot_occupancy           mean fraction of cache slots resident over
                           the timed region (continuous batching's job
                           is to keep this near 1.0)
  prefix_hit_rate          fraction of prompt tokens served from the
                           radix prefix cache instead of prefilled
  cache_block_utilization  mean fraction of the paged pool's blocks
                           live during the timed region
  max_admission_stall_ms   the longest a decode step waited on that
                           tick's admission work (chunked prefill is
                           supposed to bound this to one chunk)
  weight_swap_ms           in-place weight hot-swap latency: the
                           update_params call to the first post-swap
                           token, with the trace counters asserted
                           unchanged (no recompile)
  rollout_tok_s            rl.EngineSampler trajectory-generation rate
                           through the warm engine (tokens/s)
  ttft_ms_p50 / _p99       submit-to-first-token percentiles over the
                           timed region (the flight recorder's TTFT)
  retraces_unexpected      retrace-sentinel violations of the pinned
                           compile-once paths (must be 0 in a bench)
  trace_overhead_pct       flight-recorder cost: wall-time delta of the
                           same workload with per-request tracing
                           sampled at 1.0 vs 0.0. Only measured when
                           RAY_TPU_INFER_BENCH_TRACE_OVERHEAD=1 (it
                           doubles the run); 0.0 otherwise
  priority_mix             the PRIORITY_MIX knob this run used ("" off)
  preemptions              streams evicted for a higher class during
                           the priority phase (0 when the mix is unset)
  reprefill_blocks         resume blocks re-prefilled that the radix
                           cache did not cover
  queue_wait_ms_p99_by_class  per-class p99 submit-to-first-token (ms),
                           keyed by class id ({} when the mix is unset)
  disagg_decode_tpot_ms_p99 / colocated_decode_tpot_ms_p99
                           client-observed inter-token gap p99 for the
                           decode streams of the disagg A/B phase,
                           role-split vs colocated — the disagg
                           headline: the role-split number stays flat
                           under long-prefill interference while the
                           colocated one absorbs whole prefill chunks
                           between decode ticks
  disagg_ttft_ms_p99 / colocated_ttft_ms_p99
                           submit-to-first-token p99 for those streams
                           (the disagg side includes the KV handoff)
  kv_transfer_gbps         KV-block handoff bandwidth, export blob to
                           imported pool blocks (GB/s, import wall)
  kv_blocks_streamed       paged KV blocks shipped prefill -> decode
  kv_dtype / weight_dtype  the quantization knobs this run used
  pool_bytes               device bytes of the preallocated KV block
                           pool(s), scale arrays included
  capacity_streams_per_gb  concurrent mean-context streams one GiB of
                           pool budget holds (1 GiB / kv_bytes_per_token
                           / mean context) — the capacity lever
                           kv_dtype="int8" pulls
  capacity_vs_f32          kv-bytes-per-token ratio vs a full-precision
                           f32 pool of the same geometry (2.0 for the
                           default bf16 pool, >3x for int8+scales)
  quality_logprob_delta    quantization quality proxy: mean |per-token
                           greedy logprob delta| vs an f32-pool f32-
                           weight engine on the same prompts (0.0 when
                           nothing is quantized — nothing to compare)

Knobs (env vars, platform-tuned defaults in main()):
  RAY_TPU_INFER_BENCH_SLOTS          resident decode slots (cache batch)
  RAY_TPU_INFER_BENCH_MAX_LEN        per-request cache capacity
  RAY_TPU_INFER_BENCH_PROMPT        prompt length per request
  RAY_TPU_INFER_BENCH_NEW            generated tokens per request
  RAY_TPU_INFER_BENCH_REQUESTS       total requests in the timed region
  RAY_TPU_INFER_BENCH_BLOCK          paged-cache block size
  RAY_TPU_INFER_BENCH_CHUNK          prefill chunk budget (tokens/tick)
  RAY_TPU_INFER_BENCH_SHARED_PREFIX  tokens of system prompt shared by
                                     every request (0 = fully random);
                                     exercises radix sharing
  RAY_TPU_INFER_BENCH_RAGGED         1 = ragged prompt lengths, drawn
                                     uniformly from [PROMPT/2, PROMPT]
  RAY_TPU_INFER_BENCH_SPEC           "" (off) | "ngram" | "draft":
                                     speculative decoding backend. When
                                     set, prompts switch to a repeated-
                                     motif workload (the case n-gram
                                     lookahead exists for), a second
                                     spec-enabled engine runs the same
                                     traffic, and the JSON reports
                                     acceptance_rate / tokens_per_step /
                                     spec_decode_tok_s alongside the
                                     unchanged baseline headline
  RAY_TPU_INFER_BENCH_SPEC_K         speculated tokens per step (k)
  RAY_TPU_INFER_BENCH_DRAFT_LAYERS   draft model depth for SPEC=draft
  RAY_TPU_INFER_BENCH_KV_DTYPE       "f32" | "int8": paged KV pool
                                     element type (int8 = per-row-scale
                                     quantized pool, models/gpt.py)
  RAY_TPU_INFER_BENCH_WEIGHT_DTYPE   "f32" | "int8": weight-only decode
                                     matmul precision
  RAY_TPU_INFER_BENCH_PRIORITY_MIX   comma-separated per-class request
                                     counts, lowest class first (e.g.
                                     "3,0,1" = 3 class-0 + 1 class-2).
                                     When set, an extra phase runs the
                                     mix through a priority-enabled
                                     engine — the low classes admitted
                                     and decoding first, the high wave
                                     arriving into a loaded pool — and
                                     the JSON gains `priority_mix`,
                                     `preemptions`, `reprefill_blocks`,
                                     and `queue_wait_ms_p99_by_class`
                                     (all neutral when unset)
  RAY_TPU_INFER_BENCH_CACHE_BLOCKS   paged-pool size for the priority
                                     phase (0 = engine default); size it
                                     below the mix's total footprint to
                                     force block-pressure preemption
  RAY_TPU_INFER_BENCH_DISAGG         1 (default) = run the disaggregated
                                     prefill/decode A/B: the same mixed
                                     workload (decode streams + long-
                                     prefill interference) through equal
                                     engine counts colocated vs role-
                                     split, reporting client-observed
                                     decode TPOT/TTFT p99 per mode plus
                                     kv_transfer_gbps for the KV-block
                                     handoffs; 0 = skip (zeros in JSON)
  RAY_TPU_INFER_BENCH_PREFILL_REPLICAS  prefill-role engines in the A/B
  RAY_TPU_INFER_BENCH_DECODE_REPLICAS   decode-role engines in the A/B

Baseline: single-token decode is HBM-bandwidth-bound — every step
streams the full parameter set plus the live KV prefix through the chip
regardless of batch. `vs_baseline` is measured decode tokens/s divided
by the bandwidth-roofline tokens/s (params + mean live cache bytes per
step, slots tokens per step, chip HBM bandwidth from the table below):
1.0 means decode runs at memory speed; the gap is dispatch + compute +
unfused overhead. CPU smoke reports 0.0, as in bench.py.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def decode_roofline_tokens_per_sec(cfg, slots: int, mean_ctx: float,
                                   device) -> float:
    """Bandwidth-bound decode ceiling: one step reads all params once
    plus each slot's live K/V prefix, and emits `slots` tokens.

    Quantization rescales the denominator — that is the whole point of
    the int8 paths: `weight_dtype="int8"` reads the layer matmuls at 1
    byte/param (embed/norms stay full precision), and `kv_dtype="int8"`
    reads each cached position at H*(Dh + 4) bytes per K or V row (int8
    payload + one f32 scale per (position, head)) instead of
    H*Dh*bpe."""
    # param count straight from config (no tracing needed):
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    matmul_params = L * (4 * d * d + 3 * d * f)
    full_params = v * d + cfg.max_seq_len * d + d + L * 2 * d
    bpe = 2 if "bfloat16" in cfg.dtype else 4
    w_bpe = 1 if cfg.weight_dtype == "int8" else bpe
    if cfg.kv_dtype == "int8":
        kv_row = cfg.n_heads * (cfg.head_dim + 4)
    else:
        kv_row = cfg.n_heads * cfg.head_dim * bpe
    kv_bytes = slots * mean_ctx * 2 * kv_row
    bytes_per_step = (full_params * bpe + matmul_params * w_bpe
                      + kv_bytes)
    from ray_tpu.util import telemetry
    return (telemetry.device_peaks(device)["hbm_bytes_per_s"] * slots
            / bytes_per_step)


def main():
    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import InferenceEngine

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if on_tpu:
        from ray_tpu.util import telemetry
        from ray_tpu.util.compile_cache import enable_compile_cache
        enable_compile_cache()
        telemetry.device_peaks(devices[0])    # unknown kind: raise now
        cfg = gpt.GPTConfig(vocab_size=50304, d_model=1024, n_layers=12,
                            n_heads=16, d_ff=4096, max_seq_len=1024)
        slots, max_len, prompt_len, new_tokens, requests = \
            8, 1024, 128, 128, 32
    else:   # CPU smoke mode — the full engine path on a toy model.
        cfg = gpt.small(n_layers=1, max_seq_len=64, d_model=64,
                        d_ff=256, n_heads=2, vocab_size=256)
        slots, max_len, prompt_len, new_tokens, requests = 2, 32, 6, 4, 4

    slots = _env_int("RAY_TPU_INFER_BENCH_SLOTS", slots)
    max_len = _env_int("RAY_TPU_INFER_BENCH_MAX_LEN", max_len)
    prompt_len = _env_int("RAY_TPU_INFER_BENCH_PROMPT", prompt_len)
    new_tokens = _env_int("RAY_TPU_INFER_BENCH_NEW", new_tokens)
    requests = _env_int("RAY_TPU_INFER_BENCH_REQUESTS", requests)
    block_size = _env_int("RAY_TPU_INFER_BENCH_BLOCK", 16)
    chunk = _env_int("RAY_TPU_INFER_BENCH_CHUNK", 0)
    shared_prefix = _env_int("RAY_TPU_INFER_BENCH_SHARED_PREFIX", 0)
    ragged = _env_int("RAY_TPU_INFER_BENCH_RAGGED", 0)
    spec = os.environ.get("RAY_TPU_INFER_BENCH_SPEC", "")
    spec_k = _env_int("RAY_TPU_INFER_BENCH_SPEC_K", 4)
    draft_layers = _env_int("RAY_TPU_INFER_BENCH_DRAFT_LAYERS", 1)
    kv_dtype = os.environ.get("RAY_TPU_INFER_BENCH_KV_DTYPE", "f32")
    weight_dtype = os.environ.get(
        "RAY_TPU_INFER_BENCH_WEIGHT_DTYPE", "f32")
    if kv_dtype != "f32" or weight_dtype != "f32":
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype,
                                  weight_dtype=weight_dtype)
    if spec not in ("", "ngram", "draft"):
        raise SystemExit("SPEC must be '', 'ngram' or 'draft'")
    if prompt_len + new_tokens > max_len:
        raise SystemExit("PROMPT + NEW must fit in MAX_LEN")
    if shared_prefix >= prompt_len:
        raise SystemExit("SHARED_PREFIX must be < PROMPT")

    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(0, cfg.vocab_size, shared_prefix)

    if spec:
        # Repeated-suffix workload: each prompt tiles a short motif, so
        # the request's own history predicts its continuation — the
        # regime n-gram lookahead (and cheap drafting) pays off in.
        def make_prompt():
            motif = rng.integers(0, cfg.vocab_size, 4)
            reps = -(-prompt_len // motif.size)
            return np.tile(motif, reps)[:prompt_len].astype(np.int32)
    else:
        def make_prompt():
            p = prompt_len
            if ragged:
                p = int(rng.integers(
                    max(prompt_len // 2, shared_prefix + 1),
                    prompt_len + 1))
            suffix = rng.integers(0, cfg.vocab_size, p - shared_prefix)
            return np.concatenate([system_prompt, suffix]) \
                .astype(np.int32)

    def run_engine(extra_kwargs):
        eng = InferenceEngine(params, cfg, slots=slots, max_len=max_len,
                              block_size=block_size,
                              prefill_chunk=chunk or None,
                              **extra_kwargs)
        # Warmup: compiles the prefill chunk buckets and the (single)
        # decode/verify executables, then drops compile time from the
        # accounting.
        for _ in range(min(requests, slots)):
            eng.submit(make_prompt(), max_new_tokens=new_tokens)
        eng.run_until_idle()
        eng.reset_stats()
        for _ in range(requests):
            eng.submit(make_prompt(), max_new_tokens=new_tokens)
        # Wall time of the timed region (not just attributed device
        # time): the flight recorder's per-token work happens between
        # device calls, so only wall time can see its overhead.
        t0 = time.perf_counter()
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        return eng, eng.stats(), wall

    eng, s, _ = run_engine({})
    assert s["decode_traces"] == 1, "decode recompiled mid-bench"
    assert s["retraces_unexpected"] == 0, "retrace sentinel tripped"

    # --- flight-recorder overhead probe (opt-in: doubles the run) ------
    trace_overhead_pct = 0.0
    if _env_int("RAY_TPU_INFER_BENCH_TRACE_OVERHEAD", 0):
        _, _, wall_on = run_engine({"telemetry_sample": 1.0})
        _, _, wall_off = run_engine({"telemetry_sample": 0.0})
        trace_overhead_pct = ((wall_on - wall_off)
                              / max(wall_off, 1e-9) * 100.0)

    # --- quantization quality proxy ------------------------------------
    # Greedy-decode the same prompts through the (warm, pre-swap)
    # quantized engine and a fresh full-precision one, and report the
    # mean absolute per-token logprob drift — the pinned bound for
    # "int8 is tight-allclose to f32". 0.0 when nothing is quantized.
    quality_logprob_delta = 0.0
    if cfg.kv_dtype != "f32" or cfg.weight_dtype != "f32":
        import dataclasses
        fcfg = dataclasses.replace(cfg, kv_dtype="f32",
                                   weight_dtype="f32")
        feng = InferenceEngine(params, fcfg, slots=slots,
                               max_len=max_len, block_size=block_size,
                               prefill_chunk=chunk or None)
        deltas = []
        for p in [make_prompt() for _ in range(min(requests, slots))]:
            a = [t.logprob for t in
                 eng.generate(p, max_new_tokens=new_tokens)]
            b = [t.logprob for t in
                 feng.generate(p, max_new_tokens=new_tokens)]
            deltas.extend(abs(x - y) for x, y in zip(a, b))
        quality_logprob_delta = float(np.mean(deltas))

    # --- RL flywheel probe: in-place weight hot-swap + engine rollout --
    # Reuses the warm baseline engine: update_params must not retrigger
    # any compilation (trace counters pinned), weight_swap_ms runs from
    # the update_params call to the first post-swap token, and
    # rollout_tok_s is the EngineSampler's trajectory-generation rate.
    from ray_tpu.rl.sampler import EngineSampler
    sampler = EngineSampler(eng, max_new_tokens=new_tokens,
                            temperature=1.0)
    probe = [make_prompt() for _ in range(min(requests, slots))]
    # First swap warms the donated-copy executable (one compile, ever);
    # the second is the steady-state measurement.
    eng.update_params(gpt.init_params(jax.random.PRNGKey(2), cfg))
    sampler.rollout(probe)
    traces_before = (eng.decode_traces, eng.prefill_traces,
                     eng.swap_traces)
    eng.update_params(gpt.init_params(jax.random.PRNGKey(3), cfg))
    sampler.rollout(probe)
    assert (eng.decode_traces, eng.prefill_traces,
            eng.swap_traces) == traces_before, \
        "weight hot-swap retriggered compilation"
    swap_stats = eng.stats()
    assert swap_stats["swaps"] == 2 and swap_stats["params_version"] == 2
    weight_swap_ms = swap_stats["weight_swap_ms"]
    rollout_tok_s = sampler.last_rollout_tok_s

    # --- priority-mix phase: class contention under a tight pool -------
    priority_mix = os.environ.get("RAY_TPU_INFER_BENCH_PRIORITY_MIX", "")
    preemptions = reprefill_blocks = 0
    wait_p99_by_class: dict[str, float] = {}
    if priority_mix:
        mix = [int(x) for x in priority_mix.split(",")]
        cache_blocks = _env_int("RAY_TPU_INFER_BENCH_CACHE_BLOCKS", 0)
        pkw = {"priority_classes": max(len(mix), 2)}
        if cache_blocks:
            pkw["cache_blocks"] = cache_blocks
        peng = InferenceEngine(params, cfg, slots=slots, max_len=max_len,
                               block_size=block_size,
                               prefill_chunk=chunk or None, **pkw)
        # Low classes first, pumped until they hold blocks and decode —
        # so the higher waves land on a loaded pool and any preemption
        # is real block pressure, not queue ordering.
        for cls, n in enumerate(mix):
            for _ in range(n):
                peng.submit(make_prompt(), max_new_tokens=new_tokens,
                            priority=cls)
            for _ in range(200):
                if not peng.stats()["pending"]:
                    break
                peng.step()
        peng.run_until_idle()
        ps = peng.stats()
        preemptions = ps["preemptions"]
        reprefill_blocks = ps["reprefill_blocks"]
        wait_p99_by_class = {
            c: round(pc["queue_wait_ms_p99"], 3)
            for c, pc in ps["per_class"].items()}
        peng.check_invariants()

    # --- disaggregated prefill/decode A/B ------------------------------
    # Same mixed workload (decode streams + long-prefill interference)
    # through the same total engine count, split two ways. Colocated:
    # every engine takes both kinds of traffic, so each long prompt's
    # chunked prefill runs BETWEEN that engine's decode ticks and
    # stretches its streams' inter-token gaps. Disagg: prefill-role
    # engines absorb the long prompts and hand finished KV blocks to
    # decode-role engines, whose ticks stay pure decode. TPOT is
    # measured CLIENT-SIDE (inter-token arrival gaps at the consumer) —
    # the engine's own p99_token_latency_ms only times the decode device
    # call and cannot see prefill chunks sitting between ticks.
    disagg = _env_int("RAY_TPU_INFER_BENCH_DISAGG", 1)
    pre_n = _env_int("RAY_TPU_INFER_BENCH_PREFILL_REPLICAS", 1)
    dec_n = _env_int("RAY_TPU_INFER_BENCH_DECODE_REPLICAS", 1)
    disagg_tpot_p99 = coloc_tpot_p99 = 0.0
    disagg_ttft_p99 = coloc_ttft_p99 = 0.0
    kv_transfer_gbps = 0.0
    kv_blocks_streamed = 0
    if disagg:
        import threading

        total_engines = pre_n + dec_n
        n_streams = slots * dec_n
        n_long = max(2, requests)
        long_p = min(max_len - 2,
                     max(prompt_len * 4, prompt_len + 2 * block_size))

        def make_long():
            return rng.integers(0, cfg.vocab_size, long_p) \
                .astype(np.int32)

        def new_engine(role=None):
            ekw = {"role": role} if role else {}
            return InferenceEngine(params, cfg, slots=slots,
                                   max_len=max_len,
                                   block_size=block_size,
                                   prefill_chunk=chunk or None, **ekw)

        def drain(e, rid, recs, t_submit):
            ttft, gaps, last = None, [], t_submit
            for _tok in e.tokens_for(rid):
                now = time.perf_counter()
                if ttft is None:
                    ttft = (now - t_submit) * 1e3
                else:
                    gaps.append((now - last) * 1e3)
                last = now
            recs.append((ttft, gaps))

        def _p99(xs):
            return float(np.percentile(xs, 99)) if xs else 0.0

        def collect(recs):
            ttfts = [t for t, _ in recs if t is not None]
            gaps = [g for _, gs in recs for g in gs]
            return _p99(ttfts), _p99(gaps)

        # -- colocated baseline ----------------------------------------
        engines = [new_engine() for _ in range(total_engines)]
        for e in engines:       # warm both prompt-shape buckets
            e.generate(make_prompt(), max_new_tokens=2)
            e.generate(make_long(), max_new_tokens=1)
        stream_recs: list = []
        sink: list = []
        threads = []
        for i in range(n_streams):
            e = engines[i % total_engines]
            t0 = time.perf_counter()
            rid = e.submit(make_prompt(), max_new_tokens=new_tokens)
            th = threading.Thread(target=drain,
                                  args=(e, rid, stream_recs, t0),
                                  daemon=True)
            th.start()
            threads.append(th)
        time.sleep(0.05)        # let the streams reach steady decode
        for j in range(n_long):
            e = engines[j % total_engines]
            t0 = time.perf_counter()
            rid = e.submit(make_long(), max_new_tokens=1)
            th = threading.Thread(target=drain, args=(e, rid, sink, t0),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300)
        coloc_ttft_p99, coloc_tpot_p99 = collect(stream_recs)

        # -- disaggregated ---------------------------------------------
        pres = [new_engine("prefill") for _ in range(pre_n)]
        decs = [new_engine("decode") for _ in range(dec_n)]
        for k, de in enumerate(decs):   # warm prefill + import + decode
            pe = pres[k % pre_n]
            for mk, mn in ((make_long, 1), (make_prompt, 2)):
                blob = pe.handoff_for(
                    pe.submit(mk(), max_new_tokens=mn))
                list(de.tokens_for(de.import_handoff(blob)))
        stream_recs, sink, threads = [], [], []
        kv_bytes_streamed = 0
        import_wall = 0.0
        for i in range(n_streams):
            pe, de = pres[i % pre_n], decs[i % dec_n]
            t0 = time.perf_counter()
            rid = pe.submit(make_prompt(), max_new_tokens=new_tokens)
            blob = pe.handoff_for(rid)
            ti = time.perf_counter()
            drid = de.import_handoff(blob)
            import_wall += time.perf_counter() - ti
            kv_bytes_streamed += blob["kv_bytes"]
            kv_blocks_streamed += blob["n_blocks"]
            th = threading.Thread(target=drain,
                                  args=(de, drid, stream_recs, t0),
                                  daemon=True)
            th.start()
            threads.append(th)
        time.sleep(0.05)

        _kv_mu = threading.Lock()

        def long_disagg(pe, de, t0):
            nonlocal kv_bytes_streamed, kv_blocks_streamed, import_wall
            rid = pe.submit(make_long(), max_new_tokens=1)
            blob = pe.handoff_for(rid)
            ti = time.perf_counter()
            drid = de.import_handoff(blob)
            with _kv_mu:
                import_wall += time.perf_counter() - ti
                kv_bytes_streamed += blob["kv_bytes"]
                kv_blocks_streamed += blob["n_blocks"]
            drain(de, drid, sink, t0)

        for j in range(n_long):
            th = threading.Thread(
                target=long_disagg,
                args=(pres[j % pre_n], decs[j % dec_n],
                      time.perf_counter()),
                daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300)
        disagg_ttft_p99, disagg_tpot_p99 = collect(stream_recs)
        kv_transfer_gbps = kv_bytes_streamed / max(import_wall,
                                                   1e-9) / 1e9
        for pe in pres:
            assert pe.stats()["decode_steps"] == 0, \
                "prefill engine decoded"
            pe.check_invariants()
        for de in decs:
            de.check_invariants()

    spec_stats = None
    if spec:
        ekw = {"spec": spec, "spec_k": spec_k}
        if spec == "draft":
            import dataclasses
            dcfg = dataclasses.replace(cfg, n_layers=draft_layers)
            ekw["draft_cfg"] = dcfg
            ekw["draft_params"] = gpt.init_params(
                jax.random.PRNGKey(1), dcfg)
        _, spec_stats, _ = run_engine(ekw)
        assert spec_stats["decode_traces"] <= 1, \
            "decode recompiled mid-bench"
        assert spec_stats["verify_traces"] == 1, \
            "verify recompiled mid-bench"

    prefill_tok_s = s["prefill_tokens"] / max(s["prefill_time_s"], 1e-9)
    decode_tok_s = s["decode_tokens"] / max(s["decode_time_s"], 1e-9)
    spec_decode_tok_s = (
        spec_stats["decode_tokens"] / max(spec_stats["decode_time_s"],
                                          1e-9)
        if spec_stats else 0.0)
    mean_ctx = prompt_len + new_tokens / 2
    vs_baseline = (decode_tok_s / decode_roofline_tokens_per_sec(
        cfg, slots, mean_ctx, devices[0])) if on_tpu else 0.0

    print(json.dumps({
        "metric": "gpt_decode_tokens_per_sec",
        "value": round(decode_tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "prefill_tokens_per_sec": round(prefill_tok_s, 1),
        "decode_tokens_per_sec": round(decode_tok_s, 1),
        "p50_token_latency_ms": round(s["p50_token_latency_ms"], 3),
        "p99_token_latency_ms": round(s["p99_token_latency_ms"], 3),
        "slot_occupancy": round(s["slot_occupancy"], 3),
        "prefix_hit_rate": round(s["prefix_hit_rate"], 3),
        "cache_block_utilization": round(
            s["cache_block_utilization"], 3),
        "max_admission_stall_ms": round(
            s["max_admission_stall_ms"], 3),
        "block_size": s["block_size"],
        "cache_blocks": s["cache_blocks"],
        "shared_prefix": shared_prefix,
        # quantization / capacity
        "kv_dtype": cfg.kv_dtype,
        "weight_dtype": cfg.weight_dtype,
        "pool_bytes": s["pool_bytes"],
        "capacity_streams_per_gb": round(
            (1 << 30) / (s["kv_bytes_per_token"] * mean_ctx), 1),
        "capacity_vs_f32": round(
            (cfg.n_layers * 2 * cfg.n_heads * cfg.head_dim * 4)
            / s["kv_bytes_per_token"], 3),
        "quality_logprob_delta": round(quality_logprob_delta, 5),
        # speculative decoding (zeros / 1.0-neutral when SPEC is off)
        "spec": spec,
        "spec_k": spec_k if spec else 0,
        "acceptance_rate": round(
            spec_stats["acceptance_rate"] if spec_stats else 0.0, 3),
        "tokens_per_step": round(
            spec_stats["tokens_per_step"] if spec_stats
            else s["tokens_per_step"], 3),
        "spec_decode_tok_s": round(spec_decode_tok_s, 1),
        # RL flywheel probe
        "weight_swap_ms": round(weight_swap_ms, 3),
        "rollout_tok_s": round(rollout_tok_s, 1),
        # telemetry plane
        "ttft_ms_p50": round(s["ttft_ms_p50"], 3),
        "ttft_ms_p99": round(s["ttft_ms_p99"], 3),
        "retraces_unexpected": s["retraces_unexpected"],
        "trace_overhead_pct": round(trace_overhead_pct, 2),
        # priority/preemption phase (neutral when the mix is unset)
        "priority_mix": priority_mix,
        "preemptions": preemptions,
        "reprefill_blocks": reprefill_blocks,
        "queue_wait_ms_p99_by_class": wait_p99_by_class,
        # disaggregated prefill/decode A/B (zeros when DISAGG=0)
        "disagg": int(bool(disagg)),
        "disagg_prefill_replicas": pre_n if disagg else 0,
        "disagg_decode_replicas": dec_n if disagg else 0,
        "disagg_decode_tpot_ms_p99": round(disagg_tpot_p99, 3),
        "colocated_decode_tpot_ms_p99": round(coloc_tpot_p99, 3),
        "disagg_ttft_ms_p99": round(disagg_ttft_p99, 3),
        "colocated_ttft_ms_p99": round(coloc_ttft_p99, 3),
        "kv_transfer_gbps": round(kv_transfer_gbps, 4),
        "kv_blocks_streamed": kv_blocks_streamed,
    }))


if __name__ == "__main__":
    main()
