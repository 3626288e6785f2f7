"""The quickest proof that both TPU paths still start on the chip.

    python chip_smoke.py            # one chip: serve phase (both families),
                                    # then train phase
    python chip_smoke.py --chips 4  # one four-chip host: sharded train step
                                    # against one chip, then two replicas

Drives the serving path (`serve.run` of an `InferenceReplica` that asked
for a chip, concurrent `handle.stream` calls) and the training path
(`spmd.make_gpt_trainer` + `loop.TrainLoop` with the prefetcher) once at
the widths of `WIDTHS`, random weights from `--seed`, and checks what
comes out against references computed the plain way. What a cell of the
benchmark proves on every PR (rates and latencies under load:
`benchmarks/run.py`) is not repeated here; the helpers the two share are
the benchmark's (`benchmarks/harness`), imported.
The serve phase runs its streams a second time under a `jax.profiler`
trace taken inside the replica, Python tracer off, and prints what the
program's own spans (`engine/*`, `stream/*`) and named kernels say. It
then serves the two families that keep a state a sequence, each from an
engine in a process of its own at the published head size so that its
kernels run, and fails on a fallback of any as the first part does for
the paged kernels: the power-retention family (`models/retention.py`,
`--phase serve-retention`: a state block and no pages) and the KDA-and-
latent family (`models/linear_latent.py`, `--phase serve-hybrid`: a state
block and growing latent pages in one pool, experts chosen by groups).
Then the window-and-full family
(`models/window_moe.py`, `--phase serve-window`: grouped key-value heads,
pages that grow beside a ring of pages that do not, four `gqa_*` kernels),
then the state-space-and-latent-experts one (`models/mamba_moe.py`,
`--phase serve-mamba`: a state block of Mamba-2 states and convolution
tails beside one attention layer's pages, experts without a gate matrix
in a latent), then the parallel-hybrid one (`models/parallel_hybrid.py`,
`--phase serve-parallel-hybrid`: a state block and pages in every layer,
a state head of 128 x 256 and a group of 5 query heads a key-value head),
and last the short-convolution one (`models/shortconv_moe.py`, `--phase
serve-shortconv`: convolution tails beside pages whose rows hold two
key-value heads of 64 side by side, every expert of the router held and
none shared), and the shortcut one (`models/shortcut_moe.py`, `--phase
serve-shortcut`: two latent blocks and two MLPs a layer with the experts
beside them, a softmax router whose last outputs are identity experts).
After the dense train phase it trains the window-and-full family
(`models/window_moe_train.py`, `--phase train-window`: the banded and the
full flash kernels at grouped heads, the expert kernels' backward pass at
an expert width of 896) and fails if a kernel is missing or the first
loss leaves the plain forward's.

A chip belongs to one process at a time, so this process never
initialises a JAX backend: every phase runs in one process of its own
that holds the chip, one after the other. Each phase prints one JSON
line; the last line is `{"ok": ..., "device": {...}}` with the device as
JAX reports it. Any failed check raises, and the exit code is then not 0.
Without an accelerator the script fails within seconds and prints no
result.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import logging
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

import ray_tpu
from benchmarks.harness import serve_cell
from benchmarks.harness import spans as spans_mod
from benchmarks.harness import trace as trace_mod
from benchmarks.harness.common import CompileWatch, device_report
from benchmarks.harness.serve_replica import BenchReplica
from ray_tpu import serve
from ray_tpu._private import native
from ray_tpu.serve.engine import InferenceReplica

# Published-GPT-2-medium-like widths, all 12 layers: a dense model that
# fits one chip with its optimizer state and leaves the flash, fused-loss
# and paged kernels a plan each.
WIDTHS = dict(vocab_size=50304, d_model=1024, n_layers=12, n_heads=16,
              d_ff=4096, max_seq_len=1024)
TRAIN_CFG = dict(WIDTHS, attn_impl="flash", logits_dtype="bfloat16",
                 remat_policy="dots", loss_impl="fused")
# Engine logprobs come from bf16 activations and weights cast to bf16 at
# use, the reference from float32 at the highest matmul precision. bf16
# rounds at 2^-9 relative; over the 24 residual adds of 12 layers that
# is about 1% of the final activations, i.e. ~1e-2 on logits of spread
# 0.64 (1024 dims x embed scale 0.02). First chip run (PR 21): max
# 3.02e-2 over 64 tokens. A wrong mask or scale in a kernel moves
# logprobs by tenths.
LOGPROB_MAX_TOL = 6e-2
LOGPROB_MEAN_TOL = 2e-2
LOSS_TOL = 2e-2
PHASE_TIMEOUT_S = 900


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# helpers that run inside a process that owns the chip
# ---------------------------------------------------------------------------

class _Records(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def watch_op_fallbacks() -> list[str]:
    """Messages `ops.backend.note_fallback` logs from here on: each is an
    `impl="auto"` that found no kernel plan on a TPU backend."""
    handler = _Records()
    logging.getLogger("ray_tpu.ops").addHandler(handler)
    return handler.messages


def compile_report(watch: CompileWatch) -> dict:
    return {"compile_s": round(watch.compile_s, 2),
            "compiles": watch.compiles, "cache_hits": watch.cache_hits}


def kernels_of(jitted, *args):
    """(names of the Pallas kernels in `jitted` lowered for `args`,
    number of `tpu_custom_call`s left in the compiled program, the
    executable)."""
    lowered = jitted.lower(*args)
    names = sorted(set(re.findall(r'kernel_name = "([^"]+)"',
                                  lowered.as_text())))
    compiled = lowered.compile()
    return names, compiled.as_text().count(
        'custom_call_target="tpu_custom_call"'), compiled


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

class SmokeReplica(InferenceReplica):
    """`InferenceReplica` plus what has to run inside the process that
    owns the chip; the program itself grows no API."""

    def __init__(self, *args, **kwargs):
        from ray_tpu.util.compile_cache import enable_compile_cache
        self._cache_dir = enable_compile_cache()
        self._fallbacks = watch_op_fallbacks()
        self._compiles = CompileWatch()
        self._seed = kwargs.get("seed", 0)
        t0 = time.perf_counter()
        super().__init__(*args, **kwargs)
        self._init_s = time.perf_counter() - t0

    # The traced window is the benchmark's: the engine's counts from
    # zero, a profiler session in this process (Python tracer off), and
    # at its end the counts of `serve_replica.ENGINE_STATS` with the
    # programs made meanwhile.
    window_start = BenchReplica.window_start
    window_stop = BenchReplica.window_stop

    def inspect(self, prompt, tokens, logprobs) -> dict:
        """Stats, the kernels in the paged forwards at this engine's
        shapes, and how far the logprobs the engine streamed for
        `prompt` -> `tokens` are from a float32 teacher-forced forward
        over the same tokens on the XLA attention path."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt
        eng = self.engine
        stats = self.stats()

        cfg32 = dataclasses.replace(eng.cfg, dtype="float32",
                                    attn_impl="xla")
        seq = jnp.asarray(np.concatenate([prompt, tokens])[None],
                          jnp.int32)
        # the f32 masters this replica was made from: the engine keeps
        # the tree its steps read, not them
        masters = gpt.init_params(jax.random.PRNGKey(self._seed), eng.cfg)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(
                lambda p, s: gpt.completion_logprobs(
                    p, s, jnp.asarray([len(prompt)]), len(tokens), cfg32)
            )(masters, seq)
        del masters
        diff = np.abs(np.asarray(ref[0], np.float64)
                      - np.asarray(logprobs, np.float64))

        params, cache = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (eng.params, eng.cache))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        slots, mb = eng.num_slots, eng.max_blocks
        decode_k, decode_n, _ = kernels_of(
            jax.jit(lambda p, t, c, pos, tb: gpt.decode_step_paged(
                p, t, c, pos, tb, eng.cfg)),
            params, i32(slots), cache, i32(slots), i32(slots, mb))
        chunk = eng.chunk_buckets[-1]
        prefill_k, prefill_n, _ = kernels_of(
            jax.jit(lambda p, t, c, tb, st, ln: gpt.prefill_paged(
                p, t, c, eng.cfg, block_table=tb, start=st, length=ln)),
            params, i32(1, chunk), cache, i32(mb), i32(), i32())
        return {
            "pid": os.getpid(),
            "stats": {k: stats[k] for k in (
                "platform", "device_kind", "device_count",
                "visible_chips", "decode_traces", "prefill_traces",
                "retraces_unexpected", "decode_tokens", "prefill_tokens",
                "p50_token_latency_ms", "p99_token_latency_ms",
                "ttft_ms_p50")},
            "logprob_max_abs_diff": float(diff.max()),
            "logprob_mean_abs_diff": float(diff.mean()),
            "logprob_mean": float(np.mean(logprobs)),
            "kernels": {"decode_step_paged": decode_k,
                        "prefill_paged": prefill_k},
            "tpu_custom_calls": {"decode_step_paged": decode_n,
                                 "prefill_paged": prefill_n},
            "op_fallbacks": list(self._fallbacks),
            "replica_init_s": round(self._init_s, 2),
            "cache_dir": self._cache_dir,
            **compile_report(self._compiles),
            "memory_peak_bytes": device_report()["memory_peak_bytes"],
        }


def traced_window(window: dict, trace_dir: str) -> dict:
    """What the benchmark's two reductions (`harness/spans.py` by name,
    `harness/trace.py` by shape) say of the trace the replica wrote into
    `trace_dir`, beside the replica's own counts for the same window."""
    path = trace_mod.find_xplane(trace_dir)
    by_shape = trace_mod.reduce(path)
    return {**window, **spans_mod.reduce(path),
            **{k: by_shape[k] for k in ("busy_s", "modules", "lines")}}


def in_replica(replica, method: str, *args):
    """Call a method of the deployment's callable inside `replica`."""
    return ray_tpu.get(replica.handle_method.remote(method, args, {}),
                       timeout=PHASE_TIMEOUT_S)


def stop_workers_or_fail() -> None:
    """After `ray_tpu.shutdown()`: no worker may be left (a replica left
    alive still holds the chip), and the fork factory is stopped too, so
    that the script leaves nothing running."""
    left = serve_cell.stop_workers()
    check(not left, f"workers left after shutdown: {left}")


def serve_phase(cfg_kwargs: dict, *, platform: str, replicas: int,
                streams: int, prompt_lens: tuple[int, int],
                new_tokens: int, slots: int, max_len: int,
                seed: int) -> None:
    """`serve.run` of `replicas` chip-holding replicas behind one
    handle, `streams` concurrent greedy `handle.stream` calls, then the
    in-replica checks of every replica. Prints what it measured, then
    holds it to the checks."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg_kwargs["vocab_size"],
                            int(rng.integers(prompt_lens[0],
                                             prompt_lens[1] + 1))
                            ).astype(np.int32) for _ in range(streams)]
    ray_tpu.init()
    try:
        t0 = time.perf_counter()
        app = serve.deployment(
            SmokeReplica, num_replicas=replicas,
            ray_actor_options={"num_tpus": 1},
        ).bind(cfg_kwargs, slots=slots, max_len=max_len, seed=seed)
        handle = serve.run(app, name="smoke")

        def run_streams(prompts) -> list:
            outs: list = [None] * streams
            errors: list = []

            def consume(i):
                try:
                    outs[i] = list(handle.stream(prompts[i], new_tokens,
                                                 timeout=PHASE_TIMEOUT_S))
                except BaseException as e:       # re-raised below
                    errors.append(e)

            threads = [threading.Thread(target=consume, args=(i,))
                       for i in range(streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(PHASE_TIMEOUT_S)
            check(not any(t.is_alive() for t in threads),
                  "a stream did not finish")
            if errors:
                raise errors[0]
            return outs

        outs = run_streams(prompts)
        wall_s = time.perf_counter() - t0
        for i, out in enumerate(outs):
            check(len(out) == new_tokens,
                  f"stream {i} returned {len(out)} of {new_tokens} tokens")
            check(all(0 <= int(t) < cfg_kwargs["vocab_size"] for t in out),
                  f"stream {i} returned a token outside the vocabulary")

        # A worker that asked for no chip must stay off the TPU runtime
        # while the replicas hold it.
        @ray_tpu.remote
        def cpu_worker_platform():
            import jax
            return jax.devices()[0].platform

        cpu_platform = ray_tpu.get(cpu_worker_platform.remote(),
                                   timeout=120)
        check(cpu_platform == "cpu",
              f"a worker without num_tpus runs on {cpu_platform}")

        # Streams of the same lengths again (other tokens: no prefix
        # hits), every program compiled by now, under a profiler trace
        # taken inside the first replica.
        handle._refresh(force=True)
        traced = handle._replicas[0]
        trace_dir = spans_mod.trace_dir()
        in_replica(traced, "window_start", trace_dir)
        again = run_streams([rng.integers(
            0, cfg_kwargs["vocab_size"], len(p)).astype(np.int32)
            for p in prompts])
        window = in_replica(traced, "window_stop")
        check(all(len(o) == new_tokens for o in again),
              "a traced stream came back short")

        reports = [
            in_replica(r, "inspect", prompts[0], [int(t) for t in outs[0]],
                       [t.logprob for t in outs[0]])
            for r in handle._replicas]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        stop_workers_or_fail()

    trace_report = traced_window(window, trace_dir)
    shutil.rmtree(os.path.dirname(trace_dir), ignore_errors=True)
    emit({"phase": "serve_trace", **trace_report})
    emit({"phase": "serve", "replicas": reports, "streams": streams,
          "new_tokens": new_tokens,
          "prompt_lens": [len(p) for p in prompts],
          "wall_s": round(wall_s, 2), "cpu_worker_platform": cpu_platform})
    check(len(reports) == replicas, f"{len(reports)} replicas answered")
    for rep in reports:
        st = rep["stats"]
        check(st["platform"] == platform,
              f"replica runs on {st['platform']}, not {platform}")
        check(st["decode_tokens"] > 0,
              f"replica {rep['pid']} served no stream")
        check(st["decode_traces"] == 1 and st["retraces_unexpected"] == 0,
              f"compile-once broke: {st}")
        check(rep["logprob_max_abs_diff"] <= LOGPROB_MAX_TOL
              and rep["logprob_mean_abs_diff"] <= LOGPROB_MEAN_TOL,
              f"engine logprobs are {rep['logprob_max_abs_diff']} (max) / "
              f"{rep['logprob_mean_abs_diff']} (mean) from the float32 "
              f"recompute (tolerances {LOGPROB_MAX_TOL} / "
              f"{LOGPROB_MEAN_TOL})")
        check(not rep["op_fallbacks"],
              f"ops fell back to pure JAX: {rep['op_fallbacks']}")
        if platform == "tpu":
            check("paged_decode" in rep["kernels"]["decode_step_paged"]
                  and rep["tpu_custom_calls"]["decode_step_paged"] > 0,
                  f"no paged decode kernel: {rep['kernels']}")
            check("paged_mq" in rep["kernels"]["prefill_paged"]
                  and rep["tpu_custom_calls"]["prefill_paged"] > 0,
                  f"no paged prefill kernel: {rep['kernels']}")
    check_serve_trace(trace_report, on_tpu=platform == "tpu",
                      replicas=replicas)
    # every stream here starts inside the window
    check(replicas > 1
          or (trace_report["engine"]["deliver_wait_ms_p99"] > 0
              and trace_report["engine"]["submits"] == streams),
          f"first yields or submits are missing from the window's "
          f"stats: {trace_report['engine']}")
    scoped = [rep["stats"]["visible_chips"] for rep in reports]
    check(len({rep["pid"] for rep in reports}) == replicas
          and len(set(scoped)) == replicas,
          f"replicas share a process or a chip: chips {scoped}")


def check_serve_trace(rep: dict, *, on_tpu: bool, replicas: int) -> None:
    """The traced pass: the program's spans are in the trace with the
    Python tracer off, no program was made under it, and on a chip the
    kernels go by their names. The tables count the spans that lie
    inside the window (first to last device op), so the trace holds
    nearly all the ticks the engine counted, not one more: device planes
    that ended before the spans did would leave most of them outside."""
    spans = rep["spans"]
    if replicas > 1 and "engine/tick" not in spans:
        return      # the router sent this replica none of the streams
    check(rep["programs_in_window"] == 0,
          f"programs compiled or loaded under the trace: {rep['compiled']}")
    wanted = {"engine/tick", "engine/admit", "engine/prefill_chunk",
              "engine/decode_build", "engine/decode_dispatch",
              "engine/token_sync", "engine/emit", "stream/reply"}
    if replicas == 1:       # several streams here: all but one sleep
        wanted.add("stream/wait")
    check(wanted <= set(spans), f"spans missing from the trace: "
          f"{sorted(wanted - set(spans))}")
    st = rep["engine"]
    ticks = spans["engine/tick"][0]
    waits = spans.get("stream/wait", [0])[0]
    # the smoke's submits come before the window's first device op
    submits = spans.get("engine/submit", [0])[0]
    check(0.9 * st["ticks"] - 2 <= ticks <= st["ticks"]
          and waits <= st["stream_waits"]
          and submits <= st["submits"],
          f"the trace holds {ticks} ticks, {waits} stream waits and "
          f"{submits} submits, the engine counted {st['ticks']}, "
          f"{st['stream_waits']} and {st['submits']}")
    if on_tpu:
        check({"paged_decode", "paged_mq"} <= set(rep["kernels"]),
              f"kernels in the trace: {sorted(rep['kernels'])}")


# ---------------------------------------------------------------------------
# serve phase, second family: a state of fixed size a sequence
# ---------------------------------------------------------------------------

# `models/retention.py` at the published head (128 dims, so both kernels
# have a plan) and otherwise tiny: 6 query heads over 2 key-value heads, a
# state block of 19 MB
RETENTION_CFG = dict(vocab_size=512, d_model=256, n_layers=2, n_heads=6,
                     n_kv_heads=2, head_dim=128, d_ff=512, max_seq_len=1024)
# bfloat16 activations against the float32 definition, two layers; a squared
# score doubles a relative error (first chip run, PR 34: see PERF.md)
RETENTION_LOGPROB_MAX_TOL = 6e-2
RETENTION_LOGPROB_MEAN_TOL = 2e-2

# `models/linear_latent.py` at the published head (128 x 128 states, so
# both KDA kernels have a plan), latent rows of 160 values in pages of 128,
# and otherwise tiny: layers 1-7 of a period of six (a dense layer, five
# KDA layers around one latent layer), 8 held experts of a 32-wide router
# in 4 groups
HYBRID_CFG = dict(
    vocab_size=512, d_model=256, n_layers=7, first_layer=1, n_heads=2,
    kda_head_dim=128, kv_rank=128, nope_dim=64, rope_dim=32, v_dim=64,
    indexer_types=("none",) * 8,
    mixer_types=("kda",) * 5 + ("latent", "kda", "kda"),
    mlp_types=("dense", "dense") + ("sparse",) * 6, d_ff=512, expert_ff=128,
    router_width=32, held_count=8, experts_per_token=4, n_group=4,
    topk_group=2, eps=1e-6, rope_theta=6e6, max_seq_len=1024)
# bfloat16 activations against the float32 definition, seven layers
# (chip runs, PR 40: 0.184 largest, 0.0106 mean; the largest are tokens whose
# expert choice the activations' rounding flipped; see PERF.md)
HYBRID_LOGPROB_MAX_TOL = 5e-1
HYBRID_LOGPROB_MEAN_TOL = 3e-2


# `models/window_moe.py` at the published head (128 dims, pages of 128, so
# the four `gqa_*` kernels have a plan) and otherwise tiny: one period
# (window x 3, full), 16 query heads over 2 key-value heads, a window of
# 256 positions so that the longer prompts pass it and their ring of 7
# pages is written again in place, 8 held experts of a 32-wide router
WINDOW_CFG = dict(
    vocab_size=512, d_model=256, n_layers=4, n_heads=16, n_kv_heads=2,
    head_dim=128, window=256, expert_ff=128, shared_experts=4,
    router_width=32, held_count=8, experts_per_token=4, max_seq_len=1024)
# bfloat16 activations against the float32 definition, four layers
# (chip run, PR 44: 0.222 largest, 0.0152 mean over six streams)
WINDOW_LOGPROB_MAX_TOL = 5e-1
WINDOW_LOGPROB_MEAN_TOL = 5e-2


# `models/mamba_moe.py` at the published state head (64 x 128, a pair a lane
# tile, so both `mamba2_*` kernels have a plan) and attention head (128
# dims, pages of 128, 16 query heads a key-value head: `gqa_full_*`), and
# otherwise tiny: the published pattern's first 11 layers (five state
# layers, five expert layers, one attention layer), 8 held experts of a
# 32-wide router in a 128-wide latent
MAMBA_CFG = dict(
    vocab_size=512, d_model=256, n_layers=11,
    pattern="MEMEMEM*EMEMEMEM*EME", mamba_heads=8, mamba_head_dim=64,
    n_groups=2, state_size=128, n_heads=16, n_kv_heads=1, head_dim=128,
    latent_dim=128, expert_ff=384, shared_ff=512, router_width=32,
    held_count=8, experts_per_token=6, max_seq_len=1024)
# bfloat16 activations against the float32 definition, eleven layers. The
# routed weights sum to 1 here and not to the published 5: with 6 experts
# a token each at 5 / 6, one expert flipped by the activations' rounding
# moved a token's logprob by 1.17 (chip runs, PR 50: mean 0.0319 then;
# 0.0741 largest and 0.00809 mean with the weights as they are here)
MAMBA_LOGPROB_MAX_TOL = 5e-1
MAMBA_LOGPROB_MEAN_TOL = 5e-2


# `models/parallel_hybrid.py` at the published state head (128 x 256: a
# head a lane tile over two tiles of rows) and attention group (5 query
# heads a key-value head of 128 dims, pages of 128), and otherwise tiny;
# multipliers off 1, so that each is in the program that runs
PARALLEL_HYBRID_CFG = dict(
    vocab_size=512, d_model=256, n_layers=3, mamba_heads=4,
    mamba_head_dim=128, n_groups=2, state_size=256, n_heads=10,
    n_kv_heads=2, head_dim=128, d_ff=512, max_seq_len=1024,
    embedding_multiplier=2.0, ssm_in_multiplier=0.5,
    ssm_multipliers=(0.7, 0.5, 0.7, 1.0, 0.7), ssm_out_multiplier=1.5,
    attention_in_multiplier=1.0, key_multiplier=0.5,
    attention_out_multiplier=1.5, mlp_multipliers=(0.5, 1.5),
    lm_head_multiplier=0.5)
# bfloat16 activations against the float32 definition, three layers
PARALLEL_HYBRID_LOGPROB_MAX_TOL = 5e-1
PARALLEL_HYBRID_LOGPROB_MEAN_TOL = 5e-2


# `models/shortconv_moe.py` at the published head (64 dims, two key-value
# heads a row of 128 lanes in pages of 128, a group of 4 query heads, so
# `gqa_full_*` run on pairs) and otherwise tiny: the published pattern's
# first 8 layers (both dense layers, then sparse layers under attention and
# under convolutions), every one of 8 experts held, none shared
SHORTCONV_CFG = dict(
    vocab_size=512, d_model=256, n_layers=8,
    layer_types=("conv", "conv", "attention", "conv", "conv", "conv",
                 "attention", "conv"),
    n_heads=8, n_kv_heads=2, head_dim=64, d_ff=512, expert_ff=256,
    router_width=8, held_count=8, experts_per_token=2, max_seq_len=1024)
# bfloat16 activations against the float32 definition, eight layers
# (chip runs, PR 61: 0.185 largest, 0.0158 mean over six streams; with
# every residual gain at 1 and the stream built from a table of 0.05,
# 0.780 and 0.0637: `shortconv_moe.init_params` says why it is not)
SHORTCONV_LOGPROB_MAX_TOL = 5e-1
SHORTCONV_LOGPROB_MEAN_TOL = 5e-2


# `models/shortcut_moe.py` at the published latent row (512 + 64 values in
# pages of 128, so the latent kernels run at the cell's row format) and
# head (128 + 64 / 128) and otherwise tiny: two layers (four attention
# blocks), 8 held experts of a router 48 wide whose last 16 outputs are
# identity experts, 6 a token
SHORTCUT_CFG = dict(
    vocab_size=512, d_model=256, n_layers=2, n_heads=4, q_rank=128,
    kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
    indexer_types=("none",) * 2, mlp_types=("sparse",) * 2, d_ff=512,
    expert_ff=256, router_width=48, identity_experts=16, held_count=8,
    experts_per_token=6, routed_scale=6.0, rope_theta=1e7,
    max_seq_len=1024)
# bfloat16 activations against the float32 definition, two double layers
SHORTCUT_LOGPROB_MAX_TOL = 5e-1
SHORTCUT_LOGPROB_MEAN_TOL = 5e-2


def retention_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import retention
    from ray_tpu.ops import power_retention
    cfg = retention.RetentionConfig(**cfg_kwargs)
    return {
        "phase": "serve_retention", "family": retention, "cfg": cfg,
        "params": retention.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32",
                                     retention_impl="jax"),
        "engine": {}, "table": 1,
        "decode_kernels": {"retention_step": cfg.n_layers},
        "prefill_kernels": {"retention_chunk": cfg.n_layers},
        "counters": ("state_resets", "retention_tokens_live",
                     "retention_tokens_padded", "state_folds"),
        "ring": power_retention.RING,
        "tolerances": (RETENTION_LOGPROB_MAX_TOL,
                       RETENTION_LOGPROB_MEAN_TOL)}


def hybrid_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import linear_latent
    from ray_tpu.ops import kda
    cfg = linear_latent.LinearLatentConfig(**cfg_kwargs)
    n_kda = cfg.mixers.count("kda")
    n_latent, n_sparse = cfg.n_layers - n_kda, sum(
        mlp == "sparse" for mlp, _ in cfg.kinds)
    return {
        "phase": "serve_hybrid", "family": linear_latent, "cfg": cfg,
        "params": linear_latent.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32", kda_impl="jax",
                                     sparse_impl="jax"),
        "engine": {"block_size": 128}, "table": 1 + 1024 // 128,
        "decode_kernels": {"kda_step": n_kda, "latent_row_write": n_latent,
                           "latent_decode": n_latent,
                           "experts_grouped": n_sparse},
        "prefill_kernels": {"kda_chunk": n_kda,
                            "latent_row_write": n_latent,
                            "latent_chunk_attend": n_latent,
                            "experts_grouped_prefill": n_sparse},
        "counters": ("state_resets", "kda_tokens_live", "kda_tokens_padded",
                     "latent_rows_read", "state_folds", "expert_tokens_here",
                     "expert_tokens_routed", "expert_groups_kept_here",
                     "expert_load_max_over_mean", "state_blocks"),
        "ring": kda.RING,
        "tolerances": (HYBRID_LOGPROB_MAX_TOL, HYBRID_LOGPROB_MEAN_TOL)}


def window_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import window_moe
    cfg = window_moe.WindowMoEConfig(**cfg_kwargs)
    n_window = cfg.kinds.count("window")
    n_full = cfg.n_layers - n_window
    return {
        "phase": "serve_window", "family": window_moe, "cfg": cfg,
        "params": window_moe.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32", attn_impl="jax",
                                     sparse_impl="jax"),
        "engine": {"block_size": 128}, "table": 2 * (1024 // 128),
        "decode_kernels": {"gqa_window_decode": n_window,
                           "gqa_full_decode": n_full,
                           "experts_grouped": cfg.n_layers},
        "prefill_kernels": {"gqa_window_chunk": n_window,
                            "gqa_full_chunk": n_full,
                            "experts_grouped_prefill": cfg.n_layers},
        "counters": ("window_rows_read", "full_rows_read",
                     "expert_tokens_here", "expert_tokens_routed",
                     "expert_load_max_over_mean", "bounded_blocks",
                     "bounded_ring", "bounded_pages_reused"),
        "tolerances": (WINDOW_LOGPROB_MAX_TOL, WINDOW_LOGPROB_MEAN_TOL)}


def mamba_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import mamba_moe
    from ray_tpu.ops import mamba2
    cfg = mamba_moe.MambaMoEConfig(**cfg_kwargs)
    n = {kind: cfg.kinds.count(kind)
         for kind in ("mamba", "attention", "experts")}
    return {
        "phase": "serve_mamba", "family": mamba_moe, "cfg": cfg,
        "params": mamba_moe.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32", mamba_impl="jax",
                                     attn_impl="jax", sparse_impl="jax"),
        "engine": {"block_size": 128}, "table": 1 + 1024 // 128,
        "decode_kernels": {"mamba2_step": n["mamba"],
                           "gqa_full_decode": n["attention"],
                           "experts_grouped": n["experts"]},
        "prefill_kernels": {"mamba2_chunk": n["mamba"],
                            "gqa_full_chunk": n["attention"],
                            "experts_grouped_prefill": n["experts"]},
        "counters": ("state_resets", "mamba_tokens_live",
                     "mamba_tokens_padded", "attention_rows_read",
                     "expert_tokens_here", "expert_tokens_routed",
                     "expert_load_max_over_mean", "state_blocks",
                     "state_folds"),
        "ring": mamba2.RING,
        "tolerances": (MAMBA_LOGPROB_MAX_TOL, MAMBA_LOGPROB_MEAN_TOL)}


def parallel_hybrid_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import parallel_hybrid
    from ray_tpu.ops import mamba2
    cfg = parallel_hybrid.ParallelHybridConfig(**cfg_kwargs)
    return {
        "phase": "serve_parallel_hybrid", "family": parallel_hybrid,
        "cfg": cfg,
        "params": parallel_hybrid.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32", mamba_impl="jax",
                                     attn_impl="jax"),
        "engine": {"block_size": 128}, "table": 1 + 1024 // 128,
        "decode_kernels": {"mamba2_step": cfg.n_layers,
                           "gqa_full_decode": cfg.n_layers},
        "prefill_kernels": {"mamba2_chunk": cfg.n_layers,
                            "gqa_full_chunk": cfg.n_layers},
        "counters": ("state_resets", "mamba_tokens_live",
                     "mamba_tokens_padded", "attention_rows_read",
                     "decode_rows_read_a_layer", "state_blocks",
                     "state_folds"),
        "ring": mamba2.RING,
        "tolerances": (PARALLEL_HYBRID_LOGPROB_MAX_TOL,
                       PARALLEL_HYBRID_LOGPROB_MEAN_TOL)}


def shortconv_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import shortconv_moe
    cfg = shortconv_moe.ShortConvMoEConfig(**cfg_kwargs)
    n_attn = cfg.n_layers - cfg.n_conv
    n_sparse = sum(ffn == "sparse" for _, ffn in cfg.kinds)
    return {
        "phase": "serve_shortconv", "family": shortconv_moe, "cfg": cfg,
        "params": shortconv_moe.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32", attn_impl="jax",
                                     sparse_impl="jax"),
        "engine": {"block_size": 128}, "table": 1 + 1024 // 128,
        "decode_kernels": {"gqa_full_decode": n_attn,
                           "experts_grouped": n_sparse},
        "prefill_kernels": {"gqa_full_chunk": n_attn,
                            "experts_grouped_prefill": n_sparse},
        "counters": ("state_resets", "conv_rows_live", "conv_rows_padded",
                     "attention_rows_read", "expert_tokens_here",
                     "expert_tokens_routed", "expert_load_max_over_mean",
                     "state_blocks"),
        "tolerances": (SHORTCONV_LOGPROB_MAX_TOL,
                       SHORTCONV_LOGPROB_MEAN_TOL)}


def shortcut_case(cfg_kwargs: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models import shortcut_moe
    cfg = shortcut_moe.ShortcutMoEConfig(**cfg_kwargs)
    return {
        "phase": "serve_shortcut", "family": shortcut_moe, "cfg": cfg,
        "params": shortcut_moe.init_params(jax.random.key(seed), cfg),
        "plain": dataclasses.replace(cfg, dtype="float32",
                                     sparse_impl="jax"),
        "engine": {"block_size": 128}, "table": 1024 // 128,
        "decode_kernels": {"latent_row_write": 2 * cfg.n_layers,
                           "latent_decode": 2 * cfg.n_layers,
                           "experts_grouped": cfg.n_layers},
        "prefill_kernels": {"latent_row_write": 2 * cfg.n_layers,
                            "latent_chunk_attend": 2 * cfg.n_layers,
                            "experts_grouped_prefill": cfg.n_layers},
        "counters": ("latent_rows_read", "decode_rows_live",
                     "chunk_rows_live", "expert_tokens_here",
                     "expert_tokens_routed", "identity_tokens",
                     "rows_few_experts", "rows_many_experts",
                     "expert_load_max_over_mean"),
        "tolerances": (SHORTCUT_LOGPROB_MAX_TOL,
                       SHORTCUT_LOGPROB_MEAN_TOL)}


def serve_family_phase(case: dict, *, platform: str, streams: int,
                       prompt_lens: tuple[int, int], new_tokens: int,
                       slots: int, seed: int) -> None:
    """The engine over a family that keeps more than pages that grow
    (`retention_case`, `hybrid_case`, `mamba_case`, `parallel_hybrid_case`,
    `shortconv_case`: a state a sequence;
    `window_case`: a ring of window pages), or whose layer is no chain
    (`shortcut_case`), in this process: `streams` greedy
    requests over `slots` slots (so blocks are reused), chunked
    prefill in both buckets and then steps. Holds the streamed logprobs to
    the family's float32 definition over the same tokens, the two programs
    to their kernels by name and number, and `ops.backend.note_fallback`
    to silence, as the first serve phase holds the paged kernels."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.engine import InferenceEngine
    fallbacks = watch_op_fallbacks()
    compiles = CompileWatch()
    family, cfg, params = case["family"], case["cfg"], case["params"]
    eng = InferenceEngine(params, cfg, slots=slots, max_len=1024,
                          prefill_chunk=512, prefill_buckets=(128, 512),
                          prefix_cache=False, seed=seed, **case["engine"])
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(
        prompt_lens[0], prompt_lens[1] + 1))).astype(np.int32)
        for _ in range(streams)]
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    outs = [list(eng.tokens_for(r)) for r in rids]
    wall_s = time.perf_counter() - t0
    diffs = []
    with jax.default_matmul_precision("highest"):
        for p, out in zip(prompts, outs):
            seq = np.concatenate([p, [int(t) for t in out]])
            logits = family.forward(params, jnp.asarray(seq[None], jnp.int32),
                                    case["plain"])[0, len(p) - 1:-1]
            want = jnp.take_along_axis(
                jax.nn.log_softmax(logits, -1),
                jnp.asarray(seq[len(p):, None], jnp.int32), -1)[:, 0]
            diffs.append(np.abs(np.asarray(want, np.float64) - np.asarray(
                [t.logprob for t in out], np.float64)))
    diff = np.concatenate(diffs)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (params, eng.cache))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    width = case["table"]
    decode_k, decode_n, _ = kernels_of(
        jax.jit(lambda p, t, c, pos, tb: family.decode(
            p, t, c, pos, tb, cfg)[:2]),
        abstract[0], i32(slots), abstract[1], i32(slots), i32(slots, width))
    prefill_k, prefill_n, _ = kernels_of(
        jax.jit(lambda p, t, c, tb, st, ln: family.prefill(
            p, t, c, cfg, block_table=tb, start=st, length=ln)),
        abstract[0], i32(1, 512), abstract[1], i32(width), i32(), i32())
    stats, device = eng.stats(), device_report()
    emit({"phase": case["phase"], "streams": streams,
          "new_tokens": new_tokens,
          "prompt_lens": [len(p) for p in prompts],
          "wall_s": round(wall_s, 2),
          "logprob_max_abs_diff": float(diff.max()),
          "logprob_mean_abs_diff": float(diff.mean()),
          "kernels": {"decode": decode_k, "prefill": prefill_k},
          "tpu_custom_calls": {"decode": decode_n, "prefill": prefill_n},
          "op_fallbacks": list(fallbacks),
          "stats": {k: stats[k] for k in (
              "decode_traces", "prefill_traces", "retraces_unexpected",
              "decode_tokens", "prefill_tokens", "prefill_chunks",
              "cache_blocks", "pool_bytes", "p50_token_latency_ms")
              + case["counters"]},
          **compile_report(compiles), **device})
    check(device["platform"] == platform,
          f"the engine runs on {device['platform']}")
    check(all(len(o) == new_tokens for o in outs),
          "a stream came back short")
    check(stats["decode_traces"] == 1 and stats["retraces_unexpected"] == 0
          and stats["prefill_traces"] == 2,
          f"compile-once broke: {stats['decode_traces']} decode, "
          f"{stats['prefill_traces']} prefill traces")
    if "state_resets" in case["counters"]:
        check(stats["state_resets"] == streams,
              f"{stats['state_resets']} first chunks for {streams} requests")
    if "state_folds" in case["counters"]:
        # a stream's first token is its prefill's; the steps after it
        # fill its ring, which goes into its state every `ring` of them
        folds = streams * ((new_tokens - 1) // case["ring"])
        check(stats["state_folds"] == folds,
              f"{stats['state_folds']} rings folded into their states, "
              f"{folds} wanted of {streams} streams of {new_tokens} tokens")
    if "identity_tokens" in case["counters"]:
        rows = stats["decode_rows_live"] + stats["chunk_rows_live"]
        choices = rows * cfg.n_layers * cfg.experts_per_token
        check(stats["expert_tokens_routed"] + stats["identity_tokens"]
              == choices and 0 < stats["identity_tokens"] < choices,
              f"{stats['expert_tokens_routed']} choices with an expert and "
              f"{stats['identity_tokens']} without, of {choices}")
    if "window_rows_read" in case["counters"]:
        n_window = case["cfg"].kinds.count("window")
        check(0 < stats["window_rows_read"]
              < stats["full_rows_read"] * n_window,
              f"no prompt passed the window: {stats['window_rows_read']} "
              f"window rows, {stats['full_rows_read']} full rows")
    max_tol, mean_tol = case["tolerances"]
    check(diff.max() <= max_tol and diff.mean() <= mean_tol,
          f"engine logprobs are {diff.max()} (max) / {diff.mean()} (mean) "
          f"from the float32 definition (tolerances {max_tol} / {mean_tol})")
    check(not fallbacks, f"ops fell back to pure JAX: {list(fallbacks)}")
    if platform == "tpu":
        for what, names, n, want in (
                ("decode", decode_k, decode_n, case["decode_kernels"]),
                ("prefill", prefill_k, prefill_n, case["prefill_kernels"])):
            check(names == sorted(want) and n == sum(want.values()),
                  f"the {what} program's kernels are {names} x {n}, "
                  f"wanted {want}")


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def seeded_batch(cfg, batch: int, seed: int) -> dict:
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1), np.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


def train_run(cfg, mesh_spec, devices, *, batch: int, steps: int,
              unroll: int, seed: int, watch: CompileWatch,
              trainer: str = "make_gpt_trainer",
              weight: str = "w_up") -> dict:
    """`steps` steps of `cfg` on a mesh over `devices` through
    `TrainLoop` with the prefetcher, by `train.spmd`'s `trainer`; returns
    what it measured for `check_train_run`. The generator yields the same
    seeded batch every step, so the loss has to fall strictly (fresh
    random tokens cannot go below ln(vocab))."""
    import jax

    from ray_tpu.train import loop, spmd
    mesh = mesh_spec.build(devices)
    state, step_fn, _ = getattr(spmd, trainer)(
        cfg, mesh, rng=jax.random.key(seed),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    host_batch = seeded_batch(cfg, batch, seed)

    def host_batches():
        while True:
            yield host_batch

    batches = loop.DevicePrefetcher(
        host_batches(), loop.make_placer(mesh, stacked=True), depth=2,
        group=unroll)
    layers = state.params["layers"]
    weight = (layers if isinstance(layers, dict) else layers[0])[weight]
    shard_devices = sorted(s.device.id for s in weight.addressable_shards)

    # The fused dispatch `TrainLoop` builds, lowered here to read it. The
    # loop's own compile of the same program is the second in-call run
    # of the step: it must be answered by the persistent cache.
    first = next(batches)
    t0 = time.perf_counter()
    kernels, n_calls, compiled = kernels_of(
        loop.fuse_steps(step_fn, unroll), state, first)
    lower_compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    mem = compiled.memory_analysis()
    hits_before = watch.cache_hits

    train = loop.TrainLoop(step_fn, unroll=unroll, metrics_interval=unroll)

    def chain(head, rest):
        yield head
        yield from rest

    state, warm = train.run(state, chain(first, batches), num_steps=unroll)
    t0 = time.perf_counter()
    state, timed = train.run(state, batches, num_steps=steps - unroll)
    step_s = (time.perf_counter() - t0) / (steps - unroll)   # run() drains
    return {
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "batch": batch, "unroll": unroll, "steps": steps,
        "losses": [float(m["loss"]) for m in warm + timed],
        "dispatch_traces": train.dispatch_traces,
        "retraces_unexpected": train.stats()["retraces_unexpected"],
        "kernels": kernels, "tpu_custom_calls": n_calls,
        "collectives": collectives,
        "weight_shard_devices": shard_devices,
        "lower_compile_s": round(lower_compile_s, 2),
        "cache_hits_on_second_compile": watch.cache_hits - hits_before,
        "step_s": step_s,
        "program_bytes": {"arguments": mem.argument_size_in_bytes,
                          "temporaries": mem.temp_size_in_bytes},
    }


def check_train_run(run: dict, expect_kernels: set) -> None:
    losses = run["losses"]
    check(len(losses) == run["steps"],
          f"{len(losses)} of {run['steps']} steps ran")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss is not strictly falling: {losses}")
    check(run["retraces_unexpected"] == 0 and run["dispatch_traces"] == 1,
          f"the fused dispatch traced {run['dispatch_traces']} times")
    check(expect_kernels <= set(run["kernels"])
          and run["tpu_custom_calls"] >= len(expect_kernels),
          f"kernels {run['kernels']} ({run['tpu_custom_calls']} custom "
          f"calls), expected {sorted(expect_kernels)}")


def first_loss_xla_dense(cfg, devices, *, batch: int, seed: int) -> float:
    """Loss of the first step of the same model, seed and batch built
    with XLA attention and the dense loss: what the kernels must match."""
    import jax

    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import spmd
    ref_cfg = dataclasses.replace(cfg, attn_impl="xla", loss_impl="dense")
    mesh = MeshSpec(data=1).build(devices[:1])
    state, step_fn, shard = spmd.make_gpt_trainer(
        ref_cfg, mesh, rng=jax.random.key(seed),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    _, metrics = step_fn(state, shard(seeded_batch(cfg, batch, seed)))
    return float(metrics["loss"])


TRAIN_KERNELS = {"flash_fwd", "flash_dq", "flash_dkv"}
XENT_KERNELS = {"xent_fwd", "xent_dx", "xent_de"}


def train_phase(cfg_kwargs: dict, *, platform: str, batch: int,
                steps: int, seed: int) -> None:
    """One chip: `TRAIN_CFG` through the training loop, checked against
    the XLA/dense step."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    fallbacks = watch_op_fallbacks()
    watch = CompileWatch()
    device = device_report()
    check(device["platform"] == platform,
          f"train phase runs on {device['platform']}, not {platform}")
    cfg = gpt.GPTConfig(**cfg_kwargs)
    devices = jax.devices()[:1]
    ref_loss = first_loss_xla_dense(cfg, devices, batch=batch, seed=seed)
    run = train_run(cfg, MeshSpec(data=1), devices, batch=batch,
                    steps=steps, unroll=4, seed=seed, watch=watch)
    emit({"phase": "train", "device": device_report(), **run,
          "first_loss_xla_dense": ref_loss, "op_fallbacks": fallbacks,
          "cache_dir": cache_dir, **compile_report(watch)})
    on_tpu = platform == "tpu"
    check_train_run(run, TRAIN_KERNELS | XENT_KERNELS if on_tpu else set())
    check(abs(run["losses"][0] - ref_loss) <= LOSS_TOL,
          f"first loss {run['losses'][0]} against {ref_loss} from the "
          f"XLA/dense step (tolerance {LOSS_TOL})")
    check(not fallbacks, f"ops fell back to pure JAX: {fallbacks}")
    if on_tpu:
        check(run["cache_hits_on_second_compile"] >= 1,
              "the loop's compile of the dispatch missed the persistent "
              "cache: its key moves")


# `Mellum2-12B-A2.5B`'s layer at its published head, group, window, expert
# and model widths, cut in what the kernels' plans do not depend on: two
# layers (a window layer and a full one), 16 query heads over 2, 4 experts
# held of a 16-wide router, sequences of 4,096: the band is a quarter of
# the sequence, a q block of 1,024 rows touches two kv blocks of 2,048,
# and an expert's 896 rows are one width slice.
WINDOW_TRAIN_CFG = dict(
    vocab_size=8192, d_model=2304, n_layers=2, n_heads=16, n_kv_heads=2,
    head_dim=128, window=1024, layer_types=("window", "full"),
    rope_window=(500000.0,),
    rope_full=(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782),
    expert_ff=896, router_width=16, experts_per_token=4, held_count=4,
    max_seq_len=4096, flash_block_q=1024, flash_block_kv=2048,
    expert_chunk=2048)
WINDOW_TRAIN_KERNELS = {
    "flash_fwd_band", "flash_dq_band", "flash_dkv_band",
    "experts_grouped_train", "experts_grouped_dx", "experts_grouped_dw"}


def train_window_phase(cfg_kwargs: dict, *, platform: str, batch: int,
                       steps: int, seed: int) -> None:
    """One chip: `models/window_moe_train.py` through the training loop
    (the banded and the full flash kernels at grouped heads, the expert
    kernels' backward), its first loss against the whole-sequence plain
    form's (`forward`: every score made and masked, the experts one by
    one)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import window_moe_train as wmt
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import spmd
    from ray_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    fallbacks = watch_op_fallbacks()
    watch = CompileWatch()
    device = device_report()
    check(device["platform"] == platform,
          f"train phase runs on {device['platform']}, not {platform}")
    cfg = wmt.WindowMoETrainConfig(**cfg_kwargs)
    plain = dataclasses.replace(cfg, sparse_impl="jax")
    host = seeded_batch(cfg, batch, seed)
    params = jax.jit(lambda k: wmt.init_params(k, cfg))(jax.random.key(seed))
    ref_loss = float(jax.jit(lambda p, b: jnp.mean(spmd.softmax_xent(
        wmt.forward(p, b["inputs"], plain), b["targets"])))(params, host))
    del params
    run = train_run(cfg, MeshSpec(data=1), jax.devices()[:1], batch=batch,
                    steps=steps, unroll=2, seed=seed, watch=watch,
                    trainer="make_window_moe_trainer", weight="we_up")
    emit({"phase": "train_window", "device": device_report(), **run,
          "first_loss_plain": ref_loss, "op_fallbacks": fallbacks,
          "cache_dir": cache_dir, **compile_report(watch)})
    on_tpu = platform == "tpu"
    check_train_run(run, WINDOW_TRAIN_KERNELS | TRAIN_KERNELS | XENT_KERNELS
                    if on_tpu else set())
    check(abs(run["losses"][0] - ref_loss) <= LOSS_TOL,
          f"first loss {run['losses'][0]} against {ref_loss} from the "
          f"plain forward (tolerance {LOSS_TOL})")
    check(not fallbacks, f"ops fell back to pure JAX: {fallbacks}")


def train4_phase(cfg_kwargs: dict, *, platform: str, batch: int,
                 steps: int, seed: int) -> None:
    """Four chips: the same step on `MeshSpec(fsdp=2, tensor=2)` and on
    one chip of the same host, same seed and batch."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    fallbacks = watch_op_fallbacks()
    watch = CompileWatch()
    device = device_report()
    check(device["platform"] == platform and device["count"] == 4,
          f"four-chip train phase sees {device}")
    cfg = gpt.GPTConfig(**cfg_kwargs)
    common = dict(batch=batch, steps=steps, unroll=4, seed=seed,
                  watch=watch)
    one = train_run(cfg, MeshSpec(data=1), jax.devices()[:1], **common)
    one_fallbacks = list(fallbacks)
    # Under tensor=2 each shard holds vocab/2 = 25152 embedding rows,
    # which no 128-multiple block divides: the fused loss then has no
    # kernel plan and says so (expected here, and printed).
    four = train_run(cfg, MeshSpec(data=1, fsdp=2, tensor=2),
                     jax.devices(), **common)
    four_fallbacks = fallbacks[len(one_fallbacks):]
    gaps = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    emit({"phase": "train4", "device": device_report(), "one_chip": one,
          "four_chips": four, "loss_gaps": gaps,
          "op_fallbacks_one_chip": one_fallbacks,
          "op_fallbacks_four_chips": four_fallbacks,
          "cache_dir": cache_dir, **compile_report(watch)})
    on_tpu = platform == "tpu"
    check_train_run(one, TRAIN_KERNELS | XENT_KERNELS if on_tpu else set())
    check_train_run(four, TRAIN_KERNELS if on_tpu else set())
    check(not one_fallbacks, f"ops fell back on one chip: {one_fallbacks}")
    check(max(gaps[:4]) <= LOSS_TOL,
          f"four-chip losses {four['losses'][:4]} against one chip "
          f"{one['losses'][:4]} (tolerance {LOSS_TOL})")
    check(four["weight_shard_devices"] == sorted(
        d.id for d in jax.devices()),
        f"a weight's shards sit on {four['weight_shard_devices']}")
    check(four["collectives"]["all-reduce"] > 0
          and four["collectives"]["all-gather"] > 0,
          f"expected collectives are missing: {four['collectives']}")


# ---------------------------------------------------------------------------
# the parent: owns no chip
# ---------------------------------------------------------------------------

PREFLIGHT = """
import json, jax
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)}))
"""


def run_phase_child(phase: str, seed: int) -> None:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--seed", str(seed)], timeout=PHASE_TIMEOUT_S)
    check(proc.returncode == 0,
          f"{phase} phase exited with code {proc.returncode}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=("train", "train4", "train-window",
                                        "serve-retention", "serve-hybrid",
                                        "serve-window", "serve-mamba",
                                        "serve-parallel-hybrid",
                                        "serve-shortconv",
                                        "serve-shortcut"),
                    help="how a phase child is started")
    args = ap.parse_args()

    if args.phase == "train":
        train_phase(TRAIN_CFG, platform="tpu", batch=8, steps=12,
                    seed=args.seed)
        return 0
    if args.phase == "train-window":
        train_window_phase(WINDOW_TRAIN_CFG, platform="tpu", batch=2,
                           steps=8, seed=args.seed)
        return 0
    if args.phase == "train4":
        train4_phase(TRAIN_CFG, platform="tpu", batch=8, steps=8,
                     seed=args.seed)
        return 0
    cases = {"serve-retention": (retention_case, RETENTION_CFG),
             "serve-hybrid": (hybrid_case, HYBRID_CFG),
             "serve-window": (window_case, WINDOW_CFG),
             "serve-mamba": (mamba_case, MAMBA_CFG),
             "serve-parallel-hybrid": (parallel_hybrid_case,
                                       PARALLEL_HYBRID_CFG),
             "serve-shortconv": (shortconv_case, SHORTCONV_CFG),
             "serve-shortcut": (shortcut_case, SHORTCUT_CFG)}
    if args.phase in cases:
        make, cfg_kwargs = cases[args.phase]
        case = make(cfg_kwargs, args.seed)
        serve_family_phase(case, platform="tpu", streams=6,
                           prompt_lens=(100, 700), new_tokens=32, slots=4,
                           seed=args.seed)
        return 0

    # Which device JAX finds, asked in a process that exits again.
    found = subprocess.run([sys.executable, "-c", PREFLIGHT],
                           stdout=subprocess.PIPE, text=True,
                           timeout=PHASE_TIMEOUT_S)
    if found.returncode != 0:
        print("chip_smoke: JAX could not start", file=sys.stderr)
        return 2
    device = json.loads(found.stdout.strip().splitlines()[-1])
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{device}", file=sys.stderr)
        return 2

    so_path = os.path.join(os.path.dirname(native.__file__), "libstore.so")
    had_so = os.path.exists(so_path)
    emit({"phase": "host", "dev_accel": glob.glob("/dev/accel*"),
          "dev_vfio": glob.glob("/dev/vfio/*"),
          "chips_detected": ray_tpu._detect_tpu_chips(),
          "native_arena": native.build_extension("store") is not None,
          "native_arena_built_now": not had_so,
          "JAX_COMPILATION_CACHE_DIR":
              os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    try:
        if args.chips == 1:
            serve_phase(WIDTHS, platform="tpu", replicas=1, streams=6,
                        prompt_lens=(128, 512), new_tokens=64, slots=8,
                        max_len=1024, seed=args.seed)
            run_phase_child("serve-retention", args.seed)
            run_phase_child("serve-hybrid", args.seed)
            run_phase_child("serve-window", args.seed)
            run_phase_child("serve-mamba", args.seed)
            run_phase_child("serve-parallel-hybrid", args.seed)
            run_phase_child("serve-shortconv", args.seed)
            run_phase_child("serve-shortcut", args.seed)
            run_phase_child("train", args.seed)
            run_phase_child("train-window", args.seed)
        else:
            run_phase_child("train4", args.seed)
            serve_phase(WIDTHS, platform="tpu", replicas=2, streams=6,
                        prompt_lens=(128, 512), new_tokens=64, slots=8,
                        max_len=1024, seed=args.seed)
    except BaseException:
        emit({"ok": False, "device": device})
        raise
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
