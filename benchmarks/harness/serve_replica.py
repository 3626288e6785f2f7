"""The deployment a serving cell runs: `InferenceReplica` plus the few
methods that have to run inside the process that owns the chip (after
`chip_smoke.SmokeReplica`). The program grows no API for them; they use
`InferenceEngine`'s public calls only (`submit`, `tokens_for`, `cancel`,
`generate`, `reset_stats`, `stats`).

Construction differs from `InferenceReplica.__init__` in one thing: the
weights come from `--seed` in one jitted call of the configuration's
reference module (`init_params`), not leaf by leaf from the program's
initialiser, so the reference is compared with nothing the program made
and the replica starts seconds sooner.
"""

from __future__ import annotations

import importlib
import os
import shutil
import threading
import time

import numpy as np

from benchmarks.harness import common
from ray_tpu.serve.engine import InferenceEngine, InferenceReplica

# what `window_stop` hands the driver of the engine's `stats()`
ENGINE_STATS = (
    "slots", "active", "pending", "ticks", "tick_s", "admit_s",
    "decode_build_s", "decode_dispatch_s", "token_sync_s", "emit_s",
    "prefill_time_s", "decode_time_s", "decode_steps", "decode_tokens",
    "prefill_tokens", "prefill_chunks", "stream_waits", "stream_wait_s",
    "submits", "submit_s", "slot_occupancy", "cache_block_utilization",
    "p50_token_latency_ms", "p99_token_latency_ms", "queue_wait_ms_p50",
    "queue_wait_ms_p99", "deliver_wait_ms_p50", "deliver_wait_ms_p99",
    "queue_depth", "preemptions", "evicted_blocks", "cow_copies",
    "prefix_hit_tokens", "cancelled", "sheds", "blocks_in_use",
    "cache_blocks", "block_size", "kv_bytes_per_token", "pool_bytes",
    "decode_traces", "prefill_traces", "retraces_unexpected")


class BenchReplica(InferenceReplica):

    def __init__(self, config: dict, *, seed: int):
        parts, last = {}, [time.perf_counter()]

        def mark(name):
            now = time.perf_counter()
            parts[name], last[0] = now - last[0], now

        import jax
        mark("imports")
        devices = jax.devices()
        mark("backend")
        self._compiles = common.CompileWatch()
        self._config = config
        self._ref = importlib.import_module(
            f"benchmarks.refs.{config['reference']}")
        cfg = common.model_config(config, "serve")
        params = jax.jit(lambda key: self._ref.init_params(key, config))(
            jax.random.key(seed))
        jax.block_until_ready(params)
        self._params = params     # the engine may keep a quantized copy
        mark("weights")
        block = config["program"]["serve"]
        self.engine = InferenceEngine(
            params, cfg, slots=block["slots"], max_len=block["max_len"],
            **block["engine_kwargs"])
        mark("engine")
        self._device_info = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        }
        self._parts = parts
        self._open: set = set()
        self._open_lock = threading.Lock()
        self._trace_dir = None
        self._check_fn = None

    def __call__(self, prompt, max_new_tokens: int = 8):
        """`InferenceReplica.__call__` (greedy, no priority class) that
        remembers the request's id, so that `end_load` can cancel what
        is still open."""
        rid = self.engine.submit(prompt, max_new_tokens=max_new_tokens)
        with self._open_lock:
            self._open.add(rid)
        return self._stream(rid)

    def _stream(self, rid):
        try:
            yield from self.engine.tokens_for(rid)
        finally:
            with self._open_lock:
                self._open.discard(rid)

    def describe(self) -> dict:
        eng = self.engine
        return {**self._device_info, "pid": os.getpid(),
                "setup_parts": self._parts,
                "chunk_buckets": list(eng.chunk_buckets),
                "prefill_chunk": eng.prefill_chunk,
                "block_size": eng.block_size, "max_len": eng.max_len,
                "vocab_size": eng.cfg.vocab_size}

    def warm(self, prompts, new_tokens: int) -> dict:
        """Each prompt drained here in the replica, so that every
        program a window will run exists before the load starts; the
        reference's program too."""
        for p in prompts:
            self.engine.generate(np.asarray(p, np.int32),
                                 max_new_tokens=new_tokens)
        self._reference(np.zeros(2, np.int32))
        self.engine.reset_stats()
        self._compiled_in_warm = self._compiles.compiles
        return {"compiles": self._compiles.compiles,
                "cache_hits": self._compiles.cache_hits,
                "compile_s": self._compiles.compile_s}

    def window_start(self, trace_dir: str | None) -> bool:
        """The engine's counts start from zero; with `trace_dir`, a
        profiler session opens in this process, the one that holds the
        chip. Python tracer off: the program's own spans name what the
        host does, and in a process with tens of threads the tracer cut
        the device's part of the trace short (PERF.md, PR 23)."""
        import jax
        self.engine.reset_stats()
        self._programs_at_start = self._compiles.programs()
        self._trace_dir = trace_dir
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        return True

    def window_stop(self) -> dict:
        """Counts first: closing a profiler session takes seconds, and
        under load the engine goes on ticking meanwhile."""
        import jax
        stats = self.stats()
        programs = self._compiles.programs() - self._programs_at_start
        if self._trace_dir:
            jax.profiler.stop_trace()
        return {"engine": {k: stats[k] for k in ENGINE_STATS},
                "programs_in_window": programs,
                "compiled": self._compiles.names[-programs:]
                if programs else []}

    def end_load(self) -> int:
        """Cancel every request still open: their streams end short, and
        the engine, which only ticks while a stream pulls, stands still."""
        with self._open_lock:
            rids = list(self._open)
        return sum(self.engine.cancel(rid) for rid in rids)

    def _reference(self, seq):
        """The reference's logprob of every token of `seq` after the
        first, float32 at the highest matmul precision; one program: the
        sequence is padded to `max_len` (causal, so the padding changes
        nothing before it)."""
        import jax
        import jax.numpy as jnp
        if self._check_fn is None:
            self._check_fn = jax.jit(
                lambda p, s: self._ref.token_logprobs(p, s, self._config))
        top = self.engine.max_len
        padded = np.zeros((1, top), np.int32)
        padded[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            lp = self._check_fn(self._params, jnp.asarray(padded))
        return np.asarray(lp, np.float64)[0, :len(seq) - 1]

    def check(self, samples) -> dict:
        """How far the logprobs the engine streamed (prefill, then
        decode through the paged cache) are from the reference's on
        prompt + the tokens compared, per request of the mix's plan
        (`serve_cell.check_sample`); and the device as JAX reports it
        here, after everything has run."""
        diffs = []
        for s in samples:
            seq = np.concatenate([s["prompt"], s["tokens"]]).astype(np.int32)
            want = self._reference(seq)[len(s["prompt"]) - 1:]
            diffs.append(np.abs(want - np.asarray(s["logprobs"],
                                                  np.float64)))
        every = np.concatenate(diffs) if diffs else np.zeros(0)
        return {"requests": len(diffs), "tokens": int(every.size),
                "logprob_max_abs": float(every.max()) if every.size else None,
                "logprob_mean_abs": (float(every.mean())
                                     if every.size else None),
                "per_request_max": [float(d.max()) for d in diffs],
                "device": common.device_report(),
                "compile": {"compiles": self._compiles.compiles,
                            "compile_s": self._compiles.compile_s,
                            "cache_hits": self._compiles.cache_hits,
                            "compiled_after_warm": self._compiles.names[
                                self._compiled_in_warm:]}}
