"""Small things every cell driver needs."""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchFailure(RuntimeError):
    """The run cannot produce a result: exit non-zero, print none."""


def gpt_kwargs(config: dict) -> dict:
    """The configuration file's published keys as `GPTConfig` arguments."""
    heads = config["num_attention_heads"]
    if config["num_key_value_heads"] != heads:
        raise BenchFailure("the program has full multi-head attention only")
    if config["head_dim"] * heads != config["hidden_size"]:
        raise BenchFailure("the program derives head_dim as d_model/heads")
    if not config["tie_word_embeddings"]:
        raise BenchFailure("the program ties its embedding")
    return {"vocab_size": config["vocab_size"],
            "d_model": config["hidden_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": heads,
            "d_ff": config["intermediate_size"],
            "max_seq_len": config["max_position_embeddings"]}


def widths_for_arith(config: dict) -> dict:
    return {**gpt_kwargs(config), "head_dim": config["head_dim"]}


def program_seed(seed: int) -> int:
    """`--seed` may exceed 32 signed bits; the program's seed arguments
    (PRNG keys, int32 counters) get its low 31 bits."""
    return int(seed) & 0x7FFFFFFF


def use_compile_cache() -> str:
    """JAX's persistent compilation cache for this process and every
    worker it starts: where `JAX_COMPILATION_CACHE_DIR` says, else one
    fixed directory inside the checkout (the path is part of the key).
    Every program is cached, however quick its compile: each run is a new
    process and would otherwise compile the small ones again."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


class CompileWatch:
    """Counts what JAX compiles, and what its persistent cache answers,
    from the events JAX itself records (after `chip_smoke.CompileWatch`)."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        self.names: list[str] = []      # what was compiled, in order
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            self.names.append(str(_.get("fun_name", "?")))

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def programs(self) -> int:
        """Executables made so far, compiled or read from the cache."""
        return self.compiles + self.cache_hits


def device_report() -> dict:
    import jax
    devices = jax.devices()
    peak = 0
    for d in devices:
        # On this runtime a program's temporaries are not "in use" but
        # "reserved"; the chip holds both (PERF.md, section 6, PR 23).
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
