"""Small things every cell driver needs."""

from __future__ import annotations

import importlib
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchFailure(RuntimeError):
    """The run cannot produce a result: exit non-zero, print none."""


def gpt_kwargs(config: dict, driver: str = "train") -> dict:
    """The configuration file's published keys as `GPTConfig` arguments,
    for a driver that is about to build one; what `models/gpt.py` cannot
    run is that driver's `BenchFailure`. (The arithmetic of a cell does
    not pass through here: a configuration names its own, `run.collect`.)"""
    heads = config["num_attention_heads"]
    cannot = f"the {driver} driver builds a models/gpt.py GPTConfig, which "
    if config["num_key_value_heads"] != heads:
        raise BenchFailure(cannot + "has full multi-head attention only")
    if config["head_dim"] * heads != config["hidden_size"]:
        raise BenchFailure(cannot + "derives head_dim as d_model/heads")
    if not config["tie_word_embeddings"]:
        raise BenchFailure(cannot + "ties its embedding")
    return {"vocab_size": config["vocab_size"],
            "d_model": config["hidden_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": heads,
            "d_ff": config["intermediate_size"],
            "max_seq_len": config["max_position_embeddings"]}


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on it, nested dicts key by key: how a
    configuration's `tiny` and `control` blocks and a mix's `tiny` block
    apply."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


ENTRY = {"config": "models.gpt:GPTConfig",
         "trainer": "train.spmd:make_gpt_trainer",
         "loss": "train.spmd:gpt_loss_fn"}


def entry_point(config: dict, what: str):
    """The program's `what` ("config", "trainer", "loss") for this
    configuration: `module:attribute` under `ray_tpu`, named by the file's
    `program.entry` where it is not `models/gpt.py`'s."""
    module, _, attr = config["program"].get("entry", {}).get(
        what, ENTRY[what]).partition(":")
    return getattr(importlib.import_module(f"ray_tpu.{module}"), attr)


def model_config(config: dict, driver: str, **more):
    """The program's model configuration object: the entry point
    `config`, called with the arguments the file's `program.constructor`
    maps from its published keys ({argument: key}); without one,
    `gpt_kwargs`, which refuses what `models/gpt.py` cannot run."""
    mapping = config["program"].get("constructor")
    kwargs = (gpt_kwargs(config, driver) if mapping is None
              else {arg: config[key] for arg, key in mapping.items()})
    return entry_point(config, "config")(
        **kwargs, **config["program"]["model"], **more)


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
