"""A window / full layer training cell's loss comparison read over many
seeds in one process, `loss_readings.py`'s for a configuration whose
wrong programs are programs and not faulty references: what
`train_cell.py` compares before the first step (the median over the
positions of |the program's loss - the float32 reference's|), for the
sound program and for each program the configuration's file names as one
that has to come out as not correct: its `control` (the routed experts'
operands on the float8 grid) and every entry of its `wrong_programs` (the
file with one key changed, laid over the program alone; the reference
reads the file as it is). The readings a limit is set from (PERF.md,
section 6, PR 57). Not part of a benchmark run. One line a seed on
standard output, the same appended to `--out`.

    python3 benchmarks/tools/window_train_readings.py \
        --workload mellum2-12b-a2.5b.longctx-32k --seeds 7,8,9 \
        --wrong 2 --out chiprun_out/window_train_readings.jsonl
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import signal
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common  # noqa: E402
from benchmarks.tools.loss_readings import readings  # noqa: E402


def programs(config: dict) -> dict:
    """{name: the configuration as that program reads it}, the sound one
    first."""
    wrong = {k: v for k, v in config.get("wrong_programs", {}).items()
             if isinstance(v, dict)}
    return {"sound": config,
            "control": common.merged(config, config["control"]),
            **{name: common.merged(config, over)
               for name, over in wrong.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--wrong", type=int, default=0,
                    help="the first N seeds also run the wrong programs")
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's and the mix's tiny blocks "
                         "(the CPU)")
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=int, default=1500)
    args = ap.parse_args()
    signal.alarm(args.timeout)
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    if args.tiny:
        config = common.merged(config, config["tiny"])
        mix = common.merged(mix, mix["tiny"])

    import jax

    from benchmarks.harness import traffic as traffic_mod
    from benchmarks.harness.train_cell import position_losses
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import spmd

    mesh = MeshSpec(**mix["mesh"]).build(jax.devices()[:cell["chips"]])
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    loss_fn = common.entry_point(config, "loss")
    k, t = mix["check_sequences"], mix["seq_len"]

    def program(cfg_file):
        cfg = common.model_config(cfg_file, "train",
                                  **cfg_file["program"]["train"])
        return cfg, position_losses(loss_fn, cfg, mesh, (k, t))

    every = {name: program(c) for name, c in programs(config).items()}
    cfg = every["sound"][0]
    ref_nll = jax.jit(lambda p, b: ref.token_losses(
        p, b["inputs"], b["targets"], config))

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        state, _, shard = common.entry_point(config, "trainer")(
            cfg, mesh, rng=jax.random.key(common.program_seed(seed)),
            optimizer=spmd.default_optimizer(
                **config["program"]["optimizer"]))
        params = state.params
        del state
        first = next(traffic_mod.train_batches(mix, seed, cfg.vocab_size))
        pick = np.sort(np.random.default_rng([seed, 5]).choice(
            mix["batch"], k, replace=False))
        sample = shard({name: v[pick] for name, v in first.items()})
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref_nll(params, sample))
        line = {"window_train_readings_of": args.workload, "seed": seed,
                "reference_loss": float(want.mean())}
        for name, (_, losses) in every.items():
            if name == "sound" or n < args.wrong:
                line[name] = readings(losses(params, sample), want)
        line["seconds"] = time.perf_counter() - t0
        del params
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
