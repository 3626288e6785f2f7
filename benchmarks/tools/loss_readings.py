"""A training cell's loss comparison read over many seeds in one process:
what `train_cell.py` compares before the first step (the program's loss
on the sampled sequences against the plain reference's, float32 at the
highest precision), position by position (`train_cell.position_losses`:
the program's own loss function under one-hot `mask`s). For every seed:

- `gap_plain`: the program's mean minus the reference's, signed (what is
  compared where the mix has no `check_by`);
- `gap_abs_median`: the median over the positions of |gap| (what is
  compared where the mix says `check_by` "position");
- `gap_abs_mean`, `gap_abs_q90`: the mean and the ninth decile of |gap|;

the same for the control (`--control N`, the first N seeds: the
configuration's `control` block laid over the program) and for faults
(`--faults N`, the first N seeds: the reference put in the program's place
with one key of the configuration changed, `FAULTS`, against the
reference as it is; no rounding in it: what the fault alone moves). The
readings a limit is set from (PERF.md, section 6, PR 48). Not part of a
benchmark run. One line a seed on standard output, the same appended to
`--out`, and with `--keep` every position's losses in `<out>.npz`.

    python3 benchmarks/tools/loss_readings.py \
        --workload kanana-2-30b-a3b.pretrain-8k --seeds 7,8,9 \
        --control 3 --faults 3 --out chiprun_out/loss_readings.jsonl
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

# name -> the configuration as the faulty reference reads it
FAULTS = {
    "one_expert_of_a_token_s_left_out":
        lambda c: {**c, "num_experts_per_tok": c["num_experts_per_tok"] - 1},
    "routed_scaling_factor_left_out":
        lambda c: {**c, "routed_scaling_factor": 1.0},
    "rope_theta_a_hundredth":
        lambda c: {**c, "rope_theta": c["rope_theta"] / 100.0},
    "gate_weights_not_normalised":
        lambda c: {**c, "norm_topk_prob": not c["norm_topk_prob"]},
}


def readings(got: np.ndarray, want: np.ndarray) -> dict:
    """got, want [k, T]: the losses at every position."""
    gap = got.astype(np.float64) - want
    return {"gap_plain": float(gap.mean()),
            "gap_abs_median": float(np.median(np.abs(gap))),
            "gap_abs_mean": float(np.abs(gap).mean()),
            "gap_abs_q90": float(np.quantile(np.abs(gap), 0.9))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's and the mix's tiny blocks "
                         "(the CPU)")
    ap.add_argument("--out")
    ap.add_argument("--keep", action="store_true")
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

    cfg, sound = program(config)
    control = (program(common.merged(config, config["control"]))[1]
               if args.control else None)

    def ref_nll_of(cfg_file):
        return jax.jit(lambda p, b: ref.token_losses(
            p, b["inputs"], b["targets"], cfg_file))

    ref_nll = ref_nll_of(config)
    faulty = {name: ref_nll_of(change(config))
              for name, change in FAULTS.items()} if args.faults else {}
    kept = {}

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
        host = {name: v[pick] for name, v in first.items()}
        sample = shard(host)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref_nll(params, sample))
        got = {"sound": sound(params, sample)}
        if n < args.control:
            got["control"] = control(params, sample)
        if n < args.faults:
            for name, fn in faulty.items():
                with jax.default_matmul_precision("highest"):
                    got[name] = np.asarray(fn(params, sample))
        line = {"loss_readings_of": args.workload, "seed": seed,
                "reference_loss": float(want.mean()),
                **{name: readings(v, want)
                   for name, v in got.items()},
                "seconds": time.perf_counter() - t0}
        del params
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
            if args.keep:
                kept[f"{n}.{seed}.inputs"] = host["inputs"]
                kept[f"{n}.{seed}.reference"] = want
                for name, v in got.items():
                    kept[f"{n}.{seed}.{name}"] = v.astype(np.float32)
                np.savez_compressed(args.out + ".npz", **kept)
    return 0


if __name__ == "__main__":
    sys.exit(main())
