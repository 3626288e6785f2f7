"""A training cell's gradient against its reference's, at the cell's own
size: `jax.grad` of the program's loss function (the configuration's
`program.entry`, its kernels' backward passes) and `jax.grad` of the plain
reference's loss (float32, highest precision), both at the seeded state
and on the sequences `train_cell.py` samples for its own comparison, leaf
by leaf:

    |g_program - g_reference| / |g_reference|      (2-norms over a leaf)

which reads 0 where the two agree and 1 where the program gives no
gradient. Two limits, both `tolerances`' (the file's `why` has the
readings): every leaf reads under `grad_rel`, which a wrong or missing
gradient does not; and of the leaves `floor_leaves` names, the smallest
reading over the layers is under `grad_rel_floor`, which a lower precision
in their matmuls is not. (A routed expert's gradient is a sum over the
pairs the router sent it; the two sides round differently, so they route a
few pairs in a hundred differently, and a pair that changes expert is a
whole term. That comes on top of the rounding, layer by layer and seed by
seed; the layer it touches least shows what the precision alone does.)
With `--control` the configuration's `control` block is laid over the file
and the run has to come out as not correct. A leaf whose reference
gradient is zero (a router's bias) has to be zero in the program too.

`train_cell.py` compares losses and nothing of a backward pass; this is
the check beside it, until the harness has one (PERF.md, section 7). Not
part of a benchmark run. One seed a call (the chip is one process's):

    python3 benchmarks/tools/grad_check.py \
        --workload kanana-2-30b-a3b.pretrain-8k --seed 7 [--control]
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common  # noqa: E402


def check(cell: dict, config: dict, mix: dict, *, seed: int,
          control: bool = False) -> dict:
    """-> {"correct", "whole": the reading over all leaves as one vector,
    "leaves": {leaf name: the largest reading over the layers}, "floors":
    {leaf name: the smallest, for `floor_leaves`}, "layers": {leaf name:
    every layer's reading, for the same}, "loss", "reference_loss",
    "problems"}."""
    import jax

    from benchmarks.harness import traffic as traffic_mod
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import spmd

    if control:
        config = common.merged(config, config["control"])
    cfg = common.model_config(config, "train", **config["program"]["train"])
    mesh = MeshSpec(**mix["mesh"]).build(jax.devices()[:cell["chips"]])
    state, _, shard = common.entry_point(config, "trainer")(
        cfg, mesh, rng=jax.random.key(common.program_seed(seed)),
        optimizer=spmd.default_optimizer(**config["program"]["optimizer"]))
    params = state.params
    del state                               # the moments' room is needed
    first = next(traffic_mod.train_batches(mix, seed, cfg.vocab_size))
    pick = np.sort(np.random.default_rng([seed, 5]).choice(
        mix["batch"], mix["check_sequences"], replace=False))
    sample = shard({name: v[pick] for name, v in first.items()})

    loss_fn = common.entry_point(config, "loss")
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    loss, got = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, mesh)))(params, sample)
    loss, got = float(loss), jax.tree.map(np.asarray, got)
    with jax.default_matmul_precision("highest"):
        ref_loss, want = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, b["inputs"], b["targets"], config)))(
                params, sample)
    ref_loss, want = float(ref_loss), jax.tree.map(np.asarray, want)

    tol = config["tolerances"]
    leaves, layers, problems = {}, {}, []
    off = all_of = 0.0
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        where = jax.tree_util.keystr(path)
        name = where.rsplit("'", 2)[-2]
        w = w.astype(np.float64)
        size = np.linalg.norm(w)
        if size == 0:
            if np.any(g):
                problems.append(f"{where}: the reference's gradient is "
                                f"zero, the program's is not")
            continue
        apart = np.linalg.norm(g.astype(np.float64) - w)
        off, all_of = off + apart ** 2, all_of + size ** 2
        reading = float(apart / size)
        if not reading <= tol["grad_rel"]:
            problems.append(f"{where}: {reading} of the reference's "
                            f"gradient's norm (limit {tol['grad_rel']})")
        leaves[name] = max(reading, leaves.get(name, 0.0))
        if name in tol["floor_leaves"]:
            layers.setdefault(name, []).append(reading)
    floors = {name: min(v) for name, v in layers.items()}
    for name, floor in floors.items():
        if not floor <= tol["grad_rel_floor"]:
            problems.append(f"{name}: no layer's reads under {floor} of "
                            f"the reference's gradient's norm (limit "
                            f"{tol['grad_rel_floor']})")
    return {"correct": not problems, "whole": float(np.sqrt(off / all_of)),
            "leaves": dict(sorted(leaves.items())), "floors": floors,
            "layers": layers, "loss": loss, "reference_loss": ref_loss,
            "problems": problems}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    out = check(cell, config, mix, seed=args.seed, control=args.control)
    print(json.dumps({"grad_check_of": args.workload, "seed": args.seed,
                      "control": args.control, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
