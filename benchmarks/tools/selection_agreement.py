"""How far the positions a sparse-attention program selects agree with the
reference's exact top-k, at the cell's own widths: one prompt prefilled in
chunks through the paged pool, then decode steps (teacher-forced), once
with the configuration as stated and once with its `control` block laid
over it. For each layer that owns an indexer: the share of the reference's
S_t that the program also selected, mean and least over the steps. It
says what `correct`'s logprob limits cannot: how much of a difference
between two precisions is selection (a term of the attention's average
swapped for another) and how much is rounding carried through the layers
(`tolerances.why` of `benchmarks/configs/glm-5.2.json`; PERF.md, section
6). Not part of a benchmark run. Needs a family whose decode step takes
`selections=` (`models/latent_sparse_moe.py`). One process holds the chip:

    python3 benchmarks/tools/selection_agreement.py \
        --workload glm-5.2.docqa-closed24 --seed 5 --prompt 6144 --steps 16
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common  # noqa: E402

BLOCK = 16          # the engine's default block size


def reference_sets(ref, params, seq, config, at):
    """The reference's S_t at positions `at`, one bool [len(at), T] a
    layer that owns an indexer; float32 at the highest precision."""
    import jax

    def run(p, s):
        sets = []
        ref.features(p, s, config, selections=sets)
        return [m[at[0]:at[-1] + 1] for m in sets]

    with jax.default_matmul_precision("highest"):
        return [jax.device_get(m) for m in jax.jit(run)(params, seq)]


def programs(cfg, table):
    """The family's prefill of a chunk and its decode step for one
    stream, jitted as the engine jits them (the pool donated); the step
    also returns the positions each indexer selected."""
    import jax
    fam = cfg.family

    def prefill(p, toks, cache, start, n):
        return fam.prefill(p, toks, cache, cfg, None, block_table=table,
                           start=start, length=n)[1]

    def step(p, tok, cache, pos):
        picked = []
        _, cache, _ = fam.decode(p, tok, cache, pos, table[None], cfg, None,
                                 selections=picked)
        return cache, picked

    return (jax.jit(prefill, donate_argnums=(2,)),
            jax.jit(step, donate_argnums=(2,)))


def program_sets(cfg, params, seq, n_prompt, stop, chunk):
    """The positions the program's decode steps select at
    n_prompt .. stop - 1 of seq (whole chunks long): [step][layer] ->
    int array (-1: none)."""
    import jax.numpy as jnp
    import numpy as np
    n_blocks = len(seq) // BLOCK
    table = np.arange(1, n_blocks + 1, dtype=np.int32)
    cache = cfg.family.init_pool(cfg, n_blocks + 1, BLOCK, None)
    prefill, step = programs(cfg, table)
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = seq[start:start + n]
        cache = prefill(params, jnp.asarray(toks), cache, np.int32(start),
                        np.int32(n))
    out = []
    for pos in range(n_prompt, stop):
        cache, picked = step(params, jnp.asarray(seq[pos:pos + 1]), cache,
                             jnp.asarray([pos], jnp.int32))
        out.append([np.asarray(idx[0]) for idx in picked])
    return out


def agreement(mine, want):
    """mine [step][layer] positions, want [layer] bool [steps, T] ->
    per layer {mean, least} of |mine & want| / |want|."""
    import numpy as np
    out = []
    for layer, masks in enumerate(want):
        shares = []
        for step, mask in enumerate(masks):
            idx = mine[step][layer]
            got = np.zeros(mask.shape, bool)
            got[idx[idx >= 0]] = True
            shares.append(float((got & mask).sum() / mask.sum()))
        out.append({"mean": sum(shares) / len(shares),
                    "least": min(shares)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=6144)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--tiny", action="store_true",
                    help="the file's `tiny` block laid over it (the CPU)")
    args = ap.parse_args()
    bench_run.use_checkout()
    import jax
    import numpy as np
    _, _, config, _ = bench_run.load_cell(args.workload)
    if args.tiny:
        config = common.merged(config, config["tiny"])
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    params = jax.jit(lambda key: ref.init_params(key, config))(
        jax.random.key(common.program_seed(args.seed)))
    chunk = config["program"]["serve"]["engine_kwargs"]["prefill_chunk"]
    # whole chunks, so that the reference's blocks divide the sequence;
    # causal, so what follows the last step changes nothing before it
    total = -(-(args.prompt + args.steps) // chunk) * chunk
    seq = np.random.default_rng(args.seed).integers(
        0, config["vocab_size"], total).astype(np.int32)
    at = list(range(args.prompt, args.prompt + args.steps))
    want = reference_sets(ref, params, jax.numpy.asarray(seq), config, at)
    out = {"selection_agreement_of": args.workload, "seed": args.seed,
           "prompt": args.prompt, "steps": args.steps,
           "index_topk": config["index_topk"],
           "device": common.device_report()}
    for name, over in (("stated", {}), ("control", config["control"])):
        cfg = common.model_config(common.merged(config, over), "serve")
        mine = program_sets(cfg, params, seq, args.prompt, at[-1] + 1,
                            chunk)
        out[name] = agreement(mine, want)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
