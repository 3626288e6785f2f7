"""The one general traffic generator. A traffic mix is a data file under
`benchmarks/traffic/`; this module turns it and a seed into the work of
one run. A new mix is a new data file and nothing else. Its `driver`
names the harness module (`benchmarks/harness/<driver>_cell.py`) that
runs it; a kind of traffic the repo has no driver for yet (serving:
PERF.md, section 7) brings its driver and its generator as new files.

Training mix keys:
  driver "train", batch, seq_len, unroll, mesh {axis: size},
  token_distribution {"zipf_exponent": a}, prefetch_depth,
  warm_dispatches (before the window), check_sequences (sequences of the
  first batch whose loss is compared with the reference's), trace_s (the
  part of the window a `--trace 1` run traces)
"""

from __future__ import annotations

import numpy as np


def zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** exponent
    return np.cumsum(p / p.sum())


def train_batches(mix: dict, seed: int, vocab_size: int):
    """Endless host batches {"inputs", "targets"} [batch, seq_len] from a
    seeded bounded-Zipf token stream: fresh tokens every step, skewed, so
    a model can fall below ln(vocab) by learning the unigram rates."""
    rng = np.random.default_rng([seed, 3])
    cdf = zipf_cdf(vocab_size, mix["token_distribution"]["zipf_exponent"])
    shape = (mix["batch"], mix["seq_len"] + 1)
    while True:
        toks = np.searchsorted(cdf, rng.random(shape)).astype(np.int32)
        np.minimum(toks, vocab_size - 1, out=toks)
        yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
