"""The one general traffic generator. A traffic mix is a data file under
`benchmarks/traffic/`; this module turns it and a seed into the work of
one run. A new mix is a new data file and nothing else. Its `driver`
names the harness module (`benchmarks/harness/<driver>_cell.py`) that
runs it.

Training mix keys:
  driver "train", batch, seq_len, unroll, mesh {axis: size},
  token_distribution {"zipf_exponent": a}, prefetch_depth,
  warm_dispatches (before the window), check_sequences (sequences of the
  first batch whose loss is compared with the reference's), check_by
  (optional; "position": the comparison is made position by position and
  the median of the absolute gaps is held to the configuration's
  `loss_position_abs`, where the plain mean of the sample is held to
  `loss_abs` without it: `train_cell.py`), trace_s (the part of the
  window a `--trace 1` run traces), window_s (a cap on the timed window:
  the cell measures for the smaller of it and `--seconds`)

Serving mix keys (`serve_requests`):
  driver "serve", loop "open" (rate_per_s) | "closed" (clients),
  prompt_tokens / output_tokens {median, sigma, min, max} (lognormal,
  clipped), length_block (requests a block: every block holds the same
  multiset of lengths and of gaps), order_seed (the order and pairing
  within each block: the mix's, not the run's), ramp_s (open loop: the
  load before the window; closed loop: the longest the ramp may take),
  ramp_requests (closed loop: the window opens when so many requests have
  been sent), trace_s, warm_new_tokens, request_timeout_s,
  late_limit_ms (how late the open loop's generator may run, p90; one
  engine tick where the mix does not say)

  What `correct` compares with the reference is the mix's too
  (`check_plan`): `check_requests` requests of the mix's first block,
  evenly spaced by rank of prompt + output over the whole block, the
  same requests and every token of each in every run, whatever its
  seed, its length or its speed. They are among the first requests of
  the load, in a closed loop the wave sent at t = 0 and served while
  the ramp lasts: what a request meets once slots and pages have been
  freed and taken again is not compared (PERF.md, section 2).
"""

from __future__ import annotations

import statistics

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


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a clipped lognormal, as whole lengths:
    the same multiset whoever draws it."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def serve_blocks(mix: dict):
    """Endless blocks of `length_block` requests in the order they are
    sent, each (prompt tokens, output tokens, seconds since the request
    before it or None): the mix's alone, no seed of a run in it.

    Each block holds the same prompt lengths, the same output lengths
    and (open loop) the same gaps between arrivals: the mid-quantiles of
    the two lognormals and of the exponential at the mix's rate,
    rescaled so that a block lasts exactly `length_block / rate_per_s`.
    Their pairing and order within each block come from the mix's
    `order_seed`. (Why not from `--seed`: a window holds one and a half
    request lifetimes, and which lengths fall into it moved tokens per
    second by 9.5 % from seed to seed with nothing else changed; PERF.md,
    section 6, PR 30.)"""
    order = np.random.default_rng([mix["order_seed"], 7])
    block = mix["length_block"]
    prompts = lognormal_quantiles(mix["prompt_tokens"], block)
    outputs = lognormal_quantiles(mix["output_tokens"], block)
    gaps = None
    if mix["loop"] == "open":
        q = (np.arange(block) + 0.5) / block
        gaps = -np.log1p(-q)
        gaps *= block / mix["rate_per_s"] / gaps.sum()
    while True:
        p_order, o_order = order.permutation(block), order.permutation(block)
        g_order = order.permutation(block)
        yield [(int(prompts[p_order[i]]), int(outputs[o_order[i]]),
                None if gaps is None else float(gaps[g_order[i]]))
               for i in range(block)]


def serve_requests(mix: dict, seed: int, vocab_size: int):
    """Endless requests {"index": its place in the generator's order,
    "prompt": int32 ids, "max_new_tokens": n, "due_s": seconds after the
    load's start (open loop) or None}. The lengths, their order and the
    arrival times are `serve_blocks`', the same for every seed; the seed
    draws the token ids (uniform: no two prompts share a prefix)."""
    rng = np.random.default_rng([seed, 7])
    index, due = 0, 0.0
    for block in serve_blocks(mix):
        for prompt_tokens, output_tokens, gap in block:
            if gap is not None:
                due += gap
            yield {"index": index,
                   "prompt": rng.integers(0, vocab_size, prompt_tokens,
                                          dtype=np.int32),
                   "max_new_tokens": output_tokens,
                   "due_s": None if gap is None else due}
            index += 1


def check_plan(mix: dict) -> list:
    """[(index, tokens)]: the requests whose streamed logprobs a run
    compares with the reference's, and how many tokens of each: all it
    asked for. Of the mix's first block, `check_requests` evenly spaced
    by rank of prompt + output over the whole block, the shortest and
    the longest among them. A function of the mix file alone, so two
    runs compare like with like: the requests that happened to finish
    differ with the seed, the window and the speed, and a mean weighted
    by tokens moved with them (PERF.md, section 6, PR 48)."""
    first = next(serve_blocks(mix))
    ranked = sorted(range(len(first)),
                    key=lambda i: first[i][0] + first[i][1])
    n = mix["check_requests"]
    if len(ranked) > n:
        ranked = [ranked[int(i)] for i in np.linspace(0, len(ranked) - 1, n)]
    return [(i, first[i][1]) for i in ranked]


def percentile(values, p: float) -> float | None:
    """The value at rank int(p/100 * n) of the sorted sample (the
    engine's rule); None of nothing."""
    values = sorted(values)
    if not values:
        return None
    return values[min(len(values) - 1, int(p / 100 * len(values)))]


def window_stats(requests: list, lo: float, hi: float,
                 context_every_s: float = 0.5) -> dict:
    """The client's view of the window [lo, hi). `requests`: one dict a
    request sent, {"t_ref": the time it is timed from (when it was due
    in an open loop, when it was sent in a closed one), "prompt_tokens",
    "arrivals": [each token's time at the client]}.

      tokens            tokens that arrived inside the window
      ttft_ms           of the requests whose first token arrived inside
      tpot_ms           every gap between two tokens of one stream whose
                        later token arrived inside
      decoding_context_tokens   mean, over instants `context_every_s`
                        apart, of prompt + received tokens summed over
                        the streams between their first and last token:
                        what a decode step has to read from the cache
    """
    tokens, ttft, tpot = 0, [], []
    instants = np.arange(lo, hi, context_every_s)
    context = np.zeros(len(instants))
    for r in requests:
        arr = np.asarray(r["arrivals"], np.float64)
        if arr.size == 0:
            continue
        inside = (arr >= lo) & (arr < hi)
        tokens += int(inside.sum())
        if inside[0]:
            ttft.append((arr[0] - r["t_ref"]) * 1e3)
        tpot.extend(((arr[1:] - arr[:-1])[inside[1:]] * 1e3).tolist())
        live = (instants >= arr[0]) & (instants < arr[-1])
        context[live] += r["prompt_tokens"] + np.searchsorted(
            arr, instants[live], side="right")
    return {"tokens": tokens, "window_s": hi - lo,
            "tokens_per_s": tokens / (hi - lo),
            "ttft_ms": ttft, "tpot_ms": tpot,
            "decoding_context_tokens": (float(context.mean())
                                        if len(instants) else 0.0)}
