"""What a serving cell's `correct` compares is the mix's: the same
requests and the same count of tokens of each, whoever finished what.
Pure Python, no chip, no JAX.

    python -m pytest benchmarks/tests/test_check_sample.py -q
"""

import glob
import json
import os
import random
import threading
import time

import numpy as np
import pytest

from benchmarks.harness import serve_cell, traffic
from benchmarks.harness.common import ROOT, merged

MIXES = {}
for _path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "traffic",
                                           "*.json"))):
    with open(_path) as _f:
        _mix = json.load(_f)
    if _mix["driver"] == "serve":
        MIXES[os.path.basename(_path)[:-5]] = _mix


def records_of(mix, finished: int, shuffle_seed: int, sent: int = 70):
    """The records of a load in which the first `finished` requests sent
    got every token and the rest were cut a token short, appended in
    another order than they were sent in."""
    gen = traffic.serve_requests(mix, 5, 512)
    records = []
    for _ in range(sent):
        req = next(gen)
        done = req["index"] < finished
        n = req["max_new_tokens"] - (0 if done else 1)
        records.append({"index": req["index"], "prompt": req["prompt"],
                        "tokens": list(range(n)),
                        "logprobs": [-float(i) for i in range(n)],
                        "arrivals": [float(i) for i in range(n)],
                        "ended": "complete" if done else "cut"})
    random.Random(shuffle_seed).shuffle(records)
    return records


def compared(samples):
    return [(s["index"], len(s["prompt"]), len(s["tokens"]))
            for s in samples]


@pytest.mark.parametrize("finished", [24, 32, 60])
def test_the_sample_is_the_same_whatever_finished(finished):
    mix = MIXES["docqa-closed24"]
    plan = traffic.check_plan(mix)
    samples, problems = serve_cell.check_sample(
        records_of(mix, finished, shuffle_seed=finished), plan)
    assert problems == []
    assert compared(samples) == [(19, 2560, 171), (1, 4811, 116),
                                 (5, 7407, 67), (21, 14336, 140)]
    for s in samples:
        assert s["logprobs"] == [-float(i) for i in range(len(s["tokens"]))]


def test_a_chosen_request_short_of_its_tokens_is_a_problem_not_a_resample():
    """Ten requests finished: of the four compared, 19 and 21 did not."""
    mix = MIXES["docqa-closed24"]
    plan = traffic.check_plan(mix)
    samples, problems = serve_cell.check_sample(
        records_of(mix, 10, shuffle_seed=1), plan)
    assert [s["index"] for s in samples] == [1, 5]
    assert len(problems) == 2
    assert "request 19" in problems[0] and "170 of the 171" in problems[0]
    assert "(cut)" in problems[0] and "nothing is compared in its place" \
        in problems[0]
    assert "request 21" in problems[1]
    # one that the load never reached
    samples, problems = serve_cell.check_sample(
        [r for r in records_of(mix, 60, 2) if r["index"] != 5], plan)
    assert [s["index"] for s in samples] == [19, 1, 21]
    assert len(problems) == 1 and "request 5" in problems[0] \
        and "never sent" in problems[0]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_plan_is_the_mix_s_alone(name):
    """`check_requests` requests of the first block, the shortest and the
    longest of the whole block among them; and `serve_requests` sends
    those lengths at those places whatever the seed."""
    for mix in (MIXES[name], merged(MIXES[name], MIXES[name]["tiny"])):
        plan = traffic.check_plan(mix)
        assert plan == traffic.check_plan(json.loads(json.dumps(mix)))
        assert len(plan) == mix["check_requests"] == len(dict(plan))
        a = traffic.serve_requests(mix, 3, 512)
        b = traffic.serve_requests(mix, 2**31 + 11, 512)
        first = []
        for i in range(2 * mix["length_block"]):
            x, y = next(a), next(b)
            assert x["index"] == y["index"] == i
            assert (len(x["prompt"]), x["max_new_tokens"], x["due_s"]) == \
                (len(y["prompt"]), y["max_new_tokens"], y["due_s"])
            if i < mix["length_block"]:
                first.append(len(x["prompt"]) + x["max_new_tokens"])
        assert all(i < mix["length_block"] for i, _ in plan)
        sizes = [first[i] for i, _ in plan]
        assert sizes == sorted(sizes)
        assert sizes[0] == min(first) and sizes[-1] == max(first)
        gen = traffic.serve_requests(mix, 3, 512)
        asked = [next(gen)["max_new_tokens"]
                 for _ in range(mix["length_block"])]
        assert [n for _, n in plan] == [asked[i] for i, _ in plan]


def test_the_generator_draws_what_it_drew_before_it_kept_the_index():
    """The load is the parent's: lengths, order, arrivals and token ids
    (the three permutations a block and the ids' stream, PR 30's)."""
    mix = MIXES["chat-steady"]
    order = np.random.default_rng([mix["order_seed"], 7])
    rng = np.random.default_rng([9, 7])
    block = mix["length_block"]
    prompts = traffic.lognormal_quantiles(mix["prompt_tokens"], block)
    outputs = traffic.lognormal_quantiles(mix["output_tokens"], block)
    gen = traffic.serve_requests(mix, 9, 50304)
    for _ in range(2):
        p, o = order.permutation(block), order.permutation(block)
        order.permutation(block)
        for i in range(block):
            req = next(gen)
            want = rng.integers(0, 50304, int(prompts[p[i]]), dtype=np.int32)
            assert (req["prompt"] == want).all()
            assert req["max_new_tokens"] == int(outputs[o[i]])


def load_with(records):
    load = serve_cell.Load(None, {}, iter(()))
    load.records = records
    return load


def test_the_run_waits_for_a_compared_request_and_no_longer():
    plan = [(0, 3), (1, 2)]
    late = {"index": 1, "tokens": [7], "ended": None}
    load = load_with([{"index": 0, "tokens": [1, 2, 3], "ended": None}, late])

    def finish():
        time.sleep(0.2)
        late["tokens"].append(8)

    t = threading.Thread(target=finish)
    t0 = time.perf_counter()
    t.start()
    load.await_streamed(plan, t0 + 30)
    t.join()
    assert 0.15 < time.perf_counter() - t0 < 5 and len(late["tokens"]) == 2
    # one that ended short will never get there: no wait for it
    load = load_with([{"index": 0, "tokens": [1], "ended": "short"},
                      {"index": 1, "tokens": [1, 2], "ended": "complete"}])
    t0 = time.perf_counter()
    load.await_streamed(plan, t0 + 30)
    assert time.perf_counter() - t0 < 1
    # one never sent: the deadline ends the wait
    t0 = time.perf_counter()
    load_with([]).await_streamed(plan, t0 + 0.3)
    assert 0.25 < time.perf_counter() - t0 < 2
