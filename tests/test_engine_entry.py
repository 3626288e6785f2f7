"""The engine's entry path: one consumer at a time runs `step()`, the
others sleep until that tick has handed its tokens over, `submit` waits
for no tick, and `stats()` / `cancel()` get the scheduler lock ahead of
the next tick. And the replica's reply: what is ready, not a full batch.

A tick is made slow with the fault plan's `delay` at `engine.tick`, which
sleeps under the scheduler lock as a device round trip would. Every time
bound is in units of the tick the test measured, with room to spare."""

import statistics
import threading
import time

import jax
import pytest

from ray_tpu.models import gpt
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.serve.replica import Replica
from ray_tpu.util import faults

TICK_DELAY_S = 0.04
JOIN_S = 30


def tiny_engine(slots=8, **kw):
    cfg = gpt.GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                        d_ff=64, max_seq_len=64, dtype="float32")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("prefill_buckets", (8, 16))
    return InferenceEngine(params, cfg, slots=slots, max_len=64, **kw)


def prompt(i, n=4):
    return [(7 * i + j) % 60 + 1 for j in range(n)]


def drain(eng, rid):
    return (eng.handoff_for(rid) if eng.role == "prefill"
            else list(eng.tokens_for(rid)))


@pytest.fixture
def slow_ticks():
    """A function that warms an engine up, makes its ticks slow from
    then on, and returns the tick it measured."""
    def install(eng):
        drain(eng, eng.submit(prompt(99), max_new_tokens=2))    # compile
        faults.install(faults.FaultPlan().delay(
            "engine.tick", TICK_DELAY_S, times=None))
        times = []
        for i in range(3):      # one request, one prefill, one tick
            rid = eng.submit(prompt(90 + i), max_new_tokens=1)
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
            drain(eng, rid)
        eng.reset_stats()
        return statistics.median(times)

    yield install
    faults.clear()


def run_threads(targets):
    """Start one thread per callable, join each within JOIN_S, re-raise
    the first error."""
    errors = []

    def guard(fn):
        def run():
            try:
                fn()
            except BaseException as e:      # re-raised below
                errors.append(e)
        return run

    threads = [threading.Thread(target=guard(fn), daemon=True)
               for fn in targets]
    for t in threads:
        t.start()
    for t in threads:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in threads), "a thread hangs"
    if errors:
        raise errors[0]


class Pumping:
    """One consumer that runs ticks back to back (its long stream gets
    a token a tick) until stopped."""

    def __init__(self, eng, n=56):
        self.eng, self.stop = eng, threading.Event()
        self.rid = eng.submit(prompt(0), max_new_tokens=n)
        self.got = 0
        self.thread = threading.Thread(target=self.run, daemon=True)
        self.thread.start()

    def run(self):
        for _ in self.eng.tokens_for(self.rid):
            self.got += 1
            if self.stop.is_set():
                return

    def __enter__(self):
        while self.got < 2:             # ticks are running
            time.sleep(0.005)
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join(JOIN_S)
        assert not self.thread.is_alive()


# -- one pump at a time ------------------------------------------------------

def test_eight_streams_one_in_step_and_each_served_every_tick(slow_ticks):
    eng = tiny_engine()
    slow_ticks(eng)
    inside, most = [0], [0]
    guard, step = threading.Lock(), eng.step

    def counted_step():
        with guard:
            inside[0] += 1
            most[0] = max(most[0], inside[0])
        try:
            return step()
        finally:
            with guard:
                inside[0] -= 1

    eng.step = counted_step
    n_new = 12
    rids = [eng.submit(prompt(i), max_new_tokens=n_new) for i in range(8)]
    seen = {rid: [] for rid in rids}       # tick at each token's receipt

    def consume(rid):
        def run():
            for _ in eng.tokens_for(rid):
                seen[rid].append(eng._tick_seq)
        return run

    run_threads([consume(rid) for rid in rids])
    assert most[0] == 1
    assert all(len(v) == n_new for v in seen.values())
    # token i of a stream arrives i ticks after its first, give or take
    # one: no stream's tokens pile up behind another stream's pump
    lags = [abs(t - v[0] - i) for v in seen.values()
            for i, t in enumerate(v)]
    assert statistics.median(lags) <= 1 and max(lags) <= 3, lags
    st = eng.stats()
    # the ticks ran once for all eight: about n_new of them, not 8 x
    assert st["ticks"] <= n_new + 6
    assert st["stream_waits"] >= 4 * n_new     # seven asleep a tick
    assert st["stream_wait_s"] > 0
    assert st["decode_traces"] == 1 and st["retraces_unexpected"] == 0
    eng.check_invariants()


def test_a_tick_wakes_the_consumers_it_served_and_one_more(slow_ticks):
    """Two slots, eight consumers: the six whose requests wait for a
    slot sleep through the ticks that hand them nothing, but for the one
    a tick's end wakes to take the pump if it is free. Every stream
    still ends, whoever's thread pumped."""
    eng = tiny_engine(slots=2)
    slow_ticks(eng)
    n_new = 10
    rids = [eng.submit(prompt(i), max_new_tokens=n_new) for i in range(8)]
    got = {rid: 0 for rid in rids}

    def consume(rid):
        def run():
            for _ in eng.tokens_for(rid):
                got[rid] += 1
        return run

    run_threads([consume(rid) for rid in rids])
    assert all(n == n_new for n in got.values())
    st = eng.stats()
    # a tick woke the two it served and one more, not all seven asleep
    # (with every sleeper woken every tick: over four a tick)
    assert 30 <= st["ticks"] and st["stream_waits"] <= 3 * st["ticks"]
    assert not eng._sleepers
    eng.check_invariants()


def test_a_tick_run_by_another_thread_than_the_last_is_a_handoff():
    """`pump_handoffs`: the short stream's consumer runs every tick
    until its stream ends, with the long stream decoding beside it; the
    long stream's consumer then takes the pump over, once."""
    eng = tiny_engine(slots=2)
    short = eng.submit(prompt(1), max_new_tokens=3)
    long_ = eng.submit(prompt(2), max_new_tokens=9)
    run_threads([lambda: drain(eng, short)])
    st = eng.stats()
    assert st["pump_handoffs"] == 0 and st["tick_gaps"] == st["ticks"] - 1
    assert len(drain(eng, long_)) == 9
    st = eng.stats()
    assert st["pump_handoffs"] == 1
    assert st["tick_gaps"] == st["ticks"] - 1
    assert 0 < st["tick_gap_max_s"] <= st["tick_gap_s"]


def test_submit_under_back_to_back_ticks_takes_under_half_a_tick(
        slow_ticks):
    eng = tiny_engine()
    tick = slow_ticks(eng)
    took, rids = [], []
    with Pumping(eng) as pump:
        for i in range(20):
            t0 = time.perf_counter()
            rids.append(eng.submit(prompt(i + 1), max_new_tokens=1))
            took.append(time.perf_counter() - t0)
            time.sleep(tick * 0.3)          # land all over the tick
        assert pump.thread.is_alive()       # ticks ran throughout
    # (the parent's first submit waits out the whole stream)
    assert statistics.median(took) < tick / 2 and max(took) < 2 * tick, \
        (took, tick)
    st = eng.stats()
    assert st["submits"] == 21 and 0 < st["submit_s"] < 21 * tick / 2
    for rid in rids:
        eng.cancel(rid)
    eng.check_invariants()


@pytest.mark.parametrize("pump", ["decode", "prefill"])
def test_stats_and_cancel_go_ahead_of_the_next_tick(slow_ticks, pump):
    """One consumer runs ticks back to back: a long stream, or a long
    prompt absorbed a chunk a tick beside one that decodes."""
    eng = tiny_engine(prefill_chunk=8)
    tick = slow_ticks(eng)
    took, long = [], None
    with Pumping(eng):
        if pump == "prefill":
            long_rid = eng.submit(prompt(1, 56), max_new_tokens=2)
            long = threading.Thread(
                target=lambda: list(eng.tokens_for(long_rid)),
                daemon=True)
            long.start()
        for i in range(6):
            queued = eng.submit(prompt(i + 2), max_new_tokens=1)
            for call in (eng.stats, lambda: eng.cancel(queued)):
                t0 = time.perf_counter()
                assert call()
                took.append(time.perf_counter() - t0)
            time.sleep(tick * 0.4)
        if long is not None:
            long.join(JOIN_S)
            assert not long.is_alive()
    assert max(took) < 2 * tick, (took, tick)
    eng.check_invariants()


def test_closed_loop_clients_keep_the_slots_occupied(slow_ticks):
    eng = tiny_engine()
    slow_ticks(eng)

    def client(i):
        def run():
            for k in range(3):
                rid = eng.submit(prompt(10 * i + k), max_new_tokens=20)
                assert len(list(eng.tokens_for(rid))) == 20
        return run

    run_threads([client(i) for i in range(8)])
    st = eng.stats()
    assert st["slot_occupancy"] * 8 >= 7, st["slot_occupancy"]
    # 8 x 3 x 20 tokens in little more than 3 x 20 ticks
    assert st["ticks"] <= 3 * 20 + 12
    eng.check_invariants()


def test_a_failed_tick_raises_in_the_consumer_that_ran_it_only(slow_ticks):
    prompts = [prompt(i) for i in range(4)]
    ref = tiny_engine()
    want = [[int(t) for t in ref.generate(p, max_new_tokens=10)]
            for p in prompts]
    eng = tiny_engine()
    slow_ticks(eng)
    faults.install(faults.FaultPlan()       # the streams' sixth tick
                   .delay("engine.tick", TICK_DELAY_S, times=None)
                   .fail("engine.tick", at=5, times=1))
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    got, failed = {}, []

    def consume(rid):
        def run():
            out = []
            try:
                for t in eng.tokens_for(rid):
                    out.append(int(t))
            except faults.FaultInjected:
                failed.append(rid)
            got[rid] = out
        return run

    run_threads([consume(rid) for rid in rids])
    assert len(failed) == 1
    for rid, w in zip(rids, want):
        if rid in failed:
            assert got[rid] == w[:len(got[rid])] and len(got[rid]) < 10
        else:
            assert got[rid] == w
    assert eng._tick_started is None       # the watchdog's view is closed
    assert eng.stats()["cancelled"] == 1   # the failed stream let go
    eng.check_invariants()


def test_handoff_for_from_four_threads_on_a_prefill_engine(slow_ticks):
    """Twelve prefills handed off to four waiting threads while a fifth
    reads `stats()`: every thread gets the blob of the rid it waited for,
    in the order it submitted, each blob once, and the counter a scrape
    sees only grows, to twelve. (How long a scrape waits is the two tests
    above's; on a machine six workers share it is no part of this one.)"""
    eng = tiny_engine(role="prefill", prefill_chunk=8)
    slow_ticks(eng)
    prompts = [prompt(i, 6 + 5 * (i % 3)) for i in range(12)]
    got = {k: [] for k in range(4)}     # thread -> [(rid, i, blob)]

    def worker(k):
        def run():
            for i in range(k, 12, 4):
                rid = eng.submit(prompts[i], max_new_tokens=4)
                got[k].append((rid, i, eng.handoff_for(rid)))
        return run

    t0 = time.perf_counter()
    seen = []

    def scrape():
        while ((len(seen) < 5 or seen[-1] < 12)
               and time.perf_counter() - t0 < JOIN_S):
            st = eng.stats()
            assert st["role"] == "prefill"
            seen.append(st["handoffs"])
            time.sleep(0.01)

    run_threads([worker(k) for k in range(4)] + [scrape])
    for k, handed in got.items():
        assert [i for _, i, _ in handed] == list(range(k, 12, 4))
        rids = [rid for rid, _, _ in handed]
        assert rids == sorted(rids)
        for _, i, blob in handed:
            assert list(blob["prompt"]) == prompts[i]
    assert len({rid for h in got.values() for rid, _, _ in h}) == 12
    assert seen == sorted(seen) and seen[-1] == 12
    st = eng.stats()
    assert st["handoffs"] == 12 and st["handoffs_pending"] == 0
    with pytest.raises(KeyError):
        eng.handoff_for(10_000)
    with pytest.raises(KeyError):       # a blob is handed off once
        eng.handoff_for(got[0][0][0])
    eng.check_invariants()
    assert time.perf_counter() - t0 < JOIN_S


# -- the inbox -----------------------------------------------------------------

def test_the_backlog_counts_the_inbox_and_cancel_reaches_into_it():
    eng = tiny_engine(slots=2, priority_classes=2)
    rids = [eng.submit(prompt(i), max_new_tokens=3, priority=i % 2)
            for i in range(5)]
    assert len(eng._inbox) == 5 and not eng._pending   # no tick yet
    eng.check_invariants()
    st = eng.stats()
    assert st["pending"] == st["queue_depth"] == 5
    assert {c: row["pending"] for c, row in st["per_class"].items()} == \
        {"0": 3, "1": 2}
    assert {c: row["submitted"] for c, row in st["per_class"].items()} == \
        {"0": 3, "1": 2}
    late = eng.submit(prompt(9), max_new_tokens=3)
    assert [q.rid for q in eng._inbox] == [late]
    assert eng.cancel(late) and not eng.cancel(late)
    assert late not in eng._out and not eng._inbox
    eng.check_invariants()
    assert list(eng.tokens_for(late)) == []
    outs = [list(eng.tokens_for(rid)) for rid in rids]
    assert all(len(o) == 3 for o in outs)
    st = eng.stats()
    assert st["pending"] == 0 and st["cancelled"] == 1
    assert sum(r["submitted"] for r in st["per_class"].values()) == 6
    eng.check_invariants()


def test_a_shedding_submit_counts_the_inbox_in_its_verdict():
    from ray_tpu.exceptions import OverloadedError
    eng = tiny_engine(slots=1, max_queue=2)
    a = eng.submit(prompt(1), max_new_tokens=2)
    b = eng.submit(prompt(2), max_new_tokens=2)
    with pytest.raises(OverloadedError):
        eng.submit(prompt(3), max_new_tokens=2)
    assert eng.stats()["sheds"] == 1
    assert [len(list(eng.tokens_for(r))) for r in (a, b)] == [2, 2]
    eng.check_invariants()


# -- the reply -----------------------------------------------------------------

def make_replica():
    return Replica({"callable": lambda: None,
                    "deployment_name": "entry-test",
                    "max_concurrent_queries": 4})


def test_a_slow_generator_is_answered_a_chunk_a_call():
    gap = 0.05

    def slow():
        for i in range(3):
            time.sleep(gap)
            yield i

    rep = make_replica()
    sid = rep._register_stream(slow())
    t0 = time.perf_counter()
    got, done = rep._next_chunks_sync(sid, 16)
    took = time.perf_counter() - t0
    assert (got, done) == ([0], False)
    assert gap * 0.9 <= took < 2 * gap + 0.05, took
    assert rep._next_chunks_sync(sid, 16) == ([1], False)
    assert rep._next_chunks_sync(sid, 16) == ([2], False)
    assert rep._next_chunks_sync(sid, 16) == ([], True)


def test_a_ready_stream_still_fills_the_batch():
    rep = make_replica()
    sid = rep._register_stream(iter(list(range(40))))
    assert rep._next_chunks_sync(sid, 16) == (list(range(16)), False)
    assert rep._next_chunks_sync(sid, 16) == (list(range(16, 32)), False)
    assert rep._next_chunks_sync(sid, 16) == (list(range(32, 40)), True)


def test_replies_run_on_the_replicas_own_executor():
    import asyncio
    rep = make_replica()
    names = []

    def where():
        names.append(threading.current_thread().name)
        yield 1

    sid = rep._register_stream(where())
    assert asyncio.run(rep.next_chunks(sid)) == ([1], True)
    assert names[0].startswith("replica-entry-test")
    assert rep._executor._max_workers == 4


@pytest.mark.parametrize("gap", [0.005, 0.012])
def test_a_generator_a_few_ms_a_chunk_is_answered_a_chunk_a_call(gap):
    """Gaps under the 20 ms a reply once went on collecting for: every
    `next()` waits, so every reply leaves with the one chunk it waited
    for, about a gap after the call."""
    n = 8

    def slow():
        for i in range(n):
            time.sleep(gap)
            yield i

    rep = make_replica()
    sid = rep._register_stream(slow())
    took = []
    for i in range(n):
        t0 = time.perf_counter()
        assert rep._next_chunks_sync(sid, 16) == ([i], False)
        took.append(time.perf_counter() - t0)
    assert rep._next_chunks_sync(sid, 16) == ([], True)
    assert gap * 0.9 <= statistics.median(took) < 2 * gap + 0.005, took
    st = rep.stats()
    assert st["reply_tokens"] == n and st["replies"] == n + 1


@pytest.mark.parametrize("wait", [0.012, 0.03])
def test_a_reply_ends_with_the_first_chunk_it_waited_for(wait):
    def bursty():
        yield from range(3)             # ready
        for i in range(3, 6):
            time.sleep(wait)
            yield i

    rep = make_replica()
    sid = rep._register_stream(bursty())
    t0 = time.perf_counter()
    got, done = rep._next_chunks_sync(sid, 16)
    took = time.perf_counter() - t0
    # the three that were ready and the one waited for, after one wait
    assert (got, done) == ([0, 1, 2, 3], False)
    assert wait * 0.9 <= took < 2 * wait, took
    assert rep._next_chunks_sync(sid, 16) == ([4], False)
    assert rep._next_chunks_sync(sid, 16) == ([5], False)
    assert rep._next_chunks_sync(sid, 16) == ([], True)
    assert rep.stats()["reply_tokens"] == 6


def pull_all(rep, sid, max_chunks, replies):
    """A callable that drains stream `sid` as a handle does, each
    reply's chunks appended to `replies`."""
    def run():
        done = False
        while not done:
            chunks, done = rep._next_chunks_sync(sid, max_chunks)
            replies.append(chunks)
    return run


def test_two_engine_streams_get_every_token_once_and_in_order(slow_ticks):
    """Two streams of one engine behind a `Replica`, each drained by its
    own thread through `_next_chunks_sync` as a handle would: whichever
    thread runs the tick, each reply holds its stream's next tokens."""
    eng = tiny_engine()
    n_new = 6
    want = [eng.generate(prompt(i), max_new_tokens=n_new) for i in (1, 2)]
    slow_ticks(eng)
    rep = make_replica()
    sids = [rep._register_stream(eng.tokens_for(
                eng.submit(prompt(i), max_new_tokens=n_new)))
            for i in (1, 2)]
    replies = {sid: [] for sid in sids}
    run_threads([pull_all(rep, sid, 16, replies[sid]) for sid in sids])
    assert [[int(t) for r in replies[sid] for t in r]
            for sid in sids] == want
    st = rep.stats()
    assert st["reply_tokens"] == 2 * n_new and st["streams"] == 0
    assert st["replies"] == sum(len(r) for r in replies.values())
    # a token a tick: a reply does not hold one back to wait for the next
    assert statistics.median(
        len(c) for r in replies.values() for c in r if c) == 1, replies
    eng.check_invariants()


def test_reply_tokens_loses_no_count_under_many_reply_threads():
    """More reply threads than cores, a short switch interval: the count
    is the chunks handed back, whichever thread added last."""
    import sys
    rep = make_replica()
    n_threads, n_chunks = 24, 400
    sids = [rep._register_stream(iter(range(n_chunks)))
            for _ in range(n_threads)]
    replies = {sid: [] for sid in sids}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        run_threads([pull_all(rep, sid, 7, replies[sid]) for sid in sids])
    finally:
        sys.setswitchinterval(interval)
    assert all(sum(r, []) == list(range(n_chunks)) for r in replies.values())
    st = rep.stats()
    assert st["reply_tokens"] == n_threads * n_chunks and st["streams"] == 0
