"""Program spans on the profiler's clock (`util.telemetry.Phases`): the
accumulator, the spans a CPU `jax.profiler` trace holds for the train
loop and the engine tick with the Python tracer off, and the request's
step past the engine's edge (`first_yield`, `deliver_wait_ms_*`)."""

import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.train import loop
from ray_tpu.util import telemetry

TRAIN_SPANS = {"train/next_batch", "train/host_batch", "train/place",
               "train/dispatch", "train/metrics", "train/metrics_fetch"}
TICK_SPANS = {"engine/tick", "engine/admit", "engine/prefill_chunk",
              "engine/prefill_build", "engine/prefill_dispatch",
              "engine/prefill_sync", "engine/decode_build",
              "engine/decode_put", "engine/decode_dispatch",
              "engine/token_sync", "engine/emit", "engine/submit"}


def tiny_engine(**kw):
    cfg = gpt.GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                        d_ff=64, max_seq_len=32, dtype="float32")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg, slots=2, max_len=32,
                           prefill_buckets=(8, 16), **kw)


def train_run(dispatches=3, unroll=2, **loop_kw):
    """`dispatches` fused dispatches through the prefetcher, to the end
    of the host iterator."""
    def step_fn(state, batch):
        return state + jnp.sum(batch["x"]), {"loss": jnp.mean(batch["x"])}

    host = ({"x": np.full((2, 4), i, np.float32)}
            for i in range(dispatches * unroll))
    batches = loop.DevicePrefetcher(host, lambda t: jax.tree.map(
        jnp.asarray, t), depth=2, group=unroll)
    tl = loop.TrainLoop(step_fn, unroll=unroll, metrics_interval=unroll,
                        **loop_kw)
    state, metrics = tl.run(jnp.float32(0), batches)
    return tl, batches, metrics


# -- the primitive -----------------------------------------------------------

def test_phase_counts_and_nests():
    ph = telemetry.Phases()
    for _ in range(3):
        with ph.phase("outer", step=1) as outer:
            with ph.phase("inner") as inner:
                time.sleep(1e-3)
    assert ph.count("outer") == ph.count("inner") == 3
    assert ph.seconds("outer") >= ph.seconds("inner") >= 3e-3
    assert outer.seconds >= inner.seconds >= 1e-3
    assert ph.seconds("outer", "inner") == \
        ph.seconds("outer") + ph.seconds("inner")
    assert ph.count("never") == 0 and ph.seconds("never") == 0.0


def test_phase_with_no_session_touches_the_accumulator_only():
    """No profiler session: the span is its total and nothing else — no
    ring, no list that grows with calls; `set` drops its attributes."""
    ph = telemetry.Phases()
    with ph.phase("a", tokens=3) as a:
        a.set(admitted=2)
    before = {k: list(v) for k, v in ph.totals.items()}
    for i in range(1000):
        with ph.phase("a", step=i):
            pass
    assert set(ph.totals) == set(before) == {"a"}
    assert ph.totals["a"][0] == before["a"][0] + 1000
    assert vars(telemetry.Phases).get("__slots__") == ("totals",)


def test_phase_clear_zeroes_in_place_and_an_error_still_counts():
    ph = telemetry.Phases()
    with ph.phase("a") as open_span:
        ph.clear()                      # e.g. reset_stats() mid-tick
    assert ph.totals["a"] == [1, open_span.seconds]
    with pytest.raises(KeyError):
        with ph.phase("a"):
            raise KeyError("x")
    assert ph.count("a") == 2


def test_a_process_without_jax_times_without_annotating():
    total = [0, 0.0]
    with telemetry._HostPhase(total, "stream/reply", {}) as span:
        span.set(tokens=4)
    assert total[0] == 1 and total[1] == span.seconds > 0


# -- the train loop's totals ---------------------------------------------------

def test_last_breakdown_keeps_its_keys_and_reads_the_spans():
    tl, batches, metrics = train_run(dispatches=3, unroll=2)
    bd = tl.last_breakdown
    assert set(bd) == {
        "steps", "total_s", "prefetch_s", "dispatch_s", "metrics_s",
        "checkpoint_s", "publish_s", "prefetch_share", "dispatch_share",
        "metrics_share", "checkpoint_share", "publish_share"}
    assert bd["steps"] == 6 and len(metrics) == 6
    ph = tl.phases
    assert bd["prefetch_s"] == ph.seconds("train/next_batch")
    assert bd["prefetch_share"] == \
        ph.seconds("train/next_batch") / bd["total_s"]
    assert bd["dispatch_s"] == ph.seconds("train/dispatch")
    assert ph.count("train/dispatch") == 3
    assert ph.count("train/next_batch") == 4     # the end of the iterator
    assert ph.count("train/metrics_fetch") == tl.last_ring.fetches
    assert bd["checkpoint_s"] == bd["publish_s"] == 0.0
    assert "train/checkpoint" not in ph.totals      # no hook, no span
    # the prefetcher keeps its own, beside `issued`
    assert batches.phases.count("train/place") == batches.issued == 3
    assert batches.phases.count("train/host_batch") == 4


def test_hooks_open_their_spans_only_when_set():
    class Ckpt:
        def maybe_snapshot(self, state, step):
            pass

        def flush(self):
            pass

    tl, _, _ = train_run(dispatches=2, checkpointer=Ckpt(),
                         publisher=lambda state, step: None)
    assert tl.phases.count("train/checkpoint") == 3     # 2 + flush
    assert tl.phases.count("train/publish") == 2
    assert tl.last_breakdown["checkpoint_s"] == \
        tl.phases.seconds("train/checkpoint")
    tl.run(jnp.float32(0), iter(()))        # a run starts from zero
    assert tl.phases.count("train/dispatch") == 0


# -- what a profiler trace holds, Python tracer off -----------------------------

def program_spans(out):
    """The trace under `out` and its program spans:
    {span name: [(thread, start_ns, end_ns, stats), ...]}."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if re.match(r"(train|engine|stream)/", ev.name):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return path, events


def start_trace(out):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One CPU `jax.profiler` session over a train run and an engine
    run: {span name: [(thread, start_ns, end_ns, stats), ...]}."""
    eng = tiny_engine()
    warm = eng.submit([5, 9, 3], max_new_tokens=2)      # compile outside
    list(eng.tokens_for(warm))
    eng.reset_stats()
    out = str(tmp_path_factory.mktemp("trace"))
    start_trace(out)
    try:
        tl, batches, _ = train_run(dispatches=3, unroll=2)
        rid = eng.submit([7, 1, 2, 4], max_new_tokens=4)
        tokens = list(eng.tokens_for(rid))
    finally:
        jax.profiler.stop_trace()
    path, events = program_spans(out)
    return {"events": events, "engine": eng, "loop": tl, "tokens": tokens,
            "path": path}


def inside(child, parents):
    thread, s, e, _ = child
    return any(t == thread and ps <= s and e <= pe
               for t, ps, pe, _ in parents)


def test_trace_holds_one_span_per_train_dispatch(traced):
    ev = traced["events"]
    assert TRAIN_SPANS <= set(ev)
    assert len(ev["train/dispatch"]) == 3
    assert len(ev["train/next_batch"]) == 3 + 1     # + end of iterator
    assert [d[3]["step"] for d in sorted(ev["train/dispatch"],
                                         key=lambda d: d[1])] == [0, 2, 4]
    assert all(f[3]["entries"] >= 1 for f in ev["train/metrics_fetch"])


def test_prefetcher_spans_are_children_of_next_batch(traced):
    ev = traced["events"]
    for name in ("train/host_batch", "train/place"):
        assert ev[name] and all(inside(c, ev["train/next_batch"])
                                for c in ev[name])
    assert all(inside(c, ev["train/metrics"])
               for c in ev["train/metrics_fetch"])


def test_trace_holds_one_tick_per_step_with_the_sync_inside(traced):
    ev, eng = traced["events"], traced["engine"]
    assert TICK_SPANS <= set(ev)
    st = eng.stats()
    assert len(ev["engine/tick"]) == st["ticks"] >= 3
    ticks = [t[3]["tick"] for t in ev["engine/tick"]]
    assert sorted(ticks) == list(range(min(ticks), min(ticks) + len(ticks)))
    assert len(ev["engine/token_sync"]) == st["decode_steps"]
    for name in ("engine/token_sync", "engine/decode_dispatch",
                 "engine/decode_build", "engine/emit", "engine/admit",
                 "engine/prefill_chunk"):
        assert all(inside(c, ev["engine/tick"]) for c in ev[name]), name
    assert all({"decoding", "prefilling"} <= set(t[3])
               for t in ev["engine/tick"])
    chunk, = ev["engine/prefill_chunk"]
    assert chunk[3]["tokens"] == 4 and chunk[3]["bucket"] == 8
    assert sum(a[3]["admitted"] for a in ev["engine/admit"]) == 1
    assert sum(e[3]["tokens"] for e in ev["engine/emit"]) == \
        len(traced["tokens"]) - 1       # the first comes from the prefill
    # one submit; a lone consumer runs every tick itself and never
    # sleeps through one (tests/test_engine_entry.py has the sleepers)
    assert len(ev["engine/submit"]) == st["submits"] == 1
    assert len(ev.get("stream/wait", ())) == st["stream_waits"] == 0


def test_the_chunk_is_tiled_by_three_spans_and_the_puts_lie_in_the_build(
        traced):
    ev = traced["events"]
    chunk, = ev["engine/prefill_chunk"]
    parts = [ev[f"engine/prefill_{p}"] for p in ("build", "dispatch",
                                                 "sync")]
    assert all(len(p) == 1 and inside(p[0], [chunk]) for p in parts)
    (build,), (dispatch,), (sync,) = parts
    assert chunk[1] <= build[1] and build[2] <= dispatch[1] \
        and dispatch[2] <= sync[1] and sync[2] <= chunk[2]
    covered = sum(p[2] - p[1] for p in (build, dispatch, sync))
    assert covered >= 0.9 * (chunk[2] - chunk[1])
    puts, builds = ev["engine/decode_put"], ev["engine/decode_build"]
    assert len(puts) == len(builds) == traced["engine"].stats()[
        "decode_steps"]
    assert all(inside(p, builds) for p in puts)


@pytest.fixture(scope="module")
def traced_beside_a_decoder(tmp_path_factory):
    """A second session: a prompt of three chunks absorbed while another
    stream decodes, so every chunk shares its tick with a decode step."""
    eng = tiny_engine(prefill_chunk=8)
    warm = eng.submit(list(range(1, 12)), max_new_tokens=2)  # both buckets
    list(eng.tokens_for(warm))
    resident = eng.submit([5, 9, 3], max_new_tokens=12)
    eng.step()
    eng.step()
    eng.reset_stats()
    out = str(tmp_path_factory.mktemp("trace-overlap"))
    start_trace(out)
    try:
        rid = eng.submit(list(range(20, 40)), max_new_tokens=3)
        tokens = list(eng.tokens_for(rid))
    finally:
        jax.profiler.stop_trace()
    with eng._lock:
        eng._rest()             # the resident's step in flight: counted
    stats = eng.stats()
    eng.cancel(resident)
    return {"events": program_spans(out)[1], "stats": stats,
            "tokens": tokens}


def test_a_tick_with_a_decoder_enqueues_the_chunk_then_the_step_and_reads_after(
        traced_beside_a_decoder):
    """chunk (build, enqueue), the next decode step chained behind the
    unread one (build, put, enqueue inside `engine/decode_chain`), the
    unread step's tokens, the emit, the chunk's token: in that order
    inside one `engine/tick`, every span under its old name, the chunk
    marked. The session's first tick found the engine at rest
    (`reset_stats`): its step joins from the host, outside any
    `engine/decode_chain`, and it reads the chunk's token alone."""
    ev, st = (traced_beside_a_decoder[k] for k in ("events", "stats"))
    chunks = sorted(ev["engine/prefill_chunk"], key=lambda c: c[1])
    assert [c[3]["tokens"] for c in chunks] == [8, 8, 4]
    assert all(c[3]["overlapped"] == 1 for c in chunks)
    assert st["chunks_overlapped"] == st["prefill_chunks"] == 3

    def within(name, tick):
        got = [e for e in ev[name] if inside(e, [tick])]
        assert len(got) == 1, (name, len(got))
        return got[0]

    ticks = sorted((t for t in ev["engine/tick"]
                    if any(inside(c, [t]) for c in chunks)),
                   key=lambda t: t[1])
    assert len(ticks) == 3
    for n, tick in enumerate(ticks):
        chunk, build, dispatch, sync, d_build, d_put, d_dispatch = (
            within(f"engine/{n}", tick) for n in (
                "prefill_chunk", "prefill_build", "prefill_dispatch",
                "prefill_sync", "decode_build", "decode_put",
                "decode_dispatch"))
        assert tick[3]["decoding"] == 1 and tick[3]["prefilling"] == 1
        assert inside(d_put, [d_build]) and d_build[2] <= d_dispatch[1]
        assert chunk[2] <= d_build[1]           # the chunk is enqueued first
        # the span holds the build and the enqueue, and ends before a wait
        assert inside(build, [chunk]) and inside(dispatch, [chunk])
        assert build[2] <= dispatch[1]
        assert not inside(sync, [chunk])
        if n == 0:                              # the pipeline's fill
            assert not any(inside(e, [tick]) for name in (
                "decode_chain", "token_sync", "emit")
                for e in ev[f"engine/{name}"])
            assert d_dispatch[2] <= sync[1]
            continue
        chain, token_sync, emit = (within(f"engine/{n}", tick) for n in (
            "decode_chain", "token_sync", "emit"))
        assert inside(d_build, [chain]) and inside(d_dispatch, [chain])
        # the last row of the prompt's last chunk rides the chain too
        assert chain[3]["rows"] == 1 + (n == 2)
        assert chain[2] <= token_sync[1]        # both in flight, then read
        assert token_sync[2] <= emit[1] and emit[2] <= sync[1]
    # the step a tick reads was enqueued before the prompt's last chunk:
    # the two that these ticks read emitted the resident's token alone
    assert [e[3]["tokens"] for e in sorted(ev["engine/emit"],
                                           key=lambda e: e[1])][:2] == [1] * 2
    assert st["steps_chained"] == len(ev["engine/decode_chain"]) == \
        st["decode_steps"] - 1
    assert len(traced_beside_a_decoder["tokens"]) == 3


@pytest.mark.parametrize("with_chunk", [False, True],
                         ids=["decode-only", "with-a-chunk"])
def test_a_tick_makes_one_input_transfer_a_program(with_chunk):
    """`host_puts` over one tick: one for a decode step alone, two where
    a prompt chunk is enqueued behind it (each program's input is one
    packed array: `serve.engine.pack_rows`, `pack_chunk`)."""
    eng = tiny_engine(prefill_chunk=8)
    eng.submit([5, 9, 3], max_new_tokens=20)
    eng.step()
    eng.step()
    if with_chunk:
        eng.submit(list(range(20, 40)), max_new_tokens=3)
    before = eng.stats()
    eng.step()
    after = eng.stats()
    moved = {k: after[k] - before[k] for k in (
        "decode_steps", "prefill_chunks", "chunks_overlapped", "host_puts")}
    assert moved == {"decode_steps": 1, "prefill_chunks": int(with_chunk),
                     "chunks_overlapped": int(with_chunk),
                     "host_puts": 1 + int(with_chunk)}
    eng.reset_stats()
    assert eng.stats()["host_puts"] == 0
    assert "``host_puts``" in InferenceEngine.stats.__doc__


@pytest.mark.parametrize("session", ["traced", "traced_beside_a_decoder"])
def test_the_put_spans_say_one_put_each(session, request):
    """`puts=1` on every `engine/decode_put` and `engine/prefill_build`
    of a trace, alone in the tick and overlapped, and the spans' sum is
    the engine's own count."""
    got = request.getfixturevalue(session)
    ev = got["events"]
    st = got["stats"] if "stats" in got else got["engine"].stats()
    spans = ev["engine/decode_put"] + ev["engine/prefill_build"]
    assert all(e[3]["puts"] == 1 for e in spans)
    assert len(ev["engine/decode_put"]) == st["decode_steps"]
    assert len(ev["engine/prefill_build"]) == st["prefill_chunks"]
    assert sum(e[3]["puts"] for e in spans) == st["host_puts"]
    for tick in ev["engine/tick"]:
        inside_tick = [e for e in spans if inside(e, [tick])]
        assert len(inside_tick) == sum(
            inside(c, [tick]) for name in ("decode_dispatch", "prefill_chunk")
            for c in ev[f"engine/{name}"])


# -- a step and a chunk as one program (`ServingFamily.tick`) -----------------

def fusing_engine(buckets=(8,), **kw):
    """An engine over the short-convolution family, which offers `tick`:
    chunks of 8 (one bucket, so every chunk is of the full one), five
    slots."""
    from ray_tpu.models import shortconv_moe
    cfg = shortconv_moe.ShortConvMoEConfig(
        dtype="float32", attn_impl="jax", sparse_impl="jax")
    params = shortconv_moe.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg, slots=5, max_len=64, block_size=8,
                           prefill_chunk=8, prefill_buckets=buckets,
                           prefix_cache=False, **kw), params


def tokens_of(n, seed):
    return list(np.random.default_rng(seed).integers(0, 512, n))


# prompts of one, two and five chunks of 8, greedy and at a temperature
JOINERS = [(5, 0.0), (13, 0.8), (37, 0.0)]


def mixed_run(eng, trace_to=None):
    """Two streams decode, one of them at a temperature; then the three
    `JOINERS` arrive at once, so that every one of their eight chunks
    shares its tick with a decode step. -> ([(token, logprob)] a stream,
    the two residents' first, stats)."""
    rids = [eng.submit(tokens_of(6, 1), max_new_tokens=30),
            eng.submit(tokens_of(4, 2), max_new_tokens=30, temperature=0.7)]
    for _ in range(3):
        eng.step()
    eng.reset_stats()
    if trace_to:
        start_trace(trace_to)
    try:
        rids += [eng.submit(tokens_of(n, 10 + n), max_new_tokens=5,
                            temperature=t) for n, t in JOINERS]
        eng.run_until_idle()
    finally:
        if trace_to:
            jax.profiler.stop_trace()
    eng.check_invariants()
    return [[(int(t), float(t.logprob)) for t in eng.tokens_for(r)]
            for r in rids], eng.stats()


@pytest.fixture(scope="module")
def fused_and_not(tmp_path_factory):
    """`mixed_run` through an engine whose family offers `tick`, traced,
    and through one built on the same family without it."""
    from ray_tpu.models import shortconv_moe
    out = str(tmp_path_factory.mktemp("trace-fused"))
    fused = mixed_run(fusing_engine()[0], out)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(shortconv_moe, "FAMILY",
                      shortconv_moe.FAMILY._replace(tick=None))
        two_programs = mixed_run(fusing_engine()[0])
    path, events = program_spans(out)
    return {"fused": fused, "two_programs": two_programs, "events": events,
            "path": path}


def test_a_fused_tick_gives_the_two_programs_tokens(fused_and_not):
    """The same tokens in the same order a stream; with one bucket every
    overlapped chunk is fused, the three that end a prompt too; a fused
    step is chained as a step is. A sampled joiner decodes a step later
    than behind a chunk of its own program, so under other keys: its
    first token is the two programs' (the chunk's key is the same) and
    every token's logprob is the plain forward's of its own stream."""
    from ray_tpu.models import shortconv_moe
    (got, st), (want, two) = (fused_and_not[k] for k in (
        "fused", "two_programs"))
    sampled = 2 + [t > 0 for _, t in JOINERS].index(True)
    for n, (mine, theirs) in enumerate(zip(got, want)):
        if n == sampled:
            assert mine[0][0] == theirs[0][0] and len(mine) == len(theirs)
            continue
        assert [t for t, _ in mine] == [t for t, _ in theirs]
        np.testing.assert_allclose([lp for _, lp in mine],
                                   [lp for _, lp in theirs], rtol=0,
                                   atol=1e-5)
    eng, params = fusing_engine()
    prompt = tokens_of(JOINERS[sampled - 2][0], 10 + JOINERS[sampled - 2][0])
    stream = [t for t, _ in got[sampled]]
    logits = shortconv_moe.forward(
        params, jnp.asarray([prompt + stream], jnp.int32), eng.cfg)
    natural = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
    np.testing.assert_allclose(
        [lp for _, lp in got[sampled]],
        [natural[len(prompt) - 1 + i, t] for i, t in enumerate(stream)],
        rtol=0, atol=1e-4)
    ended = len(JOINERS)        # chunks that end a prompt, all overlapped
    assert two["ticks_fused"] == two["tick_traces"] == 0
    assert two["chunks_overlapped"] == two["prefill_chunks"] == 8
    assert st["ticks_fused"] == st["prefill_chunks"] == 8
    assert st["ticks_fused_last"] == ended and two["ticks_fused_last"] == 0
    assert st["chunks_overlapped"] == 0
    assert st["tick_traces"] == 1 and st["retraces_unexpected"] == 0
    for key in ("steps_chained", "decode_steps", "decode_tokens",
                "prefill_tokens", "chain_drains"):
        assert st[key] == two[key], key
    # one transfer a program
    assert st["host_puts"] == st["decode_steps"]
    assert two["host_puts"] == two["decode_steps"] + 8
    # what the programs counted of rows is what two programs count
    for key in ("conv_rows_live", "state_resets", "attention_rows_read",
                "expert_tokens_here"):
        assert st[key] == two[key], key
    assert st["experts_reached"] < two["experts_reached"]


def test_a_fused_tick_waits_for_no_chunk(fused_and_not):
    """`engine/tick_fused` holds the step's build, put and dispatch and
    lies inside the tick's `engine/decode_chain`; its tick opens no
    `engine/prefill_chunk` and waits for no chunk's token
    (`engine/prefill_sync`), not where the chunk ended its prompt
    either: what it reads is the step the tick before left. The span
    says the chunk's live tokens, where it starts and whether it ends
    its prompt."""
    ev, (_, st) = fused_and_not["events"], fused_and_not["fused"]
    fused = sorted(ev["engine/tick_fused"], key=lambda e: e[1])
    assert len(fused) == st["ticks_fused"] == 8
    # the joiners in the order of their admission: 5, 13 and 37 tokens
    assert [(f[3]["tokens"], f[3]["bucket"], f[3]["start"],
             f[3]["ends_prompt"]) for f in fused] == [
        (5, 8, 0, 1), (8, 8, 0, 0), (5, 8, 8, 1), (8, 8, 0, 0),
        (8, 8, 8, 0), (8, 8, 16, 0), (8, 8, 24, 0), (5, 8, 32, 1)]
    assert sum(f[3]["ends_prompt"] for f in fused) == st["ticks_fused_last"]
    ticks = [t for t in ev["engine/tick"] if any(inside(f, [t])
                                                 for f in fused)]
    assert len(ticks) == 8
    # the session's first tick found the engine at rest (`reset_stats`):
    # its program chains behind nothing and the tick reads nothing
    assert [inside(f, ev["engine/decode_chain"]) for f in fused] == [
        False] + [True] * 7
    for name in ("decode_build", "decode_put", "decode_dispatch"):
        assert all(sum(inside(e, [f]) for e in ev[f"engine/{name}"]) == 1
                   for f in fused), name
    for name in ("prefill_chunk", "prefill_build", "prefill_sync"):
        assert not any(inside(e, ticks)
                       for e in ev.get(f"engine/{name}", ())), name
    ticks.sort(key=lambda t: t[1])
    for name in ("token_sync", "emit"):
        assert [sum(inside(e, [t]) for e in ev[f"engine/{name}"])
                for t in ticks] == [0] + [1] * 7, name
    assert len(ev["engine/decode_chain"]) == st["steps_chained"]


def test_the_fused_share_is_a_metric_of_the_benchmark(fused_and_not,
                                                      monkeypatch):
    from benchmarks import run as bench_run
    from benchmarks.harness import spans
    monkeypatch.setattr(spans, "summary",
                        lambda ctx: spans.reduce(fused_and_not["path"]))
    got = bench_run.read_layer_metric("ticks_fused_share",
                                      {"trace": {"modules": {}}})
    ev = fused_and_not["events"]
    assert got == pytest.approx(100.0 * 8 / len(ev["engine/tick"]))


def test_the_fused_program_is_compiled_before_a_load_and_nothing_under_it():
    """A replica warms up a request at a time, which no fused tick ever
    is: the program is compiled from the engine's construction on
    (`_compile_ahead`) and is there when the first load fuses, and the
    programs that take its outputs (the next step, the next chunk) see
    the arrays they have always seen, so nothing is compiled under the
    load: not `_tick`, and no second `_decode` or `_prefill`."""
    from benchmarks.harness.common import CompileWatch
    watch = CompileWatch()
    eng, _ = fusing_engine()
    for n in (5, 13):                       # the warm-up: both programs
        list(eng.tokens_for(eng.submit(tokens_of(n, n), max_new_tokens=4)))
    eng._tick_fn.compiled.result()
    assert "jit(_tick)" in watch.names
    warmed = watch.programs()
    _, st = mixed_run(eng)
    assert st["ticks_fused"] == 8 and st["ticks_fused_last"] == 3
    assert watch.programs() == warmed, watch.names
    assert st["tick_traces"] == st["decode_traces"] == 1


@pytest.mark.parametrize("context", ["precision", "mesh"])
def test_a_program_compiled_ahead_is_its_caller_s_program(context):
    """`_compile_ahead`'s thread traces under the matmul precision and
    the mesh of the thread that asked: the executable is the one that
    thread would compile itself, and an engine built inside a context
    gets its fused program under it, as it gets the programs it traces
    at their first call."""
    from jax.sharding import PartitionSpec as P
    from ray_tpu.serve import engine as engine_mod
    x = jnp.ones((8, 8), jnp.float32)
    if context == "precision":
        fn = jax.jit(lambda a, b: a @ b)
        inside = jax.default_matmul_precision("highest")
    else:
        fn = jax.jit(lambda a, b: jax.lax.with_sharding_constraint(
            a @ b, P("x")))
        inside = jax.set_mesh(jax.make_mesh(
            (2,), ("x",), axis_types=(jax.sharding.AxisType.Auto,)))
    outside = engine_mod._compile_ahead(
        jax.jit(lambda a, b: a @ b), x, x).compiled.result().as_text()
    with inside:
        ahead = engine_mod._compile_ahead(fn, x, x)
        want = fn.lower(x, x).compile().as_text()
        direct = fn(x, x)
        if context == "precision":
            eng, _ = fusing_engine()
    assert ahead.compiled.result().as_text() == want != outside
    np.testing.assert_array_equal(ahead(x, x), direct)
    if context == "precision":
        assert "highest" in want.lower()
        # the router's products ask for the highest themselves; under the
        # context every product of the program does
        at_default = fusing_engine()[0]._tick_fn.compiled.result().as_text()
        assert eng._tick_fn.compiled.result().as_text().lower().count(
            "highest") > at_default.lower().count("highest")


FUSED_METRICS = ("ticks_fused_share", "tick_mixer_ms", "tick_ffn_ms",
                 "tick_head_ms", "tick_compiler_ms", "lfm2_experts_tick_ms",
                 "lfm2_experts_tick_roofline", "lfm2_gqa_decode_tick_ms",
                 "lfm2_gqa_decode_tick_roofline", "lfm2_gqa_chunk_tick_ms",
                 "lfm2_gqa_chunk_tick_roofline", "chunks_fused_share")
# read off the host's spans and counters, not off a program's ops
HOST_METRICS = ("ticks_fused_share", "chunks_fused_share")


@pytest.mark.parametrize("name", FUSED_METRICS)
def test_a_fused_tick_s_metric_is_an_entry_with_a_file(name, fused_and_not,
                                                       monkeypatch):
    """Each metric the fused tick brought: `BENCHMARK.json`'s entry and
    the file beside the readers say the same (the cell's list too), the
    file's reducer is a module beside it, and on a trace that holds no
    `jit__tick` (a family without `tick`, the parent of the PR that
    brought them) the device readers find nothing and do not raise."""
    import importlib
    import json
    from benchmarks import run as bench_run
    from benchmarks.harness import spans
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry} == entry
    assert entry["workloads"] == ["lfm2-8b-a1b.chat-closed192"]
    assert entry["moves"] == "serve_tokens_per_s"
    assert " over " in spec["what"]         # what it divides by what
    assert callable(importlib.import_module(
        f"benchmarks.layer_metrics.{spec['reducer']}").read)
    if name not in HOST_METRICS:
        assert spec["args"].get("module") == "jit__tick"
        monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(
            fused_and_not["path"]))
        assert bench_run.read_layer_metric(name, {
            "trace": {"modules": {"jit__decode": [3, 0.1]}},
            "stats": {"serve": {"decoding_context_tokens": 1.0},
                      "engine": {"kv_bytes_per_token": 1}}}) is None


def test_a_fused_tick_leaves_nothing_unread_but_its_flight():
    """`update_params` and `cancel` in the tick after a fused one find
    the chunk done at its enqueue, its tokens counted, and nothing unread
    but the flight: the first reads it (`chain_drains`), as it reads a
    step; the second, of the prefilling request, leaves it, since no row
    of it is the request's. The stream beside them goes on to the tokens
    of an undisturbed run."""
    eng, params = fusing_engine()
    resident = eng.submit(tokens_of(6, 1), max_new_tokens=12)
    undisturbed = [int(t) for t in eng.tokens_for(resident)]
    resident = eng.submit(tokens_of(6, 1), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    long = eng.submit(tokens_of(37, 47), max_new_tokens=4)
    for fused_so_far in (1, 2):
        before = eng.stats()
        eng.step()
        after = eng.stats()
        assert after["ticks_fused"] == fused_so_far
        assert after["prefill_tokens"] - before["prefill_tokens"] == 8
        assert eng._flight is not None
        slot, = [s for s in eng._slots if s.rid == long]
        assert slot.filled == 8 * fused_so_far and slot.phase == "prefill"
        assert list(eng._flight.rows.values()) == [resident]
        if fused_so_far == 1:
            eng.update_params(params)
            assert eng._flight is None
            assert eng.stats()["chain_drains"] == after["chain_drains"] + 1
        else:
            assert eng.cancel(long)
            assert list(eng._flight.rows.values()) == [resident]
            assert all(s.rid != long for s in eng._slots)
            eng.check_invariants()      # reads the flight
    assert [int(t) for t in eng.tokens_for(resident)] == undisturbed
    assert eng.stats()["ticks_fused"] == 2


def alone(n, **kw):
    """The greedy stream of `tokens_of(n, 10 + n)` with the engine to
    itself: what a joiner's tokens have to be in any company."""
    eng, _ = fusing_engine()
    return [int(t) for t in eng.tokens_for(
        eng.submit(tokens_of(n, 10 + n), **kw))]


def beside_a_resident(eng):
    """A stream that decodes for 40 tokens, two steps in."""
    resident = eng.submit(tokens_of(6, 1), max_new_tokens=40)
    for _ in range(3):
        eng.step()
    return resident


@pytest.mark.parametrize("n, fused, fused_last, overlapped", [
    (16, 2, 1, 0), (13, 2, 1, 0), (11, 1, 0, 1), (5, 1, 1, 0), (3, 0, 0, 1)],
    ids=["two-full", "full-then-5-of-8", "full-then-3-of-4", "5-of-8",
         "3-of-4"])
def test_a_last_chunk_fuses_where_its_bucket_is_the_full_one(
        n, fused, fused_last, overlapped):
    """Buckets of 4 and 8: the fused program has the full bucket's shape
    alone, so a prompt's last chunk rides in it where it pads to 8 and
    goes before the step as a program of its own where it pads to 4;
    either way the stream is the one the request gets alone."""
    eng, _ = fusing_engine(buckets=(4, 8))
    beside_a_resident(eng)
    before = eng.stats()
    rid = eng.submit(tokens_of(n, 10 + n), max_new_tokens=6)
    got = [int(t) for t in eng.tokens_for(rid)]
    after = eng.stats()
    assert [after[k] - before[k] for k in (
        "ticks_fused", "ticks_fused_last", "chunks_overlapped")] == [
        fused, fused_last, overlapped]
    assert after["tick_traces"] == 1 and after["retraces_unexpected"] == 0
    assert got == alone(n, max_new_tokens=6)
    eng.check_invariants()


def fused_last_in_flight(n=5, **kw):
    """An engine one tick after a prompt of `n` tokens ended inside a
    step's program: -> (engine, params, resident's rid, joiner's rid)."""
    eng, params = fusing_engine()
    resident = beside_a_resident(eng)
    rid = eng.submit(tokens_of(n, 10 + n), **kw)
    eng.step()
    ended = eng._flight.ended
    assert ended.rid == rid and eng.stats()["ticks_fused_last"] == 1
    slot = eng._slots[ended.slot]
    # absorbed and counted at the enqueue; no row of this program, no
    # chunk left, no token yet
    assert slot.phase == "prefill" and slot.filled == n
    assert rid not in eng._flight.rows.values()
    assert eng._next_prefilling() is None
    assert not eng._out[rid]
    return eng, params, resident, rid


def _cancel_it(eng, params, rid):
    assert eng.cancel(rid)
    assert all(s.rid != rid for s in eng._slots) and rid not in eng._out
    return None


def _swap(eng, params, rid):
    # the same weights under a new version: the stream goes on as it was
    assert eng.update_params(params) == 1
    assert eng.stats()["chain_drains"] == 1
    return [0] + [1] * 5


def _preempt(eng, params, rid):
    from ray_tpu.util import faults
    faults.install(faults.FaultPlan(seed=1).fail(
        "engine.preempt", at=0, times=1))
    try:
        eng.step()
    finally:
        faults.clear()
    # the newest admission is the victim: its first token is out and the
    # resume absorbs it with the prompt
    assert eng.stats()["preemptions"] == 1
    assert [int(t) for t in eng._out[rid]] == alone(5, max_new_tokens=6)[:1]
    return [0] * 6


def _check(eng, params, rid):
    eng.check_invariants()
    return [0] * 6


@pytest.mark.parametrize("then", [_cancel_it, _swap, _preempt, _check])
def test_whoever_needs_the_engine_at_rest_reads_a_fused_last_chunk_s_token(
        then):
    """A prompt ended inside the step in flight, then `cancel` of its
    request, `update_params`, a forced preemption, `check_invariants`:
    each reads the flight and with it the chunk's token (`_rest`), so
    nothing is left unread, the first token is emitted once, under the
    version of the program that computed it, and both streams are what
    they are undisturbed."""
    eng, params, resident, rid = fused_last_in_flight(max_new_tokens=6)
    versions = then(eng, params, rid)
    if then is not _preempt:    # whose tick went on and left its step
        assert eng._flight is None
    if versions is not None:
        slot, = [s for s in eng._slots if s.rid == rid]
        assert slot.phase == ("prefill" if then is _preempt else "decode")
        got = list(eng.tokens_for(rid))
        assert [int(t) for t in got] == alone(5, max_new_tokens=6)
        assert [t.params_version for t in got] == versions
    eng.run_until_idle()
    want, _ = fusing_engine()
    assert [int(t) for t in eng.tokens_for(resident)] == [
        int(t) for t in want.tokens_for(
            want.submit(tokens_of(6, 1), max_new_tokens=40))]
    assert eng.stats()["ticks_fused_last"] >= 1
    eng.check_invariants()


@pytest.mark.parametrize("ends_by", ["max_new_tokens", "eos_id"])
def test_a_stream_that_ends_with_a_fused_last_chunk_s_token(ends_by):
    """A request of one new token has no row in the step behind its
    chunk's program; one whose first token is its `eos_id` has, and that
    row is thrown away where its step is read, as a row that ended on
    `eos_id` a step late always is. Either stream is its first token."""
    first = alone(5, max_new_tokens=1)
    kw = ({"max_new_tokens": 1} if ends_by == "max_new_tokens"
          else {"max_new_tokens": 6, "eos_id": first[0]})
    eng, _, resident, rid = fused_last_in_flight(**kw)
    slot = eng._flight.ended.slot
    eng.step()
    # the flight that carried the chunk is read: the stream is over
    assert [int(t) for t in eng._out[rid]] == first and rid in eng._done
    assert not eng._slots[slot].active
    assert (slot in eng._flight.rows) == (ends_by == "eos_id")
    before = eng.stats()["decode_tokens"]
    eng.step()
    assert eng.stats()["decode_tokens"] == before + 1   # the resident's
    assert [int(t) for t in eng.tokens_for(rid)] == first
    eng.run_until_idle()
    assert len(list(eng.tokens_for(resident))) == 40
    eng.check_invariants()


def test_a_fused_tick_behind_a_fused_last_chunk_joins_its_row():
    """Two prompts arrive at once: the tick after the one that fused the
    first's only chunk fuses the second's first chunk, so the first's
    row takes its token inside `jit__tick` (`FROM_CHUNK`), in the one
    program the engine has."""
    eng, _ = fusing_engine()
    resident = beside_a_resident(eng)
    a = eng.submit(tokens_of(5, 15), max_new_tokens=6)
    b = eng.submit(tokens_of(13, 23), max_new_tokens=6)
    eng.step()
    assert eng._flight.ended.rid == a
    eng.step()
    st = eng.stats()
    assert st["ticks_fused"] == 2 and st["ticks_fused_last"] == 1
    assert set(eng._flight.rows.values()) == {resident, a}
    assert eng._flight.chained == 2 and eng._flight.ended is None
    eng.step()
    assert eng._flight.ended.rid == b
    assert [int(t) for t in eng.tokens_for(a)] == alone(5, max_new_tokens=6)
    assert [int(t) for t in eng.tokens_for(b)] == alone(13, max_new_tokens=6)
    st = eng.stats()
    assert st["tick_traces"] == st["decode_traces"] == 1
    assert st["chunks_overlapped"] == 0 and st["ticks_fused"] == 3
    eng.run_until_idle()
    eng.check_invariants()


def test_a_family_without_tick_never_fuses(traced_beside_a_decoder):
    ev, st = (traced_beside_a_decoder[k] for k in ("events", "stats"))
    assert "engine/tick_fused" not in ev
    assert st["ticks_fused"] == st["tick_traces"] == 0
    assert st["chunks_overlapped"] == 3


def test_every_tick_says_how_long_after_the_last_it_began(traced):
    """`gap_us` and `carried` on each `engine/tick`: the engine's own
    reading of the time since the previous tick ended is the distance
    between the two events in the trace, and the first tick after
    `reset_stats()` follows nothing."""
    ticks = sorted(traced["events"]["engine/tick"], key=lambda t: t[1])
    assert all({"gap_us", "carried"} <= set(t[3]) for t in ticks)
    assert ticks[0][3]["carried"] == 0 and ticks[0][3]["gap_us"] == 0
    assert len(ticks) >= 3
    for before, after in zip(ticks, ticks[1:]):
        assert after[3]["carried"] == 1     # the stream was still open
        assert after[3]["gap_us"] * 1e3 == pytest.approx(
            after[1] - before[2], abs=0.5e6)
    st = traced["engine"].stats()
    assert st["tick_gaps"] == len(ticks) - 1
    assert st["tick_gap_s"] == pytest.approx(
        sum(t[3]["gap_us"] for t in ticks) / 1e6, abs=1e-5 * len(ticks))


READERS = {"tick_gap_ms": True, "tick_host_ms": True,
           "prefill_host_ms": True, "decode_put_ms": True,
           "idle_in_tick_ms": False, "idle_between_ticks_ms": False}


@pytest.mark.parametrize("name", READERS)
def test_the_tick_s_readers_on_the_same_trace(traced, monkeypatch, name):
    """The benchmark's readers of the new spans and attributes, over the
    CPU trace, against the engine's own totals; with no device plane the
    two idle readers have nothing to read."""
    from benchmarks import run as bench_run
    from benchmarks.harness import spans
    from benchmarks.layer_metrics import tick_events
    monkeypatch.setattr(spans, "summary",
                        lambda ctx: spans.reduce(traced["path"]))
    monkeypatch.setattr(tick_events, "find", lambda ctx: traced["path"])
    got = bench_run.read_layer_metric(name, {"trace": {"modules": {}}})
    if not READERS[name]:
        assert got is None
        return
    st = traced["engine"].stats()
    want = {
        "tick_gap_ms": 1e3 * st["tick_gap_s"] / st["tick_gaps"],
        "tick_host_ms": 1e3 * (st["tick_s"] - st["token_sync_s"]
                               - st["prefill_sync_s"]) / st["ticks"],
        "prefill_host_ms": 1e3 * (st["prefill_build_s"]
                                  + st["prefill_dispatch_s"])
        / st["prefill_chunks"],
        "decode_put_ms": 1e3 * st["decode_put_s"] / st["decode_steps"],
    }[name]
    # a mean from perf_counter beside a median or a mean from the
    # trace's clock, over three or four ticks
    assert 0 < got < 4 * want and want < 4 * got


SPLITS = {      # idle, ticks (start, end, gap, carried) -> inside, between
    "inside-a-tick": ([(12, 18)], [(10, 20, 0, 0)], (6, 0)),
    "between-two-ticks": (
        [(21, 29)], [(10, 20, 0, 0), (30, 40, 10, 1)], (0, 8)),
    # midpoint 21: the midpoint rule gives all 12 to the hand-off
    "straddles-a-tick-s-end": (
        [(15, 27)], [(10, 20, 0, 0), (30, 40, 10, 1)], (5, 7)),
    "before-an-uncarried-tick": (
        [(22, 28)], [(10, 20, 0, 0), (30, 40, 10, 0)], (0, 0)),
    "one-gap-over-two-ticks": (
        [(5, 45)], [(10, 20, 3, 1), (30, 40, 10, 1)], (20, 13)),
    "the-gap-is-the-engine-s-not-the-events": (
        [(20, 30)], [(10, 20, 0, 0), (30, 40, 6, 1)], (0, 6)),
    "many-short-gaps": (
        [(11, 12), (13, 14), (19, 22), (28, 31)],
        [(10, 20, 0, 0), (30, 40, 10, 1)], (4, 4)),
    "no-idle-time": ([], [(10, 20, 0, 0), (30, 40, 10, 1)], (0, 0)),
}


@pytest.mark.parametrize("case", SPLITS)
def test_an_idle_gap_is_split_where_a_tick_ends(case):
    from benchmarks.layer_metrics import tick_events
    idle, ticks, want = SPLITS[case]
    assert tick_events.split(idle, ticks) == want
    # the parts never exceed the whole
    assert sum(want) <= sum(e - s for s, e in idle)


def test_the_benchmarks_reducer_reads_the_same_trace(traced):
    """`benchmarks/harness/spans.py` over a CPU trace: the spans, and no
    kernel, chip or idle time to report."""
    from benchmarks.harness import spans
    s = spans.reduce(traced["path"])
    assert s["chips"] == 0 and s["kernels"] == {} and s["idle_owners"] == {}
    count, total, median = s["spans"]["train/dispatch"]
    assert count == 3 and 0 < median <= total
    assert s["spans"]["engine/tick"][0] == len(
        traced["events"]["engine/tick"])


# -- the engine's totals and the request's step past its edge -----------------------

def test_engine_times_come_from_the_spans():
    eng = tiny_engine()
    rid = eng.submit([5, 9, 3], max_new_tokens=4)
    assert len(list(eng.tokens_for(rid))) == 4
    st, ph = eng.stats(), eng._phases
    assert st["prefill_time_s"] == ph.seconds("engine/prefill_chunk") > 0
    assert st["decode_time_s"] == ph.seconds(
        "engine/decode_dispatch", "engine/token_sync") > 0
    assert st["ticks"] == ph.count("engine/tick") > 0
    assert st["tick_s"] >= st["admit_s"] + st["decode_build_s"] \
        + st["decode_dispatch_s"] + st["token_sync_s"] + st["emit_s"]
    assert st["submit_s"] == ph.seconds("engine/submit") > 0
    assert st["stream_wait_s"] == ph.seconds("stream/wait") == 0.0
    assert st["p50_token_latency_ms"] > 0
    # the chunk's three parts and the build's puts lie inside their spans
    assert st["prefill_time_s"] >= st["prefill_build_s"] \
        + st["prefill_dispatch_s"] + st["prefill_sync_s"] > 0
    assert min(st["prefill_build_s"], st["prefill_dispatch_s"],
               st["prefill_sync_s"]) > 0
    assert st["decode_build_s"] >= st["decode_put_s"] > 0
    # a lone consumer runs every tick itself, each after one that left
    # its stream open, but the first
    assert st["tick_gaps"] == st["ticks"] - 1 > 0
    assert 0 < st["tick_gap_max_s"] <= st["tick_gap_s"] < st["tick_s"]
    assert st["pump_handoffs"] == 0
    eng.reset_stats()
    st = eng.stats()
    assert st["ticks"] == st["submits"] == st["stream_waits"] == 0
    assert st["decode_time_s"] == st["prefill_time_s"] == 0.0
    assert st["deliver_wait_ms_p99"] == 0.0
    new = ("prefill_build_s", "prefill_dispatch_s", "prefill_sync_s",
           "decode_put_s", "tick_gaps", "tick_gap_s", "tick_gap_max_s",
           "pump_handoffs")
    assert [st[k] for k in new] == [0] * len(new)
    assert all(f"``{k}``" in InferenceEngine.stats.__doc__ for k in new)


def test_time_with_nothing_to_do_is_no_gap():
    """A gap counts after a tick that left work behind. The sleep
    between two requests follows a tick that left none, and so does the
    first tick after `reset_stats()`, whatever came before it."""
    eng = tiny_engine()
    for i in range(2):
        rid = eng.submit([5, 9, 3], max_new_tokens=3)
        assert len(list(eng.tokens_for(rid))) == 3
        time.sleep(0.1)
    st = eng.stats()
    assert st["tick_gaps"] == st["ticks"] - 2
    assert st["tick_gap_max_s"] <= st["tick_gap_s"] < 0.1
    rid = eng.submit([5, 9, 3], max_new_tokens=3)
    eng.step()                          # leaves the stream open
    eng.reset_stats()
    time.sleep(0.1)
    assert len(list(eng.tokens_for(rid))) == 3
    st = eng.stats()
    assert st["tick_gaps"] == st["ticks"] - 1 and st["tick_gap_s"] < 0.1


@pytest.mark.parametrize("drain_first", [False, True],
                         ids=["streamed", "finished-before-first-yield"])
def test_first_yield_follows_first_token_and_events_carry_a_tick(
        drain_first):
    """`first_yield` is the instant `tokens_for` hands the first token
    on; other streams' pumps may have finished the request by then."""
    eng = tiny_engine()
    rid = eng.submit([5, 9, 3], max_new_tokens=3)
    if drain_first:
        eng.run_until_idle()
    assert len(list(eng.tokens_for(rid))) == 3
    spans = [s for s in eng._recorder.get_spans()
             if s["attributes"]["rid"] == rid]
    by_name = {s["name"]: s for s in spans}
    assert {"engine.request", "first_token", "first_yield"} <= set(by_name)
    first, yielded = by_name["first_token"], by_name["first_yield"]
    assert first["start_ns"] <= yielded["start_ns"]
    assert yielded["trace_id"] == by_name["engine.request"]["trace_id"]
    assert all(isinstance(s["attributes"]["tick"], int) for s in spans)
    assert 1 <= first["attributes"]["tick"] <= yielded["attributes"]["tick"]
    assert yielded["attributes"]["tick"] <= eng.stats()["ticks"]
    st = eng.stats()
    waited_ms = (yielded["start_ns"] - first["start_ns"]) / 1e6
    assert st["deliver_wait_ms_p50"] == st["deliver_wait_ms_p99"] \
        == pytest.approx(waited_ms, abs=0.5)
    assert not eng._recorder._await_yield
    eng._recorder.check_invariants()


def test_deliver_wait_is_documented_and_cancel_forgets_the_wait():
    doc = InferenceEngine.stats.__doc__
    for key in ("deliver_wait_ms_p50", "deliver_wait_ms_p99",
                "stream_wait_s", "submit_s", "tick_s"):
        assert f"``{key}``" in doc
    eng = tiny_engine()
    rid = eng.submit([5, 9, 3], max_new_tokens=3)
    eng.run_until_idle()                # first token made, never yielded
    assert rid in eng._recorder._await_yield
    gen = eng.tokens_for(rid)
    gen.close()                         # never started: no finally runs
    eng.cancel(rid)
    assert not eng._recorder._await_yield
    assert eng.stats()["deliver_wait_ms_p50"] == 0.0


def test_replica_counts_its_replies():
    from ray_tpu.serve.replica import Replica

    def chunks():
        yield from range(5)

    rep = Replica({"callable": chunks, "deployment_name": "phases-test"})
    sid = rep._register_stream(chunks())
    got, done = rep._next_chunks_sync(sid, 3)
    assert (got, done) == ([0, 1, 2], False)
    got, done = rep._next_chunks_sync(sid, 3)
    assert (got, done) == ([3, 4], True)
    assert rep._next_chunks_sync(sid, 3) == (None, True)
    st = rep.stats()
    assert st["replies"] == 3 and st["reply_s"] > 0
    assert st["reply_tokens"] == 5


@pytest.fixture(scope="module")
def traced_replies(tmp_path_factory):
    """A third session: a replica's replies over a ready stream and over
    one that makes its consumer wait, in call order."""
    from ray_tpu.serve.replica import Replica

    def slow():
        yield 0
        for i in (1, 2):
            time.sleep(0.01)
            yield i

    rep = Replica({"callable": slow, "deployment_name": "phases-replies"})
    out = str(tmp_path_factory.mktemp("trace-replies"))
    start_trace(out)
    try:
        ready = rep._register_stream(iter(range(5)))
        made = rep._register_stream(slow())
        got = [rep._next_chunks_sync(sid, 3)
               for sid in (ready, ready, made, made, made)]
    finally:
        jax.profiler.stop_trace()
    path, events = program_spans(out)
    return {"path": path, "got": got, "stats": rep.stats(),
            "replies": sorted(events["stream/reply"], key=lambda e: e[1])}


def test_a_reply_span_says_what_it_carried_and_how_it_ended(traced_replies):
    assert traced_replies["got"] == [
        ([0, 1, 2], False), ([3, 4], True),     # `max_chunks`; the end
        ([0, 1], False), ([2], False),          # each ends on a wait
        ([], True)]
    assert [(r[3]["tokens"], r[3]["waited"])
            for r in traced_replies["replies"]] == [
        (3, 0), (2, 0), (2, 1), (1, 1), (0, 0)]
    st = traced_replies["stats"]
    assert (st["replies"], st["reply_tokens"]) == (5, 8)


def test_the_reply_span_s_median_is_a_metric_of_the_benchmark(
        traced_replies, monkeypatch):
    """`stream_reply_ms`: a data file over the reducer that was there,
    its entry in `BENCHMARK.json` the file's own fields, its cells those
    that report the metric it moves."""
    import statistics

    from benchmarks import run as bench_run
    from benchmarks.harness import spans

    bench = bench_run.load_json("BENCHMARK.json")
    entry = bench_run.by_name(bench["per_layer"], "stream_reply_ms",
                              "per-layer metric")
    spec = bench_run.load_json("benchmarks", "layer_metrics",
                               "stream_reply_ms.json")
    assert {k: spec[k] for k in entry} == entry
    assert (spec["reducer"], spec["args"]) == (
        "span_median_ms", {"span": "stream/reply"})
    assert (entry["layer"], entry["moves"]) == ("engine host", "tpot_p90_ms")
    assert entry["workloads"] == bench_run.by_name(
        bench["end_to_end"], "tpot_p90_ms", "metric")["workloads"]

    ctx = {"trace": {"busy_s": 0.0}}
    monkeypatch.setattr(spans, "summary",
                        lambda c: spans.reduce(traced_replies["path"]))
    want = statistics.median(e - s for _, s, e, _ in
                             traced_replies["replies"]) * 1e-6
    assert bench_run.read_layer_metric("stream_reply_ms", ctx) == \
        pytest.approx(want)
    # a trace of a program without the span, and an untraced run
    monkeypatch.setattr(spans, "summary", lambda c: {"spans": {}})
    assert bench_run.read_layer_metric("stream_reply_ms", ctx) is None
    monkeypatch.setattr(spans, "summary", lambda c: None)
    assert bench_run.read_layer_metric("stream_reply_ms", ctx) is None


# -- the window's counters in the trace (`engine/counters`) -------------------

COUNTERS = "engine/counters"
MADE_UP = "made_up_rows"        # a counter a family adds tomorrow


@pytest.fixture(scope="module")
def traced_counters(tmp_path_factory):
    """A fourth session, over the dense engine and one whose family has
    `counts` (with one more name than its `COUNTS`, as a later PR would
    add it): each decodes and reads `stats()`, decodes on and reads it
    again, then resets and reads it a third time; the second decodes
    once more, so the session's last snapshot holds counts.
    {family: [the returned dicts]}, and the events in call order."""
    from ray_tpu.models import shortconv_moe
    family = shortconv_moe.FAMILY
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(shortconv_moe, "FAMILY", family._replace(
            counts=lambda cfg, totals: {**family.counts(cfg, totals),
                                        MADE_UP: 7}))
        engines = {"dense": tiny_engine(), "counting": fusing_engine()[0]}
    for eng in engines.values():
        list(eng.tokens_for(eng.submit([5, 9, 3], max_new_tokens=2)))
        eng.reset_stats()
    out = str(tmp_path_factory.mktemp("trace-counters"))
    returned = {}
    start_trace(out)
    try:
        for name, eng in engines.items():
            got = returned[name] = []
            for prompt in ([7, 1, 2, 4], [3, 3, 8]):
                list(eng.tokens_for(eng.submit(prompt, max_new_tokens=4)))
                got.append(eng.stats())
            eng.reset_stats()
            got.append(eng.stats())
        list(eng.tokens_for(eng.submit(tokens_of(11, 3), max_new_tokens=4)))
        got.append(eng.stats())
    finally:
        jax.profiler.stop_trace()
    path, events = program_spans(out)
    events = sorted(events[COUNTERS], key=lambda e: e[1])
    assert len(events) == 3 + 4
    return {"path": path, "returned": returned,
            "events": {"dense": events[:3], "counting": events[3:]}}


@pytest.mark.parametrize("family", ["dense", "counting"])
def test_stats_under_a_session_writes_the_dict_s_numbers(traced_counters,
                                                         family):
    """One `engine/counters` event a call, every int and float entry of
    the returned dict on it at the returned value and nothing else; what
    a family counts comes by the same rule, a name no list knows among
    it."""
    from ray_tpu.models import shortconv_moe
    returned, events = (traced_counters[k][family]
                        for k in ("returned", "events"))
    assert len(returned) == len(events)
    for st, ev in zip(returned, events):
        attrs = dict(ev[3])
        assert attrs == {k: v for k, v in st.items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)}
        assert {type(attrs[k]) for k in attrs} == {int, float}
        assert not {"role", "spec", "per_class", "prefix_cache"} & set(attrs)
        counted = set(shortconv_moe.COUNTS) | {
            "expert_load_max_over_mean", MADE_UP}
        assert counted & set(attrs) == (
            counted if family == "counting" else set())
    mid = events[1][3]
    # a prompt's first token comes from its prefill
    assert mid["decode_tokens"] == 6 and mid["prefill_tokens"] == 7
    if family == "counting":
        assert mid[MADE_UP] == 7 and mid["expert_tokens_routed"] > 0


@pytest.mark.parametrize("family", ["dense", "counting"])
def test_counters_after_a_reset_are_zero(traced_counters, family):
    _, before, after = (e[3] for e in traced_counters["events"][family][:3])
    for key in ("decode_tokens", "prefill_tokens", "ticks", "host_puts",
                "decode_steps", "submits", "tick_s"):
        assert before[key] > 0 and after[key] == 0, key
    if family == "counting":
        assert after["expert_tokens_routed"] == after["conv_rows_live"] == 0
        assert after["expert_load_max_over_mean"] == 0.0
    # not reset: identity, not rate
    assert after["decode_traces"] == before["decode_traces"] == 1


def test_stats_with_no_session_builds_nothing_for_the_span(monkeypatch):
    """No profiler session: `stats()` is the dict it was, and the span's
    attributes are never gathered: `set` is not reached."""
    eng = tiny_engine()
    list(eng.tokens_for(eng.submit([5, 9, 3], max_new_tokens=3)))
    want = eng.stats()

    def set_(self, **attrs):
        raise AssertionError(f"attributes built with no session: {attrs}")

    monkeypatch.setattr(telemetry._phase_class(), "set", set_)
    assert not telemetry._phase_class().is_enabled()
    assert eng.stats() == want
    assert eng._phases.count(COUNTERS) == 2


def test_the_counters_reader_takes_the_last_snapshot(traced_counters, traced,
                                                     monkeypatch):
    """`span_counter_ratio.read`: the last event over an earlier one, a
    list summed, and nothing for a missing name, a zero divisor, a trace
    without the span or no trace."""
    from benchmarks.layer_metrics import span_counter_ratio, tick_events
    ctx = {"trace": {"modules": {}}}
    monkeypatch.setattr(tick_events, "find",
                        lambda c: traced_counters["path"])
    dense, counting = (traced_counters["returned"][k]
                       for k in ("dense", "counting"))
    last = counting[-1]
    read = span_counter_ratio.read
    assert read(ctx, "slots") == last["slots"] == 5 != dense[-1]["slots"]
    assert read(ctx, "blocks_free", "cache_blocks") == \
        last["blocks_free"] / last["cache_blocks"]
    assert read(ctx, ["slots", "cache_blocks"], ["block_size", "slots"]) \
        == (5 + last["cache_blocks"]) / (last["block_size"] + 5)
    assert read(ctx, "no_such_counter") is None
    assert read(ctx, ["slots", "no_such_counter"]) is None
    assert read(ctx, "slots", "no_such_counter") is None
    assert last["cancelled"] == 0
    assert read(ctx, "slots", "cancelled") is None
    assert read(ctx, "decode_tokens") == 3  # since the reset, not the start
    monkeypatch.setattr(tick_events, "find", lambda c: traced["path"])
    assert read(ctx, "slots") is None       # a program before the span
    monkeypatch.setattr(tick_events, "find", lambda c: None)
    assert read(ctx, "slots") is None


COUNTER_METRICS = (
    "state_fold_share", "kv_rows_per_state_read",
    "serve_expert_load_max_over_mean", "expert_pairs_here_share",
    "expert_tiles_per_expert", "identity_choice_share")


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_a_counter_s_metric_reads_what_its_cells_families_count(
        name, traced_counters, monkeypatch):
    """Each metric over the counters: `BENCHMARK.json`'s entry and the
    file beside the reader say the same, every listed cell reports what
    it moves, and every attribute the file's `args` name is the engine's
    own tally or in the `counts` of the family that the cell's
    configuration runs."""
    from benchmarks import run as bench_run
    from benchmarks.harness import common
    from benchmarks.layer_metrics import tick_events
    bench = bench_run.load_json("BENCHMARK.json")
    entry = bench_run.by_name(bench["per_layer"], name, "per-layer metric")
    spec = bench_run.load_json("benchmarks", "layer_metrics", f"{name}.json")
    assert {k: spec[k] for k in entry} == entry
    of, per = ([side] if isinstance(side, str) else side
               for side in (spec["args"]["of"], spec["args"].get("per", [])))
    reads = {*of, *per}
    engine_s = set(re.findall(r"``([a-z0-9_]+)``",
                              InferenceEngine.stats.__doc__))
    reporting = bench_run.by_name(bench["end_to_end"], entry["moves"],
                                  "metric")["workloads"]
    for cell_name in entry["workloads"]:
        assert cell_name in reporting
        _, cell, config, _ = bench_run.load_cell(cell_name)
        cfg = common.model_config(config, "serve")
        counted = set(cfg.family.counts(cfg, None))
        assert counted >= set(sys.modules[type(cfg).__module__].COUNTS)
        assert reads <= counted | engine_s, (cell_name, reads - counted)
        assert reads & counted, cell_name
    # on this trace: the last snapshot of a family that counts its
    # experts' work and neither folds nor identity choices
    monkeypatch.setattr(tick_events, "find",
                        lambda c: traced_counters["path"])
    last = traced_counters["returned"]["counting"][-1]
    want = None
    if reads <= set(last):
        want = sum(last[n] for n in of) / (
            sum(last[n] for n in per) if per else 1)
    assert (want is not None) == ("expert" in name)
    assert bench_run.read_layer_metric(
        name, {"trace": {"modules": {}}}) == want
