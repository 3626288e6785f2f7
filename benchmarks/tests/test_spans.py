"""`benchmarks/harness/spans.py` and the readers of the named metrics,
against a recorded v5e trace of a program that names its kernels and
opens its spans.

`data/v5e_named_train.xplane.pb`: three fused dispatches (unroll 2) of a
2-layer d_model-256 model, T=512, B=2, flash + fused loss,
`remat_policy=dots`, through the prefetcher; recorded on a v5e in PR 24
the way the benchmark takes its traces (Python tracer on, `bench/window`
around `TrainLoop.run`).
"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import spans, trace
from benchmarks.harness.common import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
NAMED = os.path.join(HERE, "data", "v5e_named_train.xplane.pb")
UNNAMED = os.path.join(HERE, "data", "v5e_small_train.xplane.pb")
DISPATCHES, UNROLL, LAYERS = 3, 2, 2
KERNELS = {"flash_fwd", "flash_dq", "flash_dkv",
           "xent_fwd", "xent_dx", "xent_de"}
TRAIN_SPANS = {"train/next_batch", "train/host_batch", "train/place",
               "train/dispatch", "train/metrics", "train/metrics_fetch"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = ("flash_fwd_ms", "flash_bwd_ms", "fused_xent_ms",
       "flash_fwd_runs_per_layer", "host_batch_share",
       "dispatch_enqueue_ms", "idle_owned_share")


@pytest.fixture(scope="module")
def named():
    return spans.reduce(NAMED)


@pytest.fixture(scope="module")
def by_shape():
    return trace.reduce(NAMED)


def ctx_for(path, monkeypatch):
    """What `run.py` hands a reader after a traced run whose trace is
    the recorded one."""
    monkeypatch.setattr(spans, "summary",
                        lambda ctx: spans.reduce(path))
    return {"trace": trace.reduce(path), "stats": {},
            "cell": {"chips": 1}, "traffic": {"unroll": UNROLL}}


def test_every_named_kernel_is_found(named):
    assert set(named["kernels"]) == KERNELS
    steps = DISPATCHES * UNROLL
    assert named["kernels"]["flash_dq"][0] == steps * LAYERS
    assert named["kernels"]["flash_dkv"][0] == steps * LAYERS
    for name in ("xent_fwd", "xent_dx", "xent_de"):
        assert named["kernels"][name][0] == steps
    assert all(s > 0 for _, s in named["kernels"].values())


def test_the_forward_kernel_runs_twice_a_layer(named):
    """`remat_policy=dots` does not save a kernel's output: the first
    forward and the rematerialised one both go by `flash_fwd`."""
    assert named["kernels"]["flash_fwd"][0] == \
        2 * named["kernels"]["flash_dq"][0]


def test_named_seconds_agree_with_the_shape_matched_ones(named, by_shape):
    """`flash_roofline` finds the same calls by their shapes: one trace,
    the same self times, unless two kernels share a shape."""
    dims = r"bf16\[4,512,128\]"
    calls, seconds = trace.op_seconds(
        by_shape, rf"/pallas [^<]*<- {dims},{dims},{dims}(,|$)")
    flash = ("flash_fwd", "flash_dq", "flash_dkv")
    assert calls == sum(named["kernels"][k][0] for k in flash)
    assert seconds == pytest.approx(
        sum(named["kernels"][k][1] for k in flash), rel=1e-6)
    every = sum(s for _, s in named["kernels"].values())
    _, pallas = trace.op_seconds(by_shape, r"/pallas ")
    assert every == pytest.approx(pallas, rel=1e-6)


def test_every_train_span_is_found(named, by_shape):
    assert TRAIN_SPANS <= set(named["spans"])
    count, total, median = named["spans"]["train/dispatch"]
    assert count == DISPATCHES and 0 < median <= total
    # `num_steps` ended the run after the last dispatch: no further `next`
    assert named["spans"]["train/next_batch"][0] == DISPATCHES
    assert named["spans"]["train/place"][0] == DISPATCHES
    assert named["spans"]["train/metrics_fetch"][0] >= 1
    assert named["window_s"] == pytest.approx(by_shape["window_s"])
    assert all(t <= named["window_s"] for _, t, _ in
               named["spans"].values())


def test_idle_owners_sum_to_the_idle_time(named, by_shape):
    idle = by_shape["window_s"] - by_shape["busy_s"]
    assert named["idle_s"] == pytest.approx(idle, rel=1e-6)
    assert sum(named["idle_owners"].values()) == \
        pytest.approx(named["idle_s"])
    owners = set(named["idle_owners"]) - {spans.NO_SPAN, spans.SHORT}
    assert owners and owners <= set(named["spans"])


def test_readers_on_the_named_trace(monkeypatch):
    ctx = ctx_for(NAMED, monkeypatch)
    got = {n: bench_run.read_layer_metric(n, ctx) for n in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["flash_fwd_runs_per_layer"] == 2.0
    step_ms = bench_run.read_layer_metric("train_step_device_ms", ctx)
    kernels_ms = (got["flash_fwd_ms"] + got["flash_bwd_ms"]
                  + got["fused_xent_ms"])
    assert 0 < kernels_ms < step_ms
    assert 0 <= got["idle_owned_share"] <= 100
    assert 0 < got["host_batch_share"] < 100


def test_flash_roofline_by_name_reads_what_the_shapes_read(monkeypatch):
    """`flash_roofline` takes its seconds by kernel name and its passes
    from the dispatch's runs x steps x layers; on a program whose
    operands still have the shapes PR 23's reader matched, both give the
    same number."""
    from benchmarks.harness import arith
    ctx = ctx_for(NAMED, monkeypatch)
    ctx.update(traffic={"unroll": UNROLL, "batch": 2, "seq_len": 512},
               widths={"n_heads": 2, "head_dim": 128, "n_layers": LAYERS},
               arith=arith, peaks={"flops_per_s": 197e12})
    dims = r"bf16\[4,512,128\]"
    _, seconds = trace.op_seconds(
        ctx["trace"], rf"/pallas [^<]*<- {dims},{dims},{dims}(,|$)")
    dq_calls, _ = trace.op_seconds(
        ctx["trace"], rf"/pallas {dims} <- ({dims},){{4}}")
    by_shape = 100.0 * dq_calls * arith.flash_attention_flops(
        2, 512, 2, 128, layers=1) / 197e12 / seconds
    got = bench_run.read_layer_metric("flash_roofline", ctx)
    assert got == pytest.approx(by_shape, rel=1e-9) and 0 < got < 100


def test_kernel_ms_sums_whichever_of_its_kernels_ran(monkeypatch):
    """A fused dX + dE kernel would keep one of the names: the metric is
    what ran, and nothing only where none of the names did."""
    ctx = ctx_for(NAMED, monkeypatch)
    from benchmarks.layer_metrics import kernel_ms
    three = kernel_ms.read(ctx, ["xent_fwd", "xent_dx", "xent_de"])
    two = kernel_ms.read(ctx, ["xent_fwd", "xent_dx", "xent_dx_de_fused"])
    assert 0 < two < three
    assert kernel_ms.read(ctx, ["xent_dx_de_fused"]) is None
    assert kernel_ms.read(ctx, ["xent_fwd"], steps=1) == pytest.approx(
        UNROLL * kernel_ms.read(ctx, ["xent_fwd"]))


def test_idle_gaps_are_named_by_the_program_s_spans(monkeypatch):
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(NAMED))
    gaps = spans.idle_gaps({"trace": True})
    assert gaps and len(gaps) <= 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert {g[0] for g in gaps} <= set(TRAIN_SPANS) | {spans.NO_SPAN,
                                                       spans.SHORT}


# -- a trace recorded inside a serving replica -------------------------------

SERVE = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
SERVE_LAYERS = 2
ENGINE_SPANS = {"engine/tick", "engine/admit", "engine/prefill_chunk",
                "engine/decode_build", "engine/decode_dispatch",
                "engine/token_sync", "engine/emit", "stream/wait",
                "stream/reply"}


def test_serving_readers_on_a_trace_recorded_inside_a_replica(monkeypatch):
    """`data/v5e_serve.xplane.pb`: `benchmarks/tools/record_trace.py
    --workload olmo-1b.chat-closed64` on a v5e in PR 30: the cell's driver
    at the size of the `tiny` blocks (2 layers, d_model 64, 4 slots, 8
    closed-loop clients), 0.08 s traced inside the replica's process,
    Python tracer off. Every serving metric read from a trace finds its
    spans, programs and kernels there."""
    from benchmarks.harness import arith, peaks
    named = spans.reduce(SERVE)
    assert ENGINE_SPANS <= set(named["spans"])
    assert {"paged_decode", "paged_mq"} <= set(named["kernels"])
    ctx = ctx_for(SERVE, monkeypatch)
    modules = ctx["trace"]["modules"]
    assert {"jit__decode", "jit__prefill"} <= set(modules)
    # one kernel call a layer a decode step (steps cut by the window's
    # edges are in the kernels' count and not in the modules')
    steps = modules["jit__decode"][0]
    assert 0 <= named["kernels"]["paged_decode"][0] \
        - SERVE_LAYERS * steps <= 2 * SERVE_LAYERS
    ctx.update(stats={"serve": {"decoding_context_tokens": 120.0},
                      "engine": {"kv_bytes_per_token": 1024.0}},
               arith=arith, peaks=peaks.peaks_for("TPU v5 lite"))
    by_trace = [m["name"] for m in BENCH["per_layer"]
                if "olmo-1b.chat-closed64" in m.get("workloads", ())
                and m["source"] == "device_trace"]
    got = {n: bench_run.read_layer_metric(n, ctx) for n in by_trace}
    assert len(got) >= 9 and all(v is not None for v in got.values()), got
    assert 0 < got["paged_decode_ms"] < got["decode_step_device_ms"] \
        < got["engine_tick_ms"]
    assert got["prefill_chunk_device_ms"] > 0
    for share in ("tick_host_share", "prefill_tick_share",
                  "serve_idle_owned_share", "paged_decode_roofline"):
        assert 0 < got[share] <= 100, (share, got[share])
    assert got["stream_wait_ms"] > 0
    # the bytes are the benchmark's own count
    step_s = named["kernels"]["paged_decode"][1] / steps
    assert got["paged_decode_roofline"] == pytest.approx(
        100 * 120.0 * 1024.0 / 819e9 / step_s)
    gaps = spans.idle_gaps(ctx)
    assert gaps and {g[0] for g in gaps} <= ENGINE_SPANS | {
        "engine/submit", spans.NO_SPAN, spans.SHORT}
    # a training trace has none of it: every serving reader returns nothing
    ctx = ctx_for(NAMED, monkeypatch)
    ctx.update(stats={}, arith=arith, peaks={"hbm_bytes_per_s": 819e9})
    left = {n: bench_run.read_layer_metric(n, ctx) for n in by_trace}
    assert left == dict.fromkeys(by_trace) or set(
        n for n, v in left.items() if v is not None) <= {
            "serve_idle_owned_share"}


def test_readers_return_nothing_for_a_program_without_names(monkeypatch):
    """The trace PR 23 recorded: kernels named after their scopes, no
    program span. Every new reader leaves its metric out; none raises."""
    s = spans.reduce(UNNAMED)
    assert s["spans"] == {} and not KERNELS & set(s["kernels"])
    ctx = ctx_for(UNNAMED, monkeypatch)
    assert {n: bench_run.read_layer_metric(n, ctx) for n in NEW} == \
        dict.fromkeys(NEW)


def test_no_trace_no_metric():
    """An untraced run, or a trace kept elsewhere (the CPU rehearsal's
    scratch directory): nothing found, nothing printed."""
    assert spans.summary({"trace": None}) is None
    assert spans.summary({"trace": {"modules": {}}}) is None
    ctx = {"trace": None, "stats": {}, "cell": {"chips": 1},
           "traffic": {"unroll": UNROLL}}
    assert all(bench_run.read_layer_metric(n, ctx) is None for n in NEW)


def test_new_metrics_are_entries_with_files():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        # read from the profiler's trace, so left out of a CPU run
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
