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
