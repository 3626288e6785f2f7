"""The readers of the serving tick's host time (PR 36): `tick_gap_ms`,
`idle_between_ticks_ms`, `idle_in_tick_ms` (`layer_metrics/tick_events.py`:
the `gap_us` and `carried` attributes of `engine/tick`, chip 0's idle time
split by overlap), `tick_host_ms`, `prefill_host_ms`
(`layer_metrics/span_ms_per.py`) and `decode_put_ms`.

`data/v5e_serve_ticks.xplane.pb`: `benchmarks/tools/record_trace.py
--workload olmo-1b.chat-closed64` on a v5e in PR 36: the cell's driver at
the size of the `tiny` blocks (2 layers, d_model 64, 4 slots, 8
closed-loop clients), 0.08 s traced inside the replica's process, Python
tracer off, of a program that tiles `engine/prefill_chunk`, opens
`engine/decode_put` and writes the gap before each tick on it. The four
traces recorded before it hold none of that.
"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import spans, trace
from benchmarks.harness.common import ROOT
from benchmarks.layer_metrics import tick_events

HERE = os.path.dirname(os.path.abspath(__file__))
TICKS = os.path.join(HERE, "data", "v5e_serve_ticks.xplane.pb")
BEFORE = ["v5e_serve.xplane.pb", "v5e_latent_serve.xplane.pb",
          "v5e_named_train.xplane.pb", "v5e_small_train.xplane.pb"]
SERVING = ["olmo-1b.chat-steady", "olmo-1b.chat-closed64",
           "glm-5.2.docqa-closed24", "brumby-14b.docgen-closed24"]
NEW = {"tick_gap_ms": "engine host", "idle_between_ticks_ms": "device",
       "idle_in_tick_ms": "device", "tick_host_ms": "engine host",
       "prefill_host_ms": "engine host", "decode_put_ms": "engine host"}
# read from the recorded trace (a v5e, PR 36); the readers are arithmetic
# over one file, so they give these to the digit
RECORDED = {"tick_gap_ms": 0.281, "idle_between_ticks_ms": 1.037125,
            "idle_in_tick_ms": 9.83983025, "tick_host_ms": 6.65504925,
            "prefill_host_ms": 3.8096595, "decode_put_ms": 2.1108345}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def ctx_for(path, monkeypatch):
    """What `run.py` hands a reader after a traced run whose trace is
    the recorded one."""
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(path))
    monkeypatch.setattr(tick_events, "find", lambda ctx: path)
    return {"trace": trace.reduce(path), "stats": {}, "cell": {"chips": 1}}


@pytest.mark.parametrize("recorded", BEFORE)
def test_a_program_without_the_spans_gives_nothing(recorded, monkeypatch):
    """The parent's side of a comparison: ticks without `gap_us`, a chunk
    in one span, no `engine/decode_put`, or no engine at all. Every new
    reader leaves its metric out; none raises."""
    ctx = ctx_for(os.path.join(HERE, "data", recorded), monkeypatch)
    assert {n: bench_run.read_layer_metric(n, ctx) for n in NEW} == \
        dict.fromkeys(NEW)


def test_no_trace_no_metric():
    assert tick_events.find({"trace": None}) is None
    assert tick_events.find({"trace": {"modules": {}}}) is None    # kept elsewhere
    ctx = {"trace": None, "stats": {}, "cell": {"chips": 1}}
    assert all(bench_run.read_layer_metric(n, ctx) is None for n in NEW)


def test_the_six_metrics_are_entries_with_files():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in NEW.items():
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "ms", "lower", "device_trace", layer, "tpot_p90_ms")
        assert sorted(entry["workloads"]) == sorted(SERVING)


@pytest.mark.skipif(not os.path.exists(TICKS),
                    reason="no trace of the new spans was recorded on a chip")
def test_the_readers_on_a_trace_recorded_inside_a_replica(monkeypatch):
    ctx = ctx_for(TICKS, monkeypatch)
    got = {n: bench_run.read_layer_metric(n, ctx) for n in NEW}
    assert got == pytest.approx(RECORDED, rel=1e-9), got
    named, ticks = spans.reduce(TICKS), tick_events.reduce(TICKS)
    sp = named["spans"]
    # the same window and the same ticks as `spans.reduce`
    assert ticks["ticks"] == sp["engine/tick"][0] == 8
    assert len(ticks["gaps_s"]) == 8        # every one followed a tick
    assert ticks["chips"] == named["chips"] == 1
    assert ticks["idle_s"] == pytest.approx(named["idle_s"], rel=1e-9)
    # the two parts are parts: what is left lies outside every tick and
    # every carried gap (before an uncarried tick, at the window's edges)
    both = ticks["idle_in_tick_s"] + ticks["idle_between_ticks_s"]
    assert 0 < ticks["idle_between_ticks_s"] and 0 < ticks["idle_in_tick_s"]
    assert 0.9 * ticks["idle_s"] <= both <= ticks["idle_s"] * (1 + 1e-9)
    # three spans tile the chunk; the host's part is what is not the wait
    chunk = sp["engine/prefill_chunk"]
    parts = [sp[f"engine/prefill_{p}"] for p in ("build", "dispatch", "sync")]
    assert all(p[0] == chunk[0] for p in parts)
    assert 0.9 * chunk[1] <= sum(p[1] for p in parts) <= chunk[1]
    assert got["prefill_host_ms"] == pytest.approx(
        1e3 * (parts[0][1] + parts[1][1]) / chunk[0])
    assert sp["engine/decode_put"][0] == sp["engine/decode_build"][0]
    assert got["decode_put_ms"] < 1e3 * sp["engine/decode_build"][2]
    # the tick less its two waits for the device
    assert got["tick_host_ms"] == pytest.approx(
        1e3 * (sp["engine/tick"][1] - sp["engine/token_sync"][1]
               - parts[2][1]) / sp["engine/tick"][0])
    assert 0 < got["tick_host_ms"] < 1e3 * sp["engine/tick"][1] / 8


@pytest.mark.skipif(not os.path.exists(TICKS),
                    reason="no trace of the new spans was recorded on a chip")
def test_every_serving_trace_metric_reads_the_new_trace(monkeypatch):
    """What `test_spans.py` asks of PR 30's trace, of this one: every
    metric read from a trace that lists `olmo-1b.chat-closed64` finds its
    spans, programs and kernels (PR 30's trace has not the six new ones'
    spans, and `test_spans.py` is not this PR's to edit: PERF.md, 7)."""
    from benchmarks.harness import arith, peaks
    ctx = ctx_for(TICKS, monkeypatch)
    ctx.update(stats={"serve": {"decoding_context_tokens": 120.0},
                      "engine": {"kv_bytes_per_token": 1024.0}},
               arith=arith, peaks=peaks.peaks_for("TPU v5 lite"))
    by_trace = [m["name"] for m in BENCH["per_layer"]
                if "olmo-1b.chat-closed64" in m.get("workloads", ())
                and m["source"] == "device_trace"]
    got = {n: bench_run.read_layer_metric(n, ctx) for n in by_trace}
    # at the tiny size (2 heads of 32) `paged_decode` has no kernel plan
    # since PR 33 and takes the JAX path: its two metrics find nothing
    silent = {n for n, v in got.items() if v is None}
    assert silent == {"paged_decode_ms", "paged_decode_roofline"}
    assert set(NEW) <= set(got) - silent
    assert all(v > 0 for n, v in got.items() if n not in silent), got
