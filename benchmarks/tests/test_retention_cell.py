"""CPU checks of what `brumby-14b.docgen-closed24` brought to the
benchmark: the configuration's file against the published keys, its own
arithmetic, its control at the tiny size, the mix, and the two roofline
readers on made-up traces' numbers. (The cell's rehearsal is
`test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_retention.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import peaks, retention_arith, spans, trace, traffic
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "brumby-14b.docgen-closed24"
NEW = ("retention_step_ms", "retention_chunk_ms", "retention_step_roofline",
       "retention_chunk_roofline")
# the catalog's `config` for Brumby-14B-Base, every number under its key
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def config():
    return load("benchmarks", "configs", "brumby-14b.json")


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 8
    entry = [e for e in BENCH["configs"] if e["name"] == "brumby-14b"][0]
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/brumby-14b.json"
    assert "five pipeline stages of eight" in c["deployment"]
    assert "embedding and the head" in c["deployment"]
    assert all(isinstance(a, str) and a for a in c["assumed"])
    assert any(a.startswith("gate:") for a in c["assumed"])
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (16, 16384)
    assert serve["engine_kwargs"] == {
        "prefill_chunk": 512, "prefill_buckets": [128, 512],
        "prefix_cache": False}


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b", "docgen-closed24", 1)
    mix = load("benchmarks", "traffic", "docgen-closed24.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 24)
    assert mix["prompt_tokens"] == {"median": 4096, "sigma": 0.6,
                                    "min": 1024, "max": 14336}
    assert mix["output_tokens"] == {"median": 384, "sigma": 0.6,
                                    "min": 96, "max": 1536}
    assert (mix["length_block"], mix["order_seed"], mix["ramp_requests"],
            mix["check_requests"]) == (24, 0, 40, 6)
    gen = traffic.serve_requests(mix, 2**31 + 7, 151936)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    assert max(lengths) <= config()["program"]["serve"]["max_len"]
    assert min(len(r["prompt"]) for r in block) >= 1024
    assert max(int(r["prompt"].max()) for r in block) > 140000
    for name in ("serve_tokens_per_s", "tpot_p90_ms"):
        entry = [m for m in BENCH["end_to_end"] if m["name"] == name][0]
        assert entry["workloads"][-1] == CELL


def test_parameters_and_bytes_from_the_file_s_own_keys():
    c = config()
    w = retention_arith.widths(c)
    assert (w["head_dim"], w["feature_dim"], w["n_kv_heads"]) == (
        128, 8256, 8)
    assert retention_arith.layer_parameters(w) == 330352904
    assert retention_arith.parameters(w) == c["parameters_as_run"] \
        == 4198652992
    # 8 layers x 8 heads x (8256 x 128 + 8256) x 4 B = 272.6 MB
    assert retention_arith.state_bytes(w) == 8 * 8 * 8256 * 129 * 4
    assert round(retention_arith.state_bytes(w) / 1e6, 1) == 272.6
    assert retention_arith.state_read_bytes(w, 13.5) \
        == 13.5 * retention_arith.state_bytes(w)
    # layers and head, bfloat16: 6.84 GB a decode step
    assert round(retention_arith.step_weight_bytes(w) / 1e9, 2) == 6.84
    # 2 x (5 + 1) x 8256 x 129 a token a key-value head a layer: 0.10 GFLOP
    # a token a layer
    per_layer = retention_arith.chunk_required_ops(w, 1) / 8
    assert per_layer == 8 * 2 * 6 * 8256 * 129
    assert round(per_layer / 1e9, 2) == 0.10
    assert retention_arith.chunk_required_ops(w, 512) \
        == 512 * retention_arith.chunk_required_ops(w, 1)


def test_the_program_s_pool_is_the_arithmetic_s_state_in_whole_tiles():
    """The state as the program stores it (D = 9216, whole lane tiles)
    against the arithmetic's exact D = 8256: 304.3 MB a sequence against
    272.6, and a count never takes the stored one."""
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import retention
    c = config()
    cfg = common.model_config(c, "serve")
    pool = jax.eval_shape(lambda: retention.init_pool(cfg, 17, 16))
    stored = sum(a.size * a.dtype.itemsize
                 for a in jax.tree.leaves(pool)) / 17
    assert round(stored / 1e6, 1) == 304.3
    exact = retention_arith.state_bytes(retention_arith.widths(c))
    assert stored / exact == 9216 / 8256


def test_the_control_is_not_correct(tmp_path):
    """The cell's control at the tiny size: every state rounded to
    bfloat16 at every write (`state_round`, the program's test-only
    field). Every request still gets its tokens; the logprobs are what
    fails."""
    assert config()["control"]["program"]["model"] == {
        "state_round": "bfloat16"}
    cell = CELLS[CELL]
    cfg = tiny_config(cell["config"])
    spec = {"cell": cell, "config": merged(cfg, cfg["control"]),
            "mix": tiny_mix(cell["traffic"]), "trace": False,
            "scratch": str(tmp_path), "bench": BENCH}
    result = rehearse(spec, tmp_path)["result"]
    assert not result["correct"] and result["failed"] == 0
    assert len(result["problems"]) == 1 and "logprobs" in \
        result["problems"][0]
    checks = {c[0]: c for c in result["checks"]}
    assert checks["logprob_mean_abs"][1] > 3 * checks["logprob_mean_abs"][2]


def ctx_with(monkeypatch, kernels, modules):
    """A run's context whose trace holds `kernels` {name: (calls,
    seconds)} and `modules` {name: (runs, seconds)}."""
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    monkeypatch.setattr(
        spans, "kernel_seconds",
        lambda s, names: (lambda hit: (sum(c for c, _ in hit),
                                       sum(t for _, t in hit))
                          if hit else None)(
            [s["kernels"][n] for n in names if n in s["kernels"]]))
    c = config()
    return {"trace": {"modules": modules}, "config": c,
            "cell": CELLS[CELL], "traffic": tiny_mix("docgen-closed24"),
            "arith": retention_arith, "widths": retention_arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"engine": {"decode_tokens": 1350, "decode_steps": 100,
                                 "prefill_tokens": 15000,
                                 "prefill_chunks": 40}}}


def test_the_four_readers_by_hand(monkeypatch):
    """100 decode steps whose eight `retention_step` calls took 12 ms a
    step at 13.5 decoding sequences; 40 runs of `jit__prefill` whose
    `retention_chunk` calls took 9 ms a run at 375 live tokens."""
    ctx = ctx_with(
        monkeypatch,
        {"retention_step": (800, 1.2), "retention_chunk": (320, 0.36)},
        {"jit__decode": (100, 3.0), "jit__prefill": (40, 1.2)})
    assert bench_run.read_layer_metric("retention_step_ms", ctx) \
        == pytest.approx(12.0)
    assert bench_run.read_layer_metric("retention_chunk_ms", ctx) \
        == pytest.approx(9.0)
    w = ctx["widths"]
    assert bench_run.read_layer_metric("retention_step_roofline", ctx) \
        == pytest.approx(100 * 13.5 * 272.646144e6 / 819e9 / 12e-3)
    assert bench_run.read_layer_metric("retention_chunk_roofline", ctx) \
        == pytest.approx(100 * retention_arith.chunk_required_ops(w, 375)
                         / 197e12 / 9e-3)
    # a read-modify-write that ran at the memory's full bandwidth reads
    # 50 %: 13.5 states read and written at 819 GB/s take 8.99 ms
    ctx = ctx_with(monkeypatch, {"retention_step": (800, 0.8988)},
                   {"jit__decode": (100, 3.0)})
    assert bench_run.read_layer_metric("retention_step_roofline", ctx) \
        == pytest.approx(50.0, rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the new
    kernels (the olmo replica's, recorded on a v5e), or no trace at all,
    and the reader returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    c = config()
    ctx = {"trace": trace.reduce(other), "config": c,
           "arith": retention_arith, "widths": retention_arith.widths(c),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"engine": {"decode_tokens": 30, "decode_steps": 10,
                                "prefill_tokens": 100,
                                "prefill_chunks": 4}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(NEW)
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        assert entry["source"] == "device_trace"
        assert (entry["layer"], entry["moves"]) == ("kernels", "tpot_p90_ms")
        assert entry["workloads"] == [CELL]
    # and the cell is on every list its sibling glm-5.2.docqa-closed24 is on
    # but that family's own kernels'
    for m in BENCH["per_layer"]:
        if "glm-5.2.docqa-closed24" in m.get("workloads", ()) \
                and m["layer"] != "kernels":
            assert CELL in m["workloads"], m["name"]
