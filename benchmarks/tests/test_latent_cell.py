"""CPU checks of what `glm-5.2.docqa-closed24` brought to the benchmark:
the configuration's own arithmetic, its control at the tiny size, the
agreement of the selected sets, and the three roofline shares' reader against a trace recorded on a v5e. (The
cell's rehearsal is `test_benchmark.py::test_cell_rehearsal`, which finds
it in `BENCHMARK.json`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import latent_arith, peaks, spans, trace
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glm-5.2.docqa-closed24"
LATENT = os.path.join(HERE, "data", "v5e_latent_serve.xplane.pb")


def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5.2.json")) as f:
        return json.load(f)


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    assert (c["hidden_size"], c["num_attention_heads"]) == (6144, 64)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (192, 64, 256)
    assert (c["q_lora_rank"], c["kv_lora_rank"]) == (2048, 512)
    assert (c["index_n_heads"], c["index_head_dim"],
            c["index_topk"]) == (32, 128, 2048)
    assert (c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["routed_scaling_factor"]) == (2048, 8, 2.5)
    assert c["intermediate_size"] == 12288
    assert c["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    entry = [e for e in BENCH["configs"] if e["name"] == "glm-5.2"][0]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) \
        == sorted(c["published"])
    assert entry["source"] == c["source"]
    # the layers run are the published 2-7: dense, then five sparse;
    # full, shared x 3, full, shared
    lo = c["layers_from"]
    assert c["mlp_layer_types"][lo:lo + 6] == ["dense"] + ["sparse"] * 5
    assert c["indexer_types"][lo:lo + 6] == [
        "full", "shared", "shared", "shared", "full", "shared"]
    assert len(c["indexer_types"]) == len(c["mlp_layer_types"]) == 78


def test_bytes_from_the_file_s_own_keys():
    w = latent_arith.widths(config())
    assert (w["latent_row_values"], w["index_dim"], w["value_bytes"]) \
        == (576, 128, 2)
    assert (w["n_layers"], w["full_layers"], w["sparse_layers"]) == (6, 2, 5)
    # 11 streams x 2048 rows x 1,152 B x 6 layers
    assert latent_arith.sparse_decode_read_bytes(w, 11) \
        == 11 * 2048 * 1152 * 6
    assert latent_arith.index_read_bytes(w, 70000) == 70000 * 256 * 2
    # 16 experts x 3 matrices x 2048 x 6144 x 2 B x 5 layers = 6.04 GB
    assert latent_arith.held_expert_bytes(w) == 16 * 3 * 2048 * 6144 * 2 * 5
    assert round(latent_arith.held_expert_bytes(w) / 1e9, 2) == 6.04


def test_the_control_is_not_correct(tmp_path):
    """The cell's control at the tiny size: every cache row rounded to the
    int8 grid as it is written (`cache_round`, the program's test-only
    field; the family has no `kv_dtype`). Every request still gets its
    tokens; the logprobs are what fails."""
    assert config()["control"]["program"]["model"] == {
        "cache_round": "int8"}
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


def test_selected_sets_agree_as_stated_and_not_under_the_control(
        monkeypatch, capsys):
    """`tools/selection_agreement.py` at the tiny size (float32): as
    stated the program selects the reference's S_t in every step of both
    indexers; with the control's rounded rows it loses some of them, more
    in the second indexer, whose input has been through four layers."""
    from benchmarks.tools import selection_agreement
    monkeypatch.setattr("sys.argv", [
        "selection_agreement.py", "--workload", CELL, "--seed", "2147483653",
        "--prompt", "100", "--steps", "4", "--tiny"])
    assert selection_agreement.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["index_topk"] == 16 and len(out["stated"]) == 2
    assert all(layer["least"] == 1.0 for layer in out["stated"])
    assert out["control"][1]["mean"] < out["control"][0]["mean"] <= 1.0
    assert out["control"][1]["least"] < 0.95


ROOFLINES = {
    "sparse_attn_decode_roofline": "sparse_latent_decode",
    "index_decode_roofline": "index_scores",
    "experts_prefill_roofline": "experts_grouped_prefill",
}


def ctx_for(monkeypatch):
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(LATENT))
    cfg = tiny_config("glm-5.2")
    return {"trace": trace.reduce(LATENT), "config": cfg,
            "cell": CELLS[CELL], "traffic": tiny_mix("docqa-closed24"),
            "arith": latent_arith, "widths": latent_arith.widths(cfg),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 150.0},
                      "engine": {"decode_tokens": 30, "decode_steps": 10}}}


@pytest.mark.skipif(not os.path.exists(LATENT),
                    reason="no trace of this cell was recorded on a chip")
def test_readers_on_a_trace_recorded_inside_a_replica(monkeypatch):
    """`data/v5e_latent_serve.xplane.pb`: `benchmarks/tools/record_trace.py
    --workload glm-5.2.docqa-closed24` on a v5e in PR 32, the cell's driver
    at the size of the `tiny` blocks, 0.08 s traced inside the replica's
    process. Every kernel the family adds is there under its name, and
    each new metric reads a number from it."""
    named = spans.reduce(LATENT)
    assert {"sparse_latent_decode", "index_scores", "experts_grouped",
            "experts_grouped_prefill", "latent_row_write",
            "latent_row_gather"} <= set(named["kernels"])
    ctx = ctx_for(monkeypatch)
    modules = ctx["trace"]["modules"]
    assert {"jit__decode", "jit__prefill"} <= set(modules)
    steps = modules["jit__decode"][0]
    # one call a layer a decode step: 6 layers, 2 of them with an indexer
    assert 0 <= named["kernels"]["sparse_latent_decode"][0] - 6 * steps <= 12
    assert 0 <= named["kernels"]["index_scores"][0] - 2 * steps <= 4
    for name in ("sparse_attn_decode_ms", "index_decode_ms",
                 "experts_decode_ms"):
        assert bench_run.read_layer_metric(name, ctx) > 0
    for name, kernel in ROOFLINES.items():
        got = bench_run.read_layer_metric(name, ctx)
        assert 0 < got < 100, (name, got)
        # by hand: bytes over bandwidth over the kernel's time a run
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            args = json.load(f)["args"]
        run_s = named["kernels"][kernel][1] / modules[args["module"]][0]
        w = ctx["widths"]
        need = {"sparse_attn_decode_roofline":
                latent_arith.sparse_decode_read_bytes(w, 3.0),
                "index_decode_roofline":
                latent_arith.index_read_bytes(w, 150.0),
                "experts_prefill_roofline":
                latent_arith.held_expert_bytes(w)}[name]
        assert got == pytest.approx(100 * need / 819e9 / run_s, rel=1e-9)


@pytest.mark.parametrize("name", sorted(ROOFLINES) + [
    "sparse_attn_decode_ms", "index_decode_ms", "experts_decode_ms"])
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the new
    kernels (the olmo replica's), or no trace at all, and the reader
    returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    cfg = tiny_config("glm-5.2")
    ctx = {"trace": trace.reduce(other), "config": cfg,
           "arith": latent_arith, "widths": latent_arith.widths(cfg),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"serve": {"decoding_context_tokens": 150.0},
                     "engine": {"decode_tokens": 30, "decode_steps": 10}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in list(ROOFLINES) + ["sparse_attn_decode_ms",
                                   "index_decode_ms", "experts_decode_ms"]:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "tpot_p90_ms"
        assert entry["workloads"] == [CELL]
