"""CPU checks of what `falcon-h1-34b.reason-closed96` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic, its control at the tiny size, the mix, and the
readers the cell is read through, the four it shares with
`nemotron-3-super.chat-closed96` over its own arithmetic and the four
grouped-head ones under their new names, on made-up traces' numbers.
Entries are found by name, never by place. (The cell's rehearsal is
`test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_parallel_hybrid.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import parallel_hybrid_arith as arith
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness.common import ROOT, merged
from benchmarks.layer_metrics import span_attr_roofline, tick_events
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "falcon-h1-34b", "falcon-h1-34b.reason-closed96"
SIBLING = "nemotron-3-super.chat-closed96"
NEW = ("hybrid_gqa_decode_ms", "hybrid_gqa_chunk_ms",
       "hybrid_gqa_decode_roofline", "hybrid_gqa_chunk_roofline")
SHARED = ("mamba2_step_ms", "mamba2_step_roofline", "mamba2_chunk_ms",
          "mamba2_chunk_roofline")
# the catalog's `config` for Falcon-H1-34B-Instruct, each under its key
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 72}
    assert c["num_hidden_layers"] == 6
    # the catalog's own file, where this machine has it
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "Falcon-H1-34B-Instruct"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == c["source"]
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert "pipeline" in c["deployment"] and "5,254,594,112" in \
        c["deployment"]
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    assert isinstance(c["guarantees"], str) and c["guarantees"]
    assert set(c["draws"]) == {
        "time_step", "a_range", "d_skip", "conv_bias", "embed_scale",
        "score_gain", "mamba_out_gain", "attention_out_gain", "mlp_out_gain"}
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (64, 16384)
    kw = serve["engine_kwargs"]
    assert (kw["block_size"], kw["prefill_chunk"], kw["prefill_buckets"],
            kw["prefix_cache"]) == (128, 512, [128, 512], False)
    # weights, 65 state blocks and the pages beside them fill the chip
    w = arith.widths(c)
    held = (arith.parameters(w) * 2
            + (serve["slots"] + 1) * (arith.state_bytes(w)
                                      + arith.tail_bytes(w))
            + kw["cache_blocks"] * 128 * arith.ROW_BYTES)
    assert 15.0e9 < held < 15.3e9
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}
    assert set(c["program"]["constructor"].values()) <= set(PUBLISHED)


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-closed96", 1)
    assert len(cell["why"]) <= 200 and "6 of 72" in cell["why"]
    mix = load("benchmarks", "traffic", "reason-closed96.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 96)
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 1.0,
                                    "min": 128, "max": 12288}
    assert mix["output_tokens"] == {"median": 1024, "sigma": 0.6,
                                    "min": 256, "max": 4096}
    assert (mix["length_block"], mix["order_seed"], mix["check_requests"],
            mix["trace_s"], mix["ramp_requests"]) == (24, 0, 4, 10, 160)
    gen = traffic.serve_requests(mix, 2**31 + 7, 261120)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    serve = config()["program"]["serve"]
    # a later block may pair the longest prompt with the longest output
    longest = (max(len(r["prompt"]) for r in block)
               + max(r["max_new_tokens"] for r in block))
    assert max(lengths) <= longest == 11325 <= serve["max_len"]
    assert (min(len(r["prompt"]) for r in block),
            max(len(r["prompt"]) for r in block)) == (133, 7850)
    assert max(int(r["prompt"].max()) for r in block) > 255000
    # the four requests compared are the mix's, the same in every run
    assert traffic.check_plan(mix) == [(8, 481), (10, 710), (20, 818),
                                       (21, 1125)]
    # the pages are sized to the mix (a request's pages are taken whole
    # when it is admitted; its fixed order asks 1,869 of 64 slots at the
    # most over 800 requests), not to 64 of the longest request
    assert serve["slots"] * -(-longest // 128) > \
        serve["engine_kwargs"]["cache_blocks"] > 1869
    assert CELL in by_name(BENCH["end_to_end"],
                           "serve_tokens_per_s")["workloads"]
    assert CELL not in by_name(BENCH["end_to_end"],
                               "tpot_p90_ms")["workloads"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameters_and_bytes_from_the_file_s_own_keys():
    c = config()
    w = arith.widths(c)
    assert arith.attention_branch_parameters(w) == 31457280
    assert arith.state_branch_parameters(w) == 68351072
    assert arith.mlp_parameters(w) == 330301440
    assert arith.layer_parameters(w) == 430120032
    assert arith.parameters(w) == c["parameters_as_run"] == 5254594112
    # the uncut model, from the same functions: 72 layers
    assert round(arith.parameters({**w, "n_layers": 72}) / 1e9, 2) == 33.64
    # 6 layers x 32 heads x 128 x 256 x 4 B, and 6 x 3 x 5,120 x 4 B
    assert arith.state_bytes(w) == 6 * 32 * 128 * 256 * 4 == 25165824
    assert arith.tail_bytes(w) == 6 * 3 * 5120 * 4 == 368640
    assert arith.state_read_bytes(w, 40.5) == 40.5 * 25165824
    assert w["row_bytes"] == 2048
    assert arith.ROW_BYTES == 6 * w["row_bytes"] == 12288
    assert arith.decode_read_bytes(1000.0, 99999) == 12288000.0
    # a token a layer: scores a group over N = 256, their product with x
    # a head of 128, the state's read and its update a head
    per = 2 * 2 * 128 * 256 + 32 * (2 * 128 * 128 + 4 * 128 * 256)
    assert arith.chunk_required_ops(w, 1) == 6 * per == 32243712
    assert arith.chunk_required_ops(w, 512) \
        == 512 * arith.chunk_required_ops(w, 1)
    # a chunk's attention: every query's keys up to its own, no padded
    # head and no padded query
    assert arith.chunk_attention_ops(w, 0, 512) \
        == 4 * 20 * 128 * 6 * (512 * 513 // 2)
    assert arith.chunk_attention_ops(w, 1024, 100) \
        == 4 * 20 * 128 * 6 * (100 * 1024 + 100 * 101 // 2)
    step = arith.step_required_bytes(w, 64, 64 * 2500)
    assert [round(v / 1e9, 2) for v in step.values()] == [
        3.96, 2.67, 1.20, 3.22, 1.97]


def test_the_program_s_pool_is_the_arithmetic_s_state_and_row():
    """A state block as the program stores it is the arithmetic's state
    and tails; a cached position is the arithmetic's row, in every
    layer."""
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import parallel_hybrid
    c = config()
    cfg = common.model_config(c, "serve")
    assert cfg.n_layers == 6 and cfg.rope_theta == 1e11
    assert cfg.ssm_multipliers == tuple(c["ssm_multipliers"])
    pool = jax.eval_shape(lambda: parallel_hybrid.init_pool(
        cfg, 9, 128, state_blocks=5))
    w = arith.widths(c)
    assert pool["state"].shape == (6, 5, 32, 256, 128)
    assert pool["state"].size * 4 / 5 == arith.state_bytes(w)
    assert pool["conv"].size * 4 / 5 == arith.tail_bytes(w)
    assert pool["k"].shape == (6, 9, 4, 128, 128)
    assert (pool["k"].size + pool["v"].size) * 2 / (9 * 128) \
        == arith.ROW_BYTES


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


def ctx_with(monkeypatch, kernels, modules, attrs=None):
    """A run's context whose trace holds `kernels` {name: (calls,
    seconds)}, `modules` {name: (runs, seconds)} and spans with `attrs`
    {span: [attribute values a span]}."""
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    monkeypatch.setattr(
        spans, "kernel_seconds",
        lambda s, names: (lambda hit: (sum(c for c, _ in hit),
                                       sum(t for _, t in hit))
                          if hit else None)(
            [s["kernels"][n] for n in names if n in s["kernels"]]))
    monkeypatch.setattr(tick_events, "find", lambda ctx: "made-up")
    monkeypatch.setattr(span_attr_roofline, "_cache", {})
    monkeypatch.setattr(
        span_attr_roofline, "span_attrs",
        lambda path, span, names: (attrs or {}).get(span))
    c = config()
    return {"trace": {"modules": modules}, "config": c,
            "cell": CELLS[CELL], "traffic": tiny_mix("reason-closed96"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 150000.0},
                      "engine": {"decode_tokens": 6000, "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 13824.0}}}


def test_the_eight_readers_by_hand(monkeypatch):
    """100 decode steps at 60 decoding sequences over 150,000 cached
    positions, whose six `mamba2_step` calls took 6 ms a step and whose
    six `gqa_full_decode` calls 4 ms; 40 runs of `jit__prefill` at 400
    live tokens whose `mamba2_chunk` calls took 1 ms a run and whose
    `gqa_full_chunk` calls 2 ms, chunks of 512 at 0 and at 2,048."""
    ctx = ctx_with(
        monkeypatch,
        {"mamba2_step": (600, 0.6), "mamba2_chunk": (240, 0.04),
         "gqa_full_decode": (600, 0.4), "gqa_full_chunk": (240, 0.08)},
        {"jit__decode": (100, 3.0), "jit__prefill": (40, 2.4)},
        {"engine/prefill_chunk": [(0.0, 512.0), (2048.0, 512.0)]})
    read = bench_run.read_layer_metric
    assert read("mamba2_step_ms", ctx) == pytest.approx(6.0)
    assert read("mamba2_chunk_ms", ctx) == pytest.approx(1.0)
    assert read("hybrid_gqa_decode_ms", ctx) == pytest.approx(4.0)
    assert read("hybrid_gqa_chunk_ms", ctx) == pytest.approx(2.0)
    w = ctx["widths"]
    # the shared readers take bytes and operations from this
    # configuration's own arithmetic: a state of 25.17 MB a sequence
    assert read("mamba2_step_roofline", ctx) == pytest.approx(
        100 * 60 * 25165824 / 819e9 / 6e-3)
    assert read("mamba2_chunk_roofline", ctx) == pytest.approx(
        100 * arith.chunk_required_ops(w, 400) / 197e12 / 1e-3)
    assert read("hybrid_gqa_decode_roofline", ctx) == pytest.approx(
        100 * 150000 * 12288 / 819e9 / 4e-3)
    ops = (arith.chunk_attention_ops(w, 0, 512)
           + arith.chunk_attention_ops(w, 2048, 512)) / 2
    assert read("hybrid_gqa_chunk_roofline", ctx) == pytest.approx(
        100 * ops / 197e12 / 2e-3)
    # a read-modify-write that ran at the memory's full bandwidth reads
    # 50 %: 60 states read and written at 819 GB/s take 3.687 ms
    ctx = ctx_with(monkeypatch, {"mamba2_step": (600, 0.36873)},
                   {"jit__decode": (100, 3.0)})
    assert read("mamba2_step_roofline", ctx) == pytest.approx(50.0,
                                                              rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the
    kernels (the olmo replica's, recorded on a v5e), or no trace at all,
    and the reader returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    monkeypatch.setattr(tick_events, "find", lambda ctx: other)
    c = config()
    ctx = {"trace": trace.reduce(other), "config": c, "arith": arith,
           "widths": arith.widths(c),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"serve": {"decoding_context_tokens": 100.0},
                     "engine": {"decode_tokens": 30, "decode_steps": 10,
                                "prefill_tokens": 100, "prefill_chunks": 4,
                                "kv_bytes_per_token": 13824.0}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    monkeypatch.setattr(tick_events, "find", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        # by name, and the cell a member: a later cell may join the list
        # (`run.py` reads the one in `BENCHMARK.json`, not the file's)
        entry = by_name(BENCH["per_layer"], name)
        assert {k: spec[k] for k in entry if k != "workloads"} == {
            k: v for k, v in entry.items() if k != "workloads"}
        assert entry["source"] == "device_trace"
        assert (entry["layer"], entry["moves"]) == ("kernels",
                                                    "serve_tokens_per_s")
        assert CELL in entry["workloads"]
        assert name.endswith("_ms") or entry["unit"] == "%"
        # a file over a reducer the benchmark had
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{spec['reducer']}.py"))
    # the cell is on every list its sibling of the state-space families
    # is on, and on no list whose metric moves an end-to-end metric it
    # does not report
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    joined = [m["name"] for m in BENCH["per_layer"]
              if SIBLING in m.get("workloads", ())]
    assert set(SHARED) <= set(joined)
    for m in BENCH["per_layer"]:
        lists = m.get("workloads", ())
        if m["name"] in joined:
            assert CELL in lists, m["name"]
        if m["moves"] not in reported:
            assert CELL not in lists, m["name"]
    assert CELL not in by_name(BENCH["per_layer"],
                               "serve_idle_owned_share")["workloads"]
