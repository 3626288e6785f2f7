"""CPU checks of what `command-a-plus.mixed-closed24` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic, its two controls at the tiny size, the mix, and the
six kernel metrics over their readers, on made-up traces' numbers.
Entries are found by name, never by place. (The cell's rehearsal is
`test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_window_moe.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness import window_moe_arith as arith
from benchmarks.harness.common import ROOT, merged
from benchmarks.layer_metrics import span_attr_roofline, tick_events
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "command-a-plus", "command-a-plus.mixed-closed24"
NEW = ("gqa_window_decode_ms", "gqa_full_decode_ms", "gqa_chunk_ms",
       "gqa_full_decode_roofline", "gqa_window_decode_roofline",
       "gqa_chunk_roofline")
JOINED = ("decode_step_device_ms", "prefill_chunk_device_ms",
          "prefill_tick_share", "engine_tick_ms", "tick_gap_ms",
          "tick_host_ms", "prefill_host_ms", "decode_put_ms",
          "idle_between_ticks_ms", "idle_in_tick_ms",
          "serve_idle_owned_share", "deliver_wait_ms_p50", "stream_wait_ms",
          "tick_host_share", "preemptions", "queue_wait_ms_p50",
          "slot_occupancy_pct", "experts_decode_ms",
          "experts_prefill_roofline")
# the numbers and names of the catalog's `config` for
# command-a-plus-05-2026, each under its key
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tie_word_embeddings": True, "use_embedding_sharing": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_parallel_embedding": False, "use_qk_norm": False,
    "vocab_size": 262144}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["layers_from"], c["experts_held_from"]) == (4, 16, 32768, 0, 0)
    # all 32 published layers are named, 3 window : 1 full; the four that
    # run are one whole period
    assert len(c["layer_types"]) == 32
    assert all((t == "full_attention") == ((i + 1) % 4 == 0)
               for i, t in enumerate(c["layer_types"]))
    # the floors: a whole period of four, 8 experts, an eighth of the
    # vocabulary
    assert c["num_hidden_layers"] >= c["layer_switch"]
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= 262144
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert "shared by 8 chips" in c["deployment"]
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    for key in ("attn_logit_std", "attn_out_gain", "embed_scale",
                "final_norm_gain"):
        assert any(key in a for a in c["assumed"]), key
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (16, 32768)
    kw = serve["engine_kwargs"]
    assert (kw["block_size"], kw["prefill_chunk"], kw["prefix_cache"]) == (
        128, 512, False)
    # every slot holds its longest request at once: 232 pages of the kind
    # that grows and a ring of 37 (the window and a chunk, and a page)
    assert kw["cache_blocks"] == 16 * 232 and 232 * 128 >= 28672 + 1024 - 1
    assert kw["bounded_blocks"] == 16 * 37
    assert 37 == -(-(4096 + 512 - 2) // 128) + 1
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}


def test_the_layers_that_run_are_one_whole_period():
    from benchmarks.refs import window_moe as ref
    assert ref.layer_kinds(config()) == ["window", "window", "window",
                                         "full"]
    assert ref.router_width(config()) == 128


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed-closed24", 1)
    mix = load("benchmarks", "traffic", "mixed-closed24.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 24)
    assert mix["prompt_tokens"] == {"median": 4096, "sigma": 1.0,
                                    "min": 512, "max": 28672}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.6,
                                    "min": 64, "max": 1024}
    assert (mix["length_block"], mix["order_seed"], mix["ramp_requests"],
            mix["ramp_s"], mix["trace_s"], mix["check_requests"],
            mix["warm_new_tokens"], mix["request_timeout_s"]) == (
                24, 0, 40, 300, 10, 4, 4, 600)
    gen = traffic.serve_requests(mix, 2**31 + 7, 32768)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    assert max(lengths) == 28953 <= config()["program"]["serve"]["max_len"]
    prompts = sorted(len(r["prompt"]) for r in block)
    assert (prompts[0], prompts[-1]) == (534, 28672)
    assert sum(p < 4096 for p in prompts) == 12       # half under the window
    assert max(int(r["prompt"].max()) for r in block) > 32000
    for name in ("serve_tokens_per_s", "tpot_p90_ms"):
        assert CELL in by_name(BENCH["end_to_end"], name)["workloads"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameters_and_bytes_from_the_file_s_own_keys():
    c = config()
    w = arith.widths(c)
    assert (w["window_layers"], w["full_layers"], w["window"]) == (3, 1, 4096)
    assert round(arith.attention_parameters(w) / 1e6, 1) == 142.6
    assert arith.expert_parameters(w) == 3 * 4096 * 4096
    assert round(arith.layer_parameters(w) / 1e6, 1) == 1149.8
    assert arith.parameters(w) == c["parameters_as_run"] == 4733292544
    assert arith.ROW_BYTES == w["row_bytes"] == 2 * 8 * 128 * 2
    assert arith.decode_read_bytes(1000.0, 99999) == 4096000.0
    assert arith.window_read_bytes(w, 1000.0) == 3 * 4096000.0
    # a chunk of 512 at 16,384: the full layer's queries see 16,385 ..
    # 16,896 keys, a window layer's 4,096 each
    per = 4 * 128 * 128
    full = sum(range(16385, 16897))
    assert arith.chunk_attention_ops(w, 16384, 512) == per * (
        full + 3 * 512 * 4096)
    # under the window both kinds see every earlier position
    assert arith.chunk_attention_ops(w, 0, 512) == per * 4 * sum(
        range(1, 513))
    # a chunk that straddles the window's edge: rows 3,585 .. 4,096 grow,
    # the rest stay at 4,096
    assert arith.chunk_attention_ops(w, 3840, 512) == per * (
        sum(range(3841, 4353)) + 3 * (sum(range(3841, 4097)) + 256 * 4096))
    assert round(arith.held_expert_bytes(w) / 1e9, 2) == 6.44
    kw = c["program"]["serve"]["engine_kwargs"]
    pool = arith.pool_bytes(w, kw["cache_blocks"], kw["bounded_blocks"], 128)
    assert [round(v / 1e9, 2) for v in pool.values()] == [1.95, 0.93, 7.78]
    step = arith.step_required_bytes(w, 16, 16 * 8000, 16 * 3500)
    assert [round(v / 1e9, 1) for v in step.values()] == [4.1, 3.0, 0.5, 0.7]


def test_the_program_s_pool_is_the_arithmetic_s_rows():
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import window_moe
    c = config()
    cfg = common.model_config(c, "serve")
    assert cfg.family.bounded_tokens == 4096
    pool = jax.eval_shape(lambda: window_moe.init_pool(
        cfg, 9, 128, bounded_blocks=5))
    w = arith.widths(c)
    assert (pool["k"].size + pool["v"].size) * 2 / (9 * 128) \
        == w["full_layers"] * w["row_bytes"]
    assert (pool["kw"].size + pool["vw"].size) * 2 / (5 * 128) \
        == w["window_layers"] * w["row_bytes"]


@pytest.mark.parametrize("block", ["control", "control_window"])
def test_a_control_is_not_correct(tmp_path, block):
    """The cell's two controls at the tiny size: cache rows rounded to
    the int8 grid (`cache_round`), and the full layer cut at the window
    (`full_window`), both test-only fields of the program. Every request
    still gets its tokens; the logprobs are what fails."""
    c = config()
    assert c["control"]["program"]["model"] == {"cache_round": "int8"}
    assert c["control_window"]["program"]["model"] == {"full_window": 4096}
    cell = CELLS[CELL]
    cfg = tiny_config(cell["config"])
    assert cfg["control_window"]["program"]["model"]["full_window"] \
        == cfg["sliding_window"]
    spec = {"cell": cell, "config": merged(cfg, cfg[block]),
            "mix": tiny_mix(cell["traffic"]), "trace": False,
            "scratch": str(tmp_path), "bench": BENCH}
    result = rehearse(spec, tmp_path)["result"]
    assert not result["correct"] and result["failed"] == 0
    assert len(result["problems"]) == 1 and "logprobs" in \
        result["problems"][0]
    checks = {c[0]: c for c in result["checks"]}
    assert checks["logprob_max_abs"][1] > 3 * checks["logprob_max_abs"][2]


def ctx_with(monkeypatch, kernels, modules, attrs=None):
    """A run's context whose trace holds `kernels` {name: (calls,
    seconds)}, `modules` {name: (runs, seconds)} and spans with `attrs`
    {span: [attribute values a span]}."""
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    monkeypatch.setattr(tick_events, "find", lambda ctx: "made-up")
    monkeypatch.setattr(span_attr_roofline, "_cache", {})
    monkeypatch.setattr(
        span_attr_roofline, "span_attrs",
        lambda path, span, names: (attrs or {}).get(span))
    c = config()
    return {"trace": {"modules": modules}, "config": c,
            "cell": CELLS[CELL], "traffic": tiny_mix("mixed-closed24"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 100000.0},
                      "engine": {"decode_tokens": 1500, "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 16384.0}}}


def test_the_six_readers_by_hand(monkeypatch):
    """100 decode steps over 100,000 cached positions whose one
    `gqa_full_decode` took 1 ms a step and whose three `gqa_window_decode`
    2 ms a step, half the steps at 40,000 window rows and half at 50,000;
    40 runs of `jit__prefill` whose four chunk kernels took 10 ms a run,
    chunks of 512 at 0 and at 16,384."""
    ctx = ctx_with(
        monkeypatch,
        {"gqa_full_decode": (100, 0.1), "gqa_window_decode": (300, 0.2),
         "gqa_window_chunk": (120, 0.3), "gqa_full_chunk": (40, 0.1)},
        {"jit__decode": (100, 3.0), "jit__prefill": (40, 2.4)},
        {"engine/decode_dispatch": [(40000.0,), (50000.0,)],
         "engine/prefill_chunk": [(0.0, 512.0), (16384.0, 512.0)]})
    read = bench_run.read_layer_metric
    assert read("gqa_full_decode_ms", ctx) == pytest.approx(1.0)
    assert read("gqa_window_decode_ms", ctx) == pytest.approx(2.0)
    assert read("gqa_chunk_ms", ctx) == pytest.approx(10.0)
    w = ctx["widths"]
    assert read("gqa_full_decode_roofline", ctx) == pytest.approx(
        100 * 100000 * 4096 / 819e9 / 1e-3)
    assert read("gqa_window_decode_roofline", ctx) == pytest.approx(
        100 * 45000 * 3 * 4096 / 819e9 / 2e-3)
    ops = (arith.chunk_attention_ops(w, 0, 512)
           + arith.chunk_attention_ops(w, 16384, 512)) / 2
    assert read("gqa_chunk_roofline", ctx) == pytest.approx(
        100 * ops / 197e12 / 10e-3)
    # a program whose spans lack the attributes gives nothing
    ctx = ctx_with(
        monkeypatch, {"gqa_window_decode": (300, 0.2)},
        {"jit__decode": (100, 3.0)}, {"engine/decode_dispatch": None})
    assert read("gqa_window_decode_roofline", ctx) is None


def test_span_attributes_are_read_from_a_recorded_trace():
    """`data/v5e_serve_ticks.xplane.pb` (an olmo replica's, PR 39): its
    `engine/tick` events carry `gap_us` and `carried`, which the reader
    finds wholly inside the window; an attribute no event has gives
    nothing."""
    path = os.path.join(HERE, "data", "v5e_serve_ticks.xplane.pb")
    got = span_attr_roofline.span_attrs(path, "engine/tick",
                                        ("gap_us", "carried"))
    assert len(got) == tick_events.reduce(path)["ticks"] > 0
    assert all(len(v) == 2 and v[1] in (0.0, 1.0) for v in got)
    assert span_attr_roofline.span_attrs(
        path, "engine/tick", ("bounded_rows",)) is None
    assert span_attr_roofline.span_attrs(path, "engine/no_such", ("x",)) \
        == []


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the new
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
                                "kv_bytes_per_token": 16384.0}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    monkeypatch.setattr(tick_events, "find", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = by_name(BENCH["per_layer"], name)
        assert {k: spec[k] for k in entry} == entry
        assert entry["source"] == "device_trace"
        assert (entry["layer"], entry["moves"]) == ("kernels", "tpot_p90_ms")
        assert entry["workloads"] == [CELL]
        assert name.endswith("_ms") or entry["unit"] == "%"
    # the cell is on the lists the issue names, each of which moves an
    # end-to-end metric it reports, and on no other list but its own
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"serve_tokens_per_s", "tpot_p90_ms", "setup_s"}
    on = {m["name"] for m in BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert on == set(NEW) | set(JOINED)
    for m in BENCH["per_layer"]:
        if m["name"] in on:
            assert m["moves"] in reported, m["name"]
