"""CPU checks of what `lfm2-8b-a1b.chat-closed192` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic against the issue's counts, its control at the tiny
size, the mix, and the eight kernel metrics over the readers the
benchmark had, on made-up traces' numbers. Entries are found by name,
never by place. (The cell's rehearsal is
`test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_shortconv_moe.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness import shortconv_moe_arith as arith
from benchmarks.harness.common import ROOT, merged
from benchmarks.layer_metrics import span_attr_roofline
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "lfm2-8b-a1b", "lfm2-8b-a1b.chat-closed192"
NEW = ("lfm2_experts_step_ms", "lfm2_experts_step_roofline",
       "lfm2_experts_chunk_ms", "lfm2_experts_chunk_roofline",
       "lfm2_gqa_decode_ms", "lfm2_gqa_decode_roofline",
       "lfm2_gqa_chunk_ms", "lfm2_gqa_chunk_roofline")
TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
         + ["full_attention", "conv", "conv"] * 2)
# the catalog's `config` for LFM2-8B-A1B, each under its key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    assert len(TYPES) == 24 and TYPES.count("conv") == 18
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 24}
    assert (c["num_hidden_layers"], c["layers_from"],
            c["experts_held_from"]) == (14, 0, 0)
    # depth alone: both leading dense layers and three whole periods
    from benchmarks.refs import shortconv_moe as ref
    kinds = ref.layer_kinds(c)
    assert [m for m, _ in kinds] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 3
    assert [f for _, f in kinds] == ["dense"] * 2 + ["sparse"] * 12
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert "two pipeline stages" in c["deployment"] \
        and "every one of 32 experts" in c["deployment"] \
        and "No width is cut" in c["deployment"]
    assert "dropless" in c["guarantees"] and "bit-identical" in \
        c["guarantees"]
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    for said in ("thirds in that order", "no activation", "bfloat16",
                 "rotate-half", "1e-6", "tied", "expert_bias"):
        assert any(said in a for a in c["assumed"]), said
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (128, 4992)
    kw = serve["engine_kwargs"]
    assert (kw["block_size"], kw["prefill_chunk"], kw["prefill_buckets"],
            kw["prefix_cache"]) == (128, 512, [128, 512], False)
    # every slot holds its longest request at once: 128 x 39 pages and
    # the trash page, so nothing is preempted
    assert kw["cache_blocks"] == 128 * (4992 // 128) + 1
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}
    assert c["control"]["program"]["model"] == {
        "expert_round": "float8_e4m3fn"}


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-closed192", 1)
    mix = load("benchmarks", "traffic", "chat-closed192.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 192)
    assert mix["prompt_tokens"] == {"median": 512, "sigma": 1.0,
                                    "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.7,
                                    "min": 32, "max": 2048}
    assert (mix["length_block"], mix["order_seed"], mix["check_requests"],
            mix["trace_s"], mix["ramp_requests"], mix["ramp_s"],
            mix["warm_new_tokens"], mix["request_timeout_s"]) == (
                24, 0, 4, 10, 320, 300, 4, 600)
    # the same blocks of lengths as chat-closed96
    other = load("benchmarks", "traffic", "chat-closed96.json")
    assert next(traffic.serve_blocks(mix)) == next(
        traffic.serve_blocks(other))
    gen = traffic.serve_requests(mix, 2**31 + 7, 65536)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    assert max(lengths) == 4210
    # later blocks pair the lengths anew: the longest prompt can meet the
    # longest output, and `max_len` holds that (the first chip run at
    # 4,608 refused 5 requests of 3,925 + 749)
    assert max(p + o for blk, _ in zip(traffic.serve_blocks(mix), range(50))
               for p, o, _ in blk) <= 3925 + 1065 == 4990 \
        <= config()["program"]["serve"]["max_len"]
    assert (min(len(r["prompt"]) for r in block),
            max(len(r["prompt"]) for r in block)) == (66, 3925)
    assert max(int(r["prompt"].max()) for r in block) > 65000
    assert traffic.check_plan(mix) == [(0, 137), (12, 476), (20, 197),
                                       (21, 285)]
    assert CELL in by_name(BENCH["end_to_end"],
                           "serve_tokens_per_s")["workloads"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    assert (len(BENCH["workloads"]), len(BENCH["configs"])) == (13, 11)


def test_parameters_and_bytes_are_the_issue_s_counts():
    c = config()
    w = arith.widths(c)
    assert (w["conv_layers"], w["attention_layers"], w["dense_layers"],
            w["sparse_layers"], w["head_dim"]) == (11, 3, 2, 12, 64)
    assert arith.conv_layer_parameters(w) == 16783360
    assert arith.attention_layer_parameters(w) == 10485760 + 2 * 64
    assert arith.dense_mlp_parameters(w) == 44040192
    assert arith.expert_parameters(w) == 11010048
    assert arith.sparse_ffn_parameters(w) == 352321536 + 65536 + 32
    assert arith.parameters(w) == c["parameters_as_run"] == 4667077376
    assert round(arith.parameters(w) / 1e9, 3) == 4.667
    # the uncut model, from the same functions: 18 : 6 of 24 layers, 22
    # of them sparse
    whole = {**w, "conv_layers": 18, "attention_layers": 6, "n_layers": 24,
             "sparse_layers": 22}
    assert round(arith.parameters(whole) / 1e9, 2) == 8.34
    assert arith.ROW_BYTES == w["row_bytes"] == 3 * 8 * 64 * 2 * 2 == 6144
    assert arith.tail_bytes(w) == 11 * 2 * 2048 * 2 == 90112
    assert arith.decode_read_bytes(1000.0, 99999) == 6144000.0
    assert round(arith.held_expert_bytes(w) / 1e9, 2) == 8.46
    assert round(arith.held_expert_bytes(w)
                 / (arith.parameters(w) * 2), 2) == 0.91
    # a chunk of 512 at position 1,000: 4 x 32 x 64 a query a key, 3 layers
    assert arith.chunk_attention_ops(w, 1000, 512) == 4 * 32 * 64 * 3 * (
        512 * 1000 + 512 * 513 // 2)
    step = arith.step_required_bytes(w, 128, 128 * 1000)
    assert [round(v / 1e9, 2) for v in step.values()] == [
        8.46, 0.43, 0.18, 0.27, 0.02, 0.79]
    # no less than 12.4 ms a step at 819 GB/s
    assert round(sum(step.values()) / 819e9 * 1e3, 1) == 12.4


def test_the_program_s_pool_is_the_arithmetic_s_tails_and_row():
    """A state block as the program stores it is the arithmetic's tails;
    a cached position is the arithmetic's row, two heads of 64 side by
    side in a row of 128 lanes."""
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import shortconv_moe
    c = config()
    cfg = common.model_config(c, "serve")
    assert (cfg.head_dim, cfg.kv_pack, cfg.held_count, cfg.router_width,
            cfg.experts.norm_eps) == (64, 2, 32, 32, 1e-6)
    assert [m for m, _ in cfg.kinds].count("conv") == 11
    pool = jax.eval_shape(lambda: shortconv_moe.init_pool(
        cfg, 9, 128, state_blocks=5))
    assert pool["tail"].shape == (11, 5, 2, 2048)
    assert pool["k"].shape == pool["v"].shape == (3, 9, 4, 128, 128)
    w = arith.widths(c)
    assert pool["tail"].size * 2 / 5 == arith.tail_bytes(w)
    assert (pool["k"].size + pool["v"].size) * 2 / (9 * 128) \
        == arith.ROW_BYTES


def test_the_control_is_not_correct(tmp_path):
    """The cell's control at the tiny size: the routed experts' inputs
    and matrices on the float8 grid (`expert_round`, the program's
    test-only field). Every request still gets its tokens; the logprobs
    are what fails."""
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


def ctx_with(monkeypatch, kernels, modules, chunks=()):
    """A run's context whose trace holds `kernels` {name: (calls,
    seconds)}, `modules` {name: (runs, seconds)} and whose
    `engine/prefill_chunk` spans carry `chunks` [(start, tokens)]."""
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    monkeypatch.setattr(
        spans, "kernel_seconds",
        lambda s, names: (lambda hit: (sum(c for c, _ in hit),
                                       sum(t for _, t in hit))
                          if hit else None)(
            [s["kernels"][n] for n in names if n in s["kernels"]]))
    from benchmarks.layer_metrics import tick_events
    monkeypatch.setattr(tick_events, "find", lambda ctx: "made-up")
    monkeypatch.setattr(span_attr_roofline, "span_attrs",
                        lambda *a: [tuple(map(float, c)) for c in chunks])
    span_attr_roofline._cache.clear()
    c = config()
    return {"trace": {"modules": modules}, "config": c,
            "cell": CELLS[CELL], "traffic": tiny_mix("chat-closed192"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 128000.0},
                      "engine": {"decode_tokens": 12800,
                                 "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 6163.6}}}


def test_the_eight_readers_by_hand(monkeypatch):
    """100 decode steps whose twelve `experts_grouped` calls took 12 ms a
    step and three `gqa_full_decode` calls 1.5 ms over 128,000 cached
    positions; 40 runs of `jit__prefill` whose `experts_grouped_prefill`
    calls took 11 ms a run and `gqa_full_chunk` calls 0.4 ms, every chunk
    512 tokens at position 1,000."""
    ctx = ctx_with(
        monkeypatch,
        {"experts_grouped": (1200, 1.2), "gqa_full_decode": (300, 0.15),
         "experts_grouped_prefill": (480, 0.44),
         "gqa_full_chunk": (120, 0.016)},
        {"jit__decode": (100, 1.5), "jit__prefill": (40, 0.6)},
        chunks=[(1000, 512)] * 3)
    read = bench_run.read_layer_metric
    assert read("lfm2_experts_step_ms", ctx) == pytest.approx(12.0)
    assert read("lfm2_gqa_decode_ms", ctx) == pytest.approx(1.5)
    assert read("lfm2_experts_chunk_ms", ctx) == pytest.approx(11.0)
    assert read("lfm2_gqa_chunk_ms", ctx) == pytest.approx(0.4)
    w = ctx["widths"]
    held = arith.held_expert_bytes(w)
    assert read("lfm2_experts_step_roofline", ctx) == pytest.approx(
        100 * held / 819e9 / 12e-3)
    assert read("lfm2_experts_chunk_roofline", ctx) == pytest.approx(
        100 * held / 819e9 / 11e-3)
    assert read("lfm2_gqa_decode_roofline", ctx) == pytest.approx(
        100 * 128000 * 6144 / 819e9 / 1.5e-3)
    assert read("lfm2_gqa_chunk_roofline", ctx) == pytest.approx(
        100 * arith.chunk_attention_ops(w, 1000, 512) / 197e12 / 0.4e-3)
    # every matrix read once at the memory's full bandwidth reads 100 %:
    # 8.46 GB at 819 GB/s take 10.32 ms
    ctx = ctx_with(monkeypatch, {"experts_grouped": (1200, 1.03244)},
                   {"jit__decode": (100, 1.5)})
    assert read("lfm2_experts_step_roofline", ctx) == pytest.approx(
        100.0, rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the
    cell's kernels (the olmo replica's, recorded on a v5e), or no trace
    at all, and the reader returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    c = config()
    ctx = {"trace": trace.reduce(other), "config": c, "arith": arith,
           "widths": arith.widths(c),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"serve": {"decoding_context_tokens": 100.0},
                     "engine": {"decode_tokens": 30, "decode_steps": 10,
                                "prefill_tokens": 100, "prefill_chunks": 4,
                                "kv_bytes_per_token": 6163.6}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = by_name(BENCH["per_layer"], name)
        assert {k: spec[k] for k in entry} == entry
        assert entry["source"] == "device_trace"
        assert (entry["layer"], entry["moves"]) == ("kernels",
                                                    "serve_tokens_per_s")
        assert entry["workloads"] == [CELL]
        # a share says why it cannot pass 100 %
        assert name.endswith("_ms") or (
            entry["unit"] == "%" and name.endswith("_roofline")
            and ("cannot pass 100 %" in spec["what"]
                 or "under 100 %" in spec["what"]))
        assert spec["reducer"] in ("kernel_ms", "kernel_roofline",
                                   "decode_roofline", "span_attr_roofline")
    # the cell is on every list that the other cells that report tokens
    # per second alone are on, and on no list whose metric moves an
    # end-to-end metric it does not report
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    on = {m["name"] for m in BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert on == set(NEW) | {
        "preemptions", "queue_wait_ms_p50", "slot_occupancy_pct",
        "steps_chained_share"} | {
        f"{program}_{part}_ms" for program in ("decode", "chunk")
        for part in ("mixer", "ffn", "head", "compiler")}
    for m in BENCH["per_layer"]:
        lists = m.get("workloads", ())
        if m["moves"] not in reported:
            assert CELL not in lists, m["name"]
        elif ("ling-3.0-flash-vl.reason-closed96" in lists
              and "nemotron-3-super.chat-closed96" in lists
              and "falcon-h1-34b.reason-closed96" in lists):
            assert CELL in lists, m["name"]
