"""CPU checks of what `nemotron-3-super.chat-closed96` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic, its control at the tiny size, the mix, and the four
kernel metrics over the readers the benchmark had, on made-up traces'
numbers. Entries are found by name, never by place. (The cell's rehearsal
is `test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_mamba_moe.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import mamba_moe_arith as arith
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "nemotron-3-super", "nemotron-3-super.chat-closed96"
NEW = ("mamba2_step_ms", "mamba2_step_roofline", "mamba2_chunk_ms",
       "mamba2_chunk_roofline")
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the catalog's `config` for NVIDIA-Nemotron-3-Super-120B-A12B-BF16, each
# under its key
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"], c["layers_from"],
            c["experts_held_from"]) == (11, 128, 32768, 0, 0, 0)
    assert len(PATTERN) == 88 and [PATTERN.count(k) for k in "ME*"] == [
        40, 40, 8]
    # the floors: a whole period (the pattern's first 11 layers hold the
    # model's 5 : 5 : 1), 8 experts, an eighth of the vocabulary
    from benchmarks.refs import mamba_moe as ref
    assert ref.layer_kinds(c) == "MEMEMEM*EME"
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= 131072
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert "shared by 4 chips" in c["deployment"]
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    assert any(a.startswith("state layer, weight scales")
               for a in c["assumed"])
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (64, 10240)
    kw = serve["engine_kwargs"]
    assert (kw["block_size"], kw["prefill_chunk"], kw["prefill_buckets"],
            kw["prefix_cache"]) == (128, 512, [128, 512], False)
    # every slot holds its longest request at once: 64 x 80 pages and the
    # trash page, so nothing is preempted
    assert kw["cache_blocks"] == 64 * (10240 // 128) + 1
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-closed96", 1)
    mix = load("benchmarks", "traffic", "chat-closed96.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 96)
    assert mix["prompt_tokens"] == {"median": 512, "sigma": 1.0,
                                    "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.7,
                                    "min": 32, "max": 2048}
    assert (mix["length_block"], mix["order_seed"], mix["check_requests"],
            mix["trace_s"], mix["ramp_requests"], mix["ramp_s"],
            mix["warm_new_tokens"], mix["request_timeout_s"]) == (
                24, 0, 4, 10, 160, 300, 4, 600)
    gen = traffic.serve_requests(mix, 2**31 + 7, 32768)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    assert max(lengths) == 4210 <= config()["program"]["serve"]["max_len"]
    assert max(len(r["prompt"]) for r in block) == 3925
    assert max(int(r["prompt"].max()) for r in block) > 32000
    assert traffic.check_plan(mix) == [(0, 137), (12, 476), (20, 197),
                                       (21, 285)]
    assert CELL in by_name(BENCH["end_to_end"],
                           "serve_tokens_per_s")["workloads"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameters_and_bytes_from_the_file_s_own_keys():
    c = config()
    w = arith.widths(c)
    assert (w["mamba_layers"], w["attention_layers"],
            w["expert_layers"]) == (5, 1, 5)
    assert round(arith.mamba_layer_parameters(w) / 1e6, 2) == 109.64
    assert round(arith.attention_layer_parameters(w) / 1e6, 2) == 35.65
    assert round(arith.expert_layer_parameters(w, 0) / 1e6, 2) == 54.53
    assert arith.expert_parameters(w) == 2 * 2688 * 1024 == 5505024
    assert arith.parameters(w) == c["parameters_as_run"] == 4648163712
    # the uncut model, from the same functions: 40 : 40 : 8 of 88 layers
    # with 512 experts each and the whole vocabulary
    whole = {**w, "mamba_layers": 40, "expert_layers": 40,
             "attention_layers": 8, "n_layers": 88, "experts_held": 512,
             "vocab_size": 131072}
    assert round(arith.parameters(whole) / 1e9, 1) == 120.7
    # 5 layers x 128 heads x 64 x 128 x 4 B, and 5 x 3 x 10,240 x 4 B
    assert arith.state_bytes(w) == 5 * 128 * 64 * 128 * 4 == 20971520
    assert arith.tail_bytes(w) == 5 * 3 * 10240 * 4 == 614400
    assert arith.state_read_bytes(w, 40.5) == 40.5 * 20971520
    assert arith.ROW_BYTES == w["row_bytes"] == 1024
    assert arith.decode_read_bytes(1000.0, 99999) == 1024000.0
    # a token a layer: scores a group, their product with x a head, the
    # state's read and its update a head
    per = 8 * 2 * 128 * 128 + 128 * (2 * 128 * 64 + 4 * 64 * 128)
    assert arith.chunk_required_ops(w, 1) == 5 * per == 32768000
    assert arith.chunk_required_ops(w, 512) \
        == 512 * arith.chunk_required_ops(w, 1)
    assert round(arith.held_expert_bytes(w) / 1e9, 2) == 7.05
    step = arith.step_required_bytes(w, 64, 64 * 1000)
    assert [round(v / 1e9, 1) for v in step.values()] == [6.6, 2.7, 2.0, 0.1]


def test_the_program_s_pool_is_the_arithmetic_s_state_and_row():
    """A state block as the program stores it is the arithmetic's state
    and tails; a cached position is the arithmetic's row."""
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import mamba_moe
    c = config()
    cfg = common.model_config(c, "serve")
    assert cfg.kinds == ("mamba", "experts") * 3 + (
        "mamba", "attention", "experts", "mamba", "experts")
    pool = jax.eval_shape(lambda: mamba_moe.init_pool(
        cfg, 9, 128, state_blocks=5))
    w = arith.widths(c)
    assert pool["state"].size * 4 / 5 == arith.state_bytes(w)
    assert pool["conv"].size * 4 / 5 == arith.tail_bytes(w)
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
            "cell": CELLS[CELL], "traffic": tiny_mix("chat-closed96"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 60000.0},
                      "engine": {"decode_tokens": 6000, "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 3132.0}}}


def test_the_four_readers_by_hand(monkeypatch):
    """100 decode steps at 60 decoding sequences whose five `mamba2_step`
    calls took 5 ms a step; 40 runs of `jit__prefill` whose `mamba2_chunk`
    calls took 2 ms a run at 400 live tokens."""
    ctx = ctx_with(
        monkeypatch,
        {"mamba2_step": (500, 0.5), "mamba2_chunk": (200, 0.08)},
        {"jit__decode": (100, 3.0), "jit__prefill": (40, 2.4)})
    read = bench_run.read_layer_metric
    assert read("mamba2_step_ms", ctx) == pytest.approx(5.0)
    assert read("mamba2_chunk_ms", ctx) == pytest.approx(2.0)
    w = ctx["widths"]
    assert read("mamba2_step_roofline", ctx) == pytest.approx(
        100 * 60 * 20971520 / 819e9 / 5e-3)
    assert read("mamba2_chunk_roofline", ctx) == pytest.approx(
        100 * arith.chunk_required_ops(w, 400) / 197e12 / 2e-3)
    # a read-modify-write that ran at the memory's full bandwidth reads
    # 50 %: 60 states read and written at 819 GB/s take 3.073 ms
    ctx = ctx_with(monkeypatch, {"mamba2_step": (500, 0.30727)},
                   {"jit__decode": (100, 3.0)})
    assert read("mamba2_step_roofline", ctx) == pytest.approx(50.0,
                                                              rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the new
    kernels (the olmo replica's, recorded on a v5e), or no trace at all,
    and the reader returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    c = config()
    ctx = {"trace": trace.reduce(other), "config": c, "arith": arith,
           "widths": arith.widths(c),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"serve": {"decoding_context_tokens": 100.0},
                     "engine": {"decode_tokens": 30, "decode_steps": 10,
                                "prefill_tokens": 100, "prefill_chunks": 4,
                                "kv_bytes_per_token": 3132.0}}}
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
        assert name.endswith("_ms") or entry["unit"] == "%"
    # the cell is on every list that `ling-3.0-flash-vl.reason-closed96`
    # (the other cell that reports tokens per second alone) and a sibling
    # of another family are on, and on no list whose metric moves an
    # end-to-end metric it does not report
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    for m in BENCH["per_layer"]:
        lists = m.get("workloads", ())
        if m["moves"] not in reported:
            assert CELL not in lists, m["name"]
        elif ("ling-3.0-flash-vl.reason-closed96" in lists
              and "brumby-14b.docgen-closed24" in lists):
            assert CELL in lists, m["name"]
