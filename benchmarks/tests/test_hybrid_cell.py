"""CPU checks of what `ling-3.0-flash-vl.reason-closed96` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic, its control at the tiny size, the mix, and the six
kernel metrics over the readers the benchmark had, on made-up traces'
numbers. Entries are found by name, never by place. (The cell's rehearsal
is `test_benchmark.py::test_cell_rehearsal`, which finds it in
`BENCHMARK.json`; the family against its reference is
`tests/test_linear_latent.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import linear_latent_arith as arith
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "ling-3.0-flash-vl", "ling-3.0-flash-vl.reason-closed96"
NEW = ("kda_step_ms", "kda_step_roofline", "kda_chunk_ms",
       "kda_chunk_roofline", "latent_decode_ms", "latent_decode_roofline")
# the numbers of the catalog's `config` for Ling-3.0-flash-VL that size a
# layer, each under its key
PUBLISHED = {
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "use_qk_norm": True,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "short_conv_kernel_size": 4, "rotary_dim": 64,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "no_kda_lora": True, "linear_silu": True,
    "gated_attention_proj_granularity_type": "head_wise"}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"]) == [
        "first_k_dense_replace", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_experts"], c["vocab_size"], c["layers_from"]) == (
                7, 1, 128, 39296, 1)
    # the floors: a whole period after the dense layer, 8 experts, an
    # eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] \
        >= c["layer_group_size"]
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= 157184
    assert len(c["expert_swiglu_limit_list"]) == 42
    assert not any(c["expert_swiglu_limit_list"][1:8]
                   + c["share_expert_swiglu_limit_list"][1:8])
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert "shared by 4 chips" in c["deployment"]
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    assert any(a.startswith("KDA gate, weight scales") for a in c["assumed"])
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (64, 16384)
    kw = serve["engine_kwargs"]
    assert (kw["prefill_chunk"], kw["prefix_cache"]) == (512, False)
    # every slot's longest request of the mix fits its pages
    assert kw["cache_blocks"] * kw["block_size"] >= 64 * 11325
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}


def test_the_layers_that_run_are_a_dense_one_and_a_whole_period():
    from benchmarks.refs import linear_latent as ref
    assert ref.layer_kinds(config()) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("kda", "sparse"), ("latent", "sparse"), ("kda", "sparse"),
        ("kda", "sparse")]


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-closed96", 1)
    mix = load("benchmarks", "traffic", "reason-closed96.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 96)
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 1.0,
                                    "min": 128, "max": 12288}
    assert mix["output_tokens"] == {"median": 1024, "sigma": 0.6,
                                    "min": 256, "max": 4096}
    assert (mix["length_block"], mix["order_seed"], mix["check_requests"],
            mix["trace_s"]) == (24, 0, 4, 10)
    gen = traffic.serve_requests(mix, 2**31 + 7, 39296)
    block = [next(gen) for _ in range(24)]
    lengths = [len(r["prompt"]) + r["max_new_tokens"] for r in block]
    assert max(lengths) <= config()["program"]["serve"]["max_len"]
    assert max(lengths) <= 11325
    assert max(len(r["prompt"]) for r in block) == 7850
    assert max(int(r["prompt"].max()) for r in block) > 39000
    # above capacity (96 clients on 64 slots) the end-to-end metric is the
    # tokens completed; the tail between tokens spread 3 % over six seeds
    # (PERF.md, PR 40) and is printed in the run's stats, not judged
    assert CELL in by_name(BENCH["end_to_end"],
                           "serve_tokens_per_s")["workloads"]
    assert CELL not in by_name(BENCH["end_to_end"],
                               "tpot_p90_ms")["workloads"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameters_and_bytes_from_the_file_s_own_keys():
    c = config()
    w = arith.widths(c)
    assert (w["kda_layers"], w["latent_layers"], w["dense_layers"],
            w["sparse_layers"]) == (6, 1, 1, 6)
    assert round(arith.kda_layer_parameters(w) / 1e6, 1) == 52.6
    assert round(arith.latent_layer_parameters(w) / 1e6, 1) == 32.0
    assert arith.expert_parameters(w) == 3 * 768 * 2560
    assert arith.parameters(w) == c["parameters_as_run"] == 5169366976
    # 6 layers x 32 heads x 128 x 128 x 4 B
    assert arith.state_bytes(w) == 6 * 32 * 128 * 128 * 4 == 12582912
    assert arith.state_read_bytes(w, 40.5) == 40.5 * 12582912
    assert arith.LATENT_ROW_BYTES == w["latent_row_values"] \
        * w["value_bytes"] == 1152
    assert arith.decode_read_bytes(1000.0, 99999) == 1152000.0
    # two reads and an update of 128 x 128 a head a layer a token
    assert arith.chunk_required_ops(w, 1) == 6 * 32 * 6 * 128 * 128
    assert arith.chunk_required_ops(w, 512) \
        == 512 * arith.chunk_required_ops(w, 1)
    assert round(arith.held_expert_bytes(w) / 1e9, 2) == 9.06
    step = arith.step_required_bytes(w, 64, 64 * 2800)
    assert [round(v / 1e9, 1) for v in step.values()] == [5.7, 1.6, 1.1, 0.2]


def test_the_program_s_pool_is_the_arithmetic_s_state_and_row():
    """A state block as the program stores it is the arithmetic's state
    plus the tails in float32 bytes; a latent row is stored in whole lane
    tiles (1,536 B) against the arithmetic's 1,152."""
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import linear_latent
    c = config()
    cfg = common.model_config(c, "serve")
    pool = jax.eval_shape(lambda: linear_latent.init_pool(
        cfg, 9, 128, state_blocks=5))
    w = arith.widths(c)
    assert pool["state"].size * 4 / 5 == arith.state_bytes(w)
    assert pool["conv"].size * 4 / 5 == 2 * arith.tail_bytes(w)
    assert pool["latent"].size * 4 / (9 * 128) == 1536


def test_the_control_is_not_correct(tmp_path):
    """The cell's control at the tiny size: every KDA state rounded to
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
            "cell": CELLS[CELL], "traffic": tiny_mix("reason-closed96"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 180000.0},
                      "engine": {"decode_tokens": 6000, "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 2304.0}}}


def test_the_six_readers_by_hand(monkeypatch):
    """100 decode steps at 60 decoding sequences whose six `kda_step`
    calls took 4 ms a step and whose one `latent_decode` took 0.5 ms over
    180,000 cached positions; 40 runs of `jit__prefill` whose `kda_chunk`
    calls took 2 ms a run at 400 live tokens."""
    ctx = ctx_with(
        monkeypatch,
        {"kda_step": (600, 0.4), "latent_decode": (100, 0.05),
         "kda_chunk": (240, 0.08)},
        {"jit__decode": (100, 3.0), "jit__prefill": (40, 2.4)})
    read = bench_run.read_layer_metric
    assert read("kda_step_ms", ctx) == pytest.approx(4.0)
    assert read("latent_decode_ms", ctx) == pytest.approx(0.5)
    assert read("kda_chunk_ms", ctx) == pytest.approx(2.0)
    w = ctx["widths"]
    assert read("kda_step_roofline", ctx) == pytest.approx(
        100 * 60 * 12582912 / 819e9 / 4e-3)
    assert read("latent_decode_roofline", ctx) == pytest.approx(
        100 * 180000 * 1152 / 819e9 / 0.5e-3)
    assert read("kda_chunk_roofline", ctx) == pytest.approx(
        100 * arith.chunk_required_ops(w, 400) / 197e12 / 2e-3)
    # a read-modify-write that ran at the memory's full bandwidth reads
    # 50 %: 60 states read and written at 819 GB/s take 1.844 ms
    ctx = ctx_with(monkeypatch, {"kda_step": (600, 0.18436)},
                   {"jit__decode": (100, 3.0)})
    assert read("kda_step_roofline", ctx) == pytest.approx(50.0, rel=1e-3)


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
                                "kv_bytes_per_token": 2304.0}}}
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
    # the cell is on every list that both its serving siblings of other
    # families are on and that moves an end-to-end metric it reports, and
    # on no list whose metric moves one it does not report
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    for m in BENCH["per_layer"]:
        lists = m.get("workloads", ())
        if m["moves"] not in reported:
            assert CELL not in lists, m["name"]
        elif ("glm-5.2.docqa-closed24" in lists
              and "brumby-14b.docgen-closed24" in lists):
            assert CELL in lists, m["name"]
