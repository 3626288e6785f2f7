"""CPU checks of what `mellum2-12b-a2.5b.longctx-32k` brought to the
benchmark: the configuration's file against the published keys, its own
arithmetic, the mix, the tiny cell end to end, its control and its four
other wrong programs, and the new metrics on made-up traces' numbers. (The
family against its reference is `tests/test_window_moe_train.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import peaks, spans
from benchmarks.harness import window_moe_train_arith as arith
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

CELL = "mellum2-12b-a2.5b.longctx-32k"
NEW = ("flash_band_ms", "flash_band_roofline", "flash_full_roofline")
APPENDED = ("prefetch_share", "train_step_device_ms", "mfu_pct",
            "flash_fwd_ms", "flash_bwd_ms", "fused_xent_ms",
            "host_batch_share", "dispatch_enqueue_ms", "idle_owned_share",
            "experts_train_ms", "experts_train_roofline",
            "expert_load_max_over_mean", "train_mixer_ms", "train_ffn_ms",
            "train_head_ms", "train_optimizer_ms", "train_recompute_ms",
            "train_compiler_ms")
# the catalog's `config` for Mellum2-12B-A2.5B-Instruct
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
WRONG = ("a_window_layer_reads_the_whole_triangle", "a_band_one_tile_short",
         "yarn_left_out", "one_expert_of_a_token_s_left_out")


def config():
    return load("benchmarks", "configs", "mellum2-12b-a2.5b.json")


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"])
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 24576)
    assert c["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert c["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    entry = [e for e in BENCH["configs"] if e["name"] == c["name"]][0]
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/mellum2-12b-a2.5b.json"
    assert "each layer shared by 4 chips" in c["deployment"]
    assert "seven stages" in c["deployment"]
    assert "a quarter of deployment load" in c["deployment"]
    assert "dropless" in c["guarantees"] and "64 wide" in c["guarantees"]
    assert any("split halves" in a for a in c["assumed"])
    assert any("softmax over all 64" in a for a in c["assumed"])
    assert any("48 absent experts" in d for d in c["departures"])
    assert any("multi-token" in d for d in c["departures"])
    assert c["program"]["entry"] == {
        "config": "models.window_moe_train:from_published",
        "trainer": "train.spmd:make_window_moe_trainer",
        "loss": "train.spmd:window_moe_loss_fn"}
    assert set(c["program"]["train"]) == {"flash_block_q", "flash_block_kv",
                                          "expert_chunk"}
    assert c["control"]["program"]["model"] == {
        "expert_round": "float8_e4m3fn"}
    assert set(WRONG) == {k for k, v in c["wrong_programs"].items()
                          if isinstance(v, dict)}
    assert c["tolerances"]["loss_abs"] > 0 and c["tolerances"]["why"]
    assert 0 < c["tolerances"]["loss_position_abs"] < 0.1


def test_the_file_s_assumed_numbers_are_the_program_s_constants():
    from benchmarks.harness import common
    from ray_tpu.models import window_moe_train as wmt
    c = config()
    assert c["embedding_init_scale"] == wmt.EMBED_INIT == 1.0
    cfg = common.model_config(c, "train", **c["program"]["train"])
    assert cfg.attn_logit_std == c["attn_logit_std"] == 2.4
    assert cfg.router_tied_blocks == c["router_init_tied_blocks"] == 4
    assert cfg.kinds == ("window", "window", "window", "full")
    assert (cfg.window, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        1024, 32, 4, 128)
    assert (cfg.router_width, cfg.held_count, cfg.experts_per_token) == (
        64, 16, 8)
    assert cfg.rope_window == wmt.RotarySpec(theta=500000.0)
    assert cfg.rope_full == wmt.RotarySpec(500000.0, 16.0, 8192, 32.0, 1.0,
                                           1.2772588722239782)


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b", "longctx-32k", 1)
    assert "1/4 of deployment load" in cell["why"]
    mix = load("benchmarks", "traffic", "longctx-32k.json")
    assert {k: mix[k] for k in (
        "driver", "seq_len", "batch", "unroll", "mesh", "prefetch_depth",
        "token_distribution", "warm_dispatches", "check_sequences",
        "check_by", "trace_s", "window_s")} == {
        "driver": "train", "seq_len": 32768, "batch": 1, "unroll": 2,
        "mesh": {"data": 1}, "prefetch_depth": 2,
        "token_distribution": {"zipf_exponent": 1.1}, "warm_dispatches": 2,
        "check_sequences": 1, "check_by": "position", "trace_s": 8,
        "window_s": 20}
    entry = [m for m in BENCH["end_to_end"]
             if m["name"] == "train_tokens_per_s"][0]
    assert entry["workloads"][-1] == CELL
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(APPENDED + NEW)
    # one layer in four runs the plain kernels' names here: the readers
    # that multiply by every layer would read four times too high
    assert not {"flash_roofline", "flash_fwd_runs_per_layer"} & listed


def test_operations_and_parameters_from_the_file_s_own_keys():
    w = arith.widths(config())
    assert (w["window_layers"], w["full_layers"], w["window"]) == (3, 1, 1024)
    # 2 x 2304 x 4096 + 2 x 2304 x 512 = 21.2 M; an expert 6.19 M
    assert arith.attention_params(w) == 21233664
    assert arith.expert_params(w) == 3 * 2304 * 896 == 6193152
    assert arith.routed_experts_per_token(w) == 2.0
    # 4 x (21.23 + 0.15 + 2 x 6.19) + 56.6 = 191.7 M in a token's products;
    # 4 x (21.23 + 0.15 + 16 x 6.19) + 113.2 = 595 M on the chip
    assert round(arith.active_matmul_params(w) / 1e6, 1) == 191.7
    assert round(arith.held_params(w) / 1e6, 1) == 595.2
    t = 32768
    assert arith.full_pairs(t) == t * (t + 1) // 2
    assert arith.band_pairs(t, 1024) == sum(
        min(i + 1, 1024) for i in range(t)) == 33030656
    assert arith.band_pairs(100, 1024) == arith.full_pairs(100)
    per_token = arith.train_flops_per_token(w, t)
    pairs = (arith.full_pairs(t) + 3 * arith.band_pairs(t, 1024)) / t
    assert per_token == pytest.approx(
        6 * arith.active_matmul_params(w) + 6 * 2 * 128 * 32 * pairs)
    assert round(per_token / 1e9, 2) == 2.1
    # the band is worth half of it: were the window layers to walk the
    # triangle, attention alone would be 3.2 GFLOP a token
    assert round(4 * 6 * 2 * 128 * 32 * arith.full_pairs(t) / t / 1e9, 1) \
        == 3.2
    assert arith.band_attention_flops(w, t) == pytest.approx(
        3 * 6 * 2 * 128 * 32 * 33030656)
    assert arith.full_attention_flops(w, t) == pytest.approx(
        6 * 2 * 128 * 32 * t * (t + 1) / 2)
    assert arith.expert_train_flops(w, 1) == 18 * 2304 * 896


def test_the_program_holds_the_arithmetic_s_parameters():
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import window_moe_train as wmt
    c = config()
    cfg = common.model_config(c, "train", **c["program"]["train"])
    shapes = jax.eval_shape(lambda: wmt.init_params(jax.random.key(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == arith.held_params(arith.widths(c))


def test_the_tiny_cell_is_correct_and_its_control_is_not(tmp_path):
    """The cell's driver at the tiny size, traced, and then its control:
    the routed experts' operands on the float8 grid. Every step still
    runs; the loss against the reference's is what fails."""
    cell = CELLS[CELL]
    cfg = tiny_config(cell["config"])
    spec = {"cell": cell, "config": cfg, "mix": tiny_mix(cell["traffic"]),
            "trace": True, "scratch": str(tmp_path), "bench": BENCH}
    out = rehearse(spec, tmp_path)
    assert out["result"]["correct"], out["result"]["problems"]
    stats = out["result"]["stats"]["loop"]
    assert out["metrics"]["expert_load_max_over_mean"] == pytest.approx(
        stats["expert_load_max"] / stats["expert_load_mean"])
    assert stats["expert_pairs_routed"] == stats["steps"] * 2 * 256 * 4 * 4
    # the router starts as four equal blocks: a quarter of the pairs here
    # (but a token whose two best columns score within a rounding)
    assert stats["first_step"]["expert_pairs_here"] * 4 == pytest.approx(
        stats["first_step"]["expert_pairs_routed"], rel=0.005)
    assert 0 < stats["expert_pairs_here"] < stats["expert_pairs_routed"]

    spec = {**spec, "config": merged(cfg, cfg["control"]), "trace": False}
    result = rehearse(spec, tmp_path)["result"]
    assert not result["correct"] and result["failed"] == 0
    assert len(result["problems"]) == 1 and "reference" in \
        result["problems"][0]
    checks = {c[0]: c for c in result["checks"]}
    sample = checks["program_loss_minus_reference_median_by_position"]
    assert sample[1] > 3 * sample[2]


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_program_is_not_correct_at_the_tiny_size(wrong, tmp_path):
    """Each of the file's four other wrong programs through the cell's
    driver: the program reads the file with one key changed, the
    reference the file as it is."""
    from benchmarks.tools.window_train_readings import programs
    cell = CELLS[CELL]
    cfg = tiny_config(cell["config"])
    assert programs(cfg)["sound"] == cfg and wrong in programs(cfg)
    # the driver hands its one configuration to program and reference
    # alike, so the wrong program is read through the readings' tool
    import jax
    import numpy as np
    from benchmarks.harness import common, traffic
    from benchmarks.harness.train_cell import position_losses
    from benchmarks.refs import window_moe_train as ref
    from ray_tpu.models import window_moe_train as wmt
    from ray_tpu.train import spmd
    mix = tiny_mix(cell["traffic"])
    sound = common.model_config(cfg, "train", **cfg["program"]["train"])
    params = wmt.init_params(jax.random.key(3), sound)
    batch = next(traffic.train_batches(mix, 3, sound.vocab_size))
    sample = {k: v[:1] for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.token_losses(
            params, sample["inputs"], sample["targets"], cfg))
        gaps = {}
        for name in ("sound", wrong):
            file = programs(cfg)[name]
            got = position_losses(
                spmd.window_moe_loss_fn,
                common.model_config(file, "train",
                                    **file["program"]["train"]),
                None, sample["inputs"].shape)(params, sample)
            gaps[name] = float(np.median(np.abs(got - want)))
    limit = cfg["tolerances"]["loss_position_abs"]
    assert gaps["sound"] <= limit < gaps[wrong] / 3


def ctx_with(monkeypatch, kernels, modules, loop_stats):
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    c = config()
    return {"trace": {"modules": modules}, "config": c, "cell": CELLS[CELL],
            "traffic": load("benchmarks", "traffic", "longctx-32k.json"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"loop": loop_stats}}


def test_the_three_readers_by_hand(monkeypatch):
    """Four runs of the fused dispatch (eight steps) whose 24 banded
    forward, dQ and dK/dV calls took 0.24 + 0.36 + 0.48 s and whose 8 full
    ones 0.8 + 1.2 + 1.6 s."""
    ctx = ctx_with(
        monkeypatch,
        {"flash_fwd_band": (24, 0.24), "flash_dq_band": (24, 0.36),
         "flash_dkv_band": (24, 0.48), "flash_fwd": (8, 0.8),
         "flash_dq": (8, 1.2), "flash_dkv": (8, 1.6)},
        {"jit_multi": (4, 9.0)}, {"steps": 20})
    assert bench_run.read_layer_metric("flash_band_ms", ctx) \
        == pytest.approx(135.0)
    assert bench_run.read_layer_metric("flash_band_roofline", ctx) \
        == pytest.approx(100 * 3 * 6 * 2 * 128 * 32 * 33030656
                         / 197e12 / 135e-3)
    assert bench_run.read_layer_metric("flash_full_roofline", ctx) \
        == pytest.approx(100 * 6 * 2 * 128 * 32 * 32768 * 32769 / 2
                         / 197e12 / 450e-3)
    assert bench_run.read_layer_metric("flash_fwd_ms", ctx) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of a comparison: a trace with none of the
    kernels the metric reads, or no trace at all."""
    ctx = ctx_with(monkeypatch, {"xent_fwd": (48, 1.0)},
                   {"jit_multi": (4, 6.0)}, {"steps": 20})
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def test_new_metrics_are_entries_with_files():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == "kernels"
