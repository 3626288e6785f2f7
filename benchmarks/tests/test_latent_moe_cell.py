"""CPU checks of what `kanana-2-30b-a3b.pretrain-8k` brought to the
benchmark: the configuration's file against the published keys, its own
arithmetic, the mix, the tiny cell end to end and its control, and the
new readers on made-up traces' numbers. (The family against its reference
is `tests/test_latent_moe_train.py`.)"""

import json
import os

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import latent_moe_arith, peaks, spans
from benchmarks.harness.common import ROOT, merged
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

CELL = "kanana-2-30b-a3b.pretrain-8k"
NEW = ("experts_train_ms", "experts_train_roofline",
       "expert_load_max_over_mean")
APPENDED = ("prefetch_share", "train_step_device_ms", "mfu_pct",
            "flash_roofline", "flash_fwd_ms", "flash_bwd_ms",
            "fused_xent_ms", "flash_fwd_runs_per_layer", "host_batch_share",
            "dispatch_enqueue_ms", "idle_owned_share")
# the catalog's `config` for kanana-2-30b-a3b-instruct-2601
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


def config():
    return load("benchmarks", "configs", "kanana-2-30b-a3b.json")


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"])
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 16, 16128)
    assert c["vocab_size"] * 8 == c["vocab_rows_padded"] == 129024
    entry = [e for e in BENCH["configs"] if e["name"] == c["name"]][0]
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/kanana-2-30b-a3b.json"
    assert "each layer shared by 8 chips" in c["deployment"]
    assert "dropless" in c["guarantees"]
    assert any("gamma 0.001" in a for a in c["assumed"])
    assert any("112 absent experts" in d for d in c["departures"])
    assert c["program"]["entry"] == {
        "config": "models.latent_sparse_moe:from_published",
        "trainer": "train.spmd:make_latent_moe_trainer",
        "loss": "train.spmd:latent_moe_loss_fn"}
    assert set(c["program"]["train"]) == {"flash_block_q", "flash_block_kv"}
    assert "rematerialised" in c["program"]["what"]
    assert c["tolerances"]["loss_abs"] > 0 and c["tolerances"]["why"]
    assert 0 < c["tolerances"]["loss_position_abs"] < 0.01
    assert 0 < c["tolerances"]["grad_rel_floor"] \
        < c["tolerances"]["grad_rel"] < 1
    assert c["tolerances"]["floor_leaves"] == ["we_gate", "we_up", "we_down"]


def test_the_file_s_assumed_numbers_are_the_program_s_constants():
    """gamma and the embedding's scale are constants of the program, not
    arguments; the file states them, and here the two cannot part."""
    from ray_tpu.models import latent_sparse_moe as lsm
    c = config()
    assert c["router_bias_update_rate"] == lsm.BIAS_UPDATE_RATE == 0.001
    assert c["embedding_init_scale"] == lsm.EMBED_INIT == 1.0


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b", "pretrain-8k", 1)
    mix = load("benchmarks", "traffic", "pretrain-8k.json")
    assert {k: mix[k] for k in (
        "driver", "seq_len", "batch", "unroll", "mesh", "prefetch_depth",
        "token_distribution", "warm_dispatches", "check_sequences",
        "trace_s", "window_s")} == {
        "driver": "train", "seq_len": 8192, "batch": 2, "unroll": 2,
        "mesh": {"data": 1}, "prefetch_depth": 2,
        "token_distribution": {"zipf_exponent": 1.1}, "warm_dispatches": 2,
        "check_sequences": 1, "trace_s": 6, "window_s": 20}
    entry = [m for m in BENCH["end_to_end"]
             if m["name"] == "train_tokens_per_s"][0]
    assert CELL in entry["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(APPENDED + NEW)


def test_operations_and_parameters_from_the_file_s_own_keys():
    c = config()
    w = latent_moe_arith.widths(c)
    assert (w["qk_dim"], w["v_dim"], w["head_dim"]) == (192, 128, 160)
    assert (w["dense_layers"], w["sparse_layers"]) == (1, 5)
    # 2048 x 6144 + 2048 x 576 + 512 x 8192 + 4096 x 2048 = 26.3 M
    assert latent_moe_arith.attention_params(w) == 26345472
    assert latent_moe_arith.routed_experts_per_token(w) == 0.75
    # 295 M in a token's matrix products; 688 M on the chip
    assert round(latent_moe_arith.active_matmul_params(w) / 1e6) == 295
    assert round(latent_moe_arith.held_params(w) / 1e6, 1) == 687.9
    per_token = latent_moe_arith.train_flops_per_token(w, 8192)
    attn = 3 * 640 * 32 * 4096 * 6
    assert per_token == pytest.approx(
        6 * latent_moe_arith.active_matmul_params(w) + attn)
    assert round(per_token / 1e9, 2) == 3.28
    # the six-matmul count at the mean width is the count at 192 and 128
    tri = 8192 * 8192 / 2
    assert latent_moe_arith.flash_attention_flops(
        2, 8192, 32, w["head_dim"], 1) == pytest.approx(
        2 * 32 * tri * (2 * (192 + 128) + 4 * (192 + 128)))
    assert latent_moe_arith.expert_train_flops(w, 1) == 18 * 2048 * 768


def test_the_program_holds_the_arithmetic_s_parameters():
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import latent_sparse_moe as lsm
    c = config()
    cfg = common.model_config(c, "train", **c["program"]["train"])
    assert (cfg.q_rank, cfg.index_topk, cfg.has_indexer) == (None, None,
                                                            False)
    assert cfg.kinds == (("dense", "none"),) + (("sparse", "none"),) * 5
    assert (cfg.router_width, cfg.held_count, cfg.shared_experts) == (
        128, 16, 2)
    shapes = jax.eval_shape(lambda: lsm.init_params(jax.random.key(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == latent_moe_arith.held_params(latent_moe_arith.widths(c))


def test_the_tiny_cell_is_correct_and_its_control_is_not(tmp_path):
    """The cell's driver at the tiny size, traced, and then its control:
    the routed experts' operands on the float8 grid (`expert_round`, the
    program's test-only field, told through the configuration). Every
    step still runs; the loss against the reference's is what fails."""
    cell = CELLS[CELL]
    cfg = tiny_config(cell["config"])
    spec = {"cell": cell, "config": cfg, "mix": tiny_mix(cell["traffic"]),
            "trace": True, "scratch": str(tmp_path), "bench": BENCH}
    out = rehearse(spec, tmp_path)
    assert out["result"]["correct"], out["result"]["problems"]
    stats = out["result"]["stats"]["loop"]
    assert out["metrics"]["expert_load_max_over_mean"] == pytest.approx(
        stats["expert_load_max"] / stats["expert_load_mean"])
    assert stats["expert_pairs_routed"] == stats["steps"] * 2 * 128 * 4 * 2
    assert 0 < stats["expert_pairs_here"] < stats["expert_pairs_routed"]
    assert stats["first_step"]["expert_load_max"] >= \
        stats["first_step"]["expert_load_mean"] > 0
    assert stats["last_step"]["router_bias_abs_max"] > 0

    assert config()["control"]["program"]["model"] == {
        "expert_round": "float8_e4m3fn"}
    spec = {**spec, "config": merged(cfg, cfg["control"]), "trace": False}
    result = rehearse(spec, tmp_path)["result"]
    assert not result["correct"] and result["failed"] == 0
    assert len(result["problems"]) == 1 and "reference" in \
        result["problems"][0]
    checks = {c[0]: c for c in result["checks"]}
    sample = checks["program_loss_minus_reference_median_by_position"]
    assert sample[1] > 3 * sample[2]


def test_position_losses_reads_every_position_through_the_mask():
    """A loss function that is a masked mean, as the program's is: one
    call a block, the last block past the end trimmed."""
    import jax.numpy as jnp
    from benchmarks.harness.train_cell import position_losses
    calls = []

    def loss_fn(params, batch, cfg, mesh):
        calls.append(1)
        nll = params * batch["inputs"].astype(jnp.float32)
        return jnp.sum(nll * batch["mask"]) / jnp.maximum(
            jnp.sum(batch["mask"]), 1.0)

    inputs = np.arange(2 * 7, dtype=np.int32).reshape(2, 7)
    every = position_losses(loss_fn, None, None, inputs.shape, block=4)
    got = every(jnp.float32(0.5), {"inputs": inputs})
    assert got.shape == (2, 7) and (got == 0.5 * inputs).all()
    assert len(calls) == 1                      # traced once, run 4 times


def ctx_with(monkeypatch, kernels, modules, loop_stats):
    monkeypatch.setattr(spans, "summary", lambda ctx: {"kernels": kernels})
    c = config()
    return {"trace": {"modules": modules}, "config": c, "cell": CELLS[CELL],
            "traffic": load("benchmarks", "traffic", "pretrain-8k.json"),
            "arith": latent_moe_arith, "widths": latent_moe_arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"loop": loop_stats}}


def test_the_three_readers_by_hand(monkeypatch):
    """Four runs of the fused dispatch (eight steps) whose 40 forward
    calls, 40 dX and 40 dW calls took 0.16 + 0.24 + 0.12 s, in a run that
    counted 61,440 pairs a step over 20 steps."""
    ctx = ctx_with(
        monkeypatch,
        {"experts_grouped_train": (40, 0.16), "experts_grouped_dx":
         (40, 0.24), "experts_grouped_dw": (40, 0.12),
         "flash_fwd": (48, 1.0)},
        {"jit_multi": (4, 6.0)},
        {"steps": 20, "expert_pairs_here": 20 * 61440,
         "expert_load_max": 20 * 1920, "expert_load_mean": 20 * 768.0})
    assert bench_run.read_layer_metric("experts_train_ms", ctx) \
        == pytest.approx(65.0)
    assert bench_run.read_layer_metric("experts_train_roofline", ctx) \
        == pytest.approx(100 * 18 * 2048 * 768 * 61440 / 197e12 / 65e-3)
    assert bench_run.read_layer_metric("expert_load_max_over_mean", ctx) \
        == 2.5


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_or_counters_gives_nothing(
        name, monkeypatch):
    """The parent's side of a comparison: a trace with none of the new
    kernels and a loop that counts nothing, or no trace at all."""
    ctx = ctx_with(monkeypatch, {"flash_fwd": (48, 1.0)},
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


def test_the_gradient_check_at_the_tiny_size_and_its_control():
    """`tools/grad_check.py` on the tiny cell: the program's gradient
    agrees with the reference's on every leaf, and with the routed
    experts' operands on the float8 grid (the control) it does not, by the
    experts' leaves."""
    from benchmarks.tools import grad_check
    cell = CELLS[CELL]
    cfg, mix = tiny_config(cell["config"]), tiny_mix(cell["traffic"])
    out = grad_check.check(cell, cfg, mix, seed=3)
    assert out["correct"], out["problems"]
    assert max(out["leaves"].values()) < cfg["tolerances"]["grad_rel"]
    assert set(out["floors"]) == {"we_gate", "we_up", "we_down"}
    assert all(len(v) == 2 for v in out["layers"].values())
    assert out["whole"] < cfg["tolerances"]["grad_rel"]
    assert {"w_q", "wkv_b", "router", "we_gate", "we_down", "ws_up",
            "head", "embed"} <= set(out["leaves"])
    assert "router_bias" not in out["leaves"]       # it takes no gradient
    bad = grad_check.check(cell, cfg, mix, seed=3, control=True)
    assert not bad["correct"]
    assert bad["floors"]["we_gate"] > 3 * cfg["tolerances"]["grad_rel_floor"]
    assert any(p.startswith("we_gate: no layer") for p in bad["problems"])
