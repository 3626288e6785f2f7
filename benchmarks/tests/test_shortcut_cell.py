"""CPU checks of what `longcat-flash-chat.agent-closed96` brought to the
benchmark: the configuration's file against the catalog's published keys,
its own arithmetic against the issue's counts, its control at the tiny
size, the mix, the ten new metrics over the readers the benchmark had and
the one this cell brings (`layer_metrics/scope_ms.py`), on made-up
traces' numbers and on recorded v5e traces. Entries are found by name,
never by place, and nothing here counts the benchmark's cells. (The
cell's rehearsal is `test_benchmark.py::test_cell_rehearsal`, which finds
it in `BENCHMARK.json`; the family against its reference is
`tests/test_shortcut_moe.py`.)"""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import peaks, spans, trace, traffic
from benchmarks.harness import shortcut_moe_arith as arith
from benchmarks.harness.common import ROOT, merged
from benchmarks.layer_metrics import (device_parts, scope_ms,
                                      span_attr_roofline, tick_events)
from benchmarks.tests.test_benchmark import (BENCH, CELLS, load, rehearse,
                                             tiny_config, tiny_mix)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "longcat-flash-chat", "longcat-flash-chat.agent-closed96"
KERNELS = ("longcat_latent_decode_ms", "longcat_latent_decode_roofline",
           "longcat_latent_chunk_ms", "longcat_latent_chunk_roofline",
           "longcat_experts_step_ms", "longcat_experts_step_roofline",
           "longcat_experts_chunk_ms", "longcat_experts_chunk_roofline")
SCOPES = ("longcat_shortcut_dense_ms", "longcat_shortcut_experts_ms")
NEW = KERNELS + SCOPES
PARTS_TRACE = os.path.join(HERE, "data", "v5e_parts.xplane.pb")
SHORTCUT_TRACE = os.path.join(HERE, "data", "v5e_shortcut.xplane.pb")
# the catalog's `config` for LongCat-Flash-Chat, each under its key
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def config():
    return load("benchmarks", "configs", f"{CONFIG}.json")


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_file_holds_the_published_widths_and_says_what_it_cut():
    c = config()
    differs = sorted(k for k, v in PUBLISHED.items() if c[k] != v)
    assert differs == sorted(c["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in c["reduced"]}
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"],
            c["layers_from"], c["experts_held_from"]) == (4, 16, 16384, 0, 0)
    # the floors: four layers, at least 8 experts, an eighth of the rows
    assert c["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert c["router_width"] == 512 + 256 == 768
    entry = by_name(BENCH["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/"
        "main/config.json")
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    for said in ("each layer shared by 32 chips", "routed experts 0-15 of 512",
                 "No width is cut", "six further stages", "5,172,749,312",
                 "1/32 of deployment load"):
        assert said in c["deployment"], said
    for said in ("768 wide", "dropless", "identity experts exact",
                 "bit-identical", "nothing approximate"):
        assert said in c["guarantees"], said
    assert all(isinstance(a, str) and a for a in c["assumed"] +
               c["departures"])
    for said in ("not renormalised", "after W_qb", "before W_kvb",
                 "interleaved pairs", "untied", "e_score_correction_bias",
                 "order of the four norms"):
        assert any(said in a for a in c["assumed"]), said
    assert any("applied at use" in d for d in c["departures"])
    serve = c["program"]["serve"]
    assert (serve["slots"], serve["max_len"]) == (64, 4096)
    kw = serve["engine_kwargs"]
    assert (kw["block_size"], kw["prefill_chunk"], kw["prefill_buckets"],
            kw["prefix_cache"], kw["cache_blocks"]) == (
                128, 512, [128, 512], False, 2049)
    assert set(c["tolerances"]) == {"logprob_max_abs", "logprob_mean_abs",
                                    "why"}
    assert c["control"]["program"]["model"] == {"cache_round": "int8"}


def test_the_cell_and_its_mix_are_the_issue_s():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agent-closed96", 1)
    mix = load("benchmarks", "traffic", "agent-closed96.json")
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve", "closed", 96)
    assert mix["prompt_tokens"] == {"median": 1536, "sigma": 0.8,
                                    "min": 128, "max": 3072}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.7,
                                    "min": 32, "max": 1024}
    assert (mix["length_block"], mix["order_seed"], mix["check_requests"],
            mix["trace_s"], mix["ramp_requests"], mix["ramp_s"],
            mix["warm_new_tokens"], mix["request_timeout_s"]) == (
                24, 0, 4, 10, 160, 300, 4, 600)
    gen = traffic.serve_requests(mix, 2**31 + 7, 16384)
    block = [next(gen) for _ in range(24)]
    assert (min(len(r["prompt"]) for r in block),
            max(len(r["prompt"]) for r in block)) == (301, 3072)
    # ids over the rows of the vocabulary held, and over all of them
    assert 16000 < max(int(r["prompt"].max()) for r in block) < 16384
    # later blocks pair the lengths anew: the longest prompt can meet the
    # longest output, and `max_len` holds exactly that
    assert max(p + o for blk, _ in zip(traffic.serve_blocks(mix), range(50))
               for p, o, _ in blk) <= 3072 + 1024 \
        == config()["program"]["serve"]["max_len"]
    assert traffic.check_plan(mix) == [(0, 137), (7, 535), (23, 332),
                                       (11, 392)]
    assert CELL in by_name(BENCH["end_to_end"],
                           "serve_tokens_per_s")["workloads"]


def test_parameters_and_bytes_are_the_issue_s_counts():
    c = config()
    w = arith.widths(c)
    assert arith.latent_block_parameters(w) == 90572800
    assert arith.dense_mlp_parameters(w) == 226492416
    assert arith.layer_parameters_outside_experts(w) == 638874368
    assert arith.expert_parameters(w) == 37748736
    assert arith.parameters(w) == c["parameters_as_run"] == 5172749312
    # the published model from the same functions: 560.66 B, and 27.1 B
    # active at the 8 experts a token that a level router gives
    assert round(arith.parameters(w, 512, 28, 131072) / 1e9, 2) == 560.66
    assert round((28 * (arith.layer_parameters_outside_experts(w)
                        + 8 * arith.expert_parameters(w))
                  + 131072 * 6144) / 1e9, 1) == 27.1
    assert arith.ROW_BYTES == w["row_bytes"] == 8 * 576 * 2 == 9216
    assert arith.stored_row_bytes(w) == 12288
    serve = c["program"]["serve"]
    pages = arith.pool_pages(serve["slots"], serve["max_len"],
                             serve["engine_kwargs"]["block_size"])
    assert pages == serve["engine_kwargs"]["cache_blocks"] == 2049
    assert round(pages * 128 * arith.stored_row_bytes(w) / 1e9, 2) == 3.22
    assert arith.decode_read_bytes(1000.0, 99999) == 9216000.0
    # 64 rows x 12 choices over 768 outputs, 16 held: 16 pairs, which
    # reach 10.2 experts; a chunk of 512 reaches every one
    assert arith.expected_held_pairs(w, 64) == 16.0
    assert arith.expected_held_pairs(w, 512) == 128.0
    assert round(arith.expected_experts_reached(w, 64), 2) == 10.16
    assert round(arith.expected_experts_reached(w, 512), 2) == 15.99
    assert round(arith.held_expert_bytes(w) / 1e9, 2) == 4.83
    assert arith.expected_expert_bytes(w, 1e9) == arith.held_expert_bytes(w)
    # a chunk of 512 at position 600: the expanded form is the lesser
    # (keys and values of 1,112 rows rebuilt, 40,960 a query a key)
    seen = 512 * 600 + 512 * 513 // 2
    assert arith.chunk_attention_ops(w, 600, 512) == 8 * (
        2 * 64 * 320 * seen + 2 * 1112 * 512 * 64 * 256)
    # a short last chunk deep in a prompt: absorbed (139,264 a query a
    # key, the 64 queries through the key up-projection and back)
    assert arith.chunk_attention_ops(w, 3000, 64) == 8 * (
        139264 * (64 * 3000 + 64 * 65 // 2) + 2 * 64 * 64 * 512 * 256)
    step = arith.step_required_bytes(w, 64, 64 * 1900)
    assert [round(v / 1e9, 2) for v in step.values()] == [
        3.07, 5.11, 0.2, 1.12]
    # no less than 11.6 ms a step at 819 GB/s
    assert round(sum(step.values()) / 819e9 * 1e3, 1) == 11.6


def test_the_program_s_pool_is_the_arithmetic_s_row():
    import jax
    from benchmarks.harness import common
    from ray_tpu.models import shortcut_moe
    c = config()
    cfg = common.model_config(c, "serve")
    assert (cfg.n_layers, cfg.held_count, cfg.router_width, cfg.identity_from,
            cfg.experts_per_token, cfg.q_scale, cfg.routed_scale) == (
                4, 16, 768, 512, 12, 2.0, 6.0)
    assert cfg.kv_scale == 12 ** 0.5 and cfg.rope_theta == 1e7
    kw = c["program"]["serve"]["engine_kwargs"]
    pool = jax.eval_shape(lambda: shortcut_moe.init_pool(
        cfg, kw["cache_blocks"], kw["block_size"]))
    assert pool["latent"].shape == (8, 2049, 128, 1, 384)
    w = arith.widths(c)
    assert pool["latent"].size * 4 / (2049 * 128) \
        == arith.stored_row_bytes(w)
    # the reference draws the tree the program serves, at the counts
    from benchmarks.refs import shortcut_moe as ref
    drawn = jax.eval_shape(lambda k: ref.init_params(k, c),
                           jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(drawn)) \
        == c["parameters_as_run"]


def test_the_control_is_not_correct(tmp_path):
    """The cell's control at the tiny size: every cache row on the int8
    grid (`cache_round`, the latent mixer's test-only field). Every
    request still gets its tokens; the logprobs are what fails."""
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
    monkeypatch.setattr(tick_events, "find", lambda ctx: "made-up")
    monkeypatch.setattr(
        span_attr_roofline, "span_attrs",
        lambda path, span, attrs: [
            tuple(float(dict(start=s, tokens=t)[a]) for a in attrs)
            for s, t in chunks])
    span_attr_roofline._cache.clear()
    c = config()
    return {"trace": {"modules": modules}, "config": c,
            "cell": CELLS[CELL], "traffic": tiny_mix("agent-closed96"),
            "arith": arith, "widths": arith.widths(c),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "stats": {"serve": {"decoding_context_tokens": 121600.0},
                      "engine": {"decode_tokens": 6400, "decode_steps": 100,
                                 "prefill_tokens": 16000,
                                 "prefill_chunks": 40,
                                 "kv_bytes_per_token": 12288.0}}}


def test_the_eight_kernel_readers_by_hand(monkeypatch):
    """100 decode steps of 64 rows whose four `experts_grouped` calls took
    5 ms a step and eight `latent_decode` calls 2.5 ms over 121,600
    cached positions; 40 runs of `jit__prefill` whose
    `experts_grouped_prefill` calls took 9 ms a run and
    `latent_chunk_attend` calls 6 ms, chunks of 512 at 600 and of 64 at
    3,000."""
    ctx = ctx_with(
        monkeypatch,
        {"experts_grouped": (400, 0.5), "latent_decode": (800, 0.25),
         "experts_grouped_prefill": (160, 0.36),
         "latent_chunk_attend": (320, 0.24)},
        {"jit__decode": (100, 1.5), "jit__prefill": (40, 1.2)},
        chunks=[(600, 512), (3000, 64)])
    read = bench_run.read_layer_metric
    assert read("longcat_experts_step_ms", ctx) == pytest.approx(5.0)
    assert read("longcat_latent_decode_ms", ctx) == pytest.approx(2.5)
    assert read("longcat_experts_chunk_ms", ctx) == pytest.approx(9.0)
    assert read("longcat_latent_chunk_ms", ctx) == pytest.approx(6.0)
    w = ctx["widths"]
    assert read("longcat_experts_step_roofline", ctx) == pytest.approx(
        100 * arith.expected_expert_bytes(w, 64) / 819e9 / 5e-3)
    assert read("longcat_experts_chunk_roofline", ctx) == pytest.approx(
        100 * (arith.expected_expert_bytes(w, 512)
               + arith.expected_expert_bytes(w, 64)) / 2 / 819e9 / 9e-3)
    assert read("longcat_latent_decode_roofline", ctx) == pytest.approx(
        100 * 121600 * 9216 / 819e9 / 2.5e-3)
    assert read("longcat_latent_chunk_roofline", ctx) == pytest.approx(
        100 * (arith.chunk_attention_ops(w, 600, 512)
               + arith.chunk_attention_ops(w, 3000, 64)) / 2 / 197e12 / 6e-3)
    # the experts a step reaches, each read once at the memory's full
    # bandwidth, read 100 %: 3.07 GB at 819 GB/s take 3.75 ms
    ctx = ctx_with(monkeypatch, {"experts_grouped": (400, 0.37464)},
                   {"jit__decode": (100, 1.5)})
    assert read("longcat_experts_step_roofline", ctx) == pytest.approx(
        100.0, rel=1e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_gives_nothing(name, monkeypatch):
    """The parent's side of the comparison: a trace with none of the
    cell's kernels or scopes (the olmo replica's, recorded on a v5e), or
    no trace at all, and the reader returns nothing and does not raise."""
    other = os.path.join(HERE, "data", "v5e_serve.xplane.pb")
    monkeypatch.setattr(spans, "summary", lambda ctx: spans.reduce(other))
    monkeypatch.setattr(tick_events, "find", lambda ctx: other)
    device_parts._cache.clear()
    span_attr_roofline._cache.clear()
    c = config()
    ctx = {"trace": trace.reduce(other), "config": c, "arith": arith,
           "widths": arith.widths(c),
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "stats": {"serve": {"decoding_context_tokens": 100.0},
                     "engine": {"decode_tokens": 30, "decode_steps": 10,
                                "prefill_tokens": 100, "prefill_chunks": 4,
                                "kv_bytes_per_token": 12288.0}}}
    assert bench_run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(spans, "summary", lambda ctx: None)
    monkeypatch.setattr(tick_events, "find", lambda ctx: None)
    assert bench_run.read_layer_metric(name, {**ctx, "trace": None}) is None


def scope_ctx(path, monkeypatch):
    monkeypatch.setattr(tick_events, "find", lambda ctx: path)
    device_parts._cache.clear()
    return {"trace": trace.reduce(path), "stats": {}, "cell": {"chips": 1},
            "traffic": {}}


@pytest.mark.skipif(not os.path.exists(PARTS_TRACE),
                    reason="no trace of the scoped program was recorded")
def test_scope_ms_reads_a_recorded_trace_by_a_scope_s_name(monkeypatch):
    """On the dense family's tiny trace (`test_device_parts.py`'s): by a
    part's own name the reader gives what `device_parts` gives for the
    part less what that inherited, by the name of a scope below a part
    (the decode kernel's, under `mixer`) less than the part and more
    than nothing, and nothing by a name no op carries."""
    ctx = scope_ctx(PARTS_TRACE, monkeypatch)
    program = device_parts.summary(ctx)["programs"]["jit__decode"]
    runs = ctx["trace"]["modules"]["jit__decode"][0]
    own = sum(ns for (part, _), ns in program["parts"].items()
              if part == "mixer") - program["inherited"].get("mixer", 0.0)
    mixer = scope_ms.read(ctx, "jit__decode", "mixer")
    assert mixer == pytest.approx(own * 1e-6 / runs) and mixer > 0
    names = {c for row in device_parts.summary(ctx)["ops"]
             if row[0] == "jit__decode" and row[3] and "/mixer/" in row[3]
             for c in row[3].split("/mixer/", 1)[1].split("/")[:1]}
    below = [scope_ms.read(ctx, "jit__decode", n) for n in sorted(names)]
    assert below and all(0 < v <= mixer for v in below)
    assert scope_ms.read(ctx, "jit__decode", "shortcut_dense") is None
    assert scope_ms.read(ctx, "jit__nothing", "mixer") is None
    assert scope_ms.read(ctx, "jit__decode", "mixer", per=2) \
        == pytest.approx(mixer / 2)


@pytest.mark.skipif(not os.path.exists(SHORTCUT_TRACE),
                    reason="no trace of the shortcut family was recorded")
def test_the_two_scopes_on_a_trace_of_the_tiny_cell(monkeypatch):
    """`data/v5e_shortcut.xplane.pb`: `benchmarks/tools/record_trace.py
    --workload longcat-flash-chat.agent-closed96` on a v5e (PR 65), the
    `tiny` shrink. Both scopes read above 0, together they are under the
    step's `mixer` and `ffn`, and the experts' scope lies inside `ffn`."""
    ctx = scope_ctx(SHORTCUT_TRACE, monkeypatch)
    dense = bench_run.read_layer_metric("longcat_shortcut_dense_ms", ctx)
    experts = bench_run.read_layer_metric("longcat_shortcut_experts_ms", ctx)
    mixer = bench_run.read_layer_metric("decode_mixer_ms", ctx)
    ffn = bench_run.read_layer_metric("decode_ffn_ms", ctx)
    assert dense > 0 and experts > 0
    assert experts < ffn and dense + experts < mixer + ffn


def test_new_metrics_are_entries_with_files():
    for name in NEW:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        entry = by_name(BENCH["per_layer"], name)
        assert {k: spec[k] for k in entry} == entry
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == ("kernels" if name in KERNELS
                                  else "model step, serving")
        assert entry["workloads"] == [CELL]
        # a share says why it cannot pass 100 %
        assert name.endswith("_ms") or (
            entry["unit"] == "%" and name.endswith("_roofline")
            and ("cannot pass 100 %" in spec["what"]
                 or "stays under" in spec["what"]
                 or "is the least any kernel reads" in spec["what"]))
        assert spec["reducer"] in ("kernel_ms", "kernel_roofline",
                                   "decode_roofline", "span_attr_roofline",
                                   "scope_ms")
        # every function of the arithmetic a reader names exists
        for key in ("bytes", "need"):
            if key in spec["args"]:
                assert callable(getattr(arith, spec["args"][key]))
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
        if m["moves"] not in reported:
            assert CELL not in m.get("workloads", ()), m["name"]
