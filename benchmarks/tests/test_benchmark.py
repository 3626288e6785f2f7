"""CPU checks of the benchmark's own code, and a rehearsal of every cell at
a tiny size (the four-chip cell on four virtual devices). Nothing here
yields a time, a rate or a utilization worth reading.

    python -m pytest benchmarks/tests -q
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import arith, peaks, trace, traffic
from benchmarks.harness.common import (ROOT, BenchFailure, gpt_kwargs,
                                       merged)

HERE = os.path.dirname(os.path.abspath(__file__))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CELLS = {c["name"]: c for c in BENCH["workloads"]}


def tiny_config(name):
    """The configuration file shrunk by its own `tiny` block, for the CPU
    only: a new file brings its own shrink."""
    cfg = load("benchmarks", "configs", f"{name}.json")
    return merged(cfg, cfg["tiny"])


def tiny_mix(name):
    mix = load("benchmarks", "traffic", f"{name}.json")
    return merged(mix, mix["tiny"])


# -- traffic ---------------------------------------------------------------

def test_train_batches_are_seeded_and_skewed():
    mix = load("benchmarks", "traffic", "pretrain-2k.json")
    a = next(traffic.train_batches(mix, 3, 50304))
    b = next(traffic.train_batches(mix, 3, 50304))
    assert (a["inputs"] == b["inputs"]).all()
    assert a["inputs"].shape == (mix["batch"], mix["seq_len"])
    assert (a["inputs"][:, 1:] == a["targets"][:, :-1]).all()
    assert (a["inputs"] == 0).mean() > 0.05        # Zipf: rank 1 is common
    assert a["inputs"].max() < 50304


def test_serve_requests_are_seeded_and_every_seed_sends_the_same_work():
    mix = load("benchmarks", "traffic", "chat-steady.json")
    block = mix["length_block"]

    def take(mix, seed, n=2 * block):
        gen = traffic.serve_requests(mix, seed, 50304)
        return [next(gen) for _ in range(n)]

    def shape(r):
        return len(r["prompt"]), r["max_new_tokens"], r["due_s"]

    a, b, c = take(mix, 3), take(mix, 3), take(mix, 2**31 + 9)
    for x, y, z in zip(a, b, c):
        assert (x["prompt"] == y["prompt"]).all()   # same seed, same run
        assert shape(x) == shape(y) == shape(z)     # any seed, same work
    assert any((x["prompt"] != z["prompt"]).any() for x, z in zip(a, c))
    # another order_seed: the same lengths block by block, another order
    d = take(dict(mix, order_seed=mix["order_seed"] + 1), 3)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        for lo in (0, block):
            assert sorted(map(key, a[lo:lo + block])) == \
                sorted(map(key, d[lo:lo + block]))
    assert [shape(r) for r in a] != [shape(r) for r in d]
    lens = [len(r["prompt"]) for r in a[:block]]
    spec = mix["prompt_tokens"]
    assert spec["min"] <= min(lens) and max(lens) <= spec["max"]
    assert 0.8 * spec["median"] < sorted(lens)[block // 2] \
        < 1.25 * spec["median"]
    assert max(int(r["prompt"].max()) for r in a) < 50304
    # arrivals: increasing, and a block lasts exactly block / rate
    due = [r["due_s"] for r in a]
    assert all(x < y for x, y in zip(due, due[1:]))
    assert due[block - 1] == pytest.approx(block / mix["rate_per_s"])
    assert d[block - 1]["due_s"] == pytest.approx(due[block - 1])
    closed = load("benchmarks", "traffic", "chat-closed64.json")
    assert next(traffic.serve_requests(closed, 1, 512))["due_s"] is None


def test_percentile_and_window_arithmetic():
    assert traffic.percentile([], 90) is None
    assert traffic.percentile([5.0], 90) == 5.0
    assert traffic.percentile(range(1, 101), 90) == 91
    assert traffic.percentile(range(1, 101), 50) == 51
    requests = [
        # timed from 9.0; tokens at 10.5 (inside), 11.0, 11.5, 12.5 (out)
        {"t_ref": 9.0, "prompt_tokens": 100,
         "arrivals": [10.5, 11.0, 11.5, 12.5]},
        # first token before the window: no TTFT sample, gaps count
        {"t_ref": 8.0, "prompt_tokens": 10, "arrivals": [9.5, 10.25, 11.75]},
        # nothing received yet
        {"t_ref": 11.0, "prompt_tokens": 7, "arrivals": []},
    ]
    w = traffic.window_stats(requests, 10.0, 12.0, context_every_s=1.0)
    assert w["tokens"] == 3 + 2 and w["tokens_per_s"] == 2.5
    assert w["ttft_ms"] == [1500.0]
    assert sorted(w["tpot_ms"]) == [500.0, 500.0, 750.0, 1500.0]
    # instants 10.0 and 11.0: the first stream is live at 11.0 only (two
    # tokens in: 102), the second at both (11 and 12 positions)
    assert w["decoding_context_tokens"] == pytest.approx((11 + 102 + 12) / 2)


# -- arithmetic ------------------------------------------------------------

def test_flops_from_shapes():
    olmo = {**gpt_kwargs(load("benchmarks", "configs", "olmo-1b.json")),
            "head_dim": 128}
    # the program's own count, with attention halved for causality
    d, f, layers, t, v = 2048, 8192, 16, 2048, 50304
    full = 3 * (layers * (2 * (4 * d * d + 3 * d * f) + 4 * t * d)
                + 2 * d * v)
    causal = full - 3 * layers * 2 * t * d
    assert arith.train_flops_per_token(olmo, t) == pytest.approx(causal)
    assert arith.flash_attention_flops(1, 2048, 16, 128, 1) == \
        6 * 2 * (2048 * 2048 / 2) * 128 * 16


def test_collect_takes_a_configuration_s_own_widths():
    """Grouped heads, a head size that is not hidden / heads, an untied
    output matrix and an arithmetic module of its own are data to the
    harness; the refusal is the driver's, where a `GPTConfig` is built."""
    config = dict(load("benchmarks", "configs", "olmo-1b.json"),
                  num_attention_heads=32, num_key_value_heads=4,
                  head_dim=96, tie_word_embeddings=False,
                  num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=512, arith="tests.arith_madeup")
    cell = CELLS["datadecide-300m.pretrain-2k"]
    mix = load("benchmarks", "traffic", "pretrain-2k.json")
    out = {"stats": {"end_to_end": {"train_tokens_per_s": 1000.0},
                     "train": {"train_tokens_per_s": 1000.0, "chips": 1,
                               "seq_len": 2048}}, "trace": None}
    kw = dict(seconds=1.0, peak={"flops_per_s": 1e12}, setup_s=2.0)
    assert bench_run.collect(BENCH, cell, config, mix, out, trace=False,
                             **kw) == {"train_tokens_per_s": 1000.0,
                                       "setup_s": 2.0}
    from benchmarks.tests import arith_madeup
    layered = bench_run.collect(BENCH, cell, config, mix, out, trace=True,
                                **kw)
    assert layered["mfu_pct"] == pytest.approx(
        100 * 1000.0 * arith_madeup.train_flops_per_token(
            arith_madeup.widths(config), 2048) / 1e12)
    # the dense module reads the same keys: 4 key-value heads under 32
    dense = arith.widths(config)
    assert (dense["n_kv_heads"], dense["head_dim"], dense["tied"]) == \
        (4, 96, False)
    full = arith.matmul_params({**dense, "n_kv_heads": 32})
    assert full - arith.matmul_params(dense) == \
        16 * 2 * 2048 * (32 - 4) * 96
    with pytest.raises(BenchFailure, match="train driver"):
        gpt_kwargs(config, "train")


def test_peaks_miss_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# -- reference -------------------------------------------------------------

def test_reference_matches_gpt_forward_at_a_tiny_size():
    import jax
    import jax.numpy as jnp

    from benchmarks.refs import dense_decoder
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(**gpt_kwargs(tiny_config("olmo-1b")),
                        dtype="float32", attn_impl="xla")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 512)
    want = gpt.forward(params, toks, cfg)
    config = tiny_config("olmo-1b")
    got = dense_decoder.logits(params, toks, config)
    assert float(jnp.abs(want - got).max()) < 1e-4
    lp = dense_decoder.token_logprobs(params, toks, config)
    assert lp.shape == (2, 32) and float(lp.max()) < 0
    loss = dense_decoder.loss(params, toks[:, :-1], toks[:, 1:], config)
    assert float(loss) == pytest.approx(-float(lp.mean()), rel=1e-5)
    each = dense_decoder.sequence_losses(params, toks[:, :-1], toks[:, 1:],
                                         config)
    assert each.shape == (2,) and float(each.mean()) == \
        pytest.approx(float(loss), rel=1e-6)
    # the reference's own seeded weights: the program's layout and scales
    own = jax.jit(lambda k: dense_decoder.init_params(k, config))(
        jax.random.key(3))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), params)
    for path, a in jax.tree_util.tree_leaves_with_path(own):
        b = params
        for key in path:
            b = b[key.key]
        assert float(jnp.std(a)) == pytest.approx(float(jnp.std(b)),
                                                  rel=0.1, abs=1e-6), path
    assert float(jnp.abs(gpt.forward(own, toks, cfg)
                         - dense_decoder.logits(own, toks, config)).max()) \
        < 1e-4


# -- trace reducer ---------------------------------------------------------

def test_reducer_on_a_recorded_v5e_trace():
    """`data/v5e_small_train.xplane.pb`: two fused dispatches (unroll 2) of
    a 2-layer d_model-256 model, T=512, B=2, flash + fused loss, recorded
    on a v5e in PR 23."""
    s = trace.reduce(os.path.join(HERE, "data", "v5e_small_train.xplane.pb"))
    assert s["chips"] == 1
    assert 0 < s["busy_s"] < s["window_s"] < 0.1
    runs, total, median = s["modules"]["jit_multi"]
    assert runs == 2 and median == pytest.approx(total / 2, rel=0.05)
    # self times: nothing is counted twice, so ops sum to the busy time
    assert sum(v[1] for v in s["ops"].values()) == \
        pytest.approx(s["busy_s"], rel=0.02)
    dims = r"bf16\[4,512,128\]"
    dq_calls, _ = trace.op_seconds(
        s, rf"/pallas {dims} <- ({dims},){{4}}")
    assert dq_calls == 2 * 2 * 2          # dispatches x unroll x layers
    flash_calls, flash_s = trace.op_seconds(
        s, rf"/pallas [^<]*<- {dims},{dims},{dims}(,|$)")
    assert flash_calls == 4 * dq_calls and flash_s > 0
    assert s["device_ops"][0][0].startswith("jit_multi/pallas")
    assert "idle_gaps" not in s         # spans.py's, by the program's spans


def test_op_label():
    name = ('%closed_call.3 = (bf16[4,8,128]{2,1,0:T(8,128)}, f32[4,8]{1,0}) '
            'custom-call(bf16[4,8,128]{2,1,0} %a, s32[4]{0} %b), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert trace.op_label(name) == (
        "closed_call.3", "pallas bf16[4,8,128],f32[4,8] <- bf16[4,8,128],s32[4]")
    assert trace.op_label("%all-gather-start.2 = f32[8]{0} "
                          "all-gather-start(f32[2]{0} %x)")[0] \
        == "all-gather-start.2"
    assert trace.op_label("dot_general.1") == ("dot_general.1",
                                               "dot_general.1")


# -- every cell, tiny, on the CPU -------------------------------------------

def check_result_shape(result, cell, trace_on, ctx_metrics):
    assert set(result) >= {"correct", "attempted", "failed", "device"}
    assert result["correct"], result["problems"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= cell["chips"]


def rehearse(spec, tmp_path):
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"),
         str(tmp_path / "spec.json")], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_serving_control_is_not_correct(tmp_path):
    """The control of `olmo-1b`'s serving cells, at the tiny size: the
    engine's own int8 weights and int8 cache in the program's place.
    Every request still gets its tokens; the logprobs are what fails.
    The compared requests are the mix's and the seed is fixed: the mean
    reads 0.0024651686 in ten runs of ten, the sound program 7.9e-8; the
    tiny limit, 0.0005, stands between the two."""
    cell = CELLS["olmo-1b.chat-closed64"]
    config = tiny_config(cell["config"])
    spec = {"cell": cell, "config": merged(config, config["control"]),
            "mix": tiny_mix(cell["traffic"]), "trace": False,
            "scratch": str(tmp_path), "bench": BENCH}
    result = rehearse(spec, tmp_path)["result"]
    assert not result["correct"] and result["failed"] == 0
    assert len(result["problems"]) == 1 and "logprobs" in \
        result["problems"][0]
    checks = {c[0]: c for c in result["checks"]}
    assert checks["logprob_mean_abs"][1] > 3 * checks["logprob_mean_abs"][2]
    assert checks["requests_compared"][1:] == [4, "== 4"]
    assert checks["logprob_tokens_compared"][1:] == [24, "== 24"]


@pytest.mark.parametrize("cell_name", list(CELLS))
@pytest.mark.parametrize("trace_on", [False, True])
def test_cell_rehearsal(cell_name, trace_on, tmp_path):
    """Each cell's driver end to end at a tiny size on the CPU backend
    (its own process: one that traced once should not trace again)."""
    cell = CELLS[cell_name]
    spec = {"cell": cell, "config": tiny_config(cell["config"]),
            "mix": tiny_mix(cell["traffic"]), "trace": trace_on,
            "scratch": str(tmp_path), "bench": BENCH}
    out = rehearse(spec, tmp_path)
    check_result_shape(out["result"], cell, trace_on, None)
    wanted = [m["name"] for m in
              (BENCH["per_layer"] if trace_on else BENCH["end_to_end"])
              if "workloads" not in m or cell_name in m["workloads"]]
    if trace_on:
        # a CPU trace has no device plane: trace-sourced readers return
        # nothing and are left out; every other reader must give a number
        by_source = {m["name"]: m["source"] for m in BENCH["per_layer"]}
        wanted = [n for n in wanted if by_source[n] != "device_trace"]
    assert set(out["metrics"]) >= set(wanted), (out["metrics"], wanted)
    assert all(np.isfinite(v) for v in out["metrics"].values())
