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

from benchmarks.harness import arith, peaks, trace, traffic
from benchmarks.harness.common import ROOT, gpt_kwargs

HERE = os.path.dirname(os.path.abspath(__file__))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CELLS = {c["name"]: c for c in BENCH["workloads"]}


def tiny_config(name):
    """The configuration file with every size shrunk, for the CPU only."""
    cfg = load("benchmarks", "configs", f"{name}.json")
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=2, head_dim=32, intermediate_size=128,
               vocab_size=512, max_position_embeddings=128)
    cfg["program"] = copy.deepcopy(cfg["program"])
    cfg["program"]["model"]["dtype"] = "float32"
    cfg["tolerances"] = dict(cfg["tolerances"], logprob_max_abs=1e-3,
                             logprob_mean_abs=1e-3, loss_abs=1e-3)
    return cfg


def tiny_mix(name):
    mix = load("benchmarks", "traffic", f"{name}.json")
    mix.update(batch=4, seq_len=128, unroll=2, warm_dispatches=1,
               check_sequences=4, trace_s=1)
    return mix


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
    got = dense_decoder.logits(params, toks, cfg.n_heads)
    assert float(jnp.abs(want - got).max()) < 1e-4
    lp = dense_decoder.token_logprobs(params, toks, cfg.n_heads)
    assert lp.shape == (2, 32) and float(lp.max()) < 0
    loss = dense_decoder.loss(params, toks[:, :-1], toks[:, 1:], cfg.n_heads)
    assert float(loss) == pytest.approx(-float(lp.mean()), rel=1e-5)


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
    assert s["idle_gaps"][0][0] == "loop.py:run"
    assert sum(g[1] for g in s["idle_gaps"]) == \
        pytest.approx(s["window_s"] - s["busy_s"], rel=0.02)


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


@pytest.mark.parametrize("cell_name", list(CELLS))
@pytest.mark.parametrize("trace_on", [False, True])
def test_cell_rehearsal(cell_name, trace_on, tmp_path):
    """Each cell's driver end to end at a tiny size on the CPU backend
    (its own process: one that traced once should not trace again)."""
    cell = CELLS[cell_name]
    spec = {"cell": cell, "config": tiny_config(cell["config"]),
            "mix": tiny_mix(cell["traffic"]), "trace": trace_on,
            "scratch": str(tmp_path), "bench": BENCH}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"),
         str(tmp_path / "spec.json")], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
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
