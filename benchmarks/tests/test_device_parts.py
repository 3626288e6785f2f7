"""The reader of the device's parts (`layer_metrics/device_parts.py`, PR
52) and its fourteen metrics, on recorded v5e traces.

The five traces recorded before it all hold the `/host:metadata` plane
(one `Hlo Proto` a program that ran), so the tables are found; their
programs predate the scopes, so every part reads nothing and only
`compiler` (instructions without an `op_name`) has a number.
`data/v5e_parts.xplane.pb`: `benchmarks/tools/record_trace.py --workload
olmo-1b.chat-closed64` on a v5e in PR 52, the `tiny` shrink (2 layers,
d_model 64, 4 slots, 8 closed-loop clients), 0.08 s traced inside the
replica's process, of the program that opens the five parts.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import trace
from benchmarks.harness.common import ROOT
from benchmarks.layer_metrics import device_parts, tick_events

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
PARTS_TRACE = os.path.join(DATA, "v5e_parts.xplane.pb")
TICKS = os.path.join(DATA, "v5e_serve_ticks.xplane.pb")
SERVING = ["olmo-1b.chat-closed64", "glm-5.2.docqa-closed24",
           "brumby-14b.docgen-closed24", "ling-3.0-flash-vl.reason-closed96",
           "command-a-plus.mixed-closed24", "nemotron-3-super.chat-closed96"]
TRAINING = ["datadecide-300m.pretrain-2k", "olmo-1b.pretrain-2k-fsdp4",
            "kanana-2-30b-a3b.pretrain-8k"]
DECODE = ["decode_mixer_ms", "decode_ffn_ms", "decode_head_ms",
          "decode_compiler_ms"]
CHUNK = ["chunk_mixer_ms", "chunk_ffn_ms", "chunk_head_ms",
         "chunk_compiler_ms"]
TRAIN = ["train_mixer_ms", "train_ffn_ms", "train_head_ms",
         "train_optimizer_ms", "train_recompute_ms", "train_compiler_ms"]
NEW = DECODE + CHUNK + TRAIN
# what the reader gives on the traces from before the scopes: programs in
# the plane, and the one class that needs no scope, ms a run (arithmetic
# over one file, so to the digit); `unroll` 2 for the training ones
BEFORE = {
    "v5e_serve.xplane.pb": (
        {"jit__decode": 1181, "jit_copy_block": 42, "jit__prefill": 1086},
        {"decode_compiler_ms": 0.00377225, "chunk_compiler_ms": 0.0044778}),
    "v5e_latent_serve.xplane.pb": (
        {"jit__decode": 7314, "jit__prefill": 8864},
        {"decode_compiler_ms": 0.005046111111111111,
         "chunk_compiler_ms": 0.007510888888888888}),
    "v5e_serve_ticks.xplane.pb": (
        {"jit__decode": 1391, "jit_copy_block": 42, "jit__prefill": 1171},
        {"decode_compiler_ms": 0.014368125,
         "chunk_compiler_ms": 0.010786166666666666}),
    "v5e_named_train.xplane.pb": (
        {"jit_multi": 2542}, {"train_compiler_ms": 0.055058166666666665}),
    "v5e_small_train.xplane.pb": (
        {"jit_multi": 2605}, {"train_compiler_ms": 0.07214}),
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def ctx_for(path, monkeypatch):
    """What `run.py` hands a reader after a traced run whose trace is
    the recorded one."""
    monkeypatch.setattr(tick_events, "find", lambda ctx: path)
    device_parts._cache.clear()
    return {"trace": trace.reduce(path), "stats": {}, "cell": {"chips": 1},
            "traffic": {"unroll": 2}}


@pytest.mark.parametrize("recorded", sorted(BEFORE))
def test_every_recorded_trace_holds_its_programs_hlo(recorded, monkeypatch):
    """The plane is there, each program's table has its instructions
    (1,391 and 42 in `v5e_serve_ticks`, as the proto's own count), and a
    program from before the scopes reads nothing but `compiler`."""
    path = os.path.join(DATA, recorded)
    programs, compiler = BEFORE[recorded]
    found = device_parts.tables(path)
    sizes = {}
    for name, table in found.items():       # the first bucket of a name
        sizes.setdefault(name.split("(")[0], len(table))
    assert sizes == programs
    got = {n: bench_run.read_layer_metric(n, ctx_for(path, monkeypatch))
           for n in NEW}
    assert {n: v for n, v in got.items() if v is not None} == \
        pytest.approx(compiler, rel=1e-9)
    summary = device_parts.reduce(path)
    for program in summary["programs"].values():
        assert program["table"]
        assert {part for part, _ in program["parts"]} <= {
            device_parts.UNSCOPED, device_parts.COMPILER}
        assert sum(program["parts"].values()) == pytest.approx(
            program["self_ns"])


def test_the_compilers_own_instructions_on_the_trace_the_issue_read():
    """PR 36's trace: 2,445 op events of 1,198,087 ns on chip 0; joined
    by instruction name to the table of the program that ran, 183,090 ns
    of event time lie on instructions without an `op_name`, 176,009 of it
    `copy` (ISSUE 52 read 345,991 and 187,970 from a coarser join). The
    reader's own numbers are self times inside the window: a quarter of
    the decode program."""
    from jax.profiler import ProfileData
    chip = next(p for p in ProfileData.from_file(TICKS).planes
                if p.name == "/device:TPU:0")
    ops = [ev for ln in chip.lines if ln.name == trace.OPS_LINE
           for ev in ln.events]
    assert len(ops) == 2445
    assert sum(ev.duration_ns for ev in ops) == 1198087
    summary = device_parts.reduce(TICKS)
    share = {name: p["parts"][device_parts.COMPILER, device_parts.FWD]
             / p["self_ns"] for name, p in summary["programs"].items()}
    assert share == pytest.approx({"jit__decode": 0.257998,
                                   "jit__prefill": 0.213693,
                                   "jit_copy_block": 0.141454}, abs=1e-6)
    copies = sum(ns for *_, label, _, part, _, _, _, ns in summary["ops"]
                 if part == device_parts.COMPILER
                 and label.split(" ")[1] == "copy")
    compiler = sum(p["parts"][device_parts.COMPILER, device_parts.FWD]
                   for p in summary["programs"].values())
    assert copies / compiler > 0.95


def test_no_trace_or_no_plane_gives_nothing(tmp_path, monkeypatch):
    ctx = {"trace": None, "stats": {}, "cell": {"chips": 1},
           "traffic": {"unroll": 2}}
    assert all(bench_run.read_layer_metric(n, ctx) is None for n in NEW)
    # a trace file whose one plane is not the metadata's
    other = tmp_path / "other.xplane.pb"
    name = b"/host:CPU"
    plane = b"\x12" + bytes([len(name)]) + name
    other.write_bytes(b"\x0a" + bytes([len(plane)]) + plane)
    assert device_parts.tables(str(other)) is None
    assert device_parts.reduce(str(other)) is None
    monkeypatch.setattr(tick_events, "find", lambda ctx: str(other))
    ctx["trace"] = {"modules": {"jit__decode": [1, 1.0, 1.0]}, "chips": 1}
    assert all(bench_run.read_layer_metric(n, ctx) is None for n in NEW)


def test_a_traced_run_is_parsed_once_whatever_the_number_of_metrics(
        monkeypatch):
    ctx = ctx_for(TICKS, monkeypatch)
    calls = []
    reduce = device_parts.reduce
    monkeypatch.setattr(device_parts, "reduce",
                        lambda path: calls.append(path) or reduce(path))
    for name in NEW:
        bench_run.read_layer_metric(name, ctx)
    assert calls == [TICKS]


def test_the_reader_needs_no_tensorflow():
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmarks.layer_metrics import device_parts as d; "
            "d.tables(%r); "
            "assert not [m for m in sys.modules if m.startswith"
            "(('tensorflow', 'tsl', 'xprof'))]" % (ROOT, TICKS))
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_the_fourteen_metrics_are_entries_with_files_at_the_end():
    entries = BENCH["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == NEW
    for entry in entries:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{entry['name']}.json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in entry} == entry
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert spec["reducer"] == "device_parts"
        serving = entry["name"] in DECODE + CHUNK
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ms", "lower", "device_trace")
        assert (entry["layer"], entry["moves"], entry["workloads"]) == (
            ("model step, serving", "serve_tokens_per_s", SERVING)
            if serving else ("model step", "train_tokens_per_s", TRAINING))
        assert spec["args"]["module"] == (
            "jit_multi" if not serving else
            "jit__decode" if entry["name"] in DECODE else "jit__prefill")


@pytest.mark.skipif(not os.path.exists(PARTS_TRACE),
                    reason="no trace of the scoped program was recorded")
def test_the_scoped_program_on_a_trace_recorded_inside_a_replica(
        monkeypatch):
    """Every serving metric above 0, the classes sum to the program's op
    self time, and what no part is near is under 2 % of each program
    once the layer loop's own self time is set aside (`while.N`, 2.4 us a
    run whatever the size: 4.8 % of this 49 us step, 0.04 % of the
    cell's 6.4 ms one)."""
    ctx = ctx_for(PARTS_TRACE, monkeypatch)
    got = {n: bench_run.read_layer_metric(n, ctx) for n in DECODE + CHUNK}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert all(bench_run.read_layer_metric(n, ctx) is None for n in TRAIN)
    summary = device_parts.reduce(PARTS_TRACE)
    for name in ("jit__decode", "jit__prefill"):
        program = summary["programs"][name]
        by_part = {}
        for (part, _), ns in program["parts"].items():
            by_part[part] = by_part.get(part, 0.0) + ns
        assert sum(by_part.values()) == pytest.approx(program["self_ns"])
        assert set(by_part) >= {"embed", "mixer", "ffn", "head"}
        loop = sum(ns for prog, _, label, *_, part, _, _, _, ns
                   in summary["ops"] if prog == name
                   and part == device_parts.UNSCOPED
                   and label.split(" ")[1] == "while")
        assert 0 < loop < 0.06 * program["self_ns"]
        assert by_part[device_parts.UNSCOPED] - loop \
            < 0.02 * program["self_ns"], by_part
        runs = ctx["trace"]["modules"][name][0]
        for part, metric in (("mixer", "_mixer_ms"), ("ffn", "_ffn_ms")):
            prefix = "decode" if name == "jit__decode" else "chunk"
            assert got[prefix + metric] == pytest.approx(
                by_part[part] * 1e-6 / runs)


def test_the_tool_prints_a_trace_files_table():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "device_parts.py"), TICKS],
        check=True, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}).stdout
    assert "jit__decode: 8 runs on chip 0" in out
    assert "compiler ops of jit__decode, by opcode and result" in out
    assert "copy f32[2,65,16,2,32]" in out
    assert "the reader's parse:" in out
