"""Every device op of a step belongs to a named part of the model: the
families and the trainers open one vocabulary of `jax.named_scope`s
(`models/family.py:PARTS`), a scope is metadata on the HLO
(`op_name="jit(_decode)/while/body/closed_call/mixer/dot_general"`), and
the benchmark's reader (`benchmarks/layer_metrics/device_parts.py`) joins
the trace's own copy of a program's HLO to its ops' times.

Here, on the CPU: the pure functions (a path's part and direction, the
walker over the protobuf wire format, what an instruction inherits from
its neighbours), the vocabulary's one spelling, and the compiled programs
themselves: each serving family's `_prefill` and `_decode` as the engine
jits them and both trainers' fused dispatch, at the benchmark's `tiny`
sizes, through the reader's own functions over the compiled module's
serialized proto.
"""

import functools
import importlib
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks.harness import common
from benchmarks.layer_metrics import device_parts as dp
from ray_tpu.models import family
from ray_tpu.serve.engine import InferenceEngine, pack_chunk, pack_rows

SERVING = ["olmo-1b", "glm-5.2", "brumby-14b", "ling-3.0-flash-vl",
           "command-a-plus", "nemotron-3-super"]
TRAINING = ["datadecide-300m", "kanana-2-30b-a3b"]
# the instructions that do a program's work, whatever the backend calls
# the rest
HEAVY = ("dot", "convolution", "custom-call", "scatter", "gather", "reduce",
         "fusion")
# What may stay outside every part, by the last component of its
# `op_name`, and why: none of it is a layer's work.
LOOSE = {
    "add": "a loop's counter (`while/body/add`)",
    "lt": "a loop's condition (`while/cond/lt`)",
    "while": "the loop itself: its self time is the body's dispatch",
    "iota": "the layer numbers a scan runs over",
    "dynamic_slice": "a scan's slice of its inputs that nothing with a "
                     "part reads within reach",
    "dynamic_update_slice": "a scan's stacking of a step's metrics",
    "closed_call": "a checkpoint's own wrapper around the layer",
    "jit(step)": "the jitted step's own boundary inside the fused dispatch",
    "broadcast_in_dim": "a scan's zero carry",
}


# -- a path's part and direction ---------------------------------------------

@pytest.mark.parametrize("op_name, part", [
    ("jit(_decode)/while/body/closed_call/mixer/dot_general", "mixer"),
    ("jit(_decode)/embed/gather", "embed"),
    ("jit(_prefill)/ffn/routed_experts/experts_grouped_prefill/pallas_call",
     "ffn"),
    ("jit(_decode)/mixer/ffn/mul", "mixer"),            # the outermost
    ("jit(multi)/while/body/closed_call/jit(step)/transpose(jvp(head))/mul",
     "head"),
    ("jit(step)/jvp(embed)/gather", "embed"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ffn/dot_general", "ffn"),
    ("jit(step)/optimizer/mul;jit(step)/head/mul", "optimizer"),
    ("jit(step)/vmap(jvp(mixer))/mul", "mixer"),
    ("jit(_decode)/while/body/dynamic_slice", dp.UNSCOPED),
    ("jit(head)/mul", dp.UNSCOPED),      # a jitted function's name is no scope
    ("params['embed']", dp.UNSCOPED),    # an argument's name is no scope
    ("", dp.COMPILER),
    (None, dp.COMPILER),
])
def test_part_of(op_name, part):
    assert dp.part_of(op_name) == part


@pytest.mark.parametrize("op_name, direction", [
    ("jit(step)/jvp()/while/body/closed_call/mixer/dot_general", dp.FWD),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mixer/"
     "neg", dp.BWD),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mixer/dot_general", dp.RECOMPUTE),
    ("jit(step)/transpose(jvp(embed))/scatter-add", dp.BWD),
    ("jit(step)/optimizer/mul", dp.FWD),
    (None, dp.FWD),
])
def test_direction_of(op_name, direction):
    assert dp.direction_of(op_name) == direction


# -- the wire format ---------------------------------------------------------

def varint(n: int) -> bytes:
    out = b""
    while True:
        n, low = n >> 7, n & 0x7F
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def field(number: int, value) -> bytes:
    """One field on the wire: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(name, opcode, op_name, ident, operands=(), packed=True):
    meta = field(7, field(2, op_name)) if op_name else b""
    ids = (field(36, b"".join(varint(o) for o in operands)) if packed
           else b"".join(field(36, o) for o in operands))
    return field(2, field(1, name) + field(2, opcode) + meta
                 + field(35, ident) + (ids if operands else b""))


def hlo_proto(*computations) -> bytes:
    return field(1, field(1, "jit_f") + b"".join(
        field(3, field(1, f"computation.{i}") + b"".join(rows))
        for i, rows in enumerate(computations)))


def test_the_walker_reads_varints_fixed_widths_and_nested_messages():
    message = (field(1, 300) + varint(2 << 3 | 1) + (7).to_bytes(8, "little")
               + varint(3 << 3 | 5) + (9).to_bytes(4, "little")
               + field(4, field(1, "inner")))
    got = list(dp.fields(message))
    assert [(n, v) for n, v in got[:3]] == [(1, 300), (2, 7), (3, 9)]
    assert got[3][0] == 4 and list(dp.fields(got[3][1]))[0][0] == 1
    assert bytes(list(dp.fields(got[3][1]))[0][1]) == b"inner"
    with pytest.raises(ValueError):
        list(dp.fields(varint(1 << 3 | 3)))       # a group: not written


@pytest.mark.parametrize("packed", [True, False])
def test_the_walker_reads_a_hand_made_module(packed):
    proto = hlo_proto(
        [instruction("p.1", "parameter", "", 1),
         instruction("fusion.2", "fusion", "jit(f)/mixer/mul", 2, (1,),
                     packed)],
        [instruction("copy.3", "copy", "", 3, (2, 1), packed)])
    assert dp.hlo_instructions(proto) == [
        ("p.1", "parameter", "", 1, []),
        ("fusion.2", "fusion", "jit(f)/mixer/mul", 2, [1]),
        ("copy.3", "copy", "", 3, [2, 1])]
    assert dp.hlo_instructions(b"") == []


def test_a_scans_own_slice_takes_the_part_it_feeds():
    """`while/body/dynamic_slice` (a layer's weight cut out of the stack)
    through a compiler's copy to the matmul that reads it; a gradient's
    `dynamic_update_slice` from the matmul that made it; the loop itself
    and what has no part within reach stay outside; an instruction
    without `op_name` inherits nothing."""
    scan = "jit(f)/while/body/"
    rows = [
        ("slice.1", "fusion", scan + "dynamic_slice", 1, []),
        ("copy.2", "copy", "", 2, [1]),
        ("dot.3", "fusion", scan + "closed_call/mixer/dot_general", 3, [2]),
        ("dw.4", "fusion", "jit(f)/transpose(jvp())/while/body/closed_call/"
         "checkpoint/ffn/dot_general", 4, [3]),
        ("stack.5", "fusion", "jit(f)/transpose(jvp())/while/body/"
         "dynamic_update_slice", 5, [4]),
        ("count.6", "fusion", scan + "add", 6, []),
        ("while.7", "while", "jit(f)/while", 7, [1]),
        ("after.8", "fusion", "jit(f)/head/mul", 8, [7]),
    ]
    got = dp.hlo_parts(rows)
    assert got["slice.1"] == (scan + "dynamic_slice", "mixer", dp.FWD, True)
    assert got["copy.2"][1:] == (dp.COMPILER, dp.FWD, False)
    assert got["dot.3"][1:] == ("mixer", dp.FWD, False)
    assert got["stack.5"][1:] == ("ffn", dp.BWD, True)
    assert got["count.6"][1:] == (dp.UNSCOPED, dp.FWD, False)
    # the loop inherits nothing, and nothing is inherited through it
    assert got["while.7"][1:] == (dp.UNSCOPED, dp.FWD, False)
    far = [("slice.0", "fusion", scan + "dynamic_slice", 0, [])] + [
        (f"copy.{i}", "copy", "", i, [i - 1]) for i in range(1, dp.NEAR + 2)
    ] + [("dot", "fusion", "jit(f)/mixer/dot", dp.NEAR + 2, [dp.NEAR + 1])]
    assert dp.hlo_parts(far)["slice.0"][1] == dp.UNSCOPED


# -- one vocabulary ----------------------------------------------------------

SCOPED_FILES = ["models/gpt.py", "models/latent_sparse_moe.py",
                "models/retention.py", "models/linear_latent.py",
                "models/window_moe.py", "models/mamba_moe.py",
                "train/spmd.py", "serve/engine.py"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_vocabulary_is_one_tuple_and_the_reader_spells_it_alike():
    assert family.PARTS == dp.PARTS == (
        "embed", "mixer", "ffn", "head", "optimizer")
    assert (family.EMBED, family.MIXER, family.FFN, family.HEAD,
            family.OPTIMIZER) == family.PARTS


@pytest.mark.parametrize("path", SCOPED_FILES)
def test_no_file_spells_a_part_outside_the_tuple(path):
    with open(os.path.join(ROOT, "ray_tpu", path)) as f:
        source = f.read()
    opened = re.findall(r"named_scope\(\s*([^)\s]+)", source)
    literal = [a for a in opened if a.strip("\"'f") in family.PARTS]
    assert not literal, f"{path} spells {literal}: import it from family.py"
    names = set(re.findall(r"named_scope\((EMBED|MIXER|FFN|HEAD|OPTIMIZER"
                           r"|_part\(kind\))\)", source))
    want = {"train/spmd.py": {"HEAD", "OPTIMIZER"},
            "serve/engine.py": {"EMBED", "HEAD"},
            "models/mamba_moe.py": {"EMBED", "_part(kind)", "HEAD"}}.get(
                path, {"EMBED", "MIXER", "FFN", "HEAD"})
    assert want <= names, f"{path} opens {sorted(names)}"


# -- the compiled programs ---------------------------------------------------

@pytest.fixture
def compiled_here():
    """A scope is no part of a compile-cache key
    (`jax_compilation_cache_include_metadata_in_key` is False), so the
    persistent cache `conftest.py` keeps would answer with whatever
    executable was compiled first, under its `op_name`s: off around these
    compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def tiny(name: str) -> dict:
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    return common.merged(config, config["tiny"])


def parts_of(compiled) -> list:
    """[(instruction, opcode, op_name, part, direction, inherited)] of a
    compiled program, by the reader's own functions over the module's
    serialized proto (wrapped as the `HloProto` a trace holds)."""
    module = compiled.runtime_executable().hlo_modules()[
        0].as_serialized_hlo_module_proto()
    rows = dp.hlo_instructions(field(1, module))
    parts = dp.hlo_parts(rows)
    return [(name, opcode, *parts[name]) for name, opcode, _, _, _ in rows]


def loose(rows) -> list:
    """The heavy instructions with an `op_name` and no part that `LOOSE`
    does not excuse."""
    return [(name, opcode, op_name)
            for name, opcode, op_name, part, _, _ in rows
            if opcode in HEAVY and part == dp.UNSCOPED
            and op_name.split(";", 1)[0].rsplit("/", 1)[-1] not in LOOSE]


@functools.lru_cache(maxsize=None)
def engine(name: str):
    config = tiny(name)
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    params = jax.jit(lambda key: ref.init_params(key, config))(
        jax.random.key(0))
    block = config["program"]["serve"]
    return InferenceEngine(
        params, common.model_config(config, "serve"), slots=block["slots"],
        max_len=block["max_len"], **block["engine_kwargs"])


def lowered(eng, program: str):
    slots, blocks = eng._no_prev.shape[0], eng.max_blocks
    if program == "decode":
        rows = pack_rows(np.zeros(slots, np.int32), np.zeros(slots, np.int32),
                         np.zeros(slots, np.float32),
                         np.zeros((slots, blocks), np.int32), 0)
        return eng._decode_fn.lower(eng.params, eng.cache, rows,
                                    eng._base_key, eng._no_prev,
                                    eng._no_chunk_tok)
    chunk = pack_chunk(np.zeros(3, np.int32), eng.chunk_buckets[0],
                       np.zeros(blocks, np.int32), 0, 0.0, 0)
    return eng._prefill_fn.lower(eng.params, chunk, eng.cache, eng._base_key)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("name", SERVING)
def test_a_serving_program_is_all_parts(name, program, compiled_here):
    """Every instruction that does work and carries an `op_name` names a
    part, its own or (a scan's slices) the one it feeds; the four parts a
    serving program has are all there, and nothing runs backward."""
    rows = parts_of(lowered(engine(name), program).compile())
    assert loose(rows) == []
    heavy = [(part, direction) for _, opcode, _, part, direction, _ in rows
             if opcode in HEAVY and part in dp.PARTS]
    assert {p for p, _ in heavy} == {"embed", "mixer", "ffn", "head"}
    assert {d for _, d in heavy} == {dp.FWD}
    # the kernels' and the families' own scopes stay, under their part
    below = {"olmo-1b": "mixer/", "glm-5.2": "ffn/", "brumby-14b": "mixer/",
             "ling-3.0-flash-vl": "mixer/", "command-a-plus":
             "mixer/full_attention/", "nemotron-3-super":
             "mixer/mamba_layer/"}[name]
    assert any(below in op_name for _, _, op_name, _, _, _ in rows)


@functools.lru_cache(maxsize=None)
def train_rows(name: str):
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import loop, spmd
    config = tiny(name)
    cfg = common.model_config(config, "train", **config["program"]["train"])
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, _ = common.entry_point(config, "trainer")(
        cfg, mesh, rng=jax.random.key(0),
        optimizer=spmd.default_optimizer(**config["program"]["optimizer"]))
    tokens = np.zeros((2, 2, 128), np.int32)     # unroll 2, batch 2
    return parts_of(loop.fuse_steps(step_fn, 2).lower(
        state, {"inputs": tokens, "targets": tokens}).compile())


@pytest.mark.parametrize("name", TRAINING)
def test_a_train_step_is_all_parts(name, compiled_here):
    assert loose(train_rows(name)) == []


@pytest.mark.parametrize("name", TRAINING)
@pytest.mark.parametrize("part, direction", [
    ("embed", dp.FWD), ("mixer", dp.FWD), ("ffn", dp.FWD), ("head", dp.FWD),
    ("mixer", dp.BWD), ("ffn", dp.BWD), ("head", dp.BWD), ("embed", dp.BWD),
    ("mixer", dp.RECOMPUTE), ("ffn", dp.RECOMPUTE), ("optimizer", dp.FWD)])
def test_a_train_step_runs_each_part_each_way(name, part, direction,
                                              compiled_here):
    """The backward of a scoped region reads `bwd`, a rematerialised
    forward `recompute`, and the optimizer neither."""
    found = {(p, d) for _, opcode, _, p, d, _ in train_rows(name)
             if opcode in HEAVY}
    assert (part, direction) in found
    assert ("optimizer", dp.BWD) not in found
    assert ("optimizer", dp.RECOMPUTE) not in found
