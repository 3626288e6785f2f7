"""The chip's compiler, asked without a chip: every Pallas kernel of the
serve and train paths is AOT-compiled for a described `v5e:2x2` at the
widths of the repo's bench model (d_model 1024, 16 heads x 64, vocab
50304, block_size 16). Interpret mode accepts BlockSpecs and kernels that
Mosaic refuses, so these are what guard the kernels between chip runs.

A compile that passes is not a chip run: it says nothing about results
or times. `chip_smoke.py` is the run.
"""

import functools
import hashlib
import math
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from ray_tpu.models import gpt
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import fused_xent, grouped_experts, sparse_latent
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused_xent import fused_softmax_xent
from ray_tpu.parallel import MeshSpec

SLOTS, H, D, BS, NB, MB = 8, 16, 64, 16, 513, 64
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def compile_as_on_tpu(monkeypatch):
    """The ops ask `jax.default_backend()` whether to compile or
    interpret; here it answers for the described chip. A described
    chip's executables cannot be read back from the persistent cache, so
    the cache is off around these compiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_for(topo, fn, *args):
    """`fn` compiled for one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for shape, dtype in args]
    return jax.jit(fn).lower(*args).compile()


def compiled_text(topo, fn, *args) -> str:
    """The program `fn` compiles to for one described chip."""
    return compiled_for(topo, fn, *args).as_text()


def kernel_names(text: str) -> list[str]:
    """Instruction names, `.N` cut, of the Mosaic kernels in a program."""
    return [line.split(" = ")[0].strip().removeprefix("ROOT ").lstrip("%")
            .rsplit(".", 1)[0]
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def kernel_results(text: str, name: str) -> list[str]:
    """The result's type (`bf16[1536,2048]`) of each Mosaic kernel `name`
    in a program."""
    return [m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(rf"%{name}(?:\.\d+)? = (\w+\[[\d,]*\])",
                                line)] if m]


def jaxpr_digest(fn, *args) -> str:
    """sha256 (first 16 hex digits) of `fn`'s jaxpr at `args` (shapes),
    addresses cut."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def arrays_made(text: str, dtype, size: int) -> list[str]:
    """The instructions of a program that make an array of `dtype` with
    `size` elements or more: not its parameters, and not the views of
    them that cost nothing (a bitcast, an element of a tuple)."""
    name = {BF16: "bf16", jnp.int8: "s8"}[dtype]
    return [line.strip() for line in text.splitlines()
            for m in [re.search(rf" = {name}\[([\d,]+)\]", line)]
            if m and not re.search(
                r" (parameter|get-tuple-element|bitcast)\(", line)
            and math.prod(map(int, m.group(1).split(","))) >= size]


def loop_body(text: str) -> str:
    """The instructions of a program's `while` bodies themselves: the
    lines of every computation a `while` names as its body, and not those
    of the computations these call, where a slice or a dequantize inside
    a dot's fusion is an array on paper only (`arrays_made` reads every
    line of a text; give it this one)."""
    bodies = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    lines, inside = [], False
    for line in text.splitlines():
        if not line.startswith((" ", "}")):     # a computation's heading
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", line)
            inside = bool(m) and m.group(1) in bodies
        elif inside and line.startswith(" "):
            lines.append(line)
    return "\n".join(lines)


def state_arrays_made(text: str, pool: dict) -> list[str]:
    """The instructions of a program that make an array of the shape of
    the pool's "state" or "ring": a copy of either around a kernel
    (`arrays_made`'s rule for what costs nothing; the kernel's own
    outputs are the arrays themselves, updated in place)."""
    shapes = {"f32[" + ",".join(map(str, pool[k].shape)) + "]"
              for k in ("state", "ring")}
    return [line.strip() for line in text.splitlines()
            for m in [re.search(r" = \(?(f32\[[\d,]+\])", line)]
            if m and m.group(1) in shapes and not re.search(
                r" (parameter|get-tuple-element|bitcast)\(", line)
            and "tpu_custom_call" not in line]


def describers(topo):
    """(described, arg) for one described chip: `described(tree)` gives a
    pytree's arrays (or `eval_shape`'s shapes) as arguments placed on it,
    `arg(shape, dtype=int32)` one such argument."""
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def described(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    return described, arg


def compile_for(topo, fn, *args):
    """Compile `fn` for one described chip; returns how many Mosaic
    kernels the executable holds."""
    return compiled_text(topo, fn, *args).count(
        'custom_call_target="tpu_custom_call"')


def pool_args(kv_dtype):
    """(pool, pool[, k_scale, v_scale]) shapes of a bf16 or int8 pool."""
    pools = [((NB, BS, H, D), kv_dtype)] * 2
    if kv_dtype == jnp.int8:
        pools += [((NB, BS, H), jnp.float32)] * 2
    return pools


def with_scales(op):
    def call(q, k, v, tables, pos, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return op(q, k, v, tables, pos, impl="pallas", **kw)
    return call


@pytest.mark.parametrize("kv_dtype", [BF16, jnp.int8], ids=["bf16", "int8"])
def test_paged_decode_kernel_compiles(topo, kv_dtype):
    k, v, *scales = pool_args(kv_dtype)
    assert compile_for(
        topo, with_scales(da.paged_decode_attention),
        ((SLOTS, H, D), BF16), k, v, ((SLOTS, MB), I32), ((SLOTS,), I32),
        *scales) == 1


# `olmo-1b.chat-closed64` / `olmo-1b.chat-steady`: 32 slots, 16 heads of
# 128, 2049 blocks of 16, tables of 128
CELL_SLOTS, CELL_D, CELL_NB, CELL_MB = 32, 128, 2049, 128


@pytest.mark.parametrize("kv_dtype", [BF16, jnp.int8], ids=["bf16", "int8"])
def test_paged_decode_takes_the_cells_pool_as_stored(topo, kv_dtype):
    """The serving cells' own shape: one Mosaic kernel named
    `paged_decode`, and the pool reaches it as the program's parameter:
    nothing else in the program makes an array of the pool's size (no
    copy, transpose or fusion of it at a head size of 128; at 64, above,
    XLA stores the pool padded and the wrapper lays it out once)."""
    pool = (CELL_NB, BS, H, CELL_D)
    args = [((CELL_SLOTS, H, CELL_D), BF16), (pool, kv_dtype),
            (pool, kv_dtype), ((CELL_SLOTS, CELL_MB), I32),
            ((CELL_SLOTS,), I32)]
    if kv_dtype == jnp.int8:
        args += [(pool[:3], jnp.float32)] * 2
    compiled = compiled_for(topo, with_scales(da.paged_decode_attention),
                            *args)
    text = compiled.as_text()
    assert kernel_names(text) == ["paged_decode"]
    size = CELL_NB * BS * H * CELL_D
    assert arrays_made(text, kv_dtype, size) == []
    # int8: the two scale arrays are laid out for the kernel, 2 MiB each
    assert compiled.memory_analysis().temp_size_in_bytes < size // 8


@pytest.mark.parametrize("kv_dtype", [BF16, jnp.int8], ids=["bf16", "int8"])
def test_paged_verify_kernel_compiles(topo, kv_dtype):
    k, v, *scales = pool_args(kv_dtype)
    assert compile_for(
        topo, with_scales(da.paged_verify_attention),
        ((SLOTS, 5, H, D), BF16), k, v, ((SLOTS, MB), I32),
        ((SLOTS,), I32), *scales) == 1


@pytest.mark.parametrize("chunk,kv_dtype", [
    (32, BF16), (128, BF16), (512, BF16), (128, jnp.int8)],
    ids=["C32-bf16", "C128-bf16", "C512-bf16", "C128-int8"])
def test_paged_prefill_kernel_compiles(topo, chunk, kv_dtype):
    k, v, *scales = pool_args(kv_dtype)
    assert compile_for(
        topo, with_scales(da.paged_prefill_attention),
        ((chunk, H, D), BF16), k, v, ((MB,), I32), ((), I32),
        *scales) == 1


@pytest.mark.parametrize(
    "shape", [(24, 1024, H, D), (8, 2048, 16, 64), (4, 2048, 16, 128),
              (2, 100, 4, 64), (2, 20, 4, 64), (2, 127, 4, 128)],
    ids=["bench-model", "datadecide-300m", "olmo-1b-a-chip",
         "T100", "T20", "T127"])
def test_flash_attention_fwd_bwd_compiles(topo, shape):
    """The bench model's layer, and what one chip runs a layer in each
    benchmark cell, with the plan the code picks (a head's K and V
    resident whole, 512-wide tiles walked inside). More scoped VMEM than
    a kernel may use is refused here and nowhere before the chip. So is
    a short T that is no multiple of the 8 sublanes (one tile, indexed
    statically): interpret mode on the CPU takes any index."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))
    qkv = (shape, BF16)
    text = compiled_text(topo, jax.grad(loss, argnums=(0, 1, 2)),
                         qkv, qkv, qkv)
    assert sorted(kernel_names(text)) == [
        "flash_dkv", "flash_dq", "flash_fwd"]


def test_fused_xent_fwd_bwd_compiles(topo):
    def loss(x, embed, targets):
        return jnp.mean(fused_softmax_xent(x, embed, targets,
                                           impl="pallas"))
    assert compile_for(
        topo, jax.grad(loss, argnums=(0, 1)), ((8, 1024, 1024), BF16),
        ((50304, 1024), BF16), ((8, 1024), I32)) == 3   # lse, dx, dembed


def test_flash_attention_compiles_on_a_four_device_mesh(topo):
    """The compiler cannot partition a Mosaic kernel by itself
    ("Mosaic kernels cannot be automatically partitioned"); the model's
    attention call site shard_maps it over batch rows and heads."""
    mesh = MeshSpec(data=1, fsdp=2, tensor=2).build(topo.devices)
    cfg = gpt.GPTConfig(n_heads=H, d_model=H * D, attn_impl="flash")
    sharding = NamedSharding(
        mesh, PartitionSpec(("data", "fsdp"), None, "tensor", None))
    qkv = jax.ShapeDtypeStruct((8, 1024, H, D), BF16, sharding=sharding)
    text = jax.jit(
        lambda q, k, v: gpt.attention(q, k, v, cfg.attn_impl, mesh)
    ).lower(qkv, qkv, qkv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_fused_xent_compiles_on_a_four_device_mesh(topo):
    """Same for the loss: on a data-parallel mesh the kernels run under
    shard_map over the batch axes, the whole vocab on every shard."""
    mesh = MeshSpec(data=2, fsdp=2).build(topo.devices)

    def loss(x, embed, targets):
        return jnp.mean(fused_softmax_xent(x, embed, targets,
                                           impl="pallas", mesh=mesh))
    batch = PartitionSpec(("data", "fsdp"))
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for shape, dtype, spec in (
                ((8, 1024, 1024), BF16, batch),
                ((50304, 1024), BF16, PartitionSpec()),
                ((8, 1024), I32, batch))]
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


# (B, T, d_model) a chip of the benchmark's two cells
# (`datadecide-300m.pretrain-2k`, `olmo-1b.pretrain-2k-fsdp4`), vocab 50304.
# PR 23 compiled only (8, 1024, 1024), and a 0.5 MiB refusal at d_model 2048
# became a configuration's `loss_chunk`.
CELL_LOSS_SHAPES = {"datadecide-300m": (8, 2048, 1024),
                    "olmo-1b": (4, 2048, 2048)}
V5E_VMEM = 128 << 20


_SCOPED = r'"%sscoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",' \
          r'"size":"(\d+)"'


def _assert_loss_kernels(text, n, d):
    """Three kernels by name; each compiled call carries the scoped VMEM
    the plan asked for (none: the compiler's default of 16 MiB), uses no
    more, and an ask is under the chip's."""
    assert sorted(kernel_names(text)) == ["xent_de", "xent_dx", "xent_fwd"]
    plan = fused_xent._plan(n, 50304, d)
    assert plan.block_v >= 384
    calls = "\n".join(line for line in text.splitlines()
                      if 'custom_call_target="tpu_custom_call"' in line)
    asked = [int(m) for m in re.findall(_SCOPED % "", calls)]
    used = [int(m) for m in re.findall(_SCOPED % "used_", calls)]
    assert asked == ([plan.vmem_limit] * 3 if plan.vmem_limit else [])
    assert len(used) == 3
    assert max(used) <= (plan.vmem_limit or 16 << 20) < V5E_VMEM


@pytest.mark.parametrize("cell", sorted(CELL_LOSS_SHAPES))
def test_fused_xent_compiles_at_the_cells_shapes(topo, cell):
    """The loss's forward and gradient at each cell's shape a chip."""
    b, t, d = CELL_LOSS_SHAPES[cell]
    text = compiled_text(topo, _xent_grad, ((b, t, d), BF16),
                         ((50304, d), BF16), ((b, t), I32))
    _assert_loss_kernels(text, b * t, d)


def test_fused_xent_compiles_at_olmo_1b_under_fsdp4(topo):
    """`olmo-1b.pretrain-2k-fsdp4`'s loss as the cell runs it: B=16 over
    `MeshSpec(fsdp=4)`, the embedding whole on every chip."""
    mesh = MeshSpec(fsdp=4).build(topo.devices)
    b, t, d = CELL_LOSS_SHAPES["olmo-1b"]

    def grad(x, embed, targets):
        return jax.grad(lambda x, e: jnp.mean(fused_softmax_xent(
            x, e, targets, impl="pallas", mesh=mesh)),
            argnums=(0, 1))(x, embed)
    batch = PartitionSpec(("data", "fsdp"))
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for shape, dtype, spec in (
                ((4 * b, t, d), BF16, batch),
                ((50304, d), BF16, PartitionSpec()),
                ((4 * b, t), I32, batch))]
    text = jax.jit(grad).lower(*args).compile().as_text()
    _assert_loss_kernels(text, b * t, d)


def _flash_grad(q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


def _xent_grad(x, embed, targets):
    return jax.grad(lambda x, e: jnp.mean(
        fused_softmax_xent(x, e, targets, impl="pallas")),
        argnums=(0, 1))(x, embed)


_QKV = ((8, 1024, H, D), BF16)
_POOL = pool_args(BF16)
NAMED = {
    "flash": (_flash_grad, [_QKV] * 3,
              ("flash_fwd", "flash_dq", "flash_dkv")),
    "fused_xent": (_xent_grad, [((8, 1024, 1024), BF16),
                                ((50304, 1024), BF16), ((8, 1024), I32)],
                   ("xent_fwd", "xent_dx", "xent_de")),
    "paged_decode": (with_scales(da.paged_decode_attention),
                     [((SLOTS, H, D), BF16), *_POOL, ((SLOTS, MB), I32),
                      ((SLOTS,), I32)], ("paged_decode",)),
    "paged_verify": (with_scales(da.paged_verify_attention),
                     [((SLOTS, 5, H, D), BF16), *_POOL, ((SLOTS, MB), I32),
                      ((SLOTS,), I32)], ("paged_mq",)),
    "paged_prefill": (with_scales(da.paged_prefill_attention),
                      [((128, H, D), BF16), *_POOL, ((MB,), I32),
                       ((), I32)], ("paged_mq",)),
}


@pytest.mark.parametrize("case", sorted(NAMED))
def test_kernels_carry_their_names(topo, case):
    """A kernel's `name=` is its instruction's name in the compiled
    program (`%flash_fwd.N = ... custom-call`), which is what a profiler
    trace's `XLA Ops` line shows: readers match it, not shapes."""
    fn, args, names = NAMED[case]
    text = compiled_text(topo, fn, *args)
    assert sorted(kernel_names(text)) == sorted(names)
    for name in names:
        assert f"%{name}." in text


def test_rematerialised_forward_keeps_the_forward_kernels_name(topo):
    """Under `lax.scan` + `jax.checkpoint` the second run of the forward
    kernel is `%flash_fwd.N` too: a trace counts both under one name,
    and `flash_fwd` calls / `flash_dq` calls reads 2.0."""
    def loss(q, k, v):
        def layer(x, _):
            y = jax.checkpoint(lambda x: flash_attention(
                x, k, v, causal=True))(x)
            return y, None
        out, _ = jax.lax.scan(layer, q, None, length=2)
        return jnp.sum(out.astype(jnp.float32))
    text = compiled_text(topo, jax.grad(loss, argnums=(0, 1, 2)),
                         _QKV, _QKV, _QKV)
    kernels = kernel_names(text)
    assert kernels.count("flash_fwd") == 2 * kernels.count("flash_dq") == 2
    assert kernels.count("flash_dkv") == 1


def test_gpt_dots_policy_runs_the_forward_kernel_once(topo):
    """The twin of the test above, through `gpt`'s own layer checkpoint:
    "dots" saves the forward kernel's output and lse, so the compiled
    backward holds as many `flash_fwd` as `flash_dq`."""
    cfg = gpt.GPTConfig(vocab_size=512, d_model=H * D, n_layers=2,
                        n_heads=H, d_ff=1024, max_seq_len=512,
                        attn_impl="flash", remat_policy="dots")
    params = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                            jax.random.key(0))
    one = SingleDeviceSharding(topo.devices[0])
    abstract = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    text = jax.jit(jax.grad(
        lambda p, tokens: gpt.loss_fn(p, {"tokens": tokens}, cfg))).lower(
            jax.tree.map(abstract, params),
            jax.ShapeDtypeStruct((2, 513), I32, sharding=one)
        ).compile().as_text()
    kernels = kernel_names(text)
    assert (kernels.count("flash_fwd") == kernels.count("flash_dq")
            == kernels.count("flash_dkv") == 1)


# -- the dense family at the olmo-1b serving cells' shapes: 32 slots, 16
# layers, 16 heads of 128, 2049 blocks of 16, tables of 128, chunk 64, a
# verify window of 5 (`spec_k` 4), bf16 activations over the f32 masters
# as `gpt.serving_params` leaves them (bf16, cast once)

def _olmo(**more):
    import json
    from benchmarks.harness import common
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "olmo-1b.json")) as f:
        return common.model_config(json.load(f), "serve", **more)


DENSE_KERNEL = {"decode": "paged_decode", "prefill": "paged_mq",
                "verify": "paged_mq"}


@functools.lru_cache(maxsize=None)   # three tests read the same programs
def _dense_program(topo, program, cfg, apart=False):
    """`program` of `models/gpt.py` at the cells' shapes as the engine
    jits it (the cache donated, the weights as its load-time function
    leaves them, or with `apart` the masters cast to the activation
    dtype, `wq`, `wk` and `wv` a leaf each as every tree was before
    PR 60), compiled for one described chip;
    returns (compiled, params, pool) with the arguments as described."""
    described, arg = describers(topo)

    def load(k):
        masters = gpt.init_params(k, cfg)
        if apart:
            return jax.tree.map(
                lambda a: a.astype(cfg.activation_dtype()), masters)
        return gpt.serving_params(masters, cfg)

    params = described(jax.eval_shape(load, jax.random.key(0)))
    pool = described(jax.eval_shape(
        lambda: gpt.init_kv_pool(cfg, CELL_NB, BS)))
    if program == "prefill":
        lowered = jax.jit(
            lambda p, tok, cache, tab, start, n: gpt.prefill_paged(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, 64)), pool, arg((CELL_MB,)), arg(()), arg(()))
    else:
        step = {"decode": gpt.decode_step_paged,
                "verify": gpt.verify_step_paged}[program]
        tok = (CELL_SLOTS,) if program == "decode" else (CELL_SLOTS, 5)
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: step(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg(tok), arg((CELL_SLOTS,)),
            arg((CELL_SLOTS, CELL_MB)))
    return lowered.compile(), params, pool


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_dense_family_programs_update_the_pool_in_place(topo, program,
                                                        kv_dtype):
    """The decode step, a 64-token prefill chunk and a verify step of
    `benchmarks/configs/olmo-1b.json`: the kernel is there under its
    name (the scan's body holds it once), the donated pool is the
    program's output buffer, and the temporaries hold nothing of a
    layer's size, let alone the pool's (0.19-0.39 MB by the compile's
    own count, PR 39: every kernel reads the pool where it lies). None
    of the three makes an array of even a layer's size, as stored or
    head-major (at 16 heads and blocks of 16 the same shape), that is
    not the pool itself, written in place."""
    cfg = _olmo(kv_dtype={"bf16": "f32", "int8": "int8"}[kv_dtype])
    compiled, params, pool = _dense_program(topo, program, cfg)
    text = compiled.as_text()
    assert kernel_names(text) == [DENSE_KERNEL[program]]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(pool)     # updated in place
    assert mem.temp_size_in_bytes < 2e6                 # and never copied
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    # a layer of the pool, or more: the writes
    made = [line for line in arrays_made(
        text, {"bf16": BF16, "int8": jnp.int8}[kv_dtype],
        CELL_NB * BS * H * CELL_D) if f",{H},{CELL_D}]" in line]
    assert made and all("scatter" in line for line in made), made


@pytest.mark.parametrize("weight_dtype", ["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_dense_family_programs_convert_no_weight(topo, program,
                                                 weight_dtype):
    """The same three programs over the tree `gpt.serving_params` makes
    of `olmo-1b`'s f32 masters: every leaf arrives in the dtype the step
    reads it in, so no `convert` makes an array of a weight's shape
    (cast at use, XLA hoisted the casts out of the layer loop: 2.35 GB
    of temporaries and 7 GB of traffic in every run). The int8 stacks
    are dequantized a layer at a time inside the loop, as they were;
    their embedding arrives cast like the other's."""
    cfg = _olmo(weight_dtype=weight_dtype)
    compiled, params, _ = _dense_program(topo, program, cfg)
    masters = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                             jax.random.key(0))
    assert _nbytes(masters) == 4 * gpt.num_params(masters)
    served = {"f32": 2, "int8": 1}[weight_dtype] * gpt.num_params(masters)
    assert served <= _nbytes(params) < 1.1 * served
    # the stacks and the embedding (the positions' table has the shape
    # of one layer of `wq`, which the int8 path does make, a layer a turn)
    weights = {"bf16[%s]" % ",".join(map(str, leaf.shape))
               for leaf in (masters["embed"], *(
                   masters["layers"][name] for name in gpt.QUANTIZED_WEIGHTS),
                   params["layers"]["wqkv"])}
    assert weights == {"bf16[16,2048,8192]", "bf16[16,8192,2048]",
                       "bf16[16,2048,2048]", "bf16[50304,2048]",
                       "bf16[16,2048,3,2048]"}
    made = [line.strip() for line in compiled.as_text().splitlines()
            for m in [re.search(r" = (bf16\[[\d,]+\])\S* convert\(", line)]
            if m and m.group(1) in weights]
    assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


LAYER_WEIGHT = 2048 * 2048        # elements of one of a layer's projections


def _weights_made_a_layer(text):
    """The instructions of the layer loop's own body that make a bf16 or
    int8 array of a projection's size or more, the pool's in-place
    scatters apart; and the copies of a projection's output
    ([rows, 16, 128]) that stand before those scatters."""
    body = loop_body(text)
    made = [line for dtype in (BF16, jnp.int8)
            for line in arrays_made(body, dtype, LAYER_WEIGHT)
            if not ("scatter" in line and f"[16,{CELL_NB},{BS},{H},{CELL_D}]"
                    in line.split(" fusion(")[0])]
    copies = [line.strip() for line in body.splitlines() if re.search(
        rf" = bf16\[\d+,{H},{CELL_D}\]\S* copy\(", line)]
    return body, made, copies


@pytest.mark.parametrize("weight_dtype", ["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_dense_family_programs_read_a_layers_weights_where_they_lie(
        topo, program, weight_dtype):
    """The same three programs over the tree `gpt.serving_params` makes:
    a layer's q, k and v projections are one dot over the `wqkv` stack
    and the layer's number, as `wo`'s and the feed-forward's are, so no
    instruction of the loop's body but the pool's two scatters makes an
    array of a projection's size, bf16 or int8, and no copy of a
    projection's output stands before the scatters. Held apart
    (`wq`, `wk`, `wv`; every tree before PR 60), the reshape to heads
    behind each dot made XLA want that dot's weight transposed, and the
    body of the decode step read, three times a layer (ledger, PR 59,
    `olmo-1b.chat-steady`: 0.65 of the 3.84 s the device worked):

      %constant_dynamic-slice_fusion.8 = bf16[1,2048,2048]{2,1,0:..S(1)}
          fusion(<the [16,2048,2048] stack>, <layer>)   # HBM -> VMEM
      %copy.42 = bf16[1,2048,2048]{1,2,0:..S(1)}
          copy(%constant_dynamic-slice_fusion.8)        # transposed there
      %fusion.127 = bf16[32,16,128]{2,0,1} fusion(%bitcast.113, x, ..)
      %copy.43 = bf16[32,16,128]{2,1,0} copy(%fusion.127)

    and with int8 weights a dequantized `bf16[2048,2048]` besides
    (`test_the_loop_reader_sees_a_tree_held_apart` reads them off such a
    tree). Verify, which no cell runs, keeps three copies of its
    `[32,5,1,2048]` outputs (a window of 5 on the sublanes): activations,
    0.65 MB each."""
    cfg = _olmo(weight_dtype=weight_dtype)
    compiled, params, _ = _dense_program(topo, program, cfg)
    assert params["layers"]["wqkv"].shape == (16, 2048, 3, 2048)
    body, made, copies = _weights_made_a_layer(compiled.as_text())
    assert kernel_names(body) == [DENSE_KERNEL[program]]    # the layer loop
    assert len([line for line in body.splitlines()
                if "scatter" in line and " fusion(" in line]) >= 2
    assert not made, made
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 2e6


def test_the_loop_reader_sees_a_tree_held_apart(topo):
    """The control of the test above: the decode step over masters cast
    and held apart, the tree every engine read before PR 60, which
    `gpt.layer` still takes (the key check). The reader finds the
    listing's six weight-sized instructions, three slices into VMEM and
    three transposed copies, and the three copies of `[32,16,128]`."""
    compiled, params, _ = _dense_program(topo, "decode", _olmo(), True)
    assert "wqkv" not in params["layers"]
    _, made, copies = _weights_made_a_layer(compiled.as_text())
    slices = [line for line in made if "dynamic-slice" in line.split("=")[0]]
    moved = [line for line in made if " copy(" in line]
    assert len(slices) == len(moved) == 3 and len(made) == 6, made
    assert all("bf16[1,2048,2048]" in line and "S(1)" in line
               for line in made), made
    assert len(copies) == 3, copies


# sha256 (first 16 hex digits) of the jaxpr of `gpt.loss_fn` with its
# gradient over f32 masters at the two dense training cells' model
# configurations and batch (8 x 2,049 tokens), taken on PR 59's tree,
# the commit before `gpt.layer` learned to read a served tree's `wqkv`:
# a dict without that leaf traces what it traced (`OLMO_PAGED_JAX`'s
# rule for another JAX, and for a PR that means to change the step)
DENSE_TRAIN = {"datadecide-300m": "cafa6bbd2fe12c06",
               "olmo-1b": "f35be8d376119077"}


@pytest.mark.parametrize("name", sorted(DENSE_TRAIN))
def test_dense_training_traces_to_what_it_did(name):
    import json
    from benchmarks.harness import common
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    cfg = common.model_config(config, "train", **config["program"]["train"])
    masters = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                             jax.random.key(0))
    assert "wqkv" not in masters["layers"]
    batch = {"tokens": jax.ShapeDtypeStruct((8, 2049), I32)}
    assert jaxpr_digest(
        jax.value_and_grad(lambda p, b: gpt.loss_fn(p, b, cfg)),
        masters, batch) == DENSE_TRAIN[name]


@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_dense_family_lays_out_one_layer_at_head_size_64(topo, program):
    """Sixteen heads of 64 (`datadecide-300m`'s head size): XLA stores
    that pool in a layout of its own, so rows are written into, and pages
    read from, a lay-out of it, by `paged_mq` as by `paged_decode`. The
    temporaries hold one layer's (K and V, there and back: under 1 GB),
    not the sixteen layers' (4.3 GB) that a scatter or a reshape of the
    whole carried pool costs."""
    import dataclasses
    cfg = dataclasses.replace(_olmo(), d_model=H * D)
    compiled, params, pool = _dense_program(topo, program, cfg)
    assert kernel_names(compiled.as_text()) == [DENSE_KERNEL[program]]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(pool)
    assert mem.temp_size_in_bytes < 1e9


# -- the latent / sparse / routed-expert family at glm-5.2.docqa-closed24's
# shapes: 16 slots, 16,385 blocks of 16, chunk 512, 64 heads, rows of 576
# values (384 words), index keys of 128, top 2048, 16 experts of 2048 x 6144

def _glm():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import latent_sparse_moe as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "glm-5.2.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


LS, LNB, LMB, LK = 16, 16385, 1024, 2048
U32, F32 = jnp.uint32, jnp.float32
LATENT_KERNELS = {
    "sparse_latent_decode": (
        lambda q, pool, rows, count: sparse_latent.sparse_latent_decode(
            q, pool, rows, count, dtype=BF16, impl="pallas"),
        [((2, LS, 64, 384), BF16), ((6 * LNB * BS, 1, 384), U32),
         ((LS, LK), I32), ((LS,), I32)], ("sparse_latent_decode",)),
    "index_scores": (
        lambda q, w, pool, tables, pos: sparse_latent.index_scores(
            q, w, pool, tables, pos, impl="pallas"),
        [((LS, 32, 128), BF16), ((LS, 32), F32),
         ((2 * LNB, BS, 128), BF16), ((LS, LMB), I32), ((LS,), I32)],
        ("index_scores",)),
    "latent_chunk_attend": (
        lambda q, pool, table, selected, last:
        sparse_latent.latent_chunk_attend(
            q, pool, 3, table, selected, last, mixed=512, dtype=BF16,
            impl="pallas"),
        [((64, 512, 576), BF16), ((6, LNB, BS, 1, 384), U32), ((LMB,), I32),
         ((512, LMB * BS), jnp.bool_), ((), I32)],
        ("latent_chunk_attend",)),
    # `chip_smoke.py`'s serve-hybrid phase: 2 heads, rows of 160 values
    # in pages of 128, a table of 8 pages, the 128-token bucket
    "latent_chunk_attend_smoke": (
        lambda q, pool, table, selected, last:
        sparse_latent.latent_chunk_attend(
            q, pool, 0, table, selected, last, mixed=128, dtype=BF16,
            impl="pallas"),
        [((2, 128, 160), BF16), ((1, 64, 128, 1, 128), U32), ((8,), I32),
         ((128, 1024), jnp.bool_), ((), I32)], ("latent_chunk_attend",)),
    "experts_grouped": (
        lambda x, c, w, g, u, d: grouped_experts.experts_grouped(
            x, c, w, g, u, d, held_from=0, impl="pallas"),
        [((LS, 6144), BF16), ((LS, 8), I32), ((LS, 8), F32)]
        + [((16, 2048, 6144), BF16)] * 3, ("experts_grouped",)),
    "experts_grouped_prefill": (
        lambda x, c, w, g, u, d: grouped_experts.experts_grouped(
            x, c, w, g, u, d, held_from=0, impl="pallas",
            name=grouped_experts.EXPERTS_GROUPED_PREFILL),
        [((512, 6144), BF16), ((512, 8), I32), ((512, 8), F32)]
        + [((16, 2048, 6144), BF16)] * 3, ("experts_grouped_prefill",)),
}


@pytest.mark.parametrize("case", sorted(LATENT_KERNELS))
def test_latent_family_kernels_compile_under_their_names(topo, case):
    fn, args, names = LATENT_KERNELS[case]
    text = compiled_text(topo, fn, *args)
    assert sorted(kernel_names(text)) == sorted(names)


def test_flash_attention_compiles_at_keys_192_values_128(topo):
    """`kanana-2-30b-a3b.pretrain-8k`'s layer: BH 64, T 8192, keys 192
    wide and values 128, blocks of 1024 q rows and 2048 kv rows (2048 x
    2048 asks for more scoped VMEM than a kernel may use: a 192-wide
    block is stored 256 lanes wide)."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 1024, 2048)
                       .astype(jnp.float32))
    qk, v = ((2, 8192, 32, 192), BF16), ((2, 8192, 32, 128), BF16)
    text = compiled_text(topo, jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert sorted(kernel_names(text)) == [
        "flash_dkv", "flash_dq", "flash_fwd"]


def test_experts_grouped_train_kernels_compile_under_their_names(topo):
    """The same cell's expert layer, forward and backward: 16,384 tokens,
    6 of 128 experts each, 16 held at 768 x 2048, float32 masters."""
    def loss(x, w, g, u, d, c):
        return jnp.sum(grouped_experts.experts_grouped(
            x, c, w, g, u, d, held_from=0, impl="pallas",
            name=grouped_experts.EXPERTS_GROUPED_TRAIN)[0])
    text = compiled_text(
        topo, jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
        ((16384, 2048), BF16), ((16384, 6), F32),
        *[((16, 768, 2048), F32)] * 3, ((16384, 6), I32))
    assert sorted(kernel_names(text)) == [
        "experts_grouped_dw", "experts_grouped_dx", "experts_grouped_train"]


_GLM_PROGRAMS = {}


def _glm_program(topo, program):
    """-> (the decode step or a 512-token prefill chunk of
    `benchmarks/configs/glm-5.2.json` as the engine jits it, the pool
    donated, compiled once a module; the pool's shapes)."""
    if program not in _GLM_PROGRAMS:
        from ray_tpu.models import latent_sparse_moe as lsm
        config, cfg, ref = _glm()
        described, arg = describers(topo)
        params = described(jax.eval_shape(
            lambda k: ref.init_params(k, config), jax.random.key(0)))
        pool = described(jax.eval_shape(lambda: lsm.init_pool(cfg, LNB, BS)))
        if program == "decode":
            compiled = jax.jit(
                lambda p, cache, tok, pos, tab: lsm.decode(
                    p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
                params, pool, arg((LS,)), arg((LS,)),
                arg((LS, LMB))).compile()
        else:
            compiled = jax.jit(
                lambda p, tok, cache, tab, start, n: lsm.prefill(
                    p, tok, cache, cfg, block_table=tab, start=start,
                    length=n), donate_argnums=(2,)).lower(
                params, arg((1, 512)), pool, arg((LMB,)), arg(()),
                arg(())).compile()
        _GLM_PROGRAMS[program] = compiled, pool
    return _GLM_PROGRAMS[program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_family_programs_compile_at_the_cells_shapes(topo, program):
    """The decode step and a 512-token prefill chunk of
    `benchmarks/configs/glm-5.2.json` as the engine jits them (the pool
    donated): every kernel is there under its name, the pool is updated
    in place (no copy of it among the temporaries), and weights, pool and
    temporaries fit the chip."""
    compiled, pool = _glm_program(topo, program)
    want = {"decode": {"latent_row_write": 6, "index_scores": 2,
                       "sparse_latent_decode": 6, "experts_grouped": 5},
            "prefill": {"latent_row_write": 6, "latent_chunk_attend": 6,
                        "experts_grouped_prefill": 5}}[program]
    names = kernel_names(compiled.as_text())
    assert {n: names.count(n) for n in set(names)} == want
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 1e9                 # and never copied
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_latent_prefill_chunk_sorts_nothing_of_the_context(topo):
    """A chunk's selection counts its threshold (`_select_dense`): at the
    cell's shapes (a chunk of 512 over a table of 16,384 positions) the
    compiled program's only sorts are the router's, and none has the
    context's 16,384 columns, as the two `sort f32[512,16384]` of a
    `jax.lax.top_k(scores, 2048)` had; the decode step keeps its two of
    `[16, 16384]` (`select_rows` needs the positions)."""
    def sorts(program):
        return [line for line in _glm_program(
            topo, program)[0].as_text().splitlines()
            if re.search(r" sort\(", line)]
    assert sorts("prefill")
    assert not [line[:160] for line in sorts("prefill") if "16384" in line]
    assert [line for line in sorts("decode") if "[16,16384]" in line]


def test_latent_prefill_chunk_keeps_its_score_tile_in_the_kernel(topo):
    """A chunk attends in `latent_chunk_attend`: the compiled 512-token
    chunk makes no array of a head's score tile over a block of context
    (`f32[64,512,1024]`, which the online softmax in XLA wrote and read
    seven times a layer a block), and rebuilds no keys and values
    (`bf16[1024,64,448]`)."""
    text = _glm_program(topo, "prefill")[0].as_text()
    assert "latent_chunk_attend" in text
    assert not [line[:160] for line in text.splitlines()
                if "[64,512,1024]" in line or "[1024,64,448]" in line]


# -- the power-retention family at brumby-14b.docgen-closed24's shapes: 16
# slots and the trash block, 8 layers, 40 query heads over 8 key-value heads
# of 128, D = 9216, chunks of 512 and 128, vocabulary 151,936

def _brumby():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import retention_decoder as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "brumby-14b.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


RB, RL, RNB, RHQ, RHKV, RD, RBIG = 16, 8, 17, 40, 8, 128, 9216
RPOOL = [((RL, RNB, RHKV, RD, RBIG), F32), ((RL, RNB, RHKV, 1, RBIG), F32)]
RRING = [((RL, RNB, RHKV, 3, 16, RD), F32)]     # power_retention.RING


def _retention_chunk_case(c):
    from ray_tpu.ops import power_retention as pr
    return (lambda q, k, v, g, s, z, block, first, n: pr.retention_chunk(
        q, k, v, g, s, z, 3, block, first, n, eps=1e-6, impl="pallas"),
        [((c, RHQ, RD), BF16), ((c, RHKV, RD), BF16), ((c, RHKV, RD), BF16),
         ((c, RHKV), F32)] + RPOOL + [((), I32)] * 3)


def _retention_step_case():
    from ray_tpu.ops import power_retention as pr
    assert RRING[0][0][4] == pr.RING
    return (lambda q, k, v, g, s, z, ring, blocks, held: pr.retention_step(
        q, k, v, g, s, z, ring, 3, blocks, held, eps=1e-6, impl="pallas"),
        [((RB, RHQ, RD), BF16), ((RB, RHKV, RD), BF16),
         ((RB, RHKV, RD), BF16), ((RB, RHKV), F32)] + RPOOL + RRING
        + [((RB,), I32)] * 2)


RETENTION_KERNELS = {
    "retention_chunk_512": (_retention_chunk_case(512), "retention_chunk"),
    "retention_chunk_128": (_retention_chunk_case(128), "retention_chunk"),
    "retention_step": (_retention_step_case(), "retention_step"),
}


@pytest.mark.parametrize("case", sorted(RETENTION_KERNELS))
def test_retention_kernels_compile_under_their_names(topo, case):
    (fn, args), name = RETENTION_KERNELS[case]
    assert kernel_names(compiled_text(topo, fn, *args)) == [name]


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_retention_family_programs_compile_at_the_cells_shapes(topo, program):
    """The decode step and both prefill buckets of
    `benchmarks/configs/brumby-14b.json` as the engine jits them (the
    pool donated): one kernel a layer under its name, every sequence's
    state updated in place (no copy of the pool among the temporaries),
    weights, pool and temporaries fit the chip, and in the decode
    program nothing but the kernels has the states' array for a result
    (a row's state goes back by the kernel's own DMA, and only where
    the row folds: no op of the program writes a whole `s`)."""
    from ray_tpu.models import retention
    config, cfg, ref = _brumby()
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = described(jax.eval_shape(
        lambda: retention.init_pool(cfg, RNB, 16)))
    if program == "decode":
        compiled = jax.jit(
            lambda p, cache, tok, pos, tab: retention.decode(
                p, tok, cache, pos, tab, cfg)[:2],
            donate_argnums=(1,)).lower(
            params, pool, arg((RB,)), arg((RB,)), arg((RB, 1))).compile()
        want = {"retention_step": RL}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: retention.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((1,)), arg(()),
            arg(())).compile()
        want = {"retention_chunk": RL}
    text = compiled.as_text()
    names = kernel_names(text)
    assert {n: names.count(n) for n in set(names)} == want
    if program == "decode":
        whole = "f32[%d,%d,%d,%d,%d]" % RPOOL[0][0]
        makes = [line.strip()[:120] for line in text.splitlines()
                 if re.search(r" = \(?[^=]*" + re.escape(whole) + r"[^=]* "
                              r"(?!parameter|custom-call|get-tuple-element|"
                              r"tuple|bitcast)[a-z-]+\(", line)]
        assert not makes, makes
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 1e9                 # and never copied
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# -- the KDA-and-latent family at ling-3.0-flash-vl.reason-closed96's
# shapes: 64 slots and the trash block, six KDA layers of 32 heads of 128
# around one latent layer with rows of 576 values in pages of 128, chunks
# of 512 and 128, 128 held experts of a 512-wide router, vocabulary 39,296

def _ling():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import linear_latent as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


KB, KL, KNS, KH, KD = 64, 6, 65, 32, 128
KPAGES, KBS, KMB, KWORDS = 5697, 128, 128, 384
KSTATE = [((KL, KNS, KH, KD, KD), F32)]
KRING = [((KL, KNS, 8, 3 * KH, 128), F32)]


def _kda_chunk_case(c):
    from ray_tpu.ops import kda
    return (lambda q, k, v, g, beta, s, block, first, n: kda.kda_chunk(
        q, k, v, g, beta, s, 3, block, first, n, impl="pallas"),
        [((c, KH, KD), BF16)] * 3 + [((c, KH, KD), F32), ((c, KH), F32)]
        + KSTATE + [((), I32)] * 3)


def _kda_step_case():
    from ray_tpu.ops import kda
    return (lambda q, k, v, g, beta, s, ring, blocks, held: kda.kda_step(
        q, k, v, g, beta, s, ring, 3, blocks, held, impl="pallas"),
        [((KB, KH, KD), BF16)] * 3 + [((KB, KH, KD), F32), ((KB, KH), F32)]
        + KSTATE + KRING + [((KB,), I32)] * 2)


def _latent_decode_case(heads=KH, layers=1, pages=KPAGES, mb=KMB):
    """ling's call (32 heads over one layer's pages) or, with `heads` and
    the pool's size given, longcat's; both rows hold 512 + 64 values."""
    return (lambda q, pool, tables, count: sparse_latent.latent_decode(
        q, pool, layers - 1, tables, count, dtype=BF16, values=576,
        kv_rank=512, impl="pallas"),
        [((2, KB, heads, KWORDS), BF16), ((layers, pages, KBS, 1, KWORDS),
                                          jnp.uint32),
         ((KB, mb), I32), ((KB,), I32)])


HYBRID_KERNELS = {
    "kda_chunk_512": (_kda_chunk_case(512), "kda_chunk"),
    "kda_chunk_128": (_kda_chunk_case(128), "kda_chunk"),
    "kda_step": (_kda_step_case(), "kda_step"),
    "latent_decode": (_latent_decode_case(), "latent_decode"),
    # longcat-flash-chat.agent-closed96's: 64 heads over u32[8,2050,128,1,384]
    "latent_decode_64": (_latent_decode_case(64, 8, 2050, 32),
                         "latent_decode"),
}


@pytest.mark.parametrize("case", sorted(HYBRID_KERNELS))
def test_hybrid_kernels_compile_under_their_names(topo, case):
    """One kernel a call, under its own name; the state pool and the ring
    reach `kda_step` as the program's parameters and are written in place
    (nothing of a pool's size among the temporaries)."""
    (fn, args), name = HYBRID_KERNELS[case]
    compiled = compiled_for(topo, fn, *args)
    assert kernel_names(compiled.as_text()) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# `jaxpr_digest` of the KDA kernels' calls at the cell's shapes
# (`HYBRID_KERNELS`): the chunk kernel's as PR 63's tree traced them (PR 64
# gave the step kernel its ring and did not touch the chunk kernel), the
# step kernel's as PR 64 left it. A PR that means to change what the cell
# runs replaces them and says so; under another JAX the test skips
# (`OLMO_PAGED_JAX`'s rule)
KDA_DIGESTS = {
    "kda_chunk_512": "1849b00662e90453",
    "kda_chunk_128": "58b9d3173852774c",
    "kda_step": "24538af26f05bc47",
}


@pytest.mark.parametrize("case", sorted(KDA_DIGESTS))
def test_kda_kernels_trace_to_what_they_did(case):
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    fn, args = HYBRID_KERNELS[case][0]
    assert jaxpr_digest(fn, *(jax.ShapeDtypeStruct(shape, dtype)
                              for shape, dtype in args)) == KDA_DIGESTS[case]


# seconds to trace and lower the hybrid family's decode program here, on
# the CPU: ten times what the parent's took (the read-modify-write
# `kda_step`: 0.59 + 0.35 s, alone on the machine, in the minutes PR 64's
# took 0.97 + 0.50 s); a kernel body unrolled in Python over a state's
# heads and tiles takes many times this (PR 53: 19 s)
HYBRID_TRACE_AND_LOWER_S = 10.0


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_hybrid_family_programs_compile_at_the_cells_shapes(topo, program,
                                                            caplog):
    """The decode step and both prefill buckets of
    `benchmarks/configs/ling-3.0-flash-vl.json` as the engine jits them
    (the pool donated): every kernel is there under its name, a call a
    layer of its kind, no fallback; states, rings, tails and pages are
    updated in place (no copy of the pool among the temporaries, and no
    array of the states' or the rings' shape made around a kernel);
    weights, pool and temporaries fit the chip; the decode program traces
    and lowers in seconds."""
    from ray_tpu.models import linear_latent
    config, cfg, ref = _ling()
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = described(jax.eval_shape(lambda: linear_latent.init_pool(
        cfg, KPAGES, KBS, state_blocks=KNS)))
    assert [(pool[k].shape, pool[k].dtype) for k in ("state", "ring")] == \
        KSTATE + KRING
    if program == "decode":
        started = time.monotonic()
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: linear_latent.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((KB,)), arg((KB,)),
            arg((KB, 1 + KMB)))
        # paid at every start of a process: no compile cache answers it
        assert time.monotonic() - started < HYBRID_TRACE_AND_LOWER_S
        compiled = lowered.compile()
        want = {"kda_step": KL, "latent_row_write": 1, "latent_decode": 1,
                "experts_grouped": 6}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: linear_latent.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((1 + KMB,)), arg(()),
            arg(())).compile()
        want = {"kda_chunk": KL, "latent_row_write": 1,
                "latent_chunk_attend": 1, "experts_grouped_prefill": 6}
    names = kernel_names(compiled.as_text())
    assert {n: names.count(n) for n in set(names)} == want
    assert not fallbacks(caplog)
    # neither the states nor the ring beside them are copied around a
    # kernel, nor carried into VMEM and back (`ops/mamba2.py`'s ring in
    # three arrays was, PR 56)
    assert not state_arrays_made(compiled.as_text(), pool)
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 1e9                 # and never copied
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# -- the window-and-full family at command-a-plus.mixed-closed24's shapes:
# 16 slots, 128 query heads over 8 key-value heads of 128, one full layer
# of 3,712 pages of 128 and three window layers of 592 (a ring of 37 a
# slot), tables of 256 columns a kind, chunks of 512 and 128, 16 held
# experts of a 128-wide router, vocabulary 32,768

def _command_a():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import window_moe as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "command-a-plus.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


WB, WHQ, WHKV, WD, WBS, WMB, WWIN = 16, 128, 8, 128, 128, 256, 4096
WFULL = ((1, 3713, WHKV, WBS, WD), BF16)
WRING = ((3, 593, WHKV, WBS, WD), BF16)


def _gqa_case(form, window):
    pool = WRING if window else WFULL
    if form == "decode":
        return (lambda q, k, v, t, p: da.gqa_decode_attention(
            q, k, v, t, p, layer=0, window=window, impl="pallas"),
            [((WB, WHQ, WD), BF16), pool, pool, ((WB, WMB), I32),
             ((WB,), I32)])
    return (lambda q, k, v, t, p: da.gqa_chunk_attention(
        q, k, v, t, p, layer=0, window=window, impl="pallas"),
        [((form, WHQ, WD), BF16), pool, pool, ((WMB,), I32), ((), I32)])


GQA_KERNELS = {
    "window_decode": (_gqa_case("decode", WWIN), "gqa_window_decode"),
    "full_decode": (_gqa_case("decode", None), "gqa_full_decode"),
    "window_chunk_512": (_gqa_case(512, WWIN), "gqa_window_chunk"),
    "window_chunk_128": (_gqa_case(128, WWIN), "gqa_window_chunk"),
    "full_chunk_512": (_gqa_case(512, None), "gqa_full_chunk"),
    "full_chunk_128": (_gqa_case(128, None), "gqa_full_chunk"),
}


@pytest.mark.parametrize("case", sorted(GQA_KERNELS))
def test_gqa_kernels_compile_under_their_names(topo, case):
    """One kernel a call, under its own name, and the pool reaches it as
    the program's parameter: nothing makes an array of a pool's size."""
    (fn, args), name = GQA_KERNELS[case]
    compiled = compiled_for(topo, fn, *args)
    text = compiled.as_text()
    assert kernel_names(text) == [name]
    pool = args[1][0]
    assert arrays_made(text, BF16, math.prod(pool[1:])) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# sha256 (first 16 hex digits) of the jaxpr of the paged wrappers at
# `olmo-1b`'s serving shapes (32 slots, 16 heads of 128, 16 layers of 2,049
# blocks of 16, tables of 128), taken on the commit before the kernel body
# was given grouped heads and a window (PR 43's tree). A PR that means to
# change what those two cells run replaces them and says so. A jaxpr's
# text is this JAX's: under another version the test skips, and whoever
# upgrades takes the digests anew on the tree before the upgrade's first
# change to `ops/decode_attention.py`
OLMO_PAGED_JAX = "0.9.0"
OLMO_PAGED = {"decode": "94733b9694d53a0a", "mq_512": "cbd56ac3bf4816ea",
              "mq_128": "bd50bd7197c932a9"}


@pytest.mark.parametrize("case", sorted(OLMO_PAGED))
def test_paged_kernels_at_olmo_1b_trace_to_what_they_did(case):
    """`g = 1` without a window is the program `olmo-1b`'s two cells
    run: the wrapper and the kernel body trace, equation for equation,
    to what they did before the body took grouped heads and a window
    (the kernel's serialized Mosaic module is not the same from one
    compile to the next, so the jaxpr is what is held)."""
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    pool = jax.ShapeDtypeStruct((16, CELL_NB, BS, H, CELL_D), BF16)
    scalar = jax.ShapeDtypeStruct((), I32)

    if case == "decode":
        args = (jax.ShapeDtypeStruct((CELL_SLOTS, H, CELL_D), BF16), pool,
                pool, jax.ShapeDtypeStruct((CELL_SLOTS, CELL_MB), I32),
                jax.ShapeDtypeStruct((CELL_SLOTS,), I32), scalar)

        def fn(q, k, v, t, p, layer):
            return da.paged_decode_attention(q, k, v, t, p, layer=layer,
                                             impl="pallas")
    else:
        args = (jax.ShapeDtypeStruct((int(case[3:]), H, CELL_D), BF16),
                pool, pool, jax.ShapeDtypeStruct((CELL_MB,), I32), scalar,
                scalar)

        def fn(q, k, v, t, p, layer):
            return da.paged_prefill_attention(q, k, v, t, p, layer=layer,
                                              impl="pallas")
    assert jaxpr_digest(fn, *args) == OLMO_PAGED[case]


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_window_family_programs_compile_at_the_cells_shapes(topo, program):
    """The decode step and both prefill buckets of
    `benchmarks/configs/command-a-plus.json` as the engine jits them (the
    pool donated): every kernel is there under its name, a call a layer
    of its kind; both kinds of page are updated in place (no copy of a
    pool among the temporaries); weights, pool and temporaries fit the
    chip."""
    from ray_tpu.models import window_moe
    config, cfg, ref = _command_a()
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = described(jax.eval_shape(lambda: window_moe.init_pool(
        cfg, WFULL[0][1], WBS, bounded_blocks=WRING[0][1])))
    if program == "decode":
        compiled = jax.jit(
            lambda p, cache, tok, pos, tab: window_moe.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((WB,)), arg((WB,)),
            arg((WB, 2 * WMB))).compile()
        want = {"gqa_window_decode": 3, "gqa_full_decode": 1,
                "experts_grouped": 4}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: window_moe.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((2 * WMB,)), arg(()),
            arg(())).compile()
        want = {"gqa_window_chunk": 3, "gqa_full_chunk": 1,
                "experts_grouped_prefill": 4}
    names = kernel_names(compiled.as_text())
    assert {n: names.count(n) for n in set(names)} == want
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    # under one window array (466 MB), the smallest of the pool's: none
    # of them is copied or laid out anew around its writes (a scatter
    # whose window is not contiguous in a head-major page did both)
    assert mem.temp_size_in_bytes < 256 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# -- the state-space-and-latent-experts family at
# nemotron-3-super.chat-closed96's shapes: 64 slots, five state layers of
# 128 heads of 64 x 128 in 65 state blocks, one attention layer of 32
# query heads over 2 key-value heads of 128 in 5,121 pages of 128, tables
# of 1 + 80 columns, chunks of 512 and 128, 128 held experts of width
# 2,688 in a 1,024-wide latent under a 512-wide router, 22 a token,
# vocabulary 32,768

def _nemotron():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import mamba_moe as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "nemotron-3-super.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


MB_, MH, MG, MP, MN, ML, MNS = 64, 128, 8, 64, 128, 5, 65
MPAGES, MBS, MCOLS = 5121, 128, 80
MSTATE = [((ML, MNS, MH // 2, MN, 2 * MP), jnp.float32)]
# the ring beside the states: 8 entries of 64 rows of d x, 8 of B and 2 of
# log-decays (`mamba2._entry_rows`)
MRING = [((ML, MNS, 8, 80, 128), jnp.float32)]


def _mamba2_chunk_case(c):
    from ray_tpu.ops import mamba2
    return (lambda x, dt, a, b, cc, s, block, first, n: mamba2.mamba2_chunk(
        x, dt, a, b, cc, s, 3, block, first, n, impl="pallas"),
        [((c, MH, MP), BF16), ((c, MH), jnp.float32), ((MH,), jnp.float32)]
        + [((c, MG, MN), BF16)] * 2 + MSTATE + [((), I32)] * 3)


def _mamba2_step_case():
    from ray_tpu.ops import mamba2
    return (lambda x, dt, a, b, cc, s, ring, blocks, held: mamba2.mamba2_step(
        x, dt, a, b, cc, s, ring, 3, blocks, held, impl="pallas"),
        [((MB_, MH, MP), BF16), ((MB_, MH), jnp.float32),
         ((MH,), jnp.float32)] + [((MB_, MG, MN), BF16)] * 2 + MSTATE
        + MRING + [((MB_,), I32)] * 2)


def _ungated_experts_case(n, name):
    return (lambda x, c, w, u, d: grouped_experts.experts_grouped(
        x, c, w, None, u, d, held_from=0, impl="pallas", name=name),
        [((n, 1024), BF16), ((n, 22), I32), ((n, 22), jnp.float32)]
        + [((128, 2688, 1024), BF16)] * 2)


MAMBA_KERNELS = {
    "mamba2_chunk_512": (_mamba2_chunk_case(512), "mamba2_chunk"),
    "mamba2_chunk_128": (_mamba2_chunk_case(128), "mamba2_chunk"),
    "mamba2_step": (_mamba2_step_case(), "mamba2_step"),
    "experts_ungated_decode": (_ungated_experts_case(
        64, "experts_grouped"), "experts_grouped"),
    "experts_ungated_chunk": (_ungated_experts_case(
        512, "experts_grouped_prefill"), "experts_grouped_prefill"),
}


@pytest.mark.parametrize("case", sorted(MAMBA_KERNELS))
def test_mamba_family_kernels_compile_under_their_names(topo, case):
    """One kernel a call, under its own name; the state pool reaches the
    recurrence's kernels as the program's parameter and is written in
    place (nothing of a pool's size among the temporaries)."""
    (fn, args), name = MAMBA_KERNELS[case]
    compiled = compiled_for(topo, fn, *args)
    assert kernel_names(compiled.as_text()) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# seconds to trace and lower a family's decode program here, on the CPU
TRACE_AND_LOWER_S = 20.0


def fallbacks(caplog) -> list[str]:
    """What `backend.note_fallback` logged while a program was traced."""
    return [r.getMessage() for r in caplog.records
            if "no Pallas plan" in r.getMessage()]


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_mamba_family_programs_compile_at_the_cells_shapes(topo, program,
                                                           caplog):
    """The decode step and both prefill buckets of
    `benchmarks/configs/nemotron-3-super.json` as the engine jits them
    (the pool donated): every kernel is there under its name, a call a
    layer of its kind, no fallback; states, rings, tails and pages are
    updated in place (no copy of a pool among the temporaries: under the
    state array's 1.36 GB); weights, pool and temporaries fit the chip;
    the decode program traces and lowers in seconds."""
    from ray_tpu.models import mamba_moe
    config, cfg, ref = _nemotron()
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = described(jax.eval_shape(lambda: mamba_moe.init_pool(
        cfg, MPAGES, MBS, state_blocks=MNS)))
    if program == "decode":
        started = time.monotonic()
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: mamba_moe.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((MB_,)), arg((MB_,)),
            arg((MB_, 1 + MCOLS)))
        # tracing and lowering are paid at every start of a process and
        # no compile cache answers them: about 1.5 s here; a kernel body
        # unrolled in Python over a state's tiles takes many times this
        assert time.monotonic() - started < TRACE_AND_LOWER_S
        compiled = lowered.compile()
        want = {"mamba2_step": ML, "gqa_full_decode": 1,
                "experts_grouped": 5}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: mamba_moe.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((1 + MCOLS,)), arg(()),
            arg(())).compile()
        want = {"mamba2_chunk": ML, "gqa_full_chunk": 1,
                "experts_grouped_prefill": 5}
    names = kernel_names(compiled.as_text())
    assert {n: names.count(n) for n in set(names)} == want
    assert not fallbacks(caplog)
    # neither the states nor the ring beside them are copied around a
    # kernel (a ring in three arrays was: the compiler carried the two
    # small ones into VMEM before every layer's call and back after it)
    assert not state_arrays_made(compiled.as_text(), pool)
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 1e9                 # and never copied
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# ---------------------------------------------------------------------------
# the parallel-hybrid family (`benchmarks/configs/falcon-h1-34b.json`): a
# state of 128 x 256 a head, a group of 5 query heads a key-value head
# ---------------------------------------------------------------------------

def _falcon():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import parallel_hybrid as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


FH, FG, FP, FN, FL, FHQ, FHKV, FB = 32, 2, 128, 256, 6, 20, 4, 64
FSTATE = [((FL, FB + 1, FH, FN, FP), jnp.float32)]
# 8 entries of 32 rows of d x, 4 of B and 1 of log-decays, in 40
FRING = [((FL, FB + 1, 8, 40, 128), jnp.float32)]
FPAGES = [((FL, 1900, FHKV, 128, 128), BF16)] * 2


def _wide_chunk_case(c):
    from ray_tpu.ops import mamba2
    return (lambda x, dt, a, b, cc, s, block, first, n: mamba2.mamba2_chunk(
        x, dt, a, b, cc, s, 3, block, first, n, impl="pallas"),
        [((c, FH, FP), BF16), ((c, FH), jnp.float32), ((FH,), jnp.float32)]
        + [((c, FG, FN), BF16)] * 2 + FSTATE + [((), I32)] * 3)


def _wide_step_case():
    from ray_tpu.ops import mamba2
    return (lambda x, dt, a, b, cc, s, ring, blocks, held: mamba2.mamba2_step(
        x, dt, a, b, cc, s, ring, 3, blocks, held, impl="pallas"),
        [((FB, FH, FP), BF16), ((FB, FH), jnp.float32),
         ((FH,), jnp.float32)] + [((FB, FG, FN), BF16)] * 2 + FSTATE
        + FRING + [((FB,), I32)] * 2)


def _five_decode_case():
    return (lambda q, k, v, t, pos: da.gqa_decode_attention(
        q, k, v, t, pos, layer=3, impl="pallas"),
        [((FB, FHQ, 128), BF16)] + FPAGES + [((FB, 128), I32),
                                              ((FB,), I32)])


def _five_chunk_case(c):
    return (lambda q, k, v, t, start: da.gqa_chunk_attention(
        q, k, v, t, start, layer=3, impl="pallas"),
        [((c, FHQ, 128), BF16)] + FPAGES + [((128,), I32), ((), I32)])


PARALLEL_KERNELS = {
    "mamba2_chunk_512": (_wide_chunk_case(512), "mamba2_chunk"),
    "mamba2_chunk_128": (_wide_chunk_case(128), "mamba2_chunk"),
    "mamba2_step": (_wide_step_case(), "mamba2_step"),
    "gqa_full_decode": (_five_decode_case(), "gqa_full_decode"),
    "gqa_full_chunk_512": (_five_chunk_case(512), "gqa_full_chunk"),
    "gqa_full_chunk_128": (_five_chunk_case(128), "gqa_full_chunk"),
}


@pytest.mark.parametrize("case", sorted(PARALLEL_KERNELS))
def test_parallel_hybrid_kernels_compile_under_their_names(topo, case):
    """The recurrence's kernels at a head of 128 x 256 (a head a lane
    tile, two tiles of rows) and the grouped-head kernels at a group of
    5 (padded to a sublane tile in decode, a query tile of 200 in a
    chunk): one kernel a call, under its own name, the pool in place."""
    (fn, args), name = PARALLEL_KERNELS[case]
    compiled = compiled_for(topo, fn, *args)
    assert kernel_names(compiled.as_text()) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# what `refs/parallel_hybrid.py` holds at once beside weights and pool
# (`test_parallel_hybrid_reference_fits_beside_the_pool`; 0.45 GB in
# `benchmarks/configs/falcon-h1-34b.json`'s `deployment`)
REFERENCE_BYTES = 0.45e9


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_parallel_hybrid_programs_compile_at_the_cells_shapes(topo, program,
                                                              caplog):
    """The decode step and both prefill buckets of
    `benchmarks/configs/falcon-h1-34b.json` as the engine jits them (the
    pool donated): both kernels of every layer under their names, no
    fallback; states, rings, tails and pages are updated in place;
    weights, pool (the ring beside the states in it) and temporaries
    leave the cell under the chip's 15.75 GB with the reference's 0.45
    GB beside them; the decode program traces and lowers in seconds."""
    from ray_tpu.models import parallel_hybrid
    config, cfg, ref = _falcon()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    slots, cols = serve["slots"], serve["max_len"] // kw["block_size"]
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = described(jax.eval_shape(lambda: parallel_hybrid.init_pool(
        cfg, kw["cache_blocks"] + 1, kw["block_size"],
        state_blocks=slots + 1)))
    if program == "decode":
        started = time.monotonic()
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: parallel_hybrid.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((slots,)), arg((slots,)),
            arg((slots, 1 + cols)))
        assert time.monotonic() - started < TRACE_AND_LOWER_S
        compiled = lowered.compile()
        want = {"mamba2_step": FL, "gqa_full_decode": FL}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: parallel_hybrid.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((1 + cols,)), arg(()),
            arg(())).compile()
        want = {"mamba2_chunk": FL, "gqa_full_chunk": FL}
    names = kernel_names(compiled.as_text())
    assert {n: names.count(n) for n in set(names)} == want
    assert not fallbacks(caplog)
    assert not state_arrays_made(compiled.as_text(), pool)
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 1e9                 # and never copied
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + REFERENCE_BYTES) < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


def test_parallel_hybrid_reference_fits_beside_the_pool(topo):
    """The cell's comparison runs `refs/parallel_hybrid.py` in the
    replica, beside the weights and the whole pool, on a sequence padded
    to `max_len`: what it holds at once (the float32 residual, 336 MB,
    and a block's work) has to fit in what 64 slots, their rings and the
    pages leave of the chip."""
    from ray_tpu.models import parallel_hybrid
    config, cfg, ref = _falcon()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = jax.eval_shape(lambda: parallel_hybrid.init_pool(
        cfg, kw["cache_blocks"] + 1, kw["block_size"],
        state_blocks=serve["slots"] + 1))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda p, s: ref.token_logprobs(p, s, config)).lower(
            params, arg((1, serve["max_len"]))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.temp_size_in_bytes < 0.6e9
    # the chip's allocator read its fullest 56 MB under this sum both
    # times it was asked: 15,661,937,664 B against 15.7177 GB at PR 54's
    # tree, 15,725,839,360 B against 15.7818 GB with PR 56's ring of
    # 63.9 MB beside the states (PERF.md, section 6); of the chip's
    # 15.75 GB the cell leaves 24 MB
    assert (mem.argument_size_in_bytes + pool_bytes
            + mem.temp_size_in_bytes) < 15.75e9 + 0.056e9 - 0.024e9


# sha256 (first 16 hex digits) of the jaxpr of the recurrence's kernels at
# the two cells' shapes (`nemotron-3-super.chat-closed96`, 128 heads of 64
# x 128; `falcon-h1-34b.reason-closed96`, 32 of 128 x 256): `mamba2_chunk`
# at both prefill buckets as PR 54's tree traced it (PR 56 gave the step a
# ring and left the chunk as it was: the `chunk_*` metrics are that PR's
# control), `mamba2_step` taken anew on PR 56's tree. A PR that means to
# change what those cells run replaces them and says so; under another
# JAX the test skips (`OLMO_PAGED_JAX`'s rule)
MAMBA2_DIGESTS = {
    "nemotron.mamba2_chunk_512": "b01aee938b14d69c",
    "nemotron.mamba2_chunk_128": "b1fe322f13535407",
    "falcon.mamba2_chunk_512": "b83182b9aab29639",
    "falcon.mamba2_chunk_128": "2753004016595ad9",
    "nemotron.mamba2_step": "aed1ad63426dda00",
    "falcon.mamba2_step": "39549e1edb2d37d3",
}


@pytest.mark.parametrize("case", sorted(MAMBA2_DIGESTS))
def test_mamba2_kernels_trace_to_what_they_did(case):
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    cell, kernel = case.split(".")
    cases = MAMBA_KERNELS if cell == "nemotron" else PARALLEL_KERNELS
    fn, args = cases[kernel][0]
    assert jaxpr_digest(fn, *(jax.ShapeDtypeStruct(shape, dtype)
                              for shape, dtype in args)) == \
        MAMBA2_DIGESTS[case]


# sha256 (first 16 hex digits) of the jaxpr of `experts_grouped` at the
# four shapes the benchmark ran it at before it took a second form of an
# expert (tokens, model width, expert width, held experts, experts a
# token; the gated form, which is what those cells run), and of
# `gqa_full_*` at `command-a-plus.mixed-closed24`'s: taken on PR 49's
# tree, the commit before `ops/grouped_experts.py` was given the ungated
# relu^2 form and `_width_slice` its lane rule. A PR that means to change
# what those cells run replaces them and says so; under another JAX the
# test skips (`OLMO_PAGED_JAX`'s rule)
EXPERT_SHAPES = {
    "glm-5.2.decode": (16, 6144, 2048, 16, 8, "experts_grouped"),
    "glm-5.2.prefill": (512, 6144, 2048, 16, 8, "experts_grouped_prefill"),
    "kanana-2-30b-a3b.train": (16384, 2048, 768, 16, 6,
                               "experts_grouped_train"),
    "ling-3.0-flash-vl.decode": (64, 2560, 768, 128, 8, "experts_grouped"),
    "ling-3.0-flash-vl.prefill": (512, 2560, 768, 128, 8,
                                  "experts_grouped_prefill"),
    "command-a-plus.decode": (16, 4096, 4096, 16, 8, "experts_grouped"),
    "command-a-plus.prefill": (512, 4096, 4096, 16, 8,
                               "experts_grouped_prefill"),
}
UNCHANGED = {
    "glm-5.2.decode": "dcb9f56380bb3e07",
    "glm-5.2.prefill": "0c1fdf0b9654763e",
    "kanana-2-30b-a3b.train": "e0c7a0c0d8754e1e",
    "ling-3.0-flash-vl.decode": "e736e8f0bbc7710a",
    "ling-3.0-flash-vl.prefill": "444375cd53e7d8d6",
    "command-a-plus.decode": "dc89806515198a63",
    "command-a-plus.prefill": "d4e148bdc7421574",
    "gqa_full_decode": "aea805d4770d2386",
    "gqa_full_chunk_512": "dd1d92bec09f2ab6",
    "gqa_full_chunk_128": "a50facbd2e63945d",
}


@pytest.mark.parametrize("case", sorted(UNCHANGED))
def test_shared_kernels_trace_to_what_they_did(case):
    """The gated form of `experts_grouped` at the four shapes the
    benchmark's other cells run (forward, and for the training cell its
    gradient) and `gqa_full_*` at `command-a-plus`'s trace, equation for
    equation, to what they did before this family shared them."""
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct

    if case in EXPERT_SHAPES:
        n, d, f, held, k, name = EXPERT_SHAPES[case]
        args = (shape((n, d), BF16), shape((n, k), I32),
                shape((n, k), f32)) + (shape((held, f, d), BF16),) * 3

        def forward(x, c, w, g, u, dn):
            return grouped_experts.experts_grouped(
                x, c, w, g, u, dn, held_from=0, impl="pallas", name=name)

        def fn(x, c, w, g, u, dn):
            if not name.endswith("train"):
                return forward(x, c, w, g, u, dn)
            return jax.grad(
                lambda x, w, g, u, dn: jnp.sum(forward(x, c, w, g, u, dn)[0]),
                argnums=(0, 1, 2, 3, 4))(x, w, g, u, dn)
    else:
        pool = shape(*WFULL)
        if case == "gqa_full_decode":
            args = (shape((WB, WHQ, WD), BF16), pool, pool,
                    shape((WB, WMB), I32), shape((WB,), I32))

            def fn(q, k, v, t, p):
                return da.gqa_decode_attention(q, k, v, t, p, layer=0,
                                               window=None, impl="pallas")
        else:
            args = (shape((int(case.rsplit("_", 1)[1]), WHQ, WD), BF16),
                    pool, pool, shape((WMB,), I32), shape((), I32))

            def fn(q, k, v, t, p):
                return da.gqa_chunk_attention(q, k, v, t, p, layer=0,
                                              window=None, impl="pallas")
    assert jaxpr_digest(fn, *args) == UNCHANGED[case]


# `jaxpr_digest` of `SHORTCONV_KERNELS`' three calls of `experts_grouped`,
# `lfm2-8b-a1b.chat-closed192`'s (every expert of a router of
# 32 held, 4 a token: a step's 128 rows and the 128 bucket's in row tiles
# of 32, a 512 chunk in tiles of 128), taken on PR 63's tree, which gave
# `row_tile` the held experts to plan by. Apart from `UNCHANGED`: a PR
# that means to change the plan of these calls (the tile, the grid's
# order) replaces these and says so, and leaves those alone
LFM2_PLAN = {
    "experts_grouped": "03dc07d4fdc5a8c3",
    "experts_grouped_prefill": "a6cac6ac09270951",
    "experts_grouped_prefill_128": "a10a1bf20c9a30e8",
}


@pytest.mark.parametrize("case", sorted(LFM2_PLAN))
def test_lfm2_expert_calls_trace_to_their_plan(case):
    if jax.__version__ != OLMO_PAGED_JAX:
        pytest.skip(f"digests taken under jax {OLMO_PAGED_JAX}")
    (fn, args), _ = SHORTCONV_KERNELS[case]
    assert jaxpr_digest(fn, *(jax.ShapeDtypeStruct(shape, dtype)
                              for shape, dtype in args)) == LFM2_PLAN[case]


# -- the window / full family on the training path, at
# `mellum2-12b-a2.5b.longctx-32k`'s shapes: one sequence of 32,768, 32
# query heads over 4 key-value heads of 128, a window of 1,024, 16 experts
# of 896 x 2304 held of a 64-wide router, 8 a token, 8,192 tokens a chunk

def _flash_32k(window):
    def grad(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, 2048, 2048, window).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    q, kv = ((1, 32768, 32, 128), BF16), ((1, 32768, 4, 128), BF16)
    return grad, [q, kv, kv]


FLASH_32K = {
    "band": (1024, ("flash_fwd_band", "flash_dq_band", "flash_dkv_band")),
    "full": (None, ("flash_fwd", "flash_dq", "flash_dkv")),
}


@pytest.mark.parametrize("case", sorted(FLASH_32K))
def test_flash_kernels_compile_at_32k_under_their_names(topo, case):
    """The banded and the full call, forward and backward, with grouped
    heads: K and V stay 4 heads wide (no [.., 32, 128] key or value is
    made), and dK, dV come out of the kernel at 4 heads."""
    window, names = FLASH_32K[case]
    fn, args = _flash_32k(window)
    text = compiled_text(topo, fn, *args)
    assert sorted(kernel_names(text)) == sorted(names)
    assert re.search(rf"%{names[2]}\.\d+ = \(bf16\[4,32768,128\]", text)
    # nothing repeats a key or a value to the query heads: no array of
    # q's size comes out of a broadcast, a concatenation or a gather
    # (but the test's own dO: the gradient of a sum, a constant spread)
    assert not [line for line in arrays_made(text, BF16, 32 * 32768 * 128)
                if re.search(r" (broadcast|concatenate|gather)\((?!%constant)",
                             line)]


def test_experts_grouped_train_kernels_compile_at_width_896(topo):
    """The same cell's expert layer, forward, dx and dw, a chunk of 8,192
    tokens: an expert's 896 rows are whole lane tiles and no multiple of
    256, and go through in `_width_slice`'s one slice."""
    assert grouped_experts._width_slice(896) == (896, 1)

    def loss(x, w, g, u, d, c):
        return jnp.sum(grouped_experts.experts_grouped(
            x, c, w, g, u, d, held_from=0, impl="pallas",
            name=grouped_experts.EXPERTS_GROUPED_TRAIN)[0])
    text = compiled_text(
        topo, jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
        ((8192, 2304), BF16), ((8192, 8), F32),
        *[((16, 896, 2304), F32)] * 3, ((8192, 8), I32))
    assert sorted(kernel_names(text)) == [
        "experts_grouped_dw", "experts_grouped_dx", "experts_grouped_train"]


@pytest.mark.parametrize("width,want", [
    (896, (896, 1)), (2688, (384, 7)), (768, (256, 3)), (2048, (256, 8)),
    (4096, (256, 16)), (1536, (256, 6)), (32, (32, 1))])
def test_width_slice_keeps_every_accepted_cell_s_slice(width, want):
    """mellum2 (896), nemotron (2,688), kanana and ling (768), glm
    (2,048), command-a-plus (4,096), kanana's shared pair (1,536) and the
    tests' tiny experts."""
    assert grouped_experts._width_slice(width) == want


def test_window_train_step_compiles_at_the_cells_shapes(topo):
    """`mellum2-12b-a2.5b.longctx-32k`'s fused dispatch (two steps) at
    its real shapes through the chip's compiler: every kernel under its
    name, a window layer's forward once (its output and logsumexp are
    saved), no key or value repeated to 32 heads, and arguments and
    temporaries inside the chip's 16 GB."""
    from unittest import mock

    from benchmarks import run as bench_run
    from benchmarks.harness import common
    from ray_tpu.models import window_moe_train as wmt
    from ray_tpu.parallel.sharding import (logical_to_spec, replicated,
                                           tree_shardings)
    from ray_tpu.train import loop, spmd

    _, cell, config, mix = bench_run.load_cell(
        "mellum2-12b-a2.5b.longctx-32k")
    cfg = common.model_config(config, "train", **config["program"]["train"])
    mesh = MeshSpec(**mix["mesh"]).build(topo.devices[:1])
    opt = spmd.default_optimizer(**config["program"]["optimizer"])
    _, step_fn, _ = spmd.make_window_moe_trainer(cfg, mesh, optimizer=opt,
                                                 init_state=False)

    def abstract(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            shapes, shardings)

    p_sh = tree_shardings(mesh, wmt.param_logical_axes(cfg))
    p_shape = jax.eval_shape(lambda k: wmt.init_params(k, cfg),
                             jax.random.key(0))
    state = spmd.TrainState(
        abstract(p_shape, p_sh),
        abstract(jax.eval_shape(opt.init, p_shape),
                 spmd.opt_state_shardings(opt, p_shape, p_sh, mesh)),
        jax.ShapeDtypeStruct((), I32, sharding=replicated(mesh)))
    tok = jax.ShapeDtypeStruct(
        (mix["unroll"], mix["batch"], mix["seq_len"]), I32,
        sharding=NamedSharding(mesh, PartitionSpec(
            None, *logical_to_spec(("batch",), None, mesh), None)))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = loop.fuse_steps(step_fn, mix["unroll"]).lower(
            state, {"inputs": tok, "targets": tok}).compile()
    text = compiled.as_text()
    kernels = kernel_names(text)
    for name in ("flash_fwd_band", "flash_dq_band", "flash_dkv_band"):
        assert kernels.count(name) == 3, kernels
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "xent_fwd",
                 "xent_dx", "xent_de"):
        assert kernels.count(name) == 1, kernels
    for name in ("experts_grouped_train", "experts_grouped_dx",
                 "experts_grouped_dw"):
        assert kernels.count(name) == 4, kernels
    # the lowered step holds no [1, 32768, 32, 128] key or value: the
    # only arrays of that size are q, the attention's output, and their
    # gradients
    assert not [line for line in text.splitlines()
                if re.search(r"bf16\[1,32768,32,128\]", line)
                and re.search(r"w_k|w_v", line)
                and " dot(" not in line and " fusion(" not in line]
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) \
        < 15.5 * 2**30


# ---------------------------------------------------------------------------
# the short-convolution family (`benchmarks/configs/lfm2-8b-a1b.json`):
# grouped heads of 64, two key-value heads a lane tile, every expert held
# ---------------------------------------------------------------------------

def _lfm2():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import shortconv_moe as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


LB, LHQ, LD = 128, 32, 64               # slots, query heads, a head
LPAGES = [((3, 4993, 4, 128, 128), BF16)] * 2   # 8 heads of 64 in pairs


def _pair_decode_case():
    return (lambda q, k, v, t, pos: da.gqa_decode_attention(
        q, k, v, t, pos, layer=2, impl="pallas"),
        [((LB, LHQ, LD), BF16)] + LPAGES + [((LB, 39), I32), ((LB,), I32)])


def _pair_chunk_case(c):
    return (lambda q, k, v, t, start: da.gqa_chunk_attention(
        q, k, v, t, start, layer=2, impl="pallas"),
        [((c, LHQ, LD), BF16)] + LPAGES + [((39,), I32), ((), I32)])


def _all_experts_case(n, name):
    return (lambda x, c, w, g, u, dn: grouped_experts.experts_grouped(
        x, c, w, g, u, dn, held_from=0, impl="pallas", name=name)[0],
        [((n, 2048), BF16), ((n, 4), I32), ((n, 4), jnp.float32)]
        + [((32, 1792, 2048), BF16)] * 3)


SHORTCONV_KERNELS = {
    "gqa_full_decode": (_pair_decode_case(), "gqa_full_decode"),
    "gqa_full_chunk_512": (_pair_chunk_case(512), "gqa_full_chunk"),
    "gqa_full_chunk_128": (_pair_chunk_case(128), "gqa_full_chunk"),
    "experts_grouped": (_all_experts_case(128, "experts_grouped"),
                        "experts_grouped"),
    "experts_grouped_prefill": (
        _all_experts_case(512, "experts_grouped_prefill"),
        "experts_grouped_prefill"),
    "experts_grouped_prefill_128": (
        _all_experts_case(128, "experts_grouped_prefill"),
        "experts_grouped_prefill"),
    # a step's 128 rows and a chunk's 512 in one call (`shortconv_moe.tick`)
    "experts_grouped_tick": (
        _all_experts_case(640, "experts_grouped_tick"),
        "experts_grouped_tick"),
}
# rows of a call -> rows of the expert kernel's layout, the pairs and a
# tile of padding an expert (`grouped_experts.row_tile`): a step's and
# the 128 bucket's 512 pairs in tiles of 32, a 512 chunk's 2,048 in tiles
# of 128, 48 tiles each; a fused tick's 2,560 in tiles of 128, 52 tiles
LFM2_LAYOUT_ROWS = {128: 512 + 32 * 32, 512: 2048 + 32 * 128,
                    640: 2560 + 32 * 128}


@pytest.mark.parametrize("case", sorted(SHORTCONV_KERNELS))
def test_shortconv_family_kernels_compile_under_their_names(topo, case,
                                                            caplog):
    """The grouped-head kernels at a head of 64 (a page of two key-value
    heads side by side, 8 query heads a program: one sublane tile in
    decode, 128 queries a program in a chunk) and the expert kernels at
    32 held experts of 1,792 (16 pairs an expert of a step's and of the
    128 bucket's call, so row tiles of 32): one kernel a call, under its
    own name, no fallback noted, the pool in place."""
    (fn, args), name = SHORTCONV_KERNELS[case]
    compiled = compiled_for(topo, fn, *args)
    assert kernel_names(compiled.as_text()) == [name]
    if name.startswith("experts_grouped"):
        rows = LFM2_LAYOUT_ROWS[args[0][0][0]]
        assert kernel_results(compiled.as_text(), name) == [
            f"bf16[{rows},2048]"]
    assert not fallbacks(caplog)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def _tails_made(text: str, pool: dict) -> list[str]:
    """The instructions of a program that make an array of the shape of
    the pool's tails (`arrays_made`'s rule for what costs nothing; a
    scatter or an update in place into the donated array is the array
    itself)."""
    shape = "bf16[" + ",".join(map(str, pool["tail"].shape)) + "]"
    return [line.strip() for line in text.splitlines()
            if f" = {shape}" in line and not re.search(
                r" (parameter|get-tuple-element|bitcast|scatter|"
                r"dynamic-update-slice)\(", line)
            and "fusion(" not in line]


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128",
                                     "tick_512"])
def test_shortconv_family_programs_compile_at_the_cells_shapes(topo, program,
                                                               caplog):
    """The decode step, both prefill buckets and the fused tick (a step
    and a full chunk, one program) of
    `benchmarks/configs/lfm2-8b-a1b.json` as the engine jits them (the
    pool donated): the attention and expert kernels of every layer under
    their names (the fused tick's under names of its own, one expert call
    a sparse layer over the rows of both), no fallback; no gather of a layer's pages (nothing the
    size of a layer of the pool is made) and no copy of the whole tails
    array; tails and pages updated in place; weights, pool and
    temporaries under the chip's 15.75 GB with the reference's
    temporaries beside them; no weight converted in a step."""
    from ray_tpu.models import shortconv_moe
    config, cfg, ref = _lfm2()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    slots, cols = serve["slots"], serve["max_len"] // kw["block_size"]
    described, arg = describers(topo)
    drawn = jax.eval_shape(lambda k: ref.init_params(k, config),
                           jax.random.key(0))
    # the reference's draw is a served tree: the engine's load runs nothing
    assert jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        lambda p: shortconv_moe.load(p, cfg), drawn)) == jax.tree.map(
        lambda a: (a.shape, a.dtype), drawn)
    params = described(drawn)
    pool = described(jax.eval_shape(lambda: shortconv_moe.init_pool(
        cfg, kw["cache_blocks"], kw["block_size"], state_blocks=slots + 1)))
    assert pool["k"].shape == LPAGES[0][0]
    assert pool["tail"].shape == (11, slots + 1, 2, 2048)
    if program == "decode":
        started = time.monotonic()
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: shortconv_moe.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((slots,)), arg((slots,)),
            arg((slots, 1 + cols)))
        assert time.monotonic() - started < TRACE_AND_LOWER_S
        compiled = lowered.compile()
        chunk = None
        want = {"gqa_full_decode": 3, "experts_grouped": 12}
    elif program == "tick_512":
        chunk = 512
        compiled = jax.jit(
            lambda p, ctok, tok, cache, pos, tab, ctab, start, n:
            shortconv_moe.tick(p, ctok, tok, cache, pos, tab, cfg,
                               block_table=ctab, start=start, length=n),
            donate_argnums=(3,)).lower(
            params, arg((1, chunk)), arg((slots,)), pool, arg((slots,)),
            arg((slots, 1 + cols)), arg((1 + cols,)), arg(()),
            arg(())).compile()
        want = {"gqa_full_decode_tick": 3, "gqa_full_chunk_tick": 3,
                "experts_grouped_tick": 12}
    else:
        chunk = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: shortconv_moe.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, chunk)), pool, arg((1 + cols,)), arg(()),
            arg(())).compile()
        want = {"gqa_full_chunk": 3, "experts_grouped_prefill": 12}
    text = compiled.as_text()
    names = kernel_names(text)
    assert {n: names.count(n) for n in set(names)} == want
    assert not fallbacks(caplog)
    # every sparse layer's call walks the layout `row_tile` plans: a step
    # of 128 rows x 4 over 32 experts is `experts_grouped` over [1536, 2048]
    experts = next(n for n in want if n.startswith("experts_grouped"))
    rows = LFM2_LAYOUT_ROWS[{"decode": slots, "tick_512": slots + 512}.get(
        program, chunk)]
    assert kernel_results(text, experts) == [f"bf16[{rows},2048]"] * 12
    # a chunk updates its block's tails where they lie. The step's one
    # gather and one scatter of 128 blocks' tails are made on the array
    # staged whole in VMEM (the compiler's memory-space assignment: 11.6
    # MB in for each, once out: 35 MB a step at HBM speed, 43 us), once a
    # program and not once a layer (ROADMAP S23's 4-5 %); PERF.md has the
    # measured share
    assert len(_tails_made(text, pool)) <= (
        3 if program in ("decode", "tick_512") else 0)
    # a gather of a layer through the table, or a lay-out of one, would be
    # an array of a layer's pages: 4,993 x 4 x 128 x 128
    layer = math.prod(pool["k"].shape[1:])
    assert not [line for line in arrays_made(text, BF16, layer)
                if "tpu_custom_call" not in line
                and not re.search(r" (scatter|dynamic-update-slice)\(", line)
                and "fusion(" not in line]
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 0.5e9               # and never copied
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + LFM2_REFERENCE_BYTES) < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# what `refs/shortconv_moe.py` holds at once beside weights and pool
# (`test_shortconv_reference_fits_beside_the_pool`)
LFM2_REFERENCE_BYTES = 0.9e9


def test_shortconv_reference_fits_beside_the_pool(topo):
    """The cell's comparison runs `refs/shortconv_moe.py` in the replica,
    beside the weights and the whole pool, on a sequence padded to
    `max_len`: what it holds at once has to fit in what 128 slots' pages
    leave of the chip."""
    from ray_tpu.models import shortconv_moe
    config, cfg, ref = _lfm2()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = jax.eval_shape(lambda: shortconv_moe.init_pool(
        cfg, kw["cache_blocks"], kw["block_size"],
        state_blocks=serve["slots"] + 1))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda p, s: ref.token_logprobs(p, s, config)).lower(
            params, arg((1, serve["max_len"]))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    print("reference", mem.argument_size_in_bytes, mem.temp_size_in_bytes,
          pool_bytes)
    assert mem.temp_size_in_bytes < LFM2_REFERENCE_BYTES
    assert (mem.argument_size_in_bytes + pool_bytes
            + mem.temp_size_in_bytes) < 15.75e9


# -- the shortcut family at longcat-flash-chat.agent-closed96's shapes: 64
# slots, eight attention blocks of 2,050 pages of 128 latent rows, tables
# of 32 columns, chunks of 512 and 128, 16 held experts of a router 768
# wide of which 256 outputs are identity experts, 12 a token, vocabulary
# 16,384

def _longcat():
    import json
    from benchmarks.harness import common
    from benchmarks.refs import shortcut_moe as ref
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           "longcat-flash-chat.json")) as f:
        config = json.load(f)
    return config, common.model_config(config, "serve"), ref


# rows of the layout `experts_grouped` plans for a call: all `n * k`
# choices and a tile an expert held (`grouped_experts.group_layout`),
# though a third of the choices name no expert and 31 of 32 of the rest
# another chip's: 16 live pairs a step, 128 a chunk (PERF.md, PR 65)
LONGCAT_LAYOUT_ROWS = {64: 64 * 12 + 16 * 128, 128: 128 * 12 + 16 * 128,
                       512: 512 * 12 + 16 * 128}
# what `refs/shortcut_moe.py` holds at once beside weights and pool
# (`test_shortcut_reference_fits_beside_the_pool`)
LONGCAT_REFERENCE_BYTES = 1.2e9


@pytest.mark.parametrize("program", ["decode", "prefill_512", "prefill_128"])
def test_shortcut_family_programs_compile_at_the_cells_shapes(topo, program,
                                                              caplog):
    """The decode step and both prefill buckets of
    `benchmarks/configs/longcat-flash-chat.json` as the engine jits them
    (the pool donated): the latent and expert kernels of every block and
    layer under their names, no fallback; both scopes of the shortcut in
    the program; the pool updated in place; weights, pool and temporaries
    under the chip's 15.75 GB with the reference's temporaries beside
    them; no weight converted in a step."""
    from ray_tpu.models import shortcut_moe
    config, cfg, ref = _longcat()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    slots, cols = serve["slots"], serve["max_len"] // kw["block_size"]
    described, arg = describers(topo)
    drawn = jax.eval_shape(lambda k: ref.init_params(k, config),
                           jax.random.key(0))
    # the reference's draw is a served tree: the engine's load runs nothing
    assert jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        lambda p: shortcut_moe.load(p, cfg), drawn)) == jax.tree.map(
        lambda a: (a.shape, a.dtype), drawn)
    params = described(drawn)
    # the engine asks for `cache_blocks` pages and the trash page
    pool = described(jax.eval_shape(lambda: shortcut_moe.init_pool(
        cfg, kw["cache_blocks"] + 1, kw["block_size"])))
    assert (pool["latent"].shape, pool["latent"].dtype) == (
        (8, 2050, 128, 1, 384), jnp.uint32)
    if program == "decode":
        started = time.monotonic()
        lowered = jax.jit(
            lambda p, cache, tok, pos, tab: shortcut_moe.decode(
                p, tok, cache, pos, tab, cfg), donate_argnums=(1,)).lower(
            params, pool, arg((slots,)), arg((slots,)), arg((slots, cols)))
        assert time.monotonic() - started < TRACE_AND_LOWER_S
        compiled = lowered.compile()
        want = {"latent_row_write": 8, "latent_decode": 8,
                "experts_grouped": 4}
        rows = slots
    else:
        rows = int(program.rsplit("_", 1)[1])
        compiled = jax.jit(
            lambda p, tok, cache, tab, start, n: shortcut_moe.prefill(
                p, tok, cache, cfg, block_table=tab, start=start,
                length=n), donate_argnums=(2,)).lower(
            params, arg((1, rows)), pool, arg((cols,)), arg(()),
            arg(())).compile()
        want = {"latent_row_write": 8, "latent_chunk_attend": 8,
                "experts_grouped_prefill": 4}
    text = compiled.as_text()
    names = kernel_names(text)
    assert {n: names.count(n) for n in set(names)} == want
    assert not fallbacks(caplog)
    for scope in ("ffn/shortcut_experts", "ffn/shortcut_dense",
                  "mixer/shortcut_dense"):
        assert scope in text, scope
    experts = next(n for n in want if n.startswith("experts_grouped"))
    assert kernel_results(text, experts) == [
        f"bf16[{LONGCAT_LAYOUT_ROWS[rows]},6144]"] * 4
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    assert mem.temp_size_in_bytes < 0.6e9               # and never copied
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + LONGCAT_REFERENCE_BYTES) < 15.75e9
    print(program, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


def test_shortcut_reference_fits_beside_the_pool(topo):
    """The cell's comparison runs `refs/shortcut_moe.py` in the replica,
    beside the weights and the whole pool, on a sequence padded to
    `max_len`: what it holds at once has to fit in what 64 slots' pages
    leave of the chip."""
    from ray_tpu.models import shortcut_moe
    config, cfg, ref = _longcat()
    serve = config["program"]["serve"]
    kw = serve["engine_kwargs"]
    described, arg = describers(topo)
    params = described(jax.eval_shape(
        lambda k: ref.init_params(k, config), jax.random.key(0)))
    pool = jax.eval_shape(lambda: shortcut_moe.init_pool(
        cfg, kw["cache_blocks"] + 1, kw["block_size"]))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda p, s: ref.token_logprobs(p, s, config)).lower(
            params, arg((1, serve["max_len"]))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    print("reference", mem.argument_size_in_bytes, mem.temp_size_in_bytes,
          pool_bytes)
    assert mem.temp_size_in_bytes < LONGCAT_REFERENCE_BYTES
    assert (mem.argument_size_in_bytes + pool_bytes
            + mem.temp_size_in_bytes) < 15.75e9
