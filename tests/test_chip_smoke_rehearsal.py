"""Rehearsals of `chip_smoke.py` that cost no chip time (guide
`on-chip-measurement`, section 2), each in a process of its own: the
phases at a tiny size on the CPU backend, the four-chip phase on four
virtual devices, and the whole train step AOT-compiled at the real
widths for a described `v5e:2x2`. Run them before sending the smoke to
the chip: `pytest -m slow tests/test_chip_smoke_rehearsal.py`.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ("dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4, "
        "d_ff=512, max_seq_len=128)")
TINY_TRAIN = (f"dict({TINY}, attn_impl='flash', logits_dtype='bfloat16', "
              "remat_policy='dots', loss_impl='fused')")


def run(code: str, **env) -> str:
    out = subprocess.run(
        [sys.executable, "-c", "import chip_smoke as cs\n" + code],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
             **env})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    return out.stdout


@pytest.mark.parametrize("replicas", [1, 2])
def test_serve_phase_tiny_on_cpu(replicas):
    # RAY_TPU_NUM_TPUS stands in for the chips the replicas ask for
    out = run(f"cs.serve_phase({TINY}, platform='cpu', "
              f"replicas={replicas}, streams=6, prompt_lens=(16, 60), "
              "new_tokens=8, slots=4, max_len=128, seed=0)",
              RAY_TPU_NUM_TPUS="2")
    assert '"phase": "serve"' in out


def test_serve_retention_phase_tiny_on_cpu():
    """The second family's part of the serve phase: an engine over
    `models/retention.py` in the phase's own process, float32 on the CPU
    (the plain paths), held to the definition."""
    out = run("cs.serve_family_phase(cs.retention_case(dict("
              "cs.RETENTION_CFG, head_dim=32, dtype='float32'), 0), "
              "platform='cpu', streams=5, prompt_lens=(100, 300), "
              "new_tokens=6, slots=3, seed=0)")
    assert '"phase": "serve_retention"' in out


def test_serve_hybrid_phase_tiny_on_cpu():
    """The third family's part: an engine over `models/linear_latent.py`
    (a state block and latent pages a request) in the phase's own process,
    float32 on the CPU (the plain paths), held to the definition."""
    out = run("cs.serve_family_phase(cs.hybrid_case(dict(cs.HYBRID_CFG, "
              "kda_head_dim=32, dtype='float32'), 0), platform='cpu', "
              "streams=5, prompt_lens=(100, 300), new_tokens=6, slots=3, "
              "seed=0)")
    assert '"phase": "serve_hybrid"' in out


def test_serve_window_phase_tiny_on_cpu():
    """The fourth family's part: an engine over `models/window_moe.py`
    (pages that grow and a ring of window pages a request) in the phase's
    own process, float32 on the CPU (the plain paths), held to the
    definition; the longer prompts pass the window of 256."""
    out = run("cs.serve_family_phase(cs.window_case(dict(cs.WINDOW_CFG, "
              "head_dim=32, dtype='float32'), 0), platform='cpu', "
              "streams=5, prompt_lens=(100, 600), new_tokens=6, slots=3, "
              "seed=0)")
    assert '"phase": "serve_window"' in out


def test_serve_mamba_phase_tiny_on_cpu():
    """The fifth family's part: an engine over `models/mamba_moe.py` (a
    state block of Mamba-2 states and tails beside one attention layer's
    pages a request) in the phase's own process, float32 on the CPU (the
    plain paths), held to the definition; every stream decodes past a
    fold of its ring (the phase checks `state_folds`)."""
    out = run("cs.serve_family_phase(cs.mamba_case(dict(cs.MAMBA_CFG, "
              "mamba_head_dim=16, state_size=32, head_dim=32, "
              "dtype='float32'), 0), platform='cpu', streams=5, "
              "prompt_lens=(100, 300), new_tokens=11, slots=3, seed=0)")
    assert '"phase": "serve_mamba"' in out
    assert '"state_folds": 5' in out


def test_serve_parallel_hybrid_phase_tiny_on_cpu():
    """The seventh family's part: an engine over
    `models/parallel_hybrid.py` (a state block and pages in every layer a
    request, a group of 5 query heads) in the phase's own process,
    float32 on the CPU (the plain paths), held to the definition; every
    stream decodes past a fold of its ring."""
    out = run("cs.serve_family_phase(cs.parallel_hybrid_case(dict("
              "cs.PARALLEL_HYBRID_CFG, mamba_head_dim=16, state_size=32, "
              "head_dim=32, dtype='float32'), 0), platform='cpu', "
              "streams=5, prompt_lens=(100, 300), new_tokens=11, slots=3, "
              "seed=0)")
    assert '"phase": "serve_parallel_hybrid"' in out
    assert '"state_folds": 5' in out


def test_serve_shortconv_phase_tiny_on_cpu():
    """The eighth family's part: an engine over `models/shortconv_moe.py`
    (convolution tails beside pages of paired heads, every expert held)
    in the phase's own process, float32 on the CPU (the plain paths), held
    to the definition."""
    out = run("cs.serve_family_phase(cs.shortconv_case(dict("
              "cs.SHORTCONV_CFG, d_model=64, n_heads=4, head_dim=16, "
              "d_ff=96, expert_ff=48, dtype='float32'), 0), platform='cpu', "
              "streams=5, prompt_lens=(100, 300), new_tokens=6, slots=3, "
              "seed=0)")
    assert '"phase": "serve_shortconv"' in out
    assert '"state_resets": 5' in out


def test_serve_shortcut_phase_tiny_on_cpu():
    """The ninth family's part: an engine over `models/shortcut_moe.py`
    (two latent blocks and two MLPs a layer, the experts beside them, a
    third of the router's outputs identity experts) in the phase's own
    process, float32 on the CPU (the plain paths), held to the
    definition; the phase checks that every choice is counted once, with
    an expert or without."""
    out = run("cs.serve_family_phase(cs.shortcut_case(dict("
              "cs.SHORTCUT_CFG, d_model=64, q_rank=32, kv_rank=32, "
              "nope_dim=16, rope_dim=8, v_dim=16, d_ff=96, expert_ff=48, "
              "dtype='float32'), 0), platform='cpu', streams=5, "
              "prompt_lens=(100, 300), new_tokens=6, slots=3, seed=0)")
    assert '"phase": "serve_shortcut"' in out
    assert '"identity_tokens"' in out


def test_train_phase_tiny_on_cpu():
    out = run(f"cs.train_phase({TINY_TRAIN}, platform='cpu', batch=4, "
              "steps=12, seed=0)")
    assert '"phase": "train"' in out


def test_four_chip_train_phase_on_virtual_devices():
    out = run(f"cs.train4_phase({TINY_TRAIN}, platform='cpu', batch=4, "
              "steps=8, seed=0)",
              XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert '"weight_shard_devices": [0, 1, 2, 3]' in out


AOT_TRAIN_STEP = '''
import os
os.environ.setdefault("TPU_LOG_DIR", "disabled")
from unittest import mock
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
from ray_tpu.models import gpt
from ray_tpu.parallel import MeshSpec
from ray_tpu.parallel.sharding import (
    logical_to_spec, replicated, tree_shardings)
from ray_tpu.train import loop, spmd

jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
cfg = gpt.GPTConfig(**cs.TRAIN_CFG)
mesh = MeshSpec(**MESH).build(topo.devices[:N])
opt = spmd.default_optimizer(warmup_steps=0)
_, step_fn, _ = spmd.make_gpt_trainer(cfg, mesh, optimizer=opt,
                                      init_state=False)

def abstract(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)

p_sh = tree_shardings(mesh, gpt.param_logical_axes(cfg))
p_shape = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                         jax.random.key(0))
state = spmd.TrainState(
    abstract(p_shape, p_sh),
    abstract(jax.eval_shape(opt.init, p_shape),
             spmd.opt_state_shardings(opt, p_shape, p_sh, mesh)),
    jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated(mesh)))
tok = jax.ShapeDtypeStruct(
    (4, 8, cfg.max_seq_len), jnp.int32, sharding=NamedSharding(
        mesh, P(None, *logical_to_spec(("batch",), None, mesh), None)))
with mock.patch.object(jax, "default_backend", lambda: "tpu"):
    names, calls, compiled = cs.kernels_of(
        loop.fuse_steps(step_fn, 4), state, {"inputs": tok, "targets": tok})
mem = compiled.memory_analysis()
print("KERNELS", names, calls)
print("GIB", (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2**30)
assert cs.TRAIN_KERNELS <= set(names), names
assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 15 * 2**30
'''


@pytest.mark.parametrize("n,mesh", [
    (1, "dict(data=1)"), (4, "dict(data=1, fsdp=2, tensor=2)")],
    ids=["one-chip", "fsdp2-tensor2"])
def test_fused_train_dispatch_compiles_for_v5e(n, mesh):
    """The program the train phases run, at the real widths and batch,
    through the chip's compiler: kernels present, fits the 16 GB chip."""
    out = run(f"N, MESH = {n}, {mesh}\n" + AOT_TRAIN_STEP)
    assert "KERNELS" in out


def test_train_window_phase_tiny_on_cpu():
    """`--phase train-window` at a tiny size: the trainer over
    `models/window_moe_train.py` through the loop, banded and full layers
    in interpret mode, first loss against the plain forward's."""
    out = run("cs.train_window_phase(dict(cs.WINDOW_TRAIN_CFG, "
              "vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, "
              "head_dim=16, window=40, expert_ff=32, max_seq_len=128, "
              "rope_full=(10000.0, 4.0, 32, 32.0, 1.0, 1.1), "
              "flash_block_q=128, flash_block_kv=128, expert_chunk=128, "
              "dtype='float32'), platform='cpu', batch=2, steps=6, seed=0)")
    assert '"phase": "train_window"' in out
