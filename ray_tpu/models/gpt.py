"""Flagship model: GPT-style decoder-only transformer, TPU-first.

Design choices that matter on TPU:

- **bfloat16 activations, float32 params/optimizer** — MXU-native compute
  with stable accumulation (einsums accumulate in f32 via
  ``preferred_element_type``).
- **One stacked layer pytree + ``lax.scan``** over layers: compile time is
  O(1) in depth and XLA pipelines the loop body.
- **Logical sharding axes on every parameter** (`ray_tpu.parallel.sharding`
  vocabulary): the same definition runs 1-chip, DP, FSDP, TP (megatron
  column/row split), and SP (ring attention over the ``seq`` axis) purely by
  changing the MeshSpec.
- **`jax.checkpoint` on the block** to trade FLOPs for HBM.
- **Two trees of one model.** The masters (`init_params`: `embed`,
  `pos_embed`, `final_ln_scale` and the layer stacks `ln1_scale`, `wq`,
  `wk`, `wv`, `wo`, `ln2_scale`, `w_up`, `w_gate`, `w_down`, float32) are
  what training reads and trainers publish. The served tree
  (`serving_params`, made once at load) is what the paged forwards
  read: every leaf in the dtype a step reads it in, `wq`, `wk` and `wv`
  side by side in one stack `wqkv` [L, D, 3, H * Dh] (with
  `weight_dtype="int8"`, int8 stacks and their `_scale` siblings).

The reference has no model zoo of its own (models live in user code /
RLlib's catalog, `rllib/models/catalog.py`); this model is the framework's
train/serve/bench workhorse, counterpart of the reference release
benchmarks' ResNet/GPT-2 workloads (`release/air_tests/air_benchmarks/`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_tpu.models.blocks import (copy_block, gated_mlp, gather_block,
                                   rms_norm, scatter_block, weight)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304        # multiple of 128 for MXU-friendly vocab
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    dtype: str = "bfloat16"
    remat: bool = True
    # What the layer-scan checkpoint saves for backward:
    #   "nothing"  - recompute the whole block (min HBM, max recompute)
    #   "dots"     - save matmul outputs (jax.checkpoint_policies.
    #                checkpoint_dots_with_no_batch_dims) and what the flash
    #                forward kernel made (output and row logsumexp):
    #                recompute elementwise only, bwd re-runs neither the
    #                big einsums nor the attention forward
    #   "attn_out" - save only what the flash forward kernel made
    remat_policy: str = "nothing"
    attn_impl: str = "auto"        # auto | ring | flash | xla
    # Output dtype of the block einsums. MXU accumulation is f32 either
    # way; materializing f32 OUTPUTS doubles activation HBM writes, so
    # "activation" (= cfg.dtype, bf16) is the fast path. The logits
    # matmul always emits f32 (softmax stability).
    matmul_out: str = "activation"  # activation | float32
    # Unembed output dtype. float32 is the safe default (softmax
    # stability over a 50k vocab); bfloat16 halves the HBM traffic of
    # the single biggest activation tensor — the loss upcasts to f32
    # before logsumexp either way.
    logits_dtype: str = "float32"   # float32 | bfloat16
    # Cross-entropy implementation (validated at trace time, like
    # remat_policy):
    #   "dense" - materialize [B, T, vocab] logits, then softmax-xent.
    #   "fused" - ops/fused_xent.py streams the unembed matmul in vocab
    #             chunks with an online logsumexp (forward AND backward
    #             recompute per-chunk logits), so the loss's peak live
    #             activation is O(B*T*chunk) instead of O(B*T*vocab).
    #             At bench shape the dense logits tensor is 1.6 GB f32 —
    #             the single biggest array in the step and what capped
    #             batch size at 16. Accumulation is f32 either way;
    #             fused vs dense agrees to ~1e-6 with f32 logits.
    loss_impl: str = "dense"        # dense | fused
    # Vocab rows per online-softmax step of the fused loss's scan path
    # (off a TPU, or a vocab shard no lane tile divides): its transient
    # logits block is [B, T, loss_chunk]; smaller chunks mean less live
    # memory and more loop steps. The Pallas kernels do not read it:
    # their blocks come from the shapes and the chip's VMEM
    # (ops/fused_xent._plan).
    loss_chunk: int = 512
    # Attention implementation for paged decode and verify over the block
    # pool (decode_step_paged, verify_step_paged). "auto" picks the
    # Pallas kernels on TPU and the pure-JAX fallback elsewhere; both
    # share the same math (ops/decode_attention.py).
    decode_attn_impl: str = "auto"   # auto | pallas | jax
    # Paged KV pool element type. "f32" keeps the pool in the activation
    # dtype (full precision — the bitwise-default path); "int8" stores
    # symmetric absmax int8 payloads with one f32 scale per
    # (position, head) row (ops/quant.py), quantized at write inside
    # prefill/decode/verify and dequantized inside the paged attention
    # kernels — the block table / COW / radix machinery never sees the
    # dtype. ~3-4x KV bytes/token vs an f32 pool (2x vs bf16).
    kv_dtype: str = "f32"            # f32 | int8
    # Weight precision for the paged inference forwards (prefill/decode/
    # verify — training always runs full precision). Both expect params
    # through `serving_params`, the engine's load-time function (which
    # also lays `wq`, `wk`, `wv` side by side in one leaf, `wqkv`): "f32"
    # is the published values, each rounded to `dtype` once where a step
    # would round it at use; "int8" quantizes the matmul stacks besides
    # (per-output-channel scales; dequant folds into each matmul's rhs
    # read, accumulation stays f32 via preferred_element_type).
    weight_dtype: str = "f32"        # f32 | int8
    # Attention implementation for chunked paged prefill. "auto" picks
    # the fused Pallas multi-query kernel on TPU (chunk scores stay
    # blockwise in VMEM) and the dense gather+einsum elsewhere; "jax" is
    # the legacy dense math, bit-identical to the pre-fused inline path.
    prefill_attn_impl: str = "auto"  # auto | pallas | jax

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def family(self) -> ServingFamily:
        return FAMILY


def small(**kw) -> GPTConfig:
    return GPTConfig(**{**dict(vocab_size=512, d_model=128, n_layers=2,
                               n_heads=4, d_ff=512, max_seq_len=128), **kw})


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_logical_axes(cfg: GPTConfig):
    """Pytree of logical-axis tuples, mirroring init_params' structure.
    Leading layer-stack axis is unsharded (None)."""
    layer = {
        "ln1_scale": (None, "embed"),
        "ln2_scale": (None, "embed"),
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "heads"),
        "wv": (None, "embed", "heads"),
        "wo": (None, "heads", "embed"),
        "w_up": (None, "embed", "mlp"),
        "w_gate": (None, "embed", "mlp"),
        "w_down": (None, "mlp", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "final_ln_scale": ("embed",),
        "layers": layer,
    }


def init_params(rng, cfg: GPTConfig):
    """float32 master params; cast to cfg.dtype at use sites."""
    k_emb, k_pos, k_layers = jax.random.split(rng, 3)
    d, h, f, L = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.d_ff, cfg.n_layers

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in)))

    ks = jax.random.split(k_layers, 7)
    layers = {
        "ln1_scale": jnp.ones((L, d), jnp.float32),
        "ln2_scale": jnp.ones((L, d), jnp.float32),
        "wq": norm(ks[0], (L, d, h), d),
        "wk": norm(ks[1], (L, d, h), d),
        "wv": norm(ks[2], (L, d, h), d),
        "wo": norm(ks[3], (L, h, d), h) / np.sqrt(2 * L),
        "w_up": norm(ks[4], (L, d, f), d),
        "w_gate": norm(ks[5], (L, d, f), d),
        "w_down": norm(ks[6], (L, f, d), f) / np.sqrt(2 * L),
    }
    return {
        "embed": norm(k_emb, (cfg.vocab_size, d), 1.0) * 0.02,
        "pos_embed": norm(k_pos, (cfg.max_seq_len, d), 1.0) * 0.01,
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def attention(q, k, v, impl: str, mesh: Mesh | None):
    """Causal self-attention on [B, T, H, Dh] by `impl`, a config's
    `attn_impl`."""
    if impl == "auto":
        if mesh is not None and mesh.shape.get("seq", 1) > 1:
            impl = "ring"
        else:
            impl = "flash"
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention
        attend = partial(flash_attention, causal=True)
        if mesh is not None and mesh.size > 1:
            # The compiler cannot partition a Pallas kernel; attention
            # is independent per (sequence, head), so each device runs
            # the kernel on its own batch rows and heads.
            from ray_tpu.parallel.sharding import (
                logical_to_spec,
                shard_map,
                valid_spec_for,
            )
            # a dim its mesh axes do not divide stays whole per device
            spec = valid_spec_for(
                mesh, logical_to_spec(("batch", None, "heads", None),
                                      mesh=mesh), q.shape)
            attend = shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, check_vma=False)
        # The kernel's forward rule names what it keeps for backward
        # (`flash_attention.SAVED_NAMES`), inside its custom_vjp where
        # the remat policy finds them: with both saved the bwd pass goes
        # straight to the dQ and dK/dV kernels.
        return attend(q, k, v)
    # Ring and XLA attention are plain autodiff: their backward recomputes
    # their forward whatever is saved, and the name spares that recompute
    # only its last p @ v.
    out = (ring_attention(q, k, v, mesh, causal=True) if impl == "ring"
           else reference_attention(q, k, v, causal=True))
    return checkpoint_name(out, "attn_out")


def _matmul_out(cfg: GPTConfig):
    """Element type the layer's einsums emit (`cfg.matmul_out`)."""
    return (jnp.float32 if cfg.matmul_out == "float32"
            else cfg.activation_dtype())


# The projections a served tree holds as one leaf, `wqkv`
# (`serving_params`), in the order `layer` takes them apart again.
QKV = ("wq", "wk", "wv")


def _fused_weight(lp, adt):
    """A served layer's `wqkv` [D, 3, H * Dh] in `adt`: `blocks.weight`
    for a leaf with two output axes, an int8 one's `wqkv_scale`
    [3, H * Dh] a scale an output channel."""
    w = lp["wqkv"]
    s = lp.get("wqkv_scale")
    if s is None:
        return w.astype(adt)
    return (w.astype(jnp.float32) * s[None]).astype(adt)


def layer(x, lp, cfg, pet, attend, ffn=None):
    """One transformer layer, the only spelling of it: norm, q/k/v,
    attention, output projection, residual, norm, feed-forward, residual.

    x: activations [..., D] in cfg.dtype, any leading dims ([B, T, D]
    training, [C, D] chunked prefill, [B, D] decode, [B, W, D] verify).
    lp: this layer's param slice (f32 masters cast here, leaves that
    `serving_params` cast read as they are; int8 leaves of
    `quantize_params` dequantized by `blocks.weight`). cfg: any config
    with `n_heads`, `head_dim` and `activation_dtype()`. pet: the
    einsums' output element type, the caller's choice (`_matmul_out`).

    q, k and v are one projection where `lp` holds `wqkv` (a served
    tree) and three where it holds `wq`, `wk` and `wv` (the masters:
    training, `moe.py`, `vit.py`): a static dict-key check, as
    `blocks.weight`'s for a scale, so a dict without the leaf traces
    what it traced. The same numbers are multiplied either way.

    The two parts that vary come in as arguments and return
    ``(output, kept)``, where `kept` is whatever the part makes besides
    its output (the pool with the layer's rows written, the experts' aux
    loss, None) and is handed back as it came:
    ``attend(q, k, v)`` on [..., H, Dh] -> ([..., H, Dh], kept);
    ``ffn(h, lp)`` on the normed [..., D] -> ([..., D], kept), the gated
    MLP unless given. Returns ``(x, attend's kept, ffn's kept)``."""
    adt = cfg.activation_dtype()
    lead = x.shape[:-1]
    with jax.named_scope(MIXER):
        h = rms_norm(x, lp["ln1_scale"])
        if "wqkv" in lp:
            q, k, v = jnp.unstack(jnp.einsum(
                "...d,dch->...ch", h, _fused_weight(lp, adt),
                preferred_element_type=pet).astype(adt), axis=-2)
        else:
            q, k, v = (jnp.einsum("...d,dh->...h", h,
                                  weight(lp, name, adt),
                                  preferred_element_type=pet).astype(adt)
                       for name in QKV)
        q, k, v = (a.reshape(*lead, cfg.n_heads, cfg.head_dim)
                   for a in (q, k, v))
        att, attend_kept = attend(q, k, v)
        att = jnp.einsum("...h,hd->...d",
                         att.reshape(*lead, cfg.n_heads * cfg.head_dim),
                         weight(lp, "wo", adt),
                         preferred_element_type=pet).astype(adt)
        x = x + att
    with jax.named_scope(FFN):
        h = rms_norm(x, lp["ln2_scale"])
        if ffn is None:
            ffn = partial(gated_mlp, adt=adt, pet=pet)
        ff, ffn_kept = ffn(h, lp)
        return x + ff, attend_kept, ffn_kept


def block(x, lp, cfg: GPTConfig, mesh: Mesh | None):
    """One training block. x: [B, T, D] activations in cfg.dtype; lp:
    this layer's param slice."""
    x, _, _ = layer(
        x, lp, cfg, _matmul_out(cfg),
        lambda q, k, v: (attention(q, k, v, cfg.attn_impl, mesh), None))
    return x


def forward_features(params, tokens, cfg: GPTConfig,
                     mesh: Mesh | None = None):
    """tokens [B, T] int32 -> final-norm activations [B, T, d_model] in
    cfg.dtype — everything except the unembed matmul. The fused loss
    consumes these directly so [B, T, vocab] logits never exist."""
    adt = cfg.activation_dtype()
    t = tokens.shape[1]
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens]
        x = x + params["pos_embed"].astype(adt)[:t][None]

    run = partial(block, cfg=cfg, mesh=mesh)
    if cfg.remat:
        # Measured on the v5e (PERF.md, PR 25): under "dots", saving the
        # flash forward's output and lse halves `flash_fwd`'s time a step
        # for one more activation a layer. "nothing" and "attn_out" are
        # not measured.
        policies = jax.checkpoint_policies
        policy = None
        if cfg.remat_policy in ("dots", "attn_out"):
            from ray_tpu.ops.flash_attention import SAVED_NAMES
            policy = policies.save_only_these_names(*SAVED_NAMES)
            if cfg.remat_policy == "dots":
                policy = policies.save_from_both_policies(
                    policies.checkpoint_dots_with_no_batch_dims, policy)
        elif cfg.remat_policy != "nothing":
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} "
                "(expected 'nothing' | 'dots' | 'attn_out')")
        run = jax.checkpoint(run, policy=policy)

    def scan_body(x, lp):
        return run(x, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    with jax.named_scope(HEAD):
        return rms_norm(x, params["final_ln_scale"])


def forward(params, tokens, cfg: GPTConfig, mesh: Mesh | None = None):
    """tokens [B, T] int32 -> logits [B, T, vocab] in cfg.logits_dtype
    (float32 by default)."""
    adt = cfg.activation_dtype()
    x = forward_features(params, tokens, cfg, mesh)
    with jax.named_scope(HEAD):
        return jnp.einsum(
            "btd,vd->btv", x, params["embed"].astype(adt),
            preferred_element_type=jnp.dtype(cfg.logits_dtype))


def check_loss_impl(cfg: GPTConfig) -> str:
    """Trace-time validation of the loss_impl knob (remat_policy idiom:
    a typo'd config fails the first trace, not some later step)."""
    if cfg.loss_impl not in ("dense", "fused"):
        raise ValueError(
            f"unknown loss_impl {cfg.loss_impl!r} "
            "(expected 'dense' | 'fused')")
    return cfg.loss_impl


def loss_fn(params, batch, cfg: GPTConfig, mesh: Mesh | None = None):
    """Next-token cross entropy. batch: {"tokens": [B, T]} — token t
    predicts token t+1."""
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    if check_loss_impl(cfg) == "fused":
        from ray_tpu.ops.fused_xent import fused_softmax_xent
        x = forward_features(params, tokens[:, :-1], cfg, mesh)
        with jax.named_scope(HEAD):
            nll = fused_softmax_xent(
                x, params["embed"].astype(cfg.activation_dtype()), targets,
                vocab_chunk=cfg.loss_chunk, mesh=mesh)
            return jnp.mean(nll)
    logits = forward(params, tokens[:, :-1], cfg, mesh)
    with jax.named_scope(HEAD):
        # upcast before the softmax so logits_dtype="bfloat16" configs
        # keep an f32 logsumexp (same guard as spmd.softmax_xent)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)


def completion_logprobs(params, tokens, start, width, cfg: GPTConfig,
                        mesh: Mesh | None = None):
    """Per-token natural log-likelihoods of a completion region — the
    DIFFERENTIABLE counterpart of the inference engine's emitted
    ``TokenEvent.logprob`` (one full forward instead of the KV-cache
    path; same f32 log_softmax math, so the two agree to f32 tolerance).

    tokens [B, T] int32: full padded sequences (prompt + completion).
    start [B] int32: index of each row's first completion token (>= 1).
    width (static int): completion window; returns [B, width] f32 where
    out[b, j] = log p(tokens[b, start[b]+j] | tokens[b, :start[b]+j]).
    Positions past a row's real sequence are scored against padding —
    the caller masks them (ragged lengths stay static-shaped).
    Gradients flow to params; RL losses build ratios/REINFORCE terms on
    top of this.
    """
    logits = forward(params, tokens, cfg, mesh)
    with jax.named_scope(HEAD):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        t = tokens.shape[1]
        start = jnp.asarray(start, jnp.int32)
        # Absolute position of completion token j, clipped into range so
        # padded tails index safely (caller masks them out).
        idx = jnp.clip(start[:, None]
                       + jnp.arange(width, dtype=jnp.int32)[None, :],
                       1, t - 1)                              # [B, W]
        rows = jnp.take_along_axis(
            logp, (idx - 1)[..., None], axis=1)               # [B, W, V]
        toks = jnp.take_along_axis(tokens, idx, axis=1)       # [B, W]
        return jnp.take_along_axis(rows, toks[..., None],
                                   axis=-1)[..., 0]


# ---------------------------------------------------------------------------
# paged KV cache: block pool + block tables
# ---------------------------------------------------------------------------
# The Podracer recipe (Hessel et al., 2104.06272) applied to ragged
# serving traffic: device shapes are static and resident. The allocation
# is ONE static pool whose unit is a block of `block_size` positions.
# Sequences name their blocks through an int32 block table
# [B, max_blocks] that rides into the jits as data — shapes never change,
# so prefill compiles once per chunk bucket and decode exactly once,
# while the host (serve/engine.py) is free to share, copy-on-write, and
# recycle blocks between requests. The three forwards below take `mesh`
# for their callers' signature and do not read it: params and pool
# arrive placed.

def check_quant_cfg(cfg: GPTConfig) -> bool:
    """Trace-time validation of the quantization knobs (the
    check_loss_impl idiom: a typo'd config fails the first trace, not
    some later step). Returns True when the KV pool is int8."""
    if cfg.kv_dtype not in ("f32", "int8"):
        raise ValueError(
            f"unknown kv_dtype {cfg.kv_dtype!r} (expected 'f32' | "
            "'int8')")
    if cfg.weight_dtype not in ("f32", "int8"):
        raise ValueError(
            f"unknown weight_dtype {cfg.weight_dtype!r} (expected "
            "'f32' | 'int8')")
    if cfg.prefill_attn_impl not in ("auto", "pallas", "jax"):
        raise ValueError(
            f"unknown prefill_attn_impl {cfg.prefill_attn_impl!r} "
            "(expected 'auto' | 'pallas' | 'jax')")
    return cfg.kv_dtype == "int8"


# The per-layer matmul weights the int8 weight-only path quantizes.
# Norm scales, embed and pos_embed are not quantized — they are O(d)
# reads, not the bandwidth, and the unembed shares `embed`.
QUANTIZED_WEIGHTS = (*QKV, "wo", "w_up", "w_gate", "w_down")


def quantize_params(params):
    """Per-output-channel int8 copy of a GPT param tree for the
    `weight_dtype="int8"` inference path: every `QUANTIZED_WEIGHTS`
    leaf ``[L, In, Out]`` becomes an int8 leaf plus an
    ``"<name>_scale"`` f32 ``[L, Out]`` sibling
    (`ops.quant.quantize_channels`). Embed/pos_embed/norm scales pass
    through untouched. Pure and jittable: the int8 half of
    `serving_params`."""
    from ray_tpu.ops import quant
    layers = dict(params["layers"])
    for name in QUANTIZED_WEIGHTS:
        q, s = quant.quantize_channels(layers[name])
        layers[name] = q
        layers[name + "_scale"] = s
    return {**params, "layers": layers}


def serving_params(params, cfg: GPTConfig):
    """The dense family's load-time function (`ServingFamily.load`):
    published masters in, the tree that `prefill_paged`,
    `decode_step_paged` and `verify_step_paged` read out.

    `wq`, `wk` and `wv` [L, D, H * Dh] come out side by side as one
    leaf, `wqkv` [L, D, 3, H * Dh], which `layer` reads with one dot.
    Held apart, each projection's reshape to heads made XLA give its
    dot a head-major output and want the weight transposed, so every
    layer of every step and chunk staged each [D, H * Dh] slice in VMEM,
    transposed it there and copied the product back to row-major (at
    `olmo-1b`, three slices and three copies of 8.4 MB a layer, a sixth
    of the time the device worked in `olmo-1b.chat-steady`: PERF.md,
    PR 60); the one dot
    reads its slice of the stack where it lies, as `wo`'s and the
    feed-forward's do. The third axis keeps the heads on the last one:
    under a mesh that splits the heads, a shard holds its own heads'
    q, k and v columns, and the chip stores the axis of 3 outside the
    tiles, each projection contiguous as its master was. A tree that
    already holds `wqkv` is a served tree: it is not fused or quantized
    again, and in its dtype comes back leaf for leaf.

    `weight_dtype="int8"` quantizes the `QUANTIZED_WEIGHTS`
    (`quantize_params`, the three projections each as before: a scale
    an output channel, `wqkv_scale` [L, 3, H * Dh]); the scales stay f32,
    since `blocks.weight` multiplies in f32 before its cast. For every
    `weight_dtype`, every other floating leaf (`embed`, `pos_embed`, the
    norm scales and, with `weight_dtype="f32"`, the matmul stacks) takes
    `cfg.activation_dtype()`, the dtype each step casts it to at use:
    the same rounding of the same numbers, done once, so the matmuls
    see bit-identical operands and a compiled step's `astype` of a leaf
    to its own dtype is no op. (Cast at use, XLA hoists the casts out
    of the layer loop and every run of a step converts the whole tree.)
    A leaf already in that dtype is returned as it is, so a fused
    `dtype="float32"` tree and a fused tree in bf16 come back leaf for
    leaf. Pure and jittable; the engine runs it at construction and
    on every `update_params`, so trainers go on publishing f32 masters.
    Training calls the model functions with the masters themselves and
    traces what it traced."""
    adt = cfg.activation_dtype()
    fused = "wqkv" in params["layers"]
    if cfg.weight_dtype == "int8" and not fused:
        params = quantize_params(params)
    scales = {name + "_scale" for name in (*QUANTIZED_WEIGHTS, "wqkv")}

    def cast(x):
        return (x.astype(adt) if jnp.issubdtype(x.dtype, jnp.floating)
                else x)

    layers = {name: leaf if name in scales else cast(leaf)
              for name, leaf in params["layers"].items()}
    if not fused:       # side by side: the payloads, and int8's scales
        for suffix in ("", "_scale"):
            if QKV[0] + suffix in layers:
                layers["wqkv" + suffix] = jnp.stack(
                    [layers.pop(name + suffix) for name in QKV], axis=-2)
    return {**{name: cast(leaf) for name, leaf in params.items()
               if name != "layers"}, "layers": layers}


def kv_pool_logical_axes(quantized: bool = False):
    """Logical-axis tuples for the paged block pool {"k", "v"} of
    [L, n_blocks, block_size, H, Dh]. Heads stay tensor-parallel
    (matching the wq/wk/wv column split, so each tensor shard owns its
    own heads' rows); the block axis is replicated — any block must be
    assignable to any sequence, so it cannot ride the data axes. With
    ``quantized`` the dict grows {"k_scale", "v_scale"} of
    [L, n_blocks, block_size, H] — heads sharded with their payload
    rows, blocks replicated the same way."""
    axes = (None, None, None, "heads", None)
    pool = {"k": axes, "v": axes}
    if quantized:
        scale_axes = (None, None, None, "heads")
        pool["k_scale"] = scale_axes
        pool["v_scale"] = scale_axes
    return pool


def init_kv_pool(cfg: GPTConfig, n_blocks: int, block_size: int,
                 mesh: Mesh | None = None):
    """Preallocated paged cache {"k", "v"} of
    [L, n_blocks, block_size, H, Dh], zero-filled, placed with its
    sharding annotation when a mesh is given. `cfg.kv_dtype="f32"`
    stores cfg.dtype payloads; "int8" stores int8 payloads plus
    {"k_scale", "v_scale"} f32 [L, n_blocks, block_size, H] per-row
    scales (zero rows dequantize to exact zeros, so the zero-init is
    inert either way). Block 0 is conventionally the engine's trash
    block (idle decode rows scatter there), but nothing here enforces
    that — allocation policy is the host's job."""
    quantized = check_quant_cfg(cfg)
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_heads,
             cfg.head_dim)
    payload_dt = jnp.int8 if quantized else cfg.activation_dtype()
    pool = {"k": jnp.zeros(shape, payload_dt),
            "v": jnp.zeros(shape, payload_dt)}
    if quantized:
        pool["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        pool["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    if mesh is not None:
        from ray_tpu.parallel.sharding import kv_pool_shardings
        sh = kv_pool_shardings(mesh, quantized=quantized)
        pool = {name: jax.device_put(arr, sh[name])
                for name, arr in pool.items()}
    return pool


def _scatter_kv(cache, layer, k, v, widx):
    """Write `k`/`v` [..., H, Dh] (activation dtype; N rows over the
    leading dims) into layer `layer` (a traced scalar) of the stacked
    pool `cache` at that layer's flat row numbers ``widx [N]``. The
    scatter takes three indices, ``(layer, widx // bs, widx % bs)``, into
    the pool as it is stored (no reshape: merging the block and offset
    axes is a copy wherever XLA tiles or orders them its own way, an
    int8 pool's scales for one). A row whose ``widx`` is ``n_blocks *
    bs`` or more is out of bounds on the block axis and drops — the
    padded-tail / past-table convention every paged writer shares. (One
    flat index into the stacked pool would turn that number into row 0
    of block 0 of the next layer.) An int8 pool (``"k_scale" in cache``
    — a static check) quantizes at the write: payload rows and their
    (position, head) scale cells scatter through the SAME indices, so
    single-token appends, chunked prefill and W-token verify all land
    byte-identical int8 for identical f32 inputs (`ops.quant`'s
    determinism contract). Returns the new stacked cache dict; on a pool
    that is a loop's carry or a donated argument XLA writes the rows
    where the pool lies."""
    bs, nh, hd = cache["k"].shape[2:]
    rows = {"k": k.reshape(-1, nh, hd), "v": v.reshape(-1, nh, hd)}
    if "k_scale" in cache:
        from ray_tpu.ops import quant
        rows["k"], rows["k_scale"] = quant.quantize_rows(rows["k"])
        rows["v"], rows["v_scale"] = quant.quantize_rows(rows["v"])
    return {name: pool.at[layer, widx // bs, widx % bs].set(
                rows[name].astype(pool.dtype), mode="drop")
            for name, pool in cache.items()}


def _paged_layers(params, x, cache, cfg: GPTConfig, widx, attend):
    """Scan activations x [..., D] through the layer stack with the
    stacked pool `cache` as a carry beside them, so that within a step
    the pool never leaves the buffer it was donated in: layer `li`'s K/V
    rows are written into the stacked arrays at ``(li, widx)`` first
    (`_scatter_kv`), then ``attend(q, cache, li)`` attends over layer
    `li` of the written pool: the three paged kernels (decode, verify,
    the prefill chunk) take the stacked pool and the layer's number and
    fetch the live pages themselves, so no step slices or copies a
    layer: of the pool, and over a served tree (`serving_params`) of
    the weights either, each dot reading its slice of its stack where
    it lies. (Scanning over the pool instead makes XLA
    slice every layer out, stack the written layers into a new buffer
    and copy that onto the donated one: three passes over K and over V a
    step.)

    A head size under 128 is the exception, told by the paged kernel's
    own plan (`reads_pool_where_it_lies`): XLA stores such a pool in a
    layout of its own, and rows can only be written into, and pages read
    from, a lay-out of it. That lay-out has to be one layer's, so there
    a layer is taken out of the carry, written and read as a stack of
    one, and put back where it was; the carry still never moves.
    Returns (final-norm activations, the updated pool)."""
    from ray_tpu.ops.decode_attention import reads_pool_where_it_lies
    adt = cfg.activation_dtype()
    pet = _matmul_out(cfg)
    whole = reads_pool_where_it_lies(*cache["k"].shape[2:], cache["k"].dtype,
                                     "k_scale" in cache)

    def body(carry, scanned):
        x, cache = carry                # cache["k"/"v"]: [L, nb, bs, H, Dh]
        lp, li = scanned

        def write_then_attend(q, k, v):
            if whole:
                written = _scatter_kv(cache, li, k, v, widx)
                return attend(q, written, li), written
            one = {name: jax.lax.dynamic_slice_in_dim(pool, li, 1)
                   for name, pool in cache.items()}
            one = _scatter_kv(one, 0, k, v, widx)
            return attend(q, one, 0), {
                name: jax.lax.dynamic_update_slice_in_dim(
                    cache[name], one[name], li, 0) for name in cache}

        x, cache, _ = layer(x, lp, cfg, pet, write_then_attend)
        return (x, cache), None

    (x, cache), _ = jax.lax.scan(
        body, (x, cache),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    with jax.named_scope(HEAD):
        return rms_norm(x, params["final_ln_scale"]), cache


def prefill_paged(params, tokens, cache, cfg: GPTConfig,
                  mesh: Mesh | None = None, *, block_table, start,
                  length=None):
    """One chunk of paged prefill for a single sequence: ``tokens
    [1, C]`` (right-padded to the chunk bucket C) are processed at
    absolute positions ``start .. start + length - 1``; their K/V are
    scattered into the block pool through ``block_table [max_blocks]``
    i32, and the returned logits ``[1, vocab]`` f32 are the chunk's last
    *real* position (``start + length - 1``) — the engine samples the
    request's first token from the final chunk's logits and ignores the
    rest.

    Attention is causal over the WHOLE prefix: each chunk token attends
    to every cached position written by earlier chunks (or shared via
    the radix tree) plus the causal part of its own chunk — gathered
    from the pool through the same block table it writes. `start`,
    `length` and the table are traced, so prefill compiles once per
    chunk bucket, ever.

    Attention routes through
    `ops.decode_attention.paged_prefill_attention`
    (`cfg.prefill_attn_impl`): the "jax" path is the dense gather+einsum
    this function used to inline, bit-identical; "pallas" (or "auto" on
    TPU) runs the fused kernel (`paged_mq`), whose chunk scores never
    round-trip HBM and which reads the sequence's live pages of the
    layer out of the carried pool where it lies.
    An int8 pool (`cfg.kv_dtype="int8"`) quantizes K/V inside the
    scatter and the attention op dequantizes blockwise inside."""
    check_quant_cfg(cfg)
    from ray_tpu.ops.decode_attention import paged_prefill_attention
    b, c = tokens.shape
    if b != 1:
        raise ValueError(f"paged prefill wants tokens [1, C], got "
                         f"batch {b}")
    nb, bs = cache["k"].shape[1], cache["k"].shape[2]
    if start is None:
        raise ValueError("prefill_paged needs start=")
    adt = cfg.activation_dtype()
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(c if length is None else length, jnp.int32)
    table = jnp.asarray(block_table, jnp.int32)

    with jax.named_scope(EMBED):
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        # Physical flat write indices; padded tail rows scatter out of
        # bounds and are dropped, so chunk garbage never lands in a block.
        widx = jnp.where(valid,
                         table[positions // bs] * bs + positions % bs,
                         nb * bs)
        x = params["embed"].astype(adt)[tokens[0]]
        x = x + params["pos_embed"].astype(adt)[positions]      # [C, D]

    def attend(q, cache, layer):        # q: [C, H, Dh]
        return paged_prefill_attention(
            q, cache["k"], cache["v"], table, start,
            k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
            layer=layer, impl=cfg.prefill_attn_impl)

    x, cache = _paged_layers(params, x, cache, cfg, widx, attend)
    with jax.named_scope(HEAD):
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        logits = jnp.einsum("td,vd->tv", last, params["embed"].astype(adt),
                            preferred_element_type=jnp.float32)
    return logits, cache


def decode_step_paged(params, tokens, cache, pos, tables,
                      cfg: GPTConfig, mesh: Mesh | None = None):
    """One autoregressive step for every slot through the paged cache:
    ``tokens [B]`` at positions ``pos [B]``, each slot's blocks named by
    ``tables [B, max_blocks]`` i32. Writes each token's K/V at its
    logical position's block/offset and attends over logical positions
    ``<= pos`` via `ops.decode_attention.paged_decode_attention`.
    Returns ``(logits [B, vocab] f32, cache)``.

    Shapes are static (B slots, fixed pool, fixed table width), so the
    engine's jitted wrapper still compiles exactly once; idle rows
    should point their table at the trash block (0) and any position —
    their writes collide harmlessly there and nobody reads the output.

    An int8 pool (`cfg.kv_dtype="int8"`) quantizes the appended K/V row
    (payload + per-head scale cell through the same drop-mode scatter)
    and the attention kernel dequantizes per block in VMEM."""
    check_quant_cfg(cfg)
    from ray_tpu.ops.decode_attention import paged_decode_attention
    adt = cfg.activation_dtype()
    nb, bs = cache["k"].shape[1], cache["k"].shape[2]
    mb = tables.shape[1]
    pos = pos.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    with jax.named_scope(EMBED):
        # Positions past the table's reach (speculative draft steps can
        # run a few past max_len) must DROP, not clamp — a clamped index
        # would land the write inside the slot's own last block and
        # corrupt real data.
        blk = jnp.take_along_axis(
            tables, jnp.minimum(pos // bs, mb - 1)[:, None], axis=1)[:, 0]
        widx = jnp.where(pos < mb * bs, blk * bs + pos % bs,
                         nb * bs)                # [B] flat write index
        x = params["embed"].astype(adt)[tokens]
        x = x + params["pos_embed"].astype(adt)[
            jnp.minimum(pos, cfg.max_seq_len - 1)]

    def attend(q, cache, layer):        # q: [B, H, Dh]
        return paged_decode_attention(q, cache["k"], cache["v"], tables,
                                      pos, k_scale=cache.get("k_scale"),
                                      v_scale=cache.get("v_scale"),
                                      layer=layer,
                                      impl=cfg.decode_attn_impl)

    x, cache = _paged_layers(params, x, cache, cfg, widx, attend)
    with jax.named_scope(HEAD):
        logits = jnp.einsum("bd,vd->bv", x, params["embed"].astype(adt),
                            preferred_element_type=jnp.float32)
    return logits, cache


def verify_step_paged(params, tokens, cache, pos, tables,
                      cfg: GPTConfig, mesh: Mesh | None = None):
    """Batched W-token verify forward for speculative decoding: ``tokens
    [B, W]`` — column 0 is each slot's current token, columns 1..W-1 a
    speculated continuation — where row b's token j sits at logical
    position ``pos[b] + j``. Every token's K/V is written to its
    block/offset first, then all W tokens attend in one shot through
    `ops.decode_attention.paged_verify_attention` (token j sees positions
    ``<= pos[b] + j``, i.e. the real prefix plus drafts 0..j-1 — the same
    numbers W sequential `decode_step_paged` calls would produce).
    Returns ``(logits [B, W, vocab] f32, cache)``: logits[:, j] is the
    target model's next-token distribution *after* accepting drafts
    1..j, which is exactly what the engine's in-jit accept needs.

    Rejected drafts need no device-side cleanup: their K/V sit at
    positions > the rolled-back ``pos``, which the position mask hides
    and which the next (sequential) writes overwrite before any read —
    ``pos`` is the authoritative tail. Positions that run past the table
    (tail of a near-max_len slot) drop their writes instead of clamping,
    so a slot can never corrupt its own last block. Shapes are static
    (B slots, fixed W), so the engine's verify jit compiles exactly
    once.

    An int8 pool (`cfg.kv_dtype="int8"`) runs verify quantized:
    quantize-then-dequantize is a pure function of the written values
    (`ops.quant`), so a draft row's dequantized K/V is byte-identical
    to what the sequential decode append would have produced — verify
    stays bit-identical to W sequential steps, quantized or not."""
    check_quant_cfg(cfg)
    from ray_tpu.ops.decode_attention import paged_verify_attention
    adt = cfg.activation_dtype()
    w = tokens.shape[1]
    nb, bs = cache["k"].shape[1], cache["k"].shape[2]
    mb = tables.shape[1]
    pos = pos.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    with jax.named_scope(EMBED):
        positions = pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        blk = jnp.take_along_axis(tables, jnp.minimum(positions // bs,
                                                      mb - 1), axis=1)
        widx = jnp.where(positions < mb * bs,
                         blk * bs + positions % bs,
                         nb * bs).reshape(-1)     # [B*W] flat, drop OOB
        x = params["embed"].astype(adt)[tokens]
        x = x + params["pos_embed"].astype(adt)[
            jnp.minimum(positions, cfg.max_seq_len - 1)]

    def attend(q, cache, layer):        # q: [B, W, H, Dh]
        return paged_verify_attention(q, cache["k"], cache["v"], tables,
                                      pos, k_scale=cache.get("k_scale"),
                                      v_scale=cache.get("v_scale"),
                                      layer=layer,
                                      impl=cfg.decode_attn_impl)

    x, cache = _paged_layers(params, x, cache, cfg, widx, attend)
    with jax.named_scope(HEAD):
        logits = jnp.einsum("bwd,vd->bwv", x, params["embed"].astype(adt),
                            preferred_element_type=jnp.float32)
    return logits, cache


def _prefill_family(*args, **kw):
    return (*prefill_paged(*args, **kw), None)


def _decode_family(*args, **kw):
    return (*decode_step_paged(*args, **kw), None)


FAMILY = ServingFamily(
    init_pool=init_kv_pool, prefill=_prefill_family, decode=_decode_family,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, verify=verify_step_paged,
    load=serving_params)


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
