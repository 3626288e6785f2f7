"""A decoder of window and full attention layers over routed experts, on
the training path: the sequential pre-norm block that
`Mellum2-12B-A2.5B-Instruct` names (`model_type` `mellum`). Published
layer `l` is a window layer where `layer_types[l]` says
"sliding_attention" (3 in 4) and a full layer otherwise.

The layer (`RMSNorm`: eps `rms_norm_eps`, one scale, no bias; Hq query
heads over Hkv key-value heads of d, query head `h` reads key-value head
`h // (Hq / Hkv)`):

    n = RMSNorm(x);  q, k, v = W_q n, W_k n, W_v n
    rotary on q and k, split halves, by the layer's kind (`RotarySpec`):
        window layer: plain, position i attends j with i - window < j <= i
        full layer: YaRN's frequencies, cos and sin x its factor; j <= i
    h = x + W_o concat_h softmax(q_h . k / sqrt(d)) v
    m = RMSNorm(h);  p = softmax(W_r m) in float32 over the router's width
    y = h + sum over the `experts_per_token` largest p_e, weights p_e / sum
        of the chosen (`norm_topk`), of the held experts' SwiGLU(m)

After the last layer an RMSNorm, then an untied head over the rows of the
vocabulary held. No shared expert, no bias, no q/k norm.

What it shares with `models/window_moe.py` (`command-a-plus`: a parallel
block under a mean-subtracting norm, sigmoid scores, averaged shared
experts, a tied head, full layers without positions, served) is the
period of layer kinds and the grouped heads, so this is a module of its
own beside it and not that module at other switches: of the block's seven
lines one would be common. The pieces that are common to the trained
families are imported: `models/blocks.py`'s `mm`, `rms_norm` and
`rounded`, `ops.grouped_experts`, `ops.flash_attention`.

Training (`forward_features`): attention through the flash kernels, a
window layer's under their `_band` names (`ops/flash_attention.py`: the
walk visits the band alone, K and V of 4 heads are read by 32 and never
repeated); the held experts through `ops.grouped_experts`' kernels and
their backward pass, `expert_chunk` tokens at a time, so that the
dropless layout's worst case (every pair held here) is that of a chunk
and not of the sequence; each layer a `jax.checkpoint` that keeps its
input and the flash forward's output and logsumexp. `forward` is the
whole-sequence form for tests: every score made and masked.

Parameters: the tree `benchmarks/refs/window_moe_train.py` documents (a
list of layer dicts), float32 masters cast at use.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.blocks import mm, rms_norm, rounded, unembed
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, TrainingFamily
from ray_tpu.ops import grouped_experts

EMBED_INIT = 1.0        # `latent_sparse_moe.EMBED_INIT`'s reason


class RotarySpec(NamedTuple):
    """A kind of layer's `rope_parameters` entry. `factor` 1: plain rotary
    at `theta`; above 1, YaRN: frequencies blended between `theta`'s and
    those a `factor`-th as fast over the dims whose wavelength lies
    between `original / beta_fast` and `original / beta_slow` turns, and
    cos and sin scaled by `attention_factor`."""
    theta: float = 10000.0
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_published(cls, entry: dict) -> "RotarySpec":
        if entry.get("rope_type", "default") == "default":
            return cls(theta=float(entry["rope_theta"]))
        if entry["rope_type"] != "yarn":
            raise ValueError(f"unknown rope_type {entry['rope_type']!r}")
        return cls(float(entry["rope_theta"]), float(entry["factor"]),
                   int(entry["original_max_position_embeddings"]),
                   float(entry["beta_fast"]), float(entry["beta_slow"]),
                   float(entry["attention_factor"]))


def yarn_ramp(spec: RotarySpec, dim: int):
    """(low, high, ramp [dim / 2]): YaRN's correction range in rotary
    dims and each dim's share of the slowed frequency."""
    def corr(turns):
        return (dim * math.log(spec.original / (turns * 2 * math.pi))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(corr(spec.beta_fast)), 0)
    high = min(math.ceil(corr(spec.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return low, high, ramp


def inv_freq(spec: RotarySpec, dim: int) -> np.ndarray:
    """float64 [dim / 2]: the angle a position a rotary pair turns by."""
    plain = spec.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not spec.original:
        return plain
    ramp = yarn_ramp(spec, dim)[2]
    return (1 - ramp) * plain + ramp * plain / spec.factor


def rotary(x, pos, spec: RotarySpec):
    """Rotary embedding on the last axis of x [..., T, H, d] at positions
    pos [T], split halves (dim i turns with dim i + d / 2); float32
    inside."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(
        inv_freq(spec, d), jnp.float32)
    cos = jnp.cos(ang) * spec.attention_factor
    sin = jnp.sin(ang) * spec.attention_factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


@dataclass(frozen=True)
class WindowMoETrainConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    window: int = 32
    # one entry a published layer, "window" or "full"; the layers that
    # run are [first_layer, first_layer + n_layers)
    layer_types: tuple = ("window", "window", "window", "full")
    first_layer: int = 0
    rope_window: RotarySpec = RotarySpec()
    rope_full: RotarySpec = RotarySpec()
    expert_ff: int = 32
    router_width: int = 8
    experts_per_token: int = 2
    held_from: int = 0
    held_count: int = 8
    norm_topk: bool = True
    eps: float = 1e-6
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    sparse_impl: str = "auto"        # auto | pallas | jax (the experts)
    # q and kv rows a grid step of the flash kernels, at most (the sweep
    # at 32 heads over 4, T 32,768 is `flash_attention`'s docstring's)
    flash_block_q: int = 2048
    flash_block_kv: int = 2048
    # tokens the expert layer routes at a time: the static layout of
    # `ops.grouped_experts` holds `tokens x experts_per_token` rows
    expert_chunk: int = 8192
    # std of a score over random keys that `init_params` scales W_q and
    # W_k for (`window_moe.init_params`'s reason)
    attn_logit_std: float = 2.4
    # `init_params` makes the router as this many equal blocks of columns
    # side by side (1: every column its own). With one block a chip of
    # those that share a layer, every token's choice falls evenly over
    # the chips at the start, as a trained, balanced router's does, and
    # which experts a common id's tokens choose is no longer a draw a
    # seed of how much of the work is this chip's (PERF.md, PR 57)
    router_tied_blocks: int = 1
    # test-only, for the benchmark's control (`blocks.rounded`'s grid)
    expert_round: str = "none"       # none | float8_e4m3fn

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        for name in ("rope_window", "rope_full"):   # a plain tuple will do
            object.__setattr__(self, name, RotarySpec(*getattr(self, name)))
        if set(self.layer_types) - {"window", "full"} \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("a layer is a window or a full layer, and the "
                             "query heads divide over the key-value heads")
        if self.expert_round not in ("none", "float8_e4m3fn"):
            raise ValueError(f"unknown expert_round {self.expert_round!r}")
        if self.router_width % self.router_tied_blocks:
            raise ValueError("the router's blocks divide its width")

    @property
    def kinds(self) -> tuple:
        """"window" or "full", one a layer that runs."""
        lo = self.first_layer
        return self.layer_types[lo:lo + self.n_layers]

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def training(self):
        return TRAINING


def from_published(*, hidden_size, num_hidden_layers, num_attention_heads,
                   num_key_value_heads, head_dim, sliding_window, layer_types,
                   rope_parameters, moe_intermediate_size, num_experts,
                   num_experts_per_tok, norm_topk_prob, rms_norm_eps,
                   max_position_embeddings, layers_from=0,
                   experts_held_from=0, published=None,
                   **same) -> WindowMoETrainConfig:
    """The configuration file's published keys -> `WindowMoETrainConfig`
    (`benchmarks/configs/mellum2-12b-a2.5b.json`, `program.constructor`).
    `num_experts` is how many experts are held here; the router's width
    is `published["num_experts"]` where a share is run."""
    return WindowMoETrainConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
        head_dim=head_dim, window=sliding_window,
        layer_types=["window" if t == "sliding_attention" else "full"
                     for t in layer_types],
        first_layer=layers_from,
        rope_window=RotarySpec.from_published(
            rope_parameters["sliding_attention"]),
        rope_full=RotarySpec.from_published(
            rope_parameters["full_attention"]),
        expert_ff=moe_intermediate_size,
        router_width=(published or {}).get("num_experts", num_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=num_experts, norm_topk=norm_topk_prob, eps=rms_norm_eps,
        max_seq_len=max_position_embeddings, **same)


def init_params(key, cfg: WindowMoETrainConfig):
    """float32 master parameters of the layers that run, cast at use:
    normal, fan-in^-1/2, residual outputs x (2 x layers)^-1/2, embedding
    `EMBED_INIT`, norm scales 1; W_q and W_k x `attn_logit_std`^1/2, so
    that a score over random keys has that standard deviation and a
    query's weight lies on some tens of keys, as a trained layer's does;
    the router `router_tied_blocks` equal blocks of columns (equal scores
    are chosen lowest expert first, so a token's `experts_per_token`
    choices are the best columns' copies in every block; the copies part
    with the first step, whose gradients differ by expert)."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f = cfg.expert_ff
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    experts = (cfg.held_count, f, d)
    layers = [{
        "attn_norm_scale": ones(d), "ffn_norm_scale": ones(d),
        "w_q": normal((d, hq * hd), d ** -0.5 * cfg.attn_logit_std ** 0.5),
        "w_k": normal((d, hkv * hd), d ** -0.5 * cfg.attn_logit_std ** 0.5),
        "w_v": normal((d, hkv * hd), d ** -0.5),
        "w_out": normal((hq * hd, d), (hq * hd) ** -0.5 * residual),
        "router": jnp.tile(
            normal((d, cfg.router_width // cfg.router_tied_blocks),
                   d ** -0.5), (1, cfg.router_tied_blocks)),
        "we_gate": normal(experts, d ** -0.5),
        "we_up": normal(experts, d ** -0.5),
        "we_down": normal(experts, f ** -0.5 * residual),
    } for _ in range(cfg.n_layers)]
    return {"embed": normal((cfg.vocab_size, d), EMBED_INIT),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_norm_scale": ones(d), "layers": layers}


def param_logical_axes(cfg: WindowMoETrainConfig):
    """Every leaf whole on every device: one chip's share is trained on
    one chip (the expert exchange over chips is not built)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


# ---------------------------------------------------------------------------
# pieces of the layer
# ---------------------------------------------------------------------------

def _qkv(n, lp, kind, pos, cfg):
    """Normed n [B, T, D] at positions pos [T] -> q [B, T, Hq, d], k, v
    [B, T, Hkv, d] in the activation type, rotary by the layer's kind."""
    adt = cfg.activation_dtype()
    b, t, _ = n.shape
    spec = cfg.rope_window if kind == "window" else cfg.rope_full
    q = mm(n, lp["w_q"], adt).reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = mm(n, lp["w_k"], adt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = mm(n, lp["w_v"], adt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    return rotary(q, pos, spec), rotary(k, pos, spec), v


def softmax_routing(m, lp, cfg):
    """-> (chosen [N, k] i32, weights [N, k] f32): the `experts_per_token`
    largest of a softmax over the router's whole width, in float32,
    renormalised over the chosen where `norm_topk`."""
    p = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", m.astype(jnp.float32), lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), -1)
    weights, chosen = jax.lax.top_k(p, cfg.experts_per_token)
    if cfg.norm_topk:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen.astype(jnp.int32), weights


def _experts(m, lp, cfg, kernel: str):
    """Normed m [N, D] -> (what the held experts add [N, D], counts i32
    [2 + router_width]: pairs routed here, pairs routed anywhere, every
    expert's load), `expert_chunk` tokens at a time."""
    adt = cfg.activation_dtype()
    n, d = m.shape
    chunk = math.gcd(n, cfg.expert_chunk)
    gate, up, down = (rounded(lp[name], cfg.expert_round)
                      for name in ("we_gate", "we_up", "we_down"))

    def some(rows):
        chosen, weights = softmax_routing(rows, lp, cfg)
        routed, load = grouped_experts.experts_grouped(
            rounded(rows, cfg.expert_round), chosen, weights, gate, up, down,
            held_from=cfg.held_from, impl=cfg.sparse_impl, name=kernel)
        every = jnp.sum(chosen[..., None] == jnp.arange(cfg.router_width),
                        (0, 1), dtype=jnp.int32)
        return routed.astype(adt), jnp.concatenate([
            jnp.stack([jnp.sum(load), jnp.int32(chosen.size)]), every])

    if chunk == n:
        return some(m)
    routed, counts = jax.lax.map(some, m.reshape(n // chunk, chunk, d))
    return routed.reshape(n, d), jnp.sum(counts, 0)


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: WindowMoETrainConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: every
    score made and masked, the experts by `experts_grouped`'s forward."""
    from ray_tpu.parallel.ring_attention import reference_attention
    adt = cfg.activation_dtype()
    b, t = tokens.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens]
    for lp, kind in zip(params["layers"], cfg.kinds):
        with jax.named_scope(MIXER):
            q, k, v = _qkv(rms_norm(x, lp["attn_norm_scale"], cfg.eps), lp,
                           kind, pos, cfg)
            att = reference_attention(
                q, k, v, causal=True,
                window=cfg.window if kind == "window" else None)
            x = x + mm(att.reshape(b, t, -1), lp["w_out"], adt)
        with jax.named_scope(FFN):
            m = rms_norm(x, lp["ffn_norm_scale"], cfg.eps)
            x = x + _experts(m.reshape(b * t, -1), lp, cfg,
                             grouped_experts.EXPERTS_GROUPED)[0].reshape(
                                 x.shape)
    with jax.named_scope(HEAD):
        return unembed(rms_norm(x, params["final_norm_scale"], cfg.eps),
                       params["head"], adt)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _train_layer(x, lp, kind, pos, cfg):
    """-> (x [B, T, D], the layer's expert counts [2 + router_width])."""
    from ray_tpu.ops.flash_attention import flash_attention
    adt = cfg.activation_dtype()
    b, t, d = x.shape
    with jax.named_scope(MIXER):
        q, k, v = _qkv(rms_norm(x, lp["attn_norm_scale"], cfg.eps), lp, kind,
                       pos, cfg)
        att = flash_attention(q, k, v, True, cfg.flash_block_q,
                              cfg.flash_block_kv,
                              cfg.window if kind == "window" else None)
        x = x + mm(att.reshape(b, t, -1), lp["w_out"], adt)
    with jax.named_scope(FFN):
        routed, counts = _experts(
            rms_norm(x, lp["ffn_norm_scale"], cfg.eps).reshape(b * t, d), lp,
            cfg, grouped_experts.EXPERTS_GROUPED_TRAIN)
        return x + routed.reshape(b, t, d), counts


def forward_features(params, tokens, cfg: WindowMoETrainConfig, mesh=None):
    """tokens [B, T] -> (final-normed activations [B, T, D] in the
    activation type: everything but the head, which the fused loss folds
    in; counts [layers, 2 + router_width] i32: each layer's pairs routed
    here, pairs routed anywhere, and every expert's load). Each layer is
    a `jax.checkpoint` of its own that keeps its input and the flash
    forward's output and logsumexp (`latent_sparse_moe.forward_features`'
    policy and reason)."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("one chip's share is trained on one "
                                  "chip: the expert exchange over chips "
                                  "is not built")
    from ray_tpu.ops.flash_attention import SAVED_NAMES
    adt = cfg.activation_dtype()
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    policy = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens]
    counts = []
    for lp, kind in zip(params["layers"], cfg.kinds):
        x, c = jax.checkpoint(
            lambda x, lp, kind=kind: _train_layer(x, lp, kind, pos, cfg),
            policy=policy)(x, lp)
        counts.append(c)
    with jax.named_scope(HEAD):
        return (rms_norm(x, params["final_norm_scale"], cfg.eps),
                jnp.stack(counts))


def expert_metrics(params, counts, cfg: WindowMoETrainConfig):
    """`make_train_step`'s `aux_update` for a family whose step moves
    nothing outside the optimizer: -> (params as they are, the step's
    expert metrics from `forward_features`' counts, under
    `latent_sparse_moe.update_router_bias`'s names)."""
    held = counts[:, 2 + cfg.held_from:2 + cfg.held_from + cfg.held_count]
    return params, {
        "expert_pairs_here": jnp.sum(counts[:, 0]),
        "expert_pairs_routed": jnp.sum(counts[:, 1]),
        "expert_load_max": jnp.max(held),
        "expert_load_mean": jnp.mean(held.astype(jnp.float32)),
    }


TRAINING = TrainingFamily(
    init_params=init_params, param_logical_axes=param_logical_axes,
    forward_features=forward_features, head="head",
    aux_update=expert_metrics)
