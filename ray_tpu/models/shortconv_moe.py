"""A decoder whose token mixing is, in most layers, a gated convolution
three positions long, and in the rest grouped-head softmax attention; its
feed-forward part a gated MLP in the first layers and routed experts with
no shared expert in the others. The layer that `LFM2-8B-A1B` names
(`lfm2_moe`): published layer `l` is a convolution layer where
`layer_types[l]` says "conv" (18 of 24) and an attention layer where it
says "full_attention"; its feed-forward part is dense where `l <
num_dense_layers` and sparse otherwise.

The layer (every projection without bias):

    n = RMSNorm_operator(x);  h = x + mixer(n)
    f = RMSNorm_ffn(h);       y = h + ffn(f)

    conv(n):  B | C | u = n W_in            (thirds of 3 D, in that order)
              g_t = B_t * u_t
              c_t = sum_{i<K} w[i] g_{t-K+1+i}    depthwise, causal, K taps,
                  zeros before the sequence, no bias, no activation
              conv = (C_t * c_t) W_out
    attn(n):  q = n W_q (Hq heads of d), k, v (Hkv heads); q and k normed
              over d (one learned scale each), then rotary on all d dims
              in halves; query head `h` reads key-value head `h // (Hq /
              Hkv)`, causal, scale d^-1/2; W_o
    dense:    W_down (silu(W_gate f) * W_up f)
    sparse:   s = sigmoid(f W_r), float32; the k largest of s + bias chosen;
              weights s_e / (sum of the chosen + 1e-6) x routed_scale; the
              held experts' SwiGLU(f) summed (`blocks.expert_layer`, which
              finds no shared expert in the layer's parameters)

After the last layer an RMSNorm, then the tied head. This module is new
and `models/mamba_moe.py` is not widened: that family's layer is a mixer
or a feed-forward part alone under one norm, its state a recurrence's
with rings; nothing but the pool's two kinds would be shared.

**What the engine holds for this family**: one request, two kinds of
block (`ServingFamily.state_blocks` 1 and `paged`), as
`models/mamba_moe.py`. Column 0 of its table names a state block:
`"tail" [L_conv, blocks, K - 1, D]` in the activations' type, the last K -
1 positions' `g` of every convolution layer (90,112 B a sequence at the
published widths, whatever its length). The columns after it name pages
of `"k"`, `"v"` `[L_attn, pages, Hkv / pack, block_size, pack * d]`,
head-major with `pack` key-value heads side by side where one does not
fill a lane tile (`decode_attention.gqa_pack`: two heads of 64), which is
the layout `gqa_attention` reads where it lies. A program reads its
blocks' tails once, before the first layer, and writes them once, after
the last: no layer's write stands between two reads of the array, so no
layer copies it (a chunk updates its block's slice in place; the step's
one gather and one scatter run on the array as the compiler stages it
in VMEM, 0.9 % of a step: PERF.md, PR 61). Prefill reads a first chunk's tails as zeros (a block
handed to a new sequence is reset by that), keeps the last K - 1 **live**
positions whatever the bucket's padding and writes the padding's rows
nowhere; decode's idle rows (table all 0) rewrite the trash blocks.

Parameters: the tree `benchmarks/refs/shortconv_moe.py` documents.
`forward` is the whole-sequence form for tests; `prefill`, `decode` and
`tick` (a step and another sequence's chunk as one program, each weight
read once) are what `ServingFamily` asks, one layer loop (`_layers`) over
the rows of a chunk, of a step or of both.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (Experts, cast_leaves, copy_block,
                                   expert_layer, expert_totals, gated_mlp,
                                   gather_block, mm, rms_norm, rope_halves,
                                   row_index, scatter_block, summarize,
                                   unembed, write_chunk, write_rows)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import grouped_experts

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow.
# `expert_row_tiles` over `experts_reached` is how often a call read an
# expert's matrices: 1 where every group fits a tile of `experts_grouped`'s
# layout, and each tile over that is a second read (`row_tile`)
COUNTS = ("conv_rows_live", "conv_rows_padded", "state_resets",
          "attention_rows_read", "expert_tokens_here",
          "expert_tokens_routed", "expert_row_tiles", "experts_reached")
STATE_KEYS = ("tail",)              # the pool's arrays of state blocks
KINDS = {"conv": "conv", "full_attention": "attention"}
# the leaves a step reads in float32 (`load`): the router's scores and the
# taps' sum are made there
FLOAT32_LEAVES = ("router", "router_bias", "conv_w")
TOPK_EPS = 1e-6         # beside the chosen scores' sum, as the source has it
# `init_params`' scales, the ones `benchmarks/configs/lfm2-8b-a1b.json`
# draws at and says why (`assumed`): a small table under a tied head, a
# stream that the leading dense layers build and the later ones move a
# little at a time
EMBED_INIT, DENSE_GAIN, CONV_GAIN, EXPERT_GAIN = 0.05, 8.0, 0.5, 4.0


@dataclass(frozen=True)
class ShortConvMoEConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 6
    # one entry a published layer, "conv" or "attention"; the layers that
    # run are [first_layer, first_layer + n_layers), and published layer
    # `l` has a dense feed-forward part where `l < dense_layers`
    layer_types: tuple = ("conv", "conv", "attention", "conv", "conv",
                          "conv")
    first_layer: int = 0
    dense_layers: int = 2
    conv_taps: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 96
    expert_ff: int = 32
    router_width: int = 8
    experts_per_token: int = 2
    held_from: int = 0
    held_count: int = 8
    norm_topk: bool = True
    routed_scale: float = 1.0
    rope_theta: float = 1000000.0
    eps: float = 1e-5
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    attn_impl: str = "auto"          # auto | pallas | jax (gqa_full_*)
    sparse_impl: str = "auto"        # auto | pallas | jax (the experts)
    # test-only, for the benchmark's control (`blocks.expert_layer`): the
    # routed experts' inputs and matrices on the float8_e4m3fn grid
    expert_round: str = "none"       # none | float8_e4m3fn

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - set(KINDS.values()) \
                or len(self.layer_types) < self.first_layer + self.n_layers \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("a layer is a conv or an attention layer, the "
                             "types name every layer that runs, and the "
                             "query heads divide over the key-value heads")
        if self.expert_round not in ("none", "float8_e4m3fn"):
            raise ValueError(f"unknown expert_round {self.expert_round!r}")

    @property
    def kinds(self) -> tuple:
        """("conv" | "attention", "dense" | "sparse"), one a layer that
        runs."""
        lo = self.first_layer
        return tuple(
            (kind, "dense" if lo + i < self.dense_layers else "sparse")
            for i, kind in enumerate(self.layer_types[lo:lo + self.n_layers]))

    @property
    def n_conv(self) -> int:
        return sum(mixer == "conv" for mixer, _ in self.kinds)

    @property
    def kv_pack(self) -> int:
        """Key-value heads side by side in a row of a page."""
        return da.gqa_pack(self.n_kv_heads, self.head_dim)

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts(self) -> Experts:
        return Experts(self.router_width, self.experts_per_token,
                       self.norm_topk, self.held_from,
                       routed_scale=self.routed_scale,
                       expert_round=self.expert_round, impl=self.sparse_impl,
                       norm_eps=TOPK_EPS)

    @property
    def family(self):
        return FAMILY


def from_published(*, vocab_size, hidden_size, num_hidden_layers, layer_types,
                   conv_L_cache, conv_bias, num_attention_heads,
                   num_key_value_heads, intermediate_size, num_dense_layers,
                   moe_intermediate_size, num_experts, num_experts_per_tok,
                   norm_topk_prob, routed_scaling_factor, use_expert_bias,
                   norm_eps, rope_theta, max_position_embeddings,
                   layers_from=0, experts_held_from=0, published=None,
                   **same) -> ShortConvMoEConfig:
    """The configuration file's published keys -> `ShortConvMoEConfig`
    (`benchmarks/configs/lfm2-8b-a1b.json`, `program.constructor`).
    `num_experts` is how many experts are held here; the router's width
    is `published["num_experts"]` where a share is run. `layer_types` may
    be cut to the layers that run or kept whole."""
    if conv_bias or not use_expert_bias:
        raise ValueError("this family's convolution has no bias and its "
                         "router has one")
    return ShortConvMoEConfig(
        vocab_size=vocab_size, d_model=hidden_size,
        n_layers=num_hidden_layers,
        layer_types=[KINDS[t] for t in layer_types], first_layer=layers_from,
        dense_layers=num_dense_layers, conv_taps=conv_L_cache,
        n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
        head_dim=hidden_size // num_attention_heads, d_ff=intermediate_size,
        expert_ff=moe_intermediate_size,
        router_width=(published or {}).get("num_experts", num_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=num_experts, norm_topk=norm_topk_prob,
        routed_scale=float(routed_scaling_factor), eps=norm_eps,
        rope_theta=float(rope_theta), max_seq_len=max_position_embeddings,
        **same)


def init_params(key, cfg: ShortConvMoEConfig):
    """Float32 leaves, for tests and the smoke; the tree
    `benchmarks/refs/shortconv_moe.py` documents. The embedding at
    `EMBED_INIT`: the head is tied, and a table at 1.0 would give every
    token's own row a logit of D."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 1 + 12 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for mixer, ffn in cfg.kinds:
        lp = {"operator_norm_scale": ones(d), "ffn_norm_scale": ones(d)}
        lead = residual * (DENSE_GAIN if ffn == "dense" else 1.0)
        if mixer == "conv":
            lp.update(
                w_in=normal((d, 3 * d), d ** -0.5),
                conv_w=normal((cfg.conv_taps, d), cfg.conv_taps ** -0.5),
                w_out=normal((d, d), d ** -0.5 * (
                    lead if ffn == "dense" else residual * CONV_GAIN)))
        else:
            lp.update(
                w_q=normal((d, hq * hd), d ** -0.5),
                w_k=normal((d, hkv * hd), d ** -0.5),
                w_v=normal((d, hkv * hd), d ** -0.5),
                q_norm_scale=ones(hd), k_norm_scale=ones(hd),
                w_out=normal((hq * hd, d), (hq * hd) ** -0.5 * lead))
        if ffn == "dense":
            lp.update(
                w_gate=normal((d, cfg.d_ff), d ** -0.5),
                w_up=normal((d, cfg.d_ff), d ** -0.5),
                w_down=normal((cfg.d_ff, d), cfg.d_ff ** -0.5 * lead))
        else:
            f = cfg.expert_ff
            lp.update(
                router=normal((d, cfg.router_width), d ** -0.5),
                router_bias=normal((cfg.router_width,), 0.01),
                we_gate=normal((cfg.held_count, f, d), d ** -0.5),
                we_up=normal((cfg.held_count, f, d), d ** -0.5),
                we_down=normal((cfg.held_count, f, d),
                               f ** -0.5 * residual * EXPERT_GAIN))
        layers.append(lp)
    return {"embed": normal((cfg.vocab_size, d), EMBED_INIT),
            "final_norm_scale": ones(d), "layers": layers}


def load(params, cfg: ShortConvMoEConfig):
    """`ServingFamily.load`: every floating leaf in the type the steps
    read it in, so that no step converts a weight: the router, its bias
    and the taps in float32 (`FLOAT32_LEAVES`), every other in the
    activations' type. A leaf already there is returned as it is."""
    return cast_leaves(params, cfg.activation_dtype(), FLOAT32_LEAVES)


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def init_pool(cfg: ShortConvMoEConfig, n_blocks: int, block_size: int,
              mesh=None, *, state_blocks: int):
    """{"tail"} with `state_blocks` blocks on axis 1 and {"k", "v"} with
    `n_blocks` pages, zero-filled; block 0 of each the trash block."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    adt = cfg.activation_dtype()
    pack = cfg.kv_pack
    n_attn = cfg.n_layers - cfg.n_conv

    def pages():
        return jnp.zeros((n_attn, n_blocks, cfg.n_kv_heads // pack,
                          block_size, pack * cfg.head_dim), adt)

    return {"tail": jnp.zeros((cfg.n_conv, state_blocks, cfg.conv_taps - 1,
                               cfg.d_model), adt),
            "k": pages(), "v": pages()}


# ---------------------------------------------------------------------------
# pieces of the layers
# ---------------------------------------------------------------------------

def _gated(n, lp, cfg):
    """Normed n [N, D] -> (g = B * u in the activations' type, which is
    what a tail keeps, C [N, D])."""
    adt = cfg.activation_dtype()
    d = cfg.d_model
    bcu = jnp.einsum("nd,df->nf", n, lp["w_in"].astype(adt),
                     preferred_element_type=jnp.float32).astype(adt)
    g = (bcu[:, :d].astype(jnp.float32)
         * bcu[:, 2 * d:].astype(jnp.float32)).astype(adt)
    return g, bcu[:, d:2 * d]


def _conv_out(c, conved, lp, cfg):
    """The gate C [N, D] on the taps' sum [N, D] f32, through W_out."""
    adt = cfg.activation_dtype()
    return mm((c.astype(jnp.float32) * conved).astype(adt), lp["w_out"], adt)


def conv_whole(n, lp, cfg):
    """The convolution layer of normed n [T, D] of one whole sequence by
    the definition, through W_out: -> [T, D]."""
    t, taps = n.shape[0], cfg.conv_taps
    g, c = _gated(n, lp, cfg)
    pre = jnp.pad(g.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(jnp.float32)
    return _conv_out(c, sum(w[i] * pre[i:i + t] for i in range(taps)), lp,
                     cfg)


def conv_chunk(n, lp, tail, cfg, first, length):
    """The convolution layer of a prompt chunk's normed n [C, D] after
    `tail` [K - 1, D], the sequence's last positions' g: a first chunk
    reads it as zeros, and the tail kept is the last live positions',
    whatever the padding. -> (what W_out gives [C, D], the new tail)."""
    c_len, taps = n.shape[0], cfg.conv_taps
    g, c = _gated(n, lp, cfg)
    pre = jnp.concatenate([jnp.where(first, 0, tail).astype(g.dtype), g])
    w = lp["conv_w"].astype(jnp.float32)
    wide = pre.astype(jnp.float32)
    conved = sum(w[i] * wide[i:i + c_len] for i in range(taps))
    return (_conv_out(c, conved, lp, cfg),
            jax.lax.dynamic_slice_in_dim(pre, length, taps - 1))


def conv_step(n, lp, tails, cfg):
    """The convolution layer of one decode position a row, normed n [B,
    D], each after its own `tails` [B, K - 1, D]. -> (what W_out gives
    [B, D], the new tails)."""
    g, c = _gated(n, lp, cfg)
    pre = jnp.concatenate([tails.astype(g.dtype), g[:, None]], 1)
    conved = jnp.einsum("kd,bkd->bd", lp["conv_w"].astype(jnp.float32),
                        pre.astype(jnp.float32))
    return _conv_out(c, conved, lp, cfg), pre[:, 1:]


def _qkv(n, lp, pos, cfg):
    """Normed n [N, D] at positions pos [N] -> q [N, Hq, d], k, v [N,
    Hkv, d] in the activation type: q and k normed over d, then turned."""
    adt = cfg.activation_dtype()
    rows = n.shape[0]
    q = mm(n, lp["w_q"], adt).reshape(rows, cfg.n_heads, cfg.head_dim)
    k = mm(n, lp["w_k"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim)
    v = mm(n, lp["w_v"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim)
    q = rope_halves(rms_norm(q, lp["q_norm_scale"], cfg.eps), pos,
                    cfg.rope_theta)
    k = rope_halves(rms_norm(k, lp["k_norm_scale"], cfg.eps), pos,
                    cfg.rope_theta)
    return q, k, v


def _ffn(h, lp, kind, cfg, live, kernel):
    """-> (what the feed-forward part adds to h [N, D], the expert
    layer's counts or None: `expert_layer`'s two, then the row tiles the
    call's layout gave the held experts and the experts that got a pair,
    then the loads)."""
    adt = cfg.activation_dtype()
    f = rms_norm(h, lp["ffn_norm_scale"], cfg.eps)
    if kind == "dense":
        return gated_mlp(f, lp, adt, jnp.float32)
    with jax.named_scope("routed_experts"):
        routed, _, _, counts = expert_layer(f, lp, cfg.experts, adt,
                                            live, kernel)
        load = counts[2:]
        tile = grouped_experts.row_tile(
            f.shape[0] * cfg.experts_per_token, cfg.held_count)
        plan = jnp.stack([jnp.sum(-(-load // tile)),
                          jnp.sum(load > 0, dtype=jnp.int32)])
    return routed, jnp.concatenate([counts[:2], plan, load])


def _counts(cfg, head, expert_counts):
    """`COUNTS`' first four, then the experts' four and their loads."""
    experts = expert_totals(expert_counts, 4 + cfg.held_count)
    return jnp.concatenate([jnp.stack(head).astype(jnp.int32),
                            experts.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ShortConvMoEConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: no tail
    kept, no cache, every score made and masked."""
    adt = cfg.activation_dtype()

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        live = jnp.ones((t,), bool)
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp, (mixer, ffn) in zip(params["layers"], cfg.kinds):
            with jax.named_scope(MIXER):
                n = rms_norm(x, lp["operator_norm_scale"], cfg.eps)
                if mixer == "conv":
                    x = x + conv_whole(n, lp, cfg)
                else:
                    q, k, v = _qkv(n, lp, pos, cfg)
                    att = da.reference_gqa_attention(
                        q[None], k[None], v[None],
                        jnp.zeros((1,), jnp.int32))[0]
                    x = x + mm(att.reshape(t, -1), lp["w_out"], adt)
            with jax.named_scope(FFN):
                x = x + _ffn(x, lp, ffn, cfg, live,
                             grouped_experts.EXPERTS_GROUPED)[0]
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_norm_scale"], cfg.eps),
                           params["embed"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

class _Rows:
    """A set of rows on its way through the layers, opened inside `embed`
    (its blocks' tails, every layer's, read once), `mix`ed a layer at a
    time and closed inside `head` (every layer's new tail written once).
    A subclass says what a convolution layer and an attention layer are
    for its rows; `kernel` names the attention's."""

    def __init__(self, cfg, kernel: str, x, positions, live, tails):
        self.cfg, self.kernel = cfg, kernel
        self.x, self.positions, self.live, self.tails = (x, positions, live,
                                                         tails)
        self.kept = []

    def mix(self, lp, mixer, cache, n_conv, n_attn):
        """A layer's mixer on these rows and its residual; an attention
        layer writes the rows' keys and values into `cache` first."""
        cfg = self.cfg
        n = rms_norm(self.x, lp["operator_norm_scale"], cfg.eps)
        if mixer == "conv":
            out, tail = self.conv(n, lp, self.tails[n_conv])
            self.kept.append(tail)
        else:
            q, k, v = _qkv(n, lp, self.positions, cfg)
            for key, rows in (("k", k), ("v", v)):
                cache[key] = self.write(
                    cache[key], n_attn,
                    da.heads_side_by_side(rows, cfg.kv_pack))
            att = self.attend(q, cache["k"], cache["v"], n_attn)
            out = mm(att.reshape(n.shape[0], -1), lp["w_out"],
                     cfg.activation_dtype())
        self.x = self.x + out

    def attention_rows(self):
        """Cached rows the attention layers read for the live rows."""
        return jnp.sum(jnp.where(self.live, self.positions + 1, 0)) * (
            self.cfg.n_layers - self.cfg.n_conv)


class _ChunkRows(_Rows):
    """One sequence's prompt chunk: tokens [1, C] at positions start ..
    start + length - 1; `block_table[0]` the sequence's state block, the
    rest its pages. A chunk that starts the sequence reads its tails as
    zeros."""

    def __init__(self, params, tokens, cache, cfg, block_table, start,
                 length, kernel: str):
        c = tokens.shape[1]
        if tokens.shape[0] != 1:
            raise ValueError(f"a chunk is tokens [1, C], got batch "
                             f"{tokens.shape[0]}")
        self.start = jnp.asarray(start, jnp.int32)
        self.length = jnp.asarray(c if length is None else length, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        self.block, self.pages = table[0], table[1:]
        self.first = self.start == 0
        offs = jnp.arange(c, dtype=jnp.int32)
        super().__init__(
            cfg, kernel, params["embed"].astype(
                cfg.activation_dtype())[tokens[0]],
            self.start + offs, offs < self.length,
            jax.lax.dynamic_index_in_dim(cache["tail"], self.block, 1, False))

    def conv(self, n, lp, tail):
        with jax.named_scope("short_conv_chunk"):
            return conv_chunk(n, lp, tail, self.cfg, self.first, self.length)

    def write(self, pool, layer, rows):
        return write_chunk(pool, layer, rows, self.pages, self.start,
                           self.length)

    def attend(self, q, k_pool, v_pool, layer):
        return da.gqa_attention(
            self.kernel, q[None], k_pool, v_pool, self.pages[None],
            self.start.reshape(1), layer=layer, impl=self.cfg.attn_impl)[0]

    def close(self, params, cache):
        """-> (the last live position's final-normed row [1, D], what the
        chunk counted: `COUNTS`' first four)."""
        c, n_conv = self.x.shape[0], self.cfg.n_conv
        x = rms_norm(self.x, params["final_norm_scale"], self.cfg.eps)
        last = jnp.take_along_axis(x, (self.length - 1)[None, None], axis=0)
        rows = self.attention_rows()
        if self.kept:
            cache["tail"] = jax.lax.dynamic_update_slice_in_dim(
                cache["tail"], jnp.stack(self.kept)[:, None], self.block, 1)
        return last, [self.length * n_conv, (c - self.length) * n_conv,
                      self.first, rows]


class _StepRows(_Rows):
    """One decode position for every slot: tokens [B] at positions pos
    [B]; `tables[:, 0]` each row's state block, the rest its pages. Idle
    rows name the trash blocks of both kinds, rewrite them (one scatter a
    step: they all rewrite block 0) and count nothing."""

    def __init__(self, params, tokens, cache, pos, tables, cfg, kernel: str):
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        self.blocks, self.pages = tables[:, 0], tables[:, 1:]
        self.widx = row_index(self.pages, pos, cache["k"])
        super().__init__(
            cfg, kernel, params["embed"].astype(
                cfg.activation_dtype())[tokens],
            pos, self.blocks > 0,
            cache["tail"][:, self.blocks])      # [L_conv, B, K - 1, D]

    def conv(self, n, lp, tails):
        with jax.named_scope("short_conv_step"):
            return conv_step(n, lp, tails, self.cfg)

    def write(self, pool, layer, rows):
        return write_rows(pool, layer, rows, self.widx)

    def attend(self, q, k_pool, v_pool, layer):
        return da.gqa_attention(
            self.kernel, q[:, None], k_pool, v_pool, self.pages,
            self.positions, layer=layer, impl=self.cfg.attn_impl)[:, 0]

    def close(self, params, cache):
        """-> (every row final-normed [B, D], `COUNTS`' first four)."""
        b, n_conv = self.x.shape[0], self.cfg.n_conv
        x = rms_norm(self.x, params["final_norm_scale"], self.cfg.eps)
        n_live = jnp.sum(self.live, dtype=jnp.int32)
        rows = self.attention_rows()
        if self.kept:
            cache["tail"] = cache["tail"].at[:, self.blocks].set(
                jnp.stack(self.kept))
        return x, [n_live * n_conv, (b - n_live) * n_conv, jnp.int32(0),
                   rows]


def _layers(params, cache, cfg, sets, kernel):
    """The layer loop of every program, over one set of rows or two: a
    layer's mixer on each set as its own (different sequences: they write
    disjoint blocks and pages of `cache`, a dict updated in place), its
    feed-forward half once on all the rows laid one after the other,
    through the expert kernel named `kernel`, so that two sets share one
    read of the layer's weights. -> the sparse layers' counts."""
    cuts = list(itertools.accumulate(s.x.shape[0] for s in sets))[:-1]
    n_conv = n_attn = 0
    expert_counts = []
    for lp, (mixer, ffn) in zip(params["layers"], cfg.kinds):
        with jax.named_scope(MIXER):
            for s in sets:
                s.mix(lp, mixer, cache, n_conv, n_attn)
            n_conv += mixer == "conv"
            n_attn += mixer != "conv"
        with jax.named_scope(FFN):
            x = jnp.concatenate([s.x for s in sets])
            ff, counts = _ffn(x, lp, ffn, cfg,
                              jnp.concatenate([s.live for s in sets]), kernel)
            if counts is not None:
                expert_counts.append(counts)
            for s, part in zip(sets, jnp.split(x + ff, cuts)):
                s.x = part
    return expert_counts


def prefill(params, tokens, cache, cfg: ShortConvMoEConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of one sequence (`gpt.prefill_paged`'s contract): tokens
    [1, C] at positions start .. start + length - 1; `block_table[0]` the
    sequence's state block, the rest its pages. A chunk that starts the
    sequence reads its tails as zeros. -> (logits [1, V] f32 of the
    chunk's last real position, cache, counts)."""
    cache = dict(cache)
    with jax.named_scope(EMBED):
        chunk = _ChunkRows(params, tokens, cache, cfg, block_table, start,
                           length, da.GQA_FULL_CHUNK)
    expert_counts = _layers(params, cache, cfg, [chunk],
                            grouped_experts.EXPERTS_GROUPED_PREFILL)
    with jax.named_scope(HEAD):
        last, head = chunk.close(params, cache)
        return (unembed(last, params["embed"], cfg.activation_dtype()),
                cache, _counts(cfg, head, expert_counts))


def decode(params, tokens, cache, pos, tables, cfg: ShortConvMoEConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B]; `tables[:, 0]` each row's state
    block, the rest its pages. Idle rows name the trash blocks of both
    kinds, rewrite them and count nothing.
    -> (logits [B, V] f32, cache, counts)."""
    cache = dict(cache)
    with jax.named_scope(EMBED):
        step = _StepRows(params, tokens, cache, pos, tables, cfg,
                         da.GQA_FULL_DECODE)
    expert_counts = _layers(params, cache, cfg, [step],
                            grouped_experts.EXPERTS_GROUPED)
    with jax.named_scope(HEAD):
        x, head = step.close(params, cache)
        return (unembed(x, params["embed"], cfg.activation_dtype()), cache,
                _counts(cfg, head, expert_counts))


def tick(params, chunk_tokens, step_tokens, cache, pos, tables,
         cfg: ShortConvMoEConfig, mesh=None, *, block_table, start, length):
    """`ServingFamily.tick`: `decode`'s step and `prefill`'s chunk of
    another sequence as one program, which reads every weight once: the
    mixers as the two programs run them, each dense MLP, each sparse
    layer's experts and the tied head once over the step's B rows and the
    chunk's. The kernels run under names of their own
    (`gqa_full_decode_tick`, `gqa_full_chunk_tick`, `experts_grouped_tick`).
    -> (the chunk's logits [1, V] f32, the step's [B, V], cache, counts:
    the two programs' summed, but the row tiles and the experts reached,
    which are this program's one call's a layer)."""
    cache = dict(cache)
    with jax.named_scope(EMBED):
        step = _StepRows(params, step_tokens, cache, pos, tables, cfg,
                         da.GQA_FULL_DECODE_TICK)
        chunk = _ChunkRows(params, chunk_tokens, cache, cfg, block_table,
                           start, length, da.GQA_FULL_CHUNK_TICK)
    expert_counts = _layers(params, cache, cfg, [step, chunk],
                            grouped_experts.EXPERTS_GROUPED_TICK)
    with jax.named_scope(HEAD):
        rows, step_head = step.close(params, cache)
        last, chunk_head = chunk.close(params, cache)
        logits = unembed(jnp.concatenate([rows, last]), params["embed"],
                         cfg.activation_dtype())
        b = rows.shape[0]
        return (logits[b:], logits[:b], cache,
                _counts(cfg, [a + c for a, c in zip(step_head, chunk_head)],
                        expert_counts))


def _stats(cfg, totals) -> dict:
    """`ServingFamily.counts`: `COUNTS` by name, the loads' largest over
    their mean, and the row tiles an expert reached."""
    out = summarize(COUNTS, totals, cfg.held_count)
    out["row_tiles_per_expert_reached"] = (
        out["expert_row_tiles"] / max(out["experts_reached"], 1))
    return out


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode, tick=tick,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, load=load, state_blocks=1,
    state_keys=STATE_KEYS,
    counts=_stats)
