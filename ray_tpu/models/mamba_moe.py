"""A decoder whose every layer is a mixer or a feed-forward part alone:
Mamba-2 state-space layers, LatentMoE expert layers and grouped-head
attention without positions, in the order a pattern string gives. The
layer that `NVIDIA-Nemotron-3-Super-120B-A12B-BF16` names (`nemotron_h`):
published layer `l` is what `hybrid_override_pattern[l]` says, `M` a
state layer, `E` an expert layer, `*` an attention layer (40 : 40 : 8 of
88). Every layer is `y = x + f(RMSNorm(x))` with one learned scale.

`M`, the state layer (`n = RMSNorm(x)`; H heads of P channels, G groups,
state size N, K convolution taps):

    [z | xBC | dt] = W_in n          (H P | H P + 2 G N | H; no bias)
    xBC_t <- SiLU(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j})   depthwise, causal
    xBC -> x_t [H, P], B_t, C_t [G, N]; head h reads group h // (H / G)
    d_t = softplus(dt_t + dt_bias);  a_t = exp(d_t A_h),  A_h = -exp(A_log_h)
    S_t = a_t S_{t-1} + d_t x_t B_t^T;   y_t = S_t C_t + D_h x_t
    f = W_out RMSNorm_groups(y * SiLU(z))   the gate first, then the mean
        square over each of G groups of H P / G channels, one scale

`ops/mamba2.py` has the recurrence's two kernels. `*`, the attention
layer: q = W_q n (Hq heads of d), k, v (Hkv heads), query head `h` reads
key-value head `h // (Hq / Hkv)`, causal, scale d^-1/2, no positional
term, `f = W_o concat(heads)` (`ops/decode_attention.py`'s `gqa_full_*`).
`E`, the expert layer: `s = sigmoid(W_r n)` over the router's published
width, float32, on the full hidden; the k largest of `s + b` chosen,
weights `scale x s_e / sum of the chosen s` (`blocks.routing`, one
group); `u = W_down n` into the latent; the held experts' part `r =
sum_e w_e W2_e relu(W1_e u)^2` there (`ops/grouped_experts.py`'s ungated
form); `routed = W_up r`; `shared = W2_s relu(W1_s n)^2` on the full
hidden; `f = routed + shared`. What the absent experts would add is left
out. This module is new and `models/linear_latent.py` is not widened:
that family's layer is a mixer and a feed-forward part both, under two
norms; nothing but the pool's two kinds would be shared.

**What the engine holds for this family**: one request, two kinds of
block (`ServingFamily.state_blocks` 1 and `paged`), as
`models/linear_latent.py`. Column 0 of its table names a state block:
`"state" [L_m, blocks, H / 2, N, 2 P]` float32 (`ops/mamba2.py`'s
layout), `"conv" [L_m, blocks, K - 1, H P + 2 G N]`, the last
pre-convolution `xBC` (float32 bytes of activation values), `"ring"`, the
decode tokens that are not in the state yet (`mamba2.ring_array`: read
by every token, folded into the state when it is full) and
`"held" [1, blocks]` int32, the entries a block's rings hold: one count a
block, since a sequence's state layers advance together, kept on the
device because decode steps are chained. The columns after it name pages
of `"k"`, `"v"` `[L_a, pages, Hkv, block_size, d]`, head-major as
`models/window_moe.py`'s. Prefill resets the state block on a sequence's
first chunk (`start == 0`) and leaves its rings empty, a chunk bucket's
padding leaves state, tail and pages bit for bit, and decode's idle rows
(table all 0) rewrite the trash blocks' tails and pages and move nothing
of the trash state or its rings.

Parameters: the tree `benchmarks/refs/mamba_moe.py` documents.
`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (Experts, copy_block, expert_totals,
                                   gather_block, mm, rms_norm, routing,
                                   row_index, scatter_block, summarize,
                                   unembed, write_chunk, write_rows)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import grouped_experts, mamba2

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow
COUNTS = ("mamba_tokens_live", "mamba_tokens_padded", "state_resets",
          "attention_rows_read", "state_folds", "expert_tokens_here",
          "expert_tokens_routed")
# the pool's arrays of state blocks
STATE_KEYS = ("state", "conv", "ring", "held")
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}
EMBED_INIT = 1.0


@dataclass(frozen=True)
class MambaMoEConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 3
    # one character a published layer (`KINDS`); the layers that run are
    # [first_layer, first_layer + n_layers)
    pattern: str = "ME*"
    first_layer: int = 0
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    state_size: int = 16
    conv_size: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    latent_dim: int = 32
    expert_ff: int = 48
    shared_ff: int = 96
    router_width: int = 8
    experts_per_token: int = 3
    held_from: int = 0
    held_count: int = 8
    routed_scale: float = 1.0
    norm_topk: bool = True
    eps: float = 1e-5
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    mamba_impl: str = "auto"         # auto | pallas | jax (both ops)
    attn_impl: str = "auto"          # auto | pallas | jax (gqa_full_*)
    sparse_impl: str = "auto"        # auto | pallas | jax (the experts)
    # test-only, for the benchmark's control: "bfloat16" rounds the
    # recurrence's state to bfloat16 at every write and keeps float32 bytes
    state_round: str = "none"        # none | bfloat16

    def __post_init__(self):
        if set(self.pattern) - set(KINDS) \
                or len(self.pattern) < self.first_layer + self.n_layers:
            raise ValueError(f"a layer is one of {sorted(KINDS)}, and the "
                             f"pattern names every layer that runs")
        if self.mamba_heads % (mamba2.tile_heads(self.mamba_head_dim)
                               * self.n_groups) \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("a group's state heads lie in pairs, and the "
                             "query heads divide over the key-value heads")
        if self.state_round not in ("none", "bfloat16"):
            raise ValueError(f"unknown state_round {self.state_round!r}")

    @property
    def kinds(self) -> tuple:
        """"mamba", "attention" or "experts", one a layer that runs."""
        lo = self.first_layer
        return tuple(KINDS[c] for c in self.pattern[lo:lo + self.n_layers])

    @property
    def inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts(self) -> Experts:
        return Experts(self.router_width, self.experts_per_token,
                       self.norm_topk, self.held_from,
                       routed_scale=self.routed_scale, impl=self.sparse_impl)

    @property
    def family(self):
        return FAMILY


def from_published(*, hidden_size, num_hidden_layers, hybrid_override_pattern,
                   mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                   conv_kernel, num_attention_heads, num_key_value_heads,
                   head_dim, moe_latent_size, moe_intermediate_size,
                   moe_shared_expert_intermediate_size, n_routed_experts,
                   num_experts_per_tok, routed_scaling_factor, norm_topk_prob,
                   layer_norm_epsilon, max_position_embeddings, layers_from=0,
                   experts_held_from=0, published=None,
                   **same) -> MambaMoEConfig:
    """The configuration file's published keys -> `MambaMoEConfig`
    (`benchmarks/configs/nemotron-3-super.json`, `program.constructor`).
    `n_routed_experts` is how many experts are held here; the router's
    width is `published["n_routed_experts"]` where a share is run."""
    return MambaMoEConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        pattern=hybrid_override_pattern, first_layer=layers_from,
        mamba_heads=mamba_num_heads, mamba_head_dim=mamba_head_dim,
        n_groups=n_groups, state_size=ssm_state_size, conv_size=conv_kernel,
        n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
        head_dim=head_dim, latent_dim=moe_latent_size,
        expert_ff=moe_intermediate_size,
        shared_ff=moe_shared_expert_intermediate_size,
        router_width=(published or {}).get("n_routed_experts",
                                           n_routed_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=n_routed_experts,
        routed_scale=float(routed_scaling_factor), norm_topk=norm_topk_prob,
        eps=layer_norm_epsilon, max_seq_len=max_position_embeddings, **same)


def init_params(key, cfg: MambaMoEConfig):
    """Float32 leaves, for tests; the tree `benchmarks/refs/mamba_moe.py`
    documents. A head's step and decay spread over the heads so that one
    remembers a few positions and another some thousands; the embedding
    at `EMBED_INIT`, so that a token's own vector and not its context's
    mean decides its experts."""
    d, inner = cfg.d_model, cfg.inner
    h, ch = cfg.mamba_heads, cfg.conv_channels
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lat, f, fs = cfg.latent_dim, cfg.expert_ff, cfg.shared_ff
    residual = float(cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for kind in cfg.kinds:
        lp = {"norm_scale": ones(d)}
        if kind == "mamba":
            step = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), h))
            lp.update(
                w_in=normal((d, inner + ch + h), d ** -0.5),
                conv_w=normal((cfg.conv_size, ch), cfg.conv_size ** -0.5),
                conv_b=normal((ch,), 0.1),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                a_log=jnp.log(jnp.linspace(1.0, 16.0, h)),
                d_skip=ones(h), gate_norm_scale=ones(inner),
                w_out=normal((inner, d), inner ** -0.5 * residual))
        elif kind == "attention":
            lp.update(
                w_q=normal((d, hq * hd), d ** -0.5),
                w_k=normal((d, hkv * hd), d ** -0.5),
                w_v=normal((d, hkv * hd), d ** -0.5),
                w_out=normal((hq * hd, d), (hq * hd) ** -0.5 * residual))
        else:
            lp.update(
                router=normal((d, cfg.router_width), d ** -0.5),
                router_bias=normal((cfg.router_width,), 0.01),
                latent_down=normal((d, lat), d ** -0.5),
                we_up=normal((cfg.held_count, f, lat), lat ** -0.5),
                we_down=normal((cfg.held_count, f, lat), f ** -0.5),
                latent_up=normal((lat, d), lat ** -0.5 * residual),
                ws_up=normal((d, fs), d ** -0.5),
                ws_down=normal((fs, d), fs ** -0.5 * residual))
        layers.append(lp)
    return {"embed": normal((cfg.vocab_size, d), EMBED_INIT),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_norm_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def init_pool(cfg: MambaMoEConfig, n_blocks: int, block_size: int,
              mesh=None, *, state_blocks: int):
    """{"state", "conv"} with `state_blocks` blocks on axis 1 and {"k",
    "v"} with `n_blocks` pages, zero-filled; block 0 of each the trash
    block."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    n_mamba = cfg.kinds.count("mamba")
    n_attn = cfg.kinds.count("attention")

    def pages():
        return jnp.zeros((n_attn, n_blocks, cfg.n_kv_heads, block_size,
                          cfg.head_dim), cfg.activation_dtype())

    return {**state_arrays(cfg, n_mamba, state_blocks),
            "k": pages(), "v": pages()}


def state_arrays(cfg, n_mamba: int, state_blocks: int) -> dict:
    """`STATE_KEYS`' arrays for `n_mamba` state layers, zero-filled: the
    states as `ops/mamba2.py` stores them, the convolution tails, the
    rings beside the states and how many entries a block's rings hold."""
    t = mamba2.tile_heads(cfg.mamba_head_dim)
    return {
        "state": jnp.zeros((n_mamba, state_blocks, cfg.mamba_heads // t,
                            cfg.state_size, t * cfg.mamba_head_dim),
                           jnp.float32),
        "conv": jnp.zeros((n_mamba, state_blocks, cfg.conv_size - 1,
                           cfg.conv_channels), jnp.float32),
        "ring": mamba2.ring_array(n_mamba, state_blocks, cfg.mamba_heads,
                                  cfg.n_groups, cfg.mamba_head_dim,
                                  cfg.state_size),
        "held": jnp.zeros((1, state_blocks), jnp.int32),
    }


# ---------------------------------------------------------------------------
# pieces of the layers
# ---------------------------------------------------------------------------

def _in_proj(n, lp, cfg, mup=None):
    """Normed n [N, D] -> (z [N, H P] in the activation type, xBC [N, H P
    + 2 G N] float32 values of the activation type, dt [N, H] float32).
    `mup`: a float32 factor a column of W_in's output, for a family whose
    source scales z, x, B, C and dt each by a number of its own."""
    adt = cfg.activation_dtype()
    inner, ch = cfg.inner, cfg.conv_channels
    w = lp["w_in"].astype(adt)
    if w.shape[1] % mamba2.LANES and n.shape[0] % mamba2.LANES == 0:
        # a chunk's rows are whole lane tiles and the width is not (H = 32
        # columns of dt after 9,216): the compiler then lays the product
        # out with the positions on the lanes, the convolution's tail
        # with it, and the whole pool of tails anew around every layer's
        # read and write of one block's (1.02 GB a copy at a tail of 3 x
        # 5,120: AOT for a v5e, PR 54). dt's columns in a product of
        # their own leave z | xBC whole tiles wide. The single product
        # below stays only so that the program of a width of whole tiles
        # (18,560, which holds no such copy) is the one it was
        proj = [jnp.einsum("nd,df->nf", n, part,
                           preferred_element_type=jnp.float32)
                for part in (w[:, :inner + ch], w[:, inner + ch:])]
        if mup is not None:
            proj = [proj[0] * mup[:inner + ch], proj[1] * mup[inner + ch:]]
        return (proj[0][:, :inner].astype(adt),
                proj[0][:, inner:].astype(adt).astype(jnp.float32), proj[1])
    proj = jnp.einsum("nd,df->nf", n, w, preferred_element_type=jnp.float32)
    if mup is not None:
        proj = proj * mup
    return (proj[:, :inner].astype(adt),
            proj[:, inner:inner + ch].astype(adt).astype(jnp.float32),
            proj[:, inner + ch:])


def _conv_act(conved, lp):
    """The taps' sum [N, channels] f32 -> SiLU(. + bias)."""
    return jax.nn.silu(conved + lp["conv_b"].astype(jnp.float32))


def _ssm_inputs(act, dt, lp, cfg):
    """The convolution's output [N, channels] f32 and the raw steps
    -> (x [N, H, P], B, C [N, G, N] in the activation type, d [N, H] f32
    > 0, A [H] f32 < 0)."""
    adt = cfg.activation_dtype()
    rows, inner = act.shape[0], cfg.inner
    g, ns = cfg.n_groups, cfg.state_size
    act = act.astype(adt)
    x = act[:, :inner].reshape(rows, cfg.mamba_heads, cfg.mamba_head_dim)
    b = act[:, inner:inner + g * ns].reshape(rows, g, ns)
    c = act[:, inner + g * ns:].reshape(rows, g, ns)
    step = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
    return x, b, c, step, -jnp.exp(lp["a_log"].astype(jnp.float32))


def _mamba_out(y, x, z, lp, cfg):
    """The recurrence's y [N, H, P] f32 with the skip, the gate, the
    grouped norm and W_out: -> [N, D]."""
    adt = cfg.activation_dtype()
    rows = y.shape[0]
    y = y + lp["d_skip"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(rows, -1) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(rows, cfg.n_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.eps)
    out = grouped.reshape(rows, -1) * lp["gate_norm_scale"].astype(
        jnp.float32)
    return mm(out.astype(adt), lp["w_out"], adt)


def mamba_whole(n, lp, cfg, mup=None):
    """The state layer of normed n [T, D] of one whole sequence by the
    definition, through W_out: -> [T, D]."""
    t, taps = n.shape[0], cfg.conv_size
    z, xbc, dt = _in_proj(n, lp, cfg, mup)
    pre = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(jnp.float32)
    act = _conv_act(sum(w[i] * pre[i:i + t] for i in range(taps)), lp)
    xs, b, c, step, a = _ssm_inputs(act, dt, lp, cfg)
    y = mamba2.mamba2_recurrent(xs, step, a, b, c)[0]
    return _mamba_out(y, xs, z, lp, cfg)


def mamba_chunk(n, lp, cache, cfg, layer, block, first, length, mup=None):
    """The state layer of a prompt chunk's normed n [C, D] against state
    block `block` of state layer `layer`: `cache`'s "conv" and "state"
    are replaced; a first chunk reads both as zeros, and the tail kept
    is the last live positions' xBC, whatever the padding.
    -> what W_out gives [C, D]."""
    c, taps = n.shape[0], cfg.conv_size
    z, xbc, dt = _in_proj(n, lp, cfg, mup)
    tail = jnp.where(first, 0.0, cache["conv"][layer, block])
    pre = jnp.concatenate([tail, xbc])
    w = lp["conv_w"].astype(jnp.float32)
    act = _conv_act(sum(w[i] * pre[i:i + c] for i in range(taps)), lp)
    cache["conv"] = cache["conv"].at[layer, block].set(
        jax.lax.dynamic_slice_in_dim(pre, length, taps - 1))
    xs, b, cc, step, a = _ssm_inputs(act, dt, lp, cfg)
    y, cache["state"] = mamba2.mamba2_chunk(
        xs, step, a, b, cc, cache["state"], layer, block, first, length,
        state_round=cfg.state_round, impl=cfg.mamba_impl)
    return _mamba_out(y, xs, z, lp, cfg)


def rings_emptied(cache, block):
    """`cache`'s "held" after a prefill chunk: the chunk leaves its
    block's rings empty, whoever held the block before. (A chunk after
    decode steps of the same sequence does not occur, so nothing waits in
    them: a preempted stream prefills again from its first token.)"""
    return cache["held"].at[0, block].set(0)


def rings_stepped(cache, blocks, cfg):
    """What a decode step does to its rows' rings (`mamba2.ring_after`):
    -> (held [B] before the step, for every state layer's `mamba_step`;
    `cache`'s "held" after it, written once, after the last state layer:
    idle rows all name block 0 and all leave its count as it was; how
    many rows fold)."""
    held = cache["held"][0, blocks]
    fold, after = mamba2.ring_after(blocks, held, cfg.state_round)
    return (held, cache["held"].at[0, blocks].set(after),
            jnp.sum(fold, dtype=jnp.int32))


def mamba_step(n, lp, cache, cfg, layer, blocks, held, mup=None):
    """The state layer of one decode position a row, normed n [B, D], each
    against its own state block, whose rings hold `held` [B] entries:
    `cache`'s "conv", "state" and "ring" are replaced (a live row's token
    goes into its ring, and the ring into the state when it is full).
    -> what W_out gives [B, D]."""
    z, xbc, dt = _in_proj(n, lp, cfg, mup)
    pre = jnp.concatenate([cache["conv"][layer, blocks], xbc[:, None]], 1)
    act = _conv_act(jnp.einsum(
        "kc,bkc->bc", lp["conv_w"].astype(jnp.float32), pre), lp)
    cache["conv"] = cache["conv"].at[layer, blocks].set(pre[:, 1:])
    xs, bb, cc, step, a = _ssm_inputs(act, dt, lp, cfg)
    y, cache["state"], cache["ring"] = mamba2.mamba2_step(
        xs, step, a, bb, cc, cache["state"], cache["ring"], layer, blocks,
        held, state_round=cfg.state_round, impl=cfg.mamba_impl)
    return _mamba_out(y, xs, z, lp, cfg)


def _qkv(n, lp, cfg):
    """Normed n [N, D] -> q [N, Hq, d], k, v [N, Hkv, d]; no positional
    term."""
    adt = cfg.activation_dtype()
    rows = n.shape[0]
    return (mm(n, lp["w_q"], adt).reshape(rows, cfg.n_heads, cfg.head_dim),
            mm(n, lp["w_k"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim),
            mm(n, lp["w_v"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim))


def _relu2_mlp(n, w_up, w_down, adt):
    up = jnp.einsum("nd,df->nf", n, w_up.astype(adt),
                    preferred_element_type=jnp.float32)
    return mm(jnp.square(jax.nn.relu(up)).astype(adt), w_down, adt)


def _part(kind: str) -> str:
    """A layer is a mixer or a feed-forward part alone."""
    return MIXER if kind in ("mamba", "attention") else FFN


def _experts(n, lp, cfg, live, kernel):
    """-> (what the held experts, through the latent, and the shared one
    add [N, D]; counts i32: pairs routed here, pairs routed anywhere,
    then the pairs each held expert got; rows where `live` is false count
    nothing)."""
    adt = cfg.activation_dtype()
    chosen, weights = routing(n, lp, cfg.experts)
    if live is not None:
        chosen = jnp.where(live[:, None], chosen, -1)
    with jax.named_scope("latent_projections"):
        u = mm(n, lp["latent_down"], adt)
    with jax.named_scope("routed_experts"):
        r, load = grouped_experts.experts_grouped(
            u, chosen, weights, None, lp["we_up"], lp["we_down"],
            held_from=cfg.held_from, impl=cfg.sparse_impl, name=kernel)
    with jax.named_scope("latent_projections"):
        routed = mm(r.astype(adt), lp["latent_up"], adt)
    with jax.named_scope("shared_experts"):
        shared = _relu2_mlp(n, lp["ws_up"], lp["ws_down"], adt)
    counts = jnp.concatenate([
        jnp.stack([jnp.sum(load), jnp.sum(chosen >= 0, dtype=jnp.int32)]),
        load])
    return routed + shared, counts


def _counts(cfg, head, expert_counts):
    """`COUNTS`' first five, then the experts' two and their loads."""
    experts = expert_totals(expert_counts, 2 + cfg.held_count)
    return jnp.concatenate([jnp.stack(head).astype(jnp.int32),
                            experts.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: MambaMoEConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: the
    recurrence token by token, no state kept, no cache."""
    adt = cfg.activation_dtype()

    def one(seq):
        t = seq.shape[0]
        live = jnp.ones((t,), bool)
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp, kind in zip(params["layers"], cfg.kinds):
            with jax.named_scope(_part(kind)):
                n = rms_norm(x, lp["norm_scale"], cfg.eps)
                if kind == "mamba":
                    x = x + mamba_whole(n, lp, cfg)
                elif kind == "attention":
                    q, k, v = _qkv(n, lp, cfg)
                    att = da.reference_gqa_attention(
                        q[None], k[None], v[None],
                        jnp.zeros((1,), jnp.int32))[0]
                    x = x + mm(att.reshape(t, -1), lp["w_out"], adt)
                else:
                    x = x + _experts(n, lp, cfg, live,
                                     grouped_experts.EXPERTS_GROUPED)[0]
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_norm_scale"], cfg.eps),
                           params["head"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def prefill(params, tokens, cache, cfg: MambaMoEConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of one sequence (`gpt.prefill_paged`'s contract): tokens
    [1, C] at positions start .. start + length - 1; `block_table[0]` the
    sequence's state block, the rest its pages. A chunk that starts the
    sequence resets state and tail. -> (logits [1, V] f32 of the chunk's
    last real position, cache, counts)."""
    c = tokens.shape[1]
    if tokens.shape[0] != 1:
        raise ValueError(f"prefill wants tokens [1, C], got batch "
                         f"{tokens.shape[0]}")
    adt = cfg.activation_dtype()
    cache = dict(cache)
    with jax.named_scope(EMBED):
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(c if length is None else length, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        block, pages = table[0], table[1:]
        first = start == 0
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        x = params["embed"].astype(adt)[tokens[0]]
    n_mamba = n_attn = 0
    expert_counts = []
    for lp, kind in zip(params["layers"], cfg.kinds):
        with jax.named_scope(_part(kind)):
            n = rms_norm(x, lp["norm_scale"], cfg.eps)
            if kind == "mamba":
                with jax.named_scope("mamba_layer"):
                    x = x + mamba_chunk(n, lp, cache, cfg, n_mamba, block,
                                        first, length)
                n_mamba += 1
            elif kind == "attention":
                with jax.named_scope("attention_layer"):
                    q, k, v = _qkv(n, lp, cfg)
                    cache["k"] = write_chunk(
                        cache["k"], n_attn, k, pages, start, length)
                    cache["v"] = write_chunk(
                        cache["v"], n_attn, v, pages, start, length)
                    att = da.gqa_chunk_attention(
                        q, cache["k"], cache["v"], pages, start, layer=n_attn,
                        impl=cfg.attn_impl)
                    x = x + mm(att.reshape(c, -1), lp["w_out"], adt)
                n_attn += 1
            else:
                ff, counts = _experts(n, lp, cfg, valid,
                                      grouped_experts.EXPERTS_GROUPED_PREFILL)
                expert_counts.append(counts)
                x = x + ff
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        rows = jnp.sum(jnp.where(valid, positions + 1, 0)) * n_attn
        cache["held"] = rings_emptied(cache, block)
        return (unembed(last, params["head"], adt), cache,
                _counts(cfg, [length * n_mamba, (c - length) * n_mamba, first,
                              rows, jnp.int32(0)], expert_counts))


def decode(params, tokens, cache, pos, tables, cfg: MambaMoEConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B]; `tables[:, 0]` each row's state
    block, the rest its pages. Idle rows name the trash blocks of both
    kinds, rewrite their tails and pages and count nothing.
    -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    cache = dict(cache)
    b = tokens.shape[0]
    with jax.named_scope(EMBED):
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        blocks, pages = tables[:, 0], tables[:, 1:]
        live = blocks > 0
        held, held_after, folds = rings_stepped(cache, blocks, cfg)
        widx = row_index(pages, pos, cache["k"])
        x = params["embed"].astype(adt)[tokens]
    n_mamba = n_attn = 0
    expert_counts = []
    for lp, kind in zip(params["layers"], cfg.kinds):
        with jax.named_scope(_part(kind)):
            n = rms_norm(x, lp["norm_scale"], cfg.eps)
            if kind == "mamba":
                with jax.named_scope("mamba_layer"):
                    x = x + mamba_step(n, lp, cache, cfg, n_mamba, blocks,
                                       held)
                n_mamba += 1
            elif kind == "attention":
                with jax.named_scope("attention_layer"):
                    q, k, v = _qkv(n, lp, cfg)
                    cache["k"] = write_rows(cache["k"], n_attn, k, widx)
                    cache["v"] = write_rows(cache["v"], n_attn, v, widx)
                    att = da.gqa_decode_attention(
                        q, cache["k"], cache["v"], pages, pos, layer=n_attn,
                        impl=cfg.attn_impl)
                    x = x + mm(att.reshape(b, -1), lp["w_out"], adt)
                n_attn += 1
            else:
                ff, counts = _experts(n, lp, cfg, live,
                                      grouped_experts.EXPERTS_GROUPED)
                expert_counts.append(counts)
                x = x + ff
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        n_live = jnp.sum(live, dtype=jnp.int32)
        rows = jnp.sum(jnp.where(live, pos + 1, 0)) * n_attn
        cache["held"] = held_after
        return (unembed(x, params["head"], adt), cache,
                _counts(cfg, [n_live * n_mamba, (b - n_live) * n_mamba,
                              jnp.int32(0), rows, folds], expert_counts))


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, state_blocks=1, state_keys=STATE_KEYS,
    counts=lambda cfg, totals: summarize(COUNTS, totals, cfg.held_count))
