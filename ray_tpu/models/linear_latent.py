"""A decoder that mixes two kinds of sequence layer: Kimi Delta Attention
(KDA, a delta-rule linear attention over a state of fixed size) and
latent attention over a cache that grows, with the feed-forward layers
of `models/latent_sparse_moe.py` (a dense layer, then routed experts
chosen by groups plus a shared expert). The layer that
`Ling-3.0-flash-VL`'s language model names: published layer `l` is a
latent layer where `(l + 1) % layer_group_size == 0` and a KDA layer
otherwise (5 : 1).

The KDA layer (`n = RMSNorm(x)`; H heads of d, keys and values alike):

    q~, k~, v~ = W_q n, W_k n, W_v n
    q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
              (depthwise causal convolution over time, `conv_size` taps)
    q^h <- q^h / |q^h| x d^-1/2,   k^h <- k^h / |k^h|
    g_t = gate_floor x sigmoid(exp(A^h) x (W_f n_t + b_f))   a channel
    beta_t^h = sigmoid(w_beta^h . n_t)
    S_t^h = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1}^h + beta_t k_t v_t^T
    o_t^h = (S_t^h)^T q_t
    y_t = W_o concat_h( RMSNorm_head(o_t^h) x sigmoid(w_og^h . n_t) )

`ops/kda.py` has the recurrence's two kernels. The latent layer is
`latent_sparse_moe`'s without indexer or query bottleneck (`project`, the
row format, `decode_attend` over every cached row, `prefill_attend`),
each head's output times the same head-wise gate `sigmoid(w_og^h . n)`.
No rotary in a KDA layer: the decay carries position.

**What the engine holds for this family**: one request, two kinds of
block (`ServingFamily.state_blocks` 1 and `paged`). Column 0 of its table
names a state block: `"state" [L_kda, blocks, H, d, d]` float32,
`"conv" [L_kda, blocks, conv_size - 1, 3 H d]`, the last pre-convolution
projections (float32 bytes of activation values), and the ring beside the
state: `"ring"`, the decode tokens that are not in the state yet
(`kda.ring_array`: read every step, folded into the state when a block's
ring is full, so that a decode step reads a state once and writes it once
a ring), and `"held" [1, blocks]` int32, the entries a block's rings
hold: one count a block, since all layers step together. The columns
after it name pages of `"latent" [L_latent, pages, block_size, 1,
words]`, one row a token. Prefill resets the state block on a sequence's
first chunk (`start == 0`) and leaves its rings empty, a chunk bucket's
padding leaves state and tail bit for bit, and decode's idle rows (table
all 0) rewrite the trash blocks' tails and pages and move nothing of the
trash state or its rings.

Parameters: the tree `benchmarks/refs/linear_latent.py` documents.
`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.models.blocks import (copy_block, expert_totals, gather_block,
                                   kept_groups, mm, rms_norm, router_scores,
                                   scatter_block, summarize, unembed)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import grouped_experts, kda

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow
COUNTS = ("kda_tokens_live", "kda_tokens_padded", "state_resets",
          "latent_rows_read", "state_folds", "expert_tokens_here",
          "expert_tokens_routed", "expert_groups_kept_here")
# the pool's arrays of state blocks
STATE_KEYS = ("state", "conv", "ring", "held")
NORM_EPS = 1e-6                     # of a head's q and k


@dataclass(frozen=True)
class LinearLatentConfig(lsm.LatentSparseMoEConfig):
    # the latent layers have neither indexer nor query bottleneck
    q_rank: int | None = None
    index_topk: int | None = None
    indexer_types: tuple = ("none", "none", "none")
    # one entry a published layer, beside `mlp_types`
    mixer_types: tuple = ("kda", "kda", "latent")
    kda_head_dim: int = 16
    conv_size: int = 4
    gate_floor: float = -5.0
    # test-only, for the benchmark's control: "bfloat16" rounds the KDA
    # state to bfloat16 at every write and keeps float32 bytes
    state_round: str = "none"       # none | bfloat16
    kda_impl: str = "auto"          # auto | pallas | jax (both ops)

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if self.has_indexer or len(self.mixer_types) != len(self.mlp_types) \
                or set(self.mixer_types) - {"kda", "latent"}:
            raise ValueError("one mixer a layer, kda or latent, and no "
                             "indexer")
        if self.state_round not in ("none", "bfloat16"):
            raise ValueError(f"unknown state_round {self.state_round!r}")

    @property
    def mixers(self) -> tuple:
        lo = self.first_layer
        return self.mixer_types[lo:lo + self.n_layers]

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_heads * self.kda_head_dim

    @property
    def family(self):
        return FAMILY

    training = None     # `latent_sparse_moe`'s trainer is not this family's


def from_published(*, hidden_size, num_hidden_layers, num_attention_heads,
                   head_dim, kv_lora_rank, qk_nope_head_dim,
                   qk_rope_head_dim, v_head_dim, intermediate_size,
                   moe_intermediate_size, num_experts, num_experts_per_tok,
                   routed_scaling_factor, norm_topk_prob, n_group,
                   topk_group, rms_norm_eps, max_position_embeddings,
                   first_k_dense_replace, layer_group_size,
                   short_conv_kernel_size, kda_lower_bound, layers_from=0,
                   experts_held_from=0, published=None,
                   **same) -> LinearLatentConfig:
    """The configuration file's published keys -> `LinearLatentConfig`
    (`benchmarks/configs/ling-3.0-flash-vl.json`, `program.constructor`).
    `num_experts` is how many experts are held here; the router's width
    is `published["num_experts"]` where a share is run."""
    published = published or {}
    n = layers_from + num_hidden_layers
    dense = layers_from + first_k_dense_replace
    return LinearLatentConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        n_heads=num_attention_heads, kda_head_dim=head_dim,
        kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
        rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
        indexer_types=["none"] * n,
        mixer_types=["latent" if (i + 1) % layer_group_size == 0 else "kda"
                     for i in range(n)],
        mlp_types=["dense" if i < dense else "sparse" for i in range(n)],
        first_layer=layers_from, d_ff=intermediate_size,
        expert_ff=moe_intermediate_size,
        router_width=published.get("num_experts", num_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=num_experts, routed_scale=routed_scaling_factor,
        norm_topk=norm_topk_prob, n_group=n_group, topk_group=topk_group,
        eps=rms_norm_eps, max_seq_len=max_position_embeddings,
        conv_size=short_conv_kernel_size, gate_floor=float(kda_lower_bound),
        **same)


def init_params(key, cfg: LinearLatentConfig):
    """Float32 leaves, for tests; the tree `benchmarks/refs/
    linear_latent.py` documents. Gate biases spread over a head's
    channels so that a channel remembers from about ten to a few thousand
    positions; the embedding at `latent_sparse_moe.EMBED_INIT`, so that
    a token's own vector and not its context's mean decides its experts."""
    d, nh, hd = cfg.d_model, cfg.n_heads, cfg.kda_head_dim
    qk = cfg.nope_dim + cfg.rope_dim
    fs = cfg.expert_ff * cfg.shared_experts
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 20 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for (mlp, _), mixer in zip(cfg.kinds, cfg.mixers):
        lp = {"attn_norm_scale": ones(d), "ffn_norm_scale": ones(d),
              "w_og": normal((d, nh), d ** -0.5)}
        if mixer == "kda":
            lp.update(
                w_q=normal((d, nh * hd), d ** -0.5),
                w_k=normal((d, nh * hd), d ** -0.5),
                w_v=normal((d, nh * hd), d ** -0.5),
                conv_q=normal((cfg.conv_size, nh * hd), cfg.conv_size ** -0.5),
                conv_k=normal((cfg.conv_size, nh * hd), cfg.conv_size ** -0.5),
                conv_v=normal((cfg.conv_size, nh * hd), cfg.conv_size ** -0.5),
                a_log=jnp.linspace(-0.2, 0.2, nh),
                w_f=normal((d, nh * hd), d ** -0.5),
                b_f=jnp.tile(jnp.linspace(-4.0, -9.5, hd), nh),
                w_beta=normal((d, nh), d ** -0.5),
                o_norm_scale=ones(hd),
                w_out=normal((nh * hd, d), (nh * hd) ** -0.5 * residual))
        else:
            lp.update(
                w_q=normal((d, nh * qk), d ** -0.5),
                wkv_a=normal((d, cfg.kv_rank + cfg.rope_dim), d ** -0.5),
                kv_norm_scale=ones(cfg.kv_rank),
                wkv_b=normal((cfg.kv_rank, nh * (cfg.nope_dim + cfg.v_dim)),
                             cfg.kv_rank ** -0.5),
                w_out=normal((nh * cfg.v_dim, d),
                             (nh * cfg.v_dim) ** -0.5 * residual))
        if mlp == "dense":
            lp.update(w_gate=normal((d, cfg.d_ff), d ** -0.5),
                      w_up=normal((d, cfg.d_ff), d ** -0.5),
                      w_down=normal((cfg.d_ff, d),
                                    cfg.d_ff ** -0.5 * residual))
        else:
            experts = (cfg.held_count, cfg.expert_ff, d)
            lp.update(
                router=normal((d, cfg.router_width), d ** -0.5),
                router_bias=normal((cfg.router_width,), 0.01),
                we_gate=normal(experts, d ** -0.5),
                we_up=normal(experts, d ** -0.5),
                we_down=normal(experts, cfg.expert_ff ** -0.5 * residual),
                ws_gate=normal((d, fs), d ** -0.5),
                ws_up=normal((d, fs), d ** -0.5),
                ws_down=normal((fs, d), fs ** -0.5 * residual))
        layers.append(lp)
    return {"embed": normal((cfg.vocab_size, d), lsm.EMBED_INIT),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def init_pool(cfg: LinearLatentConfig, n_blocks: int, block_size: int,
              mesh=None, *, state_blocks: int):
    """`STATE_KEYS`' arrays with `state_blocks` blocks on axis 1 (the
    rings beside the states, and how many entries a block's rings hold)
    and {"latent"} with `n_blocks` pages, zero-filled; block 0 of each
    the trash block."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    n_kda = cfg.mixers.count("kda")
    hd = cfg.kda_head_dim
    return {
        "state": jnp.zeros((n_kda, state_blocks, cfg.n_heads, hd, hd),
                           jnp.float32),
        "conv": jnp.zeros((n_kda, state_blocks, cfg.conv_size - 1,
                           cfg.conv_channels), jnp.float32),
        "ring": kda.ring_array(n_kda, state_blocks, cfg.n_heads, hd),
        "held": jnp.zeros((1, state_blocks), jnp.int32),
        "latent": lsm.latent_pool(cfg, cfg.n_layers - n_kda, n_blocks,
                                  block_size),
    }


# ---------------------------------------------------------------------------
# pieces of the KDA layer
# ---------------------------------------------------------------------------

def _conv_taps(lp):
    return jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]],
                           -1).astype(jnp.float32)


def _pre_conv(h, lp, cfg):
    """Normed h [N, D] -> the three projections side by side [N, 3 H d],
    float32 values of the activation type."""
    adt = cfg.activation_dtype()
    return jnp.concatenate([mm(h, lp[w], adt) for w in ("w_q", "w_k", "w_v")],
                           -1).astype(jnp.float32)


def _heads(conved, h, lp, cfg):
    """The convolution's output [N, 3 H d] f32 and the normed input h
    -> (q, k, v [N, H, d] in the activation type, g [N, H, d] f32,
    beta [N, H] f32)."""
    adt = cfg.activation_dtype()
    n = h.shape[0]
    nh, hd = cfg.n_heads, cfg.kda_head_dim
    q, k, v = jnp.split(jax.nn.silu(conved).reshape(n, 3 * nh, hd), 3, 1)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                 + NORM_EPS)

    def f32(w):
        return jnp.einsum("nd,df->nf", h, lp[w].astype(adt),
                          preferred_element_type=jnp.float32)

    scale = jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]
    g = cfg.gate_floor * jax.nn.sigmoid(scale * (
        f32("w_f") + lp["b_f"].astype(jnp.float32)).reshape(n, nh, hd))
    return ((unit(q) * hd ** -0.5).astype(adt), unit(k).astype(adt),
            v.astype(adt), g, jax.nn.sigmoid(f32("w_beta")))


def _gate(h, lp, cfg):
    """The head-wise output gate of normed h [N, D]: f32 [N, H, 1]."""
    return jax.nn.sigmoid(jnp.einsum(
        "nd,dh->nh", h, lp["w_og"].astype(cfg.activation_dtype()),
        preferred_element_type=jnp.float32))[..., None]


def _kda_out(o, h, lp, cfg):
    """The recurrence's output o [N, H, d] f32 through the head norm, the
    gate and W_o: -> [N, D]."""
    adt = cfg.activation_dtype()
    o = rms_norm(o, lp["o_norm_scale"], cfg.eps)
    return mm((o * _gate(h, lp, cfg)).astype(adt).reshape(
        o.shape[0], -1), lp["w_out"], adt)


def _latent_out(att, h, lp, cfg):
    """Latent attention's heads att [N, H, v] through the gate and W_o."""
    adt = cfg.activation_dtype()
    return mm((att.astype(jnp.float32) * _gate(h, lp, cfg)).astype(
        adt).reshape(att.shape[0], -1), lp["w_out"], adt)


def _groups_here(x, lp, cfg, live):
    """Of the groups the live rows' routers kept, how many are held here
    whole (int32 scalar; 0 for a dense layer or a router without
    groups)."""
    per = cfg.router_width // cfg.n_group
    lo, hi = -(-cfg.held_from // per), (cfg.held_from + cfg.held_count) // per
    if "router" not in lp or cfg.n_group == 1 or hi <= lo:
        return jnp.int32(0)
    h2 = rms_norm(x, lp["ffn_norm_scale"], cfg.eps)
    kept = kept_groups(router_scores(h2, lp)[1], cfg.n_group,
                       cfg.topk_group)[:, lo:hi]
    return jnp.sum(kept & live[:, None], dtype=jnp.int32)


def _counts(cfg, head, expert_counts):
    """`COUNTS`' first five, then the experts' three and their loads."""
    experts = expert_totals(expert_counts, 3 + cfg.held_count)
    return jnp.concatenate([jnp.stack(head).astype(jnp.int32),
                            experts.astype(jnp.int32)])


def _feed_forward(x, lp, cfg, live, kernel):
    """`latent_sparse_moe.feed_forward` and this layer's counts (pairs
    here, pairs routed, groups kept here, the held experts' loads), None
    for a dense layer."""
    with jax.named_scope(FFN):
        groups = _groups_here(x, lp, cfg, live)
    x, counts = lsm.feed_forward(x, lp, cfg, live, kernel)
    if counts is None:
        return x, None
    return x, jnp.concatenate([counts[:2], groups[None], counts[2:]])


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: LinearLatentConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: the
    recurrence token by token, no state kept, no cache."""
    adt = cfg.activation_dtype()
    taps = cfg.conv_size

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        live = jnp.ones((t,), bool)
        causal = pos[None, :] <= pos[:, None]
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp, mixer in zip(params["layers"], cfg.mixers):
            with jax.named_scope(MIXER):
                h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
                if mixer == "kda":
                    pre = jnp.pad(_pre_conv(h, lp, cfg),
                                  ((taps - 1, 0), (0, 0)))
                    w = _conv_taps(lp)
                    conved = sum(w[i] * pre[i:i + t] for i in range(taps))
                    q, k, v, g, beta = _heads(conved, h, lp, cfg)
                    x = x + _kda_out(kda.kda_recurrent(q, k, v, g, beta)[0], h,
                                     lp, cfg)
                else:
                    q_nope, q_rope, row = lsm.project(h, lp, pos, cfg)
                    x = x + _latent_out(lsm.attend_full(
                        q_nope, q_rope, row, causal, lp, cfg), h, lp, cfg)
            x, _ = _feed_forward(x, lp, cfg, live,
                                 grouped_experts.EXPERTS_GROUPED)
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_ln_scale"], cfg.eps),
                           params["head"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def prefill(params, tokens, cache, cfg: LinearLatentConfig, mesh=None, *,
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
    taps = cfg.conv_size
    state, conv, latent = cache["state"], cache["conv"], cache["latent"]
    nb, bs = latent.shape[1], latent.shape[2]
    with jax.named_scope(EMBED):
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(c if length is None else length, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        block, pages = table[0], table[1:]
        first = start == 0
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        widx = jnp.where(valid, pages[positions // bs] * bs + positions % bs,
                         nb * bs)
        every = lsm.every_earlier(positions, valid, pages.shape[0] * bs)
        x = params["embed"].astype(adt)[tokens[0]]
    n_kda = n_latent = 0
    expert_counts = []
    for lp, mixer in zip(params["layers"], cfg.mixers):
        with jax.named_scope(MIXER):
            h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
            if mixer == "kda":
                tail = jnp.where(first, 0.0, conv[n_kda, block])
                pre = jnp.concatenate([tail, _pre_conv(h, lp, cfg)])
                w = _conv_taps(lp)
                conved = sum(w[i] * pre[i:i + c] for i in range(taps))
                # the last live positions' projections, whatever the padding
                conv = conv.at[n_kda, block].set(
                    jax.lax.dynamic_slice_in_dim(pre, length, taps - 1))
                q, k, v, g, beta = _heads(conved, h, lp, cfg)
                o, state = kda.kda_chunk(
                    q, k, v, g, beta, state, n_kda, block, first, length,
                    state_round=cfg.state_round, impl=cfg.kda_impl)
                x = x + _kda_out(o, h, lp, cfg)
                n_kda += 1
            else:
                q_nope, q_rope, row = lsm.project(h, lp, positions, cfg)
                latent = lsm.write_latent(latent, n_latent, row, widx, cfg)
                att = lsm.prefill_attend(q_nope, q_rope, latent, n_latent,
                                          pages, positions, valid, every, lp,
                                          cfg)
                x = x + _latent_out(att.reshape(c, cfg.n_heads, cfg.v_dim), h,
                                    lp, cfg)
                n_latent += 1
        x, counts = _feed_forward(x, lp, cfg, valid,
                                  grouped_experts.EXPERTS_GROUPED_PREFILL)
        if counts is not None:
            expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        rows = jnp.sum(jnp.where(valid, positions + 1, 0)) * n_latent
        # the chunk leaves its block's rings empty, whoever held the
        # block before (a chunk after decode steps of the same sequence
        # does not occur: a preempted stream prefills again from its
        # first token)
        return (unembed(last, params["head"], adt),
                {**cache, "state": state, "conv": conv, "latent": latent,
                 "held": cache["held"].at[0, block].set(0)},
                _counts(cfg, [length, c - length, first, rows, jnp.int32(0)],
                        expert_counts))


def decode(params, tokens, cache, pos, tables, cfg: LinearLatentConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B]; `tables[:, 0]` each row's state
    block, the rest its pages. Idle rows name the trash blocks of both
    kinds, rewrite their tails and pages and count nothing. Every KDA
    layer's step reads `held` as it was before the step; it is written
    once, after the last (idle rows all name block 0 and all leave its
    count as it was).
    -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    taps = cfg.conv_size
    state, conv, latent = cache["state"], cache["conv"], cache["latent"]
    ring = cache["ring"]
    b = tokens.shape[0]
    with jax.named_scope(EMBED):
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        blocks, pages = tables[:, 0], tables[:, 1:]
        live = blocks > 0
        held = cache["held"][0, blocks]
        fold, held_after = kda.ring_after(blocks, held, cfg.state_round)
        widx = lsm.decode_write_index(latent, pages, pos)
        x = params["embed"].astype(adt)[tokens]
    n_kda = n_latent = 0
    expert_counts = []
    for lp, mixer in zip(params["layers"], cfg.mixers):
        with jax.named_scope(MIXER):
            h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
            if mixer == "kda":
                pre = jnp.concatenate(
                    [conv[n_kda, blocks], _pre_conv(h, lp, cfg)[:, None]], 1)
                conved = jnp.einsum("kc,bkc->bc", _conv_taps(lp), pre)
                conv = conv.at[n_kda, blocks].set(pre[:, 1:])
                q, k, v, g, beta = _heads(conved, h, lp, cfg)
                o, state, ring = kda.kda_step(
                    q, k, v, g, beta, state, ring, n_kda, blocks, held,
                    state_round=cfg.state_round, impl=cfg.kda_impl)
                x = x + _kda_out(o, h, lp, cfg)
                n_kda += 1
            else:
                q_nope, q_rope, row = lsm.project(h, lp, pos, cfg)
                latent = lsm.write_latent(latent, n_latent, row, widx, cfg)
                att = lsm.decode_attend(q_nope, q_rope, latent, n_latent,
                                        pages, pos, lp, cfg)
                x = x + _latent_out(att, h, lp, cfg)
                n_latent += 1
        x, counts = _feed_forward(x, lp, cfg, live,
                                  grouped_experts.EXPERTS_GROUPED)
        if counts is not None:
            expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        rows = jnp.sum(jnp.where(live, pos + 1, 0)) * n_latent
        n_live = jnp.sum(live, dtype=jnp.int32)
        return (unembed(x, params["head"], adt),
                {"state": state, "conv": conv, "latent": latent,
                 "ring": ring,
                 "held": cache["held"].at[0, blocks].set(held_after)},
                _counts(cfg, [n_live, b - n_live, jnp.int32(0), rows,
                              jnp.sum(fold, dtype=jnp.int32)],
                        expert_counts))


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, state_blocks=1, state_keys=STATE_KEYS,
    counts=lambda cfg, totals: summarize(COUNTS, totals, cfg.held_count))
