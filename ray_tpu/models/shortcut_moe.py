"""A decoder whose layer is not a chain: two latent-attention blocks and
two dense MLPs in a row, with the routed experts on a shortcut beside
them. The layer that `LongCat-Flash-Chat` names (the LongCat-Flash
report's shortcut-connected MoE): the experts read what the first
attention block's norm wrote, and their result joins the stream only at
the layer's end, after the first MLP, the second attention block and the
second MLP have run. Published layer `l`, input `x`:

    a1 = x  + attn_{2l}  (RMSNorm_in0(x))
    n1 = RMSNorm_post0(a1)
    s  = moe(n1)                      # used at the layer's end only
    h1 = a1 + mlp_0(n1)
    a2 = h1 + attn_{2l+1}(RMSNorm_in1(h1))
    y  = a2 + mlp_1(RMSNorm_post1(a2)) + s

    mlp_i(n) = W_down (silu(W_gate n) * W_up n)
    attn(n):  `latent_sparse_moe`'s latent attention with a query
              bottleneck and no indexer (`project`, the row format,
              `decode_attend` over every cached row, `prefill_attend`),
              q times `q_scale` = (D / q_rank)^1/2 after W_qb, and the
              normed latent times `kv_scale` = (D / kv_rank)^1/2 before
              W_kvb: the cached row is the normed latent unscaled, and
              since W_kvb is linear the scale multiplies the queries'
              no-position part (the scores) and the heads' output (the
              values) instead, two products a block on a step's own rows
    moe(n):   p = softmax(n W_r) over the router's whole width, float32;
              the k largest of p + bias chosen; weights p_e x
              routed_scale, not renormalised; outputs `identity_from ..`
              have no expert and return n (`blocks.expert_layer`'s
              identity part, which a token's own chip adds), the others
              the held experts' SwiGLU(n), this chip's share

In a deployment the experts' exchange hides behind `mlp_0`, the second
attention block and `mlp_1`; on one chip there is no exchange and the
shortcut hides nothing. The scopes `shortcut_experts` (router, routed
and identity part) and `shortcut_dense` (what they stand beside) say
what each costs, under `family.PARTS`' `mixer` and `ffn`, which here
alternate twice a layer.

**What the engine holds for this family**: pages alone
(`ServingFamily.state_blocks` 0 and `paged`), `"latent"` uint32 `[2 x
layers, n_blocks, block_size, 1, words]` (`ops/sparse_latent.py`'s row
format): block `2l` and `2l + 1` of the pool are layer `l`'s two
attention blocks. A chunk bucket's padding writes the trash page and
routes nowhere; decode's idle rows (table all 0) rewrite the trash page
and count nothing.

Parameters: the tree `benchmarks/refs/shortcut_moe.py` documents.
`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks. No `verify`, no training path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.models.blocks import (Experts, cast_leaves, copy_block,
                                   expert_layer, expert_totals, gated_mlp,
                                   gather_block, mm, rms_norm, routing,
                                   scatter_block, summarize, unembed)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import grouped_experts

# below `family.PARTS`: the experts on their shortcut, and the dense path
# their exchange would hide behind in a deployment
SHORTCUT_EXPERTS, SHORTCUT_DENSE = "shortcut_experts", "shortcut_dense"
# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow.
# A row's compute varies with how many of its choices have an expert:
# `rows_few_experts` chose `FEW` or fewer, `rows_many_experts` `MANY` or
# more
COUNTS = ("latent_rows_read", "decode_rows_live", "decode_rows_idle",
          "chunk_rows_live", "chunk_rows_padded", "expert_tokens_here",
          "expert_tokens_routed", "identity_tokens", "rows_few_experts",
          "rows_many_experts")
FEW, MANY = 4, 10
# the leaves a step reads in float32 (`load`): the router's scores
FLOAT32_LEAVES = ("router", "router_bias")
# `init_params`' draws for the router, the ones `benchmarks/configs/
# longcat-flash-chat.json` states (`draws`)
ROUTER_GAIN, ROUTER_BIAS = 4.0, 0.0005


@dataclass(frozen=True)
class ShortcutMoEConfig(lsm.LatentSparseMoEConfig):
    # `n_layers` counts published (double) layers; every one has experts
    # and no latent block has an indexer. `router_width` is the router's
    # whole width, the outputs without an expert included
    index_topk: int | None = None
    indexer_types: tuple = ("none", "none", "none")
    mlp_types: tuple = ("sparse", "sparse", "sparse")
    shared_experts: int = 0
    router_width: int = 12
    identity_experts: int = 4       # the router's last outputs
    experts_per_token: int = 3
    norm_topk: bool = False
    scale_q: bool = True            # mla_scale_q_lora
    scale_kv: bool = True           # mla_scale_kv_lora

    def __post_init__(self):
        super().__post_init__()
        if self.has_indexer or self.q_rank is None \
                or set(self.mlp_types) != {"sparse"} \
                or not 0 <= self.identity_experts < self.router_width:
            raise ValueError("latent blocks with a query bottleneck and no "
                             "indexer, experts in every layer, and fewer "
                             "identity outputs than the router is wide")

    @property
    def identity_from(self) -> int:
        return self.router_width - self.identity_experts

    @property
    def q_scale(self) -> float:
        return (self.d_model / self.q_rank) ** 0.5 if self.scale_q else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.d_model / self.kv_rank) ** 0.5 if self.scale_kv else 1.0

    @property
    def experts(self) -> Experts:
        return Experts(self.router_width, self.experts_per_token,
                       self.norm_topk, self.held_from,
                       routed_scale=self.routed_scale,
                       expert_round=self.expert_round, impl=self.sparse_impl,
                       score_func="softmax", identity_from=self.identity_from)

    @property
    def family(self):
        return FAMILY

    training = None     # `latent_sparse_moe`'s trainer is not this family's


def from_published(*, hidden_size, ffn_hidden_size, expert_ffn_hidden_size,
                   num_layers, num_attention_heads, kv_lora_rank, q_lora_rank,
                   qk_rope_head_dim, v_head_dim, qk_nope_head_dim,
                   mla_scale_q_lora, mla_scale_kv_lora, routed_scaling_factor,
                   n_routed_experts, zero_expert_num, zero_expert_type,
                   moe_topk, rms_norm_eps, max_position_embeddings,
                   attention_bias, attention_method, layers_from=0,
                   experts_held_from=0, published=None,
                   **same) -> ShortcutMoEConfig:
    """The configuration file's published keys -> `ShortcutMoEConfig`
    (`benchmarks/configs/longcat-flash-chat.json`, `program.constructor`).
    `n_routed_experts` is how many experts are held here; the router is
    `published["n_routed_experts"]` + `zero_expert_num` wide where a
    share is run."""
    if attention_bias or attention_method != "MLA" \
            or zero_expert_type != "identity":
        raise ValueError("this family's projections have no bias, its "
                         "attention is latent, and its zero-computation "
                         "experts return their input")
    n = layers_from + num_layers
    return ShortcutMoEConfig(
        d_model=hidden_size, n_layers=num_layers,
        n_heads=num_attention_heads, q_rank=q_lora_rank,
        kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
        rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
        indexer_types=["none"] * n, mlp_types=["sparse"] * n,
        first_layer=layers_from, d_ff=ffn_hidden_size,
        expert_ff=expert_ffn_hidden_size,
        router_width=(published or {}).get(
            "n_routed_experts", n_routed_experts) + zero_expert_num,
        identity_experts=zero_expert_num, experts_per_token=moe_topk,
        held_from=experts_held_from, held_count=n_routed_experts,
        routed_scale=float(routed_scaling_factor), scale_q=mla_scale_q_lora,
        scale_kv=mla_scale_kv_lora, eps=rms_norm_eps,
        max_seq_len=max_position_embeddings, **same)


def init_params(key, cfg: ShortcutMoEConfig):
    """Float32 leaves, for tests and the smoke; the tree
    `benchmarks/refs/shortcut_moe.py` documents. The router's logits
    spread by `ROUTER_GAIN`, so that the chosen hold most of a softmax
    over the whole width."""
    d, nh, rq, rkv = cfg.d_model, cfg.n_heads, cfg.q_rank, cfg.kv_rank
    qk = cfg.nope_dim + cfg.rope_dim
    residual = (4.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 24 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def attention():
        return {"attn_norm_scale": ones(d),
                "wq_a": normal((d, rq), d ** -0.5), "q_norm_scale": ones(rq),
                "wq_b": normal((rq, nh * qk), rq ** -0.5),
                "wkv_a": normal((d, rkv + cfg.rope_dim), d ** -0.5),
                "kv_norm_scale": ones(rkv),
                "wkv_b": normal((rkv, nh * (cfg.nope_dim + cfg.v_dim)),
                                rkv ** -0.5),
                "w_out": normal((nh * cfg.v_dim, d),
                                (nh * cfg.v_dim) ** -0.5 * residual)}

    def mlp():
        return {"ffn_norm_scale": ones(d),
                "w_gate": normal((d, cfg.d_ff), d ** -0.5),
                "w_up": normal((d, cfg.d_ff), d ** -0.5),
                "w_down": normal((cfg.d_ff, d), cfg.d_ff ** -0.5 * residual)}

    experts = (cfg.held_count, cfg.expert_ff, d)
    layers = [{
        "attn": (attention(), attention()), "mlp": (mlp(), mlp()),
        "router": normal((d, cfg.router_width), d ** -0.5 * ROUTER_GAIN),
        "router_bias": normal((cfg.router_width,), ROUTER_BIAS),
        "we_gate": normal(experts, d ** -0.5),
        "we_up": normal(experts, d ** -0.5),
        "we_down": normal(experts, cfg.expert_ff ** -0.5 * residual),
    } for _ in range(cfg.n_layers)]
    return {"embed": normal((cfg.vocab_size, d), lsm.EMBED_INIT),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


def load(params, cfg: ShortcutMoEConfig):
    """`ServingFamily.load`: every floating leaf in the type the steps
    read it in, so that no step converts a weight: the router and its
    bias in float32, every other in the activations' type. A tree already
    there is handed back as it is, and the engine then runs nothing."""
    return cast_leaves(params, cfg.activation_dtype(), FLOAT32_LEAVES)


def init_pool(cfg: ShortcutMoEConfig, n_blocks: int, block_size: int,
              mesh=None):
    """{"latent"}: the rows of two attention blocks a layer, zero-filled;
    page 0 the trash page."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    return {"latent": lsm.latent_pool(cfg, 2 * cfg.n_layers, n_blocks,
                                      block_size)}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _project(n, ap, pos, cfg):
    """`latent_sparse_moe.project` and the two published scales: q times
    `q_scale`, and `kv_scale`'s part of the scores on the queries'
    no-position half, which is the half that meets W_kvb's keys."""
    adt = cfg.activation_dtype()
    q_nope, q_rope, row = lsm.project(n, ap, pos, cfg)
    return ((q_nope.astype(jnp.float32)
             * (cfg.q_scale * cfg.kv_scale)).astype(adt),
            (q_rope.astype(jnp.float32) * cfg.q_scale).astype(adt), row)


def _out(att, ap, cfg):
    """The heads' outputs [N, H * v] times `kv_scale` (W_kvb's values of
    the scaled latent), through W_o."""
    adt = cfg.activation_dtype()
    return mm((att.astype(jnp.float32) * cfg.kv_scale).astype(adt),
              ap["w_out"], adt)


def _choices(n1, lp, cfg, live):
    """What a row's choice says of its compute: (choices of live rows
    that name no expert, live rows with `FEW` or fewer experts chosen,
    those with `MANY` or more), int32. The router again, which the
    compiler finds is `expert_layer`'s."""
    free = routing(n1, lp, cfg.experts)[0] >= cfg.identity_from
    real = cfg.experts_per_token - jnp.sum(free, -1)
    return jnp.stack([jnp.sum(free & live[:, None]),
                      jnp.sum((real <= FEW) & live),
                      jnp.sum((real >= MANY) & live)]).astype(jnp.int32)


def _layer(x, lp, latent, block: int, cfg, live, kernel, attend):
    """One published layer on x [N, D]; `attend(n, ap, latent, block)` is
    an attention block of normed n before W_o: -> (heads [N, H * v],
    latent). -> (y, latent, counts: `expert_layer`'s two, `_choices`'
    three, the held experts' loads)."""
    adt = cfg.activation_dtype()
    (a0, a1), (m0, m1) = lp["attn"], lp["mlp"]
    with jax.named_scope(MIXER):
        att, latent = attend(rms_norm(x, a0["attn_norm_scale"], cfg.eps), a0,
                             latent, block)
        x = x + _out(att, a0, cfg)
    with jax.named_scope(FFN):
        n1 = rms_norm(x, m0["ffn_norm_scale"], cfg.eps)
        with jax.named_scope(SHORTCUT_EXPERTS):
            routed, _, identity, counts = expert_layer(
                n1, lp, cfg.experts, adt, live, kernel)
            shortcut = routed + identity
            counts = jnp.concatenate([
                counts[:2], _choices(n1, lp, cfg, live), counts[2:]])
        with jax.named_scope(SHORTCUT_DENSE):
            x = x + gated_mlp(n1, m0, adt, jnp.float32)[0]
    with jax.named_scope(MIXER), jax.named_scope(SHORTCUT_DENSE):
        att, latent = attend(rms_norm(x, a1["attn_norm_scale"], cfg.eps), a1,
                             latent, block + 1)
        x = x + _out(att, a1, cfg)
    with jax.named_scope(FFN):
        with jax.named_scope(SHORTCUT_DENSE):
            x = x + gated_mlp(rms_norm(x, m1["ffn_norm_scale"], cfg.eps), m1,
                              adt, jnp.float32)[0]
        return x + shortcut, latent, counts


def _counts(cfg, head, expert_counts):
    """`COUNTS`' first five, then the experts' five and their loads."""
    experts = expert_totals(expert_counts, 5 + cfg.held_count)
    return jnp.concatenate([jnp.stack(head).astype(jnp.int32),
                            experts.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ShortcutMoEConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: expanded
    heads over the sequence's own rows, no cache."""
    adt = cfg.activation_dtype()

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        causal = pos[None, :] <= pos[:, None]

        def attend(n, ap, latent, block):
            q_nope, q_rope, row = _project(n, ap, pos, cfg)
            return lsm.attend_full(q_nope, q_rope, row, causal, ap,
                                   cfg).reshape(t, -1), latent

        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp in params["layers"]:
            x, _, _ = _layer(x, lp, None, 0, cfg, jnp.ones((t,), bool),
                             grouped_experts.EXPERTS_GROUPED, attend)
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_ln_scale"], cfg.eps),
                           params["head"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def prefill(params, tokens, cache, cfg: ShortcutMoEConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of one sequence (`gpt.prefill_paged`'s contract): tokens
    [1, C] at positions start .. start + length - 1; every attention
    block's row is written through `block_table` before the block
    attends. -> (logits [1, V] f32 of the chunk's last real position,
    cache, counts)."""
    c = tokens.shape[1]
    if tokens.shape[0] != 1:
        raise ValueError(f"prefill wants tokens [1, C], got batch "
                         f"{tokens.shape[0]}")
    adt = cfg.activation_dtype()
    latent = cache["latent"]
    nb, bs = latent.shape[1], latent.shape[2]
    with jax.named_scope(EMBED):
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(c if length is None else length, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        widx = jnp.where(valid, table[positions // bs] * bs + positions % bs,
                         nb * bs)
        every = lsm.every_earlier(positions, valid, table.shape[0] * bs)
        x = params["embed"].astype(adt)[tokens[0]]

    def attend(n, ap, latent, block):
        q_nope, q_rope, row = _project(n, ap, positions, cfg)
        latent = lsm.write_latent(latent, block, row, widx, cfg)
        return lsm.prefill_attend(q_nope, q_rope, latent, block, table,
                                  positions, valid, every, ap, cfg), latent

    expert_counts = []
    for i, lp in enumerate(params["layers"]):
        x, latent, counts = _layer(
            x, lp, latent, 2 * i, cfg, valid,
            grouped_experts.EXPERTS_GROUPED_PREFILL, attend)
        expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        rows = jnp.sum(jnp.where(valid, positions + 1, 0)) * 2 * cfg.n_layers
        zero = jnp.int32(0)
        return (unembed(last, params["head"], adt), {"latent": latent},
                _counts(cfg, [rows, zero, zero, length, c - length],
                        expert_counts))


def decode(params, tokens, cache, pos, tables, cfg: ShortcutMoEConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B], pages named by tables [B,
    max_blocks]; every attention block reads every cached row of a
    stream's pages. Idle rows point their table at the trash page,
    route nowhere and count nothing.
    -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    latent = cache["latent"]
    b = tokens.shape[0]
    with jax.named_scope(EMBED):
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        live = tables[:, 0] > 0
        widx = lsm.decode_write_index(latent, tables, pos)
        x = params["embed"].astype(adt)[tokens]

    def attend(n, ap, latent, block):
        q_nope, q_rope, row = _project(n, ap, pos, cfg)
        latent = lsm.write_latent(latent, block, row, widx, cfg)
        return lsm.decode_attend(q_nope, q_rope, latent, block, tables, pos,
                                 ap, cfg).reshape(b, -1), latent

    expert_counts = []
    for i, lp in enumerate(params["layers"]):
        x, latent, counts = _layer(x, lp, latent, 2 * i, cfg, live,
                                   grouped_experts.EXPERTS_GROUPED, attend)
        expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        rows = jnp.sum(jnp.where(live, pos + 1, 0)) * 2 * cfg.n_layers
        n_live = jnp.sum(live, dtype=jnp.int32)
        zero = jnp.int32(0)
        return (unembed(x, params["head"], adt), {"latent": latent},
                _counts(cfg, [rows, n_live, b - n_live, zero, zero],
                        expert_counts))


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, load=load,
    counts=lambda cfg, totals: summarize(COUNTS, totals, cfg.held_count))
