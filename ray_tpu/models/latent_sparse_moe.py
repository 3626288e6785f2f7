"""Decoder with latent attention, routed experts and, where a layer has
one, a learned sparse selection of the keys: served and trained.

One family, two published shapes of its layer. With an indexer and a query
bottleneck (`glm_moe_dsa`: GLM-5.2) it is served through the engine
(`prefill`, `decode`, the paged pool). Without either (`deepseek_v3`:
Kanana-2; `indexer_types` all "none", `q_rank` None) every query attends
to every earlier position; that layer is trained (`forward_features`,
`train.spmd.make_latent_moe_trainer`) and served through the same entry
points: a decode step streams a stream's live pages whole against the
absorbed query (`ops.sparse_latent.latent_decode`), a prefill chunk
attends to every cached row, and the pool has no index keys.

Three mechanisms in one layer, none of which `models/gpt.py` has:

- **Latent attention.** Queries pass through a low-rank bottleneck; keys
  and values are rebuilt per head from one compressed vector a token
  (`c_kv`, `kv_rank` wide) plus one rotary key shared by all heads
  (`k_rope`). The cache holds that row, `kv_rank + rope_dim` values a
  token a layer, and nothing per head. Prefill rebuilds keys and values
  from the rows (expanded heads); a decode step never does: the query's
  no-position part goes through the key up-projection once
  (`q_nope W_uk`), is scored against `c_kv` itself, and the weighted sum
  of `c_kv` rows goes through the value up-projection (absorbed form: the
  row is read once for all heads).
- **Learned sparse attention.** A layer that owns an indexer
  (`indexer_types` "full") scores every earlier position with a small
  multi-head ReLU scorer over a cached index key (`index_dim` values a
  token) and attention reads the `index_topk` best only, exactly. A
  "shared" layer reads the selection the nearest "full" layer before it
  made in the same step; a selection is never recomputed and never kept
  across steps.
- **Routed experts, this chip's share.** A sigmoid router over the
  published width chooses `experts_per_token`; the chip holds experts
  `held_from .. held_from + held_count - 1` and computes their part,
  dropless, plus the shared expert every chip computes alike. What the
  absent experts would add is left out (no code stands in for them).

Training (`forward_features`) runs the expanded-head form through
`ops.flash_attention` (keys `nope_dim + rope_dim` wide, values `v_dim`),
the held experts through `ops.grouped_experts`' kernels and their
backward pass, and rematerialises layer by layer. `router_bias` takes no
gradient: `update_router_bias` moves it after a step by the step's own
expert counts.

The pool is two kinds of state under one block table: `"latent"` uint32
`[L, n_blocks, block_size, 1, words]` (`ops/sparse_latent.py`'s row format)
for every layer and `"index"` `[L_full, n_blocks, block_size, index_dim]`
for the layers that own an indexer. Blocks are axis 1 of both, so
`blocks.copy_block` / `gather_block` / `scatter_block` move them together.

Rotary positions are interleaved pairs. The dense layer, the shared
expert, the norms, the router and the held experts are `models/blocks.py`'s
(`gated_mlp`, `rms_norm`, `expert_layer` over `cfg.experts`). Parameters: the
tree `benchmarks/refs/latent_sparse_moe.py` documents (a list of layer
dicts; layers differ, so they are not stacked), leaves of any float type,
cast at use. A layer without the bottleneck has `w_q` [D, H*(nope+rope)]
in place of `wq_a`, `q_norm_scale` and `wq_b`.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (Experts, copy_block, expert_layer,
                                   expert_totals, gated_mlp, gather_block,
                                   layer_norm, mm, rms_norm, rope_pairs,
                                   scatter_block, summarize, unembed)
from ray_tpu.models.family import (EMBED, FFN, HEAD, MIXER, ServingFamily,
                                   TrainingFamily)
from ray_tpu.ops import grouped_experts, sparse_latent

NEG_INF = sparse_latent.NEG_INF

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow
COUNTS = ("index_scanned_tokens", "index_selected_tokens",
          "index_layer_runs", "index_layer_reuses",
          "index_chunk_selections", "index_chunk_thresholds",
          "chunk_attend_blocks",
          "expert_tokens_here", "expert_tokens_routed")

# training. The embedding's scale in `init_params`: not the 0.02 of the
# other leaves' family, because a residual that starts that small is soon
# the mean value vector of the sequence's earlier positions, alike for
# every token, and the router then sends them all to the same experts
# (PERF.md, PR 38: one expert of 80 got every pair).
EMBED_INIT = 1.0
BIAS_UPDATE_RATE = 0.001    # gamma of `update_router_bias`


@dataclass(frozen=True)
class LatentSparseMoEConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_rank: int | None = 32      # None: q = h W_q, no bottleneck
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    index_heads: int = 4
    index_dim: int = 16
    index_topk: int | None = 16
    # one entry a published layer; the layers that run are
    # [first_layer, first_layer + n_layers). "none": the layer has no
    # indexer and attends to every earlier position
    indexer_types: tuple = ("full", "shared", "full")
    mlp_types: tuple = ("dense", "sparse", "sparse")
    first_layer: int = 0
    d_ff: int = 128
    expert_ff: int = 32
    shared_experts: int = 1
    router_width: int = 8
    experts_per_token: int = 2
    held_from: int = 0
    held_count: int = 8
    routed_scale: float = 2.5
    norm_topk: bool = True
    # group-limited choice (the published keys): the router's width in
    # `n_group` equal groups, experts chosen inside the `topk_group` best
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    eps: float = 1e-5
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    # test-only, for the benchmark's control: "int8" rounds a cache row,
    # as it is written, to the int8 grid of its own largest magnitude
    # (`ops.quant.quantize_rows`) and keeps that value in the pool's own
    # element type. An int8 pool's numbers without its bytes: it saves
    # nothing, and this family has no `kv_dtype` for that reason
    cache_round: str = "none"        # none | int8
    sparse_impl: str = "auto"        # auto | pallas | jax (the three ops)
    # training (`forward_features`): q and kv rows a grid step of the flash
    # kernels, at most (the sweep at keys 192, values 128, T 8192 is
    # PERF.md's, PR 38; 2048 x 2048 does not fit VMEM)
    flash_block_q: int = 1024
    flash_block_kv: int = 2048
    # test-only, for the benchmark's control: the routed experts' inputs
    # and matrices rounded to this type's grid before their matmuls
    expert_round: str = "none"       # none | float8_e4m3fn

    def __post_init__(self):
        for name in ("indexer_types", "mlp_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        indexers = {ix for _, ix in self.kinds}
        if "none" in indexers:
            if indexers != {"none"}:
                raise ValueError("layers with and without an indexer do "
                                 "not mix")
        elif self.kinds[0][1] != "full":
            raise ValueError("the first layer run must own an indexer")
        elif self.q_rank is None or self.index_topk is None:
            raise ValueError("an indexer reads the query bottleneck: "
                             "q_rank and index_topk are numbers")
        if self.router_width % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.topk_group} of {self.n_group} groups over a router "
                f"{self.router_width} wide")
        if self.cache_round not in ("none", "int8"):
            raise ValueError(f"unknown cache_round {self.cache_round!r}")
        if self.expert_round not in ("none", "float8_e4m3fn"):
            raise ValueError(f"unknown expert_round {self.expert_round!r}")

    @property
    def has_indexer(self) -> bool:
        return self.kinds[0][1] != "none"

    @property
    def kinds(self) -> tuple:
        """((mlp type, indexer type), ...) of the layers that run."""
        lo, hi = self.first_layer, self.first_layer + self.n_layers
        return tuple(zip(self.mlp_types[lo:hi], self.indexer_types[lo:hi]))

    @property
    def row_values(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def row_words(self) -> int:
        return sparse_latent.row_words(self.row_values,
                                       self.activation_dtype())

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts(self) -> Experts:
        return Experts(
            self.router_width, self.experts_per_token, self.norm_topk,
            self.held_from, self.n_group, self.topk_group, self.routed_scale,
            self.expert_round, self.sparse_impl)

    @property
    def family(self):
        return FAMILY

    @property
    def training(self):
        return TRAINING


def from_published(*, hidden_size, num_hidden_layers, num_attention_heads,
                   q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                   qk_rope_head_dim, v_head_dim, intermediate_size,
                   moe_intermediate_size, n_shared_experts, n_routed_experts,
                   num_experts_per_tok, routed_scaling_factor,
                   norm_topk_prob, rms_norm_eps, max_position_embeddings,
                   index_n_heads=0, index_head_dim=0, index_topk=None,
                   indexer_types=None, mlp_layer_types=None,
                   first_k_dense_replace=None, layers_from=0,
                   experts_held_from=0, published=None, n_group=1,
                   topk_group=1, **same) -> LatentSparseMoEConfig:
    """The configuration from a published `config.json`'s own keys
    (`glm_moe_dsa`'s names, or `deepseek_v3`'s, which has no indexer: no
    `indexer_types`, and `first_k_dense_replace` leading dense layers in
    place of `mlp_layer_types`). `n_routed_experts` is how many experts
    are held here; the router's width is `published["n_routed_experts"]`
    where a share is run, else the same number. Keys this module spells
    as the source does (`vocab_size`, `rope_theta`, `dtype`, ...) pass
    through."""
    n_published = layers_from + num_hidden_layers
    if mlp_layer_types is None:
        mlp_layer_types = ["dense" if i < first_k_dense_replace else "sparse"
                           for i in range(n_published)]
    if indexer_types is None:
        indexer_types = ["none"] * n_published
    return LatentSparseMoEConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        n_heads=num_attention_heads, q_rank=q_lora_rank,
        kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
        rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
        index_heads=index_n_heads, index_dim=index_head_dim,
        index_topk=index_topk, indexer_types=indexer_types,
        mlp_types=mlp_layer_types, first_layer=layers_from,
        d_ff=intermediate_size, expert_ff=moe_intermediate_size,
        shared_experts=n_shared_experts,
        router_width=(published or {}).get("n_routed_experts",
                                           n_routed_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=n_routed_experts, routed_scale=routed_scaling_factor,
        norm_topk=norm_topk_prob, n_group=n_group, topk_group=topk_group,
        eps=rms_norm_eps, max_seq_len=max_position_embeddings, **same)


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def latent_pool(cfg, n_layers: int, n_blocks: int, block_size: int):
    """The latent rows of `n_layers` layers, zero-filled."""
    return jnp.zeros((n_layers, n_blocks, block_size, 1, cfg.row_words),
                     jnp.uint32)


def init_pool(cfg: LatentSparseMoEConfig, n_blocks: int, block_size: int,
              mesh=None):
    """{"latent"} and, where layers own an indexer, {"index"}, zero-filled;
    blocks on axis 1 of both."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    pool = {"latent": latent_pool(cfg, cfg.n_layers, n_blocks, block_size)}
    if cfg.has_indexer:
        n_full = sum(ix == "full" for _, ix in cfg.kinds)
        pool["index"] = jnp.zeros(
            (n_full, n_blocks, block_size, cfg.index_dim),
            cfg.activation_dtype())
    return pool


def _stored(x, cfg):
    """A cache row as the pool keeps it (`cfg.cache_round`)."""
    if cfg.cache_round == "int8":
        from ray_tpu.ops import quant
        q, s = quant.quantize_rows(x[..., None, :])
        x = (q.astype(jnp.float32) * s[..., None])[..., 0, :].astype(x.dtype)
    return x


def _flat_at(pool, layer: int, widx):
    """A layer's flat indices widx (nb * bs and beyond: dropped) as row
    numbers of the whole pool, layers and blocks flattened. The pool is
    written and read as one array of rows, in place when it is donated; a
    layer is never sliced out of it."""
    n_layers, nb, bs = pool.shape[:3]
    return jnp.where(widx < nb * bs, layer * nb * bs + widx,
                     n_layers * nb * bs)


def write_latent(latent, layer: int, row, widx, cfg):
    """Cache rows row [N, kv_rank + rope] into a layer of the latent
    pool."""
    words = latent.shape[-1]
    return sparse_latent.write_rows(
        latent.reshape(-1, 1, words),
        sparse_latent.pack_rows(row, words), _flat_at(latent, layer, widx),
        impl=cfg.sparse_impl).reshape(latent.shape)


def _write_index(index, layer: int, keys, widx):
    """Index keys [N, Di] into a layer of the index pool."""
    di = index.shape[-1]
    return index.reshape(-1, di).at[_flat_at(index, layer, widx)].set(
        keys.astype(index.dtype), mode="drop").reshape(index.shape)


# ---------------------------------------------------------------------------
# pieces of the layer
# ---------------------------------------------------------------------------

def project(h, lp, pos, cfg):
    """Normed h [N, D] at positions pos [N] -> (q_nope [N, H, nope],
    q_rope [N, H, rope], row [N, kv_rank + rope]: the cache row
    `[c_kv | k_rope]`)."""
    adt = cfg.activation_dtype()
    n = h.shape[0]
    if "w_q" in lp:
        q = mm(h, lp["w_q"], adt)
    else:
        c_q = rms_norm(mm(h, lp["wq_a"], adt), lp["q_norm_scale"], cfg.eps)
        q = mm(c_q, lp["wq_b"], adt)
    q = q.reshape(n, cfg.n_heads, -1)
    q_nope = q[..., :cfg.nope_dim]
    q_rope = rope_pairs(q[..., cfg.nope_dim:], pos, cfg.rope_theta)
    kv = mm(h, lp["wkv_a"], adt)
    c_kv = rms_norm(kv[:, :cfg.kv_rank], lp["kv_norm_scale"], cfg.eps)
    k_rope = rope_pairs(kv[:, cfg.kv_rank:], pos, cfg.rope_theta)
    return q_nope, q_rope, _stored(
        jnp.concatenate([c_kv, k_rope], -1), cfg)


def _index_parts(x, lp, pos, cfg):
    """The indexer of a "full" layer from the residual x [N, D]: -> (q_I
    [N, J, Di] f32, k_I [N, Di] (the cached index key, in the pool's
    type), w [N, J] f32). In float32 throughout, from its own norm of x
    and its own low-rank query: which positions are selected is a
    discrete choice, and the activations' 8 bits would flip it at the
    threshold far more often than float32 does."""
    f32 = jnp.float32
    n, rp = x.shape[0], cfg.rope_dim

    def mm32(a, w):
        # three bfloat16 passes on the MXU: 2^-16, far under the cached
        # key's own rounding
        return jnp.einsum("...d,df->...f", a, w.astype(f32),
                          precision=jax.lax.Precision.HIGH)

    def turned(a):
        return jnp.concatenate([rope_pairs(a[..., :rp], pos, cfg.rope_theta),
                                a[..., rp:]], -1)

    h = rms_norm(x.astype(f32), lp["attn_norm_scale"], cfg.eps)
    c_q = rms_norm(mm32(h, lp["wq_a"]), lp["q_norm_scale"], cfg.eps)
    q_i = turned(mm32(c_q, lp["wi_q"]).reshape(
        n, cfg.index_heads, cfg.index_dim))
    k_i = turned(layer_norm(mm32(h, lp["wi_k"]), lp["ik_norm_scale"],
                            cfg.eps, lp["ik_norm_bias"]))
    w = mm32(h, lp["wi_w"]) * (cfg.index_heads ** -0.5
                               * cfg.index_dim ** -0.5)
    return q_i, _stored(k_i, cfg).astype(cfg.activation_dtype()), w


def _kv_up(lp, cfg, adt):
    """W_kvb as [kv_rank, H, nope + v]."""
    return lp["wkv_b"].astype(adt).reshape(
        cfg.kv_rank, cfg.n_heads, cfg.nope_dim + cfg.v_dim)


def _sm_scale(cfg) -> float:
    return (cfg.nope_dim + cfg.rope_dim) ** -0.5


def feed_forward(x, lp, cfg, live=None,
                 kernel: str = grouped_experts.EXPERTS_GROUPED,
                 every_load: bool = False):
    """x += the layer's feed-forward; -> (x, expert counts or None)."""
    adt = cfg.activation_dtype()
    with jax.named_scope(FFN):
        h2 = rms_norm(x, lp["ffn_norm_scale"], cfg.eps)
        if "router" in lp:
            routed, shared, _, counts = expert_layer(
                h2, lp, cfg.experts, adt, live, kernel, every_load)
            return x + routed + shared, counts
        return x + gated_mlp(h2, lp, adt, jnp.float32)[0], None


def _counts(cfg, pos, live, expert_counts, chunk_block: int = 0):
    """The int32 vector a program returns: `COUNTS`, then the held
    experts' loads. pos [N]: each query's position; live [N]: the rows
    that count. A prefill chunk (`chunk_block`: the positions a step of
    its loops over the context takes) adds its "full" layers to the chunk
    selections, and to the chunk thresholds where `_prefill_select` took
    one (its context is past the top-k), and counts the context blocks
    that `latent_chunk_attend` walked, every layer's; a decode step adds
    to none."""
    chunk = chunk_block > 0
    n_full = sum(ix == "full" for _, ix in cfg.kinds)
    topk = cfg.index_topk or 0
    scanned = jnp.sum(jnp.where(live, pos + 1, 0))
    selected = jnp.sum(jnp.where(live, jnp.minimum(pos + 1, topk), 0))
    last = _last(pos, live)
    past = last >= topk
    walked = last // chunk_block + 1 if chunk else 0
    experts = expert_totals(expert_counts, 2 + cfg.held_count)
    return jnp.concatenate([
        jnp.stack([scanned * n_full, selected * n_full,
                   jnp.int32(n_full), jnp.int32(cfg.n_layers - n_full),
                   jnp.int32(n_full * chunk), n_full * chunk * past,
                   cfg.n_layers * walked,
                   experts[0], experts[1]]).astype(jnp.int32),
        experts[2:].astype(jnp.int32)])


# ---------------------------------------------------------------------------
# whole sequence (tests, and the share test)
# ---------------------------------------------------------------------------

def _order_keys(scores):
    """float32 -> the int32 whose signed order is the float's: a
    negative's low 31 bits are flipped. -inf is the smallest key of a
    number and -0.0 lies under +0.0, the order `jax.lax.top_k` sorts by."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _select_dense(scores, valid, k: int, block: int | None = None,
                  n_blocks=1):
    """scores [N, S] f32 with -inf where not `valid` -> bool [N, S]: the
    k largest of each row (all valid ones while there are no more). Of
    equal scores at the threshold the earliest positions are taken, as
    `jax.lax.top_k` takes them in a decode step: the same set either
    way. Nothing past the first `n_blocks` blocks of `block` columns is
    valid, and the mask is made for those blocks alone (one block of all
    S where none is given).

    The threshold is counted, not sorted for: the row's k-th largest key
    is the largest t with count(key >= t) >= k, fixed from its top bit
    down, one compare and one row sum a bit. It starts as the smallest
    int32; the bit under trial is flipped (the sign bit to 0, every other
    to 1) and stays so where k keys still reach it. 32 passes whatever
    the data, exact, and no index is carried along. A pass reads the
    whole width: on the chip the keys stay in VMEM across the loop (7 us
    a pass of [512, 16384]), and a pass that looped over the live blocks
    alone took 16 at a third of the width (PERF.md, PR 46)."""
    n, s = scores.shape
    block = block or s
    k = min(k, s)
    keys = _order_keys(scores)

    def fix(i, kth):
        trial = kth ^ (jnp.int32(1) << (31 - i))
        reach = jnp.sum(keys >= trial, -1, keepdims=True, dtype=jnp.int32)
        return jnp.where(reach >= k, trial, kth)

    kth = jax.lax.fori_loop(
        0, 32, fix, jnp.full((n, 1), jnp.iinfo(jnp.int32).min, jnp.int32))
    need = k - jnp.sum(keys > kth, -1, keepdims=True, dtype=jnp.int32)

    def pick(j, carry):
        seen, out = carry
        key = jax.lax.dynamic_slice_in_dim(keys, j * block, block, axis=1)
        ties = key == kth
        seen = seen + jnp.cumsum(ties, -1, dtype=jnp.int32)
        took = jax.lax.dynamic_slice_in_dim(valid, j * block, block, axis=1) \
            & ((key > kth) | (ties & (seen <= need)))
        return seen[:, -1:], jax.lax.dynamic_update_slice_in_dim(
            out, took, j * block, axis=1)

    return jax.lax.fori_loop(
        0, n_blocks, pick,
        (jnp.zeros((n, 1), jnp.int32), jnp.zeros((n, s), bool)))[1]


def attend_full(q_nope, q_rope, row, selected, lp, cfg):
    """Expanded-head latent attention of a whole sequence over its own
    rows [T, kv_rank + rope], masked to `selected` bool [T, T]:
    -> [T, H, v] in the activation type."""
    adt = cfg.activation_dtype()
    c_kv, k_rope = row[:, :cfg.kv_rank], row[:, cfg.kv_rank:]
    kv = jnp.einsum("sc,chd->shd", c_kv, _kv_up(lp, cfg, adt),
                    preferred_element_type=jnp.float32).astype(adt)
    s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :cfg.nope_dim],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                      preferred_element_type=jnp.float32))
    p = jax.nn.softmax(jnp.where(selected[None], s * _sm_scale(cfg),
                                 NEG_INF), -1)
    return jnp.einsum("hqk,khd->qhd", p.astype(adt), kv[..., cfg.nope_dim:],
                      preferred_element_type=jnp.float32).astype(adt)


def forward(params, tokens, cfg: LatentSparseMoEConfig, selections=None):
    """tokens [B, T] -> float32 logits [B, T, V], no cache: every layer
    dense over its own sequence, masked to the selection. With
    `selections`, a list, each "full" layer's bool [T, T] is appended."""
    adt = cfg.activation_dtype()

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        causal = pos[None, :] <= pos[:, None]
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        selected = None if cfg.has_indexer else causal
        for lp in params["layers"]:
            with jax.named_scope(MIXER):
                h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
                q_nope, q_rope, row = project(h, lp, pos, cfg)
                if "wi_q" in lp:
                    q_i, k_i, w = _index_parts(x, lp, pos, cfg)
                    s = sparse_latent.index_dots(q_i, k_i, "qjd,kd->qjk")
                    scores = jnp.where(causal, jnp.einsum(
                        "qj,qjk->qk", w, jax.nn.relu(s)), -jnp.inf)
                    selected = _select_dense(scores, causal, cfg.index_topk)
                    if selections is not None:
                        selections.append(selected)
                att = attend_full(q_nope, q_rope, row, selected, lp, cfg)
                x = x + mm(att.reshape(t, -1), lp["w_out"], adt)
            x, _ = feed_forward(x, lp, cfg)
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_ln_scale"], cfg.eps),
                           params["head"], adt)

    if selections is not None:          # the list is filled outside a map
        return jnp.stack([one(seq) for seq in tokens])
    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# training: layers without an indexer, whole sequences, expanded heads
# ---------------------------------------------------------------------------

def init_params(key, cfg: LatentSparseMoEConfig):
    """float32 master parameters of the layers that run, cast at use:
    normal, fan-in^-1/2, residual outputs x (2 x layers)^-1/2, embedding
    `EMBED_INIT`, norm scales 1, `router_bias` 0 (the tree of the module's
    header; a layer with an indexer is served from stored weights and is
    not made here)."""
    if cfg.has_indexer:
        raise NotImplementedError("init_params makes layers without an "
                                  "indexer (the ones that are trained)")
    d, nh, rkv = cfg.d_model, cfg.n_heads, cfg.kv_rank
    qk = cfg.nope_dim + cfg.rope_dim
    fs = cfg.expert_ff * cfg.shared_experts
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 12 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for mlp, _ in cfg.kinds:
        lp = {"attn_norm_scale": ones(d), "ffn_norm_scale": ones(d),
              "wkv_a": normal((d, rkv + cfg.rope_dim), d ** -0.5),
              "kv_norm_scale": ones(rkv),
              "wkv_b": normal((rkv, nh * (cfg.nope_dim + cfg.v_dim)),
                              rkv ** -0.5),
              "w_out": normal((nh * cfg.v_dim, d),
                              (nh * cfg.v_dim) ** -0.5 * residual)}
        if cfg.q_rank is None:
            lp["w_q"] = normal((d, nh * qk), d ** -0.5)
        else:
            lp.update(wq_a=normal((d, cfg.q_rank), d ** -0.5),
                      q_norm_scale=ones(cfg.q_rank),
                      wq_b=normal((cfg.q_rank, nh * qk), cfg.q_rank ** -0.5))
        if mlp == "dense":
            lp.update(w_gate=normal((d, cfg.d_ff), d ** -0.5),
                      w_up=normal((d, cfg.d_ff), d ** -0.5),
                      w_down=normal((cfg.d_ff, d),
                                    cfg.d_ff ** -0.5 * residual))
        else:
            experts = (cfg.held_count, cfg.expert_ff, d)
            lp.update(
                router=normal((d, cfg.router_width), d ** -0.5),
                router_bias=jnp.zeros((cfg.router_width,), jnp.float32),
                we_gate=normal(experts, d ** -0.5),
                we_up=normal(experts, d ** -0.5),
                we_down=normal(experts, cfg.expert_ff ** -0.5 * residual),
                ws_gate=normal((d, fs), d ** -0.5),
                ws_up=normal((d, fs), d ** -0.5),
                ws_down=normal((fs, d), fs ** -0.5 * residual))
        layers.append(lp)
    return {"embed": normal((cfg.vocab_size, d), EMBED_INIT),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


def param_logical_axes(cfg: LatentSparseMoEConfig):
    """Every leaf whole on every device: one chip's share is trained on
    one chip (the expert exchange over chips is not built)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return jax.tree.map(lambda a: (None,) * a.ndim, shapes)


def is_router_bias(params):
    """The tree of bools that is true at the `router_bias` leaves: the
    ones no optimizer moves."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) == "router_bias",
        params)


def _train_attention(h, lp, pos, cfg):
    """Latent attention of normed h [B, T, D] over each sequence's
    earlier positions, keys and values rebuilt for every head, through
    W_o: -> [B, T, D]."""
    from ray_tpu.ops.flash_attention import flash_attention
    adt = cfg.activation_dtype()
    b, t, d = h.shape
    nh = cfg.n_heads
    q_nope, q_rope, row = project(h.reshape(b * t, d), lp, pos, cfg)
    kv = jnp.einsum("sc,chd->shd", row[:, :cfg.kv_rank], _kv_up(lp, cfg, adt),
                    preferred_element_type=jnp.float32).astype(adt)
    k_rope = jnp.broadcast_to(row[:, None, cfg.kv_rank:],
                              (b * t, nh, cfg.rope_dim))
    q = jnp.concatenate([q_nope, q_rope], -1).reshape(b, t, nh, -1)
    k = jnp.concatenate([kv[..., :cfg.nope_dim], k_rope],
                        -1).reshape(b, t, nh, -1)
    v = kv[..., cfg.nope_dim:].reshape(b, t, nh, cfg.v_dim)
    att = flash_attention(q, k, v, True, cfg.flash_block_q,
                          cfg.flash_block_kv)
    return mm(att.reshape(b, t, nh * cfg.v_dim), lp["w_out"], adt)


def _train_layer(x, lp, pos, cfg):
    """-> (x [B, T, D], the layer's expert counts [2 + router_width] i32,
    None for a dense layer)."""
    b, t, d = x.shape
    with jax.named_scope(MIXER):
        x = x + _train_attention(
            rms_norm(x, lp["attn_norm_scale"], cfg.eps), lp, pos, cfg)
    x, counts = feed_forward(
        x.reshape(b * t, d), lp, cfg,
        kernel=grouped_experts.EXPERTS_GROUPED_TRAIN, every_load=True)
    return x.reshape(b, t, d), counts


def forward_features(params, tokens, cfg: LatentSparseMoEConfig, mesh=None):
    """tokens [B, T] -> (final-normed activations [B, T, D] in the
    activation type: everything but the head, which the fused loss folds
    in; counts [sparse layers, 2 + router_width] i32: each sparse layer's
    pairs routed here, pairs routed anywhere, and every expert's load).
    Each layer is a `jax.checkpoint` of its own that keeps its input and
    the flash forward's output and logsumexp, so that its backward runs
    the dQ and dK/dV kernels without the forward again; everything else of
    the layer is made again (at the cell's size 4.5 GB are too little for
    the matmuls' outputs)."""
    if cfg.has_indexer:
        raise NotImplementedError("training a layer with an indexer is "
                                  "not built: the selection has no "
                                  "backward pass")
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("one chip's share is trained on one "
                                  "chip: the expert exchange over chips "
                                  "is not built")
    from ray_tpu.ops.flash_attention import SAVED_NAMES
    adt = cfg.activation_dtype()
    b, t = tokens.shape
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32), b)
    layer = jax.checkpoint(
        lambda x, lp: _train_layer(x, lp, pos, cfg),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens]
    counts = []
    for lp in params["layers"]:
        x, c = layer(x, lp)
        if c is not None:
            counts.append(c)
    with jax.named_scope(HEAD):
        counts = jnp.stack(counts) if counts else jnp.zeros(
            (0, 2 + cfg.router_width), jnp.int32)
        return rms_norm(x, params["final_ln_scale"], cfg.eps), counts


def update_router_bias(params, counts, cfg: LatentSparseMoEConfig):
    """The step's own part of the router: every sparse layer's
    `router_bias` moves by `BIAS_UPDATE_RATE` x sign(mean load - load)
    over the router's whole width (the auxiliary-loss-free balancing of
    the DeepSeek-V3 report), from `forward_features`' counts. -> (params,
    the step's expert metrics)."""
    layers, row = [], 0
    for lp in params["layers"]:
        if "router" in lp:
            load = counts[row, 2:].astype(jnp.float32)
            bias = lp["router_bias"] + BIAS_UPDATE_RATE * jnp.sign(
                jnp.mean(load) - load).astype(lp["router_bias"].dtype)
            lp, row = {**lp, "router_bias": bias}, row + 1
        layers.append(lp)
    held = counts[:, 2 + cfg.held_from:2 + cfg.held_from + cfg.held_count]
    return {**params, "layers": layers}, {
        "expert_pairs_here": jnp.sum(counts[:, 0]),
        "expert_pairs_routed": jnp.sum(counts[:, 1]),
        "expert_load_max": jnp.max(held),
        "expert_load_mean": jnp.mean(held.astype(jnp.float32)),
        "router_bias_abs_max": jnp.max(jnp.stack(
            [jnp.max(jnp.abs(lp["router_bias"])) for lp in layers
             if "router" in lp])),
    }


# ---------------------------------------------------------------------------
# paged prefill of one chunk
# ---------------------------------------------------------------------------

def _prefill_select(q_i, w, index, layer: int, table, positions, valid, cfg):
    """A chunk's selection over the cached context: q_i [C, J, Di],
    w [C, J], index [L_full, nb, bs, Di] -> bool [C, S]. Plain
    `jax.numpy`. A chunk whose last position is under the top-k selects
    every live position and scores nothing. Any other gathers the
    context's keys through the table, scores them a block of positions at
    a time, only as far as the chunk's last position, and makes the mask
    for those blocks alone."""
    from ray_tpu.ops.decode_attention import gather_kv_pages
    _, nb, bs, di = index.shape
    c, s = q_i.shape[0], table.shape[0] * bs
    sb = sparse_latent.context_block(s, bs)
    last = _last(positions, valid)
    live = every_earlier(positions, valid, s)

    def over_the_context():
        keys = gather_kv_pages(index.reshape(-1, bs, di),
                               table[None] + layer * nb)[0]     # [S, Di]

        def block(j, scores):
            ks = jax.lax.dynamic_slice_in_dim(keys, j * sb, sb)
            dots = sparse_latent.index_dots(q_i, ks, "qjd,sd->qjs")
            return jax.lax.dynamic_update_slice_in_dim(scores, jnp.where(
                jax.lax.dynamic_slice_in_dim(live, j * sb, sb, axis=1),
                jnp.einsum("qj,qjs->qs", w, jax.nn.relu(dots)), -jnp.inf),
                j * sb, axis=1)

        n_blocks = last // sb + 1
        scores = jax.lax.fori_loop(0, n_blocks, block,
                                   jnp.full((c, s), -jnp.inf, jnp.float32))
        return _select_dense(scores, live, cfg.index_topk, sb, n_blocks)

    return jax.lax.cond(last < cfg.index_topk, lambda: live,
                        over_the_context)


def prefill_attend(q_nope, q_rope, latent, layer: int, table, positions,
                   valid, selected, lp, cfg):
    """Absorbed latent attention of a chunk's queries, q_nope [C, H, nope]
    and q_rope [C, H, rope], over the selected positions of the cached
    context (`decode_attend`'s form: the queries through the key
    up-projection, scored against the stored rows only as far as the
    chunk's last position, the mix of rows through the value
    up-projection). -> [C, H * v]."""
    adt = cfg.activation_dtype()
    up = _kv_up(lp, cfg, adt)
    q_abs = jnp.einsum("qhd,chd->hqc", q_nope, up[..., :cfg.nope_dim],
                       preferred_element_type=jnp.float32).astype(adt)
    q = jnp.concatenate([q_abs, q_rope.transpose(1, 0, 2)], -1) \
        * jnp.asarray(_sm_scale(cfg), adt)
    mixed = sparse_latent.latent_chunk_attend(
        q, latent, layer, table, selected, _last(positions, valid),
        mixed=cfg.kv_rank, dtype=adt, impl=cfg.sparse_impl)
    # heads first, then a transpose: asked for `qhd`, XLA's CPU dot picks
    # its loop by the chunk's bucket and a live row's bits follow the
    # padding
    att = jnp.einsum("hqc,chd->hqd", mixed, up[..., cfg.nope_dim:],
                     preferred_element_type=jnp.float32).astype(adt)
    return att.transpose(1, 0, 2).reshape(-1, cfg.n_heads * cfg.v_dim)


def _last(positions, valid):
    """A chunk's last live position."""
    return jnp.max(jnp.where(valid, positions, 0))


def every_earlier(positions, valid, s: int):
    """The selection of a layer without an indexer: bool [C, S], a live
    query's row true at every position up to its own."""
    return (jnp.arange(s, dtype=jnp.int32)[None, :] <= positions[:, None]) \
        & valid[:, None]


def _pool_of(latent, index):
    return {"latent": latent} if index is None else {
        "latent": latent, "index": index}


def prefill(params, tokens, cache, cfg: LatentSparseMoEConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of paged prefill of one sequence (`gpt.prefill_paged`'s
    contract): tokens [1, C] at positions start .. start + length - 1;
    every layer's cache row, and a "full" layer's index key, is written
    through `block_table` before the layer attends. -> (logits [1, V] f32
    of the chunk's last real position, cache, counts)."""
    c = tokens.shape[1]
    if tokens.shape[0] != 1:
        raise ValueError(f"paged prefill wants tokens [1, C], got batch "
                         f"{tokens.shape[0]}")
    adt = cfg.activation_dtype()
    nb, bs = cache["latent"].shape[1], cache["latent"].shape[2]
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(c if length is None else length, jnp.int32)
    table = jnp.asarray(block_table, jnp.int32)
    latent, index = cache["latent"], cache.get("index")
    with jax.named_scope(EMBED):
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        widx = jnp.where(valid,
                         table[positions // bs] * bs + positions % bs,
                         nb * bs)
        x = params["embed"].astype(adt)[tokens[0]]
        selected = None if cfg.has_indexer else every_earlier(
            positions, valid, table.shape[0] * bs)
    full, expert_counts = 0, []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
            q_nope, q_rope, row = project(h, lp, positions, cfg)
            latent = write_latent(latent, i, row, widx, cfg)
            if "wi_q" in lp:
                q_i, k_i, w = _index_parts(x, lp, positions, cfg)
                index = _write_index(index, full, k_i, widx)
                selected = _prefill_select(q_i, w, index, full, table,
                                           positions, valid, cfg)
                full += 1
            att = prefill_attend(q_nope, q_rope, latent, i, table,
                                  positions, valid, selected, lp, cfg)
            x = x + mm(att, lp["w_out"], adt)
        x, counts = feed_forward(
            x, lp, cfg, valid, grouped_experts.EXPERTS_GROUPED_PREFILL)
        if counts is not None:
            expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        return (unembed(last, params["head"], adt), _pool_of(latent, index),
                _counts(cfg, positions, valid, expert_counts,
                        sparse_latent.context_block(table.shape[0] * bs,
                                                    bs)))


# ---------------------------------------------------------------------------
# paged decode step
# ---------------------------------------------------------------------------

def select_rows(scores, tables, pos, cfg, block_size: int):
    """A decode step's selection from the indexer's scores [B, S] (-inf
    past pos): -> (rows [B, K] i32 physical row numbers, the live ones
    first and 0 after; count [B] i32; idx [B, K] the logical positions)."""
    k = min(cfg.index_topk, scores.shape[1])
    _, idx = jax.lax.top_k(scores, k)
    count = jnp.minimum(pos + 1, k).astype(jnp.int32)
    live = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    blocks = jnp.take_along_axis(tables, idx // block_size, axis=1)
    rows = jnp.where(live, blocks * block_size + idx % block_size, 0)
    return rows.astype(jnp.int32), count, jnp.where(live, idx, -1)


def decode_write_index(latent, tables, pos):
    """Where a decode step's rows go: each stream's flat index of position
    pos[b] in a layer of the latent pool through tables [B, max_blocks]
    (past the table: dropped)."""
    nb, bs = latent.shape[1], latent.shape[2]
    mb = tables.shape[1]
    blk = jnp.take_along_axis(
        tables, jnp.minimum(pos // bs, mb - 1)[:, None], axis=1)[:, 0]
    return jnp.where(pos < mb * bs, blk * bs + pos % bs, nb * bs)


def decode_attend(q_nope, q_rope, latent, layer: int, tables, pos, lp, cfg,
                  rows=None, count=None):
    """Absorbed latent attention of one query a stream, q_nope [B, H, nope]
    and q_rope [B, H, rope], over layer `layer` of the latent pool: over
    the selected rows `rows[b, :count[b]]` (a layer's numbers within its
    own layer), or with none given over every cached row of the stream's
    pages up to pos[b]. -> [B, H, v] in the activation type."""
    adt = cfg.activation_dtype()
    nb, bs = latent.shape[1], latent.shape[2]
    up = _kv_up(lp, cfg, adt)
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, up[..., :cfg.nope_dim],
                       preferred_element_type=jnp.float32).astype(adt)
    q = sparse_latent.split_query(
        jnp.concatenate([q_abs, q_rope], -1) * jnp.asarray(
            _sm_scale(cfg), adt), cfg.row_words)
    if rows is None:
        out = sparse_latent.latent_decode(
            q, latent, layer, tables, pos + 1, dtype=adt,
            values=cfg.row_values, kv_rank=cfg.kv_rank, impl=cfg.sparse_impl)
    else:
        out = sparse_latent.sparse_latent_decode(
            q, latent.reshape(-1, 1, cfg.row_words), rows + layer * nb * bs,
            count, dtype=adt, impl=cfg.sparse_impl)
    mixed = sparse_latent.join_parts(out, cfg.kv_rank).astype(adt)
    return jnp.einsum("bhc,chd->bhd", mixed, up[..., cfg.nope_dim:],
                      preferred_element_type=jnp.float32).astype(adt)


def decode(params, tokens, cache, pos, tables,
           cfg: LatentSparseMoEConfig, mesh=None, selections=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B], blocks named by tables
    [B, max_blocks]. Absorbed attention over the rows a "full" layer's
    indexer selected in this step. Idle rows point their table at the
    trash block; they compute garbage nobody reads and count nothing.
    -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    nb, bs = cache["latent"].shape[1], cache["latent"].shape[2]
    b = tokens.shape[0]
    pos = pos.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    latent, index = cache["latent"], cache.get("index")
    with jax.named_scope(EMBED):
        widx = decode_write_index(cache["latent"], tables, pos)
        live = tables[:, 0] > 0
        x = params["embed"].astype(adt)[tokens]
    rows = count = None
    full, expert_counts = 0, []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            h = rms_norm(x, lp["attn_norm_scale"], cfg.eps)
            q_nope, q_rope, row = project(h, lp, pos, cfg)
            latent = write_latent(latent, i, row, widx, cfg)
            if "wi_q" in lp:
                q_i, k_i, w = _index_parts(x, lp, pos, cfg)
                index = _write_index(index, full, k_i, widx)
                scores = sparse_latent.index_scores(
                    q_i, w, index.reshape(-1, bs, cfg.index_dim),
                    tables + full * nb, pos, impl=cfg.sparse_impl)
                rows, count, idx = select_rows(scores, tables, pos, cfg, bs)
                if selections is not None:
                    selections.append(idx)
                full += 1
            att = decode_attend(q_nope, q_rope, latent, i, tables, pos, lp,
                                cfg, rows, count)
            x = x + mm(att.reshape(b, -1), lp["w_out"], adt)
        x, counts = feed_forward(x, lp, cfg, live)
        if counts is not None:
            expert_counts.append(counts)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_ln_scale"], cfg.eps)
        return (unembed(x, params["head"], adt), _pool_of(latent, index),
                _counts(cfg, pos, live, expert_counts))


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block,
    counts=lambda cfg, totals: summarize(COUNTS, totals, cfg.held_count))
TRAINING = TrainingFamily(
    init_params=init_params, param_logical_axes=param_logical_axes,
    forward_features=forward_features, head="head",
    aux_update=update_router_bias, frozen=is_router_bias)
