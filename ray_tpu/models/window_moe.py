"""A decoder whose attention layers differ in how far back they keep:
softmax attention with grouped key-value heads, window layers and full
layers in a period, a parallel block (one norm a layer feeds attention
and experts alike), and routed experts beside shared ones that are
averaged. The layer that `command-a-plus-05-2026`'s language model names
(`cohere2_moe`): published layer `l` is a window layer where
`layer_types[l]` says "sliding_attention" (3 in 4) and a full layer
otherwise.

The layer (`n = LayerNorm(x)`: mean subtracted, no bias, one scale; Hq
query heads over Hkv key-value heads of d, query head `h` reads
key-value head `h // (Hq / Hkv)`):

    q, k, v = W_q n, W_k n, W_v n
    window layer: rotary on q and k (interleaved pairs, all d dims);
        position i attends j with i - window < j <= i
    full layer: no positional term; j <= i
    a = W_o concat_h softmax(q_h . k / sqrt(d)) v
    routed = sum over the 8 largest of s = sigmoid(W_r n), weights s_e /
        sum of the chosen, of the held experts' SwiGLU(n)
    shared = mean of the shared experts' SwiGLU(n)
    y = x + a + routed + shared

After the last layer a LayerNorm, then the tied head over the rows of
the embedding held here. This module is new and `models/gpt.py` is not
widened: gpt's layers are alike and scanned, its block is sequential,
its norm an RMS norm, its pool one kind; nothing but the block moves
would be shared.

**What the engine holds for this family**: pages of two kinds under one
table (`ServingFamily.bounded_keys`). `"k"`, `"v"` `[L_full, pages, Hkv,
bs, d]` are the full layers' pages, which grow with the sequence;
`"kw"`, `"vw"` `[L_window, pages, Hkv, bs, d]` are the window layers'
pages, of which a request never needs more than the window and a chunk
hold. A page is head-major: one key-value head's `bs` rows lie together,
so `ops.decode_attention`'s kernel fetches a head's page once for the
query heads that read it. The table is the full layers' columns, then as
many for the window layers; column `j` of either half names the page of
positions `j * bs ..`, wherever the engine keeps it. Prefill writes a
chunk's rows before it attends, a chunk bucket's padding is written
nowhere, and decode's idle rows (table all 0) rewrite the trash pages.

Experts: `models/blocks.py`'s `routing` and `expert_layer`
(`ops/grouped_experts.py`) over the held experts, without groups or
bias. What the absent experts would add is left out.

Parameters: the tree `benchmarks/refs/window_moe.py` documents.
`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (Experts, copy_block, expert_layer,
                                   gather_block, layer_norm, mm, rope_pairs,
                                   scatter_block, summarize, unembed,
                                   write_chunk, write_rows)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import grouped_experts, quant

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits; the held experts' loads follow
COUNTS = ("window_rows_read", "full_rows_read", "expert_tokens_here",
          "expert_tokens_routed")
BOUNDED_KEYS = ("kw", "vw")         # the pool's arrays of window pages
EMBED_INIT = 0.02


@dataclass(frozen=True)
class WindowMoEConfig:
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
    expert_ff: int = 32
    shared_experts: int = 4
    router_width: int = 8
    experts_per_token: int = 2
    held_from: int = 0
    held_count: int = 8
    norm_topk: bool = True
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    eps: float = 1e-5
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    attn_impl: str = "auto"          # auto | pallas | jax (the four kernels)
    sparse_impl: str = "auto"        # auto | pallas | jax (the experts)
    # test-only, for the benchmark's controls. "int8" rounds a cache row,
    # as it is written, to the int8 grid of its own largest magnitude and
    # keeps that value in the pool's type (an int8 pool's numbers, not
    # its bytes). `full_window` cuts the full layers at so many
    # positions, as if they were window layers: the window wrong.
    # `expert_round` is `blocks.expert_layer`'s: the routed
    # experts' inputs and matrices on the float8_e4m3fn grid (a probe,
    # not one of the cell's controls: with an eighth of the experts held
    # it reads inside the sound runs' range, PERF.md section 6, PR 44)
    cache_round: str = "none"        # none | int8
    full_window: int | None = None
    expert_round: str = "none"       # none | float8_e4m3fn

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - {"window", "full"} \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("a layer is a window or a full layer, and the "
                             "query heads divide over the key-value heads")
        if self.cache_round not in ("none", "int8"):
            raise ValueError(f"unknown cache_round {self.cache_round!r}")
        if self.expert_round not in ("none", "float8_e4m3fn"):
            raise ValueError(f"unknown expert_round {self.expert_round!r}")

    @property
    def kinds(self) -> tuple:
        """"window" or "full", one a layer that runs."""
        lo = self.first_layer
        return self.layer_types[lo:lo + self.n_layers]

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def experts(self) -> Experts:
        return Experts(self.router_width, self.experts_per_token,
                       self.norm_topk, self.held_from,
                       expert_round=self.expert_round, impl=self.sparse_impl)

    @property
    def family(self):
        return FAMILY._replace(bounded_tokens=self.window)


def from_published(*, hidden_size, num_hidden_layers, num_attention_heads,
                   num_key_value_heads, head_dim, sliding_window, layer_types,
                   intermediate_size, num_shared_experts, num_experts,
                   num_experts_per_tok, norm_topk_prob, layer_norm_eps,
                   rope_theta, max_position_embeddings, logit_scale=1.0,
                   layers_from=0,
                   experts_held_from=0, published=None,
                   **same) -> WindowMoEConfig:
    """The configuration file's published keys -> `WindowMoEConfig`
    (`benchmarks/configs/command-a-plus.json`, `program.constructor`).
    `num_experts` is how many experts are held here; the router's width
    is `published["num_experts"]` where a share is run."""
    return WindowMoEConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
        head_dim=head_dim, window=sliding_window,
        layer_types=["window" if t == "sliding_attention" else "full"
                     for t in layer_types],
        first_layer=layers_from, expert_ff=intermediate_size,
        shared_experts=num_shared_experts,
        router_width=(published or {}).get("num_experts", num_experts),
        experts_per_token=num_experts_per_tok, held_from=experts_held_from,
        held_count=num_experts, norm_topk=norm_topk_prob,
        eps=layer_norm_eps, rope_theta=float(rope_theta),
        logit_scale=float(logit_scale),
        max_seq_len=max_position_embeddings, **same)


def init_params(key, cfg: WindowMoEConfig, logit_std: float = 2.4):
    """Float32 leaves, for tests; the tree `benchmarks/refs/window_moe.py`
    documents. W_q and W_k are scaled so that a score over random keys
    has the standard deviation `logit_std`: a query's weight then lies on
    some tens of keys, as a trained layer's does, and a wrong window
    shows (with plain fan-in scales attention is nearly uniform)."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f, fs = cfg.expert_ff, cfg.expert_ff * cfg.shared_experts
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 1 + 12 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    layers = [{
        "norm_scale": jnp.ones((d,), jnp.float32),
        "w_q": normal((d, hq * hd), d ** -0.5 * logit_std ** 0.5),
        "w_k": normal((d, hkv * hd), d ** -0.5 * logit_std ** 0.5),
        "w_v": normal((d, hkv * hd), d ** -0.5),
        "w_out": normal((hq * hd, d), (hq * hd) ** -0.5 * residual),
        "router": normal((d, cfg.router_width), d ** -0.5),
        "we_gate": normal((cfg.held_count, f, d), d ** -0.5),
        "we_up": normal((cfg.held_count, f, d), d ** -0.5),
        "we_down": normal((cfg.held_count, f, d), f ** -0.5 * residual),
        "ws_gate": normal((d, fs), d ** -0.5),
        "ws_up": normal((d, fs), d ** -0.5),
        "ws_down": normal((fs, d), f ** -0.5 * residual),
    } for _ in range(cfg.n_layers)]
    return {"embed": normal((cfg.vocab_size, d), EMBED_INIT),
            "final_norm_scale": jnp.ones((d,), jnp.float32),
            "layers": layers}


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def init_pool(cfg: WindowMoEConfig, n_blocks: int, block_size: int,
              mesh=None, *, bounded_blocks: int):
    """{"k", "v"} with `n_blocks` pages on axis 1 for the full layers and
    {"kw", "vw"} with `bounded_blocks` for the window layers, zero-filled,
    head-major; page 0 of each kind the trash page."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    n_window = cfg.kinds.count("window")
    adt = cfg.activation_dtype()

    def pages(layers, n):
        return jnp.zeros((layers, n, cfg.n_kv_heads, block_size,
                          cfg.head_dim), adt)

    # four arrays of their own: the engine donates each
    n_full = cfg.n_layers - n_window
    return {"k": pages(n_full, n_blocks), "v": pages(n_full, n_blocks),
            "kw": pages(n_window, bounded_blocks),
            "vw": pages(n_window, bounded_blocks)}


def _stored(rows, cfg):
    """Cache rows [N, Hkv, d] as the pool keeps them (`cfg.cache_round`)."""
    if cfg.cache_round == "int8":
        q, s = quant.quantize_rows(rows)
        rows = (q.astype(jnp.float32) * s[..., None]).astype(rows.dtype)
    return rows


# ---------------------------------------------------------------------------
# pieces of the layer
# ---------------------------------------------------------------------------

def _qkv(n, lp, kind, pos, cfg):
    """Normed n [N, D] at positions pos [N] -> q [N, Hq, d], k, v [N,
    Hkv, d] in the activation type, rotary applied in a window layer."""
    adt = cfg.activation_dtype()
    rows = n.shape[0]
    q = mm(n, lp["w_q"], adt).reshape(rows, cfg.n_heads, cfg.head_dim)
    k = mm(n, lp["w_k"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim)
    v = mm(n, lp["w_v"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim)
    if kind == "window":
        q, k = (rope_pairs(a, pos, cfg.rope_theta) for a in (q, k))
    return q, k, v


def _experts(n, lp, cfg, live, kernel):
    """-> (what the held experts and the shared ones add [N, D], counts).
    The shared experts' matrices lie side by side, so their sum is one
    gated MLP and their mean a quarter of it."""
    with jax.named_scope("routed_experts"):
        routed, shared, _, counts = expert_layer(
            n, lp, cfg.experts, cfg.activation_dtype(), live, kernel)
    with jax.named_scope("shared_experts"):
        shared = shared * (1.0 / cfg.shared_experts)
    return routed + shared.astype(routed.dtype), counts


def _window_of(kind, cfg):
    return cfg.window if kind == "window" else cfg.full_window


def _counts(window_rows, full_rows, expert_counts):
    experts = sum(expert_counts)
    return jnp.concatenate([
        jnp.stack([window_rows, full_rows, experts[0],
                   experts[1]]).astype(jnp.int32),
        experts[2:].astype(jnp.int32)])


def _rows_read(pos, live, cfg):
    """(rows a window layer, rows a full layer) that queries at `pos`
    have to read, summed over the live ones and the layers of each
    kind."""
    n_window = cfg.kinds.count("window")
    seen = jnp.where(live, pos + 1, 0)
    full = seen if cfg.full_window is None else jnp.minimum(
        seen, cfg.full_window)
    return (jnp.sum(jnp.minimum(seen, cfg.window)) * n_window,
            jnp.sum(full) * (cfg.n_layers - n_window))


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: WindowMoEConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: no
    cache, every score made and masked."""
    adt = cfg.activation_dtype()

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        live = jnp.ones((t,), bool)
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp, kind in zip(params["layers"], cfg.kinds):
            with jax.named_scope(MIXER):
                n = layer_norm(x, lp["norm_scale"], cfg.eps)
                q, k, v = _qkv(n, lp, kind, pos, cfg)
                att = da.reference_gqa_attention(
                    q[None], k[None], v[None], jnp.zeros((1,), jnp.int32),
                    _window_of(kind, cfg))[0]
                a = mm(att.reshape(t, -1), lp["w_out"], adt)
            with jax.named_scope(FFN):
                ff, _ = _experts(n, lp, cfg, live,
                                 grouped_experts.EXPERTS_GROUPED)
                x = x + a + ff
        with jax.named_scope(HEAD):
            return cfg.logit_scale * unembed(
                layer_norm(x, params["final_norm_scale"], cfg.eps),
                params["embed"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

POOLS = {"window": BOUNDED_KEYS, "full": ("k", "v")}   # a kind's K and V


def prefill(params, tokens, cache, cfg: WindowMoEConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of one sequence (`gpt.prefill_paged`'s contract): tokens
    [1, C] at positions start .. start + length - 1; `block_table` the
    full layers' columns, then the window layers'.
    -> (logits [1, V] f32 of the chunk's last real position, cache,
    counts)."""
    c = tokens.shape[1]
    if tokens.shape[0] != 1:
        raise ValueError(f"prefill wants tokens [1, C], got batch "
                         f"{tokens.shape[0]}")
    adt = cfg.activation_dtype()
    cache = dict(cache)
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(c if length is None else length, jnp.int32)
    table = jnp.asarray(block_table, jnp.int32)
    half = table.shape[0] // 2
    tables = {"full": table[:half], "window": table[half:]}
    with jax.named_scope(EMBED):
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        valid = offs < length
        x = params["embed"].astype(adt)[tokens[0]]
    at = {"window": 0, "full": 0}
    expert_counts = []
    for lp, kind in zip(params["layers"], cfg.kinds):
        with jax.named_scope(MIXER):
            n = layer_norm(x, lp["norm_scale"], cfg.eps)
            q, k, v = _qkv(n, lp, kind, positions, cfg)
            kk, vk = POOLS[kind]
            layer = at[kind]
            cache[kk] = write_chunk(cache[kk], layer, _stored(k, cfg),
                                    tables[kind], start, length)
            cache[vk] = write_chunk(cache[vk], layer, _stored(v, cfg),
                                    tables[kind], start, length)
            with jax.named_scope(f"{kind}_attention"):
                att = da.gqa_chunk_attention(
                    q, cache[kk], cache[vk], tables[kind], start, layer=layer,
                    window=_window_of(kind, cfg), impl=cfg.attn_impl)
            a = mm(att.reshape(c, -1), lp["w_out"], adt)
        with jax.named_scope(FFN):
            ff, counts = _experts(n, lp, cfg, valid,
                                  grouped_experts.EXPERTS_GROUPED_PREFILL)
            expert_counts.append(counts)
            x = x + a + ff
        at[kind] += 1
    with jax.named_scope(HEAD):
        x = layer_norm(x, params["final_norm_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        return (cfg.logit_scale * unembed(last, params["embed"], adt), cache,
                _counts(*_rows_read(positions, valid, cfg), expert_counts))


def decode(params, tokens, cache, pos, tables, cfg: WindowMoEConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B]; `tables` each row's full columns,
    then its window columns. Idle rows name the trash pages, rewrite them
    and count nothing. -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    cache = dict(cache)
    bs = cache["k"].shape[3]
    pos = pos.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    half = tables.shape[1] // 2
    tabs = {"full": tables[:, :half], "window": tables[:, half:]}
    with jax.named_scope(EMBED):
        live = jnp.any(tables > 0, -1)
        page = jnp.minimum(pos // bs, half - 1)[:, None]
        widx = {kind: jnp.where(
            pos < half * bs,
            jnp.take_along_axis(tab, page, 1)[:, 0] * bs + pos % bs,
            cache[POOLS[kind][0]].shape[1] * bs)
            for kind, tab in tabs.items()}
        x = params["embed"].astype(adt)[tokens]
    at = {"window": 0, "full": 0}
    expert_counts = []
    for lp, kind in zip(params["layers"], cfg.kinds):
        with jax.named_scope(MIXER):
            n = layer_norm(x, lp["norm_scale"], cfg.eps)
            q, k, v = _qkv(n, lp, kind, pos, cfg)
            kk, vk = POOLS[kind]
            layer = at[kind]
            cache[kk] = write_rows(cache[kk], layer, _stored(k, cfg),
                                   widx[kind])
            cache[vk] = write_rows(cache[vk], layer, _stored(v, cfg),
                                   widx[kind])
            with jax.named_scope(f"{kind}_attention"):
                att = da.gqa_decode_attention(
                    q, cache[kk], cache[vk], tabs[kind], pos, layer=layer,
                    window=_window_of(kind, cfg), impl=cfg.attn_impl)
            a = mm(att.reshape(att.shape[0], -1), lp["w_out"], adt)
        with jax.named_scope(FFN):
            ff, counts = _experts(n, lp, cfg, live,
                                  grouped_experts.EXPERTS_GROUPED)
            expert_counts.append(counts)
            x = x + a + ff
        at[kind] += 1
    with jax.named_scope(HEAD):
        x = layer_norm(x, params["final_norm_scale"], cfg.eps)
        return (cfg.logit_scale * unembed(x, params["embed"], adt), cache,
                _counts(*_rows_read(pos, live, cfg), expert_counts))


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, bounded_keys=BOUNDED_KEYS,
    counts=lambda cfg, totals: summarize(COUNTS, totals, cfg.held_count))
