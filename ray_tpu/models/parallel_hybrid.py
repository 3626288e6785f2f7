"""A decoder whose every layer holds both kinds of per-sequence memory: a
Mamba-2 state branch and a grouped-head attention branch read one norm's
output side by side, their outputs are scaled and added into the
residual together, and a gated MLP follows under a norm of its own. The
layer that `Falcon-H1-34B-Instruct` names (`falcon_h1`, arXiv:2507.22448):
one kind of layer, and fourteen published multipliers that sit where the
published modelling code has them (`n = RMSNorm(x)`, one learned scale):

    x0 = E[token] * embedding_multiplier
    n  = RMSNorm_in(x)
    [z | xBC | dt] = (W_in (n * ssm_in_multiplier)) * mup
        mup: `ssm_multipliers` by segment, z | x | B | C | dt
    the state layer of `models/mamba_moe.py` from there to W_out (taps,
        SiLU, d_t = softplus(dt_t + dt_bias) without a clamp, S_t = a_t
        S_{t-1} + d_t x_t B_t^T, y_t = S_t C_t + D x_t, the gate first
        and then the grouped norm)
    m = (W_out g) * ssm_out_multiplier
    na = n * attention_in_multiplier (the attention's one input)
    q = W_q na; k = (W_k na) * key_multiplier; v = W_v na; rotary in
        halves on every dim of q and k (float32)
    a = W_o softmax_causal(q k^T / sqrt(d)) v, query head h reads
        key-value head h // (Hq / Hkv)
    x <- x + m + a * attention_out_multiplier
    f = RMSNorm_ff(x)
    x <- x + (W_d (W_u f * SiLU((W_g f) * mlp_multipliers[0])))
             * mlp_multipliers[1]
    logits = (W_head RMSNorm_final(x)) * lm_head_multiplier

Every multiplier is applied at run time, in the place above; none is
folded into a weight, so the family has no `load` and the tree it reads
is the published one (`benchmarks/refs/parallel_hybrid.py` documents it).

The state branch is `models/mamba_moe.py`'s by import: `mamba_whole`,
`mamba_chunk` and `mamba_step` (the projection's split, the taps, the
recurrence through `ops/mamba2.py`, the skip, the gate, the grouped norm,
W_out, the pool's state and tail writes) take this module's
configuration as they take that one's, for they read widths by name, and
`mup` is the one argument they gained (None there: that family's
programs are as they were). Attention is `ops/decode_attention.py`'s
`gqa_full_*` kernels over head-major pages, written by
`models/blocks.py`'s page writes; rotary is its `rope_halves`.

**What the engine holds for this family**: one request, two kinds of
block (`ServingFamily.state_blocks` 1 and `paged`), as
`models/mamba_moe.py`, and here both in every layer: column 0 of its
table names a state block, `"state" [L, blocks, H / t, N, t P]` float32
(`ops/mamba2.py`'s layout), `"conv" [L, blocks, K - 1, H P + 2 G N]`,
`"ring"`, the decode tokens that are not in the state yet, and
`"held" [1, blocks]`, the entries a block's ring holds
(`mamba_moe.state_arrays`); the columns after it name pages of `"k"`,
`"v"` `[L, pages, Hkv, block_size, d]`, all but `"held"` with `L =
n_layers`. Prefill resets the state block on a sequence's first chunk
and leaves its rings empty, a chunk bucket's padding leaves state, tail
and pages bit for bit, and decode's idle rows (table all 0) rewrite the
trash blocks' tails and pages and move nothing of the trash state or its
rings.

`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import mamba_moe
from ray_tpu.models.blocks import (copy_block, gather_block, mm, rms_norm,
                                   rope_halves, row_index, scatter_block,
                                   summarize, unembed, write_chunk,
                                   write_rows)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import mamba2

# what the prefill and decode programs count, in the order of the int32
# vector they return beside the logits: the first four as
# `models/mamba_moe.py`'s (over the layers), then the cached rows one
# layer's attention read in decode steps alone, then the rows whose rings
# went into their states (a decode step's, once whatever the layers)
COUNTS = ("mamba_tokens_live", "mamba_tokens_padded", "state_resets",
          "attention_rows_read", "decode_rows_read_a_layer", "state_folds")
STATE_KEYS = mamba_moe.STATE_KEYS


@dataclass(frozen=True)
class ParallelHybridConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 2
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    state_size: int = 16
    conv_size: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128
    rope_theta: float = 1e11
    eps: float = 1e-5
    max_seq_len: int = 128
    # the published multipliers, each applied where the source applies it
    embedding_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)   # z | x | B | C | dt
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)                  # gate, down
    lm_head_multiplier: float = 1.0
    dtype: str = "bfloat16"
    mamba_impl: str = "auto"         # auto | pallas | jax (both ops)
    attn_impl: str = "auto"          # auto | pallas | jax (gqa_full_*)
    # test-only, for the benchmark's control: "bfloat16" rounds the
    # recurrence's state to bfloat16 at every write and keeps float32 bytes
    state_round: str = "none"        # none | bfloat16

    def __post_init__(self):
        tile = mamba2.tile_heads(self.mamba_head_dim)
        if self.mamba_heads % (tile * self.n_groups) \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("a group's state heads fill whole lane tiles, "
                             "and the query heads divide over the "
                             "key-value heads")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are five (z, x, B, C, dt) and "
                             "mlp_multipliers two (gate, down)")
        if self.state_round not in ("none", "bfloat16"):
            raise ValueError(f"unknown state_round {self.state_round!r}")

    @property
    def inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def family(self):
        return FAMILY


def from_published(*, hidden_size, num_hidden_layers, mamba_n_heads,
                   mamba_d_head, mamba_n_groups, mamba_d_state, mamba_d_conv,
                   num_attention_heads, num_key_value_heads, head_dim,
                   intermediate_size, rope_theta, rms_norm_eps,
                   max_position_embeddings, embedding_multiplier,
                   ssm_in_multiplier, ssm_multipliers, ssm_out_multiplier,
                   attention_in_multiplier, key_multiplier,
                   attention_out_multiplier, mlp_multipliers,
                   lm_head_multiplier, **same) -> ParallelHybridConfig:
    """The configuration file's published keys -> `ParallelHybridConfig`
    (`benchmarks/configs/falcon-h1-34b.json`, `program.constructor`)."""
    return ParallelHybridConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        mamba_heads=mamba_n_heads, mamba_head_dim=mamba_d_head,
        n_groups=mamba_n_groups, state_size=mamba_d_state,
        conv_size=mamba_d_conv, n_heads=num_attention_heads,
        n_kv_heads=num_key_value_heads, head_dim=head_dim,
        d_ff=intermediate_size, rope_theta=float(rope_theta),
        eps=rms_norm_eps, max_seq_len=max_position_embeddings,
        embedding_multiplier=float(embedding_multiplier),
        ssm_in_multiplier=float(ssm_in_multiplier),
        ssm_multipliers=tuple(float(m) for m in ssm_multipliers),
        ssm_out_multiplier=float(ssm_out_multiplier),
        attention_in_multiplier=float(attention_in_multiplier),
        key_multiplier=float(key_multiplier),
        attention_out_multiplier=float(attention_out_multiplier),
        mlp_multipliers=tuple(float(m) for m in mlp_multipliers),
        lm_head_multiplier=float(lm_head_multiplier), **same)


def init_params(key, cfg: ParallelHybridConfig):
    """Float32 leaves, for tests; the tree
    `benchmarks/refs/parallel_hybrid.py` documents, at plain fan-in
    scales (the tests' multipliers are near 1). A head's step and decay
    spread over the heads as `mamba_moe.init_params` spreads them."""
    d, inner, h = cfg.d_model, cfg.inner, cfg.mamba_heads
    ch, ff = cfg.conv_channels, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    residual = float(cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 11 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    step = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), h))
    layers = [{
        "norm_scale": ones(d),
        "w_in": normal((d, inner + ch + h), d ** -0.5),
        "conv_w": normal((cfg.conv_size, ch), cfg.conv_size ** -0.5),
        "conv_b": normal((ch,), 0.1),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "d_skip": ones(h), "gate_norm_scale": ones(inner),
        "w_out": normal((inner, d), inner ** -0.5 * residual),
        "w_q": normal((d, hq * hd), d ** -0.5),
        "w_k": normal((d, hkv * hd), d ** -0.5),
        "w_v": normal((d, hkv * hd), d ** -0.5),
        "w_o": normal((hq * hd, d), (hq * hd) ** -0.5 * residual),
        "ffn_norm_scale": ones(d),
        "w_gate": normal((d, ff), d ** -0.5),
        "w_up": normal((d, ff), d ** -0.5),
        "w_down": normal((ff, d), ff ** -0.5 * residual),
    } for _ in range(cfg.n_layers)]
    return {"embed": normal((cfg.vocab_size, d), 1.0),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_norm_scale": ones(d), "layers": layers}


def init_pool(cfg: ParallelHybridConfig, n_blocks: int, block_size: int,
              mesh=None, *, state_blocks: int):
    """{"state", "conv"} with `state_blocks` blocks on axis 1 and {"k",
    "v"} with `n_blocks` pages, every layer's, zero-filled; block 0 of
    each the trash block."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")

    def pages():
        return jnp.zeros((cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size,
                          cfg.head_dim), cfg.activation_dtype())

    return {**mamba_moe.state_arrays(cfg, cfg.n_layers, state_blocks),
            "k": pages(), "v": pages()}


# ---------------------------------------------------------------------------
# pieces of the layer
# ---------------------------------------------------------------------------

def _mup(cfg):
    """`ssm_multipliers` a column of W_in's output, float32."""
    gn = cfg.n_groups * cfg.state_size
    z, x, b, c, dt = cfg.ssm_multipliers
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in ((cfg.inner, z), (cfg.inner, x), (gn, b), (gn, c),
                     (cfg.mamba_heads, dt))])


def _scaled(x, m: float):
    """x * m in x's type, the product made in float32 as the published
    code's is (a tensor times a Python number): rounded to bfloat16
    first, 0.0375 would be 0.037598. Nothing where the multiplier is 1."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _qkv(n, pos, lp, cfg):
    """Normed n [N, D] at positions pos [N] -> q [N, Hq, d], k, v [N, Hkv,
    d]: the key's multiplier, then rotary in halves on q and k."""
    adt = cfg.activation_dtype()
    rows = n.shape[0]
    n = _scaled(n, cfg.attention_in_multiplier)
    q = mm(n, lp["w_q"], adt).reshape(rows, cfg.n_heads, cfg.head_dim)
    k = _scaled(mm(n, lp["w_k"], adt), cfg.key_multiplier).reshape(
        rows, cfg.n_kv_heads, cfg.head_dim)
    v = mm(n, lp["w_v"], adt).reshape(rows, cfg.n_kv_heads, cfg.head_dim)
    return (rope_halves(q, pos, cfg.rope_theta),
            rope_halves(k, pos, cfg.rope_theta), v)


def _mixed(x, m, att, lp, cfg):
    """The residual after both branches: the state branch's m [N, D] and
    the attention's heads att [N, Hq, d], each with its multiplier."""
    adt = cfg.activation_dtype()
    a = mm(att.reshape(att.shape[0], -1), lp["w_o"], adt)
    return x + _scaled(m, cfg.ssm_out_multiplier) \
        + _scaled(a, cfg.attention_out_multiplier)


def _mlp(x, lp, cfg):
    adt = cfg.activation_dtype()
    gate_mult, down_mult = cfg.mlp_multipliers
    with jax.named_scope(FFN):
        f = rms_norm(x, lp["ffn_norm_scale"], cfg.eps)
        hidden = mm(f, lp["w_up"], adt) * jax.nn.silu(
            _scaled(mm(f, lp["w_gate"], adt), gate_mult))
        return x + _scaled(mm(hidden, lp["w_down"], adt), down_mult)


def _embed(params, tokens, cfg):
    return _scaled(params["embed"].astype(cfg.activation_dtype())[tokens],
                   cfg.embedding_multiplier)


def _logits(x, params, cfg):
    """Final-normed x [..., D] -> logits [..., V] f32."""
    return _scaled(unembed(x, params["head"], cfg.activation_dtype()),
                   cfg.lm_head_multiplier)


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ParallelHybridConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition: the
    recurrence token by token, no state kept, no cache."""
    mup = _mup(cfg)

    def one(seq):
        t = seq.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        with jax.named_scope(EMBED):
            x = _embed(params, seq, cfg)
        for lp in params["layers"]:
            with jax.named_scope(MIXER):
                n = rms_norm(x, lp["norm_scale"], cfg.eps)
                m = mamba_moe.mamba_whole(
                    _scaled(n, cfg.ssm_in_multiplier), lp, cfg, mup)
                q, k, v = _qkv(n, pos, lp, cfg)
                att = da.reference_gqa_attention(
                    q[None], k[None], v[None], jnp.zeros((1,), jnp.int32))[0]
                x = _mixed(x, m, att, lp, cfg)
            x = _mlp(x, lp, cfg)
        with jax.named_scope(HEAD):
            return _logits(
                rms_norm(x, params["final_norm_scale"], cfg.eps), params, cfg)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def prefill(params, tokens, cache, cfg: ParallelHybridConfig, mesh=None, *,
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
    cache = dict(cache)
    layers = cfg.n_layers
    with jax.named_scope(EMBED):
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(c if length is None else length, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        block, pages = table[0], table[1:]
        first = start == 0
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = start + offs
        mup = _mup(cfg)
        x = _embed(params, tokens[0], cfg)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            n = rms_norm(x, lp["norm_scale"], cfg.eps)
            with jax.named_scope("state_branch"):
                m = mamba_moe.mamba_chunk(
                    _scaled(n, cfg.ssm_in_multiplier), lp, cache, cfg, i,
                    block, first, length, mup)
            with jax.named_scope("attention_branch"):
                q, k, v = _qkv(n, positions, lp, cfg)
                cache["k"] = write_chunk(cache["k"], i, k, pages, start,
                                         length)
                cache["v"] = write_chunk(cache["v"], i, v, pages, start,
                                         length)
                att = da.gqa_chunk_attention(
                    q, cache["k"], cache["v"], pages, start, layer=i,
                    impl=cfg.attn_impl)
            x = _mixed(x, m, att, lp, cfg)
        x = _mlp(x, lp, cfg)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        rows = jnp.sum(jnp.where(offs < length, positions + 1, 0)) * layers
        counts = jnp.stack([length * layers, (c - length) * layers,
                            first.astype(jnp.int32), rows, jnp.int32(0),
                            jnp.int32(0)])
        cache["held"] = mamba_moe.rings_emptied(cache, block)
        return _logits(last, params, cfg), cache, counts.astype(jnp.int32)


def decode(params, tokens, cache, pos, tables, cfg: ParallelHybridConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B]; `tables[:, 0]` each row's state
    block, the rest its pages. Idle rows name the trash blocks of both
    kinds, rewrite their tails and pages and count nothing.
    -> (logits [B, V] f32, cache, counts)."""
    cache = dict(cache)
    b = tokens.shape[0]
    layers = cfg.n_layers
    with jax.named_scope(EMBED):
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        blocks, pages = tables[:, 0], tables[:, 1:]
        live = blocks > 0
        held, held_after, folds = mamba_moe.rings_stepped(cache, blocks, cfg)
        widx = row_index(pages, pos, cache["k"])
        mup = _mup(cfg)
        x = _embed(params, tokens, cfg)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            n = rms_norm(x, lp["norm_scale"], cfg.eps)
            with jax.named_scope("state_branch"):
                m = mamba_moe.mamba_step(
                    _scaled(n, cfg.ssm_in_multiplier), lp, cache, cfg, i,
                    blocks, held, mup)
            with jax.named_scope("attention_branch"):
                q, k, v = _qkv(n, pos, lp, cfg)
                cache["k"] = write_rows(cache["k"], i, k, widx)
                cache["v"] = write_rows(cache["v"], i, v, widx)
                att = da.gqa_decode_attention(
                    q, cache["k"], cache["v"], pages, pos, layer=i,
                    impl=cfg.attn_impl)
            x = _mixed(x, m, att, lp, cfg)
        x = _mlp(x, lp, cfg)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        n_live = jnp.sum(live, dtype=jnp.int32)
        rows = jnp.sum(jnp.where(live, pos + 1, 0))
        counts = jnp.stack([n_live * layers, (b - n_live) * layers,
                            jnp.int32(0), rows * layers, rows, folds])
        cache["held"] = held_after
        return _logits(x, params, cfg), cache, counts.astype(jnp.int32)


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, state_blocks=1, state_keys=STATE_KEYS,
    counts=lambda cfg, totals: summarize(COUNTS, totals))
