"""A decoder whose sequence mixer is power retention: the layer that
`model_type: brumby` (Brumby-14B-Base) names, served from a state of
fixed size a sequence instead of a cache that grows.

The layer (`n = RMSNorm(x)` with a learned scale; 40 query heads over 8
key-value heads at the published widths, five a group):

    q^i = rot(norm_q(W_q^i n)),  k^j = rot(norm_k(W_k^j n)),  v^j = W_v^j n
    log g^j_t = log sigmoid(w_g^j . n_t + b_g^j)          (<= 0, a scalar)
    a^i[t, s] = (q^i_t . k^j_s)^2 / d  x  exp(sum_{r=s+1..t} log g^j_r)
    o^i_t = sum_{s<=t} a^i[t, s] v^j_s / (sum_{s<=t} a^i[t, s] + eps)
    h = x + W_o concat_i(o^i);   y = h + W_down(silu(W_gate m) * W_up m),
    m = RMSNorm(h)

`norm_q`, `norm_k`: RMSNorm over a head's dims with a learned scale;
`rot`: rotary in halves (`blocks.rope_halves`). `ops/power_retention.py`
has the state form of the same numbers, the two kernels and the layout.

**What the engine holds for this family**: one block a sequence, the
state of every layer and key-value head (`s [L, blocks, Hkv, d, D]`,
`z [L, blocks, Hkv, 1, D]`, float32), the rings of the decode tokens that
are not in it yet (`ring [L, blocks, Hkv, 3, RING, d]`: `k`, `v`, `log g`
of up to `power_retention.RING` tokens) and how many the block's rings
hold (`held [1, blocks]`, int32: one count a block, since all layers
step together). A decode step reads a row's state once and writes its
token into the rings; when they are full (every `RING`-th token of the
sequence's decode, each row in its own turn) the step folds them into the
state and writes it back. The family says what a request holds through
`ServingFamily.state_blocks` (1, and not `paged`); the engine then gives
a request one block whatever its length and keeps no prefix tree, and
its block moves (copy, hand-off) carry all four arrays.
Prefill resets the block on a sequence's first chunk (`start == 0`),
leaves it untouched by a chunk bucket's padding, reads no ring and leaves
the block's count at 0 (a chunk runs only on a sequence that is not
decoding, and a block that another sequence left must not hand its count
on); decode's idle rows (table 0) move nothing, of the trash block
either.

`forward` is the whole-sequence form for tests; `prefill` and `decode`
are what `ServingFamily` asks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (copy_block, gather_block, mm, rms_norm,
                                   rope_halves, scatter_block, summarize,
                                   unembed)
from ray_tpu.models.family import EMBED, FFN, HEAD, MIXER, ServingFamily
from ray_tpu.ops import power_retention

# what the programs count, in the order of the int32 vector each returns
# beside the logits: the prefill program the first three, the decode
# program the last (rows whose ring went into their state)
COUNTS = ("retention_tokens_live", "retention_tokens_padded", "state_resets",
          "state_folds")


@dataclass(frozen=True)
class RetentionConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128
    rope_theta: float = 1e6
    eps: float = 1e-6               # RMSNorm's
    retention_eps: float = 1e-6     # the normaliser's
    max_seq_len: int = 128
    dtype: str = "bfloat16"
    # test-only, for the benchmark's control: "bfloat16" rounds the state
    # to bfloat16 at every write and keeps float32 bytes
    state_round: str = "none"       # none | bfloat16
    retention_impl: str = "auto"    # auto | pallas | jax (both ops)

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} key-value heads")
        if self.state_round not in ("none", "bfloat16"):
            raise ValueError(f"unknown state_round {self.state_round!r}")
        power_retention.feature_dim(self.head_dim)

    @property
    def feature_dim(self) -> int:
        return power_retention.feature_dim(self.head_dim)

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def family(self):
        return FAMILY


def from_published(*, hidden_size, num_hidden_layers, num_attention_heads,
                   num_key_value_heads, intermediate_size, rms_norm_eps,
                   max_position_embeddings, **same):
    """The configuration file's published keys -> `RetentionConfig`
    (`benchmarks/configs/brumby-14b.json`, `program.constructor`)."""
    return RetentionConfig(
        d_model=hidden_size, n_layers=num_hidden_layers,
        n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
        d_ff=intermediate_size, eps=rms_norm_eps,
        max_seq_len=max_position_embeddings, **same)


def init_params(key, cfg: RetentionConfig):
    """Float32 leaves, for tests; the tree `benchmarks/refs/
    retention_decoder.py` documents. Gate biases spread over the heads so
    that a head remembers from tens to thousands of positions."""
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    residual = (2.0 * cfg.n_layers) ** -0.5
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    layers = [{
        "mix_norm_scale": jnp.ones((d,)), "mlp_norm_scale": jnp.ones((d,)),
        "w_q": normal((d, hq * hd), d ** -0.5),
        "w_k": normal((d, hkv * hd), d ** -0.5),
        "w_v": normal((d, hkv * hd), d ** -0.5),
        "q_norm_scale": jnp.ones((hd,)), "k_norm_scale": jnp.ones((hd,)),
        "w_g": normal((d, hkv), d ** -0.5),
        "b_g": jnp.linspace(4.0, 8.0, hkv),
        "w_o": normal((hq * hd, d), (hq * hd) ** -0.5 * residual),
        "w_gate": normal((d, f), d ** -0.5),
        "w_up": normal((d, f), d ** -0.5),
        "w_down": normal((f, d), f ** -0.5 * residual),
    } for _ in range(cfg.n_layers)]
    return {"embed": normal((cfg.vocab_size, d), 0.02),
            "head": normal((cfg.vocab_size, d), d ** -0.5),
            "final_norm_scale": jnp.ones((d,)), "layers": layers}


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def init_pool(cfg: RetentionConfig, n_blocks: int, block_size: int,
              mesh=None, *, state_blocks: int | None = None):
    """{"s", "z", "ring"}, zero-filled float32, and {"held"}, int32
    [1, blocks]: the entries a block's rings hold; blocks on axis 1 of
    all, a block one sequence's state. The family holds no pages, so its
    one count is of state blocks: `state_blocks` as the engine names it,
    `n_blocks` for a caller that names no other; `block_size` sizes
    nothing."""
    if mesh is not None:
        raise ValueError("this family's pool is not sharded over a mesh")
    blocks = state_blocks or n_blocks
    shape = (cfg.n_layers, blocks, cfg.n_kv_heads)
    return {"s": jnp.zeros(shape + (cfg.head_dim, cfg.feature_dim),
                           jnp.float32),
            "z": jnp.zeros(shape + (1, cfg.feature_dim), jnp.float32),
            "ring": jnp.zeros(shape + (3, power_retention.RING,
                                       cfg.head_dim), jnp.float32),
            "held": jnp.zeros((1, blocks), jnp.int32)}


# ---------------------------------------------------------------------------
# pieces of the layer
# ---------------------------------------------------------------------------

def _project(h, lp, pos, cfg):
    """Normed h [N, D] at positions pos [N] -> (q [N, Hq, d], k, v
    [N, Hkv, d], log g [N, Hkv] float32)."""
    adt = cfg.activation_dtype()
    n = h.shape[0]
    q = mm(h, lp["w_q"], adt).reshape(n, cfg.n_heads, cfg.head_dim)
    k = mm(h, lp["w_k"], adt).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    v = mm(h, lp["w_v"], adt).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    q = rope_halves(rms_norm(q, lp["q_norm_scale"], cfg.eps), pos,
                    cfg.rope_theta)
    k = rope_halves(rms_norm(k, lp["k_norm_scale"], cfg.eps), pos,
                    cfg.rope_theta)
    gate = jnp.einsum("nd,dj->nj", h, lp["w_g"].astype(adt),
                      preferred_element_type=jnp.float32)
    return q, k, v, jax.nn.log_sigmoid(gate + lp["b_g"].astype(jnp.float32))


def _mlp(x, lp, cfg):
    adt = cfg.activation_dtype()
    with jax.named_scope(FFN):
        m = rms_norm(x, lp["mlp_norm_scale"], cfg.eps)
        hidden = jax.nn.silu(mm(m, lp["w_gate"], adt)) * mm(m, lp["w_up"], adt)
        return x + mm(hidden, lp["w_down"], adt)


def _mixed(x, o, lp, cfg):
    """The residual after the retention's output o [N, Hq, d] f32."""
    adt = cfg.activation_dtype()
    return x + mm(o.astype(adt).reshape(o.shape[0], -1), lp["w_o"], adt)


# ---------------------------------------------------------------------------
# whole sequence (tests)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: RetentionConfig):
    """tokens [B, T] -> logits [B, T, V] f32, by the definition (the
    masked square over the whole sequence; no state)."""
    adt = cfg.activation_dtype()

    def one(seq):
        pos = jnp.arange(seq.shape[0])
        with jax.named_scope(EMBED):
            x = params["embed"].astype(adt)[seq]
        for lp in params["layers"]:
            with jax.named_scope(MIXER):
                q, k, v, logg = _project(
                    rms_norm(x, lp["mix_norm_scale"], cfg.eps), lp, pos, cfg)
                o = power_retention.retention_quadratic(
                    q, k, v, logg, eps=cfg.retention_eps)
                x = _mixed(x, o, lp, cfg)
            x = _mlp(x, lp, cfg)
        with jax.named_scope(HEAD):
            return unembed(rms_norm(x, params["final_norm_scale"], cfg.eps),
                           params["head"], adt)

    return jax.lax.map(one, tokens)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def prefill(params, tokens, cache, cfg: RetentionConfig, mesh=None, *,
            block_table, start, length=None):
    """One chunk of one sequence (`gpt.prefill_paged`'s contract): tokens
    [1, C] at positions start .. start + length - 1, against the state in
    block `block_table[0]`; a chunk that starts the sequence resets it.
    -> (logits [1, V] f32 of the chunk's last real position, cache,
    counts)."""
    c = tokens.shape[1]
    if tokens.shape[0] != 1:
        raise ValueError(f"prefill wants tokens [1, C], got batch "
                         f"{tokens.shape[0]}")
    adt = cfg.activation_dtype()
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(c if length is None else length, jnp.int32)
    block = jnp.asarray(block_table, jnp.int32)[0]
    first = start == 0
    positions = start + jnp.arange(c, dtype=jnp.int32)
    s, z = cache["s"], cache["z"]
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens[0]]
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            q, k, v, logg = _project(
                rms_norm(x, lp["mix_norm_scale"], cfg.eps), lp, positions, cfg)
            o, s, z = power_retention.retention_chunk(
                q, k, v, logg, s, z, i, block, first, length,
                eps=cfg.retention_eps, state_round=cfg.state_round,
                impl=cfg.retention_impl)
            x = _mixed(x, o, lp, cfg)
        x = _mlp(x, lp, cfg)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        last = jnp.take_along_axis(x, (length - 1)[None, None], axis=0)
        counts = jnp.stack([length, c - length, first.astype(jnp.int32),
                            jnp.zeros((), jnp.int32)])
        # a chunk leaves its block's rings empty, whoever held it before
        held = cache["held"].at[0, block].set(0)
        return (unembed(last, params["head"], adt),
                {**cache, "s": s, "z": z, "held": held}, counts)


def decode(params, tokens, cache, pos, tables, cfg: RetentionConfig,
           mesh=None):
    """One token for every slot (`gpt.decode_step_paged`'s contract):
    tokens [B] at positions pos [B], each row's state in block
    `tables[:, 0]`. A row's token goes into its block's rings, and the
    rings into the state when they are full; idle rows name the trash
    block and move nothing of it.
    -> (logits [B, V] f32, cache, counts)."""
    adt = cfg.activation_dtype()
    blocks = tables.astype(jnp.int32)[:, 0]
    s, z, ring = cache["s"], cache["z"], cache["ring"]
    held = cache["held"][0, blocks]
    fold, after = power_retention.ring_after(blocks, held, cfg.state_round)
    with jax.named_scope(EMBED):
        x = params["embed"].astype(adt)[tokens]
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(MIXER):
            q, k, v, logg = _project(
                rms_norm(x, lp["mix_norm_scale"], cfg.eps), lp,
                pos.astype(jnp.int32), cfg)
            o, s, z, ring = power_retention.retention_step(
                q, k, v, logg, s, z, ring, i, blocks, held,
                eps=cfg.retention_eps, state_round=cfg.state_round,
                impl=cfg.retention_impl)
            x = _mixed(x, o, lp, cfg)
        x = _mlp(x, lp, cfg)
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["final_norm_scale"], cfg.eps)
        counts = jnp.zeros((len(COUNTS),), jnp.int32).at[-1].set(
            jnp.sum(fold, dtype=jnp.int32))
        # idle rows all name block 0 and all leave its count as it was
        held = cache["held"].at[0, blocks].set(after)
        return (unembed(x, params["head"], adt),
                {"s": s, "z": z, "ring": ring, "held": held}, counts)


FAMILY = ServingFamily(
    init_pool=init_pool, prefill=prefill, decode=decode,
    copy_block=copy_block, gather_block=gather_block,
    scatter_block=scatter_block, state_blocks=1, paged=False,
    counts=lambda cfg, totals: summarize(COUNTS, totals),
    state_keys=("s", "z", "ring", "held"))
