"""Mixture-of-Experts transformer, expert-parallel over the mesh.

Absent from the reference (SURVEY.md §2.4: no EP anywhere in Ray) — on
TPU expert parallelism is a sharding spec, so the framework ships it as a
first-class model family. Design (Mesh-TensorFlow / Switch formulation,
the one that maps onto MXU + ICI all-to-alls):

- Expert FFN weights carry a leading ``expert`` logical axis; sharding
  them over the mesh's ``expert`` axis makes XLA insert the dispatch/
  combine all-to-alls.
- Routing is dense one-hot dispatch/combine einsums with a fixed
  per-expert **capacity** (static shapes — no data-dependent gather, so
  the whole thing jits and tiles onto the MXU). Overflowing tokens are
  dropped by the mask, standard Switch behavior.
- Top-1 (Switch) or top-2 (GShard/Mixtral-style) routing with the
  load-balancing auxiliary loss from Shazeer et al.: mean(fraction of
  tokens * fraction of router probability) * n_experts.

Same conventions as models/gpt.py: stacked-layer pytree + lax.scan,
bfloat16 activations with f32 accumulation, logical axes for every param.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.models.blocks import rms_norm
from ray_tpu.models.gpt import attention, layer


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 50304
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 2048              # per-expert FFN width
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
    max_seq_len: int = 1024
    dtype: str = "bfloat16"
    remat: bool = True
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def activation_dtype(self):
        return jnp.dtype(self.dtype)


def small(**kw) -> MoEConfig:
    return MoEConfig(**{**dict(vocab_size=512, d_model=128, n_layers=2,
                               n_heads=4, d_ff=256, n_experts=4, top_k=2,
                               max_seq_len=128), **kw})


def param_logical_axes(cfg: MoEConfig):
    layer = {
        "ln1_scale": (None, "embed"),
        "ln2_scale": (None, "embed"),
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "heads"),
        "wv": (None, "embed", "heads"),
        "wo": (None, "heads", "embed"),
        "router": (None, "embed", "expert"),
        "w_up": (None, "expert", "embed", "mlp"),
        "w_gate": (None, "expert", "embed", "mlp"),
        "w_down": (None, "expert", "mlp", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "final_ln_scale": ("embed",),
        "layers": layer,
    }


def init_params(rng, cfg: MoEConfig):
    k_emb, k_pos, k_layers = jax.random.split(rng, 3)
    d = cfg.d_model
    h = cfg.n_heads * cfg.head_dim
    f, E, L = cfg.d_ff, cfg.n_experts, cfg.n_layers

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in)))

    ks = jax.random.split(k_layers, 8)
    layers = {
        "ln1_scale": jnp.ones((L, d), jnp.float32),
        "ln2_scale": jnp.ones((L, d), jnp.float32),
        "wq": norm(ks[0], (L, d, h), d),
        "wk": norm(ks[1], (L, d, h), d),
        "wv": norm(ks[2], (L, d, h), d),
        "wo": norm(ks[3], (L, h, d), h) / np.sqrt(2 * L),
        "router": norm(ks[4], (L, d, E), d) * 0.1,
        "w_up": norm(ks[5], (L, E, d, f), d),
        "w_gate": norm(ks[6], (L, E, d, f), d),
        "w_down": norm(ks[7], (L, E, f, d), f) / np.sqrt(2 * L),
    }
    return {
        "embed": norm(k_emb, (cfg.vocab_size, d), 1.0) * 0.02,
        "pos_embed": norm(k_pos, (cfg.max_seq_len, d), 1.0) * 0.01,
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# routing + expert FFN
# ---------------------------------------------------------------------------

def _route(h, router_w, cfg: MoEConfig):
    """-> (dispatch [N, E, C] one-hot-ish mask, combine [N, E, C] weights,
    aux load-balance loss). N = B*T flattened tokens."""
    n = h.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * K * n / E))

    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [N, E]

    dispatch = jnp.zeros((n, E, capacity), jnp.float32)
    combine = jnp.zeros((n, E, capacity), jnp.float32)
    # running per-expert fill count, updated after each of the K choices
    fill = jnp.zeros((E,), jnp.float32)
    masked = probs
    top1_assign = None
    for k in range(K):
        idx = jnp.argmax(masked, axis=-1)                    # [N]
        onehot = jax.nn.one_hot(idx, E)                      # [N, E]
        if top1_assign is None:
            top1_assign = onehot
        gate = jnp.sum(probs * onehot, axis=-1)              # [N]
        # position of each token within its chosen expert's buffer
        pos = jnp.cumsum(onehot, axis=0) - onehot + fill[None]   # [N, E]
        pos_tok = jnp.sum(pos * onehot, axis=-1)             # [N]
        keep = pos_tok < capacity
        pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity)
        contrib = (onehot[:, :, None] * pos_oh[:, None, :]
                   * keep[:, None, None])
        dispatch = dispatch + contrib
        combine = combine + contrib * gate[:, None, None]
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
        masked = masked * (1.0 - onehot)                     # next choice

    # Shazeer load-balance aux: E * mean_e(frac_tokens_e * frac_prob_e),
    # on the top-1 assignment
    frac_tokens = jnp.mean(top1_assign, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
    # renormalize combine weights over the K picks (Mixtral-style)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux


def _moe_ffn(x, lp, cfg: MoEConfig):
    """x: [B, T, D] -> (out [B, T, D], aux loss). Dense dispatch/combine
    einsums; expert dim `e` is the sharded axis."""
    adt = cfg.activation_dtype()
    b, t, d = x.shape
    h = x.reshape(b * t, d)
    dispatch, combine, aux = _route(h, lp["router"], cfg)
    # tokens -> expert buffers [E, C, D]
    xs = jnp.einsum("nec,nd->ecd", dispatch.astype(adt), h,
                    preferred_element_type=jnp.float32).astype(adt)
    up = jnp.einsum("ecd,edf->ecf", xs, lp["w_up"].astype(adt),
                    preferred_element_type=jnp.float32).astype(adt)
    gate = jnp.einsum("ecd,edf->ecf", xs, lp["w_gate"].astype(adt),
                      preferred_element_type=jnp.float32).astype(adt)
    act = jax.nn.silu(gate) * up
    down = jnp.einsum("ecf,efd->ecd", act, lp["w_down"].astype(adt),
                      preferred_element_type=jnp.float32).astype(adt)
    out = jnp.einsum("nec,ecd->nd", combine.astype(adt), down,
                     preferred_element_type=jnp.float32).astype(adt)
    return out.reshape(b, t, d), aux


def _block(x, lp, cfg: MoEConfig, mesh: Mesh | None):
    """`gpt.layer` with the experts as its feed-forward; every einsum
    emits float32 (this model never got `matmul_out`: ROADMAP D6).
    -> (x, this layer's aux loss)."""
    x, _, aux = layer(
        x, lp, cfg, jnp.float32,
        lambda q, k, v: (attention(q, k, v, cfg.attn_impl, mesh), None),
        lambda h, lp: _moe_ffn(h, lp, cfg))
    return x, aux


def forward(params, tokens, cfg: MoEConfig, mesh: Mesh | None = None):
    """tokens [B, T] -> (logits [B, T, vocab] f32, aux loss scalar)."""
    adt = cfg.activation_dtype()
    t = tokens.shape[1]
    x = params["embed"].astype(adt)[tokens]
    x = x + params["pos_embed"].astype(adt)[:t][None]

    block = partial(_block, cfg=cfg, mesh=mesh)
    if cfg.remat:
        block = jax.checkpoint(block)

    def scan_body(carry, lp):
        x, aux_sum = carry
        x, aux = block(x, lp)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rms_norm(x, params["final_ln_scale"])
    logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(adt),
                        preferred_element_type=jnp.float32)
    return logits, aux_sum / cfg.n_layers


def loss_fn(params, batch, cfg: MoEConfig, mesh: Mesh | None = None):
    tokens = batch["tokens"]
    logits, aux = forward(params, tokens[:, :-1], cfg, mesh)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + cfg.aux_loss_coeff * aux


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
