"""Vision Transformer (ViT) classifier, TPU-first.

Third model family next to GPT (language) and ResNet (conv vision):
patchify → linear embed → pre-norm transformer encoder (bidirectional
attention) → mean-pool → linear head. Same conventions as models/gpt.py:
stacked-layer pytree + lax.scan, bf16 activations / f32 accumulation,
logical sharding axes so DP/FSDP/TP come from the MeshSpec. Counterpart
of the reference release benchmarks' vision workloads
(`release/air_tests/air_benchmarks/mlperf-train/resnet50_ray_air.py`).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.models.blocks import rms_norm
from ray_tpu.models.gpt import layer


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    channels: int = 3
    dtype: str = "bfloat16"
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.channels * self.patch_size ** 2

    def activation_dtype(self):
        return jnp.dtype(self.dtype)


def small(**kw) -> ViTConfig:
    return ViTConfig(**{**dict(image_size=32, patch_size=4, num_classes=10,
                               d_model=128, n_layers=2, n_heads=4,
                               d_ff=256), **kw})


def param_logical_axes(cfg: ViTConfig):
    layer = {
        "ln1_scale": (None, "embed"),
        "ln2_scale": (None, "embed"),
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "heads"),
        "wv": (None, "embed", "heads"),
        "wo": (None, "heads", "embed"),
        "w_up": (None, "embed", "mlp"),
        "w_down": (None, "mlp", "embed"),
    }
    return {
        "patch_embed": (None, "embed"),
        "pos_embed": (None, "embed"),
        "final_ln_scale": ("embed",),
        "head": ("embed", None),
        "head_bias": (None,),
        "layers": layer,
    }


def init_params(rng, cfg: ViTConfig):
    k_patch, k_pos, k_head, k_layers = jax.random.split(rng, 4)
    d = cfg.d_model
    h = cfg.n_heads * cfg.head_dim
    f, L = cfg.d_ff, cfg.n_layers

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in)))

    ks = jax.random.split(k_layers, 6)
    layers = {
        "ln1_scale": jnp.ones((L, d), jnp.float32),
        "ln2_scale": jnp.ones((L, d), jnp.float32),
        "wq": norm(ks[0], (L, d, h), d),
        "wk": norm(ks[1], (L, d, h), d),
        "wv": norm(ks[2], (L, d, h), d),
        "wo": norm(ks[3], (L, h, d), h) / np.sqrt(2 * L),
        "w_up": norm(ks[4], (L, d, f), d),
        "w_down": norm(ks[5], (L, f, d), f) / np.sqrt(2 * L),
    }
    return {
        "patch_embed": norm(k_patch, (cfg.patch_dim, d), cfg.patch_dim),
        "pos_embed": norm(k_pos, (cfg.num_patches, d), 1.0) * 0.02,
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "head": norm(k_head, (d, cfg.num_classes), d),
        "head_bias": jnp.zeros((cfg.num_classes,), jnp.float32),
        "layers": layers,
    }


def _patchify(images, cfg: ViTConfig):
    """[B, H, W, C] -> [B, N, patch_dim]."""
    b, hgt, wid, c = images.shape
    p = cfg.patch_size
    x = images.reshape(b, hgt // p, p, wid // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hgt // p) * (wid // p), p * p * c)


def _attend(q, k, v):
    """Bidirectional attention on [B, T, H, Dh] — XLA fuses this softmax
    chain well at ViT sequence lengths (<= ~1k patches), no flash kernel
    needed."""
    adt = q.dtype
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(scores / np.sqrt(q.shape[-1]),
                           axis=-1).astype(adt)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(adt), None


def _gelu_mlp(h, lp):
    """Ungated GELU feed-forward on normed [B, T, D]."""
    adt = h.dtype
    up = jnp.einsum("btd,df->btf", h, lp["w_up"].astype(adt),
                    preferred_element_type=jnp.float32).astype(adt)
    return jnp.einsum("btf,fd->btd", jax.nn.gelu(up),
                      lp["w_down"].astype(adt),
                      preferred_element_type=jnp.float32).astype(adt), None


def _block(x, lp, cfg: ViTConfig):
    """`gpt.layer` with bidirectional attention and an ungated GELU
    feed-forward; every einsum emits float32."""
    x, _, _ = layer(x, lp, cfg, jnp.float32, _attend, _gelu_mlp)
    return x


def forward(params, images, cfg: ViTConfig, mesh: Mesh | None = None):
    """images [B, H, W, C] float -> logits [B, num_classes] f32."""
    adt = cfg.activation_dtype()
    patches = _patchify(images.astype(adt), cfg)
    x = jnp.einsum("bnp,pd->bnd", patches, params["patch_embed"].astype(adt),
                   preferred_element_type=jnp.float32).astype(adt)
    x = x + params["pos_embed"].astype(adt)[None]

    block = partial(_block, cfg=cfg)
    if cfg.remat:
        block = jax.checkpoint(block)

    def scan_body(x, lp):
        return block(x, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_ln_scale"])
    pooled = jnp.mean(x.astype(jnp.float32), axis=1)
    return pooled @ params["head"] + params["head_bias"]


def loss_fn(params, batch, cfg: ViTConfig, mesh: Mesh | None = None):
    """Softmax cross entropy. batch: {"images": [B,H,W,C],
    "labels": [B]}."""
    logits = forward(params, batch["images"], cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    return -jnp.mean(ll)


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
