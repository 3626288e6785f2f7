"""Plain reference for the dense decoder both OLMo shapes run as here:
RMSNorm with a scale, learned absolute positions, full multi-head causal
attention, SwiGLU, tied unembedding. Written from the layer equations in
`jax.numpy`, float32, no kernels, no cache, no batching tricks; it calls
nothing of `ray_tpu`. It reads the program's parameter pytree (weights
are data): embed [V, d], pos_embed [P, d], final_ln_scale [d], and under
"layers" arrays stacked over depth: ln1_scale, ln2_scale [L, d];
wq, wk, wv [L, d, H*Dh]; wo [L, H*Dh, d]; w_up, w_gate [L, d, f];
w_down [L, f, d].

Departures from the published OLMo block, both outside every matrix
multiplication and both the program's (see the configuration files):
learned positions instead of rotary ones, RMSNorm with a scale instead
of the non-parametric LayerNorm.

Every function takes the configuration file's data and reads the sizes
it needs from the published keys; `init_params` makes seeded weights in
that layout (the scales of `gpt.init_params`, copied), so a serving cell
compares the program with nothing the program made.

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def init_params(key, config: dict):
    """float32 weights from `key`, in one traceable call."""
    d, f = config["hidden_size"], config["intermediate_size"]
    h = config["num_attention_heads"] * config["head_dim"]
    n = config["num_hidden_layers"]
    ks = jax.random.split(key, 9)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    residual = 1.0 / jnp.sqrt(2.0 * n)
    return {
        "embed": normal(ks[0], (config["vocab_size"], d), 0.02),
        "pos_embed": normal(ks[1], (config["max_position_embeddings"], d),
                            0.01),
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "layers": {
            "ln1_scale": jnp.ones((n, d), jnp.float32),
            "ln2_scale": jnp.ones((n, d), jnp.float32),
            "wq": normal(ks[2], (n, d, h), d ** -0.5),
            "wk": normal(ks[3], (n, d, h), d ** -0.5),
            "wv": normal(ks[4], (n, d, h), d ** -0.5),
            "wo": normal(ks[5], (n, h, d), h ** -0.5 * residual),
            "w_up": normal(ks[6], (n, d, f), d ** -0.5),
            "w_gate": normal(ks[7], (n, d, f), d ** -0.5),
            "w_down": normal(ks[8], (n, f, d), f ** -0.5 * residual),
        },
    }


def block(x, lp, n_heads: int):
    b, t, d = x.shape
    h = rms_norm(x, lp["ln1_scale"])
    q = (h @ lp["wq"]).reshape(b, t, n_heads, -1)
    k = (h @ lp["wk"]).reshape(b, t, n_heads, -1)
    v = (h @ lp["wv"]).reshape(b, t, n_heads, -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, -1)
    x = x + att @ lp["wo"]
    h = rms_norm(x, lp["ln2_scale"])
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V]."""
    n_heads = config["num_attention_heads"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t = tokens.shape[1]
    x = p["embed"][tokens] + p["pos_embed"][:t][None]

    def layer(x, lp):
        return block(x, lp, n_heads), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = rms_norm(x, p["final_ln_scale"])
    return x @ p["embed"].T


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]."""
    lp = jax.nn.log_softmax(logits(params, tokens[:, :-1], config), -1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], -1)[..., 0]


def sequence_losses(params, inputs, targets, config: dict):
    """Mean next-token cross entropy of each sequence: [B]."""
    lp = jax.nn.log_softmax(logits(params, inputs, config), -1)
    return -jnp.mean(jnp.take_along_axis(lp, targets[..., None], -1)[..., 0],
                     axis=-1)


def loss(params, inputs, targets, config: dict):
    """Mean next-token cross entropy over pre-shifted inputs/targets."""
    return jnp.mean(sequence_losses(params, inputs, targets, config))
