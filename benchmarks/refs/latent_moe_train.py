"""Plain reference for training a decoder with latent (MLA) attention and
sigmoid-routed experts, the layer that `model_type: deepseek_v3` names
(Kanana-2-30B-A3B): `refs/latent_sparse_moe.py`'s layer without the
indexer and without the query bottleneck. Written from the layer
equations in `jax.numpy`, float32, no kernels; it calls nothing of
`ray_tpu`. The pieces the two layers spell alike (norm,
rotary, router, experts) are that file's own functions.

The layer. Input x [T, D], h = RMSNorm(x), eps `rms_norm_eps`.

1. q = h W_q -> heads x (`qk_nope_head_dim` + `qk_rope_head_dim`) =
   [q_nope | q_rope], rotary on q_rope (interleaved pairs, `rope_theta`).
   [c_kv (`kv_lora_rank`) | k_rope] = h W_kva; c_kv = RMSNorm(c_kv);
   rotary on k_rope, one head shared by all; per head
   [k_nope | v (`v_head_dim`)] = c_kv W_kvb; k = [k_nope | k_rope].
2. softmax(q k^T / sqrt(nope + rope)) v over every position s <= t;
   x += concat_h(.) W_o.
3. h2 = RMSNorm(x). Dense layer (the first `first_k_dense_replace`):
   SwiGLU of width `intermediate_size`. Sparse layer: the router, the held
   experts and the shared expert of `refs/latent_sparse_moe.py`, the
   shared part one SwiGLU of width `n_shared_experts` x
   `moe_intermediate_size`. x += y.
4. Final RMSNorm, untied head over the rows of the vocabulary held; the
   loss is the mean negative log-likelihood of the targets over them. No
   auxiliary loss.

`router_bias` is data here: it enters the choice of experts and takes no
gradient. How a step moves it is the program's (`assumed`, in the
configuration's file).

Parameters: embed, head [V, D]; final_ln_scale [D]; "layers": a list, one
dict a layer, with attn_norm_scale, ffn_norm_scale [D]; w_q
[D, H*(nope+rope)]; wkv_a [D, Rkv+rope]; kv_norm_scale [Rkv]; wkv_b
[Rkv, H*(nope+v)]; w_out [H*v, D]; then a dense layer's or a sparse
layer's leaves as `refs/latent_sparse_moe.py` lists them.

The reference runs beside the trained model's state: heads in groups,
queries in blocks, the held experts one at a time, the logits a block of
positions at a time; it never holds a [T, T] array of all heads. A layer
and, inside it, a block of queries are each a `jax.checkpoint`: that
changes no number, forward or backward, and lets `jax.grad` of `loss` fit
beside the parameters at the cell's size too
(`benchmarks/tools/grad_check.py`; without it the backward pass would
keep every block's [heads, queries, T] scores of every layer).

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.refs.latent_sparse_moe import (_block, f32, feed_forward,
                                               rms_norm, rope)

HEAD_GROUP = 8          # heads whose keys and values are held at once
QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
TOKEN_BLOCK = 1024      # positions whose logits are held at once


def attention(h, lp, pos, config: dict):
    """Latent attention of normed h [T, D] over every earlier position,
    through W_o: -> [T, D]."""
    t = h.shape[0]
    nh, rkv = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, theta = config["v_head_dim"], config["rope_theta"]
    hg = _block(nh, HEAD_GROUP)
    groups = nh // hg
    kv = h @ f32(lp["wkv_a"])
    c_kv = rms_norm(kv[:, :rkv], lp["kv_norm_scale"],
                    config["rms_norm_eps"])
    k_rope = rope(kv[:, rkv:], pos, theta)                     # [T, rp]
    qb = _block(t, QUERY_BLOCK)
    stacked = (
        lp["w_q"].reshape(-1, groups, hg * (nope + rp)).swapaxes(0, 1),
        lp["wkv_b"].reshape(rkv, groups, hg * (nope + vd)).swapaxes(0, 1),
        lp["w_out"].reshape(groups, hg * vd, -1))

    def group(out, ws):
        w_q, wkv_b, w_out = ws
        q = (h @ f32(w_q)).reshape(t, hg, nope + rp)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta)
        kv_h = (c_kv @ f32(wkv_b)).reshape(t, hg, nope + vd)
        k_nope, v = kv_h[..., :nope], kv_h[..., nope:]

        def block(i):
            rows = i * qb + jnp.arange(qb)
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qr, k_rope))
            causal = jnp.arange(t)[None, :] <= rows[:, None]
            s = jnp.where(causal[None], s * (nope + rp) ** -0.5, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                              v).reshape(qb, hg * vd)

        att = jax.lax.map(jax.checkpoint(block),
                          jnp.arange(t // qb)).reshape(t, hg * vd)
        return out + att @ f32(w_out), None

    return jax.lax.scan(group, jnp.zeros_like(h), stacked)[0]


def layer(x, lp, pos, config: dict):
    """One layer on the residual x [T, D]."""
    eps = config["rms_norm_eps"]
    x = x + attention(rms_norm(x, lp["attn_norm_scale"], eps), lp, pos,
                      config)
    return x + feed_forward(rms_norm(x, lp["ffn_norm_scale"], eps), lp,
                            config)


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    pos = jnp.arange(seq.shape[0])
    x = f32(params["embed"])[seq]
    for lp in params["layers"]:
        x = jax.checkpoint(lambda x, lp: layer(x, lp, pos, config))(x, lp)
    return rms_norm(x, params["final_ln_scale"], config["rms_norm_eps"])


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: features(params, seq, config) @ f32(params["head"]).T,
        tokens)


def token_losses(params, inputs, targets, config: dict):
    """The negative log-likelihood of every target [B, T] after inputs
    [B, T], over the rows of the vocabulary held: -> float32 [B, T]. The
    logits are made a block of positions at a time."""
    head = f32(params["head"])

    def one(pair):
        seq, want = pair
        x = features(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            ws = jax.lax.dynamic_slice_in_dim(want, i * tb, tb)
            lp = jax.nn.log_softmax(xs @ head.T, -1)
            return -jnp.take_along_axis(lp, ws[:, None], -1)[:, 0]

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)

    return jax.lax.map(one, (inputs, targets))


def loss(params, inputs, targets, config: dict, weights=None):
    """Mean negative log-likelihood of targets [B, T] after inputs
    [B, T]; with `weights` [B, T], the mean weighted by them."""
    nll = token_losses(params, inputs, targets, config)
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / jnp.sum(weights)
