"""Plain reference for training a decoder of window and full attention
layers over softmax-routed experts, the layer that `model_type: mellum`
names (Mellum2-12B-A2.5B-Instruct): a sequential pre-norm block, grouped
key-value heads, rotary in both kinds of layer with different parameters.
Written from the layer equations in `jax.numpy`, float32, no kernels; it
calls nothing of `ray_tpu`. Every function takes the configuration file's
data and reads its sizes from the published keys. The pieces other
references spell alike (RMS norm, the divisor search) are
`refs/latent_sparse_moe.py`'s own functions.

The layer. Input x [T, D], eps `rms_norm_eps`; published layer `l` (the
layers run are `layers_from` .. `layers_from + num_hidden_layers - 1`) is
a window layer where `layer_types[l]` is "sliding_attention".

1. n = RMSNorm(x). q = n W_q -> `num_attention_heads` x `head_dim`; k, v
   = n W_k, n W_v -> `num_key_value_heads` x `head_dim`; query head h
   reads key-value head h // (heads / key-value heads).
2. Rotary on q and k over split halves (dim i turns with dim i + d/2) by
   `rope_parameters[layer type]`. "default": angle pos x theta^(-2i/d).
   "yarn" (dim d, base theta, `factor`, `original_max_position_embeddings`
   L, `beta_fast`, `beta_slow`): corr(r) = d ln(L / (2 pi r)) / (2 ln
   theta); low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)),
   clamped to [0, d - 1]; ramp_i = clip((i - low) / (high - low), 0, 1)
   for i < d/2; angle pos x ((1 - ramp_i) theta^(-2i/d) + ramp_i
   theta^(-2i/d) / factor); cos and sin x `attention_factor`.
3. softmax(q k^T / sqrt(d)) v over the positions s <= t, and in a window
   layer s > t - `sliding_window`; x += concat_h(.) W_o.
4. m = RMSNorm(x). p = softmax(m W_r) over the router's whole width; the
   `num_experts_per_tok` largest, weights p_e / sum of the chosen
   (`norm_topk_prob`); x += sum of the chosen held experts' W_down
   (silu(W_gate m) * (W_up m)). The experts held are `experts_held_from`
   .. + `num_experts` - 1; what the absent ones would add is left out.
5. Final RMSNorm, untied head over the rows of the vocabulary held; the
   loss is the mean negative log-likelihood of the targets over them. No
   auxiliary loss.

Parameters: embed, head [V, D]; final_norm_scale [D]; "layers": a list,
one dict a layer, with attn_norm_scale, ffn_norm_scale [D]; w_q
[D, H*d]; w_k, w_v [D, Hkv*d]; w_out [H*d, D]; router [D, E]; we_gate,
we_up, we_down [held, F, D].

The reference runs beside the trained model's state: one key-value head's
group of query heads at a time, queries in blocks, the held experts one
at a time, the logits a block of positions at a time; 32 x 32,768^2
scores never exist at once. A layer and, inside it, a block of queries
are each a `jax.checkpoint`: that changes no number and lets `jax.grad`
of `loss` fit at a test's size.

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.refs.latent_sparse_moe import _block, f32, rms_norm

QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
TOKEN_BLOCK = 1024      # positions whose logits are held at once


def layer_kinds(config: dict) -> list:
    lo = config.get("layers_from", 0)
    return config["layer_types"][lo:lo + config["num_hidden_layers"]]


def inv_freq(entry: dict, d: int):
    """float32 [d / 2]: the angle a position turns rotary pair i by,
    from one entry of `rope_parameters`."""
    theta = float(entry["rope_theta"])
    plain = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if entry.get("rope_type", "default") == "default":
        return jnp.asarray(plain, jnp.float32)

    def corr(turns):
        return (d * math.log(entry["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(entry["beta_fast"])), 0)
    high = min(math.ceil(corr(entry["beta_slow"])), d - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append((1 - ramp) * f + ramp * f / entry["factor"])
    return jnp.asarray(out, jnp.float32)


def rope(x, pos, entry: dict):
    """x [T, H, d] at positions pos [T], split halves."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq(entry, d)
    gain = float(entry.get("attention_factor", 1.0))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(n, lp, kind: str, pos, config: dict):
    """Normed n [T, D] -> [T, D]: the layer's attention through W_o."""
    t = n.shape[0]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, group = config["head_dim"], hq // hkv
    entry = config["rope_parameters"][kind]
    window = config["sliding_window"] if kind == "sliding_attention" else None
    qb = _block(t, QUERY_BLOCK)
    stacked = (lp["w_q"].reshape(-1, hkv, group * d).swapaxes(0, 1),
               lp["w_k"].reshape(-1, hkv, d).swapaxes(0, 1),
               lp["w_v"].reshape(-1, hkv, d).swapaxes(0, 1),
               lp["w_out"].reshape(hkv, group * d, -1))

    def one_kv_head(out, ws):
        w_q, w_k, w_v, w_out = ws
        q = rope((n @ f32(w_q)).reshape(t, group, d), pos, entry)
        k = rope((n @ f32(w_k))[:, None], pos, entry)[:, 0]      # [T, d]
        v = n @ f32(w_v)

        def block(i):
            rows = i * qb + jnp.arange(qb)
            qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            s = jnp.einsum("qgd,kd->gqk", qs, k) * d ** -0.5
            cols = jnp.arange(t)[None, :]
            live = cols <= rows[:, None]
            if window is not None:
                live &= cols > rows[:, None] - window
            p = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", p, v).reshape(qb, group * d)

        att = jax.lax.map(jax.checkpoint(block),
                          jnp.arange(t // qb)).reshape(t, group * d)
        return out + att @ f32(w_out), None

    return jax.lax.scan(one_kv_head, jnp.zeros_like(n), stacked)[0]


def routed_part(m, lp, config: dict):
    """What the held experts add to normed m [T, D]: a plain loop over
    them, each over every token, weighted by the router's weight for it
    (zero where the token did not choose it)."""
    p = jax.nn.softmax(m @ f32(lp["router"]), -1)
    weights, chosen = jax.lax.top_k(p, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    first = config.get("experts_held_from", 0)

    def expert(y, e):
        i, w_gate, w_up, w_down = e
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), -1)
        out = (jax.nn.silu(m @ f32(w_gate).T) * (m @ f32(w_up).T)) \
            @ f32(w_down)
        return y + mine[:, None] * out, None

    held = lp["we_gate"].shape[0]
    return jax.lax.scan(expert, jnp.zeros_like(m),
                        (jnp.arange(held), lp["we_gate"], lp["we_up"],
                         lp["we_down"]))[0]


def layer(x, lp, kind: str, pos, config: dict):
    """One layer on the residual x [T, D]."""
    eps = config["rms_norm_eps"]
    x = x + attention(rms_norm(x, lp["attn_norm_scale"], eps), lp, kind,
                      pos, config)
    return x + routed_part(rms_norm(x, lp["ffn_norm_scale"], eps), lp,
                           config)


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    pos = jnp.arange(seq.shape[0])
    x = f32(params["embed"])[seq]
    for lp, kind in zip(params["layers"], layer_kinds(config)):
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, kind, pos, config))(x, lp)
    return rms_norm(x, params["final_norm_scale"], config["rms_norm_eps"])


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
