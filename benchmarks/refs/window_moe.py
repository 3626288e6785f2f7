"""Plain reference for a decoder with grouped key-value heads, window
layers and full layers in a period, a parallel block and routed experts
beside shared ones that are averaged: the language model of
`command-a-plus-05-2026` (`model_type` `cohere2_moe`). Written from the
layer equations in `jax.numpy`, float32, no cache, no kernels, no
batching; it calls nothing of `ray_tpu`. Every function takes the
configuration file's data and reads its sizes from the published keys.

Which layer is which: published layer `l` is a window layer where
`layer_types[l]` is "sliding_attention" and a full layer otherwise; the
layers that run are `layers_from .. layers_from + num_hidden_layers - 1`.

The layer (x in R^D; `use_parallel_block`: one norm a layer):

    n = LayerNorm(x): (x - mean) / sqrt(var + layer_norm_eps) x scale,
        no bias
    q = W_q n   [num_attention_heads x head_dim]
    k, v = W_k n, W_v n   [num_key_value_heads x head_dim]; query head h
        reads key-value head h // (heads / key-value heads)
    window layer: rotary on all of q and k (`rotary_pct` 1, interleaved
        pairs as `rope_gptj`, `rope_theta`); position i attends j with
        i - sliding_window < j <= i
    full layer: no positional term; j <= i
    a = W_o concat_h softmax(q_h . k / sqrt(head_dim)) v
    s = sigmoid(W_r n) over the router's published width
        (`expert_selection_fn`), the `num_experts_per_tok` largest chosen,
        weights s_e / sum of the chosen s (`norm_topk_prob`)
    routed = sum over the chosen experts held here of
        w_e W_down^e (silu(W_gate^e n) * W_up^e n), width `intermediate_size`
    shared = (1 / num_shared_experts) sum_j E_j(n)   (`"average"`)
    y = x + a + routed + shared

After the last layer a LayerNorm, then logits = `logit_scale` x E^T h
with the embedding tied. Only the experts this chip holds
(`experts_held_from`, `num_experts` of them) add their part; what the
absent ones would add is left out, here as in the program.

Parameters (weights are data; the program reads this same tree): embed
[V, D] (the head too); final_norm_scale [D]; "layers": a list, one dict a
layer, with norm_scale [D]; w_q [D, Hq*d]; w_k, w_v [D, Hkv*d]; w_out
[Hq*d, D]; router [D, E_published]; we_gate, we_up, we_down [E_held, F,
D]; ws_gate, ws_up [D, S*F] and ws_down [S*F, D], the S shared experts'
matrices side by side, so that one gated MLP over them is their sum.

The reference runs beside the served model's weights and pool, on a
sequence padded to the engine's longest (32,768 positions): it never
holds q, a score matrix or an expert's hidden layer for the whole
sequence. Positions go in blocks of `TOKEN_BLOCK`; a block's queries
meet, a key-value head at a time, the keys their mask can reach (a window
layer: the `sliding_window + TOKEN_BLOCK` positions that end with the
block, so the key blocks that the mask zeroes whole are skipped; a full
layer: every position, a sub-block of queries at a time), the held
experts run one at a time, and the head a slice of the vocabulary at a
time. Cost at the cell's size, by count: 2.26 GFLOP a token a layer
outside attention (every held expert over every token: the plain loop)
x 4 x 32,768 = 296 TFLOP, 70 TFLOP in the full layer, 30 in the three
window layers, 9 in the head: about 405 TFLOP a padded sequence.

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOKEN_BLOCK = 512       # positions that go through a layer at once
QUERY_BLOCK = 64        # of them, queries that meet every key at once
VOCAB_SLICE = 8192      # rows of the embedding widened at once


def layer_kinds(config: dict) -> list:
    """"window" or "full", one a layer that runs."""
    lo = config.get("layers_from", 0)
    return ["window" if t == "sliding_attention" else "full"
            for t in config["layer_types"][
                lo:lo + config["num_hidden_layers"]]]


def router_width(config: dict) -> int:
    return config.get("published", {}).get("num_experts",
                                           config["num_experts"])


def _block(t: int, want: int) -> int:
    """The largest divisor of t that is at most `want`."""
    b = min(t, want)
    while t % b:
        b -= 1
    return b


def f32(a):
    return a.astype(F32)


def layer_norm(x, scale, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(scale)


def rope(x, pos, theta: float):
    """Rotary embedding on the last axis of x [T, ..., d], interleaved
    pairs: (x[2i], x[2i+1]) turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def swiglu(h, w_gate, w_up, w_down):
    """[D, F], [D, F], [F, D] matrices."""
    return (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call: the share's
    shapes (`num_experts` experts held, `vocab_size` rows). W_q and W_k
    carry `attn_logit_std`^1/2 each over the fan-in scale, the embedding
    is normal at `embed_scale`, W_o carries `attn_out_gain` and the final
    norm's scale is `final_norm_gain` (the file's `assumed` says why
    each)."""
    d, hq, hkv, hd = (config["hidden_size"], config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    f, shared = config["intermediate_size"], config["num_shared_experts"]
    held, width = config["num_experts"], router_width(config)
    n_layers = config["num_hidden_layers"]
    residual = (2.0 * n_layers) ** -0.5
    sharp = config["attn_logit_std"] ** 0.5
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    keys = iter(jax.random.split(key, 1 + 12 * n_layers))
    layers = [{
        "norm_scale": jnp.ones((d,), bf),
        "w_q": normal(next(keys), (d, hq * hd), d ** -0.5 * sharp),
        "w_k": normal(next(keys), (d, hkv * hd), d ** -0.5 * sharp),
        "w_v": normal(next(keys), (d, hkv * hd), d ** -0.5),
        "w_out": normal(next(keys), (hq * hd, d), (hq * hd) ** -0.5
                        * residual * config["attn_out_gain"]),
        "router": normal(next(keys), (d, width), d ** -0.5),
        "we_gate": normal(next(keys), (held, f, d), d ** -0.5),
        "we_up": normal(next(keys), (held, f, d), d ** -0.5),
        "we_down": normal(next(keys), (held, f, d), f ** -0.5 * residual),
        "ws_gate": normal(next(keys), (d, shared * f), d ** -0.5),
        "ws_up": normal(next(keys), (d, shared * f), d ** -0.5),
        "ws_down": normal(next(keys), (shared * f, d),
                          f ** -0.5 * residual),
    } for _ in range(n_layers)]
    return {"embed": normal(next(keys), (config["vocab_size"], d),
                            config["embed_scale"]),
            "final_norm_scale": jnp.full((d,), config["final_norm_gain"], bf),
            "layers": layers}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def keys_values(x, lp, kind, config: dict):
    """Every position's keys and values [T, Hkv, d], a block of positions
    at a time."""
    t = x.shape[0]
    hkv, hd = config["num_key_value_heads"], config["head_dim"]
    tb = _block(t, TOKEN_BLOCK)

    def block(i):
        n = layer_norm(jax.lax.dynamic_slice_in_dim(x, i * tb, tb),
                       lp["norm_scale"], config["layer_norm_eps"])
        k = (n @ f32(lp["w_k"])).reshape(tb, hkv, hd)
        if kind == "window":
            k = rope(k, i * tb + jnp.arange(tb), config["rope_theta"])
        return k, (n @ f32(lp["w_v"])).reshape(tb, hkv, hd)

    k, v = jax.lax.map(block, jnp.arange(t // tb))
    return k.reshape(t, hkv, hd), v.reshape(t, hkv, hd)


def attention(n, first, k, v, lp, kind, config: dict):
    """Normed n [N, D], the positions first .. first + N - 1, against the
    sequence's keys and values k, v [T, Hkv, d]: -> a [N, D], through W_o.
    One key-value head at a time, its group of query heads together."""
    rows, t = n.shape[0], k.shape[0]
    hq, hkv, hd = (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"])
    g, window = hq // hkv, config["sliding_window"]
    pos = first + jnp.arange(rows)
    if kind == "window":
        # the keys the block's masks can reach end with the block
        span = min(t, window + rows)
        lo = jnp.clip(first + rows - span, 0, t - span)
        k = jax.lax.dynamic_slice_in_dim(k, lo, span)
        v = jax.lax.dynamic_slice_in_dim(v, lo, span)
        qb = rows
    else:
        span, lo, qb = t, 0, _block(rows, QUERY_BLOCK)
    kpos = lo + jnp.arange(span)

    def head(out, ws):
        w_q, w_out, k_h, v_h = ws           # [D, g*d], [g*d, D], [S, d] x 2
        q = (n @ f32(w_q)).reshape(rows, g, hd)
        if kind == "window":
            q = rope(q, pos, config["rope_theta"])

        def queries(i):
            q_i = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            at = jax.lax.dynamic_slice_in_dim(pos, i * qb, qb)[:, None]
            live = kpos[None, :] <= at
            if kind == "window":
                live &= kpos[None, :] > at - window
            s = jnp.einsum("qgd,kd->gqk", q_i, k_h) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", p, v_h)

        att = jax.lax.map(queries, jnp.arange(rows // qb))
        return out + att.reshape(rows, g * hd) @ f32(w_out), None

    stacked = (lp["w_q"].reshape(-1, hkv, g * hd).swapaxes(0, 1),
               lp["w_out"].reshape(hkv, g * hd, -1),
               k.swapaxes(0, 1), v.swapaxes(0, 1))
    return jax.lax.scan(head, jnp.zeros((rows, lp["w_out"].shape[1]), F32),
                        stacked)[0]


def routing(n, lp, config: dict):
    """-> (chosen expert ids [N, k], their weights [N, k]): the k largest
    sigmoid scores, normalised to 1."""
    s = jax.nn.sigmoid(n @ f32(lp["router"]))
    weights, chosen = jax.lax.top_k(s, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights


def routed_part(n, lp, config: dict):
    """What the held experts add: a plain loop over them, each over every
    token, weighted by the router's weight for it (zero where the token
    did not choose it)."""
    chosen, weights = routing(n, lp, config)
    first = config.get("experts_held_from", 0)

    def expert(y, e):
        i, w_gate, w_up, w_down = e
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), -1)
        out = (jax.nn.silu(n @ f32(w_gate).T) * (n @ f32(w_up).T)) \
            @ f32(w_down)
        return y + mine[:, None] * out, None

    held = lp["we_gate"].shape[0]
    return jax.lax.scan(expert, jnp.zeros_like(n),
                        (jnp.arange(held), lp["we_gate"], lp["we_up"],
                         lp["we_down"]))[0]


def shared_part(n, lp, config: dict):
    """The mean of the shared experts' outputs, one expert at a time
    (their matrices lie side by side in the tree)."""
    s, d = config["num_shared_experts"], n.shape[-1]
    stacked = (lp["ws_gate"].reshape(d, s, -1).swapaxes(0, 1),
               lp["ws_up"].reshape(d, s, -1).swapaxes(0, 1),
               lp["ws_down"].reshape(s, -1, d))
    total = jax.lax.scan(lambda y, w: (y + swiglu(n, *w), None),
                         jnp.zeros_like(n), stacked)[0]
    return total / s


def feed_forward(n, lp, config: dict):
    return routed_part(n, lp, config) + shared_part(n, lp, config)


def layer(x, lp, kind, config: dict):
    """x [T, D] -> y [T, D], a block of positions at a time."""
    t = x.shape[0]
    k, v = keys_values(x, lp, kind, config)
    tb = _block(t, TOKEN_BLOCK)

    def block(i):
        x_b = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
        n = layer_norm(x_b, lp["norm_scale"], config["layer_norm_eps"])
        return (x_b + attention(n, i * tb, k, v, lp, kind, config)
                + feed_forward(n, lp, config))

    return jax.lax.map(block, jnp.arange(t // tb)).reshape(x.shape)


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    x = f32(params["embed"][seq])
    for lp, kind in zip(params["layers"], layer_kinds(config)):
        x = layer(x, lp, kind, config)
    return layer_norm(x, params["final_norm_scale"],
                      config["layer_norm_eps"])


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: config["logit_scale"] * (
            features(params, seq, config) @ f32(params["embed"]).T), tokens)


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]. The
    logits are made a block of positions and a slice of the vocabulary at
    a time."""
    embed = params["embed"]
    vs = _block(embed.shape[0], VOCAB_SLICE)
    slices = embed.reshape(embed.shape[0] // vs, vs, -1)

    def one(seq):
        x = features(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)
            z = jax.lax.map(
                lambda rows: config["logit_scale"] * (xs @ f32(rows).T),
                slices)                                     # [V/vs, tb, vs]
            z = z.swapaxes(0, 1).reshape(tb, -1)
            return jnp.take_along_axis(z, want[:, None], -1)[:, 0] \
                - jax.nn.logsumexp(z, -1)

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
