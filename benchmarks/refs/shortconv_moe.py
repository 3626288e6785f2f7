"""Plain reference for a decoder whose token mixing is a gated short
convolution in most layers and grouped-head softmax attention in the
rest, with a dense gated MLP in the first layers and routed experts
without a shared one in the others: the language model of `LFM2-8B-A1B`
(`lfm2_moe`). Written from the layer equations in `jax.numpy`, float32,
no kernels, no cache, no chunk form, no batching; it calls nothing of
`ray_tpu`. Every function takes the configuration file's data and reads
its sizes from the published keys.

Which layer is which: published layer `l` is a convolution layer where
`layer_types[l]` says "conv" and an attention layer where it says
"full_attention" (the layers that run are `layers_from .. layers_from +
num_hidden_layers - 1`, and the list names at least those); its feed-forward
part is dense where `l < num_dense_layers`, sparse otherwise. D =
`hidden_size`, eps = `norm_eps`, no projection has a bias.

    n = RMSNorm_operator(x);  h = x + mixer(n)
    f = RMSNorm_ffn(h);       y = h + ffn(f)

    conv(n):  B | C | u = n W_in          (W_in [D, 3 D], thirds in that order)
              g_t = B_t * u_t
              c_t = sum_{i<K} w[i] g_{t-K+1+i}   K = `conv_L_cache` taps a
                  channel, zeros before the sequence, no bias, no activation
              conv = (C_t * c_t) W_out

    attn(n):  q = n W_q (`num_attention_heads` heads of d = D / heads), k, v
              (`num_key_value_heads` heads); q and k <- RMSNorm over d, one
              learned scale of d each; rotary on all d dims, rotate-half
              form ((x[i], x[i + d/2]) turned by pos * theta^(-2i/d)),
              theta `rope_theta`; causal softmax(q k^T / sqrt(d)) v, query
              head h reads key-value head h // (Hq / Hkv); W_o

    dense:    W_down (silu(W_gate f) * W_up f), width `intermediate_size`
    sparse:   s = sigmoid(f W_r) over the router's published width; the
              `num_experts_per_tok` largest of s + b chosen; weights s of
              the chosen / (their sum + 1e-6) (`norm_topk_prob`) x
              `routed_scaling_factor`; sum over the chosen e of w_e
              W2_e (silu(W1_e f) * W3_e f), width `moe_intermediate_size`;
              no shared expert. Only the experts this chip holds
              (`experts_held_from`, `num_experts` of them) add their part.

    logits = RMSNorm_final(y) E^T      (E the embedding, tied)

Conventions the source's keys do not settle are the configuration file's
`assumed`.

Parameters (weights are data; the program reads this same tree): embed
[V, D]; final_norm_scale [D]; "layers": a list, one dict a layer, with
operator_norm_scale, ffn_norm_scale [D]; in a convolution layer w_in [D,
3 D], conv_w [K, D], w_out [D, D]; in an attention layer w_q [D, Hq d],
w_k, w_v [D, Hkv d], q_norm_scale, k_norm_scale [d], w_out [Hq d, D];
with a dense feed-forward part w_gate, w_up [D, F], w_down [F, D]; with a
sparse one router [D, E_published], router_bias [E_published], we_gate
(W1), we_up (W3), we_down (W2) [E_held, F_e, D].

The reference runs beside the served model's weights and pool, on a
sequence padded to the engine's longest: it upcasts at use, walks queries
in blocks and key-value heads one at a time, the held experts one at a
time (each over every token) and the head a block of positions at a time
(the embedding is indexed before it is widened).

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
TOKEN_BLOCK = 512       # positions whose logits are held at once
TOPK_EPS = 1e-6         # beside the chosen scores' sum (`assumed`)


def layer_kinds(config: dict) -> list:
    """[(mixer "conv" | "full_attention", feed-forward "dense" |
    "sparse")], one a layer that runs."""
    lo = config.get("layers_from", 0)
    types = config["layer_types"][lo:lo + config["num_hidden_layers"]]
    return [(t, "dense" if lo + i < config["num_dense_layers"] else "sparse")
            for i, t in enumerate(types)]


def router_width(config: dict) -> int:
    return config.get("published", {}).get("num_experts",
                                           config["num_experts"])


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _block(t: int, want: int) -> int:
    """The largest divisor of t that is at most `want`."""
    b = min(t, want)
    while t % b:
        b -= 1
    return b


def f32(a):
    return a.astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call: the share's
    shapes (`num_experts` experts held, `vocab_size` rows). The file's
    `draws` names every scale that is not fan-in^-1/2 (its `assumed` says
    why each): the embedding at `embed_scale`; what adds to the residual
    (a convolution's W_out, W_o, a dense W_down, an expert's W2) at
    fan-in^-1/2 x (2 x layers)^-1/2, and besides that both outputs of a
    layer with a dense feed-forward part x `dense_gain`, a later
    convolution's W_out x `conv_out_gain` and an expert's W2 x
    `expert_down_gain`; `router_bias` normal at `router_bias`. The router, its bias and the
    taps are bfloat16 values kept in float32 leaves: the steps read them
    there, and a served tree then needs no conversion at load."""
    draws = config["draws"]
    d, hd = config["hidden_size"], head_dim(config)
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    taps = config["conv_L_cache"]
    ff, fe = config["intermediate_size"], config["moe_intermediate_size"]
    held, width = config["num_experts"], router_width(config)
    kinds = layer_kinds(config)
    residual = (2.0 * len(kinds)) ** -0.5
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 1 + 12 * len(kinds)))
    layers = []
    for mixer, ffn in kinds:
        lp = {"operator_norm_scale": ones(d), "ffn_norm_scale": ones(d)}
        lead = draws["dense_gain"] if ffn == "dense" else 1.0
        if mixer == "conv":
            lp.update({
                "w_in": normal(next(keys), (d, 3 * d), d ** -0.5),
                "conv_w": f32(normal(next(keys), (taps, d), taps ** -0.5)),
                "w_out": normal(next(keys), (d, d), d ** -0.5 * residual * (
                    lead if ffn == "dense" else draws["conv_out_gain"])),
            })
        else:
            lp.update({
                "w_q": normal(next(keys), (d, hq * hd), d ** -0.5),
                "w_k": normal(next(keys), (d, hkv * hd), d ** -0.5),
                "w_v": normal(next(keys), (d, hkv * hd), d ** -0.5),
                "q_norm_scale": ones(hd), "k_norm_scale": ones(hd),
                "w_out": normal(next(keys), (hq * hd, d),
                                (hq * hd) ** -0.5 * residual * lead),
            })
        if ffn == "dense":
            lp.update({
                "w_gate": normal(next(keys), (d, ff), d ** -0.5),
                "w_up": normal(next(keys), (d, ff), d ** -0.5),
                "w_down": normal(next(keys), (ff, d),
                                 ff ** -0.5 * residual * lead),
            })
        else:
            lp.update({
                "router": f32(normal(next(keys), (d, width), d ** -0.5)),
                # small beside the scores' spread: the bias exists to
                # level the experts' load, not to skew it
                "router_bias": f32(normal(next(keys), (width,),
                                          draws["router_bias"])),
                "we_gate": normal(next(keys), (held, fe, d), d ** -0.5),
                "we_up": normal(next(keys), (held, fe, d), d ** -0.5),
                "we_down": normal(next(keys), (held, fe, d),
                                  fe ** -0.5 * residual
                                  * draws["expert_down_gain"]),
            })
        layers.append(lp)
    return {"embed": normal(next(keys), (config["vocab_size"], d),
                            draws["embed_scale"]),
            "final_norm_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def conv_layer(n, lp, config: dict):
    """The gated short convolution of normed n [T, D], through W_out."""
    t, d = n.shape
    taps = config["conv_L_cache"]
    w_in = lp["w_in"]
    b = n @ f32(w_in[:, :d])
    c = n @ f32(w_in[:, d:2 * d])
    u = n @ f32(w_in[:, 2 * d:])
    padded = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    conved = sum(f32(lp["conv_w"][i]) * padded[i:i + t] for i in range(taps))
    return (c * conved) @ f32(lp["w_out"])


def rotate_half(x, theta):
    """Rotary embedding on x [T, H, d] at positions 0 .. T - 1, all d
    dims: (x[i], x[i + d/2]) turned by pos * theta^(-2i/d)."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layer(n, lp, config: dict):
    """Grouped-head causal attention of normed n [T, D] over every earlier
    position, q and k normed and turned, through W_o: -> [T, D]."""
    t = n.shape[0]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps, theta = head_dim(config), config["norm_eps"], \
        float(config["rope_theta"])
    per = hq // hkv
    qb = _block(t, QUERY_BLOCK)
    k = rotate_half(rms_norm((n @ f32(lp["w_k"])).reshape(t, hkv, hd),
                             lp["k_norm_scale"], eps), theta).swapaxes(0, 1)
    v = (n @ f32(lp["w_v"])).reshape(t, hkv, hd).swapaxes(0, 1)
    stacked = (lp["w_q"].reshape(-1, hkv, per * hd).swapaxes(0, 1),
               lp["w_out"].reshape(hkv, per * hd, -1), k, v)

    def kv_head(out, ws):
        w_q, w_out, k, v = ws
        q = rotate_half(rms_norm((n @ f32(w_q)).reshape(t, per, hd),
                                 lp["q_norm_scale"], eps), theta)

        def block(i):
            rows = i * qb + jnp.arange(qb)
            s = jnp.einsum("qhd,kd->hqk", jax.lax.dynamic_slice_in_dim(
                q, i * qb, qb), k) * hd ** -0.5
            live = jnp.arange(t)[None, :] <= rows[:, None]
            s = jnp.where(live[None], s, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1), v)

        att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, per * hd)
        return out + att @ f32(w_out), None

    return jax.lax.scan(kv_head, jnp.zeros_like(n), stacked)[0]


def dense_mlp(f, lp, config: dict):
    return (jax.nn.silu(f @ f32(lp["w_gate"])) * (f @ f32(lp["w_up"]))) \
        @ f32(lp["w_down"])


def routing(f, lp, config: dict):
    """-> (chosen expert ids [T, k], their weights [T, k]): sigmoid
    scores; the k largest of score + bias; weights from the scores alone,
    over their sum + 1e-6, scaled."""
    s = jax.nn.sigmoid(f @ f32(lp["router"]))
    _, chosen = jax.lax.top_k(s + f32(lp["router_bias"]),
                              config["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, chosen, -1)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + TOPK_EPS)
    return chosen, weights * config["routed_scaling_factor"]


def expert_layer(f, lp, config: dict, rounded=lambda a: a):
    """What the held experts add [T, D]: a plain loop over them, each over
    every token, weighted by the router's weight for it (zero where the
    token did not choose it). `rounded`: tests put an expert's input and
    matrices on a coarser grid through it."""
    chosen, weights = routing(f, lp, config)
    first = config.get("experts_held_from", 0)
    fin = rounded(f)

    def expert(out, e):
        i, w1, w3, w2 = e
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), -1)
        hidden = jax.nn.silu(fin @ rounded(f32(w1)).T) \
            * (fin @ rounded(f32(w3)).T)
        return out + mine[:, None] * (hidden @ rounded(f32(w2))), None

    held = lp["we_gate"].shape[0]
    return jax.lax.scan(expert, jnp.zeros_like(f),
                        (jnp.arange(held), lp["we_gate"], lp["we_up"],
                         lp["we_down"]))[0]


MIXERS = {"conv": conv_layer, "full_attention": attention_layer}
FFNS = {"dense": dense_mlp, "sparse": expert_layer}


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    eps = config["norm_eps"]
    x = f32(params["embed"][seq])
    for lp, (mixer, ffn) in zip(params["layers"], layer_kinds(config)):
        x = x + MIXERS[mixer](
            rms_norm(x, lp["operator_norm_scale"], eps), lp, config)
        x = x + FFNS[ffn](rms_norm(x, lp["ffn_norm_scale"], eps), lp, config)
    return rms_norm(x, params["final_norm_scale"], eps)


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: features(params, seq, config) @ f32(params["embed"]).T,
        tokens)


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]. The
    logits are made a block of positions at a time."""

    def one(seq):
        x = features(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)
        head = f32(params["embed"])
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)
            lp = jax.nn.log_softmax(xs @ head.T, -1)
            return jnp.take_along_axis(lp, want[:, None], -1)[:, 0]

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
