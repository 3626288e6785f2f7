"""Plain reference for a decoder that mixes Kimi Delta Attention (KDA)
layers with latent-attention (MLA) layers and routes its experts by
groups: the language model of `Ling-3.0-flash-VL`, the KDA layer after
Kimi Linear (arXiv:2510.26692). Written from the layer equations in
`jax.numpy`, float32, no kernels, no cache, no chunk form; it calls
nothing of `ray_tpu`. Every function takes the configuration file's data
and reads its sizes from the published keys.

Which layer is which: published layer `l` is a latent layer where
`(l + 1) % layer_group_size == 0` and a KDA layer otherwise; layers below
`layers_from + first_k_dense_replace` have a dense feed-forward, the
others routed experts. The layers that run are `layers_from ..
layers_from + num_hidden_layers - 1`. `n = RMSNorm(x)`, eps
`rms_norm_eps`; H = `num_attention_heads` heads of d = `head_dim`.

KDA layer (`short_conv_kernel_size` K, `kda_lower_bound` floor):

    q~, k~, v~ = W_q n, W_k n, W_v n                      [H x d] each
    q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~)):
        conv(x)_t = sum_{i < K} w[i] x_{t - K + 1 + i}, a channel of its
        own taps, zeros before the sequence (four shifted products)
    q^h <- q^h / sqrt(|q^h|^2 + 1e-6) x d^-1/2,  k^h likewise without the
        scale
    g_t = floor x sigmoid(exp(A^h) x (W_f n_t + b_f))     [H x d], in (floor, 0)
    beta_t^h = sigmoid(w_beta^h . n_t)
    S_t^h = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1}^h + beta_t k_t v_t^T,
        S_0 = 0, [d x d], a `lax.scan` over the positions
    o_t^h = (S_t^h)^T q_t
    x += W_o concat_h( RMSNorm(o_t^h; o_norm_scale) x sigmoid(w_og^h . n_t) )

Latent layer (`q_lora_rank` null: no query bottleneck): q = W_q n ->
heads x (`qk_nope_head_dim` + `qk_rope_head_dim`) = [q_nope | q_rope];
[c_kv (`kv_lora_rank`) | k_rope] = W_kva n; c_kv = RMSNorm(c_kv)
(`use_qk_norm`); rotary (interleaved pairs, `rope_theta`) on q_rope and on
k_rope, one head shared by all; per head [k_nope | v] = c_kv W_kvb;
softmax over every earlier position of (q_nope . k_nope + q_rope .
k_rope) / sqrt(nope + rope); each head's output times the same head-wise
gate sigmoid(w_og^h . n); W_o.

Feed-forward on h2 = RMSNorm(x). Dense: SwiGLU of `intermediate_size`.
Sparse: s = sigmoid(h2 W_r) over the router's published width; the choice
is made on s + b: `n_group` equal groups, a group's score the sum of its
two largest, the `topk_group` best groups kept, the `num_experts_per_tok`
largest inside them chosen; weights s of the chosen, normalised to 1,
x `routed_scaling_factor`; routed experts SwiGLU of
`moe_intermediate_size` and one shared expert of
`moe_shared_expert_intermediate_size`. Only the experts this chip holds
(`experts_held_from`, `num_experts` of them) add their part; what the
absent ones would add is left out, here as in the program. The expert
clamps (`expert_swiglu_limit_list`) are 0 at every layer this cut runs.

Departures from the published model: the vision tower and the
multi-token-prediction module are no part of the next-token text forward
pass and are left out. Conventions the source's keys do not settle are
the configuration file's `assumed`.

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_ln_scale [D]; "layers": a list, one dict a layer, with
attn_norm_scale, ffn_norm_scale [D]; w_og [D, H]; in a KDA layer w_q, w_k,
w_v, w_f [D, H*d]; conv_q, conv_k, conv_v [K, H*d]; a_log [H]; b_f [H*d];
w_beta [D, H]; o_norm_scale [d]; w_out [H*d, D]; in a latent layer w_q
[D, H*(nope+rope)]; wkv_a [D, Rkv+rope]; kv_norm_scale [Rkv]; wkv_b
[Rkv, H*(nope+v)]; w_out [H*v, D]; in a dense layer w_gate, w_up [D, F],
w_down [F, D]; in a sparse layer router [D, E_published], router_bias
[E_published], we_gate, we_up, we_down [E_held, Fe, D], ws_gate, ws_up
[D, Fs], ws_down [Fs, D].

The reference runs beside the served model's weights and pool, on a
sequence padded to the engine's longest: it upcasts at use, walks latent
heads in groups and queries in blocks, the held experts one at a time and
the head a block of positions at a time (the embedding is indexed before
it is widened).

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_GROUP = 8          # latent heads whose keys and values are held at once
QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
TOKEN_BLOCK = 1024      # positions whose logits are held at once


def layer_kinds(config: dict) -> list:
    """[(mixer, mlp type)] of the layers that run."""
    lo = config.get("layers_from", 0)
    dense = lo + config["first_k_dense_replace"]
    return [("latent" if (i + 1) % config["layer_group_size"] == 0
             else "kda", "dense" if i < dense else "sparse")
            for i in range(lo, lo + config["num_hidden_layers"])]


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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


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
    shapes (`num_experts` experts held, `vocab_size` rows). The gate's
    bias b_f runs over a head's channels between the two values of
    `gate_bias` and A over the heads between those of `gate_log_scale`;
    the embedding is normal at `embed_scale` and a KDA layer's W_o at
    `kda_out_gain` times the other residual outputs' scale (the file's
    `assumed` says why each)."""
    d, nh, hd = (config["hidden_size"], config["num_attention_heads"],
                 config["head_dim"])
    rkv = config["kv_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, taps = config["v_head_dim"], config["short_conv_kernel_size"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    fs = config["moe_shared_expert_intermediate_size"]
    held, width = config["num_experts"], router_width(config)
    kinds = layer_kinds(config)
    residual = (2.0 * len(kinds)) ** -0.5
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw, and
        # 5.17 B of them are most of a replica's start
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 2 + 24 * len(kinds)))
    layers = []
    for mixer, mlp in kinds:
        lp = {"attn_norm_scale": ones(d), "ffn_norm_scale": ones(d),
              "w_og": normal(next(keys), (d, nh), d ** -0.5)}
        if mixer == "kda":
            lp.update({
                "w_q": normal(next(keys), (d, nh * hd), d ** -0.5),
                "w_k": normal(next(keys), (d, nh * hd), d ** -0.5),
                "w_v": normal(next(keys), (d, nh * hd), d ** -0.5),
                "conv_q": normal(next(keys), (taps, nh * hd), taps ** -0.5),
                "conv_k": normal(next(keys), (taps, nh * hd), taps ** -0.5),
                "conv_v": normal(next(keys), (taps, nh * hd), taps ** -0.5),
                "a_log": jnp.linspace(*config["gate_log_scale"],
                                      nh).astype(F32),
                "w_f": normal(next(keys), (d, nh * hd), d ** -0.5),
                "b_f": jnp.tile(jnp.linspace(*config["gate_bias"], hd),
                                nh).astype(F32),
                "w_beta": normal(next(keys), (d, nh), d ** -0.5),
                "o_norm_scale": ones(hd),
                "w_out": normal(next(keys), (nh * hd, d),
                                (nh * hd) ** -0.5 * residual
                                * config["kda_out_gain"]),
            })
        else:
            lp.update({
                "w_q": normal(next(keys), (d, nh * (nope + rp)), d ** -0.5),
                "wkv_a": normal(next(keys), (d, rkv + rp), d ** -0.5),
                "kv_norm_scale": ones(rkv),
                "wkv_b": normal(next(keys), (rkv, nh * (nope + vd)),
                                rkv ** -0.5),
                "w_out": normal(next(keys), (nh * vd, d),
                                (nh * vd) ** -0.5 * residual),
            })
        if mlp == "dense":
            lp.update({
                "w_gate": normal(next(keys), (d, f), d ** -0.5),
                "w_up": normal(next(keys), (d, f), d ** -0.5),
                "w_down": normal(next(keys), (f, d), f ** -0.5 * residual),
            })
        else:
            lp.update({
                "router": normal(next(keys), (d, width), d ** -0.5),
                # small beside the scores' spread (0.2): the correction
                # bias exists to level the experts' load, not to skew it
                "router_bias": normal(next(keys), (width,), 0.01),
                "we_gate": normal(next(keys), (held, fe, d), d ** -0.5),
                "we_up": normal(next(keys), (held, fe, d), d ** -0.5),
                "we_down": normal(next(keys), (held, fe, d),
                                  fe ** -0.5 * residual),
                "ws_gate": normal(next(keys), (d, fs), d ** -0.5),
                "ws_up": normal(next(keys), (d, fs), d ** -0.5),
                "ws_down": normal(next(keys), (fs, d),
                                  fs ** -0.5 * residual),
            })
        layers.append(lp)
    v = config["vocab_size"]
    return {"embed": normal(next(keys), (v, d), config["embed_scale"]),
            "head": normal(next(keys), (v, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def head_gate(h, lp):
    """sigmoid(w_og^h . n): [T, H, 1]."""
    return jax.nn.sigmoid(h @ f32(lp["w_og"]))[..., None]


def causal_conv(x, taps):
    """x [T, C], taps [K, C] -> [T, C]: K shifted products, zeros before
    the sequence."""
    k, t = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(f32(taps[i]) * padded[i:i + t] for i in range(k))


def kda(h, lp, config: dict):
    """The KDA layer of normed h [T, D], through W_o: -> [T, D]."""
    t = h.shape[0]
    nh, hd = config["num_attention_heads"], config["head_dim"]

    def head(w, conv):
        return jax.nn.silu(causal_conv(h @ f32(lp[w]), lp[conv])).reshape(
            t, nh, hd)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(head("w_q", "conv_q")) * hd ** -0.5
    k = unit(head("w_k", "conv_k"))
    v = head("w_v", "conv_v")
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(f32(lp["a_log"]))[:, None]
        * (h @ f32(lp["w_f"]) + f32(lp["b_f"])).reshape(t, nh, hd))
    beta = jax.nn.sigmoid(h @ f32(lp["w_beta"]))             # [T, H]

    def step(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[..., None]                        # [H, dk, dv]
        u = beta[:, None] * (v - jnp.einsum("hc,hcv->hv", k, s))
        s = s + k[..., None] * u[:, None, :]
        return s, jnp.einsum("hc,hcv->hv", q, s)

    _, o = jax.lax.scan(step, jnp.zeros((nh, hd, hd), F32),
                        (q, k, v, g, beta))
    o = rms_norm(o, lp["o_norm_scale"], config["rms_norm_eps"])
    return (o * head_gate(h, lp)).reshape(t, nh * hd) @ f32(lp["w_out"])


def latent_attention(h, lp, pos, config: dict):
    """Latent attention of normed h [T, D] over every earlier position,
    through the gate and W_o: -> [T, D]."""
    t = h.shape[0]
    nh, rkv = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, theta = config["v_head_dim"], config["rope_theta"]
    hg = _block(nh, HEAD_GROUP)
    groups = nh // hg
    kv = h @ f32(lp["wkv_a"])
    c_kv = rms_norm(kv[:, :rkv], lp["kv_norm_scale"],
                    config["rms_norm_eps"])
    k_rope = rope(kv[:, rkv:], pos, theta)                   # [T, rp]
    gate = head_gate(h, lp)                                  # [T, H, 1]
    qb = _block(t, QUERY_BLOCK)
    stacked = (
        lp["w_q"].reshape(-1, groups, hg * (nope + rp)).swapaxes(0, 1),
        lp["wkv_b"].reshape(rkv, groups, hg * (nope + vd)).swapaxes(0, 1),
        lp["w_out"].reshape(groups, hg * vd, -1),
        gate.reshape(t, groups, hg, 1).swapaxes(0, 1))

    def group(out, ws):
        w_q, wkv_b, w_out, gate = ws
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
            live = jnp.arange(t)[None, :] <= rows[:, None]
            s = jnp.where(live[None], s * (nope + rp) ** -0.5, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, hg, vd)
        return out + (att * gate).reshape(t, hg * vd) @ f32(w_out), None

    return jax.lax.scan(group, jnp.zeros_like(h), stacked)[0]


def routing(h2, lp, config: dict):
    """-> (chosen expert ids [T, k], their weights [T, k]): sigmoid
    scores; on score + bias the `topk_group` groups whose two largest sum
    highest, then the k largest inside them; weights from the scores
    alone, normalised and scaled."""
    s = jax.nn.sigmoid(h2 @ f32(lp["router"]))
    biased = s + f32(lp["router_bias"])
    t, n_group = h2.shape[0], config["n_group"]
    grouped = biased.reshape(t, n_group, -1)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)        # [T, groups]
    _, keep = jax.lax.top_k(score, config["topk_group"])
    kept = jnp.any(keep[..., None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, -1)
    _, chosen = jax.lax.top_k(masked, config["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, chosen, -1)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights * config["routed_scaling_factor"]


def routed_part(h2, lp, config: dict):
    """What the held experts add: a plain loop over them, each over every
    token, weighted by the router's weight for it (zero where the token
    did not choose it)."""
    chosen, weights = routing(h2, lp, config)
    first = config.get("experts_held_from", 0)

    def expert(y, e):
        i, w_gate, w_up, w_down = e
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), -1)
        out = (jax.nn.silu(h2 @ f32(w_gate).T) * (h2 @ f32(w_up).T)) \
            @ f32(w_down)
        return y + mine[:, None] * out, None

    held = lp["we_gate"].shape[0]
    return jax.lax.scan(expert, jnp.zeros_like(h2),
                        (jnp.arange(held), lp["we_gate"], lp["we_up"],
                         lp["we_down"]))[0]


def shared_part(h2, lp):
    return swiglu(h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def feed_forward(h2, lp, config: dict):
    if "router" in lp:
        return routed_part(h2, lp, config) + shared_part(h2, lp)
    return swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    eps = config["rms_norm_eps"]
    pos = jnp.arange(seq.shape[0])
    x = f32(params["embed"][seq])
    for lp, (mixer, _) in zip(params["layers"], layer_kinds(config)):
        h = rms_norm(x, lp["attn_norm_scale"], eps)
        x = x + (kda(h, lp, config) if mixer == "kda"
                 else latent_attention(h, lp, pos, config))
        x = x + feed_forward(rms_norm(x, lp["ffn_norm_scale"], eps), lp,
                             config)
    return rms_norm(x, params["final_ln_scale"], eps)


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: features(params, seq, config) @ f32(params["head"]).T,
        tokens)


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]. The
    logits are made a block of positions at a time."""

    def one(seq):
        x = features(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)
        head = f32(params["head"])
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)
            lp = jax.nn.log_softmax(xs @ head.T, -1)
            return jnp.take_along_axis(lp, want[:, None], -1)[:, 0]

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
