"""Plain reference for a decoder with latent (MLA) attention, a learned
sparse selection of the keys each query attends to, and sigmoid-routed
experts with a shared expert: the layer that `model_type: glm_moe_dsa`
(GLM-5.2) names, in the DeepSeek-V3.2 formulation. Written from the layer
equations in `jax.numpy`, float32, no kernels, no cache; it calls nothing
of `ray_tpu`. Every function takes the configuration file's data and reads
its sizes from the published keys.

The layer. Input x [T, D], eps `rms_norm_eps`, rotary `rope_theta`,
interleaved pairs (x[2i], x[2i+1]) (`rope_interleave`).

1. h = RMSNorm(x). Queries: c_q = RMSNorm(h W_qa) (`q_lora_rank`);
   q = c_q W_qb -> heads x (`qk_nope_head_dim` + `qk_rope_head_dim`) =
   [q_nope | q_rope], rotary on q_rope. Keys and values:
   [c_kv (`kv_lora_rank`) | k_rope] = h W_kva; c_kv = RMSNorm(c_kv);
   rotary on k_rope, one head shared by all; per head
   [k_nope | v (`v_head_dim`)] = c_kv W_kvb. Score
   (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope). A cache would
   hold c_kv and k_rope per token.
2. Indexer, in layers whose `indexer_types` entry is "full":
   q_I = c_q W_Iq -> `index_n_heads` x `index_head_dim`,
   k_I = LayerNorm(h W_Ik) (one head), rotary on the first
   `qk_rope_head_dim` dims of both, w = h W_Iw x heads^-1/2 x dim^-1/2;
   I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s]); S_t = the
   `index_topk` positions s <= t of largest I (all of them while
   t < index_topk), exact; of equal scores the earlier position. A
   "shared" layer uses S_t of the nearest
   "full" layer before it.
3. Attention: softmax over s in S_t only; x += concat_h(sum_s p v) W_o.
4. h2 = RMSNorm(x). Dense layer (`mlp_layer_types` "dense"): SwiGLU of
   width `intermediate_size`. Sparse layer: g = sigmoid(h2 W_r) over the
   router's published width; choose the `num_experts_per_tok` largest of
   g + b (`noaux_tc`, `n_group` 1: no group limit); weights
   g_e / sum g_e x `routed_scaling_factor`; y = sum_e weight_e
   SwiGLU_e(h2) (width `moe_intermediate_size`) + SwiGLU_shared(h2).
   x += y. Only the experts this chip holds (`experts_held_from`,
   `n_routed_experts` of them) add their part; what the absent ones would
   add is left out, here as in the program.
5. Final RMSNorm, untied head over the rows of the vocabulary held.

The multi-token-prediction module is no part of the next-token forward
pass and is left out. Conventions the source's keys do not settle are the
configuration file's `assumed`.

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_ln_scale [D]; "layers": a list, one dict a layer, with
attn_norm_scale, ffn_norm_scale [D]; wq_a [D, Rq]; q_norm_scale [Rq];
wq_b [Rq, H*(nope+rope)]; wkv_a [D, Rkv+rope]; kv_norm_scale [Rkv];
wkv_b [Rkv, H*(nope+v)]; w_out [H*v, D]; in "full" layers wi_q [Rq, J*Di],
wi_k [D, Di], ik_norm_scale, ik_norm_bias [Di], wi_w [D, J]; in a dense
layer w_gate, w_up [D, F], w_down [F, D]; in a sparse layer router
[D, E_published], router_bias [E_published], we_gate, we_up, we_down
[E_held, Fe, D] (every expert matrix with its expert width first, so that
a slice of the width is whole rows), ws_gate, ws_up [D, Fs], ws_down
[Fs, D].

The reference runs beside the served model's weights and cache, on a
sequence padded to the engine's longest: it upcasts at use, walks heads in
groups, queries in blocks and the held experts one at a time, and never
holds a [T, T] float array (the selection is a [T, T] bool).

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_GROUP = 8          # heads whose keys and values are held at once
QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
INDEX_BLOCK = 128       # queries whose index scores are held at once
TOKEN_BLOCK = 1024      # positions whose logits are held at once


def layer_kinds(config: dict) -> list:
    """[(mlp type, indexer type)] of the layers that run: the published
    lists from `layers_from` on."""
    lo = config.get("layers_from", 0)
    hi = lo + config["num_hidden_layers"]
    kinds = list(zip(config["mlp_layer_types"][lo:hi],
                     config["indexer_types"][lo:hi]))
    if kinds[0][1] != "full":
        raise ValueError("the first layer run must own an indexer")
    return kinds


def router_width(config: dict) -> int:
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])


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


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(scale) + f32(bias)


def rope(x, pos, theta: float):
    """Rotary embedding on the last axis of x [T, ..., d], interleaved
    pairs: (x[2i], x[2i+1]) turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv                      # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def top_k_mask(scores, k: int):
    """bool, true at the k largest of each row; of equal scores at the
    threshold the earliest positions are taken, as `jax.lax.top_k` takes
    them."""
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above, ties = scores > kth, scores == kth
    need = k - jnp.sum(above, -1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, -1) <= need))


def swiglu(h, w_gate, w_up, w_down):
    """[D, F], [D, F], [F, D] matrices."""
    return (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call: the share's
    shapes (`n_routed_experts` experts held, `vocab_size` rows)."""
    d, nh = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd = config["v_head_dim"]
    ij, idim = config["index_n_heads"], config["index_head_dim"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    fs = fe * config["n_shared_experts"]
    held, width = config["n_routed_experts"], router_width(config)
    kinds = layer_kinds(config)
    residual = (2.0 * len(kinds)) ** -0.5
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, F32) * scale).astype(bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 2 + 20 * len(kinds)))
    layers = []
    for mlp, indexer in kinds:
        lp = {
            "attn_norm_scale": ones(d), "ffn_norm_scale": ones(d),
            "wq_a": normal(next(keys), (d, rq), d ** -0.5),
            "q_norm_scale": ones(rq),
            "wq_b": normal(next(keys), (rq, nh * (nope + rp)), rq ** -0.5),
            "wkv_a": normal(next(keys), (d, rkv + rp), d ** -0.5),
            "kv_norm_scale": ones(rkv),
            "wkv_b": normal(next(keys), (rkv, nh * (nope + vd)),
                            rkv ** -0.5),
            "w_out": normal(next(keys), (nh * vd, d),
                         (nh * vd) ** -0.5 * residual),
        }
        if indexer == "full":
            lp.update({
                "wi_q": normal(next(keys), (rq, ij * idim), rq ** -0.5),
                "wi_k": normal(next(keys), (d, idim), d ** -0.5),
                "ik_norm_scale": ones(idim),
                "ik_norm_bias": jnp.zeros((idim,), bf),
                "wi_w": normal(next(keys), (d, ij), d ** -0.5),
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
    return {"embed": normal(next(keys), (v, d), 0.02),
            "head": normal(next(keys), (v, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def index_selection(h, c_q, lp, pos, config: dict):
    """The indexer of a "full" layer: -> bool [T, T], row t true at the
    positions S_t (the `index_topk` largest I[t, s] over s <= t, every
    s <= t while there are no more than that)."""
    t = h.shape[0]
    ij, idim = config["index_n_heads"], config["index_head_dim"]
    rp, theta = config["qk_rope_head_dim"], config["rope_theta"]
    eps = config["rms_norm_eps"]

    def turned(x):
        return jnp.concatenate([rope(x[..., :rp], pos, theta),
                                x[..., rp:]], -1)

    q_i = turned((c_q @ f32(lp["wi_q"])).reshape(t, ij, idim))
    k_i = turned(layer_norm(h @ f32(lp["wi_k"]), lp["ik_norm_scale"],
                            lp["ik_norm_bias"], eps))
    w = (h @ f32(lp["wi_w"])) * (ij ** -0.5 * idim ** -0.5)
    k = min(config["index_topk"], t)
    qb = _block(t, INDEX_BLOCK)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q_i, i * qb, qb)
        ws = jax.lax.dynamic_slice_in_dim(w, i * qb, qb)
        scores = jnp.einsum("qj,qjk->qk", ws, jax.nn.relu(
            jnp.einsum("qjd,kd->qjk", qs, k_i)))
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        return causal & top_k_mask(scores, k)

    return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, t)


def attention(h, c_q, lp, selected, pos, config: dict):
    """Latent attention of normed h [T, D] over the selected positions
    (bool [T, T]), through W_o: -> [T, D]."""
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
        lp["wq_b"].reshape(-1, groups, hg * (nope + rp)).swapaxes(0, 1),
        lp["wkv_b"].reshape(rkv, groups, hg * (nope + vd)).swapaxes(0, 1),
        lp["w_out"].reshape(groups, hg * vd, -1))

    def group(out, ws):
        wq_b, wkv_b, w_out = ws
        q = (c_q @ f32(wq_b)).reshape(t, hg, nope + rp)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta)
        kv_h = (c_kv @ f32(wkv_b)).reshape(t, hg, nope + vd)
        k_nope, v = kv_h[..., :nope], kv_h[..., nope:]

        def block(i):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
            live = jax.lax.dynamic_slice_in_dim(selected, i * qb, qb)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qr, k_rope))
            s = jnp.where(live[None], s * (nope + rp) ** -0.5, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                              v).reshape(qb, hg * vd)

        att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, hg * vd)
        return out + att @ f32(w_out), None

    return jax.lax.scan(group, jnp.zeros_like(h), stacked)[0]


def routing(h2, lp, config: dict):
    """-> (chosen expert ids [T, k], their weights [T, k]): sigmoid
    scores, the k largest of score + bias, weights from the scores
    alone, normalised and scaled."""
    g = jax.nn.sigmoid(h2 @ f32(lp["router"]))
    _, chosen = jax.lax.top_k(g + f32(lp["router_bias"]),
                              config["num_experts_per_tok"])
    weights = jnp.take_along_axis(g, chosen, -1)
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


def features(params, seq, config: dict, selections: list | None = None):
    """seq [T] -> final-normed activations [T, D]. With `selections`, a
    list, each "full" layer's S_t (bool [T, T]) is appended to it."""
    eps = config["rms_norm_eps"]
    t = seq.shape[0]
    pos = jnp.arange(t)
    x = f32(params["embed"])[seq]
    selected = None
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm_scale"], eps)
        c_q = rms_norm(h @ f32(lp["wq_a"]), lp["q_norm_scale"], eps)
        if "wi_q" in lp:
            selected = index_selection(h, c_q, lp, pos, config)
            if selections is not None:
                selections.append(selected)
        x = x + attention(h, c_q, lp, selected, pos, config)
        x = x + feed_forward(rms_norm(x, lp["ffn_norm_scale"], eps), lp, config)
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
        # the whole padded sequence (causal: the last position's output
        # is dropped), so that the blocks divide it
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
