"""Plain reference for a decoder whose layer holds two latent-attention
(MLA) blocks and two dense MLPs in a row, with the routed experts on a
shortcut beside them and a softmax router whose last outputs are
zero-computation experts: the layer that `LongCat-Flash-Chat` names (the
LongCat-Flash technical report, arXiv:2509.01322: shortcut-connected
MoE). Written from the layer equations in `jax.numpy`, float32, expanded
heads, no kernels, no cache, no batching; it calls nothing of `ray_tpu`.
Every function takes the configuration file's data and reads its sizes
from the published keys.

Published layer `l`, input x [T, D], eps `rms_norm_eps`, no bias anywhere
(`attention_bias` false):

    a1 = x  + attn_{2l}  (RMSNorm_in0(x))
    n1 = RMSNorm_post0(a1)
    s  = moe(n1)                          the shortcut: used at the layer's
                                          end only
    h1 = a1 + mlp_0(n1)
    a2 = h1 + attn_{2l+1}(RMSNorm_in1(h1))
    y  = a2 + mlp_1(RMSNorm_post1(a2)) + s

    mlp_i(n) = W_down (silu(W_gate n) * W_up n)        `ffn_hidden_size`

    attn(n), H = `num_attention_heads` heads:
      c_q = RMSNorm(n W_qa) (`q_lora_rank`);
      q = (c_q W_qb) x (D / q_lora_rank)^1/2 (`mla_scale_q_lora`)
        -> heads x (`qk_nope_head_dim` + `qk_rope_head_dim`) = [q_nope | q_rope]
      [c | k_r] = n W_kva (`kv_lora_rank` + rope); c = RMSNorm(c); a cache
        would hold [c | rope(k_r)] a position
      [k_nope | v]_h = (c x (D / kv_lora_rank)^1/2) W_kvb
        (`mla_scale_kv_lora`: keys and values)
      rotary (interleaved pairs, `rope_theta`, no scaling of positions) on
        q_rope and on k_r, one rotary key shared by all heads
      causal softmax((q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope))
        over every earlier position, times v_h; then W_o

    moe(n), router `n_routed_experts` (published) + `zero_expert_num`
    wide, `moe_topk` a token:
      p = softmax(n W_r) over the whole width
      chosen = the `moe_topk` largest of p + e_score_correction_bias (the
        bias in the choice alone)
      w_e = p_e x `routed_scaling_factor` (the chosen are not
        renormalised)
      moe = sum over chosen e < published n_routed_experts of
              w_e W2_e (silu(W1_e n) * W3_e n)   (`expert_ffn_hidden_size`)
          + (sum over the other chosen e of w_e) x n    (`zero_expert_type`
              identity: such an expert returns its input)
    Only the experts this chip holds (`experts_held_from`,
    `n_routed_experts` of them) add their part; what the absent ones
    would add is left out, here as in the program. The identity experts
    have no weights: a token's own chip adds their part, so it is whole
    here.

    logits = RMSNorm_final(y) W_head^T          (head untied)

Conventions the source's keys do not settle are the configuration file's
`assumed`.

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_ln_scale [D]; "layers": a list, one dict a published
layer, with "attn": a pair of dicts (attn_norm_scale [D], the block's
input norm; wq_a [D, Rq]; q_norm_scale [Rq]; wq_b [Rq, H*(nope+rope)];
wkv_a [D, Rkv+rope]; kv_norm_scale [Rkv]; wkv_b [Rkv, H*(nope+v)]; w_out
[H*v, D]), "mlp": a pair of dicts (ffn_norm_scale [D], the norm after the
attention block before it; w_gate, w_up [D, F]; w_down [F, D]), router
[D, E_published + zero] and router_bias [E_published + zero] (float32
leaves of bfloat16 values: the program reads them in float32), we_gate,
we_up, we_down [E_held, Fe, D].

The reference runs beside the served model's weights and pool, on a
sequence padded to the engine's longest: it upcasts at use, walks heads
in groups, queries in blocks, an MLP's width in slices, the held experts
one at a time and the head a block of positions at a time.

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
WIDTH_SLICE = 2048      # of a dense MLP's width, held at once
TOKEN_BLOCK = 512       # positions whose logits are held at once


def routed_width(config: dict) -> int:
    """The router's outputs that have an expert, over all chips."""
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


def router_width(config: dict) -> int:
    return routed_width(config) + config["zero_expert_num"]


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
    ang = pos.astype(F32)[:, None] * inv                      # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call: the share's
    shapes (`n_routed_experts` experts held, `vocab_size` rows). The
    file's `draws` names every scale that is not fan-in^-1/2 (its
    `assumed` says why each): the embedding at `embed_scale`; W_o at
    fan-in^-1/2 x `attention_out_gain`; the router at D^-1/2 x
    `router_gain`, so that a token's logits spread by `router_gain` and
    its `moe_topk` chosen hold most of a softmax over the whole width;
    `router_bias` normal at `router_bias`. The router and its bias are
    bfloat16 values kept in float32 leaves: the steps read them there,
    and a served tree then needs no conversion at load."""
    draws = config["draws"]
    d, nh = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd = config["v_head_dim"]
    f, fe = config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    held, width = config["n_routed_experts"], router_width(config)
    n_layers = config["num_layers"]
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 2 + 24 * n_layers))

    def attention():
        return {
            "attn_norm_scale": ones(d),
            "wq_a": normal(next(keys), (d, rq), d ** -0.5),
            "q_norm_scale": ones(rq),
            "wq_b": normal(next(keys), (rq, nh * (nope + rp)), rq ** -0.5),
            "wkv_a": normal(next(keys), (d, rkv + rp), d ** -0.5),
            "kv_norm_scale": ones(rkv),
            "wkv_b": normal(next(keys), (rkv, nh * (nope + vd)),
                            rkv ** -0.5),
            "w_out": normal(next(keys), (nh * vd, d),
                            (nh * vd) ** -0.5 * draws["attention_out_gain"]),
        }

    def mlp():
        return {
            "ffn_norm_scale": ones(d),
            "w_gate": normal(next(keys), (d, f), d ** -0.5),
            "w_up": normal(next(keys), (d, f), d ** -0.5),
            "w_down": normal(next(keys), (f, d), f ** -0.5),
        }

    layers = [{
        "attn": (attention(), attention()), "mlp": (mlp(), mlp()),
        "router": f32(normal(next(keys), (d, width),
                             d ** -0.5 * draws["router_gain"])),
        "router_bias": f32(normal(next(keys), (width,),
                                  draws["router_bias"])),
        "we_gate": normal(next(keys), (held, fe, d), d ** -0.5),
        "we_up": normal(next(keys), (held, fe, d), d ** -0.5),
        "we_down": normal(next(keys), (held, fe, d), fe ** -0.5),
    } for _ in range(n_layers)]
    v = config["vocab_size"]
    return {"embed": normal(next(keys), (v, d), draws["embed_scale"]),
            "head": normal(next(keys), (v, d), d ** -0.5),
            "final_ln_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def attention(n, ap, pos, config: dict):
    """Latent attention of normed n [T, D] over every earlier position,
    heads expanded, through W_o: -> [T, D]."""
    t, d = n.shape
    nh, rkv = config["num_attention_heads"], config["kv_lora_rank"]
    rq = config["q_lora_rank"]
    nope, rp = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, theta = config["v_head_dim"], config["rope_theta"]
    eps = config["rms_norm_eps"]
    q_scale = (d / rq) ** 0.5 if config["mla_scale_q_lora"] else 1.0
    kv_scale = (d / rkv) ** 0.5 if config["mla_scale_kv_lora"] else 1.0
    hg = _block(nh, HEAD_GROUP)
    groups = nh // hg
    c_q = rms_norm(n @ f32(ap["wq_a"]), ap["q_norm_scale"], eps)
    kv = n @ f32(ap["wkv_a"])
    c = rms_norm(kv[:, :rkv], ap["kv_norm_scale"], eps) * kv_scale
    k_rope = rope(kv[:, rkv:], pos, theta)                     # [T, rp]
    qb = _block(t, QUERY_BLOCK)
    stacked = (
        ap["wq_b"].reshape(rq, groups, hg * (nope + rp)).swapaxes(0, 1),
        ap["wkv_b"].reshape(rkv, groups, hg * (nope + vd)).swapaxes(0, 1),
        ap["w_out"].reshape(groups, hg * vd, d))

    def group(out, ws):
        wq_b, wkv_b, w_out = ws
        q = ((c_q @ f32(wq_b)) * q_scale).reshape(t, hg, nope + rp)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta)
        kv_h = (c @ f32(wkv_b)).reshape(t, hg, nope + vd)
        k_nope, v = kv_h[..., :nope], kv_h[..., nope:]

        def block(i):
            rows = i * qb + jnp.arange(qb)
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qr, k_rope))
            live = jnp.arange(t)[None, :] <= rows[:, None]
            s = jnp.where(live[None], s * (nope + rp) ** -0.5, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                              v).reshape(qb, hg * vd)

        att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, hg * vd)
        return out + att @ f32(w_out), None

    return jax.lax.scan(group, jnp.zeros_like(n), stacked)[0]


def mlp(n, mp):
    """The dense SwiGLU of normed n [T, D], a slice of its width at a
    time: -> [T, D]."""
    d, f = mp["w_gate"].shape
    fs = _block(f, WIDTH_SLICE)
    stacked = (mp["w_gate"].reshape(d, f // fs, fs).swapaxes(0, 1),
               mp["w_up"].reshape(d, f // fs, fs).swapaxes(0, 1),
               mp["w_down"].reshape(f // fs, fs, d))

    def part(out, ws):
        w_gate, w_up, w_down = ws
        return out + (jax.nn.silu(n @ f32(w_gate)) * (n @ f32(w_up))) \
            @ f32(w_down), None

    return jax.lax.scan(part, jnp.zeros_like(n), stacked)[0]


def routing(n, lp, config: dict):
    """-> (chosen output ids [T, k], their weights [T, k]): softmax over
    the router's whole width, the k largest of p + bias, weights p x the
    scaling factor, not renormalised."""
    p = jax.nn.softmax(n @ f32(lp["router"]), -1)
    _, chosen = jax.lax.top_k(p + f32(lp["router_bias"]), config["moe_topk"])
    return chosen, jnp.take_along_axis(p, chosen, -1) \
        * config["routed_scaling_factor"]


def routed_part(n, lp, chosen, weights, config: dict):
    """What the held experts add: a plain loop over them, each over every
    token, weighted by the router's weight for it (zero where the token
    did not choose it)."""
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


def identity_part(n, chosen, weights, config: dict):
    """What the zero-computation experts add: each returns its input, so
    together n times the sum of their weights."""
    free = chosen >= routed_width(config)
    return jnp.sum(jnp.where(free, weights, 0.0), -1, keepdims=True) * n


def moe(n, lp, config: dict):
    chosen, weights = routing(n, lp, config)
    return routed_part(n, lp, chosen, weights, config) \
        + identity_part(n, chosen, weights, config)


def layer(x, lp, pos, config: dict):
    """One published layer: x [T, D] -> y [T, D]."""
    eps = config["rms_norm_eps"]
    (a0, a1), (m0, m1) = lp["attn"], lp["mlp"]
    a1_ = x + attention(rms_norm(x, a0["attn_norm_scale"], eps), a0, pos,
                        config)
    n1 = rms_norm(a1_, m0["ffn_norm_scale"], eps)
    s = moe(n1, lp, config)
    h1 = a1_ + mlp(n1, m0)
    a2 = h1 + attention(rms_norm(h1, a1["attn_norm_scale"], eps), a1, pos,
                        config)
    return a2 + mlp(rms_norm(a2, m1["ffn_norm_scale"], eps), m1) + s


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    pos = jnp.arange(seq.shape[0])
    x = f32(params["embed"][seq])
    for lp in params["layers"]:
        x = layer(x, lp, pos, config)
    return rms_norm(x, params["final_ln_scale"], config["rms_norm_eps"])


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
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)
            lp = jax.nn.log_softmax(xs @ f32(params["head"]).T, -1)
            return jnp.take_along_axis(lp, want[:, None], -1)[:, 0]

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
