"""Plain reference for a decoder whose layers are Mamba-2 state-space
layers, LatentMoE expert layers and grouped-head attention layers, each a
mixer or a feed-forward part alone: the language model of
`NVIDIA-Nemotron-3-Super-120B-A12B-BF16` (`nemotron_h`; the state layer
after Mamba-2, arXiv:2405.21060). Written from the layer equations in
`jax.numpy`, float32, no kernels, no cache, no chunk form; it calls
nothing of `ray_tpu`. Every function takes the configuration file's data
and reads its sizes from the published keys.

Which layer is which: published layer `l` is what character `l` of
`hybrid_override_pattern` says, `M` a state layer, `E` an expert layer,
`*` an attention layer; the layers that run are `layers_from ..
layers_from + num_hidden_layers - 1`. Every layer is `x += f(n)`, `n =
RMSNorm(x)` with one learned scale and `layer_norm_epsilon`; after the
last an RMSNorm and the untied head.

`M` (H = `mamba_num_heads` heads of P = `mamba_head_dim`, G = `n_groups`,
N = `ssm_state_size`, K = `conv_kernel`):

    [z | xBC | dt] = W_in n        (H P | H P + 2 G N | H)
    xBC_t <- SiLU(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j}), a channel of its
        own taps, zeros before the sequence (K shifted products)
    xBC -> x_t [H, P], B_t, C_t [G, N]; head h reads group h // (H / G)
    d_t = softplus(dt_t + dt_bias) [H];  a_t = exp(d_t A), A = -exp(A_log)
    S_t = a_t S_{t-1} + d_t x_t B_t^T,  S_0 = 0, [P, N] a head, a
        `lax.scan` over the positions
    y_t = S_t C_t + D x_t
    x += W_out ( RMSNorm_groups(y_t * SiLU(z_t)) ): the gate first, then
        the mean square over each of G groups of H P / G channels, eps
        `layer_norm_epsilon`, one learned scale [H P]

`*`: q = W_q n (`num_attention_heads` heads of `head_dim`), k, v
(`num_key_value_heads` heads), no bias, no positional term; query head h
reads key-value head h // (Hq / Hkv); softmax over every earlier position
of q . k / sqrt(head_dim); W_o.

`E`: s = sigmoid(W_r n) over the router's published width; the
`num_experts_per_tok` largest of s + b chosen (`n_group` 1: no group
limit); weights s of the chosen, normalised to 1 (`norm_topk_prob`), x
`routed_scaling_factor`; u = W_down n (`moe_latent_size`); routed =
W_up sum_e w_e W2_e relu(W1_e u)^2 with experts of
`moe_intermediate_size` in the latent and no gate matrix
(`mlp_hidden_act` relu2); shared = W2_s relu(W1_s n)^2 of
`moe_shared_expert_intermediate_size` on the full hidden. Only the
experts this chip holds (`experts_held_from`, `n_routed_experts` of them)
add their part; what the absent ones would add is left out, here as in
the program.

Departures from the published model: the multi-token-prediction module
(`num_nextn_predict_layers`) is no part of the next-token forward pass
and is left out. Conventions the source's keys do not settle are the
configuration file's `assumed`.

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_norm_scale [D]; "layers": a list, one dict a layer,
with norm_scale [D]; in a state layer w_in [D, 2 H P + 2 G N + H]; conv_w
[K, H P + 2 G N]; conv_b [H P + 2 G N]; dt_bias, a_log, d_skip [H];
gate_norm_scale [H P]; w_out [H P, D]; in an attention layer w_q [D,
Hq d]; w_k, w_v [D, Hkv d]; w_out [Hq d, D]; in an expert layer router
[D, E_published], router_bias [E_published], latent_down [D, R], we_up,
we_down [E_held, F, R], latent_up [R, D], ws_up [D, Fs], ws_down [Fs, D].

The reference runs beside the served model's weights and pool, on a
sequence padded to the engine's longest: it upcasts at use, walks
queries in blocks and key-value heads one at a time, the held experts
one at a time (each over every token: 14 TFLOP a layer at 10,240
positions, which is most of its 95 TFLOP a padded sequence) and the head
a block of positions at a time (the embedding is indexed before it is
widened).

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries that attend to the whole sequence at once
TOKEN_BLOCK = 1024      # positions whose logits are held at once


def layer_kinds(config: dict) -> str:
    """The pattern's characters of the layers that run."""
    lo = config.get("layers_from", 0)
    return config["hybrid_override_pattern"][
        lo:lo + config["num_hidden_layers"]]


def router_width(config: dict) -> int:
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


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


def relu2_mlp(h, w_up, w_down):
    """[D, F] and [F, D] matrices."""
    return jnp.square(jax.nn.relu(h @ f32(w_up))) @ f32(w_down)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call: the share's
    shapes (`n_routed_experts` experts held, `vocab_size` rows). The
    file's `draws` names every scale that is not fan-in^-1/2 (its
    `assumed` says why each): a head's step `softplus(dt_bias)`
    log-spaced over the heads between `time_step_min` and
    `time_step_max` (floored at `time_step_floor`), `A` uniform in
    `a_range`, the skip `D` at `d_skip`, the embedding at `embed_scale`,
    a state layer's W_out at `mamba_out_gain` and the latent's W_up at
    `latent_up_gain` times the residual outputs' scale."""
    draws = config["draws"]
    d = config["hidden_size"]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, ns, taps = (config["n_groups"], config["ssm_state_size"],
                   config["conv_kernel"])
    inner, ch = h * p, h * p + 2 * g * ns
    hq, hkv, hd = (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"])
    lat, fe = config["moe_latent_size"], config["moe_intermediate_size"]
    fs = config["moe_shared_expert_intermediate_size"]
    held, width = config["n_routed_experts"], router_width(config)
    kinds = layer_kinds(config)
    residual = float(len(kinds)) ** -0.5
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    def centred(k, shape, scale):
        """`normal` with zero sum over the input channels (the axis
        before the last, or for an expert's [F, R] the width): what
        reads a one-signed activation (relu^2; the gated norm of a y
        whose x, B and C are SiLU's) then adds no vector that every
        token shares, which random routers would all follow. The mean
        is taken in float32 and the draw stays bfloat16."""
        w = jax.random.normal(k, shape, bf)
        mean = jnp.mean(w, 1 if len(shape) == 3 else 0, keepdims=True,
                        dtype=F32)
        return (w - mean.astype(bf)) * jnp.asarray(scale, bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 2 + 8 * len(kinds)))
    step = jnp.maximum(jnp.exp(jnp.linspace(
        jnp.log(config["time_step_min"]), jnp.log(config["time_step_max"]),
        h)), config["time_step_floor"]).astype(F32)
    layers = []
    for kind in kinds:
        lp = {"norm_scale": ones(d)}
        if kind == "M":
            lp.update({
                "w_in": normal(next(keys), (d, inner + ch + h), d ** -0.5),
                "conv_w": normal(next(keys), (taps, ch), taps ** -0.5),
                "conv_b": normal(next(keys), (ch,), draws["conv_bias"]),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), F32, *draws["a_range"])),
                "d_skip": jnp.full((h,), draws["d_skip"], F32),
                "gate_norm_scale": ones(inner),
                "w_out": centred(next(keys), (inner, d),
                                 inner ** -0.5 * residual
                                 * draws["mamba_out_gain"]),
            })
        elif kind == "*":
            lp.update({
                "w_q": normal(next(keys), (d, hq * hd), d ** -0.5),
                "w_k": normal(next(keys), (d, hkv * hd), d ** -0.5),
                "w_v": normal(next(keys), (d, hkv * hd), d ** -0.5),
                "w_out": normal(next(keys), (hq * hd, d),
                                (hq * hd) ** -0.5 * residual),
            })
        else:
            lp.update({
                "router": normal(next(keys), (d, width), d ** -0.5),
                # small beside the scores' spread: the correction bias
                # exists to level the experts' load, not to skew it
                "router_bias": normal(next(keys), (width,), 0.01),
                "latent_down": normal(next(keys), (d, lat), d ** -0.5),
                "we_up": normal(next(keys), (held, fe, lat), lat ** -0.5),
                "we_down": centred(next(keys), (held, fe, lat), fe ** -0.5),
                "latent_up": normal(next(keys), (lat, d),
                                    lat ** -0.5 * residual
                                    * draws["latent_up_gain"]),
                "ws_up": normal(next(keys), (d, fs), d ** -0.5),
                "ws_down": centred(next(keys), (fs, d),
                                   fs ** -0.5 * residual),
            })
        layers.append(lp)
    v = config["vocab_size"]
    return {"embed": normal(next(keys), (v, d), draws["embed_scale"]),
            "head": normal(next(keys), (v, d), d ** -0.5),
            "final_norm_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def causal_conv(x, taps, bias):
    """x [T, C], taps [K, C], bias [C] -> [T, C]: K shifted products,
    zeros before the sequence."""
    k, t = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(f32(taps[i]) * padded[i:i + t] for i in range(k)) + f32(bias)


def state_layer(n, lp, config: dict):
    """The Mamba-2 layer of normed n [T, D], through W_out: -> [T, D]."""
    t = n.shape[0]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, ns = config["n_groups"], config["ssm_state_size"]
    inner = h * p
    w_in = lp["w_in"]
    z = n @ f32(w_in[:, :inner])
    xbc = n @ f32(w_in[:, inner:2 * inner + 2 * g * ns])
    dt = n @ f32(w_in[:, 2 * inner + 2 * g * ns:])
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    x = xbc[:, :inner].reshape(t, h, p)
    b = xbc[:, inner:inner + g * ns].reshape(t, g, ns)
    c = xbc[:, inner + g * ns:].reshape(t, g, ns)
    step = jax.nn.softplus(dt + f32(lp["dt_bias"]))          # [T, H]
    decay = jnp.exp(step * -jnp.exp(f32(lp["a_log"])))

    def token(s, row):
        x, b, c, step, decay = row
        # head h reads group h // (H / G)
        b, c = jnp.repeat(b, h // g, 0), jnp.repeat(c, h // g, 0)
        s = decay[:, None, None] * s \
            + (step[:, None] * x)[:, :, None] * b[:, None, :]    # [H, P, N]
        return s, jnp.einsum("hpn,hn->hp", s, c)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, ns), F32),
                        (x, b, c, step, decay))
    y = (y + f32(lp["d_skip"])[:, None] * x).reshape(t, inner) \
        * jax.nn.silu(z)
    grouped = y.reshape(t, g, inner // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True)
        + config["layer_norm_epsilon"])
    return (grouped.reshape(t, inner) * f32(lp["gate_norm_scale"])) \
        @ f32(lp["w_out"])


def attention_layer(n, lp, config: dict):
    """Grouped-head causal attention of normed n [T, D] over every earlier
    position, no positional term, through W_o: -> [T, D]."""
    t = n.shape[0]
    hq, hkv, hd = (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"])
    per = hq // hkv
    qb = _block(t, QUERY_BLOCK)
    k = (n @ f32(lp["w_k"])).reshape(t, hkv, hd).swapaxes(0, 1)
    v = (n @ f32(lp["w_v"])).reshape(t, hkv, hd).swapaxes(0, 1)
    stacked = (lp["w_q"].reshape(-1, hkv, per * hd).swapaxes(0, 1),
               lp["w_out"].reshape(hkv, per * hd, -1), k, v)

    def kv_head(out, ws):
        w_q, w_out, k, v = ws
        q = (n @ f32(w_q)).reshape(t, per, hd)

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


def routing(n, lp, config: dict):
    """-> (chosen expert ids [T, k], their weights [T, k]): sigmoid
    scores; the k largest of score + bias; weights from the scores alone,
    normalised and scaled."""
    s = jax.nn.sigmoid(n @ f32(lp["router"]))
    _, chosen = jax.lax.top_k(s + f32(lp["router_bias"]),
                              config["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, chosen, -1)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights * config["routed_scaling_factor"]


def routed_latent(n, lp, config: dict):
    """What the held experts add, in the latent [T, R]: a plain loop over
    them, each over every token, weighted by the router's weight for it
    (zero where the token did not choose it)."""
    chosen, weights = routing(n, lp, config)
    first = config.get("experts_held_from", 0)
    u = n @ f32(lp["latent_down"])

    def expert(r, e):
        i, w_up, w_down = e
        mine = jnp.sum(jnp.where(chosen == first + i, weights, 0.0), -1)
        out = jnp.square(jax.nn.relu(u @ f32(w_up).T)) @ f32(w_down)
        return r + mine[:, None] * out, None

    held = lp["we_up"].shape[0]
    return jax.lax.scan(expert, jnp.zeros_like(u),
                        (jnp.arange(held), lp["we_up"], lp["we_down"]))[0]


def expert_layer(n, lp, config: dict):
    return (routed_latent(n, lp, config) @ f32(lp["latent_up"])
            + relu2_mlp(n, lp["ws_up"], lp["ws_down"]))


LAYERS = {"M": state_layer, "*": attention_layer, "E": expert_layer}


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    eps = config["layer_norm_epsilon"]
    x = f32(params["embed"][seq])
    for lp, kind in zip(params["layers"], layer_kinds(config)):
        x = x + LAYERS[kind](rms_norm(x, lp["norm_scale"], eps), lp, config)
    return rms_norm(x, params["final_norm_scale"], eps)


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
