"""Plain reference for a decoder whose every layer holds a Mamba-2 state
branch and a grouped-head attention branch side by side under one norm,
their outputs scaled and added into the residual, then a gated MLP: the
language model of `Falcon-H1-34B-Instruct` (`falcon_h1`; "Falcon-H1: A
Family of Hybrid-Head Language Models", arXiv:2507.22448). Written from
the layer equations in `jax.numpy`, float32, no kernels, no cache, no
chunk form; it calls nothing of `ray_tpu`. Every function takes the
configuration file's data and reads its sizes and its multipliers from
the published keys.

`n = RMSNorm(x)` with one learned scale and `rms_norm_eps`. The published
multipliers sit where the published modelling code has them
(`transformers/models/falcon_h1/modeling_falcon_h1.py`):

    x0 = E[token] * embedding_multiplier
    every layer (H = `mamba_n_heads` heads of P = `mamba_d_head`, G =
    `mamba_n_groups`, N = `mamba_d_state`, K = `mamba_d_conv`):
      n = RMSNorm_in(x)
      -- state branch
      [z | xBC | dt] = (W_in (n * ssm_in_multiplier)) * mup
          (H P | H P + 2 G N | H; `ssm_multipliers` by segment: z | x | B
          | C | dt)
      xBC_t <- SiLU(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j}), a channel of
          its own taps, zeros before the sequence
      xBC -> x_t [H, P], B_t, C_t [G, N]; head h reads group h // (H / G)
      d_t = softplus(dt_t + dt_bias);  a_t = exp(d_t A),  A = -exp(A_log)
      S_t = a_t S_{t-1} + d_t x_t B_t^T,  S_0 = 0, [P, N] a head, a
          `lax.scan` over the positions;   y_t = S_t C_t + D x_t
      g = RMSNorm_groups(y * SiLU(z)): the gate first
          (`mamba_norm_before_gate` false), then the mean square over
          each of G groups of H P / G channels, one learned scale [H P]
      m = (W_out g) * ssm_out_multiplier
      -- attention branch, the same n (`num_attention_heads` heads of
      `head_dim` over `num_key_value_heads`, no bias)
      na = n * attention_in_multiplier (the attention's one input)
      q = W_q na;  k = (W_k na) * key_multiplier;  v = W_v na;  rotary on
      every dim of q and k, in halves: (x[i], x[i + d/2]) turned by pos *
      rope_theta^(-2i/d)
      a = W_o softmax_causal(q k^T / sqrt(d)) v, query head h reads
          key-value head h // (Hq / Hkv)
      x <- x + m + a * attention_out_multiplier
      -- feed-forward
      f = RMSNorm_ff(x)
      x <- x + (W_d (W_u f * SiLU((W_g f) * mlp_multipliers[0])))
               * mlp_multipliers[1]
    logits = (W_head RMSNorm_final(x)) * lm_head_multiplier   (untied)

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_norm_scale [D]; "layers": a list, one dict a layer,
with norm_scale [D]; w_in [D, 2 H P + 2 G N + H]; conv_w [K, H P + 2 G N];
conv_b [H P + 2 G N]; dt_bias, a_log, d_skip [H]; gate_norm_scale [H P];
w_out [H P, D]; w_q [D, Hq d]; w_k, w_v [D, Hkv d]; w_o [Hq d, D];
ffn_norm_scale [D]; w_gate, w_up [D, F]; w_down [F, D].

The reference runs in the replica beside the served model's weights and
its whole pool, on a sequence padded to the engine's longest, so what it
holds at once decides how many slots and pages the cell can have (0.45 GB
at 16,384 positions by the chip's compiler; `tests/test_aot_tpu_compile.py`
holds it): the residual is the one array of the sequence's size and
width, written in place from the embedding's rows on; a layer walks it a
block of positions at a time (the recurrence's state and the
convolution's tail carried from block to block; a block's keys and
values, 1,024 numbers a position, written before its queries read), a
block of queries scored against the whole sequence at a time, the MLP a
block of its width at a time, and the final norm and the head a block of
positions by a block of vocabulary rows (a row of logits is 261,120
wide). Weights are upcast at use.

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOKEN_BLOCK = 512       # positions a layer works on at once
QUERY_BLOCK = 64        # queries that attend to the whole sequence at once
WIDTH_BLOCK = 3072      # columns of the MLP held at once
VOCAB_BLOCK = 8192      # rows of the head whose logits are held at once


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


def sizes(config: dict) -> dict:
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    g, ns = config["mamba_n_groups"], config["mamba_d_state"]
    return {"h": h, "p": p, "g": g, "ns": ns, "inner": h * p,
            "ch": h * p + 2 * g * ns, "taps": config["mamba_d_conv"]}


def mup_vector(config: dict):
    """`ssm_multipliers` a column of W_in's output: z | x | B | C | dt."""
    s = sizes(config)
    gn = s["g"] * s["ns"]
    z, x, b, c, dt = config["ssm_multipliers"]
    return jnp.concatenate([
        jnp.full((s["inner"],), z, F32), jnp.full((s["inner"],), x, F32),
        jnp.full((gn,), b, F32), jnp.full((gn,), c, F32),
        jnp.full((s["h"],), dt, F32)])


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call. Every matrix
    whose input or output a published multiplier scales is drawn at
    fan-in^-1/2 over that multiplier, so that with the multipliers
    applied as published z, x, B, C, dt, the scores, both branches, the
    MLP and the logits arrive at the scale plain fan-in draws give a
    model without them (as a trained model's weights do: a multiplier
    of a hundredth beside a plain draw is a dead branch). The file's
    `draws` names the rest (its `assumed` says why each): a head's step
    `softplus(dt_bias)` log-spaced over the heads across `time_step`,
    `A` uniform in `a_range`, the skip `D` at `d_skip`, the embedding's
    rows at `embed_scale` after the multiplier, the scores at
    `score_gain`, and the state branch's, the attention branch's and the
    MLP's outputs at `mamba_out_gain`, `attention_out_gain` and
    `mlp_out_gain` times the residual outputs' scale."""
    draws = config["draws"]
    s = sizes(config)
    d, v = config["hidden_size"], config["vocab_size"]
    h, inner, ch = s["h"], s["inner"], s["ch"]
    gn = s["g"] * s["ns"]
    hq, hkv, hd = (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"])
    ff = config["intermediate_size"]
    n_layers = config["num_hidden_layers"]
    residual = float(n_layers) ** -0.5
    gate_mult, down_mult = config["mlp_multipliers"]
    bf = jnp.bfloat16

    def normal(k, shape, scale):
        # drawn in bfloat16: half the random bits of a float32 draw
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    def centred(k, shape, scale):
        """`normal` with zero sum over the input channels: what reads a
        one-signed activation (the gated norm of a y whose x, B and C
        are SiLU's) then adds no vector that every token shares
        (`refs/mamba_moe.py`)."""
        w = jax.random.normal(k, shape, bf)
        mean = jnp.mean(w, 0, keepdims=True, dtype=F32)
        return (w - mean.astype(bf)) * jnp.asarray(scale, bf)

    def ones(n):
        return jnp.ones((n,), bf)

    keys = iter(jax.random.split(key, 2 + 16 * n_layers))
    lo, hi = draws["time_step"]
    step = jnp.exp(jnp.linspace(jnp.log(lo), jnp.log(hi), h)).astype(F32)
    # W_in's columns, each segment over its own two multipliers
    fan = d ** -0.5 / config["ssm_in_multiplier"]
    z_m, x_m, b_m, c_m, dt_m = config["ssm_multipliers"]
    layers = []
    for _ in range(n_layers):
        layers.append({
            "norm_scale": ones(d),
            "w_in": jnp.concatenate([
                normal(next(keys), (d, width), fan / m)
                for width, m in ((inner, z_m), (inner, x_m), (gn, b_m),
                                 (gn, c_m), (h, dt_m))], axis=1),
            "conv_w": normal(next(keys), (s["taps"], ch),
                             s["taps"] ** -0.5),
            "conv_b": normal(next(keys), (ch,), draws["conv_bias"]),
            # softplus(dt_bias) = step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (h,), F32, *draws["a_range"])),
            "d_skip": jnp.full((h,), draws["d_skip"], F32),
            "gate_norm_scale": ones(inner),
            "w_out": centred(next(keys), (inner, d),
                             inner ** -0.5 * residual
                             * draws["mamba_out_gain"]
                             / config["ssm_out_multiplier"]),
            "w_q": normal(next(keys), (d, hq * hd),
                          d ** -0.5 * draws["score_gain"] ** 0.5
                          / config["attention_in_multiplier"]),
            "w_k": normal(next(keys), (d, hkv * hd),
                          d ** -0.5 * draws["score_gain"] ** 0.5
                          / config["attention_in_multiplier"]
                          / config["key_multiplier"]),
            "w_v": normal(next(keys), (d, hkv * hd),
                          d ** -0.5 / config["attention_in_multiplier"]),
            "w_o": normal(next(keys), (hq * hd, d),
                          (hq * hd) ** -0.5 * residual
                          * draws["attention_out_gain"]
                          / config["attention_out_multiplier"]),
            "ffn_norm_scale": ones(d),
            "w_gate": normal(next(keys), (d, ff), d ** -0.5 / gate_mult),
            "w_up": normal(next(keys), (d, ff), d ** -0.5),
            "w_down": normal(next(keys), (ff, d),
                             ff ** -0.5 * residual * draws["mlp_out_gain"]
                             / down_mult),
        })
    return {"embed": normal(next(keys), (v, d), draws["embed_scale"]
                            / config["embedding_multiplier"]),
            "head": normal(next(keys), (v, d),
                           d ** -0.5 / config["lm_head_multiplier"]),
            "final_norm_scale": ones(d), "layers": layers}


# ---------------------------------------------------------------------------
# the layer's parts, each on a block of positions
# ---------------------------------------------------------------------------

def rope(x, pos, theta: float):
    """x [T, H, d] at positions pos [T], in halves."""
    half = x.shape[-1] // 2
    # the published theta, 1e11, is a whole number past 32 bits
    inv = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def keys_values(n, pos, lp, config: dict):
    """Normed n [T, D] at positions pos -> (k [Hkv, T, d] with the key
    multiplier and rotary, v [Hkv, T, d])."""
    t = n.shape[0]
    hkv, hd = config["num_key_value_heads"], config["head_dim"]
    n = n * config["attention_in_multiplier"]
    k = (n @ f32(lp["w_k"])).reshape(t, hkv, hd) * config["key_multiplier"]
    v = (n @ f32(lp["w_v"])).reshape(t, hkv, hd)
    return (rope(k, pos, config["rope_theta"]).swapaxes(0, 1),
            v.swapaxes(0, 1))


def state_branch(n, carry, lp, config: dict):
    """The Mamba-2 branch of normed n [T, D], positions that follow what
    `carry` (states [H, P, N], the last K - 1 positions' xBC) has seen:
    -> (m [T, D] with `ssm_out_multiplier`, carry)."""
    s = sizes(config)
    t, h, p, g, ns = n.shape[0], s["h"], s["p"], s["g"], s["ns"]
    inner, taps = s["inner"], s["taps"]
    state, tail = carry
    proj = ((n * config["ssm_in_multiplier"]) @ f32(lp["w_in"])) \
        * mup_vector(config)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + s["ch"]],
                  proj[:, inner + s["ch"]:])
    padded = jnp.concatenate([tail, xbc])
    conv = sum(f32(lp["conv_w"][i]) * padded[i:i + t]
               for i in range(taps)) + f32(lp["conv_b"])
    act = jax.nn.silu(conv)
    x = act[:, :inner].reshape(t, h, p)
    b = act[:, inner:inner + g * ns].reshape(t, g, ns)
    c = act[:, inner + g * ns:].reshape(t, g, ns)
    step = jax.nn.softplus(dt + f32(lp["dt_bias"]))          # [T, H]
    decay = jnp.exp(step * -jnp.exp(f32(lp["a_log"])))

    def token(st, row):
        x, b, c, step, decay = row
        # head h reads group h // (H / G)
        b, c = jnp.repeat(b, h // g, 0), jnp.repeat(c, h // g, 0)
        st = decay[:, None, None] * st \
            + (step[:, None] * x)[:, :, None] * b[:, None, :]    # [H, P, N]
        return st, jnp.einsum("hpn,hn->hp", st, c)

    state, y = jax.lax.scan(token, state, (x, b, c, step, decay))
    y = (y + f32(lp["d_skip"])[:, None] * x).reshape(t, inner) \
        * jax.nn.silu(z)
    grouped = y.reshape(t, g, inner // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True)
        + config["rms_norm_eps"])
    m = (grouped.reshape(t, inner) * f32(lp["gate_norm_scale"])) \
        @ f32(lp["w_out"])
    return m * config["ssm_out_multiplier"], (state, padded[t:])


def attention_branch(n, pos, k, v, lp, config: dict):
    """Grouped-head causal attention of the queries of normed n [T, D] at
    positions pos over the whole sequence's k, v [Hkv, S, d]:
    -> a [T, D] with `attention_out_multiplier`."""
    t, total = n.shape[0], k.shape[1]
    hq, hkv, hd = (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"])
    per = hq // hkv
    qb = _block(t, QUERY_BLOCK)
    q = rope(((n * config["attention_in_multiplier"])
              @ f32(lp["w_q"])).reshape(t, hq, hd), pos,
             config["rope_theta"])
    q = q.reshape(t, hkv, per, hd).swapaxes(0, 1)            # [Hkv, T, per, d]
    w_o = lp["w_o"].reshape(hkv, per * hd, -1)

    def kv_head(out, ws):
        q, k, v, w_o = ws

        def block(i):
            rows = jax.lax.dynamic_slice_in_dim(pos, i * qb, qb)
            s = jnp.einsum("qhd,kd->hqk", jax.lax.dynamic_slice_in_dim(
                q, i * qb, qb), k) * hd ** -0.5
            live = jnp.arange(total)[None, :] <= rows[:, None]
            s = jnp.where(live[None], s, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1), v)

        att = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, per * hd)
        return out + att @ f32(w_o), None

    out = jax.lax.scan(kv_head, jnp.zeros_like(n), (q, k, v, w_o))[0]
    return out * config["attention_out_multiplier"]


def mlp(f, lp, config: dict):
    """The gated MLP of normed f [T, D], a block of its width at a time:
    -> [T, D] with both multipliers."""
    gate_mult, down_mult = config["mlp_multipliers"]
    ff = lp["w_gate"].shape[1]
    wb = _block(ff, WIDTH_BLOCK)

    def part(out, i):
        w_gate = jax.lax.dynamic_slice_in_dim(lp["w_gate"], i * wb, wb, 1)
        w_up = jax.lax.dynamic_slice_in_dim(lp["w_up"], i * wb, wb, 1)
        w_down = jax.lax.dynamic_slice_in_dim(lp["w_down"], i * wb, wb, 0)
        mid = (f @ f32(w_up)) * jax.nn.silu((f @ f32(w_gate)) * gate_mult)
        return out + mid @ f32(w_down), None

    out = jax.lax.scan(part, jnp.zeros_like(f), jnp.arange(ff // wb))[0]
    return out * down_mult


def layer(x, lp, config: dict):
    """One layer over the whole sequence x [T, D], a block of positions at
    a time. A block's keys and values are written before its queries
    read: attention is causal, so what a later block will write is
    masked for this one."""
    eps = config["rms_norm_eps"]
    s = sizes(config)
    t = x.shape[0]
    tb = _block(t, TOKEN_BLOCK)
    hkv, hd = config["num_key_value_heads"], config["head_dim"]

    def block(i, carry):
        x, k, v, carry = carry
        xb = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
        pos = i * tb + jnp.arange(tb)
        n = rms_norm(xb, lp["norm_scale"], eps)
        kb, vb = keys_values(n, pos, lp, config)
        k = jax.lax.dynamic_update_slice_in_dim(k, kb, i * tb, 1)
        v = jax.lax.dynamic_update_slice_in_dim(v, vb, i * tb, 1)
        m, carry = state_branch(n, carry, lp, config)
        xb = xb + m + attention_branch(n, pos, k, v, lp, config)
        xb = xb + mlp(rms_norm(xb, lp["ffn_norm_scale"], eps), lp, config)
        # the residual's block in place: the one array of the sequence's
        # size and width
        return (jax.lax.dynamic_update_slice_in_dim(x, xb, i * tb, 0), k, v,
                carry)

    rows = jnp.zeros((hkv, t, hd), F32)
    zero = (jnp.zeros((s["h"], s["p"], s["ns"]), F32),
            jnp.zeros((s["taps"] - 1, s["ch"]), F32))
    return jax.lax.fori_loop(0, t // tb, block, (x, rows, rows, zero))[0]


def residual(params, seq, config: dict):
    """seq [T] -> the residual after the last layer [T, D], before the
    final norm. The embedding's rows are read a block of positions at a
    time, so no bfloat16 copy of the sequence's size stands beside it."""
    t = seq.shape[0]
    tb = _block(t, TOKEN_BLOCK)

    def rows(i, x):
        at = jax.lax.dynamic_slice_in_dim(seq, i * tb, tb)
        return jax.lax.dynamic_update_slice_in_dim(
            x, f32(params["embed"][at]) * config["embedding_multiplier"],
            i * tb, 0)

    x = jax.lax.fori_loop(
        0, t // tb, rows, jnp.zeros((t, params["embed"].shape[1]), F32))
    for lp in params["layers"]:
        x = layer(x, lp, config)
    return x


def final_norm(params, x, config: dict):
    return rms_norm(x, params["final_norm_scale"], config["rms_norm_eps"])


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: (final_norm(params, residual(params, seq, config), config)
                     @ f32(params["head"]).T)
        * config["lm_head_multiplier"], tokens)


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]. The
    final norm and the logits are made a block of positions by a block of
    vocabulary rows at a time: the running log-sum-exp and the wanted
    row's logit."""
    mult = config["lm_head_multiplier"]
    v = params["head"].shape[0]
    vb = _block(v, VOCAB_BLOCK)

    def one(seq):
        x = residual(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = final_norm(
                params, jax.lax.dynamic_slice_in_dim(x, i * tb, tb), config)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)

            def rows(carry, j):
                top, total, mine = carry
                head = jax.lax.dynamic_slice_in_dim(params["head"], j * vb,
                                                    vb, 0)
                lg = (xs @ f32(head).T) * mult               # [tb, vb]
                new = jnp.maximum(top, jnp.max(lg, -1))
                total = total * jnp.exp(top - new) \
                    + jnp.sum(jnp.exp(lg - new[:, None]), -1)
                here = (want >= j * vb) & (want < (j + 1) * vb)
                got = jnp.take_along_axis(
                    lg, jnp.clip(want - j * vb, 0, vb - 1)[:, None], -1)[:, 0]
                return (new, total, jnp.where(here, got, mine)), None

            start = (jnp.full((tb,), -jnp.inf, F32), jnp.zeros((tb,), F32),
                     jnp.zeros((tb,), F32))
            top, total, mine = jax.lax.scan(rows, start,
                                            jnp.arange(v // vb))[0]
            return mine - top - jnp.log(total)

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
