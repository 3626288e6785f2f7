"""Plain reference for a decoder whose sequence mixer is power retention
(power 2): the layer that `model_type: brumby` (Brumby-14B-Base) names,
after Manifest AI, "Scaling Context Requires Rethinking Attention"
(arXiv:2507.04239). Written from the layer equations in `jax.numpy`,
float32, no kernels, no state, no feature map; it calls nothing of
`ray_tpu`. Every function takes the configuration file's data and reads
its sizes from the published keys.

The layer. Input x [T, D]; `n = RMSNorm(x)` with a learned scale, eps
`rms_norm_eps`; H = `num_attention_heads` query heads over J =
`num_key_value_heads` key-value heads of d = `head_dim`, H / J a group
(`j = i // (H / J)`); p = 2.

    q^i = rot(norm_q(W_q^i n)) in R^d,  i < H
    k^j = rot(norm_k(W_k^j n)),  v^j = W_v^j n,  j < J
    log g^j_t = log sigmoid(w_g^j . n_t + b_g^j)   <= 0, one scalar a
                key-value head a token
    a^i[t, s] = (q^i_t . k^j_s / sqrt(d))^p  x  exp(sum_{r=s+1..t} log g^j_r),
                s <= t
    o^i_t = sum_s a^i[t, s] v^j_s / (sum_s a^i[t, s] + eps)
    h = x + W_o concat_i(o^i)
    y = h + W_down(silu(W_gate m) * W_up m),  m = RMSNorm(h)

`norm_q`, `norm_k`: RMSNorm over the d dims of a head, each with one
learned scale [d]; `rot`: rotary in halves, (x[i], x[i + d/2]) turned by
pos * `rope_theta`^(-2i/d), after the norms and before the power (the
Qwen3 layout, whose keys the source's config reproduces). Final RMSNorm
and an untied head.

The same numbers come from a state of fixed size (`S_t = g_t S_{t-1} +
phi(k_t) v_t^T` with phi the symmetric second power, `phi(q) . phi(k) =
(q . k)^2`), which is how the program serves it. This module computes the
first, quadratic form and never builds phi: that is what makes it
independent of the program. Conventions the source's keys do not settle
are the configuration file's `assumed`.

Parameters (weights are data; the program reads this same tree): embed,
head [V, D]; final_norm_scale [D]; "layers": a list, one dict a layer,
with mix_norm_scale, mlp_norm_scale [D]; w_q [D, H*d]; w_k, w_v [D, J*d];
q_norm_scale, k_norm_scale [d]; w_g [D, J]; b_g [J]; w_o [H*d, D];
w_gate, w_up [D, F]; w_down [F, D].

The reference runs beside the served model's weights and states, on a
sequence padded to the engine's longest: a layer makes every position's
keys and values first and then walks the positions a block at a time
(their queries against the whole sequence one key-value head at a time,
the MLP a slice of its hidden width at a time); the head takes a block of
positions and the vocabulary in slices. No [T, T] array of more than one
block of queries is ever held, and no matrix of the MLP or the head is
upcast whole.

On a TPU a float32 matmul runs in reduced precision unless the highest
precision is asked for, so callers wrap these in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
POSITION_BLOCK = 256    # positions whose queries see the whole sequence
TOKEN_BLOCK = 1024      # positions whose logits are held at once
FF_SLICE = 2176         # columns of the MLP's hidden width held at once
VOCAB_SLICES = 8        # slices of the head a block of logits is made in


def f32(a):
    return a.astype(F32)


def _block(t: int, want: int) -> int:
    """The largest divisor of t that is at most `want`."""
    b = min(t, want)
    while t % b:
        b -= 1
    return b


def init_params(key, config: dict):
    """bfloat16 weights from `key`, in one traceable call (`assumed`:
    normal, fan-in^-1/2, residual outputs x (2 x layers)^-1/2, embedding
    0.02, norm scales 1; gate biases spread evenly over the key-value
    heads between the file's two `gate_bias` values, so that a head's
    state remembers from tens to thousands of positions, as a trained
    gate's does and a gate of zero-mean weights alone does not)."""
    d, hd = config["hidden_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    f, layers = config["intermediate_size"], config["num_hidden_layers"]
    residual = (2.0 * layers) ** -0.5
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 2 + 8 * layers))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, F32) * scale).astype(bf)

    def ones(n):
        return jnp.ones((n,), bf)

    out = [{
        "mix_norm_scale": ones(d), "mlp_norm_scale": ones(d),
        "w_q": normal((d, hq * hd), d ** -0.5),
        "w_k": normal((d, hkv * hd), d ** -0.5),
        "w_v": normal((d, hkv * hd), d ** -0.5),
        "q_norm_scale": ones(hd), "k_norm_scale": ones(hd),
        "w_g": normal((d, hkv), d ** -0.5),
        "b_g": jnp.linspace(*config["gate_bias"], hkv).astype(bf),
        "w_o": normal((hq * hd, d), (hq * hd) ** -0.5 * residual),
        "w_gate": normal((d, f), d ** -0.5),
        "w_up": normal((d, f), d ** -0.5),
        "w_down": normal((f, d), f ** -0.5 * residual),
    } for _ in range(layers)]
    v = config["vocab_size"]
    return {"embed": normal((v, d), 0.02),
            "head": normal((v, d), d ** -0.5),
            "final_norm_scale": ones(d), "layers": out}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


def rope(x, pos, theta: float):
    """x [T, heads, d]: (x[i], x[i + d/2]) turned by pos * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def keys_values(x, lp, config: dict):
    """x [T, D] -> (k, v [T, J, d], the running sum of log g [T, J]), a
    block of positions at a time."""
    t = x.shape[0]
    hd, hkv = config["head_dim"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    pb = _block(t, POSITION_BLOCK)

    def block(i):
        pos = i * pb + jnp.arange(pb)
        n = rms_norm(jax.lax.dynamic_slice_in_dim(x, i * pb, pb),
                     lp["mix_norm_scale"], eps)
        k = rope(rms_norm((n @ f32(lp["w_k"])).reshape(pb, hkv, hd),
                          lp["k_norm_scale"], eps), pos, theta)
        v = (n @ f32(lp["w_v"])).reshape(pb, hkv, hd)
        return k, v, jax.nn.log_sigmoid(n @ f32(lp["w_g"]) + f32(lp["b_g"]))

    k, v, logg = jax.lax.map(block, jnp.arange(t // pb))
    return (k.reshape(t, hkv, hd), v.reshape(t, hkv, hd),
            jnp.cumsum(logg.reshape(t, hkv), axis=0))


def retention(q, k, v, cum_q, cum, at, config: dict):
    """A block of queries q [N, H, d] at positions `at` [N] (running log
    decay cum_q [N, J]) against every position's k, v [T, J, d] and cum
    [T, J] -> concat_i(o^i) [N, H*d]: the quadratic form, one key-value
    head at a time."""
    n, hq, hd = q.shape
    t, hkv = k.shape[:2]
    group = hq // hkv
    seen = at[:, None] >= jnp.arange(t)[None, :]

    def head(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * group, group, 1)
        score = jnp.einsum("tgd,sd->gts", qj, k[:, j]) / (hd ** 0.5)
        decay = jnp.exp(jnp.where(
            seen, cum_q[:, j][:, None] - cum[:, j][None, :], -jnp.inf))
        a = score * score * decay[None]
        return jnp.einsum("gts,sd->tgd", a, v[:, j]) / (
            jnp.sum(a, -1).T[..., None] + config["retention_eps"])

    o = jax.lax.map(head, jnp.arange(hkv))                   # [J, N, g, d]
    return o.transpose(1, 0, 2, 3).reshape(n, hq * hd)


def mlp(m, lp):
    """m [N, D] -> [N, D], a slice of the hidden width at a time: each
    matrix is upcast a slice at once."""
    f = lp["w_gate"].shape[1]
    fs = _block(f, FF_SLICE)

    def part(acc, i):
        gate, up = (f32(jax.lax.dynamic_slice_in_dim(lp[n], i * fs, fs, 1))
                    for n in ("w_gate", "w_up"))
        down = f32(jax.lax.dynamic_slice_in_dim(lp["w_down"], i * fs, fs, 0))
        return acc + (jax.nn.silu(m @ gate) * (m @ up)) @ down, None

    return jax.lax.scan(part, jnp.zeros_like(m), jnp.arange(f // fs))[0]


def layer(x, lp, config: dict):
    """x [T, D] -> y [T, D]: every position's keys, values and decay
    first, then a block of positions at a time its queries, their
    retention over the whole sequence, the output projection and the
    MLP."""
    t = x.shape[0]
    hd, hq = config["head_dim"], config["num_attention_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    k, v, cum = keys_values(x, lp, config)
    pb = _block(t, POSITION_BLOCK)

    def block(i):
        pos = i * pb + jnp.arange(pb)
        xs = jax.lax.dynamic_slice_in_dim(x, i * pb, pb)
        n = rms_norm(xs, lp["mix_norm_scale"], eps)
        q = rope(rms_norm((n @ f32(lp["w_q"])).reshape(pb, hq, hd),
                          lp["q_norm_scale"], eps), pos, theta)
        o = retention(q, k, v, jax.lax.dynamic_slice_in_dim(cum, i * pb, pb),
                      cum, pos, config)
        h = xs + o @ f32(lp["w_o"])
        return h + mlp(rms_norm(h, lp["mlp_norm_scale"], eps), lp)

    return jax.lax.map(block, jnp.arange(t // pb)).reshape(t, -1)


def features(params, seq, config: dict):
    """seq [T] -> final-normed activations [T, D]."""
    x = f32(params["embed"][seq])
    for lp in params["layers"]:
        x = layer(x, lp, config)
    return rms_norm(x, params["final_norm_scale"], config["rms_norm_eps"])


def logits(params, tokens, config: dict):
    """tokens [B, T] -> float32 logits [B, T, V] (small sizes: tests)."""
    return jax.lax.map(
        lambda seq: features(params, seq, config) @ f32(params["head"]).T,
        tokens)


def token_logprobs(params, tokens, config: dict):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) for every i: [B, T-1]. A
    block of positions at a time, and for each the vocabulary in slices:
    the running maximum and sum of the softmax's normaliser, and the
    wanted token's logit from the slice that holds it."""

    def one(seq):
        # the whole padded sequence (causal: the last position's output
        # is dropped), so that the blocks divide it
        x = features(params, seq, config)
        t = x.shape[0]
        tb = _block(t, TOKEN_BLOCK)
        v = params["head"].shape[0]
        vs = v // _block(v, VOCAB_SLICES)
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def block(i):
            xs = jax.lax.dynamic_slice_in_dim(x, i * tb, tb)
            want = jax.lax.dynamic_slice_in_dim(nxt, i * tb, tb)

            def part(carry, s):
                top, total, got = carry
                rows = f32(jax.lax.dynamic_slice_in_dim(
                    params["head"], s * vs, vs))
                z = xs @ rows.T                              # [tb, vs]
                new = jnp.maximum(top, jnp.max(z, -1))
                total = total * jnp.exp(top - new) + jnp.sum(
                    jnp.exp(z - new[:, None]), -1)
                at = want - s * vs
                mine = (at >= 0) & (at < vs)
                picked = jnp.take_along_axis(
                    z, jnp.clip(at, 0, vs - 1)[:, None], -1)[:, 0]
                return (new, total, jnp.where(mine, picked, got)), None

            (top, total, got), _ = jax.lax.scan(
                part, (jnp.full((tb,), -jnp.inf, F32), jnp.zeros((tb,), F32),
                       jnp.zeros((tb,), F32)), jnp.arange(v // vs))
            return got - top - jnp.log(total)

        return jax.lax.map(block, jnp.arange(t // tb)).reshape(-1)[:-1]

    return jax.lax.map(one, tokens)
