"""One transformer layer: every model forward in `ray_tpu/models/` goes
through `gpt.layer`, and the layer equations themselves are pinned
against plain loops over the layers written here."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt, moe, vit
from ray_tpu.parallel.ring_attention import reference_attention

MODELS = pathlib.Path(gpt.__file__).parent
# What only the layer function may compute with.
LAYER_KEYS = {"ln1_scale", "wq", "wk", "wv", "wo", "ln2_scale"}


def gpt_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=3, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32", attn_impl="xla"), **kw})


def _paged(fn):
    """A paged forward on a fresh 6-block pool, slots' tables [1,2],[3,4]."""
    def run():
        cfg = gpt_cfg()
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        return fn(params, gpt.init_kv_pool(cfg, 6, 8), cfg,
                  jnp.asarray([[1, 2], [3, 4]], jnp.int32))
    return run


def _forward_features():
    cfg = gpt_cfg()
    return gpt.forward_features(gpt.init_params(jax.random.PRNGKey(0), cfg),
                                jnp.zeros((2, 8), jnp.int32), cfg)


def _moe_forward():
    cfg = moe.small(n_layers=3)
    return moe.forward(moe.init_params(jax.random.PRNGKey(0), cfg),
                       jnp.zeros((2, 8), jnp.int32), cfg)


def _vit_forward():
    cfg = vit.small(n_layers=3)
    return vit.forward(vit.init_params(jax.random.PRNGKey(0), cfg),
                       jnp.zeros((2, 32, 32, 3)), cfg)


ENTRY_POINTS = {
    "forward_features": _forward_features,
    "prefill_paged": _paged(lambda p, pool, cfg, tables: gpt.prefill_paged(
        p, jnp.zeros((1, 8), jnp.int32), pool, cfg, block_table=tables[0],
        start=0)),
    "decode_step_paged": _paged(
        lambda p, pool, cfg, tables: gpt.decode_step_paged(
            p, jnp.zeros((2,), jnp.int32), pool, jnp.zeros((2,), jnp.int32),
            tables, cfg)),
    "verify_step_paged": _paged(
        lambda p, pool, cfg, tables: gpt.verify_step_paged(
            p, jnp.zeros((2, 3), jnp.int32), pool,
            jnp.zeros((2,), jnp.int32), tables, cfg)),
    "moe.forward": _moe_forward,
    "vit.forward": _vit_forward,
}


def _readers():
    """{(file, function)} of the functions under `ray_tpu/models/` whose
    source holds one of `LAYER_KEYS` as a string."""
    found = set()
    for path in sorted(MODELS.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
                    isinstance(n, ast.Constant) and n.value in LAYER_KEYS
                    for n in ast.walk(fn)):
                found.add((path.name, fn.name))
    return found


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_every_forward_goes_through_the_one_layer(monkeypatch, entry):
    """Each entry point scans its layers through `gpt.layer`: the scan
    traces its body once, so one call whatever the depth. And besides
    parameter initialisation and the logical-axes tables, `layer` is the
    only function in `ray_tpu/models/` that names the attention weights
    or the norm scales."""
    calls = []
    layer = gpt.layer

    def counted(*args, **kwargs):
        calls.append(1)
        return layer(*args, **kwargs)

    for mod in (gpt, moe, vit):
        monkeypatch.setattr(mod, "layer", counted)
    ENTRY_POINTS[entry]()
    assert len(calls) == 1
    computing = {(f, fn) for f, fn in _readers()
                 if fn not in ("init_params", "param_logical_axes")}
    assert computing == {("gpt.py", "layer")}


# ---------------------------------------------------------------------------
# the layer equations, written out
# ---------------------------------------------------------------------------

def _rms(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def _plain_forward(params, tokens, cfg, ffn):
    """Embed, then for each layer: norm, q/k/v, causal attention, output
    projection, residual, norm, `ffn(h, lp) -> (out, aux)`, residual;
    final norm and the tied unembedding. -> (logits f32, summed aux)."""
    adt = cfg.activation_dtype()
    b, t = tokens.shape
    mm = lambda x, w: jnp.matmul(
        x, w.astype(adt), preferred_element_type=jnp.float32).astype(adt)
    x = params["embed"].astype(adt)[tokens] \
        + params["pos_embed"].astype(adt)[:t][None]
    aux_sum = 0.0
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms(x, lp["ln1_scale"].astype(adt))
        q, k, v = (mm(h, lp[n]).reshape(b, t, cfg.n_heads, cfg.head_dim)
                   for n in ("wq", "wk", "wv"))
        att = reference_attention(q, k, v, causal=True)
        x = x + mm(att.reshape(b, t, -1), lp["wo"])
        out, aux = ffn(_rms(x, lp["ln2_scale"].astype(adt)), lp)
        x, aux_sum = x + out, aux_sum + aux
    x = _rms(x, params["final_ln_scale"].astype(adt))
    return jnp.matmul(x, params["embed"].astype(adt).T,
                      preferred_element_type=jnp.float32), aux_sum


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                        ("bfloat16", 5e-2)])
def test_moe_forward_is_the_plain_loop(dtype, atol):
    cfg = moe.small(dtype=dtype, attn_impl="xla", n_layers=3)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits, aux = moe.forward(params, tokens, cfg)
    want, aux_sum = _plain_forward(
        params, tokens, cfg, lambda h, lp: moe._moe_ffn(h, lp, cfg))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=atol, rtol=atol)
    np.testing.assert_allclose(float(aux), float(aux_sum) / cfg.n_layers,
                               rtol=1e-2 if dtype == "bfloat16" else 1e-5)


def test_gpt_forward_is_the_plain_loop():
    cfg = gpt_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)

    def swiglu(h, lp):
        return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"], 0.0

    want, _ = _plain_forward(params, tokens, cfg, swiglu)
    np.testing.assert_allclose(
        np.asarray(gpt.forward(params, tokens, cfg)), np.asarray(want),
        atol=1e-5, rtol=1e-5)
