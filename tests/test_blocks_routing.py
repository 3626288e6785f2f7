"""`blocks.routing` and `blocks.expert_layer` with the two fields a
softmax router with identity outputs needs (`Experts.score_func`,
`Experts.identity_from`): with the defaults every routed configuration
traces to the program it was, not an op more; a softmax's scores sum to
one over the router's whole width; the bias moves the choice and not the
weights; an identity output is chosen like any other and costs the held
experts nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import blocks
from ray_tpu.ops import grouped_experts

N, D, E, F, HELD = 24, 32, 16, 8, 4


# `routing` and `expert_layer` as they were before the two fields, kept
# here to compare the traced programs with

def _router_scores_before(h2, lp):
    g = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h2.astype(jnp.float32),
        lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if "router_bias" not in lp:
        return g, g
    return g, g + lp["router_bias"].astype(jnp.float32)


def _routing_before(h2, lp, experts):
    g, biased = _router_scores_before(h2, lp)
    if experts.n_group > 1:
        biased = jnp.where(jnp.repeat(
            blocks.kept_groups(biased, experts.n_group, experts.topk_group),
            experts.router_width // experts.n_group, 1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, experts.experts_per_token)
    weights = jnp.take_along_axis(g, chosen, -1)
    if experts.norm_topk:
        total = jnp.sum(weights, -1, keepdims=True)
        if experts.norm_eps:
            total = total + experts.norm_eps
        weights = weights / total
    return chosen.astype(jnp.int32), weights * experts.routed_scale


def _expert_layer_before(h2, lp, experts, adt, live=None,
                         kernel=grouped_experts.EXPERTS_GROUPED,
                         every_load=False):
    chosen, weights = _routing_before(h2, lp, experts)
    if live is not None:
        chosen = jnp.where(live[:, None], chosen, -1)
    grid = experts.expert_round
    routed, load = grouped_experts.experts_grouped(
        blocks.rounded(h2, grid), chosen, weights,
        blocks.rounded(lp["we_gate"], grid), blocks.rounded(lp["we_up"], grid),
        blocks.rounded(lp["we_down"], grid), held_from=experts.held_from,
        impl=experts.impl, name=kernel)
    shared = None
    if "ws_gate" in lp:
        shared, _ = blocks.gated_mlp(
            h2, {"w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
                 "w_down": lp["ws_down"]}, adt, jnp.float32)
    here = jnp.sum(load)
    if every_load:
        load = jnp.sum(chosen[..., None] == jnp.arange(experts.router_width),
                       (0, 1), dtype=jnp.int32)
    counts = jnp.concatenate([
        jnp.stack([here, jnp.sum(chosen >= 0, dtype=jnp.int32)]), load])
    return routed.astype(adt), shared, counts


def layer(bias=True, shared=True, seed=0):
    keys = iter(jax.random.split(jax.random.key(seed), 9))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    lp = {"router": normal((D, E), D ** -0.5),
          "we_gate": normal((HELD, F, D), D ** -0.5),
          "we_up": normal((HELD, F, D), D ** -0.5),
          "we_down": normal((HELD, F, D), F ** -0.5)}
    if bias:
        lp["router_bias"] = normal((E,), 0.01)
    if shared:
        lp.update(ws_gate=normal((D, F), D ** -0.5),
                  ws_up=normal((D, F), D ** -0.5),
                  ws_down=normal((F, D), F ** -0.5))
    return lp, normal((N, D))


# what the configurations that go through `blocks.routing` today build
# (`cfg.experts` of glm-5.2 / kanana-2, ling-3.0-flash-vl, command-a-plus,
# lfm2-8b-a1b, nemotron-3-super), at a small width
FAMILIES = {
    "latent": (blocks.Experts(E, 4, True, 4, 1, 1, 2.5, "none", "jax"),
               dict(bias=True, shared=True)),
    "latent_groups": (blocks.Experts(E, 4, True, 4, 4, 2, 2.5, "none", "jax"),
                      dict(bias=True, shared=True)),
    "window": (blocks.Experts(E, 2, True, 0, routed_scale=1.0, impl="jax"),
               dict(bias=False, shared=True)),
    "shortconv": (blocks.Experts(E, 4, True, 8, impl="jax", norm_eps=1e-6),
                  dict(bias=True, shared=False)),
    "float8_control": (blocks.Experts(E, 4, False, 4, impl="jax",
                                      expert_round="float8_e4m3fn"),
                       dict(bias=True, shared=False)),
}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_routing_traces_to_the_program_it_was(name):
    experts, how = FAMILIES[name]
    lp, h2 = layer(**how)
    assert experts.score_func == "sigmoid" and experts.identity_from is None
    now = jax.make_jaxpr(lambda h, p: blocks.routing(h, p, experts))(h2, lp)
    before = jax.make_jaxpr(
        lambda h, p: _routing_before(h, p, experts))(h2, lp)
    assert str(now) == str(before)


@pytest.mark.parametrize("every_load", [False, True], ids=["held", "every"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_expert_layer_traces_to_the_program_it_was(name, every_load):
    experts, how = FAMILIES[name]
    lp, h2 = layer(**how)
    live = jnp.arange(N) < N - 3

    def now(h, p):
        routed, shared, identity, counts = blocks.expert_layer(
            h, p, experts, jnp.bfloat16, live, every_load=every_load)
        assert identity is None
        return routed, shared, counts

    def before(h, p):
        return _expert_layer_before(h, p, experts, jnp.bfloat16, live,
                                    every_load=every_load)

    assert str(jax.make_jaxpr(now)(h2, lp)) \
        == str(jax.make_jaxpr(before)(h2, lp))


SOFTMAX = blocks.Experts(E, 5, False, 4, routed_scale=6.0, impl="jax",
                         score_func="softmax", identity_from=12)


def test_softmax_scores_sum_to_one_over_the_whole_width():
    lp, h2 = layer()
    g, biased = blocks.router_scores(h2, lp, "softmax")
    np.testing.assert_allclose(np.asarray(jnp.sum(g, -1)), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(biased - g),
                               np.broadcast_to(lp["router_bias"], (N, E)),
                               atol=1e-7)
    # the weights are the chosen scores times the scale, not renormalised
    chosen, weights = blocks.routing(h2, lp, SOFTMAX)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(jnp.take_along_axis(g, chosen, -1)) * 6.0, rtol=1e-6)
    assert (np.asarray(jnp.sum(weights, -1)) < 6.0).all()
    with pytest.raises(KeyError):
        blocks.router_scores(h2, lp, "tanh")


def test_the_bias_moves_the_choice_and_not_the_weights():
    lp, h2 = layer()
    lifted = {**lp, "router_bias": lp["router_bias"].at[3].set(10.0)}
    chosen, weights = blocks.routing(h2, lp, SOFTMAX)
    chosen_l, weights_l = blocks.routing(h2, lifted, SOFTMAX)
    # output 3 is now every row's first choice, and was not before
    assert (np.asarray(chosen_l)[:, 0] == 3).all()
    assert not (np.asarray(chosen) == 3).any(-1).all()
    # its weight is its score, which no bias entered
    g = blocks.router_scores(h2, lp, "softmax")[0]
    np.testing.assert_allclose(np.asarray(weights_l[:, 0]),
                               np.asarray(g[:, 3]) * 6.0, rtol=1e-6)
    # an output both choices hold has the same weight in both
    for row in range(N):
        both = set(np.asarray(chosen[row])) & set(np.asarray(chosen_l[row]))
        for e in both:
            a = float(weights[row][list(np.asarray(chosen[row])).index(e)])
            b = float(weights_l[row][list(np.asarray(chosen_l[row])).index(e)])
            assert a == b


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_an_identity_output_is_chosen_like_any_other(impl):
    """Outputs 12-15 have no expert: the held experts (4-7) see the pairs
    that chose them and no other, and the identity part is the row's
    input times the sum of its identity weights."""
    lp, h2 = layer(shared=False)
    experts = SOFTMAX._replace(impl=impl)
    chosen, weights = blocks.routing(h2, lp, experts)
    assert (np.asarray(chosen) >= 12).any() and (np.asarray(chosen) < 12).any()
    routed, shared, identity, counts = blocks.expert_layer(
        h2, lp, experts, jnp.float32)
    assert shared is None
    held = (np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8)
    free = np.asarray(chosen) >= 12
    assert int(counts[0]) == held.sum() == int(counts[2:].sum())
    assert int(counts[1]) == N * 5 - free.sum()
    np.testing.assert_allclose(
        np.asarray(identity),
        (np.asarray(weights) * free).sum(-1, keepdims=True) * np.asarray(h2),
        rtol=1e-6, atol=1e-7)
    want = grouped_experts.reference_experts_grouped(
        h2, chosen, weights, lp["we_gate"], lp["we_up"], lp["we_down"], 4)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                               rtol=0, atol=2e-5)
