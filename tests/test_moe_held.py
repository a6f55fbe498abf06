"""HeldExpertsMoE (nn/moe.py): the sigmoid-routed expert layer that is
told which experts it holds. Exact at any load, a share of the uncut
layer, and equal to the benchmark's plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from tensorlink_tpu.nn.moe import HeldExpertsMoE

D, F, E, K = 32, 16, 32, 4
SCALE = 2.446


def _layer(held=None, **kw):
    return HeldExpertsMoE(D, F, E, K, held=held, routed_scale=SCALE, **kw)


@pytest.fixture(scope="module")
def whole():
    """The uncut layer's weights from the benchmark's rules (so the
    selection bias is not nought), and a batch."""
    from benchmark import weights

    layer = _layer()
    shapes = jax.eval_shape(layer.init, jax.random.key(0))
    params = weights.make_tree(17, shapes)
    x = jax.random.normal(jax.random.key(1), (2, 48, D))
    return layer, params, x


def _share(params, first, count):
    """What the chip holding experts [first, first + count) is given."""
    cut = lambda w: w[:, first:first + count]  # noqa: E731
    return dict(params, experts={
        n: {"w": cut(params["experts"][n]["w"])} for n in ("up", "gate", "down")
    })


def _cfg(first=0):
    return {
        "num_experts_per_token": K, "routed_scaling_factor": SCALE,
        "moe_renormalize": True, "deployment_share": {"first_expert": first},
    }


def test_uncut_layer_is_the_reference(whole):
    layer, params, x = whole
    np.testing.assert_allclose(
        layer.apply(params, x), ref._experts(x, params, _cfg(), None),
        atol=2e-5)


@pytest.mark.parametrize("first,count", [(0, 8), (8, 8), (24, 8), (4, 1)])
def test_a_share_is_the_reference_given_the_same_share(whole, first, count):
    _, params, x = whole
    part = _share(params, first, count)
    np.testing.assert_allclose(
        _layer((first, count)).apply(part, x),
        ref._experts(x, part, _cfg(first), None), atol=2e-5)


@pytest.mark.parametrize("chips", [2, 4, 32])
def test_the_shares_add_up_to_the_uncut_layer(whole, chips):
    """Every chip's own experts' part, with what all chips compute alike
    (the shared expert) counted once, is the whole layer's result."""
    layer, params, x = whole
    n = E // chips
    shared = layer.children["shared"].apply(params["shared"], x)
    total = shared
    for c in range(chips):
        part = _layer((c * n, n)).apply(_share(params, c * n, n), x)
        total = total + (part - shared)
    np.testing.assert_allclose(total, layer.apply(params, x), atol=5e-5)


def test_gradients_are_the_references_and_the_bias_gets_none(whole):
    _, params, x = whole
    part, cfg = _share(params, 8, 8), _cfg(8)
    layer = _layer((8, 8))
    ct = jax.random.normal(jax.random.key(3), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x) * ct), (0, 1))(part, x)
    want = jax.grad(
        lambda p, x: jnp.sum(ref._experts(x, p, cfg, None) * ct), (0, 1))(part, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-9)
    assert not np.any(got[0]["router"]["bias"])
    assert np.any(got[0]["router"]["w"])


def test_the_bias_chooses_and_weighs_nothing(whole):
    """A large bias on expert 9 puts it into every token's choice; its
    weight there is still its own score's share."""
    _, params, x = whole
    bias = params["router"]["bias"].at[9].set(10.0)
    biased = dict(params, router=dict(params["router"], bias=bias))
    layer = _layer((9, 1))
    stats = layer.routing_stats(_share(biased, 9, 1), x)
    assert stats["routes"] == x.shape[0] * x.shape[1] and stats["overflow"] == 0
    np.testing.assert_allclose(
        layer.apply(_share(biased, 9, 1), x),
        ref._experts(x, _share(biased, 9, 1), _cfg(9), None), atol=2e-5)


def test_every_token_to_one_held_expert_and_none_is_dropped(whole):
    """The load a capacity factor would cut: all 96 tokens choose held
    expert 2 (of 4 held). Every route is computed."""
    _, params, x = whole
    w = params["router"]["w"].at[:, 10].set(0.0)
    bias = jnp.zeros((E,)).at[10].set(5.0)
    hot = dict(params, router={"w": w, "bias": bias})
    layer = _layer((8, 4))
    stats = layer.routing_stats(_share(hot, 8, 4), x)
    tokens = x.shape[0] * x.shape[1]
    assert stats["per_expert"][2] == tokens and stats["overflow"] == 0
    assert stats["rows"] == tokens * 4
    out = layer.apply(_share(hot, 8, 4), x)
    np.testing.assert_allclose(
        out, ref._experts(x, _share(hot, 8, 4), _cfg(8), None), atol=2e-5)
    # expert 10's part alone, token by token, is in it: take it out and
    # the rest no longer depends on that expert's weights
    only = _layer((10, 1))
    assert only.routing_stats(_share(hot, 10, 1), x)["routes"] == tokens


def test_a_row_bound_that_holds_changes_nothing(whole):
    _, params, x = whole
    part = _share(params, 0, 8)
    routes = _layer((0, 8)).routing_stats(part, x)["routes"]
    tight = _layer((0, 8), row_bound=routes)
    assert tight.rows(96) == routes
    np.testing.assert_allclose(
        tight.apply(part, x), _layer((0, 8)).apply(part, x), atol=1e-6)


def test_more_routes_than_rows_is_a_non_finite_result(whole):
    _, params, x = whole
    part = _share(params, 0, 8)
    routes = _layer((0, 8)).routing_stats(part, x)["routes"]
    short = _layer((0, 8), row_bound=routes - 1)
    assert short.routing_stats(part, x)["overflow"] == 1
    assert bool(jnp.isnan(short.apply(part, x)).all())


def test_no_bias_no_shared_expert(whole):
    _, params, x = whole
    layer = _layer(select_bias=False, shared_experts=0)
    own = layer.init(jax.random.key(5))
    assert "bias" not in own["router"] and "shared" not in own
    assert own["experts"]["up"]["w"].shape == (D, E, F)
    assert own["experts"]["down"]["w"].shape == (F, E, D)
    assert jax.tree.structure(layer.param_spec()) == jax.tree.structure(
        jax.tree.map(lambda _: 0, own))
    assert bool(jnp.isfinite(layer.apply(own, x)).all())


@pytest.mark.parametrize("held", [(30, 4), (-1, 2), (0, 0)])
def test_a_range_outside_the_router_is_refused(held):
    with pytest.raises(ValueError, match="not a range"):
        _layer(held)


def test_rows_past_the_routes_may_hold_anything(whole, monkeypatch):
    """On the chip a grouped matmul leaves the rows past its groups as
    memory had them, in its result and in the cotangent it hands back
    (PR 29 met NaN there). Stand-in: a ``ragged_dot`` that writes NaN
    into those rows both ways. Output and gradients stay what they are."""
    _, params, x = whole
    part = _share(params, 8, 8)
    layer = _layer((8, 8))
    ct = jax.random.normal(jax.random.key(3), x.shape)

    def f(p, x):
        return jnp.sum(layer.apply(p, x) * ct)

    clean = jax.grad(f, (0, 1))(part, x)
    real = jax.lax.ragged_dot

    def past(sizes, like):
        return (jnp.arange(like.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return jnp.where(past(sizes, lhs), jnp.nan, real(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(past(sizes, lhs), 0, g))
        return jnp.where(past(sizes, lhs), jnp.nan, d_lhs), d_rhs, None

    dirty.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
    assert layer.routing_stats(part, x)["routes"] < layer.rows(96)
    got = jax.grad(f, (0, 1))(part, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(clean)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(
        layer.apply(part, x), ref._experts(x, part, _cfg(8), None), atol=2e-5)
