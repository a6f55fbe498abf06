"""``benchmark/balance.py``: the solver alone on synthetic scores with a
planted common offset, then the tiny Kimi preset with weights by the
benchmark's rules: every expert's load on batch 0, the held experts'
routes over seeds with and without the balance, the same seed the same
bias to the bit, and the program's own router counting what the
family's scores say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import balance, traffic, weights
from benchmark.families import kimi_linear as fam
from benchmark.tests.test_kimi_linear import KIMI_MIX, KIMI_TINY

SEEDS = (1, 2, 3, 4, 2**31 + 11)
# 512 tokens choose 4 of 16: 128 an expert. Tokens near an expert's
# threshold lie some 1 / (512 * 0.6) = 3e-3 of score apart, 300 times the
# rule's last u (1e-5), so the rule ends on the fixed point itself; a
# count is whole and two tokens can tie, which leaves 1 or 2
TOKENS_OFF = 2


# ------------------------------------------------------------ the solver
def _planted(seed, n=2048, e=32):
    k1, k2 = jax.random.split(jax.random.key(seed))
    common = 0.5 * jax.random.normal(k1, (e,))  # every token's, by expert
    return common, jax.nn.sigmoid(common + jax.random.normal(k2, (n, e)))


@pytest.mark.parametrize("seed", [0, 1])
def test_solver_evens_a_planted_common_offset(seed):
    k, (common, scores) = 4, _planted(seed)
    mean = scores.shape[0] * k / scores.shape[1]
    before = np.asarray(balance.loads(scores, jnp.zeros(32), k))
    assert before.max() > 2 * mean and before.min() < mean / 2
    bias = balance.solve(scores, k)
    after = np.asarray(balance.loads(scores, bias, k))
    assert after.sum() == before.sum() == scores.shape[0] * k
    assert np.abs(after - mean).max() <= TOKENS_OFF
    assert bias.dtype == jnp.float32 and abs(float(bias.mean())) < 1e-6
    # the bias undoes the offset: the favoured experts are held back
    assert np.corrcoef(np.asarray(bias), np.asarray(common))[0, 1] < -0.9
    # fresh tokens with the same common part: even to their own noise
    # (a load of 256 has a standard deviation of about 15)
    fresh = jax.nn.sigmoid(common + jax.random.normal(
        jax.random.key(seed + 100), scores.shape))
    assert np.abs(
        np.asarray(balance.loads(fresh, bias, k)) - mean).max() < 0.25 * mean
    again = balance.solve(scores, k)
    assert np.array_equal(np.asarray(bias), np.asarray(again))


def test_lay_over_replaces_the_named_leaves_only():
    tree = {"a": {"router": {"bias": jnp.ones(4), "w": jnp.ones((2, 4))}}}
    assert balance.lay_over(tree, {}) is tree
    new = balance.lay_over(tree, {"a/router/bias": np.arange(4.0)})
    assert new["a"]["router"]["w"] is tree["a"]["router"]["w"]
    assert np.array_equal(new["a"]["router"]["bias"], np.arange(4.0))
    assert new["a"]["router"]["bias"].dtype == jnp.float32
    assert balance.paths(tree) == ["a/router/bias"]


# --------------------------------------------------- the tiny Kimi preset
@pytest.fixture(scope="module")
def kimi():
    model = fam.build(KIMI_TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    routed = fam.routed_experts(KIMI_TINY)
    return {
        "model": model, "shapes": shapes, "k": routed["k"],
        "held": routed["held"],
        # float32 compute at the tiny size: the masters need no cast
        "scores_of": jax.jit(lambda p, ids: fam.router_scores(model, p, ids)),
    }


def _ids(seed, step):
    return traffic.train_batch(KIMI_MIX, KIMI_TINY["vocab_size"], seed, step)[
        :, :-1]


def _seeded(params):
    """The selection biases as ``weights.py`` alone makes them."""
    flat = dict(
        (weights.path_str(p), np.asarray(x))
        for p, x in jax.tree_util.tree_flatten_with_path(params)[0]
    )
    return {p: flat[p] for p in balance.paths(params)}


def _count(kimi, params, biases, ids):
    """What each expert layer's experts get of ``ids`` under ``biases``,
    by a forward pass of its own: a layer's smallest and largest load
    over all its experts, and the routes to the held ones."""
    layers = kimi["scores_of"](
        balance.lay_over(params, biases), jnp.asarray(ids))
    first, held = kimi["held"]
    out = []
    for path, s in zip(biases, layers):
        n = np.asarray(balance.loads(s, jnp.asarray(biases[path]), kimi["k"]))
        out.append({"least": int(n.min()), "most": int(n.max()),
                    "held": int(n[first:first + held].sum())})
    return out


@pytest.fixture(scope="module")
def by_seed(kimi):
    out = {}
    for seed in SEEDS:
        params = weights.make_tree(seed, kimi["shapes"])
        biases = balance.run(fam, kimi["model"], params, KIMI_TINY, KIMI_MIX,
                             seed)

        def count(b, step):
            return _count(kimi, params, b, _ids(seed, step))

        out[seed] = {
            "params": params, "biases": biases,
            "seeded": count(_seeded(params), 0),
            "batch0": count(biases, 0), "batch7": count(biases, 7),
        }
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_every_expert_gets_its_share_of_batch_0(by_seed, seed):
    got = by_seed[seed]
    assert list(got["biases"]) == [
        f"blocks/{i}/mlp/router/bias" for i in (1, 2, 3, 4)]
    for b in got["biases"].values():
        assert b.shape == (16,) and b.dtype == np.float32
        assert abs(b.mean()) < 1e-6
    for layer in got["batch0"]:
        assert 128 - TOKENS_OFF <= layer["least"] <= layer["most"] <= (
            128 + TOKENS_OFF)
        assert abs(layer["held"] - 512) <= 2 * TOKENS_OFF
    # the seeded bias starves and floods experts of the same tokens
    assert min(f["least"] for f in got["seeded"]) < 0.75 * 128
    assert max(f["most"] for f in got["seeded"]) > 1.4 * 128


def _range_pct(values):
    return 100.0 * (max(values) - min(values)) / np.mean(values)


def test_the_held_routes_no_longer_follow_the_seed(by_seed):
    def layers(which):
        return [f["held"] for s in SEEDS for f in by_seed[s][which]]

    def totals(which):
        return [sum(f["held"] for f in by_seed[s][which]) for s in SEEDS]

    assert _range_pct(layers("seeded")) > 20 and _range_pct(
        totals("seeded")) > 10
    assert _range_pct(layers("batch0")) < 1.6
    assert _range_pct(totals("batch0")) < 0.5
    # a later batch: the tokens' own noise is left, not the seed's
    # favourites (512 routes a layer: a standard deviation of about 4 %)
    assert _range_pct(totals("batch7")) < _range_pct(totals("seeded")) / 2
    for s in SEEDS:
        for f in by_seed[s]["batch7"]:
            assert abs(f["held"] - 512) < 0.15 * 512


def test_the_same_seed_gives_the_same_bias_to_the_bit(kimi, by_seed):
    seed = SEEDS[-1]
    params = weights.make_tree(seed, kimi["shapes"])
    again = balance.run(fam, kimi["model"], params, KIMI_TINY, KIMI_MIX, seed)
    for path, b in by_seed[seed]["biases"].items():
        assert b.tobytes() == again[path].tobytes()
    other = by_seed[SEEDS[0]]["biases"]
    assert any(not np.array_equal(other[p], again[p]) for p in again)


def test_the_bias_is_whole_in_the_compute_dtype(kimi):
    """The step casts its masters to the compute dtype: the bias comes
    rounded to it already, so the program and the reference, which takes
    the float32 numbers, choose under the same bias."""
    cfg = {**KIMI_TINY, "train": {**KIMI_TINY["train"],
                                  "compute_dtype": "bfloat16"}}
    params = weights.make_tree(3, kimi["shapes"])
    for b in balance.run(fam, kimi["model"], params, cfg, KIMI_MIX, 3).values():
        assert b.dtype == np.float32 and np.ptp(b) > 0
        assert np.array_equal(
            b, np.asarray(jnp.asarray(b).astype(jnp.bfloat16), np.float32))


def test_the_program_routes_as_the_scores_say(kimi, by_seed, monkeypatch):
    """The program's own router (``HeldExpertsMoE._route``, run eagerly so
    that its counts can be read) sends the held experts what
    ``router_scores`` and the bias say it will, layer by layer."""
    from tensorlink_tpu.nn.moe import HeldExpertsMoE

    seed = SEEDS[1]
    got = by_seed[seed]
    seen = []
    real = HeldExpertsMoE._route

    def recording(self, params, xf):
        out = real(self, params, xf)
        seen.append((int(out[3]), [int(n) for n in out[2]]))
        return out

    monkeypatch.setattr(HeldExpertsMoE, "_route", recording)
    params = balance.lay_over(got["params"], got["biases"])
    # no remat: under jax.checkpoint a block is traced, and counts unread
    eager = fam.build(
        {**KIMI_TINY, "train": {**KIMI_TINY["train"], "remat": False}})
    eager.apply(params, jnp.asarray(_ids(seed, 0)))
    assert [routes for routes, _ in seen] == [
        f["held"] for f in got["batch0"]]
    for _, sizes in seen:
        assert len(sizes) == 4 and all(
            abs(n - 128) <= TOKENS_OFF for n in sizes)
