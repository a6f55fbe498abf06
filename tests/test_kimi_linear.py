"""Kimi-Linear (models/kimi_linear.py) at tiny widths on the CPU, seeded
weights from the benchmark's rules, against the benchmark's plain
reference: logits, loss, every leaf's gradient; and through
``Trainer.train_step`` as a user would train it."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import kimi_linear as ref
from conftest import count_equations as _count
from tensorlink_tpu.config import TrainConfig
from tensorlink_tpu.models.kimi_linear import (
    KimiBlock,
    KimiLinear,
    KimiLinearConfig,
)
from tensorlink_tpu.train.trainer import Trainer, softmax_cross_entropy

TINY = KimiLinearConfig.tiny()
# the tiny preset in the configuration file's (HF's) keys
REF_CFG = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
        "head_dim": 16,
    },
    "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "num_experts_per_token": 4,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "deployment_share": {"first_expert": 4},
}
LEAVES = [
    weights.path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(KimiLinear(TINY).init, jax.random.key(0)))[0]
]


def loss_fn(module, params, batch, rng):
    return softmax_cross_entropy(
        module.apply(params, batch["input_ids"]), batch["labels"]
    )


def _flat(tree):
    return {
        weights.path_str(p): x
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.fixture(scope="module")
def both():
    """Program and reference on one seeded tree and batch: logits, loss
    and gradients of each, computed once."""
    model = KimiLinear(TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_tree(5, shapes)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 97)), jnp.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, None))(params)
        ref_loss, ref_grads = ref.loss_and_grads(params, ids, REF_CFG, 1)
        logits = model.apply(params, batch["input_ids"])
        ref_logits = ref.logits_fn(params, batch["input_ids"], REF_CFG)
    return {
        "logits": (logits, ref_logits), "loss": (loss, ref_loss),
        "grads": (_flat(grads), _flat(ref_grads)), "model": model,
        "params": params, "batch": batch,
    }


def test_logits_are_the_references(both):
    got, want = both["logits"]
    assert got.shape == (2, 96, TINY.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-3 * float(jnp.abs(want).max()))


def test_loss_is_the_references(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) < 2e-5 * float(want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_is_the_references(both, leaf):
    got, want = both["grads"][0][leaf], both["grads"][1][leaf]
    assert got.shape == want.shape
    if leaf.endswith("router/bias"):  # chooses, and gets no gradient
        assert not np.any(got) and not np.any(want)
        return
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(
        got, want, atol=2e-3 * float(jnp.abs(want).max()))


def test_the_layer_pattern():
    model = KimiLinear(TINY)
    kinds = [
        (b.mixer_kind, b.ffn_kind)
        for b in model.children["blocks"].children.values()
    ]
    assert kinds == [
        ("kda", "mlp"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"),
    ]
    full = KimiLinearConfig.kimi_linear_48b()
    assert sorted(full.kda_layers + full.full_attn_layers) == list(range(1, 28))
    assert all(n % 4 == 0 or n == 27 for n in full.full_attn_layers)
    with pytest.raises(ValueError, match="exactly one mixer"):
        KimiBlock(dataclasses.replace(TINY, kda_layers=(1, 2)), 3)


def test_the_chip_share_preset_is_602_million_parameters():
    cfg = KimiLinearConfig.kimi_linear_l5e8()
    shapes = jax.eval_shape(KimiLinear(cfg).init, jax.random.key(0))
    sizes = {
        weights.path_str(p): int(np.prod(x.shape))
        for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
    }

    def under(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    assert round(under("blocks/0/mixer") / 1e6, 1) == 39.5  # KDA
    assert round(under("blocks/3/mixer") / 1e6, 1) == 29.1  # MLA
    assert round(under("blocks/0/mlp") / 1e6, 1) == 63.7  # dense SwiGLU
    assert round(under("blocks/1/mlp/experts") / 1e6, 1) == 56.6  # 8 held
    assert sizes["blocks/1/mlp/router/w"] == 2304 * 256  # the whole router
    assert sizes["tok_emb/table"] == sizes["lm_head/w"] == 20480 * 2304
    assert round(sum(sizes.values()) / 1e6) == 602
    assert cfg.remat and cfg.held_experts == (0, 8)


def test_a_cache_is_refused_with_the_reason(both):
    model, params, batch = both["model"], both["params"], both["batch"]
    for kw in ({"cache": {}}, {"caches": []}):
        with pytest.raises(NotImplementedError, match="kvpool.py"):
            model.apply(params, batch["input_ids"], **kw)
    hidden = model.apply(params, batch["input_ids"], logits=False)
    assert hidden.shape == (2, 96, TINY.dim)


@functools.lru_cache(maxsize=None)
def _four_steps(remat):
    """Losses of four ``Trainer`` steps on the fixture's batch (made
    again here: a cached function takes no fixture)."""
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 97)), jnp.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    model = KimiLinear(dataclasses.replace(TINY, remat=remat))
    trainer = Trainer(model, loss_fn, TrainConfig(
        batch_size=2, micro_batches=1, learning_rate=3e-3, optimizer="adam",
        grad_clip_norm=1.0, dtype="bfloat16",
    ))
    state = trainer.init_state(jax.random.key(1))
    losses = []
    for _ in range(4):
        state, stats = trainer.train_step(state, batch, jax.random.key(0))
        assert not bool(stats["nonfinite"])
        losses.append(float(stats["loss"]))
    assert np.isfinite(float(stats["grad_norm"]))
    return losses


@pytest.mark.parametrize("remat", [False, True])
def test_trains_through_the_trainer(remat):
    """bf16 compute on f32 masters, Adam, clipping: the loss on one
    batch falls, nothing is non-finite, and recomputing the blocks
    changes no number of the first step."""
    losses = _four_steps(remat)
    assert losses[-1] < losses[0] - 0.05, losses
    assert losses[0] == pytest.approx(_four_steps(False)[0], rel=1e-6)


def _grad_of(both, remat):
    model = KimiLinear(dataclasses.replace(TINY, remat=remat))
    return jax.grad(lambda p: loss_fn(model, p, both["batch"], None))


def _plain_checkpoint(monkeypatch):
    """``KimiLinear``'s block remat as a plain ``jax.checkpoint``: a
    policy of None is its default, which keeps nothing."""
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names", lambda *_: None)


@pytest.mark.parametrize("blocks,solves", [
    ("kept", 16), ("recomputed whole", 16), ("plain checkpoint", 20),
])
def test_block_remat_adds_no_pass_of_the_scan(both, monkeypatch, blocks, solves):
    """The scan's passes, read off the gradient's jaxpr by its solve. A
    KDA layer holds four (forward, the row's recompute, two in its
    backward), with or without ``remat``: the block's recompute finds
    the scan's output kept. A plain ``jax.checkpoint`` of each block,
    which keeps no name, runs the forward once more a layer."""
    if blocks == "plain checkpoint":
        _plain_checkpoint(monkeypatch)
    grad = _grad_of(both, remat=blocks != "recomputed whole")
    text = jax.make_jaxpr(grad)(both["params"])
    assert _count(text.jaxpr, "triangular_solve") == solves


@pytest.fixture(scope="module")
def remat_grads(both):
    return _flat(_grad_of(both, True)(both["params"]))


@pytest.mark.parametrize("against,rel", [
    ("plain checkpoint", 0.0), ("recomputed nothing", 1e-5),
])
def test_block_remat_changes_no_gradient(
        both, remat_grads, monkeypatch, against, rel):
    """float32 on the CPU, op by op. What the block's recompute reads
    back is the value it would have computed: every leaf's gradient
    equals, bit for bit, that of a plain ``jax.checkpoint`` of each
    block. Against no remat at all (the fixture's) it stands where that
    one does, 3e-6 of a leaf's norm at the most: a checkpoint's body is
    compiled as one program, which XLA fuses."""
    if against == "plain checkpoint":
        _plain_checkpoint(monkeypatch)
        want = _flat(_grad_of(both, True)(both["params"]))
    else:
        want = both["grads"][0]
    for leaf in LEAVES:
        gap = float(jnp.linalg.norm(remat_grads[leaf] - want[leaf]))
        assert gap <= rel * float(jnp.linalg.norm(want[leaf])), (leaf, gap)


def test_too_many_routes_for_the_rows_is_a_nonfinite_step(both):
    """The visible overflow: a row bound the load breaks makes the loss
    NaN and the trainer's flag true, and the update is not applied."""
    model = KimiLinear(dataclasses.replace(TINY, moe_row_bound=8))
    trainer = Trainer(model, loss_fn, TrainConfig(
        batch_size=2, micro_batches=1, learning_rate=1e-3, optimizer="adam",
        grad_clip_norm=1.0, skip_nonfinite_updates=True,
    ))
    state = trainer.init_state(jax.random.key(1))
    before = jax.tree.map(np.asarray, state.params)
    state, stats = trainer.train_step(state, both["batch"], jax.random.key(0))
    assert bool(stats["nonfinite"]) and not np.isfinite(float(stats["loss"]))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(a, b)
