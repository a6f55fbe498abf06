"""Laguna (models/laguna.py) at tiny widths on the CPU, seeded weights
from the benchmark's rules, against the benchmark's plain reference:
logits, loss, every leaf's gradient, three ``Trainer`` steps; the rotary
spec (partial rotary, YaRN at the published numbers against HF's own
computation, the Llama path to the bit); the heads' gates; grouped
query heads of 9 and 6 through the flash entry; the expert share; the
counts of the whole model and of the cut."""

import dataclasses
import functools
import json
import math
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import laguna as fam
from benchmark.reference import laguna as ref
from tensorlink_tpu.config import TrainConfig
from tensorlink_tpu.models.laguna import (
    FULL,
    WINDOW,
    Laguna,
    LagunaBlock,
    LagunaConfig,
)
from tensorlink_tpu.nn.attention import (
    MultiHeadAttention,
    Rotary,
    Yarn,
    apply_rope,
    dot_product_attention,
)
from tensorlink_tpu.nn.moe import HeldExpertsMoE
from tensorlink_tpu.nn.module import module_from_config
from tensorlink_tpu.train.trainer import Trainer, TrainState, softmax_cross_entropy

TINY = LagunaConfig.tiny()
CELL_CFG = json.loads((
    pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "configs"
    / "laguna-s2.1-l5e8.json").read_text())
FULL_ROPE = CELL_CFG["rope_parameters"]["full_attention"]
# the tiny preset in the configuration file's (HF's) keys
REF_CFG = {
    "hidden_size": 32, "head_dim": 8, "num_key_value_heads": 2,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_hidden_layers": 5, "sliding_window": 8, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 16,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    },
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "deployment_share": {"first_expert": 4},
}
HP = {"learning_rate": 3e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
      "clip_norm": 1.0}
LEAVES = [
    weights.path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(Laguna(TINY).init, jax.random.key(0)))[0]
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


def _ids():
    return jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 65)), jnp.int32)


@pytest.fixture(scope="module")
def both():
    """Program and reference on one seeded tree and batch: logits, loss
    and gradients of each, computed once."""
    model = Laguna(TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_tree(5, shapes)
    ids = _ids()
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, None)))(params)
        ref_loss, ref_grads = jax.jit(
            lambda p: ref.loss_and_grads(p, ids, REF_CFG, 1))(params)
        logits = jax.jit(model.apply)(params, batch["input_ids"])
        ref_logits = jax.jit(
            lambda p: ref.logits_fn(p, batch["input_ids"], REF_CFG))(params)
    return {
        "logits": (logits, ref_logits), "loss": (loss, ref_loss),
        "grads": (_flat(grads), _flat(ref_grads)), "model": model,
        "params": params, "batch": batch, "ids": ids,
    }


def test_logits_are_the_references(both):
    got, want = both["logits"]
    assert got.shape == (2, 64, TINY.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


def test_loss_is_the_references(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) < 2e-6 * float(want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_is_the_references(both, leaf):
    """Both attention kinds (blocks 0 and 4 full, 1-3 window), the dense
    feed-forward (block 0) and the expert layers; the selection bias
    chooses only and has no gradient on either side."""
    got, want = both["grads"][0][leaf], both["grads"][1][leaf]
    assert got.shape == want.shape
    if leaf.endswith("router/bias"):
        assert not float(jnp.abs(got).max()) and not float(jnp.abs(want).max())
        return
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(
        got, want, atol=1e-4 * float(jnp.abs(want).max()))


def test_three_trainer_steps_follow_the_reference(both):
    """float32 through ``Trainer.train_step`` on the normal path: each
    step's loss and gradient norm beside the reference's own Adam."""
    trainer = Trainer(both["model"], loss_fn, TrainConfig(
        batch_size=2, micro_batches=1, learning_rate=HP["learning_rate"],
        optimizer="adam", weight_decay=0.0, schedule="constant",
        warmup_steps=0, grad_clip_norm=HP["clip_norm"], dtype="float32",
    ))
    copy = jax.tree.map(jnp.array, both["params"])  # the step donates
    state = TrainState.create(copy, trainer.optimizer)
    params = both["params"]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    ref_grads = jax.jit(
        lambda p: ref.loss_and_grads(p, both["ids"], REF_CFG, 1))
    with jax.default_matmul_precision("highest"):
        for t in range(1, 4):
            state, stats = trainer.train_step(
                state, both["batch"], jax.random.key(0))
            loss, grads = ref_grads(params)
            params, m, v, _, norm = ref.adam_step(
                params, m, v, grads, jnp.float32(t), HP)
            assert float(stats["loss"]) == pytest.approx(float(loss), rel=1e-5)
            assert float(stats["grad_norm"]) == pytest.approx(
                float(norm), rel=2e-4)
    for leaf, got in _flat(state.params).items():
        want, start = _flat(params)[leaf], _flat(both["params"])[leaf]
        moved = float(jnp.linalg.norm(want - start))
        if leaf.endswith("router/bias"):
            assert moved == 0 and not float(jnp.abs(got - start).max())
            continue
        gap = float(jnp.linalg.norm(got - want)) / moved
        assert gap < 0.02, (leaf, gap)


# ------------------------------------------------------------- the rotary
def _old_apply_rope(x, positions, theta=10000.0):
    """``apply_rope`` as it stood before the rotary spec, line for line."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    while angles.ndim < x.ndim:
        angles = angles[..., None, :] if angles.ndim == x.ndim - 1 else angles[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("theta", [10000.0, 500000.0])
def test_the_llama_path_rotates_to_the_bit_as_before(dtype, theta):
    x = jax.random.normal(jax.random.key(1), (2, 40, 4, 128)).astype(dtype)
    pos = jnp.arange(40)[None] + jnp.array([[0], [7]])
    want = jax.jit(_old_apply_rope, static_argnums=2)(x, pos, theta)
    got = jax.jit(apply_rope, static_argnums=2)(x, pos, Rotary(theta))
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_rope_block_holds_its_spec_through_a_config():
    """A block with ``rope=True, rope_theta`` (the Llama family's) gives
    its attention ``Rotary(rope_theta)``, a rope-less one none, and the
    spec survives ``config()`` as JSON and back, as spec-shipping sends
    it."""
    from tensorlink_tpu.nn.transformer import TransformerBlock

    block = TransformerBlock(32, 4, causal=True, rope=True, rope_theta=1e6)
    assert block.children["attn"].rotary == Rotary(1e6, 1.0, None)
    assert TransformerBlock(32, 4).children["attn"].rotary is None
    spec = Rotary(5e5, 0.5, Yarn(16.0, 32, 32.0, 1.0))
    attn = MultiHeadAttention(32, 4, 2, causal=True, rotary=spec)
    back = module_from_config(json.loads(json.dumps(attn.config())))
    assert back.rotary == spec


def _hf_config(spec, head_dim=128):
    return types.SimpleNamespace(
        rope_theta=spec["rope_theta"], head_dim=head_dim, hidden_size=3072,
        num_attention_heads=48, max_position_embeddings=1048576,
        partial_rotary_factor=spec.get("partial_rotary_factor", 1.0),
        rope_scaling=dict(spec),
    )


def test_yarn_is_hfs_at_the_published_numbers():
    """The full layers' inverse frequencies and attention factor, the
    program's and the reference's, against
    ``transformers.modeling_rope_utils._compute_yarn_parameters``."""
    torch = pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")

    want, factor = rope_utils._compute_yarn_parameters(
        _hf_config(FULL_ROPE), torch.device("cpu"))
    want = want.numpy()
    assert want.shape == (32,)  # 64 of 128 dimensions rotate
    rotary = fam._rotary(FULL_ROPE)
    assert rotary == Rotary(
        500000, 0.5, Yarn(128, 8192, 32, 1, 1.4852030263919618))
    got, got_factor = rotary.frequencies(64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    assert got_factor == pytest.approx(factor) == pytest.approx(
        0.1 * math.log(128) + 1)
    dim, inv, scale = ref.rope_frequencies(FULL_ROPE, 128)
    assert dim == 64 and scale == pytest.approx(factor)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    # at 4,096 positions YaRN moves frequencies: the blend is no identity
    plain = 1 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert np.abs(want / plain - 1).max() > 0.9
    # without a given factor: 0.1 ln(factor) + 1, as HF infers it
    assert Yarn(128, 8192).attention_factor is None
    assert Rotary(500000, 0.5, Yarn(128, 8192)).frequencies(64)[1] == (
        pytest.approx(factor))


def test_the_window_layers_rotate_as_hfs_default():
    torch = pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")

    spec = CELL_CFG["rope_parameters"]["sliding_attention"]
    want, factor = rope_utils._compute_default_rope_parameters(
        _hf_config(spec), torch.device("cpu"))
    got, got_factor = fam._rotary(spec).frequencies(128)
    assert factor == got_factor == 1.0
    np.testing.assert_allclose(np.asarray(got), want.numpy(), rtol=1e-6)


def test_partial_rotary_rotates_the_first_dims_alone():
    """The first half of a head turns by pairs (i, i + 32), scaled by
    the attention factor; the second half passes through."""
    spec = Rotary(500000.0, 0.5, Yarn(128.0, 8192, 32.0, 1.0, 1.5))
    x = jax.random.normal(jax.random.key(2), (1, 64, 2, 128))
    pos = jnp.arange(64)[None]
    y = apply_rope(x, pos, spec)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    pair = lambda a: jnp.hypot(a[..., :32], a[..., 32:64])  # noqa: E731
    np.testing.assert_allclose(pair(y), 1.5 * pair(x), rtol=1e-5)
    np.testing.assert_array_equal(y[:, 0, :, :64], 1.5 * x[:, 0, :, :64])


def test_the_spec_survives_the_modules_config():
    m = MultiHeadAttention(32, 4, 2, causal=True, rotary=TINY.full_rotary,
                           head_gate=True)
    rebuilt = module_from_config(json.loads(json.dumps(m.config())))
    assert rebuilt.rotary == TINY.full_rotary and rebuilt.head_gate


# -------------------------------------------------------- the heads' gate
def test_a_gate_of_ones_is_ungated_attention():
    m = MultiHeadAttention(32, 6, 2, head_dim=8, causal=True, head_gate=True)
    p = m.init(jax.random.key(3))
    out = jax.random.normal(jax.random.key(4), (2, 5, 6, 8))
    np.testing.assert_array_equal(
        m._out(p, out, jnp.ones((2, 5, 6))), m._out(p, out, None))


def test_each_head_is_scaled_by_its_own_gate():
    """The gated module against the ungated one on the same q, k, v, o:
    head h of token t times sigmoid(x W_g)[t, h], before ``o``."""
    x = jax.random.normal(jax.random.key(5), (2, 12, 32))
    gated = MultiHeadAttention(32, 6, 2, 8, use_bias=False, causal=True,
                               head_gate=True, attn_impl="reference")
    plain = MultiHeadAttention(32, 6, 2, 8, use_bias=False, causal=True,
                               attn_impl="reference")
    p = gated.init(jax.random.key(6))
    assert p["g"]["w"].shape == (32, 6) and set(p["g"]) == {"w"}
    heads = []
    for h in range(6):  # one head at a time through the ungated module
        keep = jnp.zeros((6, 8)).at[h].set(1.0).reshape(48, 1)
        heads.append(plain.apply({**p, "o": {"w": p["o"]["w"] * keep}}, x))
    gate = jax.nn.sigmoid(x @ p["g"]["w"])
    want = sum(gate[..., h:h + 1] * heads[h] for h in range(6))
    np.testing.assert_allclose(gated.apply(p, x), want, rtol=1e-5, atol=1e-6)


# ------------------------------------ grouped query heads through flash
@pytest.mark.parametrize("heads,window", [(9, 512), (6, None)],
                         ids=["72_8_window", "48_8_full"])
def test_gqa_groups_of_9_and_6_through_the_flash_entry(heads, window):
    """A key head read by 9 (window layers: 72 on 8) or 6 (full layers:
    48 on 8) query heads at head width 128, the Pallas kernels
    interpreted, forward and gradient, against the reference attention
    over 1,024 positions, where a band of 512 binds."""
    from tensorlink_tpu.ops.flash import flash_attention_impl

    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (1, 1024, heads, 128))
    k, v = (jax.random.normal(kk, (1, 1024, 1, 128)) for kk in ks[1:3])
    do = jax.random.normal(ks[3], (1, 1024, heads, 128))

    def run(fn):
        out, vjp = jax.vjp(lambda *a: fn(*a), q, k, v)
        return (out, *vjp(do))

    got = run(lambda *a: flash_attention_impl(
        *a, causal=True, window=window, interpret=True))
    want = run(lambda *a: dot_product_attention(*a, causal=True, window=window))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))
    if window is not None:  # the band's edge: key q - 512 is out, q - 511 in
        v1 = jnp.zeros_like(v).at[0, 0].set(1.0)
        out = flash_attention_impl(
            q, k, v1, causal=True, window=window, interpret=True)
        assert float(jnp.abs(out[0, 511]).max()) > 0
        assert float(jnp.abs(out[0, 512]).max()) == 0


# ------------------------------------------------------- the expert share
def test_the_32_shares_of_8_experts_add_up_to_the_uncut_layer():
    """256 experts, top 10, one shared: what each of 32 chips computes
    (its 8 experts' part and the shared expert), the shared expert
    counted once, is the whole layer, and the whole layer is the
    reference's."""
    dim, hidden, n, k = 16, 8, 256, 10
    whole = HeldExpertsMoE(dim, hidden, n, k, routed_scale=2.5)
    p = weights.make_tree(11, jax.eval_shape(whole.init, jax.random.key(0)))
    x = jax.random.normal(jax.random.key(8), (2, 8, dim))
    shared = whole.children["shared"].apply(p["shared"], x)
    parts = []
    for first in range(0, n, 8):
        share = HeldExpertsMoE(dim, hidden, n, k, held=(first, 8),
                               routed_scale=2.5)
        ps = {**p, "experts": jax.tree.map(
            lambda w: w[:, first:first + 8], p["experts"])}
        parts.append(share.apply(ps, x) - shared)
    y = whole.apply(p, x)
    np.testing.assert_allclose(sum(parts) + shared, y, atol=1e-5)
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "moe_routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            ref._experts(x, p, cfg, None), y, atol=1e-5)


# ------------------------------------------------------ pattern and counts
def test_the_layer_pattern_of_the_whole_model():
    cfg = LagunaConfig.laguna_s21()
    assert cfg.num_layers == 48
    assert [cfg.layer_types.count(t) for t in (WINDOW, FULL)] == [36, 12]
    assert {
        (t, h) for t, h in zip(cfg.layer_types, cfg.num_attention_heads_per_layer)
    } == {(WINDOW, 72), (FULL, 48)}
    assert cfg.mlp_layer_types.count("dense") == 1


def test_the_cut_is_the_published_first_five_layers():
    whole, cut = LagunaConfig.laguna_s21(), LagunaConfig.laguna_s21_l5e8()
    assert cut.layer_types == whole.layer_types[:5]
    assert cut.num_attention_heads_per_layer == whole.num_attention_heads_per_layer[:5]
    assert cut.mlp_layer_types == whole.mlp_layer_types[:5]
    assert cut.vocab_size * 8 == whole.vocab_size
    assert cut.held_experts == (0, 8) and cut.num_experts == 256
    assert dataclasses.replace(
        cut, layer_types=whole.layer_types,
        num_attention_heads_per_layer=whole.num_attention_heads_per_layer,
        mlp_layer_types=whole.mlp_layer_types, vocab_size=whole.vocab_size,
        held_experts=None, moe_row_bound=None, remat=False) == whole


def test_the_stage_preset_is_811_million_parameters():
    """As the configuration file builds it and as the preset does: the
    dense layer 157.44 M, a window expert layer 148.86 M, a full one
    129.92 M, the table and head 77.07 M."""
    for model in (Laguna(LagunaConfig.laguna_s21_l5e8()), fam.build(CELL_CFG)):
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        sizes = {
            n: sum(x.size for x in jax.tree.leaves(shapes["blocks"][str(n)]))
            for n in range(5)
        }
        assert sizes == {0: 157_440_000, 1: 148_863_232, 2: 148_863_232,
                         3: 148_863_232, 4: 129_915_136}
        total = sum(x.size for x in jax.tree.leaves(shapes))
        assert total - sum(sizes.values()) == 2 * 12544 * 3072 + 3072
        assert round(total / 1e6, 1) == 811.0


def test_the_block_scopes_by_kind():
    blocks = [LagunaBlock(TINY, i) for i in range(5)]
    assert [b.attn_scope for b in blocks] == ["attn", "swa", "swa", "swa", "attn"]
    assert [b.ffn_kind for b in blocks] == ["mlp", "moe", "moe", "moe", "moe"]
    assert [b.children["attn"].window for b in blocks] == [None, 8, 8, 8, None]
    with pytest.raises(ValueError, match="no such kind"):
        LagunaBlock(dataclasses.replace(
            TINY, layer_types=("chunked_attention",) * 5), 0)


def test_a_cache_is_refused_with_the_reason(both):
    model, params, batch = both["model"], both["params"], both["batch"]
    for kw in ({"cache": {}}, {"caches": []}):
        with pytest.raises(NotImplementedError, match="kvpool.py"):
            model.apply(params, batch["input_ids"], **kw)
    hidden = jax.eval_shape(
        lambda p, i: model.apply(p, i, logits=False), params, batch["input_ids"])
    assert hidden.shape == (2, 64, TINY.dim)


@functools.lru_cache(maxsize=None)
def _four_steps(remat):
    ids = _ids()
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    model = Laguna(dataclasses.replace(TINY, remat=remat))
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
    return losses


@pytest.mark.parametrize("remat", [False, True])
def test_trains_through_the_trainer_in_bf16(remat):
    """bf16 compute on f32 masters, Adam, clipping: the loss on one
    batch falls and nothing is non-finite, with and without the blocks
    recomputed."""
    losses = _four_steps(remat)
    assert losses[-1] < losses[0] - 0.05, losses
    assert losses[0] == pytest.approx(_four_steps(False)[0], rel=1e-3)
