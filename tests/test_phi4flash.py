"""Phi-4-mini-flash (models/phi4flash.py) at tiny widths on the CPU,
seeded weights from the benchmark's rules, against the benchmark's plain
reference: logits, loss, every leaf's gradient, three ``Trainer`` steps;
the layer pattern and the counts of the whole model and of the cut; the
values that go from layer to layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import phi4flash as ref
from conftest import count_equations as _count
from tensorlink_tpu.config import TrainConfig
from tensorlink_tpu.models.phi4flash import (
    Phi4Flash,
    Phi4FlashBlock,
    Phi4FlashConfig,
    layer_kind,
)
from tensorlink_tpu.nn.diff_attention import DifferentialAttention, lambda_init
from tensorlink_tpu.nn.mamba import GatedMemoryUnit, MambaMixer
from tensorlink_tpu.train.trainer import Trainer, softmax_cross_entropy

TINY = Phi4FlashConfig.tiny()
# the tiny preset in the configuration file's (HF's) keys
REF_CFG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_norm_eps": 1e-5, "sliding_window": 16,
    "published_num_hidden_layers": 32, "layers": [0, 1, 16, 17, 18, 19],
    "mamba": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4},
}
HP = {"learning_rate": 3e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
      "clip_norm": 1.0}
LEAVES = [
    weights.path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(Phi4Flash(TINY).init, jax.random.key(0)))[0]
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
        np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 97)), jnp.int32)


@pytest.fixture(scope="module")
def both():
    """Program and reference on one seeded tree and batch: logits, loss
    and gradients of each, computed once."""
    model = Phi4Flash(TINY)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_tree(5, shapes)
    ids = _ids()
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
        "params": params, "batch": batch, "ids": ids,
    }


def test_logits_are_the_references(both):
    got, want = both["logits"]
    assert got.shape == (2, 96, TINY.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


def test_loss_is_the_references(both):
    got, want = both["loss"]
    assert abs(float(got) - float(want)) < 2e-6 * float(want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_is_the_references(both, leaf):
    got, want = both["grads"][0][leaf], both["grads"][1][leaf]
    assert got.shape == want.shape
    if leaf.endswith("mixer/k/b"):  # moves no softmax: nought to rounding
        ref_q = both["grads"][1][leaf.replace("/k/b", "/q/b")]
        assert float(jnp.abs(got).max()) < 1e-5 * float(jnp.abs(ref_q).max())
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
    from tensorlink_tpu.train.trainer import TrainState

    copy = jax.tree.map(jnp.array, both["params"])  # the step donates
    state = TrainState.create(copy, trainer.optimizer)
    params = both["params"]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        for t in range(1, 4):
            state, stats = trainer.train_step(
                state, both["batch"], jax.random.key(0))
            loss, grads = ref.loss_and_grads(params, both["ids"], REF_CFG, 1)
            params, m, v, _, norm = ref.adam_step(
                params, m, v, grads, jnp.float32(t), HP)
            assert float(stats["loss"]) == pytest.approx(float(loss), rel=1e-5)
            assert float(stats["grad_norm"]) == pytest.approx(
                float(norm), rel=2e-4)
    for leaf, got in _flat(state.params).items():
        if leaf.endswith("mixer/k/b"):
            # a key's bias moves no softmax: its gradient is rounding,
            # and Adam steps by the learning rate whatever a gradient's size
            continue
        want, start = _flat(params)[leaf], _flat(both["params"])[leaf]
        # by the leaf's norm: where an element's gradient is rounding,
        # Adam still steps by the learning rate, either way
        moved = float(jnp.linalg.norm(want - start))
        gap = float(jnp.linalg.norm(got - want)) / moved
        assert gap < 0.02, (leaf, gap)  # 0.001 at the most here


def test_the_layer_pattern_of_the_whole_model():
    kinds = [layer_kind(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in
            ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert [layer_kind(i, 32) for i in TINY.layers] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    assert [layer_kind(i, 32) for i in range(32)] == [
        ref.layer_kind(i, 32) for i in range(32)]
    blocks = list(Phi4Flash(TINY).children["blocks"].children.values())
    assert [b.index for b in blocks] == list(TINY.layers)
    assert [b.gives_memory for b in blocks] == [0, 0, 1, 0, 0, 0]
    assert [b.gives_kv for b in blocks] == [0, 0, 0, 1, 0, 0]
    assert blocks[1].children["mixer"].window == TINY.sliding_window
    assert blocks[3].children["mixer"].window is None
    assert lambda_init(0) == pytest.approx(0.2) and lambda_init(17) > 0.79


@pytest.mark.parametrize("layers,reads", [
    ((0, 1, 18), "layer 16's scan"), ((0, 1, 16, 19), "layer 17's k and v"),
    ((1, 0), "in their order"),
])
def test_a_cut_that_leaves_out_what_a_layer_reads_is_refused(layers, reads):
    with pytest.raises(ValueError, match=reads):
        Phi4Flash(dataclasses.replace(TINY, layers=layers))


def _sizes(cfg):
    shapes = jax.eval_shape(Phi4Flash(cfg).init, jax.random.key(0))
    return {
        weights.path_str(p): int(np.prod(x.shape))
        for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
    }


def test_the_whole_model_is_3_85_billion_parameters():
    sizes = _sizes(Phi4FlashConfig.phi4_mini_flash())
    assert round(sum(sizes.values()) / 1e9, 2) == 3.85  # published: 3.8 B
    assert sizes["tok_emb/table"] == 200064 * 2560 and "lm_head/w" not in sizes


def test_the_stage_preset_is_697_million_parameters():
    cfg = Phi4FlashConfig.phi4_mini_flash_l6()
    sizes = _sizes(cfg)

    def under(prefix):
        return sum(n for k, n in sizes.items() if k.startswith(prefix))

    assert round(under("blocks/0/mlp") / 1e6, 2) == 78.64  # SwiGLU
    assert round(under("blocks/0/mixer") / 1e6, 2) == 41.24  # Mamba
    assert round(under("blocks/1/mixer") / 1e6, 2) == 19.67  # self-attention
    assert round(under("blocks/4/mixer") / 1e6, 2) == 26.21  # GMU
    assert round(under("blocks/5/mixer") / 1e6, 2) == 13.11  # cross: q and o
    assert "blocks/5/mixer/k/w" not in sizes and "blocks/3/mixer/k/w" in sizes
    assert sizes["tok_emb/table"] == 25008 * 2560
    assert round(sum(sizes.values()) / 1e6, 1) == 697.1
    assert cfg.remat and cfg.layers == (0, 1, 16, 17, 18, 19)
    assert {k.rsplit("/", 1)[-1] for k in sizes} == {
        "w", "b", "bias", "scale", "table"}  # weights.make_leaf's five


def _dense_maps(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v, every head written out."""
    T, d = q.shape[0], q.shape[-1]
    s = (q @ k.T) / np.sqrt(d)
    i, j = np.arange(T)[:, None], np.arange(T)[None]
    keep = (j <= i) & ((j > i - window) if window else True)
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ v


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("cross", [False, True])
def test_differential_attention_is_two_softmax_maps(window, cross):
    H, Hkv, d, D, T, index = 4, 2, 8, 32, 40, 17
    layer = DifferentialAttention(D, H, Hkv, d, index, window=window, cross=cross)
    params = weights.make_tree(7, jax.eval_shape(layer.init, jax.random.key(0)))
    x = jax.random.normal(jax.random.key(1), (1, T, D))
    kv = None
    if cross:
        kv = tuple(
            jax.random.normal(jax.random.key(i), (1, T, Hkv, d)) for i in (2, 3))
    with jax.default_matmul_precision("highest"):
        out, (k, v) = layer.apply(params, x, kv=kv)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    xs = np.asarray(x[0], np.float64)
    q = (xs @ p["q"]["w"] + p["q"]["b"]).reshape(T, H, d)
    if cross:
        assert k is kv[0] and v is kv[1]
        assert set(p) == {
            "q", "o", "subln", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
    else:
        np.testing.assert_allclose(
            k[0].reshape(T, -1), xs @ p["k"]["w"] + p["k"]["b"], atol=1e-5)
    kk, vv = np.asarray(k[0], np.float64), np.asarray(v[0], np.float64)
    lam = (
        np.exp(p["lambda_q1"]["b"] @ p["lambda_k1"]["b"])
        - np.exp(p["lambda_q2"]["b"] @ p["lambda_k2"]["b"]) + lambda_init(index)
    )
    heads = []
    for j in range(H // 2):  # pair j: query heads 2j, 2j+1
        g = j // (H // Hkv)  # on key pair g: key heads 2g, 2g+1
        values = np.concatenate([vv[:, 2 * g], vv[:, 2 * g + 1]], -1)
        a1 = _dense_maps(q[:, 2 * j], kk[:, 2 * g], values, window)
        a2 = _dense_maps(q[:, 2 * j + 1], kk[:, 2 * g + 1], values, window)
        o = a1 - lam * a2
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5)
        heads.append(o * p["subln"]["scale"] * (1 - lambda_init(index)))
    want = np.concatenate(heads, -1) @ p["o"]["w"] + p["o"]["b"]
    np.testing.assert_allclose(out[0], want, atol=2e-5)


def test_a_window_binds():
    """The band and the full map differ past the window and nowhere
    before it."""
    layer = {
        w: DifferentialAttention(32, 4, 2, 8, 1, window=w) for w in (16, None)
    }
    params = weights.make_tree(
        7, jax.eval_shape(layer[16].init, jax.random.key(0)))
    x = jax.random.normal(jax.random.key(1), (1, 40, 32))
    band, full = (layer[w].apply(params, x)[0] for w in (16, None))
    np.testing.assert_allclose(band[:, :16], full[:, :16], atol=1e-6)
    assert float(jnp.abs(band[:, 16:] - full[:, 16:]).max()) > 1e-3


def test_what_goes_from_layer_to_layer(both, monkeypatch):
    """The gated memory unit gets layer 16's scan output (not layer 0's)
    and the cross layer layer 17's k and v (not layer 1's), as the very
    arrays those layers returned."""
    seen = {"mamba": [], "attn": []}
    real_mamba, real_attn = MambaMixer.apply, DifferentialAttention.apply
    real_gmu = GatedMemoryUnit.apply

    def mamba(self, params, x, **kw):
        out = real_mamba(self, params, x, **kw)
        seen["mamba"].append(out[1])
        return out

    def attn(self, params, x, *, kv=None, **kw):
        seen["attn"].append((self.layer_index, kv))
        out = real_attn(self, params, x, kv=kv, **kw)
        seen.setdefault("kv", {})[self.layer_index] = out[1]
        return out

    def gmu(self, params, x, memory, **kw):
        seen["gmu"] = memory
        return real_gmu(self, params, x, memory, **kw)

    monkeypatch.setattr(MambaMixer, "apply", mamba)
    monkeypatch.setattr(DifferentialAttention, "apply", attn)
    monkeypatch.setattr(GatedMemoryUnit, "apply", gmu)
    both["model"].apply(both["params"], both["batch"]["input_ids"])
    assert len(seen["mamba"]) == 2 and seen["gmu"] is seen["mamba"][1]
    assert seen["gmu"].shape == (2, 96, 2 * TINY.dim)
    assert [(i, kv is None) for i, kv in seen["attn"]] == [
        (1, True), (17, True), (19, False)]
    assert seen["attn"][2][1] is seen["kv"][17]
    assert all(a is b for a, b in zip(seen["kv"][19], seen["kv"][17]))


def test_a_block_hands_on_what_it_does_not_give():
    block = Phi4FlashBlock(TINY, 1)  # a window layer
    params = block.init(jax.random.key(0))
    x = jnp.ones((1, 8, TINY.dim))
    memory, kv = object(), object()
    y, m, k = block.apply(params, x, memory, kv)
    assert m is memory and k is kv and y.shape == x.shape


def test_a_cache_is_refused_with_the_reason(both):
    model, params, batch = both["model"], both["params"], both["batch"]
    for kw in ({"cache": {}}, {"caches": []}):
        with pytest.raises(NotImplementedError, match="kvpool.py"):
            model.apply(params, batch["input_ids"], **kw)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        MambaMixer(32).apply({}, jnp.ones((1, 4, 32)), cache={})
    with pytest.raises(NotImplementedError, match="no cache"):
        DifferentialAttention(32, 4, 2, 8, 1).apply(
            {}, jnp.ones((1, 4, 32)), cache={})
    hidden = model.apply(params, batch["input_ids"], logits=False)
    assert hidden.shape == (2, 96, TINY.dim)


@functools.lru_cache(maxsize=None)
def _four_steps(remat):
    ids = _ids()
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    model = Phi4Flash(dataclasses.replace(TINY, remat=remat))
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
def test_trains_through_the_trainer_in_bf16(remat):
    """bf16 compute on f32 masters from the model's own initialisation
    (the published decay), Adam, clipping: the loss on one batch falls,
    nothing is non-finite, and recomputing the blocks changes no number
    of the first step."""
    losses = _four_steps(remat)
    assert losses[-1] < losses[0] - 0.05, losses
    # bf16: a checkpoint's body is compiled as one piece, which XLA fuses
    # (and rounds) differently; float32 is held bit for bit below
    assert losses[0] == pytest.approx(_four_steps(False)[0], rel=1e-4)


def _grad_of(both, remat):
    model = Phi4Flash(dataclasses.replace(TINY, remat=remat))
    return jax.grad(lambda p: loss_fn(model, p, both["batch"], None))


def _plain_checkpoint(monkeypatch):
    """The block remat as a plain ``jax.checkpoint``: a policy of None
    is its default, which keeps nothing."""
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names", lambda *_: None)


@pytest.mark.parametrize("blocks,scans", [
    ("kept", 10), ("recomputed whole", 10), ("plain checkpoint", 14),
])
def test_block_remat_adds_no_pass_of_the_scan(both, monkeypatch, blocks, scans):
    """The scan's passes, read off the gradient's jaxpr by its ``scan``
    equations. A Mamba layer holds five (the forward over the chunks
    and the tokens inside one; the backward over the chunks and, inside
    one, the tokens again and their transpose), with or without
    ``remat``: the
    block's recompute finds the scan's output and chunk states kept. A
    plain ``jax.checkpoint`` of each block, which keeps no name, runs
    the forward's two once more a layer."""
    if blocks == "plain checkpoint":
        _plain_checkpoint(monkeypatch)
    grad = _grad_of(both, remat=blocks != "recomputed whole")
    text = jax.make_jaxpr(grad)(both["params"])
    assert _count(text.jaxpr, "scan") == scans


@pytest.mark.parametrize("against,rel", [
    ("plain checkpoint", 0.0), ("recomputed nothing", 1e-5),
])
def test_block_remat_changes_no_gradient(both, monkeypatch, against, rel):
    """float32 on the CPU. What the block's recompute reads back is the
    value it would have computed: every leaf's gradient equals, bit for
    bit, that of a plain ``jax.checkpoint`` of each block, and stands at
    rounding from no remat at all."""
    got = _flat(_grad_of(both, True)(both["params"]))
    if against == "plain checkpoint":
        _plain_checkpoint(monkeypatch)
        want = _flat(_grad_of(both, True)(both["params"]))
    else:
        want = both["grads"][0]
    for leaf in LEAVES:
        gap = float(jnp.linalg.norm(got[leaf] - want[leaf]))
        assert gap <= rel * float(jnp.linalg.norm(want[leaf])), (leaf, gap)
