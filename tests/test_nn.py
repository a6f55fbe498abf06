"""NN core: layers, attention, transformer blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tensorlink_tpu import nn
from tensorlink_tpu.nn.attention import Rotary, apply_rope, dot_product_attention
from tensorlink_tpu.nn.module import init_module


KEY = jax.random.key(0)


def test_dense_shapes_and_spec():
    m = nn.Dense(8, 16, shard="col")
    p = m.init(KEY)
    y = m.apply(p, jnp.ones((2, 8)))
    assert y.shape == (2, 16)
    assert m.param_spec() == {"w": P(None, "model"), "b": P("model")}
    row = nn.Dense(8, 16, shard="row")
    assert row.param_spec() == {"w": P("model", None), "b": P()}


def test_embedding_and_tying():
    m = nn.Embedding(100, 16)
    p = m.init(KEY)
    ids = jnp.array([[1, 2], [3, 4]])
    e = m.apply(p, ids)
    assert e.shape == (2, 2, 16)
    logits = m.attend(p, e)
    assert logits.shape == (2, 2, 100)


def test_layernorm_rmsnorm_stats():
    x = jax.random.normal(KEY, (4, 32)) * 5 + 3
    ln = nn.LayerNorm(32)
    y = ln.apply(ln.init(KEY), x)
    np.testing.assert_allclose(np.mean(np.asarray(y), -1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.std(np.asarray(y), -1), 1.0, atol=1e-2)
    rms = nn.RMSNorm(32)
    yr = rms.apply(rms.init(KEY), x)
    assert yr.shape == x.shape and not np.allclose(np.asarray(yr), np.asarray(x))


def test_dropout_train_vs_eval():
    m = nn.Dropout(0.5)
    x = jnp.ones((100, 100))
    assert (m.apply({}, x) == x).all()  # eval: identity
    y = m.apply({}, x, rng=KEY, train=True)
    frac_zero = float((y == 0).mean())
    assert 0.3 < frac_zero < 0.7


def test_attention_causality():
    """Output at position t must not depend on tokens after t."""
    m = nn.MultiHeadAttention(16, 4, causal=True)
    p = m.init(KEY)
    x = jax.random.normal(KEY, (1, 8, 16))
    y1 = m.apply(p, x)
    x2 = x.at[0, -1].set(999.0)  # change only the last token
    y2 = m.apply(p, x2)
    np.testing.assert_allclose(
        np.asarray(y1[0, :-1]), np.asarray(y2[0, :-1]), atol=1e-5
    )
    assert not np.allclose(np.asarray(y1[0, -1]), np.asarray(y2[0, -1]))


def test_attention_padding_mask():
    m = nn.MultiHeadAttention(16, 4)
    p = m.init(KEY)
    x = jax.random.normal(KEY, (1, 6, 16))
    mask = jnp.ones((1, 1, 6, 6), bool).at[:, :, :, 3:].set(False)
    y_masked = m.apply(p, x, mask=mask)
    # changing a masked-out token must not affect OTHER positions' outputs
    # (its own query still changes, so exclude position 4 itself)
    x2 = x.at[0, 4].set(7.0)
    y2 = m.apply(p, x2, mask=mask)
    keep = [0, 1, 2, 3, 5]
    np.testing.assert_allclose(
        np.asarray(y_masked[0, keep]), np.asarray(y2[0, keep]), atol=1e-5
    )


def test_gqa_matches_repeat():
    q = jax.random.normal(KEY, (2, 4, 8, 16))
    k = jax.random.normal(jax.random.key(1), (2, 4, 2, 16))
    v = jax.random.normal(jax.random.key(2), (2, 4, 2, 16))
    out = dot_product_attention(q, k, v)
    out_ref = dot_product_attention(
        q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref), atol=1e-6)


def test_rope_relative_shift_invariance():
    """RoPE attention scores depend only on relative positions."""
    D = 16
    q = jax.random.normal(KEY, (1, 1, 1, D))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, D))
    s1 = jnp.sum(
        apply_rope(q, jnp.array([[3]])) * apply_rope(k, jnp.array([[1]]))
    )
    s2 = jnp.sum(
        apply_rope(q, jnp.array([[10]])) * apply_rope(k, jnp.array([[8]]))
    )
    np.testing.assert_allclose(float(s1), float(s2), atol=1e-4)


def test_kv_cache_decode_matches_full_forward():
    """Incremental decode through the cache == full causal forward."""
    m = nn.MultiHeadAttention(16, 4, causal=True, rotary=Rotary())
    p = m.init(KEY)
    T = 6
    x = jax.random.normal(KEY, (2, T, 16))
    full = m.apply(p, x)
    cache = m.init_cache(2, T, dtype=jnp.float32)
    outs = []
    for t in range(T):
        o, cache = m.apply(p, x[:, t : t + 1], cache=cache)
        outs.append(o)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc), atol=1e-4)


@pytest.mark.parametrize("style,norm", [("pre", "layer"), ("post", "layer"), ("pre", "rms")])
def test_transformer_block(style, norm):
    blk = nn.TransformerBlock(
        32, 4, norm_style=style, norm=norm, causal=True, dropout=0.1
    )
    p = init_module(blk, KEY)
    x = jax.random.normal(KEY, (2, 5, 32))
    y = blk.apply(p, x)
    assert y.shape == x.shape
    y_train = blk.apply(p, x, rng=jax.random.key(3), train=True)
    assert not np.allclose(np.asarray(y), np.asarray(y_train))


def test_stack_and_sequential_slicing():
    stack = nn.TransformerStack(
        4, nn.TransformerBlock, dim=16, num_heads=2, causal=True
    )
    p = stack.init(KEY)
    x = jax.random.normal(KEY, (1, 3, 16))
    y = stack.apply(p, x)
    assert y.shape == x.shape
    seq = nn.Sequential(stack.blocks())
    assert len(seq[:2]) == 2
    spec = stack.param_spec()
    # every block's attention q is column-sharded
    assert spec["0"]["attn"]["q"]["w"] == P(None, "model")


def test_param_spec_tree_matches_params():
    blk = nn.TransformerBlock(16, 2)
    p = blk.init(KEY)
    spec = blk.param_spec()
    assert jax.tree.structure(p) == jax.tree.structure(
        spec, is_leaf=lambda x: isinstance(x, P)
    )


def test_module_config_serializable():
    import json

    blk = nn.TransformerBlock(16, 2, causal=True)
    cfg = blk.config()
    s = json.dumps(cfg)
    assert "TransformerBlock" in s


def test_fresh_prefill_guard_poisons_nonempty_cache():
    """The fresh-keys prefill contract (T-wide mask => attend projected
    k/v) holds only for an EMPTY cache; a chunked-prefill caller at
    index>0 would silently drop cached context, so the output is
    NaN-poisoned there instead (the index is traced — no trace-time
    raise possible)."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    mha = MultiHeadAttention(32, 4, causal=True, attn_impl="reference")
    params = mha.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 4, 32))
    cache = mha.init_cache(2, 16, dtype=jnp.float32)
    m = jnp.tril(jnp.ones((1, 1, 4, 4), bool))

    ok, cache1 = mha.apply(params, x, cache=cache, mask=m)
    assert np.isfinite(np.asarray(ok)).all()  # index 0: legit prefill
    bad, _ = mha.apply(params, x, cache=cache1, mask=m)  # index 4
    assert np.isnan(np.asarray(bad)).all()


def test_single_token_prefill_width1_mask_is_fresh():
    """A T==1 write at cache index 0 with a [B,1,1,1] mask is a fresh
    single-token prefill (ADVICE r5: classified non-fresh, the width-1
    mask broadcast over the whole cache and blessed unwritten zero-key
    slots). It must match the cacheless forward exactly; at index>0 the
    same shape is a misuse and hits the fresh-keys NaN poison."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    mha = MultiHeadAttention(32, 4, causal=True, attn_impl="reference")
    params = mha.init(jax.random.key(0))
    cache = mha.init_cache(2, 16, dtype=jnp.float32)
    x1 = jax.random.normal(jax.random.key(1), (2, 1, 32))
    m1 = jnp.ones((2, 1, 1, 1), bool)

    out, cache1 = mha.apply(params, x1, cache=cache, mask=m1)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha.apply(params, x1)), atol=1e-5
    )
    # decode-shaped misuse: width-1 mask with a non-empty cache is loud
    bad, _ = mha.apply(params, x1, cache=cache1, mask=m1)
    assert np.isnan(np.asarray(bad)).all()


def test_width1_mask_rejected_for_multi_token_cache_write():
    """T>1 with a width-1 mask is neither the fresh form (mask is not
    T-wide) nor a cache-width mask: it used to be blessed and broadcast
    over every slot — now it raises instead (ADVICE r5)."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    mha = MultiHeadAttention(32, 4, causal=True, attn_impl="reference")
    params = mha.init(jax.random.key(0))
    cache = mha.init_cache(2, 16, dtype=jnp.float32)
    x4 = jax.random.normal(jax.random.key(2), (2, 4, 32))
    with pytest.raises(ValueError, match="cache-width"):
        mha.apply(params, x4, cache=cache, mask=jnp.ones((2, 1, 4, 1), bool))
