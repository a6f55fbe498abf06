"""Latent attention without rotary (nn/mla.py) against the benchmark's
plain reference, and the flash kernels at a value width of their own
(interpret mode: the CPU runs the kernels' bodies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from tensorlink_tpu.nn.attention import dot_product_attention
from tensorlink_tpu.nn.mla import LatentAttention
from tensorlink_tpu.ops.flash import flash_attention, flash_block_for

CFG = {
    "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "rms_norm_eps": 1e-5,
}


@pytest.fixture(scope="module")
def module_and_params():
    from benchmark import weights

    mod = LatentAttention(32, 2, 16, 8, 16, 24)
    shapes = jax.eval_shape(mod.init, jax.random.key(0))
    x = jax.random.normal(jax.random.key(2), (2, 96, 32))
    return mod, weights.make_tree(13, shapes), x


def test_module_is_the_reference(module_and_params):
    mod, params, x = module_and_params
    np.testing.assert_allclose(
        mod.apply(params, x), ref._mla(x, params, CFG, None), atol=2e-5)


def test_module_gradients_are_the_references(module_and_params):
    mod, params, x = module_and_params
    ct = jax.random.normal(jax.random.key(3), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(mod.apply(p, x) * ct), (0, 1))(params, x)
    want = jax.grad(
        lambda p, x: jnp.sum(ref._mla(x, p, CFG, None) * ct), (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))


def test_shared_key_channels_and_refusal(module_and_params):
    """The last ``rope_dim`` channels of every head's key are one
    projection of x: moving that slice of kv_a moves every head alike."""
    mod, params, x = module_and_params
    assert params["kv_a"]["w"].shape == (32, 24 + 8)
    assert params["q"]["w"].shape == (32, 2 * 24)
    assert params["kv_b"]["w"].shape == (24, 2 * 32)
    with pytest.raises(NotImplementedError, match="latent"):
        mod.apply(params, x, cache={})


def _qkv(B, T, H, Hkv, D, Dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(T + D), 4)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, Dv), dtype)
    return q, k, v, jax.random.normal(ks[3], (B, T, H, Dv), dtype)


# q, k wider than v (MLA), narrower (nothing in the kernels prefers a
# side), causal in sub-tiles (T = 256: two 128-blocks) and whole-block,
# grouped heads, a padding mask
@pytest.mark.parametrize("T,H,Hkv,D,Dv,causal,masked", [
    (256, 2, 2, 24, 16, True, False),
    (256, 2, 2, 16, 32, True, False),
    (128, 2, 2, 24, 16, False, False),
    (128, 4, 2, 24, 16, True, False),
    (128, 2, 2, 24, 16, True, True),
])
def test_flash_with_its_own_value_width(T, H, Hkv, D, Dv, causal, masked):
    q, k, v, ct = _qkv(2, T, H, Hkv, D, Dv)
    kv_mask = None
    mask = None
    if masked:
        kv_mask = (jnp.arange(T)[None] < jnp.array([[T], [T - 37]])).astype(jnp.float32)
        mask = kv_mask[:, None, None, :] > 0

    def kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_mask, causal, True) * ct)

    def plain(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=causal, mask=mask) * ct)

    out = flash_attention(q, k, v, kv_mask, causal, True)
    assert out.shape == (2, T, H, Dv)
    np.testing.assert_allclose(
        out, dot_product_attention(q, k, v, causal=causal, mask=mask),
        atol=2e-5)
    for a, b in zip(jax.grad(kernel, (0, 1, 2))(q, k, v),
                    jax.grad(plain, (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()))


def test_wide_heads_get_smaller_blocks():
    """At q, k 192 wide a 1024-block's dq kernel does not fit a v5e's
    16 MB of VMEM (tests/test_tpu_compile.py compiles the real shape)."""
    assert flash_block_for(4096, 4, 192) == 512
    assert flash_block_for(4096, 4, 128) == flash_block_for(4096, 4) == 1024
    assert flash_block_for(384, 4, 192) == 128


def test_kv_of_another_length_or_batch_is_refused():
    from tensorlink_tpu.ops.pallas.flash_attention import flash_attention_fwd

    q = jnp.zeros((1, 2, 128, 24))
    with pytest.raises(ValueError, match="bad kv shapes"):
        flash_attention_fwd(q, q, jnp.zeros((1, 2, 64, 16)), interpret=True)
    with pytest.raises(ValueError, match="bad kv shapes"):
        flash_attention_fwd(q, jnp.zeros((1, 2, 128, 16)), q, interpret=True)
