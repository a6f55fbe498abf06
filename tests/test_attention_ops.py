"""Flash attention (Pallas, interpret mode on CPU) + ring attention parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tensorlink_tpu.config import MeshConfig
from tensorlink_tpu.nn.attention import dot_product_attention
from tensorlink_tpu.ops.flash import flash_attention
from tensorlink_tpu.ops.pallas.flash_attention import flash_attention_fwd
from tensorlink_tpu.parallel.sp import ring_attention
from tensorlink_tpu.runtime.mesh import make_mesh

KEY = jax.random.key(0)


def _qkv(B=2, T=128, H=4, D=64, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = flash_attention_fwd(qt, kt, vt, causal=causal, interpret=True).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pallas_flash_multiblock():
    q, k, v = _qkv(T=256)
    ref = dot_product_attention(q, k, v, causal=True)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = flash_attention_fwd(
        qt, kt, vt, causal=True, block_q=128, block_k=128, interpret=True
    ).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_entry_grad():
    q, k, v = _qkv(T=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_padding_mask(causal):
    """kv_mask [B, Tk] (BERT attention_mask shape) on the kernel path."""
    q, k, v = _qkv(B=2, T=128, H=2, D=32)
    lengths = jnp.array([100, 57])
    kv_mask = (jnp.arange(128)[None, :] < lengths[:, None])
    ref = dot_product_attention(
        q, k, v, causal=causal, mask=kv_mask[:, None, None, :]
    )
    out = flash_attention(q, k, v, kv_mask, causal, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_backward_kernel_parity(causal):
    """Blockwise Pallas backward (dq/dk/dv) vs reference vjp, with a
    padding mask, multi-block seq (interpret mode)."""
    q, k, v = _qkv(B=1, T=256, H=2, D=32)
    lengths = jnp.array([200])
    kv_mask = (jnp.arange(256)[None, :] < lengths[:, None])

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_mask, causal, True) ** 2)

    def loss_ref(q, k, v):
        out = dot_product_attention(
            q, k, v, causal=causal, mask=kv_mask[:, None, None, :]
        )
        return jnp.sum(out ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_pallas_flash_backward_long_seq():
    """Grad parity at seq 1024 in interpret mode (VERDICT next #6)."""
    q, k, v = _qkv(B=1, T=1024, H=1, D=64)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, None, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_fully_masked_rows():
    """A batch row whose keys are ALL masked: forward 0, grads finite,
    and the jnp fallback agrees with the kernel convention."""
    from tensorlink_tpu.ops.flash import _fallback_attn

    q, k, v = _qkv(B=2, T=8, H=1, D=16)
    kv_mask = jnp.stack([jnp.zeros(8, bool), jnp.ones(8, bool)])

    out = flash_attention(q, k, v, kv_mask, False, True)
    assert np.allclose(np.asarray(out[0]), 0.0)
    fb = _fallback_attn(q, k, v, kv_mask, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fb), atol=2e-5)
    # causal + padding: rows before the first valid key are zero in both
    kv2 = jnp.stack([jnp.arange(8) >= 3, jnp.ones(8, bool)])
    out2 = flash_attention(q, k, v, kv2, True, True)
    fb2 = _fallback_attn(q, k, v, kv2, True)
    assert np.allclose(np.asarray(out2[0, :3]), 0.0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(fb2), atol=2e-5)

    g = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, kv_mask, False, True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a in g:
        assert np.all(np.isfinite(np.asarray(a)))


def test_flash_bad_blocks_raises():
    q = jnp.zeros((1, 2, 100, 32))
    with pytest.raises(ValueError):
        flash_attention_fwd(q, q, q, block_q=64, block_k=64)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_parity(devices, causal):
    mesh = make_mesh(MeshConfig(seq=8))
    q, k, v = _qkv(B=2, T=64, H=2, D=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_global_mask_parity(devices, causal):
    """Ring attention with a GLOBAL replicated key-padding mask (VERDICT
    r3 weak #6: the ring used to reject masks): matches the reference on
    a padded batch, fwd and grad."""
    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=2, T=32, H=2, D=16)
    mask = np.ones((2, 1, 1, 32), bool)
    mask[0, :, :, 24:] = False  # row 0: padded tail
    mask[1, :, :, :5] = False  # row 1: padded head
    mask = jnp.asarray(mask)
    ref = np.asarray(dot_product_attention(q, k, v, causal=causal, mask=mask))
    out = np.asarray(jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal, mask=mask)
    )(q, k, v))
    if causal:
        # row 1's queries 0-4 have NO attendable key (head padding +
        # causal): the output there is undefined — the ring yields 0,
        # the reference yields the uniform-softmax average. Compare only
        # well-defined query positions (real code masks those outputs).
        out, ref = out[:, 5:], ref[:, 5:]
    np.testing.assert_allclose(out, ref, atol=2e-5)

    # grads on a loss over well-defined queries only (same reason)
    q_valid = np.ones((2, 32, 1, 1), np.float32)
    if causal:
        q_valid[1, :5] = 0.0
    q_valid = jnp.asarray(q_valid)

    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.mean(
            (ring_attention(q, k, v, mesh, causal=causal, mask=mask)
             * q_valid) ** 2
        ),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gref = jax.grad(
        lambda q, k, v: jnp.mean(
            (dot_product_attention(q, k, v, causal=causal, mask=mask)
             * q_valid) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gr, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_attention_rejects_sharded_mask(devices):
    """A token-sharded (local-length) mask cannot follow the rotating
    k-blocks — must raise, not silently misapply."""
    from tensorlink_tpu.parallel.sp import ring_attention_impl

    mesh = make_mesh(MeshConfig(seq=4))
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(B=1, T=32, H=2, D=16)
    bad_mask = jnp.ones((1, 1, 1, 8), bool)  # local length, not global

    with pytest.raises(ValueError, match="GLOBAL"):
        jax.jit(
            lambda q, k, v: jax.shard_map(
                lambda q_, k_, v_: ring_attention_impl(
                    q_, k_, v_, causal=False, mask=bad_mask
                ),
                mesh=mesh,
                in_specs=(P(None, "seq"),) * 3,
                out_specs=P(None, "seq"),
                axis_names=frozenset({"seq"}),
                check_vma=False,
            )(q, k, v)
        )(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_parity(devices, causal):
    """The Pallas-kernel ring (VERDICT r4 weak #5: the ring's local block
    math was plain einsum) matches the reference and the einsum ring,
    fwd and grads, on a 8-shard ring."""
    mesh = make_mesh(MeshConfig(seq=8))
    q, k, v = _qkv(B=2, T=64, H=2, D=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    run = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal, use_flash=True, interpret=True
    ))
    np.testing.assert_allclose(
        np.asarray(run(q, k, v)), np.asarray(ref), atol=2e-5
    )
    # einsum-ring cross-check: the two ring paths agree with each other
    out_einsum = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal
    ))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(run(q, k, v)), np.asarray(out_einsum), atol=2e-5
    )

    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.mean(ring_attention(
            q, k, v, mesh, causal=causal, use_flash=True, interpret=True
        ) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gref = jax.grad(
        lambda q, k, v: jnp.mean(
            dot_product_attention(q, k, v, causal=causal) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gr, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_flash_gqa_narrow(devices):
    """GQA rides the flash ring with NARROW K/V (no repeat before the
    rotation — Hkv/H-th the ICI bytes): parity incl. dk/dv group sums."""
    mesh = make_mesh(MeshConfig(seq=4))
    B, T, H, Hkv, D = 2, 32, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    ref = dot_product_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, use_flash=True, interpret=True
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.mean(ring_attention(
            q, k, v, mesh, causal=True, use_flash=True, interpret=True
        ) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gref = jax.grad(
        lambda q, k, v: jnp.mean(
            dot_product_attention(q, k, v, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    assert gr[1].shape == k.shape  # narrow dk came home at Hkv heads
    for a, b in zip(gr, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_padding_mask(devices, causal):
    """Global key-padding vector on the flash ring: parity with the
    reference on well-defined rows, fwd and grads (same undefined-row
    carve-out as the einsum-ring mask test)."""
    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=2, T=32, H=2, D=16)
    mask = np.ones((2, 1, 1, 32), bool)
    mask[0, :, :, 24:] = False
    mask[1, :, :, :5] = False
    mask = jnp.asarray(mask)
    ref = np.asarray(dot_product_attention(q, k, v, causal=causal, mask=mask))
    out = np.asarray(jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal, mask=mask, use_flash=True,
        interpret=True,
    ))(q, k, v))
    if causal:
        out, ref = out[:, 5:], ref[:, 5:]
    np.testing.assert_allclose(out, ref, atol=2e-5)

    q_valid = np.ones((2, 32, 1, 1), np.float32)
    if causal:
        q_valid[1, :5] = 0.0
    q_valid = jnp.asarray(q_valid)
    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.mean((ring_attention(
            q, k, v, mesh, causal=causal, mask=mask, use_flash=True,
            interpret=True,
        ) * q_valid) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gref = jax.grad(
        lambda q, k, v: jnp.mean(
            (dot_product_attention(q, k, v, causal=causal, mask=mask)
             * q_valid) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gr, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_attention_grad_parity(devices):
    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=1, T=32, H=2, D=16)

    def loss_ring(q, k, v):
        return jnp.mean(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(dot_product_attention(q, k, v, causal=True) ** 2)

    gr_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr_ring, gr_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_qkv_fused_parity_and_roundtrip():
    """Fused q/k/v projection (decode-perf option): fuse_qkv_params
    converts a separate-layout tree and the fused module reproduces the
    separate module bitwise-close, incl. GQA interleave, cache decode,
    and config() round-trip."""
    from tensorlink_tpu.nn.attention import (
        MultiHeadAttention, fuse_qkv_params,
    )
    from tensorlink_tpu.nn.module import module_from_config

    for H, Hkv in ((4, 4), (4, 2), (4, 1)):
        sep = MultiHeadAttention(32, H, num_kv_heads=Hkv, causal=True,
                                 rope=True, use_bias=True)
        fus = MultiHeadAttention(32, H, num_kv_heads=Hkv, causal=True,
                                 rope=True, use_bias=True, qkv_fused=True)
        p = sep.init(KEY)
        pf = fuse_qkv_params(p, H, Hkv, sep.head_dim)
        assert pf["qkv"]["w"].shape == (32, Hkv * (H // Hkv + 2) * sep.head_dim)
        x = jax.random.normal(jax.random.key(1), (2, 16, 32))
        np.testing.assert_allclose(
            np.asarray(fus.apply(pf, x)), np.asarray(sep.apply(p, x)),
            atol=1e-5,
        )
        # cached decode step parity
        cache = sep.init_cache(2, 16, dtype=jnp.float32)
        o1, c1 = sep.apply(p, x[:, :4], cache=cache)
        o1f, c1f = fus.apply(pf, x[:, :4], cache=cache)
        np.testing.assert_allclose(np.asarray(o1f), np.asarray(o1), atol=1e-5)
        step = x[:, 4:5]
        o2, _ = sep.apply(p, step, cache=c1)
        o2f, _ = fus.apply(pf, step, cache=c1f)
        np.testing.assert_allclose(np.asarray(o2f), np.asarray(o2), atol=1e-5)

    # config round trip preserves the flag and layout
    rebuilt = module_from_config(fus.config())
    assert rebuilt.qkv_fused
    np.testing.assert_allclose(
        np.asarray(rebuilt.apply(pf, x)), np.asarray(fus.apply(pf, x)),
        atol=0,
    )
    # cross-attention refuses the fused layout loudly
    with pytest.raises(NotImplementedError, match="cross"):
        fus.apply(pf, x, kv=x)
    with pytest.raises(NotImplementedError):
        fus.project_kv(pf, x)


def test_qkv_fused_tp_spec_and_engine_decode(devices):
    """The fused projection column-shards head-aligned under TP, and an
    InferenceEngine decode on a fused GPT-2 matches the separate-layout
    engine token-for-token."""
    from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config
    from tensorlink_tpu.nn.attention import fuse_qkv_params
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig, InferenceEngine,
    )

    cfgs = GPT2Config.tiny()
    import dataclasses
    cfgf = dataclasses.replace(cfgs, qkv_fused=True)
    ms, mf = GPT2(cfgs), GPT2(cfgf)
    ps = ms.init(KEY)
    spec = mf.param_spec()
    blk0 = spec["blocks"]["0"]["attn"]
    assert blk0["qkv"]["w"] == P(None, "model")

    # convert every block's attention params to the fused layout
    import copy
    pf = copy.deepcopy(jax.tree.map(np.asarray, ps))
    for name, bp in pf["blocks"].items():
        bp["attn"] = fuse_qkv_params(
            bp["attn"], cfgs.num_heads, cfgs.num_heads, 32 // cfgs.num_heads
        )
    mesh = make_mesh(MeshConfig())
    kw = dict(max_len=32, cache_dtype=jnp.float32, param_dtype=jnp.float32)
    es = InferenceEngine(mesh, ms, ps, **kw)
    ef = InferenceEngine(mesh, mf, pf, **kw)
    ids = np.asarray(jax.random.randint(KEY, (2, 5), 0, cfgs.vocab_size))
    gen = GenerationConfig(max_new_tokens=6)
    np.testing.assert_array_equal(es.generate(ids, gen), ef.generate(ids, gen))


def test_attn_impl_pluggable():
    """flash_attention_impl drops into MultiHeadAttention unchanged."""
    from tensorlink_tpu import nn
    from tensorlink_tpu.ops.flash import flash_attention_impl

    m_ref = nn.MultiHeadAttention(32, 4, causal=True)
    m_flash = nn.MultiHeadAttention(
        32, 4, causal=True, attn_impl=flash_attention_impl
    )
    p = m_ref.init(KEY)
    x = jax.random.normal(KEY, (2, 64, 32))
    np.testing.assert_allclose(
        np.asarray(m_ref.apply(p, x)),
        np.asarray(m_flash.apply(p, x)),
        atol=1e-5,
    )
    # masked path falls back to the reference implementation
    mask = jnp.ones((2, 1, 64, 64), bool)
    np.testing.assert_allclose(
        np.asarray(m_ref.apply(p, x, mask=mask)),
        np.asarray(m_flash.apply(p, x, mask=mask)),
        atol=1e-5,
    )


def test_flash_impl_padding_mask_routes_to_kernel():
    """A [B,1,1,Tk] padding mask (what Bert.apply builds from
    attention_mask) is extracted to the kernel's kv_mask, not the
    fallback — parity against the reference masked path."""
    from tensorlink_tpu.ops.flash import _as_kv_mask, flash_attention_impl

    q, k, v = _qkv(B=2, T=128, H=2, D=32)
    pad = (jnp.arange(128)[None, :] < 77)
    mask4 = pad[:, None, None, :] & jnp.ones((2, 1, 1, 1), bool)
    kv, ok = _as_kv_mask(mask4, 2, 128)
    assert ok and kv.shape == (2, 128)
    out = flash_attention_impl(q, k, v, mask=mask4, interpret=True,
                               min_kernel_seq=0)
    ref = dot_product_attention(q, k, v, mask=mask4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_impl_batch1_mask_broadcast():
    """A broadcastable [1,1,1,Tk] mask under B>1 must produce a [B,Tk]
    kv_mask (review finding: out-of-bounds batch block index)."""
    from tensorlink_tpu.ops.flash import _as_kv_mask, flash_attention_impl

    q, k, v = _qkv(B=2, T=128, H=2, D=32)
    mask4 = (jnp.arange(128) < 77)[None, None, None, :]
    assert mask4.shape == (1, 1, 1, 128)
    kv, ok = _as_kv_mask(mask4, 2, 128)
    assert ok and kv.shape == (2, 128)
    out = flash_attention_impl(q, k, v, mask=mask4, interpret=True,
                               min_kernel_seq=0)
    ref = dot_product_attention(q, k, v, mask=mask4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_impl_gqa_repeat():
    """GQA (Hkv < H) is read in-kernel via the BlockSpec index map (no
    jnp.repeat materialization); dk/dv sum back over each group."""
    from tensorlink_tpu.ops.flash import flash_attention_impl

    B, T, H, Hkv, D = 1, 64, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_impl(
            q, k, v, causal=True, interpret=True, min_kernel_seq=0) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(
        float(loss_flash(q, k, v)), float(loss_ref(q, k, v)), rtol=1e-5
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_auto_threshold_routes_short_seq_to_reference():
    """'auto' keeps the einsum below MIN_KERNEL_SEQ_AUTO (measured faster
    on v5e at short seq); explicit 'flash' forces the kernel. Verified by
    probing which inner path runs, not just output parity."""
    from unittest import mock

    from tensorlink_tpu.nn.attention import resolve_attn_impl
    from tensorlink_tpu.ops import flash as flash_mod

    q, k, v = _qkv(B=2, T=128, H=2, D=32)
    with mock.patch.object(
        flash_mod, "flash_attention", wraps=flash_mod.flash_attention
    ) as kern:
        resolve_attn_impl("auto")(q, k, v, interpret=True)
        assert kern.call_count == 0  # short seq: reference path
        resolve_attn_impl("flash")(q, k, v, interpret=True)
        assert kern.call_count == 1  # explicit flash: kernel forced


def test_attn_impl_config_roundtrip():
    """attn_impl string survives Module.config() spec-shipping."""
    from tensorlink_tpu.nn.module import module_from_config
    from tensorlink_tpu.nn.transformer import TransformerBlock

    blk = TransformerBlock(32, 4, causal=True, attn_impl="flash")
    cfg = blk.config()
    rebuilt = module_from_config(cfg)
    assert rebuilt.attn_impl == "flash"
    assert rebuilt.children["attn"].attn_impl == "flash"
    p = blk.init(KEY)
    x = jax.random.normal(KEY, (2, 64, 32))
    np.testing.assert_allclose(
        np.asarray(blk.apply(p, x)), np.asarray(rebuilt.apply(p, x)), atol=1e-6
    )


def test_ring_attention_long_context_memory_shape(devices):
    """Sequence 8x the per-device shard runs without materializing full KV."""
    mesh = make_mesh(MeshConfig(seq=8))
    q, k, v = _qkv(B=1, T=512, H=2, D=32)
    out = jax.jit(lambda *a: ring_attention(*a, mesh, causal=True))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_parity(devices, causal):
    """Ulysses all_to_all SP: parity vs full attention (H=8 divisible by
    seq axis 4)."""
    from tensorlink_tpu.parallel.sp import ulysses_attention

    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=2, T=32, H=8, D=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention_mask_and_grads(devices):
    """Padding masks work on the ulysses path (each device holds all
    tokens after the swap — the ring path cannot express this), and
    gradients match the reference."""
    from tensorlink_tpu.parallel.sp import ulysses_attention

    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=2, T=32, H=4, D=16)
    mask = (jnp.arange(32)[None, :] < 20)[:, None, None, :]
    mask = jnp.broadcast_to(mask, (2, 1, 1, 32))
    ref = dot_product_attention(q, k, v, mask=mask)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh, mask=mask)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_u(q, k, v):
        return jnp.mean(ulysses_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(dot_product_attention(q, k, v, causal=True) ** 2)

    gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ulysses_head_divisibility(devices):
    from tensorlink_tpu.parallel.sp import ulysses_attention

    mesh = make_mesh(MeshConfig(seq=4))
    q, k, v = _qkv(B=1, T=16, H=2, D=8)  # 2 heads, 4-way seq axis
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh))(q, k, v)


@pytest.mark.parametrize("hkv", [4, 2])
def test_ulysses_gqa_narrow_and_fallback(devices, hkv):
    """GQA under ulysses: Hkv=4 divides the 4-way axis (K/V swap at their
    own narrow head count — Hkv/H-th the collective bytes); Hkv=2 does not
    and falls back to shipping repeated K/V. Both must match the
    reference."""
    from tensorlink_tpu.parallel.sp import ulysses_attention

    mesh = make_mesh(MeshConfig(seq=4))
    B, T, H, D = 2, 32, 8, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, hkv, D))
    v = jax.random.normal(ks[2], (B, T, hkv, D))
    ref = dot_product_attention(q, k, v, causal=True)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=True)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------------------------------- sliding window (SWA)


def test_sliding_window_matches_full_when_wide():
    """window >= T is exactly full causal attention."""
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(2, 8, 4, 16)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, 8, 4, 16)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, 8, 4, 16)), jnp.float32)
    full = dot_product_attention(q, k, v, causal=True)
    wide = dot_product_attention(q, k, v, causal=True, window=8)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(wide))


def test_sliding_window_equals_explicit_band_mask():
    """window=W == a hand-built band mask (i-W, i] — causal and not."""
    r = np.random.default_rng(1)
    T, W = 10, 3
    q = jnp.asarray(r.normal(size=(1, T, 2, 8)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, T, 2, 8)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, T, 2, 8)), jnp.float32)
    i = np.arange(T)[:, None]
    j = np.arange(T)[None, :]

    band = jnp.asarray(((j <= i) & (j > i - W))[None, None])
    np.testing.assert_allclose(
        np.asarray(dot_product_attention(q, k, v, causal=True, window=W)),
        np.asarray(dot_product_attention(q, k, v, mask=band)),
        atol=1e-6,
    )
    sym = jnp.asarray((np.abs(i - j) < W)[None, None])
    np.testing.assert_allclose(
        np.asarray(dot_product_attention(q, k, v, window=W)),
        np.asarray(dot_product_attention(q, k, v, mask=sym)),
        atol=1e-6,
    )


def test_sliding_window_decode_matches_prefill():
    """Cached single-token decode under a window reproduces the
    windowed full-forward logits — across the boundary where old
    tokens fall out of the window."""
    from tensorlink_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig.mistral_tiny()  # window 8
    m = Llama(cfg)
    p = m.init(jax.random.key(0))
    T = 20  # well past the window
    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (1, T))
    )
    full = m.apply(p, ids)  # [1, T, V] windowed (module carries window)

    caches = m.init_caches(1, 32, dtype=jnp.float32)
    outs = []
    for t in range(T):
        step, caches = m.apply(p, ids[:, t : t + 1], caches=caches)
        outs.append(step[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, axis=1)), np.asarray(full),
        atol=2e-4, rtol=2e-4,
    )


def test_sliding_window_blockwise_decode_parity():
    """Large cache (> DECODE_BLOCK) triggers the blockwise decode path;
    the windowed block-skip + mask must reproduce the reference windowed
    attention exactly."""
    from tensorlink_tpu.nn.attention import (
        DECODE_BLOCK,
        decode_attention_blockwise,
    )

    r = np.random.default_rng(3)
    B, H, D, L, W = 2, 4, 16, 2 * DECODE_BLOCK, 64
    live = L - 17  # live prefix not block-aligned
    q = jnp.asarray(r.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
    kpos = np.arange(L)
    start = max(0, live - W)
    mask = jnp.asarray(
        ((kpos < live) & (kpos >= start))[None, None, None, :]
    )
    mask = jnp.broadcast_to(mask, (B, 1, 1, L))

    out = decode_attention_blockwise(
        q, k, v, jnp.int32(live), mask=mask, start=jnp.int32(start)
    )
    ref = dot_product_attention(
        q, k, v, causal=True, q_offset=live - 1, window=W
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_blockwise_decode_multi_query_parity():
    """Tq > 1 (the speculative verify-K form): the K+1 candidate
    queries share one length-bounded block loop; per-query causal
    masks must reproduce the reference attention at every query."""
    from tensorlink_tpu.nn.attention import (
        DECODE_BLOCK,
        decode_attention_blockwise,
    )

    r = np.random.default_rng(4)
    B, T, H, D, L = 2, 5, 4, 16, 2 * DECODE_BLOCK
    f0 = L - 40  # per-row frontier (uniform here; mask carries truth)
    q = jnp.asarray(r.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
    kpos = np.arange(L)[None, None, None, :]
    qend = (f0 + np.arange(T) + 1)[None, None, :, None]
    mask = jnp.asarray(np.broadcast_to(kpos < qend, (B, 1, T, L)))
    out = decode_attention_blockwise(
        q, k, v, jnp.int32(f0 + T), mask=mask
    )
    ref = dot_product_attention(q, k, v, causal=True, q_offset=f0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )
    # a frontier within K slots of the region end yields a bound past
    # capacity (those scatter writes were dropped); the loop must clamp
    # instead of re-running the clamped last block, which double-counts
    # its softmax mass (review repro: 5.9e-2 output error unclamped)
    over = decode_attention_blockwise(
        q, k, v, jnp.int32(L + T), mask=mask
    )
    np.testing.assert_allclose(
        np.asarray(over), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_sliding_window_impl_support():
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    # reference/flash/auto honor the window; ring/ulysses would
    # silently drop it and are rejected
    for ok in ("reference", "flash", "auto"):
        MultiHeadAttention(32, 4, causal=True, attn_impl=ok, window=8)
    for bad in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="sliding-window"):
            MultiHeadAttention(32, 4, causal=True, attn_impl=bad, window=8)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [32, 128, 200])
def test_pallas_flash_window_matches_reference(causal, window):
    """Kernel band mask + block skipping == reference windowed attention
    (window crossing block boundaries, aligned, and larger than a
    block)."""
    q, k, v = _qkv(B=1, T=256, H=2, D=32)
    ref = dot_product_attention(q, k, v, causal=causal, window=window)
    out = flash_attention(
        q, k, v, None, causal, True, window  # interpret mode
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_pallas_flash_window_grads_match_reference():
    q, k, v = _qkv(B=1, T=256, H=2, D=32)
    W = 64

    def f_kernel(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, None, True, True, W) ** 2)

    def f_ref(q_, k_, v_):
        return jnp.sum(
            dot_product_attention(q_, k_, v_, causal=True, window=W) ** 2
        )

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


def test_pallas_flash_window_with_padding_mask():
    """Window + key-padding compose in-kernel."""
    q, k, v = _qkv(B=2, T=128, H=2, D=32)
    kv_mask = jnp.asarray(
        np.random.default_rng(5).integers(0, 2, (2, 128)), jnp.float32
    ).at[:, :4].set(1.0)
    W = 48
    ref_mask = (kv_mask[:, None, None, :] > 0)
    ref = dot_product_attention(
        q, k, v, causal=True, mask=ref_mask, window=W
    )
    out = flash_attention(q, k, v, kv_mask, True, True, W)
    # kernel zeroes fully-masked rows; reference mean(v)'s them — compare
    # only rows with a surviving key in the band
    i = np.arange(128)[:, None]; j = np.arange(128)[None, :]
    band = (j <= i) & (j > i - W)
    valid = (np.asarray(kv_mask)[:, None, :] > 0) & band[None]
    rows = valid.any(-1)  # [B, T]
    np.testing.assert_allclose(
        np.asarray(out)[rows], np.asarray(ref)[rows], atol=2e-5, rtol=2e-5
    )


def test_pallas_flash_window_restricted_grid_with_kv_mask():
    """Restricted-grid windowed kernels WITH a kv padding mask (advisor
    r4: the mask BlockSpec's kv_block(i,j) DMA indexing in restricted
    mode had no coverage — the other window tests ran either single
    k-block shapes or kv_mask=None). T=1024, W=128, 128-blocks: win_nk
    (4) < nk_full (8). Forward + all three grads vs the reference."""
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd, flash_attention_fwd_lse,
    )

    r = np.random.default_rng(11)
    B, T, H, D, W = 2, 1024, 2, 32, 128
    q, k, v = (
        jnp.asarray(r.normal(size=(B, T, H, D)), jnp.float32)
        for _ in range(3)
    )
    kv_mask = np.ones((B, T), np.float32)
    kv_mask[0, 700:] = 0.0  # padded tail inside the band range
    kv_mask[1, :50] = 0.0  # padded head
    kv_mask = jnp.asarray(kv_mask)
    mask4 = (kv_mask > 0)[:, None, None, :]

    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out, lse = flash_attention_fwd_lse(
        qt, kt, vt, kv_mask, causal=True, block_q=128, block_k=128,
        interpret=True, window=W,
    )
    ref = dot_product_attention(q, k, v, causal=True, window=W, mask=mask4)
    # rows whose entire band is padding emit zeros from the kernel and
    # uniform-average from the reference — compare defined rows only:
    # row 0's queries past 700+W-1 see only the padded tail in their
    # band; row 1's queries 0..49 see only padding (causal + head-pad)
    out_bthd = np.asarray(out.swapaxes(1, 2))
    refn = np.asarray(ref)
    d0 = 700 + W - 1  # first row-0 query whose whole band is padded
    np.testing.assert_allclose(
        out_bthd[0, :d0], refn[0, :d0], atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        out_bthd[1, 50:], refn[1, 50:], atol=2e-5, rtol=2e-5
    )

    g = jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)
    # zero the undefined rows' cotangent so both sides agree there
    gz = np.array(g)  # writable copy
    gz[0, :, d0:] = 0.0
    gz[1, :, :50] = 0.0
    g = jnp.asarray(gz)
    dq, dk, dv = flash_attention_bwd(
        qt, kt, vt, out, lse, g, kv_mask, causal=True,
        block_q=128, block_k=128, interpret=True, window=W,
    )
    def loss(q_, k_, v_):
        o = dot_product_attention(
            q_, k_, v_, causal=True, window=W, mask=mask4
        )
        return jnp.sum(o * g.swapaxes(1, 2))

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in ((dq, gq, "dq"), (dk, gk, "dk"), (dv, gv, "dv")):
        av = np.asarray(a.swapaxes(1, 2))
        bv = np.asarray(b)
        if name == "dq":
            # undefined rows produce zero dq in the kernel; reference
            # may differ there — compare defined region
            np.testing.assert_allclose(av[0, :d0], bv[0, :d0], atol=1e-4)
            np.testing.assert_allclose(av[1, 50:], bv[1, 50:], atol=1e-4)
        else:
            np.testing.assert_allclose(av, bv, atol=1e-4)


def test_pallas_flash_window_restricted_grid_parity():
    """T=2048 with a small window: the k-grid is genuinely RESTRICTED
    ((bq+W+bk)/bk+1 < Tk/bk) — skipped blocks' DMA never happens, and
    init/finalize key on grid-local indices. Forward + grads parity."""
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd, flash_attention_fwd_lse,
    )

    r = np.random.default_rng(7)
    B, T, H, D, W = 1, 2048, 2, 32, 200
    q, k, v = (
        jnp.asarray(r.normal(size=(B, T, H, D)), jnp.float32)
        for _ in range(3)
    )
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out, lse = flash_attention_fwd_lse(
        qt, kt, vt, None, causal=True, block_q=512, block_k=512,
        interpret=True, window=W,
    )
    ref = dot_product_attention(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(
        np.asarray(out.swapaxes(1, 2)), np.asarray(ref),
        atol=2e-5, rtol=2e-5,
    )

    g = jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)
    dq, dk, dv = flash_attention_bwd(
        qt, kt, vt, out, lse, g, None, causal=True,
        block_q=512, block_k=512, interpret=True, window=W,
    )
    def ref_loss(q_, k_, v_):
        o = dot_product_attention(q_, k_, v_, causal=True, window=W)
        return jnp.sum(o.swapaxes(1, 2) * g)
    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in ((dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(
            np.asarray(a.swapaxes(1, 2)), np.asarray(b),
            atol=5e-5, rtol=5e-5,
        )


def test_rolling_cache_multitoken_write_wraps():
    """Advisor r4: a multi-token write whose span crosses the ring edge
    must WRAP (modular scatter), not clamp — chunked-prefill/speculative
    callers write T>1 at index>0. Pin slot contents directly."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    attn = MultiHeadAttention(16, 2, causal=True)
    p = attn.init(KEY)
    cap = 8
    cache = attn.init_cache(1, cap, dtype=jnp.float32, rolling=True)
    cache = dict(cache, index=jnp.int32(6))  # wslot 6; T=4 crosses edge
    x = jax.random.normal(jax.random.key(5), (1, 4, 16))
    # chunked write at index>0: declare non-fresh via cache-width mask
    mask = jnp.ones((1, 1, 4, cap), bool)
    _, new_cache = attn.apply(p, x, cache=cache, mask=mask,
                              positions=jnp.arange(6, 10)[None])
    k_proj = attn.children["k"].apply(p["k"], x).reshape(1, 4, 2, 8)
    got = np.asarray(new_cache["k"])
    want_slots = [(6 + i) % cap for i in range(4)]  # 6, 7, 0, 1
    for i, s in enumerate(want_slots):
        np.testing.assert_allclose(
            got[0, s], np.asarray(k_proj)[0, i], atol=1e-6,
            err_msg=f"token {i} did not land in wrapped slot {s}",
        )


def test_fresh_keys_explicit_param():
    """fresh_keys overrides the mask-width inference (advisor r4: the
    contract was heuristic-only): True forces the prompt-width path,
    and raises loudly without a T-wide mask."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    attn = MultiHeadAttention(16, 2, causal=True)
    p = attn.init(KEY)
    x = jax.random.normal(jax.random.key(6), (1, 4, 16))
    cache = attn.init_cache(1, 16, dtype=jnp.float32)
    tri = jnp.tril(jnp.ones((4, 4), bool))[None, None]
    o_inferred, _ = attn.apply(p, x, cache=cache, mask=tri)
    o_forced, _ = attn.apply(p, x, cache=cache, mask=tri, fresh_keys=True)
    np.testing.assert_allclose(
        np.asarray(o_inferred), np.asarray(o_forced), atol=0
    )
    with pytest.raises(ValueError, match="fresh_keys"):
        attn.apply(p, x, cache=cache, mask=jnp.ones((1, 1, 4, 16), bool),
                   fresh_keys=True)
    # fresh_keys=False needs a CACHE-width mask (the non-fresh path
    # masks cache slots; a T-wide mask cannot express it) — raises
    # loudly instead of a broadcast crash deep below (review finding)
    with pytest.raises(ValueError, match="cache-width"):
        attn.apply(p, x, cache=cache, mask=tri, fresh_keys=False)
    # fresh_keys=False + cache-width mask == the default non-fresh path
    wide = jnp.ones((1, 1, 4, 16), bool)
    o_false, _ = attn.apply(p, x, cache=cache, mask=wide, fresh_keys=False)
    o_default, _ = attn.apply(p, x, cache=cache, mask=wide)
    np.testing.assert_allclose(
        np.asarray(o_false), np.asarray(o_default), atol=0
    )
    # the capacity==T aliasing case: an explicit False attends the
    # cache even though the mask is also T-wide
    cache16 = attn.init_cache(1, 4, dtype=jnp.float32)
    o_alias, _ = attn.apply(p, x, cache=cache16, mask=tri,
                            fresh_keys=False)
    assert o_alias.shape == o_inferred.shape


def test_window_supports_window_escape_hatch():
    """A user callable marked supports_window=True passes the window
    validation (advisor r4: identity allowlist refused honoring
    callables); unmarked callables still raise."""
    from tensorlink_tpu.nn.attention import (
        MultiHeadAttention, dot_product_attention,
    )

    def honoring(q, k, v, **kw):
        return dot_product_attention(q, k, v, **kw)

    honoring.supports_window = True
    m = MultiHeadAttention(16, 2, causal=True, attn_impl=honoring, window=4)
    p = m.init(KEY)
    x = jax.random.normal(jax.random.key(7), (1, 8, 16))
    ref = MultiHeadAttention(16, 2, causal=True, attn_impl="reference",
                             window=4)
    np.testing.assert_allclose(
        np.asarray(m.apply(p, x)), np.asarray(ref.apply(p, x)), atol=1e-6
    )

    def silent(q, k, v, **kw):
        return dot_product_attention(q, k, v)

    with pytest.raises(ValueError, match="supports_window"):
        MultiHeadAttention(16, 2, causal=True, attn_impl=silent, window=4)


# ------------------------- per-row cache indices (continuous batching)
def test_vector_cache_index_matches_scalar_decode():
    """[B]-shaped cache index (parallel/serving.py slot form): a decode
    step where every row happens to share the same index must match the
    scalar-index path bitwise, and a row parked AT capacity must write
    nothing (mode="drop")."""
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    m = MultiHeadAttention(
        32, 4, num_kv_heads=2, causal=True, rope=True,
        attn_impl="reference",
    )
    p = m.init(KEY)
    B, L = 3, 16
    cache = m.init_cache(B, L, jnp.float32)
    r = np.random.default_rng(0)
    x0 = jnp.asarray(r.standard_normal((B, 5, 32)), jnp.float32)
    mask5 = jnp.broadcast_to(
        jnp.tril(jnp.ones((5, 5), bool))[None, None], (B, 1, 5, 5)
    )
    pos5 = jnp.broadcast_to(jnp.arange(5)[None], (B, 5))
    _, cache = m.apply(p, x0, cache=cache, mask=mask5, positions=pos5)

    x1 = jnp.asarray(r.standard_normal((B, 1, 32)), jnp.float32)
    valid = jnp.broadcast_to(
        (jnp.arange(L) < 6)[None, None, None, :], (B, 1, 1, L)
    )
    pos = jnp.full((B, 1), 5)
    o_scalar, c_s = m.apply(p, x1, cache=cache, positions=pos, mask=valid)
    cache_v = dict(cache)
    cache_v["index"] = jnp.full((B,), 5, jnp.int32)
    o_vec, c_v = m.apply(p, x1, cache=cache_v, positions=pos, mask=valid)
    np.testing.assert_array_equal(np.asarray(o_scalar), np.asarray(o_vec))
    np.testing.assert_array_equal(np.asarray(c_s["k"]), np.asarray(c_v["k"]))
    np.testing.assert_array_equal(
        np.asarray(c_v["index"]), np.full((B,), 6)
    )

    # heterogeneous indices: each row writes ITS slot; a row at capacity
    # drops its write instead of clobbering slot L-1
    cache_d = dict(cache)
    cache_d["index"] = jnp.asarray([5, L, 3], jnp.int32)
    _, c_d = m.apply(p, x1, cache=cache_d, positions=pos, mask=valid)
    np.testing.assert_array_equal(
        np.asarray(c_d["k"][1]), np.asarray(cache["k"][1])
    )
    assert not np.array_equal(
        np.asarray(c_d["k"][2, 3]), np.asarray(cache["k"][2, 3])
    )


def test_vector_cache_index_contract_errors():
    from tensorlink_tpu.nn.attention import MultiHeadAttention

    m = MultiHeadAttention(
        32, 4, causal=True, rope=True, attn_impl="reference"
    )
    p = m.init(KEY)
    cache = m.init_cache(2, 8, jnp.float32)
    cache = dict(cache)
    cache["index"] = jnp.zeros((2,), jnp.int32)
    # T > 1 on the per-row path is the speculative verify-K form (ISSUE
    # 7): token t of row r writes slot index[r] + t and the frontier
    # advances by T — no longer a contract error
    x2 = jnp.zeros((2, 2, 32), jnp.float32)
    out2, c2up = m.apply(
        p, x2, cache=cache, positions=jnp.zeros((2, 2), jnp.int32)
    )
    assert out2.shape == (2, 2, 32)
    np.testing.assert_array_equal(np.asarray(c2up["index"]), [2, 2])
    x1 = jnp.zeros((2, 1, 32), jnp.float32)
    # rope consumes positions; per-row indices cannot reconstruct them
    with pytest.raises(ValueError, match="positions"):
        m.apply(p, x1, cache=cache)
    with pytest.raises(ValueError, match="cache-width"):
        m.apply(
            p, x1, cache=cache, positions=jnp.zeros((2, 1), jnp.int32),
            mask=jnp.ones((2, 1, 1, 3), bool),
        )
    # a rope-less module (learned positions live at the embedding) may
    # omit positions on the per-row path — nothing consumes them
    m2 = MultiHeadAttention(32, 4, causal=True, attn_impl="reference")
    p2 = m2.init(KEY)
    c2 = dict(m2.init_cache(2, 8, jnp.float32))
    c2["index"] = jnp.zeros((2,), jnp.int32)
    out, _ = m2.apply(p2, x1, cache=c2)
    assert out.shape == (2, 1, 32)


# ------------------------------------------- fused decode glue (Pallas)
@pytest.mark.parametrize("kind", ["layer", "rms"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_glue_kernel_matches_fallback(kind, dtype):
    """The fused residual+norm kernel (interpret mode) == the jnp
    fallback == the unfused layers.py math."""
    from tensorlink_tpu.nn.layers import LayerNorm, RMSNorm
    from tensorlink_tpu.ops.pallas.decode_glue import fused_residual_norm

    r = np.random.default_rng(0)
    D = 256
    x = jnp.asarray(r.standard_normal((2, 1, D)), dtype)
    res = jnp.asarray(r.standard_normal((2, 1, D)), dtype)
    scale = jnp.asarray(r.standard_normal(D), jnp.float32)
    bias = (
        jnp.asarray(r.standard_normal(D), jnp.float32)
        if kind == "layer" else None
    )
    eps = 1e-5
    rk, yk = fused_residual_norm(
        x, res, scale, bias, eps=eps, kind=kind, interpret=True
    )
    rf, yf = fused_residual_norm(x, res, scale, bias, eps=eps, kind=kind)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(rk, np.float32), np.asarray(rf, np.float32),
        rtol=tol, atol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(yk, np.float32), np.asarray(yf, np.float32),
        rtol=tol, atol=tol,
    )
    # against the module the block would otherwise run
    norm = LayerNorm(D, eps=eps) if kind == "layer" else RMSNorm(D, eps=eps)
    params = {"scale": scale} if bias is None else {
        "scale": scale, "bias": bias,
    }
    y_mod = norm.apply(params, (x + res))
    np.testing.assert_allclose(
        np.asarray(yk, np.float32), np.asarray(y_mod, np.float32),
        rtol=max(tol, 2e-6), atol=max(tol, 2e-6),
    )


def test_decode_glue_rejects_bad_shapes():
    from tensorlink_tpu.ops.pallas.decode_glue import fused_residual_norm

    x = jnp.zeros((2, 1, 8))
    with pytest.raises(ValueError, match="mismatch"):
        fused_residual_norm(x, jnp.zeros((2, 2, 8)), jnp.ones(8))
    with pytest.raises(ValueError, match="kind"):
        fused_residual_norm(x, x, jnp.ones(8), kind="batch")


# --------------------------------------- flash block-size overrides
def test_flash_block_override_registry():
    from tensorlink_tpu.ops.flash import (
        clear_flash_block_overrides,
        flash_block_for,
        set_flash_block_override,
    )

    clear_flash_block_overrides()
    try:
        assert flash_block_for(512) == 512  # heuristic default
        assert flash_block_for(8192) == 1024  # capped (v5e, PR 28)
        set_flash_block_override(512, 256)
        set_flash_block_override(512, 128, batch=8)
        assert flash_block_for(512, 8) == 128  # exact (seq, batch) wins
        assert flash_block_for(512, 2) == 256  # any-batch next
        assert flash_block_for(1024, 8) == 1024  # untouched shapes keep
        with pytest.raises(ValueError, match="divide"):
            set_flash_block_override(512, 96)
    finally:
        clear_flash_block_overrides()
    assert flash_block_for(512) == 512


def test_flash_override_kernel_parity():
    """An overridden block size changes the grid, not the math."""
    from tensorlink_tpu.ops.flash import (
        clear_flash_block_overrides,
        flash_attention,
        set_flash_block_override,
    )

    q, k, v = _qkv(T=256)
    ref = np.asarray(flash_attention(q, k, v, causal=True, interpret=True))
    set_flash_block_override(256, 64)
    try:
        out = np.asarray(
            flash_attention(q, k, v, causal=True, interpret=True)
        )
    finally:
        clear_flash_block_overrides()
    np.testing.assert_allclose(out, ref, atol=2e-5)


# ------------------------- the causal diagonal in sub-tiles (ISSUE 28)
def _kernels_vs_reference(q, k, v, g, block, *, causal=True, kv_mask=None,
                          window=None, fwd_tol, bwd_tol):
    """The three kernels (interpret mode) against the plain path and
    its ``jax.vjp``: o, lse, dq, dk, dv. q, g: [B, T, H, D]; k, v may
    have fewer heads (GQA). The reference computes in f32 from the same
    (possibly bf16) values; rows that padding empties read 0 there, as
    in the kernels (``_fallback_attn``)."""
    from tensorlink_tpu.ops.flash import _fallback_attn
    from tensorlink_tpu.ops.pallas.flash_attention import (
        LSE_MASKED, flash_attention_bwd, flash_attention_fwd_lse,
    )

    qt, kt, vt, gt = (x.swapaxes(1, 2) for x in (q, k, v, g))
    kw = dict(causal=causal, block_q=block, block_k=block, interpret=True,
              window=window)
    o, lse = flash_attention_fwd_lse(qt, kt, vt, kv_mask, **kw)
    dq, dk, dv = flash_attention_bwd(qt, kt, vt, o, lse, gt, kv_mask, **kw)
    assert o.dtype == q.dtype and lse.dtype == jnp.float32
    assert (dq.dtype, dk.dtype, dv.dtype) == (q.dtype, k.dtype, v.dtype)

    q32, k32, v32, g32 = (x.astype(jnp.float32) for x in (q, k, v, g))
    ref, vjp = jax.vjp(
        lambda q_, k_, v_: _fallback_attn(
            q_, k_, v_, kv_mask, causal, window),
        q32, k32, v32,
    )
    rq, rk, rv = vjp(g32)
    # the reference's lse, from its own scores
    T, rep = q.shape[1], q.shape[2] // k.shape[2]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q32, jnp.repeat(k32, rep, axis=2)
    ) * q.shape[-1] ** -0.5
    from tensorlink_tpu.nn.attention import band_keep

    keep = jnp.ones((1, 1, T, T), bool)
    if causal or window is not None:
        keep = band_keep(
            jnp.arange(T)[:, None], jnp.arange(T)[None, :], causal, window
        )[None, None]
    if kv_mask is not None:
        keep = jnp.logical_and(keep, (kv_mask > 0)[:, None, None, :])
    rlse = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    rlse = jnp.where(jnp.any(keep, axis=-1), rlse, LSE_MASKED)

    def close(a, b, tol):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=tol, rtol=tol)

    close(o.swapaxes(1, 2), ref, fwd_tol)
    close(lse, rlse, fwd_tol)
    for a, b in ((dq, rq), (dk, rk), (dv, rv)):
        close(a.swapaxes(1, 2), b, bwd_tol)


def _rand(shape, dtype, seed):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize(
    "T,block",
    [(512, 512), (1024, 512), (1024, 256), (256, 128), (1024, 1024)],
)
def test_flash_causal_subtiles_match_reference(T, block, D, dtype):
    """Pure causal calls take the diagonal-aware body: blocks below the
    diagonal unmasked, the block on it in sub-tiles (one strip, several,
    and with blocks below it), forward and backward."""
    q, k, v, g = (_rand((1, T, 2, D), dtype, 28 + i) for i in range(4))
    f32 = dtype == jnp.float32
    _kernels_vs_reference(
        q, k, v, g, block,
        fwd_tol=2e-5 if f32 else 2e-2, bwd_tol=1e-4 if f32 else 2e-2,
    )


@pytest.mark.parametrize("case", [
    "kv_mask_empties_rows", "kv_mask_tail", "window", "gqa", "non_causal",
    "non_causal_kv_mask",
])
def test_flash_bodies_beside_the_diagonal_path(case):
    """What must keep its behaviour: a padding mask keeps its ``where``
    on every tile, also where it empties whole rows (keys 0..299 of one
    sequence masked: its rows 0..299 see nothing and read 0); a window
    and ``causal=False`` keep the whole-block body; GQA reads the
    unrepeated heads through either."""
    T, H, D, block = 512, 4, 64, 256
    causal, kv_mask, window, hkv = True, None, None, H
    if case == "kv_mask_empties_rows":
        kv_mask = jnp.stack([jnp.arange(T) >= 300, jnp.arange(T) < 400])
    elif case == "kv_mask_tail":
        kv_mask = jnp.arange(T)[None, :] < jnp.array([[130], [512]])
    elif case == "window":
        window = 200
    elif case == "gqa":
        hkv = 2
    elif case == "non_causal":
        causal = False
    elif case == "non_causal_kv_mask":
        causal = False
        kv_mask = jnp.arange(T)[None, :] < jnp.array([[130], [512]])
    q, g = (_rand((2, T, H, D), jnp.float32, 5 + i) for i in range(2))
    k, v = (_rand((2, T, hkv, D), jnp.float32, 7 + i) for i in range(2))
    _kernels_vs_reference(
        q, k, v, g, block, causal=causal, kv_mask=kv_mask, window=window,
        fwd_tol=2e-5, bwd_tol=1e-4,
    )


@pytest.mark.parametrize("Tq,Tk", [(256, 512), (512, 256)])
def test_flash_causal_unequal_lengths(Tq, Tk):
    """Causal with more k-blocks than q-blocks (and the reverse): the
    index maps that aim skipped steps at the diagonal block stay inside
    both arrays, and the k-blocks no query reaches get zero dk, dv."""
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd, flash_attention_fwd_lse,
    )

    q, g = (_rand((1, Tq, 2, 64), jnp.float32, 40 + i) for i in range(2))
    k, v = (_rand((1, Tk, 2, 64), jnp.float32, 42 + i) for i in range(2))
    qt, kt, vt, gt = (x.swapaxes(1, 2) for x in (q, k, v, g))
    kw = dict(causal=True, block_q=128, block_k=128, interpret=True)
    o, lse = flash_attention_fwd_lse(qt, kt, vt, None, **kw)
    grads = flash_attention_bwd(qt, kt, vt, o, lse, gt, None, **kw)
    ref, vjp = jax.vjp(
        lambda q_, k_, v_: dot_product_attention(q_, k_, v_, causal=True),
        q, k, v,
    )
    np.testing.assert_allclose(
        np.asarray(o.swapaxes(1, 2)), np.asarray(ref), atol=2e-5)
    for a, b in zip(grads, vjp(g)):
        np.testing.assert_allclose(
            np.asarray(a.swapaxes(1, 2)), np.asarray(b), atol=1e-4)


def _branches(jaxpr):
    """Every ``cond`` branch of a kernel body as the multiset of what it
    holds: ``(primitive names, [dot_general (contracted size, result
    elements)])``, nested jaxprs included."""
    def walk(jp, names, dots):
        for e in jp.eqns:
            names.append(e.primitive.name)
            if e.primitive.name == "dot_general":
                (lc, _), _ = e.params["dimension_numbers"]
                dots.append((
                    e.invars[0].aval.shape[lc[0]],
                    int(np.prod(e.outvars[0].aval.shape)),
                ))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, names, dots)

    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "cond":
            for br in e.params["branches"]:
                names, dots = [], []
                walk(br.jaxpr, names, dots)
                if dots:
                    out.append((names, dots))
    return out


# bound on the diagonal block's computed share, by (block, forward?):
# 128-wide sub-tiles in the forward (10 of 16, 36 of 64), 256-wide in
# the backward kernels (3 of 4 at a 512-block, 10 of 16 at 1024)
@pytest.mark.parametrize("block,fwd_share,bwd_share", [
    (512, 5 / 8, 3 / 4), (1024, 5 / 8, 5 / 8),
])
def test_flash_causal_kernels_skip_tiles_above_the_diagonal(
        block, fwd_share, bwd_share):
    """The mechanism, from the kernels' traced bodies: on the diagonal
    block the score-shaped matmuls (contracted over the head dim: S in
    all three, dP in the two backward kernels) produce at most that
    share of block x block elements; the body of a block below the
    diagonal builds no position mask at all."""
    from tensorlink_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd, flash_attention_fwd_lse,
    )

    B, H, T, D = 1, 1, 2 * block, 64
    t4 = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((B, H, T), jnp.float32)
    kw = dict(causal=True, block_q=block, block_k=block)
    text = [
        jax.make_jaxpr(lambda q, k, v: flash_attention_fwd_lse(
            q, k, v, None, **kw))(t4, t4, t4),
        jax.make_jaxpr(lambda q, k, v, o, l, g: flash_attention_bwd(
            q, k, v, o, l, g, None, **kw))(t4, t4, t4, t4, lse, t4),
    ]
    kernels = {}

    def find(jaxpr):  # the entry points are jitted: look inside
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                kernels[e.params["name"]] = e.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(e.params):
                find(sub)

    for jp in text:
        find(jp.jaxpr)
    assert sorted(kernels) == [
        "tl_flash_bwd_dkv", "tl_flash_bwd_dq", "tl_flash_fwd"]
    for name, body in kernels.items():
        below = [b for b in _branches(body) if "iota" not in b[0]]
        diagonal = [b for b in _branches(body) if "iota" in b[0]]
        assert len(below) == 1 and len(diagonal) == 1, name
        kinds = 1 if name == "tl_flash_fwd" else 2  # S; S and dP
        share = fwd_share if name == "tl_flash_fwd" else bwd_share

        def scores(branch):
            return sum(n for contracted, n in branch[1] if contracted == D)

        assert scores(below[0]) == kinds * block * block, name
        assert scores(diagonal[0]) <= share * kinds * block * block, name
        for prim in ("iota", "select_n", "lt", "le", "ge", "gt"):
            assert prim not in below[0][0], (name, prim)


# ------------------------------------------- paged-decode kernel (ISSUE 20)


def _paged_case(
    *, B=2, T=1, H=4, Hkv=4, D=16, bs=4, MB=4, lives=None, quant=False,
    seed=0,
):
    """Random paged-pool case: distinct physical pages per live block,
    sentinel (NB) table entries past the write frontier, garbage in
    unmapped pool slots — the layout the serving engine produces."""
    from tensorlink_tpu.ops.quant import quantize_kv_int8

    r = np.random.default_rng(seed)
    lives = list(lives) if lives is not None else [bs * MB] * B
    NB = B * MB + 3  # spare pages so garbage slots exist
    q = jnp.asarray(r.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(r.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    v = jnp.asarray(r.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    perm = r.permutation(NB)
    bt = np.full((B, MB), NB, np.int32)  # sentinel everywhere first
    nxt = 0
    for b, live in enumerate(lives):
        for j in range(-(-live // bs)):
            bt[b, j] = perm[nxt]
            nxt += 1
    lengths = jnp.asarray(lives, jnp.int32)
    scales = {}
    if quant:
        k, ks = quantize_kv_int8(k)
        v, vs = quantize_kv_int8(v)
        scales = {"k_scale": ks, "v_scale": vs}
    return q, k, v, jnp.asarray(bt), lengths, scales


def _paged_pair(case, **kw):
    from tensorlink_tpu.ops.pallas.paged_decode import (
        paged_decode_attention,
        paged_decode_reference,
    )

    q, k, v, bt, lengths, scales = case
    ref_kw = {k_: v_ for k_, v_ in kw.items() if k_ != "pages_per_step"}
    ref = paged_decode_reference(q, k, v, bt, lengths, **scales, **ref_kw)
    out = paged_decode_attention(
        q, k, v, bt, lengths, **scales, interpret=True, **kw
    )
    return np.asarray(ref), np.asarray(out)


@pytest.mark.parametrize("live", [1, 3, 4, 5, 8, 16])
def test_paged_kernel_parity_block_boundaries(live):
    """Kernel == jnp reference at every live-length alignment: mid-
    block, exact block boundary, single token, full view (bs=4, 4
    pages). Rows past the frontier hold sentinel table entries."""
    ref, out = _paged_pair(_paged_case(lives=[live, max(1, live - 1)]))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_kernel_parity_gqa_and_garbage_pool():
    """GQA (H=4 over Hkv=2) reads the unrepeated pools via the
    h//group index map; NaN garbage in unmapped pool slots must never
    leak — the clamped index maps only ever DMA LIVE pages, so the
    kernel on a NaN-poisoned pool must equal the reference on the
    clean one (the jnp reference itself would 0*NaN-poison, which is
    fine: production pools hold finite stale data, never NaN)."""
    from tensorlink_tpu.ops.pallas.paged_decode import (
        paged_decode_attention,
        paged_decode_reference,
    )

    q, k, v, bt, lengths, _ = _paged_case(H=4, Hkv=2, lives=[5, 9], seed=3)
    ref = np.asarray(paged_decode_reference(q, k, v, bt, lengths))
    mapped = np.unique(np.asarray(bt)[np.asarray(bt) < k.shape[0]])
    poison_k, poison_v = np.array(k), np.array(v)
    for slot in range(k.shape[0]):
        if slot not in mapped:
            poison_k[slot] = np.nan
            poison_v[slot] = np.nan
    out = np.asarray(paged_decode_attention(
        q, jnp.asarray(poison_k), jnp.asarray(poison_v), bt, lengths,
        interpret=True,
    ))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [1, 3, 8])
def test_paged_kernel_parity_window(window):
    """Sliding-window masking in logical coordinates, including a
    window small enough that whole leading pages fall out of the band
    (their index maps clamp to the band start — no re-DMA, no math)."""
    ref, out = _paged_pair(
        _paged_case(lives=[16, 7], seed=1), window=window
    )
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T", [2, 3, 5])
def test_paged_kernel_parity_verify_widths(T):
    """T > 1 (speculative verify-K chunks): query t sits at logical
    position lengths - T + t, so each chunk row sees a different
    causal frontier inside the same page."""
    ref, out = _paged_pair(
        _paged_case(T=T, lives=[16, max(T, 6)], seed=2)
    )
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_kernel_parity_int8_pools():
    """int8 pools + per-(slot, head) scales: the kernel dequantizes in
    VMEM, the reference in the gathered view — identical math, so the
    parity bound stays the float one."""
    ref, out = _paged_pair(
        _paged_case(lives=[11, 4], quant=True, seed=4)
    )
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    ref, out = _paged_pair(
        _paged_case(T=3, H=4, Hkv=2, lives=[16, 9], quant=True, seed=5),
        window=5,
    )
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_kernel_parity_explicit_mask_and_masked_rows():
    """A view-width boolean mask composes with the causal/positional
    keep; a row whose mask kills EVERY position must return zeros via
    the l==0 guard, not NaN."""
    case = _paged_case(lives=[9, 6], seed=6)
    q, k, v, bt, lengths, scales = case
    B, T = q.shape[0], q.shape[1]
    Lv = bt.shape[1] * k.shape[1]
    r = np.random.default_rng(7)
    mask = r.integers(0, 2, (B, 1, T, Lv)).astype(bool)
    mask[1] = False  # fully masked row
    ref, out = _paged_pair(case, mask=jnp.asarray(mask))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("pages", [1, 2, 4])
def test_paged_kernel_pages_per_step_changes_grid_not_math(pages):
    """G (pages per superstep — the autotuned knob) re-shapes the
    scratch stripe and grid only."""
    case = _paged_case(lives=[13, 16], seed=8)
    ref, out = _paged_pair(case, pages_per_step=pages)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_kernel_kill_switch_and_gate(monkeypatch):
    """TL_PAGED_KERNEL=0 gates the kernel off everywhere (the serving
    path then runs the pre-kernel XLA gather bit-for-bit); "interpret"
    force-engages the emulated kernel off-TPU; per-head masks and
    ragged GQA stay on the XLA path."""
    from tensorlink_tpu.ops.pallas.paged_decode import paged_decode_ok

    case = _paged_case(lives=[4])
    q, k = case[0], case[1]
    monkeypatch.setenv("TL_PAGED_KERNEL", "0")
    assert not paged_decode_ok(q, k, interpret=True)
    monkeypatch.setenv("TL_PAGED_KERNEL", "interpret")
    assert paged_decode_ok(q, k)
    # D=16 is not lane-aligned: real-TPU mode refuses, interpret allows
    assert not paged_decode_ok(q, k, interpret=False) or (
        jax.devices()[0].platform == "tpu" and q.shape[-1] % 128 == 0
    )
    bad_mask = jnp.ones((2, 4, 1, 16), bool)  # per-head mask
    assert not paged_decode_ok(q, k, mask=bad_mask, interpret=True)


def test_paged_override_roundtrip_and_validation():
    """set/clear/snapshot mirror the flash-block override discipline;
    resolution prefers exact (max_blocks, block_size) over agnostic,
    then the LANES//bs heuristic."""
    from tensorlink_tpu.ops.pallas.paged_decode import (
        clear_paged_block_overrides,
        paged_block_overrides,
        paged_pages_for,
        set_paged_block_override,
    )

    clear_paged_block_overrides()
    try:
        assert paged_pages_for(16, 8) == 16  # heuristic: LANES//8 capped
        assert paged_pages_for(4, 64) == 2
        set_paged_block_override(16, 4)
        set_paged_block_override(16, 2, block_size=8)
        assert paged_block_overrides() == [(16, None, 4), (16, 8, 2)]
        # idempotent re-set: same value, no retrace churn
        set_paged_block_override(16, 4)
        assert paged_block_overrides() == [(16, None, 4), (16, 8, 2)]
        assert paged_pages_for(16, 8) == 2   # exact wins
        assert paged_pages_for(16, 16) == 4  # agnostic next
        with pytest.raises(ValueError, match="outside"):
            set_paged_block_override(8, 9)
        with pytest.raises(ValueError, match="outside"):
            set_paged_block_override(8, 0)
    finally:
        clear_paged_block_overrides()
    assert paged_pages_for(16, 8) == 16
