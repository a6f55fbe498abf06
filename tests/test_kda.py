"""Kimi Delta Attention: the chunked recurrence (ops/kda.py) against its
token-by-token definition, forward and every gradient, its forward
kernel (ops/pallas/kda.py, interpreted) against both, and the module
(nn/kda.py) against the benchmark's plain reference."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from conftest import count_equations as _count
from tensorlink_tpu.nn.kda import KimiDeltaAttention, causal_conv
from tensorlink_tpu.ops import kda as ops_kda
from tensorlink_tpu.ops.kda import kda_chunked

kda_recurrent = ref.delta_rule  # the definition, token by token

NAMES = ("q", "k", "v", "g", "beta")


def _inputs(seed, B=2, T=160, H=2, dk=16, dv=8, decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _loss(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


# decay: exp(g) a step near 1 (nothing forgotten: the triangular solve
# carries everything), as the seeded weights give it, and near 0 (a chunk
# decays by e^-3000: any exp(-G) would overflow)
@pytest.mark.parametrize("decay", [1e-4, 1.0, 8.0, 60.0])
def test_chunked_is_the_recurrence(decay):
    args = _inputs(0, decay=decay)
    want, got = kda_recurrent(*args), kda_chunked(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6 * float(jnp.abs(want).max()) + 1e-7)


@pytest.mark.parametrize("decay", [1e-4, 1.0, 60.0])
@pytest.mark.parametrize("wrt", range(5), ids=NAMES)
def test_chunked_gradients_are_the_recurrences(decay, wrt):
    args = _inputs(1, decay=decay)
    want = jax.grad(_loss(kda_recurrent), argnums=wrt)(*args)
    got = jax.grad(_loss(kda_chunked), argnums=wrt)(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, want, atol=5e-5 * float(jnp.abs(want).max()) + 1e-9)


@pytest.mark.parametrize("T,chunk,sub", [
    (8, 64, 16),     # shorter than a chunk, and than a sub-block
    (48, 64, 16),    # one short chunk of three sub-blocks
    (64, 64, 16),    # one chunk
    (256, 64, 16),   # four chunks: the state is handed on three times
    (96, 32, 8), (64, 16, 16),
    (100, 64, 16),   # padded to two chunks
    (40, 64, 16),    # shorter than a chunk: padded to three sub-blocks
])
def test_chunk_shapes(T, chunk, sub):
    args = _inputs(2, T=T)
    np.testing.assert_allclose(
        kda_chunked(*args, chunk=chunk, sub=sub), kda_recurrent(*args),
        atol=1e-6)


@pytest.mark.parametrize("B", [1, 4])
def test_rows_go_through_one_at_a_time(B):
    args = _inputs(3, B=B, T=128)
    want = jax.grad(_loss(kda_recurrent), argnums=(0, 3))(*args)
    got = jax.grad(_loss(kda_chunked), argnums=(0, 3))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()))


def test_what_does_not_divide_is_refused():
    with pytest.raises(ValueError, match="not blocks of 12"):
        kda_chunked(*_inputs(4, T=64), chunk=32, sub=12)


def test_bf16_operands_stay_near():
    """q, k, v in bf16, as a bf16 step hands them over: the matmuls
    take bf16 operands, the state and the sums stay float32."""
    q, k, v, g, beta = _inputs(5, dk=32, dv=32)
    want = kda_recurrent(q, k, v, g, beta)
    got = kda_chunked(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 0.01, err


# ------------------------------------------------- the forward kernel
# heads 128 wide, as the kernel takes them; one jitted function, so the
# shapes the tests share are traced and compiled once
_by_kernel = jax.jit(functools.partial(kda_chunked, interpret=True))


def _wide(seed, **kw):
    return _inputs(seed, **{"T": 128, "dk": 128, "dv": 128, **kw})


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("decay", [1e-4, 1.0, 8.0, 60.0])
def test_kernel_is_the_recurrence(decay):
    """The same decays, the last of which overflows any exp(-G): against
    the definition as the XLA path is held to it, and against the XLA
    path to 1e-5 of the norm (float32: the same arithmetic, in another
    order)."""
    args = _wide(0, decay=decay)
    want, got = kda_recurrent(*args), _by_kernel(*args)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6 * float(jnp.abs(want).max()) + 1e-7)
    assert _rel(got, kda_chunked(*args)) < 1e-5


@pytest.mark.parametrize("B", [1, 4])
def test_kernel_on_a_padded_length(B):
    """100 tokens are padded to two chunks; a state is handed on once a
    row, and rows do not meet."""
    args = _wide(6, B=B, T=100)
    got = _by_kernel(*args)
    assert got.shape == (B, 100, 2, 128)
    assert _rel(got, kda_recurrent(*args)) < 2e-6
    assert _rel(got, kda_chunked(*args)) < 1e-5


def test_kernel_takes_bf16_operands_as_the_xla_path_does():
    q, k, v, g, beta = _wide(5)
    half = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    got, xla = _by_kernel(*half), kda_chunked(*half)
    assert got.dtype == jnp.float32
    # the same roundings in the same places: far nearer each other than
    # either is to the float32 recurrence
    assert _rel(got, xla) < 1e-3
    assert _rel(got, kda_recurrent(q, k, v, g, beta)) < 0.01


@pytest.fixture(scope="module")
def kernel_and_xla_gradients():
    args = _wide(7, T=100)
    wrt = tuple(range(5))
    return (
        jax.jit(jax.grad(_loss(_by_kernel), wrt))(*args),
        jax.jit(jax.grad(_loss(kda_chunked), wrt))(*args),
    )


@pytest.mark.parametrize("wrt", range(5), ids=NAMES)
def test_gradients_through_the_kernels_rule_are_the_xla_paths(
        kernel_and_xla_gradients, wrt):
    """The custom rule's backward is the XLA program's own, at a primal
    that stands 1e-6 off: equal to rounding."""
    got, want = (g[wrt] for g in kernel_and_xla_gradients)
    assert got.dtype == want.dtype and bool(jnp.isfinite(got).all())
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("path,solves,kernels", [
    ("kernel", 3, 1), ("xla", 4, 0),
])
def test_the_kernels_rule_runs_the_xla_forward_once(path, solves, kernels):
    """Passes of the scan, read off the gradient's jaxpr by their solve:
    the XLA path holds the forward, the row's recompute and two in the
    backward; behind the kernel the rule itself is the checkpoint, so
    the XLA forward runs once (to linearise), not twice."""
    fn = _by_kernel if path == "kernel" else kda_chunked
    grad = jax.make_jaxpr(jax.grad(_loss(fn), range(5)))(*_wide(7, T=100))
    assert _count(grad.jaxpr, "triangular_solve") == solves
    assert _count(grad.jaxpr, "pallas_call") == kernels


@pytest.mark.parametrize("on_chip,solves,kernels", [
    (True, 12, 4), (False, 16, 0),
])
def test_block_remat_recomputes_no_kernel(monkeypatch, on_chip, solves, kernels):
    """Kimi-Linear's four KDA layers at heads of 128 under the block's
    remat, the gate steered as a TPU would open it (a jaxpr is traced,
    never lowered): the kept o leaves the kernel's call out of the
    recompute (its residuals are its own inputs), so a layer holds one
    kernel call and three solves; with the gate closed, four solves."""
    from tensorlink_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    monkeypatch.setattr(ops_kda, "on_tpu", lambda: on_chip)
    model = KimiLinear(dataclasses.replace(
        KimiLinearConfig.tiny(), kda_head_dim=128, remat=True))
    params = jax.eval_shape(model.init, jax.random.key(0))
    ids = jnp.zeros((2, 129), jnp.int32)
    grad = jax.make_jaxpr(jax.grad(lambda p: softmax_cross_entropy(
        model.apply(p, ids[:, :-1]), ids[:, 1:])))(params)
    assert _count(grad.jaxpr, "triangular_solve") == solves
    assert _count(grad.jaxpr, "pallas_call") == kernels


@pytest.mark.parametrize("what,reason", [
    (dict(dk=64, dv=64), "64 / 64 wide"),
    (dict(T=48), "48 tokens"),
    (dict(g_dtype=jnp.bfloat16), "g, beta not float32"),
])
def test_a_closed_gate_says_why_and_takes_the_xla_path(what, reason):
    """Asked for the kernel (``interpret``) at a shape it does not take,
    ``kda_chunked`` is the XLA program and records the reason. Off the
    TPU and not asked, the gate is silent."""
    from tensorlink_tpu.runtime.flight import default_recorder

    what = dict(what)
    g_dtype = what.pop("g_dtype", jnp.float32)
    q, k, v, g, beta = _wide(8, **what)
    g = g.astype(g_dtype)
    def events():
        return default_recorder().events(kind="kernel.gate_closed")

    seen = len(events())
    want = kda_chunked(q, k, v, g, beta)
    assert len(events()) == seen
    got = kda_chunked(q, k, v, g, beta, interpret=True)
    new = events()[seen:]
    assert [e["attrs"]["kernel"] for e in new] == ["tl_kda_fwd"]
    assert reason in new[0]["attrs"]["reason"], new
    np.testing.assert_array_equal(got, want)


def test_causal_conv_is_a_left_padded_convolution():
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    want = jax.lax.conv_general_dilated(
        jnp.pad(x, ((0, 0), (3, 0), (0, 0))), w[:, None, :], (1,), "VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6,
    )
    np.testing.assert_allclose(causal_conv(x, w), want, atol=1e-5)
    # nothing of the future: the first output sees x[0] under the last tap
    np.testing.assert_allclose(causal_conv(x, w)[:, 0], x[:, 0] * w[3], atol=1e-6)


@pytest.fixture(scope="module")
def module_and_reference():
    from benchmark import weights

    mod = KimiDeltaAttention(32, num_heads=2, head_dim=16)
    shapes = jax.eval_shape(mod.init, jax.random.key(0))
    params = weights.make_tree(11, shapes)
    cfg = {
        "linear_attn_config": {"num_heads": 2, "head_dim": 16},
        "rms_norm_eps": 1e-5,
    }
    x = jax.random.normal(jax.random.key(2), (2, 160, 32))
    return mod, params, cfg, x


def test_module_is_the_reference(module_and_reference):
    mod, params, cfg, x = module_and_reference
    np.testing.assert_allclose(
        mod.apply(params, x), ref._kda(x, params, cfg, None), atol=2e-5)


def test_module_gradients_are_the_references(module_and_reference):
    mod, params, cfg, x = module_and_reference
    ct = jax.random.normal(jax.random.key(3), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(mod.apply(p, x) * ct), (0, 1))(params, x)
    want = jax.grad(
        lambda p, x: jnp.sum(ref._kda(x, p, cfg, None) * ct), (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))


def test_module_init_and_refusal(module_and_reference):
    mod, params, _, x = module_and_reference
    own = mod.init(jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    # the decay's step starts between 1e-3 and 1e-1, its rate between 1 and 16
    dt = jax.nn.softplus(own["dt_bias"]["b"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
    assert 0.0 <= float(own["A_log"]["b"].min()) <= float(own["A_log"]["b"].max()) <= np.log(16)
    assert bool(jnp.isfinite(mod.apply(own, x)).all())
    with pytest.raises(NotImplementedError, match="recurrent state"):
        mod.apply(params, x, cache={})
