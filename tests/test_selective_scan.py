"""``ops/selective_scan.py`` against the token-by-token recurrence
(``benchmark/reference/phi4flash.py::selective_scan``, the definition):
forward and every gradient, under the published initialisation's decay
(A_n = -n, a step of 1e-3..1e-1: a state lives for hundreds of tokens,
so most of an output crosses chunk boundaries) and under the benchmark's
seeded weights' (A about -1, a step about 0.7: it dies in ten), at a
length that is no whole number of chunks. The Pallas kernels
(``ops/pallas/selective_scan.py``) run interpreted, against the XLA path
and the recurrence, over three time blocks and three channel blocks."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.phi4flash import selective_scan as recurrence
from tensorlink_tpu.ops import selective_scan as ops
from tensorlink_tpu.ops.pallas import selective_scan as kernel
from tensorlink_tpu.ops.selective_scan import KEPT, selective_scan
from tensorlink_tpu.runtime.flight import default_recorder

T, E, N = 150, 24, 16  # nine chunks of 16 and 6 tokens
# the kernels' shape: two whole time blocks and a part of a third, and
# three blocks of channels in each kernel
KT, KE = 2 * kernel.TIME + 44, 2688
NAMES = ("y", "du", "ddelta", "dA", "dB", "dC", "dD")


def operands(regime: str, B: int, dtype, T: int = T, E: int = E):
    ks = jax.random.split(jax.random.key(3), 7)
    if regime == "published":
        A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (E, N))
        delta = jnp.exp(jax.random.uniform(
            ks[0], (B, T, E), minval=np.log(1e-3), maxval=np.log(1e-1)))
    else:  # benchmark/weights.py: A_log and the step's bias 0.02 * normal
        A = -jnp.exp(0.02 * jax.random.normal(ks[0], (E, N)))
        delta = jax.nn.softplus(0.3 * jax.random.normal(ks[1], (B, T, E)))
    return (
        jax.random.normal(ks[2], (B, T, E)).astype(dtype), delta, A,
        jax.random.normal(ks[3], (B, T, N)),
        jax.random.normal(ks[4], (B, T, N)),
        1.0 + 0.1 * jax.random.normal(ks[5], (E,)),
    ), jax.random.normal(ks[6], (B, T, E))


def _run(fn, args, ct):
    """fn's output and its six gradients under the loss sum(y * ct)."""

    def loss(*a):
        y = fn(*a).astype(jnp.float32)
        return jnp.sum(y * ct), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(6), has_aux=True))(*args)
    return (y, *grads)


def _plain(u, *rest):
    return recurrence(u.astype(jnp.float32), *rest)


@functools.lru_cache(maxsize=None)
def both(regime, B, dtype):
    """(chunked, recurrence): each the output and the six gradients."""
    args, ct = operands(regime, B, dtype)
    return _run(selective_scan, args, ct), _run(_plain, args, ct)


@functools.lru_cache(maxsize=None)
def by_kernel(regime, B, dtype):
    """{kernels, xla, recurrence}: each the output and the six gradients
    at the kernels' shape; the kernels interpreted."""
    args, ct = operands(regime, B, dtype, KT, KE)
    return {
        "kernels": _run(
            functools.partial(selective_scan, interpret=True), args, ct),
        "xla": _run(selective_scan, args, ct),
        "recurrence": _run(_plain, args, ct),
    }


def _gap(a, b) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    assert a.shape == b.shape and float(jnp.linalg.norm(b)) > 0
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("regime", ["published", "seeded"])
def test_chunked_is_the_recurrence(regime, B, dtype, name):
    got, want = both(regime, B, jnp.dtype(dtype))
    gap = _gap(got[NAMES.index(name)], want[NAMES.index(name)])
    # bf16: y leaves in u's dtype, rounded once (2^-9 an element)
    assert gap < (4e-3 if dtype == "bfloat16" else 2e-5), gap


@pytest.mark.parametrize("against", ["xla", "recurrence"])
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("regime", ["published", "seeded"])
def test_kernels_are_the_xla_path_and_the_recurrence(
    regime, B, dtype, name, against
):
    """The kernel path, interpreted, over 300 tokens (two time blocks of
    128 and 44 tokens padded to a third: the state and its cotangent are
    handed across blocks both ways) and 2,688 channels (three blocks in
    each kernel, so dB and dC are sums of partial sums)."""
    runs = by_kernel(regime, B, jnp.dtype(dtype))
    i = NAMES.index(name)
    gap = _gap(runs["kernels"][i], runs[against][i])
    assert gap < (4e-3 if dtype == "bfloat16" else 2e-5), gap


def test_the_published_decay_crosses_chunks_and_the_seeded_does_not():
    """What share of the output the state carried into a chunk gives:
    the same call with every chunk a sequence of its own, against the
    recurrence. Under the seeded weights only a chunk's first few
    tokens read it (a state halves every token; an eighth of the output
    at chunks of 16): the benchmark's check sees little of a hand-over
    and nothing of a long memory, so this file has to."""
    shares = {}
    for regime in ("published", "seeded"):
        (u, delta, A, Bm, Cm, D), _ = operands(regime, 1, jnp.float32)
        D = jnp.zeros_like(D)  # the scan's own output, no skip
        C = ops.CHUNK
        cut = [
            x[:, :4 * C].reshape(4, C, x.shape[-1]) for x in (u, delta, Bm, Cm)
        ]
        alone = selective_scan(cut[0], cut[1], A, cut[2], cut[3], D)
        want = recurrence(u, delta, A, Bm, Cm, D)[:, :4 * C]
        shares[regime] = float(
            jnp.linalg.norm(alone.reshape(1, 4 * C, E)[:, C:] - want[:, C:])
            / jnp.linalg.norm(want[:, C:]))
    assert shares["published"] > 0.4 and shares["seeded"] < 0.15, shares


def _all_avals(jaxpr):
    for e in jaxpr.eqns:
        yield from (v.aval for v in e.outvars)
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _all_avals(sub)


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_no_array_of_every_token_and_state_exists(what):
    """[B, T, d_inner, N] (5.4 GB at the benchmark's shape) is in no
    equation, forward or backward: the largest array with a state axis
    is a chunk's, [CHUNK, B, N, d_inner], inside the backward's chunk."""
    B = 2
    args, ct = operands("published", B, jnp.float32)
    fn = selective_scan
    if what == "gradient":
        fn = jax.grad(
            lambda *a: jnp.sum(selective_scan(*a) * ct), argnums=range(6))
    sizes = [
        int(np.prod(a.shape)) for a in _all_avals(jax.make_jaxpr(fn)(*args).jaxpr)
        if hasattr(a, "shape")
    ]
    padded = -(-T // ops.CHUNK) * ops.CHUNK
    assert max(sizes) < B * T * E * N
    assert max(sizes) <= max(ops.CHUNK * B * N * E, (padded // ops.CHUNK) * B * N * E)


def test_kept_names_are_on_the_output_and_the_chunk_states():
    from conftest import count_equations

    args, _ = operands("seeded", 1, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(selective_scan(*a)), argnums=0))(*args).jaxpr
    assert count_equations(jaxpr, "name") == len(KEPT) == 2


def test_a_short_sequence_is_one_chunk():
    args, _ = operands("published", 1, jnp.float32)
    short = [x[:, :5] if x.ndim == 3 else x for x in args]
    np.testing.assert_allclose(
        selective_scan(*short), recurrence(*short), rtol=1e-5, atol=1e-6)


def _abstract(channels=256, states=16, dtype=jnp.bfloat16, delta=jnp.float32):
    """u, delta, Bm, Cm as the gate sees them."""
    return (
        jax.ShapeDtypeStruct((2, 256, channels), dtype),
        jax.ShapeDtypeStruct((2, 256, channels), delta),
        jax.ShapeDtypeStruct((2, 256, states), dtype),
        jax.ShapeDtypeStruct((2, 256, states), dtype),
    )


@pytest.mark.parametrize("where,operands_,reason", [
    ("a mesh XLA partitions", _abstract(), "partitioned by XLA"),
    ("channels off the lane tiles", _abstract(channels=200),
     "200 channels: no whole number of 128-lane tiles"),
    ("another number of states", _abstract(states=4),
     "4 states a channel, not 16"),
    ("a bfloat16 step", _abstract(delta=jnp.bfloat16), "delta not float32"),
    ("an integer u", _abstract(dtype=jnp.int32), "u, B, C not bfloat16"),
])
def test_the_gate_closes_with_a_reason(monkeypatch, devices, where, operands_, reason):
    """On a TPU (as the gate is told here) each condition the kernels
    do not take closes the gate, and the flight recorder says why."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    seen = len(default_recorder().events(kind="kernel.gate_closed"))
    if where == "a mesh XLA partitions":
        with jax.set_mesh(jax.make_mesh((2,), ("data",), devices=devices[:2])):
            assert not ops._kernel_path(*operands_, interpret=False)
    else:
        assert not ops._kernel_path(*operands_, interpret=False)
    new = default_recorder().events(kind="kernel.gate_closed")[seen:]
    assert len(new) == 1 and new[0]["attrs"]["kernel"] == kernel.NAME
    assert reason in new[0]["attrs"]["reason"], new


@pytest.mark.parametrize("on_a_tpu", [False, True])
def test_the_gate_opens_silently(monkeypatch, on_a_tpu):
    """Off the TPU the gate is shut and says nothing (the XLA path is
    the only one there); on one, at the Phi cell's widths, it opens."""
    monkeypatch.setattr(ops, "on_tpu", lambda: on_a_tpu)
    seen = len(default_recorder().events(kind="kernel.gate_closed"))
    assert ops._kernel_path(*_abstract(5120), interpret=False) is on_a_tpu
    assert len(default_recorder().events(kind="kernel.gate_closed")) == seen
