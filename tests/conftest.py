"""Test harness: N logical devices in one process.

The reference's only multi-node story was N threading.Thread role instances
over localhost sockets (tests/ml/test_job.py:38-46). The TPU-native analogue
is an 8-device virtual CPU mesh so DP/PP/TP/SP paths run hermetically.
The environment is set before jax is imported; nothing else is needed.
"""

import collections
import contextlib
import functools
import os

# tests run on the CPU whatever the machine holds; set before jax is
# imported, this is all it takes (child processes inherit it)
os.environ["JAX_PLATFORMS"] = "cpu"
# the WorkerNode capability microbench defaults ON in production; the
# suite constructs dozens of ephemeral workers and must not pay a
# per-worker bench — tests that exercise it opt back in with
# NodeConfig(capability_bench=True)
os.environ.setdefault("TL_CAPABILITY_BENCH", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "xla_disable_hlo_passes" not in _flags:
    # XLA:CPU aborts the PROCESS on a bf16 pipeline all-reduce with this
    # pass on (runtime/mesh.py virtual_cpu_xla_flags has the story; the
    # flags are spelt out here because jax is not imported yet)
    _flags += " --xla_disable_hlo_passes=all-reduce-promotion"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# engines and workers turn the persistent compilation cache on at
# construction (runtime/compile_cache.py), by default inside the
# checkout; in-process tests stay hermetic — nothing they compile is
# written there or read back. Tests of the cache itself run a child.
jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)


class ProgramCounts:
    """How often each jitted program was traced and compiled, by the
    function's name (``tl_decode``, ``tl_spec_chunk``, ...).

    ``traces`` counts runs of a program's Python body: :meth:`jit`
    stands in for ``jax.jit`` and bumps the counter from inside the
    traced function. ``compiles`` counts XLA compilations as
    ``jax.monitoring`` reports them (:meth:`on_event`). A jitted
    call's ``_cache_size()`` is neither: it counts dispatch signatures,
    and two placements of one argument are two of those at no trace and
    no compile."""

    def __init__(self, jit):
        self.traces = collections.Counter()
        self.compiles = collections.Counter()
        self._jit = jit

    def jit(self, fn, **kw):
        @functools.wraps(fn)
        def traced(*a, **k):
            self.traces[fn.__name__] += 1
            return fn(*a, **k)

        return self._jit(traced, **kw)

    def on_event(self, event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[fun_name.removeprefix("jit(").removesuffix(")")] += 1

    def clear(self):
        self.traces.clear()
        self.compiles.clear()


@contextlib.contextmanager
def counting_programs():
    """Count traces and compiles of every program jitted through
    ``jax.jit`` inside the block: see :class:`ProgramCounts`."""
    counts = ProgramCounts(jax.jit)
    jax.monitoring.register_event_duration_secs_listener(counts.on_event)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "jit", counts.jit)
        try:
            yield counts
        finally:
            jax.monitoring.unregister_event_duration_listener(counts.on_event)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "asyncio: async test executed via asyncio.run"
    )


def pytest_sessionfinish(session, exitstatus):
    """CI post-mortem: on a failing tier-1 run, dump the process flight
    recorder + versions as a postmortem bundle into $TL_CI_DIAG_DIR so
    the workflow can upload it as an artifact (the same bundle
    `node.postmortem()` / the crash handler writes)."""
    d = os.environ.get("TL_CI_DIAG_DIR")
    if not d or exitstatus == 0:
        return
    try:
        from tensorlink_tpu.runtime.flight import (
            default_recorder,
            write_postmortem,
        )

        os.makedirs(d, exist_ok=True)
        write_postmortem(
            os.path.join(d, "postmortem.json"),
            f"pytest exit {exitstatus}",
            recorder=default_recorder(),
        )
    except Exception as e:  # noqa: BLE001 — diagnostics must not mask the run
        print(f"ci-diag postmortem failed: {e}")  # noqa: T201


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests with asyncio.run (no pytest-asyncio in env)."""
    import asyncio
    import inspect

    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            n: pyfuncitem.funcargs[n]
            for n in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


# Shared toy-problem helpers (used by test_train.py and test_parallel.py).


def count_equations(jaxpr, primitive: str) -> int:
    """Equations of that primitive in a jaxpr, nested jaxprs included
    (a scan's body once, however many steps it takes)."""
    return sum(
        (e.primitive.name == primitive) + sum(
            count_equations(sub, primitive)
            for sub in jax.core.jaxprs_in_params(e.params))
        for e in jaxpr.eqns
    )


def toy_batch(n=64, d=16, classes=4, seed=0):
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    x = r.normal(size=(n, d)).astype(np.float32)
    w = r.normal(size=(d, classes))
    y = np.argmax(x @ w, axis=-1)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def mlp_loss(module, params, batch, rng):
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    return softmax_cross_entropy(module.apply(params, batch["x"]), batch["y"])
