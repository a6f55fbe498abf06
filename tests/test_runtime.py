"""Core runtime: config, mesh, metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.config import FrameworkConfig, MeshConfig, TrainConfig
from tensorlink_tpu.runtime.mesh import MeshRuntime, make_mesh, local_device_info
from tensorlink_tpu.runtime.metrics import (
    Metrics,
    pipeline_bubble_fraction,
)


def test_config_roundtrip():
    cfg = FrameworkConfig(
        mesh=MeshConfig(data=2, pipe=4), train=TrainConfig(batch_size=16)
    )
    assert FrameworkConfig.from_json(cfg.to_json()) == cfg


def test_micro_batch_size_validation():
    with pytest.raises(ValueError):
        TrainConfig(batch_size=10, micro_batches=3).micro_batch_size
    assert TrainConfig(batch_size=12, micro_batches=3).micro_batch_size == 4


def test_mesh_shapes(devices):
    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2))
    assert mesh.shape == {"data": 2, "pipe": 2, "model": 2, "seq": 1}
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=16))


def test_mesh_runtime_shard_batch(devices):
    rt = MeshRuntime.create(MeshConfig(data=8))
    x = jnp.arange(32.0).reshape(16, 2)
    xs = rt.shard_batch(x)
    assert xs.sharding.spec == jax.sharding.PartitionSpec(("data",))
    np.testing.assert_allclose(np.asarray(xs), np.asarray(x))
    assert rt.describe()["num_devices"] == 8


def test_local_device_info():
    info = local_device_info()
    assert len(info) >= 1 and "platform" in info[0]


def test_bubble_fraction():
    assert pipeline_bubble_fraction(1, 8) == 0.0
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pipeline_bubble_fraction(4, 32) < 0.1


def test_metrics_snapshot():
    m = Metrics()
    for i in range(5):
        m.observe("loss", 1.0 / (i + 1))
    m.incr("steps", 5)
    snap = m.snapshot()
    assert snap["counters"]["steps"] == 5
    assert snap["loss"]["n"] == 5


def test_parse_op_breakdown_synthetic():
    """Category aggregation, lane filtering, and wrapper exclusion over
    a hand-built Chrome-trace event list (the format jax.profiler
    writes; live shape verified on the r4 v5e capture)."""
    from tensorlink_tpu.runtime.profiling import parse_op_breakdown

    meta = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "Steps"}},
    ]
    op = lambda tid, cat, dur, name="op": {
        "ph": "X", "pid": 1, "tid": tid, "ts": 0, "dur": dur,
        "name": name, "args": {"hlo_category": cat},
    }
    events = meta + [
        op(1, "convolution fusion", 800),
        op(1, "convolution fusion", 40),
        op(1, "loop fusion", 100),
        op(1, "while", 940),          # wrapper: excluded from total
        op(2, "loop fusion", 999),    # wrong lane: ignored
        {"ph": "X", "pid": 1, "tid": 1, "dur": 5, "name": "x",
         "args": {}},                 # no category: ignored
    ]
    out = parse_op_breakdown(events)
    assert out["total_s"] == pytest.approx(940e-6)
    conv = out["categories"]["convolution fusion"]
    assert conv["ops"] == 2
    assert conv["fraction"] == pytest.approx(840 / 940)
    assert out["control_flow_wrapper_s"]["while"] == pytest.approx(940e-6)
    assert "Steps-lane" not in out["categories"]


def test_op_breakdown_graceful_on_cpu():
    """CPU captures carry no hlo_category metadata; the helper must
    return an empty-but-well-formed result, not crash."""
    import jax.numpy as jnp

    from tensorlink_tpu.runtime.profiling import op_breakdown

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    float(f(x))  # warm
    out = op_breakdown(f, x)
    assert set(out) >= {"total_s", "categories", "control_flow_wrapper_s"}
    assert out["total_s"] == 0.0 and out["categories"] == {}
