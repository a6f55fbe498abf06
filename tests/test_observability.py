"""HTTP status endpoint, metrics, tracing, profiling, CLI (survey §§5.1,
5.5, 5.6: the reference had a single Flask route, no tracer, no CLI)."""

import asyncio
import json
import subprocess
import sys

import pytest

from tensorlink_tpu.config import NodeConfig


async def _http_raw(host: str, port: int, request: bytes) -> tuple[int, bytes, bytes]:
    """-> (status, header bytes, body bytes)"""
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(request)
    await writer.drain()
    raw = await reader.read(1 << 20)
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), head, body


async def _http_get(host: str, port: int, path: str) -> tuple[int, dict]:
    status, _, body = await _http_raw(
        host, port, f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
    )
    return status, json.loads(body) if body else {}


@pytest.mark.asyncio
async def test_status_endpoint_routes():
    from tensorlink_tpu.roles.worker import WorkerNode

    node = WorkerNode(
        NodeConfig(role="worker", host="127.0.0.1", port=0, http_status_port=0)
    )
    await node.start()
    try:
        port = node._http.bound_port
        st, body = await _http_get("127.0.0.1", port, "/healthz")
        # backward-compatible 200 shape: "ok": true preserved, health
        # detail keys additive (truthful health is tested in test_flight)
        assert st == 200 and body["ok"] is True
        st, body = await _http_get("127.0.0.1", port, "/node")
        assert st == 200
        assert body["node_id"] == node.node_id and body["role"] == "worker"
        node.metrics.observe("loss", 1.5)
        node.metrics.incr("steps")
        st, body = await _http_get("127.0.0.1", port, "/metrics")
        assert st == 200
        assert body["loss"]["last"] == 1.5 and body["counters"]["steps"] == 1
        st, _ = await _http_get("127.0.0.1", port, "/nope")
        assert st == 404
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_validator_jobs_route():
    from tensorlink_tpu.roles.registry import InMemoryRegistry
    from tensorlink_tpu.roles.validator import ValidatorNode

    node = ValidatorNode(
        NodeConfig(role="validator", host="127.0.0.1", port=0, http_status_port=0),
        registry=InMemoryRegistry(),
    )
    await node.start()
    try:
        st, body = await _http_get("127.0.0.1", node._http.bound_port, "/jobs")
        assert st == 200 and body == {}
    finally:
        await node.stop()


def test_cli_info_runs():
    import os

    # the child is CPU-only and its environment says so
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tensorlink_tpu", "info"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr
    info = json.loads(out.stdout)
    assert info["device_count"] >= 1


def test_profiling_helpers():
    import jax.numpy as jnp

    from tensorlink_tpu.runtime.profiling import trace
    from tensorlink_tpu.runtime.tracing import region
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with trace(d):
            with region("train.step"):
                (jnp.ones((16, 16)) @ jnp.ones((16, 16))).block_until_ready()
        import os

        assert any(os.scandir(d)), "profiler trace wrote nothing"


# ------------------------------------------------------------ tracing


def test_tracer_nesting_decorator_and_bounds():
    from tensorlink_tpu.runtime.tracing import Tracer, current_span

    t = Tracer("svc", max_spans=4)
    with t.span("outer", {"k": 1}) as outer:
        assert current_span() is outer
        with t.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    assert current_span() is None

    names = [s.name for s in t.spans()]
    assert names == ["inner", "outer"]  # recorded at exit

    # error status is stamped and the exception propagates
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    assert t.spans()[-1].status == "error"

    # bounded buffer: oldest evicted
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 4


def test_tracer_async_decorator_and_remote_parent():
    from tensorlink_tpu.runtime.tracing import Tracer

    t = Tracer("svc")

    async def work():
        with t.span("work"):
            return 7

    assert asyncio.run(work()) == 7
    assert t.spans()[-1].name == "work"

    with t.span("child", remote={"trace_id": "abc", "span_id": "def"}) as s:
        assert s.trace_id == "abc" and s.parent_id == "def"


def test_chrome_trace_span_buffer_overflow_eviction_order():
    """max_spans overflow: the buffer keeps the NEWEST spans in record
    order, and to_chrome_trace exports exactly those — an overflowing
    tracer must never export evicted spans or scramble ordering."""
    from tensorlink_tpu.runtime.tracing import Tracer

    t = Tracer("svc", max_spans=4)
    for i in range(10):
        with t.span(f"s{i}", {"i": i}):
            pass
    assert len(t) == 4
    assert [s.name for s in t.spans()] == ["s6", "s7", "s8", "s9"]
    xs = [e for e in t.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["s6", "s7", "s8", "s9"]
    assert [e["args"]["i"] for e in xs] == [6, 7, 8, 9]
    # timestamps of the kept window are monotone (record order == time)
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    # a nested survivor whose PARENT was evicted still exports cleanly
    t2 = Tracer("svc", max_spans=1)
    with t2.span("outer"):
        with t2.span("inner"):
            pass
    # inner recorded first (exit order), then outer evicted it... no:
    # outer exits LAST, so it evicts inner — the newest span wins
    assert [s.name for s in t2.spans()] == ["outer"]
    assert len(t2.to_chrome_trace()["traceEvents"]) == 3  # 2 meta + 1 X


def test_histogram_quantile_bounds_empty_and_single():
    """Satellite: q=0 / q=1 at the degenerate ends — empty histograms
    answer nan (never a fake 0.0), a single observation answers within
    its bucket for EVERY q, and overflow observations clamp."""
    import math

    from tensorlink_tpu.runtime.metrics import Histogram

    h = Histogram(buckets=(0.1, 1.0, 10.0))
    assert math.isnan(h.quantile(0.0))
    assert math.isnan(h.quantile(1.0))
    snap = h.snapshot()
    assert snap["n"] == 0 and math.isnan(snap["p50"])

    h.observe(0.5)  # single observation, bucket (0.1, 1.0]
    assert h.quantile(0.0) == pytest.approx(0.1)  # bucket lower bound
    assert h.quantile(1.0) == pytest.approx(1.0)  # bucket upper bound
    assert 0.1 <= h.quantile(0.5) <= 1.0
    assert h.snapshot()["sum"] == pytest.approx(0.5)

    # single observation BELOW the first bound interpolates from 0
    h2 = Histogram(buckets=(0.1, 1.0))
    h2.observe(0.05)
    assert h2.quantile(0.0) == pytest.approx(0.0)
    assert h2.quantile(1.0) == pytest.approx(0.1)

    # single observation ABOVE the last bound clamps to it (q=0 and q=1)
    h3 = Histogram(buckets=(0.1, 1.0))
    h3.observe(50.0)
    assert h3.quantile(0.0) == pytest.approx(1.0)
    assert h3.quantile(1.0) == pytest.approx(1.0)


def test_chrome_trace_export_shape():
    from tensorlink_tpu.runtime.tracing import Tracer

    t = Tracer("svc")
    with t.span("a", {"x": 1}):
        pass
    ct = t.to_chrome_trace()
    assert set(ct) == {"traceEvents"}
    xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 1
    e = xs[0]
    assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
    assert e["args"]["x"] == 1 and e["args"]["trace_id"]
    # metadata rows name the process (service) and each trace
    metas = [ev for ev in ct["traceEvents"] if ev.get("ph") == "M"]
    assert any(ev["name"] == "process_name" for ev in metas)
    assert any(ev["name"] == "thread_name" for ev in metas)


@pytest.mark.asyncio
async def test_two_node_trace_propagation_and_spans_route():
    """Acceptance: a user-style requester's span becomes the parent of
    the worker-side dispatch span (one cross-node trace), GET /spans
    serves valid Chrome-trace JSON for it, and messages sent with NO
    active span carry no _trace envelope field."""
    from tensorlink_tpu.p2p.node import Node
    from tensorlink_tpu.roles.worker import WorkerNode

    worker = WorkerNode(
        NodeConfig(role="worker", host="127.0.0.1", port=0, http_status_port=0)
    )
    user = Node(NodeConfig(role="user", host="127.0.0.1", port=0))
    await worker.start()
    await user.start()
    try:
        peer = await user.connect("127.0.0.1", worker.port)
        with user.tracer.span("user.request") as root:
            resp = await user.request(peer, {"type": "STATS_REQUEST"})
        assert resp["type"] == "STATS"
        rpc = [s for s in worker.tracer.spans() if s.name == "rpc.STATS_REQUEST"]
        assert len(rpc) == 1
        assert rpc[0].trace_id == root.trace_id  # one trace
        assert rpc[0].parent_id == root.span_id  # stitched across nodes

        # /spans serves it as Chrome-trace JSON
        st, _, body = await _http_raw(
            "127.0.0.1", worker._http.bound_port,
            b"GET /spans HTTP/1.1\r\n\r\n",
        )
        assert st == 200
        events = json.loads(body)["traceEvents"]
        mine = [
            e for e in events
            if e.get("ph") == "X"
            and e.get("args", {}).get("trace_id") == root.trace_id
        ]
        assert mine and all(
            isinstance(e["ts"], (int, float)) and "dur" in e for e in mine
        )

        # no active span -> no envelope overhead
        seen = {}
        orig = worker._handlers["PING"]

        async def spy(node, p, msg):
            seen.update(msg)
            return await orig(node, p, msg)

        worker.on("PING", spy)
        await user.request(peer, {"type": "PING"})
        assert "_trace" not in seen
    finally:
        await user.stop()
        await worker.stop()


# ------------------------------------------------------------ metrics


def test_metrics_snapshot_min_max_additive():
    from tensorlink_tpu.runtime.metrics import Metrics

    m = Metrics()
    for v in (3.0, 1.0, 2.0):
        m.observe("loss", v)
    snap = m.snapshot()
    # r0 shape intact ...
    assert snap["loss"]["last"] == 2.0 and snap["loss"]["n"] == 3
    # ... plus the additive spread keys
    assert snap["loss"]["min"] == 1.0 and snap["loss"]["max"] == 3.0
    assert "histograms" not in snap  # absent until one is recorded


def test_histogram_quantiles_and_snapshot():
    import math

    from tensorlink_tpu.runtime.metrics import Histogram

    h = Histogram(buckets=(0.1, 1.0, 10.0))
    assert math.isnan(h.quantile(0.5))
    for v in [0.05] * 50 + [0.5] * 40 + [5.0] * 9 + [100.0]:
        h.observe(v)
    snap = h.snapshot()
    assert snap["n"] == 100
    assert snap["p50"] <= 0.1  # half the mass is in the first bucket
    assert 0.1 < snap["p90"] <= 1.0
    assert 1.0 < snap["p99"] <= 10.0
    # overflow observations clamp to the last finite bound
    assert h.quantile(1.0) == 10.0


def _parse_prom(text: str) -> dict:
    """Tiny Prometheus text-format parser: name -> {type, help, samples}."""
    metrics: dict = {}
    current = None
    pending_help: tuple[str, str] | None = None
    for line in text.strip().splitlines():
        if line.startswith("# HELP"):
            _, _, name, doc = line.split(None, 3)
            pending_help = (name, doc)
        elif line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert name not in metrics, f"duplicate TYPE for {name}"
            assert pending_help is not None and pending_help[0] == name, (
                f"TYPE for {name} not preceded by its HELP"
            )
            current = metrics.setdefault(
                name, {"type": kind, "help": pending_help[1], "samples": {}}
            )
            pending_help = None
        else:
            assert current is not None, f"sample before TYPE: {line}"
            key, val = line.rsplit(" ", 1)
            current["samples"][key] = float(val)
    return metrics


def test_prometheus_exposition():
    from tensorlink_tpu.runtime.metrics import Metrics

    m = Metrics()
    m.incr("msgs_in", 7)
    m.incr("msg:PING", 2)  # colon legal in prom names
    m.observe("loss", 1.25)
    for v in (0.002, 0.03, 0.4, 20.0):
        m.observe_hist("rpc_seconds", v)
    parsed = _parse_prom(m.to_prometheus())
    assert parsed["tensorlink_msgs_in_total"]["type"] == "counter"
    assert parsed["tensorlink_msgs_in_total"]["samples"][
        "tensorlink_msgs_in_total"
    ] == 7
    assert parsed["tensorlink_loss"]["type"] == "gauge"
    h = parsed["tensorlink_rpc_seconds"]
    assert h["type"] == "histogram"
    samples = h["samples"]
    assert samples["tensorlink_rpc_seconds_count"] == 4
    assert samples["tensorlink_rpc_seconds_sum"] == pytest.approx(20.432)
    assert samples['tensorlink_rpc_seconds_bucket{le="+Inf"}'] == 4
    # buckets are cumulative (monotone non-decreasing)
    bucket_counts = [
        v for k, v in samples.items() if "_bucket" in k and "+Inf" not in k
    ]
    assert bucket_counts == sorted(bucket_counts)


@pytest.mark.asyncio
async def test_metrics_prom_route_and_cache_control():
    from tensorlink_tpu.roles.worker import WorkerNode

    node = WorkerNode(
        NodeConfig(role="worker", host="127.0.0.1", port=0, http_status_port=0)
    )
    await node.start()
    try:
        node.metrics.incr("steps")
        node.metrics.observe_hist("step_seconds", 0.1)
        port = node._http.bound_port
        st, head, body = await _http_raw(
            "127.0.0.1", port, b"GET /metrics?format=prom HTTP/1.1\r\n\r\n"
        )
        assert st == 200
        assert b"text/plain" in head and b"Cache-Control: no-store" in head
        parsed = _parse_prom(body.decode())
        assert parsed["tensorlink_steps_total"]["samples"][
            "tensorlink_steps_total"
        ] == 1
        assert "tensorlink_step_seconds" in parsed
        # plain GET /metrics still serves the JSON snapshot
        st, body2 = await _http_get("127.0.0.1", port, "/metrics")
        assert st == 200 and body2["counters"]["steps"] == 1
    finally:
        await node.stop()


# ------------------------------------------------------------ http server


@pytest.mark.asyncio
async def test_http_head_405_and_timeout():
    from tensorlink_tpu.runtime.http_status import StatusServer

    class FakeNode:
        def status(self):
            return {"ok": 1}

    srv = StatusServer(FakeNode(), "127.0.0.1", 0, timeout_s=0.3)
    await srv.start()
    try:
        port = srv.bound_port
        # HEAD: headers only, correct Content-Length, no body
        st, head, body = await _http_raw(
            "127.0.0.1", port, b"HEAD /healthz HTTP/1.1\r\n\r\n"
        )
        assert st == 200 and body == b""
        assert b"Content-Length:" in head and b"Cache-Control: no-store" in head
        # non-GET/HEAD -> 405
        st, _, _ = await _http_raw(
            "127.0.0.1", port, b"POST /healthz HTTP/1.1\r\n\r\n"
        )
        assert st == 405
        # header-trickle client: the overall deadline closes the
        # connection with no response instead of pinning the task
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /healthz HTTP/1.1\r\n")  # never finishes headers
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(1 << 16), timeout=5.0)
        assert raw == b""
        writer.close()
    finally:
        await srv.stop()


# ------------------------------------------------------------ profiling


def test_op_breakdown_keeps_caller_log_dir(tmp_path):
    """End-to-end CPU capture with an explicit log_dir: the empty-
    categories contract holds (CPU traces carry no hlo_category) AND the
    capture directory is kept + reported for later Perfetto inspection."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.runtime.profiling import op_breakdown

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((32, 32))
    float(f(x))  # warm: profile execution, not compilation
    out = op_breakdown(f, x, log_dir=str(tmp_path))
    assert out["total_s"] == 0.0 and out["categories"] == {}
    assert out["trace_dir"] == str(tmp_path)
    import os

    assert any(os.scandir(tmp_path)), "capture not kept in caller's dir"


# ------------------------------------------------------------ straggler


def test_straggler_report_skew_and_heartbeat():
    import time as _time

    from tensorlink_tpu.runtime.metrics import Metrics
    from tensorlink_tpu.runtime.tracing import straggler_report

    m = Metrics()
    for _ in range(4):
        m.observe("stage0_fwd_s", 0.10)
        m.observe("stage1_fwd_s", 0.30)  # straggler
        m.observe("stage0_bwd_s", 0.10)
        m.observe("stage1_bwd_s", 0.30)
    m.observe("loss", 1.0)  # non-stage series must be ignored

    class P:
        last_seen = _time.time() - 5.0

    rep = straggler_report(m, {"peer-a": P()})
    assert rep["slowest_stage"] == 1
    # totals 0.2 vs 0.6 -> median 0.4 -> skew 1.5
    assert rep["skew"] == pytest.approx(1.5, rel=0.01)
    assert rep["stages"]["1"]["fwd_mean_s"] == pytest.approx(0.30)
    assert rep["heartbeat_age_s"]["peer-a"] == pytest.approx(5.0, abs=0.5)
    # empty metrics -> structurally valid, no skew keys
    empty = straggler_report(Metrics())
    assert empty["stages"] == {} and "skew" not in empty


# ------------------------------------------------------------ logging


def test_json_formatter_extras_and_trace_ids():
    import logging

    from tensorlink_tpu.runtime.tracing import Tracer
    from tensorlink_tpu.utils.logging import JsonFormatter

    fmt = JsonFormatter()
    logger = logging.getLogger("tensorlink_tpu.test_fmt")
    rec = logger.makeRecord(
        "tensorlink_tpu.test_fmt", logging.INFO, __file__, 1,
        "hello %s", ("world",), None,
        extra={"job_id": "j1", "weird": object()},
    )
    out = json.loads(fmt.format(rec))
    assert out["msg"] == "hello world"
    assert out["job_id"] == "j1"  # extra fields survive
    assert isinstance(out["weird"], str)  # non-JSON extras stringified
    assert "trace_id" not in out  # no active span

    t = Tracer("svc")
    with t.span("logging") as s:
        out2 = json.loads(fmt.format(rec))
    assert out2["trace_id"] == s.trace_id and out2["span_id"] == s.span_id
