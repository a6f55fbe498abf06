"""Distributed span tracing — dependency-free.

The reference's only observability is per-peer message counters and a
PING latency probe (src/p2p/smart_node.py:855-892); a user→validator→
worker RPC leaves no correlated record anywhere. Here every node carries
a :class:`Tracer` with a bounded in-memory span buffer; spans opened on
one node propagate over the p2p envelope (p2p/node.py injects a
``_trace`` field into outbound messages while a span is active, and the
receiving dispatch opens a child span), so one job's RPC chain stitches
into a single trace across roles.

Export is the Chrome-trace ``traceEvents`` format — the same format a
jax.profiler capture writes and ``profiling.parse_op_breakdown`` already
consumes — served by ``GET /spans`` on the node's StatusServer and
openable directly in Perfetto (ui.perfetto.dev) or chrome://tracing.

Clocks: spans are stamped with wall-clock ``time.time_ns()`` on both
ends so spans from different nodes land on one shared timeline (skew is
whatever NTP leaves, microseconds on a LAN — fine for ms-scale RPCs);
durations subtract the same clock, so a span is internally consistent
even if the host steps its clock between traces.

Every span also goes to the profiler: :func:`region` (which
``Tracer.span`` is built on) enters a ``jax.profiler.TraceAnnotation``
named ``tl.<name>``, so inside a ``jax.profiler`` capture the program's
spans lie on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
device's lines, on one clock. :func:`scope` is the device-side twin (a
``jax.named_scope`` in every instruction's op path) and
the jitted functions are called ``tl_<what>``, which is the name their
launches carry. The names are one closed table, :data:`VOCABULARY`.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
import uuid
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

# The active span for the current task/thread. contextvars (not a
# thread-local): asyncio handlers running concurrently in one thread each
# see their own span, and to_thread copies the context so StageRunner
# work keeps its parent.
_current_span: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "tensorlink_tpu_current_span", default=None
)


def _new_id() -> str:
    """128-bit random id, hex, truncated to 16 chars (64 bits — the same
    width OpenTelemetry uses for span ids; collision-safe for a buffer of
    thousands)."""
    return uuid.uuid4().hex[:16]


@dataclass
class Span:
    """One timed operation. ``trace_id`` groups a causal chain (shared
    across nodes), ``parent_id`` is the span that caused this one —
    possibly on a different node (wire context)."""

    name: str
    trace_id: str
    span_id: str
    parent_id: str | None = None
    attrs: dict[str, Any] = field(default_factory=dict)
    start_ns: int = 0
    end_ns: int | None = None
    status: str = "ok"

    @property
    def duration_ns(self) -> int:
        return 0 if self.end_ns is None else max(self.end_ns - self.start_ns, 0)

    def context(self) -> dict[str, str]:
        """Wire form for cross-node propagation (the ``_trace`` field)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "attrs": self.attrs,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "status": self.status,
        }


def current_span() -> Span | None:
    """The task's active span, or None (used by JsonFormatter to stamp
    trace_id/span_id onto log records)."""
    return _current_span.get()


def current_trace_context() -> dict[str, str] | None:
    """Wire context of the active span, or None when no span is active —
    the one-ContextVar-read fast path p2p ``send`` uses, so untraced
    nodes pay no envelope overhead."""
    s = _current_span.get()
    return None if s is None else s.context()


# ------------------------------------------------------ the tl. vocabulary
# Every name the program writes into a profiler capture, with the layer
# it belongs to and what it bounds. Kinds: "span" (host interval,
# region()), "event" (host instant, event()), "scope" (a component of
# every enclosed device instruction's op path, scope()), "program" (a
# jitted function: its launches read ``jit_tl_<name>`` on the device's
# ``XLA Modules`` line; the function handed to ``jax.jit`` is simply
# called ``tl_<name>``). Host names appear as ``tl.<name>``.
VOCABULARY: dict[str, tuple[str, str, str]] = {
    # -- host: the two hot loops
    "train.step": ("span", "train/trainer.py", "one call of the jitted train step (Trainer, ShardedTrainer): host dispatch, returns before the device ends"),
    "serve.step": ("span", "parallel/serving.py", "one scheduler turn of either engine, under its lock"),
    "serve.admit": ("span", "parallel/serving.py", "deadline expiry and admission of waiting requests into free slots"),
    "serve.prefill_dispatch": ("span", "parallel/serving.py", "host side of one prefill (chunk) launch"),
    "serve.grow_blocks": ("span", "parallel/serving.py", "block-table growth ahead of the decode frontier (paged engine)"),
    "serve.decode_dispatch": ("span", "parallel/serving.py", "host side of one decode / spec chunk launch"),
    "serve.drain": ("span", "parallel/serving.py", "the host's wait for the oldest chunks in flight: the device's time, not the host's"),
    "serve.admitted": ("event", "parallel/serving.py", "a request got its slot: rid, waited_ms (submit to slot)"),
    "serve.first_token": ("event", "parallel/serving.py", "the host holds a request's first token as an int: rid, ttft_ms (submit to token)"),
    # -- device: scopes inside the programs
    "embed": ("scope", "model embedding", "token/position lookup and embedding dropout"),
    "attn": ("scope", "nn/attention.py", "a block's attention half: its norm, projections, attention, residual"),
    "swa": ("scope", "nn/attention.py", "a Laguna block's sliding-window attention half: norm, projections, rotary, the flash kernels, the heads' gates, o, residual"),
    "mlp": ("scope", "nn/transformer.py", "a block's feed-forward half (dense or MoE): its norm, matmuls, residual"),
    "kda": ("scope", "nn/kda.py", "a Kimi-Linear block's KDA half: norm, projections, short convolutions, gates, head norm, residual"),
    "kda.scan": ("scope", "ops/kda.py", "the chunked gated delta-rule recurrence inside tl.kda, forward and backward"),
    "mla": ("scope", "nn/mla.py", "a Kimi-Linear block's latent-attention half: norm, projections, the flash kernels, residual"),
    "moe": ("scope", "nn/moe.py", "a Kimi-Linear block's expert half (HeldExpertsMoE): norm, shared expert, residual"),
    "moe.route": ("scope", "nn/moe.py", "inside tl.moe: router scores, top-k, the sort of routes into rows"),
    "moe.experts": ("scope", "nn/moe.py", "inside tl.moe: gather of rows, the grouped matmuls of the held experts, scatter back"),
    "mamba": ("scope", "nn/mamba.py", "a Phi-4-mini-flash block's Mamba half: norm, projections, short convolution, step, gate, residual"),
    "mamba.scan": ("scope", "ops/selective_scan.py", "the chunked selective scan inside tl.mamba, forward and backward"),
    "gmu": ("scope", "nn/mamba.py", "a Phi-4-mini-flash block's gated-memory half: norm, gate projection, product with another layer's scan output, out-projection, residual"),
    "head": ("scope", "model head", "final norm and the unembedding matmul"),
    "loss": ("scope", "train/trainer.py", "the loss from logits"),
    "train.cast": ("scope", "train/trainer.py", "the dtype policy: master weights to the compute dtype, and the gradients' way back"),
    "train.accumulate": ("scope", "train/trainer.py", "gradient accumulation over micro-batches: the zero tree and acc + g/micro"),
    "train.sentinel": ("scope", "train/trainer.py", "the non-finite check over loss and gradients"),
    "train.clip": ("scope", "train/optim.py", "global-norm clipping"),
    "train.optimizer": ("scope", "train/optim.py", "optimizer update, its application, and the skip-on-non-finite select"),
    "serve.sample": ("scope", "parallel/serving.py", "sampling from the last logits"),
    "serve.cache_write": ("scope", "parallel/serving.py", "KV written back into the engine's state: graft of a prefilled cache, index and validity bookkeeping"),
    # -- device: programs
    "train_step": ("program", "train/trainer.py", "Trainer's step"),
    "sharded_train_step": ("program", "parallel/engine.py", "ShardedTrainer's step"),
    "decode": ("program", "parallel/serving.py", "decode chunk of either engine"),
    "spec_chunk": ("program", "parallel/serving.py", "speculative decode chunk"),
    "prefill": ("program", "parallel/serving.py", "the contiguous engine's whole-prompt prefill"),
    "prefill_chunk": ("program", "parallel/serving.py", "the paged engine's prefill chunk"),
    "pool_table": ("program", "parallel/serving.py", "point a slot's block-table row"),
    "pool_retire": ("program", "parallel/serving.py", "kill a slot on device"),
    "pool_copy": ("program", "parallel/serving.py", "copy-on-write of one block"),
    "pool_graft": ("program", "parallel/serving.py", "scatter imported blocks into the pools"),
    "pool_adopt": ("program", "parallel/serving.py", "adopt an imported prefill into a slot"),
}
# What the table closes: the names the two hot loops and the device
# programs write. A span opened through ``Tracer.span`` (RPC dispatch
# ``rpc.<mtype>``, ``stage<i>.*``, StepTelemetry's ``trainer.step``,
# the per-request ``serving.*`` legs) reaches a capture under the name
# its call site gives it, and is outside the table: ``mtype`` comes
# from the wire, and no reader of a capture looks for those.
PREFIX = "tl."


def known(name: str) -> bool:
    """Whether ``name`` (with or without ``tl.``) is in the table."""
    return name.removeprefix(PREFIX) in VOCABULARY


# jax.profiler.TraceAnnotation, looked up on first use so the module
# stays importable without jax; tests put a recording stand-in here
_annotate: Callable | None = None


def _annotation(name: str, attrs: dict):
    global _annotate
    if _annotate is None:
        import jax

        _annotate = jax.profiler.TraceAnnotation
    return _annotate(PREFIX + name, **attrs)


class region:
    """The one host span: always a ``TraceAnnotation`` ``tl.<name>``
    (next to free while no profile is being taken; keep ``attrs`` to
    numbers and short strings), and a recorded :class:`Span` too when a
    ``tracer`` is given. The hot loops give none: their phases are for
    a capture, and a node's span buffer is for per-request and RPC
    spans. ``with region(...) as s`` gives that span, or None."""

    __slots__ = ("_name", "_tracer", "_remote", "_attrs", "_ann", "_span",
                 "_token")

    def __init__(
        self, name: str, tracer: "Tracer | None" = None, /, *,
        remote: dict | None = None, **attrs,
    ):
        self._name, self._tracer = name, tracer
        self._remote, self._attrs = remote, attrs
        self._span = None

    def __enter__(self) -> "Span | None":
        self._ann = _annotation(self._name, self._attrs)
        self._ann.__enter__()
        if self._tracer is None:
            return None
        s = self._span = self._tracer.start_span(
            self._name, self._attrs, self._remote
        )
        self._token = _current_span.set(s)
        return s

    def __exit__(self, exc_type, exc, tb) -> None:
        s = self._span
        if s is not None:
            if exc_type is not None:
                s.status = "error"
                s.attrs.setdefault("error", exc_type.__name__)
            _current_span.reset(self._token)
            self._tracer.finish_span(s, s.status)
        self._ann.__exit__(exc_type, exc, tb)


def event(name: str, /, **attrs) -> None:
    """An instant on the host's line of a capture, carrying numbers the
    caller already has. Nothing is recorded outside a capture."""
    with _annotation(name, attrs):
        pass


def scope(name: str):
    """``jax.named_scope("tl.<name>")``: every instruction traced
    inside carries it in its op path, backward ones inside
    ``transpose(jvp(...))``. Metadata only: no instruction changes."""
    import jax

    return jax.named_scope(PREFIX + name)


class Tracer:
    """Per-node span recorder with a bounded buffer (oldest evicted).

    Usage::

        with tracer.span("train_step", {"step": 3}):
            ...                        # child spans nest automatically

    A span opened while another is active becomes its child (same
    trace_id); ``remote=`` instead parents onto a wire context received
    from a peer, which is how cross-node chains stitch.
    """

    def __init__(self, service: str = "node", max_spans: int = 2048):
        self.service = service
        self.max_spans = max_spans
        self._spans: deque[Span] = deque(maxlen=max_spans)
        self._lock = threading.Lock()  # handlers record from worker threads

    # -------------------------------------------------------------- record
    def start_span(
        self,
        name: str,
        attrs: dict | None = None,
        remote: dict | None = None,
    ) -> Span:
        parent = _current_span.get()
        if remote is not None and remote.get("trace_id"):
            # remote contexts arrive from the WIRE: cap id lengths so a
            # hostile peer cannot pin megabytes per span in the buffer
            # (and in every /spans response) via a giant _trace field
            trace_id = str(remote["trace_id"])[:64]
            parent_id = (
                str(remote["span_id"])[:64] if remote.get("span_id") else None
            )
        elif parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = _new_id(), None
        return Span(
            name=name,
            trace_id=trace_id,
            span_id=_new_id(),
            parent_id=parent_id,
            attrs=dict(attrs or {}),
            start_ns=time.time_ns(),
        )

    def span(
        self,
        name: str,
        attrs: dict | None = None,
        remote: dict | None = None,
    ):
        """A recorded span that is also a profiler annotation: see
        :func:`region`."""
        return region(name, self, remote=remote, **(attrs or {}))

    def finish_span(self, s: Span, status: str = "ok") -> Span:
        """Close and record a span obtained from :meth:`start_span`
        without ever making it the ambient context — for spans held
        open across awaits in different tasks (the disaggregated-
        serving front end keeps one root span per request from submit
        to result and parents each leg's RPC span onto it via
        ``remote=s.context()``)."""
        s.end_ns = time.time_ns()
        s.status = status
        with self._lock:
            self._spans.append(s)
        return s

    def record_span(
        self,
        name: str,
        start_ns: int,
        end_ns: int,
        attrs: dict | None = None,
        *,
        trace_id: str | None = None,
        parent: Span | None = None,
        status: str = "ok",
    ) -> Span:
        """Append an already-finished span from explicit timestamps —
        for reconstructed timelines (the serving engines stitch each
        request's queue/prefill/decode phases at finish time, from
        stamps taken on the hot path where opening a live span per
        phase would mean span context churn per token chunk). Same
        buffer/eviction as live spans; ``parent`` nests it under
        another recorded span, ``trace_id`` groups siblings."""
        s = Span(
            name=name,
            trace_id=(
                trace_id if trace_id is not None
                else (parent.trace_id if parent is not None else _new_id())
            ),
            span_id=_new_id(),
            parent_id=parent.span_id if parent is not None else None,
            attrs=dict(attrs or {}),
            start_ns=int(start_ns),
            end_ns=int(end_ns),
            status=status,
        )
        with self._lock:
            self._spans.append(s)
        return s

    # -------------------------------------------------------------- read
    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def to_chrome_trace(self) -> dict:
        """Finished spans as a Chrome-trace object ``{"traceEvents":
        [...]}`` — complete ("X") events in microseconds, one pid per
        tracer (named after the service), one tid per trace so each
        causal chain gets its own timeline row in Perfetto. Span ids and
        attrs ride in ``args``."""
        pid = zlib.crc32(self.service.encode()) & 0x7FFFFFFF
        events: list[dict] = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": self.service},
            }
        ]
        tids_named: set[int] = set()
        for s in self.spans():
            if s.end_ns is None:
                continue
            tid = zlib.crc32(s.trace_id.encode()) & 0x7FFFFFFF
            if tid not in tids_named:
                tids_named.add(tid)
                events.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": pid,
                        "tid": tid,
                        "args": {"name": f"trace {s.trace_id[:8]}"},
                    }
                )
            events.append(
                {
                    "ph": "X",
                    "name": s.name,
                    "cat": "span" if s.status == "ok" else "span,error",
                    "pid": pid,
                    "tid": tid,
                    "ts": s.start_ns / 1e3,
                    "dur": s.duration_ns / 1e3,
                    "args": {
                        "trace_id": s.trace_id,
                        "span_id": s.span_id,
                        "parent_id": s.parent_id,
                        **s.attrs,
                    },
                }
            )
        return {"traceEvents": events}


# ----------------------------------------------------------- step telemetry
class StepTelemetry:
    """Shared train-step instrumentation for Trainer/ShardedTrainer: a
    (shape, dtype, rng-variant) cache key decides whether THIS call
    compiles — the span is labeled ``{prefix}.compile_step`` vs
    ``{prefix}.step`` accordingly, and compile time never pollutes the
    ``step_seconds`` latency histogram. Host-side dispatch time; a first
    call's duration is dominated by the XLA compile."""

    def __init__(
        self,
        tracer: "Tracer | None",
        metrics: Any,
        prefix: str,
        attrs: dict | None = None,
    ):
        self.tracer = tracer
        self.metrics = metrics
        self.prefix = prefix
        self.attrs = dict(attrs or {})
        self._seen: set = set()

    @staticmethod
    def shape_key(batch: Any, rng: Any) -> tuple:
        """jit cache-key proxy: a new signature means the call retraces."""
        import jax  # deferred: this module stays importable without jax

        return (
            rng is None,
            tuple(
                (getattr(x, "shape", ()), str(getattr(x, "dtype", "")))
                for x in jax.tree.leaves(batch)
            ),
        )

    def seen(self, batch: Any, rng: Any) -> bool:
        """Whether this call signature already compiled — i.e. the next
        :meth:`step` will be a real step, not a compile (the device
        timer skips compile calls)."""
        return self.shape_key(batch, rng) in self._seen

    @contextlib.contextmanager
    def step(self, batch: Any, rng: Any) -> Iterator[None]:
        key = self.shape_key(batch, rng)
        first = key not in self._seen
        self._seen.add(key)
        t0 = time.perf_counter()
        with region(
            f"{self.prefix}.compile_step" if first else f"{self.prefix}.step",
            self.tracer, **self.attrs,
        ):
            yield
        if self.metrics is not None:
            dt = time.perf_counter() - t0
            self.metrics.observe("compile_s" if first else "step_s", dt)
            if not first:
                self.metrics.observe_hist("step_seconds", dt)
            self.metrics.incr("train_steps")

    @contextlib.contextmanager
    def data(self) -> Iterator[None]:
        """Wrap the batch fetch: ``{prefix}.data`` span + ``data_s``
        series, so input-pipeline stalls show on the step timeline."""
        t0 = time.perf_counter()
        with region(f"{self.prefix}.data", self.tracer):
            yield
        if self.metrics is not None:
            self.metrics.observe("data_s", time.perf_counter() - t0)


# ---------------------------------------------------------------- straggler
def straggler_report(
    metrics: Any, peers: dict[str, Any] | None = None
) -> dict:
    """Per-stage step-time skew + peer heartbeat age — the "which stage
    is slow, and is its worker even alive" view surfaced at ``/node``.

    Reads the rolling ``stage{i}_fwd_s`` / ``stage{i}_bwd_s`` series the
    master records per micro-batch RPC (roles/user.py) — or a worker's
    own local-compute series — and reports each stage's mean time, the
    slowest stage, and skew = slowest / median (1.0 = perfectly even;
    MPMD pipeline work treats this ratio as the straggler signal:
    pipeline throughput is gated by the max, not the mean). ``peers``
    (node_id -> object with ``last_seen``) adds per-peer heartbeat age:
    a straggler whose heartbeat is also stale is dead, not slow.
    """
    import re

    stage_means: dict[str, dict[str, float]] = {}
    series = getattr(metrics, "series", {}) or {}
    for name, q in series.items():
        m = re.fullmatch(r"stage(\d+)_(fwd|bwd)_s", name)
        if not m or not q:
            continue
        vals = list(q)
        rec = stage_means.setdefault(m.group(1), {})
        rec[f"{m.group(2)}_mean_s"] = sum(vals) / len(vals)
        rec[f"{m.group(2)}_n"] = len(vals)
    out: dict[str, Any] = {"stages": stage_means}
    totals = {
        k: v.get("fwd_mean_s", 0.0) + v.get("bwd_mean_s", 0.0)
        for k, v in stage_means.items()
    }
    if totals:
        ordered = sorted(totals.values())
        n = len(ordered)
        # true median (middle pair averaged for even n): with 2 stages
        # the upper-middle shortcut made skew identically 1.0
        median = (ordered[(n - 1) // 2] + ordered[n // 2]) / 2
        slowest = max(totals, key=totals.get)
        out["slowest_stage"] = int(slowest)
        out["slowest_mean_s"] = totals[slowest]
        out["skew"] = (totals[slowest] / median) if median > 0 else float("inf")
    if peers:
        now = time.time()
        out["heartbeat_age_s"] = {
            nid[:16]: round(now - getattr(p, "last_seen", now), 3)
            for nid, p in peers.items()
        }
    return out
