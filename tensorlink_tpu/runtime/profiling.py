"""Profiling: jax.profiler traces and the always-on device-time
telemetry layer.

The reference's only timing instrumentation is a PING/PONG latency probe
(src/p2p/smart_node.py:889-892); there is no tracer of any kind (survey
§5.1). Here, three tiers:

- offline: `trace()` wraps `jax.profiler.trace` so any training or
  inference region can be captured and opened in XProf/TensorBoard, and
  `op_breakdown` aggregates a capture into per-HLO-category device time;
- on-demand: `timed_capture` runs a BOUNDED capture of whatever the
  process is doing right now (serves ``GET /profile?ms=N``), refusing
  concurrent captures — jax.profiler is process-global;
- always-on: :class:`DispatchTimer` attributes wall time per dispatched
  program into device-busy vs host-gap with NO extra synchronization —
  timing rides the host syncs the serving engines and trainer already
  perform — and :func:`measure_capability` is the short startup
  microbench (peak matmul TFLOPs + HBM read GB/s) those numbers are
  normalized against (MFU/MBU), cached in the autotune store so
  restarts skip it.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/tensorlink_tpu_trace") -> Iterator[str]:
    """Capture an XLA/device trace of the enclosed region.

    View with: `tensorboard --logdir <dir>` (profile plugin) or xprof.
    """
    with jax.profiler.trace(log_dir):
        yield log_dir


def parse_op_breakdown(trace_events: list, lane: str = "XLA Ops") -> dict:
    """Aggregate a Chrome-trace event list (the ``trace.json.gz`` a
    jax.profiler capture writes) into per-HLO-category device time.

    Control-flow wrapper events (category ``while``/``conditional``)
    enclose their body ops and would double-count, so they are reported
    separately and excluded from ``total_s``/fractions. CPU captures
    carry no ``hlo_category`` metadata — the result is then empty
    (``total_s == 0``); this is a TPU instrument.

    Live r4 reference point (BERT-base batch 32, 50-step scan, v5e):
    83.8% "convolution fusion" (matmuls + the elementwise work fused
    into them), 6.0% copies, 5.8% loop fusion — the MFU ceiling lives
    inside the matmul fusions' HBM streams, not in unfused overhead.
    """
    import collections

    tids = {
        (e["pid"], e["tid"]): e.get("args", {}).get("name", "")
        for e in trace_events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    in_lane = lambda e: tids.get((e.get("pid"), e.get("tid"))) == lane
    have_lane = any(v == lane for v in tids.values())
    cat = collections.Counter()
    nops = collections.Counter()
    wrappers = collections.Counter()
    for e in trace_events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        c = (e.get("args") or {}).get("hlo_category")
        if c is None or (have_lane and not in_lane(e)):
            continue
        if c in ("while", "conditional"):
            wrappers[c] += e["dur"]
            continue
        cat[c] += e["dur"]
        nops[c] += 1
    total_us = sum(cat.values())
    return {
        "total_s": total_us / 1e6,
        "control_flow_wrapper_s": {
            k: v / 1e6 for k, v in wrappers.items()
        },
        "categories": {
            c: {
                "s": d / 1e6,
                "fraction": (d / total_us) if total_us else 0.0,
                "ops": nops[c],
            }
            for c, d in cat.most_common()
        },
    }


def _newest_trace_events(d: str) -> list | None:
    """Event list of the NEWEST capture under ``d`` (by mtime: each
    jax.profiler.trace writes a new timestamped subdir, and a reused
    log_dir holds older runs — os.walk order would return an arbitrary
    one; review finding), or None when no trace file was produced."""
    import gzip
    import json as _json
    import os

    traces = []
    for root, _, files in os.walk(d):
        for name in files:
            if name.endswith("trace.json.gz"):
                p = os.path.join(root, name)
                traces.append((os.path.getmtime(p), p))
    if not traces:
        return None
    tj = max(traces)[1]
    return _json.loads(gzip.open(tj).read())["traceEvents"]


def op_breakdown(fn, *args, log_dir: str | None = None) -> dict:
    """Run ``fn(*args)`` once under a fresh jax.profiler capture and
    return its parse_op_breakdown. ``fn`` should be pre-compiled/warm —
    a first call would profile compilation. Forces a host read of the
    first output leaf so the capture spans the real device work."""
    import shutil
    import tempfile

    own_dir = log_dir is None
    d = log_dir or tempfile.mkdtemp(prefix="tlt_profile_")
    try:
        with jax.profiler.trace(d):
            out = fn(*args)
            leaf = jax.tree.leaves(out)[0]
            float(jax.numpy.asarray(leaf).reshape(-1)[0])
        events = _newest_trace_events(d)
        if events is None:
            return {"total_s": 0.0, "control_flow_wrapper_s": {},
                    "categories": {}, "error": "no trace file produced"}
        result = parse_op_breakdown(events)
        if not own_dir:
            result["trace_dir"] = d  # caller keeps the capture
        return result
    finally:
        if own_dir:
            shutil.rmtree(d, ignore_errors=True)


# --------------------------------------------------- on-demand capture
# jax.profiler is process-global: two concurrent start_trace calls
# corrupt each other, so captures serialize on this lock and a second
# requester is REFUSED (the StatusServer turns it into a 409), never
# queued — an operator asking "what is the chip doing right now" must
# not silently measure a minute later.
_capture_lock = threading.Lock()

# hard bound on one capture: /profile is an unauthenticated loopback
# endpoint, and an unbounded capture both pins the profiler and grows
# an arbitrarily large trace file
MAX_PROFILE_MS = 10_000
MIN_PROFILE_MS = 10


class ProfileBusyError(RuntimeError):
    """A jax.profiler capture is already running in this process."""


def _clamp_ms(ms) -> int:
    return max(MIN_PROFILE_MS, min(int(ms), MAX_PROFILE_MS))


def timed_capture(ms: int = 200, log_dir: str | None = None) -> dict:
    """Capture ``ms`` milliseconds of whatever this process is doing
    under jax.profiler and return the parsed ``op_breakdown`` bundle
    (the ``GET /profile?ms=N`` payload). Blocking for the duration —
    callers on an event loop must ``asyncio.to_thread`` it. With
    ``log_dir`` the raw capture is retained there (``trace_dir`` in the
    result) for XProf/TensorBoard; otherwise it is parsed and deleted.
    Raises :class:`ProfileBusyError` when a capture is already live."""
    import shutil
    import tempfile

    ms = _clamp_ms(ms)
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusyError(
            "a jax.profiler capture is already running in this process"
        )
    try:
        own_dir = log_dir is None
        d = log_dir or tempfile.mkdtemp(prefix="tlt_profile_")
        try:
            jax.profiler.start_trace(d)
            try:
                time.sleep(ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
            events = _newest_trace_events(d)
            out = {
                "duration_ms": ms,
                "op_breakdown": (
                    parse_op_breakdown(events) if events is not None
                    else {"total_s": 0.0, "control_flow_wrapper_s": {},
                          "categories": {}, "error": "no trace produced"}
                ),
            }
            if not own_dir:
                out["trace_dir"] = d
            return out
        finally:
            if own_dir:
                shutil.rmtree(d, ignore_errors=True)
    finally:
        _capture_lock.release()


# ------------------------------------------- always-on device timing
class _Dispatch:
    """One in-flight program dispatch: host enqueue time + an output
    array probed for readiness (never a donated input)."""

    __slots__ = ("program", "t_dispatch", "probe", "done", "busy_s")

    def __init__(self, program: str, t_dispatch: float, probe: Any):
        self.program = program
        self.t_dispatch = t_dispatch
        self.probe = probe
        self.done = False
        # stamped at finalization: this dispatch's device-busy share.
        # Callers that kept the handle (the serving engines) read it to
        # apportion device time to the requests the chunk served.
        self.busy_s = 0.0


def _probe_ready(probe: Any) -> bool:
    fn = getattr(probe, "is_ready", None)
    if fn is None:
        return False  # older jax: finalized at the next explicit sync
    try:
        return bool(fn())
    except Exception:  # noqa: BLE001 — a deleted/donated buffer
        return True


class DispatchTimer:
    """Per-program device-busy vs host-gap attribution with no added
    synchronization.

    The serving engines and the trainer dispatch their programs through
    ONE donated state tree, so device execution is strictly serialized
    in dispatch order. That makes wall time decomposable from three
    host-side observations alone:

    - ``dispatch``: when the host enqueued the program (the jit call
      returned);
    - ``ready``: when the program's output became observable — stamped
      opportunistically by :meth:`poll` (``Array.is_ready`` on the FIFO
      head, one cheap call per scheduler step) or exactly by
      :meth:`drained` right after a host sync the caller was doing
      anyway;
    - the previous program's ready time (the device "frontier").

    Per finalized dispatch: ``busy = ready - max(dispatch, frontier)``
    (what the device actually executed) and ``gap = max(dispatch -
    frontier, 0)`` (the device sat idle waiting for the host — the
    pipeline bubble). ``host_gap_frac = gap / (gap + busy)`` is the
    HOST-BOUND signal tldiag flags above 0.3.

    Granularity: a dispatch finalized by ``poll`` is stamped at the
    poll, so ``busy`` can overshoot by up to one scheduler iteration;
    a dispatch finalized by a sync is exact when the host blocked.
    Finalization is strictly FIFO — a sync of chunk N finalizes every
    earlier outstanding dispatch first (they provably completed), so a
    drained chunk's time is never charged to the wrong program.

    Metrics cardinality is BOUNDED: per-program series use the program
    name only (a small fixed set — never a request id), and at most
    ``MAX_PROGRAMS`` distinct names register before the rest lump under
    ``"other"``. Thread-safe; the lock outlives any caller lock and
    takes nothing else.
    """

    MAX_PROGRAMS = 8

    def __init__(self, metrics=None, ewma: float = 0.1, clock=None):
        self.metrics = metrics
        self.alpha = float(ewma)
        self._clock = clock or time.perf_counter
        self._lock = threading.Lock()
        self._fifo: collections.deque[_Dispatch] = collections.deque()
        self._frontier: float | None = None
        self.programs: dict[str, dict] = {}

    # ------------------------------------------------------------ record
    def dispatch(self, program: str, probe: Any = None) -> _Dispatch:
        """Note one enqueued program; call RIGHT AFTER the jit call
        returns so host dispatch overhead counts as host gap, not
        device busy. ``probe`` is an output leaf (e.g. the chunk's
        token array) polled for readiness — never a donated input."""
        e = _Dispatch(str(program), self._clock(), probe)
        with self._lock:
            self._fifo.append(e)
        return e

    def poll(self) -> None:
        """Opportunistic ready stamping: finalize FIFO-head dispatches
        whose probe reports ready. One ``is_ready`` call per pending
        head per invocation — cheap enough for every scheduler step."""
        now = self._clock()
        with self._lock:
            while self._fifo and _probe_ready(self._fifo[0].probe):
                self._finalize_locked(self._fifo.popleft(), now)

    def drained(self, e: _Dispatch) -> None:
        """Exact finalization right after the caller host-synced this
        dispatch's payload. Earlier outstanding dispatches provably
        completed before it (serialized device queue) and finalize
        first at the same instant."""
        now = self._clock()
        with self._lock:
            # the done check lives INSIDE the lock: a concurrent poll()
            # may finalize e between an unlocked read and the loop
            # below, which would then drain the whole FIFO — charging
            # still-executing dispatches as finished
            if e.done:
                return
            while self._fifo:
                head = self._fifo.popleft()
                self._finalize_locked(head, now)
                if head is e:
                    break

    def count_tokens(self, program: str, n: int) -> None:
        """Attribute ``n`` emitted tokens to ``program`` (device
        tokens/sec in the snapshot)."""
        if n <= 0:
            return
        with self._lock:
            _, rec = self._program_locked(str(program))
            rec["tokens"] += int(n)

    # ---------------------------------------------------------- internals
    def _program_locked(self, name: str) -> tuple[str, dict]:
        """(canonical name, record) — past MAX_PROGRAMS distinct names
        everything lumps under "other". The canonical name is what the
        METRICS emission must use too, or the registry cardinality
        grows with the raw name set the cap exists to bound."""
        rec = self.programs.get(name)
        if rec is None:
            if len(self.programs) >= self.MAX_PROGRAMS:
                name = "other"
                rec = self.programs.get(name)
            if rec is None:
                rec = self.programs[name] = {
                    "count": 0, "busy_s": 0.0, "gap_s": 0.0,
                    "busy_ewma_s": None, "tokens": 0,
                }
        return name, rec

    def _finalize_locked(self, e: _Dispatch, t_ready: float) -> None:
        e.done = True
        e.probe = None  # release the device array promptly
        start = (
            e.t_dispatch if self._frontier is None
            else max(e.t_dispatch, self._frontier)
        )
        busy = max(t_ready - start, 0.0)
        e.busy_s = busy
        gap = (
            max(e.t_dispatch - self._frontier, 0.0)
            if self._frontier is not None else 0.0
        )
        self._frontier = max(self._frontier or t_ready, t_ready)
        name, rec = self._program_locked(e.program)
        rec["count"] += 1
        rec["busy_s"] += busy
        rec["gap_s"] += gap
        a = self.alpha
        rec["busy_ewma_s"] = (
            busy if rec["busy_ewma_s"] is None
            else (1.0 - a) * rec["busy_ewma_s"] + a * busy
        )
        if self.metrics is not None:
            from tensorlink_tpu.runtime.metrics import DEVICE_BUCKETS

            # fixed name set: one histogram + one gauge per CANONICAL
            # program name (bounded by MAX_PROGRAMS, overflow lumped
            # under "other") — never a per-request or raw label
            self.metrics.observe_hist(
                f"dev_{name}_busy_s", busy, buckets=DEVICE_BUCKETS
            )
            self.metrics.observe(f"dev_{name}_gap_s", gap)

    # -------------------------------------------------------------- read
    def snapshot(self) -> dict:
        """Aggregate view: per-program totals/EWMAs plus the engine-wide
        device-busy vs host-gap split."""
        with self._lock:
            progs = {
                name: dict(rec) for name, rec in self.programs.items()
            }
            pending = len(self._fifo)
        busy = sum(r["busy_s"] for r in progs.values())
        gap = sum(r["gap_s"] for r in progs.values())
        for r in progs.values():
            if r["tokens"] and r["busy_s"] > 0:
                r["device_tokens_per_sec"] = round(
                    r["tokens"] / r["busy_s"], 1
                )
        return {
            "programs": progs,
            "pending": pending,
            "device_busy_s": round(busy, 6),
            "host_gap_s": round(gap, 6),
            "host_gap_frac": (
                round(gap / (gap + busy), 4) if (gap + busy) > 0 else 0.0
            ),
        }


# ------------------------------------------------ capability microbench
CAPABILITY_SCHEMA = 1


def measure_capability(
    *,
    matmul_dim: int = 512,
    hbm_mb: int = 64,
    reps: int = 4,
    store=None,
    key: str | None = None,
    recorder=None,
) -> dict:
    """Short microbench of THIS chip: peak bf16 matmul TFLOPs and HBM
    read GB/s — the denominators per-program MFU/MBU are computed
    against, and the roofline record workers publish for placement
    (ROADMAP item 1 input).

    With ``store``/``key`` (an :class:`runtime.autotune.AutotuneStore`
    and its chip-global key), a record measured by an earlier process
    on the SAME chip is returned without running anything (``cached:
    True``) and a fresh measurement is merge-saved so restarts skip it.

    Each timed region ends in a scalar host read, which waits for the
    device as ``block_until_ready`` does."""
    from tensorlink_tpu.runtime.compile_cache import runtime_fingerprint

    rt = runtime_fingerprint()
    if store is not None and key:
        rec = store.load(key)
        cap = (rec or {}).get("capability")
        if (
            isinstance(cap, dict)
            and cap.get("schema") == CAPABILITY_SCHEMA
            and cap.get("chip") == rt["chip"]
        ):
            return {**cap, "cached": True}

    import jax.numpy as jnp

    t_all = time.perf_counter()
    n = int(matmul_dim)
    x = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    y = mm(x, x)
    float(y[0, 0].astype(jnp.float32))  # compile + warm, synced
    t0 = time.perf_counter()
    for _ in range(reps):
        y = mm(y, x)  # chained: the calls serialize on the data dep
    float(y[0, 0].astype(jnp.float32))
    dt = time.perf_counter() - t0
    peak_tflops = (2.0 * n**3 * reps) / dt / 1e12 if dt > 0 else 0.0

    m = max(int(hbm_mb) * (1 << 20) // 4, 1024)
    buf = jnp.ones((m,), jnp.float32)
    rd = jax.jit(lambda a: a.sum())
    float(rd(buf))  # compile + warm
    t0 = time.perf_counter()
    s = None
    for _ in range(reps):
        s = rd(buf)
    float(s)
    dt = time.perf_counter() - t0
    hbm_gbps = (4.0 * m * reps) / dt / 1e9 if dt > 0 else 0.0

    cap = {
        "schema": CAPABILITY_SCHEMA,
        "chip": rt["chip"],
        "peak_tflops": round(peak_tflops, 4),
        "hbm_gbps": round(hbm_gbps, 3),
        "matmul_dim": n,
        "hbm_mb": int(hbm_mb),
        "measure_s": round(time.perf_counter() - t_all, 4),
        "measured_at": time.time(),
    }
    if recorder is not None:
        try:
            recorder.record(
                "capability.measured", chip=cap["chip"],
                peak_tflops=cap["peak_tflops"], hbm_gbps=cap["hbm_gbps"],
                measure_s=cap["measure_s"],
            )
        except Exception:  # noqa: BLE001 — telemetry must not measure
            pass
    if store is not None and key:
        try:
            store.update(key, {"capability": cap})
        except Exception:  # noqa: BLE001 — caching is best-effort
            pass
    return cap
