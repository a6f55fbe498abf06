"""From the profiler's trace to numbers. The reduction is the
benchmark's own, over ``jax.profiler.ProfileData`` and nothing else; it
is checked on a small recorded trace in ``benchmark/tests/``.

What a TPU trace of this installation looks like (read by hand in PR
26): one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Ops``
(one event per executed HLO instruction; a ``while`` spans the
instructions of its body, so self time is what ranks them) and a line
``XLA Modules`` (one event per launched program). Host threads are
lines of the ``/host:CPU`` plane; the harness's own
``TraceAnnotation`` spans (``bench.*``) lie there on the same clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"  # the harness's span around the traced loop
MIN_GAP_NS = 10_000  # shorter gaps are the device's own, not the host's


def start(logdir: str) -> None:
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest(logdir: str) -> str:
    files = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def instruction(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: a
    device event's name is the whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def kind(text: str) -> str:
    """``fusion.12`` -> ``fusion``: instructions of one kind rank as
    one line of the breakdown."""
    return re.sub(r"[._\d]+$", "", instruction(text))


_WRAPPERS = re.compile(r"^(?:transpose_|jvp_|checkpoint_|remat_)+")


def kernel_of(text: str) -> str:
    return _WRAPPERS.sub("", kind(text))


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    dur: int  # ns
    self_ns: int = 0

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    """One chip's trace (averaged over chips where several are used)."""

    ops: list[Event]  # device instructions, with self time
    modules: list[Event]  # launched programs
    spans: list[Event]  # the harness's host spans
    busy_ns: float  # union of instruction intervals, mean over chips
    chips: int
    window_ns: int | None = None  # the traced window, on the trace's clock

    def kernel_events(self, kernel: str) -> list[Event]:
        """The executed instructions that ARE the kernel ``kernel``
        (under autodiff the name is wrapped: ``jvp_tl_flash_fwd_``,
        ``transpose_jvp_tl_flash_bwd_dq__``), not those that read its
        result."""
        return [e for e in self.ops if kernel_of(e.name) == kernel]

    def modules_holding(self, *kernels: str, without=()) -> list[Event]:
        """Launched programs inside which every one of ``kernels`` ran
        and none of ``without``: how a program is recognised while the
        program itself gives its jitted functions no stable name."""
        marks = {
            k: sorted(e.start for e in self.kernel_events(k))
            for k in (*kernels, *without)
        }

        def holds(m: Event, k: str) -> bool:
            i = bisect.bisect_left(marks[k], m.start)
            return i < len(marks[k]) and marks[k][i] < m.end

        return [
            m for m in self.modules
            if all(holds(m, k) for k in kernels)
            and not any(holds(m, k) for k in without)
        ]


def union_ns(events: list[Event]) -> int:
    """Total length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list[Event]) -> list[tuple[int, int]]:
    """The idle intervals between the union's pieces."""
    out, cur_e = [], None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is not None and e.start > cur_e:
            out.append((cur_e, e.start))
        cur_e = e.end if cur_e is None else max(cur_e, e.end)
    return out


def self_times(events: list[Event]) -> None:
    """Self time of nested intervals on one line: an event's duration
    less what its children cover."""
    stack: list[Event] = []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        e.self_ns = e.dur
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            stack[-1].self_ns -= e.dur
        stack.append(e)


def _events(line) -> list[Event]:
    return [
        Event(e.name, int(e.start_ns), int(e.duration_ns))
        for e in line.events
    ]


def clip(events: list[Event], t0: int, t1: int) -> list[Event]:
    """The parts of ``events`` that lie inside [t0, t1]: work that was
    in flight when the window opened or closed counts as far as it lies
    inside, and not beyond."""
    out = []
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            out.append(Event(e.name, a, b - a))
    return out


def reduce(path: str, chips: int = 1, window_span: str = WINDOW_SPAN) -> Reduced:
    """Device instructions, launched programs and the harness's spans
    of one trace. Where the harness marked its traced loop with a span
    ``window_span``, everything is clipped to that span: the profiler
    starts before it and stops after it, and the device runs on past
    the host's last turn."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: list[tuple[list[Event], list[Event]]] = []
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            dev_ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            if dev_ops:
                devices.append((
                    dev_ops,
                    _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                ))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [
                    e for e in _events(ln) if e.name.startswith(SPAN_PREFIX)
                ]
    if not devices:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    window = next((e for e in spans if e.name == window_span), None)
    spans = [e for e in spans if e.name != window_span]
    if window is not None:
        devices = [
            (clip(o, window.start, window.end), clip(m, window.start, window.end))
            for o, m in devices
        ]
        spans = clip(spans, window.start, window.end)
    busy = sum(union_ns(o) for o, _ in devices) / len(devices)
    ops, modules = devices[0]  # chip 0 stands for the names and programs
    self_times(ops)
    return Reduced(
        ops, modules, spans, busy, len(devices),
        window.dur if window is not None else None,
    )


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device instructions that took most (self) time, and the
    longest idle gaps by what the harness's host loop was doing."""
    by_name: dict[str, int] = {}
    for e in red.ops:
        by_name[kind(e.name)] = by_name.get(kind(e.name), 0) + e.self_ns
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    by_span: dict[str, int] = {}
    # the harness's spans follow one another on one thread: sorted by
    # start they are sorted by end too, so a gap finds its spans by
    # bisection
    spans = sorted(red.spans, key=lambda e: e.start)
    ends = [s.end for s in spans]
    for g0, g1 in gaps(red.ops):
        if g1 - g0 < MIN_GAP_NS:
            name = "device:between_instructions"
        else:
            name, cover = "host:outside_bench_spans", 0
            i = bisect.bisect_right(ends, g0)
            while i < len(spans) and spans[i].start < g1:
                c = min(spans[i].end, g1) - max(spans[i].start, g0)
                if c > cover:
                    name, cover = spans[i].name, c
                i += 1
        by_span[name] = by_span.get(name, 0) + (g1 - g0)
    idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in device_ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in idle],
    }
