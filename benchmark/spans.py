"""The program's own names in a run's trace: the ``tl.`` host spans and
events (``tensorlink_tpu/runtime/tracing.py``: ``region``, ``event``),
the ``tl.`` scopes in every device instruction's op path (``scope``),
and launches by program name (``jit_tl_train_step``). What
``trace.reduce`` hands a reader holds none of them (it keeps the host
events that start with ``bench.`` and drops event metadata), so the
readers that need them come here.

Which trace: ``run["tracedir"]`` where a reader is given one (the tests
do; ``run.py`` does not yet), else the newest ``*.xplane.pb`` by
modification time under ``benchmark/.trace/`` (``trace.start`` clears
the cell's directory before the process's own capture, so that is the
newest), and only if it is the capture ``run["trace"]`` was reduced
from: the same window and as many instructions. Another cell's older
capture, found because this process wrote none, gives ``None``. A later
``benchmark`` issue should put ``tracedir`` into ``run`` in ``run.py``
and retire the lookup.

How it is read: ``jax.profiler.ProfileData`` gives an event's own stats
but not its metadata's, and a device instruction's op path (``tf_op``)
is a stat of its metadata. So the file is read from the protobuf wire
format here, with no ``tensorflow`` or ``tsl`` import. The schema
(tsl/profiler/protobuf/xplane.proto), as far as it is used:

    XSpace         1 planes*
    XPlane         2 name, 3 lines*, 4 event_metadata{id: XEventMetadata},
                   5 stat_metadata{id: XStatMetadata}
    XLine          2 name, 3 timestamp_ns, 4 events*
    XEvent         1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats*
    XEventMetadata 1 id, 2 name, 5 stats*
    XStatMetadata  1 id, 2 name
    XStat          1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                   6 bytes, 7 ref (a stat_metadata id whose name is the value)

The two clocks: a capture stamps the host's events and the device's
from two clocks that stand 0.3 to 2.5 ms apart, another distance in
every capture (PERF.md section 7). ``Scoped.offset`` brackets it from
the runtime's own events, which carry the launch's ``run_id`` as the
device's ``XLA Modules`` events do: the host enqueues a launch
(``DoEnqueueProgram``) before the device starts it, and learns of its
end (``CompleteCallbacks``) after the device ended it. Only
``idle_in_program_pct`` lays host spans over device gaps, and it moves
the gaps by the bracket's middle; everything else stays on one clock.

A program without the names (the parent of the PR that added them, an
older recording) gives no ``tl.`` span and no scoped instruction: every
reader built on this file then returns ``None``, never 0.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import statistics
import struct
from pathlib import Path

from benchmark import trace
from benchmark.trace import Event

HERE = Path(__file__).resolve().parent
PREFIX = "tl."
_SCOPE = re.compile(r"tl\.[a-z_.]*[a-z]")

# what the per-layer metrics sum, by the innermost tl. scope of an
# instruction's op path
GROUPS = {
    "attn": ("tl.attn",),
    "mlp": ("tl.mlp",),
    "head_loss": ("tl.embed", "tl.head", "tl.loss"),
    # every scope of Trainer._step: the four the issue lists and the
    # dtype policy's casts (tl.train.cast), which belong to no model layer
    "update": ("tl.train.",),
    # the engines' own work inside their programs, beside the model's
    "sample": ("tl.serve.sample",),
    "cache_write": ("tl.serve.cache_write",),
}
UNSCOPED = "unscoped"
# libtpu's own host events about a launch, each with the launch's run_id
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


# ------------------------------------------------------------ wire format
def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair for a length-delimited field, raw bytes for the
    fixed widths."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield key >> 3, v


def _text(b: bytes, span: tuple[int, int]) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b: bytes, span, stat_names: dict[int, str]):
    """One XStat -> (name, value)."""
    name, value = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(b, v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(b: bytes, span) -> tuple[int, tuple[int, int] | None]:
    key, value = 0, None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


@dataclasses.dataclass
class Op(Event):
    """A device instruction with the op path its metadata carries."""

    path: str = ""

    @property
    def scope(self) -> str | None:
        """The innermost ``tl.`` scope of the op path."""
        found = _SCOPE.findall(self.path)
        return found[-1] if found else None


@dataclasses.dataclass
class Span(Event):
    """A ``tl.`` host span or event with its arguments."""

    args: dict = dataclasses.field(default_factory=dict)


def _plane(b: bytes, span) -> dict:
    """One XPlane: its name, its lines' spans, and the two metadata
    maps (an event's metadata as name and stats, unparsed)."""
    out = {"name": "", "lines": [], "events": {}, "stats": {}}
    for f, v in _fields(b, *span):
        if f == 2:
            out["name"] = _text(b, v)
        elif f == 3:
            out["lines"].append(v)
        elif f == 4:
            key, value = _map_entry(b, v)
            out["events"][key] = value
        elif f == 5:
            key, value = _map_entry(b, v)
            for f2, v2 in _fields(b, *value) if value else ():
                if f2 == 2:
                    out["stats"][key] = _text(b, v2)
    return out


def _event_metadata(b: bytes, span, stat_names) -> tuple[str, dict]:
    name, stats = "", {}
    for f, v in _fields(b, *span) if span else ():
        if f == 2:
            name = _text(b, v)
        elif f == 5:
            k, val = _stat(b, v, stat_names)
            stats[k] = val
    return name, stats


def _line(b: bytes, span):
    """One XLine -> (name, timestamp_ns, [event spans])."""
    name, t0, events = "", 0, []
    for f, v in _fields(b, *span):
        if f == 2:
            name = _text(b, v)
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            events.append(v)
    return name, t0, events


def _event(b: bytes, span):
    """One XEvent -> (metadata_id, start offset in ns, duration in ns,
    [stat spans])."""
    mid = off = dur = 0
    stats = []
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = _signed(v)
        elif f == 4:
            stats.append(v)
    return mid, off // 1000, dur // 1000, stats


# ---------------------------------------------------------------- a trace
@dataclasses.dataclass
class Scoped:
    """Chip 0's instructions with their op paths, its launches by
    name, and the program's host spans, all clipped to the harness's
    window as ``trace.reduce`` clips."""

    ops: list[Op]  # with self time
    modules: list[Event]  # launched programs, unclipped names
    spans: list[Span]  # tl.* host spans and events
    window: tuple[int, int] | None
    # host clock less device clock, ns: (at least, at most), or None
    # where the runtime's events are missing
    offset: tuple[int, int] | None = None

    def modules_named(self, name: str) -> list[Event]:
        """Launches of the program ``jit_<name>`` (``tl_train_step``):
        the trace's name is ``jit_tl_train_step(<fingerprint>)``."""
        want = re.compile(rf"^jit_{re.escape(name)}(\(|$)")
        return [m for m in self.modules if want.match(m.name)]

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


def own_trace(run: dict | None = None) -> str | None:
    """The run's own capture (see the module docstring)."""
    root = (run or {}).get("tracedir") or str(HERE / ".trace")
    if os.path.isfile(root):
        return root
    files = glob.glob(f"{root}/**/*.xplane.pb", recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _clip(events, t0, t1):
    """As ``trace.clip``, keeping what an Op or a Span carries."""
    out = []
    for e in events:
        a, z = max(e.start, t0), min(e.end, t1)
        if z > a or (e.dur == 0 and t0 <= e.start <= t1):
            out.append(dataclasses.replace(e, start=a, dur=z - a))
    return out


def read(path: str, window_span: str = trace.WINDOW_SPAN) -> Scoped:
    with open(path, "rb") as f:
        b = f.read()
    planes = [
        _plane(b, v) for f, v in _fields(b, 0, len(b)) if f == 1
    ]
    ops: list[Op] = []
    modules: list[Event] = []
    spans: list[Span] = []
    window = None
    # by run_id: the device's launch, and the host's two events about it
    launched: dict[int, Event] = {}
    enqueued: dict[int, int] = {}
    completed: dict[int, int] = {}
    for plane in planes:
        on_chip = plane["name"] == trace.DEVICE_PREFIX + "0"
        if not on_chip and not plane["name"].startswith("/host:"):
            continue
        meta: dict[int, tuple[str, dict]] = {}  # an id is its plane's own
        for ln in plane["lines"]:
            line, t0, events = _line(b, ln)
            if on_chip and line not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            for ev in events:
                mid, off, dur, stat_spans = _event(b, ev)
                if mid not in meta:
                    meta[mid] = _event_metadata(
                        b, plane["events"].get(mid), plane["stats"]
                    )
                text, stats = meta[mid]
                if on_chip and line == trace.OPS_LINE:
                    op_path = str(stats.get("tf_op") or "").rstrip(":")
                    ops.append(Op(text, t0 + off, dur, path=op_path))
                elif on_chip:
                    modules.append(Event(text, t0 + off, dur))
                    run_id = _args(b, stat_spans, plane["stats"]).get("run_id")
                    launched[run_id] = modules[-1]
                elif text in (ENQUEUE, COMPLETE):
                    run_id = _args(b, stat_spans, plane["stats"]).get("run_id")
                    which = enqueued if text == ENQUEUE else completed
                    which.setdefault(run_id, t0 + off)
                elif text == window_span:
                    window = (t0 + off, t0 + off + dur)
                elif text.startswith(PREFIX):
                    args = _args(b, stat_spans, plane["stats"])
                    spans.append(Span(text, t0 + off, dur, args=args))
    if window is not None:
        ops = [o for o in _clip(ops, *window) if o.dur]
        spans = _clip(spans, *window)  # instants stay
    for events in (ops, spans):  # by start, a parent before its children
        events.sort(key=lambda e: (e.start, -e.dur))
    trace.self_times(ops)
    return Scoped(
        ops, modules, spans, window, _offset(launched, enqueued, completed)
    )


def _args(b: bytes, stat_spans, stat_names) -> dict:
    return dict(_stat(b, s, stat_names) for s in stat_spans)


def _offset(launched, enqueued, completed) -> tuple[int, int] | None:
    """Host clock less device clock, bracketed: no launch starts on
    the device before the host enqueued it, and the host hears of no
    launch's end before the device ended it. The tightest pair of each
    kind bounds it; a launch that waited in the device's queue gives a
    slack bound and loses to one that found the device idle."""
    launched.pop(None, None)  # an event without the stat pairs with nothing
    least = [enqueued[r] - m.start for r, m in launched.items() if r in enqueued]
    most = [completed[r] - m.end for r, m in launched.items() if r in completed]
    if not least or not most or max(least) > min(most):
        return None
    return max(least), min(most)


def of(run: dict) -> Scoped | None:
    """The run's trace, read once and kept in ``run`` for the next
    reader of the same line."""
    if "tl_scoped" not in run:
        path = own_trace(run)
        sc = None if path is None else read(path)
        red = run.get("trace")
        if sc is not None and red is not None and "tracedir" not in run and (
            len(sc.ops) != len(red.ops)
            or (sc.window[1] - sc.window[0] if sc.window else 0) != red.window_ns
        ):
            sc = None  # not the capture this run was reduced from
        run["tl_scoped"] = sc
    return run["tl_scoped"]


# ------------------------------------------------------- what readers ask
def group_of(scope: str | None) -> str:
    for group, prefixes in GROUPS.items():
        if scope and any(
            scope == p or (p.endswith(".") and scope.startswith(p))
            for p in prefixes
        ):
            return group
    return UNSCOPED if scope is None else "other"


def step_split(run: dict, program: str = "tl_train_step") -> dict | None:
    """Per launch of ``jit_<program>`` that lies whole inside the
    window, the device self time of its instructions by group (ns);
    the median over launches of each group, of ``scoped`` (under any
    ``tl.`` scope) and of ``total``. ``None`` where the program's name
    or every scope is missing."""
    key = "tl_split." + program
    if key not in run:
        run[key] = _step_split(of(run), program)
    return run[key]


def _step_split(sc: Scoped | None, program: str) -> dict | None:
    if sc is None:
        return None
    launches = [
        m for m in sc.modules_named(program)
        if sc.window is None
        or (m.start >= sc.window[0] and m.end <= sc.window[1])
    ]
    if not launches or not any(o.scope for o in sc.ops):
        return None
    starts = [o.start for o in sc.ops]
    rows = []
    for m in launches:
        row = dict.fromkeys((*GROUPS, "other", UNSCOPED), 0)
        i = bisect.bisect_left(starts, m.start)
        while i < len(sc.ops) and sc.ops[i].start < m.end:
            o = sc.ops[i]
            row[group_of(o.scope)] += o.self_ns
            i += 1
        row["total"] = sum(row.values())
        row["scoped"] = row["total"] - row[UNSCOPED]
        row["launch"] = m.dur
        rows.append(row)
    return {k: statistics.median(r[k] for r in rows) for k in rows[0]}


def group_ms(
    run: dict, group: str, program: str = "tl_train_step"
) -> float | None:
    split = step_split(run, program)
    return None if split is None else split[group] / 1e6


STEP, DRAIN = "tl.serve.step", "tl.serve.drain"
# the two spans under which a hot loop dispatches; each lies on the
# thread that dispatches
DISPATCHING = ("tl.train.step", STEP)


def idle_in_program_pct(run: dict) -> float | None:
    """Share of the device's idle time (gaps of ``trace.MIN_GAP_NS``
    and over between instructions inside the window) that lies under
    ``tl.train.step`` or ``tl.serve.step``, less what lies under
    ``tl.serve.drain`` (the host waiting for the device: not the
    host's). No other ``tl.`` span covers: an RPC or a request span
    lasts through many gaps and answers for none. The device's gaps are
    moved onto the host's clock by the middle of ``Scoped.offset``;
    half its width, a tenth of a millisecond to three, is what one gap
    can be off by. ``None`` where the capture does not bracket the
    offset: laid over each other as stamped, the two clocks read 0 on
    a recording whose true share is a third."""
    sc = of(run)
    if sc is None or sc.offset is None:
        return None
    cover = [s for s in sc.spans if s.dur > 0 and s.name in DISPATCHING]
    drains = sc.named(DRAIN)
    if not cover:
        return None
    shift = sum(sc.offset) // 2
    idle = [
        (g0 + shift, g1 + shift) for g0, g1 in trace.gaps(sc.ops)
        if g1 - g0 >= trace.MIN_GAP_NS
    ]
    total = sum(g1 - g0 for g0, g1 in idle)
    if not total:
        return None
    under = sum(
        trace.union_ns(_clip(cover, g0, g1))
        - trace.union_ns(_clip(drains, g0, g1))
        for g0, g1 in idle
    )
    return 100.0 * under / total


def event_values(run: dict, name: str, arg: str) -> list[float]:
    sc = of(run)
    if sc is None:
        return []
    out = []
    for s in sc.named(name):
        try:
            out.append(float(s.args[arg]))
        except (KeyError, TypeError, ValueError):
            pass
    return out


def _per_turn(sc: Scoped | None, child: str) -> list[tuple[Span, int]]:
    """Each ``tl.serve.step`` of the window with the time (ns) of the
    ``child`` spans that start inside it."""
    steps = sc.named(STEP) if sc is not None else []
    kids = sorted(sc.named(child), key=lambda s: s.start) if steps else []
    starts = [k.start for k in kids]
    out = []
    for st in steps:
        i = bisect.bisect_left(starts, st.start)
        inside = 0
        while i < len(kids) and kids[i].start < st.end:
            inside += min(kids[i].end, st.end) - kids[i].start
            i += 1
        out.append((st, inside))
    return out


def sched_host_ms(run: dict) -> float | None:
    """Self time of ``tl.serve.step`` a turn: its duration less the
    ``tl.serve.drain`` inside it (the one child that is the device's
    time), median over the window's turns."""
    turns = _per_turn(of(run), DRAIN)
    if not turns:
        return None
    return statistics.median(st.dur - d for st, d in turns) / 1e6


def phase_ms(run: dict, phase: str) -> float | None:
    """Host time a turn under the ``tl.serve.<phase>`` child of
    ``tl.serve.step``, median over the window's turns that hold the
    phase (a turn with nothing to decode opens no ``decode_dispatch``)."""
    held = [ns for _, ns in _per_turn(of(run), f"tl.serve.{phase}") if ns]
    return statistics.median(held) / 1e6 if held else None
