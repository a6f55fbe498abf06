"""``benchmark/spans.py`` and the readers built on it, on two traces
recorded on a TPU v5e in PR 27 through the benchmark's own drivers (two
layers at the cells' widths: ``Trainer`` steps of 4 x 1,024 tokens as 2
micro-batches; the paged engine serving 6 clients on 4 slots), and on
the two older recordings, whose program had no names of its own."""

import statistics

import pytest

import benchmark.run as runner
from benchmark import spans, trace
from benchmark.tests.test_trace import _unpack

TRAIN_READERS = (
    "attn_device_ms.train", "mlp_device_ms.train",
    "head_loss_device_ms.train", "update_device_ms.train",
)
SERVE_READERS = (
    "sched_host_ms", "queue_wait_p50_s", "ttft_p50_ms",
    "idle_in_program_pct.serve",
)
# tl.serve.step's five children, each with the reader of its host time
PHASE_READERS = {
    "tl.serve.admit": "admit_host_ms",
    "tl.serve.prefill_dispatch": "prefill_dispatch_host_ms",
    "tl.serve.grow_blocks": "grow_blocks_host_ms",
    "tl.serve.decode_dispatch": "decode_dispatch_host_ms",
    "tl.serve.drain": "drain_wait_ms",
}
# (reader, program, group): the engines' programs split by scope
SCOPE_READERS = (
    ("attn_device_ms.decode", "tl_decode", "attn"),
    ("sample_device_ms.decode", "tl_decode", "sample"),
    ("cache_write_device_ms.decode", "tl_decode", "cache_write"),
    ("sample_device_ms.prefill", "tl_prefill_chunk", "sample"),
    ("cache_write_device_ms.prefill", "tl_prefill_chunk", "cache_write"),
)
WAITING = (
    *SERVE_READERS, *PHASE_READERS.values(), *(r for r, _, _ in SCOPE_READERS)
)
CHILDREN = [
    "tl.serve.admit", "tl.serve.prefill_dispatch", "tl.serve.grow_blocks",
    "tl.serve.decode_dispatch", "tl.serve.drain",
]


def _read(name, run):
    return runner.load_reader(name).read(run)


@pytest.fixture(scope="module")
def train(tmp_path_factory):
    path = _unpack("train_2l_scoped.xplane.pb", tmp_path_factory.mktemp("t"))
    return {"tracedir": path, "trace": trace.reduce(path)}


@pytest.fixture(scope="module")
def serve(tmp_path_factory):
    path = _unpack("serve_2l_scoped.xplane.pb", tmp_path_factory.mktemp("s"))
    return {"tracedir": path, "trace": trace.reduce(path)}


def test_varint_fields_and_signed():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64
    b = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62, 0x19]) + bytes(8)
    got = list(spans._fields(b, 0, len(b)))
    assert got[0] == (1, 300) and got[1] == (2, (5, 7)) and got[2][0] == 3
    assert spans._text(b, got[1][1]) == "ab"
    assert spans._signed((1 << 64) - 5) == -5 and spans._signed(7) == 7
    with pytest.raises(ValueError):
        list(spans._fields(bytes([0x0B]), 0, 1))  # a group: not in an XSpace


@pytest.mark.parametrize("path,scope,group", [
    ("jit(tl_train_step)/while/body/closed_call/transpose(jvp(tl.attn))/"
     "tl_flash_bwd_dq/pallas_call", "tl.attn", "attn"),
    ("jit(tl_train_step)/while/body/closed_call/jvp(tl.mlp)/dot_general",
     "tl.mlp", "mlp"),
    ("jit(tl_train_step)/tl.train.optimizer/add", "tl.train.optimizer", "update"),
    ("jit(tl_train_step)/while/body/closed_call/tl.train.accumulate/add",
     "tl.train.accumulate", "update"),
    ("jit(tl_train_step)/while/body/closed_call/jvp(tl.head)/dot_general",
     "tl.head", "head_loss"),
    ("jit(tl_decode)/while/body/tl.serve.sample/argmax", "tl.serve.sample",
     "sample"),
    ("jit(tl_prefill_chunk)/tl.serve.cache_write/scatter",
     "tl.serve.cache_write", "cache_write"),
    ("jit(tl_decode)/tl.serve.retire/select", "tl.serve.retire", "other"),
    ("jit(_step)/while/body/closed_call/transpose(jvp())/dot_general", None,
     "unscoped"),
    ("", None, "unscoped"),
])
def test_innermost_scope_and_group(path, scope, group):
    op = spans.Op("x", 0, 1, path=path)
    assert op.scope == scope and spans.group_of(op.scope) == group


def test_wire_reader_agrees_with_profile_data(train, serve):
    """The same instructions, launches and busy time as ``trace.reduce``
    finds through ``jax.profiler.ProfileData``, and an op path for
    every instruction that the compiler gave one."""
    for run in (train, serve):
        sc, red = spans.of(run), run["trace"]
        assert len(sc.ops) == len(red.ops)
        assert trace.union_ns(sc.ops) == red.busy_ns
        assert [o.self_ns for o in sc.ops[:500]] == [o.self_ns for o in red.ops[:500]]
        assert sc.window[1] - sc.window[0] == red.window_ns
        assert all(isinstance(o.path, str) for o in sc.ops)
        # what carries no path is what the compiler made itself (copies,
        # slices, the glue between fusions), never a kernel
        bare = {trace.kernel_of(o.name) for o in sc.ops if not o.path}
        assert {"copy-done"} <= bare and not any(k.startswith("tl_") for k in bare)
        pathless = sum(o.self_ns for o in sc.ops if not o.path)
        assert pathless < 0.1 * sum(o.self_ns for o in sc.ops)


def test_launches_by_name_are_the_launches_by_kernel(train, serve):
    tr, red = spans.of(train), train["trace"]
    by_kernel = red.modules_holding("tl_flash_bwd_dkv")
    by_name = tr.modules_named("tl_train_step")
    whole = [m for m in by_name if m.start >= tr.window[0] and m.end <= tr.window[1]]
    assert {m.name.split("(")[0] for m in by_name} == {"jit_tl_train_step"}
    assert len(by_name) >= len(by_kernel) >= len(whole) >= 2
    assert {(m.start, m.dur) for m in whole} <= {(m.start, m.dur) for m in by_kernel}
    assert tr.modules_named("tl_train") == []  # the whole name or nothing
    sv, red = spans.of(serve), serve["trace"]
    w0, w1 = sv.window

    def inside(ms):
        return {(m.start, m.dur) for m in ms if m.start >= w0 and m.end <= w1}

    decode = inside(sv.modules_named("tl_decode"))
    prefill = inside(sv.modules_named("tl_prefill_chunk"))
    assert decode and prefill and not decode & prefill
    assert decode <= {(m.start, m.dur) for m in red.modules_holding("tl_decode_glue")}
    assert prefill <= {
        (m.start, m.dur)
        for m in red.modules_holding("tl_paged_decode", without=("tl_decode_glue",))
    }
    assert {m.name.split("(")[0] for m in sv.modules} >= {
        "jit_tl_decode", "jit_tl_prefill_chunk", "jit_tl_pool_table",
    }


def test_scope_metrics_sum_to_the_step(train):
    split = spans.step_split(train)
    four = sum(_read(r, train) for r in TRAIN_READERS)
    rest = split[spans.UNSCOPED] / 1e6
    step_ms = _read("train_step_device_ms", train)
    assert split["other"] == 0
    # (medians over launches, each group's own: equal to a part in 10,000)
    assert four + rest == pytest.approx(split["total"] / 1e6, rel=1e-4)
    assert four + rest == pytest.approx(step_ms, rel=0.005)
    scoped = _read("scoped_device_pct.train", train)
    assert scoped == pytest.approx(100 * four / (four + rest), rel=1e-4)
    assert 90 < scoped <= 100
    assert all(_read(r, train) > 0 for r in TRAIN_READERS)
    # the flash kernels are the attention's: no less than their own time
    kernels = sum(
        e.dur for k in ("tl_flash_fwd", "tl_flash_bwd_dq", "tl_flash_bwd_dkv")
        for e in train["trace"].kernel_events(k)
    ) / len(train["trace"].modules_holding("tl_flash_bwd_dkv")) / 1e6
    assert _read("attn_device_ms.train", train) > 0.9 * kernels


def test_train_host_spans_and_idle_share(train):
    sc = spans.of(train)
    steps = sc.named("tl.train.step")
    assert len(steps) >= 2 and {s.name for s in sc.spans} == {"tl.train.step"}
    # this recording's device clock stands 1.4 to 1.9 ms behind the
    # host's: as stamped, every launch starts BEFORE the span that
    # dispatched it, and the share under the span would read 0
    launches = sc.modules_named("tl_train_step")
    assert all(m.start < s.start < m.end for m, s in zip(launches, steps))
    lo, hi = sc.offset
    assert 1.3e6 < lo < hi < 2.0e6
    # moved by even the least of the bracket, each launch starts after
    # its span began (and within a millisecond of its end: the enqueue
    # is another thread's)
    assert all(
        s.start < m.start + lo and m.start + hi < s.end + 1e6
        for m, s in zip(launches, steps)
    )
    share = _read("idle_in_program_pct.train", train)
    # by hand: gaps of 3.1 ms between steps, 0.9 to 1.1 ms of each
    # under tl.train.step
    assert 25 < share < 35


@pytest.mark.parametrize("pairs,want", [
    # (launch start, launch end, enqueued at, completed at), device / host
    ([(100, 200, 150, 260), (300, 400, 340, 470)], (50, 60)),
    # a launch that waited in the queue bounds nothing: the idle one does
    ([(100, 200, 20, 900), (300, 400, 340, 470)], (40, 70)),
    # bounds that cross are no bracket
    ([(100, 200, 180, 260), (300, 400, 310, 450)], None),
    ([], None),
])
def test_offset_is_bracketed_by_the_tightest_pairs(pairs, want):
    launched = {i: trace.Event("m", a, z - a) for i, (a, z, _, _) in enumerate(pairs)}
    enqueued = {i: p[2] for i, p in enumerate(pairs)}
    completed = {i: p[3] for i, p in enumerate(pairs)}
    assert spans._offset(launched, enqueued, completed) == want
    # a launch whose enqueue or completion fell outside the capture
    assert spans._offset(launched, {}, completed) is None


def test_serve_spans_events_and_waiting_readers(serve):
    sc = spans.of(serve)
    names = {s.name for s in sc.spans}
    assert {"tl.serve.step", *CHILDREN} <= names
    assert names <= {
        "tl.serve.step", *CHILDREN, "tl.serve.admitted", "tl.serve.first_token",
    }
    # children nest inside a step, in order
    step = next(
        s for s in sc.named("tl.serve.step")
        if s.start > sc.window[0] and s.end < sc.window[1]
        and any(c.name == "tl.serve.decode_dispatch" and s.start <= c.start < s.end
                for c in sc.spans)
    )
    kids = [
        c.name for c in sc.spans
        if c.name in CHILDREN and step.start <= c.start and c.end <= step.end
    ]
    assert kids == CHILDREN
    adm = sc.named("tl.serve.admitted")
    first = sc.named("tl.serve.first_token")
    assert adm and first
    assert all(a.args["waited_ms"] >= 0 and a.args["rid"] >= 0 for a in adm)
    waited = {a.args["rid"]: a.args["waited_ms"] for a in adm}
    for f in first:
        assert f.args["ttft_ms"] >= waited.get(f.args["rid"], 0)
    values = {r: _read(r, serve) for r in SERVE_READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["queue_wait_p50_s"] == pytest.approx(
        statistics.median(waited.values()) / 1e3
    )
    assert values["ttft_p50_ms"] == pytest.approx(
        statistics.median(f.args["ttft_ms"] for f in first)
    )
    steps = sc.named("tl.serve.step")
    assert 0 < values["sched_host_ms"] <= statistics.median(s.dur for s in steps) / 1e6
    assert 0 < values["idle_in_program_pct.serve"] < 100
    lo, hi = sc.offset
    assert 0 <= lo < hi < 0.3e6  # this capture's clocks: 0.3 ms at most


def test_each_phase_of_a_turn_has_its_reader(serve):
    sc = spans.of(serve)
    values = {c: _read(r, serve) for c, r in PHASE_READERS.items()}
    assert list(values) == CHILDREN
    assert all(v is not None and v > 0 for v in values.values()), values
    for child, v in values.items():
        assert v == pytest.approx(
            statistics.median(s.dur for s in sc.named(child) if s.dur) / 1e6
        )
    # a turn's own time is its four host phases and what lies between
    four = sum(v for c, v in values.items() if c != "tl.serve.drain")
    assert 0.5 * four < _read("sched_host_ms", serve) < 2 * four
    # the wait is the device's: of the order of the decode chunk it waits for
    decode_ms = statistics.median(
        m.dur for m in sc.modules_named("tl_decode")
    ) / 1e6
    assert 0.3 * decode_ms < values["tl.serve.drain"] < 1.5 * decode_ms


def test_serving_programs_split_by_scope(serve):
    for program in ("tl_decode", "tl_prefill_chunk"):
        split = spans.step_split(serve, program)
        parts = sum(split[g] for g in (*spans.GROUPS, "other", spans.UNSCOPED))
        # (each a median over launches of its own: equal to a part in 1,000)
        assert parts == pytest.approx(split["total"], rel=1e-3)
        assert split["total"] == pytest.approx(split["launch"], rel=0.005)
        assert split["other"] == 0 and split["update"] == 0
        assert split["scoped"] > 0.85 * split["total"]
    got = {r: _read(r, serve) for r, _, _ in SCOPE_READERS}
    for reader, program, group in SCOPE_READERS:
        ns = spans.step_split(serve, program)[group]
        assert got[reader] == (pytest.approx(ns / 1e6) if ns else None), reader
    # the decode chunk is attention first; the engine's own scopes are
    # microseconds (greedy sampling fuses into the head's argmax)
    assert got["attn_device_ms.decode"] > 5
    assert got["sample_device_ms.decode"] is None
    assert 0 < got["cache_write_device_ms.decode"] < 0.1
    assert 0 < got["sample_device_ms.prefill"] < 0.1
    assert 0 < got["cache_write_device_ms.prefill"] < 0.1


@pytest.mark.parametrize("old", ["train_2l.xplane.pb", "serve_2l.xplane.pb"])
def test_every_reader_returns_none_on_the_unscoped_recordings(old, tmp_path):
    run = {"tracedir": _unpack(old, tmp_path)}
    sc = spans.of(run)
    assert sc.ops and not sc.spans and not any(o.scope for o in sc.ops)
    assert sc.modules_named("tl_train_step") == []
    assert sc.offset is not None  # the runtime's events need no tl. name
    for reader in (*TRAIN_READERS, "scoped_device_pct.train",
                   "idle_in_program_pct.train", *WAITING):
        assert _read(reader, run) is None, reader


def test_own_trace_is_the_newest_and_none_without_one(tmp_path):
    import os

    assert spans.own_trace({"tracedir": str(tmp_path)}) is None
    assert spans.of({"tracedir": str(tmp_path)}) is None
    for reader in (*TRAIN_READERS, *WAITING):
        assert _read(reader, {"tracedir": str(tmp_path)}) is None
    a = tmp_path / "cell_a" / "plugins" / "profile" / "1" / "vm.xplane.pb"
    b = tmp_path / "cell_b" / "plugins" / "profile" / "0" / "vm.xplane.pb"
    for i, f in enumerate((b, a)):
        f.parent.mkdir(parents=True)
        f.write_bytes(b"")
        os.utime(f, (1000 + i, 1000 + i))
    assert spans.own_trace({"tracedir": str(tmp_path)}) == str(a)
    assert spans.own_trace({"tracedir": str(b)}) == str(b)


def test_another_cells_capture_is_not_this_runs(train, serve, monkeypatch):
    """Without ``tracedir`` the newest file under ``.trace/`` is taken
    only if it is what ``run["trace"]`` was reduced from."""
    monkeypatch.setattr(spans, "own_trace", lambda run=None: train["tracedir"])
    own = {"trace": train["trace"]}
    assert spans.of(own) is not None
    assert _read("attn_device_ms.train", own) > 0
    other = {"trace": serve["trace"]}  # this run's capture did not write
    assert spans.of(other) is None
    for reader in (*TRAIN_READERS, "idle_in_program_pct.train", *WAITING):
        assert _read(reader, other) is None, reader
