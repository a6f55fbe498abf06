"""The reduction from trace to numbers, on synthetic intervals and on
two small traces recorded on a TPU v5e in PR 26 (two layers at the
cells' widths: the paged engine serving 8 requests, three ``Trainer``
steps)."""

import gzip
import shutil
from pathlib import Path

import pytest

from benchmark import trace
from benchmark.trace import Event

DATA = Path(__file__).parent / "data"


def _unpack(name, tmp_path):
    out = tmp_path / name
    with gzip.open(DATA / f"{name}.gz") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


def test_union_gaps_and_self_time():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5),
          Event("d", 31, 2)]
    assert trace.union_ns(ev) == 15 + 5
    assert trace.gaps(ev) == [(15, 30)]
    trace.self_times(ev)
    assert [e.self_ns for e in ev] == [10, 10, 3, 2]


@pytest.mark.parametrize("text,kernel", [
    ("%tl_paged_decode.2 = bf16[1,32]{1,0} custom-call(%x)", "tl_paged_decode"),
    ("%jvp_tl_flash_fwd_.3 = bf16[2] custom-call(%q)", "tl_flash_fwd"),
    ("%transpose_jvp_tl_flash_bwd_dq__.1 = f32[2] custom-call(%q)", "tl_flash_bwd_dq"),
    # an instruction that READS the kernel's result is not the kernel
    ("%fusion.9 = bf16[2] fusion(%tl_paged_decode.2)", "fusion"),
])
def test_kernel_of(text, kernel):
    assert trace.kernel_of(text) == kernel


def test_train_trace(tmp_path):
    red = trace.reduce(_unpack("train_2l.xplane.pb", tmp_path))
    # 3 steps x 2 layers x 2 micro-batches of each flash kernel
    for k in ("tl_flash_fwd", "tl_flash_bwd_dq", "tl_flash_bwd_dkv"):
        assert len(red.kernel_events(k)) == 12
    assert len(red.modules) == 3 == len(red.modules_holding("tl_flash_bwd_dkv"))
    assert [s.name for s in red.spans] == ["bench.step"] * 3
    busy = red.busy_ns / 1e9
    assert busy == pytest.approx(0.0632519, rel=1e-4)
    # a program's instructions lie inside its launch
    assert busy <= sum(m.dur for m in red.modules) / 1e9 * 1.001
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) == 10 and bd["device_ops"][0][0] == "fusion"
    assert sum(s for _, s in bd["device_ops"]) <= busy
    assert bd["idle_gaps"][0][0] == "bench.step"


def test_clipped_to_the_harness_window(tmp_path):
    """The device runs on past the host's last turn and the profiler
    starts before the loop: with a window span, busy time is what lies
    inside it and can never pass it."""
    path = _unpack("serve_2l.xplane.pb", tmp_path)
    whole = trace.reduce(path)
    red = trace.reduce(path, window_span="bench.go")
    assert whole.window_ns is None and red.window_ns == 6685852350 - 50411886
    assert 0 < red.busy_ns <= red.window_ns and red.busy_ns <= whole.busy_ns
    assert red.spans == []  # the window span itself is no gap's owner
    ev = [Event("a", 0, 10), Event("b", 20, 10), Event("c", 40, 10)]
    assert [(e.start, e.dur) for e in trace.clip(ev, 5, 45)] == [
        (5, 5), (20, 10), (40, 5)
    ]
    # the training trace: cut to its second step
    path = _unpack("train_2l.xplane.pb", tmp_path)
    step2 = trace.reduce(path).spans[1]
    one = trace.reduce(path)  # no window span: everything
    assert len(one.modules) == 3
    cut = trace.clip(one.modules, step2.start, step2.end)
    assert 1 <= len(cut) <= 2 and sum(m.dur for m in cut) <= step2.dur


def test_serve_trace(tmp_path):
    red = trace.reduce(_unpack("serve_2l.xplane.pb", tmp_path))
    calls = red.kernel_events("tl_paged_decode")
    decode = red.modules_holding("tl_decode_glue")
    prefill = red.modules_holding("tl_paged_decode", without=("tl_decode_glue",))
    # 33 decode chunks of 8 steps x 2 layers, 32 prefill chunks x 2 layers
    assert (len(decode), len(prefill)) == (33, 32)
    assert len(calls) == 33 * 16 + 32 * 2
    assert len(red.kernel_events("tl_decode_glue")) == 33 * 16
    assert sum(e.dur for e in calls) / 1e9 == pytest.approx(6.0354, rel=1e-3)
    assert trace.breakdown(red)["device_ops"][0][0] == "tl_paged_decode"
