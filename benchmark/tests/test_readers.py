"""Every per-layer reader that BENCHMARK.json names exists, reads the
recorded traces and the drivers' counters, and returns nothing (never
0) where there is nothing to read."""

import json
import types

import pytest

import benchmark.run as runner
from benchmark import harness, trace
from benchmark.tests import tiny
from benchmark.tests.test_trace import _unpack

BENCH = tiny.bench_with_serving()
PEAKS = harness.peaks_for("TPU v5 lite")


def _cfg(name):
    conf = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((harness.ROOT / conf["file"]).read_text())


def _mix(name):
    return json.loads((harness.HERE / "traffic" / f"{name}.json").read_text())


def _read(name, run):
    return runner.load_reader(name).read(run)


def test_every_metric_has_a_reader_and_a_cell():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert callable(runner.load_reader(m["name"]).read)
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).exists()
    for w in BENCH["workloads"]:
        assert (harness.HERE / "limits" / f"{w['name']}.json").exists()
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").exists()


def test_serve_readers_on_the_recorded_trace(tmp_path):
    red = trace.reduce(_unpack("serve_2l.xplane.pb", tmp_path))
    cfg = dict(_cfg("mistral7b-l16"), num_hidden_layers=2)
    run = {
        "trace": red, "window_s": 7.0, "config": cfg,
        "mix": _mix("decode_heavy"), "peaks": PEAKS, "chips": 1,
        "counters": {
            "finished": [(100, 24)] * 8, "counter_window_s": 7.0,
            "step_samples": [
                {"busy_slots": 8, "slots": 8, "prefilling": 0, "queued": 0,
                 "blocks_in_use": 64}
            ] * 4,
            "prompt_tokens_admitted": 800, "prefix_matched_tokens": 0,
            "decode_chunk": 8, "prefill_chunk": 32,
            "memory_peak_bytes": 4e9,
        },
    }
    assert _read("device_idle_pct.serve", run) == pytest.approx(
        100 * (1 - 6.6055575 / 7.0)
    )
    # 33 launches of the decode program, 196 ms each
    assert _read("decode_program_ms", run) == pytest.approx(196.2, rel=0.01)
    assert 1.5 < _read("prefill_device_pct", run) < 2.5
    # 8 rows x 113 live tokens x 4 KiB a call against 10.2 ms: a
    # thousandth of the roofline, and never 0 or above 100
    assert 0.02 < _read("paged_decode_roofline", run) < 0.2
    assert _read("slots_occupied_pct", run) == 100.0
    assert _read("prefix_hit_pct", run) == 0.0
    assert _read("peak_hbm_pct.serve", run) == 25.0
    mfu = _read("mfu_pct.serve", run)
    # 8 x 124 tokens x 2 x 0.567 G parameters over 7 s of 197 TFLOP/s
    assert mfu == pytest.approx(100 * 8 * 124 * 2 * 0.5673e9 / (7 * 197e12), rel=0.02)


def test_train_readers_on_the_recorded_trace(tmp_path):
    red = trace.reduce(_unpack("train_2l.xplane.pb", tmp_path))
    cfg = dict(_cfg("gpt2-medium"), n_layer=2)
    mix = dict(_mix("train_lm_s1024"), batch_size=4, micro_batches=2)
    run = {
        "trace": red, "window_s": 0.08, "config": cfg, "mix": mix,
        "peaks": PEAKS, "chips": 1,
        "counters": {"steps": 3, "tokens_per_step": 4096, "seq_len": 1024,
                     "counter_window_s": 0.08, "memory_peak_bytes": 8e9},
    }
    assert _read("train_step_device_ms", run) == pytest.approx(21.1, rel=0.02)
    assert 5 < _read("flash_roofline", run) < 40
    assert 10 < _read("mfu_pct.train", run) < 60
    assert _read("device_idle_pct.train", run) == pytest.approx(
        100 * (1 - 0.0632519 / 0.08), rel=1e-3
    )
    assert _read("peak_hbm_pct.train", run) == 50.0


@pytest.mark.parametrize("name,moves", [
    (m["name"], m["moves"]) for m in BENCH["per_layer"]
    if not m["name"].startswith("device_idle")
])
def test_nothing_to_read_returns_nothing(name, moves):
    empty = types.SimpleNamespace(
        ops=[], modules=[], spans=[], busy_ns=0, chips=1,
        kernel_events=lambda k: [], modules_holding=lambda *a, **k: [],
    )
    run = {"trace": empty, "window_s": 1.0, "counters": {}, "peaks": PEAKS,
           "chips": 1, "config": _cfg("mistral7b-l16"),
           "mix": _mix("train_lm_s1024")}
    if moves == "train_tok_per_s":
        run["config"] = _cfg("gpt2-medium")
    assert _read(name, run) is None
