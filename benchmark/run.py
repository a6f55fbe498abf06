#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. It knows no model and no
cell by name: ``BENCHMARK.json`` names the cell's configuration file
and traffic mix; the mix's file names its driver
(``benchmark/drivers/<driver>.py``), the configuration's file its
family (``benchmark/families/<family>.py`` and
``benchmark/reference/<family>.py``); the cell's limits are in
``benchmark/limits/<cell>.json``; each per-layer metric has a reader,
``benchmark/layer_metrics/<metric>.py``. It fails without a TPU or on a
device kind that ``benchmark/peaks.json`` does not hold.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))


def load_reader(metric: str):
    path = HERE / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + metric.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reported(metric: dict, cell: str, cell_e2e: set) -> bool:
    """Whether ``metric`` (an entry of BENCHMARK.json) is this cell's."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in cell_e2e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program under test, from this checkout and nowhere else
    import tensorlink_tpu  # noqa: F401  (absent: exit non-zero, no result)
    from benchmark import harness

    try:
        bench, cell = harness.open_cell(
            args.workload, T0, seed=args.seed, seconds=args.seconds
        )
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    cell.trace = bool(args.trace)
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.mix['driver']}"
    )
    try:
        res = driver.run(cell)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4

    line = result_line(bench, cell, res)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def result_line(bench: dict, cell, res: dict) -> dict:
    """The contract's last line, from what the driver measured."""
    from benchmark import harness, trace

    device = dict(cell.device, memory_peak_bytes=res["memory_peak_bytes"])
    e2e = {
        m["name"]: m for m in bench["end_to_end"]
        if m["name"] in res["end_to_end"]
        and cell.name in m.get("workloads", [cell.name])
    }
    metrics, extra = {}, {}
    if not cell.trace:
        for name, m in e2e.items():
            metrics[name] = {"value": res["end_to_end"][name], "unit": m["unit"]}
    else:
        red = trace.reduce(trace.newest(cell.tracedir), cell.chips)
        # the traced window on the trace's own clock (the harness's span
        # around its loop); the host's clock only if that span is missing
        window_s = (
            red.window_ns / 1e9 if red.window_ns
            else res["traced"]["window_s"]
        )
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = window_s
        extra["breakdown"] = trace.breakdown(red)
        run = {
            "trace": red, "window_s": window_s,
            "counters": res["counters"], "config": cell.config,
            "mix": cell.mix, "peaks": cell.peaks, "chips": cell.chips,
        }
        for m in bench["per_layer"]:
            if not reported(m, cell.name, set(e2e)):
                continue
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    ok, checks = harness.decide(res["checks"])
    return {
        "correct": bool(ok), "attempted": res["attempted"],
        "failed": res["failed"], "metrics": metrics, "device": device,
        **extra, "checks": checks,
    }


if __name__ == "__main__":
    sys.exit(main())
