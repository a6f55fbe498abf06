#!/usr/bin/env python3
"""Readings for a cell's limits, on the chip, at the cell's own size:

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 --modes fp8,int8[,half_batch]

For each seed, in ONE process: the cell's driver as a run drives it
(a short window at the cell's own load; training needs none), then the
plain reference, then the control: the reference computed in the
nearest precision below the configuration's (``fp8``/``int8`` for
bf16), put in the program's place; ``half_batch`` is a training fault
planted in the reference. The program's numbers and each control's go
through the cell's own limits and ``harness.decide``, as a run's do:
the program has to come out correct and every control not correct, or
the exit code is 1. The READING lines are what PERF.md section 2 sets
the limits from. Never part of a measured run: ``run.py`` knows no
``--modes``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--modes", default="fp8")
    args = ap.parse_args(argv)
    from benchmark import harness

    modes = tuple(m for m in args.modes.split(",") if m)
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        _, cell = harness.open_cell(
            args.workload, time.perf_counter(), seed=seed,
            seconds=args.seconds, control_modes=modes,
        )
        driver = importlib.import_module(
            f"benchmark.drivers.{cell.mix['driver']}"
        )
        res = driver.run(cell)
        verdicts = {}
        for who, numbers in {"program": res["numbers"], **res["controls"]}.items():
            print(f"-- {who}, seed {seed}", file=sys.stderr, flush=True)
            ok, _ = harness.decide(harness.against(numbers, cell.limits))
            verdicts[who] = {"correct": ok, **numbers}
            as_expected &= ok == (who == "program")
        print("READING " + json.dumps({
            "workload": cell.name, "seed": seed, **verdicts,
            "end_to_end": res["end_to_end"],
        }), flush=True)
        del res
        gc.collect()
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
