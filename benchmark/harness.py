"""What every driver shares: the device check, the table of peaks, the
kernels found in a compiled program, percentiles, and the decision of
``correct`` from numbers and their limits."""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# the names the repo's Pallas kernels carry in a compiled program
# (copied from chip_smoke.py, which passed on the chip in PR 24)
KERNELS = (
    "tl_paged_decode", "tl_decode_glue",
    "tl_flash_fwd", "tl_flash_bwd_dq", "tl_flash_bwd_dkv",
    "tl_kda_fwd",
)


class BenchFailure(RuntimeError):
    """The run cannot give a result (no chip, a compile in the window)."""


def annot(name: str):
    """A host span in the profiler's own trace (``bench.*``): what the
    harness was doing, on the device's clock. Next to free when no
    trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def open_cell(workload: str, t_start: float, **more):
    """The cell named ``workload`` in BENCHMARK.json with its files
    read, the compile cache on and the chip looked for: what a driver's
    ``run`` takes. The persistent cache lies at a fixed path inside the
    checkout unless the machine names one; the program takes the one it
    is given."""
    import os
    import types

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if work is None:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    limits = json.loads((HERE / "limits" / f"{work['name']}.json").read_text())

    import jax

    from tensorlink_tpu.runtime.compile_cache import enable_compile_cache

    # the cache holds every program of a cell, whatever size the machine
    # caps it at: an LRU cache smaller than a cell's programs evicts one
    # to write the next, and then every run compiles them all again (the
    # Kimi cell's come to 190 MiB; PERF.md section 6, PR 34)
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compile_cache(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    )
    t_chip = time.perf_counter()
    device, peaks = device_or_fail(work["chips"])
    note(phase="chip", imports_s=round(t_chip - t_start, 3),
         chip_s=round(time.perf_counter() - t_chip, 3))
    cell = types.SimpleNamespace(
        name=work["name"],
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=json.loads((HERE / "traffic" / f"{work['traffic']}.json").read_text()),
        limits=limits["limits"], chips=work["chips"], device=device,
        peaks=peaks, tracedir=str(HERE / ".trace" / work["name"]),
        t_start=t_start, trace=False, **more,
    )
    return bench, cell


def note(**facts) -> None:
    """A line of facts that are not metrics, before the last line."""
    print(json.dumps(facts, default=str), flush=True)


def device_or_fail(chips: int) -> tuple[dict, dict]:
    """The device as JAX reports it and its published peaks. Anything
    but ``chips`` TPU chips of a kind in ``peaks.json`` is an error:
    there is no CPU fallback and no default peak."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu" or device["count"] < chips:
        raise BenchFailure(
            f"need {chips} tpu chip(s), jax reports "
            f"{device['count']} x {device['platform']}"
        )
    device["count"] = chips
    return device, peaks_for(device["kind"])


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table or kind == "source":
        raise BenchFailure(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:chips]
    )


def kernels_in(compiled_text: str) -> list[str]:
    """The repo's kernels present as TPU custom calls in a program."""
    return sorted({
        k for line in compiled_text.splitlines()
        if "tpu_custom_call" in line for k in KERNELS if k in line
    })


def gate_reasons() -> list[str]:
    """Why kernel gates closed so far (ops/pallas gate_closed events)."""
    from tensorlink_tpu.runtime.flight import default_recorder

    return sorted({
        f"{e['attrs']['kernel']}: {e['attrs']['reason']}"
        for e in default_recorder().events(kind="kernel.gate_closed")
    })


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of ALL values (no interpolation past
    the data: the 95th of 100 is the 95th smallest)."""
    v = sorted(values)
    if not v:
        raise BenchFailure("no sample to take a percentile of")
    rank = math.ceil(round(q * len(v), 9))  # 0.95 * 20 is 19, not 19.000...4
    return float(v[max(0, min(len(v) - 1, rank - 1))])


def against(numbers: dict, limits: dict) -> dict:
    """``numbers`` beside the cell's limits, as ``decide`` takes them.
    A number with no limit is not compared."""
    return {k: (v, limits[k]) for k, v in numbers.items() if k in limits}


def decide(checks: dict) -> tuple[bool, dict]:
    """``checks``: name -> (number, limit). Correct when every number
    is finite and at or under its limit. Prints each beside its limit
    as the last lines of standard error."""
    out, ok = {}, True
    for name, (value, limit) in checks.items():
        value = float(value)
        good = math.isfinite(value) and value <= limit
        ok &= good
        out[name] = {"value": value, "limit": limit}
        print(
            f"check {name}: {value:.6g} (limit {limit:g})"
            f"{'' if good else '  <-- FAILS'}", file=sys.stderr, flush=True,
        )
    return ok, out


class CompileCounter:
    """Counts XLA compilations (and reads of the persistent cache, which
    are compilations that an earlier run paid for) from now on: the
    measured window must see none."""

    EVENTS = ("backend_compile_duration", "cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith(self.EVENTS):
            self.count += 1

    def none_since(self, count: int) -> None:
        if self.count != count:
            raise BenchFailure(
                f"{self.count - count} compilation(s) inside the measured "
                "window: a shape was not warmed up"
            )
