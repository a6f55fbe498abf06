"""Driver ``serve_engine``: the worker's paged serving engine, driven
in-process by one host loop, as ``chip_smoke.py::_run_paged`` builds it
(that construction ran on the chip in PR 24). A closed loop of the
mix's ``clients``; the engine's knobs from the configuration's
``engine`` group.

The client's clock: a request is sent at ``submit`` and is in the
client's hands when ``result`` has returned its finished stream. No
token reaches a client of this system earlier.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmark import harness, trace, traffic, weights
from benchmark.harness import BenchFailure, note


class Loop:
    """The one host loop: every idle client sends, the engine steps,
    what finished is handed over and its client is idle again. ``done``
    collects every request that ended, failed ones too."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, sched, stream, clients):
        self.sched, self.stream = sched, stream
        self.idle = clients
        self.inflight: dict[int, dict] = {}
        self.done: list[dict] = []
        self.samples: list[dict] | None = None  # traced sub-window only

    def _send(self, req) -> None:
        rec = {"ids": req.ids, "sent": self.clock()}
        try:
            with harness.annot("bench.submit"):
                rid = self.sched.submit(req.ids, max_new=req.max_new)
        except Exception as e:  # refused: counts as failed and as the worst
            rec.update(failed=repr(e), tokens=None, done=self.clock())
            self.done.append(rec)
            self.idle += 1
            return
        self.inflight[rid] = rec

    def turn_until(self, t: float) -> float:
        """Turns until the clock passes ``t``; the time it stopped."""
        while self.clock() < t:
            self.turn()
        return self.clock()

    def turn(self) -> None:
        """One turn of the loop."""
        with harness.annot("bench.traffic"):
            n, self.idle = self.idle, 0
            reqs = [next(self.stream) for _ in range(n)]
        for req in reqs:
            self._send(req)
        with harness.annot("bench.step"):
            self.sched.step()
        if self.samples is not None:
            st = self.sched.stats()
            self.samples.append({
                "busy_slots": st["busy_slots"], "slots": st["slots"],
                "prefilling": st.get("prefilling", 0),
                "queued": st["queued"],
                "blocks_in_use": st.get("pool", {}).get("blocks_in_use"),
            })
        with harness.annot("bench.result"):
            for meter in self.sched.drain_meters():
                rec = self.inflight.pop(meter["rid"], None)
                if rec is None:
                    continue
                try:
                    rec["tokens"] = np.asarray(self.sched.result(meter["rid"]))
                    rec["failed"] = None
                except Exception as e:
                    rec["tokens"], rec["failed"] = None, repr(e)
                rec["done"] = self.clock()
                self.done.append(rec)
                self.idle += 1


def build_engine(cfg: dict, seed: int, mix: dict, recorder):
    """Set-up copied from ``chip_smoke.py::serve_kernel_phase`` /
    ``_run_paged``, but with the weights made on the device in the
    served type (a float32 ``model.init`` of 3.75 G parameters would
    not fit the chip) and with no ``TL_*`` switch touched."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import MeshConfig
    from tensorlink_tpu.parallel.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from tensorlink_tpu.parallel.serving import PagedContinuousBatchingEngine
    from tensorlink_tpu.runtime.mesh import make_mesh

    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    model = family.build(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    eng = cfg["engine"]
    if "paged" != eng["kind"] or {eng["sampling"], mix["sampling"]} != {"greedy"}:
        raise BenchFailure("this driver serves the paged engine, greedy")
    t0 = time.perf_counter()
    params = weights.make_tree(seed, shapes, jnp.bfloat16)
    engine = InferenceEngine(
        make_mesh(MeshConfig()), model, params, max_len=eng["max_len"],
        cache_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    del params
    jax.block_until_ready(engine.params)
    t1 = time.perf_counter()
    sched = PagedContinuousBatchingEngine(
        engine, slots=eng["slots"],
        gen=GenerationConfig(
            max_new_tokens=mix["output_tokens"]["max"],
            eos_token_id=mix.get("eos"),
        ),
        block_size=eng["block_size"], decode_chunk=eng["decode_chunk"],
        pipeline_depth=eng["pipeline_depth"],
        prefill_chunk=eng["prefill_chunk"],
        prefix_cache=eng["prefix_cache"], warm_buckets=True,
        recorder=recorder,
    )
    note(
        phase="engine", weights_s=round(t1 - t0, 3),
        engine_s=round(time.perf_counter() - t1, 3),
        compiles=[e["attrs"] for e in recorder.events(kind="serving.compile")],
    )
    return family, sched


def program_kernels(sched) -> dict:
    """Which of the repo's kernels each compiled serving program holds
    (a second lower + compile of what the engine runs: with the
    persistent cache on, a read)."""
    return {
        p["name"]: harness.kernels_in(p["lower"]().compile().as_text())
        for p in sched.audit_programs()
    }


def sample_for_check(done: list[dict], n: int, seed: int) -> list[dict]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    ok = [r for r in done if r["failed"] is None]
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r["ids"]) + len(r["tokens"]))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed), 4])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in pick]


def served_gap(family, cfg, seed, sample, control_modes=()):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample``; the
    number of tokens compared; and, for each of ``control_modes``
    (never in a measured run), the same gap of the token that the
    reference in that lower precision puts first at each position."""
    import jax

    seqs = [np.concatenate([r["ids"], r["tokens"]]) for r in sample]
    n_prompt = [len(r["ids"]) for r in sample]
    score = family.reference().score
    with jax.default_matmul_precision("highest"):
        logits = score(cfg, seed, seqs, n_prompt)
        worst, count = 0.0, 0
        for r, lg in zip(sample, logits):
            tok = np.asarray(r["tokens"])
            gap = lg.max(-1) - lg[np.arange(len(tok)), tok]
            worst, count = max(worst, float(gap.max())), count + len(tok)
        controls = {}
        for mode in control_modes:
            low = score(cfg, seed, seqs, n_prompt, mode=mode)
            controls[mode] = max(
                float((lg.max(-1) - lg[np.arange(len(lg)), lo.argmax(-1)]).max())
                for lg, lo in zip(logits, low)
            )
    return worst, count, controls


def run(cell) -> dict:
    from tensorlink_tpu.runtime.flight import FlightRecorder

    cfg, mix, seed = cell.config, cell.mix, cell.seed
    rec = FlightRecorder()
    family, sched = build_engine(cfg, seed, mix, rec)
    stream = traffic.RequestStream(mix, cfg["vocab_size"], seed)
    if mix["loop"] != "closed":
        raise BenchFailure("this driver drives a closed loop")
    loop = Loop(sched, stream, mix["clients"])
    compiles = harness.CompileCounter()

    # ---- ramp (set-up that the traffic needs): every client sends, and
    # the window opens once each of those first requests has finished,
    # so every slot has turned over
    t_ramp = time.perf_counter()
    loop.turn()
    first = set(loop.inflight)
    while first & set(loop.inflight):
        loop.turn()
    ramp_s = time.perf_counter() - t_ramp
    ramp_done = len(loop.done)

    # ---- the window
    n_compiles = compiles.count
    n_events = rec.counts.get("serving.compile", 0)
    stats0 = sched.stats()
    t_open = time.perf_counter()
    setup_s = t_open - cell.t_start
    t_close = t_open + cell.seconds
    traced = None
    if cell.trace:
        trace_s = min(float(mix.get("trace_seconds", 3)), cell.seconds / 2)
        t_counters_end = loop.turn_until(t_close - trace_s)
        stats1 = sched.stats()
        loop.samples = []
        trace.start(cell.tracedir)
        with harness.annot(trace.WINDOW_SPAN):
            tw0 = time.perf_counter()
            t_close = loop.turn_until(tw0 + trace_s)
        trace.stop()
        traced = {"window_s": t_close - tw0}
    else:
        t_close = t_counters_end = loop.turn_until(t_close)
        stats1 = sched.stats()
    compiles.none_since(n_compiles)
    if rec.counts.get("serving.compile", 0) != n_events:
        raise BenchFailure("the engine compiled inside the measured window")
    mem_peak = harness.memory_peak_bytes(cell.chips)

    in_window = [r for r in loop.done[ramp_done:] if t_open <= r["done"] <= t_close]
    failed = [r for r in in_window if r["failed"] is not None]
    ok = [r for r in in_window if r["failed"] is None]
    window_s = t_close - t_open
    if not ok:
        raise BenchFailure("no request finished inside the window")
    worst = max(r["done"] - r["sent"] for r in in_window)
    lat = [
        (r["done"] - r["sent"]) if r["failed"] is None else worst
        for r in in_window
    ]
    end_to_end = {
        "serve_out_tok_per_s": sum(len(r["tokens"]) for r in ok) / window_s,
        "serve_req_p95_s": harness.percentile(lat, 0.95),
        "setup_s": setup_s,
    }
    counted = [r for r in ok if r["done"] <= t_counters_end]
    counters = {
        "finished": [(len(r["ids"]), len(r["tokens"])) for r in counted],
        "counter_window_s": t_counters_end - t_open,
        "step_samples": loop.samples,
        "prompt_tokens_admitted":
            stats1["prompt_tokens_total"] - stats0["prompt_tokens_total"],
        "prefix_matched_tokens":
            stats1["prefix_matched_tokens"] - stats0["prefix_matched_tokens"],
        "decode_chunk": cfg["engine"]["decode_chunk"],
        "prefill_chunk": cfg["engine"]["prefill_chunk"],
        "memory_peak_bytes": mem_peak,
    }
    note(
        phase="window", ramp_s=round(ramp_s, 3), ramp_requests=ramp_done,
        window_s=round(window_s, 3), finished=len(ok), failed=len(failed),
        req_p50_s=harness.percentile(lat, 0.5),
        engine_admission=stats1.get("admission"),
        pool=stats1.get("pool"), gates_closed=harness.gate_reasons(),
    )
    kernels = program_kernels(sched)
    note(phase="programs", kernels=kernels)

    # ---- correctness: after the window, after the peak was read, with
    # the program's state freed
    check = mix.get("check", {})
    sample = sample_for_check(ok, check.get("requests", 6), seed)
    del sched, loop.sched, loop
    gc.collect()
    t0 = time.perf_counter()
    gap, n_tok, controls = served_gap(
        family, cfg, seed, sample, getattr(cell, "control_modes", ())
    )
    note(phase="check", seconds=round(time.perf_counter() - t0, 3),
         requests=len(sample), served_tokens=n_tok, served_logit_gap=gap,
         controls=controls)
    numbers = {"served_logit_gap": gap}
    return {
        "attempted": len(in_window), "failed": len(failed),
        "end_to_end": end_to_end, "counters": counters, "traced": traced,
        "checks": harness.against(numbers, cell.limits),
        "memory_peak_bytes": mem_peak, "kernels": kernels, "numbers": numbers,
        "controls": {m: {"served_logit_gap": g} for m, g in controls.items()},
    }
