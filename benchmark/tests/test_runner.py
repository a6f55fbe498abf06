"""The runner end to end at tiny sizes on the CPU: the same drivers,
references, generator and result line as on the chip, with the look
for a chip skipped. Then the same run with the timed path broken
underneath, once for each fault a cell can have, and the control (the
reference in fp8 put in the program's place): ``correct`` has to come
out false for every one of them."""

import json

import numpy as np
import pytest

import benchmark.run as runner
from benchmark import harness
from benchmark.drivers import serve_engine, train
from benchmark.tests import tiny

BENCH = tiny.bench_with_serving()


def _line(cell, res):
    return runner.result_line(BENCH, cell, res)


# ------------------------------------------------------------------ serving
def _serve(requests=None, **kw):
    mix = tiny.SERVE_TINY
    if requests:  # how many finished requests the check samples
        mix = {**mix, "check": {"requests": requests}}
    cell = tiny.cell(tiny.LLAMA_TINY, mix, tiny.SERVE_LIMITS, **kw)
    cell.name = "mistral7b-l16.decode_heavy"  # to pick the cell's metrics
    return cell, serve_engine.run(cell)


def test_serve_runs_and_is_correct(capsys):
    cell, res = _serve(seed=2**31 + 77, seconds=1.0)
    line = _line(cell, res)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert set(line["metrics"]) == {
        "serve_out_tok_per_s", "serve_req_p95_s", "setup_s",
    }
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"]["served_logit_gap"]["limit"] == 0.1
    assert "check served_logit_gap" in capsys.readouterr().err.splitlines()[-1]


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from tensorlink_tpu.parallel.serving import PagedContinuousBatchingEngine

    real = PagedContinuousBatchingEngine.result

    def altered(self, rid, **kw):
        toks = np.array(real(self, rid, **kw))
        toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % 128
        return toks

    monkeypatch.setattr(PagedContinuousBatchingEngine, "result", altered)
    cell, res = _serve(seed=5, seconds=0.5)
    assert _line(cell, res)["correct"] is False


@pytest.mark.parametrize("seed", [6, 2**31 + 8])
def test_serve_control_fp8_is_not_correct(seed):
    """The control need not decode: at each position of the run's own
    prompts and served tokens, the gap of the token fp8 puts first,
    through the cell's limit and ``decide`` as ``controls.py`` does.
    A widest gap swings over a few dozen tokens (fp8 read 0.04 to 0.56
    over 4 requests here, 0.31 to 0.94 over 16), so this samples 16."""
    cell, res = _serve(
        seed=seed, seconds=0.5, requests=16, control_modes=("fp8",)
    )
    assert harness.decide(res["checks"])[0] is True
    low = res["controls"]["fp8"]
    assert harness.decide(harness.against(low, cell.limits))[0] is False
    assert res["numbers"]["served_logit_gap"] < low["served_logit_gap"] / 3


# ----------------------------------------------------------------- training
def _train(**kw):
    cell = tiny.cell(tiny.GPT2_TINY, tiny.TRAIN_TINY, tiny.TRAIN_LIMITS, **kw)
    cell.name = "gpt2-medium.train_lm_s1024"
    return cell, train.run(cell)


def test_train_runs_and_is_correct():
    cell, res = _train(seed=3, seconds=0.5)
    line = _line(cell, res)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tok_per_s", "setup_s"}
    assert set(line["checks"]) == set(tiny.TRAIN_LIMITS)


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    from tensorlink_tpu.train.trainer import Trainer

    def stuck(self, state, batch, rng):
        loss = self.eval_loss(state, batch, rng)
        return state, {"loss": loss, "grad_norm": loss * 0}

    monkeypatch.setattr(Trainer, "train_step", stuck)
    cell, res = _train(seed=4, seconds=0.2)
    line = _line(cell, res)
    assert line["correct"] is False
    # a state left unchanged reads 1 by the measure
    assert line["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out(monkeypatch):
    import jax

    from tensorlink_tpu.train.trainer import Trainer

    real = Trainer.train_step

    def half(self, state, batch, rng):
        batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return real(self, state, batch, rng)

    monkeypatch.setattr(Trainer, "train_step", half)
    cell, res = _train(seed=4, seconds=0.2)
    line = _line(cell, res)
    assert line["correct"] is False
    assert line["checks"]["global_norm_gap"]["value"] > 10 * 0.002


@pytest.mark.parametrize("seed", [1, 2])
def test_train_control_fp8_is_not_correct(seed):
    import jax

    from benchmark.families import gpt2

    cfg, mix = tiny.GPT2_TINY, tiny.TRAIN_TINY
    shapes = jax.eval_shape(gpt2.build(cfg).init, jax.random.key(0))
    ref = train.reference_run(gpt2, cfg, mix, seed, shapes, 3)
    low = train.reference_run(gpt2, cfg, mix, seed, shapes, 3, mode="fp8")
    ok, _ = harness.decide({
        k: (v, tiny.TRAIN_LIMITS[k]) for k, v in train.compare(low, ref).items()
    })
    assert ok is False


def test_controls_script_passes_every_reading_through_decide(monkeypatch, capsys):
    """``controls.py`` keeps the cell's limits: the program comes out
    correct, the control and the fault not, and that is its exit code."""
    from benchmark import controls

    def open_tiny(workload, t_start, **more):
        cell = tiny.cell(tiny.GPT2_TINY, tiny.TRAIN_TINY, tiny.TRAIN_LIMITS, **more)
        return BENCH, cell

    monkeypatch.setattr(harness, "open_cell", open_tiny)
    argv = ["--workload", "tiny", "--seeds", "1", "--seconds", "0.2"]
    assert controls.main(argv + ["--modes", "fp8,half_batch"]) == 0
    out = capsys.readouterr()
    reading = json.loads(out.out.split("READING ")[-1].splitlines()[0])
    assert reading["program"]["correct"] is True
    assert reading["fp8"]["correct"] is False
    assert reading["half_batch"]["correct"] is False
    assert "FAILS" in out.err
    # a control that the limits let through is a failure of the script
    monkeypatch.setattr(
        tiny, "TRAIN_LIMITS", {k: 1e9 for k in tiny.TRAIN_LIMITS}
    )
    assert controls.main(argv + ["--modes", "fp8"]) == 1


# ------------------------------------------------------------- the command
def test_command_fails_without_a_tpu():
    """``run.py`` itself has no CPU switch: off a TPU it prints no
    result and exits non-zero."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2-medium.train_lm_s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode not in (0, 1), out.stderr[-500:]
    assert "need 1 tpu chip" in out.stderr
    assert not out.stdout.strip().startswith('{"correct"')
