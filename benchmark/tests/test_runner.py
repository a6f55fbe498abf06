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


# --------------------------------------------- routed experts (balance.py)
def test_kimi_run_balances_and_one_bias_reaches_every_tree(monkeypatch, capsys):
    """A tiny Kimi run through the driver: the ``balance`` note, ``correct``,
    and the same bias leaves, to the bit, in the program's tree, the
    reference's tree and the start that changes are taken from; the
    bias's own change after the three steps is 0 on both sides. (The fp8
    control and the half-batch fault of this family, through the same
    driver with the balance in it: ``test_kimi_linear.py``.)"""
    import jax

    from benchmark import balance, weights
    from benchmark.tests import test_kimi_linear as kimi
    from tensorlink_tpu.train.trainer import TrainState

    made, laid = [], []
    real_run, real_start = balance.run, train.start_tree
    real_create = TrainState.create

    def bias_leaves(tree):
        return {
            weights.path_str(p): np.asarray(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]
            if weights.path_str(p).endswith("router/bias")
        }

    def run(*a):
        made.append(real_run(*a))
        return made[-1]

    def start_tree(*a):
        tree = real_start(*a)
        laid.append(bias_leaves(tree))
        return tree

    def create(params, optimizer):  # the program's tree, before Adam's
        laid.append(bias_leaves(params))
        return real_create(params, optimizer)

    monkeypatch.setattr(balance, "run", run)
    monkeypatch.setattr(train, "start_tree", start_tree)
    monkeypatch.setattr(TrainState, "create", create)
    cell = tiny.cell(kimi.KIMI_TINY, kimi.KIMI_MIX, kimi.LIMITS, seed=6,
                     seconds=0.3)
    cell.name = "kimi-linear-l5e8.train_lm_s4096"
    line = _line(cell, train.run(cell))
    assert line["correct"] is True and line["failed"] == 0
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"phase"')]
    (note,) = [n for n in notes if n["phase"] == "balance"]
    assert len(note["layers"]) == 4 and note["rounds"] == balance.ROUNDS
    assert note["mean_load"] == 128.0
    assert all(abs(n - 512) <= 4 for n in note["held_routes"])
    assert all(n >= 126 for n in note["expert_least"])
    assert all(n <= 130 for n in note["expert_most"])
    phases = [n["phase"] for n in notes]
    assert phases.index("balance") < phases.index("setup")
    # the program's tree, the program's delta start, the reference's
    # start, the reference's delta start: one set of numbers
    (biases,) = made
    assert len(laid) == 4
    for tree in laid:
        assert set(tree) == set(biases)
        for path, b in biases.items():
            assert tree[path].tobytes() == b.tobytes()
    compare = next(n for n in notes if n["phase"] == "compare")
    assert compare["leaves_left_out"] >= 4  # the four biases among them


def test_bias_change_after_three_steps_is_nought():
    """The bias chooses only: no gradient, so Adam leaves it where the
    balance put it, and a start tree without the balance's numbers
    would read the whole difference as a change."""
    from benchmark.tests import test_kimi_linear as kimi

    cfg, mix, seed = kimi.KIMI_TINY, kimi.KIMI_MIX, 6
    _, _, trainer, state, shapes, biases = train.build_trainer(cfg, mix, seed)
    import jax

    key = jax.random.key(0)
    for i in range(3):
        batch = train.to_device(train.traffic.train_batch(
            mix, cfg["vocab_size"], seed, i))
        state, _ = trainer.train_step(state, batch, key)
    delta = train.delta_norms(state.params, seed, shapes, biases)
    seeded = train.delta_norms(state.params, seed, shapes, {})
    for path in biases:
        assert delta[path] == 0.0
        assert seeded[path] > 0.01
    moved = [n for n, d in delta.items() if d > 0]
    assert len(moved) == len(delta) - len(biases)


_GPT2_RUN = """
import json, sys
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_enable_compilation_cache", False)
from benchmark import balance
from benchmark.drivers import train
from benchmark.tests import tiny

def never(*a, **kw):
    raise AssertionError("the balance ran for a family with no router")

balance.run = balance.solve = balance.loads = never
cell = tiny.cell(tiny.GPT2_TINY, tiny.TRAIN_TINY, tiny.TRAIN_LIMITS, seed=3,
                 seconds=0.3)
res = train.run(cell)
print(json.dumps({{"phase": "done", "attempted": res["attempted"]}}))
"""


def test_gpt2_run_compiles_what_it_compiled_before():
    """No router, nothing runs: no function of ``balance.py`` that
    compiles is called, and the tiny GPT-2 run's set-up counts one
    compilation fewer than the parent of PR 34 counted for the same run
    in a fresh process (17, measured on its checkout: jax 0.9.0, compile
    cache off): the tree's builder is kept, so the start that changes
    are taken from is not compiled again. (In a process that has run
    other tests the count is smaller, which is why this one starts its
    own.)"""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _GPT2_RUN.format(root=str(harness.ROOT))],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    notes = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith('{"phase"')]
    assert notes[-1]["phase"] == "done" and notes[-1]["attempted"] >= 1
    assert not any(n["phase"] == "balance" for n in notes)
    setup = next(n for n in notes if n["phase"] == "setup")
    assert setup["compilations"] == 17 - 1


def test_open_cell_lifts_the_machines_cap_on_the_compile_cache(monkeypatch):
    """A machine may cap JAX's cache (``JAX_COMPILATION_CACHE_MAX_SIZE``)
    under what one cell's programs come to; the LRU then evicts one
    program to write the next and every run compiles again (PR 34, on
    the chip: set-up 133 s for 42). The harness lifts the cap before
    the cache is opened."""
    import jax

    from tensorlink_tpu.runtime import compile_cache

    opened = []
    monkeypatch.setattr(
        compile_cache, "enable_compile_cache",
        lambda d: opened.append(jax.config.jax_compilation_cache_max_size))
    monkeypatch.setattr(
        harness, "device_or_fail",
        lambda chips: ({"platform": "tpu", "kind": "TPU v5 lite",
                        "count": chips}, harness.peaks_for("TPU v5 lite")))
    before = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", 192 << 20)
    try:
        _, cell = harness.open_cell(
            "kimi-linear-l5e8.train_lm_s4096", 0.0, seed=1, seconds=1.0)
        assert opened == [-1]
    finally:
        jax.config.update("jax_compilation_cache_max_size", before)
    assert cell.mix["driver"] == "train" and cell.chips == 1


def test_kernels_in_names_the_kda_forward_kernel():
    text = (
        '%a = f32[4] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(tl_train_step)/tl_kda_fwd"}\n'
        '%b = f32[4] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(tl_train_step)/tl_flash_fwd"}\n'
        '%c = f32[4] fusion(%x), metadata={op_name="tl_flash_bwd_dq"}\n'
    )
    assert harness.kernels_in(text) == ["tl_flash_fwd", "tl_kda_fwd"]


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
