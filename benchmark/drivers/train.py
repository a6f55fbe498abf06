"""Driver ``train``: ``Trainer.train_step`` on one chip, one fresh
batch a step drawn on the host from the seed and handed over inside the
window, the loss read on the host every step (which ends the step), as
``chip_smoke.py::train_phase`` drives it.

Set-up builds ONE trainer and state, drives it through its first
``check.steps`` steps from the seed (the steps the reference follows),
and hands that same object to the window.
"""

from __future__ import annotations

import functools
import gc
import importlib
import time

import numpy as np

from benchmark import balance, harness, trace, traffic, weights
from benchmark.harness import BenchFailure, note

_B1 = 0.9  # Adam's first-moment decay, as the configuration states it


def build_trainer(cfg: dict, mix: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.config import TrainConfig
    from tensorlink_tpu.train.trainer import Trainer, TrainState

    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    model = family.build(cfg)
    hp = cfg["train"]
    if hp["b1"] != _B1:
        raise BenchFailure("the gradient is read back from Adam's m at b1 0.9")
    trainer = Trainer(model, family.train_loss, TrainConfig(
        batch_size=mix["batch_size"], micro_batches=mix["micro_batches"],
        learning_rate=hp["learning_rate"], optimizer=hp["optimizer"],
        weight_decay=hp["weight_decay"], schedule=hp["schedule"],
        warmup_steps=hp["warmup_steps"], grad_clip_norm=hp["clip_norm"],
        dtype=hp["compute_dtype"],
    ))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_tree(seed, shapes, jnp.float32)
    # routed experts: the selection bias from the load on batch 0, before
    # Adam's moments are there; the same numbers go to every tree made
    # from the seed (benchmark/balance.py). No router, nothing runs
    biases = {}
    if hasattr(family, "router_scores"):
        biases = balance.run(family, model, params, cfg, mix, seed)
        params = balance.lay_over(params, biases)
    state = TrainState.create(params, trainer.optimizer)
    return family, model, trainer, state, shapes, biases


def to_device(ids: np.ndarray) -> dict:
    import jax.numpy as jnp

    return {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }


def leaf_norms(tree) -> dict[str, float]:
    """The Euclidean norm of every leaf, by path. One small program."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs
    ])([x for _, x in flat])
    return {
        weights.path_str(p): float(n) for (p, _), n in zip(flat, norms)
    }


def start_tree(seed, shapes, biases):
    """The weights before the first step, made again from the seed,
    with the selection biases the driver holds laid over them."""
    import jax.numpy as jnp

    return balance.lay_over(
        weights.make_tree(seed, shapes, jnp.float32), biases
    )


def delta_norms(params, seed, shapes, biases) -> dict[str, float]:
    """Per leaf, the norm of what training has changed: the weights
    now, less the weights before the first step."""
    import jax

    start = start_tree(seed, shapes, biases)
    diff = jax.jit(
        lambda a, b: jax.tree.map(lambda x, y: x - y, a, b)
    )(params, start)
    del start
    return leaf_norms(diff)


def reference_run(family, cfg, mix, seed, shapes, steps: int, mode=None,
                  rows=None, biases=None) -> dict:
    """The plain reference through the first ``steps`` steps on the same
    batches: each step's loss, the first gradient as the optimizer gets
    it, and the parameters' change, by leaf. ``rows`` keeps only those
    rows of every batch (a planted fault of the control script).
    ``biases`` are the selection biases the program was given (numbers
    by path, no code of the program's): any bias is a valid weight, so a
    fault in the program's forward pass changes at which weights the two
    are compared and hides no gap."""
    import jax
    import jax.numpy as jnp

    ref = family.reference()
    hp = cfg["train"]
    block = mix.get("check", {}).get("rows_per_block", 1)
    vocab = cfg["vocab_size"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, ids, t):
        loss, grads = ref.loss_and_grads(params, ids, cfg, block, mode)
        params, m, v, clipped, norm = ref.adam_step(
            params, m, v, grads, t, hp
        )
        return params, m, v, loss, clipped, norm

    with jax.default_matmul_precision("highest"):
        params = start_tree(seed, shapes, biases)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, norms, grad = [], [], None
        for i in range(steps):
            ids = traffic.train_batch(mix, vocab, seed, i)
            if rows is not None:
                ids = ids[rows]
            params, m, v, loss, clipped, norm = step(
                params, m, v, jnp.asarray(ids), jnp.float32(i + 1)
            )
            losses.append(float(loss))
            norms.append(float(norm))
            if i == 0:
                grad = leaf_norms(clipped)
            del clipped
        del m, v
        delta = delta_norms(params, seed, shapes, biases)
    return {"losses": losses, "norms": norms, "grad": grad, "delta": delta}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's
    norm of one leaf, against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    worst, at = 0.0, ""
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med)
        if not gap <= worst:  # a nan is the worst
            worst, at = gap, n
    return float(worst), at


def compare(prog: dict, ref: dict) -> dict:
    """The numbers of the training check (see PERF.md section 2)."""
    med = float(np.median(list(ref["grad"].values())))
    # leaves whose gradient is nought to rounding in the reference (a
    # key's bias under softmax) move under Adam by round-off alone:
    # left out of the change by a rule on the reference's gradient
    live = {n for n, g in ref["grad"].items() if g >= 1e-3 * med}
    grad_gap, grad_at = worst_leaf_gap(prog["grad"], ref["grad"])
    delta_gap, delta_at = worst_leaf_gap(prog["delta"], ref["delta"], live)
    loss_gap = max(
        abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])
    )
    # the gradient's global norm before clipping, as the program's own
    # step reports it, each of the followed steps
    norm_gap = max(
        abs(a - b) / b for a, b in zip(prog["norms"], ref["norms"])
    )
    note(
        phase="compare", losses=prog["losses"], ref_losses=ref["losses"],
        norms=prog["norms"], ref_norms=ref["norms"],
        grad_gap_at=grad_at, delta_gap_at=delta_at,
        leaves=len(ref["grad"]), leaves_left_out=len(ref["grad"]) - len(live),
    )
    return {
        "loss_gap": loss_gap, "global_norm_gap": norm_gap,
        "grad_norm_gap": grad_gap,
        "delta_norm_gap": delta_gap,
    }


def run(cell) -> dict:
    import jax

    cfg, mix, seed = cell.config, cell.mix, cell.seed
    vocab = cfg["vocab_size"]
    check_steps = mix["check"]["steps"]
    compiles = harness.CompileCounter()
    t0 = time.perf_counter()
    family, model, trainer, state, shapes, biases = build_trainer(
        cfg, mix, seed
    )
    key = jax.random.key(0)  # dropout is 0: the key moves nothing
    jax.block_until_ready(state)
    built_s = time.perf_counter() - t0

    def one_step(state, i):
        with harness.annot("bench.batch"):
            batch = to_device(traffic.train_batch(mix, vocab, seed, i))
        with harness.annot("bench.train_step"):
            state, stats = trainer.train_step(state, batch, key)
            loss = float(stats["loss"])  # the host's read ends the step
        return state, loss, stats

    # ---- the first steps, through the window's own call and feed
    prog = {"losses": [], "norms": []}
    for i in range(check_steps):
        state, loss, stats = one_step(state, i)
        prog["losses"].append(loss)
        prog["norms"].append(float(stats["grad_norm"]))
        if i == 0:
            cold_s = time.perf_counter() - t0
            prog["grad"] = {
                n: g / (1 - _B1)
                for n, g in leaf_norms(state.opt_state["m"]).items()
            }
    prog["delta"] = delta_norms(state.params, seed, shapes, biases)
    state, _, _ = one_step(state, check_steps)  # one more: all is warm
    note(phase="setup", import_s=round(t0 - cell.t_start, 3),
         weights_s=round(built_s, 3), first_step_s=round(cold_s - built_s, 3),
         checked_steps_s=round(time.perf_counter() - t0 - cold_s, 3),
         compilations=compiles.count, losses=prog["losses"])

    # ---- the window
    n_compiles = compiles.count
    tokens_per_step = mix["batch_size"] * mix["seq_len"]
    t_open = time.perf_counter()
    setup_s = t_open - cell.t_start
    t_close = t_open + cell.seconds
    losses: list[float] = []
    traced = None

    def steps_until(t: float, state):
        """Steps until the clock passes ``t``; the time the last ended."""
        t_end = time.perf_counter()
        while t_end < t:
            state, loss, _ = one_step(state, check_steps + 1 + len(losses))
            losses.append(loss)
            t_end = time.perf_counter()
        return state, t_end

    if cell.trace:
        trace_s = min(float(mix.get("trace_seconds", 3)), cell.seconds / 2)
        state, t_counters_end = steps_until(t_close - trace_s, state)
        counter_steps = len(losses)
        trace.start(cell.tracedir)
        with harness.annot(trace.WINDOW_SPAN):
            tw0 = time.perf_counter()
            state, t_end = steps_until(tw0 + trace_s, state)
        trace.stop()
        traced = {"window_s": t_end - tw0}
    else:
        # the rate is over the whole window: the steps that ended in
        # it, over its whole length (the last step ends it)
        state, t_end = steps_until(t_close, state)
        t_counters_end, counter_steps = t_end, len(losses)
    steps = len(losses)
    compiles.none_since(n_compiles)
    if not steps:
        raise BenchFailure("no step ended inside the window")
    window_s = max(t_end - t_open, cell.seconds)
    mem_peak = harness.memory_peak_bytes(cell.chips)
    end_to_end = {
        "train_tok_per_s": steps * tokens_per_step / window_s,
        "setup_s": setup_s,
    }
    counters = {
        "steps": counter_steps, "tokens_per_step": tokens_per_step,
        "counter_window_s": t_counters_end - t_open,
        "seq_len": mix["seq_len"], "memory_peak_bytes": mem_peak,
    }
    note(phase="window", steps=steps, window_s=round(window_s, 3),
         last_loss=losses[-1], finite=bool(np.isfinite(losses).all()),
         gates_closed=harness.gate_reasons())
    batch = to_device(traffic.train_batch(mix, vocab, seed, 0))
    step_prog = trainer.audit_programs(state, batch, key)[0]
    kernels = {"step": harness.kernels_in(
        step_prog["lower"]().compile().as_text()
    )}
    note(phase="programs", kernels=kernels)

    # ---- correctness: after the window, the peak read, the state freed
    del state, trainer, model, batch, step_prog
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_run(
        family, cfg, mix, seed, shapes, check_steps, biases=biases
    )
    numbers = compare(prog, ref)
    note(phase="check", seconds=round(time.perf_counter() - t0, 3), **numbers)
    controls = {}
    for mode in getattr(cell, "control_modes", ()):  # never in a measured run
        how = {"mode": mode}
        if mode == "half_batch":  # a fault, planted in the reference
            how = {"rows": slice(0, mix["batch_size"] // 2)}
        low = reference_run(
            family, cfg, mix, seed, shapes, check_steps, biases=biases, **how
        )
        controls[mode] = compare(low, ref)
    return {
        "attempted": steps, "failed": int(not np.isfinite(losses).all()),
        "end_to_end": end_to_end, "counters": counters, "traced": traced,
        "checks": harness.against(numbers, cell.limits),
        "memory_peak_bytes": mem_peak, "kernels": kernels,
        "controls": controls, "numbers": numbers,
    }
