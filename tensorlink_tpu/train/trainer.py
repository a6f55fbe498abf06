"""Single-host training engine: TrainState + jit train step.

Replaces the reference's thread-per-micro-batch forward/backward with one
jit-compiled step; micro-batching for gradient accumulation is a lax.scan
(pipeline micro-batching lives in parallel/pp.py). The loss/grad math runs
in the configured compute dtype (bf16 on TPU) with f32 params + f32
optimizer state.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from tensorlink_tpu.config import TrainConfig
from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.runtime.tracing import region, scope
from tensorlink_tpu.train.optim import (
    Optimizer,
    apply_updates,
    clip_by_global_norm,
    make_optimizer,
    make_schedule,
)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE; labels are int ids. Computed in f32."""
    with scope("loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - ll)


def mse_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    with scope("loss"):
        return jnp.mean(jnp.square(pred.astype(jnp.float32) - target.astype(jnp.float32)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: Optimizer) -> "TrainState":
        return cls(
            params=params,
            opt_state=optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
        )


def finish_step(cfg: TrainConfig, optimizer: Optimizer, state, loss, grads):
    """What every train step does with its loss and gradients, traced
    inside the step program (Trainer and ShardedTrainer alike): the
    non-finite sentinel, clipping, the optimizer, and the new state."""
    # non-finite sentinel, in-jit and BEFORE clipping (clipping a
    # tree with an inf leaf turns the norm nan and poisons every
    # grad — the flag must name the raw anomaly): one all-reduce
    # over grad leaves + the loss scalar, no host sync here
    with scope("train.sentinel"):
        grads_finite = jax.tree_util.tree_reduce(
            lambda a, g: a & jnp.isfinite(g).all(),
            grads,
            jnp.array(True),
        )
        nonfinite = ~(jnp.isfinite(loss) & grads_finite)
    if cfg.grad_clip_norm:
        with scope("train.clip"):
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    else:
        gnorm = jnp.zeros(())
    with scope("train.optimizer"):
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        if cfg.train_only == "lora":
            # AdamW's decoupled weight decay would otherwise shrink
            # frozen weights with zero grad
            from tensorlink_tpu.nn.lora import mask_to_lora

            updates = mask_to_lora(updates)
        params = apply_updates(state.params, updates)
        new_state = TrainState(
            params=params, opt_state=opt_state, step=state.step + 1
        )
        if cfg.skip_nonfinite_updates:
            # select the OLD state wholesale (params, moments, step): a
            # poisoned batch must leave no trace in the model — not even
            # an optimizer-moment update or a schedule tick
            new_state = jax.tree.map(
                lambda new, old: jnp.where(nonfinite, old, new),
                new_state,
                state,
            )
    return new_state, {
        "loss": loss,
        "grad_norm": gnorm,
        "nonfinite": nonfinite,
    }


class Trainer:
    """Builds jit train/eval steps for a (module, loss_fn) pair.

    loss_fn(module, params, batch, rng) -> scalar loss. The Trainer handles
    optimizer state, grad clipping, dtype policy, and optional gradient
    accumulation over micro-batches.
    """

    def __init__(
        self,
        module: Module,
        loss_fn: Callable,
        cfg: TrainConfig = TrainConfig(),
        optimizer: Optimizer | None = None,
        donate: bool = True,
        tracer=None,
        metrics=None,
        flight=None,
    ):
        self.module = module
        self.loss_fn = loss_fn
        self.cfg = cfg
        # observability (runtime/tracing.Tracer + runtime/metrics.Metrics,
        # both optional): train_step emits trainer.compile_step /
        # trainer.step spans and step_s / step_seconds metrics; wrap the
        # batch fetch in data_span() to see input-pipeline stalls on the
        # same timeline
        self.tracer = tracer
        self.metrics = metrics
        # flight recorder (runtime/flight.py): non-finite loss/grad
        # anomalies become black-box events. Telemetry-enabled trainers
        # default to the process recorder — the host-side stats read the
        # anomaly check needs is only paid when telemetry is on anyway.
        if flight is None and (tracer is not None or metrics is not None):
            from tensorlink_tpu.runtime.flight import default_recorder

            flight = default_recorder()
        self.flight = flight
        self._telemetry = None
        self._timer = None
        if tracer is not None or metrics is not None:
            from tensorlink_tpu.runtime.profiling import DispatchTimer
            from tensorlink_tpu.runtime.tracing import StepTelemetry

            self._telemetry = StepTelemetry(tracer, metrics, "trainer")
            # per-step device-busy vs host-gap attribution: the
            # telemetry path already syncs per step (the non-finite
            # check below), so the device timer rides that sync — an
            # uninstrumented trainer stays fully async and untimed
            self._timer = DispatchTimer(metrics=metrics)
        if cfg.fsdp:
            # same convention as the train_only guard: a mode this class
            # cannot honor must fail loudly, not run silently replicated
            raise ValueError(
                "TrainConfig(fsdp=True) has no effect on the single-host "
                "Trainer: wrap its ._step with "
                "parallel.dp.fsdp_train_step(step, mesh, state) (which "
                "shards params+moments over the data axis), or use "
                "ShardedTrainer on a mesh with a data axis"
            )
        sched = make_schedule(
            cfg.schedule, cfg.learning_rate, cfg.warmup_steps, cfg.total_steps
        )
        self.optimizer = optimizer or make_optimizer(
            cfg.optimizer, sched, cfg.weight_decay,
            moment_dtype=cfg.opt_moment_dtype,
        )
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self.donate = bool(donate)

        # the function's name is the program's: launches read
        # jit_tl_train_step in a device trace
        def tl_train_step(state, batch, rng):
            return self._step(state, batch, rng)

        self._train_step = jax.jit(
            tl_train_step, donate_argnums=(0,) if donate else ()
        )
        self._eval_step = jax.jit(self._eval)

    # -- state ----------------------------------------------------------
    def init_state(self, key: jax.Array) -> TrainState:
        params = self.module.init(key)
        return TrainState.create(params, self.optimizer)

    # -- inner step (traced) --------------------------------------------
    def _loss_for_grad(self, params, batch, rng):
        with scope("train.cast"):
            cast = jax.tree.map(
                lambda x: x.astype(self.compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                params,
            )
        return self.loss_fn(self.module, cast, batch, rng)

    def _step(self, state: TrainState, batch, rng):
        micro = self.cfg.micro_batches

        if micro <= 1:
            loss, grads = jax.value_and_grad(self._loss_for_grad)(
                state.params, batch, rng
            )
        else:
            # gradient accumulation over micro-batches via scan
            def micro_batches(b):
                return jax.tree.map(
                    lambda x: x.reshape(micro, x.shape[0] // micro, *x.shape[1:]), b
                )

            mb = micro_batches(batch)
            with scope("train.accumulate"):
                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params
                )

            def body(acc, xs):
                mb_i, r = xs
                loss_i, g = jax.value_and_grad(self._loss_for_grad)(
                    state.params, mb_i, r
                )
                with scope("train.accumulate"):
                    acc = jax.tree.map(
                        lambda a, gi: a + gi.astype(jnp.float32) / micro,
                        acc, g,
                    )
                return acc, loss_i

            rngs = jax.random.split(rng, micro)
            grads, losses = jax.lax.scan(body, zero, (mb, rngs))
            loss = jnp.mean(losses)

        if self.cfg.train_only == "lora":
            # mask GRADS before clipping/optimizer (frozen params must
            # not pollute the clip norm or accumulate moments);
            # finish_step masks the final updates too
            from tensorlink_tpu.nn.lora import mask_to_lora

            grads = mask_to_lora(grads)
        return finish_step(self.cfg, self.optimizer, state, loss, grads)

    def _eval(self, params, batch, rng):
        return self._loss_for_grad(params, batch, rng)

    # -- audit -----------------------------------------------------------
    def audit_programs(self, state: TrainState, batch, rng=None) -> list[dict]:
        """Compiled-program inventory for tlhlo (analysis/hlo.py): the
        jitted train step, with the donated-leaf count (params + moments
        + step) the input/output aliasing must cover. ``lower()`` needs
        only avals — nothing executes."""
        donated = len(jax.tree.leaves(state)) if self.donate else 0
        return [{
            "name": "step",
            "dtype": str(self.compute_dtype),
            "donated": donated,
            "lower": lambda: self._train_step.lower(state, batch, rng),
        }]

    # -- observability ---------------------------------------------------
    def data_span(self):
        """Wrap the batch fetch: a ``trainer.data`` span + ``data_s``
        series, so input-pipeline stalls show on the step timeline."""
        if self._telemetry is None:
            return contextlib.nullcontext()
        return self._telemetry.data()

    # -- public ----------------------------------------------------------
    def device_time(self) -> dict | None:
        """Per-step device-busy vs host-gap attribution (None on an
        uninstrumented trainer): ``host_gap_frac`` here is the input-
        pipeline/host-work bubble — the device idle between the end of
        one train step and the dispatch of the next."""
        return None if self._timer is None else self._timer.snapshot()

    def train_step(self, state: TrainState, batch, rng):
        if self._telemetry is None:
            with region("train.step"):
                return self._train_step(state, batch, rng)
        # skip device timing on a compile call (StepTelemetry's cache
        # key): charging XLA compile as device-busy would poison the
        # EWMAs for the whole run
        time_this = self._timer is not None and self._telemetry.seen(
            batch, rng
        )
        with self._telemetry.step(batch, rng), region("train.step"):
            state, stats = self._train_step(state, batch, rng)
        disp = (
            self._timer.dispatch("train_step", stats.get("loss"))
            if time_this else None
        )
        # host-side anomaly accounting. bool() forces a device sync, so
        # it rides ONLY the telemetry path — an uninstrumented trainer
        # keeps the fully-async dispatch (the in-jit flag is still in
        # stats for callers that want it)
        nonfinite = bool(stats.get("nonfinite", False))
        if disp is not None:
            self._timer.drained(disp)  # right after the sync above
        if nonfinite:
            if self.metrics is not None:
                self.metrics.incr("train_nonfinite_total")
            if self.flight is not None:
                self.flight.record(
                    "train_nonfinite",
                    "error",
                    step=int(state.step),
                    loss=float(stats["loss"]),
                    skipped=self.cfg.skip_nonfinite_updates,
                )
        return state, stats

    def eval_loss(self, state: TrainState, batch, rng=None):
        return self._eval_step(state.params, batch, rng)
