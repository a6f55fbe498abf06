"""ShardedTrainer: one jit-compiled train step over the whole mesh.

This is the bridge between a job's stage placement and the data plane —
the TPU answer to DistributedModel's thread-and-socket forward/backward
(src/ml/distributed.py:79-197). A model is split into
(embed, N homogeneous blocks, head); blocks are stacked on a [S, L/S, ...]
leading axis and sharded over ``pipe``; embed/head params live on the mesh
replicated (or TP-sharded by their own specs); the whole
fwd+loss+bwd+optimizer step is ONE XLA program:

- micro-batches stream through the Pipeline's ppermute schedule,
- the ``data`` axis shards the micro-batch dimension (DP),
- the ``model`` axis shards weight matrices by each layer's PartitionSpec
  (TP) inside every stage,
- gradient allreduce over ``data`` and TP collectives over ``model`` are
  inserted by the SPMD partitioner.

So the reference's entire L3+L4 hot path (FORWARD/BACKWARD messages,
per-micro threads, busy-waits) compiles down to ICI collectives inside a
single program launch per step.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorlink_tpu.config import TrainConfig
from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.parallel.pp import Pipeline, stack_stage_params
from tensorlink_tpu.parallel.pp1f1b import Pipeline1F1B
from tensorlink_tpu.runtime.metrics import pipeline_bubble_fraction
from tensorlink_tpu.runtime.tracing import region, scope
from tensorlink_tpu.train.optim import make_optimizer, make_schedule
from tensorlink_tpu.train.trainer import TrainState, finish_step


@dataclasses.dataclass
class PipelineParts:
    """Model split for the engine. ``head_fn(params, x, batch)`` returns
    the final output (sees ALL params so weight tying works)."""

    embed_fn: Callable[[Any, Any], jax.Array]  # (params, batch) -> [B, ...]
    block: Module  # homogeneous block (for specs)
    block_params: dict  # {"0": ..., "L-1": ...}
    block_fn: Callable[[Any, jax.Array], jax.Array]
    head_fn: Callable[[Any, jax.Array, Any], jax.Array]
    embed_params: Any
    head_params: Any
    # blocks with an auxiliary loss (MoE router load balancing):
    # block_fn_aux(lp, x[, rng]) -> (x, aux). Used when
    # TrainConfig.moe_aux_weight > 0; both pipeline schedules carry it.
    block_fn_aux: Callable[..., Any] | None = None
    # per-batch auxiliary inputs for the blocks (e.g. the attention
    # padding mask): extras_fn(batch) -> pytree with leading [B, ...]
    # leaves, or None. The engine reslices it per micro and hands it to
    # every stage REPLICATED (under seq sharding the mask stays global,
    # which is what lets ring/ulysses apply padding); block_fn /
    # block_fn_aux must then accept a fourth argument.
    extras_fn: Callable[[Any], Any] | None = None
    # whether head_fn + loss reduce UNIFORMLY over token positions
    # (e.g. causal-LM mean CE). Required True for 1F1B at mesh seq>1,
    # where head_loss runs per token shard and results are pmean'd — a
    # position-selective head (BERT's CLS pooling) would silently pool
    # the wrong token on shards > 0. None = unknown = rejected there.
    head_per_token: bool | None = None


def _stacked_spec(
    block: Module, num_stages: int, model_axis="model",
    example_layer_params=None, fsdp_data_size: int = 1,
):
    """Per-block PartitionSpec tree -> stacked [pipe, layer, ...] specs.
    ``example_layer_params`` (one layer's params) lets the spec tree
    follow param-tree surgery the module can't know about (LoRA
    adapters). ``fsdp_data_size`` > 1 additionally shards each block
    leaf over ``data`` (parallel/dp.py fsdp_spec) BEFORE the [pipe,
    layer] prefix is added, so the FSDP dim is always a real weight dim
    and never the stage/layer stacking axes."""
    spec = block.param_spec(model_axis)
    if example_layer_params is not None:
        from tensorlink_tpu.nn.lora import lora_spec_tree

        spec = lora_spec_tree(spec, example_layer_params)
    if fsdp_data_size > 1:
        from tensorlink_tpu.parallel.dp import fsdp_spec_tree

        spec = fsdp_spec_tree(spec, example_layer_params, fsdp_data_size)
    return jax.tree.map(
        lambda s: P("pipe", None, *s),
        spec,
        is_leaf=lambda x: isinstance(x, P),
    )


def reshape_stages(tree, new_stages: int):
    """Re-factor stacked stage leaves [S, Lps, ...] for a different
    pipeline depth: stack_stage_params lays layers out stage-major and
    contiguous (stage s holds layers [s*Lps, (s+1)*Lps)), so changing S
    is a pure reshape through the flat [L, ...] layout — no data
    movement beyond resharding. Works identically on param and
    optimizer-moment trees (same stacked structure)."""

    def leaf(a):
        L = a.shape[0] * a.shape[1]
        if L % new_stages:
            raise ValueError(
                f"{L} layers not divisible by {new_stages} stages"
            )
        return a.reshape(new_stages, L // new_stages, *a.shape[2:])

    return jax.tree.map(leaf, tree)


class ShardedTrainer:
    """Builds the fully sharded train/eval steps for one mesh + model."""

    def __init__(
        self,
        mesh: Mesh,
        cfg: TrainConfig,
        parts: PipelineParts,
        loss_fn: Callable[[jax.Array, Any], jax.Array],
        embed_module: Module | None = None,
        head_module: Module | None = None,
        loss_reduction: str = "uniform_mean",
        tracer=None,
        metrics=None,
        flight=None,
    ):
        """``loss_reduction`` declares how loss_fn reduces over the batch:

        - "uniform_mean": a plain unweighted mean over examples (and, for
          per-token losses, tokens) — every schedule supported.
        - "batch_normalized": normalized by a per-BATCH quantity (e.g.
          mean over the batch's non-pad tokens). GPipe applies loss_fn
          once over the full batch, so this is fine there; 1F1B averages
          per-micro losses, which would SILENTLY differ (pp1f1b.py class
          docstring) — so 1F1B rejects it up front instead.
        """
        self.mesh = mesh
        self.cfg = cfg
        self.parts = parts
        self.loss_fn = loss_fn
        # observability (optional): engine.compile_step / engine.step
        # spans + step_s series + step_seconds histogram per train_step
        # dispatch. Per-stage timing inside the single XLA program is the
        # profiler's job (runtime/profiling.op_breakdown); the schedule-
        # level skew lives in measure_bubble and — on the socket path —
        # in the master's stage{i}_fwd_s series (tracing.straggler_report).
        self.tracer = tracer
        self.metrics = metrics
        self._telemetry = None
        if tracer is not None or metrics is not None:
            from tensorlink_tpu.runtime.tracing import StepTelemetry

            self._telemetry = StepTelemetry(
                tracer, metrics, "engine",
                # num_stages is derived further down — read the mesh here
                {"stages": mesh.shape["pipe"], "micros": cfg.micro_batches},
            )
        # same contract as train/trainer.py: telemetry-enabled trainers
        # account non-finite steps (counter + flight event); the in-jit
        # flag is in stats either way
        if flight is None and (tracer is not None or metrics is not None):
            from tensorlink_tpu.runtime.flight import default_recorder

            flight = default_recorder()
        self.flight = flight
        if loss_reduction not in ("uniform_mean", "batch_normalized"):
            raise ValueError(
                f"unknown loss_reduction {loss_reduction!r}; declare "
                "'uniform_mean' or 'batch_normalized'"
            )
        if loss_reduction == "batch_normalized" and cfg.pp_schedule == "1f1b":
            raise ValueError(
                "pp_schedule='1f1b' computes the batch loss as the "
                "unweighted mean of per-micro losses, which differs from "
                "a per-batch-normalized loss (e.g. mean over the batch's "
                "non-pad tokens). Use pp_schedule='gpipe' (loss_fn runs "
                "once over the full batch there) or renormalize per "
                "example and declare loss_reduction='uniform_mean'."
            )
        self.loss_reduction = loss_reduction  # train_only validated by
        # TrainConfig.__post_init__ (shared with the single-host Trainer)
        self.num_stages = mesh.shape["pipe"]
        L = len(parts.block_params)
        if L % self.num_stages:
            raise ValueError(f"{L} blocks not divisible by pipe={self.num_stages}")
        self.layers_per_stage = L // self.num_stages
        if cfg.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown pp_schedule {cfg.pp_schedule!r}")
        block_fn = parts.block_fn
        block_fn_aux = parts.block_fn_aux
        self.aux_weight = float(getattr(cfg, "moe_aux_weight", 0.0) or 0.0)
        if self.aux_weight:
            if block_fn_aux is None:
                raise ValueError(
                    "moe_aux_weight > 0 requires PipelineParts.block_fn_aux"
                )
        elif block_fn_aux is not None:
            import logging

            logging.getLogger("tensorlink_tpu.engine").warning(
                "model carries an MoE aux loss but moe_aux_weight=0: the "
                "router trains unregularized"
            )
        # 1F1B recomputes each stage forward inside its per-micro vjp, so
        # it is remat-by-construction; checkpoint only helps GPipe
        if cfg.remat and cfg.pp_schedule == "gpipe":
            block_fn = jax.checkpoint(block_fn)
            if block_fn_aux is not None:
                block_fn_aux = jax.checkpoint(block_fn_aux)
        self.block_fn = block_fn
        self.block_fn_aux = block_fn_aux
        self.seq = mesh.shape.get("seq", 1)
        seq_impl = getattr(parts.block, "attn_impl", None)
        ring = seq_impl in ("ring", "ulysses")  # both need the seq axis bound
        if self.seq > 1:
            if not ring:
                raise ValueError(
                    "mesh seq>1 shards the token dim inside the pipeline; "
                    "build the model with attn_impl='ring' or 'ulysses' "
                    "so attention spans the full sequence over the seq axis"
                )
            if cfg.pp_schedule == "1f1b" and parts.head_per_token is not True:
                # under seq sharding 1F1B runs head_loss per token shard
                # and pmeans — a position-selective head (CLS pooling)
                # silently pools the wrong token on shards > 0
                raise NotImplementedError(
                    "pp_schedule='1f1b' with mesh seq>1 requires "
                    "PipelineParts.head_per_token=True (a head+loss that "
                    "reduces uniformly over token positions, e.g. "
                    "causal-LM mean CE); this model's parts declare "
                    f"head_per_token={parts.head_per_token!r}. Use "
                    "pp_schedule='gpipe', whose head runs on the "
                    "re-assembled full sequence."
                )
        # ring models bind the seq axis even at seq=1 so axis_index /
        # axis_size inside ring_attention_local are always in scope
        self._seq_axis = "seq" if ring else None
        self.pipeline = Pipeline(
            mesh,
            block_fn,
            self.num_stages,
            self.layers_per_stage,
            seq_axis=self._seq_axis,
            block_fn_aux=block_fn_aux,
        )
        sched = make_schedule(
            cfg.schedule, cfg.learning_rate, cfg.warmup_steps, cfg.total_steps
        )
        self.optimizer = make_optimizer(
            cfg.optimizer, sched, cfg.weight_decay,
            moment_dtype=cfg.opt_moment_dtype,
        )
        self.compute_dtype = jnp.dtype(cfg.dtype)

        # shardings ----------------------------------------------------
        from tensorlink_tpu.nn.lora import lora_spec_tree
        from tensorlink_tpu.parallel.dp import fsdp_spec_tree

        fsdp_n = mesh.shape.get("data", 1) if cfg.fsdp else 1
        if cfg.fsdp and fsdp_n <= 1:
            import logging

            logging.getLogger("tensorlink_tpu.engine").warning(
                "fsdp=True on a mesh with data axis size %d: nothing to "
                "shard over — params/moments stay as replicated-DP would "
                "leave them (a mesh-shape sweep hitting data=1 is legal, "
                "so this warns instead of raising)",
                fsdp_n,
            )
        stacked_specs = _stacked_spec(
            parts.block, self.num_stages,
            example_layer_params=parts.block_params["0"],
            fsdp_data_size=fsdp_n,
        )
        embed_specs = (
            embed_module.param_spec() if embed_module is not None
            else jax.tree.map(lambda _: P(), parts.embed_params)
        )
        head_specs = (
            head_module.param_spec() if head_module is not None
            else jax.tree.map(lambda _: P(), parts.head_params)
        )
        # adapters may also live in embed/head trees (e.g. a LoRA'd head)
        embed_specs = lora_spec_tree(embed_specs, parts.embed_params)
        head_specs = lora_spec_tree(head_specs, parts.head_params)
        if fsdp_n > 1:
            embed_specs = fsdp_spec_tree(
                embed_specs, parts.embed_params, fsdp_n
            )
            head_specs = fsdp_spec_tree(head_specs, parts.head_params, fsdp_n)
        self.param_specs = {
            "embed": embed_specs,
            "stages": stacked_specs,
            "head": head_specs,
        }
        self._param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            self.param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._repl = NamedSharding(mesh, P())
        self._batch_sh = NamedSharding(mesh, P(("data",)))
        self._state_shardings = None  # set in init_state
        self._step_fn = None
        self._eval_fn = None

    # -- state -----------------------------------------------------------
    def init_state(self) -> TrainState:
        params = {
            "embed": self.parts.embed_params,
            "stages": stack_stage_params(self.parts.block_params, self.num_stages),
            "head": self.parts.head_params,
        }
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, s), params, self._param_shardings
        )
        opt_state = self.optimizer.init(params)
        opt_state = jax.device_put(opt_state, self._opt_shardings(opt_state))
        return TrainState(
            params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32)
        )

    def _opt_shardings(self, opt_state):
        """Optimizer moments shard exactly like their params (free
        ZeRO-style sharding over pipe/model)."""
        return {
            k: self._param_shardings if isinstance(v, dict) else self._repl
            for k, v in opt_state.items()
        }

    def adopt_state(self, state: TrainState) -> TrainState:
        """Adopt a TrainState produced by a trainer on a DIFFERENT mesh
        shape (elastic resume, SURVEY §7.5.4: membership change =>
        re-form mesh + recompile, state carries over). Stage leaves are
        re-factored to this trainer's pipeline depth (reshape_stages)
        and everything is re-placed under this mesh's shardings; embed/
        head/scalars pass through. The checkpoint side needs no mesh
        knowledge — restore host-side, then adopt."""
        S = self.num_stages

        def fix(tree):
            if not isinstance(tree, dict) or "stages" not in tree:
                return tree
            return {
                k: (reshape_stages(v, S) if k == "stages" else v)
                for k, v in tree.items()
            }

        params = fix(state.params)
        opt_state = {k: fix(v) for k, v in state.opt_state.items()}
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, s), params, self._param_shardings
        )
        opt_state = jax.device_put(opt_state, self._opt_shardings(opt_state))
        return TrainState(
            params=params, opt_state=opt_state,
            step=jax.device_put(state.step, self._repl),
        )

    # -- step ------------------------------------------------------------
    def _cast(self, params):
        with scope("train.cast"):
            return jax.tree.map(
                lambda x: x.astype(self.compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                params,
            )

    def _micro_extras(self, batch, m: int):
        """extras_fn output resliced to [M, mb, ...] leaves (or None)."""
        if self.parts.extras_fn is None:
            return None
        ex = self.parts.extras_fn(batch)
        if ex is None:
            return None
        return jax.tree.map(
            lambda a: a.reshape(m, a.shape[0] // m, *a.shape[1:]), ex
        )

    def _loss(self, params, batch, rng):
        """rng=None -> eval mode (no dropout anywhere)."""
        cfg = self.cfg
        cast = self._cast(params)
        r_embed = r_pipe = r_head = None
        if rng is not None:
            r_embed, r_pipe, r_head = jax.random.split(rng, 3)
        x = self.parts.embed_fn(cast["embed"], batch, rng=r_embed)  # [B, ...]
        B = x.shape[0]
        m = cfg.micro_batches
        if B % m:
            raise ValueError(f"batch {B} not divisible by micro_batches {m}")
        xs = x.reshape(m, B // m, *x.shape[1:])
        extras = self._micro_extras(batch, m)
        if self.aux_weight:
            ys, aux = self.pipeline.apply_with_aux(
                cast["stages"], xs, rng=r_pipe, extras=extras
            )
        else:
            ys = self.pipeline(cast["stages"], xs, rng=r_pipe, extras=extras)
            aux = 0.0
        y = ys.reshape(B, *ys.shape[2:])
        out = self.parts.head_fn(cast, y, batch, rng=r_head)
        return self.loss_fn(out, batch) + self.aux_weight * aux

    def _loss_and_grads_1f1b(self, params, batch, rng):
        """Manual-gradient path: the 1F1B interleave cannot be expressed
        as jax.grad (backwards start mid-forward), so Pipeline1F1B emits
        grads directly; the cast/embed chain is closed by hand (the vjp
        of a dtype cast is the cast back)."""
        cfg = self.cfg
        m = cfg.micro_batches
        r_embed = r_pipe = None
        if rng is not None:
            # SAME split as _loss so embed + block dropout masks stay
            # bitwise-identical across schedules (review finding: a
            # 2-way split here silently diverged every mask). The third
            # key goes unused — the pipe derives per-micro head streams
            # from r_pipe, since 1F1B applies head dropout per micro
            # (GPipe: once over the full batch; masks differ there by
            # construction).
            r_embed, r_pipe, _ = jax.random.split(rng, 3)

        def embed_all(embed_f32):
            ep = self._cast(embed_f32)
            x = self.parts.embed_fn(ep, batch, rng=r_embed)
            B = x.shape[0]
            if B % m:
                raise ValueError(f"batch {B} not divisible by micro_batches {m}")
            return x.reshape(m, B // m, *x.shape[1:])

        xs, embed_vjp = jax.vjp(embed_all, params["embed"])
        cast_stages = self._cast(params["stages"])
        cast_aux = {
            "embed": self._cast(params["embed"]),
            "head": self._cast(params["head"]),
        }
        micro_batches = jax.tree.map(
            lambda a: a.reshape(m, a.shape[0] // m, *a.shape[1:]), batch
        )

        def head_loss(aux_p, y, mb, rng_h):
            out = self.parts.head_fn(
                {"embed": aux_p["embed"], "head": aux_p["head"]}, y, mb, rng=rng_h
            )
            return self.loss_fn(out, mb)

        pipe = Pipeline1F1B(
            self.mesh,
            self.block_fn,
            self.num_stages,
            self.layers_per_stage,
            head_loss,
            block_fn_aux=self.block_fn_aux,
            aux_weight=self.aux_weight,
            seq_axis=self._seq_axis,
        )
        loss, gsp, gaux, dxs = pipe.train_grads(
            cast_stages, cast_aux, xs, micro_batches, rng=r_pipe,
            extras=self._micro_extras(batch, m),
        )
        (dembed,) = embed_vjp(dxs.astype(xs.dtype))
        grads = {
            # tied weights (e.g. GPT-2 lm-head=wte): the head-side
            # contribution from the last stage's vjp adds to the
            # embed_fn-side one
            "embed": jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), dembed, gaux["embed"]
            ),
            "stages": jax.tree.map(
                lambda g, p: g.astype(p.dtype), gsp, params["stages"]
            ),
            "head": jax.tree.map(
                lambda g, p: g.astype(p.dtype), gaux["head"], params["head"]
            ),
        }
        return loss, grads

    def _step(self, state: TrainState, batch, rng):
        if rng is None:
            # deterministic per-step dropout streams without caller plumbing
            rng = jax.random.fold_in(jax.random.key(self.cfg.seed), state.step)
        if self.cfg.pp_schedule == "1f1b":
            loss, grads = self._loss_and_grads_1f1b(state.params, batch, rng)
        else:
            loss, grads = jax.value_and_grad(self._loss)(state.params, batch, rng)
        if self.cfg.train_only == "lora":
            # parameter-efficient fine-tune, inside the SAME sharded
            # program (schedules/axes unchanged). Grads mask BEFORE
            # clipping/optimizer — frozen params must not dominate the
            # clip norm (>99% of it) or accumulate Adam moments — and
            # updates mask again AFTER: AdamW's decoupled weight decay
            # updates params even at zero grad (review finding).
            from tensorlink_tpu.nn.lora import mask_to_lora

            grads = mask_to_lora(grads)
        return finish_step(self.cfg, self.optimizer, state, loss, grads)

    def _jit_step(self):
        # the function's name is the program's: launches read
        # jit_tl_sharded_train_step in a device trace
        def tl_sharded_train_step(state, batch, rng):
            return self._step(state, batch, rng)

        return jax.jit(tl_sharded_train_step, donate_argnums=(0,))

    def train_step(self, state: TrainState, batch, rng=None):
        if self._step_fn is None:
            self._step_fn = self._jit_step()
        batch = jax.device_put(batch, self._batch_sh)
        # telemetry keys on (shape, dtype, rng-variant) — a retrace is
        # labeled compile_step and kept out of the latency histogram
        cm = (
            self._telemetry.step(batch, rng)
            if self._telemetry is not None
            else contextlib.nullcontext()
        )
        # rng=None traces the step-derived-rng variant; an explicit key
        # traces a second variant — both cached by jit.
        # set_mesh makes the trainer's mesh ambient during tracing so
        # modules that pin intermediate shardings on Auto axes (MoE's
        # all_to_all dispatch, nn/moe.py) can engage; everything else is
        # unaffected (all axes here are Auto outside the pipe shard_map).
        with cm, region("train.step"), jax.set_mesh(self.mesh):
            state, stats = self._step_fn(state, batch, rng)
        # host-side anomaly accounting rides ONLY the telemetry path —
        # bool() forces a device sync (same tradeoff as train/trainer.py)
        if self._telemetry is not None and bool(stats.get("nonfinite", False)):
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

    def eval_fn(self, state: TrainState, batch):
        if self._eval_fn is None:
            self._eval_fn = jax.jit(self._loss)
        with jax.set_mesh(self.mesh):
            return self._eval_fn(state.params, batch, None)

    def audit_programs(self, state: TrainState, batch, rng=None) -> list[dict]:
        """Compiled-program inventory for tlhlo (analysis/hlo.py): the
        fully sharded train step, lowered under the trainer's ambient
        mesh exactly as ``train_step`` traces it. A fresh jit on
        purpose — the lazily-built ``_step_fn`` may belong to a live
        training loop whose trace cache must not see audit avals."""
        donated = len(jax.tree.leaves(state))
        fn = self._jit_step()
        sharded_batch = jax.device_put(batch, self._batch_sh)

        def lower():
            with jax.set_mesh(self.mesh):
                return fn.lower(state, sharded_batch, rng)

        return [{
            "name": "step",
            "dtype": str(self.cfg.dtype),
            "donated": donated,
            "lower": lower,
        }]

    # -- reporting ------------------------------------------------------
    @property
    def bubble_fraction(self) -> float:
        return pipeline_bubble_fraction(self.num_stages, self.cfg.micro_batches)

    def measure_bubble(
        self, state, batch, repeats: int = 3, factors: tuple = (1, 2, 3, 4)
    ) -> dict:
        """MEASURED pipeline bubble, not the closed form: time the GPipe
        pipeline forward (the engine's forward path regardless of the
        training schedule — 1F1B's interleave lives in its own grads-only
        program) at k*M micro-batches for each k in ``factors`` (same
        per-micro shape), least-squares fit t = tick_s * (micros + extra):
        the intercept ``extra`` is the measured warmup/drain overhead in
        tick units (ideally S-1), and bubble = extra / (M + extra).

        Multi-point LSQ instead of the round-3 two-point fit: on a noisy
        host a single pair put all variance into the intercept
        (MULTICHIP_r03 recorded 0.78 vs closed-form 0.20 from exactly
        this). The intercept still absorbs fixed per-call dispatch, so
        the fraction is an UPPER bound on the true schedule bubble —
        tight when tick time dominates dispatch; r2 of the fit is
        reported so a noise-dominated measurement is visible. Each timed
        call ends in a device->host read, which waits for the device as
        block_until_ready does."""
        import time as _time

        import numpy as _np

        m = self.cfg.micro_batches
        cast = self._cast(state.params)
        x = self.parts.embed_fn(cast["embed"], batch, rng=None)
        B = x.shape[0]
        xs1 = x.reshape(m, B // m, *x.shape[1:])

        if getattr(self, "_bubble_fn", None) is None:
            # cached like _step_fn: a fresh jit closure per call would
            # recompile the pipeline per invocation
            self._bubble_fn = jax.jit(lambda sp, xs: self.pipeline(sp, xs))
        run = self._bubble_fn

        def timed(xs):
            # MIN of per-call times, not the mean: OS-scheduler stalls
            # only ever ADD time, and one stall in the mean was enough to
            # push the 3-point fit's r2 under the 0.95 validity bar on
            # the live r4 run (r2=0.947, measurement discarded). The
            # repeatable minimum is the schedule's actual cost.
            out = run(cast["stages"], xs)
            float(jnp.sum(out[-1]).astype(jnp.float32))  # sync (warmup)
            best = float("inf")
            for _ in range(repeats):
                t0 = _time.perf_counter()
                out = run(cast["stages"], xs)
                float(jnp.sum(out[-1]).astype(jnp.float32))
                best = min(best, _time.perf_counter() - t0)
            return best

        micros = _np.asarray([k * m for k in factors], _np.float64)
        times = _np.asarray(
            [timed(jnp.concatenate([xs1] * k, axis=0)) for k in factors]
        )
        # LSQ t = tick_s * micros + c; extra = c / tick_s
        A = _np.stack([micros, _np.ones_like(micros)], axis=1)
        (tick_s, c), res, *_ = _np.linalg.lstsq(A, times, rcond=None)
        ss_tot = float(((times - times.mean()) ** 2).sum())
        r2 = 1.0 - float(res[0]) / ss_tot if len(res) and ss_tot > 0 else 0.0
        # a 2-point or rank-deficient fit has empty residuals — that is
        # the confident-garbage failure mode this rewrite exists to flag,
        # never a valid measurement
        valid = tick_s > 0 and len(micros) >= 3 and len(res) == 1 and r2 > 0.95
        invalid_reason = None
        if not valid:
            invalid_reason = (
                f"fit rejected: tick_s={tick_s:.3e}, points={len(micros)}, "
                f"residuals={len(res)}, r2={r2:.3f} (need >0.95)"
            )
        # a CPU host with fewer cores than stages SERIALIZES the virtual
        # devices: idle pipeline slots cost no wall time and the bubble
        # is structurally unobservable — whatever lands in the intercept
        # is scheduler noise (a clean r2=0.98 fit measured 0.60 on the
        # r4 dryrun host). Guarded HERE so every caller (bench child,
        # driver dryrun) inherits it; real chips are one device per
        # stage and unaffected.
        dev0 = next(iter(self.mesh.devices.flat))
        if dev0.platform == "cpu":
            import os as _os

            try:
                cores = len(_os.sched_getaffinity(0))
            except AttributeError:  # non-Linux
                cores = _os.cpu_count() or 1
            if cores < self.num_stages:
                valid = False
                invalid_reason = (
                    f"host serializes stages ({cores} cores < "
                    f"{self.num_stages} stages): bubble unobservable; "
                    "closed_form_bubble_fraction is the honest figure"
                )
        extra_ticks = c / tick_s if valid else float("nan")
        measured = (
            extra_ticks / (m + extra_ticks)
            if valid and extra_ticks > 0 else (0.0 if valid else float("nan"))
        )
        return {
            "valid": bool(valid),
            "invalid_reason": invalid_reason,
            "schedule_timed": "gpipe",  # self.pipeline IS the GPipe path
            "micros_timed": [int(v) for v in micros],
            "times_s": [float(t) for t in times],
            "fit_r2": r2,
            "tick_s": float(tick_s),
            "measured_extra_ticks": float(extra_ticks),
            "measured_bubble_fraction": float(measured),
            "closed_form_bubble_fraction": self.bubble_fraction,
            "num_stages": self.num_stages,
            "micro_batches": m,
        }

    def describe(self) -> dict:
        return {
            "mesh": dict(self.mesh.shape),
            "num_stages": self.num_stages,
            "layers_per_stage": self.layers_per_stage,
            "micro_batches": self.cfg.micro_batches,
            "pp_schedule": self.cfg.pp_schedule,
            "bubble_fraction": self.bubble_fraction,
            "dtype": str(self.compute_dtype),
        }
