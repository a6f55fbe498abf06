"""Continuous-batching serving engine over ``InferenceEngine``.

The engine's ``generate()`` is one synchronous XLA program per BATCH:
every prompt in the batch prefills together, decodes together, and the
whole batch finishes together. Real traffic arrives staggered — the
job-lifecycle premise of the source paper's validator/job queue — so a
static batch either waits to fill (latency) or runs part-empty
(throughput). This module serves a FIXED-SLOT decode batch instead:

- the KV cache is allocated once as ``[slots, L, Hkv, D]`` per layer;
  each slot row is an independent request with its own write index
  (``nn/attention.py`` per-row cache indices), validity mask, logical
  position, and RNG stream;
- an admission queue interleaves PREFILL of arriving prompts (a batch-1
  program that scatters the prompt's k/v into a free slot's cache
  region) with DECODE of in-flight ones;
- decode runs in jitted chunks of ``decode_chunk`` tokens with the
  whole device state DONATED (the multi-GB cache is updated in place,
  never copied per step) and the host keeps ``pipeline_depth`` chunks
  in flight before syncing the oldest — dispatch overlaps device work,
  no per-token host sync;
- a slot is freed on EOS / max-tokens and immediately re-admissible.

Determinism: the sampling key for the token at logical position ``n``
of a request is ``fold_in(key(request_seed), n)`` — a function of the
request alone, so a request's tokens do not depend on which slot it
landed in or what other traffic shared the batch.

API: ``submit() -> rid`` (non-blocking, queue-backpressured),
``result(rid)`` (drives the loop until that request finishes),
``aresult(rid)`` (asyncio wrapper for node event loops). Per-request
TTFT/TPOT land in a ``Metrics`` registry as histograms.

``PagedContinuousBatchingEngine`` replaces the per-slot contiguous
cache regions with a paged KV cache (parallel/kvpool.py): fixed-size
blocks allocated from a shared pool through per-slot block tables,
copy-on-write prefix sharing keyed by prompt hash (a request whose
prompt prefix is already resident maps those blocks and skips their
prefill entirely), chunked prefill interleaved with decode dispatches
(a long arriving prompt cannot stall in-flight decodes), and
block-granular free on EOS/eviction with typed ``PoolExhaustedError``
backpressure. HBM then scales with LIVE tokens, not slots x max_len.

Both engines optionally decode SPECULATIVELY (``draft=`` /
``speculative=``, parallel/speculative.py): each dispatched chunk runs
``rounds`` rounds of draft-K-tokens + verify-all-K(+1 bonus)-in-one-
target-weight-pass, rolling the KV write frontier back to the first
rejection (contiguous: an index reset inside the slot region; paged:
logical-index truncation — no block churn, rejected scatter writes
land in blocks the very next verify overwrites before reading).
Greedy output is token-identical with speculation on or off; the
bench headline becomes ``accepted_tokens_per_weight_pass``.
"""

from __future__ import annotations

import asyncio
import collections
import enum
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorlink_tpu.parallel.inference import (
    GenerationConfig,
    InferenceEngine,
    declared_compute_dtype,
    sample_logits,
    spec_verify,
)
from tensorlink_tpu.parallel.kvpool import (
    BlockPool,
    PoolExhaustedError,
    PrefixIndex,
    kv_residency,
    kv_summary,
)
from tensorlink_tpu.parallel.speculative import (
    AdaptiveKController,
    SpecConfig,
    SpeculativeDecoder,
    autopair_draft,
    ngram_propose,
)
from tensorlink_tpu.runtime import chaos
from tensorlink_tpu.runtime.autotune import (
    AutotuneStore,
    apply_flash_overrides,
    apply_paged_overrides,
    model_fingerprint,
    store_key,
)
from tensorlink_tpu.runtime.compile_cache import (
    cache_entries,
    enable_compile_cache,
)
from tensorlink_tpu.runtime.metrics import DEFAULT_BUCKETS
from tensorlink_tpu.runtime.tracing import event, region, scope

__all__ = [
    "ContinuousBatchingEngine",
    "DeadlineExceededError",
    "OverloadedError",
    "PagedContinuousBatchingEngine",
    "PoolExhaustedError",
    "PoolOverloadedError",
    "PromptTooLongError",
    "Priority",
    "QueueFullError",
    "ServingError",
    "SpecConfig",
    "autopair_draft",
    "serve_error_from_wire",
    "serve_error_to_wire",
]

# speculation self-healing acts only after this many verified proposals
# — a couple of unlucky first rounds must not kill a good draft
HEAL_MIN_PROPOSED = 32

# per-request acceptance-rate histogram bounds (a rate lives in [0, 1];
# the latency-shaped default buckets would bin every value together)
_ACCEPTANCE_BUCKETS = tuple(i / 10 for i in range(1, 11))

# retry-after TPOT stand-in before the FIRST request finishes (a cold
# engine has measured nothing); every later estimate is the EWMA of
# this engine's own completions
_RETRY_TPOT_FALLBACK_S = 0.02

# per-priority TTFT buckets extend the latency-shaped defaults upward:
# under deliberate oversubscription a BATCH request legitimately waits
# far past the 10 s default cap (that queueing IS the measurement the
# serving_under_load round reports), and a saturated top bucket would
# flatten its p99 into the INTERACTIVE one
_TTFT_CLASS_BUCKETS = (*DEFAULT_BUCKETS, 30.0, 60.0, 120.0)


def _is_index_leaf(leaf) -> bool:
    """A per-slot cache write-index vector ([S] int) — the only 1-D
    integer leaf in a serving-form KV cache (k/v are 4-D)."""
    return (
        getattr(leaf, "ndim", None) == 1
        and jnp.issubdtype(leaf.dtype, jnp.integer)
    )


def _cache_index(caches):
    for leaf in jax.tree.leaves(caches):
        if _is_index_leaf(leaf):
            return leaf
    raise ValueError("serving caches carry no per-slot index vector")


def _with_cache_index(caches, new_index):
    return jax.tree.map(
        lambda c: new_index if _is_index_leaf(c) else c, caches
    )


class Priority(enum.IntEnum):
    """SLO class on ``submit()``. Lower value = more protected: the
    scheduler admits, queues, and — under pool pressure — PRESERVES
    requests in this order (a BATCH stream is always preempted or shed
    before any STANDARD one, STANDARD before INTERACTIVE; within a
    class, newest first). The token-identical preempt/resume machinery
    makes demotion safe: a preempted stream continues exactly where it
    left off once pressure clears."""

    INTERACTIVE = 0
    STANDARD = 1
    BATCH = 2


_PRIO_NAMES = {int(p): p.name.lower() for p in Priority}


def _coerce_priority(p) -> int:
    if isinstance(p, str):
        try:
            return int(Priority[p.upper()])
        except KeyError:
            raise ValueError(
                f"unknown priority {p!r} (use "
                f"{'/'.join(n.name for n in Priority)})"
            ) from None
    return int(Priority(int(p)))


class ServingError(RuntimeError):
    """Base class for scheduler rejections."""


class PromptTooLongError(ServingError):
    """Prompt (plus its token budget) cannot fit a slot's cache region."""


class OverloadedError(ServingError):
    """Typed 429: the scheduler shed this request. ``retry_after_s``
    is DERIVED, not a constant — measured TPOT x the token backlog
    ahead of a new arrival / decode width x pool pressure — so a
    client honoring it re-arrives roughly when capacity exists.
    ``reason`` says which resource shed it (``queue_full``,
    ``pool_exhausted``, ``displaced``)."""

    def __init__(
        self, msg: str, *, retry_after_s: float | None = None,
        reason: str = "overloaded",
    ):
        super().__init__(msg)
        self.retry_after_s = retry_after_s
        self.reason = reason


class QueueFullError(OverloadedError):
    """Admission queue at max_queue — back-pressure the caller."""

    def __init__(self, msg: str, **kw):
        kw.setdefault("reason", "queue_full")
        super().__init__(msg, **kw)


class PoolOverloadedError(OverloadedError, PoolExhaustedError):
    """Paged backpressure: the queue backed up on KV blocks, not decode
    width. Catchable as either ``PoolExhaustedError`` (the pool-level
    type admission has always raised) or ``OverloadedError`` (the
    retry-after contract)."""

    def __init__(self, msg: str, **kw):
        kw.setdefault("reason", "pool_exhausted")
        super().__init__(msg, **kw)


class DeadlineExceededError(ServingError):
    """The request's deadline is (or became) unmeetable: rejected at
    admission when measured TPOT proves the decode alone cannot finish
    in time, or cancelled later — slot and KV blocks freed — when the
    deadline passes while queued/running/awaited."""

    def __init__(self, msg: str, *, rid: int | None = None):
        super().__init__(msg)
        self.rid = rid


# typed scheduler errors crossing the mesh (disaggregated serving): a
# remote leg's rejection must re-raise as the SAME type on the caller,
# retry-after contract included, so a client's except-clauses work
# identically for local and remote engines
_WIRE_ERRORS = {
    cls.__name__: cls
    for cls in (
        ServingError, PromptTooLongError, OverloadedError,
        QueueFullError, PoolOverloadedError, DeadlineExceededError,
        PoolExhaustedError,
        # result(timeout_s=) soft timeout: the request is STILL RUNNING
        # and collectable later — the client must see TimeoutError, not
        # a generic failure, to know a re-poll can succeed
        TimeoutError,
    )
}


def serve_error_to_wire(e: BaseException) -> dict:
    """Scheduler exception -> SERVE_FAILED reply dict."""
    out = {
        "type": "SERVE_FAILED",
        "error_type": type(e).__name__,
        "error": str(e)[:300],
    }
    ra = getattr(e, "retry_after_s", None)
    if ra is not None:
        out["retry_after_s"] = ra
    return out


def serve_error_from_wire(resp: dict) -> BaseException:
    """SERVE_FAILED reply -> the typed exception to raise locally.
    Unknown types degrade to ``ServingError`` (an older peer may ship
    a type this build does not know)."""
    cls = _WIRE_ERRORS.get(str(resp.get("error_type")), ServingError)
    msg = str(resp.get("error", "remote serving leg failed"))
    if issubclass(cls, OverloadedError):
        return cls(msg, retry_after_s=resp.get("retry_after_s"))
    return cls(msg)


@dataclass
class _Request:
    rid: int
    ids: np.ndarray | None  # [T0] prompt tokens (dropped once finished)
    max_new: int
    seed: int
    submitted_at: float
    priority: int = int(Priority.STANDARD)
    deadline_s: float | None = None
    deadline_at: float | None = None  # perf_counter absolute
    # terminal failure (shed / deadline miss / cancel): result() raises
    # this instead of returning tokens
    failed: BaseException | None = None
    # wall-clock anchor for the reconstructed span timeline: every
    # other stamp is perf_counter (monotonic), converted at emission
    submitted_ns: int = 0
    slot: int | None = None
    first_token: jax.Array | None = None  # device scalar from prefill
    first_token_at: float | None = None
    # TTFT decomposition stamps: admission (slot mapped), first prefill
    # program dispatched (== admission on the contiguous engine; a later
    # scheduler step on the paged chunked-prefill path)
    admitted_at: float | None = None
    prefill_started_at: float | None = None
    prefill_chunks: int = 0
    # in-flight DispatchTimer token for this request's (last) prefill
    disp: object | None = None
    tokens: list[int] = field(default_factory=list)
    done: bool = False
    finished_at: float | None = None
    # prefill-leg hold (disaggregated serving): the scheduler prefills
    # this request but never dispatches decode for it — its filled KV
    # blocks are exported over the wire instead (prefill_export)
    hold: bool = False
    # speculative-decoding accounting (0 when speculation is off)
    spec_rounds: int = 0  # verify passes this request was live for
    spec_proposed: int = 0  # drafted tokens verified on its behalf
    spec_accepted: int = 0  # drafted tokens accepted into its stream
    # work-receipt metering (runtime/ledger.py): device-busy seconds
    # apportioned from this request's share of drained dispatches,
    # claimed flops/HBM bytes from the AOT cost model, KV
    # block-seconds integrated from the paged pool's alloc/release
    # stream, and the billing identity the submitter declared
    tenant: str | None = None
    busy_s: float = 0.0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    kv_block_s: float = 0.0
    kv_blocks_now: int = 0
    kv_anchor: float | None = None
    wire_bytes: int = 0
    # prefill dispatch handles not yet folded into busy_s (chunked
    # prefill stacks several; FIFO finalization means all are stamped
    # by the time the first token syncs)
    disp_hist: list = field(default_factory=list)


class ContinuousBatchingEngine:
    """Fixed-slot continuous batching over a built ``InferenceEngine``.

    ``slots``: decode batch width (compiled once; a slot row is one
    request). ``decode_chunk``: tokens decoded per dispatched program —
    larger amortizes dispatch, smaller reduces wasted steps after EOS.
    ``pipeline_depth``: decode chunks kept in flight before the host
    syncs the oldest (the host-off-critical-path knob).
    ``prefill_block``: prompt lengths round up to a multiple of this, so
    prefill retraces are bounded by max_len / prefill_block buckets.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        slots: int = 8,
        gen: GenerationConfig | None = None,
        decode_chunk: int = 8,
        pipeline_depth: int = 2,
        prefill_block: int = 32,
        max_queue: int | None = None,
        keep_results: int = 1024,
        prefill_cache_max: int = 32,
        warm_buckets: bool = False,
        draft: InferenceEngine | None = None,
        speculative: SpecConfig | bool | None = None,
        compile_cache_dir: str | None = None,
        autotune_dir: str | None = None,
        metrics=None,
        recorder=None,
        tracer=None,
        device_timing: bool = True,
        capability: dict | None = None,
        metering: bool = True,
    ):
        if engine.rolling:
            raise NotImplementedError(
                "continuous batching over a rolling (ring) cache would "
                "need per-row wrap bookkeeping; use the monotone cache"
            )
        if engine.kv_seq_shard:
            raise NotImplementedError(
                "continuous batching with kv_seq_shard is not wired yet "
                "(the per-slot scatter writes need owner-aware sharding)"
            )
        self.engine = engine
        self.gen = gen or GenerationConfig()
        if not 0.0 < self.gen.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1] (1.0 = off), got {self.gen.top_p}"
            )
        self.slots = int(slots)
        self.decode_chunk = int(decode_chunk)
        self.pipeline_depth = max(int(pipeline_depth), 0)
        self.prefill_block = int(prefill_block)
        self.max_queue = max_queue
        # finished requests kept readable through result(); older ones
        # are evicted so steady traffic cannot grow host memory forever
        self.keep_results = max(int(keep_results), 1)
        self.metrics = metrics
        self.recorder = recorder
        self.tracer = tracer
        self.L = engine.cache_len
        self._lock = threading.Lock()

        # always-on per-dispatch device timing (runtime/profiling.py):
        # every decode/spec/prefill dispatch is attributed into
        # device-busy vs host-gap, riding the drains that already
        # synchronize — no block_until_ready added to the hot path.
        # ``capability`` (measure_capability record) supplies the peak
        # TFLOPs / HBM GB/s that turn per-program flops/bytes (captured
        # at AOT compile) into MFU/MBU.
        from tensorlink_tpu.runtime.profiling import DispatchTimer

        self._timer = DispatchTimer(metrics=metrics) if device_timing else None
        self.capability = capability
        self._prog_cost: dict[str, dict] = {}
        # per-phase TTFT decomposition EWMAs (queue vs prefill-compute
        # vs first-dispatch), folded in at _finish
        self._ttft_decomp: dict[str, float] = {}
        # work-receipt metering (runtime/ledger.py): finished-request
        # meter dicts, rid-addressable for the reply path and drainable
        # once for heartbeat piggybacking — both bounded
        self.metering = bool(metering)
        # what this engine's finished requests bill as: "serve"
        # (colocated), or the disagg "prefill_leg"/"decode_leg" —
        # roles/worker.py sets it from the serving mode
        self.meter_kind = "serve"
        self._meter_log: collections.OrderedDict[int, dict] = (
            collections.OrderedDict()
        )
        self._meter_fresh: collections.deque = collections.deque(maxlen=512)
        self._metered_total = 0

        self._queue: collections.deque[_Request] = collections.deque()
        self._requests: dict[int, _Request] = {}
        # SLO-aware admission state: measured TPOT/TTFT EWMAs feed the
        # retry-after computation and the deadline-feasibility check;
        # shed/deadline counters feed stats() (tldiag SHEDDING flag)
        self._tpot_ewma: float | None = None
        self._ttft_ewma: float | None = None
        self._sheds = 0
        self._shed_by_prio: dict[int, int] = {}
        self._last_shed_at: float | None = None
        self._deadline_misses = 0
        self._deadlined = 0  # live requests carrying a deadline
        self._done_order: collections.deque[int] = collections.deque()
        self._slot_req: list[_Request | None] = [None] * self.slots
        self._free: list[int] = list(range(self.slots))[::-1]
        # (device tokens [K, S], dispatch-time slot->request snapshot)
        self._inflight: collections.deque = collections.deque()
        self._next_rid = 0
        # bounded LRU of AOT-compiled prefill programs, one per prompt-
        # length bucket: unbounded growth was a slow host-memory leak
        # under adversarial prompt-length mixes (ROADMAP item 5)
        self.prefill_cache_max = max(int(prefill_cache_max), 1)
        self._prefill_jit: collections.OrderedDict[int, object] = (
            collections.OrderedDict()
        )

        # speculative decoding (parallel/speculative.py): a draft
        # engine implies draft-model speculation; ``speculative`` alone
        # (True or a SpecConfig) enables n-gram self-speculation
        self.spec: SpeculativeDecoder | None = None
        if draft is not None or speculative:
            cfg = (
                speculative if isinstance(speculative, SpecConfig)
                else SpecConfig()
            )
            self.spec = SpeculativeDecoder(engine, draft, cfg)
        self.spec_rounds_total = 0  # (live row, verify pass) pairs
        self.spec_emitted_total = 0
        self.spec_accepted_total = 0
        self.spec_proposed_total = 0
        self.spec_fallback_total = 0
        # LOW-ACCEPT self-healing (SpecConfig.self_heal_accept): recent
        # acceptance EWMA + how the engine already downgraded, if it did
        self._heal_acc: float | None = None
        self._heal_proposed = 0
        self.spec_self_healed: dict | None = None
        # per-dispatch masked-K array staged by the paged step() so the
        # block-growth bound and the dispatched operand can never skew
        self._k_dispatch: list[int] | None = None

        # persistent XLA compilation cache (ROADMAP item 5): restarts
        # reuse kernels; compile events below report per-program hits
        self._cc_dir = enable_compile_cache(
            compile_cache_dir, recorder=recorder
        )
        self._cc_entries = cache_entries(self._cc_dir) if self._cc_dir else 0

        # persistent autotune store (runtime/autotune.py), loaded BEFORE
        # any program traces so persisted flash-block overrides shape
        # the very kernels about to compile — the measured-constants
        # side of the compile cache's warm restart
        self.autotune_warm_start_s: float | None = None
        self._autotune_key: str | None = None
        self._autotune_record: dict | None = None
        self._autotune = AutotuneStore.resolve(
            autotune_dir, recorder=recorder
        )
        if self._autotune is not None:
            self._autotune_load()

        # adaptive masked-K controller: per-request effective K is a
        # traced operand of the one spec-chunk program, chosen from the
        # measured acceptance (and warm-started from the stored prior)
        self._kctl: AdaptiveKController | None = None
        if self.spec is not None and self.spec.cfg.adaptive:
            self._kctl = AdaptiveKController(
                self.spec.cfg,
                # n-gram proposals are free; only the verify-width
                # position cost should pull K down then
                draft_cost=0.0 if self.spec.mode == "ngram" else None,
                prior=(self._autotune_record or {}).get("k_prior"),
            )

        self._state = self._init_state()
        self._decode = self._build_decode()
        if warm_buckets:
            self._warm()

    # --------------------------------------------------------- device state
    def _init_state(self):
        eng, S, L = self.engine, self.slots, self.L
        caches = eng.model.init_caches(S, L, dtype=eng.cache_dtype)
        # scalar per-layer write index -> per-slot vector (the serving
        # cache form nn/attention.py scatters by)
        caches = jax.tree.map(
            lambda c: jnp.zeros((S,), jnp.int32)
            if getattr(c, "ndim", None) == 0
            and jnp.issubdtype(c.dtype, jnp.integer) else c,
            caches,
        )
        state = {
            "caches": caches,
            "valid": jnp.zeros((S, L), bool),  # attendable cache slots
            "n_valid": jnp.zeros((S,), jnp.int32),  # logical token count
            "tok": jnp.zeros((S,), jnp.int32),  # last sampled, unfed token
            "seed": jnp.zeros((S,), jnp.uint32),
            "remaining": jnp.zeros((S,), jnp.int32),
            "live": jnp.zeros((S,), bool),
        }
        self._add_spec_state(state)
        mesh = eng.mesh
        if mesh.shape.get(eng.data_axis, 1) > 1 and S % mesh.shape[eng.data_axis] == 0:
            # slots ride the data axis exactly like engine batch rows
            def shard(x):
                spec = P(eng.data_axis, *([None] * (x.ndim - 1)))
                return jax.device_put(x, NamedSharding(mesh, spec))

            state = jax.tree.map(shard, state)
        else:
            state = self._on_mesh(state)
        return state

    def _on_mesh(self, state):
        """Fresh state placed as every program's output is: on the
        engine's mesh. A ``jnp.zeros`` carries no mesh in its type and
        a program's output does, so a program whose first call took
        fresh state was traced and compiled a second time on its next
        (``jax.device_put`` without a target places and commits
        nothing)."""
        return jax.device_put(state, NamedSharding(self.engine.mesh, P()))

    def _add_spec_state(self, state: dict) -> None:
        """Speculation state riding the donated serving tree: a per-slot
        draft KV cache (draft-model mode, same slot layout/capacity as
        the target view so one frontier and one validity mask serve
        both) or a slot-aligned token-id buffer (n-gram mode — the
        context prompt-lookup drafts from, entirely on device)."""
        if self.spec is None:
            return
        if self.spec.mode == "draft":
            state["draft"] = self.spec.init_draft_caches(self.slots, self.L)
        else:
            state["ids"] = jnp.zeros((self.slots, self.L), jnp.int32)

    def _fill_token(self) -> int:
        return self.gen.eos_token_id if self.gen.eos_token_id is not None else 0

    # ------------------------------------------------------------- autotune
    def _autotune_buckets(self) -> tuple[int, ...]:
        """The program-shape set this engine's tuning was measured
        against — part of the store key, so a reconfigured engine never
        trusts constants measured for different programs."""
        top = min(self.L, self.engine.max_len)
        buckets = range(self.prefill_block, top + 1, self.prefill_block)
        return tuple(list(buckets)[: self.prefill_cache_max])

    def _autotune_load(self) -> None:
        """Load + apply the persisted tuning record for this (jax,
        chip, model, buckets) key: flash-block overrides installed
        (before any trace), K prior staged for the controller. A miss
        — absent, corrupt, or stale-keyed — is a silent cold start."""
        t0 = time.perf_counter()
        self._autotune_key = store_key(
            model_fingerprint(self.engine.params), self._autotune_buckets()
        )
        rec = self._autotune.load(self._autotune_key)
        if rec is None:
            return
        applied = apply_flash_overrides(rec)
        paged_applied = apply_paged_overrides(rec)
        self._autotune_record = rec
        self.autotune_warm_start_s = round(time.perf_counter() - t0, 4)
        self._event(
            "autotune.warm_start", key=self._autotune_key,
            flash_overrides=applied,
            paged_overrides=paged_applied,
            has_k_prior=bool(rec.get("k_prior")),
            warm_start_s=self.autotune_warm_start_s,
        )

    def save_autotune(self, **extra) -> str | None:
        """Persist this process's measured knobs — the installed
        flash-block overrides, this engine's bucket set, the adaptive
        controller's K posterior, plus any caller extras (e.g. the
        ``autopair_draft`` verdict's JSON-safe ``["persistable"]`` form
        as ``draft_pair=``). Non-serializable extras are dropped with a
        warn event, never allowed to crash the save — persisting tuning
        is telemetry-grade, not load-bearing. Returns the written path,
        or None when no store is configured. Explicit on purpose: a
        loader must be able to trust that a warm start byte-identically
        re-reads what the measuring process wrote."""
        if self._autotune is None:
            return None
        import json

        from tensorlink_tpu.ops.flash import flash_block_overrides
        from tensorlink_tpu.ops.pallas.paged_decode import (
            paged_block_overrides,
        )

        with self._lock:  # a self-heal may be swapping the controller
            rec = {
                "flash_blocks": [list(t) for t in flash_block_overrides()],
                "paged_kernel": [list(t) for t in paged_block_overrides()],
                "prefill_buckets": list(self._autotune_buckets()),
            }
            if self._kctl is not None:
                rec["k_prior"] = self._kctl.prior()
        for k, v in extra.items():
            try:
                json.dumps(v)
            except TypeError:
                self._event(
                    "autotune.extra_dropped", "warn", key=k,
                    type=type(v).__name__,
                )
                continue
            rec[k] = v
        key = self._autotune_key or store_key(
            model_fingerprint(self.engine.params), self._autotune_buckets()
        )
        return str(self._autotune.save(key, rec))

    # ------------------------------------------------------------- programs
    def _build_decode(self):
        if self.spec is not None:
            return self._build_spec_chunk()
        eng = self.engine
        model, S, L, K = eng.model, self.slots, self.L, self.decode_chunk
        gen = self.gen
        temperature, top_k, top_p = (
            float(gen.temperature), int(gen.top_k), float(gen.top_p)
        )
        eos = gen.eos_token_id
        fill = self._fill_token()

        def sample_row(seed, n, logits_row):
            # key depends on (request seed, logical position) ONLY —
            # slot assignment and co-tenants cannot change the draw
            key = jax.random.fold_in(jax.random.key(seed), n)
            return sample_logits(logits_row, key, temperature, top_k, top_p)

        def tl_decode(params, state):
            def step(state, _):
                caches, valid = state["caches"], state["valid"]
                live, tok = state["live"], state["tok"]
                n_valid, remaining = state["n_valid"], state["remaining"]
                rows = jnp.arange(S)
                index = _cache_index(caches)
                # the fed token's cache slot becomes attendable for live
                # rows; a retired row's index parks at its final value
                # (its write is never validated, or dropped at capacity)
                with scope("serve.cache_write"):
                    valid = valid.at[rows, index].max(live, mode="drop")
                logits, caches = model.apply(
                    params,
                    tok[:, None],
                    caches=caches,
                    positions=n_valid[:, None],
                    mask=valid[:, None, None, :],
                )
                # the module advanced EVERY row's index by 1; only live
                # rows actually consumed a slot
                with scope("serve.cache_write"):
                    new_index = index + live.astype(jnp.int32)
                    caches = _with_cache_index(caches, new_index)
                    new_n_valid = n_valid + live.astype(jnp.int32)
                with scope("serve.sample"):
                    nxt = jax.vmap(sample_row)(
                        state["seed"], new_n_valid, logits[:, -1]
                    ).astype(jnp.int32)
                    emit = jnp.where(live, nxt, fill)
                with scope("serve.cache_write"):
                    remaining = remaining - live.astype(jnp.int32)
                    ended = remaining <= 0
                    if eos is not None:
                        ended = ended | (nxt == eos)
                    new_state = {
                        "caches": caches,
                        "valid": valid,
                        "n_valid": new_n_valid,
                        "tok": jnp.where(live, nxt, tok),
                        "seed": state["seed"],
                        "remaining": remaining,
                        "live": live & ~ended,
                    }
                return new_state, emit

            state, toks = jax.lax.scan(step, state, None, length=K)
            return state, toks  # toks: [K, S]

        # donate the whole serving state: the KV cache updates in place
        # across chunk calls instead of being copied per dispatch
        return jax.jit(tl_decode, donate_argnums=(1,))

    # ----------------------------------------------------- speculative chunk
    def _spec_open_mask(self, state, f0):
        """History-validity mask for the verify/draft passes, OPEN at and
        after the frontier: the T==K+1 per-row attention path bounds each
        query at ``kslot <= index + t`` internally, so opening the fresh
        region here cannot leak future slots — it only admits the chunk's
        own causal prefix. (The paged engine overrides this to None: its
        rows are never padded, so the in-logical-coordinates causality of
        the paged attention path is already exact.)"""
        ar = jnp.arange(self.L)[None, :]
        return (state["valid"] | (ar >= f0[:, None]))[:, None, None, :]

    def _build_spec_chunk(self):
        """ONE jitted program for speculative serving: ``rounds`` rounds
        of draft-K + verify-K-in-one-target-weight-pass, whole state
        donated. Per round and live row it emits 1..K+1 tokens (the
        accepted prefix plus the correction/bonus) and rolls the KV
        write frontier back to the first rejection — an index reset
        only: rejected scatter writes sit at/after the rolled-back
        frontier, are never validated, and the next round's verify
        overwrites them before reading (nn/attention.py T>1 per-row
        path / the paged path's logical-coordinate causality).

        MASKED K: the program is compiled at ``k_max = cfg.k`` proposal
        width, and a per-row effective K rides in as the TRACED operand
        ``k_eff [S]`` — the adaptive controller changes a request's K
        between dispatches without a single retrace (tlint TL501 /
        tlhlo TLH105: still ONE spec program per engine). Row ``s``
        spends at most ``k_eff[s]`` proposals per round; the draft
        scan's entropy early-exit can retire a row even earlier
        (``k_live <= k_eff``), and ``spec_verify``'s own k_live clamp
        keeps the output distribution exactly the target's at any mask.

        Outputs per dispatch: ``toks [R, K+1, S]``, ``n_emit [R, S]``
        (0 marks a row that was not live that round — the host's
        liveness signal), ``n_acc [R, S]`` (accepted proposals BEFORE
        the EOS/budget clips — the draft-quality signal), ``fallback
        [R, S]`` (n-gram rows that found no match and burned the
        pass), and ``n_prop [R, S]`` (proposals the row actually stood
        behind — the acceptance-rate denominator under masking)."""
        eng, spec = self.engine, self.spec
        model, S, L = eng.model, self.slots, self.L
        K, R = spec.cfg.k, spec.cfg.rounds
        gen = self.gen
        temperature, top_k, top_p = (
            float(gen.temperature), int(gen.top_k), float(gen.top_p)
        )
        eos = gen.eos_token_id
        draft_mode = spec.mode == "draft"
        draft_fn = spec.build_draft_fn(gen) if draft_mode else None

        def round_fn(params, dparams, state, k_eff):
            caches, valid = state["caches"], state["valid"]
            live, tok = state["live"], state["tok"]
            n_valid, remaining = state["n_valid"], state["remaining"]
            seed = state["seed"]
            f0 = _cache_index(caches)  # [S] target write frontier
            open_mask = self._spec_open_mask(state, f0)
            if draft_mode:
                props, dlg, dcaches, k_live = draft_fn(
                    dparams, state["draft"], tok, n_valid, seed,
                    open_mask, k_eff, live,
                )
                fb = jnp.zeros((S,), bool)
            else:
                props, found = ngram_propose(
                    state["ids"], valid, f0, tok, K, spec.cfg.ngram
                )
                dlg = None
                fb = live & ~found
                k_live = k_eff  # no draft distribution to early-exit on
            # ONE target weight pass verifies all K proposals (+ the
            # bonus position): feed [tok, d_1..d_K]
            toks_in = jnp.concatenate([tok[:, None], props], axis=1)
            positions = n_valid[:, None] + jnp.arange(K + 1)[None, :]
            logits, caches = model.apply(
                params, toks_in, caches=caches, positions=positions,
                mask=open_mask,
            )
            with scope("serve.sample"):
                if dlg is None:
                    def vrow(lg, pr, s, n, kl):
                        return spec_verify(
                            lg, pr, spec.verify_key(s, n),
                            temperature, top_k, top_p, k_live=kl,
                        )

                    n_raw, emitted = jax.vmap(vrow)(
                        logits, props, seed, n_valid, k_live
                    )
                else:
                    def vrow(lg, pr, dl, s, n, kl):
                        return spec_verify(
                            lg, pr, spec.verify_key(s, n),
                            temperature, top_k, top_p, draft_logits=dl,
                            k_live=kl,
                        )

                    n_raw, emitted = jax.vmap(vrow)(
                        logits, props, dlg, seed, n_valid, k_live
                    )
            idxk = jnp.arange(K + 1)
            # draft-quality truth BEFORE the EOS/budget clips below: a
            # clipped emission is the REQUEST ending, not the draft
            # being wrong — charging it as rejection would deflate
            # acceptance_rate (and trip tldiag LOW-ACCEPT) on
            # short-generation traffic with a perfectly good draft.
            # (spec_verify already capped n_raw - 1 at k_live, so a
            # masked position is neither accepted nor attempted.)
            n_acc = jnp.where(live, n_raw - 1, 0)
            n_prop = jnp.where(live, k_live, 0).astype(jnp.int32)
            if eos is not None:
                hit = (emitted == eos) & (idxk[None, :] < n_raw[:, None])
                eos_pos = jnp.min(
                    jnp.where(hit, idxk[None, :], K + 1), axis=1
                )
                n_raw = jnp.minimum(n_raw, eos_pos + 1)
            # budget clip keeps host and device token counts aligned
            # (remaining >= 1 on live rows; max guards parked garbage)
            n_raw = jnp.minimum(n_raw, jnp.maximum(remaining, 1))
            n_emit = jnp.where(live, n_raw, 0).astype(jnp.int32)
            new_remaining = remaining - n_emit
            ended = new_remaining <= 0
            if eos is not None:
                ended = ended | (eos_pos < n_emit)
            tok_new = jnp.take_along_axis(
                emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
            )[:, 0]
            with scope("serve.cache_write"):
                ar = jnp.arange(L)[None, :]
                newly = (ar >= f0[:, None]) & (ar < (f0 + n_emit)[:, None])
                nf = f0 + n_emit  # rolled-back frontier (rollback = reset)
                new_state = {
                    **state,
                    "caches": _with_cache_index(caches, nf),
                    "valid": valid | newly,
                    "n_valid": n_valid + n_emit,
                    "tok": jnp.where(live, tok_new, tok),
                    "remaining": new_remaining,
                    "live": live & ~ended,
                }
                if draft_mode:
                    # draft frontier follows the target's exactly (the K+1
                    # draft steps covered every slot up to f0+K, so no hole)
                    new_state["draft"] = _with_cache_index(dcaches, nf)
                else:
                    # bank the fed tokens for future prompt-lookups: slots
                    # [f0, f0+n_emit) now hold genuine sequence tokens;
                    # later slots hold rejected garbage past the frontier
                    rows = jnp.arange(S)[:, None]
                    new_state["ids"] = state["ids"].at[
                        rows, f0[:, None] + idxk[None, :]
                    ].set(toks_in, mode="drop")
            return new_state, (
                emitted.T, n_emit, n_acc.astype(jnp.int32), fb, n_prop,
            )

        def tl_spec_chunk(params, dparams, state, k_eff):
            # guard garbage input: the device contract below (emission
            # and block growth both bounded by k_eff + 1) only holds
            # inside [1, K]
            k_eff = jnp.clip(k_eff.astype(jnp.int32), 1, K)
            state, out = jax.lax.scan(
                lambda st, _: round_fn(params, dparams, st, k_eff),
                state, None, length=R,
            )
            return (state, *out)

        return self._jit_program(tl_spec_chunk)

    def _spec_k_array(self) -> list[int]:
        """Per-slot effective K for the NEXT dispatched spec chunk:
        the controller's per-request choice for occupied slots, k_max
        for free/parked rows (their k is never consumed — the device
        masks by liveness)."""
        K = self.spec.cfg.k
        if self._kctl is None:
            return [K] * self.slots
        return [
            K if r is None else min(self._kctl.k_for(r.rid), K)
            for r in self._slot_req
        ]

    def _decode_extra(self) -> tuple:
        """Trailing traced operands of the decode/spec program — the
        masked-K array under speculation, nothing otherwise. Consumes
        the step()-staged array when one exists so the paged engine's
        block-growth bound and the dispatched operand can never skew
        (a drain between the two may move the controller)."""
        if self.spec is None:
            return ()
        ks = self._k_dispatch
        self._k_dispatch = None
        if ks is None:
            ks = self._spec_k_array()
        if self._kctl is not None:
            # count only rows live on THIS chunk: a slot mid-chunked-
            # prefill occupies _slot_req but emits nothing, and would
            # bias k_mean toward the prior whenever prefill overlaps
            # decode (the common paged regime)
            pending = self._pending_slots()
            self._kctl.note_dispatch(
                k for s, (r, k) in enumerate(zip(self._slot_req, ks))
                if r is not None and s not in pending
            )
        return (jnp.asarray(np.asarray(ks, np.int32)),)

    def _jit_program(self, fn):
        """jit one serving program written as ``fn(params, dparams,
        state, *rest)``: draft mode threads the draft weights as a real
        argument (a closure capture would bake them into the program as
        constants); otherwise ``dparams`` is bound to None and dropped
        from the traced signature. The donated-state protocol matching
        ``_program_args`` lives HERE and nowhere else — the spec chunk
        and both prefill forms must never diverge on it."""
        if self.spec is not None and self.spec.mode == "draft":
            return jax.jit(fn, donate_argnums=(2,))

        def run(params, state, *a):
            return fn(params, None, state, *a)

        # the function's name is the program's in a device trace
        # (jit_tl_prefill_chunk, ...): both forms carry fn's
        run.__name__ = run.__qualname__ = fn.__name__
        return jax.jit(run, donate_argnums=(1,))

    def _decode_program_name(self) -> str:
        return "spec_chunk" if self.spec is not None else "decode"

    def _dispatch_decode(self) -> tuple:
        """Dispatch one decode/spec chunk; returns (device payload for
        the in-flight queue ((toks,) plain, (toks, n_emit, n_acc,
        fallback, n_prop) speculative), dispatch-timer token)."""
        h = chaos.ACTIVE  # fault injection (runtime/chaos.py): a
        if h is not None:  # disarmed harness costs one identity test
            h.apply_sync(
                "serving.dispatch", program=self._decode_program_name()
            )
        out = self._decode(*self._program_args(), *self._decode_extra())
        self._state = out[0]
        disp = None
        if self._timer is not None:
            # probe = the chunk's token OUTPUT (never the donated state)
            disp = self._timer.dispatch(self._decode_program_name(), out[1])
        return out[1:], disp

    def _bucket(self, t0: int) -> int:
        b = -(-t0 // self.prefill_block) * self.prefill_block
        return min(b, self.L)

    def _build_prefill(self, Tp: int):
        eng = self.engine
        model, S, L = eng.model, self.slots, self.L
        gen = self.gen
        temperature, top_k, top_p = (
            float(gen.temperature), int(gen.top_k), float(gen.top_p)
        )
        eos = gen.eos_token_id
        spec = self.spec
        draft_mode = spec is not None and spec.mode == "draft"

        def tl_prefill(params, dparams, state, ids, pad_mask, slot, seed,
                       max_new):
            pos = jnp.maximum(jnp.cumsum(pad_mask, axis=-1) - 1, 0)
            nv = pad_mask.sum(-1)[0].astype(jnp.int32)
            small = model.init_caches(1, Tp, dtype=eng.cache_dtype)
            # fresh-keys prefill over the just-projected k/v (engine
            # contract): key must be a real prompt token at or before
            # the query; left padding => slot order == logical order
            qslot = jnp.arange(Tp)[None, None, :, None]
            kslot = jnp.arange(Tp)[None, None, None, :]
            causal = (kslot <= qslot) & pad_mask.astype(bool)[:, None, None, :]
            logits, small = model.apply(
                params, ids, caches=small, positions=pos, mask=causal
            )
            with scope("serve.sample"):
                key0 = jax.random.fold_in(jax.random.key(seed), nv)
                tok0 = sample_logits(
                    logits[0, -1], key0, temperature, top_k, top_p
                ).astype(jnp.int32)
            done0 = max_new <= 1
            if eos is not None:
                done0 = done0 | (tok0 == eos)

            def graft(big, small_leaf):
                if getattr(big, "ndim", None) == 4:
                    return jax.lax.dynamic_update_slice(
                        big, small_leaf.astype(big.dtype), (slot, 0, 0, 0)
                    )
                if _is_index_leaf(big):  # per-slot write index
                    return big.at[slot].set(small_leaf.astype(big.dtype))
                return big

            with scope("serve.cache_write"):
                caches = jax.tree.map(graft, state["caches"], small)
                valid_row = jnp.zeros((L,), bool).at[:Tp].set(
                    pad_mask[0].astype(bool)
                )
                new_state = {
                    **state,
                    "caches": caches,
                    "valid": state["valid"].at[slot].set(valid_row),
                    "n_valid": state["n_valid"].at[slot].set(nv),
                    "tok": state["tok"].at[slot].set(tok0),
                    "seed": state["seed"].at[slot].set(seed),
                    "remaining": state["remaining"].at[slot].set(
                        (max_new - 1).astype(jnp.int32)
                    ),
                    "live": state["live"].at[slot].set(~done0),
                }
            if draft_mode:
                # the draft's own prompt pass: identical slot layout, so
                # the same graft lands it beside the target's cache
                dmodel = spec.draft.model
                dsmall = dmodel.init_caches(
                    1, Tp, dtype=spec.draft.cache_dtype
                )
                _, dsmall = dmodel.apply(
                    dparams, ids, caches=dsmall, positions=pos, mask=causal
                )
                new_state["draft"] = jax.tree.map(
                    graft, state["draft"], dsmall
                )
            elif spec is not None:
                # n-gram context buffer: prompt ids in slot layout (pads
                # stay garbage — excluded via the validity mask)
                new_state["ids"] = jax.lax.dynamic_update_slice(
                    state["ids"], ids, (slot, 0)
                )
            return new_state, tok0

        return self._jit_program(tl_prefill)

    def _get_prefill(self, Tp: int):
        """Compiled prefill program for bucket ``Tp`` from the bounded
        LRU cache — built, AOT-lowered, and compiled on first use with
        ``compile_s`` logged to the flight recorder (the cold-start
        number ROADMAP item 5 tracks). Evicting a bucket only means a
        recompile if that prompt length ever returns."""
        fn = self._prefill_jit.get(Tp)
        if fn is not None:
            self._prefill_jit.move_to_end(Tp)
            return fn
        if self._timer is not None:
            # about to pay an XLA compile: stamp anything already-ready
            # NOW so the compile seconds don't inflate an in-flight
            # dispatch's busy window (poll granularity, cold start)
            self._timer.poll()
        t0 = time.perf_counter()
        jitfn = self._build_prefill(Tp)
        i32 = jnp.int32
        try:
            # lower/compile ahead of the first call: admission then
            # dispatches a ready executable, and the compile cost is a
            # measured, attributable event instead of a mystery stall
            # inside the first unlucky submit()
            fn = jitfn.lower(
                *self._program_args(),
                jax.ShapeDtypeStruct((1, Tp), i32),
                jax.ShapeDtypeStruct((1, Tp), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((), jnp.uint32),
                jax.ShapeDtypeStruct((), i32),
            ).compile()
            aot = True
        except Exception:  # noqa: BLE001 — AOT is an optimization only
            fn = jitfn
            aot = False
        compile_s = self._record_compile("prefill", t0, aot, bucket=Tp)
        if aot:
            # per-bucket flops differ; the LAST compiled bucket's cost
            # stands in for "prefill" (advisory MFU, not a pin)
            self._note_cost("prefill", fn)
        if self.metrics is not None:
            self.metrics.observe("serving_prefill_compile_s", compile_s)
        self._prefill_jit[Tp] = fn
        while len(self._prefill_jit) > self.prefill_cache_max:
            old, _ = self._prefill_jit.popitem(last=False)
            self._event("serving.prefill_evict", bucket=old)
        return fn

    def _program_args(self) -> tuple:
        """Leading (params[, draft params], state) args EVERY serving
        program (decode/spec chunk and the prefill forms) takes — the
        draft-model form threads the draft weights as a real argument
        (a closure capture would bake them into the program as
        constants). One method on purpose: decode and prefill diverging
        here would mean two incompatible donated-state protocols."""
        if self.spec is not None and self.spec.mode == "draft":
            return (self.engine.params, self.spec.draft_params, self._state)
        return (self.engine.params, self._state)

    def _warm(self) -> None:
        """Pre-compile the decode chunk and the prefill bucket set at
        construction (``warm_buckets=True``): first-request TTFT then
        measures serving, not XLA. Buckets warm smallest-first (typical
        traffic skews short) up to the prefill-cache bound."""
        t0 = time.perf_counter()
        aot = True
        try:
            self._decode = self._decode.lower(
                *self._program_args(), *self._decode_extra()
            ).compile()
        except Exception:  # noqa: BLE001 — fall back to lazy jit
            aot = False
        self._record_compile("decode", t0, aot)
        if aot:
            self._note_cost(self._decode_program_name(), self._decode)
        # the same bucket set the autotune store keys on — one
        # computation on purpose, so persisted tuning can never key on
        # a different set than the engine actually warms
        for Tp in self._autotune_buckets():
            self._get_prefill(Tp)

    # ---------------------------------------------------------------- audit
    def _audit_dtype(self) -> str:
        return declared_compute_dtype(self.engine.params)

    def _audit_decode_extra(self) -> tuple:
        """Side-effect-free stand-in for ``_decode_extra`` (same avals):
        auditing a live engine must not feed the controller's dispatch
        accounting or steal a staged masked-K array."""
        if self.spec is None:
            return ()
        return (jnp.full((self.slots,), self.spec.cfg.k, jnp.int32),)

    def audit_programs(self) -> list[dict]:
        """Compiled-program inventory for tlhlo (analysis/hlo.py): one
        entry per load-bearing program with the donated-leaf count the
        input/output aliasing must cover and a ``lower()`` thunk.
        ``lower()`` needs only avals, so nothing here executes, copies,
        or invalidates the (donated) live serving state — safe on a
        serving engine mid-traffic. Fresh jits are built on purpose:
        ``_warm()`` may have replaced the engine's own handles with
        AOT-compiled executables, which cannot re-lower."""
        dt = self._audit_dtype()
        with self._lock:  # snapshot the state tree vs in-flight chunks
            donated = len(jax.tree.leaves(self._state))
            args = self._program_args()
            extra = self._audit_decode_extra()
            spec_on = self.spec is not None  # a self-heal may swap it
        progs = [{
            "name": "spec_chunk" if spec_on else "decode",
            "dtype": dt,
            "donated": donated,
            "lower": lambda: self._build_decode().lower(*args, *extra),
        }]
        Tp = self._bucket(1)  # smallest prefill bucket
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        def lower_prefill(Tp=Tp):
            return self._build_prefill(Tp).lower(
                *args, sds((1, Tp), i32), sds((1, Tp), i32),
                sds((), i32), sds((), jnp.uint32), sds((), i32),
            )

        progs.append({
            # a speculative engine's prefill is a DIFFERENT program
            # (it grafts the draft cache / n-gram ids into the larger
            # donated tree) — name it apart so both get audited
            "name": f"prefill_b{Tp}" + ("_spec" if spec_on else ""),
            "dtype": dt,
            "donated": donated,
            "lower": lower_prefill,
        })
        return progs

    # --------------------------------------------------------------- events
    def _event(self, kind: str, severity: str = "info", **data) -> None:
        if self.recorder is not None:
            try:
                self.recorder.record(kind, severity, **data)
            except Exception:  # noqa: BLE001 — telemetry must not serve 500s
                pass

    def _note_cost(self, program: str, compiled) -> None:
        """Stash an AOT-compiled program's XLA cost analysis (flops +
        bytes accessed) under the DispatchTimer program name, so
        ``device_time`` can derive per-program MFU/MBU from measured
        device-busy time. Opportunistic: captured only where an AOT
        compile already happened — never a hot-path compile."""
        if self._timer is None:
            return
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            rec = {}
            if cost.get("flops"):
                rec["flops"] = float(cost["flops"])
            if cost.get("bytes accessed"):
                rec["bytes"] = float(cost["bytes accessed"])
            if rec:
                self._prog_cost[program] = rec
        except Exception:  # noqa: BLE001 — advisory; not every backend reports
            pass

    def _record_compile(self, program: str, t0: float, aot: bool = True,
                        **extra) -> float:
        """Emit one ``serving.compile`` event; when the persistent
        compilation cache is active, report whether this compile was
        served from it (no new cache entries = hit — the ROADMAP-5
        restart-reuses-kernels evidence)."""
        compile_s = time.perf_counter() - t0
        data = dict(
            program=program, compile_s=round(compile_s, 4), aot=aot,
            **extra,
        )
        if self._cc_dir:
            n = cache_entries(self._cc_dir)
            # aot=False means the AOT compile FAILED and fell back to
            # lazy jit: nothing compiled yet, so "no new entries" is
            # not a hit — stamping one would fake the restart-reuses-
            # kernels evidence exactly when it's broken. The counter
            # still refreshes so the lazy compile (whenever it lands)
            # is not misattributed to the next recorded program.
            if aot:
                # n > 0 guards a silently-inoperative cache (backend
                # pinned off, read-only dir): an empty directory that
                # never grows must read as misses, not as a perfect
                # hit streak fabricating the restart evidence
                data["compile_cache_hit"] = bool(
                    0 < n <= self._cc_entries
                )
                if self.metrics is not None:
                    self.metrics.incr(
                        "compile_cache_hits_total"
                        if data["compile_cache_hit"]
                        else "compile_cache_misses_total"
                    )
            self._cc_entries = n
        self._event("serving.compile", **data)
        return compile_s

    # ----------------------------------------------------------------- API
    def submit(
        self, ids, *, max_new: int | None = None, seed: int = 0,
        priority: Priority | int | str = Priority.STANDARD,
        deadline_s: float | None = None,
        tenant: str | None = None,
        _hold: bool = False,
    ) -> int:
        """Enqueue one prompt (1-D token array). Returns a request id;
        never blocks. ``priority`` is the request's SLO class
        (:class:`Priority`): it orders admission from the queue and
        protects the stream under pool pressure (BATCH is preempted /
        shed before STANDARD before INTERACTIVE). ``deadline_s``
        (seconds from now) makes lateness a typed failure: admission
        raises ``DeadlineExceededError`` when the measured TPOT proves
        the decode alone cannot finish in time, and a queued/running
        request whose deadline passes is cancelled — slot and KV
        blocks freed — with ``result()`` raising the same type.

        Raises ``PromptTooLongError`` when the prompt plus its token
        budget cannot fit a slot's cache region, and an
        ``OverloadedError`` (``QueueFullError`` /
        ``PoolOverloadedError``) carrying a measured ``retry_after_s``
        past ``max_queue`` pending admissions — unless a strictly
        lower-priority queued request can be shed to make room."""
        ids = np.asarray(ids).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        max_new = int(max_new if max_new is not None else self.gen.max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        prio = _coerce_priority(priority)
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        t0 = int(ids.size)
        with self._lock:
            # a due mode downgrade applies BEFORE this prompt admits:
            # the new request must not prefill into a program the
            # engine has already measured as a loss
            self._maybe_self_heal()
            # under the lock: the paged fit check reads the block pool,
            # which a concurrent self-heal rebuild swaps (tlint TL601)
            self._check_fit(t0, max_new)
            self._check_deadline_feasible(max_new, deadline_s, prio)
            # expired work frees its slot/blocks before this arrival
            # competes for them
            self._expire_deadlines_locked()
            # fill free slots first so max_queue bounds genuinely
            # WAITING work, not work a free slot could take right now
            self._admit_waiting()
            self._check_backpressure(prio)
            rid = self._next_rid
            self._next_rid += 1
            now = time.perf_counter()
            req = _Request(
                rid=rid, ids=ids, max_new=max_new, seed=int(seed),
                submitted_at=now,
                priority=prio, deadline_s=deadline_s,
                deadline_at=(
                    now + deadline_s if deadline_s is not None else None
                ),
                # wall-clock anchor: the span timeline converts the
                # monotonic stamps against this pair
                submitted_ns=time.time_ns(),
                # billing identity for the work receipt; clamped — it
                # crosses trust boundaries verbatim
                tenant=(str(tenant)[:128] if tenant else None),
            )
            # internal (prefill_export): the hold must be set UNDER the
            # admission lock — set after submit() returns, a concurrent
            # pump thread could dispatch decode for the request in the
            # race window and consume the first token the export needs
            req.hold = _hold
            if deadline_s is not None:
                self._deadlined += 1
            self._requests[rid] = req
            self._admit_or_queue(req)
        if self.metrics is not None:
            self.metrics.incr("serving_requests_total")
            self.metrics.incr(
                f"serving_requests_total:{_PRIO_NAMES[prio]}"
            )
        self._event(
            "serving.submit", rid=rid, prompt_len=t0,
            priority=_PRIO_NAMES[prio],
        )
        return rid

    # ------------------------------------------------- admission control
    def _check_deadline_feasible(
        self, max_new: int, deadline_s: float | None, prio: int
    ) -> None:
        """Reject work whose deadline is PROVABLY unmeetable: even with
        zero queueing, ``max_new`` tokens cost at least
        ``(max_new - 1) x measured TPOT`` of decode — a floor built
        from this engine's own finished requests, never a guess. With
        nothing measured yet (cold engine), nothing is provable and
        the request admits."""
        if deadline_s is None or self._tpot_ewma is None:
            return
        floor = (max_new - 1) * self._tpot_ewma
        if floor <= deadline_s:
            return
        self._deadline_misses += 1
        if self.metrics is not None:
            self.metrics.incr("serving_deadline_miss_total")
            self.metrics.incr(
                f"serving_deadline_miss_total:{_PRIO_NAMES[prio]}"
            )
        self._event(
            "serving.deadline_miss", "warn", phase="admission",
            priority=_PRIO_NAMES[prio], deadline_s=deadline_s,
            service_floor_s=round(floor, 4),
        )
        raise DeadlineExceededError(
            f"deadline {deadline_s}s is provably unmeetable: "
            f"{max_new} tokens x measured TPOT "
            f"{self._tpot_ewma:.5f}s/token = {floor:.3f}s of decode "
            "alone"
        )

    def _pool_pressure_locked(self) -> float:
        return 1.0  # contiguous slots: the queue estimate is complete

    def _retry_after_locked(self) -> float:
        """Measured retry-after: TPOT x the token backlog ahead of a
        new arrival / decode width x pool pressure. Uses the EWMA of
        this engine's own finished requests; before anything finished
        the fallback is one conservative guess — replaced by a
        measurement the moment one exists."""
        tpot = (
            self._tpot_ewma if self._tpot_ewma is not None
            else _RETRY_TPOT_FALLBACK_S
        )
        ahead = 0
        for r in self._slot_req:
            if r is not None and not r.done:
                ahead += max(r.max_new - len(r.tokens), 1)
        for r in self._queue:
            ahead += max(r.max_new - len(r.tokens), 1)
        eta = tpot * ahead / max(self.slots, 1)
        return round(max(eta * self._pool_pressure_locked(), tpot), 4)

    def _note_shed(
        self, prio: int, reason: str, retry_after_s: float | None,
        rid: int | None = None,
    ) -> None:
        self._sheds += 1
        self._shed_by_prio[prio] = self._shed_by_prio.get(prio, 0) + 1
        self._last_shed_at = time.perf_counter()
        name = _PRIO_NAMES.get(prio, "standard")
        if self.metrics is not None:
            # bounded cardinality by construction: Priority is a closed
            # 3-member enum, so the per-class counter family is fixed
            self.metrics.incr("serving_shed_total")
            self.metrics.incr(f"serving_shed_total:{name}")
        self._event(
            "serving.shed", "warn", rid=rid, priority=name,
            reason=reason, retry_after_s=retry_after_s,
            queued=len(self._queue),
        )

    def _displace_for_locked(self, prio: int) -> bool:
        """Make queue room for a higher-priority arrival by shedding
        the newest queued request of a STRICTLY lower class (its
        result() raises the OverloadedError it would have gotten at
        submit, retry-after included). False when nothing queued is
        lower-priority — the arrival itself must shed."""
        if not self._queue:
            return False
        victim = max(self._queue, key=lambda r: (r.priority, r.rid))
        if victim.priority <= prio:
            return False
        ra = self._retry_after_locked()
        self._abort_locked(victim, OverloadedError(
            f"request {victim.rid} shed: displaced by a "
            f"{_PRIO_NAMES[prio]} admission under backpressure; "
            f"retry in {ra}s",
            retry_after_s=ra, reason="displaced",
        ))
        return True

    def _abort_locked(self, req: _Request, error: BaseException) -> None:
        """Terminal failure for a queued or running request: ``failed``
        set (result() raises it), queue entry removed, slot and — on
        the paged engine — device row + KV blocks freed via the usual
        ``_finish`` path. Caller holds the scheduler lock."""
        req.failed = error
        name = _PRIO_NAMES.get(req.priority, "standard")
        if isinstance(error, DeadlineExceededError):
            self._deadline_misses += 1
            if self.metrics is not None:
                self.metrics.incr("serving_deadline_miss_total")
                self.metrics.incr(
                    f"serving_deadline_miss_total:{name}"
                )
            self._event(
                "serving.deadline_miss", "warn", rid=req.rid,
                priority=name, deadline_s=req.deadline_s,
                phase="queued" if req.slot is None else "running",
            )
        elif isinstance(error, OverloadedError):
            self._note_shed(
                req.priority, error.reason, error.retry_after_s,
                rid=req.rid,
            )
        if req in self._queue:
            self._queue.remove(req)
        if req.slot is not None and not req.done:
            self._drain_for_abort(req)
        if not req.done:
            self._finish(req)

    def _drain_for_abort(self, req: _Request) -> None:
        """Pre-``_finish`` safety for aborting a RUNNING request. The
        contiguous engine needs none: a slot's cache region is private,
        and the next admission's prefill fully resets the row. The
        paged engine overrides (retire the device row, then drain
        in-flight chunks) — blocks must never return to the pool while
        a dispatched chunk could still write through the old table."""

    def cancel(self, rid: int, *, error: BaseException | None = None) -> bool:
        """Cancel a queued or running request: its slot and (paged) KV
        blocks free immediately and ``result(rid)`` raises. Returns
        False when the request is unknown or already finished."""
        with self._lock:
            req = self._requests.get(rid)
            if req is None or req.done:
                return False
            self._abort_locked(
                req, error or ServingError(f"request {rid} cancelled")
            )
            return True

    def _expire_deadlines_locked(self) -> None:
        """Cancel queued/running requests whose deadline passed — an
        abandoned deadline must free its slot and blocks for work that
        can still make its SLO, not pin them until max-tokens. O(1)
        when no live request carries a deadline."""
        if not self._deadlined:
            return
        now = time.perf_counter()
        expired = [
            r for r in self._queue
            if r.deadline_at is not None and r.deadline_at < now
        ]
        expired += [
            r for r in self._slot_req
            if r is not None and not r.done
            and r.deadline_at is not None and r.deadline_at < now
        ]
        for req in expired:
            self._abort_locked(req, DeadlineExceededError(
                f"request {req.rid} missed its {req.deadline_s}s "
                "deadline; cancelled", rid=req.rid,
            ))

    def _check_fit(self, t0: int, max_new: int) -> None:
        if t0 + max_new > self.engine.max_len:
            raise PromptTooLongError(
                f"prompt {t0} + new {max_new} exceeds engine max_len "
                f"{self.engine.max_len}"
            )
        if self._bucket(t0) < t0 or self._bucket(t0) + max_new > self.L:
            raise PromptTooLongError(
                f"prompt {t0} (padded {self._bucket(t0)}) + new {max_new} "
                f"exceeds the slot cache region ({self.L} slots)"
            )

    def _check_backpressure(
        self, prio: int = int(Priority.STANDARD)
    ) -> None:
        if (
            self.max_queue is None
            or self._free
            or len(self._queue) < self.max_queue
        ):
            return
        if self._displace_for_locked(prio):
            return  # a lower-priority queued request was shed instead
        ra = self._retry_after_locked()
        self._note_shed(prio, "queue_full", ra)
        raise QueueFullError(
            f"{len(self._queue)} requests pending (max_queue="
            f"{self.max_queue}); retry in {ra}s",
            retry_after_s=ra,
        )

    def _admit_or_queue(self, req: _Request) -> None:
        if self._free:
            self._admit(req)  # prefill dispatches immediately
        else:
            self._queue.append(req)

    def _next_queued_locked(self) -> _Request:
        """Admission order: priority class first, FIFO (rid) within —
        a preempted request resumes ahead of later same-class arrivals
        because it keeps its original rid."""
        return min(self._queue, key=lambda r: (r.priority, r.rid))

    def _admit_waiting(self) -> None:
        while self._free and self._queue:
            req = self._next_queued_locked()
            self._queue.remove(req)
            self._admit(req)

    def _admit(self, req: _Request) -> None:
        slot = self._free.pop()
        req.slot = slot
        self._slot_req[slot] = req
        t0 = int(req.ids.size)
        Tp = self._bucket(t0)
        ids = np.zeros((1, Tp), np.int32)
        pm = np.zeros((1, Tp), np.int32)
        ids[0, Tp - t0:] = req.ids
        pm[0, Tp - t0:] = 1
        fn = self._get_prefill(Tp)
        args = (
            *self._program_args(), jnp.asarray(ids),
            jnp.asarray(pm), jnp.int32(slot), jnp.uint32(req.seed),
            jnp.int32(req.max_new),
        )
        self._note_admitted(req)
        with region("serve.prefill_dispatch"):
            try:
                self._state, tok0 = fn(*args)
            except (TypeError, ValueError):
                # an AOT executable is stricter than jit about input
                # shardings/avals; if a jax-version quirk rejects the
                # call (argument checking happens before the donated
                # state is consumed), fall back to the plain jit path
                # for this bucket
                fn = self._prefill_jit[Tp] = self._build_prefill(Tp)
                self._state, tok0 = fn(*args)
        # admission IS the prefill dispatch on this engine (the paged
        # engine stamps these apart, chunked prefill runs later steps)
        req.prefill_started_at = time.perf_counter()
        req.prefill_chunks += 1
        req.first_token = tok0
        if self._timer is not None:
            req.disp = self._timer.dispatch("prefill", tok0)
            if self.metering:
                req.disp_hist.append(req.disp)
        self._event("serving.admit", rid=req.rid, slot=slot, padded=Tp)

    def _note_admitted(self, req: _Request) -> None:
        """Stamp the slot grant; the first one (a preempted request
        gets another) is the ``tl.serve.admitted`` event of a capture."""
        first = req.admitted_at is None
        req.admitted_at = time.perf_counter()
        if first:
            event(
                "serve.admitted", rid=req.rid,
                waited_ms=(req.admitted_at - req.submitted_at) * 1e3,
            )

    def _maybe_record_ttft(self, req: _Request) -> None:
        if req.first_token_at is not None or req.first_token is None:
            return
        if req.failed is not None:
            # a shed/cancelled request's first token may still drain
            # after the abort — the scheduler killed it, so its "TTFT"
            # is not a latency the per-class histograms should serve
            return
        ready = getattr(req.first_token, "is_ready", None)
        if ready is None or ready():
            req.first_token_at = time.perf_counter()
            ttft = req.first_token_at - req.submitted_at
            self._ttft_ewma = (
                ttft if self._ttft_ewma is None
                else 0.8 * self._ttft_ewma + 0.2 * ttft
            )
            if self.metrics is not None:
                self.metrics.observe_hist("serving_ttft_s", ttft)
                # per-SLO-class latency (bounded: Priority is a closed
                # 3-member enum) — the bench/tldiag per-priority p99s
                self.metrics.observe_hist(
                    f"serving_ttft_s:{_PRIO_NAMES[req.priority]}", ttft,
                    buckets=_TTFT_CLASS_BUCKETS,
                )

    def _ewma_decomp(self, name: str, value: float) -> None:
        old = self._ttft_decomp.get(name)
        self._ttft_decomp[name] = round(
            value if old is None else 0.8 * old + 0.2 * value, 6
        )

    def _emit_request_timeline(self, req: _Request) -> None:
        """Per-request span tree at finish: queue wait, prefill, decode
        stitched under one ``serving.request`` root (its own trace in
        /spans — one Perfetto row per request), plus the TTFT-
        decomposition EWMAs ``stats()`` serves. Stamps were taken on
        the hot path; reconstruction here costs one finished request's
        worth of work, never a per-token span."""
        sub, adm = req.submitted_at, req.admitted_at
        ps, ft = req.prefill_started_at, req.first_token_at
        if adm is not None:
            self._ewma_decomp("queue_s", adm - sub)
            if ps is not None:
                self._ewma_decomp("dispatch_s", ps - adm)
                if ft is not None:
                    self._ewma_decomp("prefill_s", ft - ps)
        if self.tracer is None or not req.submitted_ns:
            return

        def ns(t: float | None) -> int | None:
            return (
                None if t is None
                else req.submitted_ns + int((t - sub) * 1e9)
            )

        end = ns(req.finished_at) or req.submitted_ns
        root = self.tracer.record_span(
            "serving.request", req.submitted_ns, end,
            {
                "rid": req.rid, "tokens": len(req.tokens),
                "prefill_chunks": req.prefill_chunks,
                "spec_rounds": req.spec_rounds,
            },
        )
        if adm is not None:
            self.tracer.record_span(
                "serving.queue_wait", req.submitted_ns, ns(adm),
                {"rid": req.rid}, parent=root,
            )
        if ps is not None and ft is not None:
            self.tracer.record_span(
                "serving.prefill", ns(ps), ns(ft),
                {"rid": req.rid, "chunks": req.prefill_chunks},
                parent=root,
            )
        if ft is not None:
            self.tracer.record_span(
                "serving.decode", ns(ft), end,
                {
                    "rid": req.rid, "tokens": len(req.tokens),
                    "spec_rounds": req.spec_rounds,
                },
                parent=root,
            )

    # ---------------------------------------------------------- metering
    def _meter_apportion(self, disp, live) -> None:
        """Split one drained chunk's device-busy seconds (and the AOT
        cost model's per-dispatch flops/bytes) equally across the rows
        that occupied the batch: a slot bills for the lane it held —
        the chunk's device cost was invariant to how many of its rows
        emitted. Called right after the chunk finalized, so
        ``disp.busy_s`` is stamped; pure host arithmetic, no sync."""
        share = 1.0 / len(live)
        cost = self._prog_cost.get(disp.program) or {}
        busy = disp.busy_s * share
        fl = cost.get("flops", 0.0) * share
        by = cost.get("bytes", 0.0) * share
        for req in live:
            req.busy_s += busy
            req.flops += fl
            req.hbm_bytes += by

    def _meter_fold_prefill(self, req: _Request) -> None:
        """Fold the request's finalized prefill dispatches into its
        meter. Prefill programs serve ONE request, so the whole
        dispatch bills to it. FIFO finalization means every chunk is
        stamped by the time the first token syncs; a handle not yet
        finalized (aborted mid-prefill) stays parked."""
        if not req.disp_hist:
            return
        rest = []
        for d in req.disp_hist:
            if not d.done:
                rest.append(d)
                continue
            req.busy_s += d.busy_s
            cost = self._prog_cost.get(d.program)
            if cost:
                req.flops += cost.get("flops", 0.0)
                req.hbm_bytes += cost.get("bytes", 0.0)
        req.disp_hist = rest

    def _meter_kv(self, req: _Request, blocks: int | None = None) -> None:
        """Integrate KV block-seconds: fold the (blocks x elapsed)
        rectangle since the last holding change, then anchor at the
        new count. Called at alloc/grow/preempt/finish on the paged
        engine; the contiguous engine holds no pool blocks."""
        now = time.perf_counter()
        if req.kv_anchor is not None:
            req.kv_block_s += req.kv_blocks_now * (now - req.kv_anchor)
        req.kv_anchor = now
        if blocks is not None:
            req.kv_blocks_now = int(blocks)

    def _meter_finish(self, req: _Request, kind: str | None = None) -> None:
        """Freeze the finished request's accumulators into the meter
        record a work receipt is built from (runtime/ledger.py).
        Wall-clock start/end reconstruct from the ``submitted_ns``
        anchor the span timeline already keeps — monotonic stamps
        never leave the host they were taken on."""
        if not self.metering:
            return
        self._meter_fold_prefill(req)
        self._meter_kv(req, 0)
        t0 = (req.submitted_ns or time.time_ns()) / 1e9
        end = (
            req.finished_at if req.finished_at is not None
            else time.perf_counter()
        )
        meter = {
            "rid": req.rid,
            "tenant": req.tenant or "anonymous",
            "kind": kind or self.meter_kind,
            "t_start": t0,
            "t_end": t0 + max(end - req.submitted_at, 0.0),
            "prompt_tokens": (
                int(req.ids.size) if req.ids is not None else 0
            ),
            "emitted_tokens": len(req.tokens),
            "busy_s": req.busy_s,
            "flops": req.flops,
            "hbm_bytes": req.hbm_bytes,
            "kv_block_s": req.kv_block_s,
            "wire_bytes": req.wire_bytes,
        }
        self._meter_log[req.rid] = meter
        while len(self._meter_log) > 4 * self.keep_results:
            self._meter_log.popitem(last=False)
        self._meter_fresh.append(meter)
        self._metered_total += 1

    def meter(self, rid: int) -> dict | None:
        """The finished request's meter record — None until it
        finishes (or after bounded eviction). Values are immutable
        once written."""
        with self._lock:
            return self._meter_log.get(rid)

    def drain_meters(self, limit: int = 64) -> list[dict]:
        """Up to ``limit`` finished meters not yet drained — the
        heartbeat-piggyback source. Each meter is handed out exactly
        once; a lost carrier frame loses the receipt (the reply-path
        copy and the bounded ``meter()`` log remain)."""
        out: list[dict] = []
        with self._lock:
            while self._meter_fresh and len(out) < limit:
                out.append(self._meter_fresh.popleft())
        return out

    def _finish(self, req: _Request) -> None:
        req.done = True
        req.finished_at = time.perf_counter()
        self._meter_finish(req)
        req.ids = None  # prompt no longer needed; keep retention light
        self._emit_request_timeline(req)
        slot = req.slot
        if slot is not None and self._slot_req[slot] is req:
            self._slot_req[slot] = None
            self._free.append(slot)
        if req.deadline_at is not None:
            self._deadlined = max(self._deadlined - 1, 0)
        # measured TPOT — the deadline-feasibility floor, the
        # retry-after computation, and the per-class histograms all
        # derive from it. Aborted requests are excluded EVERYWHERE: a
        # shed/cancelled stream's finished_at is the abort time, so its
        # "TPOT" would fold post-preemption queue wait into a
        # service-rate measurement (inflating exactly the per-class
        # p99s the overload bench reads).
        tpot = None
        if (
            req.failed is None
            and req.first_token_at is not None
            and len(req.tokens) > 1
        ):
            tpot = (
                (req.finished_at - req.first_token_at)
                / (len(req.tokens) - 1)
            )
            self._tpot_ewma = (
                tpot if self._tpot_ewma is None
                else 0.8 * self._tpot_ewma + 0.2 * tpot
            )
        if self._kctl is not None:
            # fold the finished request's acceptance into the prior the
            # next request starts from (and the autotune store persists)
            self._kctl.forget(req.rid)
        # bounded result retention: results stay readable (result() is
        # idempotent) until keep_results newer requests finished — a
        # steady-traffic scheduler must not grow host memory forever
        self._done_order.append(req.rid)
        while len(self._done_order) > self.keep_results:
            self._requests.pop(self._done_order.popleft(), None)
        if self.metrics is not None:
            self.metrics.incr("serving_tokens_total", len(req.tokens))
            if tpot is not None:
                self.metrics.observe_hist("serving_tpot_s", tpot)
                self.metrics.observe_hist(
                    f"serving_tpot_s:{_PRIO_NAMES[req.priority]}",
                    tpot,
                )
            if req.spec_proposed:
                # per-request acceptance rate, alongside TTFT/TPOT in
                # the same registry (tldiag reads the aggregate from
                # /node; pathological acceptance means the draft is a
                # bad match for this traffic, not a correctness issue)
                self.metrics.observe_hist(
                    "serving_spec_acceptance",
                    req.spec_accepted / req.spec_proposed,
                    buckets=_ACCEPTANCE_BUCKETS,
                )
        self._event(
            "serving.finish", rid=req.rid, tokens=len(req.tokens),
        )

    def _append_token(self, req: _Request, tok: int) -> None:
        if req.done:
            return
        req.tokens.append(int(tok))
        eos = self.gen.eos_token_id
        if len(req.tokens) >= req.max_new or (
            eos is not None and int(tok) == eos
        ):
            self._finish(req)

    def _drain_one(self) -> None:
        h = chaos.ACTIVE  # scripted drain-loop stall (worker-kill /
        if h is not None:  # failover blackout emulation in-process)
            h.apply_sync("serving.drain")
        payload, snapshot, disp = self._inflight.popleft()
        for req in snapshot:
            if req is not None:
                self._take_first(req)
        if self.spec is None:
            arr = np.asarray(payload[0])  # [K, S] — THE host sync point
            if disp is not None:
                self._timer.drained(disp)  # right after the sync: exact
            if self.metering and disp is not None:
                # apportion BEFORE the append loop: a request the loop
                # finishes freezes its meter with this chunk included
                live = [
                    r for r in snapshot if r is not None and not r.done
                ]
                if live:
                    self._meter_apportion(disp, live)
            emitted = 0
            for k in range(arr.shape[0]):
                for s, req in enumerate(snapshot):
                    if req is not None and not req.done:
                        self._append_token(req, arr[k, s])
                        emitted += 1
            if self._timer is not None:
                self._timer.count_tokens("decode", emitted)
            return
        self._drain_spec(payload, snapshot, disp)

    def _drain_spec(self, payload, snapshot, disp=None) -> None:
        """Drain one speculative chunk: ``toks [R, K+1, S]`` gated by
        ``n_emit [R, S]`` (0 = the row was not live that round), with
        ``n_acc [R, S]`` the verifier's PRE-CLIP accepted-proposal
        count (EOS/budget truncation is the request ending, not a
        rejection) and ``n_prop [R, S]`` the proposals the row actually
        stood behind (== k under static K; < k when the controller
        masked or the draft early-exited). Per live (row, round) pair
        tokens-per-weight-pass is exactly ``n_emit``; acceptance rate
        is ``n_acc / n_prop`` — and the same ratio feeds the adaptive
        controller, closing the measure→adapt loop per request."""
        toks = np.asarray(payload[0])  # THE host sync point
        if disp is not None:
            self._timer.drained(disp)  # right after the sync: exact
        if self.metering and disp is not None:
            live = [r for r in snapshot if r is not None and not r.done]
            if live:
                self._meter_apportion(disp, live)
        ne = np.asarray(payload[1])
        na = np.asarray(payload[2])
        fb = np.asarray(payload[3])
        nprop = np.asarray(payload[4])
        rounds = emitted = accepted = rejected = proposed = 0
        for r in range(toks.shape[0]):
            for s, req in enumerate(snapshot):
                cnt = int(ne[r, s])
                if req is None or cnt <= 0:
                    continue
                rounds += 1
                emitted += cnt
                acc = int(na[r, s])
                prop = int(nprop[r, s])
                accepted += acc
                rejected += prop - acc
                proposed += prop
                if self._kctl is not None:
                    self._kctl.observe(req.rid, prop, acc)
                if not req.done:
                    req.spec_rounds += 1
                    req.spec_proposed += prop
                    req.spec_accepted += acc
                for k in range(cnt):
                    if req.done:
                        break
                    self._append_token(req, toks[r, k, s])
        if self._timer is not None:
            self._timer.count_tokens("spec_chunk", emitted)
        self.spec_rounds_total += rounds
        self.spec_emitted_total += emitted
        self.spec_accepted_total += accepted
        self.spec_proposed_total += proposed
        nfb = int(fb.sum())
        self.spec_fallback_total += nfb
        if proposed and self.spec.cfg.self_heal_accept is not None:
            # recent-acceptance EWMA for the self-healing gate — the
            # lifetime totals above would take forever to reflect a
            # draft that went bad mid-flight (or was always bad)
            lam = self.spec.cfg.ewma
            a = accepted / proposed
            self._heal_acc = (
                a if self._heal_acc is None
                else (1.0 - lam) * self._heal_acc + lam * a
            )
            self._heal_proposed += proposed
        if self.metrics is not None:
            if accepted:
                self.metrics.incr("spec_accepted_total", accepted)
            if rejected:
                self.metrics.incr("spec_rejected_total", rejected)
            if nfb:
                self.metrics.incr("spec_fallback_total", nfb)

    def _maybe_self_heal(self) -> None:
        """The tldiag LOW-ACCEPT flag made self-healing (ROADMAP item
        3): when the recent-acceptance EWMA sits below
        ``SpecConfig.self_heal_accept`` after at least
        ``HEAL_MIN_PROPOSED`` verified proposals, the engine downgrades
        its own speculation mode — draft -> n-gram -> off — instead of
        waiting for an operator to read the cluster table. Every
        rejected proposal was a wasted draft step; below ~0.3 the extra
        passes cost more than the accepted tokens buy.

        Only fires DEVICE-IDLE (no live slots, no in-flight chunks, no
        mid-prefill work): the mode swap rebuilds the donated state and
        the one decode program, which must never yank buffers from
        under a dispatched chunk. Queued requests are fine — they admit
        under the new mode. Mode counters reset so the cleared
        condition is measurable; the history lives in the
        ``serving.spec_self_heal`` event and ``stats()
        ["spec_self_healed"]``. Caller holds the scheduler lock."""
        spec = self.spec
        if spec is None or spec.cfg.self_heal_accept is None:
            return
        if self._heal_acc is None or self._heal_proposed < HEAL_MIN_PROPOSED:
            return
        if self._heal_acc >= spec.cfg.self_heal_accept:
            return
        if any(r is not None for r in self._slot_req) or self._inflight:
            return
        if self._pending_prefills():
            return
        frm, to = spec.mode, "ngram" if spec.mode == "draft" else "nonspec"
        healed = {
            "from": frm, "to": to,
            "acceptance": round(self._heal_acc, 4),
            "proposed": self._heal_proposed,
        }
        self._event("serving.spec_self_heal", "warn", **healed)
        if self.metrics is not None:
            self.metrics.incr("spec_self_heal_total")
        self.spec_self_healed = healed
        if to == "ngram":
            self.spec = SpeculativeDecoder(self.engine, None, spec.cfg)
            if self._kctl is not None:
                # fresh controller: proposals are free now and the bad
                # draft's acceptance prior says nothing about n-gram
                self._kctl = AdaptiveKController(spec.cfg, draft_cost=0.0)
        else:
            self.spec = None
            self._kctl = None
        self.spec_rounds_total = 0
        self.spec_emitted_total = 0
        self.spec_accepted_total = 0
        self.spec_proposed_total = 0
        self.spec_fallback_total = 0
        self._heal_acc = None
        self._heal_proposed = 0
        self._k_dispatch = None
        # rebuild the (one) decode program and donated state for the
        # new mode; the prefill closures capture the spec tree too
        self._state = self._init_state()
        self._decode = self._build_decode()
        self._prefill_jit.clear()

    def _pending_prefills(self) -> int:
        return 0  # the paged engine overrides (chunked prefill queue)

    def _pending_slots(self):
        return ()  # paged: the slots still mid-chunked-prefill

    def _note_first_token(self, req: _Request) -> None:
        """``tl.serve.first_token``: the host holds the request's first
        token as an int (a resumed request's re-prefill is not one)."""
        if not req.tokens and req.failed is None:
            event(
                "serve.first_token", rid=req.rid,
                ttft_ms=(time.perf_counter() - req.submitted_at) * 1e3,
            )

    def _take_first(self, req: _Request) -> None:
        """Fold the prefill's first token into the stream (syncs a
        long-since-computed scalar). TTFT is recorded here at the
        latest — _maybe_record_ttft covers every earlier opportunity,
        including jax builds without Array.is_ready. (Guarded on the
        pending device scalar alone: a paged-engine request resumed
        after preemption re-prefills with tokens already banked, so
        ``req.tokens`` may legitimately be non-empty here.)"""
        if req.first_token is not None:
            t0 = int(np.asarray(req.first_token))
            self._note_first_token(req)
            if req.disp is not None and self._timer is not None:
                self._timer.drained(req.disp)  # prefill synced here
            req.disp = None
            if self.metering:
                # fold BEFORE the append: a max_new=1 request finishes
                # inside it, and its meter must include the prefill
                self._meter_fold_prefill(req)
            self._maybe_record_ttft(req)
            req.first_token = None
            self._append_token(req, t0)

    def step(self) -> bool:
        """One scheduler iteration: admit waiting prompts into free
        slots, dispatch one decode chunk, sync the oldest chunk once
        ``pipeline_depth`` are in flight. Returns False when fully idle
        (nothing queued, running, or in flight)."""
        with self._lock, region("serve.step"):
            self._maybe_self_heal()
            with region("serve.admit"):
                # (admission IS the prefill dispatch on this engine:
                # serve.prefill_dispatch nests inside)
                self._expire_deadlines_locked()
                self._admit_waiting()
            busy = any(r is not None for r in self._slot_req)
            if busy:
                with region("serve.decode_dispatch"):
                    payload, disp = self._dispatch_decode()
                self._inflight.append((payload, tuple(self._slot_req), disp))
            for r in self._slot_req:
                if r is not None:
                    self._maybe_record_ttft(r)
            if self._timer is not None:
                # opportunistic ready stamping: one is_ready per pending
                # FIFO head per step — the attribution granularity
                self._timer.poll()
            with region("serve.drain"):
                while len(self._inflight) > (
                    self.pipeline_depth if busy else 0
                ):
                    self._drain_one()
            if not busy:
                self._maybe_self_heal()  # just drained fully idle
            return bool(
                busy or self._queue or self._inflight
            )

    def result(
        self, rid: int, *, timeout_s: float | None = None,
        deadline_s: float | None = None,
    ) -> np.ndarray:
        """Drive the serving loop until request ``rid`` finishes; return
        its generated tokens (length <= its max_new; ends at EOS).

        ``deadline_s`` bounds the wait with CANCELLATION: past it the
        request is aborted — its slot and (paged) KV blocks freed, so
        an abandoned caller never pins capacity until max-tokens — and
        a typed ``DeadlineExceededError`` raised. ``timeout_s`` is the
        legacy soft bound: it raises ``TimeoutError`` but leaves the
        request running (a later ``result()`` can still collect it).
        A request that was shed or deadline-cancelled elsewhere raises
        its recorded failure here instead of returning tokens."""
        # under the lock: a pump thread's _finish may be evicting old
        # entries from this dict concurrently (tlint TL601)
        with self._lock:
            req = self._requests.get(rid)
        if req is None:
            raise KeyError(
                f"unknown request id {rid} (never submitted, or its "
                f"result was evicted after {self.keep_results} newer "
                "completions — raise keep_results to retain more)"
            )
        now = time.perf_counter()
        cancel_at = now + deadline_s if deadline_s is not None else None
        timeout_at = now + timeout_s if timeout_s is not None else None
        while not req.done:
            progressed = self.step()
            if not progressed and not req.done:
                raise ServingError(
                    f"request {rid} cannot complete: scheduler idle "
                    "(internal accounting bug)"
                )
            now = time.perf_counter()
            if cancel_at is not None and now > cancel_at and not req.done:
                err = DeadlineExceededError(
                    f"request {rid} not done within deadline_s="
                    f"{deadline_s}; cancelled and freed", rid=rid,
                )
                if self.cancel(rid, error=err):
                    raise err
                # lost the race: a pump thread finished the request
                # between the done check and the cancel — fall through
                # to its real outcome instead of claiming a miss
                continue
            if timeout_at is not None and now > timeout_at and not req.done:
                raise TimeoutError(f"request {rid} not done in {timeout_s}s")
        if req.failed is not None:
            raise req.failed
        return np.asarray(req.tokens, np.int32)

    async def asubmit(
        self, ids, *, max_new: int | None = None, seed: int = 0,
        priority: Priority | int | str = Priority.STANDARD,
        deadline_s: float | None = None,
        tenant: str | None = None,
    ) -> int:
        """Asyncio wrapper for ``submit``: admission dispatches a
        prefill (and, for a new prompt-length bucket, compiles one) and
        may contend with a pump thread holding the scheduler lock
        across a chunk sync — none of which belongs on a node's event
        loop."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self.submit(
                ids, max_new=max_new, seed=seed, priority=priority,
                deadline_s=deadline_s, tenant=tenant,
            )
        )

    async def aresult(
        self, rid: int, *, timeout_s: float | None = None,
        deadline_s: float | None = None,
    ):
        """Asyncio wrapper: pump in a worker thread so a node event loop
        can serve generation without blocking its RPC handlers."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self.result(
                rid, timeout_s=timeout_s, deadline_s=deadline_s
            )
        )

    def run_until_idle(self) -> None:
        """Process everything queued/in-flight to completion."""
        while self.step():
            pass

    def _spec_stats(self) -> dict:
        """Aggregate speculation counters. A "weight pass" is one
        (live row, verify round) pair — the per-sequence unit the
        non-speculative decode spends one full weight read per token
        on; ``accepted_tokens_per_weight_pass`` > 1.0 is the bandwidth-
        roofline win."""
        prop = self.spec_proposed_total
        wp = self.spec_rounds_total
        out = {
            "mode": self.spec.mode,
            "k": self.spec.cfg.k,
            "rounds": self.spec.cfg.rounds,
            "weight_passes": wp,
            "emitted_tokens": self.spec_emitted_total,
            "accepted_total": self.spec_accepted_total,
            "proposed_total": prop,
            "acceptance_rate": (
                round(self.spec_accepted_total / prop, 4) if prop else 0.0
            ),
            "accepted_tokens_per_weight_pass": (
                round(self.spec_emitted_total / wp, 4) if wp else 0.0
            ),
            "fallback_total": self.spec_fallback_total,
            # per-request acceptance of the streams live RIGHT NOW —
            # the /node view an operator reads when one tenant's
            # traffic defeats the draft while the aggregate looks fine
            "live_requests": {
                r.rid: round(r.spec_accepted / r.spec_proposed, 4)
                for r in self._slot_req
                if r is not None and r.spec_proposed
            },
        }
        out["adaptive"] = self._kctl is not None
        if self._kctl is not None:
            # the controller's live picture: mean dispatched K and the
            # persistable posterior (what save_autotune would write)
            out["k_mean"] = round(self._kctl.k_mean(), 3)
            out["k_prior"] = self._kctl.prior()
        return out

    def _device_time_locked(self) -> dict | None:
        """Per-program device-busy/host-gap attribution + derived
        MFU/MBU (when an AOT compile captured the program's cost and a
        capability record supplies the chip peaks)."""
        if self._timer is None:
            return None
        snap = self._timer.snapshot()
        cap = self.capability or {}
        for name, rec in snap["programs"].items():
            cost = self._prog_cost.get(name)
            if not cost or not rec["count"] or rec["busy_s"] <= 0:
                continue
            per = rec["busy_s"] / rec["count"]
            if cost.get("flops") and cap.get("peak_tflops"):
                rec["mfu"] = round(
                    cost["flops"] / per / (cap["peak_tflops"] * 1e12), 4
                )
            if cost.get("bytes") and cap.get("hbm_gbps"):
                rec["mbu"] = round(
                    cost["bytes"] / per / (cap["hbm_gbps"] * 1e9), 4
                )
        return snap

    def device_time(self) -> dict | None:
        """Public (locked) form of the per-program attribution — what
        ``capability_record`` piggybacks on heartbeats."""
        with self._lock:
            return self._device_time_locked()

    def stats(self) -> dict:
        """Host-side scheduler snapshot (queue depth, slot occupancy)."""
        with self._lock:
            out = {
                "slots": self.slots,
                "busy_slots": sum(
                    1 for r in self._slot_req if r is not None
                ),
                "queued": len(self._queue),
                "inflight_chunks": len(self._inflight),
                "requests": len(self._requests),
            }
            adm: dict = {
                "retry_after_s": self._retry_after_locked(),
                "shed_total": self._sheds,
                "deadline_miss_total": self._deadline_misses,
            }
            if self._tpot_ewma is not None:
                adm["tpot_ewma_s"] = round(self._tpot_ewma, 6)
            if self._ttft_ewma is not None:
                adm["ttft_ewma_s"] = round(self._ttft_ewma, 6)
            if self._sheds:
                adm["shed_by_priority"] = {
                    _PRIO_NAMES[p]: n
                    for p, n in sorted(self._shed_by_prio.items())
                }
                adm["last_shed_age_s"] = round(
                    time.perf_counter() - self._last_shed_at, 3
                )
            # the SLO-admission picture tldiag reads from /node: what a
            # shed client is being told (retry_after_s), how much was
            # shed per class, and the measured EWMAs behind both
            out["admission"] = adm
            out["metering"] = {
                "enabled": self.metering,
                "metered_total": self._metered_total,
                "undrained": len(self._meter_fresh),
            }
            dt = self._device_time_locked()
            if dt is not None:
                out["device_time"] = dt
            if self._ttft_decomp:
                # TTFT decomposed: queue wait vs first prefill dispatch
                # vs prefill compute (EWMAs over finished requests)
                out["ttft_decomp"] = dict(self._ttft_decomp)
            if self.spec is not None:
                out["spec"] = self._spec_stats()
            if self.spec_self_healed is not None:
                # survives even after spec drops to None — tldiag reads
                # this to render SELF-HEALED(mode) instead of LOW-ACCEPT
                out["spec_self_healed"] = self.spec_self_healed
            if self.autotune_warm_start_s is not None:
                out["autotune_warm_start_s"] = self.autotune_warm_start_s
            return out


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over a PAGED KV cache (ROADMAP item 1).

    Instead of one contiguous ``max_len`` cache region per slot, every
    layer's k/v live in shared pools of ``num_blocks`` fixed-size
    blocks (``nn/attention.py`` paged form) addressed through per-slot
    block tables — ``block_table[pos // bs] * bs + pos % bs`` instead
    of ``slot_base + pos``. The host-side ``BlockPool``/``PrefixIndex``
    (parallel/kvpool.py) decide which block ids each slot maps:

    - **admission** matches the prompt against the prefix index; full
      blocks already resident map straight into the block table
      (refcount++) and their tokens are NEVER re-prefilled. A matched
      partial tail block is revived exclusively when idle or
      copy-on-written when it has live sharers.
    - **chunked prefill**: remaining prompt tokens run in fixed
      ``prefill_chunk``-token programs, at most one per scheduler step,
      interleaved with decode dispatches — a long arriving prompt
      cannot stall in-flight decodes.
    - **decode** grows a slot's block table lazily (blocks allocated
      just ahead of the write frontier) and frees block-granular on
      EOS/eviction. When the pool cannot cover a live slot's next
      chunk, the newest request is preempted — its blocks free, it
      re-queues, and the (request-seed, position) sampling keys make
      the resumed stream token-identical.
    - **backpressure**: a request that could never fit raises
      ``PoolExhaustedError`` at submit; a full queue behind a starved
      pool raises it instead of ``QueueFullError``.

    Every device program is shape-static: ONE decode chunk program and
    ONE prefill chunk program serve any request mix (block tables,
    indices, chunk offsets are all traced operands) — strictly fewer
    programs than the contiguous engine's per-bucket prefills.

    ``num_blocks`` defaults to ``slots * cache_len / block_size``
    (parity capacity — nothing is ever tighter than the contiguous
    engine); size it smaller to cap HBM by LIVE tokens instead of
    ``slots x max_len``.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefill_chunk: int = 32,
        prefix_cache: bool = True,
        kv_quant: str | None = None,
        **kw,
    ):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r} (None or 'int8')"
            )
        self.block_size = int(block_size)
        self.prefill_chunk = int(prefill_chunk)
        self.prefix_cache = bool(prefix_cache)
        self.kv_quant = kv_quant
        self._num_blocks_arg = num_blocks
        super().__init__(engine, **kw)

    # --------------------------------------------------------- device state
    def _init_state(self):
        eng, S, L, bs = self.engine, self.slots, self.L, self.block_size
        if L % bs:
            raise ValueError(
                f"block_size {bs} must divide the cache view width {L}"
            )
        if eng.mesh.shape.get(eng.data_axis, 1) > 1:
            raise NotImplementedError(
                "paged serving does not shard over the data axis yet: "
                "the block pools have no slot-batch dimension to split "
                "(all slots scatter into the same pool)"
            )
        self.max_blocks = MB = L // bs
        nb = self._num_blocks_arg
        if nb is None:
            nb = S * MB  # parity capacity: never tighter than contiguous
        self.pool = BlockPool(
            int(nb), bs, metrics=self.metrics, recorder=self.recorder
        )
        self.index = PrefixIndex(bs) if self.prefix_cache else None
        if self.index is not None:
            self.pool.evict_hook = self.index.forget_block
        try:
            stack = eng.model.children["blocks"]
            attns = [blk.children["attn"] for blk in stack.blocks()]
            caches = [
                {"attn": a.init_paged_cache(
                    self.pool.num_blocks, bs, S, MB,
                    dtype=eng.cache_dtype, quant=self.kv_quant,
                )}
                for a in attns
            ]
        except (AttributeError, KeyError) as e:
            raise NotImplementedError(
                "paged serving requires the standard decoder cache tree "
                "([{'attn': cache}] per block, models/gpt2.py & "
                "models/llama.py)"
            ) from e
        # host-side mirrors of the device block tables
        self._slot_blocks: list[list[int]] = [[] for _ in range(S)]
        self._slot_ub = [0] * S  # device write-frontier upper bound
        self._slot_limit = [0] * S  # prompt + budget cap, in tokens
        self._pending: dict[int, dict] = {}  # slot -> prefill job
        self.prefix_matched_tokens = 0
        self.prompt_tokens_total = 0
        self.prefilled_tokens = 0
        self.peak_blocks_in_use = 0
        # the pool siblings beyond k/v (int8 scales) ride every block
        # operation — prefill chunk, copy, graft, export — by key, so
        # the programs built below stay form-agnostic (set BEFORE the
        # builds: the op closures bind it)
        self._pool_keys = tuple(
            name for name in caches[0]["attn"]
            if name not in ("index", "block_table")
        )
        # bytes ONE pool block occupies across all layers (k + v + any
        # scale siblings) — the unit the footprint/wire bench keys and
        # the serve_llm savings printout multiply by
        self.kv_block_bytes = len(caches) * int(sum(
            int(np.prod(a.shape[1:])) * a.dtype.itemsize
            for name, a in caches[0]["attn"].items()
            if name in self._pool_keys
        ))
        self._prefill_chunk_fn = self._build_prefill_chunk()
        self._table_op = self._build_table_op()
        self._retire_op = self._build_retire_op()
        self._copy_op = self._build_copy_op()
        self._graft_op = self._build_graft_op()
        self._adopt_op = self._build_adopt_op()
        # immutable pool geometry (block shape never changes across
        # self-heal rebuilds): import_prefill validates payloads
        # against these OUTSIDE the scheduler lock, so multi-MB
        # payload staging never stalls live decode threads
        self._n_layers = len(caches)
        self._block_shape = tuple(
            caches[0]["attn"]["k"].shape[1:]
        )  # (bs, Hkv, D)
        # disaggregated-serving accounting (prefill_export /
        # import_prefill + note_disagg_transfer): the stats() "disagg"
        # block tldiag reads ROLE/XFER-STALLED from
        self.disagg: dict[str, int] = {
            "exports": 0, "export_blocks": 0, "export_tokens": 0,
            "imports": 0, "import_blocks": 0, "import_tokens": 0,
            "fallbacks": 0,
        }
        self._disagg_ewma: dict[str, float] = {}
        state = {
            "caches": caches,
            "valid": jnp.zeros((S, L), bool),
            "n_valid": jnp.zeros((S,), jnp.int32),
            "tok": jnp.zeros((S,), jnp.int32),
            "seed": jnp.zeros((S,), jnp.uint32),
            "remaining": jnp.zeros((S,), jnp.int32),
            "live": jnp.zeros((S,), bool),
        }
        # speculation rides the same donated tree; the draft cache is
        # CONTIGUOUS per slot even here (the draft is small — paging it
        # would buy little and cost a second block-table program)
        self._add_spec_state(state)
        return self._on_mesh(state)

    # ------------------------------------------------------------- programs
    def _build_prefill_chunk(self):
        """ONE shape-static program prefills any prompt: ``C`` tokens of
        slot ``slot`` starting at logical position ``start`` (``nreal <=
        C`` real, rest right-pad). The whole serving state is donated;
        the chunk writes through the slot's block table into the shared
        pools and, on the final chunk, samples the first token with the
        same ``fold_in(key(seed), n)`` stream as the decode scan."""
        eng = self.engine
        model, L, C = eng.model, self.L, self.prefill_chunk
        gen = self.gen
        temperature, top_k, top_p = (
            float(gen.temperature), int(gen.top_k), float(gen.top_p)
        )
        eos = gen.eos_token_id
        spec = self.spec
        draft_mode = spec is not None and spec.mode == "draft"

        def tl_prefill_chunk(params, dparams, state, ids, slot, start,
                             nreal, seed, max_new, is_final):
            caches = state["caches"]
            # pool arrays (k/v and any int8 scale siblings) pass through
            # by key; only index/block_table take the 1-row slot view
            tmp = [
                {"attn": {
                    **{name: lc["attn"][name] for name in self._pool_keys},
                    "index": jnp.full((1,), start, jnp.int32),
                    "block_table": jax.lax.dynamic_slice_in_dim(
                        lc["attn"]["block_table"], slot, 1, axis=0
                    ),
                }}
                for lc in caches
            ]
            positions = (start + jnp.arange(C))[None, :]
            # mask=None: the paged attention path builds causality (and
            # the window band) in logical coordinates from the index
            logits, new_tmp = model.apply(
                params, ids, caches=tmp, positions=positions, mask=None
            )
            new_caches = [
                {"attn": {
                    **{name: nt["attn"][name] for name in self._pool_keys},
                    "index": lc["attn"]["index"].at[slot].set(start + nreal),
                    "block_table": lc["attn"]["block_table"],
                }}
                for lc, nt in zip(caches, new_tmp)
            ]
            n_end = start + nreal
            last = jax.lax.dynamic_index_in_dim(
                logits[0], nreal - 1, axis=0, keepdims=False
            )
            with scope("serve.sample"):
                key0 = jax.random.fold_in(jax.random.key(seed), n_end)
                tok0 = sample_logits(
                    last, key0, temperature, top_k, top_p
                ).astype(jnp.int32)
            done0 = max_new <= 1
            if eos is not None:
                done0 = done0 | (tok0 == eos)
            with scope("serve.cache_write"):
                new_state = {
                    **state,
                    "caches": new_caches,
                    "valid": state["valid"].at[slot].set(
                        jnp.arange(L) < n_end
                    ),
                    "n_valid": state["n_valid"].at[slot].set(n_end),
                    "tok": state["tok"].at[slot].set(tok0),
                    "seed": state["seed"].at[slot].set(seed),
                    "remaining": state["remaining"].at[slot].set(
                        jnp.where(is_final, max_new - 1, 0)
                    ),
                    "live": state["live"].at[slot].set(is_final & ~done0),
                }
            if draft_mode:
                # the draft prefills the same chunk through its
                # CONTIGUOUS per-slot cache: a 1-row scalar-index slice,
                # cache-width masking implied (paged rows are unpadded,
                # so slot order == logical order — the module's own
                # causal/window predicates are exact)
                dmodel = spec.draft.model
                dc = state["draft"]
                tmp_d = [
                    {"attn": {
                        "k": jax.lax.dynamic_slice_in_dim(
                            lc["attn"]["k"], slot, 1, axis=0
                        ),
                        "v": jax.lax.dynamic_slice_in_dim(
                            lc["attn"]["v"], slot, 1, axis=0
                        ),
                        "index": start,
                    }}
                    for lc in dc
                ]
                _, new_d = dmodel.apply(
                    dparams, ids, caches=tmp_d, positions=positions,
                    mask=None,
                )
                new_state["draft"] = [
                    {"attn": {
                        "k": jax.lax.dynamic_update_slice(
                            lc["attn"]["k"], nt["attn"]["k"],
                            (slot, 0, 0, 0),
                        ),
                        "v": jax.lax.dynamic_update_slice(
                            lc["attn"]["v"], nt["attn"]["v"],
                            (slot, 0, 0, 0),
                        ),
                        "index": lc["attn"]["index"].at[slot].set(
                            start + nreal
                        ),
                    }}
                    for lc, nt in zip(dc, new_d)
                ]
            elif spec is not None:
                # n-gram context buffer: paged rows are unpadded, so the
                # chunk lands at its logical positions directly (the pad
                # tail past nreal is overwritten by the next chunk and
                # never becomes valid)
                new_state["ids"] = jax.lax.dynamic_update_slice(
                    state["ids"], ids, (slot, start)
                )
            return new_state, tok0

        return self._jit_program(tl_prefill_chunk)

    def _map_caches(self, state, fn):
        return {
            **state,
            "caches": [
                {"attn": fn(lc["attn"])} for lc in state["caches"]
            ],
        }

    def _build_table_op(self):
        """Point a slot's device block-table row (every layer) at
        ``row``; at admission also reset the row's write index to the
        first position the new request will write (its old parked index
        could otherwise alias a SHARED block through the new table)."""

        def tl_pool_table(state, slot, row, start, set_start):
            def upd(c):
                idx = jnp.where(set_start, start, c["index"][slot])
                return {
                    **c,
                    "index": c["index"].at[slot].set(idx),
                    "block_table": c["block_table"].at[slot].set(row),
                }

            return self._map_caches(state, upd)

        return jax.jit(tl_pool_table, donate_argnums=(0,))

    def _build_retire_op(self):
        """Kill a slot on device: live off, valid row cleared, block
        table to the sentinel so any in-flight parked write DROPS
        instead of landing in a block about to be remapped."""
        NB, L = self.pool.num_blocks, self.L

        def tl_pool_retire(state, slot):
            state = self._map_caches(
                state,
                lambda c: {
                    **c,
                    "block_table": c["block_table"].at[slot].set(
                        jnp.full((self.max_blocks,), NB, jnp.int32)
                    ),
                },
            )
            return {
                **state,
                "live": state["live"].at[slot].set(False),
                "valid": state["valid"].at[slot].set(
                    jnp.zeros((L,), bool)
                ),
            }

        return jax.jit(tl_pool_retire, donate_argnums=(0,))

    def _build_copy_op(self):
        """Copy-on-write: duplicate block ``src`` into ``dst`` across
        every layer's pool arrays — k/v AND any int8 scale siblings
        (the sharer keeps ``src`` byte-for-byte; the writer extends
        ``dst``)."""
        keys = self._pool_keys

        def tl_pool_copy(state, src, dst):
            return self._map_caches(
                state,
                lambda c: {
                    **c,
                    **{
                        name: c[name].at[dst].set(c[name][src])
                        for name in keys
                    },
                },
            )

        return jax.jit(tl_pool_copy, donate_argnums=(0,))

    # ------------------------------------------- disaggregated serving
    # Prefill/decode disaggregation across the mesh (ROADMAP item 1):
    # the paged KV BLOCK is the wire unit. prefill_export runs chunked
    # prefill into the local pool and reads back ONLY the request's
    # filled blocks ([n_blocks, block_size, Hkv, D] per layer — never a
    # contiguous cache); import_prefill on the decode side allocates
    # local block ids, scatter-grafts the payloads into its own pools
    # through ONE shape-static program, points the slot's block table
    # at them, and decodes as if it had prefilled locally. Sampling
    # keys are (request seed, logical position), so the decode leg is
    # token-identical to colocated serving by construction.

    _GRAFT_WIDTH = 8  # blocks scatter-grafted per import dispatch

    def _build_graft_op(self):
        """Scatter up to ``_GRAFT_WIDTH`` received blocks into every
        layer's pool arrays at once — k/v and any int8 scale siblings:
        ``bids`` rows past the pool width (the padding sentinel) DROP,
        so one shape-static program serves any block count."""
        keys = self._pool_keys

        def tl_pool_graft(state, blocks, bids):
            def upd(c, bl):
                return {
                    **c,
                    **{
                        name: c[name].at[bids].set(
                            bl[name].astype(c[name].dtype), mode="drop"
                        )
                        for name in keys
                    },
                }

            return {
                **state,
                "caches": [
                    {"attn": upd(lc["attn"], bl)}
                    for lc, bl in zip(state["caches"], blocks)
                ],
            }

        return jax.jit(tl_pool_graft, donate_argnums=(0,))

    def _build_adopt_op(self):
        """Adopt an imported prefill into a slot's scalar row state —
        exactly what the final prefill chunk would have left behind:
        valid over the prompt, write index parked at ``n_valid`` (set
        separately via ``_set_row``), the already-sampled first token
        staged as the next fed token."""
        L = self.L
        spec = self.spec
        ngram = spec is not None and spec.mode == "ngram"

        def tl_pool_adopt(state, slot, nv, tok, seed, remaining, live, ids_row):
            out = {
                **state,
                "valid": state["valid"].at[slot].set(jnp.arange(L) < nv),
                "n_valid": state["n_valid"].at[slot].set(nv),
                "tok": state["tok"].at[slot].set(tok),
                "seed": state["seed"].at[slot].set(seed),
                "remaining": state["remaining"].at[slot].set(remaining),
                "live": state["live"].at[slot].set(live),
            }
            if ngram:
                # the n-gram drafter's prompt-lookup context: the
                # decode leg proposes from the SAME banked ids a local
                # prefill would have written
                out["ids"] = state["ids"].at[slot].set(ids_row)
            return out

        return jax.jit(tl_pool_adopt, donate_argnums=(0,))

    def _disagg_guard(self) -> None:
        with self._lock:  # a self-heal may swap self.spec
            spec = self.spec
        if spec is not None and spec.mode == "draft":
            raise NotImplementedError(
                "disaggregated serving with a draft model would need "
                "the draft's prefill cache shipped beside the target's "
                "blocks; use n-gram speculation or a non-spec decode "
                "leg"
            )

    def prefill_export(
        self, ids, *, max_new: int | None = None, seed: int = 0,
        priority: Priority | int | str = Priority.STANDARD,
        deadline_s: float | None = None, timeout_s: float | None = None,
        tenant: str | None = None,
    ) -> dict:
        """Run this request's PREFILL leg only and export the result.

        The prompt admits through the normal queue (priority-ordered,
        prefix-matched against the local index, chunked prefill
        interleaved with any co-resident traffic) but the slot is HELD:
        the scheduler never dispatches decode for it. Once the final
        chunk lands, the filled blocks are read back at block
        granularity and the slot torn down — the prompt prefix STAYS
        registered in the local ``PrefixIndex``, so a repeat export of
        a shared prefix re-prefills only the tail.

        Returns the payload dict ``parallel/kvwire.py`` packs: per-layer
        ``[n_blocks, block_size, Hkv, D]`` k/v stacks, the prompt ids,
        the first sampled token, and the RNG/budget scalars the decode
        leg needs for a token-identical continuation. Never materializes
        a contiguous cache: the only device reads are block gathers."""
        self._disagg_guard()
        rid = self.submit(
            ids, max_new=max_new, seed=seed, priority=priority,
            deadline_s=deadline_s, tenant=tenant, _hold=True,
        )
        with self._lock:
            req = self._requests[rid]
        deadline = (
            time.perf_counter() + timeout_s if timeout_s is not None
            else None
        )
        idle_recheck = False
        while True:
            export_err: BaseException | None = None
            with self._lock:
                if req.failed is not None:
                    raise req.failed
                slot = req.slot
                if (
                    slot is not None
                    and self._slot_req[slot] is req
                    and slot not in self._pending
                    and req.first_token is not None
                ):
                    try:
                        return self._export_slot_locked(req, slot)
                    except BaseException as e:
                        # re-raised below, after cancel(rid) OUTSIDE
                        # the non-reentrant lock: a failed export (the
                        # accounting-mismatch guard, a device error in
                        # the gather) must not leave the held slot and
                        # its blocks pinned forever
                        export_err = e
            if export_err is not None:
                self.cancel(rid)
                raise export_err
            if idle_recheck:
                # an idle step() can race a concurrent pump thread that
                # drained our final chunk between the readiness check
                # and our step() — the re-check above just said we are
                # STILL not ready after an idle pass, so this really is
                # stuck; cancel so the held slot + blocks do not leak
                self.cancel(rid)
                raise ServingError(
                    f"prefill-export request {rid} cannot complete: "
                    "scheduler idle (internal accounting bug)"
                )
            progressed = self.step()
            if deadline is not None and time.perf_counter() > deadline:
                self.cancel(rid)
                raise TimeoutError(
                    f"prefill of request {rid} not done in {timeout_s}s"
                )
            idle_recheck = (
                not progressed and req.failed is None and not req.done
            )

    def _coerce_kv_form(self, layers: list, src_quant: str | None) -> list:
        """Convert imported KV layers from the payload's pool form into
        THIS engine's form, host-side in numpy. Matching forms pass
        through untouched (int8 blocks + scales graft natively — the
        wire and the staging both pay quantized bytes). int8 -> float
        engines dequantize to f32 (the graft op casts to the pool dtype
        on device); float -> int8 engines quantize with the exact
        ``ops.quant.quantize_kv_int8`` math so a re-export is
        bit-identical to a locally-written pool."""
        if src_quant == self.kv_quant:
            return layers
        out = []
        if src_quant == "int8":  # -> float pools
            for bl in layers:
                ent = {}
                for kv in ("k", "v"):
                    q = np.asarray(bl[kv], np.float32)
                    s = np.asarray(bl[kv + "_scale"], np.float32)
                    ent[kv] = q * s[..., None]
                out.append(ent)
            return out
        for bl in layers:  # float -> int8 pools
            ent = {}
            for kv in ("k", "v"):
                xf = np.asarray(bl[kv]).astype(np.float32)
                absmax = np.max(np.abs(xf), axis=-1)
                s = np.where(absmax > 0, absmax / 127.0, 1.0).astype(
                    np.float32
                )
                ent[kv] = np.clip(
                    np.rint(xf / s[..., None]), -127, 127
                ).astype(np.int8)
                ent[kv + "_scale"] = s
            out.append(ent)
        return out

    def _export_slot_locked(self, req: _Request, slot: int) -> dict:
        bs = self.block_size
        prompt_ids = np.asarray(req.ids, np.int32).reshape(-1)
        t0 = int(prompt_ids.size)
        bids = list(self._slot_blocks[slot])
        need = -(-t0 // bs)
        if len(bids) != need:  # held slots never grow past the prompt
            raise ServingError(
                f"export expected {need} prompt blocks, slot maps "
                f"{len(bids)} (internal accounting bug)"
            )
        tok0 = int(np.asarray(req.first_token))
        self._note_first_token(req)
        if req.disp is not None and self._timer is not None:
            self._timer.drained(req.disp)
            req.disp = None
        self._maybe_record_ttft(req)
        # the block gathers MUST sync under the scheduler lock: every
        # serving program DONATES the state tree, so a leaf reference
        # captured here and read after releasing the lock could be
        # invalidated by the very next dispatched chunk (use-after-
        # donate) — the lock hold is the price of zero-copy donation
        idx = jnp.asarray(np.asarray(bids, np.int32))
        layers = [
            {
                name: np.asarray(lc["attn"][name][idx])
                for name in self._pool_keys
            }
            for lc in self._state["caches"]
        ]
        payload = {
            "prompt_ids": prompt_ids,
            "layers": layers,
            "n_valid": t0,
            "tok0": tok0,
            "seed": int(req.seed),
            "remaining": int(req.max_new) - 1,
            "block_size": bs,
        }
        if self.kv_quant is not None:
            # int8 blocks + scales ship NATIVELY: the wire pays the
            # quantized bytes, never a dequantized intermediate
            payload["kv_quant"] = self.kv_quant
        if self.index is not None:
            payload["prefix_digest"] = self.index.chain_digest(prompt_ids)
        self.disagg["exports"] += 1
        self.disagg["export_blocks"] += len(bids)
        self.disagg["export_tokens"] += t0
        if self.metrics is not None:
            self.metrics.incr("kv_blocks_exported_total", len(bids))
        self._event(
            "serving.kv_export", rid=req.rid, blocks=len(bids),
            tokens=t0,
        )
        req.first_token = None
        # teardown: the paged _finish retires the device row BEFORE the
        # blocks return to the pool; the registered prefix keeps them
        # reusable, so the local cache stays warm for the next export
        self._finish(req)
        return payload

    def import_prefill(
        self, payload: dict, *,
        priority: Priority | int | str = Priority.STANDARD,
        deadline_s: float | None = None,
        tenant: str | None = None,
        wire_bytes: int = 0,
    ) -> int:
        """Graft a prefill leg's exported blocks into THIS engine's pool
        and start decoding them: the decode side of disaggregated
        serving. Validates geometry and the chained prefix digest
        (kvpool.PrefixIndex.chain_digest — the ids the payload claims
        must reproduce the digest the prefill leg computed), allocates
        local block ids, scatter-grafts the payloads through the one
        shape-static graft program, points the slot's block table at
        them, and registers the prompt prefix in the local index so the
        remote blocks serve future prefix hits HERE too.

        Raises ``OverloadedError``/``PoolOverloadedError`` (typed 429 +
        measured retry-after) when no slot or blocks are free — an
        imported payload is never queued host-side — and ``ValueError``
        on a payload this engine cannot trust. Returns the rid; drive
        ``result(rid)``/``step()`` exactly like a local submission."""
        self._disagg_guard()
        prompt_ids = np.asarray(payload["prompt_ids"], np.int32).reshape(-1)
        t0 = int(prompt_ids.size)
        n_valid = int(payload["n_valid"])
        tok0 = int(payload["tok0"])
        seed = int(payload["seed"])
        remaining = int(payload["remaining"])
        bs = int(payload["block_size"])
        layers = payload["layers"]
        prio = _coerce_priority(priority)
        if bs != self.block_size:
            raise ValueError(
                f"payload block_size {bs} != engine block_size "
                f"{self.block_size}"
            )
        if n_valid != t0 or t0 == 0:
            raise ValueError(
                f"payload n_valid {n_valid} != prompt length {t0}"
            )
        if remaining < 0:
            raise ValueError(f"negative remaining budget {remaining}")
        nblk = -(-t0 // bs)
        max_new = remaining + 1  # tok0 is already the first generation
        # geometry validation + payload staging run OUTSIDE the lock:
        # _n_layers/_block_shape are immutable engine geometry, and the
        # multi-MB host->device staging must not stall live decode
        # threads behind the scheduler lock
        if len(layers) != self._n_layers:
            raise ValueError(
                f"payload has {len(layers)} layers, engine has "
                f"{self._n_layers}"
            )
        src_quant = payload.get("kv_quant")
        if src_quant is None and "k_scale" in layers[0]:
            src_quant = "int8"  # older producer shipping scales inline
        if src_quant not in (None, "int8"):
            raise ValueError(f"unknown payload kv_quant {src_quant!r}")
        src_keys = (
            ("k", "v", "k_scale", "v_scale") if src_quant == "int8"
            else ("k", "v")
        )
        for i, bl in enumerate(layers):
            for name in src_keys:
                if name not in bl:
                    raise ValueError(
                        f"layer {i} missing {name} blocks for "
                        f"kv_quant={src_quant!r}"
                    )
                want = (
                    (nblk, *self._block_shape) if name in ("k", "v")
                    else (nblk, *self._block_shape[:-1])
                )
                shape = tuple(np.asarray(bl[name]).shape)
                if shape != want:
                    raise ValueError(
                        f"layer {i} {name} blocks have shape {shape}, "
                        f"expected {want}"
                    )
        layers = self._coerce_kv_form(layers, src_quant)
        # pre-stage the graft groups (pad the tail group to the fixed
        # _GRAFT_WIDTH); only the tiny bid arrays depend on allocation
        W = self._GRAFT_WIDTH
        groups: list[list[dict]] = []
        for off in range(0, nblk, W):
            stacked = []
            for bl in layers:
                ent = {}
                for name in self._pool_keys:
                    arr = np.asarray(bl[name])[off:off + W]
                    if arr.shape[0] < W:
                        pad = np.zeros(
                            (W - arr.shape[0], *arr.shape[1:]), arr.dtype
                        )
                        arr = np.concatenate([arr, pad], axis=0)
                    ent[name] = jnp.asarray(arr)
                stacked.append(ent)
            groups.append(stacked)
        ids_row = np.zeros((self.L,), np.int32)
        ids_row[:t0] = prompt_ids[: self.L]
        eos = self.gen.eos_token_id
        done0 = remaining <= 0 or (eos is not None and tok0 == eos)
        with self._lock:
            digest = payload.get("prefix_digest")
            if digest is not None and self.index is not None:
                # the index is swapped by self-heal rebuilds: read it
                # under the lock
                if self.index.chain_digest(prompt_ids) != digest:
                    raise ValueError(
                        "prefix digest mismatch: the payload's prompt "
                        "ids do not correspond to its blocks"
                    )
            self._check_fit(t0, max_new)
            self._expire_deadlines_locked()
            if not self._free:
                ra = self._retry_after_locked()
                self._note_shed(prio, "no_decode_slot", ra)
                raise OverloadedError(
                    f"no free decode slot for imported prefill; retry "
                    f"in {ra}s", retry_after_s=ra, reason="no_decode_slot",
                )
            try:
                bids = self.pool.alloc(nblk)
            except PoolExhaustedError as e:
                ra = self._retry_after_locked()
                self._note_shed(prio, "pool_exhausted", ra)
                raise PoolOverloadedError(
                    f"{e}; retry in {ra}s", retry_after_s=ra
                ) from e
            rid = self._next_rid
            self._next_rid += 1
            now = time.perf_counter()
            req = _Request(
                rid=rid, ids=prompt_ids, max_new=max_new, seed=seed,
                submitted_at=now, priority=prio, deadline_s=deadline_s,
                deadline_at=(
                    now + deadline_s if deadline_s is not None else None
                ),
                submitted_ns=time.time_ns(),
                tenant=(str(tenant)[:128] if tenant else None),
                # the packed blob this leg received over the wire —
                # folded into the decode-leg receipt
                wire_bytes=max(int(wire_bytes), 0),
            )
            if deadline_s is not None:
                self._deadlined += 1
            self._requests[rid] = req
            slot = self._free.pop()
            req.slot = slot
            req.admitted_at = now
            self._slot_req[slot] = req
            self._slot_blocks[slot] = list(bids)
            if self.metering:
                self._meter_kv(req, len(bids))
            self._slot_limit[slot] = min(t0 + max_new, self.L)
            self._slot_ub[slot] = t0
            try:
                # graft the received blocks into the pools, one staged
                # group per dispatch of the one shape-static program
                # (pad rows carry the pool-width sentinel and DROP)
                sent = self.pool.num_blocks
                for gi, stacked in enumerate(groups):
                    grp = bids[gi * W:(gi + 1) * W]
                    bid_arr = np.full((W,), sent, np.int32)
                    bid_arr[: len(grp)] = grp
                    self._state = self._graft_op(
                        self._state, stacked, jnp.asarray(bid_arr)
                    )
                self._set_row(slot, start=t0)
                self._state = self._adopt_op(
                    self._state, jnp.int32(slot), jnp.int32(t0),
                    jnp.int32(tok0), jnp.uint32(seed),
                    jnp.int32(remaining),
                    jnp.bool_(not done0), jnp.asarray(ids_row),
                )
                if self.index is not None:
                    newly = self.index.register(prompt_ids, list(bids))
                    for b in newly:
                        self.pool.mark_cached(b, priority=prio)
            except BaseException:
                # a failed device dispatch (e.g. RESOURCE_EXHAUSTED
                # staging a big payload) must not leak the slot, the
                # blocks, or a never-finishable request — repeat
                # imports would otherwise bleed the engine dry
                try:
                    self._state = self._retire_op(
                        self._state, jnp.int32(slot)
                    )
                except Exception:  # noqa: BLE001 — best-effort retire
                    pass
                self._slot_req[slot] = None
                self._slot_blocks[slot] = []
                self._slot_ub[slot] = 0
                self._slot_limit[slot] = 0
                self._free.append(slot)
                for b in reversed(bids):
                    self.pool.release(b)
                if deadline_s is not None:
                    self._deadlined = max(self._deadlined - 1, 0)
                self._requests.pop(rid, None)
                raise
            self.disagg["imports"] += 1
            self.disagg["import_blocks"] += nblk
            self.disagg["import_tokens"] += t0
            req.first_token = np.int32(tok0)
            if done0:
                # nothing to decode: the request is complete at import
                req.first_token = None
                self._maybe_record_ttft_stamp(req)
                self._append_token(req, tok0)
        if self.metrics is not None:
            self.metrics.incr("kv_blocks_imported_total", nblk)
            self.metrics.incr("serving_requests_total")
            self.metrics.incr(
                f"serving_requests_total:{_PRIO_NAMES[prio]}"
            )
        self._event(
            "serving.kv_import", rid=rid, blocks=nblk, tokens=t0,
            slot=slot,
        )
        return rid

    def _maybe_record_ttft_stamp(self, req: _Request) -> None:
        # an import that finishes instantly has no device scalar to
        # await; stamp its (trivially zero) TTFT directly
        if req.first_token_at is None:
            req.first_token_at = time.perf_counter()

    def disagg_wire_ewma_s(self) -> float:
        """Measured wire-transfer EWMA, 0.0 until a transfer completed.
        The prefill role charges this against an end-to-end deadline
        BEFORE shipping: the decode leg re-anchors its budget at import
        arrival, so un-charged wire time would silently extend the SLO
        (per-transfer wall time is unknowable across node clocks)."""
        with self._lock:
            return float(self._disagg_ewma.get("wire_s_ewma") or 0.0)

    def note_disagg_transfer(
        self, *, prefill_s: float | None = None,
        wire_s: float | None = None, wire_bytes: int | None = None,
        fallback: bool = False,
    ) -> None:
        """Fold one completed prefill-leg transfer into the EWMAs the
        tldiag XFER-STALLED flag reads (wire-transfer time exceeding
        prefill compute means the DCN hop, not the chip, bounds this
        worker). Called by the worker role after each SERVE_PREFILL."""
        with self._lock:
            for name, v in (
                ("prefill_s_ewma", prefill_s), ("wire_s_ewma", wire_s),
            ):
                if v is None:
                    continue
                old = self._disagg_ewma.get(name)
                self._disagg_ewma[name] = round(
                    v if old is None else 0.8 * old + 0.2 * v, 6
                )
            if wire_bytes:
                self.disagg["wire_bytes"] = (
                    self.disagg.get("wire_bytes", 0) + int(wire_bytes)
                )
            if fallback:
                self.disagg["fallbacks"] += 1

    def _warm(self) -> None:
        """AOT-compile the (single) decode and prefill-chunk programs at
        construction, logging ``compile_s`` per program."""
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        plans = (
            ("decode", "_decode",
             (*self._program_args(), *self._audit_decode_extra())),
            (
                "prefill_chunk", "_prefill_chunk_fn",
                (
                    *self._program_args(),
                    sds((1, self.prefill_chunk), i32),
                    sds((), i32), sds((), i32), sds((), i32),
                    sds((), jnp.uint32), sds((), i32),
                    sds((), jnp.bool_),
                ),
            ),
        )
        for program, attr, args in plans:
            t0 = time.perf_counter()
            try:
                setattr(
                    self, attr, getattr(self, attr).lower(*args).compile()
                )
                aot = True
            except Exception:  # noqa: BLE001 — AOT is an optimization only
                aot = False
            self._record_compile(program, t0, aot)
            if aot:
                # map onto the DispatchTimer program names: the decode
                # attr runs as the spec chunk when speculation is on
                self._note_cost(
                    self._decode_program_name() if attr == "_decode"
                    else "prefill_chunk",
                    getattr(self, attr),
                )

    def _spec_open_mask(self, state, f0):
        """Paged rows are never padded and attend in LOGICAL
        coordinates (nn/attention.py paged path: every slot at or
        before a query's position is genuine history, causality and the
        window band fold internally), so the verify/draft passes need
        no caller mask at all."""
        return None

    def _pending_prefills(self) -> int:
        return len(self._pending)

    def _pending_slots(self):
        return self._pending  # dict keyed by slot — membership is O(1)

    def _autotune_buckets(self) -> tuple[int, ...]:
        # ONE shape-static prefill-chunk program serves every prompt:
        # the chunk width IS the bucket set
        return (self.prefill_chunk,)

    def audit_programs(self) -> list[dict]:
        """Paged inventory: the (single) decode/spec chunk plus the ONE
        shape-static prefill-chunk program that serves every prompt
        (the per-bucket prefill family of the contiguous engine does
        not exist here — that is the point of chunked prefill)."""
        dt = self._audit_dtype()
        with self._lock:  # snapshot the state tree vs in-flight chunks
            donated = len(jax.tree.leaves(self._state))
            args = self._program_args()
            extra = self._audit_decode_extra()
            spec_on = self.spec is not None  # a self-heal may swap it
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        def lower_chunk():
            return self._build_prefill_chunk().lower(
                *args, sds((1, self.prefill_chunk), i32),
                sds((), i32), sds((), i32), sds((), i32),
                sds((), jnp.uint32), sds((), i32), sds((), jnp.bool_),
            )

        return [
            {
                "name": "spec_chunk" if spec_on else "decode",
                "dtype": dt,
                "donated": donated,
                "lower": lambda: self._build_decode().lower(*args, *extra),
            },
            {
                # distinct per spec mode, like the contiguous prefill
                "name": "prefill_chunk" + ("_spec" if spec_on else ""),
                "dtype": dt,
                "donated": donated,
                "lower": lower_chunk,
            },
        ]

    # ------------------------------------------------------------ admission
    def _check_fit(self, t0: int, max_new: int) -> None:
        if t0 + max_new > self.engine.max_len:
            raise PromptTooLongError(
                f"prompt {t0} + new {max_new} exceeds engine max_len "
                f"{self.engine.max_len}"
            )
        if t0 + max_new > self.L:
            raise PromptTooLongError(
                f"prompt {t0} + new {max_new} exceeds the block-table "
                f"view ({self.L} positions)"
            )
        bs = self.block_size
        need = -(-(t0 + max_new) // bs)
        if need > self.pool.num_blocks:
            raise PoolExhaustedError(
                f"request worst case is {need} blocks of {bs} tokens; "
                f"the pool holds {self.pool.num_blocks} total"
            )

    def _pool_pressure_locked(self) -> float:
        # a near-full pool inflates the retry-after: freed capacity is
        # contended by every queued request, so the naive TPOT x
        # backlog estimate under-promises exactly when shedding peaks
        util = self.pool.in_use / self.pool.num_blocks
        return min(4.0, 1.0 / max(1.0 - util, 0.25))

    def _check_backpressure(
        self, prio: int = int(Priority.STANDARD)
    ) -> None:
        if self.max_queue is None or len(self._queue) < self.max_queue:
            return
        if self._free:
            # slots are free yet admissions back up: the queue is
            # starved on KV blocks, not on decode width
            if self._displace_for_locked(prio):
                return
            ra = self._retry_after_locked()
            self._note_shed(prio, "pool_exhausted", ra)
            raise PoolOverloadedError(
                f"{len(self._queue)} requests pending on KV blocks "
                f"({self.pool.in_use}/{self.pool.num_blocks} in use, "
                f"max_queue={self.max_queue}); retry in {ra}s",
                retry_after_s=ra,
            )
        super()._check_backpressure(prio)

    def _admit_or_queue(self, req: _Request) -> None:
        # queue first, then drain in (priority, rid) order: a non-empty
        # queue means the best-priority head is starved on blocks, and
        # only a STRICTLY higher-priority arrival may pass it (same-
        # class bypass would let steady small-prompt traffic starve a
        # queued long prompt forever)
        self._queue.append(req)
        self._admit_waiting()

    def _admit_waiting(self) -> None:
        # (priority, FIFO-within-class): when the best head cannot get
        # blocks, try preempting strictly lower-priority RUNNING work
        # for it; with no such victims, everyone behind it waits too
        while self._free and self._queue:
            head = self._next_queued_locked()
            if self._try_admit(head):
                self._queue.remove(head)
                continue
            victims = [
                s for s, r in enumerate(self._slot_req)
                if r is not None and r.priority > head.priority
            ]
            if not victims:
                break
            # priority-then-newest, the same order pool pressure uses
            self._preempt(max(
                victims,
                key=lambda s: (
                    self._slot_req[s].priority, self._slot_req[s].rid
                ),
            ))

    def _try_admit(self, req: _Request) -> bool:
        """Map a request into a free slot: prefix-match, retain/COW
        shared blocks, allocate the rest, point the device block table,
        and queue the chunked prefill. False (request stays queued) when
        the pool cannot cover the prompt right now."""
        if req.tokens:
            # preemption resume: re-prefill prompt + banked tokens; the
            # positional sampling keys make the continuation exact
            ids_full = np.concatenate(
                [np.asarray(req.ids), np.asarray(req.tokens)]
            ).astype(np.int32)
        else:
            ids_full = np.asarray(req.ids, np.int32)
        t0 = len(ids_full)
        max_new_eff = req.max_new - len(req.tokens)
        bs = self.block_size
        hits: list[int] = []
        nmatch = 0
        tail = None
        if self.index is not None:
            # never match the whole prompt: the final token must prefill
            # so its logits can seed the first sample
            hits, nmatch, tail = self.index.match(
                ids_full, max_tokens=t0 - 1
            )
        n_new = -(-t0 // bs) - len(hits) - (1 if tail is not None else 0)
        taken: list[int] = []
        cow_src = None
        tail_bid = None
        try:
            for b in hits:
                # a hit UPGRADES the block's eviction class to the most
                # protected consumer: a prefix warmed by BATCH but hit
                # by INTERACTIVE now shields interactive traffic
                self.pool.retain(b, priority=req.priority)
                taken.append(b)
            if tail is not None:
                bid, fill = tail
                if self.pool.refcount(bid) == 0:
                    # sole owner: revive and extend in place — the index
                    # entry vouches only for its first `fill` tokens,
                    # which stay untouched
                    self.pool.retain(bid, priority=req.priority)
                    taken.append(bid)
                    tail_bid = bid
                else:
                    # live sharers: copy-on-write before this request
                    # may write into the block
                    (tail_bid,) = self.pool.alloc(1)
                    taken.append(tail_bid)
                    cow_src = bid
            new_blocks = self.pool.alloc(n_new) if n_new > 0 else []
            taken.extend(new_blocks)
        except PoolExhaustedError:
            for b in reversed(taken):
                self.pool.release(b)
            return False
        slot = self._free.pop()
        req.slot = slot
        self._note_admitted(req)
        self._slot_req[slot] = req
        self._slot_blocks[slot] = (
            hits + ([tail_bid] if tail is not None else []) + new_blocks
        )
        if self.metering:
            self._meter_kv(req, len(self._slot_blocks[slot]))
        self._slot_limit[slot] = min(t0 + max_new_eff, self.L)
        self._slot_ub[slot] = t0
        if cow_src is not None:
            self._state = self._copy_op(
                self._state, jnp.int32(cow_src), jnp.int32(tail_bid)
            )
            if self.metrics is not None:
                self.metrics.incr("kv_cow_copies_total")
            self._event(
                "kvpool.cow", rid=req.rid, src=cow_src, dst=tail_bid,
                fill=tail[1],
            )
        self._set_row(slot, start=nmatch)
        self._pending[slot] = {
            "ids": ids_full, "pos": nmatch, "seed": req.seed,
            "max_new": max_new_eff,
        }
        self.prompt_tokens_total += t0
        self.prefix_matched_tokens += nmatch
        self.prefilled_tokens += t0 - nmatch
        if nmatch and self.metrics is not None:
            self.metrics.incr("prefix_hits_total", nmatch)
        self._event(
            "serving.admit", rid=req.rid, slot=slot,
            prefix_hit_tokens=nmatch,
            blocks=len(self._slot_blocks[slot]),
        )
        return True

    def _set_row(self, slot: int, start: int | None = None) -> None:
        row = np.full((self.max_blocks,), self.pool.num_blocks, np.int32)
        blocks = self._slot_blocks[slot]
        row[: len(blocks)] = blocks
        self._state = self._table_op(
            self._state, jnp.int32(slot), jnp.asarray(row),
            jnp.int32(0 if start is None else start),
            jnp.bool_(start is not None),
        )

    # ------------------------------------------------------------- prefill
    def _dispatch_prefill_chunk(self) -> bool:
        """At most ONE chunk per scheduler step — the chunked-prefill
        contract: decode dispatches interleave, so in-flight TPOT stays
        bounded by one chunk's latency, not a whole prompt's."""
        if not self._pending:
            return False
        # SLO order for the one-chunk-per-step budget too: an
        # INTERACTIVE prompt's TTFT must not wait behind a BATCH
        # prompt's remaining chunks
        slot = min(
            self._pending,
            key=lambda s: (self._slot_req[s].priority, self._slot_req[s].rid),
        )
        job = self._pending[slot]
        ids, pos = job["ids"], job["pos"]
        C = self.prefill_chunk
        nreal = min(C, len(ids) - pos)
        buf = np.zeros((1, C), np.int32)
        buf[0, :nreal] = ids[pos:pos + nreal]
        is_final = pos + nreal >= len(ids)
        self._state, tok0 = self._prefill_chunk_fn(
            *self._program_args(), jnp.asarray(buf),
            jnp.int32(slot), jnp.int32(pos), jnp.int32(nreal),
            jnp.uint32(job["seed"]), jnp.int32(job["max_new"]),
            jnp.bool_(is_final),
        )
        job["pos"] = pos + nreal
        req = self._slot_req[slot]
        if req.prefill_started_at is None:
            req.prefill_started_at = time.perf_counter()
        req.prefill_chunks += 1
        if self._timer is not None:
            # every chunk is its own dispatch; tok0 (a device scalar
            # output, garbage on non-final chunks) is the ready probe
            req.disp = self._timer.dispatch("prefill_chunk", tok0)
            if self.metering:
                req.disp_hist.append(req.disp)
        self._event(
            "serving.prefill_chunk", rid=req.rid, slot=slot, start=pos,
            tokens=nreal, final=is_final,
        )
        if is_final:
            req.first_token = tok0
            del self._pending[slot]
            self._slot_ub[slot] = len(ids)
            if self.index is not None:
                # register the PROMPT prefix (not generated tokens) as
                # soon as its blocks are written — a concurrent request
                # sharing the prefix hits while this one still decodes
                newly = self.index.register(
                    np.asarray(req.ids, np.int32),
                    self._slot_blocks[slot],
                )
                for b in newly:
                    # priority-aware reuse: under allocation pressure
                    # the pool evicts BATCH-cached prefixes before
                    # STANDARD before INTERACTIVE (kvpool.py)
                    self.pool.mark_cached(b, priority=req.priority)
        return True

    # ------------------------------------------------------ blocks / decode
    def _release_slot_blocks(self, slot: int) -> None:
        for b in self._slot_blocks[slot]:
            self.pool.release(b)
        self._slot_blocks[slot] = []
        self._slot_ub[slot] = 0
        self._slot_limit[slot] = 0
        self._pending.pop(slot, None)

    def _finish(self, req: _Request) -> None:
        slot = req.slot
        owns = slot is not None and self._slot_req[slot] is req
        super()._finish(req)
        if owns:
            # retire the device row BEFORE the blocks go back to the
            # pool: the decode program scatter-writes every row's k/v
            # each step (parked rows included — harmless in the
            # contiguous engine where the parked index stays inside the
            # slot's own region), so without the sentinel table this
            # row's parked write would land, via the stale block table,
            # in a block the pool may hand to another request. All ops
            # thread through the one donated state, so chunks dispatched
            # after this retire see the sentinel and DROP the write.
            self._state = self._retire_op(self._state, jnp.int32(slot))
            self._release_slot_blocks(slot)

    def _preempt(self, slot: int) -> None:
        """Evict a live request to free its blocks: retire the slot on
        device FIRST (its parked writes must drop before any block is
        remapped), drain in-flight chunks (their tokens are genuine),
        then release and re-queue at the FRONT. The resumed request
        re-prefills prompt+banked tokens and continues token-identical
        (sampling keys depend on position, not history)."""
        req = self._slot_req[slot]
        self._event(
            "serving.preempt", "warn", rid=req.rid, slot=slot,
            tokens=len(req.tokens),
        )
        if self.metrics is not None:
            self.metrics.incr("serving_preempt_total")
        self._state = self._retire_op(self._state, jnp.int32(slot))
        while self._inflight:
            self._drain_one()
        if req.done:
            return  # finished in flight; _finish already freed everything
        self._release_slot_blocks(slot)
        if self.metering:
            self._meter_kv(req, 0)  # holds nothing while re-queued
        self._slot_req[slot] = None
        req.slot = None
        self._free.append(slot)
        # (priority, rid) ordering makes queue position irrelevant: the
        # preempted request resumes ahead of later same-class arrivals
        # because it keeps its original rid
        self._queue.append(req)

    def _drain_for_abort(self, req: _Request) -> None:
        # same discipline as _preempt: retire the device row FIRST so
        # parked writes drop, then drain in-flight chunks — only then
        # may _finish return this slot's blocks to the pool (a chunk
        # dispatched before the retire could still write through the
        # old table into a block about to be remapped)
        self._state = self._retire_op(self._state, jnp.int32(req.slot))
        while self._inflight:
            self._drain_one()

    def _alloc_with_preemption(self, n: int, protect: int):
        """Allocate ``n`` blocks, preempting under pressure in
        priority-then-newest order: the newest request of the LEAST
        protected class among the others (a BATCH stream always goes
        before any STANDARD one, STANDARD before INTERACTIVE — the SLO
        contract). Returns None when ``protect`` itself had to be
        preempted (pool too small for the live set)."""
        while True:
            try:
                return self.pool.alloc(n)
            except PoolExhaustedError:
                victims = [
                    s for s, r in enumerate(self._slot_req)
                    if r is not None and s != protect
                ]
                if not victims:
                    self._preempt(protect)
                    return None
                self._preempt(
                    max(victims, key=lambda s: (
                        self._slot_req[s].priority,
                        self._slot_req[s].rid,
                    ))
                )

    def _advance_bound(self, slot: int) -> int:
        """Max tokens the NEXT dispatched chunk can advance this slot
        by. Under adaptive speculation this reads the step()-staged
        masked-K array — the device clamps each round's emission at
        ``k_eff + 1`` for exactly the ``k_eff`` that array will carry,
        so the bound is simultaneously SAFE (never below what the
        device can write) and TIGHT (a low-acceptance row the
        controller shrank to k_min reserves ``rounds * (k_min + 1)``
        positions, not ``rounds * (k_max + 1)`` — the `_slot_ub`
        overshoot the static bound paid for tokens that never
        arrived)."""
        if self.spec is None:
            return self.decode_chunk
        k = self.spec.cfg.k
        if self._k_dispatch is not None:
            k = self._k_dispatch[slot]
        return self.spec.cfg.rounds * (k + 1)

    def _grow_blocks(self, decoding: list[int]) -> list[int]:
        """Extend block tables ahead of the decode write frontier: the
        next chunk advances each live row by up to ``_advance_bound``
        positions (``decode_chunk``, or ``rounds * (k_eff+1)`` under
        speculation) with NO host sync, so the blocks must exist before
        dispatch. Returns the decoding set minus any preempted slots.

        Under low-acceptance speculation ``_slot_ub`` overshoots the
        true frontier (rejected rounds advance less than the bound),
        so a slot can hold blocks ahead of need — DELIBERATELY never
        clamped back from drained ``n_emit``: the drain runs
        ``pipeline_depth`` chunks behind dispatch and slots re-admit
        between the two, so a host-side clamp that guessed low would
        leave table entries at the sentinel and the device would DROP
        that token's k/v — silent output corruption, vs. bounded
        padding (the bound saturates at the request's own
        prompt+budget limit, and preemption handles real pressure).
        The adaptive controller tightens the bound the SAFE way: it
        shrinks what the device may emit, then reserves exactly
        that."""
        bs = self.block_size
        for slot in decoding:
            req = self._slot_req[slot]
            if req is None or slot in self._pending:
                continue  # preempted (or re-queued) by an earlier growth
            target = min(
                self._slot_ub[slot] + self._advance_bound(slot),
                self._slot_limit[slot],
            )
            need = -(-target // bs)
            have = len(self._slot_blocks[slot])
            if need > have:
                got = self._alloc_with_preemption(need - have, slot)
                if got is None:
                    continue  # the slot itself was evicted
                self._slot_blocks[slot].extend(got)
                self._set_row(slot)
                if self.metering:
                    self._meter_kv(req, len(self._slot_blocks[slot]))
            self._slot_ub[slot] = target
        return [
            s for s in decoding
            if self._slot_req[s] is not None and s not in self._pending
        ]

    def step(self) -> bool:
        """One scheduler iteration: admit, dispatch at most one prefill
        chunk, grow block tables, dispatch one decode chunk, drain."""
        with self._lock, region("serve.step"):
            self._maybe_self_heal()
            with region("serve.admit"):
                self._expire_deadlines_locked()
                self._admit_waiting()
            with region("serve.prefill_dispatch"):
                prefilling = self._dispatch_prefill_chunk()
            decoding = [
                s for s, r in enumerate(self._slot_req)
                if r is not None and s not in self._pending and not r.hold
            ]
            if decoding and self.spec is not None:
                # stage the masked-K array NOW: block growth below and
                # the dispatch's k_eff operand must read the SAME
                # values, or a controller update from a preemption
                # drain could widen the device's bound past the blocks
                # just grown
                self._k_dispatch = self._spec_k_array()
            with region("serve.grow_blocks"):
                if decoding:
                    decoding = self._grow_blocks(decoding)
            if decoding:
                with region("serve.decode_dispatch"):
                    payload, disp = self._dispatch_decode()
                live = set(decoding)
                # mid-prefill slots are NOT live on device: their rows
                # emit fill tokens that must never reach a request
                snap = tuple(
                    r if s in live else None
                    for s, r in enumerate(self._slot_req)
                )
                self._inflight.append((payload, snap, disp))
            for r in self._slot_req:
                if r is not None:
                    self._maybe_record_ttft(r)
            if self._timer is not None:
                self._timer.poll()
            # an undispatched staged array must not leak into a later
            # step whose controller has moved on
            self._k_dispatch = None
            busy = bool(decoding or prefilling)
            with region("serve.drain"):
                while len(self._inflight) > (
                    self.pipeline_depth if busy else 0
                ):
                    self._drain_one()
            if not busy:
                self._maybe_self_heal()  # just drained fully idle
            self.peak_blocks_in_use = max(
                self.peak_blocks_in_use, self.pool.in_use
            )
            if self.metrics is not None:
                st = self.pool.stats()
                self.metrics.observe("kv_blocks_in_use", st["blocks_in_use"])
                self.metrics.observe("kv_pool_utilization", st["utilization"])
            return bool(
                busy or self._queue or self._inflight or self._pending
            )

    # --------------------------------------------------------------- stats
    def _prefix_hit_rate_locked(self) -> float:
        if not self.prompt_tokens_total:
            return 0.0
        return self.prefix_matched_tokens / self.prompt_tokens_total

    def prefix_hit_rate(self) -> float:
        """Fraction of submitted prompt tokens served from resident
        prefix blocks (never re-prefilled)."""
        with self._lock:
            return self._prefix_hit_rate_locked()

    def stats(self) -> dict:
        out = super().stats()
        # the admission counters are written under the scheduler lock
        # (_try_admit); reading them unlocked can tear the snapshot —
        # e.g. prompt_tokens_total from one admission and
        # prefix_matched_tokens from the next (tlint TL601)
        with self._lock:
            out.update(
                {
                    "pool": self.pool.stats(),
                    "prefilling": len(self._pending),
                    "peak_blocks_in_use": self.peak_blocks_in_use,
                    "prompt_tokens_total": self.prompt_tokens_total,
                    "prefix_matched_tokens": self.prefix_matched_tokens,
                    "prefilled_tokens": self.prefilled_tokens,
                    "prefix_cache_hit_rate": round(
                        self._prefix_hit_rate_locked(), 4
                    ),
                }
            )
            if any(self.disagg.values()) or self._disagg_ewma:
                # disaggregated-serving legs this engine served: export/
                # import counters plus the prefill-vs-wire EWMAs behind
                # the tldiag XFER-STALLED flag
                out["disagg"] = {**self.disagg, **self._disagg_ewma}
        return out

    def kv_stats(self, limit: int = 64) -> dict:
        """Locked KV/prefix residency snapshot — the ``GET /kv`` body.
        The scheduler lock serializes against admission/eviction, so
        the chains, refcounts and pool counters are one consistent
        instant, never a table torn mid-admission (tlint TL601)."""
        with self._lock:
            return kv_residency(self.pool, self.index, limit=limit)

    def kv_stats_summary(self) -> dict:
        """Scalar residency summary for the heartbeat delta (same lock
        contract as :meth:`kv_stats`)."""
        with self._lock:
            return kv_summary(self.pool, self.index)
