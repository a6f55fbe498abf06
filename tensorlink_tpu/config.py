"""Typed configuration for the whole framework.

The reference scatters configuration across `.env` keys read at import time,
a hardcoded contract-ABI path, constructor kwargs, and inline magic constants
(survey of src/p2p/smart_node.py:20-41, src/p2p/connection.py:39,
src/ml/distributed.py:16). Here all of it is a single tree of frozen
dataclasses with no import-time side effects; every subsystem takes its
config object explicitly.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Mapping


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh: axes (data, pipe, model, seq).

    The product of the axis sizes must equal the number of participating
    devices. ``pipe`` maps to pipeline stages (the TPU-native replacement for
    the reference's one-worker-per-submodule vertical partitioning,
    src/ml/distributed.py:305-378), ``data`` to data-parallel replicas
    (the reference's planned-but-unbuilt dp_factor, src/roles/user.py:161),
    ``model`` to tensor-parallel shards, ``seq`` to sequence/context
    parallelism (ring attention).
    """

    data: int = 1
    pipe: int = 1
    model: int = 1
    seq: int = 1

    AXIS_NAMES = ("data", "pipe", "model", "seq")

    @property
    def num_devices(self) -> int:
        return self.data * self.pipe * self.model * self.seq

    @property
    def shape(self) -> tuple[int, int, int, int]:
        return (self.data, self.pipe, self.model, self.seq)

    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.AXIS_NAMES, self.shape))


@dataclass(frozen=True)
class DistributedConfig:
    """Multi-HOST mesh formation (SURVEY §2.4/§5.8: jax.distributed +
    gRPC coordination over DCN, the road to a v4-32-style pod slice).

    One SPMD program spans every process: each host contributes its
    local chips and `jax.distributed.initialize` joins them into one
    global device set, from which `make_mesh` builds the (data, pipe,
    model, seq) mesh. The reference's analogue is its whole multi-machine
    premise (socket workers, src/p2p/smart_node.py:490-537) — here the
    DATA plane is one compiled program and only job control rides the
    P2P overlay.

    ``coordinator`` is "host:port" of process 0. ``num_processes`` and
    ``process_id`` may be None when the platform supplies them (TPU pod
    metadata); on CPU/manual deployments set them explicitly.
    """

    coordinator: str | None = None  # None = single-process (no init)
    num_processes: int | None = None
    process_id: int | None = None
    # bound local devices per host (None = all; CPU tests use
    # xla_force_host_platform_device_count instead)
    local_device_ids: tuple | None = None

    @property
    def enabled(self) -> bool:
        return self.coordinator is not None


@dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters + micro-batching.

    ``micro_batches`` plays the role of the reference's
    batch_size // micro_batch_size thread count (src/ml/distributed.py:91),
    but here it is the static length of the pipeline schedule loop.
    """

    batch_size: int = 32
    micro_batches: int = 4
    learning_rate: float = 2e-5
    weight_decay: float = 0.0
    optimizer: str = "adamw"  # adam | adamw | sgd
    warmup_steps: int = 0
    total_steps: int = 1000
    schedule: str = "constant"  # constant | linear | cosine
    grad_clip_norm: float | None = 1.0
    seed: int = 0
    dtype: str = "bfloat16"  # compute dtype; params stay f32
    remat: bool = False  # jax.checkpoint each stage/block
    pp_schedule: str = "gpipe"  # gpipe | 1f1b (bounded-memory interleave)
    # weight of the MoE router load-balancing loss added to the task loss
    # (0 = off; requires PipelineParts.block_fn_aux; works under both
    # pipeline schedules)
    moe_aux_weight: float = 0.0
    # "lora" = train ONLY LoRA adapter leaves (nn/lora.py lora_init'd
    # params): base weights ride the same sharded update program with a
    # zero update, so every schedule/axis combination works unchanged
    train_only: str | None = None
    # FSDP/ZeRO-3: shard params + optimizer moments over the ``data``
    # axis as well (parallel/dp.py fsdp_spec_tree); XLA all-gathers at
    # use and reduce-scatters grads. Replicated DP otherwise.
    fsdp: bool = False
    # storage dtype of adam/adamw m+v ("bfloat16" halves optimizer-state
    # bytes and HBM traffic; update math stays f32 — train/optim.py)
    opt_moment_dtype: str = "float32"
    # non-finite sentinel (runtime/flight.py): Trainer._step always
    # reports a ``nonfinite`` flag in its stats; with this set, a step
    # whose loss/grads are non-finite leaves params, optimizer moments,
    # and the step counter UNCHANGED (the anomaly is still counted in
    # train_nonfinite_total and recorded as a flight event)
    skip_nonfinite_updates: bool = False

    def __post_init__(self):
        # validated HERE so BOTH trainers (train/trainer.py Trainer and
        # parallel/engine.py ShardedTrainer) reject a typo'd mode — a
        # silently ignored train_only would full-fine-tune a run the
        # user believes is frozen-base LoRA
        if self.train_only not in (None, "lora"):
            raise ValueError(
                f"unknown train_only {self.train_only!r}; supported: 'lora'"
            )
        from tensorlink_tpu.train.optim import SUPPORTED_MOMENT_DTYPES

        if self.opt_moment_dtype not in SUPPORTED_MOMENT_DTYPES:
            # same allowlist the P2P worker schema enforces — one source
            # of truth stops a local config from silently doing what a
            # remote job would reject (fp16's narrow exponent can
            # over/underflow the second moment)
            raise ValueError(
                f"unsupported opt_moment_dtype {self.opt_moment_dtype!r}; "
                f"supported: {SUPPORTED_MOMENT_DTYPES}"
            )

    @property
    def micro_batch_size(self) -> int:
        if self.batch_size % self.micro_batches:
            raise ValueError(
                f"batch_size={self.batch_size} not divisible by "
                f"micro_batches={self.micro_batches}"
            )
        return self.batch_size // self.micro_batches


@dataclass(frozen=True)
class NodeConfig:
    """Control-plane node identity + transport settings.

    Replaces the reference's SmartNode ctor kwargs + BASE_PORT scanning
    (src/p2p/smart_node.py:41,103-112,949-967).
    """

    role: str = "worker"  # user | worker | validator
    host: str = "127.0.0.1"
    port: int = 0  # 0 = OS-assigned
    base_port: int = 38751
    max_connections: int = 64
    handshake_timeout_s: float = 10.0
    connect_timeout_s: float = 5.0  # per-candidate dial bound (alt_hosts)
    request_timeout_s: float = 5.0
    dht_replication: int = 3
    dht_buckets: int = 256
    heartbeat_interval_s: float = 2.0
    heartbeat_miss_limit: int = 3
    compression: str = "zstd"  # none | zlib | zstd
    compression_min_bytes: int = 4096
    off_chain: bool = True  # in-memory Registry instead of web3
    # chain binding when off_chain=False (reference reads CONTRACT/CHAIN_URL
    # from .env at import time, src/p2p/smart_node.py:20-30; here they are
    # explicit typed config, no import-time side effects)
    chain_url: str | None = None  # EVM JSON-RPC endpoint
    chain_contract: str | None = None  # registry contract address
    chain_sender: str | None = None  # from-address for node-managed txs
    key_dir: str | None = None  # None = ephemeral in-memory identity
    http_status_port: int | None = None  # aiohttp status endpoint
    # TP width for loaded stages: 1 = single device, -1 = all local
    # devices, N>1 = first N local devices (every chip a worker, SURVEY
    # §7.2 — the stage is sharded by the module's own PartitionSpecs)
    stage_tp_devices: int = 1
    # periodic DHT persistence (reference: save_dht_state every 600 s,
    # src/p2p/smart_node.py:701-728); None disables
    dht_snapshot_path: str | None = None
    dht_snapshot_interval_s: float = 600.0
    # NAT traversal (reference: miniupnpc IGD mapping + upward port scan,
    # src/p2p/smart_node.py:787-816,949-967). Off by default: cluster and
    # public-IP nodes need no mapping; port=-1 requests the base_port scan.
    upnp: bool = False
    upnp_lease_s: int = 0  # 0 = indefinite mapping
    upnp_timeout_s: float = 3.0
    upnp_ssdp_addr: tuple = ("239.255.255.250", 1900)  # overridable in tests
    # cadence of the validator's cached-registry refresh (serves the
    # non-blocking is_validator_local gate on the event loop)
    registry_refresh_s: float = 30.0
    # health sentinel loop (runtime/flight.py): event-loop lag probe,
    # watchdog trip-edge checks, memory watermark gauges
    health_interval_s: float = 1.0
    # a placed job whose train_step has not COMPLETED within this
    # deadline flips the master's /healthz unhealthy (armed on the first
    # step, disarmed by DistributedJob.shutdown); None disables
    step_watchdog_s: float | None = 300.0
    # persistent XLA compilation cache (runtime/compile_cache.py): a
    # restarted node reloads its compiled serving/stage programs from
    # disk instead of re-paying XLA. JAX_COMPILATION_CACHE_DIR, when
    # set, decides the directory and this field is then only checked
    # against it; otherwise None defers to TL_COMPILE_CACHE_DIR, and
    # with both unset the cache lives in <checkout>/.jax_cache.
    compile_cache_dir: str | None = None
    # persistent autotune store (runtime/autotune.py): measured
    # flash-block overrides, prefill-bucket sets, and the adaptive-
    # speculation K prior reload beside the compile cache, so a
    # restart warm-starts the CONSTANTS as well as the kernels. None
    # defers to TL_AUTOTUNE_DIR; both unset = off.
    autotune_dir: str | None = None
    # capability microbench at WorkerNode start (runtime/profiling.py
    # measure_capability): peak matmul TFLOPs + HBM read GB/s, cached
    # in the autotune store under the chip-global key so restarts skip
    # the measurement; published at /metrics, /node, and on heartbeat
    # PONGs (the validator fleet table ROADMAP-1 placement consumes).
    # None = on unless the TL_CAPABILITY_BENCH=0 environment kill
    # switch is set (the test suite sets it: dozens of ephemeral
    # workers must not each pay the bench); True forces it regardless.
    capability_bench: bool | None = None
    # retained jax.profiler captures from GET /profile (None = parsed
    # and discarded per request)
    profile_dir: str | None = None
    # on-node ring-buffer time-series (runtime/timeseries.py): every
    # metric sampled at this cadence into the fixed-memory retention
    # tiers behind GET /history, heartbeat deltas, and GET /fleet.
    # False turns the sampler (and the heartbeat delta it feeds) off —
    # the toggle the observability-overhead bench flips.
    timeseries_enabled: bool = True
    timeseries_interval_s: float = 1.0
    # SLO alert rules (runtime/alerts.py): path to an slo.json rule
    # file; None = the default rule set (host-bound / kv-pressure /
    # heartbeat-stale, no latency targets)
    slo_path: str | None = None

    def __post_init__(self):
        # wire serialization (msgpack/json) round-trips tuples as lists;
        # normalize so config equality survives to_dict/from_dict
        object.__setattr__(self, "upnp_ssdp_addr", tuple(self.upnp_ssdp_addr))


@dataclass(frozen=True)
class FrameworkConfig:
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    node: NodeConfig = field(default_factory=NodeConfig)
    distributed: DistributedConfig = field(default_factory=DistributedConfig)

    # ------------------------------------------------------------------
    # (De)serialization — configs travel inside job records on the wire.
    # ------------------------------------------------------------------
    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "FrameworkConfig":
        dist = dict(d.get("distributed", {}))
        if dist.get("local_device_ids") is not None:
            dist["local_device_ids"] = tuple(dist["local_device_ids"])
        return cls(
            mesh=MeshConfig(**d.get("mesh", {})),
            train=TrainConfig(**d.get("train", {})),
            node=NodeConfig(**d.get("node", {})),
            distributed=DistributedConfig(**dist),
        )

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "FrameworkConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kw: Any) -> "FrameworkConfig":
        return dataclasses.replace(self, **kw)


def config_from_env(env: Mapping[str, str] | None = None) -> FrameworkConfig:
    """Optional env-var overrides (explicit, never at import time)."""
    env = dict(os.environ if env is None else env)
    mesh = MeshConfig(
        data=int(env.get("TLTPU_MESH_DATA", 1)),
        pipe=int(env.get("TLTPU_MESH_PIPE", 1)),
        model=int(env.get("TLTPU_MESH_MODEL", 1)),
        seq=int(env.get("TLTPU_MESH_SEQ", 1)),
    )
    return FrameworkConfig(mesh=mesh)
