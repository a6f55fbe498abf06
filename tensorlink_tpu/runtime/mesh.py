"""Device mesh management.

The reference's notion of capacity is a per-process `get_gpu_memory()` poll
(src/p2p/torch_node.py:27, src/ml/model_analyzer.py:10-27) and placement is
one worker socket per offloaded submodule. Here capacity is a set of TPU
devices arranged into one logical `jax.sharding.Mesh`; placement means
assigning pipeline stages / shards to mesh coordinates, and XLA inserts the
ICI collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorlink_tpu.config import DistributedConfig, MeshConfig

_distributed_initialized = False


def initialize_distributed(cfg: DistributedConfig) -> dict:
    """Join this process into one multi-HOST JAX runtime (SURVEY
    §2.4/§5.8: jax.distributed + gRPC coordination over DCN).

    After this returns, ``jax.devices()`` is the GLOBAL device set of
    every participating process, and ``make_mesh`` over it yields one
    mesh whose SPMD programs span hosts — collectives ride ICI within a
    host/slice and DCN across, inserted by XLA from the same shardings
    as the single-host path. No-op (with a report) when the config is
    single-process or this process already initialized.

    Returns a summary dict {enabled, process_id, num_processes,
    global_devices, local_devices} for logs/status endpoints.
    """
    global _distributed_initialized
    if not cfg.enabled:
        return {"enabled": False}
    if not _distributed_initialized:
        kw = {}
        if cfg.num_processes is not None:
            kw["num_processes"] = cfg.num_processes
        if cfg.process_id is not None:
            kw["process_id"] = cfg.process_id
        if cfg.local_device_ids is not None:
            kw["local_device_ids"] = list(cfg.local_device_ids)
        jax.distributed.initialize(cfg.coordinator, **kw)
        _distributed_initialized = True
    return {
        "enabled": True,
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
    }


def shutdown_distributed() -> None:
    """Leave the multi-process runtime (tests spawn several in a row)."""
    global _distributed_initialized
    if _distributed_initialized:
        jax.distributed.shutdown()
        _distributed_initialized = False


def virtual_cpu_xla_flags(n_devices: int, flags: str = "") -> str:
    """``XLA_FLAGS`` for an ``n_devices``-device virtual CPU platform,
    merged into the ``flags`` already set. Takes effect only if it is
    in the environment before anything asks jax for its devices (the
    backend is built on the first such call). ``tests/conftest.py``
    spells the same flags out, because it must not import jax first."""
    import re

    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags += f" --xla_force_host_platform_device_count={n_devices}"
    elif int(m.group(1)) < n_devices:
        # an existing smaller count would win over ours
        flags = (
            flags[: m.start()]
            + f"--xla_force_host_platform_device_count={n_devices}"
            + flags[m.end():]
        )
    if "xla_disable_hlo_passes" not in flags:
        # XLA:CPU's AllReducePromotion pass aborts the PROCESS on the
        # bf16 all-reduce jax 0.9.0 emits for a psum inside a partially
        # manual shard_map (ShardedTrainer with pipe > 1; CHANGES.md,
        # PR 24, has the reproducer); the CPU runtime reduces bf16
        # without it
        flags += " --xla_disable_hlo_passes=all-reduce-promotion"
    return flags.strip()


def make_mesh(cfg: MeshConfig, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the global mesh with axes (data, pipe, model, seq).

    Axis order puts ``model`` and ``seq`` innermost so tensor/sequence
    collectives (the highest-bandwidth traffic) ride adjacent-device ICI
    links, while ``data`` (lowest-frequency traffic: one allreduce per step)
    is outermost and may span DCN on multi-host topologies.
    """
    devices = list(jax.devices() if devices is None else devices)
    if cfg.num_devices > len(devices):
        raise ValueError(
            f"mesh {cfg.shape} needs {cfg.num_devices} devices, "
            f"have {len(devices)}"
        )
    grid = np.array(devices[: cfg.num_devices]).reshape(cfg.shape)
    return Mesh(grid, MeshConfig.AXIS_NAMES)


@dataclasses.dataclass
class MeshRuntime:
    """Owns the mesh + common shardings for one job."""

    cfg: MeshConfig
    mesh: Mesh

    @classmethod
    def create(
        cls, cfg: MeshConfig | None = None, devices: Sequence[jax.Device] | None = None
    ) -> "MeshRuntime":
        cfg = cfg or MeshConfig(data=len(devices or jax.devices()))
        return cls(cfg=cfg, mesh=make_mesh(cfg, devices))

    # Common shardings --------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def batch_sharded(self) -> NamedSharding:
        """Batch dim over (data,); used for inputs."""
        return NamedSharding(self.mesh, P(("data",)))

    def shard_batch(self, batch):
        return jax.device_put(batch, self.batch_sharded)

    def replicate(self, tree):
        return jax.device_put(tree, self.replicated)

    # Introspection -----------------------------------------------------
    def describe(self) -> dict:
        return {
            "axes": self.cfg.axis_sizes(),
            "num_devices": self.cfg.num_devices,
            "device_kinds": sorted({d.device_kind for d in self.mesh.devices.flat}),
        }


def local_device_info() -> list[dict]:
    """Per-device capacity info, the TPU analogue of the reference's
    get_gpu_memory worker self-report (src/roles/worker.py:363-381)."""
    out = []
    for d in jax.devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            pass
        out.append(
            {
                "id": d.id,
                "platform": d.platform,
                "device_kind": d.device_kind,
                "bytes_limit": stats.get("bytes_limit"),
                "bytes_in_use": stats.get("bytes_in_use"),
            }
        )
    return out
