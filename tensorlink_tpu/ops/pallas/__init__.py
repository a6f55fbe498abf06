"""Pallas TPU kernels, and the one place their gates ask about the device.

Every kernel keeps a ``jnp`` path beside it (CPU tests run it, and the
parity tests compare against it), chosen by a static gate at trace
time. Off the TPU that choice is the only one there is and stays
silent. ON a TPU a gate that closes must not pass for the kernel having
run: it records a ``kernel.gate_closed`` event with the reason on the
process flight recorder (``runtime/flight.py default_recorder``), where
``/events``, a post-mortem bundle and ``chip_smoke.py`` read it.
"""

from __future__ import annotations

import jax

from tensorlink_tpu.runtime.flight import default_recorder


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def partitioned_by_xla() -> str | None:
    """Why a Mosaic kernel cannot lower in the trace this is called
    from, or None. XLA's partitioner cannot split a kernel: on more
    than one device it lowers only where every mesh axis is manual
    (inside a ``shard_map`` over all of them). The mesh is the one
    ambient while tracing — a ``shard_map`` always shows it, a plain
    multi-device ``jit`` only under ``jax.set_mesh``, which is why the
    engines that own a mesh trace under it."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return None
    auto = [n for n in mesh.axis_names if n not in mesh.manual_axes]
    if not auto:
        return None
    return (
        f"mesh axes {auto} of {dict(mesh.shape)} are partitioned by "
        "XLA, which cannot split a Mosaic kernel"
    )


def gate_closed(kernel: str, reason: str, **attrs) -> bool:
    """Record why ``kernel``'s gate closed; returns False so a gate can
    ``return gate_closed(...)``. Gates run while tracing, so this is one
    event per traced call site, not one per step."""
    default_recorder().record(
        "kernel.gate_closed", kernel=kernel, reason=reason, **attrs
    )
    return False
