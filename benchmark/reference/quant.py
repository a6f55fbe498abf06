"""Lower precision for the control: the reference's matmul operands
rounded to int8 or to fp8 (e4m3), values kept in float32 ("fake
quantization"), scaled per row of the contracted axis as a W8A8
deployment scales them. The gradient passes straight through the
rounding, so a training control still trains.

The control is the reference put in the program's place, one precision
step below what the configuration states (bf16 -> int8 or fp8). It is
never part of a measured run: tests and the on-chip control script
call it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _round(x, mode: str, axis: int):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if mode == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if mode == "fp8":
        s = amax / 448.0  # e4m3's largest finite value
        return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    raise ValueError(f"unknown control precision {mode!r}")


def lower(x, mode: str | None, axis: int = -1):
    """``x`` as the control's matmul sees it; identity for the reference
    proper (``mode`` None)."""
    if mode is None:
        return x
    return x + jax.lax.stop_gradient(_round(x, mode, axis) - x)
