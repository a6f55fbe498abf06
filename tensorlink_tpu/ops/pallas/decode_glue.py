"""Fused residual-add + norm for the decode hot path.

BENCH_r05's decode op-breakdown blames ~240 tiny fused elementwise ops
per token step (66% "loop fusion") for the gap to the bandwidth bound:
at T=1 every per-layer add/mean/var/rsqrt/scale chain is its own
launch-bound fusion. This kernel collapses the residual add and the
following norm — the glue between attention/MLP and the next matmul —
into ONE kernel emitting both the carried residual (``x + a``) and its
normalized form, halving the elementwise launch count per transformer
block on the decode path.

The math matches nn/layers.py LayerNorm/RMSNorm bit-for-bit in intent:
f32 accumulation, ``rsqrt(var + eps)``, cast back to the compute dtype.
Decode shapes are tiny (rows = serving slots), so the whole operand set
lives in VMEM with no grid.

Off-TPU (and for any shape the kernel doesn't cover) the public entry
takes the identical jnp expression — CPU CI exercises both that path
(always) and the kernel via ``interpret=True`` parity tests. On a TPU a
refusal records its reason (``ops/pallas gate_closed``).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorlink_tpu.ops.pallas import (
    gate_closed,
    on_tpu,
    partitioned_by_xla,
)

LANES = 128

# toggled by env (TL_DECODE_GLUE=0 disables) so a suspect kernel can be
# ruled out in production without a code change
_ENABLED = os.environ.get("TL_DECODE_GLUE", "1") == "1"


def _norm_f32(r, scale, bias, eps: float, kind: str):
    """The shared f32 norm expression (kernel body AND fallback — one
    home so they cannot drift)."""
    if kind == "layer":
        mu = jnp.mean(r, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(r - mu), axis=-1, keepdims=True)
        y = (r - mu) * jax.lax.rsqrt(var + eps)
    elif kind == "rms":
        ms = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
        y = r * jax.lax.rsqrt(ms + eps)
    else:
        raise ValueError(f"unknown norm kind {kind!r}")
    y = y * scale
    if bias is not None:
        y = y + bias
    return y


def _kernel_bias(x_ref, res_ref, scale_ref, bias_ref, r_ref, y_ref,
                 *, eps: float, kind: str):
    r = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    r_ref[...] = r.astype(r_ref.dtype)
    y = _norm_f32(
        r, scale_ref[...].astype(jnp.float32),
        bias_ref[...].astype(jnp.float32), eps, kind,
    )
    y_ref[...] = y.astype(y_ref.dtype)


def _kernel_nobias(x_ref, res_ref, scale_ref, r_ref, y_ref,
                   *, eps: float, kind: str):
    r = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    r_ref[...] = r.astype(r_ref.dtype)
    y = _norm_f32(r, scale_ref[...].astype(jnp.float32), None, eps, kind)
    y_ref[...] = y.astype(y_ref.dtype)


def _kernel_ok(x, interpret: bool) -> bool:
    if not interpret and not on_tpu():
        return False
    closed = partial(gate_closed, "decode_glue", x=x.shape)
    if not _ENABLED:
        return closed("TL_DECODE_GLUE=0")
    if not interpret and (why := partitioned_by_xla()):
        return closed(why)
    D = x.shape[-1]
    if D % LANES:
        return closed(f"feature dim {D} is not a multiple of {LANES} lanes")
    # decode rows are few — everything fits VMEM ungridded (64 rows x
    # 8192 f32 is 2 MB)
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    if rows * D * 4 > 8 * 1024 * 1024:
        return closed(f"{rows} rows x {D} does not fit VMEM ungridded")
    return True


def fused_residual_norm(
    x: jax.Array,  # [..., D] branch output (attention / MLP)
    res: jax.Array,  # [..., D] carried residual
    scale: jax.Array,  # [D] norm gain
    bias: jax.Array | None = None,  # [D] LayerNorm bias
    *,
    eps: float = 1e-6,
    kind: str = "layer",  # "layer" | "rms"
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (x + res, norm(x + res) * scale [+ bias]), both in x.dtype.

    One kernel launch on TPU for what is otherwise a chain of small
    elementwise fusions; identical-math jnp fallback elsewhere.
    """
    if x.shape != res.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {res.shape}")
    if kind not in ("layer", "rms"):
        raise ValueError(f"unknown norm kind {kind!r}")
    lead, D = x.shape[:-1], x.shape[-1]
    if _kernel_ok(x, interpret):
        x2 = x.reshape(-1, D)
        r2 = res.astype(x.dtype).reshape(-1, D)
        kern = (
            partial(_kernel_bias, eps=float(eps), kind=kind)
            if bias is not None
            else partial(_kernel_nobias, eps=float(eps), kind=kind)
        )
        ops = [x2, r2, scale.reshape(1, D)]
        if bias is not None:
            ops.append(bias.reshape(1, D))
        out_shape = (
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
        )
        r, y = pl.pallas_call(
            kern,
            out_shape=out_shape,
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(ops),
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ),
            interpret=interpret,
            name="tl_decode_glue",
        )(*ops)
        return r.reshape(*lead, D), y.reshape(*lead, D)
    # fallback: same f32 math, XLA-fused
    r = (x.astype(jnp.float32) + res.astype(jnp.float32))
    y = _norm_f32(
        r, scale.astype(jnp.float32),
        None if bias is None else bias.astype(jnp.float32), eps, kind,
    )
    return r.astype(x.dtype), y.astype(x.dtype)


def should_fuse(x, norm_kind: str, *, interpret: bool = False) -> bool:
    """Engage the fused decode glue? Called by TransformerBlock on its
    decode (cached, single-token, eval) path only."""
    return norm_kind in ("layer", "rms") and _kernel_ok(x, interpret)
