"""The state-space half of a SambaY decoder-hybrid-decoder (Ren et al.
2025, "Decoder-hybrid-decoder architecture for efficient reasoning with
long generation", arXiv:2507.06607; Phi-4-mini-flash-reasoning): the
Mamba-1 mixer of the self-decoder and the gated memory unit that reads
one Mamba layer's scan output in the cross-decoder.

Mamba (Gu & Dao 2023), d_inner = expand * dim channels, N states a
channel, R = dt_rank:

    [u, z] = x W_in
    u = SiLU(conv(u) + b_conv)                   (depthwise, causal)
    [dt, B_t, C_t] = u W_x                       (R + N + N)
    Delta = softplus(dt W_dt + b_dt)             [T, d_inner]
    s_t = exp(Delta_t A) * s_{t-1} + (Delta_t u_t) B_t^T,  A = -exp(A_log)
    y_t = s_t C_t + D * u_t
    out = (y * SiLU(z)) W_out

The recurrence runs in chunks (``ops/selective_scan.py``) under the
scope ``tl.mamba.scan``; the caller's scope (``tl.mamba``) holds the
rest. ``apply`` returns ``(out, y)``: y, the scan's output before the
gate, is the memory a later layer's ``GatedMemoryUnit`` reads,

    out = (SiLU(x W_1) * y) W_2

token for token. The state is float32 and lives inside the call: there
is no cache, so no decode path (``cache=`` is refused).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tensorlink_tpu.nn.kda import Leaf, _dt_bias, causal_conv
from tensorlink_tpu.nn.layers import Dense, _lecun_normal
from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.ops.selective_scan import selective_scan
from tensorlink_tpu.runtime.tracing import scope


def _a_log(key, shape):
    """log of 1..N in every channel (S4D-real)."""
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape
    ))


class MambaMixer(Module):
    def __init__(
        self,
        dim: int,
        d_state: int = 16,
        d_conv: int = 4,
        expand: int = 2,
        dt_rank: int | None = None,
    ):
        super().__init__()
        self.dim, self.d_state, self.d_conv = dim, d_state, d_conv
        self.d_inner = inner = expand * dim
        self.dt_rank = rank = dt_rank or math.ceil(dim / 16)
        self.child("in_proj", Dense(dim, 2 * inner, use_bias=False, shard="col"))
        self.child("conv", Leaf("w", (d_conv, inner), _lecun_normal))
        self.child("conv_bias", Leaf("b", (inner,), lambda k, s: jnp.zeros(s)))
        self.child("x_proj", Dense(inner, rank + 2 * d_state, use_bias=False))
        self.child("dt_proj", Dense(rank, inner, use_bias=False))
        self.child("dt_bias", Leaf("b", (inner,), _dt_bias))
        self.child("A_log", Leaf("b", (inner, d_state), _a_log))
        self.child("D", Leaf("scale", (inner,), lambda k, s: jnp.ones(s)))
        self.child("out_proj", Dense(inner, dim, use_bias=False, shard="row"))

    def apply(self, params, x, *, cache=None, **_):
        """-> (out [B,T,dim], y [B,T,d_inner]): the mixer's output and
        the scan's output before the gate."""
        if cache is not None:
            raise NotImplementedError(
                "MambaMixer keeps its state inside the call: no pool or "
                "wire format holds a recurrent state yet"
            )
        f32 = jnp.float32
        inner, N, R = self.d_inner, self.d_state, self.dt_rank

        def dense(n, h):
            return self.children[n].apply(params[n], h)

        uz = dense("in_proj", x)
        u, z = uz[..., :inner], uz[..., inner:]
        u = jax.nn.silu(
            causal_conv(u, params["conv"]["w"])
            + params["conv_bias"]["b"].astype(u.dtype)
        )
        dbc = dense("x_proj", u)
        delta = jax.nn.softplus(
            dense("dt_proj", dbc[..., :R]).astype(f32)
            + params["dt_bias"]["b"].astype(f32)
        )
        A = -jnp.exp(params["A_log"]["b"].astype(f32))
        with scope("mamba.scan"):
            y = selective_scan(
                u, delta, A, dbc[..., R:R + N], dbc[..., R + N:],
                params["D"]["scale"],
            )
        return dense("out_proj", y * jax.nn.silu(z)), y


class GatedMemoryUnit(Module):
    """``(SiLU(x W_1) * memory) W_2``: the memory is another layer's,
    the gate this layer's own."""

    def __init__(self, dim: int, d_mem: int):
        super().__init__()
        self.dim, self.d_mem = dim, d_mem
        self.child("in_proj", Dense(dim, d_mem, use_bias=False, shard="col"))
        self.child("out_proj", Dense(d_mem, dim, use_bias=False, shard="row"))

    def apply(self, params, x, memory, **_):
        ch = self.children
        gate = jax.nn.silu(ch["in_proj"].apply(params["in_proj"], x))
        return ch["out_proj"].apply(
            params["out_proj"], gate * memory.astype(gate.dtype)
        )
