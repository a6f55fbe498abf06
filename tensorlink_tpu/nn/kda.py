"""Kimi Delta Attention (KDA; Kimi Team 2025, "Kimi Linear"): the
linear-attention mixer of the Kimi-Linear models. A gated delta rule
whose decay is per channel, behind short causal convolutions, with a
gated per-head norm on the way out:

    q~, k~, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))
    q = q~ / |q~| / sqrt(d_k),   k = k~ / |k~|               (per head)
    g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)      [H, d_k]
    beta = sigmoid(x W_beta)                                 [H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y = (RMSNorm_head(o) * sigmoid((x W_ga) W_gb)) W_o

The recurrence runs in chunks (``ops/kda.py``) under the scope
``tl.kda.scan``; the caller's scope (``tl.kda``) holds the rest. Its
output o carries the name ``ops/kda.py::KEPT``, so a block remat that
saves that name recomputes everything here but the scan. On a TPU the
step's own pass of the scan is the kernel ``tl_kda_fwd``, which reads
q, k, v, g as [B, T, H*d], the shape the projections and convolutions
write: the [B, T, H, d] they are reshaped to here for the per-head norms
is a view the compiler need not lay out. The state is
float32 and lives inside the call: there is no cache, so no decode path
(``cache=`` is refused).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorlink_tpu.nn.layers import Dense, RMSNorm, _lecun_normal
from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.ops.kda import kda_chunked
from tensorlink_tpu.runtime.tracing import scope


class Leaf(Module):
    """One array that is no projection matrix, under the leaf name the
    rest of the tree uses for its kind: ``w`` (taps of a convolution,
    fan-in first) or ``b`` (a vector added or exponentiated)."""

    def __init__(self, name: str, shape: tuple, init):
        super().__init__()
        self.name, self.shape = name, tuple(shape)
        self._initializer = init  # (key, shape) -> array

    def init(self, key):
        make = self._initializer
        return {self.name: make(key, self.shape)}

    def param_spec(self, model_axis: str = "model"):
        return {self.name: P()}


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))


def _dt_bias(key, shape):
    """softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x, taps):
    """Depthwise along time: tap j of ``taps`` [K, C] meets
    x[t - (K - 1) + j]; x [B, T, C]."""
    K, T = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * taps[j].astype(x.dtype) for j in range(K))


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-6)


class KimiDeltaAttention(Module):
    def __init__(
        self,
        dim: int,
        num_heads: int,
        head_dim: int = 128,
        conv_kernel: int = 4,
        norm_eps: float = 1e-5,
    ):
        super().__init__()
        self.dim, self.num_heads, self.head_dim = dim, num_heads, head_dim
        self.conv_kernel, self.norm_eps = conv_kernel, norm_eps
        inner = num_heads * head_dim
        for n in ("q", "k", "v"):
            self.child(n, Dense(dim, inner, use_bias=False, shard="col"))
            self.child(n + "_conv", Leaf("w", (conv_kernel, inner), _lecun_normal))
        # the decay's and the output gate's projections go through a
        # bottleneck one head wide
        self.child("f_a", Dense(dim, head_dim, use_bias=False))
        self.child("f_b", Dense(head_dim, inner, use_bias=False))
        self.child("A_log", Leaf("b", (num_heads,), _a_log))
        self.child("dt_bias", Leaf("b", (inner,), _dt_bias))
        self.child("beta", Dense(dim, num_heads, use_bias=False))
        self.child("g_a", Dense(dim, head_dim, use_bias=False))
        self.child("g_b", Dense(head_dim, inner, use_bias=False))
        self.child("o_norm", RMSNorm(head_dim, eps=norm_eps))
        self.child("o", Dense(inner, dim, use_bias=False, shard="row"))

    def apply(self, params, x, *, cache=None, **_):
        if cache is not None:
            raise NotImplementedError(
                "KimiDeltaAttention keeps its state inside the call: no "
                "pool or wire format holds a recurrent state yet"
            )
        B, T, _ = x.shape
        H, d = self.num_heads, self.head_dim
        f32 = jnp.float32

        def dense(n, h=x):
            return self.children[n].apply(params[n], h)

        def branch(n):
            y = jax.nn.silu(causal_conv(dense(n), params[n + "_conv"]["w"]))
            return y.reshape(B, T, H, d)

        q, k, v = branch("q"), branch("k"), branch("v")
        q = (_l2norm(q) * d ** -0.5).astype(x.dtype)
        k = _l2norm(k).astype(x.dtype)
        f = dense("f_b", dense("f_a")).astype(f32) + params["dt_bias"]["b"].astype(f32)
        g = -jnp.exp(params["A_log"]["b"].astype(f32))[:, None] * jax.nn.softplus(
            f.reshape(B, T, H, d)
        )
        beta = jax.nn.sigmoid(dense("beta").astype(f32))
        with scope("kda.scan"):
            o = kda_chunked(q, k, v, g, beta)
        o = self.children["o_norm"].apply(params["o_norm"], o)
        gate = jax.nn.sigmoid(dense("g_b", dense("g_a")).astype(f32))
        o = (o * gate.reshape(B, T, H, d)).astype(x.dtype)
        return dense("o", o.reshape(B, T, H * d))
