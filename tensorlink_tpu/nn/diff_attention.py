"""Differential attention (Ye et al. 2024, "Differential Transformer",
arXiv:2410.05258) as Phi-4-mini-flash-reasoning's attention layers use
it: heads pair up, each pair takes two softmax maps over the pair's
shared values and subtracts one from the other,

    q, k, v = x W_q + b_q, x W_k + b_k, x W_v + b_v
                                 H / Hkv / Hkv heads of d; pair j = (2j, 2j+1)
    a1 = softmax(q1 k1^T / sqrt(d) + mask) [v1 | v2]
    a2 = softmax(q2 k2^T / sqrt(d) + mask) [v1 | v2]      (H/2 heads of 2d)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i)         i: the layer's published index
    out = reshape(RMSNorm_2d(a1 - lambda a2) * (1 - lambda_init)) W_o + b_o

so a layer is two calls of ``ops/flash.py::flash_attention_impl`` at q,
k ``d`` wide and v ``2d`` wide, H/2 query heads on Hkv/2 key heads,
causal, over a band of ``window`` keys or all of them. A layer with
``cross=True`` has no k and v of its own (it projects q alone) and
reads those another layer projected: ``apply`` takes them as ``kv``
and every layer returns the pair it used, ``(out, (k, v))`` with k and
v [B, T, Hkv, d] as projected. lambda and the softmax statistics are
float32. The published checkpoint fuses the three projections into one
``Wqkv``; here each is a matrix and a bias of its own, as in
``MultiHeadAttention``, which is a relabelling of columns. Training and
whole-sequence scoring only (``cache=`` is refused).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tensorlink_tpu.nn.kda import Leaf
from tensorlink_tpu.nn.layers import Dense, RMSNorm
from tensorlink_tpu.nn.module import Module


def _lambda_vector(key, shape):
    return 0.1 * jax.random.normal(key, shape)


def lambda_init(layer_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


class DifferentialAttention(Module):
    def __init__(
        self,
        dim: int,
        num_heads: int,
        num_kv_heads: int,
        head_dim: int,
        layer_index: int,
        window: int | None = None,
        cross: bool = False,
        norm_eps: float = 1e-5,
    ):
        super().__init__()
        if num_heads % 2 or num_kv_heads % 2:
            raise ValueError("differential attention pairs its heads up")
        self.dim, self.head_dim = dim, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.layer_index, self.window, self.cross = layer_index, window, cross
        self.child("q", Dense(dim, num_heads * head_dim, shard="col"))
        if not cross:
            for n in ("k", "v"):
                self.child(n, Dense(dim, num_kv_heads * head_dim, shard="col"))
        for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            self.child(n, Leaf("b", (head_dim,), _lambda_vector))
        self.child("subln", RMSNorm(2 * head_dim, eps=norm_eps))
        self.child("o", Dense(num_heads * head_dim, dim, shard="row"))

    def apply(self, params, x, *, kv=None, cache=None, **_):
        if cache is not None:
            raise NotImplementedError(
                "DifferentialAttention has no cache: no pool holds a pair "
                "of key heads over shared values, or one layer's keys for "
                "seven"
            )
        if self.cross != (kv is not None):
            raise ValueError("a cross layer, and only it, is given k and v")
        # function-level: ops/flash.py imports nn/attention.py
        from tensorlink_tpu.ops.flash import flash_attention_impl

        B, T, _ = x.shape
        H, Hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        ch, f32 = self.children, jnp.float32

        def dense(n):
            return ch[n].apply(params[n], x)

        q = dense("q").reshape(B, T, H // 2, 2, d)
        k, v = kv or (dense(n).reshape(B, T, Hkv, d) for n in ("k", "v"))
        k2 = k.reshape(B, T, Hkv // 2, 2, d)
        vv = v.reshape(B, T, Hkv // 2, 2 * d)
        a1, a2 = (
            flash_attention_impl(
                q[:, :, :, i], k2[:, :, :, i], vv, causal=True,
                window=self.window,
            ) for i in (0, 1)
        )

        def dot(a, b):
            return jnp.sum(params[a]["b"].astype(f32) * params[b]["b"].astype(f32))

        init = lambda_init(self.layer_index)
        lam = (
            jnp.exp(dot("lambda_q1", "lambda_k1"))
            - jnp.exp(dot("lambda_q2", "lambda_k2")) + init
        )
        o = ch["subln"].apply(
            params["subln"], a1.astype(f32) - lam * a2.astype(f32)
        ) * (1.0 - init)
        out = ch["o"].apply(params["o"], o.astype(x.dtype).reshape(B, T, H * d))
        return out, (k, v)
