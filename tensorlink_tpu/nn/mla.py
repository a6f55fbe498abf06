"""Multi-head latent attention without rotary (MLA, DeepSeek-V2; the
``mla_use_nope`` form of the Kimi-Linear models): keys and values come
out of one low-rank latent, and every head's key carries ``rope_dim``
channels that all heads share (they would carry the rotary phase; here
they carry none):

    q = x W_q                                   [H, nope + rope]
    [c ; k_pe] = x W_kva                        [rank + rope]
    [k_nope ; v] = RMSNorm(c) W_kvb             [H, nope + v_dim]
    k_h = [k_nope_h ; k_pe]
    y = softmax_causal(q k^T / sqrt(nope + rope)) v W_o

q and k are ``nope + rope`` wide and v ``v_dim``: the flash kernels take
the two widths as they come (``ops/flash.py``), so no [T, T] scores
reach HBM on the kernel path. Training and whole-sequence scoring only:
the latent is not cached (``cache=`` is refused).
"""

from __future__ import annotations

import jax.numpy as jnp

from tensorlink_tpu.nn.layers import Dense, RMSNorm
from tensorlink_tpu.nn.module import Module


class LatentAttention(Module):
    def __init__(
        self,
        dim: int,
        num_heads: int,
        nope_dim: int = 128,
        rope_dim: int = 64,
        v_dim: int = 128,
        kv_rank: int = 512,
        norm_eps: float = 1e-5,
    ):
        super().__init__()
        self.dim, self.num_heads = dim, num_heads
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.kv_rank, self.norm_eps = kv_rank, norm_eps
        H = num_heads
        self.child("q", Dense(dim, H * (nope_dim + rope_dim), use_bias=False,
                              shard="col"))
        self.child("kv_a", Dense(dim, kv_rank + rope_dim, use_bias=False))
        self.child("kv_norm", RMSNorm(kv_rank, eps=norm_eps))
        self.child("kv_b", Dense(kv_rank, H * (nope_dim + v_dim),
                                 use_bias=False, shard="col"))
        self.child("o", Dense(H * v_dim, dim, use_bias=False, shard="row"))

    def apply(self, params, x, *, cache=None, **_):
        if cache is not None:
            raise NotImplementedError(
                "LatentAttention has no cache: the pools and the wire "
                "format hold per-head keys and values, not a latent"
            )
        # function-level: ops/flash.py imports nn/attention.py
        from tensorlink_tpu.ops.flash import flash_attention_impl

        B, T, _ = x.shape
        H, nope, rope = self.num_heads, self.nope_dim, self.rope_dim
        ch = self.children
        q = ch["q"].apply(params["q"], x).reshape(B, T, H, nope + rope)
        kv = ch["kv_a"].apply(params["kv_a"], x)
        c = ch["kv_norm"].apply(params["kv_norm"], kv[..., :self.kv_rank])
        k_pe = kv[..., self.kv_rank:]
        kv = ch["kv_b"].apply(params["kv_b"], c).reshape(
            B, T, H, nope + self.v_dim)
        k = jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(k_pe[:, :, None], (B, T, H, rope)),
        ], -1)
        o = flash_attention_impl(q, k, kv[..., nope:], causal=True)
        return ch["o"].apply(params["o"], o.reshape(B, T, H * self.v_dim))
