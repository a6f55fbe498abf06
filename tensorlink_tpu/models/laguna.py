"""Laguna (poolside/Laguna-S-2.1, ``model_type`` ``laguna``): a decoder
whose layers differ in their attention and their feed-forward. Layer i
(0-based, as the published config counts), pre-norm,

    h = x + Attn_i(RMSNorm(x));  y = h + FFN_i(RMSNorm(h))

``layer_types[i]`` is ``sliding_attention`` (a causal band of
``sliding_window`` keys, rotary at theta 10,000 over every dimension) or
``full_attention`` (every earlier key, YaRN over the first half of a
head's dimensions); ``num_attention_heads_per_layer[i]`` query heads on
``num_kv_heads`` key heads, each head gated by ``sigmoid(x W_g)`` before
``o`` (``MultiHeadAttention(head_gate=True)``); ``mlp_layer_types[i]``
is ``dense`` (a SwiGLU) or ``sparse`` (``nn/moe.py::HeldExpertsMoE``:
sigmoid router over every expert, top k of score plus selection bias,
renormalised and scaled, one shared expert). RMSNorm, no bias anywhere,
untied head.

One chip's share of an expert-parallel deployment is a config like any
other: ``held_experts = (first, count)`` of ``num_experts`` (the router
keeps its width), ``vocab_size`` the slice of the vocabulary held here.

Scopes: a window layer's attention half is ``tl.swa``, a full layer's
``tl.attn``; the feed-forward half ``tl.mlp`` or ``tl.moe``.

Trained through ``Trainer`` like every model here. Not served: a window
cache beside a full one, at head counts that differ by layer, has no
place in the KV pools or the wire format, so ``apply(..., cache=...)``
refuses.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from tensorlink_tpu.nn.attention import MultiHeadAttention, Rotary, Yarn
from tensorlink_tpu.nn.layers import Dense, Embedding, RMSNorm
from tensorlink_tpu.nn.moe import HeldExpertsMoE
from tensorlink_tpu.nn.module import Module, Sequential
from tensorlink_tpu.nn.transformer import FeedForward
from tensorlink_tpu.runtime.tracing import scope

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
PERIOD = (FULL, WINDOW, WINDOW, WINDOW)


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 3072
    layer_types: tuple[str, ...] = PERIOD * 12
    num_attention_heads_per_layer: tuple[int, ...] = (48, 72, 72, 72) * 12
    mlp_layer_types: tuple[str, ...] = (DENSE,) + (SPARSE,) * 47
    num_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 12288  # the dense layers' feed-forward
    sliding_window: int = 512
    window_rotary: Rotary = Rotary(10000.0)
    full_rotary: Rotary = Rotary(
        500000.0, 0.5, Yarn(128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    )
    moe_hidden_dim: int = 1024  # every routed expert and the shared one
    num_experts: int = 256
    experts_per_token: int = 10
    shared_experts: int = 1
    routed_scale: float = 2.5
    # the experts that live here, (first, count); None: all of them
    held_experts: tuple[int, int] | None = None
    # rows the expert layers' sorted dispatch is built for (None: every
    # route there could be); see HeldExpertsMoE
    moe_row_bound: int | None = None
    rms_eps: float = 1e-6
    # recompute each block in the backward pass instead of keeping its
    # activations
    remat: bool = False

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def laguna_s21(cls) -> "LagunaConfig":
        return cls()

    @classmethod
    def laguna_s21_l5e8(cls) -> "LagunaConfig":
        """One chip's share of the first five layers (the dense layer and
        a whole period: window, window, window, full) when 32 chips share
        each expert layer: experts 0-7 of 256, an eighth of the
        vocabulary."""
        return cls(
            vocab_size=12544, layer_types=(FULL,) + PERIOD[1:] + (FULL,),
            num_attention_heads_per_layer=(48, 72, 72, 72, 48),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 4, held_experts=(0, 8),
            moe_row_bound=10240, remat=True,
        )

    @classmethod
    def tiny(cls) -> "LagunaConfig":
        return cls(
            vocab_size=128, dim=32, layer_types=(FULL,) + PERIOD[1:] + (FULL,),
            num_attention_heads_per_layer=(12, 18, 18, 18, 12),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 4, num_kv_heads=2,
            head_dim=8, hidden_dim=64, sliding_window=8,
            full_rotary=Rotary(500000.0, 0.5, Yarn(16.0, 32, 32.0, 1.0)),
            moe_hidden_dim=16, num_experts=16, experts_per_token=4,
            held_experts=(4, 4),
        )


class LagunaBlock(Module):
    """One layer: ``x += attn(norm(x)); x += ffn(norm(x))``. Each half
    runs under the scope of what it is (``tl.swa`` or ``tl.attn``;
    ``tl.mlp`` or ``tl.moe``), so every instruction of a block reads
    exactly one of them, or a scope nested inside it."""

    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        kind, ffn = cfg.layer_types[layer], cfg.mlp_layer_types[layer]
        if kind not in (WINDOW, FULL) or ffn not in (DENSE, SPARSE):
            raise ValueError(f"layer {layer}: no such kind ({kind}, {ffn})")
        self.layer = layer
        window = kind == WINDOW
        self.attn_scope = "swa" if window else "attn"
        self.ffn_kind = "mlp" if ffn == DENSE else "moe"
        self.child("norm1", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        self.child("attn", MultiHeadAttention(
            cfg.dim, cfg.num_attention_heads_per_layer[layer],
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            use_bias=False, causal=True, attn_impl="flash",
            window=cfg.sliding_window if window else None,
            rotary=cfg.window_rotary if window else cfg.full_rotary,
            head_gate=True,
        ))
        self.child("norm2", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        if ffn == DENSE:
            self.child("mlp", FeedForward(
                cfg.dim, cfg.hidden_dim, activation="silu", use_bias=False,
                gated=True,
            ))
        else:
            self.child("mlp", HeldExpertsMoE(
                cfg.dim, cfg.moe_hidden_dim, cfg.num_experts,
                cfg.experts_per_token, held=cfg.held_experts,
                shared_experts=cfg.shared_experts,
                routed_scale=cfg.routed_scale, renormalize=True,
                row_bound=cfg.moe_row_bound,
            ))

    def apply(self, params, x, **_):
        ch = self.children
        with scope(self.attn_scope):
            h = ch["norm1"].apply(params["norm1"], x)
            x = x + ch["attn"].apply(params["attn"], h)
        with scope(self.ffn_kind):
            h = ch["norm2"].apply(params["norm2"], x)
            x = x + ch["mlp"].apply(params["mlp"], h)
        return x


class Laguna(Module):
    def __init__(self, cfg: LagunaConfig = LagunaConfig()):
        super().__init__()
        n = cfg.num_layers
        if not len(cfg.num_attention_heads_per_layer) == len(
                cfg.mlp_layer_types) == n:
            raise ValueError("one attention kind, head count and ffn a layer")
        self.cfg_obj = cfg
        self.child("tok_emb", Embedding(cfg.vocab_size, cfg.dim))
        # by index from 0 (``blocks/<i>``); no two need be alike, so
        # there is nothing to stack or scan over
        self.child("blocks", Sequential([LagunaBlock(cfg, i) for i in range(n)]))
        self.child("norm_f", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        self.child("lm_head", Dense(cfg.dim, cfg.vocab_size, use_bias=False,
                                    shard="col"))

    def apply(self, params, input_ids, *, cache=None, caches=None,
              logits: bool = True, **_):
        if cache is not None or caches is not None:
            raise NotImplementedError(
                "Laguna is trained and scored over whole sequences: a "
                "window cache beside a full one, at head counts that "
                "differ by layer, has no place in kvpool.py's pools or "
                "kvwire.py's format yet"
            )
        ch = self.children
        with scope("embed"):
            x = ch["tok_emb"].apply(params["tok_emb"], input_ids)
        for name, block in ch["blocks"].children.items():
            run = jax.checkpoint(block.apply) if self.cfg_obj.remat else block.apply
            x = run(params["blocks"][name], x)
        with scope("head"):
            x = ch["norm_f"].apply(params["norm_f"], x)
            if not logits:
                return x
            return ch["lm_head"].apply(params["lm_head"], x)
