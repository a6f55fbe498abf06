"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B; Kimi Team 2025): a
decoder whose layers are not alike. Per layer (1-based, as the published
config counts) the mixer is Kimi Delta Attention (``nn/kda.py``, a
gated delta-rule recurrence) or MLA without rotary (``nn/mla.py``), 3 : 1;
the feed-forward is a dense SwiGLU in the leading layers and then the
sigmoid-routed expert layer with a shared expert
(``nn/moe.py::HeldExpertsMoE``). Pre-norm, RMSNorm, no bias anywhere,
untied head, no positional encoding of any kind (the recurrence and the
convolutions carry order).

One chip's share of an expert-parallel deployment is a config like any
other: ``held_experts = (first, count)`` of ``num_experts`` (the router
keeps its width), ``vocab_size`` the slice of the vocabulary held here.

Trained through ``Trainer`` like every model here. Not served: neither
the KDA state nor MLA's latent has a place in the KV pools or the wire
format, so ``apply(..., cache=...)`` refuses.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from tensorlink_tpu.nn.kda import KimiDeltaAttention
from tensorlink_tpu.nn.layers import Dense, Embedding, RMSNorm
from tensorlink_tpu.nn.mla import LatentAttention
from tensorlink_tpu.nn.moe import HeldExpertsMoE
from tensorlink_tpu.nn.module import Module, Sequential
from tensorlink_tpu.nn.transformer import FeedForward
from tensorlink_tpu.ops.kda import KEPT
from tensorlink_tpu.runtime.tracing import scope


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    dim: int = 2304
    num_layers: int = 27
    # which layers (1-based) carry which mixer
    kda_layers: tuple[int, ...] = (
        1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
        25, 26,
    )
    full_attn_layers: tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    mla_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    hidden_dim: int = 9216  # the dense layers' feed-forward
    first_dense_layers: int = 1
    moe_hidden_dim: int = 1024
    num_experts: int = 256
    experts_per_token: int = 8
    shared_experts: int = 1
    routed_scale: float = 2.446
    renormalize: bool = True
    # the experts that live here, (first, count); None: all of them
    held_experts: tuple[int, int] | None = None
    # rows the expert layers' sorted dispatch is built for (None: every
    # route there could be); see HeldExpertsMoE
    moe_row_bound: int | None = None
    rms_eps: float = 1e-5
    # recompute each block in the backward pass instead of keeping its
    # activations, but for a KDA block's scan output (ops/kda.py::KEPT,
    # float32 [B,T,H,d]): the recompute then holds no pass of the scan
    remat: bool = False

    @classmethod
    def kimi_linear_48b(cls) -> "KimiLinearConfig":
        return cls()

    @classmethod
    def kimi_linear_l5e8(cls) -> "KimiLinearConfig":
        """One chip's share of the first five layers when 32 chips share
        each layer: experts 0-7 of 256, an eighth of the vocabulary."""
        return cls(
            vocab_size=20480, num_layers=5, kda_layers=(1, 2, 3, 5),
            full_attn_layers=(4,), held_experts=(0, 8), remat=True,
        )

    @classmethod
    def tiny(cls) -> "KimiLinearConfig":
        return cls(
            vocab_size=128, dim=32, num_layers=5, kda_layers=(1, 2, 3, 5),
            full_attn_layers=(4,), kda_heads=2, kda_head_dim=16,
            mla_heads=2, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            kv_lora_rank=24, hidden_dim=64, moe_hidden_dim=16,
            num_experts=16, experts_per_token=4, held_experts=(4, 4),
        )


class KimiBlock(Module):
    """One layer: ``x += mixer(norm(x)); x += ffn(norm(x))``. Each half
    runs under the scope of what it is (``tl.kda`` or ``tl.mla``;
    ``tl.mlp`` or ``tl.moe``), so every instruction of a block reads
    exactly one of them, or a scope nested inside it."""

    def __init__(self, cfg: KimiLinearConfig, layer: int):
        super().__init__()
        if (layer in cfg.kda_layers) == (layer in cfg.full_attn_layers):
            raise ValueError(f"layer {layer} needs exactly one mixer")
        self.layer = layer
        self.mixer_kind = "kda" if layer in cfg.kda_layers else "mla"
        self.ffn_kind = "mlp" if layer <= cfg.first_dense_layers else "moe"
        self.child("norm1", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        if self.mixer_kind == "kda":
            self.child("mixer", KimiDeltaAttention(
                cfg.dim, cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel,
                norm_eps=cfg.rms_eps,
            ))
        else:
            self.child("mixer", LatentAttention(
                cfg.dim, cfg.mla_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                cfg.v_head_dim, cfg.kv_lora_rank, norm_eps=cfg.rms_eps,
            ))
        self.child("norm2", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        if self.ffn_kind == "mlp":
            self.child("mlp", FeedForward(
                cfg.dim, cfg.hidden_dim, activation="silu", use_bias=False,
                gated=True,
            ))
        else:
            self.child("mlp", HeldExpertsMoE(
                cfg.dim, cfg.moe_hidden_dim, cfg.num_experts,
                cfg.experts_per_token, held=cfg.held_experts,
                shared_experts=cfg.shared_experts,
                routed_scale=cfg.routed_scale, renormalize=cfg.renormalize,
                row_bound=cfg.moe_row_bound,
            ))

    def apply(self, params, x, **_):
        ch = self.children
        with scope(self.mixer_kind):
            h = ch["norm1"].apply(params["norm1"], x)
            x = x + ch["mixer"].apply(params["mixer"], h)
        with scope(self.ffn_kind):
            h = ch["norm2"].apply(params["norm2"], x)
            x = x + ch["mlp"].apply(params["mlp"], h)
        return x


class KimiLinear(Module):
    def __init__(self, cfg: KimiLinearConfig = KimiLinearConfig()):
        super().__init__()
        self.cfg_obj = cfg
        self.child("tok_emb", Embedding(cfg.vocab_size, cfg.dim))
        # by index from 0 (``blocks/<i>``); no two need be alike, so
        # there is nothing to stack or scan over
        self.child("blocks", Sequential(
            [KimiBlock(cfg, i + 1) for i in range(cfg.num_layers)]
        ))
        self.child("norm_f", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        self.child("lm_head", Dense(cfg.dim, cfg.vocab_size, use_bias=False,
                                    shard="col"))

    def apply(self, params, input_ids, *, cache=None, caches=None,
              logits: bool = True, **_):
        if cache is not None or caches is not None:
            raise NotImplementedError(
                "KimiLinear is trained and scored over whole sequences: "
                "its recurrent (KDA) and latent (MLA) states have no place "
                "in kvpool.py's pools or kvwire.py's format yet"
            )
        ch = self.children
        with scope("embed"):
            x = ch["tok_emb"].apply(params["tok_emb"], input_ids)
        for name, block in ch["blocks"].children.items():
            run = block.apply
            if self.cfg_obj.remat:
                run = jax.checkpoint(
                    run,
                    policy=jax.checkpoint_policies.save_only_these_names(KEPT),
                )
            x = run(params["blocks"][name], x)
        with scope("head"):
            x = ch["norm_f"].apply(params["norm_f"], x)
            if not logits:
                return x
            return ch["lm_head"].apply(params["lm_head"], x)
