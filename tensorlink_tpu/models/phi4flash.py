"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning,
``model_type`` ``phi4flash``): the SambaY decoder-hybrid-decoder (Ren et
al. 2025, arXiv:2507.06607) with differential attention (Ye et al. 2024,
arXiv:2410.05258). A decoder whose layers are not alike and whose later
layers read what earlier ones computed. Layer i of L (0-based, as the
published code counts), every one ``x += mixer(LN(x)); x += FFN(LN(x))``
with a SwiGLU feed-forward, mixer by kind:

    i even, i <= L/2        ``mamba``   Mamba-1 (``nn/mamba.py``); layer
                                        L/2's scan output is the memory M
    i odd,  i <  L/2        ``window``  differential attention over a
                                        causal band of ``sliding_window``
    i = L/2 + 1             ``full``    the same over every earlier key;
                                        its k and v are the shared K, V
    i even, i >= L/2 + 2    ``gmu``     gated memory unit on M
    i odd,  i >= L/2 + 3    ``cross``   differential attention with a
                                        query of its own on K, V

(0 .. L/2 + 1 is the self-decoder, the rest the cross-decoder.)
LayerNorm with gain and bias, bias on the attention projections, the
convolution and the step and nowhere else, head tied to the embedding,
no positional encoding of any kind (the recurrence and the convolutions
carry order).

One pipeline stage's share is a config like any other: ``layers`` names
the published indices held here (each block keeps its published index:
the kind and ``lambda_init`` follow from it), ``vocab_size`` the slice of
the vocabulary held here. M and K, V go from block to block as values,
beside x, so under ``remat`` they are saved as block inputs and the
scan's output and chunk states by name (``ops/selective_scan.py::KEPT``).

Trained through ``Trainer`` like every model here. Not served: a
recurrent state beside a windowed cache, and one layer's cache read by
seven, have no place in the KV pools or the wire format, so
``apply(..., cache=...)`` refuses.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from tensorlink_tpu.nn.diff_attention import DifferentialAttention
from tensorlink_tpu.nn.layers import Embedding, LayerNorm
from tensorlink_tpu.nn.mamba import GatedMemoryUnit, MambaMixer
from tensorlink_tpu.nn.module import Module, Sequential
from tensorlink_tpu.nn.transformer import FeedForward
from tensorlink_tpu.ops.selective_scan import KEPT
from tensorlink_tpu.runtime.tracing import scope


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    dim: int = 2560
    num_layers: int = 32  # L, the published depth: it sets the pattern
    # the published indices of the layers held here; None: all L
    layers: tuple[int, ...] | None = None
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    hidden_dim: int = 10240
    sliding_window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    norm_eps: float = 1e-5
    # recompute each block in the backward pass instead of keeping its
    # activations, but for a Mamba block's scan output and chunk states
    remat: bool = False

    @classmethod
    def phi4_mini_flash(cls) -> "Phi4FlashConfig":
        return cls()

    @classmethod
    def phi4_mini_flash_l6(cls) -> "Phi4FlashConfig":
        """One pipeline stage's six layers (a period of the self-decoder,
        the period that ends it and gives M and K, V, a period of the
        cross-decoder) and an eighth of the vocabulary."""
        return cls(vocab_size=25008, layers=(0, 1, 16, 17, 18, 19), remat=True)

    @classmethod
    def tiny(cls) -> "Phi4FlashConfig":
        return cls(
            vocab_size=128, dim=32, layers=(0, 1, 16, 17, 18, 19),
            num_heads=4, num_kv_heads=2, head_dim=8, hidden_dim=64,
            sliding_window=16, d_state=4, dt_rank=4,
        )

    def held_layers(self) -> tuple[int, ...]:
        return tuple(range(self.num_layers)) if self.layers is None else self.layers


def layer_kind(index: int, num_layers: int) -> str:
    half = num_layers // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "gmu"
    if index < half:
        return "window"
    return "full" if index == half + 1 else "cross"


class Phi4FlashBlock(Module):
    """One layer. Each half runs under the scope of what it is
    (``tl.mamba``, ``tl.gmu`` or ``tl.attn``; ``tl.mlp``), so every
    instruction of a block reads exactly one of them, or a scope nested
    inside it. ``apply`` takes and returns ``(x, memory, kv)``: a block
    hands on what it was given unless it is the one that gives it."""

    def __init__(self, cfg: Phi4FlashConfig, index: int):
        super().__init__()
        half = cfg.num_layers // 2
        self.index, self.kind = index, layer_kind(index, cfg.num_layers)
        # the three attention kinds are one half to a reader: tl.attn
        self.scope = self.kind if self.kind in ("mamba", "gmu") else "attn"
        self.gives_memory = index == half
        self.gives_kv = index == half + 1
        self.child("norm1", LayerNorm(cfg.dim, eps=cfg.norm_eps))
        if self.kind == "mamba":
            mixer = MambaMixer(
                cfg.dim, cfg.d_state, cfg.d_conv, cfg.expand, cfg.dt_rank
            )
        elif self.kind == "gmu":
            mixer = GatedMemoryUnit(cfg.dim, cfg.expand * cfg.dim)
        else:
            mixer = DifferentialAttention(
                cfg.dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, index,
                window=cfg.sliding_window if self.kind == "window" else None,
                cross=self.kind == "cross", norm_eps=cfg.norm_eps,
            )
        self.child("mixer", mixer)
        self.child("norm2", LayerNorm(cfg.dim, eps=cfg.norm_eps))
        self.child("mlp", FeedForward(
            cfg.dim, cfg.hidden_dim, activation="silu", use_bias=False,
            gated=True,
        ))

    def apply(self, params, x, memory=None, kv=None, **_):
        ch = self.children
        mixer, p = ch["mixer"], params["mixer"]
        with scope(self.scope):
            h = ch["norm1"].apply(params["norm1"], x)
            if self.kind == "mamba":
                out, y = mixer.apply(p, h)
                memory = y if self.gives_memory else memory
            elif self.kind == "gmu":
                out = mixer.apply(p, h, memory)
            else:
                out, own = mixer.apply(
                    p, h, kv=kv if self.kind == "cross" else None
                )
                kv = own if self.gives_kv else kv
            x = x + out
        with scope("mlp"):
            h = ch["norm2"].apply(params["norm2"], x)
            x = x + ch["mlp"].apply(params["mlp"], h)
        return x, memory, kv


class Phi4Flash(Module):
    def __init__(self, cfg: Phi4FlashConfig = Phi4FlashConfig()):
        super().__init__()
        self.cfg_obj = cfg
        held, half = cfg.held_layers(), cfg.num_layers // 2
        kinds = {layer_kind(i, cfg.num_layers) for i in held}
        if "gmu" in kinds and half not in held:
            raise ValueError(f"a gated memory unit reads layer {half}'s scan")
        if "cross" in kinds and half + 1 not in held:
            raise ValueError(f"a cross layer reads layer {half + 1}'s k and v")
        if list(held) != sorted(set(held)):
            raise ValueError("layers are held once each, in their order")
        self.child("tok_emb", Embedding(cfg.vocab_size, cfg.dim))
        # by position from 0 (``blocks/<j>``); no two need be alike, so
        # there is nothing to stack or scan over
        self.child("blocks", Sequential(
            [Phi4FlashBlock(cfg, i) for i in held]
        ))
        self.child("norm_f", LayerNorm(cfg.dim, eps=cfg.norm_eps))

    def apply(self, params, input_ids, *, cache=None, caches=None,
              logits: bool = True, **_):
        if cache is not None or caches is not None:
            raise NotImplementedError(
                "Phi4Flash is trained and scored over whole sequences: a "
                "recurrent (Mamba) state beside a windowed cache, and one "
                "layer's keys and values read by every cross layer, have "
                "no place in kvpool.py's pools or kvwire.py's format yet"
            )
        ch = self.children
        with scope("embed"):
            x = ch["tok_emb"].apply(params["tok_emb"], input_ids)
        memory = kv = None
        for name, block in ch["blocks"].children.items():
            run = block.apply
            if self.cfg_obj.remat:
                run = jax.checkpoint(
                    run,
                    policy=jax.checkpoint_policies.save_only_these_names(*KEPT),
                )
            x, memory, kv = run(params["blocks"][name], x, memory, kv)
        with scope("head"):
            x = ch["norm_f"].apply(params["norm_f"], x)
            if not logits:
                return x
            return ch["tok_emb"].attend(params["tok_emb"], x)
