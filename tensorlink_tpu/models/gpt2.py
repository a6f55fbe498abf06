"""GPT-2, TPU-native (BASELINE.json config[2]: GPT-2-medium 8PP x 2DP)."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.nn.layers import Dropout, Embedding, LayerNorm
from tensorlink_tpu.nn.transformer import TransformerBlock, TransformerStack
from tensorlink_tpu.runtime.tracing import scope


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_len: int = 1024
    dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    attn_impl: str = "auto"  # auto | flash | reference | ring (seq-parallel)
    # fused q/k/v projection: one matmul per layer instead of three —
    # measured decode win at small batch (nn/attention.py qkv_fused)
    qkv_fused: bool = False

    @classmethod
    def small(cls) -> "GPT2Config":
        return cls()

    @classmethod
    def medium(cls) -> "GPT2Config":
        return cls(dim=1024, num_layers=24, num_heads=16)

    @classmethod
    def tiny(cls) -> "GPT2Config":
        return cls(vocab_size=128, dim=32, num_layers=2, num_heads=2, max_len=64)


class GPT2(Module):
    """Pre-LN decoder with learned positions and tied LM head."""

    def __init__(self, cfg: GPT2Config = GPT2Config()):
        super().__init__()
        self.cfg_obj = cfg
        self.child("wte", Embedding(cfg.vocab_size, cfg.dim))
        self.child("wpe", Embedding(cfg.max_len, cfg.dim))
        self.child("drop", Dropout(cfg.dropout))
        self.child(
            "blocks",
            TransformerStack(
                cfg.num_layers,
                TransformerBlock,
                dim=cfg.dim,
                num_heads=cfg.num_heads,
                hidden_dim=4 * cfg.dim,
                norm_style="pre",
                norm="layer",
                norm_eps=cfg.layer_norm_eps,
                activation="gelu",  # gelu_new (tanh approx)
                use_bias=True,
                causal=True,
                dropout=cfg.dropout,
                attn_impl=cfg.attn_impl,
                qkv_fused=cfg.qkv_fused,
            ),
        )
        self.child("ln_f", LayerNorm(cfg.dim, eps=cfg.layer_norm_eps))

    def apply(
        self,
        params,
        input_ids,
        *,
        caches=None,
        positions=None,
        mask=None,
        rng=None,
        train=False,
        logits: bool = True,
        **_,
    ):
        B, T = input_ids.shape
        if positions is None:
            if caches is not None:
                idx = caches[0]["attn"]["index"]
                if getattr(idx, "ndim", 0) == 1:
                    # per-row serving index ([B]): each row sits at its
                    # own position (bare [B] + [1,T] would broadcast to
                    # a bogus [B,T]-transposed table lookup)
                    idx = idx[:, None]
                positions = idx + jnp.arange(T)[None, :]
            else:
                positions = jnp.arange(T)[None, :]
        r0, r1 = jax.random.split(rng) if rng is not None else (None, None)
        with scope("embed"):
            x = self.children["wte"].apply(params["wte"], input_ids)
            x = x + self.children["wpe"].apply(params["wpe"], positions)
            x = self.children["drop"].apply(params["drop"], x, rng=r0, train=train)

        blocks = self.children["blocks"]
        if caches is not None:
            attn_caches = [c["attn"] for c in caches]
            x, new_attn = blocks.apply(
                params["blocks"], x, mask=mask, caches=attn_caches, rng=r1, train=train
            )
            new_caches = [{"attn": c} for c in new_attn]
        else:
            new_caches = None
            x = blocks.apply(params["blocks"], x, mask=mask, rng=r1, train=train)

        with scope("head"):
            x = self.children["ln_f"].apply(params["ln_f"], x)
            out = self.children["wte"].attend(params["wte"], x) if logits else x
        if caches is not None:
            return out, new_caches
        return out

    def as_pipeline_parts(self, params):
        """Split into (embed, blocks, head) for the ShardedTrainer.
        The LM head stays tied to wte (head_fn sees all params)."""
        from tensorlink_tpu.parallel.engine import PipelineParts

        stack = self.children["blocks"]
        block = stack.blocks()[0]
        wte, wpe = self.children["wte"], self.children["wpe"]
        ln_f = self.children["ln_f"]

        drop = self.children["drop"]

        def embed_fn(emb_params, batch, rng=None):
            ids = batch["input_ids"]
            T = ids.shape[1]
            pos = jnp.arange(T)[None, :]
            with scope("embed"):
                tok = wte.apply(emb_params["wte"], ids)
                x = tok + wpe.apply(emb_params["wpe"], pos).astype(tok.dtype)
                return drop.apply({}, x, rng=rng, train=rng is not None)

        def head_fn(all_params, x, batch, rng=None):
            with scope("head"):
                h = ln_f.apply(all_params["head"]["ln_f"], x)
                return wte.attend(all_params["embed"]["wte"], h)

        return PipelineParts(
            embed_fn=embed_fn,
            block=block,
            block_params=params["blocks"],
            block_fn=lambda bp, x, rng=None: block.apply(
                bp, x, rng=rng, train=rng is not None
            ),
            head_fn=head_fn,
            embed_params={"wte": params["wte"], "wpe": params["wpe"]},
            head_params={"ln_f": params["ln_f"]},
            # ln_f + tied-logits CE is a uniform per-token reduction, so
            # 1F1B may run the head per token shard under seq sharding
            head_per_token=True,
        )

    def init_caches(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                    rolling: bool = False):
        stack = self.children["blocks"]
        return [
            {"attn": blk.children["attn"].init_cache(
                batch, max_len, dtype, rolling=rolling
            )}
            for blk in stack.blocks()
        ]
