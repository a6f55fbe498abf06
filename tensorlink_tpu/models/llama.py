"""Llama 2/3 family, TPU-native (BASELINE.json config[4]: Llama-3-8B
sharded inference).

RMSNorm + RoPE + grouped-query attention + SwiGLU, no biases, untied LM
head — built from the framework's own blocks so TP `PartitionSpec`s
(Megatron col/row splits per block) and pipeline slicing apply unchanged.
Weights import from HF `LlamaForCausalLM` checkpoints via
models/hf_import.py; the reference would have shipped the whole module as
a pickle (src/p2p/torch_node.py:159-162).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.nn.layers import Dense, Embedding, RMSNorm
from tensorlink_tpu.nn.transformer import TransformerBlock, TransformerStack
from tensorlink_tpu.runtime.tracing import scope


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    hidden_dim: int = 14336
    max_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    attn_impl: str = "auto"  # auto | flash | reference | ring (seq-parallel)
    # Mixture-of-Experts FFN (Mixtral-style): 0 = dense. Experts shard
    # over the mesh 'model' axis (nn/moe.py — expert parallelism as
    # tensor sharding; see that module for the measured collective set).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # sliding-window attention (Mistral-style): each token attends the
    # last `attn_window` positions only. Supported by the reference impl
    # and the Pallas flash kernel (in-kernel band mask + whole-block
    # skip: O(T*window) long-seq cost); ring/ulysses reject it loudly.
    # None = full causal attention.
    attn_window: int | None = None
    # fused q/k/v projection (nn/attention.py qkv_fused): decode-perf
    # option; per-kv-group layout keeps TP head-aligned
    qkv_fused: bool = False

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def mistral_7b(cls) -> "LlamaConfig":
        """Mistral-7B-v0.1 shape: Llama trunk + 4096-token sliding
        window (the architecture's distinguishing feature)."""
        return cls(vocab_size=32000, dim=4096, num_layers=32,
                   num_heads=32, num_kv_heads=8, hidden_dim=14336,
                   max_len=32768, rope_theta=10000.0,
                   attn_window=4096)

    @classmethod
    def mixtral_8x7b(cls) -> "LlamaConfig":
        """Mixtral-8x7B shape: Llama-2-ish trunk, 8 experts, top-2."""
        return cls(vocab_size=32000, dim=4096, num_layers=32, num_heads=32,
                   num_kv_heads=8, hidden_dim=14336, max_len=32768,
                   rope_theta=1e6, moe_experts=8, moe_top_k=2)

    @classmethod
    def mistral_tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=128, dim=32, num_layers=2, num_heads=4,
                   num_kv_heads=2, hidden_dim=64, max_len=64,
                   rope_theta=10000.0, attn_window=8)

    @classmethod
    def moe_tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=128, dim=32, num_layers=2, num_heads=4,
                   num_kv_heads=2, hidden_dim=64, max_len=64,
                   rope_theta=10000.0, moe_experts=4, moe_top_k=2)

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(dim=8192, num_layers=80, num_heads=64, num_kv_heads=8,
                   hidden_dim=28672)

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls(vocab_size=32000, dim=4096, num_layers=32, num_heads=32,
                   num_kv_heads=32, hidden_dim=11008, max_len=4096,
                   rope_theta=10000.0, rms_eps=1e-5)

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=128, dim=32, num_layers=2, num_heads=4,
                   num_kv_heads=2, hidden_dim=64, max_len=64,
                   rope_theta=10000.0)


class Llama(Module):
    def __init__(self, cfg: LlamaConfig = LlamaConfig()):
        super().__init__()
        self.cfg_obj = cfg
        self.child("tok_emb", Embedding(cfg.vocab_size, cfg.dim))
        self.child(
            "blocks",
            TransformerStack(
                cfg.num_layers,
                TransformerBlock,
                dim=cfg.dim,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                hidden_dim=cfg.hidden_dim,
                norm_style="pre",
                norm="rms",
                norm_eps=cfg.rms_eps,
                activation="silu",
                use_bias=False,
                gated_mlp=True,
                causal=True,
                rope=True,
                rope_theta=cfg.rope_theta,
                dropout=0.0,
                attn_impl=cfg.attn_impl,
                moe_experts=cfg.moe_experts,
                moe_top_k=cfg.moe_top_k,
                moe_capacity_factor=cfg.moe_capacity_factor,
                attn_window=cfg.attn_window,
                qkv_fused=cfg.qkv_fused,
            ),
        )
        self.child("norm_f", RMSNorm(cfg.dim, eps=cfg.rms_eps))
        self.child("lm_head", Dense(cfg.dim, cfg.vocab_size, use_bias=False, shard="col"))

    def _embed(self, emb_params, input_ids):
        with scope("embed"):
            return self.children["tok_emb"].apply(emb_params, input_ids)

    def _head(self, norm_params, head_params, x, logits: bool = True):
        with scope("head"):
            x = self.children["norm_f"].apply(norm_params, x)
            if not logits:
                return x
            return self.children["lm_head"].apply(head_params, x)

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
        x = self._embed(params["tok_emb"], input_ids)
        blocks = self.children["blocks"]
        if caches is not None:
            attn_caches = [c["attn"] for c in caches]
            x, new_attn = blocks.apply(
                params["blocks"], x, mask=mask, caches=attn_caches,
                positions=positions, rng=rng, train=train,
            )
            new_caches = [{"attn": c} for c in new_attn]
        else:
            new_caches = None
            x = blocks.apply(
                params["blocks"], x, mask=mask, positions=positions,
                rng=rng, train=train,
            )
        out = self._head(params["norm_f"], params["lm_head"], x, logits)
        if caches is not None:
            return out, new_caches
        return out

    def apply_with_aux(
        self, params, input_ids, *, positions=None, mask=None, rng=None,
        train=False, **_,
    ):
        """-> (logits, aux): the summed MoE router load-balancing loss
        across blocks (0.0 for dense configs). Mixtral-style training
        adds ``aux_weight * aux`` to the task loss."""
        x = self._embed(params["tok_emb"], input_ids)
        x, aux = self.children["blocks"].apply_with_aux(
            params["blocks"], x, mask=mask, positions=positions,
            rng=rng, train=train,
        )
        return self._head(params["norm_f"], params["lm_head"], x), aux

    def as_pipeline_parts(self, params):
        from tensorlink_tpu.parallel.engine import PipelineParts

        stack = self.children["blocks"]
        block = stack.blocks()[0]

        def embed_fn(emb_params, batch, rng=None):
            return self._embed(emb_params["tok_emb"], batch["input_ids"])

        def head_fn(all_params, x, batch, rng=None):
            head = all_params["head"]
            return self._head(head["norm_f"], head["lm_head"], x)

        return PipelineParts(
            embed_fn=embed_fn,
            block=block,
            block_params=params["blocks"],
            block_fn=lambda bp, x, rng=None: block.apply(
                bp, x, rng=rng, train=rng is not None
            ),
            head_fn=head_fn,
            embed_params={"tok_emb": params["tok_emb"]},
            head_params={"norm_f": params["norm_f"], "lm_head": params["lm_head"]},
            # MoE configs: the router's load-balancing loss rides the
            # pipeline when TrainConfig.moe_aux_weight > 0 (both schedules)
            block_fn_aux=(
                (lambda bp, x, rng=None: block.apply_with_aux(
                    bp, x, rng=rng, train=rng is not None))
                if self.cfg_obj.moe_experts else None
            ),
            # norm_f + lm_head CE reduces uniformly over tokens (1F1B can
            # run the head per token shard under seq sharding)
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
