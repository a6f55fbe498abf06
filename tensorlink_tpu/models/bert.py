"""BERT, TPU-native.

Replaces the reference's opaque HF ``BertModel`` submodule shipping
(reference workload: tests/ml/test_full_train.py:56-175 fine-tunes
``BertForSequenceClassification``) with a native implementation whose
blocks are the framework's own `TransformerBlock`s — so the pipeline
partitioner, TP specs, and spec-shipping all apply directly. Weights
import from HF checkpoints via models/hf_import.py.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.nn.layers import Dense, Dropout, Embedding, LayerNorm
from tensorlink_tpu.nn.transformer import TransformerBlock, TransformerStack
from tensorlink_tpu.runtime.tracing import scope


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    hidden_dim: int = 3072
    max_len: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    # auto | flash | reference | ring | ulysses — ulysses is the natural
    # seq-parallel fit for BERT (bidirectional + padding masks; the mask
    # ships globally through the engine's extras channel)
    attn_impl: str = "auto"

    @classmethod
    def base(cls) -> "BertConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "BertConfig":
        return cls(vocab_size=128, dim=32, num_layers=2, num_heads=2, hidden_dim=64, max_len=64)


class Bert(Module):
    def __init__(self, cfg: BertConfig = BertConfig()):
        super().__init__()
        self.cfg_obj = cfg
        self.child("tok_emb", Embedding(cfg.vocab_size, cfg.dim))
        self.child("pos_emb", Embedding(cfg.max_len, cfg.dim))
        self.child("type_emb", Embedding(cfg.type_vocab_size, cfg.dim))
        self.child("emb_norm", LayerNorm(cfg.dim, eps=cfg.layer_norm_eps))
        self.child("emb_drop", Dropout(cfg.dropout))
        self.child(
            "encoder",
            TransformerStack(
                cfg.num_layers,
                TransformerBlock,
                dim=cfg.dim,
                num_heads=cfg.num_heads,
                hidden_dim=cfg.hidden_dim,
                norm_style="post",
                norm="layer",
                norm_eps=cfg.layer_norm_eps,
                activation="gelu_exact",
                use_bias=True,
                dropout=cfg.dropout,
                attn_impl=cfg.attn_impl,
            ),
        )
        self.child("pooler", Dense(cfg.dim, cfg.dim))

    def apply(
        self,
        params,
        input_ids,
        *,
        token_type_ids=None,
        attention_mask=None,  # [B, T] 1=real token
        rng=None,
        train=False,
        **_,
    ):
        B, T = input_ids.shape
        pos = jnp.arange(T)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        r0, r1 = jax.random.split(rng) if rng is not None else (None, None)
        with scope("embed"):
            x = (
                self.children["tok_emb"].apply(params["tok_emb"], input_ids)
                + self.children["pos_emb"].apply(params["pos_emb"], pos)
                + self.children["type_emb"].apply(params["type_emb"], token_type_ids)
            )
            x = self.children["emb_norm"].apply(params["emb_norm"], x)
            x = self.children["emb_drop"].apply(params["emb_drop"], x, rng=r0, train=train)

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        h = self.children["encoder"].apply(
            params["encoder"], x, mask=mask, rng=r1, train=train
        )
        with scope("head"):
            pooled = jnp.tanh(self.children["pooler"].apply(params["pooler"], h[:, 0]))
        return {"last_hidden_state": h, "pooled": pooled}


def bert_pipeline_parts(model: "Bert", params: dict, num_classes_head=None):
    """Split a Bert (or BertClassifier param tree) into pipeline parts.
    If ``num_classes_head`` is given, params must be a BertClassifier tree
    and the head produces classification logits from [CLS]."""
    from tensorlink_tpu.parallel.engine import PipelineParts

    bert = model
    bp = params if num_classes_head is None else params["bert"]
    stack = bert.children["encoder"]
    block = stack.blocks()[0]

    emb_drop = bert.children["emb_drop"]

    def embed_fn(emb_params, batch, rng=None):
        ids = batch["input_ids"]
        T = ids.shape[1]
        pos = jnp.arange(T)[None, :]
        tt = batch.get("token_type_ids")
        tt = jnp.zeros_like(ids) if tt is None else tt
        x = (
            bert.children["tok_emb"].apply(emb_params["tok_emb"], ids)
            + bert.children["pos_emb"].apply(emb_params["pos_emb"], pos)
            + bert.children["type_emb"].apply(emb_params["type_emb"], tt)
        )
        x = bert.children["emb_norm"].apply(emb_params["emb_norm"], x)
        return emb_drop.apply({}, x, rng=rng, train=rng is not None)

    if num_classes_head is not None:
        from tensorlink_tpu.nn.layers import Dropout

        cls_drop = Dropout(bert.cfg_obj.dropout)

        def head_fn(all_params, x, batch, rng=None):
            pooled = jnp.tanh(
                bert.children["pooler"].apply(all_params["head"]["pooler"], x[:, 0])
            )
            pooled = cls_drop.apply({}, pooled, rng=rng, train=rng is not None)
            hw = all_params["head"]["cls"]
            return pooled @ hw["w"].astype(pooled.dtype) + hw["b"].astype(pooled.dtype)

        head_params = {"pooler": bp["pooler"], "cls": params["head"]}
    else:
        def head_fn(all_params, x, batch, rng=None):
            return x  # last_hidden_state

        # no pooler in the optimized tree: head_fn never uses it, and
        # decoupled weight decay would silently shrink unused params
        # (review finding)
        head_params = {}

    def extras_fn(batch):
        # global [B, 1, 1, T] key-padding mask, replicated to every stage
        # (and every seq shard — ring/ulysses slice it by global offset);
        # absent mask -> no extras, blocks run the dense path
        am = batch.get("attention_mask")
        if am is None:
            return None
        return {"mask": am[:, None, None, :].astype(bool)}

    def block_fn(blk_p, x, rng=None, extras=None):
        return block.apply(
            blk_p, x, mask=None if extras is None else extras["mask"],
            rng=rng, train=rng is not None,
        )

    return PipelineParts(
        embed_fn=embed_fn,
        block=block,
        block_params=bp["encoder"],
        block_fn=block_fn,
        extras_fn=extras_fn,
        # CLS pooling selects token 0 — NOT a uniform per-token
        # reduction, so 1F1B+seq>1 must reject it (engine guard); the
        # headless variant's reduction depends on the caller's loss_fn,
        # so it stays None (unknown)
        head_per_token=False if num_classes_head is not None else None,
        head_fn=head_fn,
        embed_params={
            "tok_emb": bp["tok_emb"],
            "pos_emb": bp["pos_emb"],
            "type_emb": bp["type_emb"],
            "emb_norm": bp["emb_norm"],
        },
        head_params=head_params,
    )


class BertClassifier(Module):
    """BertForSequenceClassification equivalent — the reference's e2e
    fine-tune workload (tests/ml/test_full_train.py:75)."""

    def __init__(self, cfg: BertConfig, num_classes: int):
        super().__init__()
        self.num_classes = num_classes
        self.child("bert", Bert(cfg))
        self.child("drop", Dropout(cfg.dropout))
        self.child("head", Dense(cfg.dim, num_classes))

    def apply(self, params, input_ids, *, attention_mask=None, rng=None, train=False, **kw):
        r0, r1 = jax.random.split(rng) if rng is not None else (None, None)
        out = self.children["bert"].apply(
            params["bert"],
            input_ids,
            attention_mask=attention_mask,
            rng=r0,
            train=train,
            **kw,
        )
        pooled = self.children["drop"].apply(params["drop"], out["pooled"], rng=r1, train=train)
        return self.children["head"].apply(params["head"], pooled)
