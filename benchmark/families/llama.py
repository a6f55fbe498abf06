"""Llama-shaped family: the program's model built from a configuration
file (HF key names), and the counts the yardstick needs."""

from __future__ import annotations


def build(cfg: dict):
    """The system under test: ``tensorlink_tpu``'s own model."""
    from tensorlink_tpu.models.llama import Llama, LlamaConfig

    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    return Llama(LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        hidden_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        attn_window=cfg.get("sliding_window"),
    ))


def matmul_params(cfg: dict) -> int:
    """Parameters a token's forward pass multiplies by (the embedding
    is a lookup and is left out; the head is in)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return cfg["num_hidden_layers"] * layer + D * cfg["vocab_size"]


def attn_flops(cfg: dict, context: float) -> float:
    """Forward attention FLOPs of ONE token attending ``context`` keys:
    QK^T and PV over every query head and layer."""
    ctx = min(context, cfg.get("sliding_window") or context)
    return (
        4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
        * cfg["head_dim"] * ctx
    )


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token in one layer, each KV head once."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def reference():
    from benchmark.reference import llama

    return llama
