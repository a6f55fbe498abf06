"""GPT-2 family: the program's model built from a configuration file
(HF key names), its training loss, and the counts the yardstick needs."""

from __future__ import annotations


def build(cfg: dict):
    """The system under test: ``tensorlink_tpu``'s own model. Dropout 0
    (``assumed`` in the configuration file)."""
    from tensorlink_tpu.models.gpt2 import GPT2, GPT2Config

    return GPT2(GPT2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_len=cfg["n_positions"], dropout=0.0,
        layer_norm_eps=cfg["layer_norm_epsilon"],
    ))


def train_loss(module, params, batch, rng):
    """Next-token cross-entropy through the program's own forward pass
    and loss (what a user of ``Trainer`` writes)."""
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    return softmax_cross_entropy(
        module.apply(params, batch["input_ids"]), batch["labels"]
    )


def matmul_params(cfg: dict) -> int:
    D = cfg["n_embd"]
    return cfg["n_layer"] * 12 * D * D + D * cfg["vocab_size"]


def attn_flops(cfg: dict, context: float) -> float:
    """Forward attention FLOPs of one token attending ``context`` keys."""
    return 4.0 * cfg["n_layer"] * cfg["n_embd"] * context


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3x the forward), no recomputation counted.
    Causal attention over a sequence: a token sees on average
    (seq_len + 1) / 2 keys."""
    fwd = 2.0 * matmul_params(cfg) + attn_flops(cfg, (seq_len + 1) / 2)
    return 3.0 * fwd


def reference():
    from benchmark.reference import gpt2

    return gpt2
