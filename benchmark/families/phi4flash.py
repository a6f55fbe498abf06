"""Phi-4-mini-flash family (SambaY with differential attention): the
program's model built from a configuration file (HF key names, the
sizes the published config omits under ``mamba``), its training loss,
and the counts the yardstick needs. The counts are of needed work: the
recurrence as its definition states it, a window layer's band and not
the blocks a kernel visits, no recomputation."""

from __future__ import annotations


def build(cfg: dict):
    """The system under test: ``tensorlink_tpu``'s own model."""
    from tensorlink_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig

    m, heads = cfg["mamba"], cfg["num_attention_heads"]
    return Phi4Flash(Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["published_num_hidden_layers"],
        layers=tuple(cfg["layers"]), num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        hidden_dim=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"], d_state=m["d_state"],
        d_conv=m["d_conv"], expand=m["expand"], dt_rank=m["dt_rank"],
        norm_eps=cfg["layer_norm_eps"], remat=bool(cfg["train"].get("remat")),
    ))


def train_loss(module, params, batch, rng):
    """Next-token cross-entropy through the program's own forward pass
    and loss (what a user of ``Trainer`` writes)."""
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    return softmax_cross_entropy(
        module.apply(params, batch["input_ids"]), batch["labels"]
    )


def layer_kinds(cfg: dict) -> dict[str, int]:
    """How many of the held layers are of each kind."""
    from benchmark.reference.phi4flash import layer_kind

    kinds = dict.fromkeys(("mamba", "window", "full", "gmu", "cross"), 0)
    for i in cfg["layers"]:
        kinds[layer_kind(i, cfg["published_num_hidden_layers"])] += 1
    return kinds


def matmul_params(cfg: dict) -> float:
    """Weights a token is multiplied with: every projection and the
    tied table once, as the head (the lookup multiplies nothing)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    m = cfg["mamba"]
    E, N, R = m["expand"] * D, m["d_state"], m["dt_rank"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = D // H
    mixer = {
        "mamba": D * 2 * E + E * (R + 2 * N) + R * E + E * D,
        "gmu": 2 * D * E,
        "window": D * (H + 2 * Hkv) * d + H * d * D,
        "cross": D * H * d + H * d * D,
    }
    mixer["full"] = mixer["window"]
    kinds = layer_kinds(cfg)
    return (
        sum(n * (mixer[k] + 3 * D * F) for k, n in kinds.items())
        + D * cfg["vocab_size"]
    )


def attn_flops(cfg: dict, context: float, seq_len: int | None = None) -> float:
    """Forward FLOPs of one token's mixers beside the projections.
    Differential attention, a pair of heads: two score maps at width d
    and two products with the pair's values at width 2d, over the keys
    the token sees: ``context`` of them, in a window layer the mean of
    ``min(t + 1, window)`` over a sequence of ``seq_len`` (``context``
    itself where no length is given). Mamba, a channel and state, as the
    recurrence states it: the decay's product, the input's, their sum
    and the read-out (multiply and add): 2 + 1 + 1 + 2 = 6, with the
    exponential's own product Delta A one more: 7. The short
    convolution, the gates and the norms are left out as everywhere."""
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // H
    W = cfg["sliding_window"]
    m = cfg["mamba"]
    kinds = layer_kinds(cfg)
    if seq_len is None:
        band = min(context, W)
    else:  # mean over t of min(t + 1, W)
        full = min(W, seq_len)
        band = (full * (full + 1) / 2 + (seq_len - full) * W) / seq_len
    pair = 2 * 2.0 * (d + 2 * d)  # two maps: scores at d, values at 2d
    attn = (H // 2) * pair
    scan = 7.0 * m["expand"] * cfg["hidden_size"] * m["d_state"]
    return (
        kinds["window"] * attn * band
        + (kinds["full"] + kinds["cross"]) * attn * context
        + kinds["mamba"] * scan
    )


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3x the forward), no recomputation counted.
    Causal attention over a sequence: a token sees on average
    (seq_len + 1) / 2 keys, in a window layer the band's mean."""
    fwd = 2.0 * matmul_params(cfg) + attn_flops(
        cfg, (seq_len + 1) / 2, seq_len
    )
    return 3.0 * fwd


def reference():
    from benchmark.reference import phi4flash

    return phi4flash
