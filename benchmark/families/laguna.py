"""Laguna family: the program's model built from a configuration file
(HF key names), its training loss, and the counts the yardstick needs.
The counts are of needed work: a window layer's band and not the blocks
a kernel visits, each layer at its own head count, the held experts'
share of a token's routes, no recomputation, no padding of rows."""

from __future__ import annotations

WINDOW = "sliding_attention"


def _share(cfg: dict) -> dict:
    return cfg.get("deployment_share", {})


def _rotary(spec: dict):
    from tensorlink_tpu.nn.attention import Rotary, Yarn

    yarn = None
    if spec.get("rope_type", "default") == "yarn":
        yarn = Yarn(
            spec["factor"], spec["original_max_position_embeddings"],
            spec["beta_fast"], spec["beta_slow"], spec.get("attention_factor"),
        )
    return Rotary(spec["rope_theta"], spec.get("partial_rotary_factor", 1.0), yarn)


def build(cfg: dict):
    """The system under test: ``tensorlink_tpu``'s own model."""
    from tensorlink_tpu.models.laguna import Laguna, LagunaConfig

    share, train, rope = _share(cfg), cfg["train"], cfg["rope_parameters"]
    if set(cfg["gating_types"]) != {"per_head"}:
        raise ValueError(f"gating {set(cfg['gating_types'])}: per_head only")
    shared, rest = divmod(
        cfg["shared_expert_intermediate_size"], cfg["moe_intermediate_size"])
    if rest or not cfg["norm_topk_prob"]:
        raise ValueError("shared experts as routed ones, top-k renormalised")
    return Laguna(LagunaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        hidden_dim=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        window_rotary=_rotary(rope["sliding_attention"]),
        full_rotary=_rotary(rope["full_attention"]),
        moe_hidden_dim=cfg["moe_intermediate_size"],
        num_experts=share.get("router_width", cfg["num_experts"]),
        experts_per_token=cfg["num_experts_per_tok"], shared_experts=shared,
        routed_scale=cfg["moe_routed_scaling_factor"],
        held_experts=(share.get("first_expert", 0), cfg["num_experts"]),
        moe_row_bound=train.get("moe_row_bound"),
        rms_eps=cfg["rms_norm_eps"], remat=bool(train.get("remat")),
    ))


def train_loss(module, params, batch, rng):
    """Next-token cross-entropy through the program's own forward pass
    and loss (what a user of ``Trainer`` writes)."""
    from tensorlink_tpu.train.trainer import softmax_cross_entropy

    return softmax_cross_entropy(
        module.apply(params, batch["input_ids"]), batch["labels"]
    )


def routed_experts(cfg: dict) -> dict:
    """The router's choice, for ``benchmark/balance.py``: experts a token
    chooses, and the experts held here (first, count)."""
    return {
        "k": cfg["num_experts_per_tok"],
        "held": (_share(cfg).get("first_expert", 0), cfg["num_experts"]),
    }


def router_scores(model, params, ids, bias_for=None):
    """For each expert layer in order, the [tokens, E] float32 sigmoid
    scores its router sees for ``ids``: the model's own modules, a block
    composed as ``LagunaBlock.apply`` composes it, the score as
    ``HeldExpertsMoE._route`` takes it. Traceable; ``params`` come in the
    step's compute dtype. ``bias_for(scores) -> [E]``, where given, is
    asked at each expert layer, and that layer's experts then choose
    under the bias it returns in place of ``params``' own."""
    import jax
    import jax.numpy as jnp

    ch = model.children
    x = ch["tok_emb"].apply(params["tok_emb"], ids)
    scores = []
    for name, block in ch["blocks"].children.items():
        p, b = params["blocks"][name], block.children
        x = x + b["attn"].apply(p["attn"], b["norm1"].apply(p["norm1"], x))
        h = b["norm2"].apply(p["norm2"], x)
        mlp = p["mlp"]
        if block.ffn_kind == "moe":
            router = mlp["router"]
            scores.append(jax.nn.sigmoid(
                h.reshape(-1, h.shape[-1]).astype(jnp.float32)
                @ router["w"].astype(jnp.float32)
            ))
            if bias_for is not None:
                bias = bias_for(scores[-1]).astype(router["bias"].dtype)
                mlp = {**mlp, "router": {**router, "bias": bias}}
        x = x + b["mlp"].apply(mlp, h)
    return scores


def _attn_params(cfg: dict, heads: int) -> int:
    D, d, G = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return 2 * D * heads * d + 2 * D * G * d + D * heads  # q, o; k, v; gate


def matmul_params(cfg: dict) -> float:
    """Weights a token is multiplied with, on average: every projection
    at its layer's head count, the heads' gates, the shared expert, and
    of the held experts the share of a token's routes that falls on them
    (``k * held / router width``), the head once."""
    D = cfg["hidden_size"]
    width = _share(cfg).get("router_width", cfg["num_experts"])
    expert = 3 * D * cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"] / cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] / width
    ffn = {
        "dense": 3 * D * cfg["intermediate_size"],
        "sparse": D * width + expert * (shared + routed),
    }
    return sum(
        _attn_params(cfg, heads) + ffn[kind]
        for heads, kind in zip(
            cfg["num_attention_heads_per_layer"], cfg["mlp_layer_types"])
    ) + D * cfg["vocab_size"]


def attn_flops(cfg: dict, context: float, seq_len: int | None = None) -> float:
    """Forward FLOPs of one token's attention beside the projections:
    each query head's scores and values at ``head_dim`` over the keys it
    sees: ``context`` of them, in a window layer the mean of
    ``min(t + 1, window)`` over a sequence of ``seq_len`` (``min(context,
    window)`` where no length is given)."""
    W, d = cfg["sliding_window"], cfg["head_dim"]
    if seq_len is None:
        band = min(context, W)
    else:  # mean over t of min(t + 1, W)
        full = min(W, seq_len)
        band = (full * (full + 1) / 2 + (seq_len - full) * W) / seq_len
    return sum(
        2 * 2.0 * heads * d * (band if kind == WINDOW else context)
        for heads, kind in zip(
            cfg["num_attention_heads_per_layer"], cfg["layer_types"])
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
    from benchmark.reference import laguna

    return laguna
