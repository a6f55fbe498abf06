"""Kimi-Linear family: the program's model built from a configuration
file (HF key names), its training loss, and the counts the yardstick
needs. The counts are of needed work: the recurrence as its definition
states it, the held experts' share of a token's routes, no
recomputation, no padding of rows or heads."""

from __future__ import annotations


def _share(cfg: dict) -> dict:
    return cfg.get("deployment_share", {})


def build(cfg: dict):
    """The system under test: ``tensorlink_tpu``'s own model."""
    from tensorlink_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

    la, share, train = cfg["linear_attn_config"], _share(cfg), cfg["train"]
    return KimiLinear(KimiLinearConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        kda_layers=tuple(la["kda_layers"]),
        full_attn_layers=tuple(la["full_attn_layers"]),
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        mla_heads=cfg["num_attention_heads"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"], hidden_dim=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        moe_hidden_dim=cfg["moe_intermediate_size"],
        num_experts=share.get("router_width", cfg["num_experts"]),
        experts_per_token=cfg["num_experts_per_token"],
        shared_experts=cfg["num_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        renormalize=cfg["moe_renormalize"],
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
        "k": cfg["num_experts_per_token"],
        "held": (_share(cfg).get("first_expert", 0), cfg["num_experts"]),
    }


def router_scores(model, params, ids, bias_for=None):
    """For each expert layer in order, the [tokens, E] float32 sigmoid
    scores its router sees for ``ids``: the model's own modules, a block
    composed as ``KimiBlock.apply`` composes it, the score as
    ``HeldExpertsMoE._route`` takes it. Traceable; ``params`` come in the
    step's compute dtype. ``bias_for(scores) -> [E]``, where given, is
    asked at each expert layer, and that layer's experts then choose
    under the bias it returns in place of ``params``' own: a layer's
    bias changes what the layers after it see. Having this function is
    what has ``drivers/train.py`` set the selection bias from the load
    (``benchmark/balance.py``)."""
    import jax
    import jax.numpy as jnp

    ch = model.children
    x = ch["tok_emb"].apply(params["tok_emb"], ids)
    scores = []
    for name, block in ch["blocks"].children.items():
        p, b = params["blocks"][name], block.children
        x = x + b["mixer"].apply(p["mixer"], b["norm1"].apply(p["norm1"], x))
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


def _layers(cfg: dict) -> tuple[int, int, int, int]:
    """(KDA layers, MLA layers, dense feed-forwards, expert layers)."""
    n = cfg["num_hidden_layers"]
    kda = len(cfg["linear_attn_config"]["kda_layers"])
    dense = min(cfg["first_k_dense_replace"], n)
    return kda, n - kda, dense, n - dense


def matmul_params(cfg: dict) -> float:
    """Weights a token is multiplied with, on average: every projection,
    the shared expert, and of the held experts the share of a token's
    routes that falls on them (``k * held / router width``)."""
    D = cfg["hidden_size"]
    la = cfg["linear_attn_config"]
    inner, d = la["num_heads"] * la["head_dim"], la["head_dim"]
    kda = 4 * D * inner + 2 * (D * d + d * inner) + D * la["num_heads"]
    H = cfg["num_attention_heads"]
    nope, rope, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    rank = cfg["kv_lora_rank"]
    mla = (
        D * H * (nope + rope) + D * (rank + rope) + rank * H * (nope + dv)
        + H * dv * D
    )
    width = _share(cfg).get("router_width", cfg["num_experts"])
    expert = 3 * D * cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_token"] * cfg["num_experts"] / width
    moe = D * width + expert * (cfg["num_shared_experts"] + routed)
    n_kda, n_mla, n_dense, n_moe = _layers(cfg)
    return (
        n_kda * kda + n_mla * mla + n_dense * 3 * D * cfg["intermediate_size"]
        + n_moe * moe + D * cfg["vocab_size"]
    )


def attn_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one token's mixers beside the projections. MLA:
    the scores over ``context`` keys at q, k width and the values at v
    width. KDA, a head, as the recurrence states it: decay of the state
    (d_k d_v), k^T S, the rank-one update and S^T q (2 d_k d_v each)."""
    n_kda, n_mla, _, _ = _layers(cfg)
    la = cfg["linear_attn_config"]
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = 2.0 * H * (qk + cfg["v_head_dim"]) * context
    kda = 7.0 * la["num_heads"] * la["head_dim"] ** 2
    return n_mla * mla + n_kda * kda


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3x the forward), no recomputation counted.
    Causal attention over a sequence: a token sees on average
    (seq_len + 1) / 2 keys."""
    fwd = 2.0 * matmul_params(cfg) + attn_flops(cfg, (seq_len + 1) / 2)
    return 3.0 * fwd


def reference():
    from benchmark.reference import kimi_linear

    return kimi_linear
