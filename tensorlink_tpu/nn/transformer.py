"""Transformer building blocks shared by BERT / GPT-2 / ViT / Llama.

A `TransformerStack` is a `Sequential` of homogeneous blocks — which is
exactly what the pipeline partitioner slices into stages (the reference
instead walked arbitrary nn.Module trees and shipped whatever subtree fit,
src/roles/user.py:316-425)."""

from __future__ import annotations

import jax

from tensorlink_tpu.nn.module import Module
from tensorlink_tpu.nn.layers import Dense, Dropout, LayerNorm, RMSNorm
from tensorlink_tpu.nn.attention import MultiHeadAttention
from tensorlink_tpu.runtime.tracing import scope


ACTIVATIONS = {
    "gelu": jax.nn.gelu,  # tanh approximation (GPT-2's gelu_new)
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),  # BERT
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
}


def _decode_glue():
    # lazy: pallas machinery only loads when a decode path actually runs
    from tensorlink_tpu.ops.pallas import decode_glue

    return decode_glue


class FeedForward(Module):
    """MLP block; ``gated=True`` gives the SwiGLU variant (Llama)."""

    def __init__(
        self,
        dim: int,
        hidden_dim: int,
        activation: str = "gelu",
        use_bias: bool = True,
        gated: bool = False,
        dropout: float = 0.0,
    ):
        super().__init__()
        self.dim = dim
        self.hidden_dim = hidden_dim
        self.activation = activation
        self.gated = gated
        self.use_bias = use_bias
        self.dropout = dropout
        self.child("up", Dense(dim, hidden_dim, use_bias=use_bias, shard="col"))
        if gated:
            self.child("gate", Dense(dim, hidden_dim, use_bias=use_bias, shard="col"))
        self.child("down", Dense(hidden_dim, dim, use_bias=use_bias, shard="row"))
        self.child("drop", Dropout(dropout))

    def apply(self, params, x, *, rng=None, train=False, **_):
        act = ACTIVATIONS[self.activation]
        h = self.children["up"].apply(params["up"], x)
        if self.gated:
            h = act(self.children["gate"].apply(params["gate"], x)) * h
        else:
            h = act(h)
        h = self.children["drop"].apply(params["drop"], h, rng=rng, train=train)
        return self.children["down"].apply(params["down"], h)


class TransformerBlock(Module):
    """One attention + MLP block.

    ``norm_style``: "pre" (GPT-2/ViT/Llama) or "post" (BERT).
    ``norm``: "layer" or "rms".
    """

    def __init__(
        self,
        dim: int,
        num_heads: int,
        hidden_dim: int | None = None,
        num_kv_heads: int | None = None,
        norm_style: str = "pre",
        norm: str = "layer",
        norm_eps: float = 1e-6,
        activation: str = "gelu",
        use_bias: bool = True,
        gated_mlp: bool = False,
        causal: bool = False,
        rope: bool = False,
        rope_theta: float = 10000.0,
        dropout: float = 0.0,
        attn_impl: str = "auto",
        moe_experts: int = 0,
        moe_top_k: int = 2,
        moe_capacity_factor: float = 1.25,
        attn_window: int | None = None,  # sliding window (Mistral)
        qkv_fused: bool = False,  # fused q/k/v projection (decode perf)
    ):
        super().__init__()
        self.dim = dim
        self.norm_style = norm_style
        hidden_dim = hidden_dim or 4 * dim
        # constructor args stored for config()/spec-shipping reconstruction
        self.num_heads = num_heads
        self.hidden_dim = hidden_dim
        self.num_kv_heads = num_kv_heads
        self.norm = norm
        self.norm_eps = norm_eps
        self.activation = activation
        self.use_bias = use_bias
        self.gated_mlp = gated_mlp
        self.causal = causal
        self.rope = rope
        self.rope_theta = rope_theta
        self.dropout = dropout
        self.attn_impl = attn_impl
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.attn_window = attn_window
        self.qkv_fused = qkv_fused
        norm_cls = RMSNorm if norm == "rms" else LayerNorm
        self.child("norm1", norm_cls(dim, eps=norm_eps))
        self.child("norm2", norm_cls(dim, eps=norm_eps))
        self.child(
            "attn",
            MultiHeadAttention(
                dim,
                num_heads,
                num_kv_heads=num_kv_heads,
                use_bias=use_bias,
                causal=causal,
                rope=rope,
                rope_theta=rope_theta,
                attn_impl=attn_impl,
                window=attn_window,
                qkv_fused=qkv_fused,
            ),
        )
        if moe_experts:
            from tensorlink_tpu.nn.moe import MoEFeedForward

            # the MoE FFN supports neither biases nor internal dropout —
            # fail loudly instead of silently diverging from the dense
            # FeedForward it replaces (review finding)
            if use_bias:
                raise ValueError("moe_experts requires use_bias=False")
            if dropout:
                raise ValueError("moe_experts requires dropout=0")
            self.child(
                "mlp",
                MoEFeedForward(
                    dim,
                    hidden_dim,
                    num_experts=moe_experts,
                    top_k=moe_top_k,
                    capacity_factor=moe_capacity_factor,
                    gated=gated_mlp,
                    activation=activation,
                ),
            )
        else:
            self.child(
                "mlp",
                FeedForward(
                    dim,
                    hidden_dim,
                    activation=activation,
                    use_bias=use_bias,
                    gated=gated_mlp,
                    dropout=dropout,
                ),
            )
        self.child("drop", Dropout(dropout))

    def _mlp(self, mlp_params, h, rng, train):
        """-> (out, aux). Dense FFN has no auxiliary loss."""
        mlp = self.children["mlp"]
        if hasattr(mlp, "apply_with_aux"):
            return mlp.apply_with_aux(mlp_params, h, rng=rng, train=train)
        return mlp.apply(mlp_params, h, rng=rng, train=train), 0.0

    def _run(self, params, x, mask, cache, positions, rng, train):
        attn = self.children["attn"]
        n1, n2 = self.children["norm1"], self.children["norm2"]
        drop = self.children["drop"]
        r1, r2, r3 = (
            jax.random.split(rng, 3) if rng is not None else (None, None, None)
        )

        # two scopes a block, each a half with its norm and residual:
        # every device instruction of the block reads tl.attn or tl.mlp
        # in its op path (the fused residual+norm2 kernel is the mlp's)
        new_cache = None
        if self.norm_style == "pre":
            with scope("attn"):
                h = n1.apply(params["norm1"], x)
                a = attn.apply(params["attn"], h, mask=mask, cache=cache, positions=positions)
                if cache is not None:
                    a, new_cache = a
                fuse = (
                    cache is not None and not train and x.shape[1] == 1
                    and _decode_glue().should_fuse(a, self.norm)
                )
                if not fuse:
                    x = x + drop.apply(params["drop"], a, rng=r1, train=train)
            with scope("mlp"):
                if fuse:
                    # decode fast path: residual add + norm2 in ONE
                    # kernel launch (T=1 steps are launch-bound; the
                    # add/mean/var/rsqrt/scale chain is otherwise 2
                    # tiny fusions per block per token — see
                    # ops/pallas/decode_glue.py)
                    x, h = _decode_glue().fused_residual_norm(
                        a, x, params["norm2"]["scale"],
                        params["norm2"].get("bias"),
                        eps=self.norm_eps, kind=self.norm,
                    )
                else:
                    h = n2.apply(params["norm2"], x)
                m, aux = self._mlp(params["mlp"], h, r2, train)
                x = x + drop.apply(params["drop"], m, rng=r3, train=train)
        else:  # post-LN (BERT)
            with scope("attn"):
                a = attn.apply(params["attn"], x, mask=mask, cache=cache, positions=positions)
                if cache is not None:
                    a, new_cache = a
                x = n1.apply(params["norm1"], x + drop.apply(params["drop"], a, rng=r1, train=train))
            with scope("mlp"):
                m, aux = self._mlp(params["mlp"], x, r2, train)
                x = n2.apply(params["norm2"], x + drop.apply(params["drop"], m, rng=r3, train=train))
        return x, new_cache, aux

    def apply(self, params, x, *, mask=None, cache=None, positions=None, rng=None, train=False, **_):
        x, new_cache, _ = self._run(params, x, mask, cache, positions, rng, train)
        if cache is not None:
            return x, new_cache
        return x

    def apply_with_aux(self, params, x, *, mask=None, positions=None, rng=None, train=False, **_):
        """-> (out, aux_loss): the MoE router's load-balancing loss (0 for
        dense blocks). Trainers add ``aux_weight * aux`` to the task loss
        (review finding: plain apply() silently discarded it)."""
        x, _, aux = self._run(params, x, mask, None, positions, rng, train)
        return x, aux

    def router_input(self, params, x, *, mask=None, positions=None):
        """The tensor this block's MLP/router actually sees, per the
        block's OWN norm-style wiring — probes (bench MoE leg, the
        capacity-sweep example) must measure routing stats on this, not
        on a hand-reassembled forward that silently drifts when the
        wiring changes (review finding)."""
        attn = self.children["attn"]
        n1, n2 = self.children["norm1"], self.children["norm2"]
        if self.norm_style == "pre":
            h = n1.apply(params["norm1"], x)
            a = attn.apply(params["attn"], h, mask=mask, positions=positions)
            return n2.apply(params["norm2"], x + a)
        a = attn.apply(params["attn"], x, mask=mask, positions=positions)
        return n1.apply(params["norm1"], x + a)

    def routing_stats(self, params, x, *, mask=None, positions=None) -> dict:
        """MoE router telemetry on the input this block's router sees.
        Raises for dense blocks (no router to probe)."""
        mlp = self.children["mlp"]
        if not hasattr(mlp, "routing_stats"):
            raise ValueError("routing_stats: this block's MLP is dense")
        return mlp.routing_stats(
            params["mlp"], self.router_input(params, x, mask=mask,
                                             positions=positions)
        )


class TransformerStack(Module):
    """N homogeneous blocks. params: {"0": block0, ...}."""

    def __init__(self, num_layers: int, make_block, **block_kw):
        super().__init__()
        self.num_layers = num_layers
        for i in range(num_layers):
            self.child(str(i), make_block(**block_kw))

    def apply(self, params, x, *, mask=None, caches=None, positions=None, rng=None, train=False, **_):
        new_caches = [] if caches is not None else None
        for i in range(self.num_layers):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            blk = self.children[str(i)]
            if caches is not None:
                x, c = blk.apply(
                    params[str(i)], x, mask=mask, cache=caches[i],
                    positions=positions, rng=r, train=train,
                )
                new_caches.append(c)
            else:
                x = blk.apply(
                    params[str(i)], x, mask=mask, positions=positions,
                    rng=r, train=train,
                )
        if caches is not None:
            return x, new_caches
        return x

    def apply_with_aux(self, params, x, *, mask=None, positions=None, rng=None, train=False, **_):
        """-> (out, summed aux losses of all MoE blocks)."""
        aux = 0.0
        for i in range(self.num_layers):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            x, a = self.children[str(i)].apply_with_aux(
                params[str(i)], x, mask=mask, positions=positions,
                rng=r, train=train,
            )
            aux = aux + a
        return x, aux

    def blocks(self) -> list[Module]:
        return [self.children[str(i)] for i in range(self.num_layers)]
