"""Mixture-of-Experts feed-forward with expert parallelism.

Two layers, two dispatches; which serves which caller:

- ``MoEFeedForward`` (softmax router, top-k, a capacity factor, one-hot
  dispatch tensors [B, T, E, C]): ``TransformerBlock`` with
  ``moe_experts`` set, i.e. the Llama-shaped trunk's Mixtral preset, on
  one device or sharded over a mesh axis, where the dense dispatch is
  what the partitioner turns into collectives. It drops routes over
  capacity and its tensors grow with E * C: right for 8 experts, not
  buildable at 256.
- ``HeldExpertsMoE`` (sigmoid router with a selection bias, top-k
  renormalised and scaled, shared experts; sorted rows and a grouped
  matmul): ``models/kimi_linear.py``. The layer is told which experts
  it holds, routes over all of them and computes its own experts' part;
  one chip's share of an expert-parallel layer runs it without the
  exchange. It drops nothing and its cost follows the routed rows.

``MoEFeedForward``. The reference has no MoE/expert parallelism at all
(survey §2.3: "EP — absent"); this is TPU-native from scratch. Design:

- Experts are ONE stacked param tree with a leading [E, ...] axis, sharded
  over the mesh's ``model`` axis (`P(model, ...)`) — expert parallelism is
  just tensor sharding on that axis. No per-expert Python modules, no
  host-side routing.
- **Collective lowering** (both verified against compiled HLO in
  tests/test_moe.py): with no ambient mesh the partitioner falls back to
  all-gather (tokens to the expert shards) + all-reduce (partial combine
  outputs) — O(E)-redundant ICI traffic and compute. When an ambient mesh
  (``jax.set_mesh``) carries ``ep_axis``, `apply` additionally shards the
  token-group dim over (data, ep_axis) and pins the dispatched [E, G, C, D]
  tensor to `P(ep_axis, ...)`: the group->expert reshard then compiles to
  **all_to_all** over ``ep_axis`` (t5x/GShard-style), each device routes
  and computes only its 1/N token slice, and the redundant gather/reduce
  pair disappears. The module stays mesh-agnostic: the ambient mesh is
  read at trace time (`jax.sharding.get_abstract_mesh()`), only
  Auto-partitioned axes are used (so it composes inside the pipeline
  shard_map, where ``pipe``/``seq`` are Manual), and with no mesh in
  context behavior is bit-identical to the fallback.
- Token-choice top-k routing (Switch/GShard style) with a capacity
  factor: position-in-expert comes from a cumulative sum over the token
  axis, overflow tokens are dropped (their residual path carries them).
- The router's auxiliary load-balancing loss (mean fraction x mean
  probability per expert, scaled by E) is returned alongside the output
  so trainers can add ``aux_weight * aux_loss``.

Everything is dense einsum algebra on one-hot dispatch tensors —
MXU-shaped, static shapes, no data-dependent control flow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorlink_tpu.nn.module import Module, register_module_type
from tensorlink_tpu.nn.layers import _lecun_normal, _normal
from tensorlink_tpu.runtime.tracing import scope


def _auto_ambient_axes() -> tuple:
    """Names of ambient-mesh axes the SPMD partitioner controls (Auto).

    Manual axes (bound by an enclosing shard_map — the engine's ``pipe``/
    ``seq``) must not appear in a with_sharding_constraint spec; Explicit
    axes would need explicit-sharding plumbing this module doesn't do.
    Empty when no ``jax.set_mesh`` context is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(
        name
        for name, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Auto
    )


@register_module_type
class MoEFeedForward(Module):
    """Drop-in replacement for FeedForward: [B, T, D] -> [B, T, D].

    ``apply`` returns just the output; ``apply_with_aux`` returns
    ``(output, aux_loss)`` for load-balanced training.
    """

    def __init__(
        self,
        dim: int,
        hidden_dim: int,
        num_experts: int = 8,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        gated: bool = True,
        router_noise: float = 0.0,
        activation: str = "gelu",
        ep_axis: str | None = "model",
    ):
        super().__init__()
        self.dim = dim
        self.hidden_dim = hidden_dim
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gated = gated
        self.router_noise = router_noise
        self.activation = activation
        # mesh axis the all_to_all dispatch rides (module docstring);
        # engages only when an ambient mesh carries it as an Auto axis
        self.ep_axis = ep_axis

    def init(self, key):
        E, D, H = self.num_experts, self.dim, self.hidden_dim
        kr, ku, kg, kd = jax.random.split(key, 4)
        params = {
            "router": {"w": _normal(kr, (D, E))},
            "up": _lecun_normal(ku, (E, D, H), fan_in=D),
            "down": _lecun_normal(kd, (E, H, D), fan_in=H),
        }
        if self.gated:
            params["gate"] = _lecun_normal(kg, (E, D, H), fan_in=D)
        return params

    def param_spec(self, model_axis: str = "model"):
        spec = {
            "router": {"w": P()},
            # expert axis sharded: this IS expert parallelism (each
            # device computes only its experts; see module docstring for
            # the measured collective lowering)
            "up": P(model_axis, None, None),
            "down": P(model_axis, None, None),
        }
        if self.gated:
            spec["gate"] = P(model_axis, None, None)
        return spec

    def capacity(self, tokens_per_group: int) -> int:
        c = int(self.capacity_factor * self.top_k * tokens_per_group
                / self.num_experts)
        return max(c, 1)

    def _route(self, logits, rng=None, train=False):
        """logits [B, T, E] -> (dispatch [B, T, E, C], combine [B, T, E, C],
        aux_loss). Top-k with per-expert capacity."""
        B, T, E = logits.shape
        C = self.capacity(T)
        if train and self.router_noise > 0 and rng is not None:
            logits = logits + self.router_noise * jax.random.normal(
                rng, logits.shape, logits.dtype
            )
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

        dispatch = jnp.zeros((B, T, E, C), jnp.float32)
        combine = jnp.zeros((B, T, E, C), jnp.float32)
        # running per-expert fill, so expert k=2 choices respect capacity
        # consumed by k=1 choices
        fill = jnp.zeros((B, E), jnp.int32)
        masked = probs
        importance = jnp.zeros((B, E), jnp.float32)
        for _ in range(self.top_k):
            idx = jnp.argmax(masked, axis=-1)  # [B, T]
            onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B, T, E]
            importance = importance + onehot.mean(axis=1)
            # position of each token within its chosen expert
            pos = jnp.cumsum(onehot, axis=1) - onehot + fill[:, None, :]
            pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [B, T]
            keep = pos < C
            w = jnp.sum(probs * onehot, axis=-1) * keep  # [B, T]
            poh = jax.nn.one_hot(pos, C, dtype=jnp.float32)  # [B, T, C]
            sel = onehot[..., None] * poh[:, :, None, :]  # [B, T, E, C]
            dispatch = dispatch + sel * keep[..., None, None]
            combine = combine + sel * w[..., None, None]
            fill = fill + jnp.sum(
                onehot * keep[..., None], axis=1
            ).astype(jnp.int32)
            masked = masked * (1.0 - onehot)  # exclude chosen expert

        # normalize combine weights over the selected experts
        denom = combine.sum(axis=(2, 3), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)

        # GShard aux loss: E * mean(fraction_routed) . mean(router_prob)
        frac = importance / self.top_k  # [B, E] mean one-hot over tokens
        mean_prob = probs.mean(axis=1)  # [B, E]
        aux = E * jnp.mean(jnp.sum(frac * mean_prob, axis=-1))
        return dispatch, combine, aux

    def _ep_plan(self):
        """(group_spec_axes, ep_axis) for the all_to_all dispatch path, or
        (None, None) when no usable ambient mesh — see module docstring.
        Token groups (= batch rows; routing/capacity is per row) co-shard
        over ``data`` when present, so EP composes with DP: the reshard is
        an all_to_all over ``ep_axis`` inside each data slice."""
        if not self.ep_axis:
            return None, None
        axes = _auto_ambient_axes()
        if self.ep_axis not in axes:
            return None, None
        # dict.fromkeys dedupes while keeping order: ep_axis="data"
        # (EP over the DP axis) must not produce a duplicate-axis spec
        groups = tuple(
            a for a in dict.fromkeys(("data", self.ep_axis)) if a in axes
        )
        return groups, self.ep_axis

    def apply_with_aux(self, params, x, *, rng=None, train=False, **_):
        B, T, D = x.shape
        groups, ep = self._ep_plan()
        wsc = jax.lax.with_sharding_constraint
        if ep is not None:
            # each device routes only its token-group slice
            x = wsc(x, P(groups, None, None))
        logits = x.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
        dispatch, combine, aux = self._route(logits, rng=rng, train=train)
        dispatch = dispatch.astype(x.dtype)
        combine = combine.astype(x.dtype)
        if ep is not None:
            dispatch = wsc(dispatch, P(groups, None, None, None))

        # dispatch -> [E, B, C, D]; under SPMD with `up`/`down` sharded
        # on E each device computes this einsum only for its expert
        # shard (tokens reach it via all_to_all when the ambient-mesh
        # constraint below engages, else all-gather; see docstring)
        expert_in = jnp.einsum("btec,btd->ebcd", dispatch, x)
        if ep is not None:
            # group-sharded -> expert-sharded over the SAME mesh axis:
            # this is the pin that compiles to all_to_all
            data = tuple(a for a in groups if a != ep) or None
            expert_in = wsc(expert_in, P(ep, data, None, None))
        up = jnp.einsum("ebcd,edh->ebch", expert_in, params["up"].astype(x.dtype))
        if self.gated:
            g = jnp.einsum(
                "ebcd,edh->ebch", expert_in, params["gate"].astype(x.dtype)
            )
            h = jax.nn.silu(g) * up
        else:
            from tensorlink_tpu.nn.transformer import ACTIVATIONS

            h = ACTIVATIONS[self.activation](up)
        expert_out = jnp.einsum("ebch,ehd->ebcd", h, params["down"].astype(x.dtype))
        if ep is not None:
            # all_to_all back: every group re-collects its tokens, the
            # combine einsum below is then device-local per group
            expert_out = wsc(expert_out, P(None, groups, None, None))
        out = jnp.einsum("btec,ebcd->btd", combine, expert_out)
        return out, aux

    def apply(self, params, x, *, rng=None, train=False, **_):
        out, _ = self.apply_with_aux(params, x, rng=rng, train=train)
        return out

    def routing_stats(self, params, x) -> dict:
        """Router telemetry for benchmarks/monitoring: the fraction of
        (token, choice) routes dropped by the capacity limit (their
        residual path carries the token unchanged) and the aux loss.
        dispatch sums to the KEPT route count, so
        drop = 1 - sum(dispatch) / (B*T*top_k)."""
        B, T, _ = x.shape
        logits = x.astype(jnp.float32) @ params["router"]["w"].astype(
            jnp.float32
        )
        dispatch, _, aux = self._route(logits)
        kept = float(jnp.sum(dispatch))
        return {
            "drop_fraction": 1.0 - kept / (B * T * self.top_k),
            "aux_loss": float(aux),
            "capacity_per_expert": self.capacity(T),
        }


class HeldExpertsMoE(Module):
    """Sigmoid-routed experts with shared experts (DeepSeek-V3 / Kimi
    style), told which experts it holds: [B, T, D] -> [B, T, D].

        s = sigmoid(x W_r)                  float32, over all E experts
        chosen = top-k of s + bias          (the bias chooses only, and
                                             gets no gradient)
        w_e = scale * s_e / sum_chosen s
        y = sum_{e chosen and held} w_e E_e(x) + E_shared(x)

    ``held = (first, count)``: the experts ``[first, first + count)``
    live here (``None``: all of them); the expert weights are ``count``
    deep, the router stays E wide. What an absent expert would add is
    left out and nothing stands in for it. The routes to held experts
    are sorted by expert into ``row_bound`` rows, gathered, put through
    three grouped matmuls (``lax.ragged_dot``) and scattered back, so
    the cost follows the rows and no [T, E, C] tensor exists. No route
    to a held expert is dropped: ``row_bound`` None is every route there
    could be, ``tokens * min(k, count)``. A smaller bound is the
    caller's promise about its load; if the routes ever outnumber it the
    output is NaN (a non-finite loss, the trainers' ``nonfinite``
    flag), never a silently dropped route. ``routing_stats`` counts.
    """

    def __init__(
        self,
        dim: int,
        hidden_dim: int,
        num_experts: int,
        top_k: int,
        held: tuple[int, int] | None = None,
        shared_experts: int = 1,
        routed_scale: float = 1.0,
        renormalize: bool = True,
        select_bias: bool = True,
        row_bound: int | None = None,
    ):
        super().__init__()
        from tensorlink_tpu.nn.transformer import FeedForward

        first, count = held or (0, num_experts)
        if not 0 <= first <= first + count <= num_experts or count < 1:
            raise ValueError(f"held {held} is not a range of {num_experts}")
        self.dim, self.hidden_dim = dim, hidden_dim
        self.num_experts, self.top_k = num_experts, top_k
        self.held = (first, count)
        self.shared_experts, self.routed_scale = shared_experts, routed_scale
        self.renormalize, self.select_bias = renormalize, select_bias
        self.row_bound = row_bound
        if shared_experts:
            self.child("shared", FeedForward(
                dim, hidden_dim * shared_experts, activation="silu",
                use_bias=False, gated=True,
            ))

    def init(self, key):
        D, F, E = self.dim, self.hidden_dim, self.num_experts
        n = self.held[1]
        kr, ku, kg, kd, ks = jax.random.split(key, 5)
        params = {
            "router": {"w": _normal(kr, (D, E))},
            # fan-in first, the held experts second: [D, n, F], [F, n, D]
            "experts": {
                "up": {"w": _lecun_normal(ku, (D, n, F))},
                "gate": {"w": _lecun_normal(kg, (D, n, F))},
                "down": {"w": _lecun_normal(kd, (F, n, D))},
            },
        }
        if self.select_bias:
            params["router"]["bias"] = jnp.zeros((E,))
        if self.shared_experts:
            params["shared"] = self.children["shared"].init(ks)
        return params

    def param_spec(self, model_axis: str = "model"):
        spec = {
            "router": {"w": P()},
            "experts": {n: {"w": P()} for n in ("up", "gate", "down")},
        }
        if self.select_bias:
            spec["router"]["bias"] = P()
        if self.shared_experts:
            spec["shared"] = self.children["shared"].param_spec(model_axis)
        return spec

    def rows(self, tokens: int) -> int:
        """Rows the sorted dispatch is built for at ``tokens`` tokens."""
        most = tokens * min(self.top_k, self.held[1])
        return most if self.row_bound is None else min(self.row_bound, most)

    def _route(self, params, xf):
        """xf [N, D] -> (tok [R] token of each row, w [R] its weight, 0
        on an empty row, sizes [count] rows of each held expert, routes:
        how many routes to held experts there were)."""
        N, k = xf.shape[0], self.top_k
        first, count = self.held
        R = self.rows(N)
        f32 = jnp.float32
        s = jax.nn.sigmoid(xf.astype(f32) @ params["router"]["w"].astype(f32))
        pick = s
        if self.select_bias:
            pick = s + params["router"]["bias"].astype(f32)
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(pick), k)  # [N, k]
        w = jnp.take_along_axis(s, idx, -1)
        if self.renormalize:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * self.routed_scale
        local = idx - first
        mine = (local >= 0) & (local < count)
        # routes by held expert, the absent experts' after them all
        key = jnp.where(mine, local, count).reshape(N * k)
        order = jnp.argsort(key, stable=True)[:R]
        routes = jnp.sum(mine)
        live = jnp.arange(R) < routes
        ends = jnp.minimum(
            jnp.cumsum(jnp.bincount(key, length=count + 1)[:count]), R
        )
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        return (
            order // k, jnp.where(live, w.reshape(N * k)[order], 0.0),
            sizes, routes,
        )

    def apply(self, params, x, **_):
        B, T, D = x.shape
        xf = x.reshape(B * T, D)
        with scope("moe.route"):
            tok, w, sizes, routes = self._route(params, xf)
        with scope("moe.experts"):
            ex = params["experts"]
            up, gate, down = (
                ex[n]["w"].astype(x.dtype).swapaxes(0, 1)
                for n in ("up", "gate", "down")
            )
            # past the routes the grouped matmul writes nothing: such a
            # row holds whatever memory held, forward and backward (NaN
            # on a v5e, PR 29), so every operand and result of it is
            # cleared there, which clears its cotangents too
            live = (w > 0)[:, None]

            def clear(r):
                return jnp.where(live, r, 0)

            def grouped(lhs, rhs):
                return clear(jax.lax.ragged_dot(clear(lhs), rhs, sizes))

            rows = xf[tok]
            h = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
            out = grouped(h, down) * w[:, None].astype(x.dtype)
            y = jnp.zeros_like(xf).at[tok].add(out)
            y = jnp.where(routes > w.shape[0], jnp.nan, y)
        y = y.reshape(B, T, D)
        if self.shared_experts:
            y = y + self.children["shared"].apply(params["shared"], x)
        return y

    def routing_stats(self, params, x) -> dict:
        """Routes to held experts, the rows built for them, and the
        routes past the bound (0, or the output is NaN)."""
        xf = x.reshape(-1, x.shape[-1])
        _, _, sizes, routes = self._route(params, xf)
        R = self.rows(xf.shape[0])
        return {
            "routes": int(routes), "rows": R,
            "overflow": max(int(routes) - R, 0),
            "per_expert": [int(n) for n in sizes],
        }
