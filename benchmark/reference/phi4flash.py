"""Plain Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``: the
SambaY decoder-hybrid-decoder, arXiv:2507.06607, with differential
attention, arXiv:2410.05258) in float32 ``jax.numpy``: forward pass,
next-token cross-entropy, its gradients by ``jax.grad``, global-norm
clipping and Adam. No kernels, no chunking of the recurrence, nothing of
``tensorlink_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

Layer i of L = ``published_num_hidden_layers`` (0-based; ``layers``
lists the published indices that are held), every one
``x += mixer(LN(x)); x += FFN(LN(x))``, ``FFN(h) = (silu(h W_g) * (h
W_u)) W_d``, LayerNorm with gain and bias, logits by the token table.
The mixer: i even: Mamba for i <= L/2, gated memory unit for i >= L/2 +
2; i odd: differential attention, over a causal band of
``sliding_window`` keys for i < L/2, over every earlier key at i = L/2 +
1, and for i >= L/2 + 3 with a query of its own on layer L/2 + 1's k
and v. Layer L/2's Mamba gives the memory M the gated memory units read.

Mamba, a channel (state s [N], s_0 = 0), token by token:
    [u, z] = h W_in;  u = silu(conv4(u) + b_conv)
    [dt, B_t, C_t] = u W_x;  Delta = softplus(dt W_dt + b_dt)
    s_t = exp(Delta_t A) * s_{t-1} + (Delta_t u_t) B_t,   A = -exp(A_log)
    y_t = s_t . C_t + D u_t          (M = y where the layer gives it)
    out = (y * silu(z)) W_out
Gated memory unit: (silu(h W_1) * M) W_2.
Differential attention: heads pair up (2j, 2j+1); a pair's two score
maps share the pair's values [v1 | v2]:
    a_i = softmax(q_i k_i^T / sqrt(d) + mask) [v1 | v2]        i = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i)
    out = reshape(rmsnorm_2d(a1 - lambda a2) * (1 - lambda_init)) W_o + b_o
The weights arrive as the benchmark's own tree (``benchmark/weights.py``):
  tok_emb/table [V,D]  norm_f/{scale,bias}
  blocks/<j>/{norm1,norm2}/{scale,bias}   blocks/<j>/mlp/{up,gate,down}/w
  blocks/<j>/mixer (Mamba): in_proj/w [D,2E] conv/w [4,E] conv_bias/b [E]
      x_proj/w [E,R+2N] dt_proj/w [R,E] dt_bias/b [E] A_log/b [E,N]
      D/scale [E] out_proj/w [E,D]
  blocks/<j>/mixer (GMU): in_proj/w [D,E] out_proj/w [E,D]
  blocks/<j>/mixer (attention): q/{w,b} [D,Hd] {k,v}/{w,b} [D,Hkv d] (a
      cross layer has q alone) lambda_{q1,k1,q2,k2}/b [d] subln/scale
      [2d] o/{w,b}

Departures of form, not of mathematics: the recurrence runs under
``lax.scan`` with ``jax.checkpoint`` around each 64 tokens, and each
layer is rematerialised, so that a row of 4,096 tokens fits beside the
weights, gradients and moments; attention goes through in blocks of
queries; rows go through in blocks (``rows_per_block``) with the
gradient summed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import _ln, adam_step, global_norm  # noqa: F401
from benchmark.reference.kimi_linear import _conv, _swiglu
from benchmark.reference.quant import lower

SEGMENT = 64  # tokens of the recurrence under one jax.checkpoint
QUERY_BLOCK = 512


def _dense(x, p, mode):
    y = lower(x, mode, -1) @ lower(p["w"], mode, 0)
    return y + p["b"] if "b" in p else y


def selective_scan(u, delta, A, Bm, Cm, D):
    """The recurrence, token by token. u, delta [B,T,E]; A [E,N]; Bm, Cm
    [B,T,N]; D [E] -> y [B,T,E]."""
    B, T, E = u.shape
    seg = math.gcd(SEGMENT, T)

    def token(s, x):
        u_t, d_t, b_t, c_t = x
        s = jnp.exp(d_t[..., None] * A) * s
        s = s + (d_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1) + D * u_t

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs, unroll=4)

    def by_time(x):  # [B,T,...] -> [T/seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(T // seg, seg, *x.shape[1:])

    s0 = jnp.zeros((B, E, A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(segment, s0, tuple(map(by_time, (u, delta, Bm, Cm))))
    return jnp.moveaxis(y.reshape(T, B, E), 0, 1)


def _mamba(x, p, cfg, mode):
    """-> (the mixer's output, the scan's output y before the gate)."""
    m = cfg["mamba"]
    E, N, R = m["expand"] * cfg["hidden_size"], m["d_state"], m["dt_rank"]
    uz = _dense(x, p["in_proj"], mode)
    u, z = uz[..., :E], uz[..., E:]
    u = jax.nn.silu(_conv(u, p["conv"]["w"]) + p["conv_bias"]["b"])
    dbc = _dense(u, p["x_proj"], mode)
    delta = jax.nn.softplus(
        _dense(dbc[..., :R], p["dt_proj"], mode) + p["dt_bias"]["b"]
    )
    y = selective_scan(
        u, delta, -jnp.exp(p["A_log"]["b"]), dbc[..., R:R + N],
        dbc[..., R + N:], p["D"]["scale"],
    )
    return _dense(y * jax.nn.silu(z), p["out_proj"], mode), y


def _gmu(x, p, memory, mode):
    gate = jax.nn.silu(_dense(x, p["in_proj"], mode))
    return _dense(gate * memory, p["out_proj"], mode)


def softmax_map(q, k, v, window, mode):
    """softmax(q k^T / sqrt(d) + mask) v, causal, over the last
    ``window`` keys (None: all). q [B,T,G,r,d]: r query heads on each of
    the G key heads; k [B,T,G,d]; v [B,T,G,dv] -> [B,T,G,r,dv]."""
    B, T, G, r, d = q.shape
    qb = math.gcd(QUERY_BLOCK, T)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(_, xs):
        q_i, start = xs  # [B, qb, G, r, d]
        s = jnp.einsum(
            "bqgrd,bkgd->bgrqk", lower(q_i, mode), lower(k, mode)
        ) / math.sqrt(d)
        qpos = (start + jnp.arange(qb))[:, None]
        keep = qpos >= kpos[None]
        if window is not None:
            keep = keep & (kpos[None] > qpos - window)
        s = jnp.where(keep, s, -jnp.inf)
        return None, jnp.einsum(
            "bgrqk,bkge->bqgre", lower(jax.nn.softmax(s, -1), mode),
            lower(v, mode, 1),
        )

    qs = jnp.moveaxis(q.reshape(B, T // qb, qb, G, r, d), 1, 0)
    _, o = jax.lax.scan(block, None, (qs, jnp.arange(T // qb) * qb))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, G, r, -1)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _diff_attn(x, p, cfg, index, window, kv, mode):
    """-> (the layer's output, the (k, v) it used, as projected)."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    B, T, _ = x.shape
    q = _dense(x, p["q"], mode).reshape(B, T, Hkv // 2, H // Hkv, 2, d)
    if kv is None:
        k, v = (_dense(x, p[n], mode).reshape(B, T, Hkv, d) for n in ("k", "v"))
    else:
        k, v = kv
    k2 = k.reshape(B, T, Hkv // 2, 2, d)
    vv = v.reshape(B, T, Hkv // 2, 2 * d)  # a pair's [v1 | v2]
    a1, a2 = (
        softmax_map(q[..., i, :], k2[:, :, :, i], vv, window, mode)
        for i in (0, 1)
    )
    lam = (
        jnp.exp(jnp.sum(p["lambda_q1"]["b"] * p["lambda_k1"]["b"]))
        - jnp.exp(jnp.sum(p["lambda_q2"]["b"] * p["lambda_k2"]["b"]))
        + lambda_init(index)
    )
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg["layer_norm_eps"]
    ) * p["subln"]["scale"] * (1.0 - lambda_init(index))
    return _dense(o.reshape(B, T, H * d), p["o"], mode), (k, v)


def layer_kind(index: int, num_layers: int) -> str:
    half = num_layers // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "gmu"
    if index < half:
        return "window"
    return "full" if index == half + 1 else "cross"


def logits_fn(params, ids, cfg, mode=None):
    """[B,T] ids -> [B,T,V] logits."""
    eps, L = cfg["layer_norm_eps"], cfg["published_num_hidden_layers"]
    x = params["tok_emb"]["table"][ids]
    memory = kv = None
    for j, index in enumerate(cfg["layers"]):
        kind = layer_kind(index, L)

        @jax.checkpoint
        def layer(x, p, memory, kv, kind=kind, index=index):
            h = _ln(x, p["norm1"], eps)
            if kind == "mamba":
                out, y = _mamba(h, p["mixer"], cfg, mode)
                memory = y if index == L // 2 else memory
            elif kind == "gmu":
                out = _gmu(h, p["mixer"], memory, mode)
            else:
                out, own = _diff_attn(
                    h, p["mixer"], cfg, index,
                    cfg["sliding_window"] if kind == "window" else None,
                    kv if kind == "cross" else None, mode,
                )
                kv = own if index == L // 2 + 1 else kv
            x = x + out
            x = x + _swiglu(_ln(x, p["norm2"], eps), p["mlp"], mode)
            return x, memory, kv

        x, memory, kv = layer(x, params["blocks"][str(j)], memory, kv)
    x = _ln(x, params["norm_f"], eps)
    return lower(x, mode, -1) @ lower(params["tok_emb"]["table"], mode, 1).T


def loss_fn(params, ids, cfg, mode=None):
    """Mean next-token cross-entropy of ``ids`` [B,T+1]."""
    logits = logits_fn(params, ids[:, :-1], cfg, mode)
    logz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - ll)


def loss_and_grads(params, ids, cfg, rows_per_block, mode=None):
    """Loss and gradient of the mean over all rows, rows going through
    in blocks so that the activations of one block are all that lives:
    one gradient through a scan over the blocks, each recomputed in the
    backward pass, so that the blocks' gradients are summed leaf by leaf
    and no second gradient tree lives beside the sum."""
    n = ids.shape[0] // rows_per_block
    blocks = ids[: n * rows_per_block].reshape(n, rows_per_block, -1)

    def mean_loss(params):
        @jax.checkpoint
        def body(total, rows):
            return total + loss_fn(params, rows, cfg, mode) / n, None

        return jax.lax.scan(body, jnp.zeros((), jnp.float32), blocks)[0]

    return jax.value_and_grad(mean_loss)(params)
