"""Plain Kimi-Linear (Kimi Team 2025, "Kimi Linear: an expressive,
efficient attention architecture"; HF ``KimiLinearForCausalLM``) in
float32 ``jax.numpy``: forward pass, next-token cross-entropy, its
gradients by ``jax.grad``, global-norm clipping and Adam. No kernels, no
chunking, nothing of ``tensorlink_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

Pre-norm blocks, RMSNorm, no bias on any projection, untied head. A
layer's mixer is Kimi Delta Attention (``linear_attn_config.kda_layers``,
1-based) or MLA without rotary (``full_attn_layers``); its feed-forward
is a dense SwiGLU (the first ``first_k_dense_replace`` layers) or the
sigmoid-routed expert layer, of which ``num_experts`` experts are held
here, the first of them ``deployment_share.first_expert`` (the router
stays ``deployment_share.router_width`` wide). What the absent experts
would add is left out, as in the program.

KDA, a head (d = 128), token by token, state S [d_k, d_v], S_0 = 0:
    q~, k~, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
    q = q~ / |q~| / sqrt(d_k),  k = k~ / |k~|
    g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)     (per channel)
    beta = sigmoid(x W_beta)
    S' = exp(g)[:, None] * S
    S  = S' + beta * k (v - k^T S')^T
    o  = S^T q
    y  = (rmsnorm(o) * sigmoid((x W_ga) W_gb)) W_o
The weights arrive as the benchmark's own tree (``benchmark/weights.py``):
  tok_emb/table [V,D]  norm_f/scale  lm_head/w [D,V]
  blocks/<i>/{norm1,norm2}/scale
  blocks/<i>/mixer (KDA): {q,k,v}/w [D,H*d]  {q,k,v}_conv/w [4,H*d]
      f_a/w [D,d] f_b/w [d,H*d] A_log/b [H] dt_bias/b [H*d] beta/w [D,H]
      g_a/w [D,d] g_b/w [d,H*d] o_norm/scale [d] o/w [H*d,D]
  blocks/<i>/mixer (MLA): q/w [D,H*192] kv_a/w [D,512+64] kv_norm/scale
      [512] kv_b/w [512,H*256] o/w [H*128,D]
  blocks/<i>/mlp (dense): {up,gate,down}/w
  blocks/<i>/mlp (experts): router/{w [D,256], bias [256]}
      experts/{up,gate}/w [D,E,F] experts/down/w [F,E,D]
      shared/{up,gate,down}/w

Departures of form, not of mathematics: the recurrence runs under
``lax.scan`` with ``jax.checkpoint`` around each 64 tokens, and each
layer is rematerialised, so that a row of 4,096 tokens fits beside the
weights, gradients and moments; attention goes through in blocks of
queries; the expert layer is a loop (a scan) over the held experts with a
mask; rows go through in blocks (``rows_per_block``) with the gradient
summed.
The selection bias gets no gradient (``assumed`` in the configuration).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import adam_step, global_norm  # noqa: F401
from benchmark.reference.quant import lower

SEGMENT = 64  # tokens of the recurrence under one jax.checkpoint
QUERY_BLOCK = 512


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dense(x, p, mode):
    return lower(x, mode, -1) @ lower(p["w"], mode, 0)


def _swiglu(x, p, mode):
    h = jax.nn.silu(_dense(x, p["gate"], mode)) * _dense(x, p["up"], mode)
    return _dense(h, p["down"], mode)


def _conv(x, w):
    """Causal depthwise convolution along time: tap j of ``w`` [K, C]
    meets x[t - (K - 1) + j]."""
    K = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(K))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. q, k, g [B,T,H,dk]; v [B,T,H,dv];
    beta [B,T,H] -> o [B,T,H,dv]."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    seg = math.gcd(SEGMENT, T)

    def token(S, x):
        # sums over d_k as multiply-and-reduce: exact float32 on the
        # vector unit (a matmul of one row would cost six MXU passes)
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[..., None] * S
        u = b_t[..., None] * (v_t - jnp.sum(k_t[..., None] * S, -2))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.sum(q_t[..., None] * S, -2)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs, unroll=4)

    def by_time(x):  # [B,T,...] -> [T/seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(T // seg, seg, *x.shape[1:])

    S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    xs = tuple(map(by_time, (q, k, v, jnp.exp(g), beta)))
    _, o = jax.lax.scan(segment, S0, xs)
    return jnp.moveaxis(o.reshape(T, B, H, dv), 0, 1)


def _kda(x, p, cfg, mode):
    la = cfg["linear_attn_config"]
    H, d = la["num_heads"], la["head_dim"]
    B, T, _ = x.shape

    def branch(n):
        y = jax.nn.silu(_conv(_dense(x, p[n], mode), p[n + "_conv"]["w"]))
        return y.reshape(B, T, H, d)

    q, k, v = branch("q"), branch("k"), branch("v")
    q, k = _l2(q) * d ** -0.5, _l2(k)
    f = _dense(_dense(x, p["f_a"], mode), p["f_b"], mode) + p["dt_bias"]["b"]
    g = -jnp.exp(p["A_log"]["b"])[:, None] * jax.nn.softplus(
        f.reshape(B, T, H, d)
    )
    beta = jax.nn.sigmoid(_dense(x, p["beta"], mode))
    o = delta_rule(
        lower(q, mode), lower(k, mode), lower(v, mode), g, beta
    )
    o = _rms(o, p["o_norm"]["scale"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_dense(_dense(x, p["g_a"], mode), p["g_b"], mode))
    return _dense((o * gate.reshape(B, T, H, d)).reshape(B, T, H * d),
                  p["o"], mode)


def _mla(x, p, cfg, mode):
    H = cfg["num_attention_heads"]
    nope, rope, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    rank = cfg["kv_lora_rank"]
    B, T, _ = x.shape
    q = _dense(x, p["q"], mode).reshape(B, T, H, nope + rope)
    kv = _dense(x, p["kv_a"], mode)
    c, k_pe = kv[..., :rank], kv[..., rank:]
    c = _rms(c, p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    kv = _dense(c, p["kv_b"], mode).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([
        kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None], (B, T, H, rope))
    ], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    qb = math.gcd(QUERY_BLOCK, T)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(_, xs):
        q_i, start = xs  # [B, qb, H, D]
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", lower(q_i, mode), lower(k, mode)
        ) * scale
        keep = (start + jnp.arange(qb))[:, None] >= kpos[None]
        s = jnp.where(keep, s, -jnp.inf)
        return None, jnp.einsum(
            "bhqk,bkhd->bqhd", lower(jax.nn.softmax(s, -1), mode),
            lower(v, mode, 1),
        )

    qs = jnp.moveaxis(q.reshape(B, T // qb, qb, H, -1), 1, 0)
    _, o = jax.lax.scan(block, None, (qs, jnp.arange(T // qb) * qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv)
    return _dense(o, p["o"], mode)


def route(x, p, cfg, mode=None):
    """-> (chosen [.., E_all] bool, weight [.., E_all]) over the whole
    router: sigmoid scores, the top ``num_experts_per_token`` of score +
    bias (the bias chooses and gets no gradient), the chosen scores
    renormalised and scaled."""
    s = jax.nn.sigmoid(_dense(x, p["router"], mode))
    pick = jax.lax.stop_gradient(s + p["router"]["bias"])
    idx = jax.lax.top_k(pick, cfg["num_experts_per_token"])[1]
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), -2) > 0
    w = jnp.where(chosen, s, 0.0)
    if cfg.get("moe_renormalize", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w * cfg["routed_scaling_factor"]


def _experts(x, p, cfg, mode):
    """Every token through every held expert, one expert after another
    (a ``lax.scan`` over the stacked weights, so that one expert is
    compiled once), each weighed by the router: 0 where it was not
    chosen."""
    first = cfg.get("deployment_share", {}).get("first_expert", 0)
    _, w = route(x, p, cfg, mode)
    held = p["experts"]["up"]["w"].shape[1]
    stacked = {  # [D, E, F] -> [E, D, F]
        n: {"w": jnp.moveaxis(p["experts"][n]["w"], 1, 0)}
        for n in ("up", "gate", "down")
    }
    w = jnp.moveaxis(w[..., first:first + held], -1, 0)  # [E, ...]

    def one(y, e):
        weights, w_e = e
        return y + w_e[..., None] * _swiglu(x, weights, mode), None

    y, _ = jax.lax.scan(one, _swiglu(x, p["shared"], mode), (stacked, w))
    return y


def logits_fn(params, ids, cfg, mode=None):
    """[B,T] ids -> [B,T,V] logits."""
    eps = cfg["rms_norm_eps"]
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    x = params["tok_emb"]["table"][ids]
    for i in range(cfg["num_hidden_layers"]):
        mixer = _kda if i + 1 in kda else _mla
        ffn = _swiglu if i < cfg["first_k_dense_replace"] else None

        @jax.checkpoint
        def layer(x, p, mixer=mixer, ffn=ffn):
            x = x + mixer(_rms(x, p["norm1"]["scale"], eps), p["mixer"],
                          cfg, mode)
            h = _rms(x, p["norm2"]["scale"], eps)
            if ffn is not None:
                return x + ffn(h, p["mlp"], mode)
            return x + _experts(h, p["mlp"], cfg, mode)

        x = layer(x, params["blocks"][str(i)])
    x = _rms(x, params["norm_f"]["scale"], eps)
    return _dense(x, params["lm_head"], mode)


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
    and no second gradient tree lives beside the sum (with one the fp8
    control's step did not fit the chip)."""
    n = ids.shape[0] // rows_per_block
    blocks = ids[: n * rows_per_block].reshape(n, rows_per_block, -1)

    def mean_loss(params):
        @jax.checkpoint
        def body(total, rows):
            return total + loss_fn(params, rows, cfg, mode) / n, None

        return jax.lax.scan(body, jnp.zeros((), jnp.float32), blocks)[0]

    return jax.value_and_grad(mean_loss)(params)
