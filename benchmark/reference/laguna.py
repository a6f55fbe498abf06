"""Plain Laguna (poolside/Laguna-S-2.1, ``model_type`` ``laguna``) in
float32 ``jax.numpy``: forward pass, next-token cross-entropy, its
gradients (``jax.vjp`` a layer at a time), global-norm clipping and
Adam. No kernels,
nothing of ``tensorlink_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

Layer i, pre-norm, ``h = x + Attn_i(RMSNorm(x)); y = h + FFN_i(RMSNorm(h))``,
RMSNorm with a gain, no bias anywhere, untied head after a final RMSNorm.
Attention of layer i, H = ``num_attention_heads_per_layer[i]`` query
heads on G = ``num_key_value_heads`` of d = ``head_dim``, query head h on
key head h // (H / G):
    q, k, v = x W_q, x W_k, x W_v;  q, k rotated by the layer kind's
        ``rope_parameters``: the first ``partial_rotary_factor`` of a
        head's dimensions, rotate-half pairing among them, the rest
        passing through; inverse frequencies theta^(-2j/dim), or YaRN's
        blend of those and of them over ``factor`` (HF's
        ``_compute_yarn_parameters``), cos and sin times
        ``attention_factor``
    o_h = sigmoid(x W_g)_h softmax(q_h k^T / sqrt(d) + mask) v
        mask causal, and in a ``sliding_attention`` layer k > q - window
    out = concat_h(o_h) W_o
Feed-forward: ``dense`` layers a SwiGLU, ``sparse`` layers the
sigmoid-routed experts: s = sigmoid(x W_r) over the whole router, the top
``num_experts_per_tok`` of s + bias (the bias chooses and gets no
gradient), w = scale * s / sum_chosen s,
``y = sum_{chosen and held} w_e SwiGLU_e(x) + SwiGLU_shared(x)``, of which
``num_experts`` experts are held here from
``deployment_share.first_expert`` (the router stays
``deployment_share.router_width`` wide); what the absent experts would
add is left out, as in the program.
The weights arrive as the benchmark's own tree (``benchmark/weights.py``):
  tok_emb/table [V,D]  norm_f/scale  lm_head/w [D,V]
  blocks/<i>/{norm1,norm2}/scale
  blocks/<i>/attn/{q [D,Hd], k, v [D,Gd], o [Hd,D], g [D,H]}/w
  blocks/<i>/mlp (dense): {up,gate,down}/w
  blocks/<i>/mlp (sparse): router/{w [D,256], bias [256]}
      experts/{up,gate}/w [D,E,F] experts/down/w [F,E,D]
      shared/{up,gate,down}/w

Departures of form, not of mathematics, so that the step fits one chip
beside the weights and both moments (9.1 GiB at the cell's size): the
gradient is taken by hand, a row at a time and a layer at a time (each
layer's ``jax.vjp`` from the layer's input, kept from a forward pass of
the row), and summed into one gradient tree, so that no second tree
lives beside it; attention goes through in blocks of queries, a window
layer's block against the keys its band can reach; the expert layer is a
loop (a scan) over the held experts with a mask; ``adam_step`` gives the
clipped gradient's norm by leaf, which is all the check reads of it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt2
from benchmark.reference.kimi_linear import _dense, _rms, _swiglu
from benchmark.reference.quant import lower

QUERY_BLOCK = 256


def rope_frequencies(spec: dict, head_dim: int):
    """-> (rotated dimensions, inverse frequencies [dims / 2] float32,
    cos / sin factor) for one layer kind's ``rope_parameters``."""
    f32 = np.float32
    dim = int(head_dim * spec.get("partial_rotary_factor", 1.0))
    base = spec["rope_theta"]
    pos = f32(base) ** (np.arange(0, dim, 2, dtype=f32) / f32(dim))
    if spec.get("rope_type", "default") == "default":
        return dim, 1 / pos, 1.0
    if spec["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {spec['rope_type']!r} here")
    factor = spec["factor"]
    orig = spec["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(spec.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(spec.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=f32) - low) / f32(high - low), 0, 1)
    extrapolate = 1 - ramp
    inv = (1 / (factor * pos)) * (1 - extrapolate) + (1 / pos) * extrapolate
    scale = spec.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return dim, inv.astype(f32), float(scale)


def rotate(x, spec: dict):
    """x [B,T,heads,d] at positions 0..T-1."""
    T, d = x.shape[1], x.shape[-1]
    dim, inv, scale = rope_frequencies(spec, d)
    half = dim // 2
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(angles) * scale)[None, :, None]
    sin = (jnp.sin(angles) * scale)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dim:]], -1)


def softmax_map(q, k, v, window, mode):
    """softmax(q k^T / sqrt(d) + mask) v, causal, over the last
    ``window`` keys (None: all). q [B,T,G,r,d]: r query heads on each of
    the G key heads; k, v [B,T,G,d] -> [B,T,G,r,d]. A block of queries
    meets every key, or in a window the ``window`` keys before it and
    its own (the keys before the first are padding, masked)."""
    B, T, G, r, d = q.shape
    qb = math.gcd(QUERY_BLOCK, T)
    pad = 0 if window is None else window
    span = T if window is None else qb + window
    kp, vp = (jnp.pad(a, ((0, 0), (pad, 0), (0, 0), (0, 0))) for a in (k, v))

    @jax.checkpoint
    def block(_, xs):
        q_i, start = xs  # [B, qb, G, r, d]
        first = 0 if window is None else start  # in the padded keys
        k_i, v_i = (
            jax.lax.dynamic_slice_in_dim(a, first, span, 1) for a in (kp, vp))
        s = jnp.einsum(
            "bqgrd,bkgd->bgrqk", lower(q_i, mode), lower(k_i, mode)
        ) / math.sqrt(d)
        qpos = (start + jnp.arange(qb))[:, None]
        kpos = (first - pad + jnp.arange(span))[None]
        keep = (qpos >= kpos) & (kpos >= 0)
        if window is not None:
            keep = keep & (kpos > qpos - window)
        s = jnp.where(keep, s, -jnp.inf)
        return None, jnp.einsum(
            "bgrqk,bkgd->bqgrd", lower(jax.nn.softmax(s, -1), mode),
            lower(v_i, mode, 1),
        )

    qs = jnp.moveaxis(q.reshape(B, T // qb, qb, G, r, d), 1, 0)
    _, o = jax.lax.scan(block, None, (qs, jnp.arange(T // qb) * qb))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, G, r, d)


def _attention(x, p, cfg, layer, mode):
    """One key head and the query heads it serves at a time (a scan over
    the key heads, each recomputed in the backward pass), the heads'
    outputs then through ``o`` together."""
    kind = cfg["layer_types"][layer]
    H, G = cfg["num_attention_heads_per_layer"][layer], cfg["num_key_value_heads"]
    d, r = cfg["head_dim"], H // G
    B, T, _ = x.shape
    spec = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None

    def cols(name, g, width):  # the group's columns of a projection
        return {"w": jax.lax.dynamic_slice_in_dim(p[name]["w"], g * width, width, 1)}

    @jax.checkpoint
    def group(_, g):
        q = rotate(_dense(x, cols("q", g, r * d), mode).reshape(B, T, r, d), spec)
        k = rotate(_dense(x, cols("k", g, d), mode).reshape(B, T, 1, d), spec)
        v = _dense(x, cols("v", g, d), mode).reshape(B, T, 1, d)
        o = softmax_map(q.reshape(B, T, 1, r, d), k, v, window, mode)
        gate = jax.nn.sigmoid(_dense(x, cols("g", g, r), mode))  # [B, T, r]
        return None, (o.reshape(B, T, r, d) * gate[..., None]).reshape(B, T, r * d)

    _, o = jax.lax.scan(group, None, jnp.arange(G))  # [G, B, T, r d]
    return _dense(jnp.moveaxis(o, 0, 2).reshape(B, T, H * d), p["o"], mode)


def route(x, p, cfg, mode=None):
    """-> (chosen [.., E_all] bool, weight [.., E_all]) over the whole
    router: sigmoid scores, the top ``num_experts_per_tok`` of score +
    bias (the bias chooses and gets no gradient), the chosen scores
    renormalised (``norm_topk_prob``) and scaled."""
    s = jax.nn.sigmoid(_dense(x, p["router"], mode))
    pick = jax.lax.stop_gradient(s + p["router"]["bias"])
    idx = jax.lax.top_k(pick, cfg["num_experts_per_tok"])[1]
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), -2) > 0
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w * cfg["moe_routed_scaling_factor"]


def _experts(x, p, cfg, mode):
    """Every token through every held expert, one expert after another
    (a loop over the held experts, each one's weights read where they
    lie), each weighed by the router: 0 where it was not chosen; the
    shared expert beside them."""
    first = cfg.get("deployment_share", {}).get("first_expert", 0)
    _, w = route(x, p, cfg, mode)
    held = p["experts"]["up"]["w"].shape[1]
    w = w[..., first:first + held]

    @jax.checkpoint
    def one(e, y):
        weights = {
            n: {"w": jax.lax.dynamic_index_in_dim(
                p["experts"][n]["w"], e, 1, keepdims=False)}
            for n in ("up", "gate", "down")
        }
        w_e = jax.lax.dynamic_index_in_dim(w, e, -1, keepdims=False)
        return y + w_e[..., None] * _swiglu(x, weights, mode)

    return jax.lax.fori_loop(0, held, one, _swiglu(x, p["shared"], mode))


def _block(p, x, cfg, i, mode):
    """Layer i: ``h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))``."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p["norm1"]["scale"], eps), p["attn"], cfg, i, mode)
    h = _rms(x, p["norm2"]["scale"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + _swiglu(h, p["mlp"], mode)
    return x + _experts(h, p["mlp"], cfg, mode)


def _head(p, x, cfg, mode):
    x = _rms(x, p["norm_f"]["scale"], cfg["rms_norm_eps"])
    return _dense(x, p["lm_head"], mode)


def _nll(logits, labels):
    """Cross-entropy of each token."""
    logz = jax.nn.logsumexp(logits, -1)
    return logz - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]


def logits_fn(params, ids, cfg, mode=None):
    """[B,T] ids -> [B,T,V] logits."""
    x = params["tok_emb"]["table"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(params["blocks"][str(i)], x, cfg, i, mode)
    return _head(params, x, cfg, mode)


def loss_fn(params, ids, cfg, mode=None):
    """Mean next-token cross-entropy of ``ids`` [B,T+1]."""
    return jnp.mean(_nll(logits_fn(params, ids[:, :-1], cfg, mode), ids[:, 1:]))


def loss_and_grads(params, ids, cfg, rows_per_block, mode=None):
    """Loss and gradient of the mean over every token of ``ids``
    [B,T+1], by hand: a row at a time (``rows_per_block`` is not
    needed: one row's activations are what lives), a forward pass that
    keeps each layer's input, then the head's and each layer's
    ``jax.vjp`` from its kept input, last layer first, each added into
    one gradient tree as it comes. An optimization barrier ties a
    layer's recompute to the cotangent coming back into it, so that the
    compiler cannot hoist every layer's forward into one pass and keep
    all their residuals."""
    del rows_per_block
    L = cfg["num_hidden_layers"]
    rows = ids.shape[0]
    scale = 1.0 / (rows * (ids.shape[1] - 1))
    head = {"norm_f": params["norm_f"], "lm_head": params["lm_head"]}

    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    def row(r, carry):
        total, grads = carry
        ids_r = jax.lax.dynamic_slice_in_dim(ids, r, 1, 0)
        inputs, labels = ids_r[:, :-1], ids_r[:, 1:]
        xs = [params["tok_emb"]["table"][inputs]]
        for i in range(L):
            xs.append(_block(params["blocks"][str(i)], xs[-1], cfg, i, mode))
        loss, back = jax.vjp(
            lambda p, x: scale * jnp.sum(_nll(_head(p, x, cfg, mode), labels)),
            head, xs[-1],
        )
        d_head, dx = back(jnp.ones_like(loss))
        grads = {**grads, **add({k: grads[k] for k in head}, d_head)}
        blocks = dict(grads["blocks"])
        for i in reversed(range(L)):
            x_i, dx = jax.lax.optimization_barrier((xs[i], dx))
            _, back = jax.vjp(
                lambda p, x, i=i: _block(p, x, cfg, i, mode),
                params["blocks"][str(i)], x_i,
            )
            d_p, dx = back(dx)
            blocks[str(i)] = add(blocks[str(i)], d_p)
        table = grads["tok_emb"]["table"].at[inputs[0]].add(dx[0])
        grads = {**grads, "blocks": blocks, "tok_emb": {"table": table}}
        return total + loss, grads

    zeros = jax.tree.map(jnp.zeros_like, params)
    return jax.lax.fori_loop(0, rows, row, (jnp.zeros((), jnp.float32), zeros))


def adam_step(params, m, v, grads, t, hp):
    """One Adam step as the configuration states it (as
    ``reference/gpt2.py`` takes it): clip the gradient to ``clip_norm``
    by its global norm, bias-corrected moments, no weight decay; ``t``
    counts from 1. Each leaf's clipped gradient is formed where its
    moments are updated and nowhere else, and in its place this gives
    that gradient's norm by leaf (a scalar under the leaf's own path),
    which is all the check reads of it: a clipped tree beside the
    gradient does not fit the chip at the cell's size. Returns the
    global norm before clipping too."""
    b1, b2, eps, lr = hp["b1"], hp["b2"], hp["eps"], hp["learning_rate"]
    norm = gpt2.global_norm(grads)
    scale = jnp.minimum(1.0, hp["clip_norm"] / (norm + 1e-9))
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def leaf(p, a, b, g):
        g = g * scale
        a = b1 * a + (1 - b1) * g
        b = b2 * b + (1 - b2) * g * g
        return p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps), a, b

    new = jax.tree.map(leaf, params, m, v, grads)
    pick = [jax.tree.map(lambda t3, k=k: t3[k], new,
                         is_leaf=lambda x: isinstance(x, tuple))
            for k in range(3)]
    norms = jax.tree.map(
        lambda g: scale * jnp.sqrt(jnp.sum(jnp.square(g))), grads)
    return (*pick, norms, norm)
