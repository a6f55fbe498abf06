"""Plain GPT-2 (Radford et al. 2019; HF ``GPT2LMHeadModel``) in
float32 ``jax.numpy``: forward pass, next-token cross-entropy, its
gradients by ``jax.grad``, global-norm clipping and Adam. No kernels,
no cache, nothing of ``tensorlink_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

Pre-LN blocks, learned positions, biases everywhere, tanh-approximated
GELU ("gelu_new"), LM head tied to the token table. The weights arrive
as the benchmark's own tree (``benchmark/weights.py``):
  wte/table [V,D]  wpe/table [P,D]  ln_f/{scale,bias}
  blocks/<i>/{norm1,norm2}/{scale,bias}
  blocks/<i>/attn/{q,k,v,o}/{w,b}   blocks/<i>/mlp/{up,down}/{w,b}
Layers run under ``lax.scan`` over the stacked blocks (one layer is
compiled once) and each is rematerialised in the backward pass, so a
step at the timed size fits beside nothing else on the chip; rows go
through in blocks (``rows_per_block``) with the gradient summed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.quant import lower


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p, mode):
    return lower(x, mode, -1) @ lower(p["w"], mode, 0) + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(
        math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def _block(x, p, heads, eps, mode):
    B, T, D = x.shape
    hd = D // heads
    h = _ln(x, p["norm1"], eps)
    q, k, v = (
        _dense(h, p["attn"][n], mode).reshape(B, T, heads, hd)
        for n in ("q", "k", "v")
    )
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", lower(q, mode), lower(k, mode)
    ) / math.sqrt(hd)
    keep = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(keep, s, -jnp.inf)
    a = jnp.einsum(
        "bhqk,bkhd->bqhd", lower(jax.nn.softmax(s, -1), mode),
        lower(v, mode, 1),
    ).reshape(B, T, D)
    x = x + _dense(a, p["attn"]["o"], mode)
    h = _ln(x, p["norm2"], eps)
    h = _gelu_new(_dense(h, p["mlp"]["up"], mode))
    return x + _dense(h, p["mlp"]["down"], mode)


def stack_blocks(blocks: dict):
    n = len(blocks)
    return jax.tree.map(
        lambda *xs: jnp.stack(xs), *(blocks[str(i)] for i in range(n))
    )


def logits_fn(params, ids, cfg, mode=None):
    """[B,T] ids -> [B,T,V] logits."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    T = ids.shape[1]
    x = params["wte"]["table"][ids] + params["wpe"]["table"][:T][None]
    blocks = {
        k: v for k, v in stack_blocks(params["blocks"]).items()
        if k in ("norm1", "norm2", "attn", "mlp")
    }
    blocks["mlp"] = {k: blocks["mlp"][k] for k in ("up", "down")}

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, heads, eps, mode), None

    x, _ = jax.lax.scan(body, x, blocks)
    x = _ln(x, params["ln_f"], eps)
    return lower(x, mode, -1) @ lower(params["wte"]["table"], mode, 1).T


def loss_fn(params, ids, cfg, mode=None):
    """Mean next-token cross-entropy of ``ids`` [B,T+1]."""
    logits = logits_fn(params, ids[:, :-1], cfg, mode)
    logz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - ll)


def loss_and_grads(params, ids, cfg, rows_per_block, mode=None):
    """Loss and gradient of the mean over all rows, rows going through
    in blocks so that the activations of one block are all that lives."""
    n = ids.shape[0] // rows_per_block
    blocks = ids[: n * rows_per_block].reshape(n, rows_per_block, -1)

    def body(acc, rows):
        loss, g = jax.value_and_grad(loss_fn)(params, rows, cfg, mode)
        return jax.tree.map(lambda a, b: a + b / n, acc, g), loss

    zero = jax.tree.map(jnp.zeros_like, params)
    grads, losses = jax.lax.scan(body, zero, blocks)
    return jnp.mean(losses), grads


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adam_step(params, m, v, grads, t, hp):
    """One Adam step as the configuration states it: clip the gradient
    to ``clip_norm`` by its global norm, bias-corrected moments, no
    weight decay. ``t`` counts from 1. Returns the clipped gradient too
    (it is what the optimizer gets) and the global norm before clipping."""
    b1, b2, eps, lr = hp["b1"], hp["b2"], hp["eps"], hp["learning_rate"]
    norm = global_norm(grads)
    if hp.get("clip_norm"):
        scale = jnp.minimum(1.0, hp["clip_norm"] / (norm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
        params, m, v,
    )
    return params, m, v, grads, norm
