"""Plain Llama-shaped decoder (Touvron et al. 2023; Mistral-7B, Jiang et
al. 2023) in float32 ``jax.numpy``: RMSNorm, rotary positions in the
half-split ("rotate_half") form, grouped-query attention with a causal
sliding-window mask, SwiGLU, untied head. No kernels, no cache, no
batching, nothing of ``tensorlink_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

It never holds the model: the weights of one layer are made again from
the seed (``benchmark/weights.py``, rounded to the served type first,
since those rounded values ARE the model that is served), used for all
the sequences, and dropped. One compiled layer serves every layer.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference.quant import lower

LAYER_LEAVES = (
    ("norm1/scale", lambda c: (c["hidden_size"],)),
    ("norm2/scale", lambda c: (c["hidden_size"],)),
    ("attn/q/w", lambda c: (c["hidden_size"], c["num_attention_heads"] * c["head_dim"])),
    ("attn/k/w", lambda c: (c["hidden_size"], c["num_key_value_heads"] * c["head_dim"])),
    ("attn/v/w", lambda c: (c["hidden_size"], c["num_key_value_heads"] * c["head_dim"])),
    ("attn/o/w", lambda c: (c["num_attention_heads"] * c["head_dim"], c["hidden_size"])),
    ("mlp/up/w", lambda c: (c["hidden_size"], c["intermediate_size"])),
    ("mlp/gate/w", lambda c: (c["hidden_size"], c["intermediate_size"])),
    ("mlp/down/w", lambda c: (c["intermediate_size"], c["hidden_size"])),
)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B,T,H,D], positions 0..T-1; pairs (i, i + D/2) rotate."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(x, w, cfg, mode=None):
    B, T, _ = x.shape
    H, G, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta, window = cfg["rms_norm_eps"], cfg["rope_theta"], cfg.get("sliding_window")

    def mm(a, m):
        return lower(a, mode, -1) @ lower(m, mode, 0)

    h = _rms(x, w["norm1/scale"], eps)
    q = _rope(mm(h, w["attn/q/w"]).reshape(B, T, H, hd), theta)
    k = _rope(mm(h, w["attn/k/w"]).reshape(B, T, G, hd), theta)
    v = mm(h, w["attn/v/w"]).reshape(B, T, G, hd)
    # query head h reads key/value head h // (H/G)
    q = q.reshape(B, T, G, H // G, hd)
    s = jnp.einsum(
        "bqgrd,bkgd->bgrqk", lower(q, mode), lower(k, mode)
    ) / math.sqrt(hd)
    qp, kp = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = kp <= qp
    if window:
        keep &= kp > qp - window
    s = jnp.where(keep, s, -jnp.inf)
    a = jnp.einsum(
        "bgrqk,bkgd->bqgrd", lower(jax.nn.softmax(s, -1), mode),
        lower(v, mode, 1),
    ).reshape(B, T, H * hd)
    x = x + mm(a, w["attn/o/w"])
    h = _rms(x, w["norm2/scale"], eps)
    h = jax.nn.silu(mm(h, w["mlp/gate/w"])) * mm(h, w["mlp/up/w"])
    return x + mm(h, w["mlp/down/w"])


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, served_dtype: str, mode):
    """The jitted pieces for one configuration (kept across calls: the
    control script scores many seeds in one process)."""
    cfg = json.loads(cfg_json)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    dtype = jnp.dtype(served_dtype)

    def leaf(key, path, shape, salt_=None):
        return weights.make_leaf(key, path, shape, dtype, salt_).astype(
            jnp.float32
        )

    @jax.jit
    def embed(key, ids):
        return leaf(key, "tok_emb/table", (V, D))[ids]

    @jax.jit
    def layer_weights(key, salts):
        return {
            name: leaf(key, name, shape(cfg), salts[j])
            for j, (name, shape) in enumerate(LAYER_LEAVES)
        }

    @jax.jit
    def apply_layer(x, w):
        return layer(x, w, cfg, mode)

    @jax.jit
    def head_weights(key):
        return leaf(key, "norm_f/scale", (D,)), leaf(key, "lm_head/w", (D, V))

    @functools.partial(jax.jit, static_argnames="n_out")
    def head(x, start, norm, w, n_out):
        x = jax.lax.dynamic_slice_in_dim(x, start, n_out)
        h = _rms(x, norm, cfg["rms_norm_eps"])
        return lower(h, mode, -1) @ lower(w, mode, 0)

    return embed, layer_weights, apply_layer, head_weights, head


OUT_BLOCK = 128  # the slice of scored positions is rounded up to this
LEN_BLOCK = 512  # and a sequence's length to this, to keep shapes few


def score(cfg, seed, seqs, n_prompt, *, served_dtype="bfloat16", mode=None):
    """For each sequence (prompt then served tokens) the logits that
    predict its served tokens: a list of [n_served, V] float32 arrays.
    ``mode`` None is the reference; "int8"/"fp8" the control.

    Layer by layer: one layer's weights are made once and every
    sequence goes through them, each at its own length rounded up to
    LEN_BLOCK (a few shapes, so that later runs find them compiled)."""
    key = weights.seed_key(seed)
    keep = {k: v for k, v in cfg.items() if not isinstance(v, (dict, list))}
    embed, layer_weights, apply_layer, head_weights, head = _programs(
        json.dumps(keep, sort_keys=True), served_dtype, mode
    )
    n_out = max(len(s) - n for s, n in zip(seqs, n_prompt))
    n_out = -(-n_out // OUT_BLOCK) * OUT_BLOCK
    xs = []
    for s, n in zip(seqs, n_prompt):
        # room for a whole slice from the last prompt position on
        T = -(-(n - 1 + n_out) // LEN_BLOCK) * LEN_BLOCK
        ids = np.zeros((1, T), np.int32)
        ids[0, : len(s)] = s
        xs.append(embed(key, jnp.asarray(ids)))
    for i in range(cfg["num_hidden_layers"]):
        salts = jnp.asarray(
            [weights.salt(f"blocks/{i}/{name}") for name, _ in LAYER_LEAVES],
            jnp.int32,
        )
        w = layer_weights(key, salts)
        xs = [apply_layer(x, w) for x in xs]
    del w
    norm, w_head = head_weights(key)
    out = []
    for x, s, n in zip(xs, seqs, n_prompt):
        # position p predicts token p + 1
        logits = np.asarray(head(x[0], n - 1, norm, w_head, n_out))
        out.append(logits[: len(s) - n])
    return out
