"""Weight-only int8 quantization for serving.

The decode phase of autoregressive inference is memory-bound: every
generated token re-reads every weight matrix from HBM. Storing Dense
weights as int8 with a per-output-channel scale cuts that traffic 2x
(vs bf16) to 4x (vs f32); the dequantize multiply fuses into the matmul
under XLA, and activations/accumulation stay in the compute dtype, so
quality loss is the per-channel rounding error only (symmetric absmax,
~0.4% relative on typical layers).

Scope: 2-D ``{"w": ...}`` leaves of Dense-shaped subtrees (matmul
weights — where the bytes are), plus the paged KV-block form
(``quantize_kv_int8``/``dequantize_kv`` — per-token-slot, per-kv-head
scales riding the block pools as sibling arrays, see
``nn/attention.py init_paged_cache(quant="int8")``). Embeddings, norms,
biases, and contiguous KV caches stay in their original dtypes.
Training is unaffected: quantize at serving time (InferenceEngine
``quantize="int8"``), never in the optimizer loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_weight_int8(w) -> dict:
    """[in, out] float -> {"q": int8 [in, out], "s": f32 [out]} with a
    symmetric per-output-channel absmax scale."""
    w = jnp.asarray(w)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127).astype(
        jnp.int8
    )
    return {"q": q, "s": s.astype(jnp.float32)}


def dequantize_weight(qw: dict, dtype=jnp.float32):
    return (qw["q"].astype(dtype) * qw["s"].astype(dtype))


def quantize_kv_int8(x):
    """[..., D] float -> (int8 [..., D], f32 scale [...]) with a
    symmetric per-vector absmax scale — one scale per (token slot,
    kv head), computable at cache-WRITE time from the fresh k/v alone
    (no pool read-modify), which is what lets the paged decode/prefill
    programs quantize in place. Same scale convention as
    ``quantize_weight_int8``; a zero vector takes scale 1.0 so it
    round-trips to exact zeros."""
    xf = jnp.asarray(x).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype=jnp.float32):
    """Inverse of ``quantize_kv_int8``: int8 [..., D] + scale [...] ->
    ``dtype`` [..., D] (f32 multiply, then one cast — the form both the
    XLA paged fallback and the Pallas kernel share)."""
    return (
        q.astype(jnp.float32) * s[..., None].astype(jnp.float32)
    ).astype(dtype)


def quantize_params_int8(module, params):
    """Quantize the ``w`` of every Dense submodule of ``module``,
    walking the MODULE tree in lockstep with the param tree — only
    Dense.apply understands the {"q", "s"} form, so a path heuristic
    over the params alone would also catch look-alike 2-D ``w`` leaves
    that other code reads as raw arrays (the MoE router's
    ``params["router"]["w"]``, T5's relative-bias table — review
    finding: quantizing those crashes serving). Everything that is not
    a Dense weight passes through untouched."""
    from tensorlink_tpu.nn.layers import Dense

    if isinstance(module, Dense):
        w = params.get("w")
        if (
            hasattr(w, "ndim") and w.ndim == 2
            and jnp.issubdtype(jnp.asarray(w).dtype, jnp.floating)
        ):
            return {**params, "w": quantize_weight_int8(w)}
        return params
    out = dict(params) if isinstance(params, dict) else params
    for name, child in getattr(module, "children", {}).items():
        if isinstance(params, dict) and name in params:
            out[name] = quantize_params_int8(child, params[name])
    return out


def is_quantized(params) -> bool:
    """True if the tree contains any {"q", "s"} quantized-weight dicts."""
    found = False

    def walk(t):
        nonlocal found
        if isinstance(t, dict):
            if set(t) == {"q", "s"}:
                found = True
                return
            for v in t.values():
                walk(v)

    walk(params)
    return found


def quantized_random_init(module, key, dtype=jnp.bfloat16):
    """Random-init a model DIRECTLY in int8-quantized serving form —
    never materializing the float weights.

    An 8B-parameter model is ~32 GB in f32: `model.init` + quantize
    would blow both host RAM and a 16 GB v5e before serving could
    start, while the int8 form (~8.5 GB) fits. Dense 2-D weights become
    {"q": uniform int8, "s": per-channel scale such that the effective
    weight std matches LeCun 1/sqrt(fan_in)} (uniform[-127,127] has std
    ~73.3); Dense biases are zeros; norm gains (leaves named ``scale``)
    are ONES, matching the real init — a normal(0, 0.02) draw there
    multiplies every layer's activations by ~0.02 and collapses the
    forward pass ~50x per layer (ADVICE r5); every other leaf
    (embeddings, biases elsewhere) is a normal(0, 0.02) draw in
    ``dtype``, created leaf-by-leaf on device. Intended for serving
    benchmarks and capacity tests
    (random weights, real shapes/dtypes/layout); real checkpoints go
    through quantize_params_int8."""
    import numpy as np

    from tensorlink_tpu.nn.layers import Dense

    shapes = jax.eval_shape(module.init, key)

    def leaf_normal(k, shp, std=0.02):
        # module-level jits: one compile per distinct (shape, dtype) —
        # a per-leaf lambda compiled FRESH for every leaf (~150 compiles
        # for the 8B init); the cached form does it in the ~15 distinct
        # shapes
        return _normal_leaf(k, tuple(shp), jnp.dtype(dtype), float(std))

    def walk(mod, shp, k):
        if isinstance(mod, Dense):
            out = {}
            for name, leaf in shp.items():
                k, k1 = jax.random.split(k)
                if name == "w" and leaf.ndim == 2:
                    fan_in, fan_out = leaf.shape
                    s_val = 1.0 / (73.3 * float(np.sqrt(fan_in)))
                    out["w"] = {
                        "q": _int8_leaf(k1, tuple(leaf.shape)),
                        "s": jnp.full((fan_out,), s_val, jnp.float32),
                    }
                else:
                    out[name] = jnp.zeros(leaf.shape, dtype)
            return out
        if isinstance(shp, dict):
            out = {}
            children = getattr(mod, "children", {})
            for name, sub in shp.items():
                k, k1 = jax.random.split(k)
                if name in children:
                    out[name] = walk(children[name], sub, k1)
                elif isinstance(sub, dict):
                    out[name] = walk(mod, sub, k1)
                elif name == "scale":
                    # norm gain: ones, as in the real init — random gains
                    # shrink activations ~50x per layer (module docstring)
                    out[name] = jnp.ones(sub.shape, dtype)
                else:
                    out[name] = leaf_normal(k1, sub.shape)
            return out
        return leaf_normal(k, shp.shape)

    return walk(module, shapes, key)


import functools as _functools


@_functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_leaf(k, shape, dtype, std):
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


@_functools.partial(jax.jit, static_argnums=(1,))
def _int8_leaf(k, shape):
    return jax.random.randint(k, shape, -127, 128, jnp.int8)


def quantized_spec_tree(spec_tree, params):
    """PartitionSpec tree matching a quantized param tree: ``q`` keeps
    the weight's spec; the per-output-channel ``s`` takes the spec of the
    weight's LAST axis (col-split weights shard their scales, row-split
    and replicated weights replicate them)."""

    def convert(spec, leaf):
        if isinstance(leaf, dict) and set(leaf) == {"q", "s"}:
            last = spec[-1] if isinstance(spec, P) and len(spec) else None
            return {"q": spec, "s": P(last)}
        return spec

    # walk both trees in lockstep (specs are a prefix-shaped tree of P
    # leaves; the quantized tree replaced some array leaves with dicts)
    def walk(spec, leaf):
        if isinstance(leaf, dict) and not (set(leaf) == {"q", "s"}):
            return {k: walk(spec[k], leaf[k]) for k in leaf}
        return convert(spec, leaf)

    return walk(spec_tree, params)


def quantization_report(params, qparams) -> dict:
    """Bytes before/after + worst per-layer relative error — the honest
    'what did int8 cost me' summary. Errors come from the ALREADY
    quantized leaves in ``qparams`` (no re-quantization pass)."""
    def nbytes(t):
        return sum(
            jnp.asarray(x).size * jnp.asarray(x).dtype.itemsize
            for x in jax.tree.leaves(t)
        )

    worst = 0.0

    def walk(orig, quant):
        nonlocal worst
        if isinstance(quant, dict) and set(quant) == {"q", "s"}:
            d = dequantize_weight(quant) - jnp.asarray(orig, jnp.float32)
            rel = float(
                jnp.linalg.norm(d) / (jnp.linalg.norm(orig) + 1e-12)
            )
            worst = max(worst, rel)
            return
        if isinstance(quant, dict):
            for k in quant:
                walk(orig[k], quant[k])

    walk(params, qparams)
    before, after = nbytes(params), nbytes(qparams)
    return {
        "bytes_before": int(before),
        "bytes_after": int(after),
        "compression": round(before / max(after, 1), 2),
        "worst_layer_rel_error": worst,
    }
