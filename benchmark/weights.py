"""Weights from the seed, made by the benchmark and by nobody else.

One rule per leaf, keyed by the leaf's path, so that the program's tree
and the plain reference can each be given the same numbers without
either one taking anything the other has made: the program gets the
whole tree in one jitted call (matrices in the type they are served or
trained in, never a float32 copy of a model that is served in bf16),
and the reference asks for single leaves again, layer by layer, after
the program's state is freed.

A leaf's values depend on (seed, path, shape) only:
  ``.../w``      normal / sqrt(fan_in)      (projection matrices)
  ``.../table``  normal * 0.02              (embeddings)
  ``.../scale``  1 + 0.1 * normal           (norm gains, kept float32)
  ``.../b``, ``.../bias``  0.02 * normal    (kept float32)
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def salt(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def make_leaf(key, path: str, shape, matrix_dtype=jnp.float32, salt_=None):
    """The one rule. Traceable; ``path`` and ``shape`` are static.
    ``salt_`` (default ``salt(path)``) may be a traced scalar, so that
    one compiled program makes the same leaf of any layer."""
    k = jax.random.fold_in(key, salt(path) if salt_ is None else salt_)
    name = path.rsplit("/", 1)[-1]
    x = jax.random.normal(k, shape, jnp.float32)
    if name == "scale":
        return 1.0 + 0.1 * x
    if name in ("b", "bias"):
        return 0.02 * x
    if name == "table":
        return (0.02 * x).astype(matrix_dtype)
    if name == "w":
        return (x * (1.0 / math.sqrt(shape[0]))).astype(matrix_dtype)
    raise ValueError(f"no rule for leaf {path!r}")


def make_tree(seed: int, shapes, matrix_dtype=jnp.float32):
    """The whole tree in ONE jitted call on the device. ``shapes`` is a
    pytree of ShapeDtypeStruct (``jax.eval_shape`` of the model's own
    ``init`` gives it: shapes are not numbers)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [(path_str(p), tuple(s.shape)) for p, s in flat]

    @jax.jit
    def build(key):
        return [make_leaf(key, p, s, matrix_dtype) for p, s in paths]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
