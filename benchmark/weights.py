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

One leaf's rule is not the last word: a routed expert layer's selection
bias (``.../router/bias``). The ``train`` driver replaces what the rule
gives it, before the first step, by the bias that evens the experts'
load on the run's first batch (``benchmark/balance.py``), and lays
those same numbers over every tree it makes from the seed: the
program's, the reference's, the start that changes are taken from. The
reference takes numbers and no code. Any bias is a valid weight, so a
fault in the program's forward pass, through which the scores are
taken, changes at which weights the two are compared and hides no gap.
"""

from __future__ import annotations

import functools
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


@functools.cache
def _builder(leaves: tuple, matrix_dtype):
    """The one program that makes ``leaves`` ((path, shape) each), kept
    for the life of the process: a run makes the same tree twice in
    set-up (the program's weights, the start its changes are taken from)
    and twice more for the reference, and a program made anew each time
    is traced, lowered and read from the cache each time: 4 s of the
    host's for the Kimi tree. The loaded program stays on the device,
    17 to 19 MB that ``memory_peak_bytes`` now holds (PERF.md section 6,
    PR 34)."""

    @jax.jit
    def build(key):
        return [make_leaf(key, p, s, matrix_dtype) for p, s in leaves]

    return build


def make_tree(seed: int, shapes, matrix_dtype=jnp.float32):
    """The whole tree in ONE jitted call on the device. ``shapes`` is a
    pytree of ShapeDtypeStruct (``jax.eval_shape`` of the model's own
    ``init`` gives it: shapes are not numbers)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = tuple((path_str(p), tuple(s.shape)) for p, s in flat)
    return jax.tree_util.tree_unflatten(
        treedef, _builder(leaves, matrix_dtype)(seed_key(seed))
    )
