"""Persistent XLA compilation cache.

JAX ships a content-addressed on-disk compilation cache: the cache key
hashes the optimized HLO + compile options + backend version, so a
restarted process (or a second node on identical hardware) that lowers
the same serving program loads the compiled executable from disk
instead of paying XLA all over again. The serving engines compile a
small, fixed program set (ONE decode/spec chunk + prefill buckets), so
a warm cache turns their multi-second cold start into file reads.

This module is the one switch for it:

- :func:`enable_compile_cache` turns the cache on, process-wide. WHERE
  it lives is decided outside the program first: when
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken that
  directory and this module sets none — an explicit argument,
  ``NodeConfig.compile_cache_dir`` or ``TL_COMPILE_CACHE_DIR`` asking
  for another one is recorded as a ``compile_cache.conflict`` event and
  not honored. Without it the directory is the explicit argument, then
  ``$TL_COMPILE_CACHE_DIR``, then :data:`DEFAULT_DIR` — one fixed path
  inside the checkout, because the path is part of what a cache hit
  depends on: a directory named after a pid, a time or a temporary
  file never hits. The cache is global, so the first directory wins.
  The min-size/min-compile-time floors are dropped so even the small
  CI/CPU programs cache (the defaults skip sub-second compiles —
  exactly the ones our tests can observe).
- :func:`cache_entries` counts on-disk entries; the serving engines
  diff it around each compile to label ``serving.compile`` flight
  events with ``compile_cache_hit`` (no new entry = the executable came
  from the cache) — the restart-reuses-kernels evidence a bench or an
  operator can read straight off ``/events``.

A ``None`` return means the directory could not be created; callers
then skip the bookkeeping (an unwritable directory must not take down
serving).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache.compilation_cache import reset_cache

from tensorlink_tpu.runtime.flight import default_recorder

__all__ = [
    "DEFAULT_DIR", "cache_entries", "enable_compile_cache",
    "runtime_fingerprint",
]

ENV_VAR = "TL_COMPILE_CACHE_DIR"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def runtime_fingerprint() -> dict:
    """The (jax version, chip) half of every persisted-tuning key: the
    same invariants XLA's own compile-cache key hashes. Shared by this
    cache's events and the autotune store (runtime/autotune.py) so the
    two warm-restart layers — compiled kernels and the measured
    constants that pick them — can never key on different facts. A
    process that cannot name its device has no business persisting
    tuning for one: the backend's error goes to the caller."""
    return {"jax": jax.__version__, "chip": jax.devices()[0].device_kind}


_active_dir: str | None = None


def enable_compile_cache(cache_dir: str | None = None, *,
                         recorder=None) -> str | None:
    """Turn on JAX's persistent compilation cache; returns the active
    directory (see the module docstring for how it is chosen), or None
    when it cannot be created. Idempotent; a later request for a
    different directory is recorded, not honored."""
    global _active_dir
    rec = recorder if recorder is not None else default_recorder()
    outside = os.environ.get(JAX_ENV_VAR)
    requested = cache_dir or os.environ.get(ENV_VAR)
    if _active_dir is None:
        d = str(Path(outside or requested or DEFAULT_DIR).expanduser())
        try:
            Path(d).mkdir(parents=True, exist_ok=True)
        except OSError as e:
            rec.record(
                "compile_cache.init_failed", severity="warn",
                dir=d, error=repr(e),
            )
            return None
        if not outside:
            jax.config.update("jax_compilation_cache_dir", d)
        # cache EVERYTHING: the defaults skip small/fast compiles, which
        # on CPU (CI) is every program — a floor here would make the
        # feature untestable and silently useless off-TPU
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # jax initializes its cache backend LAZILY on the first compile
        # and never re-reads its config afterwards — any jit that ran
        # before this call (model init, mesh probes) would pin the cache
        # to "disabled" without this reset
        reset_cache()  # tlint: disable=TL503 cache-enable reset
        _active_dir = d
        rec.record("compile_cache.enabled", dir=d, entries=cache_entries(d))
    if requested and str(Path(requested).expanduser()) != _active_dir:
        rec.record(
            "compile_cache.conflict", severity="warn",
            active=_active_dir, requested=requested,
        )
    return _active_dir


def cache_entries(cache_dir: str | None) -> int:
    """Number of persisted executables in the cache directory (0 for
    missing/None — callers diff this around compiles to detect hits)."""
    if not cache_dir:
        return 0
    try:
        return sum(
            1 for p in Path(cache_dir).iterdir()
            if p.is_file() and not p.name.startswith(".")
        )
    except OSError:
        return 0
