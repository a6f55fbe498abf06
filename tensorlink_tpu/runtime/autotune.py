"""Persistent autotuning store: measured knobs survive restarts.

The persistent XLA compile cache (runtime/compile_cache.py) already
makes a restart reuse compiled KERNELS; this module extends the same
warm-restart story (PAPERS.md, "Scalable Training of Language Models
using JAX pjit and TPUv4") to the MEASURED CONSTANTS that pick those
kernels — the values a process pays a calibration sweep to learn and
then forgets at exit:

- flash-attention block overrides (``ops/flash.py
  set_flash_block_override`` — the per-(seq, batch) tuning sweep);
- the serving engines' prefill-bucket sets (what to pre-warm);
- the adaptive-speculation K prior (``parallel/speculative.py
  AdaptiveKController`` — acceptance + measured draft cost, so a
  restarted engine's first dispatch already runs near the learned K);
- the measured draft pairing (``autopair_draft`` verdict), so a
  restart skips the calibration burst entirely.

Keying mirrors the compile cache: a record is only trusted when its
``(jax version, chip, model fingerprint, bucket set)`` all match the
loading process (``runtime_fingerprint`` is shared with the compile
cache on purpose). Anything else — different chip, upgraded jax, a
resized model, a corrupt or truncated file — reads as a clean MISS and
the process cold-starts exactly as if the store were empty; a tuning
cache must never be able to crash (or mis-tune) serving.

One JSON file per key, written atomically (tmp + rename), so two
processes racing a save leave one intact record, never a torn one.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

from tensorlink_tpu.runtime.compile_cache import runtime_fingerprint
from tensorlink_tpu.runtime.flight import default_recorder

__all__ = [
    "AutotuneStore",
    "apply_flash_overrides",
    "apply_paged_overrides",
    "model_fingerprint",
    "store_key",
]

ENV_VAR = "TL_AUTOTUNE_DIR"
SCHEMA = 1

# model-independent records (e.g. a WorkerNode's flash blocks, tuned
# before any model is loaded) key on this sentinel fingerprint
GLOBAL_MODEL = "global"


def model_fingerprint(params) -> str:
    """Cheap structural fingerprint of a param tree: every leaf's path,
    shape, and dtype — no weight bytes read (an 8B model must
    fingerprint in microseconds). Tuned constants depend on program
    SHAPES, which this pins; two models with identical structure share
    tuning by design."""
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(str(path).encode())
        h.update(str(getattr(leaf, "shape", ())).encode())
        h.update(str(getattr(leaf, "dtype", "?")).encode())
    return h.hexdigest()[:16]


def store_key(model_fp: str, buckets) -> str:
    """One store key = hash of (jax version, chip, model fingerprint,
    bucket set) — the compile cache's invariants plus the program-shape
    set the tuned values were measured against."""
    rt = runtime_fingerprint()
    h = hashlib.sha256()
    h.update(rt["jax"].encode())
    h.update(rt["chip"].encode())
    h.update(str(model_fp).encode())
    h.update(",".join(str(int(b)) for b in sorted(buckets)).encode())
    return h.hexdigest()[:24]


def apply_flash_overrides(record: dict) -> int:
    """Install a record's persisted flash-block overrides
    (``[[seq, batch|null, block], ...]``); returns how many applied.
    Invalid entries (block no longer divides seq after a config change)
    are skipped, not fatal — stale tuning must degrade to the
    heuristic, never to a crash."""
    from tensorlink_tpu.ops.flash import set_flash_block_override

    applied = 0
    for entry in record.get("flash_blocks") or []:
        try:
            seq, batch, block = entry
            set_flash_block_override(
                int(seq), int(block),
                batch=None if batch is None else int(batch),
            )
            applied += 1
        except (TypeError, ValueError):
            continue
    return applied


def apply_paged_overrides(record: dict) -> int:
    """Install a record's persisted paged-decode kernel tuning
    (``[[max_blocks, block_size|null, pages], ...]`` — the
    pages-per-superstep choice per table geometry, see
    ``ops/pallas/paged_decode.py``); returns how many applied. Same
    skip-not-crash discipline as ``apply_flash_overrides``."""
    from tensorlink_tpu.ops.pallas.paged_decode import (
        set_paged_block_override,
    )

    applied = 0
    for entry in record.get("paged_kernel") or []:
        try:
            max_blocks, block_size, pages = entry
            set_paged_block_override(
                int(max_blocks), int(pages),
                block_size=None if block_size is None else int(block_size),
            )
            applied += 1
        except (TypeError, ValueError):
            continue
    return applied


class AutotuneStore:
    """Directory of per-key tuning records. ``resolve`` takes the
    explicit argument, then ``$TL_AUTOTUNE_DIR``, else None (= feature
    off, every call a no-op)."""

    def __init__(self, root: str, *, recorder=None):
        self.root = Path(root).expanduser()
        self.recorder = recorder

    @classmethod
    def resolve(cls, root: str | None = None, *,
                recorder=None) -> "AutotuneStore | None":
        d = root if root is not None else os.environ.get(ENV_VAR)
        if not d:
            return None
        store = cls(d, recorder=recorder)
        try:
            store.root.mkdir(parents=True, exist_ok=True)
        except OSError as e:
            store._event(
                "autotune.init_failed", severity="warn",
                dir=str(store.root), error=repr(e),
            )
            return None
        return store

    # ----------------------------------------------------------- events
    def _event(self, kind: str, severity: str = "info", **data) -> None:
        rec = self.recorder if self.recorder is not None else default_recorder()
        try:
            rec.record(kind, severity, **data)
        except Exception:  # noqa: BLE001 — telemetry must not tune
            pass

    # -------------------------------------------------------------- io
    def path(self, key: str) -> Path:
        return self.root / f"tune-{key}.json"

    def load(self, key: str) -> dict | None:
        """The record for ``key``, or None for missing / unreadable /
        corrupt / stale (schema or key mismatch — e.g. a jax upgrade
        changed the key this process computes but an old file was
        renamed into place). Every None is a clean cold start."""
        p = self.path(key)
        try:
            raw = p.read_bytes()
        except OSError:
            return None
        try:
            rec = json.loads(raw.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):  # binary garbage incl.
            self._event(
                "autotune.corrupt", severity="warn", path=str(p),
            )
            return None
        if not isinstance(rec, dict) or rec.get("schema") != SCHEMA:
            self._event(
                "autotune.stale", severity="warn", path=str(p),
                schema=rec.get("schema") if isinstance(rec, dict) else None,
            )
            return None
        if rec.get("key") != key:
            self._event(
                "autotune.stale", severity="warn", path=str(p),
                key=rec.get("key"), expected=key,
            )
            return None
        return rec

    def update(self, key: str, patch: dict) -> Path:
        """Merge ``patch`` into the record under ``key`` (load-modify-
        save; missing/stale records start empty). The writer-owns-its-
        keys discipline callers follow: WorkerNode persists
        ``flash_blocks`` and the capability microbench persists
        ``capability`` under the SAME chip-global key — a blind save
        from either would silently drop the other's measurement."""
        rec = self.load(key) or {}
        for stamp in ("schema", "key", "jax", "chip", "saved_at"):
            rec.pop(stamp, None)  # save() re-stamps these
        rec.update(patch)
        return self.save(key, rec)

    def save(self, key: str, record: dict) -> Path:
        """Atomically persist ``record`` under ``key`` (schema, key, and
        runtime facts stamped here, so a loader can validate them)."""
        rec = dict(record)
        rec["schema"] = SCHEMA
        rec["key"] = key
        rec.update(runtime_fingerprint())
        rec["saved_at"] = time.time()
        p = self.path(key)
        tmp = p.with_suffix(f".tmp.{os.getpid()}")
        tmp.write_text(json.dumps(rec, sort_keys=True, indent=1))
        tmp.replace(p)
        self._event("autotune.saved", path=str(p), key=key)
        return p
