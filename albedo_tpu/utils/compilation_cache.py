"""Persistent XLA-executable cache across processes.

The reference's JVM warms its code cache within one long-lived Spark session;
a JAX job pays XLA compilation again in every fresh process. JAX's persistent
compilation cache serializes compiled executables to disk keyed by HLO
fingerprint, so repeat runs (the ``loadOrCreate`` philosophy,
``utils/ModelUtils.scala:7-21``, applied to executables) skip the compile.

**Where it lives.** The cache directory is part of the cache key, so a
directory that moves never hits. One rule places it, and the ``jax.export``
blobs of ``utils.aot`` with it (``<dir>/aot-export``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at import and this module
  sets no other directory in code — whoever launches the process (the chip
  tool, a container volume) owns the placement.
- unset: ``<checkout>/.jax-cache``, resolved from this package's own
  location — not the working directory, not ``ALBEDO_DATA_DIR``, not a pid
  or a date — and git-ignored.

Disable with ``ALBEDO_JAX_CACHE=0`` (``--no-compilation-cache``).

Preemption hardening: jax's on-disk cache writes entries with a bare
``write_bytes`` — a process killed mid-write (pod preemption, the fault
harness's ``kill`` action) leaves a TRUNCATED serialized executable that a
later process happily deserializes. :func:`harden_jax_cache_writes` patches
the write to the tmp + ``os.replace`` protocol every other artifact in this
repo already uses, closing the torn-write window; stale tmp files from a
killed writer are swept when the cache is enabled. The patch mirrors
``jax._src.lru_cache.LRUCache.put`` of the installed jax (0.9.0).
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_ENABLED = False
_PATCHED = False


def default_cache_dir() -> Path:
    """The fixed in-checkout cache directory (see module doc)."""
    return Path(__file__).resolve().parents[2] / ".jax-cache"


def cache_dir() -> Path:
    """The directory both on-disk executable caches live under: wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it, else :func:`default_cache_dir`."""
    placed = os.environ.get(ENV_DIR)
    return Path(placed) if placed else default_cache_dir()


def harden_jax_cache_writes() -> None:
    """Make jax's persistent-compilation-cache writes atomic (idempotent).

    Call sites are anywhere jax is already imported and about to compile
    (``utils.aot``, the CLI after ``init_distributed``); before jax is
    imported there is nothing to patch.
    """
    global _PATCHED
    if _PATCHED:
        return
    from jax._src import lru_cache as _lc

    cls = _lc.LRUCache
    orig_put = cls.put

    def put(self, key: str, val: bytes) -> None:
        if not key:
            raise ValueError("key cannot be empty")
        if self.eviction_enabled and len(val) > self.max_size:
            orig_put(self, key, val)  # keep jax's too-large warning path
            return
        cache_path = self.path / f"{key}{_lc._CACHE_SUFFIX}"
        if self.eviction_enabled:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if cache_path.exists():
                return
            self._evict_if_needed(additional_size=len(val))
            tmp = self.path / f"{key}.albedo-tmp-{os.getpid()}"
            tmp.write_bytes(val)
            os.replace(tmp, cache_path)  # a kill leaves tmp, never a torn entry
            if self.eviction_enabled:
                atime_path = self.path / f"{key}{_lc._ATIME_SUFFIX}"
                atime_path.write_bytes(time.time_ns().to_bytes(8, "little"))
        finally:
            if self.eviction_enabled:
                self.lock.release()

    cls.put = put
    _PATCHED = True


def _sweep_stale_tmp(cache_dir: Path, max_age_s: float = 3600.0) -> None:
    """Remove tmp files a killed writer left behind (best-effort).

    Age-gated: a tmp file younger than ``max_age_s`` may belong to a LIVE
    writer in another process (compose `serve` warming while a trainer
    runs) — deleting it mid-write would break that writer's os.replace.
    """
    now = time.time()
    try:
        for p in Path(cache_dir).glob("*.albedo-tmp-*"):
            try:
                if now - p.stat().st_mtime >= max_age_s:
                    p.unlink(missing_ok=True)
            except OSError:
                continue
    except OSError:
        pass


def enable_persistent_compilation_cache() -> bool:
    """Idempotently switch on JAX's persistent compilation cache at
    :func:`cache_dir`.

    Returns True if the cache is active after the call. A set
    ``JAX_COMPILATION_CACHE_DIR`` wins (no directory is set in code);
    ``ALBEDO_JAX_CACHE=0`` is the kill switch.
    """
    global _ENABLED
    if os.environ.get("ALBEDO_JAX_CACHE", "1") == "0":
        return False
    jax_imported = "jax" in sys.modules
    if jax_imported:
        # Re-invocations after jax lands still apply the atomic-write patch
        # (the first call usually runs pre-import, where there is nothing
        # to patch).
        harden_jax_cache_writes()
    if _ENABLED:
        return True
    placed = os.environ.get(ENV_DIR)
    target = cache_dir()
    target.mkdir(parents=True, exist_ok=True)
    _sweep_stale_tmp(target)
    if not jax_imported:
        # jax not imported yet (e.g. a host-only CLI job that may never touch
        # it): configure via env vars, which jax reads at import — the call
        # stays free of the multi-second jax import.
        os.environ.setdefault(ENV_DIR, str(target))
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    else:
        import jax

        if not placed:
            jax.config.update("jax_compilation_cache_dir", str(target))
        if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
            # Executables this small recompile faster than they deserialize;
            # only persist genuinely expensive compiles.
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    _ENABLED = True
    return True
