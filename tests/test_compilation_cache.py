"""Where the on-disk executable caches live (utils/compilation_cache.py).

The cache directory is part of the cache key, so a directory that moves never
hits: the default is ONE fixed path inside the checkout — independent of the
working directory and of ``ALBEDO_DATA_DIR`` — a set
``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set in code, and
the ``jax.export`` blobs live beneath whichever directory is in force."""

import os
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

import albedo_tpu  # noqa: E402
from albedo_tpu.utils import aot  # noqa: E402
from albedo_tpu.utils import compilation_cache as cc  # noqa: E402

CHECKOUT = Path(albedo_tpu.__file__).resolve().parents[1]


def test_default_is_fixed_inside_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = CHECKOUT / ".jax-cache"
    assert cc.default_cache_dir() == want
    assert cc.cache_dir() == want
    # Not cwd-relative...
    monkeypatch.chdir(tmp_path)
    assert cc.cache_dir() == want
    # ...and not derived from the artifact store's location.
    monkeypatch.setenv("ALBEDO_DATA_DIR", str(tmp_path / "elsewhere"))
    from albedo_tpu import settings

    settings.reset_settings()
    assert cc.cache_dir() == want
    assert aot.export_dir() == want / "aot-export"
    # ...and git never sees it.
    assert ".jax-cache/" in (CHECKOUT / ".gitignore").read_text().split()


def test_set_env_wins_and_places_the_export_blobs_too(monkeypatch, tmp_path):
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert cc.cache_dir() == placed
    assert aot.export_dir() == placed / "aot-export"


def test_enable_sets_no_directory_when_env_is_set(monkeypatch, tmp_path):
    """With the variable set, the code sets no other directory: jax's own
    config value (read from the environment at import) is left alone."""
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.setattr(cc, "_ENABLED", False)
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_persistent_compilation_cache() is True
    assert jax.config.jax_compilation_cache_dir == before
    assert placed.is_dir()


def test_enable_uses_the_fixed_default_when_env_is_unset(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "default_cache_dir", lambda: tmp_path / "fixed")
    monkeypatch.setattr(cc, "_ENABLED", False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.enable_persistent_compilation_cache() is True
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_kill_switch(monkeypatch):
    monkeypatch.setenv("ALBEDO_JAX_CACHE", "0")
    monkeypatch.setattr(cc, "_ENABLED", False)
    assert cc.enable_persistent_compilation_cache() is False


def test_export_key_follows_the_package_source(monkeypatch):
    """The export layer is keyed by signature, not by program: the package
    source hash in the digest is what keeps a long-lived cache directory
    from replaying a blob an older checkout wrote."""
    key = ("als_init_fit_fused", "0.9.0", "tpu", (8, 16))
    d1 = aot.signature_digest(key)
    assert d1 == aot.signature_digest(key)
    monkeypatch.setattr(aot, "_code_fingerprint", lambda: "another-checkout")
    assert aot.signature_digest(key) != d1
    assert os.path.basename(str(aot.export_dir())) == "aot-export"
