"""Test harness: force JAX onto 8 virtual CPU devices.

This is the JAX analogue of the reference's commented-out
``local-cluster[1, 3, 12288]`` Spark master (e.g. ``ALSRecommenderBuilder.scala:18``)
— multi-device semantics without hardware, so pjit/shard_map/psum paths are
exercised in CI (SURVEY.md section 4 implication).

Must run before any ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _reset_fault_registry():
    """No armed fault, hit counter, or global event count leaks between
    tests."""
    from albedo_tpu.utils import events, faults

    faults.reset()
    events.reset_global_metrics()
    yield
    faults.reset()
    events.reset_global_metrics()


@pytest.fixture(autouse=True)
def _isolated_artifact_dir(tmp_path, monkeypatch):
    """Point the artifact store AND the executable caches at per-test temp
    dirs. The compile-cache default is a fixed directory inside the checkout
    (utils/compilation_cache.py); tests place it explicitly so every test
    starts from a cold export layer, CLI subprocesses inherit the placement,
    and a test run never writes into the checkout."""
    monkeypatch.setenv("ALBEDO_DATA_DIR", str(tmp_path / "albedo-data"))
    monkeypatch.setenv("ALBEDO_CHECKPOINT_DIR", str(tmp_path / "albedo-data/checkpoints"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax-cache"))
    from albedo_tpu import settings

    settings.reset_settings()
    yield
    settings.reset_settings()
