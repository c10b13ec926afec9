"""Compile-only: the fused fit at each fit configuration's rank, for a v5e
that is described and not attached, on a cut-down bucket list. Nothing runs
and no time is read. The topology is described inside a fixture (never at
import), and these tests live in this one file."""

import pytest

from benchmark.manifest import load_config, load_manifest

CONFIGS = {c["name"]: load_config(load_manifest(), c["name"]) for c in load_manifest()["configs"]}
CONFIGS = {name: config for name, config in CONFIGS.items() if config.get("driver") == "fit"}


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip cannot be read back from the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_fused_fit_compiles_for_a_v5e_at_the_configurations_rank(name, one_chip, no_compile_cache):
    from albedo_tpu.ops.als import als_init_fit_fused
    from benchmark.memcheck import fused_fit_shapes

    config = dict(CONFIGS[name], n_users=4096, n_items=2048)   # the rank stays
    args, kwargs = fused_fit_shapes(
        config, [(2, 256, 8), (1, 64, 128)], [(1, 128, 64), (1, 8, 2048)], one_chip)
    compiled = als_init_fit_fused.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0
    assert mem.output_size_in_bytes >= (4096 + 2048) * config["rank"] * 4
    assert "gather" in compiled.as_text()


def test_group_shapes_follow_the_degree_sequence_alone():
    import numpy as np

    from benchmark.memcheck import group_shapes

    degrees = np.array([5] * 100 + [40] * 10 + [300])
    layout = dict(batch_size=64, max_entries=1 << 12, max_len=None)
    shapes = group_shapes(degrees, layout)
    assert sum(g * b for g, b, _ in shapes) >= degrees.size
    assert shapes == group_shapes(degrees[::-1].copy(), layout)
