"""Work counts, peaks and generators: hand-computed values, independence
from the bucket layout, and seeds."""

import json

import numpy as np
import pytest

from benchmark import peaks, stars, workcounts
from benchmark.manifest import ROOT, load_config, load_manifest

CONFIGS = {c["name"]: load_config(load_manifest(), c["name"]) for c in load_manifest()["configs"]}
CONFIGS = {name: config for name, config in CONFIGS.items() if config.get("driver") == "fit"}
TINY = json.loads((ROOT / "tests/perfbench/data/tiny-r16.json").read_text())


@pytest.mark.parametrize("name,n_users,n_items,nnz,rank,cg", [
    ("ml25m-r128", 325082, 59047, 50000190, 128, 3),
    ("albedo-r50", 450000, 300000, 40000000, 50, 3),
])
def test_counts_equal_hand_computed_values(name, n_users, n_items, nnz, rank, cg):
    config = CONFIGS[name]
    assert (config["n_users"], config["n_items"], config["nnz"], config["rank"],
            config["solver"], config["cg_steps"]) == (n_users, n_items, nnz, rank, "cg", cg)
    rows = n_users + n_items
    want_bytes = 2 * nnz * (rank * 4 + 8) + 2 * rows * rank * 4
    want_flops = (2 * nnz * (9 * rank + cg * 4 * rank)
                  + rows * (2 * rank**2 + cg * (2 * rank**2 + 10 * rank))
                  + 2 * rows * rank**2)
    got = workcounts.config_counts(config)
    assert got["bytes_per_sweep"] == pytest.approx(want_bytes, rel=1e-12)
    assert got["flops_per_sweep"] == pytest.approx(want_flops, rel=1e-12)


def test_ml25m_least_time_is_bound_by_bytes_at_about_64_ms():
    least = workcounts.least_sweep_seconds(CONFIGS["ml25m-r128"], "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert least["least_s"] == pytest.approx(52.4e9 / 819e9, rel=0.02)


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("layout", [{"batch_size": 1024, "max_entries": 1 << 18},
                                    {"batch_size": 8192, "max_entries": 1 << 21}])
def test_counts_do_not_change_with_the_bucket_layout(name, layout):
    assert workcounts.config_counts({**CONFIGS[name], **layout}) == workcounts.config_counts(CONFIGS[name])


def test_cholesky_counts_differ_and_unknown_solver_raises():
    a = workcounts.sweep_flops(100, 50, 1000, 8, "cg", 3)
    b = workcounts.sweep_flops(100, 50, 1000, 8, "cholesky", 3)
    assert a != b
    with pytest.raises(ValueError):
        workcounts.sweep_flops(100, 50, 1000, 8, "lu", 3)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_of_the_v5e(kind):
    row = peaks.peaks_for(kind)
    assert (row["bf16_flops"], row["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert row["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100"])
def test_peaks_raise_on_an_unknown_device_kind(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)


def test_ml25m_published_counts_are_met_exactly_at_twice_the_users():
    """ML-25M's published counts (users, items, ratings, at least 20 a user),
    with the users and ratings doubled to clear the size floor."""
    config = CONFIGS["ml25m-r128"]
    published = config["published"]
    assert published == {"n_users": 162541, "n_items": 59047, "nnz": 25000095, "min_ratings_per_user": 20}
    assert (config["n_users"], config["n_items"], config["nnz"]) == (2 * 162541, 59047, 2 * 25000095)
    users = stars.degree_sequence(config["n_users"], config["nnz"], config["user_degrees"])
    items = stars.degree_sequence(config["n_items"], config["nnz"], config["item_degrees"])
    assert users.shape == (325082,) and items.shape == (59047,)
    assert users.sum() == items.sum() == 50000190
    assert users.min() == 20 and items.min() >= 1
    assert users.max() <= config["user_degrees"]["max"] and items.max() <= config["n_users"] // 2


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_degree_sequences_are_dealable(name):
    config = CONFIGS[name]
    for n, law in ((config["n_users"], config["user_degrees"]), (config["n_items"], config["item_degrees"])):
        deg = stars.degree_sequence(n, config["nnz"], law)
        assert deg.sum() == config["nnz"] and (np.diff(deg) <= 0).all()
        assert law["min"] <= deg.min() and deg.max() <= law["max"]


def test_the_same_seed_gives_the_same_matrix_and_another_seed_another():
    a = stars.generate_stars(TINY, 3_000_000_019)
    b = stars.generate_stars(TINY, 3_000_000_019)
    c = stars.generate_stars(TINY, 3_000_000_020)
    for key in ("rows", "cols", "vals"):
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["cols"], c["cols"])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_every_seed_has_the_same_degrees_and_no_pair_twice(seed):
    m = stars.generate_stars(TINY, seed)
    assert m["rows"].shape == (TINY["nnz"],)
    pairs = m["rows"].astype(np.int64) * TINY["n_items"] + m["cols"]
    assert np.unique(pairs).size == pairs.size
    want_u = stars.degree_sequence(TINY["n_users"], TINY["nnz"], TINY["user_degrees"])
    want_i = stars.degree_sequence(TINY["n_items"], TINY["nnz"], TINY["item_degrees"])
    assert np.array_equal(np.sort(np.bincount(m["rows"], minlength=TINY["n_users"]))[::-1], want_u)
    assert np.array_equal(np.sort(np.bincount(m["cols"], minlength=TINY["n_items"]))[::-1], want_i)


def test_every_seed_gives_the_program_the_same_bucket_shapes():
    from albedo_tpu.utils import capacity

    shapes = []
    for seed in (1, 2):
        m = stars.generate_stars(TINY, seed)
        shapes.append([
            capacity.bucket_plan_shapes(capacity.counts_indptr(m[side], n), batch_size=64, max_entries=1 << 12)
            for side, n in (("rows", TINY["n_users"]), ("cols", TINY["n_items"]))
        ])
    assert shapes[0] == shapes[1]


def test_rated_values_come_from_the_configured_levels():
    config = dict(TINY, values=CONFIGS["ml25m-r128"]["values"])
    m = stars.generate_stars(config, 5)
    assert set(np.unique(m["vals"]).tolist()) <= set(config["values"]["levels"])
    assert len(np.unique(m["vals"])) > 5
