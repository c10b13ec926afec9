"""Cold-start pipeline: parallel bucket-build determinism, the grouped
direct-to-slab builder, the fit-report stage split, the AOT export/import
round trip, and the bounded caches (ISSUE 1 acceptance gates)."""

import gc

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import bench  # noqa: E402
from albedo_tpu.datasets.ragged import (  # noqa: E402
    bucket_rows,
    group_buckets,
    grouped_bucket_rows,
)
from albedo_tpu.datasets.synthetic import synthetic_stars  # noqa: E402
from albedo_tpu.models.als import _LAYOUT_CACHES, ImplicitALS  # noqa: E402
from albedo_tpu.utils.aot import LRUCache, reset_memory_cache  # noqa: E402

FIELDS = ("row_ids", "idx", "val", "mask")


def assert_buckets_identical(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        for f in FIELDS:
            fx, fy = getattr(x, f), getattr(y, f)
            assert fx.dtype == fy.dtype and fx.shape == fy.shape
            assert fx.tobytes() == fy.tobytes(), f


def test_parallel_bucket_rows_byte_identical():
    """The thread-pool fill path must produce byte-identical buckets to the
    sequential path on both CSR (user) and CSC (item) inputs — the
    determinism gate of the cold-path pipeline."""
    m = synthetic_stars(n_users=500, n_items=260, mean_stars=14, seed=31)
    for csx in (m.csr(), m.csc()):
        seq = bucket_rows(*csx, batch_size=64, max_entries=1 << 14)
        par = bucket_rows(*csx, batch_size=64, max_entries=1 << 14, workers=4)
        assert_buckets_identical(seq, par)


def test_parallel_bucket_rows_byte_identical_with_max_len():
    m = synthetic_stars(n_users=300, n_items=150, mean_stars=10, seed=7)
    csx = m.csr()
    seq = bucket_rows(*csx, batch_size=32, max_len=5, len_multiple=4)
    par = bucket_rows(*csx, batch_size=32, max_len=5, len_multiple=4, workers=3)
    assert_buckets_identical(seq, par)


def test_grouped_builder_matches_group_buckets():
    """Filling straight into the stacked group slabs must equal
    group_buckets(bucket_rows(...)) byte-for-byte, and the on_group hook must
    fire once per group in shape-sorted order (the upload-pipeline contract)."""
    m = synthetic_stars(n_users=400, n_items=200, mean_stars=12, seed=13)
    for csx in (m.csr(), m.csc()):
        ref = group_buckets(bucket_rows(*csx, batch_size=64, max_entries=1 << 13))
        for workers in (None, 3):
            seen = []
            got = grouped_bucket_rows(
                *csx, batch_size=64, max_entries=1 << 13, workers=workers,
                on_group=lambda i, g: seen.append(i),
            )
            assert seen == list(range(len(got)))
            assert_buckets_identical(ref, got)


def test_fit_report_cold_split_fields():
    """The fit report must carry the cold-path stage split; a second fit on
    the same matrix reports a warm layout cache and a memory-cache compile."""
    m = synthetic_stars(n_users=80, n_items=50, mean_stars=6, seed=29)
    als = ImplicitALS(rank=4, max_iter=2, seed=0)
    als.fit(m)
    r = als.last_fit_report
    assert {"prep_s", "bucket_s", "upload_s", "compile_s", "compile_source",
            "device_s", "prep_cached"} <= set(r)
    assert r["prep_cached"] is False
    assert r["compile_s"] >= 0.0 and r["compile_source"] in ("compile", "disk")
    als2 = ImplicitALS(rank=4, max_iter=2, seed=0)
    als2.fit(m)
    r2 = als2.last_fit_report
    assert r2["prep_cached"] is True
    assert r2["bucket_s"] == 0.0 and r2["upload_s"] == 0.0
    assert r2["compile_source"] == "memory" and r2["compile_s"] == 0.0


def test_cold_prep_bench_record_shape():
    """cold_prep totals the split and prices it against the r5 cliff."""
    rec = bench.cold_prep_record(
        {"prep_s": 1.0, "bucket_s": 0.6, "upload_s": 0.4, "compile_s": 2.0,
         "compile_source": "compile", "device_s": 0.345, "prep_cached": False}
    )
    assert rec["total_s"] == pytest.approx(3.345)
    assert rec["r5_cold_total_s"] == bench.R5_COLD_PREP_S
    assert rec["speedup_vs_r5"] == pytest.approx(bench.R5_COLD_PREP_S / 3.345, abs=0.01)
    # The split fields ride through untouched.
    assert rec["bucket_s"] == 0.6 and rec["upload_s"] == 0.4


def test_aot_export_roundtrip_identical_factors():
    """A second process (simulated by clearing the in-memory executable LRU)
    must load the serialized export from disk and produce factors identical
    to the fresh compile's. Uses the CG solver — its program has no custom
    calls, so the disk layer engages on every backend."""
    m = synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=17)
    als = ImplicitALS(rank=4, max_iter=3, seed=5, solver="cg")
    first = als.fit(m)
    assert als.last_fit_report["compile_source"] == "compile"

    reset_memory_cache()
    als2 = ImplicitALS(rank=4, max_iter=3, seed=5, solver="cg")
    second = als2.fit(m)
    assert als2.last_fit_report["compile_source"] == "disk"
    np.testing.assert_array_equal(first.user_factors, second.user_factors)
    np.testing.assert_array_equal(first.item_factors, second.item_factors)


def test_aot_fingerprint_mismatch_discards_export_and_recompiles():
    """The output-fingerprint self-check: an export whose deserialized
    executable does not reproduce the recorded probe output is discarded
    (file deleted, mismatch counted) and the program recompiles fresh —
    divergent cached executables can never serve drifted numerics. A
    tampered sidecar stands in for a genuinely divergent executable."""
    import json as _json

    from albedo_tpu.utils import events
    from albedo_tpu.utils.aot import export_dir

    m = synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=23)
    als = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    first = als.fit(m)
    assert als.last_fit_report["compile_source"] == "compile"
    exports = list(export_dir().glob("als_init_fit_fused-*.jaxexport"))
    sidecars = list(export_dir().glob("als_init_fit_fused-*.jaxexport.fp"))
    assert exports and sidecars  # the export records its probe fingerprint

    # Tamper the recorded fingerprint: the next process's self-check must
    # refuse the (now unprovable) executable.
    sidecars[0].write_text(_json.dumps({"sha256": "0" * 64}))
    reset_memory_cache()
    als2 = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    second = als2.fit(m)
    assert als2.last_fit_report["compile_source"] == "compile"  # not "disk"
    assert events.aot_fingerprint_mismatches.total() >= 1
    np.testing.assert_array_equal(first.user_factors, second.user_factors)

    # The discarded export was rewritten by the fresh compile, with a new
    # fingerprint — and a third acquisition trusts it again.
    assert list(export_dir().glob("als_init_fit_fused-*.jaxexport"))
    new_fp = _json.loads(sidecars[0].read_text())["sha256"]
    assert new_fp != "0" * 64
    reset_memory_cache()
    als3 = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    third = als3.fit(m)
    assert als3.last_fit_report["compile_source"] == "disk"
    np.testing.assert_array_equal(first.user_factors, third.user_factors)


def test_aot_skips_disk_for_custom_call_programs():
    """On CPU a library factorisation lowers to a LAPACK custom call, which is
    not round-trip-safe (executing a deserialized copy in a fresh process can
    crash): such programs must stay memory-cached only — a second cold
    acquisition recompiles instead of reading a blob."""
    import jax.numpy as jnp

    from albedo_tpu.utils.aot import export_dir, persistent_aot_executable

    def acquire():
        return persistent_aot_executable(
            jax.jit(jnp.linalg.cholesky), (jnp.eye(4, dtype=jnp.float32),), None, None,
            key_parts=("test_cold_path", "library_cholesky"), name="library_cholesky")[2]

    assert acquire() == "compile"
    assert not list(export_dir().glob("library_cholesky-*.jaxexport"))
    reset_memory_cache()
    assert acquire() == "compile"


def test_the_exact_fit_round_trips_from_disk_like_the_cg_fit():
    """The exact solve is the program's own loops (no library factorisation,
    so no custom call on any backend): its fused fit is exported, and a
    second cold acquisition reads the blob and reproduces the factors."""
    from albedo_tpu.utils.aot import export_dir

    m = synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=19)
    als = ImplicitALS(rank=4, max_iter=2, seed=1, solver="cholesky")
    first = als.fit(m)
    assert als.last_fit_report["compile_source"] == "compile"
    assert list(export_dir().glob("als_init_fit_fused-*.jaxexport"))

    reset_memory_cache()
    als2 = ImplicitALS(rank=4, max_iter=2, seed=1, solver="cholesky")
    second = als2.fit(m)
    assert als2.last_fit_report["compile_source"] == "disk"
    np.testing.assert_array_equal(first.user_factors, second.user_factors)


def test_lru_cache_bounds_and_recency():
    c = LRUCache(maxsize=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1  # refresh recency: b is now oldest
    c.put("c", 3)
    assert len(c) == 2
    assert "b" not in c and "a" in c and "c" in c


def test_matrix_cache_released_with_matrix():
    """The device-group cache must die with its matrix (ADVICE r5 #1): a
    long-lived process fitting many matrices must not accumulate uploads."""
    m = synthetic_stars(n_users=40, n_items=30, mean_stars=4, seed=3)
    ImplicitALS(rank=4, max_iter=1, seed=0).fit(m)
    key = id(m)
    assert key in _LAYOUT_CACHES
    del m
    gc.collect()
    assert key not in _LAYOUT_CACHES


def test_a_corrupt_executable_still_fails_when_the_probe_runs_on_the_device(monkeypatch):
    """PR 29: leaves too large for the host are made and digested on the
    device. With the threshold lowered so that this fit's slabs and tables
    count as large, the drill above still ends the same way: a recorded
    digest the executable does not reproduce discards the export; the fresh
    compile is trusted again."""
    import json as _json

    from albedo_tpu.utils import aot, events

    monkeypatch.setattr(aot, "_PROBE_HOST_ELEMS", 32)
    m = synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=29)
    als = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    first = als.fit(m)
    (sidecar,) = aot.export_dir().glob("als_init_fit_fused-*.jaxexport.fp")
    recorded = _json.loads(sidecar.read_text())
    assert recorded["v"] == aot._FP_VERSION

    reset_memory_cache()
    again = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    again.fit(m)
    assert again.last_fit_report["compile_source"] == "disk"   # the digest is reproducible

    before = events.aot_fingerprint_mismatches.total()
    sidecar.write_text(_json.dumps({"sha256": "0" * 64, "v": aot._FP_VERSION}))
    reset_memory_cache()
    als2 = ImplicitALS(rank=4, max_iter=3, seed=7, solver="cg")
    second = als2.fit(m)
    assert als2.last_fit_report["compile_source"] == "compile"
    assert events.aot_fingerprint_mismatches.total() == before + 1
    np.testing.assert_array_equal(first.user_factors, second.user_factors)
    assert _json.loads(sidecar.read_text())["sha256"] == recorded["sha256"]


def test_a_sidecar_of_another_probe_version_recompiles_once_and_counts_no_mismatch():
    import json as _json

    from albedo_tpu.utils import aot, events

    m = synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=31)
    ImplicitALS(rank=4, max_iter=2, seed=7, solver="cg").fit(m)
    (sidecar,) = aot.export_dir().glob("als_init_fit_fused-*.jaxexport.fp")
    good = _json.loads(sidecar.read_text())
    sidecar.write_text(_json.dumps({"sha256": good["sha256"], "v": aot._FP_VERSION - 1}))
    before = events.aot_fingerprint_mismatches.total()
    reset_memory_cache()
    als = ImplicitALS(rank=4, max_iter=2, seed=7, solver="cg")
    als.fit(m)
    assert als.last_fit_report["compile_source"] == "compile"
    assert events.aot_fingerprint_mismatches.total() == before
    assert aot.branch_log()[-1]["branch"].startswith("disk-fingerprint-version")
    assert _json.loads(sidecar.read_text()) == good
    reset_memory_cache()
    als.fit(synthetic_stars(n_users=90, n_items=60, mean_stars=6, seed=31))
    assert als.last_fit_report["compile_source"] == "disk"


@pytest.mark.parametrize("dtype", ["float32", "int32", "bool", "bfloat16"])
def test_device_made_probe_leaves_and_their_digest(monkeypatch, dtype):
    """A large leaf made on the device holds the host pattern (whole numbers
    exactly, floats to an ulp), and its digest moves with any one element."""
    import jax
    import jax.numpy as jnp

    from albedo_tpu.utils import aot

    spec = jax.ShapeDtypeStruct((3, 5, 70), jnp.dtype(dtype))
    on_host = aot._probe_leaf(spec)
    monkeypatch.setattr(aot, "_PROBE_HOST_ELEMS", 8)
    on_device = aot._probe_leaf(spec)
    assert isinstance(on_device, jax.Array) and on_device.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(on_device, np.float64), np.asarray(on_host, np.float64),
                               rtol=1e-2 if dtype == "bfloat16" else 1e-6)
    digest = aot._device_digest_fn(spec.shape, jnp.dtype(dtype).name)
    base = np.asarray(digest(on_device))
    assert base.shape == (2,) and (np.asarray(digest(on_device)) == base).all()
    flipped = np.asarray(on_device).copy()
    flipped[2, 4, 69] = not flipped[2, 4, 69] if dtype == "bool" else flipped[2, 4, 69] + 1
    moved = np.asarray(digest(jnp.asarray(flipped)))
    assert (moved != base).all()
    swapped = np.asarray(on_device).copy()
    swapped[0, 0, [1, 2]] = swapped[0, 0, [2, 1]]       # the same values elsewhere
    if dtype != "bool":
        assert (np.asarray(digest(jnp.asarray(swapped))) != base).all()
