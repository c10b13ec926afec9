"""Implicit ALS: kernel parity vs a dense numpy reference, objective descent,
and structure recovery on planted synthetic data."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from albedo_tpu.datasets import StarMatrix, bucket_rows, synthetic_stars  # noqa: E402
from albedo_tpu.models.als import ALSModel, ImplicitALS  # noqa: E402
from albedo_tpu.ops.als import als_half_sweep, implicit_loss  # noqa: E402


def numpy_half_sweep(source, target, indptr, indices, vals, reg, alpha):
    """Dense reference for one implicit-ALS half-sweep (MLlib conventions)."""
    out = target.copy()
    yty = source.T @ source
    k = source.shape[1]
    for r in range(indptr.shape[0] - 1):
        lo, hi = indptr[r], indptr[r + 1]
        if hi == lo:
            continue
        y = source[indices[lo:hi]]            # (n, k)
        c1 = alpha * vals[lo:hi]
        a_mat = yty + (y * c1[:, None]).T @ y + reg * (hi - lo) * np.eye(k)
        b_vec = ((1.0 + c1)[:, None] * y).sum(axis=0)
        out[r] = np.linalg.solve(a_mat, b_vec)
    return out


@pytest.fixture(scope="module")
def small_matrix():
    return synthetic_stars(n_users=120, n_items=80, mean_stars=8, seed=11)


def test_half_sweep_matches_numpy(small_matrix):
    m = small_matrix
    rng = np.random.default_rng(0)
    user_f = rng.normal(0, 0.1, (m.n_users, 8)).astype(np.float32)
    item_f = rng.normal(0, 0.1, (m.n_items, 8)).astype(np.float32)
    reg, alpha = 0.3, 10.0

    indptr, cols, vals = m.csr()
    expected = numpy_half_sweep(item_f, user_f, indptr, cols, vals, reg, alpha)

    buckets = bucket_rows(indptr, cols, vals, batch_size=32)
    got = np.asarray(
        als_half_sweep(jnp.asarray(item_f), jnp.asarray(user_f), buckets, reg, alpha)
    )
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-4)


def test_half_sweep_respects_memory_budget(small_matrix):
    m = small_matrix
    indptr, cols, vals = m.csr()
    buckets = bucket_rows(indptr, cols, vals, batch_size=64, max_entries=512)
    # Budget is honored for any row that itself fits in the budget.
    assert all(b.idx.size <= 512 or b.idx.shape[0] == 1 for b in buckets)
    # Budgeted buckets still cover every nonzero exactly once.
    assert sum(int(b.mask.sum()) for b in buckets) == m.nnz


def test_objective_monotone_descent(small_matrix):
    m = small_matrix
    losses = []

    def track(it, uf, vf):
        losses.append(
            float(
                implicit_loss(
                    jnp.asarray(uf), jnp.asarray(vf),
                    jnp.asarray(m.rows), jnp.asarray(m.cols), jnp.asarray(m.vals),
                    reg=0.5, alpha=10.0,
                )
            )
        )

    ImplicitALS(rank=8, reg_param=0.5, alpha=10.0, max_iter=6, seed=1).fit(
        m, callback=track
    )
    # ALS is coordinate descent on the exact objective: monotone non-increasing.
    assert all(b <= a * (1 + 1e-5) for a, b in zip(losses, losses[1:])), losses
    assert losses[-1] < losses[0]


def test_fused_fit_matches_per_bucket_sweeps(small_matrix):
    """The single-dispatch fused fit (fori_loop + scanned shape groups) must
    produce the same factors as the per-bucket dispatch path it replaced."""
    m = small_matrix
    rank, reg, alpha, iters, seed = 6, 0.4, 8.0, 3, 9

    key = jax.random.PRNGKey(seed)
    ukey, ikey = jax.random.split(key)
    scale = 1.0 / np.sqrt(rank)
    user_f = jax.random.normal(ukey, (m.n_users, rank), jnp.float32) * scale
    item_f = jax.random.normal(ikey, (m.n_items, rank), jnp.float32) * scale

    user_buckets = bucket_rows(*m.csr(), batch_size=32)
    item_buckets = bucket_rows(*m.csc(), batch_size=32)
    uf, vf = user_f, item_f
    for _ in range(iters):
        vf = als_half_sweep(uf, vf, item_buckets, reg, alpha)
        uf = als_half_sweep(vf, uf, user_buckets, reg, alpha)

    got = ImplicitALS(
        rank=rank, reg_param=reg, alpha=alpha, max_iter=iters, seed=seed, batch_size=32
    ).fit(m)
    np.testing.assert_allclose(got.user_factors, np.asarray(uf), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got.item_factors, np.asarray(vf), rtol=1e-4, atol=1e-5)


def test_fit_deterministic(small_matrix):
    als = ImplicitALS(rank=4, max_iter=2, seed=7, alpha=5.0)
    m1 = als.fit(small_matrix)
    m2 = als.fit(small_matrix)
    np.testing.assert_allclose(m1.user_factors, m2.user_factors, rtol=1e-5, atol=1e-6)


def test_recovers_planted_structure():
    """ALS scores must rank a user's held-out items above random items."""
    m = synthetic_stars(n_users=300, n_items=150, mean_stars=20, seed=21)
    from albedo_tpu.datasets import random_split_by_user

    train, test = random_split_by_user(m, test_ratio=0.2, seed=3)
    model = ImplicitALS(rank=16, reg_param=0.1, alpha=40.0, max_iter=8, seed=0).fit(train)

    rng = np.random.default_rng(5)
    neg_items = rng.integers(0, m.n_items, size=test.nnz).astype(np.int32)
    # A random negative that the user starred in train is legitimately scored
    # high by a good model — exclude those pairs from the probe.
    collide = (train.dense() > 0)[test.rows, neg_items]
    pos = model.predict(test.rows[~collide], test.cols[~collide])
    neg = model.predict(test.rows[~collide], neg_items[~collide])
    auc_proxy = float((pos > neg).mean())

    counts = train.item_counts().astype(float)
    pop_auc = float(
        (counts[test.cols[~collide]] > counts[neg_items[~collide]]).mean()
    )
    # Held-out positives outscore random negatives, and personalization beats
    # the popularity baseline (the reference's metric gap, BASELINE.md).
    assert auc_proxy > 0.7, auc_proxy
    assert auc_proxy > pop_auc, (auc_proxy, pop_auc)


def test_cg_half_sweep_converges_to_exact_solve(small_matrix):
    """With enough steps, warm-started CG reaches the Cholesky solution (CG on
    a k-dim SPD system is exact in k steps up to float error)."""
    from albedo_tpu.datasets.ragged import device_bucket, group_buckets
    from albedo_tpu.ops.als import scan_half_sweep

    m = small_matrix
    rng = np.random.default_rng(2)
    rank, reg, alpha = 8, 0.3, 10.0
    user_f = jnp.asarray(rng.normal(0, 0.1, (m.n_users, rank)).astype(np.float32))
    item_f = jnp.asarray(rng.normal(0, 0.1, (m.n_items, rank)).astype(np.float32))
    groups = [
        device_bucket(g) for g in group_buckets(bucket_rows(*m.csr(), batch_size=32))
    ]
    reg_a, alpha_a = jnp.float32(reg), jnp.float32(alpha)
    exact = np.asarray(
        scan_half_sweep(item_f, user_f, groups, reg_a, alpha_a, "cholesky")
    )
    got = np.asarray(
        scan_half_sweep(item_f, user_f, groups, reg_a, alpha_a, "cg", cg_steps=16)
    )
    np.testing.assert_allclose(got, exact, rtol=5e-3, atol=5e-4)


def test_cg_fit_quality_matches_cholesky(small_matrix):
    """The fast path (3 warm-started CG steps/half-sweep) must land on the
    same objective value as the exact solver after a full fit."""
    m = small_matrix
    kw = dict(rank=8, reg_param=0.5, alpha=10.0, max_iter=10, seed=1)
    exact = ImplicitALS(**kw).fit(m)
    fast = ImplicitALS(**kw, solver="cg").fit(m)

    def loss(model):
        return float(
            implicit_loss(
                jnp.asarray(model.user_factors), jnp.asarray(model.item_factors),
                jnp.asarray(m.rows), jnp.asarray(m.cols), jnp.asarray(m.vals),
                reg=0.5, alpha=10.0,
            )
        )

    l_exact, l_fast = loss(exact), loss(fast)
    assert l_fast <= l_exact * 1.01, (l_fast, l_exact)
    # And the models agree on predictions, not just on the objective.
    s_exact = exact.predict(m.rows, m.cols)
    s_fast = fast.predict(m.rows, m.cols)
    corr = float(np.corrcoef(s_exact, s_fast)[0, 1])
    assert corr > 0.995, corr


def test_model_roundtrip(small_matrix, tmp_path):
    model = ImplicitALS(rank=4, max_iter=1).fit(small_matrix)
    arrays = model.to_arrays()
    loaded = ALSModel.from_arrays(arrays)
    np.testing.assert_array_equal(loaded.user_factors, model.user_factors)
    assert loaded.rank == model.rank


def test_empty_user_keeps_init_factor():
    # User 0 has no interactions: its factor should stay at initialization.
    m = StarMatrix(
        user_ids=np.array([1, 2, 3]),
        item_ids=np.array([10, 20]),
        rows=np.array([1, 2, 2], dtype=np.int32),
        cols=np.array([0, 0, 1], dtype=np.int32),
        vals=np.ones(3, dtype=np.float32),
    )
    als = ImplicitALS(rank=4, max_iter=2, seed=3)
    model = als.fit(m)
    key = jax.random.PRNGKey(3)
    ukey, _ = jax.random.split(key)
    init = np.asarray(jax.random.normal(ukey, (3, 4), jnp.float32)) / np.sqrt(4)
    np.testing.assert_allclose(model.user_factors[0], init[0], rtol=1e-6)
    assert not np.allclose(model.user_factors[1], init[1])


def test_bf16_gather_fit_quality(small_matrix):
    """bf16 gathered factors (f32 tables/accumulation) must preserve ranking
    quality: predictions track the f32 fit to high correlation and the
    objective stays within a percent."""
    m = small_matrix
    kw = dict(rank=8, reg_param=0.5, alpha=10.0, max_iter=10, seed=1, solver="cg")
    f32 = ImplicitALS(**kw).fit(m)
    bf16 = ImplicitALS(**kw, gather_dtype="bfloat16").fit(m)

    def loss(model):
        return float(
            implicit_loss(
                jnp.asarray(model.user_factors), jnp.asarray(model.item_factors),
                jnp.asarray(m.rows), jnp.asarray(m.cols), jnp.asarray(m.vals),
                reg=0.5, alpha=10.0,
            )
        )

    assert loss(bf16) <= loss(f32) * 1.01, (loss(bf16), loss(f32))
    corr = float(np.corrcoef(f32.predict(m.rows, m.cols), bf16.predict(m.rows, m.cols))[0, 1])
    assert corr > 0.995, corr


def test_landing_perm_matches_scatter(small_matrix):
    """The gather-based landing (inverse permutation) must produce exactly the
    scatter path's result — same solved values, different write mechanism."""
    from albedo_tpu.datasets.ragged import device_bucket, group_buckets
    from albedo_tpu.models.als import _landing_perm
    from albedo_tpu.ops.als import scan_half_sweep

    m = small_matrix
    rng = np.random.default_rng(5)
    rank = 8
    user_f = jnp.asarray(rng.normal(0, 0.1, (m.n_users, rank)).astype(np.float32))
    item_f = jnp.asarray(rng.normal(0, 0.1, (m.n_items, rank)).astype(np.float32))
    host_groups = group_buckets(bucket_rows(*m.csr(), batch_size=32))
    groups = [device_bucket(g) for g in host_groups]
    landing = jnp.asarray(_landing_perm(host_groups, m.n_users))
    reg_a, alpha_a = jnp.float32(0.3), jnp.float32(10.0)
    via_scatter = np.asarray(
        scan_half_sweep(item_f, user_f, groups, reg_a, alpha_a, "cholesky")
    )
    via_landing = np.asarray(
        scan_half_sweep(
            item_f, user_f, groups, reg_a, alpha_a, "cholesky", landing=landing
        )
    )
    np.testing.assert_array_equal(via_landing, via_scatter)


def test_fused_init_matches_eager_init(small_matrix):
    """The in-program seeded init (als_init_fit_fused) must produce the same
    factors as an explicit warm start from the eagerly computed seeded init —
    identical traced PRNG ops, identical key."""
    m = small_matrix
    kw = dict(rank=6, reg_param=0.5, alpha=10.0, max_iter=3, seed=7)
    fused = ImplicitALS(**kw).fit(m)

    key = jax.random.PRNGKey(7)
    ukey, ikey = jax.random.split(key)
    scale = 1.0 / np.sqrt(6)
    uf0 = np.asarray(jax.random.normal(ukey, (m.n_users, 6), jnp.float32) * scale)
    vf0 = np.asarray(jax.random.normal(ikey, (m.n_items, 6), jnp.float32) * scale)
    warm = ImplicitALS(**kw, init_factors=(uf0, vf0)).fit(m)
    # atol covers ulp-level reassociation between the two XLA programs (a
    # diverged init would differ at the 1e-1 scale, not 1e-6): observed
    # 1.2e-6 on one element of 720 on CPU.
    np.testing.assert_allclose(
        fused.user_factors, warm.user_factors, rtol=1e-5, atol=5e-6
    )


def test_fit_layout_cache_and_report(small_matrix):
    """A second fit on the same matrix reuses the bucket layout + device
    upload (prep_cached) and reports the wall-clock split."""
    m = synthetic_stars(n_users=60, n_items=40, mean_stars=6, seed=23)
    als = ImplicitALS(rank=4, max_iter=2, seed=0)
    als.fit(m)
    assert als.last_fit_report["prep_cached"] is False
    als2 = ImplicitALS(rank=4, max_iter=2, seed=0)
    als2.fit(m)
    assert als2.last_fit_report["prep_cached"] is True
    assert set(als2.last_fit_report) >= {"prep_s", "device_s", "prep_cached"}


# --- one fit: what every path decides in one place ---------------------------
# (`ImplicitALS._choose_path` / `_finish`, `ops.als.check_solver` /
# `seeded_factors`.) The paths are forced here; admission's own choices are
# covered where each path is (test_als_chunked.py, test_sharded_als.py).

FIT_PATHS = {
    "resident": dict(chunked=False),
    "chunked": dict(chunked=True),
    "sharded": dict(sharded="resident"),
    "sharded_streamed": dict(sharded="streamed"),
    "sharded_streamed_sync": dict(sharded="streamed_sync"),
}


def _estimator(path, **kw):
    from albedo_tpu.parallel import make_mesh

    forced = dict(FIT_PATHS[path])
    if "sharded" in forced:
        forced["mesh"] = make_mesh(8)
    return ImplicitALS(rank=8, batch_size=32, seed=5, **forced, **kw)


@pytest.mark.parametrize("path", ["resident", "chunked", "sharded"])
def test_unknown_solver_is_refused_before_any_layout_is_built(path):
    from albedo_tpu.models.als import _matrix_cache

    m = synthetic_stars(n_users=40, n_items=30, mean_stars=5, seed=2)
    est = _estimator(path, max_iter=1, solver="lu")
    with pytest.raises(ValueError, match=r"unknown solver 'lu' \(expected 'cholesky' or 'cg'\)"):
        est.fit(m)
    # nothing was priced, bucketed, uploaded or compiled for it
    assert _matrix_cache(m) == {}
    assert not hasattr(est, "last_fit_report")


SHARED_REPORT_KEYS = {
    "prep_s": float, "bucket_s": float, "upload_s": float, "compile_s": float,
    "compile_source": (str, type(None)), "device_s": float, "prep_cached": bool,
    "health": dict, "mode": str, "capacity": (dict, type(None)),
    "cg_gram_entry_share": float, "gather_reformed_entry_share": float,
    "gather_packed_entry_share": float, "exact_systems_per_sweep": int,
    "exact_system_share": float, "exact_lane_systems_per_sweep": int,
    "exact_lane_share": float, "landed_in_place_share": float,
    "stream_slot_row_share": float, "spans": dict,
}
OWN_REPORT_KEYS = {
    "resident": {"capacity_cross_check"},
    "chunked": {"chunked_shapes", "dispatches", "buckets", "streamed_bytes_per_sweep",
                "rows_per_dispatch", "merged_entry_share"},
    "sharded": {"shard_mode", "n_shards", "streamed_buckets", "sharded_shapes",
                "pipelined", "prefetch_wait_s", "mesh_events",
                "assembled_bytes_per_sweep", "collective_bytes_per_sweep",
                "dispatches", "shard_padded_entries"},
}


@pytest.mark.parametrize("path", list(FIT_PATHS))
def test_every_path_reports_the_shared_keys_and_spans(path, small_matrix):
    """The contract the benchmark's readers rely on, whichever path ran."""
    est = _estimator(path, max_iter=1, solver="cg")
    est.fit(small_matrix)
    rep = est.last_fit_report
    for key, kind in SHARED_REPORT_KEYS.items():
        assert isinstance(rep[key], kind), (key, rep[key])
    assert set(rep) - set(SHARED_REPORT_KEYS) == OWN_REPORT_KEYS[path.split("_")[0]]
    # the synchronous dataflow is the streamed mode's, told apart by `pipelined`
    assert rep["mode"] == ("sharded_streamed" if path.endswith("_sync") else path)
    if path.startswith("sharded"):
        # resident buckets: every device solves its own rows, nothing to pipeline
        assert rep["pipelined"] is (path == "sharded_streamed")
    assert set(rep["health"]) == {"nonfinite", "max_abs", "rms"}
    assert rep["compile_s"] >= 0 and 0 <= rep["cg_gram_entry_share"] <= 1
    assert rep["gather_packed_entry_share"] == 1.0       # rank 8: two rows a 128-lane line
    totals, counts = rep["spans"]["totals"], rep["spans"]["counts"]
    assert counts["fit"] == counts["fit.wait"] == counts["fit.prep"] == 1
    assert totals["fit"] >= totals["fit.wait"] > 0
    assert totals["fit.acquire"] >= rep["compile_s"] - 1e-3


@pytest.mark.parametrize("rank,want", [(8, 1.0), (64, 1.0), (65, 0.0), (128, 0.0)])
def test_packed_share_is_the_ranks_and_the_same_on_the_fused_and_chunked_paths(rank, want, small_matrix):
    """``gather_packed_entry_share``: every padded entry where two factor rows
    fit a 128-lane line (``ops.als.gather_packs_rows``), none where they do
    not, whichever path gathered one layout."""
    shares = []
    for path in ("resident", "chunked"):
        est = ImplicitALS(rank=rank, batch_size=32, seed=5, max_iter=1, solver="cg", **FIT_PATHS[path])
        est.fit(small_matrix)
        assert est.last_fit_report["mode"] == path
        shares.append(est.last_fit_report["gather_packed_entry_share"])
    assert shares == [want, want]
    assert all(isinstance(share, float) for share in shares)


@pytest.mark.parametrize("path", list(FIT_PATHS) + ["resident_callback"])
def test_every_path_starts_from_the_same_seeded_tables(path):
    """Every path's sweep loop runs ``max_iter`` times, so a fit of no sweeps
    returns its first-sweep input: ``ops.als.seeded_factors``. The paths that
    call it eagerly return its bits — also at a rank whose ``1/sqrt(rank)``
    rounds differently in float64, where the copies this replaced (a
    float64 scale, rounded) were one bit off the fused program's. The fused
    program traces the same function and XLA folds the draw's own last
    multiply into the scale's, so its tables may differ in the last two bits;
    so may the resident row-sharded fit's, drawn on the mesh by one program."""
    from albedo_tpu.ops.als import seeded_factors

    m = synthetic_stars(n_users=40, n_items=30, mean_stars=5, seed=2)
    rank = 7
    est = dataclasses.replace(
        _estimator(path.removesuffix("_callback"), max_iter=0), rank=rank)
    model = est.fit(m, callback=(lambda *a: None) if path.endswith("_callback") else None)
    want = seeded_factors(jax.random.PRNGKey(est.seed), m.n_users, m.n_items, rank)
    for got, table in zip((model.user_factors, model.item_factors), want):
        if path in ("resident", "sharded"):
            # Both draw inside a COMPILED program: the fused one-chip fit, and
            # ``parallel.als.make_seeded_tables``, which jits ``seeded_factors``
            # with the pad and the deal into the shards' row order so that the
            # first tables are made on the mesh and not uploaded (5.6 GB a fit
            # at 10M x 1M). XLA folds the normal draw's last multiply into the
            # ``1/sqrt(rank)`` scale where the eager call rounds twice: up to
            # 2 ulp, on one device as on four — the compiler's, not the
            # sharding's, so it cannot be bit-equal short of an eager draw
            # and an upload. The paths below call it eagerly.
            np.testing.assert_array_max_ulp(got, np.asarray(table), maxulp=2)
        else:
            np.testing.assert_array_equal(got, np.asarray(table))
