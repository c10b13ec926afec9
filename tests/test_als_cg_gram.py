"""The CG's two associations (``ops.als.bucket_cg_body``): long buckets solve
on their explicit (B, k, k) Gramian, short ones stay matrix-free; one
predicate decides, for the kernel and for ``cg_gram_entry_share`` alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from albedo_tpu.datasets.ragged import plan_buckets
from albedo_tpu.datasets.star_matrix import StarMatrix
from albedo_tpu.models.als import ImplicitALS
from albedo_tpu.ops import als as ops
from albedo_tpu.parallel import make_mesh

RANK = 16
LONG = ops.CG_GRAM_LEN_PER_RANK * RANK   # the shortest padded length that takes the Gramian
KW = dict(rank=RANK, max_iter=2, seed=1, solver="cg", batch_size=16)


def parent_cg_solve(gathered, yty, val, mask, x0, reg, alpha, cg_steps):
    """``_cg_solve`` as the parent commit wrote it: matrix-free whatever the
    shape. Kept as the reference both forms are held to."""
    c1 = alpha * val
    w = jnp.where(mask, 1.0 + c1, 0.0)
    n_b = mask.sum(axis=1).astype(jnp.float32)
    b_vec = jnp.einsum("blk,bl->bk", gathered, w, preferred_element_type=jnp.float32)
    diag = (
        jnp.diagonal(yty)[None]
        + ops._gdot("blk,bl->bk", gathered * gathered, c1)
        + (reg * n_b)[:, None]
    )
    diag = jnp.maximum(diag, 1e-12)

    def matvec(p):
        t = c1 * ops._gdot("blk,bk->bl", gathered, p)
        return p @ yty + ops._gdot("blk,bl->bk", gathered, t) + (reg * n_b)[:, None] * p

    tiny = jnp.float32(1e-30)
    x = x0
    r = b_vec - matvec(x)
    z = r / diag
    p = z
    rz = jnp.sum(r * z, axis=1)
    for _ in range(cg_steps):
        ap = matvec(p)
        step = rz / (jnp.sum(p * ap, axis=1) + tiny)
        x = x + step[:, None] * p
        r = r - step[:, None] * ap
        z = r / diag
        rz_new = jnp.sum(r * z, axis=1)
        beta = rz_new / (rz + tiny)
        p = z + beta[:, None] * p
        rz = rz_new
    return x


def bucket(length, n_rows=6, n_source=40, seed=7):
    """A padded bucket with padding slots in every row, zero-weight entries
    (``val == 0`` under a true mask) and an all-padding last row."""
    rng = np.random.default_rng(seed)
    source = rng.normal(0, 0.4, (n_source, RANK)).astype(np.float32)
    idx = rng.integers(0, n_source, (n_rows, length)).astype(np.int32)
    mask = np.arange(length)[None, :] < rng.integers(length // 2, length, (n_rows, 1))
    mask[-1] = False
    val = np.where(mask, rng.integers(1, 11, mask.shape) * 0.5, 0.0).astype(np.float32)
    val[:, ::5] = 0.0
    idx[~mask] = 0
    x0 = rng.normal(0, 0.3, (n_rows, RANK)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (source, idx, val, mask, x0))


def both_forms(length, cg_steps):
    source, idx, val, mask, x0 = bucket(length)
    yty, reg, alpha = ops.gramian(source), jnp.float32(0.5), jnp.float32(40.0)
    got = ops.bucket_cg_body(source, yty, idx, val, mask, x0, reg, alpha, cg_steps)
    want = parent_cg_solve(source[idx], yty, val, mask, x0, reg, alpha, cg_steps)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("cg_steps", [0, 1, 3])
@pytest.mark.parametrize("length", [LONG, 3 * LONG])
def test_long_bucket_on_its_gramian_agrees_with_the_matrix_free_form(length, cg_steps):
    assert ops.cg_uses_gramian(length, RANK)
    got, want = both_forms(length, cg_steps)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    if cg_steps == 0:
        np.testing.assert_array_equal(got, np.asarray(bucket(length)[4]))   # the warm start itself


@pytest.mark.parametrize("cg_steps", [0, 1, 3])
def test_bucket_just_under_the_threshold_is_bit_identical_to_the_parent(cg_steps):
    assert not ops.cg_uses_gramian(LONG - 8, RANK)
    got, want = both_forms(LONG - 8, cg_steps)
    np.testing.assert_array_equal(got, want)


def stars(long_rows=3, n_users=60, n_items=120, seed=5):
    """Every user stars 2-9 repositories; the first ``long_rows`` users star
    ``LONG`` + 5 or more, so only their buckets reach the threshold (no
    repository collects ``LONG`` stars)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(2, 10, n_users)
    lengths[:long_rows] = LONG + 5 + np.arange(long_rows)
    rows = np.repeat(np.arange(n_users), lengths)
    cols = np.concatenate([rng.choice(n_items, n, replace=False) for n in lengths])
    assert np.bincount(cols).max() <= LONG // 2
    return StarMatrix.from_interactions(
        rows + 1_000, cols + 5_000, rng.integers(1, 6, rows.size).astype(np.float32)
    )


def share_by_hand(matrix, est) -> float:
    gram = total = 0
    for indptr in (matrix.csr()[0], matrix.csc()[0]):
        for plan in plan_buckets(indptr, batch_size=est.batch_size, max_entries=est.max_entries):
            n_slots, length = plan.shape
            total += n_slots * length
            gram += n_slots * length * (length >= ops.CG_GRAM_LEN_PER_RANK * est.rank)
    return gram / total


@pytest.mark.parametrize("long_rows", [3, 0])
def test_fit_reports_the_share_of_entries_that_took_the_gramian(long_rows):
    m = stars(long_rows)
    est = ImplicitALS(**KW)
    est.fit(m)
    share = est.last_fit_report["cg_gram_entry_share"]
    assert share == pytest.approx(share_by_hand(m, est), abs=1e-12)
    assert (0.0 < share < 1.0) if long_rows else share == 0.0
    cholesky = ImplicitALS(**dict(KW, solver="cholesky"))
    cholesky.fit(m)
    assert cholesky.last_fit_report["cg_gram_entry_share"] == 0.0


def test_scan_half_sweep_takes_both_forms_in_one_program():
    """The fused sweep over a matrix with long and short buckets equals the
    parent's matrix-free CG bucket by bucket."""
    m = stars()
    est = ImplicitALS(**KW)
    ug, _, u_land, _ = est.device_groups(m)
    rng = np.random.default_rng(2)
    source = jnp.asarray(rng.normal(0, 0.3, (m.n_items, RANK)), jnp.float32)
    target = jnp.asarray(rng.normal(0, 0.3, (m.n_users, RANK)), jnp.float32)
    reg, alpha = jnp.float32(0.5), jnp.float32(40.0)
    groups = [ops.Bucket(*g) for g in ug]
    assert {ops.cg_uses_gramian(g.idx.shape[-1], RANK) for g in groups} == {True, False}
    got = ops.scan_half_sweep(source, target, groups, reg, alpha, "cg", 3, u_land)
    want = np.array(target)
    yty = ops.gramian(source)
    for g in groups:
        for row_ids, idx, val, mask in zip(g.row_ids, g.idx, g.val, g.mask):
            solved = parent_cg_solve(
                source[idx], yty, val, mask, ops.warm_start(target, row_ids), reg, alpha, 3)
            keep = np.asarray(row_ids) >= 0
            want[np.asarray(row_ids)[keep]] = np.asarray(solved)[keep]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("kwargs", [
    pytest.param({"chunked": True}, id="chunked"),
    pytest.param({"sharded": "resident"}, id="sharded-allgather"),
    pytest.param({"sharded": "streamed"}, id="sharded-streamed"),
    pytest.param({"sharded": False}, id="mesh-gspmd"),
])
def test_other_paths_match_the_fused_fit_on_a_matrix_with_long_rows(kwargs):
    m = stars()
    fused = ImplicitALS(**KW, chunked=False)
    want = fused.fit(m)
    if "sharded" in kwargs:
        kwargs = dict(kwargs, mesh=make_mesh(8))
    est = ImplicitALS(**KW, **kwargs)
    got = est.fit(m)
    np.testing.assert_allclose(got.user_factors, want.user_factors, atol=1e-4)
    np.testing.assert_allclose(got.item_factors, want.item_factors, atol=1e-4)
    if "mesh" not in kwargs:
        assert est.last_fit_report["cg_gram_entry_share"] == pytest.approx(
            fused.last_fit_report["cg_gram_entry_share"])
    assert est.last_fit_report["cg_gram_entry_share"] > 0.0
