"""Dataset layer: star matrix, reindexing, bucketing, splits, artifacts."""

import numpy as np
import pytest

from albedo_tpu.datasets import (
    StarMatrix,
    bucket_rows,
    load_or_create_npz,
    random_split_by_user,
    synthetic_stars,
)
from albedo_tpu.datasets.ragged import bucket_shapes
from albedo_tpu.datasets.split import sample_test_users


def test_star_matrix_reindex_roundtrip():
    m = StarMatrix.from_interactions(
        raw_users=[100, 7, 100, 42], raw_items=[900, 900, 800, 700]
    )
    assert m.n_users == 3 and m.n_items == 3 and m.nnz == 4
    assert sorted(m.user_ids.tolist()) == [7, 42, 100]
    # raw -> dense -> raw roundtrip
    dense = m.users_of(np.array([7, 42, 100, 9999]))
    assert dense[3] == -1
    np.testing.assert_array_equal(m.user_ids[dense[:3]], [7, 42, 100])


def test_star_matrix_dedup_keeps_last():
    m = StarMatrix.from_interactions(
        raw_users=[1, 1, 1], raw_items=[5, 5, 6], vals=[1.0, 3.0, 2.0]
    )
    assert m.nnz == 2
    d = m.dense()
    assert d[0, m.items_of(np.array([5]))[0]] == 3.0


def test_csr_csc_agree_with_dense():
    m = synthetic_stars(n_users=50, n_items=40, mean_stars=5, seed=1)
    d = m.dense()
    indptr, cols, vals = m.csr()
    for u in range(m.n_users):
        seg = slice(indptr[u], indptr[u + 1])
        np.testing.assert_allclose(d[u, cols[seg]], vals[seg])
    indptr_c, rows, vals_c = m.csc()
    for i in range(m.n_items):
        seg = slice(indptr_c[i], indptr_c[i + 1])
        np.testing.assert_allclose(d[rows[seg], i], vals_c[seg])


def test_bucket_rows_covers_all_nonzeros():
    m = synthetic_stars(n_users=300, n_items=120, mean_stars=8, seed=2)
    indptr, cols, vals = m.csr()
    buckets = bucket_rows(indptr, cols, vals, batch_size=64)
    total = sum(int(b.mask.sum()) for b in buckets)
    assert total == m.nnz
    # Every nonzero row appears exactly once across buckets.
    seen = np.concatenate([b.row_ids[b.row_ids >= 0] for b in buckets])
    expected = np.nonzero(np.diff(indptr) > 0)[0]
    np.testing.assert_array_equal(np.sort(seen), expected)
    # Padded values are zero so confidence weights vanish on pads.
    for b in buckets:
        assert (b.val[~b.mask] == 0).all()
    # Bounded shape count: ~1.15x geometric length tiers x pow-2 slot counts
    # trade a few more shapes for <=~15% per-row padding (vs 2x at pow-2 tiers).
    assert len(bucket_shapes(buckets)) <= 20


def _plan_by_row_loop(indptr, batch_size, len_multiple=8, max_len=None, max_entries=None):
    """The planner as it chunked before its tier ends came from a
    ``searchsorted``: one step a row. The reference ``plan_buckets`` must
    equal, chunk for chunk."""
    from albedo_tpu.datasets.ragged import _pad_len, _slot_tier

    lengths = np.diff(indptr)
    nonempty = np.nonzero(lengths > 0)[0]
    order = nonempty[np.argsort(lengths[nonempty], kind="stable")]
    eff = lengths[order] if max_len is None else np.minimum(lengths[order], max_len)
    out, start = [], 0
    while start < order.shape[0]:
        pad_l = _pad_len(int(eff[start]), len_multiple)
        if max_len is not None:
            pad_l = max(min(pad_l, -(-max_len // len_multiple) * len_multiple), int(eff[start]))
        allowed = batch_size
        if max_entries is not None:
            allowed = max(1, min(batch_size, max_entries // pad_l))
        end = start
        while end < order.shape[0] and end - start < allowed and eff[end] <= pad_l:
            end += 1
        b = max(end - start, min(_slot_tier(end - start), allowed))
        out.append((order[start:end].tolist(), (b, pad_l),
                    pad_l if max_len is None else min(pad_l, max_len)))
        start = end
    return out


@pytest.mark.parametrize("layout", [
    dict(batch_size=64), dict(batch_size=16, max_entries=256),
    dict(batch_size=1024, max_entries=1 << 12), dict(batch_size=32, max_len=20),
    dict(batch_size=8, max_len=13, len_multiple=2, max_entries=64),
])
@pytest.mark.parametrize("side", ["csr", "csc"])
def test_plan_buckets_equals_the_row_by_row_planner(layout, side):
    from albedo_tpu.datasets.ragged import plan_buckets

    m = synthetic_stars(n_users=700, n_items=150, mean_stars=9, seed=4)
    indptr = getattr(m, side)()[0]
    got = [(p.rows.tolist(), p.shape, p.cap) for p in plan_buckets(indptr, **layout)]
    assert got == _plan_by_row_loop(indptr, **layout)


def test_plan_buckets_takes_a_paths_own_row_allowance():
    """``rows_of``: the same tiers and rows in the same order, chunked at
    the count the path names for each padded length."""
    from albedo_tpu.datasets.ragged import plan_buckets

    m = synthetic_stars(n_users=700, n_items=150, mean_stars=9, seed=4)
    indptr = m.csr()[0]
    base = plan_buckets(indptr, batch_size=16)
    wide = plan_buckets(indptr, batch_size=16, rows_of=lambda ln: 64 if ln == 1 else 16)
    assert np.concatenate([p.rows for p in wide]).tolist() == \
        np.concatenate([p.rows for p in base]).tolist()
    assert [p.shape for p in wide if p.shape[1] > 1] == [p.shape for p in base if p.shape[1] > 1]
    ones = [p.shape[0] for p in wide if p.shape[1] == 1]
    assert max(ones) == 64 and len(ones) < sum(p.shape[1] == 1 for p in base)


def test_bucket_rows_max_len_truncates_to_tail():
    indptr = np.array([0, 5])
    cols = np.arange(5, dtype=np.int32)
    vals = np.arange(5, dtype=np.float32) + 1
    (b,) = bucket_rows(indptr, cols, vals, batch_size=4, max_len=3, len_multiple=2)
    got = b.idx[0][b.mask[0]]
    np.testing.assert_array_equal(got, [2, 3, 4])  # most recent tail kept


def test_random_split_by_user_stratified():
    m = synthetic_stars(n_users=200, n_items=100, mean_stars=10, seed=3)
    train, test = random_split_by_user(m, test_ratio=0.25, seed=7)
    assert train.nnz + test.nnz == m.nnz
    counts = m.user_counts()
    test_counts = test.user_counts()
    train_counts = train.user_counts()
    multi = counts > 1
    # Every multi-star user keeps at least one train item and gets >=1 test item.
    assert (train_counts[multi] >= 1).all()
    assert (test_counts[multi] >= 1).all()
    # Single-star users stay in train.
    single = counts == 1
    assert (test_counts[single] == 0).all()
    # No overlap.
    train_keys = set(zip(train.rows.tolist(), train.cols.tolist()))
    test_keys = set(zip(test.rows.tolist(), test.cols.tolist()))
    assert not (train_keys & test_keys)


def test_split_deterministic():
    m = synthetic_stars(n_users=100, n_items=60, mean_stars=6, seed=4)
    t1, e1 = random_split_by_user(m, 0.2, seed=5)
    t2, e2 = random_split_by_user(m, 0.2, seed=5)
    np.testing.assert_array_equal(t1.rows, t2.rows)
    np.testing.assert_array_equal(e1.cols, e2.cols)


def test_sample_test_users_includes_canary():
    m = synthetic_stars(n_users=100, n_items=50, mean_stars=5, seed=6)
    users = sample_test_users(m, n=10, always_include=np.array([3]), seed=1)
    assert 3 in users.tolist()
    assert users.dtype == np.int32


def test_load_or_create_npz_memoizes(tmp_path):
    calls = []

    def create():
        calls.append(1)
        return {"a": np.arange(5), "b": np.eye(2, dtype=np.float32)}

    first = load_or_create_npz("factors-test", create)
    second = load_or_create_npz("factors-test", create)
    assert len(calls) == 1
    np.testing.assert_array_equal(first["a"], second["a"])
    np.testing.assert_array_equal(first["b"], second["b"])


def test_synthetic_power_law_shape():
    m = synthetic_stars(n_users=500, n_items=300, mean_stars=12, seed=8)
    counts = m.item_counts()
    top10 = np.sort(counts)[-10:].sum()
    assert top10 > 0.1 * m.nnz  # popularity skew exists
    assert (m.user_counts() >= 1).all()


def test_clean_by_counts_chained_filters():
    """DataCleaner parity: item range filter first, then user range filter
    computed on the already-item-filtered interactions."""
    from albedo_tpu.datasets import clean_by_counts

    m = synthetic_stars(n_users=200, n_items=120, mean_stars=10, seed=12)
    cleaned = clean_by_counts(
        m, min_item_stargazers=3, max_item_stargazers=60,
        min_user_starred=2, max_user_starred=40,
    )
    ic_orig = m.item_counts()
    # The result is re-indexed over survivors only: map back to the original
    # dense ids through the raw vocabularies.
    orig_items = m.items_of(cleaned.item_ids[cleaned.cols])
    assert ((ic_orig[orig_items] >= 3) & (ic_orig[orig_items] <= 60)).all()
    # Every surviving user's count AFTER the item filter is in range.
    item_ok = (ic_orig >= 3) & (ic_orig <= 60)
    m1 = m.select(item_ok[m.cols])
    uc_mid = m1.user_counts()
    orig_users = m.users_of(cleaned.user_ids[np.unique(cleaned.rows)])
    assert ((uc_mid[orig_users] >= 2) & (uc_mid[orig_users] <= 40)).all()
    # Dropped something, and the vocabularies shrank with it (no ghost rows
    # for downstream factor tables).
    assert cleaned.nnz < m.nnz
    assert cleaned.n_items < m.n_items
    assert cleaned.n_items == np.unique(cleaned.cols).size
    assert cleaned.n_users == np.unique(cleaned.rows).size


def test_sparsity():
    from albedo_tpu.datasets import StarMatrix

    m = StarMatrix(
        user_ids=np.array([1, 2]),
        item_ids=np.array([10, 20]),
        rows=np.array([0, 1], dtype=np.int32),
        cols=np.array([0, 1], dtype=np.int32),
        vals=np.ones(2, dtype=np.float32),
    )
    # 2 of 4 cells filled -> sparsity 0.5 (albedo_toolkit calculate_sparsity).
    assert m.sparsity() == 0.5
