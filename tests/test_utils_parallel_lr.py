"""Sharded LR training, profiling harness, and schema assertions."""

import numpy as np
import pandas as pd
import pytest

from albedo_tpu.features.assembler import FeatureMatrix
from albedo_tpu.models.logistic_regression import LogisticRegression
from albedo_tpu.parallel import make_mesh
from albedo_tpu.utils import Timer, assert_columns, equals_ignore_nullability


def make_fm(rng, n=700):
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    cat = rng.integers(0, 6, size=n).astype(np.int32)
    bag_idx = rng.integers(0, 9, size=(n, 3)).astype(np.int32)
    bag_idx[rng.random((n, 3)) < 0.3] = -1
    bag_val = np.where(bag_idx >= 0, 1.0, 0.0).astype(np.float32)
    return FeatureMatrix(
        dense=dense, dense_names=list("abcd"),
        cat={"c": cat}, cat_sizes={"c": 6},
        bag_idx={"b": bag_idx}, bag_val={"b": bag_val}, bag_sizes={"b": 9},
    )


def test_sharded_lr_matches_single_device(rng):
    """Row-sharded batch + replicated params == single-device fit: the
    XLA-inserted psum reduction is MLlib's treeAggregate (SURVEY.md §2.5)."""
    fm = make_fm(rng, n=701)  # deliberately not divisible by 8 (padding path)
    w_true = rng.normal(size=fm.num_features)
    y = (rng.random(701) < 1 / (1 + np.exp(-(fm.to_dense() @ w_true)))).astype(np.float32)
    weights = rng.uniform(0.5, 1.5, size=701).astype(np.float32)

    mesh = make_mesh(8)
    base = LogisticRegression(max_iter=80, reg_param=0.05).fit(fm, y, sample_weight=weights)
    shard = LogisticRegression(max_iter=80, reg_param=0.05, mesh=mesh).fit(
        fm, y, sample_weight=weights
    )
    assert shard.train_loss == pytest.approx(base.train_loss, rel=1e-4)
    np.testing.assert_allclose(
        shard.predict_proba(fm), base.predict_proba(fm), rtol=5e-3, atol=5e-3
    )


def test_timer_sections(capsys):
    t = Timer()
    with t.section("a"):
        pass
    with t.section("a"):
        pass
    with t.section("b"):
        pass
    totals = t.report()
    assert t.counts["a"] == 2 and t.counts["b"] == 1
    assert set(totals) == {"a", "b"}
    out = capsys.readouterr().out.splitlines()
    assert out[0].split() == ["span", "total", "s", "calls", "self", "s"]
    assert [row.split()[0] for row in out[1:]] == ["a", "b"] and out[1].split()[2] == "2"


def test_schema_helpers():
    a = pd.DataFrame({"x": [1], "y": [1.0]})
    b = pd.DataFrame({"x": pd.array([2], dtype="Int64"), "y": [2.5]})
    assert equals_ignore_nullability(a, b)
    assert not equals_ignore_nullability(a, a.rename(columns={"x": "z"}))
    assert_columns(a, {"x": "i", "y": "f"})
    with pytest.raises(ValueError, match="missing column"):
        assert_columns(a, {"zzz": "i"})
    with pytest.raises(ValueError, match="dtype kind"):
        assert_columns(a, {"x": "f"})


def test_fit_many_grid_matches_sequential(rng):
    """The vmapped weight-column grid (CV parity) must match per-column fits,
    with and without grid sharding over the 8-device mesh."""
    fm = make_fm(rng, n=500)
    w_true = rng.normal(size=fm.num_features)
    y = (rng.random(500) < 1 / (1 + np.exp(-(fm.to_dense() @ w_true)))).astype(np.float32)
    grid = np.stack(
        [np.ones(500), rng.uniform(0.5, 2.0, 500), rng.uniform(0.1, 1.0, 500)]
    ).astype(np.float32)

    lr = LogisticRegression(max_iter=60, reg_param=0.05)
    seq = [lr.fit(fm, y, sample_weight=w) for w in grid]
    for mesh in (None, make_mesh(8)):
        many = lr.fit_many(fm, y, grid, grid_mesh=mesh)
        assert len(many) == 3
        for m, s in zip(many, seq):
            np.testing.assert_allclose(
                m.coefficients["dense"], s.coefficients["dense"], rtol=2e-2, atol=2e-3
            )
            assert m.train_loss == pytest.approx(s.train_loss, rel=1e-3)
