"""The slot count a bucket is gathered at (``ops.als.gather_slots``): the block
``_gather`` hands back holds ``source[idx]`` bit for bit whatever it grows to,
every fit path equals the same path run with the parent's gather, and one rule
decides for the kernel and for ``gather_reformed_entry_share`` alike."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from albedo_tpu.datasets.ragged import plan_buckets
from albedo_tpu.datasets.star_matrix import StarMatrix
from albedo_tpu.models.als import ImplicitALS
from albedo_tpu.ops import als as ops
from albedo_tpu.parallel import make_mesh
from albedo_tpu.utils.aot import reset_memory_cache

LENGTHS = [1, 2, 4, 8, 24, 152, 384, 1064, 20320]
SLOTS = [1, 2, 33, 103, 1024, 5461]
# every tier form whose block a CPU test can hold (the planner's own bound is 2^21)
FORMS = [(b, l) for l in LENGTHS for b in SLOTS if b * l <= 1 << 17]
TILE, MIN_PAD = ops.GATHER_INDEX_TILE, ops.GATHER_MIN_PAD


def fast(rows: int) -> bool:
    """The 256-row form: the flat count lies MIN_PAD or more short of a tile."""
    return -rows % TILE >= MIN_PAD


def parent_gather(source, idx, gather_dtype):
    """``_gather`` as the parent commit wrote it, the reference every form is
    held to."""
    if gather_dtype is None:
        return source[idx]
    return source.astype(jnp.dtype(gather_dtype))[idx]


@pytest.mark.parametrize("gather_dtype", [None, "bfloat16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("rank", [50, 128])
@pytest.mark.parametrize("n_slots,length", FORMS)
def test_block_holds_the_parents_rows_bit_for_bit(n_slots, length, rank, gather_dtype):
    rng = np.random.default_rng(n_slots * 31 + length)
    source = jnp.asarray(rng.normal(0, 0.4, (97, rank)), jnp.float32)
    idx = rng.integers(0, 97, (n_slots, length)).astype(np.int32)
    idx[:, length - length // 3:] = 0          # padding slots at every row's tail
    idx[-1] = 0                                # an all-padding row
    idx = jnp.asarray(idx)
    block = ops._gather(source, idx, gather_dtype)
    want = parent_gather(source, idx, gather_dtype)
    grown = ops.gather_slots(n_slots, length)
    assert block.shape == (grown, length, rank) and block.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(block[:n_slots]), np.asarray(want))
    # the slots beyond the bucket's own read row 0, like any padding slot
    row0 = np.asarray(parent_gather(source, jnp.zeros((1, 1), jnp.int32), gather_dtype))[0, 0]
    np.testing.assert_array_equal(
        np.asarray(block[n_slots:]), np.broadcast_to(row0, (grown - n_slots, length, rank)))


@pytest.mark.parametrize("shape,want", [
    ((103, 20320), 104), ((64, 30912), 65), ((128, 13352), 129), ((3072, 600), 3073),
    ((8192, 152), 8193), ((8192, 80), 8193), ((8192, 8), 8193), ((8192, 1), 8193),
    ((16, 94592), 17), ((256, 5008), 258),
    ((5461, 384), 5461), ((33, 62192), 33), ((118, 17664), 118),   # in the fast form as they are
    ((128, 15360), 128), ((4, 1024), 4),       # a length of whole tiles: no slot count helps
    ((1, 251648), 1), ((2, 143872), 2), ((6, 16), 6), ((1, 8), 1),  # too few slots to grow by an eighth
])
def test_slot_count_is_the_least_that_gets_the_fast_form(shape, want):
    n_slots, length = shape
    got = ops.gather_slots(n_slots, length)
    assert got == want
    assert n_slots <= got <= n_slots + n_slots // ops.GATHER_MAX_GROWTH
    if got != n_slots:
        assert fast(got * length)
        assert not any(fast(s * length) for s in range(n_slots, got))
    elif not fast(n_slots * length):
        assert not any(
            fast(s * length) for s in range(n_slots, n_slots + n_slots // ops.GATHER_MAX_GROWTH + 1))


@pytest.mark.parametrize("n_slots,length", FORMS)
def test_slot_rule_holds_on_every_tier_form(n_slots, length):
    got = ops.gather_slots(n_slots, length)
    assert n_slots <= got <= n_slots + n_slots // ops.GATHER_MAX_GROWTH
    assert got == n_slots or (fast(got * length) and not fast(n_slots * length))


def bucket(n_slots, length, rank, n_source=60, seed=3):
    rng = np.random.default_rng(seed)
    source = rng.normal(0, 0.4, (n_source, rank)).astype(np.float32)
    idx = rng.integers(0, n_source, (n_slots, length)).astype(np.int32)
    mask = np.arange(length)[None, :] < rng.integers(max(1, length // 2), length + 1, (n_slots, 1))
    mask[-1] = False
    val = np.where(mask, rng.integers(1, 11, mask.shape) * 0.5, 0.0).astype(np.float32)
    idx[~mask] = 0
    x0 = rng.normal(0, 0.3, (n_slots, rank)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (source, idx, val, mask, x0))


@pytest.mark.parametrize("solver", ["cg", "cg-gram", "cholesky"])
@pytest.mark.parametrize("n_slots,length", [(128, 8), (64, 16), (33, 24), (6, 16)])
def test_bucket_solves_equal_the_parents_whatever_the_slot_count(n_slots, length, solver, monkeypatch):
    rank = 4 if solver == "cg-gram" else 16     # rank 4: every length here takes the Gramian
    source, idx, val, mask, x0 = bucket(n_slots, length, rank)
    yty, reg, alpha = ops.gramian(source), jnp.float32(0.5), jnp.float32(40.0)

    def solve():
        if solver == "cholesky":
            return ops.bucket_solve_body(source, yty, idx, val, mask, reg, alpha)
        return ops.bucket_cg_body(source, yty, idx, val, mask, x0, reg, alpha, 3)

    got = np.asarray(solve())
    monkeypatch.setattr(ops, "gather_slots", lambda n, _: n)
    want = np.asarray(solve())
    assert got.shape == want.shape == (n_slots, rank)
    np.testing.assert_array_equal(got, want)    # each slot row's arithmetic is its own


def stars(n_users=2600, n_items=700, seed=11):
    """Enough users with at most 8 (and at most 4) stars to fill slot tiers of
    1024 - whose flat counts are whole tiles on one device and on each of 8."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, 9, n_users)
    lengths[:40] = rng.integers(9, 40, 40)
    rows = np.repeat(np.arange(n_users), lengths)
    cols = np.concatenate([rng.choice(n_items, n, replace=False) for n in lengths])
    return StarMatrix.from_interactions(
        rows + 1_000, cols + 5_000, rng.integers(1, 6, rows.size).astype(np.float32))


KW = dict(rank=8, max_iter=2, seed=1, batch_size=1024)
PATHS = [
    pytest.param({"chunked": False}, id="fused"),
    pytest.param({"chunked": False, "solver": "cholesky"}, id="fused-cholesky"),
    pytest.param({"chunked": True}, id="chunked"),
    pytest.param({"sharded": "resident"}, id="sharded-allgather"),
    pytest.param({"sharded": "resident", "shard_mode": "ring", "solver": "cholesky"}, id="sharded-ring"),
    pytest.param({"sharded": False}, id="mesh-gspmd"),
]


def fit(kwargs, matrix):
    kwargs = dict({"solver": "cg"}, **kwargs)
    if "sharded" in kwargs:
        kwargs["mesh"] = make_mesh(8)
    est = ImplicitALS(**KW, **kwargs)
    model = est.fit(matrix)
    return est, np.asarray(model.user_factors), np.asarray(model.item_factors)


def share_by_hand(matrix, est, n_shards=1) -> float:
    """Padded entries of the buckets whose flat count is in the slow form and
    has a faster one within an eighth more slots, over all padded entries."""
    reformed = total = 0
    for indptr in (matrix.csr()[0], matrix.csc()[0]):
        for plan in plan_buckets(indptr, batch_size=est.batch_size, max_entries=est.max_entries):
            n_slots, length = plan.shape
            local = -(-n_slots // n_shards)
            total += local * length
            grows = not fast(local * length) and any(
                fast(s * length) for s in range(local + 1, local + local // 8 + 1))
            reformed += local * length * grows
    return reformed / total


@pytest.mark.parametrize("kwargs", PATHS)
def test_every_fit_path_equals_itself_under_the_parents_gather(kwargs, monkeypatch, tmp_path):
    m = stars()
    est, users, items = fit(kwargs, m)
    share = est.last_fit_report["gather_reformed_entry_share"]
    if kwargs.get("shard_mode") == "ring":
        assert share == 0.0                      # the ring gathers phase by phase, not through _gather
    else:
        n_shards = 8 if kwargs.get("sharded") else 1    # each device gathers its own slots
        assert share == pytest.approx(share_by_hand(m, est, n_shards), abs=1e-12)
        assert 0.0 < share < 1.0

    # the same path on the parent's gather, from cold caches of its own
    monkeypatch.setattr(ops, "gather_slots", lambda n, _: n)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "parent-cache"))
    reset_memory_cache()
    jax.clear_caches()
    try:
        _, want_users, want_items = fit(kwargs, m)
    finally:
        reset_memory_cache()
        jax.clear_caches()
    np.testing.assert_allclose(users, want_users, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(items, want_items, rtol=1e-5, atol=1e-6)


def test_fold_in_equals_itself_under_the_parents_gather(monkeypatch):
    from albedo_tpu.streaming import foldin

    m = stars(n_users=300, n_items=200)
    model = ImplicitALS(**dict(KW, batch_size=64), solver="cg").fit(m)
    rng = np.random.default_rng(4)
    rows = [(rng.choice(200, 12, replace=False).astype(np.int32),
             rng.integers(1, 6, 12).astype(np.float32)) for _ in range(64)]
    assert ops.gather_slots(64, 16) == 65        # the batch's own rung grows
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    got = foldin.FoldInEngine(model, max_batch=64).fold_in(rows)
    monkeypatch.setattr(ops, "gather_slots", lambda n, _: n)
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    want = foldin.FoldInEngine(model, max_batch=64).fold_in(rows)
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    assert got.shape == want.shape == (64, KW["rank"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
def test_grown_bucket_compiles_without_a_block_sized_copy(solver):
    """The grown block is written once, by the gather: nothing of its size is
    copied, sliced, padded, transposed or concatenated on the way to the solve."""
    n_slots, length, rank = 128, 64, 16
    grown = ops.gather_slots(n_slots, length)
    assert grown == n_slots + 1
    source, idx, val, mask, x0 = bucket(n_slots, length, rank)
    yty, reg, alpha = ops.gramian(source), jnp.float32(0.5), jnp.float32(40.0)
    if solver == "cg":
        def solve(*a):
            return ops.bucket_cg_body(*a, reg, alpha, 3)
        args = (source, yty, idx, val, mask, x0)
    else:
        def solve(*a):
            return ops.bucket_solve_body(*a, reg, alpha)
        args = (source, yty, idx, val, mask)
    text = jax.jit(solve).lower(*args).compile().as_text()
    blocks = {form for b in (n_slots, grown) for form in (
        f"{b},{length},{rank}", f"{b * length},{rank}", f"{b * length},1,{rank}")}
    moved = [
        (shape, op) for shape, op in re.findall(
            r"= \w+\[([\d,]+)\][^ ]* (copy|slice|pad|transpose|concatenate|dynamic-slice)\(", text)
        if shape in blocks
    ]
    assert moved == []
    # the gather writes the grown block itself
    gathered = re.findall(r"= f32\[([\d,]+)\][^ ]* gather\([^\n]*als\.gather/gather", text)
    assert [int(np.prod([int(d) for d in g.split(",")])) for g in gathered] == [grown * length * rank]
