"""The slot count a bucket is gathered at (``ops.als.gather_slots``) and the
table it is gathered from (``ops.als.gather_table``: two rows a 128-lane line
where they fit): the block ``_gather`` hands back holds ``source[idx]`` bit for
bit whatever it grows to and whichever table it read, every fit path equals
the same path run with the parent's gather, and one rule each decides for the
kernel and for ``gather_reformed_entry_share`` / ``gather_packed_entry_share``
alike."""

import functools
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
    block, lines = ops._gather(source, idx, gather_dtype, rank)
    assert lines is block                      # a plain table: the rows are the lines
    want = parent_gather(source, idx, gather_dtype)
    grown = ops.gather_slots(n_slots, length)
    assert block.shape == (grown, length, rank) and block.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(block[:n_slots]), np.asarray(want))
    # the slots beyond the bucket's own read row 0, like any padding slot
    row0 = np.asarray(parent_gather(source, jnp.zeros((1, 1), jnp.int32), gather_dtype))[0, 0]
    np.testing.assert_array_equal(
        np.asarray(block[n_slots:]), np.broadcast_to(row0, (grown - n_slots, length, rank)))


@pytest.mark.parametrize("gather_dtype", [None, "bfloat16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("n_rows", [96, 97], ids=["even", "odd"])
@pytest.mark.parametrize("rank", [8, 50, 64])
@pytest.mark.parametrize("n_slots,length", [(6, 16), (33, 24), (128, 8), (103, 152)])
def test_line_table_block_folds_to_the_parents_rows_bit_for_bit(
        n_slots, length, rank, n_rows, gather_dtype):
    """A block gathered from the line table holds each entry's row in the half
    its index's parity names and zeros in the other: the two halves add up to
    ``source[idx]`` bit for bit, padding indices and grown slot rows included."""
    rng = np.random.default_rng(n_slots * 31 + length + n_rows)
    source = jnp.asarray(rng.normal(0, 0.4, (n_rows, rank)), jnp.float32)
    idx = rng.integers(0, n_rows, (n_slots, length)).astype(np.int32)
    idx[:, length - length // 3:] = 0          # padding slots at every row's tail
    idx[0, 0], idx[-1] = n_rows - 1, 0         # the last row (beside the zero row, if odd); an all-padding row
    table = ops.gather_table(source)
    assert table.shape == ((n_rows + 1) // 2, ops.LANES) and table.dtype == source.dtype
    block, lines = (np.asarray(a) for a in ops._gather(table, jnp.asarray(idx), gather_dtype, rank))
    grown = ops.gather_slots(n_slots, length)
    assert block.shape == lines.shape == (grown, length, ops.LANES)
    odd = np.pad(idx, ((0, grown - n_slots), (0, 0)))[..., None] % 2 == 1
    lower, upper = block[..., :ops.HALF], block[..., ops.HALF:]
    np.testing.assert_array_equal(np.where(odd, lower, upper), 0)
    np.testing.assert_array_equal(block[..., rank:ops.HALF], 0)      # and between the rows
    np.testing.assert_array_equal(block[..., ops.HALF + rank:], 0)
    # beside it the lines as they were fetched: the block wherever the block is not zero
    np.testing.assert_array_equal(
        np.where(odd, lines[..., ops.HALF:], lines[..., :ops.HALF]), np.where(odd, upper, lower))
    want = np.asarray(parent_gather(source, jnp.asarray(idx), gather_dtype))
    folded = np.asarray(ops._fold(jnp.asarray(block), rank))
    assert folded.dtype == want.dtype
    np.testing.assert_array_equal(folded[:n_slots], want)
    np.testing.assert_array_equal(
        folded[n_slots:], np.broadcast_to(want[-1, -1], (grown - n_slots, length, rank)))


@pytest.mark.parametrize("n_rows", [10, 11])
@pytest.mark.parametrize("rank,packs", [(8, True), (50, True), (64, True), (65, False), (128, False)])
def test_two_rows_share_a_line_only_where_they_fit_its_lanes(rank, packs, n_rows):
    assert (ops.LANES, ops.HALF) == (128, 64) and ops.gather_packs_rows(rank) is packs
    source = jnp.arange(n_rows * rank, dtype=jnp.float32).reshape(n_rows, rank)
    table = ops.gather_table(source)
    shapes = [(3, 1024, 8), (64, 304)]
    assert ops.gather_packed_entry_share(shapes, rank) == float(packs)
    assert ops.gather_packed_entry_share([], rank) == 0.0
    if not packs:
        assert table is source                  # nothing is built: the parent's program
        return
    lines = np.asarray(table)
    padded = np.zeros((n_rows + n_rows % 2, rank), np.float32)
    padded[:n_rows] = np.asarray(source)
    assert lines.shape == (len(padded) // 2, 128)
    np.testing.assert_array_equal(lines[:, :rank], padded[0::2])           # row 2i in lanes 0:k
    np.testing.assert_array_equal(lines[:, 64:64 + rank], padded[1::2])    # row 2i + 1 in lanes 64:64 + k
    np.testing.assert_array_equal(lines[:, rank:64], 0)
    np.testing.assert_array_equal(lines[:, 64 + rank:], 0)
    # a plain table handed to the gather gets the parent's gather, whatever its rank
    idx = jnp.asarray([[0, n_rows - 1, 3]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops._gather(source, idx, None, rank)[0]), np.asarray(source)[np.asarray(idx)])


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


@pytest.mark.parametrize("shape,packed,want", [
    ((51, 40888), True, (6, 9)), ((16, 125104), True, (4, 4)), ((8192, 152), True, (4, 2048)),
    ((128, 15360), True, (4, 32)), ((25, 82248), True, (5, 5)),  # more pieces where they leave no empty slot
    ((19, 108784), True, (5, 4)), ((22, 94592), True, (6, 4)),   # one and two empty slots in the last piece
    ((8192, 64), True, (1, 8192)), ((4, 108784), True, (1, 4)),  # at most GATHER_VMEM_ROWS flat rows: whole
    ((2, 900000), True, (2, 1)), ((1, 900000), True, (1, 1)),    # a row longer than the bound: a piece of its own
    ((51, 40888), False, (1, 51)), ((16, 125104), False, (1, 16)),   # a plain table: the bucket as it is
])
def test_a_line_table_bucket_is_scanned_in_pieces_the_compiler_keeps_the_table_for(shape, packed, want):
    assert ops.GATHER_VMEM_ROWS == 1 << 19
    pieces, per = ops.gather_pieces(*shape, packed)
    assert (pieces, per) == want
    assert 0 <= pieces * per - shape[0] < per                # the last piece holds a slot of the bucket's
    assert pieces == 1 or per == 1 or per * shape[1] <= ops.GATHER_VMEM_ROWS
    group = (3,) + shape
    assert ops.scanned_shape(group, 50 if packed else 128) == (3 * pieces, per, shape[1])


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
@pytest.mark.parametrize("landed", [True, False], ids=["landing", "scatter"])
def test_a_half_sweep_scanned_in_pieces_is_the_half_sweep(solver, landed, monkeypatch):
    """Every group of a layout cut into pieces (some with empty slot rows in
    the last) against the same half-sweep on whole buckets."""
    from albedo_tpu.datasets.ragged import Bucket

    m = stars(n_users=300, n_items=120)
    est = ImplicitALS(**dict(KW, batch_size=64), solver=solver)
    ug, _, u_land, _ = est.device_groups(m)
    groups = [Bucket(*g) for g in ug]
    assert all(g.idx.shape[1] % 3 for g in groups)          # no slot count that 3 divides
    rng = np.random.default_rng(2)
    source = jnp.asarray(rng.normal(0, 0.3, (m.n_items, KW["rank"])), jnp.float32)
    target = jnp.asarray(rng.normal(0, 0.3, (m.n_users, KW["rank"])), jnp.float32)
    landing = u_land if landed else None

    def half_sweep():
        return np.asarray(ops.scan_half_sweep(
            source, target, groups, jnp.float32(0.5), jnp.float32(40.0), solver, 3, landing))

    whole = half_sweep()
    monkeypatch.setattr(ops, "gather_pieces", lambda n_slots, length, packed: (3, -(-n_slots // 3)))
    # each slot row's arithmetic is its own (to the ulp: a piece of another
    # slot count may be tiled otherwise)
    np.testing.assert_allclose(half_sweep(), whole, rtol=1e-5, atol=1e-6)


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
    has a faster one within an eighth more slots, over all padded entries.
    On a mesh (``n_shards``) every device buckets its own rows — dealt to
    the shards in turn by length — and a bucket takes the slot count of the
    shard with most rows in it."""
    reformed = total = 0
    for indptr in (matrix.csr()[0], matrix.csc()[0]):
        lengths = np.sort(np.diff(indptr), kind="stable")
        tiers = {}
        for d in range(n_shards):
            mine = np.concatenate([[0], np.cumsum(lengths[d::n_shards])])
            plans = plan_buckets(mine, batch_size=est.batch_size, max_entries=est.max_entries)
            for j, plan in enumerate(plans):
                nth = sum(p.shape[1] == plan.shape[1] for p in plans[:j])
                key = (plan.shape[1], nth)
                tiers[key] = max(tiers.get(key, 0), plan.shape[0])
        for (length, _), local in tiers.items():
            total += local * length
            grows = not fast(local * length) and any(
                fast(s * length) for s in range(local + 1, local + local // 8 + 1))
            reformed += local * length * grows
    return reformed / total


PARENTS = {
    "slots": ("gather_slots", lambda n, _: n),          # PR 30's parent: the bucket's own slot count
    "lines": ("gather_packs_rows", lambda rank: False),  # PR 32's parent: the table itself
}


@pytest.mark.parametrize("parent", list(PARENTS))
@pytest.mark.parametrize("kwargs", PATHS)
def test_every_fit_path_equals_itself_under_the_parents_gather(kwargs, parent, monkeypatch, tmp_path):
    m = stars()
    est, users, items = fit(kwargs, m)
    share = est.last_fit_report["gather_reformed_entry_share"]
    packed = est.last_fit_report["gather_packed_entry_share"]
    if kwargs.get("shard_mode") == "ring":
        assert share == packed == 0.0            # the ring gathers phase by phase, not through _gather
    else:
        n_shards = 8 if kwargs.get("sharded") else 1    # each device gathers its own slots
        assert share == pytest.approx(share_by_hand(m, est, n_shards), abs=1e-12)
        assert 0.0 < share < 1.0
        assert packed == 1.0                     # rank 8: two rows a line, on both sides

    # the same path on the parent's gather, from cold caches of its own
    monkeypatch.setattr(ops, *PARENTS[parent])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "parent-cache"))
    reset_memory_cache()
    jax.clear_caches()
    try:
        # (a matrix of its own: a chunked fit's executables stay with the matrix)
        parent_est, want_users, want_items = fit(kwargs, stars())
    finally:
        reset_memory_cache()
        jax.clear_caches()
    if parent == "lines":
        assert parent_est.last_fit_report["gather_packed_entry_share"] == 0.0
    np.testing.assert_allclose(users, want_users, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(items, want_items, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("parent", list(PARENTS))
def test_fold_in_equals_itself_under_the_parents_gather(parent, monkeypatch):
    from albedo_tpu.streaming import foldin

    m = stars(n_users=300, n_items=200)
    model = ImplicitALS(**dict(KW, batch_size=64), solver="cg").fit(m)
    rng = np.random.default_rng(4)
    rows = [(rng.choice(200, 12, replace=False).astype(np.int32),
             rng.integers(1, 6, 12).astype(np.float32)) for _ in range(64)]
    assert ops.gather_slots(64, 16) == 65        # the batch's own rung grows
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    got = foldin.FoldInEngine(model, max_batch=64).fold_in(rows)
    monkeypatch.setattr(ops, *PARENTS[parent])
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    want = foldin.FoldInEngine(model, max_batch=64).fold_in(rows)
    monkeypatch.setattr(foldin, "_foldin_solve_jit", None)
    assert got.shape == want.shape == (64, KW["rank"])
    if parent == "lines":
        # one small bucket against a table the engine does not own: the plain gather, untouched
        np.testing.assert_array_equal(got, want)
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


def small_layout(rank, solver="cg"):
    """Abstract arguments of ``als_init_fit_fused`` on one small layout, whose
    user side has buckets in both of the CG's forms at rank 8."""
    m = stars(n_users=400, n_items=150)
    est = ImplicitALS(**dict(KW, rank=rank, batch_size=64), solver=solver)
    ug, ig, u_land, i_land = est.device_groups(m)
    args = (jax.random.PRNGKey(0), ug, ig, jnp.float32(0.5), jnp.float32(40.0), jnp.int32(2))
    statics = dict(n_users=m.n_users, n_items=m.n_items, rank=rank, solver=solver,
                   cg_steps=3, gather_dtype=None)
    return m, args, dict(user_landing=u_land, item_landing=i_land), statics


def enclosing(jaxpr, found, stack=()):
    """``found(eqn)`` equations of a jaxpr with the primitives that enclose each."""
    out = []
    for eqn in jaxpr.eqns:
        if found(eqn):
            out.append(stack)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += enclosing(sub, found, stack + (eqn.primitive.name,))
    return out


def test_line_tables_are_built_once_a_half_sweep_outside_every_scan():
    rank = 8
    m, args, landings, statics = small_layout(rank)
    lines = {((n + 1) // 2, ops.LANES) for n in (m.n_users, m.n_items)}
    assert len(lines) == 2

    def builds_lines(eqn):
        return eqn.primitive.name == "reshape" and eqn.outvars[0].aval.shape in lines

    fit_fn = functools.partial(ops.als_init_fit_fused.__wrapped__, **statics)
    jaxpr = jax.make_jaxpr(fit_fn)(*args, **landings).jaxpr
    built = enclosing(jaxpr, builds_lines)
    # one relayout of each table a sweep, in the sweep loop's body and in no bucket scan's
    assert len(built) == 2 and all("scan" not in stack for stack in built)
    assert all(stack.count("while") == 1 for stack in built)
    scans = enclosing(jaxpr, lambda eqn: eqn.primitive.name == "scan")
    assert len(scans) == len(args[1]) + len(args[2])     # every group's scan is there to be outside of
    # ... and the lowering keeps them two
    text = ops.als_init_fit_fused.lower(*args, **landings, **statics).as_text()
    for n_lines, width in lines:
        assert len(re.findall(
            rf"stablehlo\.reshape [^\n]*-> tensor<{n_lines}x{width}xf32>", text)) == 1


def parent_gather_at_slots(source, idx, gather_dtype, rank):
    """``ops._gather`` as PR 32's parent had it."""
    with jax.named_scope("als.gather"):
        idx = ops._with_slots(idx, ops.gather_slots(*idx.shape))
        rows = parent_gather(source, idx, gather_dtype)
        return rows, rows


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
@pytest.mark.parametrize("program", ["fused", "chunked"])
def test_rank_128_programs_are_the_parents_text(program, solver, monkeypatch):
    """Where two rows do not fit a line nothing of the line table reaches the
    program: its lowered text is, byte for byte, the one traced with the
    parent's gather and with no table form, fold or spread at all."""
    rank = 128
    if program == "fused":
        _, args, kwargs, statics = small_layout(rank, solver)
        fn = ops.als_init_fit_fused
    else:
        sds = jax.ShapeDtypeStruct
        shape = (64, 304)                    # L >= 2k: the CG on its Gramian
        args = (sds((150, rank), jnp.float32), sds((rank, rank), jnp.float32),
                sds((400, rank), jnp.float32), sds((ops.slab_entries(*shape),), jnp.int32),
                sds((), jnp.float32), sds((), jnp.float32))
        kwargs, statics = {}, dict(shape=shape, solver=solver, cg_steps=3, gather_dtype=None)
        fn = ops.chunked_bucket_update

    def lowered():
        jax.clear_caches()
        return jax.jit(fn.__wrapped__, static_argnames=tuple(statics)).lower(
            *args, **kwargs, **statics).as_text()

    text = lowered()
    monkeypatch.setattr(ops, "_gather", parent_gather_at_slots)
    monkeypatch.setattr(ops, "gather_table", lambda source: source)
    monkeypatch.setattr(ops, "gather_pieces", lambda n_slots, length, packed: (1, n_slots))
    monkeypatch.setattr(ops, "_fold", lambda x, rank, axes=(-1,): x)
    monkeypatch.setattr(ops, "_spread", lambda p, width: p)
    try:
        assert lowered() == text
    finally:
        jax.clear_caches()
