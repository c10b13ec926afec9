"""The exact solve (``solver="cholesky"``) as the fused fit runs it: against
the benchmark's plain exact reference at ranks 16 and 50, its three
sub-scopes in the lowered program, the fit report's count of the systems it
factorises, and the resident plan's price of what a bucket's solve holds."""

import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from albedo_tpu.datasets.star_matrix import StarMatrix
from albedo_tpu.models.als import ImplicitALS
from albedo_tpu.ops import als as ops
from albedo_tpu.utils import capacity
from benchmark.manifest import load_module
from benchmark.stars import generate_stars

TINY = json.loads((Path(__file__).parent / "perfbench/data/tiny-chol-r16.json").read_text())
SUB_SCOPES = ("als.cholesky.build", "als.cholesky.factor", "als.cholesky.solve")


def matrix_of(stars: dict) -> StarMatrix:
    return StarMatrix(
        user_ids=np.arange(stars["n_users"], dtype=np.int64),
        item_ids=np.arange(stars["n_items"], dtype=np.int64),
        rows=stars["rows"], cols=stars["cols"], vals=stars["vals"])


# --------------------------------------- (b) the program against the reference

@pytest.mark.parametrize("rank", [16, 50])
def test_the_fused_exact_fit_is_the_plain_exact_reference(rank):
    """Rank 16 and rank 50 are both gathered from a line table (two rows a
    128-lane line), whose ``(B', 128, 128)`` corrections are folded back to
    ``rank``. Both sides compute in float32 and the CPU's matmuls round
    nothing, so what is left is the order of the sums and the factorisation's
    own rounding, amplified by a system's condition number (up to about 1e3
    here): 2e-4 of a row's norm, where the program under CG reads 1e-1."""
    config = dict(TINY, rank=rank)
    stars = generate_stars(config, 20261004)
    als = ImplicitALS(rank=rank, reg_param=0.5, alpha=40.0, max_iter=2, seed=5, solver="cholesky")
    assert ops.gather_packs_rows(rank)
    model = als.fit(matrix_of(stars))
    report = als.last_fit_report
    assert report["mode"] == "resident" and report["capacity"]["verdict"] == "fit"
    want = load_module("reference", "als_exact").fit(stars, config, 5, 2)
    for got, ref in ((model.user_factors, want[0]), (model.item_factors, want[1])):
        norms = np.linalg.norm(ref, axis=1)
        err = np.linalg.norm(np.asarray(got) - ref, axis=1) / np.maximum(norms, np.median(norms))
        assert err.max() < 2e-4
    cg = ImplicitALS(rank=rank, reg_param=0.5, alpha=40.0, max_iter=2, seed=5, solver="cg", cg_steps=3)
    far = np.linalg.norm(np.asarray(cg.fit(matrix_of(stars)).user_factors) - want[0], axis=1)
    assert np.median(far / np.linalg.norm(want[0], axis=1)) > 1e-2


def test_the_default_estimator_and_the_default_cli_run_the_exact_solve():
    from albedo_tpu import cli

    assert ImplicitALS().solver == "cholesky"
    assert cli.build_parser().parse_args(["train_als"]).solver == "cholesky"


# ------------------------------------- (c) the solve itself, a system a lane

def bucket_of(rank: int, n: int, length: int = 8, seed: int = 0):
    """A bucket of ``n`` rows of up to ``length`` stars against a source table
    of ``4 * rank`` rows: a one-star row, an empty row, one more with one
    star, the rest random."""
    rng = np.random.default_rng(seed + 1000 * rank + n)
    source = (rng.standard_normal((4 * rank, rank)) / np.sqrt(rank)).astype(np.float32)
    idx = rng.integers(0, source.shape[0], (n, length)).astype(np.int32)
    stars = rng.integers(0, length + 1, n)
    stars[:3] = (1, 0, 1)[: min(n, 3)]
    mask = np.arange(length)[None] < stars[:, None]
    val = np.where(mask, rng.integers(1, 4, (n, length)), 0).astype(np.float32)
    return source, np.where(mask, idx, 0), val, mask


@pytest.mark.parametrize("n", [1, 17, 128, 129, 1000])
@pytest.mark.parametrize("rank", [8, 16, 50, 128])
def test_the_lane_solve_is_the_float64_solve_of_the_systems_the_kernel_builds(rank, n):
    """``YtY + sum c y y^T + reg n I`` as ``bucket_partial_terms`` leaves it,
    solved a system a lane, against numpy's float64 Cholesky and two
    triangular solves written out here: within 1e-5 of the solution's largest
    entry, and no further off than twice what ``jnp.linalg`` reads in float32
    on the same systems. Blocks of less than a lane tile, a whole one, one
    system more, and several chunks with a tail; ranks on and off the
    sublane tile."""
    source, idx, val, mask = bucket_of(rank, n)
    reg, alpha = np.float32(0.5), np.float32(40.0)
    yty = ops.gramian(jnp.asarray(source))
    c1 = alpha * val
    corr, b_vec = ops.bucket_partial_terms(
        jnp.asarray(source)[idx], jnp.asarray(c1), jnp.where(mask, 1.0 + c1, 0.0))
    n_b = mask.sum(axis=1).astype(np.float32)
    got = np.asarray(jax.jit(ops.solve_corrected)(yty, corr, b_vec, n_b, reg))
    assert got.shape == (n, rank) and np.isfinite(got).all()

    a64 = (np.asarray(yty, np.float64)[None] + np.asarray(corr, np.float64)
           + (np.float64(reg) * n_b)[:, None, None] * np.eye(rank))
    lower = np.linalg.cholesky(a64)
    z = np.linalg.solve(lower, np.asarray(b_vec, np.float64)[..., None])
    want = np.linalg.solve(np.swapaxes(lower, 1, 2), z)[..., 0]
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err <= 1e-5

    a32 = jnp.asarray(a64, jnp.float32)
    lib = jax.scipy.linalg.cho_solve((jnp.linalg.cholesky(a32), True), b_vec[..., None])[..., 0]
    assert err <= 2 * max(np.abs(np.asarray(lib) - want).max() / np.abs(want).max(), 1e-7)

    # through the bucket's own body: the rows the gather grows the block by
    # (128 x 8 and 1000 x 8 are gathered at one slot row more) never reach the result
    body = jax.jit(ops.bucket_solve_body)(
        ops.gather_table(jnp.asarray(source)), yty, idx, val, mask, reg, alpha)
    assert body.shape == (n, rank)
    np.testing.assert_allclose(np.asarray(body), got, rtol=0, atol=1e-5 * np.abs(want).max())


def bucket_args(rank: int) -> tuple:
    """Abstract arguments of one small bucket's program (``solve_rows`` /
    ``chunked_bucket_update``): source, yty, target, row_ids, idx, val, mask,
    reg, alpha."""
    sds = jax.ShapeDtypeStruct
    table = (15, ops.LANES) if ops.gather_packs_rows(rank) else (30, rank)
    return (sds(table, jnp.float32), sds((rank, rank), jnp.float32), sds((20, rank), jnp.float32),
            sds((6,), jnp.int32), sds((6, 16), jnp.int32), sds((6, 16), jnp.float32),
            sds((6, 16), jnp.bool_), sds((), jnp.float32), sds((), jnp.float32))


def slab_args(rank: int) -> tuple:
    """``bucket_args`` as ``chunked_bucket_update`` takes them: the bucket's
    four arrays as one packed slab (``ops.als.pack_slab``), its shape static."""
    sds = jax.ShapeDtypeStruct
    source, yty, target, *_, reg, alpha = bucket_args(rank)
    return source, yty, target, sds((ops.slab_entries(6, 16),), jnp.int32), reg, alpha


def stablehlo_of(solver: str, rank: int) -> str:
    return jax.jit(lambda *a: ops.solve_rows(*a, solver, 3, None)).lower(*bucket_args(rank)).as_text()


@pytest.mark.parametrize("rank", [50, 128])
def test_the_exact_program_calls_no_library_factorisation_and_the_cg_program_did_not_move(rank):
    """``solve_rows(..., "cholesky")`` lowers to the program's own loops - no
    ``cholesky``, no ``triangular_solve``, no custom call of any kind - and
    ``solve_rows(..., "cg")`` to the text of the warm start and
    ``bucket_cg_body`` lowered directly: nothing of the exact solve, nor of
    its rewriting, reaches a CG program."""
    exact = stablehlo_of("cholesky", rank)
    for op in ("cholesky", "triangular_solve", "custom_call", "potrf", "trsm"):
        assert op not in exact, op
    assert "stablehlo.while" in exact and "stablehlo.sqrt" in exact

    def cg_alone(source, yty, target, row_ids, idx, val, mask, reg, alpha):
        return ops.bucket_cg_body(
            source, yty, idx, val, mask, ops.warm_start(target, row_ids), reg, alpha, 3, gather_dtype=None)

    cg = stablehlo_of("cg", rank)
    assert cg == jax.jit(lambda *a: cg_alone(*a)).lower(*bucket_args(rank)).as_text()
    assert cg != exact


# ------------------------------------------------ (e) scopes and the counter

def lowered_text(solver: str, rank: int) -> str:
    """The op names (scope paths) of one bucket's compiled program, a line each."""
    lowered = ops.chunked_bucket_update.lower(
        *slab_args(rank), shape=(6, 16), solver=solver, cg_steps=3, gather_dtype=None)
    return "\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))))


@pytest.mark.parametrize("rank", [4, 128])
def test_the_exact_solve_carries_its_three_sub_scopes_and_the_cg_none(rank):
    exact, cg = lowered_text("cholesky", rank), lowered_text("cg", rank)
    for scope in SUB_SCOPES:
        assert f"als.cholesky/{scope}/" in exact, scope       # nested in the outermost scope
        assert scope not in cg
    assert "als.cholesky" not in cg and "als.cg" not in exact
    # what each holds: the contraction (and the folds, and the systems in the
    # solve's order), the column loop with its square roots, the back substitution
    assert re.search(r"als\.cholesky\.build/.*dot_general$", exact, re.M)
    assert re.search(r"als\.cholesky\.build/transpose$", exact, re.M)
    assert re.search(r"als\.cholesky\.factor/while/.*sqrt$", exact, re.M)
    assert re.search(r"als\.cholesky\.solve/while/", exact, re.M)
    assert not re.search(r"als\.cholesky\.(build|solve)/.*sqrt$", exact, re.M)
    # the program's own loops, no library factorisation
    assert not re.search(r"cholesky$|triangular_solve$", exact, re.M)
    # the folds of (B', 128, 128) and (B', 128) back to the rank are under .build
    assert bool(re.search(r"als\.cholesky\.build/slice$", exact, re.M)) == ops.gather_packs_rows(rank)
    # everything under the outermost scope is under one of the three
    for line in exact.split("\n"):
        assert "als.cholesky" not in line or re.search(r"als\.cholesky/als\.cholesky\.(build|factor|solve)/", line), line


def test_scopes_do_not_reach_the_programs_text():
    """``jax.named_scope`` is location metadata: the lowered program, as it
    is hashed and compared between commits, names no scope."""
    lowered = ops.chunked_bucket_update.lower(
        *slab_args(4), shape=(6, 16), solver="cholesky", cg_steps=3, gather_dtype=None)
    assert "als." not in lowered.as_text() and "als.cholesky.factor" in lowered.as_text(debug_info=True)


def test_exact_system_share_counts_every_slot_row_the_solve_is_handed():
    stars = generate_stars(TINY, 7)
    m = matrix_of(stars)
    als = ImplicitALS(rank=16, max_iter=1, seed=3, solver="cholesky", batch_size=64, max_entries=1 << 12)
    als.fit(m)
    report = als.last_fit_report
    ug, ig, _, _ = als.device_groups(m)
    by_hand = lanes = 0
    for g in (*ug, *ig):
        n, slots, length = g[1].shape
        pieces, per = ops.gather_pieces(slots, length, packed=True)     # rank 16: a line table
        by_hand += n * pieces * per       # the rows the gather grows a piece by are cut before the solve
        lanes += n * pieces * -(-per // 128) * 128
    assert report["exact_systems_per_sweep"] == by_hand
    assert report["exact_lane_systems_per_sweep"] == lanes >= by_hand
    assert report["exact_lane_share"] == pytest.approx(lanes / (TINY["n_users"] + TINY["n_items"]))
    rows = TINY["n_users"] + TINY["n_items"]
    assert report["exact_system_share"] == pytest.approx(by_hand / rows)
    # every logical row with a star is one of them; the rest are empty slots
    solved = np.count_nonzero(np.bincount(stars["rows"], minlength=TINY["n_users"])) + np.count_nonzero(
        np.bincount(stars["cols"], minlength=TINY["n_items"]))
    assert by_hand >= solved and report["exact_system_share"] >= solved / rows
    als_cg = ImplicitALS(rank=16, max_iter=1, seed=3, solver="cg", batch_size=64, max_entries=1 << 12)
    als_cg.fit(m)
    assert als_cg.last_fit_report["exact_systems_per_sweep"] == 0
    assert als_cg.last_fit_report["exact_system_share"] == 0.0
    assert als_cg.last_fit_report["exact_lane_systems_per_sweep"] == 0
    assert als_cg.last_fit_report["exact_lane_share"] == 0.0


@pytest.mark.parametrize("kwargs,devices", [
    pytest.param({"chunked": True}, 1, id="chunked"),
    pytest.param({"sharded": "resident"}, 4, id="sharded_resident"),
    pytest.param({"sharded": True, "shard_mode": "ring"}, 4, id="ring"),
])
def test_every_path_under_the_exact_solve_reports_its_systems(kwargs, devices):
    if devices > 1:
        from albedo_tpu.parallel import make_mesh

        kwargs = dict(kwargs, mesh=make_mesh(devices))
    stars = generate_stars(TINY, 7)
    als = ImplicitALS(rank=16, max_iter=1, seed=3, solver="cholesky", **kwargs)
    als.fit(matrix_of(stars))
    report = als.last_fit_report
    assert report["mode"] != "resident"
    nonempty = np.count_nonzero(np.bincount(stars["rows"], minlength=TINY["n_users"])) + np.count_nonzero(
        np.bincount(stars["cols"], minlength=TINY["n_items"]))
    assert report["exact_systems_per_sweep"] >= nonempty
    assert report["exact_system_share"] == pytest.approx(
        report["exact_systems_per_sweep"] / (TINY["n_users"] + TINY["n_items"]))
    # a block's systems ride whole 128-lane tiles: never fewer lanes than systems
    assert report["exact_lane_systems_per_sweep"] >= report["exact_systems_per_sweep"]
    assert report["exact_lane_systems_per_sweep"] % 128 == 0
    assert report["exact_lane_share"] == pytest.approx(
        report["exact_lane_systems_per_sweep"] / (TINY["n_users"] + TINY["n_items"]))


def test_exact_systems_by_hand():
    # (B, L), (N, B, L): every slot row; the row the gather grows a bucket by is cut before the solve
    assert ops.gather_slots(8192, 8) == 8193 and ops.gather_slots(1024, 40) == 1025
    assert ops.exact_systems([(8192, 8), (3, 1024, 40)]) == 8192 + 3 * 1024
    assert ops.exact_systems([]) == 0


@pytest.mark.parametrize("systems,lanes", [(8192, 8192), (8193, 8320), (17, 128), (1, 128), (128, 128), (129, 256)])
def test_exact_lanes_by_hand(systems, lanes):
    """A block's systems are solved one a lane, at whole 128-lane tiles."""
    assert ops.exact_lanes(systems) == lanes
    assert ops.exact_lane_systems([(systems, 8)]) == lanes
    assert ops.exact_lane_systems([(3, systems, 40), (systems, 8)]) == 4 * lanes
    assert ops.exact_lane_systems([]) == 0


# --------------------------------------------------- (f) the resident plan

def parents_plan_items(shapes_u, shapes_i, n_users, n_items, rank, gb=4, n=1) -> dict:
    """``plan_fit`` as it was before it took a solver, written out."""
    slabs = sum(b * 4 + b * ln * 9 for b, ln in (*shapes_u, *shapes_i))
    slots = sum(b for b, _ in (*shapes_u, *shapes_i))
    transient = max(b * ln * (rank * gb + gb) + b * rank * rank * 4 for b, ln in (*shapes_u, *shapes_i))
    return {"factor_tables": (n_users + n_items) * rank * 4, "bucket_slabs": slabs // n,
            "landing_pools": (slots // n + n_users + n_items) * rank * 4, "transient_gather": transient // n}


@pytest.mark.parametrize("rank", [16, 50, 128])
def test_the_plan_under_cg_is_the_parents_to_the_byte(rank):
    user, item = [(8192, 8), (512, 1064), (16, 125104)], [(8192, 16), (3072, 600)]
    args = (user, item, 450000, 300000, rank)
    want = parents_plan_items(user, item, 450000, 300000, rank)
    assert capacity.plan_fit(*args, solver="cg").items == want
    assert capacity.plan_fit(*args).items == want           # callers that name no solver: the benchmark's drivers
    exact = capacity.plan_fit(*args, solver="cholesky").items
    assert {k: v for k, v in exact.items() if k != "transient_gather"} == {
        k: v for k, v in want.items() if k != "transient_gather"}
    assert exact["transient_gather"] > want["transient_gather"]
    with pytest.raises(ValueError):
        capacity.plan_fit(*args, solver="lu")


def test_the_estimator_prices_its_own_solver():
    stars = generate_stars(TINY, 7)
    m = matrix_of(stars)
    cg = ImplicitALS(rank=16, solver="cg").capacity_plan(m)
    exact = ImplicitALS(rank=16, solver="cholesky").capacity_plan(m)
    assert exact.items["transient_gather"] > cg.items["transient_gather"]
    assert ImplicitALS(rank=16, solver="cholesky").admission(m).plan.items == exact.items


@pytest.mark.parametrize("rank", [16, 50, 128])
@pytest.mark.parametrize("shape", [(512, 8), (1024, 16)])
def test_the_exact_price_covers_what_a_bucket_of_short_rows_compiles_to(rank, shape):
    """A bucket's exact solve holds its systems beside its block: compiled
    (here, for the CPU) a bucket of short rows reserves 2-50 times what the
    plan under CG prices it at, and the exact plan covers it with room to
    spare (the compiler holds two of the three arrays the price counts a
    system). (A bucket of LONG rows is bounded by its gathered block, which
    the exact plan prices at the width it is gathered at - a whole line an
    entry from a line table - and the plan under CG at the rank's own:
    PERF.md section 7.)"""
    b, ln = shape
    n = 3000
    table = (n // 2, ops.LANES) if ops.gather_packs_rows(rank) else (n, rank)
    sds = jax.ShapeDtypeStruct
    args = (sds(table, jnp.float32), sds((rank, rank), jnp.float32), sds((n, rank), jnp.float32),
            sds((b,), jnp.int32), sds((b, ln), jnp.int32), sds((b, ln), jnp.float32),
            sds((b, ln), jnp.bool_), sds((), jnp.float32), sds((), jnp.float32))

    def solve(*a):
        return ops.solve_rows(*a, "cholesky", 3, None)

    compiled = jax.jit(solve).lower(*args).compile()
    temp = capacity.compiled_memory_bytes(compiled)["temp"]
    exact = capacity.plan_fit([shape], [], n, n, rank, solver="cholesky").items["transient_gather"]
    under_cg = capacity.plan_fit([shape], [], n, n, rank, solver="cg").items["transient_gather"]
    assert exact >= temp > under_cg
    assert 0.9 * exact >= temp                  # visible room, not a reading met to the byte
    width = ops.LANES if ops.gather_packs_rows(rank) else rank
    assert exact == b * ln * (width * 4 + 4) + ops.gather_slots(b, ln) * capacity.exact_system_bytes(rank)


@pytest.mark.parametrize("b,ln,rank,by_hand", [
    (8192, 8, 50, 8192 * 8 * 516 + 8193 * 122880),      # a slot row more than the bucket's rows
    (8192, 16, 128, 8192 * 16 * 516 + 8193 * 204800),   # rank 128's rows are a line each already
    (17, 16, 50, 17 * 16 * 516 + 128 * 122880),         # a small block's systems fill a lane tile
    (129, 8, 200, 129 * 8 * 804 + 256 * 630784),        # a plain table is gathered at its rank
])
def test_exact_bucket_bytes_by_hand(b, ln, rank, by_hand):
    assert capacity.exact_bucket_bytes(b, ln, rank) == by_hand
    assert capacity.plan_fit([(b, ln)], [], 10, 10, rank, solver="cholesky").items["transient_gather"] == by_hand


def test_exact_system_bytes_by_hand():
    # the correction as the contraction leaves it (unfolded (128, 128) from a line table),
    # and two tile-padded augmented systems (rank + 1, rank): as concatenated, and in the lanes
    assert capacity.exact_system_bytes(50) == 128 * 128 * 4 + 2 * 56 * 128 * 4
    assert capacity.exact_system_bytes(16) == 128 * 128 * 4 + 2 * 24 * 128 * 4
    assert capacity.exact_system_bytes(128) == 128 * 128 * 4 + 2 * 136 * 128 * 4
    assert capacity.exact_system_bytes(200) == 200 * 256 * 4 + 2 * 208 * 256 * 4
    assert math.isclose(capacity.exact_system_bytes(50) / (50 * 50 * 4), 12.29, abs_tol=0.01)
