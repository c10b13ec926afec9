"""The chunked host-streamed ALS fallback: numerics parity with the
device-resident path (both solvers), the admission wiring in ``fit``, the
als.chunked chaos site, and the over-budget-fit-completes acceptance bar."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.datasets.synthetic import synthetic_stars  # noqa: E402
from albedo_tpu.models.als import ImplicitALS  # noqa: E402
from albedo_tpu.utils import capacity, faults  # noqa: E402

KW = dict(rank=8, max_iter=3, seed=0, batch_size=16)


@pytest.fixture(autouse=True)
def _two_compile_threads(monkeypatch):
    """A chunked fit acquires its shapes on as many threads as the box has
    cores; under the suite's parallel workers two are load enough."""
    monkeypatch.setenv("ALBEDO_BUCKET_WORKERS", "2")


def _matrix(seed=1):
    return synthetic_stars(n_users=70, n_items=45, mean_stars=6, seed=seed)


class TestParity:
    @pytest.mark.parametrize("solver", ["cholesky", "cg"])
    def test_chunked_matches_resident(self, solver):
        m = _matrix()
        resident = ImplicitALS(**KW, solver=solver, chunked=False).fit(m)
        chunked = ImplicitALS(**KW, solver=solver, chunked=True).fit(m)
        np.testing.assert_allclose(
            chunked.user_factors, resident.user_factors, atol=1e-4
        )
        np.testing.assert_allclose(
            chunked.item_factors, resident.item_factors, atol=1e-4
        )

    def test_chunked_matches_resident_bf16_gathers(self):
        m = _matrix()
        kw = dict(KW, gather_dtype="bfloat16")
        resident = ImplicitALS(**kw, chunked=False).fit(m)
        chunked = ImplicitALS(**kw, chunked=True).fit(m)
        np.testing.assert_allclose(
            chunked.user_factors, resident.user_factors, atol=1e-2
        )

    def test_chunked_warm_start_matches(self):
        """``init_factors`` is the resume path: both fit paths must agree
        from the same start. The start is a seeded random table, as a
        restored checkpoint is — NOT a constant: with every entry equal all
        latent columns are identical, exact arithmetic keeps them so, and
        that symmetric point is unstable (f32 round-off splits the columns
        ~200x further per sweep in BOTH paths), so two correct paths end
        0.1-0.7 apart in factor space while their predictions still agree."""
        m = _matrix()
        rng = np.random.default_rng(5)
        init = (
            rng.normal(0, 0.3, (m.n_users, 8)).astype(np.float32),
            rng.normal(0, 0.3, (m.n_items, 8)).astype(np.float32),
        )
        resident = ImplicitALS(**KW, init_factors=init, chunked=False).fit(m)
        chunked = ImplicitALS(**KW, init_factors=init, chunked=True).fit(m)
        np.testing.assert_allclose(
            chunked.user_factors, resident.user_factors, atol=1e-4
        )

    def test_chunked_callback_sees_every_iteration(self):
        m = _matrix()
        seen = []
        ImplicitALS(**KW, chunked=True).fit(
            m, callback=lambda it, uf, vf: seen.append((it, uf.shape))
        )
        assert [it for it, _ in seen] == [0, 1, 2]
        assert all(shape == (m.n_users, 8) for _, shape in seen)


class TestAdmissionWiring:
    def test_over_budget_fit_completes_via_degrade(self, monkeypatch):
        """The acceptance bar: a fit whose resident plan busts the budget
        must complete through the chunked path — and match the resident
        result trained under a roomy budget."""
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "4g")
        m = _matrix(seed=2)
        resident = ImplicitALS(**KW).fit(m)

        est = ImplicitALS(**KW)
        plan = est.capacity_plan(m)
        chunked_plan = est.capacity_plan(m, chunked=True)
        mid = (plan.required_bytes + chunked_plan.required_bytes) // 2
        monkeypatch.setenv(
            "ALBEDO_DEVICE_MEM_BYTES", str(int(mid / capacity.headroom()))
        )
        m2 = _matrix(seed=2)  # fresh object: cold layout cache
        model = est.fit(m2)
        assert est.last_fit_report["mode"] == "chunked"
        assert est.last_fit_report["capacity"]["verdict"] == "degrade"
        np.testing.assert_allclose(
            model.user_factors, resident.user_factors, atol=1e-4
        )

    def test_warm_groups_cache_stays_resident(self, monkeypatch):
        """Already-uploaded slabs ARE device-resident — re-admitting them
        after the fact would be theater. A warm cache skips admission."""
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "4g")
        m = _matrix(seed=3)
        est = ImplicitALS(**KW)
        est.fit(m)  # warms the per-matrix device-groups cache
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "1000")
        est2 = ImplicitALS(**KW)
        est2.fit(m)
        assert est2.last_fit_report["mode"] == "resident"

    def test_chunked_site_hits_per_half_sweep(self):
        m = _matrix(seed=4)
        before = faults.FAULTS.hits("als.chunked")
        ImplicitALS(**KW, chunked=True).fit(m)
        # Two half-sweeps per iteration, three iterations.
        assert faults.FAULTS.hits("als.chunked") - before == 2 * KW["max_iter"]

    def test_chunked_fault_error_fails_the_fit(self):
        m = _matrix(seed=5)
        faults.arm("als.chunked", kind="error", at=2)
        try:
            with pytest.raises(faults.FaultInjected):
                ImplicitALS(**KW, chunked=True).fit(m)
        finally:
            faults.disarm("als.chunked")

    def test_chunked_report_shape(self):
        m = _matrix(seed=6)
        est = ImplicitALS(**KW, chunked=True)
        est.fit(m)
        report = est.last_fit_report
        assert report["mode"] == "chunked"
        assert report["chunked_shapes"] >= 1
        assert report["health"]["nonfinite"] == 0
        assert report["device_s"] >= 0
        rows = report["rows_per_dispatch"]
        assert 1 <= rows["median"] <= rows["largest"]
        assert 0.0 <= report["merged_entry_share"] <= 1.0

    def test_mesh_path_never_reroutes_to_single_device_chunked(self, monkeypatch):
        """Mesh fits run their OWN admission ladder (replicated -> sharded
        -> sharded+streamed, `tests/test_sharded_als.py`) — never the
        single-device chunked reroute. A budget too small for even the
        replicated mesh layout lands on a SHARDED rung, not on
        `mode: chunked`."""
        from albedo_tpu.parallel.mesh import make_mesh

        m = _matrix(seed=7)
        mesh = make_mesh(2)
        est = ImplicitALS(rank=8, max_iter=1, seed=0, batch_size=16, mesh=mesh)
        streamed_bytes = capacity.plan_fit_sharded(
            *est._plan_shapes(m), m.n_users, m.n_items, est.rank, 2,
            streamed=True,
        ).required_bytes
        monkeypatch.setenv("ALBEDO_MEM_HEADROOM", "1.0")
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", str(streamed_bytes + 64))
        model = est.fit(m)
        assert np.isfinite(model.user_factors).all()
        assert est.last_fit_report["mode"] in ("sharded", "sharded_streamed")


def _degraded(monkeypatch, matrix_seed, **overrides):
    """An estimator and a FRESH matrix under a device budget between the
    resident and the chunked plan: admission's own degrade, nothing forced."""
    monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "4g")
    est = ImplicitALS(**dict(KW, solver="cg", **overrides))
    probe = _matrix(seed=matrix_seed)
    mid = (est.capacity_plan(probe).required_bytes
           + est.capacity_plan(probe, chunked=True).required_bytes) // 2
    monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", str(int(mid / capacity.headroom())))
    return est, _matrix(seed=matrix_seed)


class TestStreamObservability:
    def test_degraded_fit_publishes_every_span_and_counter(self, monkeypatch):
        from albedo_tpu.models.als import CHUNKED_SPANS

        est, m = _degraded(monkeypatch, 8)
        assert est.chunked is None
        est.fit(m)
        report = est.last_fit_report
        assert report["mode"] == "chunked" and report["capacity"]["verdict"] == "degrade"
        totals, counts = report["spans"]["totals"], report["spans"]["counts"]
        assert set(CHUNKED_SPANS) <= set(totals)
        user_buckets, item_buckets = est._host_buckets(m, stream=True)
        n_user, n_item = len(user_buckets), len(item_buckets)
        assert report["buckets"] == {"user": n_user, "item": n_item}
        slots = sorted(b.shape[0] for b in (*user_buckets, *item_buckets))
        assert report["rows_per_dispatch"] == {"median": slots[len(slots) // 2], "largest": slots[-1]}
        entries = [b.idx.size for b in (*user_buckets, *item_buckets)]
        merged = [b.idx.size for b in (*user_buckets, *item_buckets) if b.shape[0] > KW["batch_size"]]
        assert report["merged_entry_share"] == pytest.approx(sum(merged) / sum(entries))
        assert report["dispatches"] == KW["max_iter"] * (n_user + n_item)
        assert counts["fit.stream.upload"] == counts["fit.stream.dispatch"] == report["dispatches"]
        # a cold estimator: one more fit.stream holding the one acquisition of every shape
        assert counts["fit.stream"] == 2 * KW["max_iter"] + 1
        # (one span around it; the steady loop's look-up a bucket opens none)
        assert counts["fit.stream.acquire"] == 1
        assert counts["fit.stream.gramian"] == 2 * KW["max_iter"]
        # every bucket shape's program, and each table's relayout each way
        assert counts["fit.stream.acquire.lower_compile"] == report["chunked_shapes"] + 4
        assert counts["fit.relayout"] == 2
        assert counts["fit.admission"] == counts["fit.init"] == counts["fit.wait"] == 1
        # by hand: one int32 slab a bucket - an index (the mask in its sign)
        # and a value's bits an entry, a row id a slot
        by_hand = sum(
            b.row_ids.shape[0] * 4 + b.idx.size * (4 + 4) for b in (*user_buckets, *item_buckets)
        )
        layout = est._stream_layout(m)
        assert by_hand == sum(b.data.nbytes for b in (*layout.user_slabs, *layout.item_slabs))
        assert report["streamed_bytes_per_sweep"] == by_hand
        assert report["upload_s"] == pytest.approx(totals["fit.stream.upload"], abs=1e-3)
        assert report["compile_s"] == pytest.approx(totals["fit.acquire"], abs=1e-3)
        children = sum(totals[f"fit.stream.{c}"] for c in ("gramian", "upload", "acquire", "dispatch"))
        assert children <= totals["fit.stream"] + 1e-3

    def test_executables_outlive_the_fit(self, monkeypatch):
        """The second fit of an estimator on its matrix acquires nothing:
        not from memory (the layer's LRU holds 8 of these shapes), not from
        disk."""
        from albedo_tpu.utils import aot

        est, m = _degraded(monkeypatch, 9)
        aot.reset_memory_cache()
        first = est.fit(m)
        shapes = est.last_fit_report["chunked_shapes"]
        assert shapes > 8 and est.last_fit_report["compile_source"] == "compile"
        before = len(aot.branch_log())
        aot.reset_memory_cache()
        second = est.fit(m)
        report = est.last_fit_report
        assert len(aot.branch_log()) == before
        assert report["mode"] == "chunked" and report["capacity"]["verdict"] == "degrade"
        assert report["compile_s"] == 0.0 and report["compile_source"] is None
        assert report["chunked_shapes"] == shapes
        counts = report["spans"]["counts"]
        assert counts["fit.stream"] == 2 * KW["max_iter"]
        # the steady loop opens no fit.stream.acquire: a warm fit has none at all
        assert not any(k.startswith("fit.stream.acquire") for k in counts)
        # nor is the matrix priced again: the verdict stays with its layout
        assert "fit.admission" not in counts
        np.testing.assert_array_equal(first.user_factors, second.user_factors)

    def test_the_table_is_donated_through_the_export(self, monkeypatch):
        """An export carries no donation: the AOT layer repeats it, or every
        bucket's dispatch would copy the whole target table."""
        import jax.numpy as jnp

        from albedo_tpu.ops.als import gather_table, pack_slab

        est, m = _degraded(monkeypatch, 10)
        est.fit(m)
        (key, compiled), *_ = est._chunked_executables(m).items()
        n_source, n_target, (b, l) = key
        target = jnp.ones((n_target, 8), jnp.float32)
        slab = pack_slab(np.full(b, -1, np.int32), np.zeros((b, l), np.int32),
                         np.zeros((b, l), np.float32), np.zeros((b, l), bool))
        compiled(   # (the fixed side's table in the form the gather reads it)
            gather_table(jnp.ones((n_source, 8), jnp.float32)), jnp.eye(8, dtype=jnp.float32), target,
            jnp.asarray(slab), jnp.float32(0.5), jnp.float32(40.0),
        )
        assert target.is_deleted()


MERGE_KW = dict(rank=8, max_iter=2, seed=0, batch_size=16, max_entries=1 << 11)


def _wide_matrix():
    """Enough rows of one, two and four stars on either side for a length
    tier to fill many ``batch_size``-row buckets, and a head of long rows
    whose bucket sets the price."""
    return synthetic_stars(n_users=900, n_items=2000, mean_stars=5, seed=21)


class TestMergedLayout:
    """The chunked fit's own layout (``_host_buckets(m, stream=True)``): a bucket is a
    dispatch, and one of one-entry rows is filled up to what the
    ``batch_size``-row layout's worst bucket of the side is priced at."""

    @pytest.mark.parametrize("side", ["user", "item"])
    @pytest.mark.parametrize("solver", ["cg", "cholesky"])
    def test_every_row_once_at_its_own_tier_under_both_caps(self, solver, side):
        from albedo_tpu.datasets.ragged import _pad_len

        m = _wide_matrix()
        est = ImplicitALS(**MERGE_KW, solver=solver)
        at = ("user", "item").index(side)
        indptr = (m.csr(), m.csc())[at][0]
        base, merged = est._host_buckets(m)[at], est._host_buckets(m, stream=True)[at]
        lengths = np.diff(indptr)
        seen = np.concatenate([b.row_ids[b.row_ids >= 0] for b in merged])
        assert sorted(seen) == list(np.nonzero(lengths)[0])          # once each, no empty row
        assert list(seen) == list(np.concatenate([b.row_ids[b.row_ids >= 0] for b in base]))

        def price(b):
            return b.shape[0] * capacity.chunked_row_bytes(b.shape[1], est.rank, None, solver)

        worst = max(price(b) for b in base)
        # what admission prices the rung's bucket in flight at covers it
        admitted = est.capacity_plan(m, chunked=True).items["worst_bucket_in_flight"]
        assert worst <= admitted
        for b in merged:
            valid = b.row_ids >= 0
            assert b.mask[~valid].sum() == 0
            # its own length tier, every entry kept
            assert {_pad_len(int(n), 8) for n in lengths[b.row_ids[valid]]} == {b.shape[1]}
            np.testing.assert_array_equal(b.mask[valid].sum(axis=1), lengths[b.row_ids[valid]])
            assert b.idx.size <= est.max_entries and price(b) <= worst
        assert len(merged) < len(base) and max(b.shape[0] for b in merged) > est.batch_size
        # every longer tier as the resident layout has it
        assert [b.shape for b in merged if b.shape[1] > 1] == [b.shape for b in base if b.shape[1] > 1]
        # (a tier's remainder rounds up to one slot tier where it was several buckets' worth)
        assert sum(b.idx.size for b in merged) <= 1.05 * sum(b.idx.size for b in base)
        # full and remainder: two shapes a length at most
        shapes = {b.shape for b in merged}
        assert all(sum(ln == l for _, ln in shapes) <= 2 for _, l in shapes)

    @pytest.mark.parametrize("solver", ["cg", "cholesky"])
    def test_fit_on_the_merged_layout_equals_the_unmerged(self, solver, monkeypatch):
        m = _wide_matrix()
        merged = ImplicitALS(**MERGE_KW, solver=solver, chunked=True)
        model = merged.fit(m)
        buckets = [b for side in merged._host_buckets(m, stream=True) for b in side]
        wide = [b for b in buckets if b.shape[0] > MERGE_KW["batch_size"]]
        assert wide and {b.shape[1] for b in wide} == {1}       # the one-entry tier, and only it
        assert merged.last_fit_report["merged_entry_share"] == pytest.approx(
            sum(b.idx.size for b in wide) / sum(b.idx.size for b in buckets))
        assert merged.last_fit_report["rows_per_dispatch"]["largest"] == max(b.shape[0] for b in wide)
        # no length tier is the merged one: the resident layout, a bucket a dispatch
        monkeypatch.setattr("albedo_tpu.models.als.STREAM_MERGED_LEN", 0)
        plain = ImplicitALS(**MERGE_KW, solver=solver, chunked=True)
        want = plain.fit(_wide_matrix())    # (a layout stays with its matrix: a fresh one)
        assert plain.last_fit_report["merged_entry_share"] == 0.0
        assert plain.last_fit_report["dispatches"] > merged.last_fit_report["dispatches"]
        np.testing.assert_allclose(model.user_factors, want.user_factors, atol=1e-4)
        np.testing.assert_allclose(model.item_factors, want.item_factors, atol=1e-4)

    def test_the_exact_solve_merges_by_its_systems_price(self):
        """Every row of the exact solve builds a ``(k, k)`` system, the CG's
        short rows none: the same rule carries fewer of them a dispatch."""
        m = _wide_matrix()
        rank = 32   # a system (4 KB) outweighs a short row's block and state

        def one_star_rows(solver):
            est = ImplicitALS(**dict(MERGE_KW, rank=rank), solver=solver)
            base, merged = est._host_buckets(m)[0], est._host_buckets(m, stream=True)[0]
            worst = max(b.shape[0] * capacity.chunked_row_bytes(b.shape[1], rank, None, solver)
                        for b in base)
            most = max(b.shape[0] for b in merged if b.shape[1] == 1)
            # as many as the price allows, in whole slot tiers, and no more
            assert most <= worst // capacity.chunked_row_bytes(1, rank, None, solver) < 2 * most + 16
            return most

        assert one_star_rows("cholesky") < one_star_rows("cg")

    @pytest.mark.parametrize("forced", [None, True])
    def test_a_side_is_planned_once_for_admission_and_the_allowance(self, forced, monkeypatch):
        """The row allowance is read off the planner's shapes that admission
        priced (``_plan_shapes``, kept with the matrix): a chunked fit plans
        each side once for them, chosen by admission or forced, and the
        layout itself once (``bucket_rows``), as the resident fit does."""
        if forced:
            est, m = ImplicitALS(**MERGE_KW, solver="cg", chunked=True), _wide_matrix()
        else:
            est, m = _degraded(monkeypatch, 8)
        planned = []
        real = capacity.bucket_plan_shapes
        monkeypatch.setattr(
            capacity, "bucket_plan_shapes",
            lambda indptr, **kw: planned.append(len(indptr)) or real(indptr, **kw))
        est.fit(m)
        assert est.last_fit_report["mode"] == "chunked"
        assert sorted(planned) == sorted([m.n_users + 1, m.n_items + 1])
        est.fit(m)          # warm: the verdict and the layout stay with the matrix
        assert len(planned) == 2
        # the streamed layout has its own key beside the resident one's
        assert est._host_buckets(m, stream=True) is est._host_buckets(m, stream=True)
        assert est._host_buckets(m, stream=True) is not est._host_buckets(m)


def test_chunked_fit_against_the_plain_reference(monkeypatch):
    """The system's chunked fit, chosen by admission, against the benchmark's
    plain reference (``benchmark/reference/als_cg.py``: its own init, blocks
    and float32 CG at highest precision, nothing of the program) after two
    sweeps from the same seed. Tolerance 2e-4 of the larger of a row's norm
    and the median row's: on the CPU both sides are exact float32 and differ
    in summation order alone (padded widths 8/16/32... against the program's
    tiers; the long rows' CG on the explicit Gramian against the
    matrix-free form), which three CG steps on systems conditioned like
    1 + 40 n amplify to ~1e-5; a dropped bucket, a stale warm start or a
    wrong landing reads 1e-1 or more."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "benchmark" / "reference" / "als_cg.py"
    spec = importlib.util.spec_from_file_location("plain_als_cg", path)
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)

    est, m = _degraded(monkeypatch, 11, max_iter=2, seed=13)
    model = est.fit(m)
    assert est.last_fit_report["mode"] == "chunked"
    stars = {"rows": m.rows, "cols": m.cols, "vals": m.vals,
             "n_users": m.n_users, "n_items": m.n_items}
    config = {"rank": est.rank, "reg_param": est.reg_param, "alpha": est.alpha,
              "cg_steps": est.cg_steps}
    want_user, want_item = reference.fit(stars, config, 13, 2)
    for got, want in ((model.user_factors, want_user), (model.item_factors, want_item)):
        norms = np.linalg.norm(want, axis=1)
        err = np.linalg.norm(got - want, axis=1) / np.maximum(norms, np.median(norms))
        assert err.max() < 2e-4, err.max()
    init = reference.init_factors(13, m.n_users, m.n_items, est.rank)
    assert np.abs(model.user_factors - np.asarray(init[0])).max() > 0.05   # it moved


def _holed_matrix():
    """``_wide_matrix`` with one user and one repository that have no star
    (the tables' last rows and a row in the middle of each): rows that no
    bucket holds, so they sit after every slot in dispatch order."""
    import dataclasses

    m = _wide_matrix()
    keep = (m.rows != 17) & (m.cols != 40)
    return dataclasses.replace(
        m, user_ids=np.arange(m.n_users + 1), item_ids=np.arange(m.n_items + 1),
        rows=m.rows[keep], cols=m.cols[keep], vals=m.vals[keep])


class TestDispatchOrder:
    """Both tables held in the chunked fit's dispatch order
    (``models.als.StreamLayout``): a bucket's slots are one block of its
    target table, landed by one block write."""

    @pytest.mark.parametrize("warm", [False, True])
    @pytest.mark.parametrize("solver", ["cg", "cholesky"])
    def test_the_dispatch_order_fit_equals_the_resident_fit(self, solver, warm):
        m = _holed_matrix()
        init = None
        if warm:
            rng = np.random.default_rng(5)
            init = (rng.normal(0, 0.3, (m.n_users, 8)).astype(np.float32),
                    rng.normal(0, 0.3, (m.n_items, 8)).astype(np.float32))
        kw = dict(MERGE_KW, solver=solver, init_factors=init)
        resident = ImplicitALS(**kw, chunked=False).fit(m)
        est = ImplicitALS(**kw, chunked=True)
        chunked = est.fit(m)
        layout = est._stream_layout(m)
        # what the layout has to show: padding slots, a merged one-entry
        # dispatch, and a row on either side that no bucket holds
        buckets = (*layout.user_slabs, *layout.item_slabs)
        assert any((b.row_ids < 0).any() for b in buckets)
        assert any(b.shape[0] > MERGE_KW["batch_size"] and b.shape[1] == 1 for b in buckets)
        for order, n_rows, empty in ((layout.user_order, m.n_users, 17), (layout.item_order, m.n_items, 40)):
            assert sorted(order[-2:]) == [empty, n_rows - 1]        # after every slot
        np.testing.assert_allclose(chunked.user_factors, resident.user_factors, atol=1e-4)
        np.testing.assert_allclose(chunked.item_factors, resident.item_factors, atol=1e-4)
        # a row with no stars keeps its factor, through both relayouts
        start_user, start_item = (np.asarray(t) for t in est._initial_factors(m))
        for got, start, empty in ((chunked.user_factors, start_user, 17), (chunked.item_factors, start_item, 40)):
            np.testing.assert_array_equal(got[[empty, -1]], start[[empty, -1]])

    def test_the_callback_sees_logical_order_at_every_iteration(self):
        m = _holed_matrix()
        seen, want = [], []
        ImplicitALS(**MERGE_KW, solver="cg", chunked=True).fit(
            m, callback=lambda it, uf, vf: seen.append((it, uf, vf)))
        ImplicitALS(**MERGE_KW, solver="cg", chunked=False).fit(
            m, callback=lambda it, uf, vf: want.append((it, uf, vf)))
        assert [it for it, *_ in seen] == [it for it, *_ in want] == [0, 1]
        for (_, uf, vf), (_, u_want, v_want) in zip(seen, want):
            assert uf.shape == (m.n_users, 8) and vf.shape == (m.n_items, 8)
            np.testing.assert_allclose(uf, u_want, atol=1e-4)
            np.testing.assert_allclose(vf, v_want, atol=1e-4)

    def test_every_row_lands_in_place_and_the_tables_hold_their_slots(self):
        from albedo_tpu.utils.capacity import stream_table_rows

        m = _holed_matrix()
        est = ImplicitALS(**MERGE_KW, solver="cg", chunked=True)
        est.fit(m)
        report = est.last_fit_report
        assert report["landed_in_place_share"] == 1.0
        user, item = est._host_buckets(m, stream=True)
        by_hand = 0
        for buckets, n_rows in ((user, m.n_users), (item, m.n_items)):
            held = {int(r) for b in buckets for r in b.row_ids if r >= 0}
            rows = sum(b.shape[0] for b in buckets) + n_rows - len(held)
            by_hand += rows
            # the planner's price of the table covers it
            assert rows <= stream_table_rows(est._plan_shapes(m)[buckets is item], n_rows)
        assert report["stream_slot_row_share"] == pytest.approx(by_hand / (m.n_users + m.n_items))
        assert report["stream_slot_row_share"] > 1.0       # padding slots hold rows of their own
        # no other path lands a block
        resident = ImplicitALS(**MERGE_KW, solver="cg", chunked=False)
        resident.fit(m)
        assert resident.last_fit_report["landed_in_place_share"] == 0.0
        assert resident.last_fit_report["stream_slot_row_share"] == 0.0

    def test_the_relabelled_slabs_map_back_to_the_layout_entry_for_entry(self):
        from albedo_tpu.ops.als import unpack_slab

        m = _holed_matrix()
        est = ImplicitALS(**MERGE_KW, solver="cholesky")
        layout = est._stream_layout(m)
        assert layout is est._stream_layout(m)           # kept with the layout
        user, item = est._host_buckets(m, stream=True)
        sides = ((user, layout.user_slabs, layout.user_order, layout.item_order),
                 (item, layout.item_slabs, layout.item_order, layout.user_order))
        unpack = jax.jit(unpack_slab, static_argnums=1)
        for base, relabelled, target_order, source_order in sides:
            assert [b.shape for b in base] == [b.shape for b in relabelled]
            offset = 0
            for b, r in zip(base, relabelled):
                row_ids, idx, val, mask = (np.asarray(a) for a in unpack(r.data, r.shape))
                np.testing.assert_array_equal(row_ids, r.row_ids)
                valid = b.row_ids >= 0
                np.testing.assert_array_equal(r.row_ids >= 0, valid)
                # the block at its offset, a padding slot's row holding no row
                np.testing.assert_array_equal(r.row_ids[valid], offset + np.flatnonzero(valid))
                np.testing.assert_array_equal(target_order[offset:offset + b.shape[0]], b.row_ids)
                np.testing.assert_array_equal(source_order[idx], b.idx)
                # the values and the mask as the bucket had them, bit for bit
                np.testing.assert_array_equal(val.view(np.int32), b.val.view(np.int32))
                np.testing.assert_array_equal(mask, b.mask)
                offset += b.shape[0]
        # an order and its inverse
        for order, pos in ((layout.user_order, layout.user_pos), (layout.item_order, layout.item_pos)):
            np.testing.assert_array_equal(order[pos], np.arange(pos.size))
            assert sorted(order[order >= 0]) == list(range(pos.size))


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
def test_the_chunked_program_lands_and_warm_starts_without_a_row_scatter_or_gather(solver):
    """``jit_als_chunked`` reads and writes its target table as one block:
    no scatter at all, no gather whose operand is the target, and the table
    still aliased to the result through the export."""
    import re

    import jax.numpy as jnp

    from albedo_tpu.ops.als import chunked_bucket_update, slab_entries
    from albedo_tpu.utils.aot import persistent_aot_executable

    sds = jax.ShapeDtypeStruct
    args = (sds((30, 8), jnp.float32), sds((8, 8), jnp.float32), sds((20, 8), jnp.float32),
            sds((slab_entries(6, 16),), jnp.int32), sds((), jnp.float32), sds((), jnp.float32))
    statics = dict(shape=(6, 16), solver=solver, cg_steps=3, gather_dtype=None)
    text = chunked_bucket_update.lower(*args, **statics).as_text()
    assert "stablehlo.scatter" not in text
    gathers = re.findall(r'"stablehlo\.gather"\(.*?\) <\{.*?\}> : \((tensor<[^>]*>)', text)
    assert gathers and "tensor<20x8xf32>" not in gathers        # the gather reads the source
    assert "stablehlo.dynamic_update_slice" in text
    compiled, _, _ = persistent_aot_executable(
        chunked_bucket_update, args, None, statics,
        key_parts=("test_als_chunked", "block", solver), name="als_chunked", donate_argnums=(2,))
    hlo = compiled.as_text()
    assert re.search(r"^HloModule jit_als_chunked\b", hlo, re.M)
    assert not re.search(r"= \S+ scatter\(", hlo)
    assert re.search(r"input_output_alias=\{ \{\}: \(2, \{\}", hlo)


# ------------------------------------------- the slab: one buffer, one transfer

def _bucket_arrays(n_slots: int, length: int, seed: int):
    """A bucket's four host arrays with what a layout carries: padding slots
    (row id -1, no entry), padding entries whose index is not 0, values of
    every sign (a negative zero among them)."""
    rng = np.random.default_rng(seed)
    row_ids = rng.permutation(4 * n_slots)[:n_slots].astype(np.int32)
    row_ids[-max(1, n_slots // 4):] = -1
    idx = rng.integers(1, 1 << 30, (n_slots, length)).astype(np.int32)
    mask = rng.random((n_slots, length)) < 0.6
    mask[row_ids < 0] = False
    val = rng.normal(0, 3, (n_slots, length)).astype(np.float32)
    val.flat[0] = -0.0
    return row_ids, idx, val, mask


@pytest.mark.parametrize("shape", [(1, 1), (8, 1), (13, 1), (13, 3), (16, 8), (7, 40)])
def test_a_packed_slab_unpacks_in_the_program_to_the_four_arrays_bit_for_bit(shape):
    import jax.numpy as jnp

    from albedo_tpu.ops.als import pack_slab, slab_entries, unpack_slab

    arrays = _bucket_arrays(*shape, seed=shape[0] * 100 + shape[1])
    slab = pack_slab(*arrays)
    assert slab.dtype == np.int32 and slab.shape == (slab_entries(*shape),)
    assert slab.nbytes == capacity.stream_slab_bytes(*shape)
    got = jax.jit(unpack_slab, static_argnums=1)(jnp.asarray(slab), shape)
    for g, want in zip(got, arrays):
        g = np.asarray(g)
        assert g.dtype == want.dtype and g.shape == want.shape
        np.testing.assert_array_equal(g.view(np.uint8), want.view(np.uint8))


@functools.partial(jax.jit, donate_argnums=(2,), static_argnames=("solver", "cg_steps", "gather_dtype"))
def _four_array_update(source, yty, target, row_ids, idx, val, mask, reg, alpha,
                       solver, cg_steps, gather_dtype):
    """A bucket's chunked program as it was handed four arrays, before the
    slab: the same warm start, solve and block write."""
    import jax.numpy as jnp

    from albedo_tpu.ops.als import solve_rows

    offset = row_ids[0]
    x0 = None
    if solver == "cg":
        x0 = jax.lax.dynamic_slice_in_dim(target, offset, row_ids.shape[0])
    solved = solve_rows(source, yty, target, row_ids, idx, val, mask, reg, alpha,
                        solver, cg_steps, gather_dtype, x0=x0)
    block = jnp.where((row_ids >= 0)[:, None], solved, 0.0)
    return jax.lax.dynamic_update_slice_in_dim(target, block, offset, 0)


def _four_array_fit(est, m):
    """The chunked fit with every bucket uploaded as its four relabelled
    arrays: the dispatch-order layout, tables and loop of ``_fit_chunked``."""
    import jax.numpy as jnp

    from albedo_tpu.ops.als import gather_table, gramian, relayout_rows

    layout = est._stream_layout(m)

    def relabel(buckets, source_pos):
        out, offset = [], 0
        for b in buckets:
            block = offset + np.arange(b.shape[0], dtype=np.int32)
            out.append((np.where(b.row_ids >= 0, block, -1).astype(np.int32),
                        np.take(source_pos, b.idx), b.val, b.mask))
            offset += b.shape[0]
        return out

    user, item = est._host_buckets(m, stream=True)
    user_b, item_b = relabel(user, layout.item_pos), relabel(item, layout.user_pos)
    uf, vf = est._initial_factors(m)
    uf = relayout_rows(uf, jnp.asarray(layout.user_order))
    vf = relayout_rows(vf, jnp.asarray(layout.item_order))
    reg, alpha = jnp.float32(est.reg_param), jnp.float32(est.alpha)
    statics = dict(solver=est.solver, cg_steps=est.cg_steps, gather_dtype=est.gather_dtype)

    def half_sweep(source, target, buckets):
        yty, table = gramian(source), gather_table(source)
        for arrays in buckets:
            target = _four_array_update(
                table, yty, target, *(jnp.asarray(a) for a in arrays), reg, alpha, **statics)
        return target

    for _ in range(est.max_iter):
        vf = half_sweep(uf, vf, item_b)
        uf = half_sweep(vf, uf, user_b)
    return (np.asarray(relayout_rows(uf, jnp.asarray(layout.user_pos))),
            np.asarray(relayout_rows(vf, jnp.asarray(layout.item_pos))))


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
def test_the_slab_fit_solves_the_tables_of_the_four_array_fit_bit_for_bit(solver):
    m = _holed_matrix()
    est = ImplicitALS(**MERGE_KW, solver=solver, chunked=True)
    model = est.fit(m)
    assert est.last_fit_report["mode"] == "chunked"
    user_want, item_want = _four_array_fit(est, m)
    np.testing.assert_array_equal(model.user_factors.view(np.uint32), user_want.view(np.uint32))
    np.testing.assert_array_equal(model.item_factors.view(np.uint32), item_want.view(np.uint32))


def test_a_bucket_is_one_transfer_a_dispatch(monkeypatch):
    from albedo_tpu.ops.als import slab_entries

    sent = []
    device_put = jax.device_put

    def counted(x, *args, **kwargs):
        sent.append((type(x), getattr(x, "dtype", None), getattr(x, "shape", None)))
        return device_put(x, *args, **kwargs)

    est, m = _degraded(monkeypatch, 11)
    est._stream_layout(m)                   # the layout's own uploads before the count
    monkeypatch.setattr(jax, "device_put", counted)
    est.fit(m)
    report = est.last_fit_report
    counts = report["spans"]["counts"]
    assert report["mode"] == "chunked"
    assert report["stream_transfers_per_dispatch"] == 1.0
    assert counts["fit.stream.upload"] == report["dispatches"] == len(sent)
    layout = est._stream_layout(m)
    shapes = [b.shape for b in (*layout.item_slabs, *layout.user_slabs)] * est.max_iter
    assert sent == [(np.ndarray, np.int32, (slab_entries(*shape),)) for shape in shapes]


@pytest.mark.parametrize("warm", [False, True])
def test_no_relayout_is_enqueued_behind_the_draw_or_the_last_buckets(warm, monkeypatch):
    """A relayout allocates a table beside the one it reads, so the fit
    waits before each pair: for the seeded (or warm-start) tables before the
    relayouts into dispatch order, and for the last half-sweep's table
    before the relayouts back. Nothing else in the fit waits, and what it
    returns is what it returned without the waits."""
    m = _holed_matrix()
    init = None
    if warm:
        rng = np.random.default_rng(5)
        init = (rng.normal(0, 0.3, (m.n_users, 8)).astype(np.float32),
                rng.normal(0, 0.3, (m.n_items, 8)).astype(np.float32))
    kw = dict(MERGE_KW, solver="cg", init_factors=init, chunked=True)
    want = ImplicitALS(**kw).fit(m)
    est = ImplicitALS(**kw)
    layout = est._stream_layout(m)
    events = []
    wait = jax.block_until_ready

    def waited(x):
        events.append(("wait", tuple(a.shape for a in jax.tree.leaves(x))))
        return wait(x)

    class Relayouts(dict):
        def __getitem__(self, key):
            program = super().__getitem__(key)

            def call(table, index):
                events.append(("relayout", table.shape, index.shape))
                return program(table, index)
            return call

    chunked_executables = ImplicitALS._chunked_executables
    kept = Relayouts()

    def executables(self, matrix, kind="shapes"):
        return kept if kind == "relayouts" else chunked_executables(self, matrix, kind)

    monkeypatch.setattr(ImplicitALS, "_chunked_executables", executables)
    monkeypatch.setattr(jax, "block_until_ready", waited)
    got = est.fit(m)
    n_user, n_item = layout.user_order.size, layout.item_order.size
    assert n_user > m.n_users and n_item > m.n_items          # padding slots
    assert events == [
        ("wait", ((m.n_users, 8), (m.n_items, 8))),
        ("relayout", (m.n_users, 8), (n_user,)),
        ("relayout", (m.n_items, 8), (n_item,)),
        ("wait", ((n_user, 8),)),
        ("relayout", (n_user, 8), (m.n_users,)),
        ("relayout", (n_item, 8), (m.n_items,)),
    ]
    np.testing.assert_array_equal(got.user_factors, want.user_factors)
    np.testing.assert_array_equal(got.item_factors, want.item_factors)
