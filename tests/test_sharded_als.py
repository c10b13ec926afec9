"""The ALX-scale sharded ALS fit (``parallel.als.ShardedALSFit`` behind
``ImplicitALS.fit``): both factor tables row-sharded over the 8-virtual-CPU
mesh, parity with the single-device resident fit pinned at atol 1e-5 across
solvers/modes, the streamed-bucket path, the ``als.shard.*`` chaos surface,
and the capacity admission ladder (forced-low-budget acceptance drill
included)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.datasets.synthetic import synthetic_stars  # noqa: E402
from albedo_tpu.models.als import ImplicitALS  # noqa: E402
from albedo_tpu.parallel import make_mesh  # noqa: E402
from albedo_tpu.parallel.als import ShardedALSFit  # noqa: E402
from albedo_tpu.utils import capacity, faults  # noqa: E402

ATOL = 1e-5
KW = dict(rank=8, max_iter=2, batch_size=32, seed=1)


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8
    return make_mesh(8)


@pytest.fixture(scope="module")
def matrix():
    return synthetic_stars(n_users=64, n_items=48, mean_stars=6, seed=3)


@pytest.fixture(scope="module")
def reference(matrix):
    """Single-device RESIDENT fit (admission bypassed) — the parity anchor."""
    return ImplicitALS(**KW, chunked=False).fit(matrix)


def _parity(model, reference):
    np.testing.assert_allclose(
        model.user_factors, reference.user_factors, atol=ATOL
    )
    np.testing.assert_allclose(
        model.item_factors, reference.item_factors, atol=ATOL
    )


class TestParity:
    def test_sharded_resident_matches_single_device(self, mesh8, matrix, reference):
        est = ImplicitALS(**KW, mesh=mesh8, sharded=True)
        model = est.fit(matrix)
        _parity(model, reference)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded"
        assert rep["n_shards"] == 8
        assert rep["streamed_buckets"] == 0

    def test_sharded_streamed_matches_single_device(self, mesh8, matrix, reference):
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed")
        model = est.fit(matrix)
        _parity(model, reference)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded_streamed"
        # Every bucket of every half-sweep re-uploaded: the star matrix was
        # never device-resident whole.
        assert rep["streamed_buckets"] > 0

    def test_ring_mode_matches_single_device(self, mesh8, matrix, reference):
        est = ImplicitALS(**KW, mesh=mesh8, sharded=True, shard_mode="ring")
        model = est.fit(matrix)
        _parity(model, reference)
        assert est.last_fit_report["shard_mode"] == "ring"

    def test_cg_with_warm_start_matches_single_device(self, mesh8, matrix):
        rng = np.random.default_rng(0)
        init = (
            rng.normal(0, 0.1, (matrix.n_users, KW["rank"])).astype(np.float32),
            rng.normal(0, 0.1, (matrix.n_items, KW["rank"])).astype(np.float32),
        )
        kw = dict(KW, solver="cg", init_factors=init)
        ref = ImplicitALS(**kw, chunked=False).fit(matrix)
        model = ImplicitALS(**kw, mesh=mesh8, sharded=True).fit(matrix)
        _parity(model, ref)

    def test_ring_with_cg_rejected(self, mesh8):
        with pytest.raises(ValueError, match="ring mode"):
            ShardedALSFit(mesh8, solver="cg", mode="ring")


class TestPipelinedDataflow:
    """The pipelined dataflow (double-buffered prefetch, overlapped ring
    phases, fused landing scatter) is numerically IDENTICAL to the
    synchronous PR 8 dataflow — the parity matrix pins streamed-pipelined
    vs streamed-synchronous vs resident against the single-device fit."""

    def _engine_fit(self, mesh8, matrix, mode="allgather", solver="cholesky",
                    streamed=True, pipelined=True, init=None):
        est = ImplicitALS(**KW, solver=solver, shard_mode=mode, mesh=mesh8)
        eng = ShardedALSFit(mesh8, solver=solver, mode=mode)
        if init is None:
            import jax as _jax
            import jax.numpy as _jnp
            ukey, ikey = _jax.random.split(_jax.random.PRNGKey(KW["seed"]))
            scale = 1.0 / np.sqrt(KW["rank"])
            init = (
                np.asarray(_jax.random.normal(
                    ukey, (matrix.n_users, KW["rank"]), _jnp.float32) * scale),
                np.asarray(_jax.random.normal(
                    ikey, (matrix.n_items, KW["rank"]), _jnp.float32) * scale),
            )
        ub, ib = est._host_buckets(matrix)
        u, v, stats = eng.fit(
            init[0], init[1], ub, ib, est.reg_param, est.alpha, KW["max_iter"],
            streamed=streamed, pipelined=pipelined,
        )
        return np.asarray(u), np.asarray(v), stats

    @pytest.mark.parametrize("mode", ["allgather", "ring"])
    def test_streamed_pipelined_matches_sync_and_resident(
        self, mesh8, matrix, reference, mode
    ):
        for streamed, pipelined in ((True, True), (True, False), (False, True)):
            u, v, stats = self._engine_fit(
                mesh8, matrix, mode=mode, streamed=streamed, pipelined=pipelined
            )
            np.testing.assert_allclose(u, reference.user_factors, atol=ATOL)
            np.testing.assert_allclose(v, reference.item_factors, atol=ATOL)
            assert stats["pipelined"] is pipelined

    def test_cg_pipelined_matches_single_device(self, mesh8, matrix):
        rng = np.random.default_rng(0)
        init = (
            rng.normal(0, 0.1, (matrix.n_users, KW["rank"])).astype(np.float32),
            rng.normal(0, 0.1, (matrix.n_items, KW["rank"])).astype(np.float32),
        )
        ref = ImplicitALS(**KW, solver="cg", init_factors=init, chunked=False).fit(matrix)
        u, v, _ = self._engine_fit(
            mesh8, matrix, solver="cg", streamed=True, pipelined=True, init=init
        )
        np.testing.assert_allclose(u, ref.user_factors, atol=ATOL)
        np.testing.assert_allclose(v, ref.item_factors, atol=ATOL)

    def test_streamed_default_is_pipelined_with_prefetch(self, mesh8, matrix, reference):
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed")
        model = est.fit(matrix)
        rep = est.last_fit_report
        assert rep["pipelined"] is True
        assert rep["streamed_buckets"] > 0
        # Uploads happened in the background thread; the sweep's stall time
        # is recorded separately from the (hidden) upload time.
        assert rep["prefetch_wait_s"] >= 0
        assert faults.FAULTS.hits("als.shard.prefetch") > 0
        _parity(model, reference)

    def test_streamed_sync_mode_reachable_for_triage(
            self, mesh8, matrix, reference, monkeypatch):
        before = faults.FAULTS.hits("als.shard.prefetch")
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed_sync")
        model = est.fit(matrix)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded_streamed"
        assert rep["pipelined"] is False
        # The synchronous path never touches the prefetch surface.
        assert faults.FAULTS.hits("als.shard.prefetch") == before
        _parity(model, reference)
        # The ladder always prices this dataflow, as its last rung: a refusal
        # names every rung it tried.
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "1k")
        with pytest.raises(capacity.CapacityExceeded) as refused:
            est.admission_mesh(matrix)
        assert refused.value.verdict.detail.rstrip(")").split(", ")[-1].startswith(
            "als_fit_sharded_streamed_sync=")


class TestPrefetchFaultSite:
    def test_prefetch_error_surfaces_as_clean_failed_fit(self, mesh8, matrix):
        # at=2: the first bucket prefetches fine, the SECOND dies in the
        # background uploader — the error must be delivered to the
        # consuming sweep and fail the fit cleanly, never hang it.
        faults.arm("als.shard.prefetch", kind="error", at=2)
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed")
        with pytest.raises(faults.FaultInjected):
            est.fit(matrix)
        assert faults.FAULTS.fired("als.shard.prefetch") == 1

    def test_prefetch_silent_on_resident_path(self, mesh8, matrix, reference):
        faults.arm("als.shard.prefetch", kind="error", at=1)
        model = ImplicitALS(**KW, mesh=mesh8, sharded=True).fit(matrix)
        assert faults.FAULTS.fired("als.shard.prefetch") == 0
        _parity(model, reference)

    def test_wedged_prefetch_bounded_by_collective_deadline(
        self, mesh8, matrix, monkeypatch
    ):
        """A prefetch thread stuck longer than the collective deadline must
        surface as PrefetchStalled — a clean failed fit, never a hang. The
        injected delay out-sleeps a shrunk deadline, exactly the
        wedged-uploader shape."""
        from albedo_tpu.parallel.als import PrefetchStalled

        monkeypatch.setenv("ALBEDO_COLLECTIVE_DEADLINE_S", "0.2")
        faults.arm("als.shard.prefetch", kind="delay", at=1, param=2.0)
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed")
        with pytest.raises(PrefetchStalled, match="collective deadline"):
            est.fit(matrix)


class TestFaultSites:
    def test_gather_fault_fails_the_fit(self, mesh8, matrix):
        faults.arm("als.shard.gather", kind="error", at=1)
        est = ImplicitALS(**KW, mesh=mesh8, sharded=True)
        with pytest.raises(faults.FaultInjected):
            est.fit(matrix)
        assert faults.FAULTS.fired("als.shard.gather") == 1

    def test_stream_fault_fails_mid_stream(self, mesh8, matrix):
        # at=2: the first bucket uploads fine, the SECOND dies — a genuinely
        # mid-stream failure, not a failed first dispatch.
        faults.arm("als.shard.stream", kind="error", at=2)
        est = ImplicitALS(**KW, mesh=mesh8, sharded="streamed")
        with pytest.raises(faults.FaultInjected):
            est.fit(matrix)
        assert faults.FAULTS.fired("als.shard.stream") == 1

    def test_stream_site_silent_when_resident(self, mesh8, matrix, reference):
        # The resident sharded path never streams, so an armed stream fault
        # must never fire there.
        faults.arm("als.shard.stream", kind="error", at=1)
        model = ImplicitALS(**KW, mesh=mesh8, sharded=True).fit(matrix)
        assert faults.FAULTS.fired("als.shard.stream") == 0
        _parity(model, reference)


class TestAdmissionLadder:
    def _plans(self, matrix, est):
        shapes_u, shapes_i = est._plan_shapes(matrix)
        args = (shapes_u, shapes_i, matrix.n_users, matrix.n_items, est.rank)
        return (
            capacity.plan_fit(*args, n_devices=8),
            capacity.plan_fit_sharded(*args, 8, streamed=False),
            capacity.plan_fit_sharded(*args, 8, streamed=True),
        )

    def test_acceptance_drill_over_budget_trains_sharded(
        self, mesh8, matrix, reference, monkeypatch
    ):
        """The ISSUE acceptance criterion: a matrix whose replicated factor
        tables + interactions exceed one device's (forced-low) budget trains
        to completion on the 8-device mesh through the sharded path, factors
        matching the single-device resident fit within atol 1e-5."""
        est = ImplicitALS(**KW, mesh=mesh8)
        replicated, sharded, _ = self._plans(matrix, est)
        # Budget between the replicated per-device plan and the sharded one.
        monkeypatch.setenv("ALBEDO_MEM_HEADROOM", "1.0")
        monkeypatch.setenv(
            "ALBEDO_DEVICE_MEM_BYTES", str(sharded.required_bytes + 64)
        )
        assert sharded.required_bytes + 64 < replicated.required_bytes
        model = est.fit(matrix)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded"
        assert rep["capacity"]["verdict"] == "degrade"
        assert rep["capacity"]["chosen"] == "als_fit_sharded"
        _parity(model, reference)

    def test_tighter_budget_degrades_to_streamed(self, mesh8, monkeypatch):
        # A matrix whose slabs outweigh a streamed bucket's transient (its
        # program assembles the source table and all-gathers its solved
        # rows; the resident dataflow holds every slab shard but assembles
        # once a half-sweep and lands locally).
        matrix = synthetic_stars(n_users=64, n_items=48, mean_stars=24, seed=3)
        reference = ImplicitALS(**KW, chunked=False).fit(matrix)
        est = ImplicitALS(**KW, mesh=mesh8)
        _, sharded, streamed = self._plans(matrix, est)
        monkeypatch.setenv("ALBEDO_MEM_HEADROOM", "1.0")
        monkeypatch.setenv(
            "ALBEDO_DEVICE_MEM_BYTES", str(streamed.required_bytes + 64)
        )
        assert streamed.required_bytes + 64 < sharded.required_bytes
        model = est.fit(matrix)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded_streamed"
        assert rep["capacity"]["chosen"] == "als_fit_sharded_streamed"
        _parity(model, reference)

    def test_refuses_when_even_streamed_busts(self, mesh8, matrix, monkeypatch):
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "1k")
        est = ImplicitALS(**KW, mesh=mesh8)
        with pytest.raises(capacity.CapacityExceeded, match="refused: capacity"):
            est.fit(matrix)

    def test_ample_budget_keeps_the_replicated_path(self, mesh8, matrix, monkeypatch):
        # Admission-only (running the fused GSPMD fit here would just re-pay
        # its compile): an ample budget verdicts `fit` on the first rung, so
        # `fit()` falls through to the existing replicated path.
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "64g")
        est = ImplicitALS(**KW, mesh=mesh8)
        v = est.admission_mesh(matrix)
        assert v.verdict == "fit" and v.chosen == "als_fit"

    def test_injected_oom_reroutes_to_sharded(self, mesh8, matrix, reference):
        faults.arm("capacity.admit", kind="oom", at=1)
        est = ImplicitALS(**KW, mesh=mesh8)
        model = est.fit(matrix)
        rep = est.last_fit_report
        assert rep["mode"] == "sharded"
        assert "injected" in rep["capacity"]["detail"]
        _parity(model, reference)


class TestResidentDataflow:
    """``sharded="resident"`` under ``shard_mode="allgather"`` (what ``train_als
    --mesh-devices 4 --sharded resident`` builds), on four of the eight
    virtual devices: every device solves its own rows against the source
    table assembled ONCE a half-sweep."""

    RANK = 16
    # the eight numbers of benchmark/compare.py; f32 throughout on the CPU
    LIMITS = dict.fromkeys(
        (f"{side}_{n}" for side in ("user", "item")
         for n in ("rows_worst", "rows_p99", "rows_median", "all_rows_worst")), 1e-4)

    @pytest.fixture(scope="class")
    def mesh4(self):
        return make_mesh(4)

    @pytest.fixture(scope="class")
    def fitted(self, mesh4):
        m = synthetic_stars(n_users=202, n_items=131, mean_stars=9, seed=5)
        est = ImplicitALS(rank=self.RANK, max_iter=2, batch_size=32, seed=11, solver="cg",
                          cg_steps=3, mesh=mesh4, sharded="resident", shard_mode="allgather")
        return m, est, est.fit(m)

    def test_matches_the_plain_reference_on_seeded_tables_and_the_bf16_control_does_not(self, fitted):
        """Against ``benchmark/reference/als_cg.py`` from the same seed, by
        the benchmark's own eight numbers: the program inside limits that
        the reference computed in bfloat16 fails."""
        import jax.numpy as jnp

        from benchmark import compare
        from benchmark.manifest import load_module

        m, est, model = fitted
        reference = load_module("reference", "als_cg")
        stars = {"rows": m.rows, "cols": m.cols, "vals": m.vals,
                 "n_users": m.n_users, "n_items": m.n_items}
        config = {"rank": est.rank, "reg_param": est.reg_param, "alpha": est.alpha,
                  "cg_steps": est.cg_steps}
        want = reference.fit(stars, config, est.seed, est.max_iter)
        ok, compared = compare.judge(compare.compare_fit(
            model.user_factors, model.item_factors, *want, stars, min_stars=4), self.LIMITS)
        assert ok, compared
        control = reference.fit(stars, config, est.seed, est.max_iter, jnp.bfloat16)
        ok, compared = compare.judge(compare.compare_fit(
            np.asarray(control[0], np.float32), np.asarray(control[1], np.float32), *want,
            stars, min_stars=4), self.LIMITS)
        assert not ok and compared["user_rows_median"]["value"] > 10 * self.LIMITS["user_rows_median"]

    def test_a_half_sweep_gathers_the_source_table_once_and_the_target_never(self, fitted, mesh4):
        """The lowered programs of one CG half-sweep: ONE all-gather, of the
        source table's shard, in the assembly; one all-reduce, the (k, k)
        psum; none in any bucket program or in the landing. The per-bucket
        program of the streamed rungs still gathers both tables in every
        bucket — what this dataflow replaced."""
        import re

        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from albedo_tpu.parallel.als import (
            assembled_bytes_per_sweep, collective_bytes_per_sweep, pad_rows, sharded_fit_engine)

        m, est, _ = fitted
        rep = est.last_fit_report
        engine = sharded_fit_engine(mesh4, "data", "cg", 3, None, "allgather")
        n_u, n_i = pad_rows(m.n_users, 4), pad_rows(m.n_items, 4)
        ug, ig = est._device_groups_sharded(m, None, engine)[:2]

        def collectives(fn, args, statics=None):
            text = fn.lower(*args, **(statics or {})).as_text()
            return {op: re.findall(rf"stablehlo\.{op}\b.*", text)
                    for op in ("all_gather", "all_reduce", "all_to_all", "collective_permute",
                               "reduce_scatter")}

        for n_source, n_target, groups in ((n_u, n_i, ig), (n_i, n_u, ug)):
            programs = engine._local_programs(
                n_source, n_target, self.RANK, [tuple(g[1].shape) for g in groups])
            assert [p[0] for p in programs] == (
                ["assemble"] + ["local_solve"] * (len(programs) - 2) + ["local_land"])
            for kind, fn, args, _, statics in programs:
                found = collectives(fn, args, statics)
                count = {op: len(lines) for op, lines in found.items()}
                if kind == "assemble":
                    assert count == {"all_gather": 1, "all_reduce": 0, "all_to_all": 0,
                                     "collective_permute": 0, "reduce_scatter": 0}
                    # the source's shard in, the whole table out
                    assert f"tensor<{n_source // 4}x{self.RANK}xf32>" in found["all_gather"][0]
                    assert f"tensor<{n_source}x{self.RANK}xf32>" in found["all_gather"][0]
                else:
                    assert not any(count.values()), (kind, count)
            source = jax.ShapeDtypeStruct((n_source, self.RANK), jnp.float32,
                                          sharding=NamedSharding(mesh4, P("data", None)))
            count = {op: len(lines) for op, lines in collectives(engine._gramian, (source,)).items()}
            assert count["all_reduce"] == 1 and sum(count.values()) == 1

        # the fit's counter is read off the compiled programs its sweeps called,
        # and meets the plan from the shapes
        assert engine._gathered[("assemble", n_u, self.RANK)] == n_u * self.RANK * 4
        assert {key[0]: b for key, b in engine._gathered.items() if key[0].startswith("local")} == {
            "local_solve": 0, "local_land": 0}
        assert rep["assembled_bytes_per_sweep"] == (n_u + n_i) * self.RANK * 4
        assert rep["assembled_bytes_per_sweep"] == assembled_bytes_per_sweep(
            m.n_users, m.n_items, self.RANK, 4)
        assert assembled_bytes_per_sweep(200, 132, self.RANK, 4) == (200 + 132) * self.RANK * 4
        assert rep["collective_bytes_per_sweep"] == collective_bytes_per_sweep(
            self.RANK, 4, rep["assembled_bytes_per_sweep"])
        assert rep["collective_bytes_per_sweep"] == (
            (n_u + n_i) * self.RANK * 4 // 4 + 2 * self.RANK**2 * 4) * 3
        assert rep["dispatches"] == est.max_iter * (len(ug) + len(ig) + 6)
        assert rep["shard_padded_entries"] == sum(
            g[1].shape[0] * (g[1].shape[1] // 4) * g[1].shape[2] for g in (*ug, *ig))

        # the program a bucket of the streamed rungs still runs
        b = est._host_buckets(m)[0][0]
        rows = 4 * -(-b.row_ids.shape[0] // 4)
        sds = jax.ShapeDtypeStruct
        args = (sds((n_i, self.RANK), jnp.float32), sds((self.RANK, self.RANK), jnp.float32),
                sds((n_u, self.RANK), jnp.float32), sds((rows,), jnp.int32),
                sds((rows, b.shape[1]), jnp.int32), sds((rows, b.shape[1]), jnp.float32),
                sds((rows, b.shape[1]), jnp.bool_), sds((), jnp.float32), sds((), jnp.float32))
        per_bucket = collectives(engine._update, args, engine._statics())["all_gather"]
        assert sum(f"tensor<{n}x{self.RANK}xf32>" in line for line in per_bucket
                   for n in (n_i, n_u)) == 2      # source and, for the CG warm start, target
        streamed = ImplicitALS(rank=self.RANK, max_iter=1, batch_size=32, seed=11, solver="cg",
                               mesh=mesh4, sharded="streamed_sync")
        streamed.fit(m)
        n_buckets = sum(len(side) for side in est._host_buckets(m))
        assert streamed.last_fit_report["assembled_bytes_per_sweep"] == assembled_bytes_per_sweep(
            m.n_users, m.n_items, self.RANK, 4,
            tuple(len(side) for side in est._host_buckets(m)), "cg")
        assert streamed.last_fit_report["assembled_bytes_per_sweep"] > (
            n_buckets // 2) * rep["assembled_bytes_per_sweep"]

    @pytest.mark.parametrize("line, want", [
        # a v5e's compiler, the assembly of gh10m-r128-x4's user table (PR 33)
        ("  %all-gather.4 = f32[10000000,128]{1,0:T(8,128)} all-gather(%param.1), channel_id=1, "
         "replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true", 5_120_000_000),
        # the asynchronous pair: the start's result names operand and result
        ("  %ags = (bf16[250,64]{1,0}, bf16[1000,64]{1,0}) all-gather-start(%p), dimensions={0}\n"
         "  %agd = bf16[1000,64]{1,0} all-gather-done(%ags)", 128_000),
        ("  %x = s32[8]{0} all-gather(%i), dimensions={0}\n  %y = f32[] all-reduce(%z)\n"
         "  %m = pred[4,16]{1,0} all-gather(%b), dimensions={0}\n"
         "  %q = f8e4m3fn[4,16]{1,0} all-gather(%c), dimensions={0}", 32 + 64 + 64),
        ("  ROOT %copy.3 = f32[1000,128]{1,0} copy(%fusion)", 0),
    ], ids=["tpu", "async", "narrow-types-beside-a-psum", "none"])
    def test_all_gather_bytes_reads_the_optimized_hlo(self, line, want):
        from albedo_tpu.parallel.als import all_gather_bytes

        class Compiled:
            def as_text(self):
                return f"HloModule jit_x\nENTRY %main {{\n{line}\n}}\n"

        assert all_gather_bytes(Compiled()) == want

    def test_rows_the_shards_do_not_divide_come_back_trimmed_with_no_whole_table_gather(self, fitted):
        """202 and 131 rows on four shards: the model's raw tables stay
        row-sharded, rows padded (51 and 33 a device), and the host copies
        are the logical rows."""
        m, est, model = fitted
        single = ImplicitALS(rank=self.RANK, max_iter=2, batch_size=32, seed=11, solver="cg",
                             chunked=False).fit(m)
        for raw, host, want, n in ((model._uf_raw, model.user_factors, single.user_factors, 202),
                                   (model._vf_raw, model.item_factors, single.item_factors, 131)):
            per = -(-n // 4)
            assert raw.shape == (4 * per, self.RANK) and not raw.sharding.is_fully_replicated
            assert {s.data.shape for s in raw.addressable_shards} == {(per, self.RANK)}
            assert host.shape == (n, self.RANK)
            np.testing.assert_allclose(host, want, atol=ATOL)
            np.testing.assert_array_equal(np.asarray(raw)[n:], 0.0)       # the padding rows
        assert (model.n_users, model.n_items) == (202, 131)
        # serving cuts its device copies when it asks for them
        assert [f.shape[0] for f in model.device_factors()] == [202, 131]
        scores, items = model.recommend(np.array([0, 201]), k=5)
        assert items.max() < 131 and np.isfinite(scores).all()
        with pytest.raises(IndexError):
            model.recommend(np.array([202]), k=5)

    def test_the_layout_is_the_degree_sequences_alone(self, mesh4):
        """Rows dealt to the shards by length: two matrices with the same
        row lengths on other rows bucket to the same shapes (one set of
        executables, the same work), and every shard's padded entries are
        within one row's of the others'."""
        from albedo_tpu.datasets.ragged import balanced_shards, shard_grouped_bucket_rows

        rng = np.random.default_rng(3)
        lengths = np.minimum(rng.zipf(1.6, 300), 90)
        shapes, per_shard = [], []
        for seed in (1, 2):
            mine = np.random.default_rng(seed).permutation(lengths)
            indptr = np.concatenate([[0], np.cumsum(mine)])
            phys_of_logical, logical_of_phys = balanced_shards(indptr, 4)
            np.testing.assert_array_equal(logical_of_phys[phys_of_logical], np.arange(300))
            groups = shard_grouped_bucket_rows(
                indptr, np.zeros(indptr[-1], np.int32), np.ones(indptr[-1], np.float32),
                logical_of_phys, 4, batch_size=16, max_entries=512)
            shapes.append([g.idx.shape for g in groups])
            per_shard.append([sum(int(g.mask[:, d * (g.idx.shape[1] // 4):(d + 1) * (g.idx.shape[1] // 4)].sum())
                                  for g in groups) for d in range(4)])
        assert shapes[0] == shapes[1]
        assert sum(per_shard[0]) == sum(per_shard[1]) == lengths.sum()
        assert max(per_shard[0]) - min(per_shard[0]) <= lengths.max()
