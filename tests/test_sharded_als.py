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

    def test_tighter_budget_degrades_to_streamed(
        self, mesh8, matrix, reference, monkeypatch
    ):
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
