"""The chunked host-streamed ALS fallback: numerics parity with the
device-resident path (both solvers), the admission wiring in ``fit``, the
als.chunked chaos site, and the over-budget-fit-completes acceptance bar."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.datasets.synthetic import synthetic_stars  # noqa: E402
from albedo_tpu.models.als import ImplicitALS  # noqa: E402
from albedo_tpu.utils import capacity, faults  # noqa: E402

KW = dict(rank=8, max_iter=3, seed=0, batch_size=16)


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
