"""Mesh-resident fold-in: per-device admission pricing, owner routing
geometry, 1-device mesh parity against the single-device engine, the
elastic streaming loss contract (clean ``MeshLost`` when no rung remains),
the rung-stamped lineage + reload-gate tolerance pin, and the retrieval
bank's recompile-free mesh publish surviving a mid-stream reshard.

Multi-shard behavior (the 8 -> 4 remesh with fold-in parity) needs virtual
host devices a warmed-up test process cannot add; that lives in the CLI
chaos drill (``tests/test_chaos_stream.py``, slow)."""

import argparse

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.builders.jobs import JobContext  # noqa: E402
from albedo_tpu.datasets import artifacts as store  # noqa: E402
from albedo_tpu.datasets import synthetic_tables  # noqa: E402
from albedo_tpu.datasets.synthetic import synthetic_stars  # noqa: E402
from albedo_tpu.models.als import ImplicitALS  # noqa: E402
from albedo_tpu.parallel.elastic import MeshLost  # noqa: E402
from albedo_tpu.parallel.foldin import ShardedFoldIn  # noqa: E402
from albedo_tpu.parallel.mesh import make_mesh  # noqa: E402
from albedo_tpu.streaming.foldin import FoldInEngine  # noqa: E402
from albedo_tpu.streaming.job import run_stream  # noqa: E402
from albedo_tpu.utils import capacity, events, faults  # noqa: E402

REG, ALPHA = 0.5, 40.0


@pytest.fixture(scope="module")
def trained():
    matrix = synthetic_stars(n_users=150, n_items=100, rank=8, mean_stars=10, seed=4)
    model = ImplicitALS(rank=8, reg_param=REG, alpha=ALPHA, max_iter=4).fit(matrix)
    return matrix, model


def _random_rows(n_items, n_rows, seed):
    """Synthetic ``(item_idx, confidence)`` fold-in rows with ragged
    lengths — what ``StarOverlay.user_row`` hands the engine."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n_rows):
        k = int(rng.integers(1, 12))
        idx = rng.choice(n_items, size=k, replace=False).astype(np.int64)
        val = rng.uniform(0.5, 4.0, size=k).astype(np.float32)
        rows.append((idx, val))
    return rows


# --- per-device pricing -------------------------------------------------------


class TestPlanFoldin:
    def test_single_device_price_is_the_legacy_plan(self):
        old = capacity.plan_foldin(64, 32, 8, 100)
        new = capacity.plan_foldin(64, 32, 8, 100, n_devices=1, mode="ring")
        assert old.workload == new.workload == "foldin"
        assert old.items == new.items
        assert "transient_assembly" not in new.items

    def test_mesh_rungs_scale_per_device(self):
        p1 = capacity.plan_foldin(64, 32, 8, 1000)
        p4 = capacity.plan_foldin(64, 32, 8, 1000, n_devices=4)
        assert p4.workload == "foldin_sharded"
        # Each device holds 1/4 of the item table and 1/4 of the slab.
        assert p4.items["frozen_item_side"] < p1.items["frozen_item_side"]
        assert p4.items["rung_slab"] == p1.items["rung_slab"] // 4
        # The all-gather transient is the whole padded item table.
        i_pad = p4.items["transient_assembly"] // (8 * 4)
        assert i_pad >= 1000 and i_pad % 4 == 0

    def test_ring_transient_undercuts_allgather(self):
        ag = capacity.plan_foldin(64, 32, 8, 1000, n_devices=4, mode="allgather")
        ring = capacity.plan_foldin(64, 32, 8, 1000, n_devices=4, mode="ring")
        assert ring.workload == "foldin_sharded_ring"
        # Ring holds two 1/n shards in flight vs the full gathered table —
        # 2/n of the all-gather transient, what the admission ladder trades on.
        assert (
            ring.items["transient_assembly"] * 4
            == ag.items["transient_assembly"] * 2
        )
        assert ring.required_bytes < ag.required_bytes


# --- owner routing geometry ---------------------------------------------------


def _geometry(n_shards: int, n_users: int) -> ShardedFoldIn:
    """Routing geometry only (pure numpy) — no mesh or device required, so
    shard counts a 1-CPU test box cannot boot are still coverable."""
    sf = ShardedFoldIn.__new__(ShardedFoldIn)
    sf.n_shards = n_shards
    sf.n_users = n_users
    return sf


class TestRouting:
    def test_owners_follow_user_table_shard_blocks(self):
        sf = _geometry(4, 100)  # rows_per = ceil(100/4) = 25
        got = sf.owners([0, 24, 25, 50, 74, 75, 99])
        assert got.tolist() == [0, 0, 1, 2, 2, 3, 3]

    def test_pad_tail_users_clamp_to_the_last_shard(self):
        sf = _geometry(4, 10)  # rows_per = 3: users 9.. belong to shard 3
        assert sf.owners([9]).tolist() == [3]

    def test_round_robin_without_a_user_table(self):
        sf = _geometry(4, 0)
        assert sf.owners([0, 1, 5, 11]).tolist() == [0, 1, 1, 3]

    def test_build_slab_routes_and_unpermutes(self):
        sf = _geometry(2, 8)  # rows_per = 4: users 0-3 -> shard 0
        rows = _random_rows(50, 5, seed=3)
        owners = np.array([0, 1, 1, 0, 1])
        idx, val, mask, pos = sf.build_slab(rows, owners)
        # 3 rows on the busiest shard -> pow2 block of 4 per shard.
        assert idx.shape[0] == 2 * 4 and idx.shape == val.shape == mask.shape
        assert (idx.shape[1] & (idx.shape[1] - 1)) == 0  # pow2 length
        for j, (ri, rv) in enumerate(rows):
            r = pos[j]
            # Row j landed inside its owner's block...
            assert owners[j] * 4 <= r < (owners[j] + 1) * 4
            # ...carrying exactly its entries.
            assert np.array_equal(idx[r, : ri.size], ri)
            assert np.allclose(val[r, : ri.size], rv)
            assert mask[r].sum() == ri.size
        assert len(set(pos.tolist())) == len(rows)


# --- ring mode stays alive in tier-1 ------------------------------------------


def test_ring_foldin_two_shards_matches_single_device(trained):
    """Tier-1 pin for ``parallel.als._ring_solve`` under shard_map's
    varying-axes check: the ring carry broke on a JAX upgrade while every
    ring fold-in test sat in the slow lane, and ring mode was dead for the
    sharded fit AND the mesh fold-in until a chip bring-up tripped over it.
    Two shards is the smallest mesh whose ``ppermute`` actually moves data."""
    matrix, model = trained
    rows = _random_rows(matrix.n_items, 11, seed=5)
    want = FoldInEngine(model, reg_param=REG, alpha=ALPHA, max_batch=16).fold_in(rows)
    got = FoldInEngine(
        model, reg_param=REG, alpha=ALPHA, max_batch=16,
        mesh=make_mesh(2), shard_mode="ring",
    ).fold_in(rows)
    assert np.allclose(got, want, atol=1e-5), np.abs(got - want).max()


# --- 1-device mesh parity -----------------------------------------------------

# Everything below compiles shard_map programs (engine construction alone
# pays the sharded-Gramian trace); the tier-1 budget on a CPU box cannot
# absorb them, so they ride the slow lane with the chaos drills. The pure
# host-side pricing/routing tests above stay tier-1.


@pytest.mark.slow
class TestMeshParity:
    @pytest.mark.parametrize("mode", ["allgather", "ring"])
    def test_mesh_engine_matches_single_device(self, trained, mode):
        matrix, model = trained
        rows = _random_rows(matrix.n_items, 23, seed=9)
        single = FoldInEngine(model, reg_param=REG, alpha=ALPHA, max_batch=16)
        mesh = FoldInEngine(
            model, reg_param=REG, alpha=ALPHA, max_batch=16,
            mesh=make_mesh(1), shard_mode=mode,
        )
        want = single.fold_in(rows)
        got = mesh.fold_in(rows)
        assert np.allclose(got, want, atol=1e-5), np.abs(got - want).max()
        assert mesh.last_admission is not None
        assert mesh.last_admission["n_devices"] == 1
        # A 1-device mesh prices as the plain fold-in rung.
        assert mesh.last_admission["chosen"] == "foldin"

    def test_warm_registers_sharded_executables(self, trained):
        _, model = trained
        engine = FoldInEngine(
            model, reg_param=REG, alpha=ALPHA, max_batch=16, mesh=make_mesh(1),
        )
        assert engine.warm((8,)) >= 1

    def test_injected_oom_degrades_never_refuses(self, trained, monkeypatch):
        """The never-refuse contract on the mesh: an injected admission oom
        forces the preferred rung over budget; the batch must still fold
        (degraded), with the verdict on the admission record."""
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", str(64 << 30))
        matrix, model = trained
        rows = _random_rows(matrix.n_items, 8, seed=2)
        engine = FoldInEngine(
            model, reg_param=REG, alpha=ALPHA, max_batch=16, mesh=make_mesh(1),
        )
        reference = FoldInEngine(
            model, reg_param=REG, alpha=ALPHA, max_batch=16,
        ).fold_in(rows)
        faults.arm("capacity.admit", kind="oom", at=1)
        try:
            solved = engine.fold_in(rows)
        finally:
            faults.disarm("capacity.admit")
        assert np.allclose(solved, reference, atol=1e-5)
        assert engine.last_admission["verdict"] in ("degrade", "refuse", "fit")
        assert engine.last_admission["chosen"] != ""


# --- the elastic streaming cycle ----------------------------------------------


def make_ctx(tag, **args_over):
    ns = argparse.Namespace(
        small=True, tables=None, now=1700000000.0, no_compilation_cache=True,
        data_policy=None, solver="cholesky", cg_steps=3, checkpoint_every=0,
        resume=False, keep_last=3, _rest=[],
        **args_over,
    )
    tables = synthetic_tables(n_users=120, n_items=80, mean_stars=10, seed=11)
    return JobContext(ns, tables=tables, tag=tag), ns


def _opts(**over):
    base = dict(
        cycles=1, delta_batch=60, stream_seed=7, deltas="",
        drift_tolerance=0.05, drift_floor=0.0, drift_every=1,
        half_life_days=7.0, recency_boost=1.0, foldout_limit=0,
        max_foldin_batch=16, probe_users=40, no_publish=False,
        keep_stream=3, refit_checkpoint_every=2,
    )
    base.update(over)
    return argparse.Namespace(**base)


@pytest.mark.slow
class TestElasticStream:
    def test_mesh_stream_journals_rung_and_stamps_lineage(self):
        """A clean mesh stream: mesh_events on the journal, the rung on the
        cycle record and the lineage stamp — and the reload gate PROMOTES
        the mesh-published generation into a single-device service (the
        stamp gate reads named lineage keys, so a rung change between
        publisher and reloader is tolerated by construction)."""
        from albedo_tpu.serving.reload import HotSwapManager
        from albedo_tpu.serving.service import RecommendationService

        ctx, ns = make_ctx("streammesh", mesh_devices=1)
        journal = run_stream(ctx, ns, _opts())
        me = journal["mesh_events"]
        assert me["n_shards_start"] == 1 and me["n_shards"] == 1
        assert me["losses"] == 0 and me["remeshes"] == []
        rec = journal["cycles"][0]["foldin"]
        assert rec["n_devices"] == 1
        assert rec["admission"]["chosen"] == "foldin"
        g1 = store.artifact_path(
            ctx.artifact_name(f"{ctx.als_key()}-stream-g1.pkl")
        )
        assert store.verify_manifest(g1) is True
        assert store.read_meta(g1)["lineage"]["n_devices"] == 1
        with RecommendationService(ctx.als_model(), ctx.matrix()) as service:
            manager = HotSwapManager(
                service, artifact_glob=f"{ctx.tag}-alsModel-*stream-g*.pkl"
            )
            assert manager.request_reload()["outcome"] == "promoted"

    def test_loss_with_no_rung_below_fails_clean_with_nothing_published(self):
        """The 1-device loss contract: a collective loss with no smaller
        rung raises MeshLost (counted, resume outcome ``failed``) and the
        drained cycle publishes NOTHING — no half-applied generation."""
        ctx, ns = make_ctx("streammeshloss", mesh_devices=1)
        losses = events.mesh_losses.total()
        failed = events.elastic_resumes.value(outcome="failed")
        faults.arm("stream.foldin.collective", kind="loss", at=1)
        try:
            with pytest.raises(MeshLost):
                run_stream(ctx, ns, _opts())
        finally:
            faults.disarm("stream.foldin.collective")
        assert events.mesh_losses.total() == losses + 1
        assert events.elastic_resumes.value(outcome="failed") == failed + 1
        g1 = store.artifact_path(
            ctx.artifact_name(f"{ctx.als_key()}-stream-g1.pkl")
        )
        assert not g1.exists()


# --- bank publish on the mesh -------------------------------------------------


@pytest.mark.slow
class TestBankMeshPublish:
    def test_mesh_foldin_publishes_and_survives_reshard(self, trained):
        """The streaming overlay on the mesh: folded rows land in the
        serving bank with no recompile, and a mid-stream ``reshard`` keeps
        SUBSEQUENT fold-ins landing on the new layout."""
        from albedo_tpu.retrieval.bank import RetrievalBank

        matrix, model = trained
        bank = RetrievalBank(max_batch=8)
        bank.register_source(
            "als", kind="user_rows", vectors=model.item_factors,
            item_ids=np.asarray(matrix.item_ids),
            user_vectors=model.user_factors,
        )
        bank.build(matrix=matrix)
        engine = FoldInEngine(
            model, reg_param=REG, alpha=ALPHA, max_batch=16, mesh=make_mesh(1),
        )
        engine.attach_bank(bank, "als")

        uidx1 = np.array([3, 7, 11], dtype=np.int64)
        solved1 = engine.fold_in(
            _random_rows(matrix.n_items, len(uidx1), seed=5), user_idx=uidx1
        )
        gen1 = bank.overlay_generation
        assert gen1 >= 1
        assert np.array_equal(bank.specs["als"].user_vectors[uidx1], solved1)

        bank.reshard(make_mesh(1))

        uidx2 = np.array([2, 19], dtype=np.int64)
        solved2 = engine.fold_in(
            _random_rows(matrix.n_items, len(uidx2), seed=6), user_idx=uidx2
        )
        assert bank.overlay_generation > gen1
        assert np.array_equal(bank.specs["als"].user_vectors[uidx2], solved2)
        # Earlier overlay rows survived the reshard, and queries answer.
        assert np.array_equal(bank.specs["als"].user_vectors[uidx1], solved1)
        vals, _ = bank.query(uidx1, k=5, sources=("als",))["als"]
        assert np.isfinite(np.asarray(vals)).all()
