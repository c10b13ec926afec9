"""Memory-budget admission (utils.capacity): detection, pricing, verdicts,
the oom fault conversion, the compiler cross-check, and the OOM-permanent
retry classification (the fail-fast-to-degrade contract)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.utils import capacity, events, faults  # noqa: E402
from albedo_tpu.utils.faults import InjectedResourceExhausted  # noqa: E402
from albedo_tpu.utils.retry import (  # noqa: E402
    RetriesExhausted,
    RetryPolicy,
    default_retry_predicate,
    is_resource_exhausted,
    retry_call,
)


# --- detection ----------------------------------------------------------------


class TestDetection:
    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "123456")
        assert capacity.device_memory_bytes() == 123456

    def test_env_override_suffixes(self, monkeypatch):
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "2g")
        assert capacity.device_memory_bytes() == 2 << 30
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "512m")
        assert capacity.device_memory_bytes() == 512 << 20

    def test_cpu_backend_reads_proc_meminfo(self, monkeypatch):
        monkeypatch.delenv("ALBEDO_DEVICE_MEM_BYTES", raising=False)
        with open("/proc/meminfo") as f:
            total_kb = next(
                int(line.split()[1]) for line in f if line.startswith("MemTotal:")
            )
        assert capacity.device_memory_bytes() == total_kb * 1024

    @pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
    def test_accelerator_without_bytes_limit_raises(self, monkeypatch, stats):
        """A chip that reports no ``bytes_limit`` must never be priced at the
        host's RAM (or a guess): that is how an over-HBM workload gets a
        ``fit`` verdict. The explicit override still answers."""

        class FakeChip:
            platform = "tpu"
            device_kind = "TPU test"

            def memory_stats(self):
                return stats

        monkeypatch.delenv("ALBEDO_DEVICE_MEM_BYTES", raising=False)
        monkeypatch.setattr(jax, "local_devices", lambda: [FakeChip()])
        with pytest.raises(RuntimeError, match="bytes_limit"):
            capacity.device_memory_bytes()
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "16g")
        assert capacity.device_memory_bytes() == 16 << 30

    def test_accelerator_budget_is_the_reported_limit(self, monkeypatch):
        class FakeChip:
            platform = "tpu"
            device_kind = "TPU test"

            def memory_stats(self):
                return {"bytes_limit": 15 << 30, "bytes_in_use": 0}

        monkeypatch.delenv("ALBEDO_DEVICE_MEM_BYTES", raising=False)
        monkeypatch.setattr(jax, "local_devices", lambda: [FakeChip()])
        assert capacity.device_memory_bytes() == 15 << 30

    def test_budget_applies_headroom(self, monkeypatch):
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "1000000")
        monkeypatch.setenv("ALBEDO_MEM_HEADROOM", "0.5")
        assert capacity.budget_bytes() == 500000

    def test_capacity_off_switch(self, monkeypatch):
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "10")
        monkeypatch.setenv("ALBEDO_CAPACITY", "off")
        plan = capacity.CapacityPlan("x", {"stuff": 10**12})
        assert capacity.admit(plan).verdict == "fit"


# --- pricing ------------------------------------------------------------------


class TestPlans:
    def test_plan_fit_items_and_monotonicity(self):
        small = capacity.plan_fit([(8, 16)], [(8, 16)], 100, 50, 8)
        big = capacity.plan_fit([(64, 128)], [(64, 128)], 100, 50, 8)
        assert set(small.items) == {
            "factor_tables", "bucket_slabs", "landing_pools", "transient_gather",
        }
        assert 0 < small.required_bytes < big.required_bytes
        # bf16 gathers stream fewer bytes.
        bf16 = capacity.plan_fit([(64, 128)], [(64, 128)], 100, 50, 8, "bfloat16")
        assert bf16.required_bytes < big.required_bytes

    def test_chunked_plan_is_cheaper_than_resident(self):
        shapes = [(64, 64), (32, 128), (128, 16)]
        resident = capacity.plan_fit(shapes, shapes, 500, 300, 16)
        chunked = capacity.plan_fit_chunked(shapes, shapes, 500, 300, 16, None, "cholesky")
        assert chunked.required_bytes < resident.required_bytes

    @pytest.mark.parametrize("solver,ln,builds_system", [
        ("cholesky", 1, True), ("cholesky", 300, True),      # the exact solve: every row
        ("cg", 1, False), ("cg", 31, False),                 # matrix-free CG: none
        ("cg", 32, True), ("cg", 300, True),                 # cg_uses_gramian: L >= 2k
    ])
    def test_chunked_row_prices_what_the_solve_builds(self, solver, ln, builds_system):
        """A slot row of the chunked fit: its slab, its gathered block (and
        one (L,) array beside it), its (k, k) system only where the kernel
        builds one, and the CG's row state everywhere."""
        from albedo_tpu.ops.als import cg_uses_gramian

        rank = 16
        assert cg_uses_gramian(ln, rank) == (ln >= 32)
        by_hand = (
            4 + ln * 8                          # the slab as it travels: row id; index, value bits
            + ln * (rank * 4 + 4)
            + (rank * rank * 4 if builds_system else 0)
            + capacity.CHUNKED_ROW_ARRAYS * rank * 4
        )
        assert capacity.chunked_row_bytes(ln, rank, None, solver) == by_hand
        # admission's price of the rung: never under a system and one
        # rank-vector a row, what it was admitted at before (the short rows
        # under CG: 1024 + 64 B against the eight rank-vectors' 512)
        held = 4 + ln * 8 + ln * (rank * 4 + 4) + rank * rank * 4 + rank * 4
        assert (held > by_hand) == (not builds_system)
        plan = capacity.plan_fit_chunked([(64, ln)], [(8, 1)], 500, 300, rank, None, solver)
        # the tables in dispatch order: a row of its own for each padding slot
        # a tier may hold (fewer than its slots); the relayouts' index vectors
        # both ways; the larger table's second copy while a relayout runs
        rows = (500 + 64) + (300 + 8)
        assert plan.items == {
            "factor_tables": rows * rank * 4, "relayout_rows": (rows + 800) * 4,
            "worst_bucket_in_flight": 64 * max(by_hand, held), "relayout_copy": 500 * rank * 4,
        }
        # bf16 gathers halve the block, not the f32 row state
        assert by_hand - capacity.chunked_row_bytes(ln, rank, "bfloat16", solver) == ln * (rank * 2 + 2)

    @pytest.mark.parametrize("solver,worst,total", [
        # the parent priced a (k, k) system a row whatever the solver and one
        # (B, k) array: a worst bucket of 1,298,038,784 B, the users' (8192, 176),
        # with its slab's four arrays (a byte an entry more than travels since)
        ("cholesky", 1_325_957_120, 12_170_015_460),   # + seven more row arrays on that bucket
        # that bucket builds no system under CG and would price at 789,086,208
        # (the items' (239, 8768) the worst): held at what the rung was admitted at
        ("cg", 1_296_596_992, 12_140_655_332),
    ])
    def test_the_streamed_cells_plan_and_verdict(self, solver, worst, total):
        """``gh10m-r128`` (10M x 1M x 100M stars, rank 128): the heaviest
        shapes of either side of the planner's 8192-row layout (from the
        configuration's degree laws, ``benchmark.stars.degree_sequence``).
        The tables in dispatch order and a relayout's copy of the user table
        beside them: 12.1 GB, over the chip's measured peak of 11.94 GB
        (PERF.md section 4; 11.89 since the fit waits for its queued work
        before each pair of relayouts, section 6), and still the degraded
        rung's verdict."""
        user = [(8192, 176), (6144, 208), (8192, 152), (3072, 280), (8192, 128), (8192, 1)]
        item = [(239, 8768), (207, 10088), (180, 11608), (78, 26880), (8192, 1)]
        plan = capacity.plan_fit_chunked(user, item, 10_000_000, 1_000_000, 128, None, solver)
        assert plan.items["worst_bucket_in_flight"] == worst
        assert plan.items["relayout_copy"] == 10_000_000 * 128 * 4
        # every tier's padding slots a row of their own, at most
        assert plan.items["factor_tables"] == (
            10_000_000 + 6 * 1023 + 1_000_000 + 239 + 207 + 180 + 78 + 1023) * 128 * 4
        assert plan.required_bytes == total > 11_937_911_808
        # one v5e: 16,909,336,064 B at the default headroom; the resident plan 20,418,549,304
        resident = capacity.CapacityPlan("als_fit", {"resident": 20_418_549_304})
        verdict = capacity.admit(
            resident, degradable=True, budget=14_372_935_654, fallback_plan=plan)
        assert verdict.verdict == "degrade"

    # The heaviest planner shapes of each configuration's sides (from its
    # degree laws, ``benchmark.stars.degree_sequence``) and the parent's
    # prices of them, to the byte: the four-array slab, the resident plan,
    # and on four devices the replicated rung and the three row-sharded ones
    # (``gh10m-r128-x4`` has ``gh10m-r128``'s shapes). Only the chunked rung
    # holds the packed slab.
    @pytest.mark.parametrize("name,user,item,sizes,solver,slabs,plans", [
        ("ml25m-r128", [(1971, 1064), (4681, 448), (3013, 696), (8192, 240)],
         [(103, 20320), (51, 40888), (118, 17664), (8192, 16)],
         (325_082, 59_047, 128), "cg", 131_964_572,
         (2_090_157_212, 817_550_247, 524_007_847, 647_325_332, 642_603_961)),
        ("albedo-r50", [(7489, 280), (6393, 328), (8192, 240)],
         [(51, 40888), (118, 17664), (22, 94592), (8192, 24)],
         (450_000, 300_000, 50), "cg", 113_586_412,
         (922_339_492, 455_584_823, 271_737_211, 324_096_426, 319_371_999)),
        ("albedo-r50-chol", [(7489, 280), (6393, 328), (8192, 240)],
         [(51, 40888), (118, 17664), (22, 94592), (8192, 24)],
         (450_000, 300_000, 50), "cholesky", 113_586_412,
         (2_440_930_932, 835_232_683, 271_737_211, 241_763_810, 237_039_383)),
        ("gh10m-r128", [(8192, 176), (6144, 208), (8192, 152)],
         [(78, 26880), (239, 8768), (11, 190280), (8192, 32)],
         (10_000_000, 1_000_000, 128), "cg", 94_735_288,
         (12_655_467_448, 11_611_866_862, 11_671_683_822, 7_373_835_949, 7_369_120_718)),
    ])
    def test_the_resident_and_sharded_plans_hold_the_four_array_slabs(
            self, name, user, item, sizes, solver, slabs, plans):
        assert sum(capacity.bucket_slab_bytes(b, ln) for b, ln in (*user, *item)) == slabs
        assert all(capacity.bucket_slab_bytes(b, ln) == b * 4 + b * ln * 9 for b, ln in (*user, *item))
        args = (user, item, *sizes)
        kw = dict(gather_dtype=None, mode="allgather", solver=solver)
        assert (
            capacity.plan_fit(*args, None, solver=solver).required_bytes,
            capacity.plan_fit(*args, gather_dtype=None, n_devices=4, solver=solver).required_bytes,
            capacity.plan_fit_sharded(*args, 4, streamed=False, **kw).required_bytes,
            capacity.plan_fit_sharded(*args, 4, streamed=True, pipelined=True, **kw).required_bytes,
            capacity.plan_fit_sharded(*args, 4, streamed=True, pipelined=False, **kw).required_bytes,
        ) == plans
        # the chunked rung's slab, as it travels: a byte an entry less
        assert all(capacity.stream_slab_bytes(b, ln) == capacity.bucket_slab_bytes(b, ln) - b * ln
                   for b, ln in (*user, *item))

    @pytest.mark.parametrize("price,args", [
        (capacity.chunked_row_bytes, (8, 16)),
        (capacity.plan_fit_chunked, ([(64, 8)], [(8, 1)], 500, 300, 16)),
    ])
    def test_the_chunked_prices_have_no_default_solver(self, price, args):
        """A forgotten solver would price the wrong kernel without a word."""
        with pytest.raises(TypeError, match="solver"):
            price(*args, None)
        cg, exact = (price(*args, None, solver) for solver in ("cg", "cholesky"))
        assert getattr(cg, "required_bytes", cg) <= getattr(exact, "required_bytes", exact)

    def test_plan_serve_scales_with_generations(self):
        one = capacity.plan_serve(1000, 500, 16, excl_entries=100, generations=1)
        two = capacity.plan_serve(1000, 500, 16, excl_entries=100, generations=2)
        assert two.items["factor_tables"] == 2 * one.items["factor_tables"]

    def test_max_foldin_entries_monotone_in_budget(self):
        lo = capacity.max_foldin_entries(16, 1000, budget=100_000)
        hi = capacity.max_foldin_entries(16, 1000, budget=1_000_000)
        assert 1 <= lo < hi

    def test_max_foldin_entries_floor_is_one(self):
        assert capacity.max_foldin_entries(16, 10**6, budget=10) == 1

    def test_max_foldin_entries_longer_rungs_amortize_the_gramian(self):
        # The per-slot (B, rank, rank) correction amortizes over length: a
        # longer rung gets a larger entry budget, and the default length=1
        # is the conservative floor (never under-prices 1-star rows).
        short = capacity.max_foldin_entries(50, 1000, budget=10_000_000)
        long_ = capacity.max_foldin_entries(50, 1000, budget=10_000_000, length=64)
        assert short < long_

    def test_bucket_plan_shapes_match_planner(self):
        from albedo_tpu.datasets.ragged import plan_buckets
        from albedo_tpu.datasets.synthetic import synthetic_stars

        m = synthetic_stars(n_users=80, n_items=40, mean_stars=6, seed=0)
        indptr = m.csr()[0]
        shapes = capacity.bucket_plan_shapes(indptr, batch_size=16)
        assert shapes == [p.shape for p in plan_buckets(indptr, batch_size=16)]
        assert all(b >= 1 and ln >= 1 for b, ln in shapes)


class TestShardedPlans:
    SHAPES = [(64, 64), (32, 128), (128, 16)]

    def test_per_device_bytes_shrink_with_devices(self):
        p1 = capacity.plan_fit_sharded(self.SHAPES, self.SHAPES, 4000, 2000, 16, 1)
        p8 = capacity.plan_fit_sharded(self.SHAPES, self.SHAPES, 4000, 2000, 16, 8)
        assert p8.required_bytes < p1.required_bytes

    def test_streamed_sync_keeps_one_slab_in_flight(self):
        resident = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=False
        )
        streamed = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=True,
            pipelined=False,
        )
        assert streamed.workload == "als_fit_sharded_streamed_sync"
        assert streamed.required_bytes < resident.required_bytes
        assert "streamed_slab_in_flight" in streamed.items
        assert "bucket_slab_shards" in resident.items
        assert (
            streamed.items["streamed_slab_in_flight"]
            < resident.items["bucket_slab_shards"]
        )

    def test_pipelined_streamed_prices_two_slabs_in_flight(self):
        """The double-buffered prefetch holds the bucket being solved AND
        the one the background uploader just landed: the pipelined-streamed
        rung prices the two LARGEST slab shards, strictly more than the
        synchronous single slab and strictly less than two copies of the
        worst (the two in-flight buckets are distinct buckets)."""
        sync = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=True,
            pipelined=False,
        )
        piped = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=True,
            pipelined=True,
        )
        assert piped.workload == "als_fit_sharded_streamed"
        assert "streamed_slabs_in_flight" in piped.items
        worst = sync.items["streamed_slab_in_flight"]
        assert worst < piped.items["streamed_slabs_in_flight"] <= 2 * worst
        # Everything else prices identically: the pipeline costs exactly
        # one extra in-flight slab, nothing hidden.
        assert piped.items["factor_table_shards"] == sync.items["factor_table_shards"]
        assert piped.items["transient_assembly"] == sync.items["transient_assembly"]

    def test_ladder_ordering_pipelined_above_sync(self):
        """The admission ladder's degradation order holds: resident >
        pipelined-streamed > synchronous-streamed, so a budget squeezed
        between the last two picks unpipelined-streamed as the cheaper
        rung instead of refusing."""
        resident = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=False
        )
        piped = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=True
        )
        sync = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, 8, streamed=True,
            pipelined=False,
        )
        assert sync.required_bytes < piped.required_bytes < resident.required_bytes
        verdict = capacity.admit_ladder(
            [resident, piped, sync], budget=sync.required_bytes + 1
        )
        assert verdict.verdict == "degrade"
        assert verdict.chosen == "als_fit_sharded_streamed_sync"

    def test_ring_transient_below_allgather(self):
        # Ring never materializes a full table: at large table sizes its
        # per-device transient is a fraction of the all-gather mode's
        # (streamed buckets: each bucket's program assembles for itself).
        ag = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**6, 10**5, 32, 8, mode="allgather", streamed=True
        )
        ring = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**6, 10**5, 32, 8, mode="ring", streamed=True
        )
        assert ring.items["transient_assembly"] < ag.items["transient_assembly"]

    def test_cg_prices_the_target_assembly_too(self):
        """Where a bucket's program assembles for itself (streamed buckets)."""
        chol = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**5, 10**5, 32, 8, solver="cholesky", streamed=True
        )
        cg = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**5, 10**5, 32, 8, solver="cg", streamed=True
        )
        assert cg.items["transient_assembly"] > chol.items["transient_assembly"]

    @pytest.mark.parametrize("solver", ["cholesky", "cg"])
    def test_resident_allgather_prices_one_assembled_table_and_no_target(self, solver):
        """The resident dataflow (every device solves its own rows): the
        source table assembled once, whole, in float32 — the larger side's,
        where a bucket's block outweighs the other half-sweep's landing —
        and nothing for the CG warm start, which reads the device's own
        shard."""
        plan = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**6, 10**5, 32, 8, solver=solver)
        assert plan.workload == "als_fit_sharded"
        assert "transient_assembly" not in plan.items
        assert plan.items["assembled_source_table"] == 10**6 * 32 * 4
        # ... and once more: the assembly's program holds its result twice
        assert plan.items["assembly_copy"] == 10**6 * 32 * 4
        assert set(plan.items) == {"factor_table_shards", "assembled_source_table",
                                   "assembly_copy", "bucket_slab_shards"}
        assert plan.items["factor_table_shards"] == (10**6 + 10**5) * 32 * 4 // 8
        other = capacity.plan_fit_sharded(
            self.SHAPES, self.SHAPES, 10**6, 10**5, 32, 8,
            solver="cg" if solver == "cholesky" else "cholesky")
        assert plan.required_bytes == other.required_bytes
        # the ring's resident buckets still go bucket by bucket
        ring = capacity.plan_fit_sharded(self.SHAPES, self.SHAPES, 10**6, 10**5, 32, 8, mode="ring")
        assert "transient_assembly" in ring.items

    def test_the_resident_price_covers_what_the_chips_held_at_10m_by_1m(self):
        """gh10m-r128-x4 (PERF.md section 4): four v5e peaked at 9.56 GB
        traced and 10.21 - 10.73 GB untraced; without the assembly's copy the
        plan read 7.99 GB. One bucket of the largest tier a side stands for
        the shapes: the copy outweighs any bucket's block."""
        plan = capacity.plan_fit_sharded(
            [(8192, 256)], [(16, 131072)], 10**7, 10**6, 128, 4, solver="cg")
        assert plan.items["assembled_source_table"] == plan.items["assembly_copy"] == 5_120_000_000
        assert plan.items["factor_table_shards"] == 1_408_000_000
        assert plan.required_bytes > 10_727_319_040          # the fullest chip, untraced
        # a side whose block outweighs the table's copy is priced by the block
        small = capacity.plan_fit_sharded(
            [(8192, 256)], [(16, 131072)], 4000, 2000, 128, 4, solver="cg")
        assert "bucket_in_flight" in small.items and "assembly_copy" not in small.items

    def test_mesh_resident_divides_slabs_not_tables(self):
        one = capacity.plan_fit(self.SHAPES, self.SHAPES, 4000, 2000, 16)
        eight = capacity.plan_fit(
            self.SHAPES, self.SHAPES, 4000, 2000, 16, n_devices=8
        )
        assert eight.items["factor_tables"] == one.items["factor_tables"]
        assert eight.items["bucket_slabs"] < one.items["bucket_slabs"]

    def test_sharded_tables_scale_down_with_devices(self):
        p2 = capacity.plan_fit_sharded(self.SHAPES, self.SHAPES, 4000, 2000, 16, 2)
        p8 = capacity.plan_fit_sharded(self.SHAPES, self.SHAPES, 4000, 2000, 16, 8)
        assert p8.items["factor_table_shards"] < p2.items["factor_table_shards"]


class TestAdmitLadder:
    def _ladder(self):
        return [
            capacity.CapacityPlan("a", {"x": 1000}),
            capacity.CapacityPlan("b", {"x": 500}),
            capacity.CapacityPlan("c", {"x": 100}),
        ]

    def test_first_rung_fits(self):
        v = capacity.admit_ladder(self._ladder(), budget=2000)
        assert v.verdict == "fit" and v.chosen == "a"

    def test_degrade_picks_first_fitting_rung(self):
        v = capacity.admit_ladder(self._ladder(), budget=600)
        assert v.verdict == "degrade" and v.chosen == "b"
        v = capacity.admit_ladder(self._ladder(), budget=200)
        assert v.verdict == "degrade" and v.chosen == "c"

    def test_refuse_when_no_rung_fits(self):
        v = capacity.admit_ladder(self._ladder(), budget=50)
        assert v.verdict == "refuse" and v.chosen == ""
        assert "every rung" in v.detail

    def test_one_counted_verdict_per_call(self):
        before = events.capacity_verdicts.value(verdict="degrade", workload="a")
        capacity.admit_ladder(self._ladder(), budget=600)
        assert events.capacity_verdicts.value(
            verdict="degrade", workload="a"
        ) == before + 1

    def test_injected_oom_lands_on_the_second_rung(self):
        faults.arm("capacity.admit", kind="oom", at=1)
        v = capacity.admit_ladder(self._ladder(), budget=10**9)
        assert v.verdict == "degrade" and v.chosen == "b"
        assert "injected" in v.detail

    def test_empty_ladder_rejected(self):
        with pytest.raises(ValueError, match="at least one"):
            capacity.admit_ladder([], budget=100)

    def test_verdict_to_dict_carries_chosen(self):
        v = capacity.admit_ladder(self._ladder(), budget=600)
        assert v.to_dict()["chosen"] == "b"


# --- admission ----------------------------------------------------------------


class TestAdmit:
    def test_fit_within_budget(self):
        v = capacity.admit(capacity.CapacityPlan("w", {"a": 10}), budget=100)
        assert v.verdict == "fit" and v.fits

    def test_degrade_when_degradable(self):
        v = capacity.admit(
            capacity.CapacityPlan("w", {"a": 1000}), budget=100, degradable=True
        )
        assert v.verdict == "degrade"

    def test_refuse_when_not_degradable(self):
        v = capacity.admit(capacity.CapacityPlan("w", {"a": 1000}), budget=100)
        assert v.verdict == "refuse"

    def test_verdicts_counted(self):
        before = events.capacity_verdicts.value(verdict="refuse", workload="w")
        capacity.admit(capacity.CapacityPlan("w", {"a": 1000}), budget=100)
        assert events.capacity_verdicts.value(
            verdict="refuse", workload="w"
        ) == before + 1

    def test_armed_oom_forces_over_budget_not_crash(self):
        faults.arm("capacity.admit", kind="oom", at=1)
        v = capacity.admit(
            capacity.CapacityPlan("w", {"a": 1}), budget=10**9, degradable=True
        )
        assert v.verdict == "degrade"
        assert "injected" in v.detail

    def test_armed_error_kind_still_propagates(self):
        # Only OOM converts to a verdict; other kinds are real failures.
        faults.arm("capacity.admit", kind="error", at=1)
        with pytest.raises(faults.FaultInjected):
            capacity.admit(capacity.CapacityPlan("w", {"a": 1}), budget=10**9)

    def test_capacity_exceeded_message_carries_pricing(self):
        v = capacity.admit(capacity.CapacityPlan("w", {"a": 1000}), budget=100)
        err = capacity.CapacityExceeded(v)
        assert "refused: capacity" in str(err)
        assert err.verdict.required_bytes == 1000

    def test_capacity_exceeded_is_retry_permanent(self):
        # A deterministic refusal must fail FAST through the pipeline's
        # stage retries — same contract as a real device OOM.
        v = capacity.admit(capacity.CapacityPlan("w", {"a": 1000}), budget=100)
        assert is_resource_exhausted(capacity.CapacityExceeded(v))
        assert not default_retry_predicate(capacity.CapacityExceeded(v))


# --- compiler cross-check -----------------------------------------------------


class TestCrossCheck:
    def test_cross_check_on_real_executable(self):
        import jax.numpy as jnp

        compiled = jax.jit(lambda x: x @ x.T).lower(
            jnp.zeros((64, 32), jnp.float32)
        ).compile()
        analysis = capacity.compiled_memory_bytes(compiled)
        if analysis is None:
            pytest.skip("backend exposes no memory_analysis")
        assert analysis["total"] >= 0
        record = capacity.cross_check(
            capacity.CapacityPlan("x", {"a": max(1, analysis["total"])}), compiled
        )
        assert record is None or record["ratio"] <= 2.0

    def test_cross_check_tolerates_garbage_handle(self):
        assert capacity.compiled_memory_bytes(object()) is None
        assert capacity.cross_check(capacity.CapacityPlan("x", {"a": 1}), object()) is None


# --- the OOM retry classification (satellite) ---------------------------------


class TestResourceExhaustedClassification:
    def test_injected_oom_is_resource_exhausted(self):
        exc = InjectedResourceExhausted("RESOURCE_EXHAUSTED: injected")
        assert is_resource_exhausted(exc)
        assert not default_retry_predicate(exc)

    def test_memoryerror_is_permanent(self):
        assert is_resource_exhausted(MemoryError("oom"))

    def test_xla_shaped_error_by_name_and_message(self):
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert is_resource_exhausted(
            XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1g")
        )
        assert not is_resource_exhausted(XlaRuntimeError("INVALID_ARGUMENT"))

    def test_ordinary_errors_stay_retryable(self):
        assert default_retry_predicate(OSError("flaky disk"))
        assert default_retry_predicate(RuntimeError("transient"))

    def test_retry_call_fails_fast_on_oom_by_default(self):
        calls = []

        def attempt():
            calls.append(1)
            raise InjectedResourceExhausted("RESOURCE_EXHAUSTED: boom")

        with pytest.raises(InjectedResourceExhausted):
            retry_call(
                attempt, policy=RetryPolicy(max_attempts=5, jitter=False),
                sleeper=lambda s: None, site="t",
            )
        assert len(calls) == 1  # no backoff budget burned re-OOMing

    def test_retry_call_still_retries_transients(self):
        calls = []

        def attempt():
            calls.append(1)
            raise OSError("flaky")

        with pytest.raises(RetriesExhausted):
            retry_call(
                attempt, policy=RetryPolicy(max_attempts=3, jitter=False),
                sleeper=lambda s: None, site="t",
            )
        assert len(calls) == 3

    def test_oom_fault_kind_fires_and_counts(self):
        faults.arm("x.site", kind="oom", at=1)
        with pytest.raises(InjectedResourceExhausted) as ei:
            faults.hit("x.site")
        assert "RESOURCE_EXHAUSTED" in str(ei.value)
        assert faults.FAULTS.fired("x.site") == 1

    def test_oom_kind_parses_from_env(self):
        reg = faults.FaultRegistry(env="a.b:oom@2")
        reg.hit("a.b")
        with pytest.raises(InjectedResourceExhausted):
            reg.hit("a.b")


# --- end to end: admission drives the estimator -------------------------------


class TestEstimatorAdmission:
    def test_admission_fit_verdict_on_roomy_budget(self, monkeypatch):
        from albedo_tpu.datasets.synthetic import synthetic_stars
        from albedo_tpu.models.als import ImplicitALS

        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "4g")
        m = synthetic_stars(n_users=60, n_items=40, mean_stars=5, seed=0)
        assert ImplicitALS(rank=8, batch_size=16).admission(m).verdict == "fit"

    def test_admission_refuses_when_even_chunked_is_over(self, monkeypatch):
        from albedo_tpu.datasets.synthetic import synthetic_stars
        from albedo_tpu.models.als import ImplicitALS

        m = synthetic_stars(n_users=60, n_items=40, mean_stars=5, seed=0)
        est = ImplicitALS(rank=8, batch_size=16)
        chunked = est.capacity_plan(m, chunked=True)
        monkeypatch.setenv(
            "ALBEDO_DEVICE_MEM_BYTES", str(chunked.required_bytes // 2)
        )
        with pytest.raises(capacity.CapacityExceeded):
            est.admission(m)

    def test_fit_report_records_verdict(self, monkeypatch):
        from albedo_tpu.datasets.synthetic import synthetic_stars
        from albedo_tpu.models.als import ImplicitALS

        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", "4g")
        m = synthetic_stars(n_users=60, n_items=40, mean_stars=5, seed=0)
        est = ImplicitALS(rank=8, max_iter=1, batch_size=16)
        est.fit(m)
        assert est.last_fit_report["mode"] == "resident"
        assert est.last_fit_report["capacity"]["verdict"] == "fit"
        assert np.isfinite(est.last_fit_report["health"]["rms"])
        # The compiler cross-check rode along (None only when the backend
        # exposes no memory_analysis).
        cross = est.last_fit_report["capacity_cross_check"]
        assert cross is None or cross["compiled_bytes"] > 0
