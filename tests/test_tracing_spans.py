"""The one span mechanism (``Timer.section`` -> total + ``albedo.<name>``
trace span), the host spans of ``ImplicitALS.fit`` and the device scopes and
module name of the fused ALS program."""

import glob
import itertools
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from albedo_tpu.datasets.synthetic import synthetic_stars
from albedo_tpu.models.als import ImplicitALS
from albedo_tpu.ops.als import CG_GRAM_LEN_PER_RANK, als_init_fit_fused
from albedo_tpu.utils.aot import persistent_aot_executable, reset_memory_cache
from albedo_tpu.utils.profiling import SPAN_PREFIX, Timer

MS = 1e-3
SCOPES = {
    "cg": ("als.init", "als.gramian", "als.gather", "als.warm_start", "als.cg",
           "als.cg.rhs", "als.cg.precond", "als.cg.matvec", "als.cg.update", "als.landing"),
    "cholesky": ("als.init", "als.gramian", "als.gather", "als.cholesky", "als.landing"),
}
SCOPES["cg-long"] = SCOPES["cg"] + ("als.cg.gram",)
# rows cut to under CG_GRAM_LEN_PER_RANK ranks never build their Gramian; whole rows do
RANK = 4
MAX_LEN = {"cg": CG_GRAM_LEN_PER_RANK * RANK // 2, "cholesky": None, "cg-long": None}


@pytest.fixture(autouse=True)
def _fresh_process():
    """Each test has a cold export directory (conftest); give it a cold
    in-memory executable cache to match."""
    reset_memory_cache()


def stars(seed=41):
    return synthetic_stars(n_users=120, n_items=70, mean_stars=7, seed=seed)


def host_events(trace_dir) -> dict[str, list[float]]:
    """Seconds of every ``albedo.*`` host event of the one trace under
    ``trace_dir``, by name."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    found: dict[str, list[float]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    found.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    return found


def test_section_is_a_total_a_count_and_a_trace_span(tmp_path):
    timer = Timer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with timer.section("stage.part", sync=jnp.ones(4) * 2):
                pass
        with timer.section("stage"):
            pass
    finally:
        jax.profiler.stop_trace()
    snap = timer.snapshot()
    assert snap["counts"] == {"stage.part": 2, "stage": 1}
    assert set(snap["totals"]) == {"stage.part", "stage"} and min(snap["totals"].values()) > 0
    events = host_events(tmp_path)
    assert len(events["albedo.stage.part"]) == 2 and len(events["albedo.stage"]) == 1
    # the span and the total are one clock's reading of one block
    assert sum(events["albedo.stage.part"]) == pytest.approx(snap["totals"]["stage.part"], abs=MS)


def test_section_without_a_profiler_and_on_an_exception_still_counts():
    timer = Timer()
    with pytest.raises(RuntimeError):
        with timer.section("boom"):
            raise RuntimeError("inside")
    timer.add("elsewhere", 1.5)
    timer.add("elsewhere", 0.5)
    assert timer.counts == {"boom": 1, "elsewhere": 2}
    assert timer.totals["elsewhere"] == 2.0


def test_report_prints_parents_first_with_self_time_and_marks_threaded_children():
    timer = Timer()
    timer.absorb({"totals": {"fit": 10.0, "fit.prep": 4.0, "fit.prep.upload": 6.0, "fit.wait": 5.0,
                             "fit.gc": 3.0, "fit.acquire": 0.5},
                  "counts": {"fit": 1, "fit.prep": 1, "fit.prep.upload": 32, "fit.wait": 1, "fit.gc": 2}})
    timer.absorb({"totals": {"fit": 2.0, "fit.wait": 1.0}, "counts": {"fit": 1, "fit.wait": 1}})
    rows = []
    assert timer.report(rows.append) == timer.totals
    assert rows[0].split() == ["span", "total", "s", "calls", "self", "s"]
    body = [row.split() for row in rows[1:]]
    assert [r[0] for r in body] == ["fit", "fit.acquire", "fit.gc", "fit.prep", "fit.prep.upload", "fit.wait"]
    assert rows[1].startswith("fit ") and rows[2].startswith("  fit.acquire") and rows[5].startswith("    fit.prep.upload")
    by_name = {r[0]: r[1:] for r in body}
    # two fits absorbed; a missing count reads as one call; self = total less the direct children,
    # the collections apart (they lie inside the other spans and are counted there too)
    assert by_name["fit"] == ["12.000000", "2", f"{12.0 - 0.5 - 4.0 - 6.0:.6f}"]
    assert by_name["fit.acquire"][1] == "1" and by_name["fit.gc"] == ["3.000000", "2", "3.000000"]
    assert by_name["fit.wait"] == ["6.000000", "2", "6.000000"]
    # children summed over threads past their parent's wall-clock: nought, and marked
    assert by_name["fit.prep"] == ["4.000000", "1", "0.000000*"]


@pytest.fixture
def no_automatic_collections():
    """Only a ``gc.collect()`` the test makes is a full collection."""
    import gc

    gc.collect()
    gc.disable()
    yield
    gc.enable()


def test_collections_are_a_span_only_while_the_block_runs_and_only_full_ones(tmp_path, no_automatic_collections):
    import gc

    before = list(gc.callbacks)
    timer = Timer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timer.section("job"), timer.collections("job"):
            assert len(gc.callbacks) == len(before) + 1
            gc.collect(0)
            gc.collect(1)
            with timer.section("job.part"):
                gc.collect()
            gc.collect()
            assert "job.gc" not in timer.totals          # the hook takes no lock: added as the block ends
        gc.collect()                                     # after the block: nobody listens
        with pytest.raises(RuntimeError), timer.collections("job"):
            raise RuntimeError("inside")
    finally:
        jax.profiler.stop_trace()
    assert gc.callbacks == before
    # two full collections; the younger generations' returned at once
    assert timer.counts["job.gc"] == 2 and 0 < timer.totals["job.gc"] <= timer.totals["job"]
    events = host_events(tmp_path)
    assert len(events["albedo.job.gc"]) == 2
    assert sum(events["albedo.job.gc"]) == pytest.approx(timer.totals["job.gc"], abs=MS)


@pytest.mark.parametrize("path", ["resident", "chunked"])
def test_fit_gc_is_published_when_a_collection_ran_inside_the_fit_and_only_then(
        path, tmp_path, no_automatic_collections):
    import gc

    before = list(gc.callbacks)
    m = stars()
    als = ImplicitALS(rank=4, max_iter=2, seed=3, solver="cg", chunked=path == "chunked")
    als.fit(m)                                            # no collection: no span
    assert "fit.gc" not in als.last_fit_report["spans"]["totals"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        als.fit(m, callback=lambda *_: gc.collect())      # one a sweep
    finally:
        jax.profiler.stop_trace()
    totals, counts = als.last_fit_report["spans"]["totals"], als.last_fit_report["spans"]["counts"]
    assert counts["fit.gc"] == 2 and 0 < totals["fit.gc"] < totals["fit"]
    assert_children_within_parents(totals)
    events = host_events(tmp_path)
    assert len(events["albedo.fit.gc"]) == 2 and len(events["albedo.fit"]) == 1
    assert sum(events["albedo.fit.gc"]) == pytest.approx(totals["fit.gc"], abs=MS)

    def boom(*_):
        raise RuntimeError("inside the fit")

    with pytest.raises(RuntimeError, match="inside the fit"):
        als.fit(m, callback=boom)
    assert gc.callbacks == before                         # gone after a fit returns or raises


def test_train_als_logs_the_span_table_after_a_real_fit_and_not_on_an_artifact_hit(capsys):
    from albedo_tpu.cli import main

    assert main(["train_als", "--small", "--solver", "cg"]) == 0
    out, err = capsys.readouterr()
    assert "[train_als] NDCG@30" in out and "fit spans" not in out      # the table is standard error's
    table = [row for row in err.splitlines() if row.startswith("[train_als] ")]
    assert table[0] == "[train_als] fit spans of 1 fit(s): seconds, calls, self seconds"
    spans = [row.split()[1] for row in table[2:-1]]
    assert spans[0] == "fit" and {"fit.prep", "fit.acquire", "fit.dispatch", "fit.wait"} <= set(spans)
    assert spans == sorted(spans, key=lambda n: n.split("."))           # parents first
    counters = table[-1]
    assert counters.startswith("[train_als] fit counters: mode=resident, compile_source=compile")
    assert "cg_gram_entry_share=" in counters and "exact_lane_share=0.0" in counters
    assert main(["train_als", "--small", "--solver", "cg"]) == 0        # today's artifact: no fit
    out, err = capsys.readouterr()
    assert "[train_als] NDCG@30" in out and "fit spans" not in err and "fit counters" not in err


def assert_children_within_parents(
    totals: dict[str, float], threaded: tuple[str, ...] = ("fit.stream.acquire.",)
) -> None:
    for name, seconds in totals.items():
        parent = name.rpartition(".")[0]
        # summed over threads: the two sides' uploads; the chunked path's
        # acquisitions (and, through ``threaded``, the resident sharded path's)
        threads = name == "fit.prep.upload" or name.startswith(threaded)
        if parent and not threads:
            assert seconds <= totals[parent] + MS, (name, seconds, totals[parent])


@pytest.fixture
def counted_clock(monkeypatch):
    """A clock the test controls: every read of ``time.perf_counter``, from
    any thread, is 50 microseconds after the read before it. A span or a
    report key is then the number of reads between its two ends, so how a key
    relates to the span that refines it (each to ``MS``) says which reads it
    is made of and not how long a loaded machine kept a thread waiting between
    two of them."""
    reads = itertools.count()
    monkeypatch.setattr(time, "perf_counter", lambda: next(reads) * 50e-6)


def test_cold_fit_publishes_its_spans_beside_the_keys_they_refine(counted_clock):
    als = ImplicitALS(rank=4, max_iter=2, seed=3, solver="cg")
    als.fit(stars())
    report = als.last_fit_report
    totals, counts = report["spans"]["totals"], report["spans"]["counts"]
    assert report["compile_source"] == "compile"
    assert set(totals) - {"fit.gc"} == {     # (a full collection may fall into any fit)
        "fit", "fit.admission", "fit.prep", "fit.prep.index", "fit.prep.index.csr",
        "fit.prep.index.csc", "fit.prep.fill", "fit.prep.fill.user", "fit.prep.fill.item",
        "fit.prep.upload", "fit.acquire", "fit.acquire.export", "fit.acquire.lower_compile",
        "fit.acquire.serialize", "fit.acquire.probe", "fit.dispatch", "fit.wait", "fit.report",
    }
    assert counts["fit"] == counts["fit.wait"] == counts["fit.acquire.probe"] == 1
    assert_children_within_parents(totals)
    assert totals["fit.acquire"] == pytest.approx(report["compile_s"], abs=MS)
    assert totals["fit.admission"] + totals["fit.prep"] == pytest.approx(report["prep_s"], abs=MS)
    assert totals["fit.prep.upload"] == pytest.approx(report["upload_s"], abs=MS)
    assert totals["fit.prep.index"] <= report["bucket_s"] + MS
    whole = report["prep_s"] + report["compile_s"] + report["device_s"]
    assert whole - MS <= totals["fit"] <= whole + 100 * MS   # + the report's own making


def test_warm_start_in_a_fresh_process_spans_deserialize_compile_and_probe():
    ImplicitALS(rank=4, max_iter=2, seed=3, solver="cg").fit(stars())
    reset_memory_cache()                      # a second process: disk layer only
    als = ImplicitALS(rank=4, max_iter=2, seed=3, solver="cg")
    als.fit(stars())                          # a fresh matrix object: cold layout
    report = als.last_fit_report
    totals = report["spans"]["totals"]
    assert report["compile_source"] == "disk"
    acquire = {k for k in totals if k.startswith("fit.acquire.")}
    assert acquire == {"fit.acquire.deserialize", "fit.acquire.lower_compile", "fit.acquire.probe"}
    parts = sum(totals[k] for k in acquire)     # the branches are all but 2% of the acquisition
    assert 0.98 * report["compile_s"] <= parts <= report["compile_s"] + MS
    assert totals["fit.acquire"] == pytest.approx(report["compile_s"], abs=MS)
    assert_children_within_parents(totals)


def test_warm_fit_in_one_process_has_no_children_to_report():
    m = stars()
    als = ImplicitALS(rank=4, max_iter=2, seed=3, solver="cg")
    als.fit(m)
    als.fit(m)
    report = als.last_fit_report
    totals = report["spans"]["totals"]
    assert set(totals) - {"fit.gc"} == {"fit", "fit.prep", "fit.acquire", "fit.dispatch", "fit.wait", "fit.report"}
    assert report["compile_s"] == 0.0 and totals["fit.acquire"] < MS
    assert totals["fit"] - totals["fit.wait"] < 50 * MS   # what fit_host_ms reads


@pytest.mark.parametrize("kwargs", [
    pytest.param({"chunked": True}, id="chunked"),
    pytest.param({"sharded": "resident"}, id="sharded"),
])
def test_degraded_paths_publish_spans_from_the_clock_reads_they_make(kwargs):
    if "sharded" in kwargs:
        from albedo_tpu.parallel import make_mesh

        kwargs = dict(kwargs, mesh=make_mesh(8))
    als = ImplicitALS(rank=4, max_iter=1, seed=3, solver="cg", **kwargs)
    als.fit(stars())
    report = als.last_fit_report
    totals = report["spans"]["totals"]
    assert report["mode"] != "resident"
    assert {"fit", "fit.prep", "fit.acquire", "fit.wait"} <= set(totals)
    assert totals["fit.acquire"] == pytest.approx(report["compile_s"], abs=MS)
    assert totals["fit.prep"] <= report["prep_s"] + MS
    if report["mode"] == "sharded":
        # every shape's executable ahead of the first sweep, on threads
        assert_children_within_parents(totals, threaded=("fit.acquire.",))
    else:
        assert_children_within_parents(totals)


def fused_fit_text(case: str) -> tuple[str, str]:
    """Compiled text of ``als_init_fit_fused`` at a tiny shape, acquired
    through the AOT layer as ``fit`` acquires it, and the layer's source."""
    m = stars(seed=43)
    solver = case.partition("-")[0]
    als = ImplicitALS(rank=RANK, max_iter=2, seed=3, solver=solver, max_len=MAX_LEN[case])
    ug, ig, u_land, i_land = als.device_groups(m)
    args = (jax.random.PRNGKey(0), ug, ig, jnp.float32(0.5), jnp.float32(40.0), jnp.int32(2))
    compiled, _, source = persistent_aot_executable(
        als_init_fit_fused, args, dict(user_landing=u_land, item_landing=i_land),
        dict(n_users=m.n_users, n_items=m.n_items, rank=RANK, solver=solver, cg_steps=3,
             gather_dtype=None),
        key_parts=("test_tracing_spans", case), name="als_init_fit_fused",
    )
    return compiled.as_text(), source


@pytest.mark.parametrize("case", ["cg", "cholesky", "cg-long"])
@pytest.mark.parametrize("acquisition", ["fresh", "second_process"])
def test_compiled_fit_carries_every_scope_and_a_stable_module_name(case, acquisition):
    text, source = fused_fit_text(case)
    if acquisition == "second_process":
        reset_memory_cache()
        text, source = fused_fit_text(case)
        # either solver is the program's own HLO (no custom call): both round-trip
        assert source == "disk"
    else:
        assert source == "compile"
    assert re.search(r"^HloModule jit_als_init_fit_fused\b", text, re.M)
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES[case]:
        assert any(f"/{scope}/" in name for name in op_names), scope
    # sub-scopes nest inside their parent; the gather is not inside the solve
    assert any("/als.cg/als.cg.matvec/" in n for n in op_names) or case == "cholesky"
    # only a long row's CG builds its Gramian, and inside als.cg
    assert any("/als.cg/als.cg.gram/" in n for n in op_names) == (case == "cg-long")
    assert not any(re.search(r"(?<!/als\.cg)/als\.cg\.gram/", n) for n in op_names)
    assert not any(re.search(r"/als\.(cg|cholesky)/.*als\.gather", n) for n in op_names)


@pytest.mark.parametrize("solver", ["cg", "cholesky"])
def test_compiled_chunked_update_carries_the_landing_scope_beside_the_shared_ones(solver):
    """The chunked path's one program: ``jit_als_chunked`` in a trace, the
    shared bodies' scopes, and its own around the landing - since PR 38 one
    block write into the table held in dispatch order, under the scope names
    the row scatter had (``als.chunk.scatter`` > ``als.landing``), and the
    CG's warm start one slice of it under ``als.warm_start``."""
    from albedo_tpu.ops.als import chunked_bucket_update

    sds = jax.ShapeDtypeStruct
    args = (sds((30, RANK), jnp.float32), sds((RANK, RANK), jnp.float32), sds((20, RANK), jnp.float32),
            sds((6,), jnp.int32), sds((6, 16), jnp.int32), sds((6, 16), jnp.float32),
            sds((6, 16), jnp.bool_), sds((), jnp.float32), sds((), jnp.float32))
    compiled, _, source = persistent_aot_executable(
        chunked_bucket_update, args, None, dict(solver=solver, cg_steps=3, gather_dtype=None),
        key_parts=("test_tracing_spans", "chunked", solver), name="als_chunked",
        donate_argnums=(2,),
    )
    assert source == "compile"
    text = compiled.as_text()
    # (either solver is the program's own HLO: exported, so renamed)
    assert re.search(r"^HloModule jit_als_chunked\b", text, re.M)
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    wanted = ("als.gather", "als.chunk.scatter") + (
        ("als.cg", "als.cg.gram", "als.warm_start") if solver == "cg" else ("als.cholesky",))
    for scope in wanted:
        assert any(f"/{scope}/" in name for name in op_names), scope
    assert any(re.search(r"/als\.chunk\.scatter/als\.landing/dynamic_update_slice$", n) for n in op_names)
    assert not any(re.search(r"/als\.chunk\.scatter/.*als\.(gather|cg)", n) for n in op_names)
    # the warm start is a slice of the table, and only the CG reads one
    warm = [n for n in op_names if "/als.warm_start/" in n]
    assert bool(warm) == (solver == "cg")
    assert any(re.search(r"/als\.warm_start/dynamic_slice$", n) for n in warm) == (solver == "cg")
    assert not any(re.search(r"/als\.(warm_start|landing)/.*(gather|scatter)", n) for n in op_names)


@pytest.mark.parametrize("program", ["fused", "chunked"])
def test_grown_gather_keeps_its_scope_and_stays_outside_the_solve(program):
    """A bucket gathered at a grown slot count (``ops.als.gather_slots``):
    the index padding belongs to ``als.gather``, the padding of what the solve
    reads and the cut back to the bucket's own slots to ``als.cg``."""
    from albedo_tpu.ops.als import chunked_bucket_update, gather_slots

    if program == "fused":
        m = stars(seed=43)
        ug, ig, _, _ = ImplicitALS(rank=RANK, solver="cg").device_groups(m)
        assert any(gather_slots(*g[1].shape[-2:]) != g[1].shape[-2] for g in (*ug, *ig))
        text, _ = fused_fit_text("cg-long")      # whole rows, as device_groups above
    else:
        sds = jax.ShapeDtypeStruct
        assert gather_slots(128, 8) == 129
        args = (sds((30, RANK), jnp.float32), sds((RANK, RANK), jnp.float32), sds((200, RANK), jnp.float32),
                sds((128,), jnp.int32), sds((128, 8), jnp.int32), sds((128, 8), jnp.float32),
                sds((128, 8), jnp.bool_), sds((), jnp.float32), sds((), jnp.float32))
        compiled, _, _ = persistent_aot_executable(
            chunked_bucket_update, args, None, dict(solver="cg", cg_steps=3, gather_dtype=None),
            key_parts=("test_tracing_spans", "chunked-grown"), name="als_chunked", donate_argnums=(2,),
        )
        text = compiled.as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(re.search(r"/als\.gather/.*pad", n) for n in op_names)       # idx grows under the gather
    assert any(re.search(r"/als\.gather/gather", n) for n in op_names)
    assert any(re.search(r"/als\.cg/.*pad", n) for n in op_names)           # val, mask, x0 under the solve
    assert not any(re.search(r"/als\.(cg|cholesky)/.*als\.gather", n) for n in op_names)
    assert not any(re.search(r"/als\.gather/.*als\.cg", n) for n in op_names)


def test_scopes_do_not_change_what_the_fit_computes():
    """Scopes are metadata: the half-sweep under them equals the same
    arithmetic written without them, on the CPU's exact f32."""
    from albedo_tpu.ops.als import bucket_cg_body, gramian

    rng = np.random.default_rng(5)
    source = jnp.asarray(rng.normal(size=(30, 4)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 30, (6, 5)), jnp.int32)
    mask = jnp.asarray(rng.random((6, 5)) < 0.8)
    val = jnp.where(mask, 1.0, 0.0).astype(jnp.float32)
    x0 = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    reg, alpha = jnp.float32(0.5), jnp.float32(40.0)
    got = bucket_cg_body(source, gramian(source), idx, val, mask, x0, reg, alpha, 64)

    y, c1 = np.asarray(source, np.float64)[np.asarray(idx)], 40.0 * np.asarray(val, np.float64)
    yty = np.asarray(source, np.float64).T @ np.asarray(source, np.float64)
    n_b = np.asarray(mask).sum(1)
    for b in range(6):   # the normal equations, solved exactly
        a = yty + (y[b].T * c1[b]) @ y[b] + 0.5 * n_b[b] * np.eye(4)
        want = np.linalg.solve(a, y[b].T @ np.where(np.asarray(mask)[b], 1.0 + c1[b], 0.0))
        np.testing.assert_allclose(np.asarray(got)[b], want, rtol=2e-3, atol=2e-4)
