"""The readers that came with PR 37 (``benchmark/span_reads.py`` and the
files under ``readers/`` that sit on it): device idle seconds under one of
the program's host spans, the exact solve's three sub-scopes, the streamed
cell's scopes through the swept reduction, the counters, the set-up fit's
span - each on a hand-made ``.xplane.pb`` or context as
``test_perfbench_phases.py`` builds them - and the entries ``BENCHMARK.json``
has of them, found by name: nothing here holds the manifest to an order or a
length, so a later PR appends its entries without an edit to this file."""

import pytest

from benchmark import manifest, phases, span_reads, streamed_phases
from benchmark.manifest import load_module
from test_perfbench_phases import ns, xspace

RESIDENT = ("ml25m-r128.fit", "albedo-r50.fit")
MF = manifest.load_manifest()

CALL = "jit(f)/call_exported/jit(f)/"
OP_NAMES = {
    "%fusion.1 = gather": CALL + "als.gather/gather:",
    "%fusion.2 = cg": CALL + "als.cg/als.cg.matvec/dot_general:",
    "%fusion.3 = scatter": CALL + "als.chunk.scatter/als.landing/scatter:",
    "%fusion.4 = build": CALL + "als.cholesky/als.cholesky.build/dot_general:",
    "%while.5 = factor": CALL + "als.cholesky/als.cholesky.factor/while:",
    "%while.6 = solve": CALL + "als.cholesky/als.cholesky.solve/while:",
    "%all-gather.7 = assemble": CALL + "als.shard.assemble/all_gather:",
}


def write_trace(tmp_path, monkeypatch, ops, modules, host, op_names=OP_NAMES):
    path = tmp_path / ".bench-trace" / "cell" / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(xspace(ops=ns(ops), modules=ns(modules), host=ns(host), op_names=op_names))
    monkeypatch.setattr(phases, "ROOT", tmp_path)
    phases.phases_of.cache_clear()
    span_reads._swept_phases.cache_clear()


def read(name, ctx):
    return load_module("readers", name).read(ctx)


# ----------------------------------------------- idle under a span (mesh)

# two half-sweeps of a row-sharded fit, 0..20 s: the device starts 2 s into
# ``fit.init``, waits 1 s for the first group's call, and 0.5 s at the fit's end
SHARDED_TRACE = dict(
    ops=[("%fusion.1 = gather", 3.0, 6.0), ("%all-gather.7 = assemble", 6.0, 7.0),
         ("%fusion.2 = cg", 8.0, 12.0), ("%fusion.1 = gather", 12.0, 15.0), ("%fusion.2 = cg", 15.0, 18.5)],
    modules=[("jit_als_sharded_local_solve(5)", 3.0, 18.5)],
    host=[("bench_window", 0.0, 20.0), ("bench_fit", 0.2, 19.8), ("albedo.fit", 0.5, 19.5),
          ("albedo.fit.init", 1.0, 4.0), ("albedo.fit.shard", 4.0, 10.0), ("albedo.fit.shard", 10.0, 16.0),
          ("albedo.fit.shard.assemble", 4.5, 6.5), ("albedo.fit.shard.dispatch", 6.5, 9.0),
          ("albedo.fit.shard.assemble", 10.5, 11.0), ("albedo.fit.shard.dispatch", 11.0, 15.5),
          ("albedo.fit.wait", 16.0, 19.4)],
)
SHARDED_CTX = {"trace": {"window_s": 20.0, "busy_s": 14.5}, "sweeps": 1,
               "traffic": {"driver": "fit_sharded", "trace_programs": ["als_sharded", "gramian"]}}
IDLE_READERS = {"idle_fit_init_ms": "fit.init", "idle_shard_assemble_ms": "fit.shard.assemble",
                "idle_shard_dispatch_ms": "fit.shard.dispatch"}


def test_idle_under_a_span_adds_up_with_the_other_labels_to_the_windows_idle(tmp_path, monkeypatch, capsys):
    write_trace(tmp_path, monkeypatch, **SHARDED_TRACE)
    got = {name: read(name, SHARDED_CTX) for name in IDLE_READERS}
    # gaps 0-3 (midpoint in fit.init), 7-8 (fit.shard.dispatch), 18.5-20 (fit.wait)
    assert got["idle_fit_init_ms"] == pytest.approx(3000.0)
    assert got["idle_shard_dispatch_ms"] == pytest.approx(1000.0)
    # a span that ran with no gap under it reads nought, not nothing
    assert got["idle_shard_assemble_ms"] == 0.0
    reduced = span_reads.window_phases(SHARDED_CTX)
    assert reduced["idle"] == {"albedo.fit.init": pytest.approx(3.0), "albedo.fit.wait": pytest.approx(1.5),
                               "albedo.fit.shard.dispatch": pytest.approx(1.0)}
    listed = sum(v for v in got.values()) / 1000.0
    others = sum(s for label, s in reduced["idle"].items()
                 if label.removeprefix("albedo.") not in IDLE_READERS.values())
    trace = SHARDED_CTX["trace"]
    assert listed + others == pytest.approx(trace["window_s"] - trace["busy_s"])   # device_idle.fit x window
    # the same reduction serves the accepted readers: one table logged, one file read
    assert read("shard_assemble_ms", SHARDED_CTX) == pytest.approx(1000.0)
    assert capsys.readouterr().err.count("phases: fit program") == 1


def test_idle_readers_give_nothing_without_the_programs_spans_or_without_the_trace(tmp_path, monkeypatch):
    no_spans = [ev for ev in SHARDED_TRACE["host"] if not ev[0].startswith("albedo.")]
    write_trace(tmp_path, monkeypatch, **dict(SHARDED_TRACE, host=no_spans))
    for name in IDLE_READERS:
        assert read(name, SHARDED_CTX) is None, name                   # a program without spans
        assert read(name, dict(SHARDED_CTX, trace=None)) is None       # an untraced run
        assert read(name, dict(SHARDED_CTX, trace={"window_s": 19.0})) is None   # another window's file
        assert read(name, dict(SHARDED_CTX, sweeps=0)) is None


# --------------------------------------------- the exact solve's sub-scopes

EXACT_TRACE = dict(
    ops=[("%while.9 = while", 2.0, 18.0), ("%fusion.1 = gather", 2.0, 4.0), ("%fusion.4 = build", 4.0, 9.0),
         ("%while.5 = factor", 9.0, 12.0), ("%while.6 = solve", 12.0, 13.0), ("%fusion.1 = gather", 13.0, 18.0)],
    modules=[("jit_als_init_fit_fused(7)", 2.0, 18.0)],
    host=[("bench_window", 0.0, 20.0), ("bench_fit", 0.5, 19.5), ("albedo.fit", 1.0, 19.0)],
)
EXACT_CTX = {"trace": {"window_s": 20.0, "busy_s": 16.0}, "sweeps": 4,
             "traffic": {"driver": "fit", "trace_programs": ["als_init_fit_fused", "jit_call"]}}
CHOL_READERS = ("chol_build_ms", "chol_factor_ms", "chol_solve_ms")


def test_the_three_sub_scopes_add_up_to_fit_chol_ms(tmp_path, monkeypatch):
    write_trace(tmp_path, monkeypatch, **EXACT_TRACE)
    got = [read(name, EXACT_CTX) for name in CHOL_READERS]
    assert got == [pytest.approx(1250.0), pytest.approx(750.0), pytest.approx(250.0)]
    assert sum(got) == pytest.approx(read("fit_chol_ms", EXACT_CTX))
    assert read("fit_gather_ms", EXACT_CTX) == pytest.approx(1750.0)


def test_an_executable_with_the_outer_scope_alone_reads_nothing_and_never_nought(tmp_path, monkeypatch):
    """XLA's persistent cache ignores op metadata: a program compiled from a
    tree without the sub-scopes carries that tree's names."""
    older = {k: v.replace("als.cholesky.build/", "").replace("als.cholesky.factor/", "")
             .replace("als.cholesky.solve/", "") for k, v in OP_NAMES.items()}
    write_trace(tmp_path, monkeypatch, **dict(EXACT_TRACE, op_names=older))
    assert read("fit_chol_ms", EXACT_CTX) == pytest.approx(2250.0)
    for name in CHOL_READERS:
        assert read(name, EXACT_CTX) is None


# ------------------------------------- the streamed cell: one swept reduction

STREAMED_TRACE = dict(
    ops=[("%fusion.1 = gather", 2.0, 4.0), ("%fusion.2 = cg", 4.0, 5.0), ("%fusion.3 = scatter", 5.0, 5.5),
         ("%fusion.1 = gather", 6.5, 8.5), ("%fusion.2 = cg", 8.5, 9.5), ("%fusion.3 = scatter", 9.5, 10.0)],
    modules=[("jit_als_chunked(3)", 2.0, 5.5), ("jit_als_chunked(4)", 6.5, 10.0)],
    host=[("bench_window", 0.0, 12.0), ("bench_fit", 0.2, 11.8), ("albedo.fit", 0.5, 11.5),
          ("albedo.fit.stream", 1.0, 10.5), ("albedo.fit.stream.upload", 1.0, 1.8),
          ("albedo.fit.stream.dispatch", 1.8, 2.5), ("albedo.fit.stream.upload", 2.5, 6.2),
          ("albedo.fit.stream.dispatch", 6.2, 6.6), ("albedo.fit.wait", 10.5, 11.4)],
)
STREAMED_CTX = {"trace": {"window_s": 12.0, "busy_s": 7.0}, "sweeps": 2,
                "traffic": {"driver": "fit_streamed", "trace_programs": ["als_chunked"]}}
STREAM_READERS = ("stream_gather_ms", "stream_cg_ms", "stream_land_ms",
                  "idle_stream_upload_ms", "idle_stream_dispatch_ms")


def test_the_streamed_readers_share_one_swept_reduction_and_never_the_searching_one(tmp_path, monkeypatch):
    write_trace(tmp_path, monkeypatch, **STREAMED_TRACE)
    monkeypatch.setattr(phases, "fit_phases", lambda ctx: pytest.fail("labelled every gap by a search"))
    monkeypatch.setattr(phases, "reduce_phases", counted := Counted(phases.reduce_phases))
    monkeypatch.setattr(streamed_phases, "idle_by_span", swept := Counted(streamed_phases.idle_by_span))
    got = {name: read(name, STREAMED_CTX) for name in STREAM_READERS}
    assert got == {
        "stream_gather_ms": pytest.approx(2000.0), "stream_cg_ms": pytest.approx(1000.0),
        "stream_land_ms": pytest.approx(500.0),
        # gaps 0-2 (midpoint 1.0: the first upload), 5.5-6.5 (6.0: the second), 10-12 (11.0: fit.wait)
        "idle_stream_upload_ms": pytest.approx(1500.0), "idle_stream_dispatch_ms": 0.0,
    }
    assert counted.calls == 1 and swept.calls == 1               # five readers, one reduction
    reduced = span_reads.window_phases(STREAMED_CTX, swept=True)
    assert sum(reduced["idle"].values()) == pytest.approx(12.0 - 7.0)
    # the window only over the gap-free part of the reduction: the searching one sees no span
    assert read("stream_gather_ms", dict(STREAMED_CTX, trace={"window_s": 11.0})) is None
    assert read("stream_gather_ms", dict(STREAMED_CTX, trace=None)) is None


class Counted:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.fn(*args, **kwargs)


def test_a_fused_fit_has_no_scatter_scope_and_reads_nothing_there(tmp_path, monkeypatch):
    write_trace(tmp_path, monkeypatch, **EXACT_TRACE)
    assert read("stream_land_ms", EXACT_CTX) is None


# ----------------------------------------- counters and the set-up fit's span

def test_a_counter_is_the_first_window_reports_key_and_nought_is_a_reading():
    ctx = {"reports": [{"cg_gram_entry_share": 0.8478, "exact_lane_share": 0.0, "merged_entry_share": 0.0123},
                       {"cg_gram_entry_share": 0.5}]}
    assert read("cg_gram_entry_share", ctx) == 0.8478
    assert read("exact_lane_share", ctx) == 0.0                  # under CG: a reading, not an absence
    assert read("merged_entry_share", ctx) == 0.0123
    for name in ("cg_gram_entry_share", "exact_lane_share", "merged_entry_share"):
        assert read(name, {"reports": [{"mode": "resident"}]}) is None    # a program without the counter
        assert read(name, {"reports": []}) is None and read(name, {}) is None


def test_setup_fit_s_is_the_set_up_fits_whole_span():
    first = {"spans": {"totals": {"fit": 25.59, "fit.prep": 7.455}, "counts": {"fit": 1}}}
    assert read("setup_fit_s", {"first_report": first, "reports": [{"spans": {"totals": {"fit": 4.8}}}]}) == 25.59
    assert read("setup_fit_s", {"first_report": {"compile_s": 9.0}}) is None
    assert read("setup_fit_s", {}) is None


# ----------------------------------------------------------- the manifest

LISTED = {   # the entries that came with PR 37: unit, better, source, layer, moves
    "fit_gather_ms": ("ms", "lower", "device_trace", "kernels", "fit_sweep_ms"),
    "fit_cg_ms": ("ms", "lower", "device_trace", "kernels", "fit_sweep_ms"),
    "fit_rest_ms": ("ms", "lower", "device_trace", "kernels", "fit_sweep_ms"),
    "fit_host_ms": ("ms", "lower", "program_span", "model", "fit_sweep_ms"),
    "setup_fit_s": ("s", "lower", "program_span", "model", "setup_s"),
    "fit_admission_s": ("s", "lower", "program_span", "admission", "setup_s"),
    "prep_index_s": ("s", "lower", "program_span", "host prep", "setup_s"),
    "fit_probe_s": ("s", "lower", "program_span", "executable acquisition", "setup_s"),
    "cg_gram_entry_share": ("x", "higher", "program_counter", "kernels", "fit_sweep_ms"),
}
# readers whose cells' lists the accepted tests hold name for name, so that no
# entry lists them until a ``benchmark`` PR does (PERF.md section 7 item 1)
UNLISTED = {"stream_gather_ms", "stream_cg_ms", "stream_land_ms", "idle_stream_upload_ms",
            "idle_stream_dispatch_ms", "merged_entry_share", "idle_shard_dispatch_ms", "idle_shard_assemble_ms",
            "idle_fit_init_ms", "chol_build_ms", "chol_factor_ms", "chol_solve_ms", "exact_lane_share"}


@pytest.mark.parametrize("name", list(LISTED))
def test_an_entry_of_the_programs_own_spans_scopes_and_counter(name):
    entry = next(m for m in MF["per_layer"] if m["name"] == name)
    assert tuple(entry[k] for k in ("unit", "better", "source", "layer", "moves")) == LISTED[name]
    assert set(RESIDENT) <= set(entry["workloads"])
    assert callable(load_module("readers", name).read)


@pytest.mark.parametrize("cell", RESIDENT)
def test_the_resident_cg_cells_report_them_in_a_traced_run_only(cell):
    assert set(LISTED) <= {m["name"] for m in manifest.metrics_for(MF, cell, True)}
    assert not set(LISTED) & {m["name"] for m in manifest.metrics_for(MF, cell, False)}


def test_every_entry_has_a_reader_file_and_every_reader_file_an_entry_or_waits_for_one():
    listed = {m["name"] for m in MF["per_layer"]}
    files = {p.stem for p in (manifest.HERE / "readers").glob("*.py")}
    assert listed <= files and files - listed <= UNLISTED
    for name in UNLISTED:
        assert callable(load_module("readers", name).read)
