"""The reduction from a traced fit to its phases (``benchmark/phases.py``):
the wire reader of the event metadata, the scope attribution, and the seven
readers that sit on it and on ``last_fit_report["spans"]``."""

import json
from pathlib import Path

import pytest

from benchmark import phases
from benchmark.manifest import load_module

FIXTURE = Path(phases.__file__).parent / "fixtures" / "v5e_fit_scopes_piece.json"
FIT = "jit_als_init_fit_fused(77)"
PREFIX = "jit(als_init_fit_fused)/call_exported/jit(als_init_fit_fused)/while/body/"
DEVICE_READERS = ("fit_gather_ms", "fit_cg_ms", "fit_rest_ms")
SPAN_READERS = ("fit_host_ms", "fit_admission_s", "prep_index_s", "fit_probe_s")


# ------------------------------------------- a serialized XSpace, by hand

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def xspace(ops, modules, host, op_names, ref_stat_for=()) -> bytes:
    """One device plane (operations and programs lines, event metadata with
    the op name as the ``tf_op`` stat) and one host plane. Events are
    ``(name, start_ns, duration_ns)``."""
    def plane(name, lines, with_stats):
        names = sorted({ev[0] for _, events in lines for ev in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        # stat metadata: 1 = a stat the reader must skip, 2 = tf_op, 3.. = values held by reference
        stat_meta = {1: "hlo_category", 2: "tf_op"}
        body = field(2, name)
        for line_name, events in lines:
            evs = b"".join(field(4, field(1, ids[n]) + field(2, s * 1000) + field(3, d * 1000)
                                 + field(4, field(1, 1) + field(3, 7)))
                           for n, s, d in events)
            body += field(3, field(2, line_name) + evs)
        for n, i in ids.items():
            meta = field(1, i) + field(2, n)
            if with_stats and n in op_names:
                meta += field(5, field(1, 1) + field(5, "loop fusion"))
                if n in ref_stat_for:
                    stat_meta[len(stat_meta) + 1] = op_names[n]
                    meta += field(5, field(1, 2) + field(7, len(stat_meta)))
                else:
                    meta += field(5, field(1, 2) + field(5, op_names[n]))
            body += field(4, field(1, i) + field(2, meta))
        for i, n in stat_meta.items():
            body += field(5, field(1, i) + field(2, field(1, i) + field(2, n)))
        return field(1, body)

    return (plane("/host:CPU", [("python3", host)], False)
            + plane("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)], True))


OP_NAMES = {
    "%fusion.1 = gather": PREFIX + "closed_call/als.gather/gather:",
    "%fusion.2 = matvec": PREFIX + "closed_call/als.cg/als.cg.matvec/blk,bk->bl/dot_general:",
    "%fusion.3 = update": PREFIX + "closed_call/als.cg/als.cg.update/add:",
    "%copy-done.4 = copy": "jit(als_init_fit_fused)/call_exported/jit(als_init_fit_fused)/while:",
    "%while.5 = while": "jit(als_init_fit_fused)/call_exported/jit(als_init_fit_fused)/while:",
}


def test_the_wire_reader_finds_the_op_name_in_the_event_metadata():
    raw = xspace(
        ops=[("%fusion.1 = gather", 10, 5), ("%fusion.2 = matvec", 20, 5), ("%bare.9 = none", 30, 1)],
        modules=[(FIT, 10, 30)], host=[("bench_window", 0, 50)],
        op_names=OP_NAMES, ref_stat_for=("%fusion.2 = matvec",),
    )
    got = phases.op_names_from_xspace(raw)
    assert got["%fusion.1 = gather"] == OP_NAMES["%fusion.1 = gather"]      # held as a string
    assert got["%fusion.2 = matvec"] == OP_NAMES["%fusion.2 = matvec"]      # held by reference
    assert "%bare.9 = none" not in got and "bench_window" not in got
    assert FIT not in got


@pytest.mark.parametrize("op_name, want", [
    (PREFIX + "closed_call/als.gather/gather:", ("als.gather",)),
    (PREFIX + "closed_call/als.cg/als.cg.matvec/blk,bl->bk/dot_general:", ("als.cg", "als.cg.matvec")),
    ("jit(f)/jit(main)/als.init/mul:", ("als.init",)),
    ("jit(f)/jit(main)/while/body/dynamic_slice:", ()),
    ("jit(f)/jit(main)/signals.gather/gather:", ()),     # a part must START with als.
    ("", ()),
    (None, ()),
])
def test_scope_path_is_the_als_parts_outermost_first(op_name, want):
    assert phases.scope_path(op_name) == want


def planes(ops, modules, host, device="/device:TPU:0"):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": list(host)}]},
        {"name": device, "lines": [{"name": "XLA Ops", "events": list(ops)},
                                   {"name": "XLA Modules", "events": list(modules)}]},
    ]


NESTED = dict(
    # a while of 8 s holding 2 + 3 + 1 s of scoped work and 1 s of a copy; an
    # unnamed op; an op of another program; an op outside the window
    ops=[("%while.5 = while", 2.0, 10.0), ("%fusion.1 = gather", 2.5, 4.5),
         ("%fusion.2 = matvec", 5.0, 8.0), ("%fusion.3 = update", 8.0, 9.0),
         ("%copy-done.4 = copy", 9.0, 10.0), ("%bare.9 = none", 10.0, 11.0),
         ("%fusion.1 = gather", 13.0, 14.0), ("%fusion.2 = matvec", 19.0, 25.0)],
    modules=[(FIT, 2.0, 11.0), ("jit__health(3)", 13.0, 14.0), (FIT, 19.0, 25.0)],
    host=[("bench_window", 0.0, 20.0), ("bench_fit", 0.5, 19.5), ("albedo.fit", 1.0, 19.0),
          ("albedo.fit.dispatch", 1.0, 2.5), ("albedo.fit.wait", 2.5, 18.5),
          ("tfrt: something", 0.0, 20.0)],
)


def test_self_time_goes_to_the_outermost_scope_inside_the_program_and_the_window():
    got = phases.reduce_phases(planes(**NESTED), OP_NAMES, ["als_init_fit_fused"])
    assert got["window_s"] == pytest.approx(20.0) and got["chips"] == 1
    assert got["program_s"] == pytest.approx(9.0 + 1.0)          # the second run clipped at 20
    assert got["scopes"] == {
        "als.cg": pytest.approx(3.0 + 1.0 + 1.0),                # matvec + update + the clipped second run
        "als.gather": pytest.approx(2.0),                        # not the other program's second
        phases.UNSCOPED: pytest.approx(1.0 + 1.0 + 1.0),         # copy, the bare op, the while's own second
    }
    assert got["inner"]["als.cg.matvec"] == pytest.approx(4.0)
    assert got["inner"]["als.cg.update"] == pytest.approx(1.0)
    assert sum(got["scopes"].values()) == pytest.approx(got["program_s"])
    assert sum(got["inner"].values()) == pytest.approx(got["program_s"])
    assert [name for name, _, _ in got["spans"]] == [
        "bench_fit", "albedo.fit", "albedo.fit.dispatch", "albedo.fit.wait"]
    # gaps 0-2, 11-13, 14-19: each whole to the innermost span over its midpoint
    assert got["idle"] == {"albedo.fit.wait": pytest.approx(2.0 + 5.0),
                           "albedo.fit.dispatch": pytest.approx(2.0)}


def test_two_chips_are_averaged_and_a_chip_without_the_program_is_not_counted():
    two = planes(**NESTED)
    two.append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("%fusion.1 = gather", 2.0, 6.0)]},
        {"name": "XLA Modules", "events": [(FIT, 2.0, 6.0)]}]})
    two.append({"name": "/device:TPU:2", "lines": [
        {"name": "XLA Ops", "events": [("%fusion.1 = gather", 2.0, 6.0)]},
        {"name": "XLA Modules", "events": [("jit__health(3)", 2.0, 6.0)]}]})
    got = phases.reduce_phases(two, OP_NAMES, ["als_init_fit_fused"])
    assert got["chips"] == 2
    assert got["program_s"] == pytest.approx((10.0 + 4.0) / 2)
    assert got["scopes"]["als.gather"] == pytest.approx((2.0 + 4.0) / 2)
    assert sum(got["scopes"].values()) == pytest.approx(got["program_s"])


def test_no_window_or_no_such_program_gives_nothing():
    no_window = planes(**dict(NESTED, host=[("bench_fit", 0.0, 20.0)]))
    assert phases.reduce_phases(no_window, OP_NAMES, ["als_init_fit_fused"]) is None
    assert phases.reduce_phases(planes(**NESTED), OP_NAMES, ["als_fit_step"]) is None


def test_a_program_without_scopes_is_all_unscoped():
    got = phases.reduce_phases(planes(**NESTED), {}, ["als_init_fit_fused"])
    assert got["scopes"] == {phases.UNSCOPED: pytest.approx(got["program_s"])}


# ------------------------------------------------- readers, from a file

def ns(events):
    return [(n, int(s * 1e9), int((e - s) * 1e9)) for n, s, e in events]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A trace file under a checkout's ``.bench-trace/`` whose reduction is
    ``NESTED``'s, and the context the driver hands a reader after it."""
    raw = xspace(ops=ns(NESTED["ops"]), modules=ns(NESTED["modules"]), host=ns(NESTED["host"]),
                 op_names=OP_NAMES)
    path = tmp_path / ".bench-trace" / "cell" / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    monkeypatch.setattr(phases, "ROOT", tmp_path)
    phases.phases_of.cache_clear()
    spans = {"totals": {"fit": 18.0, "fit.wait": 16.0, "fit.admission": 0.25, "fit.prep": 3.0,
                        "fit.prep.index": 1.5, "fit.acquire": 9.0, "fit.acquire.probe": 4.0},
             "counts": {}}
    return {
        "trace": {"window_s": 20.0, "busy_s": 12.0}, "sweeps": 4,
        "traffic": {"trace_programs": ["als_init_fit_fused", "jit_call"]},
        "first_report": {"compile_s": 9.0, "spans": spans},
        "reports": [{"device_s": 17.0, "spans": {"totals": {"fit": 18.0, "fit.wait": 16.0}}},
                    {"device_s": 17.0, "spans": {"totals": {"fit": 20.0, "fit.wait": 16.5}}}],
    }


def test_every_new_reader_reads_its_number_from_the_file_and_the_reports(traced, capsys):
    got = {name: load_module("readers", name).read(traced) for name in DEVICE_READERS + SPAN_READERS}
    assert got == {
        "fit_gather_ms": pytest.approx(1000 * 2.0 / 4),
        "fit_cg_ms": pytest.approx(1000 * 5.0 / 4),
        "fit_rest_ms": pytest.approx(1000 * 3.0 / 4),
        "fit_host_ms": pytest.approx(1000 * (2.0 + 3.5) / 2),
        "fit_admission_s": 0.25, "prep_index_s": 1.5, "fit_probe_s": 4.0,
    }
    # the three device metrics are the program's time, and the table is logged once
    assert sum(got[n] for n in DEVICE_READERS) == pytest.approx(1000 * 10.0 / 4)
    err = capsys.readouterr().err
    assert err.count("phases: fit program") == 1 and "als.cg.matvec" in err and "idle under" in err


def test_a_trace_that_is_not_the_window_the_driver_reduced_is_not_read(traced):
    stale = dict(traced, trace={"window_s": 19.0, "busy_s": 12.0})
    for name in DEVICE_READERS:
        assert load_module("readers", name).read(stale) is None


def test_nothing_is_returned_where_the_span_or_scope_is_absent(traced, tmp_path):
    # the parent of the PR that added them: reports without spans, a trace without scopes
    raw = xspace(ops=ns(NESTED["ops"]), modules=ns(NESTED["modules"]), host=ns(NESTED["host"]), op_names={})
    next(tmp_path.rglob("*.xplane.pb")).write_bytes(raw)
    phases.phases_of.cache_clear()
    parent = dict(traced, first_report={"compile_s": 9.0}, reports=[{"device_s": 17.0}])
    for name in DEVICE_READERS + SPAN_READERS:
        assert load_module("readers", name).read(parent) is None, name
    # a span that did not run (a warm layout cache has no admission) is absent, not nought
    warm = dict(traced, first_report={"spans": {"totals": {"fit": 1.0, "fit.admission": 0.0}}})
    assert load_module("readers", "fit_admission_s").read(warm) is None


def test_without_a_trace_in_the_context_no_file_is_looked_for(monkeypatch):
    monkeypatch.setattr(phases, "newest_xplane", lambda: pytest.fail("looked on the disk"))
    ctx = {"trace": None, "sweeps": 10, "reports": [], "first_report": None}
    for name in DEVICE_READERS + SPAN_READERS:
        assert load_module("readers", name).read(ctx) is None


def test_the_command_prints_the_tables_of_a_trace_already_written(traced, capsys):
    assert phases.main([]) == 0
    assert "als.gather" in capsys.readouterr().err
    assert phases.main([str(next(Path(phases.ROOT).rglob("*.xplane.pb")))]) == 0


# ------------------------------------------------ the recorded v5e piece

def test_the_recorded_v5e_piece_reduces_to_its_pinned_scopes():
    piece = json.loads(FIXTURE.read_text())
    got = phases.reduce_phases(piece["planes"], piece["op_names"], ["als_init_fit_fused"])
    want = piece["pinned"]
    assert got["program_s"] == pytest.approx(want["program_s"], rel=1e-9)
    for key in ("scopes", "inner", "idle"):
        assert got[key] == {k: pytest.approx(v, rel=1e-9, abs=1e-15) for k, v in want[key].items()}
    # the shapes the by-hand look found: every scope of the CG fit is there, the
    # compiler's own copies carry the while's name and no scope, and the scopes
    # add up to the operations' union inside the program (its idle is the rest)
    assert {"als.init", "als.gramian", "als.gather", "als.warm_start", "als.cg"} <= set(got["scopes"])
    assert {"als.cg.rhs", "als.cg.precond", "als.cg.matvec", "als.cg.update"} <= set(got["inner"])
    assert 0 < got["scopes"][phases.UNSCOPED] < 0.1 * got["program_s"]
    assert sum(got["scopes"].values()) <= got["program_s"]
    assert sum(got["scopes"].values()) == pytest.approx(got["program_s"], rel=2e-3)
    unscoped = [o for o in piece["op_names"].values() if not phases.scope_path(o)]
    assert sum(o.endswith("/while:") for o in unscoped) > 0.9 * len(unscoped) > 0
