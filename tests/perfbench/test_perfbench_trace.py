"""The reduction from a trace to busy time, program time and idle gaps."""

import json
from pathlib import Path

import pytest

from benchmark import trace

FIXTURE = Path(trace.__file__).parent / "fixtures" / "v5e_fit_piece.json"


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(0, 2), (1, 3), (3, 4), (6, 7)]) == [(0, 4), (6, 7)]
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4


def test_clip_drops_what_lies_outside_the_window():
    assert trace.clip([(-1, 1), (2, 3), (9, 12), (20, 30)], 0, 10) == [(0, 1), (2, 3), (9, 10)]


def test_gaps_are_what_the_busy_list_leaves_of_the_window():
    assert trace.gaps([(1, 2), (4, 9)], 0, 10) == [(0, 1), (2, 4), (9, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def planes(ops, modules=(), host=(), steps=()):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": list(host)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "Steps", "events": list(steps)},
        ]},
    ]


def test_overlapping_lines_and_nested_ops_are_not_counted_twice():
    """A program event, a step event and a fusion with its nested child all
    cover the same 4 seconds: busy is 4, not 12 or 16."""
    reduced = trace.reduce_planes(planes(
        ops=[("fusion.1", 1.0, 5.0), ("fusion.1/child", 2.0, 3.0), ("copy.2", 7.0, 8.0)],
        modules=[("jit_als_init_fit_fused(123)", 1.0, 8.0)],
        steps=[("step 0", 0.0, 10.0)],
        host=[("bench_window", 0.0, 10.0), ("bench_fit", 0.5, 9.5)],
    ), chips=1)
    assert reduced["busy_s"] == pytest.approx(5.0)
    assert reduced["window_s"] == pytest.approx(10.0)
    assert reduced["programs"] == {"jit_als_init_fit_fused(123)": pytest.approx(7.0)}
    ops = dict(map(tuple, reduced["device_ops"]))      # self time: the child's second is its own
    assert ops["fusion.1"] == pytest.approx(3.0) and ops["fusion.1/child"] == pytest.approx(1.0)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])
    idle = dict(map(tuple, reduced["idle_gaps"]))
    # a gap goes whole to the innermost host span over its midpoint
    assert idle == {"bench_fit": pytest.approx(1.0 + 2.0 + 2.0)}


def test_events_outside_the_window_are_clipped():
    reduced = trace.reduce_planes(planes(
        ops=[("warmup", -5.0, -1.0), ("fusion", 8.0, 14.0)],
        modules=[("prog", 8.0, 14.0)],
        host=[("bench_window", 0.0, 10.0)],
    ), chips=1)
    assert dict(map(tuple, reduced["idle_gaps"])) == {"host: no span": pytest.approx(8.0)}
    assert reduced["busy_s"] == pytest.approx(2.0)
    assert reduced["programs"]["prog"] == pytest.approx(2.0)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(80.0)


def test_busy_is_averaged_over_the_chips_used():
    two = planes(ops=[("f", 0.0, 4.0)], host=[("bench_window", 0.0, 10.0)])
    two.append({"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [("f", 0.0, 2.0)]}]})
    assert trace.reduce_planes(two, chips=2)["busy_s"] == pytest.approx(3.0)
    assert trace.reduce_planes(two, chips=1)["busy_s"] == pytest.approx(4.0)


def test_a_trace_without_a_device_plane_or_window_is_refused():
    with pytest.raises(ValueError, match="/device:TPU"):
        trace.reduce_planes([{"name": "/host:CPU", "lines": []}], chips=1)
    with pytest.raises(ValueError, match="bench_window"):
        trace.reduce_planes(planes(ops=[("f", 0.0, 1.0)]), chips=1)


def test_readers_return_nothing_without_a_trace_and_never_nought():
    from benchmark.manifest import load_manifest, load_module

    ctx = {"trace": None, "sweeps": 10, "reports": [], "first_report": None}
    for m in load_manifest()["per_layer"]:
        assert load_module("readers", m["name"]).read(ctx) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace piece in the tree")
def test_the_recorded_v5e_piece_reduces_to_its_pinned_numbers():
    piece = json.loads(FIXTURE.read_text())
    reduced = trace.reduce_planes(piece["planes"], chips=1)
    want = piece["pinned"]
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    for name, seconds in want["programs"].items():
        assert reduced["programs"][name] == pytest.approx(seconds, rel=1e-9)
    # the sum over every line of the device plane would overshoot the union
    summed = sum(e - s for p in piece["planes"] if p["name"].startswith("/device:")
                 for ln in p["lines"] for _, s, e in ln["events"])
    assert summed > reduced["busy_s"]
    ops_sum = sum(e - s for ln in piece["planes"][1]["lines"] if ln["name"] == "XLA Ops"
                  for _, s, e in ln["events"])
    assert ops_sum == pytest.approx(want["ops_line_sum_s"]) and ops_sum > 1.2 * reduced["busy_s"]
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(want["idle_pct"], rel=1e-9)
    assert 0 < want["idle_pct"] < 100
    assert reduced["idle_gaps"] == want["idle_gaps"]


def test_short_names_keep_the_instruction_its_kind_and_its_shape():
    hlo = ("%fusion.4379 = f32[2085288,128]{1,0:T(8,128)} fusion(f32[162541,128]{1,0:T(8,128)} "
           "%copy-done.2, s32[2085888]{0:T(1024)S(1)} %pad), kind=kCustom, calls=%fused_computation.15")
    assert trace.short_name(hlo) == "%fusion.4379 fusion:kCustom f32[2085288,128]"
    assert trace.short_name("%while.110 = (s32[]{:T(128)}, f32[8,128]{1,0}) while((s32[]) %t), "
                            "condition=%c, body=%b") == "%while.110 while tuple"
    assert trace.short_name("jit_call(5747181062628815938)") == "jit_call(5747181062628815938)"


def test_self_seconds_add_up_to_the_union():
    events = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("a/inner", 2.0, 3.0), ("b", 5.0, 9.0), ("lone", 12.0, 13.0)]
    own = trace.self_seconds(events)
    assert own == {"while": pytest.approx(3.0), "a": pytest.approx(2.0), "a/inner": pytest.approx(1.0),
                   "b": pytest.approx(4.0), "lone": pytest.approx(1.0)}
    assert sum(own.values()) == pytest.approx(trace.union_seconds([(s, e) for _, s, e in events]))
