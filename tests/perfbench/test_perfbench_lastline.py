"""The result line's validator: a good line passes, and each way the line of
PR 22's traced run could have been wrong is refused by name."""

import copy
import json

import pytest

from benchmark import lastline

E2E = [{"name": "fit_sweep_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "als_fit_roofline", "unit": "%"}, {"name": "fit_device_ms", "unit": "ms"}]


def good(traced: bool) -> dict:
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 5 * 10**9}
    if traced:
        device.update(busy_s=9.5, window_s=10.0)
        metrics = {"als_fit_roofline": {"value": 3.2, "unit": "%"},
                   "fit_device_ms": {"value": 901.5, "unit": "ms"}}
    else:
        metrics = {"fit_sweep_ms": {"value": 900.1, "unit": "ms"},
                   "setup_s": {"value": 71.0, "unit": "s"}}
    return lastline.build_line(
        correct=True, attempted=3, failed=0, metrics=metrics, device=device,
        compared={"user_rows_worst": {"value": 1e-3, "limit": 1e-2}},
        breakdown={"device_ops": [["fusion.1", 4.0]], "idle_gaps": [["bench_fit", 0.5]]} if traced else None,
    )


@pytest.mark.parametrize("traced", [False, True])
def test_a_good_line_passes_and_compared_comes_last(traced):
    line = good(traced)
    lastline.validate_line(line, LAYER if traced else E2E, traced)
    assert list(line)[-1] == "compared"
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    json.dumps(line, allow_nan=False)


def _drop(path):
    def change(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        del node[path[-1]]
    return change


def _set(path, value):
    def change(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return change


BAD_TRACED = {
    "a_missing_metric": (_drop(["metrics", "als_fit_roofline"]), "als_fit_roofline"),
    "a_metric_that_is_a_bare_number": (_set(["metrics", "fit_device_ms"], 901.5), "fit_device_ms"),
    "a_metric_with_a_third_key": (_set(["metrics", "fit_device_ms"], {"value": 1.0, "unit": "ms", "n": 3}), "fit_device_ms"),
    "a_metric_that_is_nan": (_set(["metrics", "fit_device_ms", "value"], float("nan")), "fit_device_ms"),
    "a_metric_with_another_unit": (_set(["metrics", "fit_device_ms", "unit"], "s"), "unit"),
    "an_end_to_end_metric_in_a_traced_line": (_set(["metrics", "setup_s"], {"value": 1.0, "unit": "s"}), "setup_s"),
    "busy_s_of_nought": (_set(["device", "busy_s"], 0.0), "busy_s"),
    "busy_s_over_window_s": (_set(["device", "busy_s"], 10.5), "busy_s"),
    "a_missing_busy_s": (_drop(["device", "busy_s"]), "busy_s"),
    "a_missing_window_s": (_drop(["device", "window_s"]), "window_s"),
    "a_missing_device_key": (_drop(["device", "memory_peak_bytes"]), "memory_peak_bytes"),
    "a_missing_device": (_drop(["device"]), "device"),
    "a_missing_correct": (_drop(["correct"]), "correct"),
    "correct_as_a_string": (_set(["correct"], "true"), "correct"),
    "more_failed_than_attempted": (_set(["failed"], 4), "failed"),
    "a_roofline_share_over_105": (_set(["metrics", "als_fit_roofline", "value"], 140.0), "als_fit_roofline"),
    "a_roofline_share_of_nought": (_set(["metrics", "als_fit_roofline", "value"], 0.0), "als_fit_roofline"),
    "a_breakdown_of_eleven": (_set(["breakdown", "device_ops"], [["op", 1.0]] * 11), "breakdown"),
    "a_breakdown_entry_without_seconds": (_set(["breakdown", "idle_gaps"], [["host"]]), "breakdown"),
}


@pytest.mark.parametrize("case", sorted(BAD_TRACED))
def test_a_traced_line_is_refused_for(case):
    change, names = BAD_TRACED[case]
    line = copy.deepcopy(good(True))
    change(line)
    with pytest.raises(lastline.LineError, match=names):
        lastline.validate_line(line, LAYER, True)


def test_an_untraced_line_may_not_carry_a_breakdown():
    line = good(False)
    line["breakdown"] = {"device_ops": [], "idle_gaps": []}
    with pytest.raises(lastline.LineError, match="breakdown"):
        lastline.validate_line(line, E2E, False)


def test_text_after_the_line_is_no_result_line():
    text = json.dumps(good(False))
    assert lastline.parse_last_line("log\n" + text + "\n")["correct"] is True
    with pytest.raises(lastline.LineError):
        lastline.parse_last_line(text + "\nprofiler: wrote trace\n")
    with pytest.raises(lastline.LineError):
        lastline.parse_last_line(text + " trailing")


def test_emit_prints_nothing_when_the_line_is_wrong(capsys):
    line = good(True)
    line["device"]["busy_s"] = 0.0
    with pytest.raises(lastline.LineError):
        lastline.emit(line, LAYER, True, "compared x: 1 (limit 2)")
    assert capsys.readouterr().out == ""


def test_emit_puts_the_object_last_on_stdout_and_the_numbers_last_on_stderr(capsys):
    print("earlier output")
    lastline.emit(good(False), E2E, False, "compared user_rows_worst: 0.001 (limit 0.01)\ncorrect: True")
    out = capsys.readouterr()
    assert lastline.parse_last_line(out.out)["metrics"]["setup_s"]["unit"] == "s"
    assert out.err.rstrip().split("\n")[-1] == "correct: True"
