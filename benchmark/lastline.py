"""The result line: built, checked against the contract, and only then
printed — last, alone, after everything else has been flushed.

The driver reads the LAST line of standard output as one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
on a traced run, optionally ``breakdown``). A run whose line would not meet
that says which field is at fault on standard error and exits non-zero
without printing a line at all.
"""

from __future__ import annotations

import json
import math
import sys

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class LineError(ValueError):
    pass


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def validate_line(line: dict, expected_metrics: list[dict], traced: bool) -> None:
    """Raise :class:`LineError` naming the first field that breaks the
    contract. ``expected_metrics`` are the manifest's entries for this cell
    in this mode (name and unit)."""
    if not isinstance(line, dict):
        raise LineError("the line is not a JSON object")
    for key in REQUIRED:
        if key not in line:
            raise LineError(f"key {key!r} is missing")
    if not isinstance(line["correct"], bool):
        raise LineError("'correct' is not true or false")
    for key in ("attempted", "failed"):
        if not (isinstance(line[key], int) and not isinstance(line[key], bool) and line[key] >= 0):
            raise LineError(f"{key!r} is not a count")
    if line["failed"] > line["attempted"]:
        raise LineError("'failed' is more than 'attempted'")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        raise LineError("'metrics' is not an object")
    want = {m["name"]: m["unit"] for m in expected_metrics}
    for name, unit in want.items():
        if name not in metrics:
            raise LineError(f"metric {name!r} is missing")
        entry = metrics[name]
        if not (isinstance(entry, dict) and set(entry) == {"value", "unit"}):
            raise LineError(f"metric {name!r} is not {{value, unit}}")
        if not _number(entry["value"]):
            raise LineError(f"metric {name!r} has no finite number as its value")
        if entry["unit"] != unit:
            raise LineError(f"metric {name!r} has unit {entry['unit']!r}, not {unit!r}")
        if unit == "%" and ("roofline" in name or "mfu" in name) and not 0 < entry["value"] <= 105:
            raise LineError(f"share {name!r} reads {entry['value']}, outside (0, 105]")
    for name in metrics:
        if name not in want:
            raise LineError(f"metric {name!r} is not one of this cell's in this mode")
    device = line["device"]
    if not isinstance(device, dict):
        raise LineError("'device' is not an object")
    for key in DEVICE_KEYS:
        if key not in device:
            raise LineError(f"device key {key!r} is missing")
    if not (isinstance(device["platform"], str) and isinstance(device["kind"], str)):
        raise LineError("device platform and kind are not strings")
    if not (isinstance(device["count"], int) and device["count"] >= 1):
        raise LineError("device count is not a positive whole number")
    if not (_number(device["memory_peak_bytes"]) and device["memory_peak_bytes"] > 0):
        raise LineError("device memory_peak_bytes is not a positive number")
    if traced:
        for key in ("busy_s", "window_s"):
            if not _number(device.get(key)):
                raise LineError(f"device key {key!r} is missing or not a number")
        if not 0 < device["busy_s"] <= device["window_s"]:
            raise LineError(
                f"busy_s {device['busy_s']} is not above 0 and at most window_s {device['window_s']}"
            )
        if "breakdown" in line:
            bd = line["breakdown"]
            if not (isinstance(bd, dict) and set(bd) <= {"device_ops", "idle_gaps"}):
                raise LineError("'breakdown' has keys other than device_ops and idle_gaps")
            for key, rows in bd.items():
                if not (isinstance(rows, list) and len(rows) <= 10):
                    raise LineError(f"breakdown {key!r} is not a list of at most 10")
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str) and _number(row[1])):
                        raise LineError(f"breakdown {key!r} has an entry that is not [name, seconds]")
    elif "breakdown" in line:
        raise LineError("'breakdown' belongs to a traced run only")


def build_line(*, correct, attempted, failed, metrics, device, compared,
               breakdown=None, extra=None) -> dict:
    """The object in the order it is printed: the contract's keys, any
    further keys the driver ignores, and the numbers compared LAST."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v["value"], "unit": v["unit"]} for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line["extra"] = extra
    line["compared"] = compared
    return line


def parse_last_line(stdout: str) -> dict:
    """What the driver does: the last line of standard output, as JSON.
    Text after the object is not a result line."""
    lines = stdout.rstrip("\n").split("\n")
    try:
        return json.loads(lines[-1])
    except (json.JSONDecodeError, IndexError) as e:
        raise LineError(f"the last line is not JSON: {e}") from e


def emit(line: dict, expected_metrics: list[dict], traced: bool, compared_text: str) -> None:
    """Validate, then print: the numbers compared as the last lines of
    standard error, the object as the last line of standard output."""
    validate_line(line, expected_metrics, traced)
    text = json.dumps(line, allow_nan=False)
    sys.stdout.flush()
    sys.stderr.write(compared_text.rstrip("\n") + "\n")
    sys.stderr.flush()
    sys.stdout.write(text + "\n")
    sys.stdout.flush()
