"""Shared by the benchmark's tests: a manifest with a tiny cell added, and
the steering that lets the command run on the CPU (in the test, never as a
switch of the command)."""

from __future__ import annotations

import copy
import json

TINY_CELL = "tiny-r16.fit"
CUT_FROM = "albedo-r50.fit"


def tiny_manifest(real: dict) -> dict:
    mf = copy.deepcopy(real)
    mf["configs"].append({
        "name": "tiny-r16", "source": "tests", "reduced": [], "why": "CPU tests",
        "file": "tests/perfbench/data/tiny-r16.json",
    })
    mf["workloads"].append({
        "name": TINY_CELL, "config": "tiny-r16", "traffic": "fit", "chips": 1, "why": "CPU tests",
    })
    for m in mf["per_layer"] + mf["end_to_end"]:
        if CUT_FROM in m.get("workloads", []):   # the metrics of the cell it is cut from
            m["workloads"].append(TINY_CELL)
    return mf


def steer_onto_cpu(monkeypatch, with_chip_check: bool = False):
    """Point the command at the tiny manifest and (unless asked not to) past
    its look for a chip. Returns the manifest."""
    from benchmark import device, manifest

    mf = tiny_manifest(manifest.load_manifest())
    monkeypatch.setattr(manifest, "load_manifest", lambda path=None: mf)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("TPU_STDERR_LOG_LEVEL", "3")
    if not with_chip_check:
        monkeypatch.setattr(device, "require_chips", lambda chips: device.describe_devices())
        monkeypatch.setattr(device, "memory_peak_bytes", lambda chips: 4096)
    return mf


def run_command(capsys, *argv) -> tuple[int, str, str]:
    from benchmark.run import main

    rc = main(list(argv))
    out = capsys.readouterr()
    return rc, out.out, out.err


def last_json(stdout: str) -> dict:
    return json.loads(stdout.rstrip("\n").split("\n")[-1])
