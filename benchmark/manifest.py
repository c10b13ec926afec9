"""The benchmark's manifest: ``BENCHMARK.json`` plus the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the NAME the manifest
gives it — a later PR adds a cell by adding files and manifest entries, never
by editing a file that is here:

- configuration  -> the ``file`` its ``configs`` entry names (``configs/<name>.json``)
- traffic mix    -> ``traffic/<traffic>.json``; its ``driver`` key picks
                    ``drivers/<driver>.py``
- per-layer metric -> ``readers/<name>.py`` with a ``read(ctx)`` function
- plain reference -> ``reference/<config's "reference" key>.py``
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class ManifestError(ValueError):
    pass


def load_manifest(path: Path | None = None) -> dict:
    path = ROOT / "BENCHMARK.json" if path is None else Path(path)
    return json.loads(path.read_text())


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str) -> dict:
    """The configuration's file, as it is run."""
    return json.loads((ROOT / _by_name(manifest["configs"], name, "config")["file"]).read_text())


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` from the benchmark's own directory by file
    path (metric names may hold dots, so this is not a dotted import)."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        raise ManifestError(f"{kind}/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_for(manifest: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics this cell's last line must carry in this mode: the
    end-to-end ones on ``--trace 0``, the per-layer ones on ``--trace 1``.

    A metric with a ``workloads`` key belongs to the cells it lists. One
    without belongs to every cell (end-to-end) or to every cell that reports
    the end-to-end metric it ``moves`` (per-layer)."""
    e2e = [
        m for m in manifest["end_to_end"]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in manifest["per_layer"]
        if (cell_name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]


def resolve_cell(manifest: dict, cell_name: str) -> dict:
    """The cell with its configuration and traffic files read in."""
    cell = _by_name(manifest["workloads"], cell_name, "workload")
    config = load_config(manifest, cell["config"])
    traffic_path = HERE / "traffic" / f"{cell['traffic']}.json"
    if not traffic_path.exists():
        raise ManifestError(f"traffic/{cell['traffic']}.json does not exist")
    traffic = json.loads(traffic_path.read_text())
    return {
        "name": cell_name,
        "chips": int(cell["chips"]),
        "config_name": cell["config"],
        "config": config,
        "traffic_name": cell["traffic"],
        "traffic": traffic,
    }
