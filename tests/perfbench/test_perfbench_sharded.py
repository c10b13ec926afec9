"""The ``fit-sharded`` cell on the CPU, four of the suite's eight virtual
devices standing for the chips: the whole command at a tiny size in both
modes, the driver's refusal of a program with no plan of its assembled
bytes, the run that must fail when the fit leaves the row-sharded resident
path or gathers more than each table once a sweep, the control and a fault that must read not
correct, each new reader against a fixture, and the files the new names
resolve to."""

import copy

import numpy as np
import pytest

from bench_helpers import last_json, run_command
from benchmark import lastline, manifest, streamed_check

CELL = "tiny-sharded-r16.fit-sharded"
REAL_CELL = "gh10m-r128-x4.fit-sharded"
ARGS = ("--workload", CELL, "--seed", "3000000019", "--seconds", "0.3")
NEW_METRICS = ("shard_fit_mfu", "shard_fit_roofline", "shard_assemble_roofline",
               "shard_assemble_ms", "shard_assembled_tables", "shard_dispatch_ms")


@pytest.fixture(autouse=True)
def _two_compile_threads(monkeypatch):
    """The sharded fit acquires its shapes on as many threads as the box has
    cores; under the suite's parallel workers two are load enough."""
    monkeypatch.setenv("ALBEDO_BUCKET_WORKERS", "2")


def tiny_manifest() -> dict:
    mf = copy.deepcopy(manifest.load_manifest())
    mf["configs"].append({
        "name": "tiny-sharded-r16", "source": "tests", "reduced": [], "why": "CPU tests",
        "file": "tests/perfbench/data/tiny-sharded-r16.json",
    })
    mf["workloads"].append({
        "name": CELL, "config": "tiny-sharded-r16", "traffic": "fit-sharded", "chips": 4,
        "why": "CPU tests",
    })
    for m in mf["per_layer"] + mf["end_to_end"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return mf


def steer(monkeypatch) -> dict:
    """The tiny manifest, past the look for a chip."""
    from benchmark import device

    mf = tiny_manifest()
    monkeypatch.setattr(manifest, "load_manifest", lambda path=None: mf)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setattr(device, "require_chips", lambda chips: device.describe_devices())
    monkeypatch.setattr(device, "memory_peak_bytes", lambda chips: 4096)
    return mf


# ------------------------------------------------------ the whole command

def test_untraced_run_is_row_sharded_and_prints_a_valid_line(monkeypatch, capsys):
    mf = steer(monkeypatch)
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc == 0, err[-3000:]
    line = lastline.parse_last_line(out)
    lastline.validate_line(line, manifest.metrics_for(mf, CELL, False), False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"fit_sweep_ms", "setup_s"}
    extra = line["extra"]
    assert (extra["mode"], extra["shard_mode"], extra["n_shards"]) == ("sharded", "allgather", 4)
    config = manifest.load_config(mf, "tiny-sharded-r16")
    # 602 and 401 rows: padded to the four shards, each table assembled once
    assert extra["assembled_bytes_per_sweep"] == (604 + 404) * config["rank"] * 4
    assert extra["compiles_in_window"] == 0 and extra["dispatches"] > 0
    assert len(extra["memory_peak_bytes_by_chip"]) == 4 and extra["planned_bytes"] > 0
    assert len(out.rstrip("\n").split("\n")) == 1
    assert "0 compilations inside it" in err and "fit.shard.assemble" in err
    assert "plan against peak" in err and "not published" not in err


def test_traced_run_reports_the_new_metrics_beside_the_old(monkeypatch, capsys):
    """The CPU has no device plane, so the reduced trace and its phases are
    made up; everything else is the real command."""
    from benchmark import device, phases, trace as trace_mod

    mf = steer(monkeypatch)
    # a chip's name, for the published peaks the shares divide by
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: dict(device.describe_devices(), kind="TPU v5 lite"))
    monkeypatch.setattr(trace_mod, "reduce_planes", lambda planes, chips: {
        "busy_s": 0.004, "window_s": 0.01,
        "programs": {"jit_als_sharded_local_solve(7)": 0.003, "jit_gramian(3)": 0.001},
        "device_ops": [["all fusion", 0.004]], "idle_gaps": [["bench_fit", 0.006]],
    })
    monkeypatch.setattr(phases, "fit_phases", lambda ctx: {
        "scopes": {"als.shard.assemble": 0.0005, "als.gather": 0.002, "als.cg": 0.001}})
    rc, out, err = run_command(capsys, *ARGS, "--trace", "1")
    assert rc == 0, err[-3000:]
    line = lastline.parse_last_line(out)
    expected = manifest.metrics_for(mf, CELL, True)
    lastline.validate_line(line, expected, True)
    assert set(NEW_METRICS) <= set(line["metrics"]) == {m["name"] for m in expected}
    assert not {"als_fit_mfu", "als_fit_roofline"} & set(line["metrics"])   # one chip's peaks
    assert all(line["metrics"][m]["value"] > 0 for m in NEW_METRICS)
    assert line["metrics"]["shard_assembled_tables"]["value"] == pytest.approx(1008 / 1003)


def test_the_driver_refuses_a_program_with_no_assembly_plan(monkeypatch, capsys):
    """The parent of the PR that brought the cell: its row-sharded fit
    assembles inside every bucket's program and has no such function. The
    run ends before a star is generated."""
    from albedo_tpu.parallel import als as parallel_als
    from benchmark import streamed_stars

    steer(monkeypatch)
    monkeypatch.setattr(streamed_stars, "generate_stars",
                        lambda *a, **k: pytest.fail("a star was generated"))
    monkeypatch.delattr(parallel_als, "assembled_bytes_per_sweep")
    with pytest.raises(SystemExit, match="no plan of assembled bytes a sweep"):
        run_command(capsys, *ARGS, "--trace", "0")
    assert capsys.readouterr().out == ""


def test_the_run_fails_when_a_sweep_gathers_more_than_each_table_once(monkeypatch, capsys):
    """The counter is read off the compiled programs the sweeps call: were an
    all-gather of a table back inside a group's program, the set-up fit would
    count it and the run would end before the window."""
    from albedo_tpu.parallel import als as parallel_als

    steer(monkeypatch)
    real = parallel_als.all_gather_bytes
    seen = []

    def with_a_table_in_every_group(compiled):
        seen.append(real(compiled))
        return seen[-1] or 404 * 16 * 4          # the item table again, where none is gathered

    parallel_als.sharded_fit_engine.cache_clear()    # no engine that has counted already
    monkeypatch.setattr(parallel_als, "all_gather_bytes", with_a_table_in_every_group)
    try:
        with pytest.raises(RuntimeError, match="row-sharded resident path.*bytes assembled a sweep"):
            run_command(capsys, *ARGS, "--trace", "0")
    finally:
        parallel_als.sharded_fit_engine.cache_clear()
    # the real count: each table in its assembly and in its relayout (once a
    # fit, outside the sweeps), and nothing in any other program
    assert sorted(b for b in seen if b) == [404 * 16 * 4] * 2 + [604 * 16 * 4] * 2
    assert capsys.readouterr().out == ""


def test_the_run_fails_when_the_fit_leaves_the_resident_path(monkeypatch, capsys):
    from benchmark.drivers import fit as fit_driver

    steer(monkeypatch)
    real = fit_driver.build_program
    monkeypatch.setattr(
        fit_driver, "build_program",
        lambda config, stars, seed, **kw: real(config, stars, seed, **dict(kw, sharded="streamed")))
    with pytest.raises(RuntimeError, match="row-sharded resident path"):
        run_command(capsys, *ARGS, "--trace", "0")
    assert capsys.readouterr().out == ""


def _line_with(monkeypatch, capsys, patch_fit):
    from albedo_tpu.models import als as als_mod

    steer(monkeypatch)
    real = als_mod.ImplicitALS.fit
    monkeypatch.setattr(als_mod.ImplicitALS, "fit",
                        lambda self, matrix, callback=None: patch_fit(real, self, matrix))
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc == 0, err[-3000:]
    return last_json(out)


def _reference_bf16(real, self, matrix):
    """The control: the reference in the program's place, in bfloat16, for
    the set-up fit whose factors the comparison reads."""
    import jax.numpy as jnp

    from albedo_tpu.models.als import ALSModel

    model = real(self, matrix)
    if getattr(self, "_control_done", False):
        return model
    self._control_done = True
    reference = manifest.load_module("reference", "als_cg")
    stars = {"rows": matrix.rows, "cols": matrix.cols, "vals": matrix.vals,
             "n_users": matrix.n_users, "n_items": matrix.n_items}
    config = {"rank": self.rank, "reg_param": self.reg_param, "alpha": self.alpha,
              "cg_steps": self.cg_steps}
    user, item = streamed_check.reference_fit(
        reference, stars, config, self.seed, self.max_iter, dtype=jnp.bfloat16)
    return ALSModel(user, item, self.rank)


def _unchanged(real, self, matrix):
    """A step that returns its state — the seeded init — unchanged."""
    sweeps, self.max_iter = self.max_iter, 0
    try:
        return real(self, matrix)
    finally:
        self.max_iter = sweeps


@pytest.mark.parametrize("broken", [_reference_bf16, _unchanged],
                         ids=["control_reference_bf16", "fault_state_unchanged"])
def test_the_control_and_a_fault_read_not_correct(monkeypatch, capsys, broken):
    line = _line_with(monkeypatch, capsys, broken)
    assert line["correct"] is False
    assert any(c["value"] > 3 * c["limit"] for c in line["compared"].values())


def test_the_control_script_reads_the_control_and_the_fault_on_one_device(monkeypatch, capsys):
    """``control_sharded.py`` without the program: one device is enough."""
    import json

    from benchmark import control_sharded, device

    steer(monkeypatch)
    asked = []
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: asked.append(chips) or device.describe_devices())
    assert control_sharded.main(["--workload", CELL, "--seeds", "5", "--min-stars", "4,8"]) == 0
    rows = [json.loads(row) for row in capsys.readouterr().out.strip().split("\n")]
    assert asked == [1] and rows[1]["seed"] == 5 and "program" not in rows[1]
    limits = manifest.load_config(tiny_manifest(), "tiny-sharded-r16")["check_limits"]
    for name in ("control_reference_bf16", "fault_unchanged"):
        assert set(rows[1][name]) == {"4", "8"} and set(rows[1][name]["4"]) == set(limits)
        assert any(rows[1][name]["4"][k] > limits[k] for k in limits), name


# ----------------------------------------------------------- the readers

FIXTURE_CONFIG = {"n_users": 10_000_000, "n_items": 1_000_000, "nnz": 100_000_000, "rank": 128,
                  "solver": "cg", "cg_steps": 3, "mesh_devices": 4}


def fixture_ctx(monkeypatch) -> dict:
    """Five sweeps of the real configuration's sizes in a made-up trace."""
    from benchmark import phases

    ctx = {
        "config": FIXTURE_CONFIG, "device_kind": "TPU v5 lite", "sweeps": 5,
        "traffic": {"trace_programs": ["als_sharded", "gramian"]},
        "trace": {"window_s": 4.0, "busy_s": 3.5,
                  "programs": {"jit_als_sharded_local_solve(1)": 3.0, "jit_als_sharded_assemble(2)": 0.4,
                               "jit_gramian(3)": 0.1, "jit_other(4)": 9.0}},
        "reports": [{"assembled_bytes_per_sweep": 11_000_000 * 512,
                     "spans": {"totals": {"fit.shard.dispatch": 0.25}, "counts": {}}}],
    }
    monkeypatch.setattr(phases, "fit_phases",
                        lambda c: {"scopes": {"als.shard.assemble": 0.4, "als.cg": 1.0}})
    return ctx


def test_each_new_reader_against_a_fixture(monkeypatch):
    ctx = fixture_ctx(monkeypatch)
    read = {name: manifest.load_module("readers", name).read(ctx) for name in NEW_METRICS}
    flops = 2 * 1e8 * (9 * 128 + 12 * 128) + 1.1e7 * (2 * 128**2 + 3 * (2 * 128**2 + 1280)) + 2 * 1.1e7 * 128**2
    assert read["shard_fit_mfu"] == pytest.approx(100 * flops * 5 / 4.0 / (4 * 197e12))
    least = (2 * 1e8 * (512 + 8) + 2 * 1.1e7 * 512) / 819e9 / 4        # bytes-bound, four chips
    assert read["shard_fit_roofline"] == pytest.approx(100 * least * 5 / 3.5)
    assert read["shard_assemble_ms"] == pytest.approx(80.0)
    into_a_chip = 1.1e7 * 512 * 3 / 4                                   # 4.224 GB a sweep
    assert into_a_chip == pytest.approx(4.224e9)
    assert read["shard_assemble_roofline"] == pytest.approx(100 * (into_a_chip / 200e9) / 0.080)
    assert read["shard_assembled_tables"] == pytest.approx(1.0)
    assert read["shard_dispatch_ms"] == pytest.approx(50.0)
    assert all(0 < read[name] <= 100 for name in NEW_METRICS if name.endswith(("mfu", "roofline")))
    # a program that assembles inside every bucket's program (1,455 buckets, CG)
    ctx["reports"][0]["assembled_bytes_per_sweep"] = 1455 * 11_000_000 * 512
    assert manifest.load_module("readers", "shard_assembled_tables").read(ctx) == pytest.approx(1455.0)


def test_the_readers_give_nothing_for_a_program_without_the_spans_scopes_and_counters(monkeypatch):
    """The parent's fits publish none of them, and no trace means no share."""
    from benchmark import phases

    monkeypatch.setattr(phases, "fit_phases", lambda c: {"scopes": {"als.cg": 1.0}})
    ctx = {"config": FIXTURE_CONFIG, "device_kind": "TPU v5 lite", "sweeps": 5,
           "traffic": {"trace_programs": ["als_sharded"]},
           "trace": {"window_s": 4.0, "busy_s": 3.5, "programs": {"jit_als_chunked(1)": 3.0}},
           "reports": [{"spans": {"totals": {"fit": 1.0}, "counts": {"fit": 1}}}]}
    for name in ("shard_fit_roofline", "shard_assemble_roofline", "shard_assemble_ms",
                 "shard_assembled_tables", "shard_dispatch_ms"):
        assert manifest.load_module("readers", name).read(ctx) is None, name
    for name in NEW_METRICS:
        assert manifest.load_module("readers", name).read(dict(ctx, trace=None, reports=[])) is None
    with pytest.raises(KeyError, match="no published ICI rate"):
        from benchmark.peaks_ici import ici_for

        ici_for("TPU v9")


# --------------------------------------------------------- the manifest

def test_every_new_name_resolves_to_a_file():
    mf = manifest.load_manifest()
    cell = manifest.resolve_cell(mf, REAL_CELL)
    config = cell["config"]
    assert cell["chips"] == config["mesh_devices"] == 4
    assert cell["traffic"]["driver"] == config["driver"] == "fit_sharded"
    assert (config["sharded"], config["shard_mode"]) == ("resident", "allgather")
    for kind, name in (("drivers", "fit_sharded"), ("reference", config["reference"]),
                       *(("readers", m) for m in NEW_METRICS)):
        assert (manifest.HERE / kind / f"{name}.py").exists(), (kind, name)
    assert cell["traffic"]["trace_programs"] == ["als_sharded", "gramian"]
    traced = {m["name"] for m in manifest.metrics_for(mf, REAL_CELL, True)}
    assert traced == {"prep_bucket_s", "prep_upload_s", "fit_compile_s", "fit_device_ms",
                      "device_idle.fit", *NEW_METRICS}
    assert {m["name"] for m in manifest.metrics_for(mf, REAL_CELL, False)} == {"fit_sweep_ms", "setup_s"}
    entry = next(c for c in mf["configs"] if c["name"] == "gh10m-r128-x4")
    assert entry["reduced"] == config["reduced"] == ["nnz", "max_iter"]
    assert entry["source"] == config["source"] and config["architecture"] is None
    # the matrix is gh10m-r128's: the same generator parameters, so the same stars a seed
    other = manifest.load_config(mf, "gh10m-r128")
    for key in ("n_users", "n_items", "nnz", "rank", "solver", "cg_steps", "max_iter", "reg_param",
                "alpha", "user_degrees", "item_degrees", "values", "check_min_stars", "reference",
                "assumed"):
        assert config[key] == other[key], key
    assert set(config["check_limits"]) == set(other["check_limits"])
    layers = {m["name"]: m["layer"] for m in mf["per_layer"] if m["name"] in NEW_METRICS}
    assert layers == {"shard_fit_mfu": "whole step", "shard_fit_roofline": "kernels",
                      "shard_assemble_roofline": "kernels", "shard_assemble_ms": "kernels",
                      "shard_assembled_tables": "mesh", "shard_dispatch_ms": "mesh"}
    for m in mf["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "fit_sweep_ms" and m["workloads"] == [REAL_CELL]
    # one cell of four on four chips: what it measures exists only across chips
    assert [w["name"] for w in mf["workloads"] if w["chips"] == 4] == [REAL_CELL]
    assert np.isclose(manifest.load_module("readers", "shard_assembled_tables").table_bytes(config),
                      5.632e9)
