"""The ``fit-streamed`` cell on the CPU: the lean reference and comparison
against the originals, the whole command at a tiny size in both modes, the
run that must fail when the fit stays resident, the control and a fault that
must read not correct, and the files the new names resolve to."""

import copy

import numpy as np
import pytest

from bench_helpers import last_json, run_command
from benchmark import compare, lastline, manifest, streamed_check
from benchmark.stars import generate_stars

CELL = "tiny-streamed-r16.fit-streamed"
REAL_CELL = "gh10m-r128.fit-streamed"
ARGS = ("--workload", CELL, "--seed", "3000000019", "--seconds", "0.3")
NEW_METRICS = ("stream_upload_ms", "stream_dispatch_ms", "stream_upload_gbps")


@pytest.fixture(autouse=True)
def _two_compile_threads(monkeypatch):
    """The chunked fit acquires its shapes on as many threads as the box has
    cores; under the suite's parallel workers two are load enough."""
    monkeypatch.setenv("ALBEDO_BUCKET_WORKERS", "2")


def tiny_config() -> dict:
    return manifest.load_config(tiny_manifest(), "tiny-streamed-r16")


def tiny_manifest() -> dict:
    mf = copy.deepcopy(manifest.load_manifest())
    mf["configs"].append({
        "name": "tiny-streamed-r16", "source": "tests", "reduced": [], "why": "CPU tests",
        "file": "tests/perfbench/data/tiny-streamed-r16.json",
    })
    mf["workloads"].append({
        "name": CELL, "config": "tiny-streamed-r16", "traffic": "fit-streamed", "chips": 1,
        "why": "CPU tests",
    })
    for m in mf["per_layer"] + mf["end_to_end"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return mf


def steer(monkeypatch, degrade: bool = True) -> dict:
    """The tiny manifest, past the look for a chip and, unless asked not to,
    under a device budget between the tiny fit's resident and chunked plans:
    the override the capacity tests use, set by the test and never by the
    benchmark."""
    from albedo_tpu.models.als import ImplicitALS
    from albedo_tpu.utils import capacity
    from benchmark import device
    from benchmark.drivers.fit import build_program

    mf = tiny_manifest()
    monkeypatch.setattr(manifest, "load_manifest", lambda path=None: mf)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setattr(device, "require_chips", lambda chips: device.describe_devices())
    monkeypatch.setattr(device, "memory_peak_bytes", lambda chips: 4096)
    if degrade:
        config = tiny_config()
        est, matrix = build_program(config, generate_stars(config, 1), 1)
        assert isinstance(est, ImplicitALS)
        mid = (est.capacity_plan(matrix).required_bytes
               + est.capacity_plan(matrix, chunked=True).required_bytes) // 2
        monkeypatch.setenv("ALBEDO_DEVICE_MEM_BYTES", str(int(mid / capacity.headroom())))
    return mf


# ------------------------------------------------ the lean check's two halves

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_lean_reference_is_the_reference_bit_for_bit(dtype):
    import jax.numpy as jnp

    # a few widths are enough: every width is a compile, in both fits
    config = dict(tiny_config(), n_users=150, n_items=90, nnz=1500)
    config["user_degrees"] = dict(config["user_degrees"], max=40)
    config["item_degrees"] = dict(config["item_degrees"], max=60)
    stars = generate_stars(config, 11)
    reference = manifest.load_module("reference", "als_cg")
    want = reference.fit(stars, config, 7, 2, dtype=jnp.dtype(dtype))
    got = streamed_check.reference_fit(reference, stars, config, 7, 2, dtype=jnp.dtype(dtype))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # and it moved: two sweeps are not the init
    init = reference.init_factors(7, stars["n_users"], stars["n_items"], config["rank"])
    assert np.abs(got[0] - np.asarray(init[0])).max() > 0.01


@pytest.mark.parametrize("case", ["sound", "non_finite", "wrong_shape"])
def test_the_blocked_comparison_gives_compare_py_its_numbers(monkeypatch, case):
    monkeypatch.setattr(streamed_check, "BLOCK_ROWS", 37)
    config = tiny_config()
    stars = generate_stars(config, 5)
    rng = np.random.default_rng(3)
    want = (rng.normal(size=(600, 16)).astype(np.float32), rng.normal(size=(400, 16)).astype(np.float32))
    want[0][::9] *= 1e-6                      # rows that are all but zero
    got = tuple(w + rng.normal(scale=1e-3, size=w.shape).astype(np.float32) for w in want)
    if case == "non_finite":
        got[1][3, 2] = np.nan
    if case == "wrong_shape":
        got = (got[0][:-1], got[1])
    theirs = compare.compare_fit(*got, *want, stars, 4)
    ours = streamed_check.compare_fit(*got, *want, stars, 4)
    assert ours == theirs
    assert (case == "sound") == all(np.isfinite(v) for v in ours.values())


# ------------------------------------- the run's time outside the window

LAWS = {"lognormal": {"law": "lognormal", "sigma": 1.2, "min": 1, "max": 20000},
        "zipf": {"law": "zipf_mandelbrot", "offset": 300, "exponent": 1.8, "min": 1, "max": 50000},
        "floored": {"law": "lognormal", "sigma": 0.8, "min": 20, "max": 600}}


@pytest.mark.parametrize("n, total, law", [
    (1000, 12345, "lognormal"), (200_000, 2_000_000, "lognormal"), (777, 50_000, "zipf"),
    (50_000, 2_000_000, "zipf"), (10, 10, "lognormal"), (10, 200_000, "lognormal"),
    (3000, 90_000, "floored"),
])
def test_the_quick_degree_sequence_is_the_generators_value_for_value(n, total, law):
    from concurrent.futures import ThreadPoolExecutor

    from benchmark import stars, streamed_stars

    with ThreadPoolExecutor(max_workers=3) as pool:
        got = streamed_stars.degree_sequence(n, total, LAWS[law], pool, 3)
    want = stars.degree_sequence(n, total, LAWS[law])
    assert got.dtype == want.dtype and got.sum() == total
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed, workers", [(5, 1), (3000000019, 3)])
def test_the_quick_generator_makes_the_generators_matrix(seed, workers):
    from benchmark import streamed_stars

    config = dict(tiny_config(), n_users=4000, n_items=900, nnz=60_000)
    want = generate_stars(config, seed)
    got = streamed_stars.generate_stars(config, seed, workers=workers)
    assert got.keys() == want.keys()
    for key in ("rows", "cols", "vals"):
        assert got[key].dtype == want[key].dtype
        np.testing.assert_array_equal(got[key], want[key])
    assert (got["n_users"], got["n_items"]) == (want["n_users"], want["n_items"])


def _streamed_planes(dispatches: int, second_thread: bool) -> tuple[list[dict], dict]:
    """A window of per-bucket dispatches as the chunked fit leaves it: three
    host spans a bucket under one a half-sweep, a program with two operations
    that do not touch, idle before each program."""
    from benchmark import trace as trace_mod

    ops = {"%fusion.1 = f32[8,16] fusion(x), kind=kLoop": "jit(als_chunked)/als.gather/gather",
           "%fusion.2 = f32[8,16] fusion(y), kind=kOutput": "jit(als_chunked)/als.cg/als.cg.matvec/dot"}
    spans, device_ops, modules, t = [], [], [], 1.0
    for i in range(dispatches):
        spans += [("albedo.fit.stream.upload", t, t + 1.0), ("albedo.fit.stream.acquire", t + 1.0, t + 1.1),
                  ("albedo.fit.stream.dispatch", t + 1.1, t + 2.5)]
        start = t + 0.7 + 0.1 * (i % 3)
        device_ops += [(name, start + j, start + j + 0.8) for j, name in enumerate(ops)]
        modules.append((f"jit_als_chunked({i % 2})", start, start + 1.8))
        t += 3.0
    half = 1.0 + 3.0 * (dispatches // 2)
    spans += [("albedo.fit.stream", 1.0, half), ("albedo.fit.stream", half, t), ("albedo.fit", 0.5, t + 0.5),
              ("bench_fit", 0.4, t + 0.6), (trace_mod.WINDOW_SPAN, 0.2, t + 1.0), ("other", 0.0, t)]
    lines = [{"name": "main", "events": spans}]
    if second_thread:   # spans of another thread overlap without nesting
        lines.append({"name": "pool", "events": [("albedo.fit.acquire.probe", 2.9, 4.05),
                                                 ("albedo.fit.acquire.probe", 3.95, 8.0)]})
    return [{"name": "/host:CPU", "lines": lines},
            {"name": "/device:TPU:0", "lines": [{"name": trace_mod.OPS_LINE, "events": device_ops},
                                                {"name": trace_mod.MODULES_LINE, "events": modules}]},
            {"name": "/device:TPU:1", "lines": [{"name": trace_mod.OPS_LINE, "events": []}]}], ops


@pytest.mark.parametrize("second_thread", [False, True])
def test_the_swept_idle_labels_are_the_reductions_own(second_thread):
    from benchmark import phases, streamed_phases

    planes, op_names = _streamed_planes(7, second_thread)
    want = phases.reduce_phases(planes, op_names, ["als_chunked"])
    got = streamed_phases.reduce_phases(planes, op_names, ["als_chunked"])
    assert got.keys() == want.keys() and list(got["idle"]) == list(want["idle"])
    assert {"albedo.fit.stream.upload", "albedo.fit.stream.dispatch"} <= set(got["idle"])
    for key in want:
        assert got[key] == (pytest.approx(want[key]) if key == "idle" else want[key]), key
    assert streamed_phases.reduce_phases(planes, op_names, ["als_init_fit_fused"]) is None
    assert streamed_phases.reduce_phases(planes[1:], op_names, ["als_chunked"]) is None
    assert streamed_phases.idle_by_span([(0.0, 0.1)], []) == {"host: no span": pytest.approx(0.1)}


# ------------------------------------------------------ the whole command

def test_untraced_run_is_chunked_by_admission_and_prints_a_valid_line(monkeypatch, capsys):
    mf = steer(monkeypatch)
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc == 0, err[-3000:]
    line = lastline.parse_last_line(out)
    lastline.validate_line(line, manifest.metrics_for(mf, CELL, False), False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"fit_sweep_ms", "setup_s"}
    extra = line["extra"]
    assert extra["mode"] == "chunked" and extra["verdict"] == "degrade"
    assert extra["compiles_in_window"] == 0 and extra["dispatches"] > 0
    assert len(out.rstrip("\n").split("\n")) == 1
    assert "0 compilations inside it" in err and "fit.stream.upload" in err


def test_traced_run_reports_the_new_metrics_beside_the_old(monkeypatch, capsys):
    """The CPU has no device plane, so the reduced trace is a made-up one;
    everything else is the real command."""
    from benchmark import trace as trace_mod

    from benchmark import device

    mf = steer(monkeypatch)
    # a chip's name, for the published peaks the two shares divide by
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: dict(device.describe_devices(), kind="TPU v5 lite"))
    monkeypatch.setattr(trace_mod, "reduce_planes", lambda planes, chips: {
        "busy_s": 0.004, "window_s": 0.01, "programs": {"jit_als_chunked(7)": 0.004},
        "device_ops": [["all fusion", 0.004]], "idle_gaps": [["bench_fit", 0.006]],
    })
    rc, out, err = run_command(capsys, *ARGS, "--trace", "1")
    assert rc == 0, err[-3000:]
    line = lastline.parse_last_line(out)
    expected = manifest.metrics_for(mf, CELL, True)
    lastline.validate_line(line, expected, True)
    assert set(NEW_METRICS) <= set(line["metrics"]) == {m["name"] for m in expected}
    assert "prep_upload_s" not in line["metrics"]
    assert all(line["metrics"][m]["value"] > 0 for m in NEW_METRICS)


def test_the_run_fails_when_the_fit_stays_resident(monkeypatch, capsys):
    steer(monkeypatch, degrade=False)
    with pytest.raises(RuntimeError, match="chunked path under admission's degrade"):
        run_command(capsys, *ARGS, "--trace", "0")
    assert capsys.readouterr().out == ""


def test_the_run_fails_when_the_path_is_forced(monkeypatch, capsys):
    from benchmark.drivers import fit as fit_driver

    steer(monkeypatch, degrade=False)
    real = fit_driver.build_program
    monkeypatch.setattr(fit_driver, "build_program",
                        lambda config, stars, seed: real(config, stars, seed, chunked=True))
    with pytest.raises(RuntimeError, match="the path was forced"):
        run_command(capsys, *ARGS, "--trace", "0")


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


# --------------------------------------------------------- the manifest

def test_every_new_name_resolves_to_a_file():
    mf = manifest.load_manifest()
    cell = manifest.resolve_cell(mf, REAL_CELL)
    config = cell["config"]
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == config["driver"] == "fit_streamed"
    for kind, name in (("drivers", "fit_streamed"), ("reference", config["reference"]),
                       *(("readers", m) for m in NEW_METRICS)):
        assert (manifest.HERE / kind / f"{name}.py").exists(), (kind, name)
    assert cell["traffic"]["trace_programs"] == ["als_chunked"]
    traced = {m["name"] for m in manifest.metrics_for(mf, REAL_CELL, True)}
    assert traced == {"prep_bucket_s", "fit_compile_s", "fit_device_ms", "als_fit_roofline",
                      "als_fit_mfu", "device_idle.fit", *NEW_METRICS}
    assert {m["name"] for m in manifest.metrics_for(mf, REAL_CELL, False)} == {"fit_sweep_ms", "setup_s"}
    entry = next(c for c in mf["configs"] if c["name"] == "gh10m-r128")
    assert entry["reduced"] == config["reduced"] == ["nnz", "max_iter"]
    assert entry["source"] == config["source"] and config["architecture"] is None
    assert set(config["check_limits"]) == {
        f"{side}_{n}" for side in ("user", "item")
        for n in ("rows_worst", "rows_p99", "rows_median", "all_rows_worst")}
    for m in mf["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] == "host stream" and m["moves"] == "fit_sweep_ms"
            assert m["workloads"] == [REAL_CELL]


def test_the_readers_give_nothing_for_a_program_without_the_spans():
    """The parent's fits publish neither the spans nor the counter."""
    ctx = {"reports": [{"spans": {"totals": {"fit": 1.0}, "counts": {"fit": 1}}}], "sweeps": 5}
    for name in NEW_METRICS:
        assert manifest.load_module("readers", name).read(ctx) is None
    assert manifest.load_module("readers", "stream_upload_ms").read({}) is None
