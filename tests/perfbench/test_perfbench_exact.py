"""The exact-solve deployment's side of the benchmark: the plain exact
reference against a float64 solve written out here, the cell under the real
command at a CPU size with its controls and faults (the CG program in the
exact program's place among them), the work counts and the two readers, and
the manifest's new entries."""

import copy
import json

import numpy as np
import pytest

from bench_helpers import last_json, run_command
from benchmark import control_exact, lastline, manifest, phases, workcounts, workcounts_exact
from benchmark.manifest import ROOT, load_module
from test_perfbench_cli import _answer_altered, _half_left_out, _model, _unchanged
from test_perfbench_phases import ns, xspace

CELL, REAL_CELL = "tiny-chol-r16.fit", "albedo-r50-chol.fit"
MF = manifest.load_manifest()
CHOL = manifest.load_config(MF, "albedo-r50-chol")
CG = manifest.load_config(MF, "albedo-r50")
TINY = json.loads((ROOT / "tests/perfbench/data/tiny-chol-r16.json").read_text())
ARGS = ("--workload", CELL, "--seed", "3000000019", "--seconds", "0.3", "--trace", "0")


# ------------------------------------------------- (a) the plain reference

def seeded_matrix():
    """40 users x 30 repositories: repository 0 starred by every user (heavy),
    repositories 1-6 by one user each, 27-29 by nobody; users 37-39 star
    nothing; the rest seeded."""
    rng = np.random.default_rng(20261004)
    pairs = {(u, 0) for u in range(37)} | {(u, 1 + u) for u in range(6)}
    while len(pairs) < 37 + 6 + 150:
        pairs.add((int(rng.integers(0, 37)), int(rng.integers(7, 27))))
    rows, cols = (np.array(x, np.int32) for x in zip(*sorted(pairs)))
    vals = rng.choice([1.0, 2.0, 3.5], size=rows.size).astype(np.float32)
    return {"n_users": 40, "n_items": 30, "rows": rows, "cols": cols, "vals": vals}


def float64_fit(stars, config, init, sweeps):
    """The same normal equations, a row at a time, in float64 numpy."""
    def half(source, target, major, minor):
        out, yty = target.copy(), source.T @ source
        for r in np.unique(major):
            sel = major == r
            y, c = source[minor[sel]], config["alpha"] * stars["vals"][sel].astype(np.float64)
            a = yty + (y * c[:, None]).T @ y + config["reg_param"] * sel.sum() * np.eye(yty.shape[0])
            out[r] = np.linalg.solve(a, ((1 + c)[:, None] * y).sum(axis=0))
        return out

    user, item = (np.asarray(t, np.float64) for t in init)
    for _ in range(sweeps):
        item = half(user, item, stars["cols"], stars["rows"])
        user = half(item, user, stars["rows"], stars["cols"])
    return user, item


@pytest.mark.parametrize("rank", [8, 50])
def test_the_exact_reference_is_the_float64_solve_of_the_same_normal_equations(rank):
    reference = load_module("reference", "als_exact")
    stars, config = seeded_matrix(), {"rank": rank, "reg_param": 0.5, "alpha": 40.0}
    got = reference.fit(stars, config, 11, 2)
    want = float64_fit(stars, config, reference.init_factors(11, 40, 30, rank), 2)
    for g, w in zip(got, want):
        # float32 at highest precision against float64. At rank 50 the
        # Gramian of 40 rows is rank-deficient and a one-star row's system
        # rests on reg * 1 alone (condition number near 1e4), so 1e-3 of the
        # table's largest entry is what float32 can promise (read: 1.3e-4 at
        # rank 50, 8e-6 at rank 8); a wrong term reads 1e-1 or more.
        assert np.abs(g - w).max() < 1e-3 * np.abs(w).max()
    init = reference.init_factors(11, 40, 30, rank)
    assert np.array_equal(got[0][37:], np.asarray(init[0])[37:])     # rows with no star keep their draw
    assert np.array_equal(got[1][27:], np.asarray(init[1])[27:])
    assert not np.isclose(got[1][:7], np.asarray(init[1])[:7]).all()  # heavy and one-star rows are solved


def test_the_reference_imports_nothing_of_the_program_and_reads_no_cg_steps():
    text = (ROOT / "benchmark/reference/als_exact.py").read_text()
    assert "albedo_tpu" not in text
    reference = load_module("reference", "als_exact")
    stars = seeded_matrix()
    a = reference.fit(stars, {"rank": 8, "reg_param": 0.5, "alpha": 40.0, "cg_steps": 1}, 3, 1)
    b = reference.fit(stars, {"rank": 8, "reg_param": 0.5, "alpha": 40.0}, 3, 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


# ------------------------------------- (c) the cell under the real command

def tiny_manifest(real: dict) -> dict:
    mf = copy.deepcopy(real)
    mf["configs"].append({"name": "tiny-chol-r16", "source": "tests", "reduced": [], "why": "CPU tests",
                          "file": "tests/perfbench/data/tiny-chol-r16.json"})
    mf["workloads"].append({"name": CELL, "config": "tiny-chol-r16", "traffic": "fit", "chips": 1,
                            "why": "CPU tests"})
    for m in mf["per_layer"] + mf["end_to_end"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return mf


def steer(monkeypatch):
    from benchmark import device

    mf = tiny_manifest(MF)
    monkeypatch.setattr(manifest, "load_manifest", lambda path=None: mf)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setattr(device, "require_chips", lambda chips: device.describe_devices())
    monkeypatch.setattr(device, "memory_peak_bytes", lambda chips: 4096)
    return mf


def line_with(monkeypatch, capsys, patch_fit=None):
    from albedo_tpu.models import als as als_mod

    mf = steer(monkeypatch)
    if patch_fit is not None:
        real = als_mod.ImplicitALS.fit
        monkeypatch.setattr(als_mod.ImplicitALS, "fit",
                            lambda self, matrix, callback=None: patch_fit(real, self, matrix))
    rc, out, err = run_command(capsys, *ARGS)
    assert rc == 0, err[-2000:]
    return mf, last_json(out), err


def test_the_cell_reads_correct_through_the_exact_solve_on_the_normal_path(monkeypatch, capsys):
    mf, line, err = line_with(monkeypatch, capsys)
    lastline.validate_line(line, manifest.metrics_for(mf, CELL, False), False)
    assert line["correct"] is True and line["failed"] == 0
    assert {"fit_sweep_ms", "setup_s"} <= set(line["metrics"])
    # float32 with exact matmuls against float32 at highest: 1e-5, under a twentieth of the limit
    assert all(c["value"] < 1e-5 for c in line["compared"].values())
    assert "'mode': 'resident'" in err and "0 compilations inside it" in err


def _cg_in_place(real, self, matrix):
    """The approximate solve in the exact program's place: what the
    repository's other cells run."""
    self.solver, self.cg_steps = "cg", 3
    return real(self, matrix)


def _reference_bf16(real, self, matrix):
    """The exact reference in bfloat16 throughout, in the program's place for
    the set-up fit (whose factors the comparison reads)."""
    import jax.numpy as jnp

    model = real(self, matrix)
    if getattr(self, "_control_done", False):
        return model
    self._control_done = True
    stars = {"rows": matrix.rows, "cols": matrix.cols, "vals": matrix.vals,
             "n_users": matrix.n_users, "n_items": matrix.n_items}
    config = {"rank": self.rank, "reg_param": self.reg_param, "alpha": self.alpha}
    user, item = load_module("reference", "als_exact").fit(
        stars, config, self.seed, self.max_iter, dtype=jnp.bfloat16)
    return _model(user, item, self.rank)


@pytest.mark.parametrize("broken", [_cg_in_place, _reference_bf16, _unchanged, _half_left_out, _answer_altered],
                         ids=["cg_program_in_place", "reference_bf16", "state_unchanged", "half_left_out",
                              "answer_altered"])
def test_the_controls_and_the_faults_read_not_correct(monkeypatch, capsys, broken):
    _, line, _ = line_with(monkeypatch, capsys, broken)
    assert line["correct"] is False
    assert any(c["value"] > 3 * c["limit"] for c in line["compared"].values())


def test_control_exact_reads_the_program_correct_and_every_control_not(monkeypatch):
    cell = manifest.resolve_cell(steer(monkeypatch), CELL)
    row = control_exact.readings(cell, 3000000019)
    assert row["correct"] == {
        "program": True, "control_program_cg": False, "control_reference_bf16": False,
        "fault_unchanged": False, "fault_half_left_out": False, "fault_row_altered": False}
    assert row["program_report"]["mode"] == row["control_program_cg_report"]["mode"] == "resident"
    assert row["program_report"]["exact_systems_per_sweep"] > 0
    assert row["control_program_cg_report"]["exact_systems_per_sweep"] == 0
    # three CG steps from a seeded start are nowhere near a converged heavy row
    assert row["control_program_cg"]["user_rows_median"] > 100 * TINY["check_limits"]["user_rows_median"]
    with pytest.raises(ValueError, match="exact"):
        control_exact.readings(dict(cell, config=dict(cell["config"], solver="cg")), 1)


# ------------------------------------------ (d) the counts and the readers

def test_exact_counts_equal_hand_computed_values_and_bound_the_solve_by_bytes():
    n_users, n_items, nnz, k = 450000, 300000, 40000000, 50
    assert (CHOL["n_users"], CHOL["n_items"], CHOL["nnz"], CHOL["rank"]) == (n_users, n_items, nnz, k)
    got = workcounts_exact.config_counts(CHOL)
    assert got["bytes_per_sweep"] == pytest.approx(2 * nnz * 4 * k + 750000 * 4 * k, rel=1e-12)
    assert got["flops_per_sweep"] == pytest.approx(
        2 * nnz * (2 * k * k + 3 * k) + 750000 * (k**3 / 3 + 4 * k * k), rel=1e-12)
    least = workcounts_exact.least_solve_seconds(CHOL, "TPU v5 lite")
    assert least["bound"] == "bytes" and least["least_s"] == pytest.approx(16.15e9 / 819e9, rel=1e-3)
    # the solve's work is the whole sweep's less the index, value and table traffic and the Gramians
    whole = workcounts.config_counts(CHOL)
    assert got["bytes_per_sweep"] < whole["bytes_per_sweep"]
    assert whole["flops_per_sweep"] - got["flops_per_sweep"] == pytest.approx(2 * 750000 * k * k, rel=1e-9)


@pytest.mark.parametrize("extra", [{"batch_size": 1024, "max_entries": 1 << 18}, {"cg_steps": 9},
                                   {"max_iter": 26}])
def test_exact_counts_read_the_logical_matrix_alone(extra):
    assert workcounts_exact.config_counts({**CHOL, **extra}) == workcounts_exact.config_counts(CHOL)


def test_exact_counts_refuse_a_configuration_that_states_another_solver():
    with pytest.raises(ValueError, match="exact"):
        workcounts_exact.config_counts(CG)


PREFIX = "jit(als_init_fit_fused)/call_exported/jit(als_init_fit_fused)/while/body/closed_call/"
TRACE = dict(
    ops=[("%while.9 = while", 2.0, 18.0), ("%fusion.1 = gather", 2.0, 4.0),
         ("%fusion.2 = build", 4.0, 6.0), ("%while.3 = factor", 6.0, 12.0),
         ("%while.4 = solve", 12.0, 17.0), ("%fusion.5 = land", 17.0, 18.0)],
    modules=[("jit_als_init_fit_fused(7)", 2.0, 18.0)],
    host=[("bench_window", 0.0, 20.0), ("bench_fit", 0.5, 19.5)],
)
SCOPED = {
    "%fusion.1 = gather": PREFIX + "als.gather/gather:",
    "%fusion.2 = build": PREFIX + "als.cholesky/als.cholesky.build/blk,bl,blm->bkm/dot_general:",
    "%while.3 = factor": PREFIX + "als.cholesky/als.cholesky.factor/cholesky:",
    "%while.4 = solve": PREFIX + "als.cholesky/als.cholesky.solve/triangular_solve:",
    "%fusion.5 = land": PREFIX[:-12] + "als.landing/gather:",
}
# the parent of the PR that added the sub-scopes: the outermost scope alone
PARENT = {k: v.replace("als.cholesky.build/", "").replace("als.cholesky.factor/", "")
          .replace("als.cholesky.solve/", "") for k, v in SCOPED.items()}


@pytest.mark.parametrize("op_names", [SCOPED, PARENT], ids=["with_sub_scopes", "parent"])
def test_both_readers_read_the_outermost_scope_with_or_without_its_sub_scopes(
        op_names, tmp_path, monkeypatch, capsys):
    raw = xspace(ops=ns(TRACE["ops"]), modules=ns(TRACE["modules"]), host=ns(TRACE["host"]), op_names=op_names)
    path = tmp_path / ".bench-trace" / "cell" / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    monkeypatch.setattr(phases, "ROOT", tmp_path)
    phases.phases_of.cache_clear()
    ctx = {"trace": {"window_s": 20.0, "busy_s": 16.0}, "sweeps": 4, "config": CHOL, "device_kind": "TPU v5 lite",
           "traffic": {"trace_programs": ["als_init_fit_fused", "jit_call"]}}
    chol_ms = load_module("readers", "fit_chol_ms").read(ctx)
    assert chol_ms == pytest.approx(1000 * (2.0 + 6.0 + 5.0) / 4)
    least = workcounts_exact.least_solve_seconds(CHOL, "TPU v5 lite")["least_s"]
    share = load_module("readers", "chol_solve_roofline").read(ctx)
    assert share == pytest.approx(100 * least * 1000 / chol_ms) and 0 < share <= 105
    err = capsys.readouterr().err
    assert ("als.cholesky.factor" in err) == (op_names is SCOPED)     # the split is in the scope table
    # a program under CG, a trace that is not this window, and no trace at all: nothing, and no error
    for other in (dict(ctx, trace={"window_s": 19.0}), dict(ctx, trace=None)):
        assert load_module("readers", "fit_chol_ms").read(other) is None
        assert load_module("readers", "chol_solve_roofline").read(other) is None
    path.write_bytes(xspace(ops=ns(TRACE["ops"]), modules=ns(TRACE["modules"]), host=ns(TRACE["host"]),
                            op_names={"%fusion.1 = gather": SCOPED["%fusion.1 = gather"]}))
    phases.phases_of.cache_clear()
    assert load_module("readers", "fit_chol_ms").read(ctx) is None
    assert load_module("readers", "chol_solve_roofline").read(ctx) is None


# -------------------------------------------------- the manifest's entries

def test_the_configuration_is_albedo_r50_but_for_the_solver_and_its_consequences():
    differ = {k for k in set(CHOL) | set(CG) if CHOL.get(k) != CG.get(k)}
    assert differ == {"name", "source", "deployment", "reference", "solver", "max_iter", "assumed", "reduced",
                      "check_limits", "cg_steps_note", "max_iter_published", "guarantee", "reduced_note"}
    assert "architecture" in CHOL and CHOL["architecture"] is None     # a deployment, no catalog model
    assert (CHOL["solver"], CHOL["reference"], CHOL["rank"], CHOL["reg_param"], CHOL["alpha"]) == (
        "cholesky", "als_exact", 50, 0.5, 40.0)
    assert CHOL["reduced"] == ["max_iter"] and CHOL["max_iter_published"] == 26 and CHOL["max_iter"] == 5
    assert "exact solution" in CHOL["guarantee"] and "not an iterate" in CHOL["guarantee"]
    entry = next(c for c in MF["configs"] if c["name"] == "albedo-r50-chol")
    assert entry["reduced"] == CHOL["reduced"] and entry["source"].startswith("https://github.com/vinta/albedo")


def test_the_cell_is_one_chip_on_the_fit_traffic_and_lists_its_two_metrics_traced():
    cell = next(w for w in MF["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("albedo-r50-chol", "fit", 1)
    assert [m["name"] for m in manifest.metrics_for(MF, REAL_CELL, False)] == ["fit_sweep_ms", "setup_s"]
    traced = [m["name"] for m in manifest.metrics_for(MF, REAL_CELL, True)]
    assert traced == ["prep_bucket_s", "prep_upload_s", "fit_compile_s", "fit_device_ms", "als_fit_roofline",
                      "als_fit_mfu", "device_idle.fit", "fit_chol_ms", "chol_solve_roofline"]
    for name in ("fit_chol_ms", "chol_solve_roofline"):
        assert next(m for m in MF["per_layer"] if m["name"] == name)["workloads"] == [REAL_CELL]
    # no other cell's metrics moved
    for other in ("ml25m-r128.fit", "albedo-r50.fit"):
        assert not {"fit_chol_ms", "chol_solve_roofline"} & {m["name"] for m in manifest.metrics_for(MF, other, True)}
