"""The whole command at a tiny size on the CPU: the end-to-end line, the
traced run that has no device to read, the refusal without a chip, the
control that must read not correct, and the timed path broken underneath."""

import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import TINY_CELL, last_json, run_command, steer_onto_cpu
from benchmark import lastline, manifest

ARGS = ("--workload", TINY_CELL, "--seed", "3000000019", "--seconds", "0.3")


def test_untraced_run_prints_a_line_the_validator_passes(monkeypatch, capsys):
    mf = steer_onto_cpu(monkeypatch)
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc == 0, err[-2000:]
    line = lastline.parse_last_line(out)
    lastline.validate_line(line, manifest.metrics_for(mf, TINY_CELL, False), False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"fit_sweep_ms", "setup_s"} <= set(line["metrics"])
    assert list(line)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert len(out.rstrip("\n").split("\n")) == 1          # nothing but the line on stdout
    tail = err.rstrip().split("\n")
    assert tail[-1] == "correct: True" and tail[-2].startswith("compared ")
    assert "0 compilations inside it" in err


def test_traced_run_on_the_cpu_has_no_device_metric_and_prints_no_line(monkeypatch, capsys):
    steer_onto_cpu(monkeypatch)
    rc, out, err = run_command(capsys, *ARGS, "--trace", "1")
    assert rc == 3 and out == ""
    assert "no /device:TPU plane" in err
    assert "does not meet the contract" in err
    for name in ("als_fit_roofline", "als_fit_mfu", "device_idle.fit", "busy_s"):
        assert f'"{name}"' not in out


def test_without_the_steering_the_command_refuses_the_cpu(monkeypatch, capsys):
    steer_onto_cpu(monkeypatch, with_chip_check=True)
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc != 0 and out == "" and "refused" in err


def test_the_command_fails_where_the_program_is_absent(tmp_path):
    import shutil

    root = manifest.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(root / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "ml25m-r128.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)},
    )
    assert done.returncode != 0 and done.stdout == ""


def _line_with(monkeypatch, capsys, patch_fit):
    """Run the tiny cell with ``ImplicitALS.fit`` wrapped by ``patch_fit``."""
    from albedo_tpu.models import als as als_mod

    steer_onto_cpu(monkeypatch)
    real = als_mod.ImplicitALS.fit
    monkeypatch.setattr(als_mod.ImplicitALS, "fit",
                        lambda self, matrix, callback=None: patch_fit(real, self, matrix))
    rc, out, err = run_command(capsys, *ARGS, "--trace", "0")
    assert rc == 0, err[-2000:]
    return last_json(out)


def _bf16_gather(real, self, matrix):
    """The program's own lower-precision path, switched on underneath (it
    separates on the CPU, whose float32 matmuls are exact; on the chip it
    does not, PERF.md section 2)."""
    self.gather_dtype = "bfloat16"
    return real(self, matrix)


def _reference_bf16(real, self, matrix):
    """The stated control: the plain reference put in the program's place and
    computed in bfloat16 throughout — for the set-up fit, whose factors the
    comparison reads (the window's fits stay the program's: nothing may
    compile inside it)."""
    import jax.numpy as jnp

    model = real(self, matrix)
    if getattr(self, "_control_done", False):
        return model
    self._control_done = True
    reference = manifest.load_module("reference", "als_cg")
    stars = {"rows": matrix.rows, "cols": matrix.cols, "vals": matrix.vals,
             "n_users": matrix.n_users, "n_items": matrix.n_items}
    config = {"rank": self.rank, "reg_param": self.reg_param, "alpha": self.alpha,
              "cg_steps": self.cg_steps}
    user, item = reference.fit(stars, config, self.seed, self.max_iter, dtype=jnp.bfloat16)
    return _model(user, item, self.rank)


@pytest.mark.parametrize("control", [_reference_bf16, _bf16_gather],
                         ids=["reference_bf16", "program_bf16_gather"])
def test_the_control_reads_not_correct(monkeypatch, capsys, control):
    line = _line_with(monkeypatch, capsys, control)
    assert line["correct"] is False
    assert any(c["value"] > 3 * c["limit"] for c in line["compared"].values())


def _model(user, item, rank):
    from albedo_tpu.models.als import ALSModel

    return ALSModel(np.asarray(user), np.asarray(item), rank)


def _unchanged(real, self, matrix):
    """A step that returns its state — the seeded init — unchanged."""
    sweeps, self.max_iter = self.max_iter, 0
    try:
        return real(self, matrix)
    finally:
        self.max_iter = sweeps


def _half_left_out(real, self, matrix):
    """Every second user row left out of the last half-sweep."""
    full = real(self, matrix)
    self.max_iter -= 1
    try:
        before = real(self, matrix)
    finally:
        self.max_iter += 1
    user = full.user_factors.copy()
    user[::2] = before.user_factors[::2]
    return _model(user, full.item_factors, self.rank)


def _answer_altered(real, self, matrix):
    """One answer altered where it is produced: a row swapped for its neighbour."""
    full = real(self, matrix)
    item = full.item_factors.copy()
    item[7] = full.item_factors[8]
    return _model(full.user_factors, item, self.rank)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _answer_altered],
                         ids=["state_unchanged", "half_left_out", "answer_altered"])
def test_a_timed_path_broken_underneath_reads_not_correct(monkeypatch, capsys, fault):
    line = _line_with(monkeypatch, capsys, fault)
    assert line["correct"] is False


def test_a_fit_that_raises_counts_as_failed_and_not_correct(monkeypatch, capsys):
    calls = {"n": 0}

    def raises_in_window(real, self, matrix):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("planted")
        return real(self, matrix)

    line = _line_with(monkeypatch, capsys, raises_in_window)
    assert line["failed"] == 1 and line["correct"] is False and line["attempted"] >= 2
