"""Driver of the ``fit`` traffic: whole ``ImplicitALS.fit`` calls back to
back on one resident matrix.

Set-up generates the matrix from the seed, builds ONE estimator and drives it
through the traffic's ``check_sweeps`` first sweeps from the seeded init —
through the window's own call, ``fit(matrix)``, which lays out and uploads the
buckets, acquires the executable and warms it. The window then runs that same
object at the configuration's ``max_iter`` (the sweep count is a traced
argument: one executable). Once the window has closed, the peak memory is
read, the program's state is dropped, and the plain reference follows the
same first sweeps from the seed; the comparison of the two factor tables
decides ``correct``.
"""

from __future__ import annotations

import gc
import shutil
import sys
import time

import numpy as np

from benchmark import compare, device, trace as trace_mod
from benchmark.manifest import ROOT, load_module
from benchmark.stars import generate_stars


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def fit_seed(seed: int) -> int:
    """The estimator's PRNG seed: ``--seed`` folded into 31 bits."""
    return int(seed) % 2147483647


def build_program(config: dict, stars: dict, seed: int, **overrides):
    """The system under test: the estimator and its matrix."""
    from albedo_tpu.datasets.star_matrix import StarMatrix
    from albedo_tpu.models.als import ImplicitALS

    matrix = StarMatrix(
        user_ids=np.arange(stars["n_users"], dtype=np.int64),
        item_ids=np.arange(stars["n_items"], dtype=np.int64),
        rows=stars["rows"], cols=stars["cols"], vals=stars["vals"],
    )
    kwargs = dict(
        rank=config["rank"], reg_param=config["reg_param"], alpha=config["alpha"],
        max_iter=config["max_iter"], seed=fit_seed(seed), solver=config["solver"],
        cg_steps=config["cg_steps"], gather_dtype=config["gather_dtype"],
    )
    kwargs.update(overrides)
    return ImplicitALS(**kwargs), matrix


class CompileCounter:
    """Counts backend compilations, so that the window can show it had none."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


def first_sweeps(als, matrix, sweeps: int):
    """The estimator's first ``sweeps`` sweeps from its seed, through the
    window's own call. Returns host copies of the factors and the report."""
    full = als.max_iter
    als.max_iter = sweeps
    try:
        model = als.fit(matrix)
        report = dict(als.last_fit_report)
        factors = (np.asarray(model.user_factors), np.asarray(model.item_factors))
    finally:
        als.max_iter = full
    return factors, report


def run_window(als, matrix, seconds: float, traced: bool, trace_dir: str | None):
    """Fits back to back: another starts only while the time so far plus the
    last fit's fits into ``seconds``; the first always runs. A traced window
    is one whole fit."""
    import jax

    reports, attempted, failed, sweeps = [], 0, 0, 0
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            while True:
                f0 = time.perf_counter()
                attempted += 1
                with jax.profiler.TraceAnnotation("bench_fit"):
                    try:
                        als.fit(matrix)
                        report = dict(als.last_fit_report)
                        bad = report["health"]["nonfinite"] or report["mode"] != "resident"
                    except Exception as e:  # a fit that raises is a failed fit
                        log(f"fit failed: {e!r}")
                        report, bad = None, True
                now = time.perf_counter()
                failed += bool(bad)
                if report is not None:
                    reports.append(report)
                    sweeps += als.max_iter
                if traced or (now - t0) + (now - f0) > seconds:
                    break
            elapsed = time.perf_counter() - t0
    finally:
        if traced:
            jax.profiler.stop_trace()
    return {"reports": reports, "attempted": attempted, "failed": failed,
            "sweeps": sweeps, "window_s": elapsed}


def check(config: dict, traffic: dict, stars: dict, seed: int, got) -> dict:
    """The numbers compared: program's first sweeps against the reference's."""
    reference = load_module("reference", config["reference"])
    want = reference.fit(stars, config, fit_seed(seed), traffic["check_sweeps"])
    return compare.compare_fit(got[0], got[1], want[0], want[1], stars, config["check_min_stars"])


def run(cell: dict, seed: int, seconds: float, traced: bool, started: float,
        expected_metrics: list[dict]) -> dict:
    config, traffic = cell["config"], cell["traffic"]
    desc = device.require_chips(cell["chips"])
    log(f"device: {desc}")
    counter = CompileCounter()

    t = time.perf_counter()
    stars = generate_stars(config, seed)
    log(f"setup: generated {stars['rows'].size} stars in {time.perf_counter() - t:.2f} s")
    als, matrix = build_program(config, stars, seed)
    t = time.perf_counter()
    got, first_report = first_sweeps(als, matrix, traffic["check_sweeps"])
    log(f"setup: first {traffic['check_sweeps']} sweeps in {time.perf_counter() - t:.2f} s; "
        f"report {({k: first_report[k] for k in ('bucket_s', 'upload_s', 'compile_s', 'compile_source', 'device_s', 'mode')})}")
    if first_report["mode"] != "resident":
        raise RuntimeError(f"the cell measures the resident path; the fit ran {first_report['mode']!r}")
    setup_s = time.perf_counter() - started
    compiles_before = counter.count

    # one trace at a time, at a fixed place inside the checkout
    trace_dir = str(ROOT / ".bench-trace" / cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_window(als, matrix, seconds, traced, trace_dir)
    compiles_in_window = counter.count - compiles_before
    peak = device.memory_peak_bytes(cell["chips"])
    log(f"window: {win['window_s']:.3f} s, {len(win['reports'])} fits, {win['sweeps']} sweeps, "
        f"{compiles_in_window} compilations inside it; peak {peak} bytes")
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compilations inside the measured window")
    del als, matrix
    gc.collect()
    reduced = None
    if traced:
        t = time.perf_counter()
        planes = trace_mod.planes_from_xplane(trace_mod.find_xplane(trace_dir))
        try:
            reduced = trace_mod.reduce_planes(planes, cell["chips"])
        except ValueError as e:  # no device plane: no device metric, and no line
            log(f"trace: {e}")
        else:
            log(f"trace: read in {time.perf_counter() - t:.2f} s; programs "
                f"{sorted(reduced['programs'].items(), key=lambda kv: -kv[1])[:6]}")

    if win["sweeps"] == 0:
        raise RuntimeError("no fit of the window completed")
    t = time.perf_counter()
    numbers = check(config, traffic, stars, seed, got)
    log(f"check: reference and comparison in {time.perf_counter() - t:.2f} s")
    ok, compared = compare.judge(numbers, config["check_limits"])

    ctx = {
        "config": config, "traffic": traffic, "device_kind": desc["kind"],
        "first_report": first_report, "reports": win["reports"], "sweeps": win["sweeps"],
        "window_s": win["window_s"], "trace": reduced,
    }
    values = {
        "setup_s": setup_s,
        "fit_sweep_ms": 1000.0 * win["window_s"] / win["sweeps"],
    }
    metrics = {}
    for m in expected_metrics:
        if m["name"] in values:
            value = values[m["name"]]
        else:
            value = load_module("readers", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(desc, memory_peak_bytes=peak)
    breakdown = None
    if reduced is not None:
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    return {
        "correct": bool(ok and win["failed"] == 0),
        "attempted": win["attempted"],
        "failed": win["failed"],
        "metrics": metrics, "device": dev, "compared": compared, "breakdown": breakdown,
        "extra": {"fits": len(win["reports"]), "sweeps": win["sweeps"],
                  "window_s": win["window_s"], "compile_source": first_report["compile_source"],
                  "compiles_in_window": compiles_in_window},
    }
