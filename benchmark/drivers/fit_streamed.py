"""Driver of the ``fit-streamed`` traffic: whole ``ImplicitALS.fit`` calls
back to back on one matrix whose resident plan does not fit the chip, so
that the program's own admission degrades every fit to the chunked,
host-streamed path.

As ``drivers/fit.py`` (whose program builder, first sweeps, compile counter
and seed folding are used as they are), with two differences. The set-up
fit and every fit of the window must report ``mode == "chunked"`` under a
``degrade`` verdict that the estimator's admission reached by itself
(``chunked`` is left at ``None``; nothing in the environment is set): a fit
that stays resident, or is forced, fails the run. And the comparison goes
through ``benchmark/streamed_check.py``: the same reference
(``reference/als_cg.py``) and the same eight numbers, in memory that a
10M-row table leaves on the chip and on the host. A run's time outside the
window is the harness's own (a run has 360 s): the matrix comes from
``streamed_stars.py`` (``stars.py``'s, value for value, in half the time), the
traced run's tables from ``streamed_phases.py``, and every stage is logged
with the seconds since the process started.
"""

from __future__ import annotations

# A tree without the chunked path's spans has nothing for this cell to read:
# it fails here, in both modes alike, before anything is generated.
from albedo_tpu.models.als import CHUNKED_SPANS

import gc
import shutil
import time

from benchmark import compare, device, streamed_check, streamed_phases, trace as trace_mod
from benchmark.drivers.fit import CompileCounter, build_program, first_sweeps, fit_seed, log
from benchmark.manifest import ROOT, load_module
from benchmark.streamed_stars import generate_stars

COUNTERS = ("mode", "chunked_shapes", "dispatches", "buckets", "streamed_bytes_per_sweep",
            "upload_s", "compile_s", "compile_source", "device_s", "cg_gram_entry_share")


def left_the_path(report: dict) -> str | None:
    """Why a fit does not count as one of this cell's, or nothing."""
    verdict = (report.get("capacity") or {}).get("verdict")
    if report["mode"] != "chunked" or verdict != "degrade":
        return f"mode {report['mode']!r} under the verdict {verdict!r}"
    return None


def log_spans(title: str, report: dict) -> None:
    totals = report["spans"]["totals"]
    counts = report["spans"]["counts"]
    rows = [f"{title}: spans, seconds (calls)"]
    rows += [f"  {name:<36} {totals[name]:>12.6f} ({counts[name]})" for name in sorted(totals)]
    missing = [name for name in CHUNKED_SPANS if name not in totals]
    if missing:
        rows.append(f"  not published: {missing}")
    rows.append(f"  counters: { {k: report.get(k) for k in COUNTERS} }")
    log("\n".join(rows))


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
                        why = ("non-finite factors" if report["health"]["nonfinite"]
                               else left_the_path(report))
                        bad = why is not None
                        if bad:
                            log(f"fit does not count: {why}")
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


def check(config: dict, traffic: dict, stars: dict, seed: int, got, dtype=None) -> dict:
    """The numbers compared: the program's first sweeps against the
    reference's (``dtype``: the control computes the reference lower)."""
    reference = load_module("reference", config["reference"])
    t = time.perf_counter()
    want = streamed_check.reference_fit(
        reference, stars, config, fit_seed(seed), traffic["check_sweeps"], dtype)
    log(f"check: reference in {time.perf_counter() - t:.2f} s")
    return streamed_check.compare_fit(
        got[0], got[1], want[0], want[1], stars, config["check_min_stars"])


def run(cell: dict, seed: int, seconds: float, traced: bool, started: float,
        expected_metrics: list[dict]) -> dict:
    config, traffic = cell["config"], cell["traffic"]
    desc = device.require_chips(cell["chips"])
    log(f"device: {desc}")
    counter = CompileCounter()

    def at(stage: str) -> None:
        log(f"[{time.perf_counter() - started:7.1f} s since the start] {stage}")

    t = time.perf_counter()
    stars = generate_stars(config, seed)
    log(f"setup: generated {stars['rows'].size} stars in {time.perf_counter() - t:.2f} s")
    at("matrix generated")
    als, matrix = build_program(config, stars, seed)
    if als.chunked is not None:
        raise RuntimeError("the cell measures admission's own choice; the path was forced")
    t = time.perf_counter()
    got, first_report = first_sweeps(als, matrix, traffic["check_sweeps"])
    log(f"setup: first {traffic['check_sweeps']} sweeps in {time.perf_counter() - t:.2f} s; "
        f"capacity {first_report.get('capacity')}")
    log_spans("set-up fit", first_report)
    why = left_the_path(first_report)
    if why:
        raise RuntimeError(f"the cell measures the chunked path under admission's degrade; the fit ran {why}")
    setup_s = time.perf_counter() - started
    at("set-up done, the window opens")
    compiles_before = counter.count

    # one trace at a time, at a fixed place inside the checkout
    trace_dir = str(ROOT / ".bench-trace" / cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_window(als, matrix, seconds, traced, trace_dir)
    compiles_in_window = counter.count - compiles_before
    at("window closed" + (", trace written" if traced else ""))
    peak = device.memory_peak_bytes(cell["chips"])
    log(f"window: {win['window_s']:.3f} s, {len(win['reports'])} fits, {win['sweeps']} sweeps, "
        f"{compiles_in_window} compilations inside it; peak {peak} bytes")
    if win["reports"]:
        log_spans("first fit of the window", win["reports"][0])
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
            # the scope and idle tables, on standard error
            streamed_phases.log_phases(trace_mod.find_xplane(trace_dir), traffic["trace_programs"])
        del planes
        at("trace reduced")

    if win["sweeps"] == 0:
        raise RuntimeError("no fit of the window completed")
    t = time.perf_counter()
    numbers = check(config, traffic, stars, seed, got)
    log(f"check: reference and comparison in {time.perf_counter() - t:.2f} s")
    at("checked")
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
    last = win["reports"][-1]
    return {
        "correct": bool(ok and win["failed"] == 0),
        "attempted": win["attempted"],
        "failed": win["failed"],
        "metrics": metrics, "device": dev, "compared": compared, "breakdown": breakdown,
        "extra": {"fits": len(win["reports"]), "sweeps": win["sweeps"],
                  "window_s": win["window_s"], "compile_source": first_report["compile_source"],
                  "compiles_in_window": compiles_in_window, "mode": last["mode"],
                  "verdict": (last.get("capacity") or {}).get("verdict"),
                  "chunked_shapes": last["chunked_shapes"], "dispatches": last["dispatches"],
                  "buckets": last["buckets"],
                  "streamed_bytes_per_sweep": last["streamed_bytes_per_sweep"]},
    }
