"""Driver of the ``fit-sharded`` traffic: whole ``ImplicitALS.fit`` calls
back to back on one matrix whose factor tables and bucket slabs are
row-sharded over a mesh of the cell's chips — what ``train_als
--mesh-devices <n> --sharded resident --shard-mode allgather`` builds.

After ``drivers/fit_streamed.py`` (whose comparison, and through it
``drivers/fit.py``'s program builder, first sweeps, compile counter and seed
folding, are used as they are), with these differences:

- **The program is asked for its plan first.** Right after the look for the
  chips and before a star is generated, the driver asks the program for its
  plan of assembled factor-table bytes a chip a sweep
  (``albedo_tpu.parallel.als.assembled_bytes_per_sweep``) and exits non-zero
  where the program has none. A program that all-gathers the whole source
  table inside every bucket's program would sit minutes in each sweep at this
  size (36,785 ms a sweep on another matrix, PERF.md section 7) and be killed;
  it fails here, in seconds, in both modes alike.
- The estimator is built on ``parallel.mesh.make_mesh(chips)`` with the
  configuration's ``sharded`` and ``shard_mode``. The set-up fit and every fit
  of the window must report ``mode == "sharded"`` under ``shard_mode ==
  "allgather"`` with resident buckets, and its counter
  ``assembled_bytes_per_sweep`` — what the compiled programs its sweeps called
  did all-gather — must not pass one assembly of each table a sweep; a fit
  that leaves that path, raises, or returns a non-finite factor counts in
  ``failed`` (the set-up fit: the run fails).
- The reference (``reference/als_cg.py`` through ``streamed_check.py``) runs on
  one of the chips, after the program's state is dropped.
- Each chip's peak is logged beside the planner's price of the path.
"""

from __future__ import annotations

import gc
import shutil
import time

from benchmark import compare, device, trace as trace_mod
from benchmark.drivers.fit import log
from benchmark.manifest import ROOT, load_module

COUNTERS = ("mode", "shard_mode", "n_shards", "sharded_shapes", "dispatches",
            "assembled_bytes_per_sweep", "collective_bytes_per_sweep", "shard_padded_entries",
            "streamed_buckets", "bucket_s", "upload_s", "compile_s", "compile_source",
            "device_s", "cg_gram_entry_share")


def tables_once_bytes(config: dict, chips: int) -> int:
    """What the layout needs a chip to assemble in a sweep: each float32
    table once (rows padded to the chips, as the mesh shards them)."""
    rows = sum(-(-config[n] // chips) * chips for n in ("n_users", "n_items"))
    return rows * config["rank"] * 4


def assembly_plan(config: dict, chips: int) -> int:
    """The program's own plan of assembled bytes a sweep for this
    configuration; ``SystemExit`` (non-zero, at once) where it has none.
    What a fit did assemble is its report's counter (:func:`left_the_path`)."""
    try:
        from albedo_tpu.parallel.als import assembled_bytes_per_sweep
    except ImportError:
        raise SystemExit(
            "refused: the program has no plan of assembled bytes a sweep "
            "(albedo_tpu.parallel.als.assembled_bytes_per_sweep): its row-sharded fit "
            "assembles the whole source table inside every bucket's program, minutes a "
            "sweep at this size") from None
    return int(assembled_bytes_per_sweep(
        config["n_users"], config["n_items"], config["rank"], chips))


def left_the_path(report: dict, config: dict, chips: int) -> str | None:
    """Why a fit does not count as one of this cell's, or nothing."""
    if report["mode"] != "sharded" or report.get("shard_mode") != config["shard_mode"]:
        return f"mode {report['mode']!r}, shard mode {report.get('shard_mode')!r}"
    if report.get("n_shards") != chips or report.get("streamed_buckets"):
        return f"{report.get('n_shards')} shards, {report.get('streamed_buckets')} streamed buckets"
    assembled = report.get("assembled_bytes_per_sweep")
    if assembled is None or assembled > tables_once_bytes(config, chips):
        return f"{assembled} bytes assembled a sweep"
    return None


def log_spans(title: str, report: dict) -> None:
    from albedo_tpu.models.als import SHARDED_SPANS

    totals, counts = report["spans"]["totals"], report["spans"]["counts"]
    rows = [f"{title}: spans, seconds (calls)"]
    rows += [f"  {name:<36} {totals[name]:>12.6f} ({counts[name]})" for name in sorted(totals)]
    missing = [name for name in SHARDED_SPANS if name not in totals]
    if missing:
        rows.append(f"  not published: {missing}")
    rows.append(f"  counters: { {k: report.get(k) for k in COUNTERS} }")
    log("\n".join(rows))


def run_window(als, matrix, seconds: float, traced: bool, trace_dir: str | None, why_not):
    """Fits back to back: another starts only while the time so far plus the
    last fit's fits into ``seconds``; the first always runs. A traced window
    is one whole fit. ``why_not(report)`` says why a fit does not count."""
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
                               else why_not(report))
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


def chip_peaks(chips: int) -> list[int]:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()[:chips]]


def planned_bytes(als, matrix, chips: int) -> dict:
    """The planner's price a chip of the row-sharded resident path and of the
    replicated rung the ``auto`` ladder would try first, beside its budget."""
    from albedo_tpu.utils import capacity

    layout = als._layout_kwargs()
    shapes = [capacity.bucket_plan_shapes(capacity.counts_indptr(ids, n), **layout)
              for ids, n in ((matrix.rows, matrix.n_users), (matrix.cols, matrix.n_items))]
    sizes = (matrix.n_users, matrix.n_items, als.rank)
    sharded = capacity.plan_fit_sharded(
        *shapes, *sizes, chips, gather_dtype=als.gather_dtype, streamed=False,
        mode=als.shard_mode, solver=als.solver)
    replicated = capacity.plan_fit(*shapes, *sizes, gather_dtype=als.gather_dtype, n_devices=chips)
    return {"sharded_resident": sharded.to_dict(), "replicated": replicated.required_bytes,
            "budget": capacity.budget_bytes()}


def run(cell: dict, seed: int, seconds: float, traced: bool, started: float,
        expected_metrics: list[dict]) -> dict:
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    desc = device.require_chips(chips)
    log(f"device: {desc}")
    planned = assembly_plan(config, chips)
    log(f"plan: {planned} bytes of assembled tables a chip a sweep "
        f"(each table once: {tables_once_bytes(config, chips)})")

    from albedo_tpu.parallel.mesh import make_mesh
    from benchmark.drivers.fit import CompileCounter, build_program, first_sweeps
    from benchmark.drivers.fit_streamed import check
    from benchmark.streamed_stars import generate_stars

    if config["mesh_devices"] != chips:
        raise RuntimeError(f"the configuration is a mesh of {config['mesh_devices']}, the cell has {chips} chips")
    counter = CompileCounter()

    def at(stage: str) -> None:
        log(f"[{time.perf_counter() - started:7.1f} s since the start] {stage}")

    def why_not(report: dict) -> str | None:
        return left_the_path(report, config, chips)

    t = time.perf_counter()
    stars = generate_stars(config, seed)
    log(f"setup: generated {stars['rows'].size} stars in {time.perf_counter() - t:.2f} s")
    at("matrix generated")
    mesh = make_mesh(chips)
    als, matrix = build_program(config, stars, seed, mesh=mesh, sharded=config["sharded"],
                                shard_mode=config["shard_mode"])
    t = time.perf_counter()
    got, first_report = first_sweeps(als, matrix, traffic["check_sweeps"])
    log(f"setup: first {traffic['check_sweeps']} sweeps in {time.perf_counter() - t:.2f} s")
    log_spans("set-up fit", first_report)
    why = why_not(first_report)
    if why:
        raise RuntimeError(f"the cell measures the row-sharded resident path; the fit ran {why}")
    setup_s = time.perf_counter() - started
    at("set-up done, the window opens")
    compiles_before = counter.count

    # one trace at a time, at a fixed place inside the checkout
    trace_dir = str(ROOT / ".bench-trace" / cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_window(als, matrix, seconds, traced, trace_dir, why_not)
    compiles_in_window = counter.count - compiles_before
    at("window closed" + (", trace written" if traced else ""))
    peaks = chip_peaks(chips)
    peak = device.memory_peak_bytes(chips)
    log(f"window: {win['window_s']:.3f} s, {len(win['reports'])} fits, {win['sweeps']} sweeps, "
        f"{compiles_in_window} compilations inside it; peak {peak} bytes, by chip {peaks}")
    if win["reports"]:
        log_spans("first fit of the window", win["reports"][0])
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compilations inside the measured window")
    plan = planned_bytes(als, matrix, chips)
    log(f"plan against peak: the planner prices the path at {plan['sharded_resident']['required_bytes']} "
        f"bytes a chip {plan['sharded_resident']['items']}; peaks by chip {peaks}; the replicated "
        f"rung the auto ladder tries first prices at {plan['replicated']} of a budget of {plan['budget']}")
    del als, matrix, mesh
    gc.collect()
    reduced = None
    if traced:
        t = time.perf_counter()
        planes = trace_mod.planes_from_xplane(trace_mod.find_xplane(trace_dir))
        try:
            reduced = trace_mod.reduce_planes(planes, chips)
        except ValueError as e:  # no device plane: no device metric, and no line
            log(f"trace: {e}")
        else:
            log(f"trace: read in {time.perf_counter() - t:.2f} s; programs "
                f"{sorted(reduced['programs'].items(), key=lambda kv: -kv[1])[:8]}")
        del planes
        at("trace reduced")

    if win["sweeps"] == 0:
        raise RuntimeError("no fit of the window completed")
    ctx = {
        "config": config, "traffic": traffic, "device_kind": desc["kind"], "chips": chips,
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
            value = load_module("readers", m["name"]).read(ctx)   # (logs the scope table once)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # read before the reference runs, so that a run cut in its longest step
    # has left its readings in the log
    log(f"metrics: { {name: m['value'] for name, m in metrics.items()} }")
    if traced:
        at("metrics read")

    t = time.perf_counter()
    numbers = check(config, traffic, stars, seed, got)
    log(f"check: reference and comparison in {time.perf_counter() - t:.2f} s")
    at("checked")
    ok, compared = compare.judge(numbers, config["check_limits"])
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
                  "shard_mode": last["shard_mode"], "n_shards": last["n_shards"],
                  "sharded_shapes": last["sharded_shapes"], "dispatches": last["dispatches"],
                  "assembled_bytes_per_sweep": last["assembled_bytes_per_sweep"],
                  "collective_bytes_per_sweep": last["collective_bytes_per_sweep"],
                  "shard_padded_entries": last["shard_padded_entries"],
                  "memory_peak_bytes_by_chip": peaks,
                  "planned_bytes": plan["sharded_resident"]["required_bytes"]},
    }
