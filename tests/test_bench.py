"""Bench harness units: the analytic ALS FLOP model and failure-path helpers.

The bench contract: ONE process initializes JAX (no child-process probe — a
chip belongs to one process at a time), emits ONE structured JSON line on
success or failure, exits non-zero when ANY phase it ran failed, and reports
MFU from an analytic FLOP model against PUBLISHED peaks only.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

import bench
from albedo_tpu.datasets.synthetic import synthetic_stars


def test_als_fit_flops_scaling():
    m = synthetic_stars(n_users=300, n_items=200, mean_stars=10, seed=1)
    one = bench.als_fit_flops(m, rank=8, iters=1, batch_size=64, max_entries=1 << 16)
    ten = bench.als_fit_flops(m, rank=8, iters=10, batch_size=64, max_entries=1 << 16)
    assert one["flops"] > 0
    assert ten["flops"] == 10 * one["flops"]
    assert ten["per_iter"] == one["per_iter"]
    # Padding can only add entries; each nnz is bucketed twice per iteration
    # (CSR user-solve + CSC item-solve), hence logical_entries = 2*nnz.
    assert one["logical_entries"] == 2 * one["logical_nnz"]
    assert one["padded_entries"] >= one["logical_entries"]
    # The Gramian term dominates and scales ~k^2: rank 16 >= ~3x rank 8.
    big = bench.als_fit_flops(m, rank=16, iters=1, batch_size=64, max_entries=1 << 16)
    assert big["flops"] > 3 * one["flops"]


def test_peak_flops_lookup():
    peak, src = bench.peak_flops_for("TPU v4")
    assert peak == 275e12 and "v4" in src
    peak, src = bench.peak_flops_for("TPU v5 lite")
    assert peak == 197e12
    assert bench.peak_hbm_gbps_for("TPU v5 lite") == 819.0
    # An unknown kind is an error, never a self-measured "peak".
    with pytest.raises(KeyError, match="weird accelerator"):
        bench.peak_flops_for("weird accelerator")
    with pytest.raises(KeyError, match="cpu"):
        bench.peak_hbm_gbps_for("cpu")


def test_bench_starts_no_child_process():
    """One process per chip: the bench has no subprocess probe to launch —
    nothing in it can start a child that would need the device."""
    import inspect

    src = inspect.getsource(bench)
    assert "subprocess" not in src and "os.fork" not in src
    for gone in ("probe_backend", "stray_accelerator_pids", "PROBE_TIMEOUT_S"):
        assert not hasattr(bench, gone)


def test_bench_error_record_is_json(tmp_path):
    """A broken backend must yield rc!=0 and ONE parseable JSON error line
    (round-1 failure mode: bare stack trace, nothing parseable) — from the
    bench process's own JAX initialization, with no probe stage."""
    proc = subprocess.run(
        [sys.executable, str(bench.__file__)],
        capture_output=True, text=True, timeout=120,
        env={
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "definitely_not_a_platform",
            "ALBEDO_JAX_CACHE": "0",
        },
    )
    assert proc.returncode != 0
    line = proc.stdout.strip().splitlines()[-1]
    record = json.loads(line)
    assert record["stage"] == "import"
    assert record["value"] is None and record["error"]


def test_watchdog_fails_the_run_but_keeps_flagship_record():
    """If the watchdog fires AFTER the ALS headline is computed (a wedged or
    crawling ranker stage), the GOOD flagship record is still the last line
    (tagged partial) — but a failed phase exits NON-ZERO: an exit 0 here is
    how a broken bring-up passes."""
    import os

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "ALBEDO_BENCH_USERS": "300", "ALBEDO_BENCH_ITEMS": "200",
        "ALBEDO_BENCH_ITERS": "1", "ALBEDO_BENCH_MEAN_STARS": "6",
        "ALBEDO_BENCH_GEMM_N": "256", "ALBEDO_BENCH_GEMM_CHAIN": "2",
        "ALBEDO_BENCH_HBM_FLOATS": str(1 << 20),
        "ALBEDO_BENCH_BREAKDOWN": "0",
        "ALBEDO_BENCH_RANKER": "1",
        # Deterministic fault injection: stall the ranker past the watchdog.
        "ALBEDO_BENCH_FAULT_SLEEP": "3600",
        "ALBEDO_BENCH_TIMEOUT": "35",
    })
    proc = subprocess.run(
        [sys.executable, str(bench.__file__)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode != 0, proc.stdout[-500:] + proc.stderr[-500:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["metric"] == "als_train_wallclock_rank50_iter26"
    assert record["value"] is not None and record["value"] > 0
    assert "watchdog" in (record["ranker_error"] or "")
    assert record["status"] == "partial"
    # A CPU run has no published peak: MFU is not measured, never faked.
    assert record["mfu"] is None and "not measured" in record["mfu_peak_source"]


def test_w2v_refscale_record_shape(monkeypatch):
    """Tiny-scale run of the reference-scale W2V bench: the record must state
    corpus volume and throughput so the multiplier is priced per token."""
    monkeypatch.setenv("ALBEDO_BENCH_W2V_TOKENS", "20000")
    monkeypatch.setenv("ALBEDO_BENCH_W2V_VOCAB", "500")
    rec = bench.w2v_refscale_bench()
    assert rec["metric"] == "w2v_train_wallclock_refscale"
    assert rec["corpus_tokens"] == 20000
    assert rec["value"] > 0 and rec["epoch_tokens_per_s"] > 0
    assert rec["vocab_size"] > 0
    assert "scale_note" in rec and "unpublished" in rec["scale_note"]
    assert rec["backend"] and rec["device_kind"]
    assert rec["virtual_devices"] >= 0


def test_watchdog_partial_status_field():
    """The watchdog re-emit carries status=partial (ADVICE r4 #1 contract)."""
    record = bench.error_record("x", "y")
    assert "status" not in record  # hard failures carry stage/error instead
    # The error-record shape is pinned by the failure contract: hardware
    # provenance is a success-record stamp only.
    for key in ("backend", "virtual_devices"):
        assert key not in record


def test_hardware_fields_shape(monkeypatch):
    """Every scenario record carries hardware provenance: backend,
    device_kind, and the forced-virtual device count (0 on real chips)."""
    fields = bench.hardware_fields()
    assert set(fields) == {"backend", "device_kind", "virtual_devices"}
    assert fields["backend"] and fields["device_kind"]
    # Under the test harness CPU is forced to 8 virtual devices; either way
    # the field is a non-negative int, and 0 whenever nothing is forced.
    assert isinstance(fields["virtual_devices"], int)
    assert fields["virtual_devices"] >= 0
    monkeypatch.setenv("XLA_FLAGS", "")
    assert bench.hardware_fields()["virtual_devices"] == 0


@pytest.mark.slow
def test_retrieval_scenario_record_shape(monkeypatch):
    """Micro-size run of the `retrieval` scenario: the parity gate must
    actually run, and the record must carry both arms' latencies, the
    speedup, and the bytes-scanned GB/s model (the RETRIEVAL_r01 shape)."""
    monkeypatch.setenv("ALBEDO_RETRIEVAL_USERS", "300")
    monkeypatch.setenv("ALBEDO_RETRIEVAL_ITEMS", "200")
    monkeypatch.setenv("ALBEDO_RETRIEVAL_CONCURRENCY", "8")
    monkeypatch.setenv("ALBEDO_RETRIEVAL_DURATION", "0.5")
    monkeypatch.setenv("ALBEDO_RETRIEVAL_TRIALS", "1")
    rec = bench.retrieval_bench()
    assert rec["metric"] == "retrieval_candidates_rps"
    assert rec["parity_checked"] > 0
    assert set(rec["sources"]) == {"als", "content", "tfidf"}
    for arm in ("bank", "fanout"):
        assert rec[arm]["rps"] > 0 and rec[arm]["p99_ms"] >= rec[arm]["p50_ms"]
    assert rec["speedup_vs_fanout"] > 0
    assert rec["bytes_scanned_per_query"] == sum(
        s["rows"] * s["dim"] * 4 for s in rec["sources"].values()
    )
    assert rec["backend"] and rec["device_kind"]
    assert rec["virtual_devices"] >= 0


@pytest.mark.slow
def test_scale_scenario_record_shape(monkeypatch, tmp_path):
    """Micro-size run of the `scale` weak-scaling scenario: the record must
    carry the full curve (per-sweep wall-clock, GB/s per chip vs roofline,
    efficiency), the per-stage overlap accounting (explicit warm + separate
    compile reporting, upload-hidden fraction, interleaved sync trials, the
    ring-phase probe), the largest-fittable estimates for both assembly
    modes, and land in MULTICHIP_r07.json."""
    out = tmp_path / "MULTICHIP_r07.json"
    monkeypatch.setenv("ALBEDO_SCALE_USERS_PER_CHIP", "200")
    monkeypatch.setenv("ALBEDO_SCALE_ITEMS", "100")
    monkeypatch.setenv("ALBEDO_SCALE_MEAN_STARS", "5")
    monkeypatch.setenv("ALBEDO_SCALE_SWEEPS", "1")
    monkeypatch.setenv("ALBEDO_SCALE_DEVICES", "1,2")
    monkeypatch.setenv("ALBEDO_SCALE_OUT", str(out))
    rec = bench.scale_bench()
    assert rec["metric"] == "sharded_als_weak_scaling"
    assert [row["n_devices"] for row in rec["weak_scaling"]] == [1, 2]
    for row in rec["weak_scaling"]:
        assert row["per_sweep_s"] > 0
        assert row["achieved_gbps_per_chip"] > 0
        assert row["roofline_frac"] is None  # CPU: no published HBM peak
        assert row["streamed_buckets_per_sweep"] > 0
        assert row["n_users"] == 200 * row["n_devices"]  # fixed work per chip
        # Compile is warmed out of the trials and reported separately —
        # a trial median can never land on a compile-bearing sweep.
        assert row["compile"]["warm_sweeps"] >= 2
        assert row["compile"]["warmup_compile_s"] >= 0
        ov = row["overlap"]
        assert ov["sync_per_sweep_s"] > 0
        assert ov["upload_s_per_sweep"] >= 0
        assert ov["prefetch_wait_s_per_sweep"] >= 0
        if ov["upload_hidden_frac"] is not None:
            assert 0 <= ov["upload_hidden_frac"] <= 1
        # Elasticity cost is visible, not silent: per-rung mesh events +
        # the measured sweep-boundary checkpoint overhead.
        me = row["mesh_events"]
        assert me["losses"] == 0 and me["resumes"] == 0
        assert me["checkpoint_s"] > 0
        assert me["checkpoint_overhead_frac_per_sweep"] >= 0
    assert rec["weak_scaling"][0]["efficiency_vs_1chip"] == 1.0
    assert rec["roofline_gbps_per_chip"] is None
    assert rec["pipeline"] == "on"
    # The ring probe must RUN: a swallowed error here hid ring mode being
    # dead on the installed JAX for nine PRs.
    probe = rec["ring_overlap_probe"]
    assert probe["overlapped_per_sweep_s"] > 0 and probe["sync_per_sweep_s"] > 0
    for mode in ("allgather", "ring"):
        assert rec["largest_fittable"][mode]["max_users"] > 0
    assert json.loads(out.read_text())["metric"] == "sharded_als_weak_scaling"
    assert rec["backend"] and rec["device_kind"]
    assert rec["virtual_devices"] >= 0


@pytest.mark.slow
def test_scoring_scenario_record_shape(monkeypatch, tmp_path):
    """Micro-size run of the `scoring` scenario: the record must carry
    users/s per chip, chip-seconds per million users, the canary score the
    publish was gated on, and the analytic 10M x 1M out-of-core admission
    (both rungs' bytes + the ladder verdict), and land in SCORING_r01.json."""
    out = tmp_path / "SCORING_r01.json"
    monkeypatch.setenv("ALBEDO_SCORING_USERS", "150")
    monkeypatch.setenv("ALBEDO_SCORING_ITEMS", "100")
    monkeypatch.setenv("ALBEDO_SCORING_SHARD_USERS", "64")
    monkeypatch.setenv("ALBEDO_SCORING_K", "10")
    monkeypatch.setenv("ALBEDO_SCORING_OUT", str(out))
    rec = bench.scoring_bench()
    assert rec["metric"] == "score_all_users_per_s_per_chip"
    assert rec["value"] > 0
    assert rec["chip_seconds_per_million_users"] > 0
    assert rec["users_scored"] > 0 and rec["rows_spilled"] > 0
    assert rec["n_shards"] >= 2  # shard_users=64 over >=100 matrix users
    assert 0.0 <= rec["canary_ndcg30"] <= 1.0
    assert rec["admission"]["workload"].startswith("score")
    ooc = rec["out_of_core_10m_x_1m"]
    assert ooc["n_users"] == 10_000_000 and ooc["n_items"] == 1_000_000
    # The streamed rung trades transient query memory for resident tables:
    # its footprint must be strictly cheaper than the resident rung's.
    assert 0 < ooc["streamed_bytes"] < ooc["resident_bytes"]
    assert ooc["verdict"]["workload"] == "score"
    assert ooc["est_chip_hours"] > 0
    assert rec["backend"] and rec["device_kind"]
    assert rec["virtual_devices"] >= 0
    assert json.loads(out.read_text())["metric"] == "score_all_users_per_s_per_chip"
