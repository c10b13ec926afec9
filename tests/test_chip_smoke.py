"""``chip_smoke.py`` off the chip: it must refuse any platform but ``tpu``
(non-zero exit, no result line), and its legs must stay runnable — the CPU
rehearsal drives the same legs through the same CLI objects at a tiny size,
so a refactor that breaks the smoke is caught before chip time is spent."""

import json
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

import chip_smoke  # noqa: E402

TINY = chip_smoke.SmokeShape(
    als_users=600, als_items=400, als_mean_stars=12.0,
    two_stage_users=500, two_stage_items=350, two_stage_mean_stars=12.0,
    small=True, w2v_full=False, bursts=(1, 2, 5, 9), two_stage_requests=6,
    foldin_rows=24,
    # CPU f32 is exact to round-off; the chip tolerances are FULL's. The
    # residual bound is CG's: 3 warm-started steps over 8 sweeps is a
    # truncated solve (Cholesky sits at ~1e-6 here).
    residual_max=1e-2, served_score_atol=1e-5, bank_parity_atol=1e-5,
    mesh_factor_atol=1e-4, mesh_foldin_atol=1e-4,
)


def test_refuses_a_platform_that_is_not_tpu(capsys):
    """No CPU mode, no fallback: off the chip the smoke exits non-zero and
    prints no result line (the driver requires exactly that in a sandbox)."""
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out  # it says what it found...
    assert '"ok"' not in out  # ...and claims nothing


def test_writes_only_under_its_output_and_cache_directories():
    assert chip_smoke.OUT_DIR == Path(chip_smoke.__file__).resolve().parent / (
        "chiprun_out/chip_smoke"
    )
    ignored = (Path(chip_smoke.__file__).parent / ".gitignore").read_text().split()
    assert "chiprun_out/" in ignored and ".jax-cache/" in ignored


def test_metrics_page_reduction():
    page = (
        '# HELP albedo_degraded_total x\n'
        'albedo_degraded_total{reason="ranker_timeout"} 2\n'
        'albedo_degraded_total{reason="bank_error"} 1\n'
        'albedo_requests_total{route="recommend",status="200"} 40\n'
        'albedo_requests_total{route="recommend",status="429"} 3\n'
    )
    m = chip_smoke.parse_metrics(page)
    assert chip_smoke.metric_total(m, "albedo_degraded_total") == 3
    assert chip_smoke.metric_total(m, "albedo_requests_total", status="429") == 3
    assert chip_smoke.metric_total(m, "albedo_missing_total") == 0


def test_warm_gate_flags_a_fresh_compile_of_als_and_lr_programs():
    def leg(programs):
        return {"compile": {"programs": programs}}

    reused = {
        "als_init_fit_fused": {"compile_source": "disk", "branch": "disk-verified"},
        "lr_lbfgs_fit": {"compile_source": "compile",
                         "branch": "plain-compile+xla-cache-verified"},
        # Not an ALS/LR program: outside the gate.
        "w2v_epoch": {"compile_source": "compile", "branch": "serialize-failed"},
    }
    assert chip_smoke.warm_gate({"legs": {"A": leg(reused)}}) == []
    fresh = {"als_init_fit_fused": {"compile_source": "compile",
                                    "branch": "exported+fingerprinted"}}
    bad = chip_smoke.warm_gate({"legs": {"A": leg(fresh)}})
    assert len(bad) == 1 and "als_init_fit_fused" in bad[0]


@pytest.mark.slow
def test_cpu_rehearsal_of_every_leg(tmp_path, capsys):
    """All four legs, tiny, on the 8 virtual CPU devices: every check that
    does not need the chip's own numbers must pass."""
    summary = chip_smoke.run_legs(TINY, tmp_path / "out", len(jax.devices()))
    assert sorted(summary["legs"]) == ["A", "B", "C", "D"]
    assert summary["failed"] == [], json.dumps(summary["failed"], indent=1)
    assert all(v == 0 for v in summary["counters"].values())
    # The compile ledger saw every program family on the path.
    seen = {n for leg in summary["legs"].values() for n in leg["compile"]["programs"]}
    assert {"als_init_fit_fused", "serve_topk", "lr_lbfgs_fit", "lr_block_logits",
            "w2v_epoch", "retrieval_query", "stream_foldin_sharded"} <= seen
