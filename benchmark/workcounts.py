"""The work an ALS sweep REQUIRES, from the configuration and the logical
matrix only — never from bucket shapes, padding, the number of passes the
implementation makes, or anything the program reports. A PR that pads less or
re-reads less must not lower its own denominator.

One sweep = item half-sweep + user half-sweep.

- bytes: each star's source factor row gathered once per half-sweep with its
  index and value, plus every factor row read and written once.
- FLOPs: the configuration's stated solver on ``nnz`` logical entries and
  ``n_users + n_items`` logical rows (per-entry and per-row constants from
  ``bench.py:als_fit_flops``), plus the two Gramians.
"""

from __future__ import annotations

from benchmark.peaks import peaks_for


def sweep_bytes(n_users: int, n_items: int, nnz: int, rank: int) -> float:
    return 2.0 * nnz * (rank * 4 + 8) + 2.0 * (n_users + n_items) * rank * 4


def sweep_flops(
    n_users: int, n_items: int, nnz: int, rank: int, solver: str, cg_steps: int
) -> float:
    k = float(rank)
    rows = float(n_users + n_items)
    if solver == "cg":
        per_entry = 9.0 * k + cg_steps * 4.0 * k
        per_row = 2.0 * k * k + cg_steps * (2.0 * k * k + 10.0 * k)
    elif solver == "cholesky":
        per_entry = 2.0 * k * k + 3.0 * k
        per_row = k**3 / 3.0 + 4.0 * k * k
    else:
        raise ValueError(f"unknown solver {solver!r}")
    # each star is an entry of BOTH half-sweeps; YtY once per half-sweep
    return 2.0 * nnz * per_entry + rows * per_row + 2.0 * rows * k * k


def config_counts(config: dict) -> dict:
    """Bytes and FLOPs per sweep of a fit configuration's file."""
    args = (config["n_users"], config["n_items"], config["nnz"], config["rank"])
    return {
        "bytes_per_sweep": sweep_bytes(*args),
        "flops_per_sweep": sweep_flops(*args, config["solver"], config["cg_steps"]),
    }


def least_sweep_seconds(config: dict, device_kind: str) -> dict:
    """The least time the chip could take for one sweep, and which peak
    bounds it."""
    counts = config_counts(config)
    peaks = peaks_for(device_kind)
    t_flops = counts["flops_per_sweep"] / peaks["bf16_flops"]
    t_bytes = counts["bytes_per_sweep"] / peaks["hbm_bytes_per_s"]
    return {
        **counts,
        "least_s": max(t_flops, t_bytes),
        "bound": "bytes" if t_bytes >= t_flops else "flops",
    }
