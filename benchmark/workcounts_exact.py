"""The work the EXACT solve of an ALS sweep requires, from the configuration
and the logical matrix only - never from bucket shapes, slot rows, pieces,
line tables, or anything the program reports (``workcounts.py``'s rule: a PR
that pads less or factorises fewer empty systems must not lower its own
denominator).

One sweep = item half-sweep + user half-sweep; ``k`` the rank, ``rows`` the
logical rows of both tables.

- bytes: each star's source factor row read once per half-sweep
  (``2 nnz 4k``) and each solved row written once (``rows 4k``).
- FLOPs: per star and half-sweep its share of the correction ``y y^T`` and of
  the b-vector (``2 k^2 + 3 k``); per row the factorisation (``k^3 / 3``) and
  the two triangular solves with the system's assembly (``4 k^2``): the
  ``"cholesky"`` constants of ``workcounts.sweep_flops``, less the Gramians,
  which are not under the solve's scope.
"""

from __future__ import annotations

from benchmark.peaks import peaks_for


def solve_bytes(n_users: int, n_items: int, nnz: int, rank: int) -> float:
    return 2.0 * nnz * rank * 4 + (n_users + n_items) * rank * 4.0


def solve_flops(n_users: int, n_items: int, nnz: int, rank: int) -> float:
    k = float(rank)
    return 2.0 * nnz * (2.0 * k * k + 3.0 * k) + (n_users + n_items) * (k**3 / 3.0 + 4.0 * k * k)


def config_counts(config: dict) -> dict:
    """Bytes and FLOPs of the exact solve per sweep of a fit configuration's
    file, which must state the exact solver."""
    if config["solver"] != "cholesky":
        raise ValueError(f"the configuration's solver is {config['solver']!r}, not the exact solve")
    args = (config["n_users"], config["n_items"], config["nnz"], config["rank"])
    return {"bytes_per_sweep": solve_bytes(*args), "flops_per_sweep": solve_flops(*args)}


def least_solve_seconds(config: dict, device_kind: str) -> dict:
    """The least time the chip could take for one sweep's exact solves, and
    which peak bounds it."""
    counts = config_counts(config)
    peaks = peaks_for(device_kind)
    t_flops = counts["flops_per_sweep"] / peaks["bf16_flops"]
    t_bytes = counts["bytes_per_sweep"] / peaks["hbm_bytes_per_s"]
    return {**counts, "least_s": max(t_flops, t_bytes),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
