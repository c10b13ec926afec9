"""Independent check of trained ALS factors against the normal equations.

The implicit-ALS user update solves ``A_u x_u = b_u`` per user
(Hu-Koren-Volinsky with MLlib's reg-by-count scaling, the system
``ops.als.bucket_solve_body`` builds on device). Recomputing that system in
numpy float64 from the trained ITEM factors and measuring how far the
trained USER row is from solving it says how exact the device solve was —
with no second fit and no device: the correctness gate the bench and
``chip_smoke.py`` share.
"""

from __future__ import annotations

import numpy as np


def normal_eq_residual(
    matrix, model, reg_param: float, alpha: float, n_sample: int = 256, seed: int = 0
) -> dict:
    """Relative residual ``|A_u x_u - b_u| / |b_u|`` of the trained user
    factors on a seeded sample of non-empty user rows.

    The last half-sweep of a fit updates the user factors from the final
    item factors, so an exact f32 solve sits at float32 round-off (~1e-6)
    here. TPU default matmul precision computes the unannotated f32 einsums
    of ``ops/als.py`` with bf16 passes, which is what puts a chip run's
    Cholesky residual near 1e-4 instead; the warm-started CG path adds its
    own small, honest truncation error. Reported, not hidden."""
    rng = np.random.default_rng(seed)
    uf = np.asarray(model.user_factors, dtype=np.float64)
    vf = np.asarray(model.item_factors, dtype=np.float64)
    yty = vf.T @ vf
    k = uf.shape[1]
    indptr, cols, vals = matrix.csr()
    nonempty = np.nonzero(np.diff(indptr) > 0)[0]
    sample = rng.choice(nonempty, size=min(n_sample, nonempty.size), replace=False)
    rel = []
    for u in sample:
        lo, hi = int(indptr[u]), int(indptr[u + 1])
        j, r = cols[lo:hi], vals[lo:hi].astype(np.float64)
        y = vf[j]  # (n_u, k)
        c1 = alpha * r
        a = yty + (y * c1[:, None]).T @ y + reg_param * r.size * np.eye(k)
        b = y.T @ (1.0 + c1)
        rel.append(np.linalg.norm(a @ uf[u] - b) / max(np.linalg.norm(b), 1e-30))
    rel = np.asarray(rel)
    return {
        "rel_residual_median": float(np.median(rel)),
        "rel_residual_p95": float(np.percentile(rel, 95)),
        "rel_residual_max": float(rel.max()),
        "rows_checked": int(rel.size),
    }
