"""The comparison that decides ``correct`` for a fit cell: the factor tables
the timed entry produced against the plain reference's, row by row.

A row's error is the norm of the difference over the reference row's norm or
the median row's, whichever is larger (some rows are all but zero). Every
number has a limit of its own, which the configuration's file states;
``PERF.md`` section 2 gives the readings each was set from.
"""

from __future__ import annotations

import numpy as np


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    want = np.asarray(want, np.float64)
    diff = np.linalg.norm(np.asarray(got, np.float64) - want, axis=1)
    norms = np.linalg.norm(want, axis=1)
    return diff / np.maximum(norms, np.median(norms))


def compare_fit(got_user, got_item, want_user, want_item, stars: dict, min_stars: int) -> dict:
    """The numbers compared, by their short names. Per table: the worst row,
    the 99th percentile and the median over the rows with at least
    ``min_stars`` stars, and the worst of ALL rows. (Rows with few stars have
    all-but-zero factors whose three CG steps amplify rounding: on sound runs
    they read as the control does, so they are held to the coarse number
    only — a rule on the logical matrix, the same for both tables.)"""
    out = {}
    for side, got, want, ids in (("user", got_user, want_user, stars["rows"]),
                                 ("item", got_item, want_item, stars["cols"])):
        got, names = np.asarray(got), ("rows_worst", "rows_p99", "rows_median", "all_rows_worst")
        if got.shape != np.asarray(want).shape or not np.isfinite(got).all():
            out.update({f"{side}_{n}": float("inf") for n in names})
            continue
        err = row_errors(got, want)
        heavy = err[np.bincount(ids, minlength=err.size) >= min_stars]
        if heavy.size == 0:
            raise ValueError(f"no {side} row has {min_stars} stars: nothing would be compared")
        out[f"{side}_rows_worst"] = float(heavy.max())
        out[f"{side}_rows_p99"] = float(np.percentile(heavy, 99))
        out[f"{side}_rows_median"] = float(np.median(heavy))
        out[f"{side}_all_rows_worst"] = float(err.max())
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, compared)``: each number beside its limit. A number with
    no limit, or a limit with no number, is a fault of the files."""
    if set(numbers) != set(limits):
        raise ValueError(f"numbers {sorted(numbers)} and limits {sorted(limits)} differ")
    compared = {k: {"value": numbers[k], "limit": float(limits[k])} for k in sorted(numbers)}
    return all(v["value"] <= v["limit"] for v in compared.values()), compared
