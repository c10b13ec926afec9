"""``stars.generate_stars`` for a configuration of 10M rows: the same matrix,
value for value, in half the time.

At ``gh10m-r128`` the generator that is there takes 102-107 s of a run on the
chip's host, half of it in ``degree_sequence``: 200 bisection steps, each
three passes over 10M float64 rows on one thread, long after the bracket has
closed to two neighbouring floats. ``stars.py`` is not edited. Here:

- ``degree_sequence`` stops at the step whose midpoint is one of the
  bracket's ends — from there on the loop in ``stars.py`` changes nothing —
  and counts a scale's stars over the threads, into buffers made once. The
  sum is of whole numbers under 2**53, so the split changes no value;
- the three permutations are drawn in the generator's order while the degree
  sequences and the stub arrays are made on another thread (a permutation
  releases the GIL), and the 100M-entry take is split over the threads.

Pairing, the sort and the duplicate trades are ``stars.py``'s own functions.
``tests/perfbench/test_perfbench_streamed.py`` holds ``degree_sequence`` and
``generate_stars`` to equality with the originals.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import stars


def degree_sequence(n: int, total: int, law: dict, pool: ThreadPoolExecutor,
                    workers: int) -> np.ndarray:
    """``stars.degree_sequence(n, total, law)``."""
    lo, hi = int(law["min"]), int(law["max"])
    if not lo * n <= total <= hi * n:
        raise ValueError(f"{total} stars cannot be dealt to {n} rows in [{lo}, {hi}]")
    if law["law"] == "lognormal":
        from scipy.special import ndtri

        q = (np.arange(n) + 0.5) / n
        w = np.exp(float(law["sigma"]) * ndtri(1.0 - q))
    elif law["law"] == "zipf_mandelbrot":
        w = (np.arange(n) + float(law["offset"])) ** -float(law["exponent"])
    else:
        raise ValueError(f"unknown degree law {law['law']!r}")
    parts = [(part, np.empty_like(part)) for part in np.array_split(w, workers)]

    def stars_at(c: float) -> int:
        def one(pair) -> float:
            part, buf = pair
            np.multiply(part, c, out=buf)
            np.floor(buf, out=buf)
            np.clip(buf, lo, hi, out=buf)
            return float(buf.sum())    # whole numbers under 2**53: exact

        return int(sum(pool.map(one, parts)))

    c_lo, c_hi = 0.0, 1.0
    while stars_at(c_hi) < total:
        c_hi *= 2.0
    for _ in range(200):  # largest scale that does not overshoot
        mid = 0.5 * (c_lo + c_hi)
        closed = mid in (c_lo, c_hi)
        if stars_at(mid) <= total:
            c_lo = mid
        else:
            c_hi = mid
        if closed:
            break
    deg = np.clip(np.floor(c_lo * w), lo, hi).astype(np.int64)
    rem = int(total - deg.sum())
    while rem > 0:  # the remainder goes to the largest rows still under the cap
        room = np.flatnonzero(deg < hi)[:rem]
        deg[room] += 1
        rem -= room.size
    return deg


def _take(source: np.ndarray, order: np.ndarray, pool: ThreadPoolExecutor, workers: int) -> np.ndarray:
    """``source[order]``, the take split over the threads."""
    out = np.empty(order.shape, source.dtype)
    bounds = np.linspace(0, order.size, workers + 1).astype(np.int64)
    list(pool.map(lambda i: np.take(source, order[bounds[i]:bounds[i + 1]],
                                    out=out[bounds[i]:bounds[i + 1]]), range(workers)))
    return out


def generate_stars(config: dict, seed: int, workers: int | None = None) -> dict:
    """``stars.generate_stars(config, seed)``."""
    n_users, n_items, nnz = config["n_users"], config["n_items"], config["nnz"]
    workers = workers or min(16, os.cpu_count() or 1)
    rng = np.random.default_rng([int(seed), 0x5747])
    with ThreadPoolExecutor(max_workers=workers) as pool, ThreadPoolExecutor(max_workers=1) as side:
        degrees = side.submit(lambda: (
            degree_sequence(n_users, nnz, config["user_degrees"], pool, workers),
            degree_sequence(n_items, nnz, config["item_degrees"], pool, workers)))
        user_order, item_order = rng.permutation(n_users), rng.permutation(n_items)

        def stubs():
            user_sorted, item_sorted = degrees.result()
            user_deg, item_deg = user_sorted[user_order], item_sorted[item_order]
            indptr = np.zeros(n_users + 1, np.int64)
            np.cumsum(user_deg, out=indptr[1:])
            return (indptr, np.repeat(np.arange(n_users, dtype=np.int32), user_deg),
                    np.repeat(np.arange(n_items, dtype=np.int32), item_deg))

        made = side.submit(stubs)
        order = rng.permutation(nnz)
        indptr, u_stub, item_stub = made.result()
        items = _take(item_stub, order, pool, workers)
    del item_stub, order
    keys, dup = stars._sorted_keys_and_duplicates(u_stub, items, indptr, n_items, workers)
    stars._trade_duplicates_away(dup, keys, u_stub, items, indptr, n_items, rng, workers)
    values = config["values"]
    levels = np.asarray(values["levels"], np.float32)
    if levels.size == 1:
        vals = np.full(nnz, levels[0], np.float32)
    else:
        vals = levels[rng.choice(levels.size, size=nnz, p=np.asarray(values["weights"]))]
    return {"rows": u_stub, "cols": items, "vals": vals,
            "n_users": n_users, "n_items": n_items}
