"""Seeded star matrices at a configuration's published counts, in memory.

The degree sequences (how many stars each user gives and each item gets) are
a FIXED function of the configuration: every seed has the same multiset of
row lengths on both sides, so every seed does the same work and the program's
bucket shapes — and with them its compiled executable — are the same for
every seed. The seed decides which user and which item has which degree, who
is paired with whom, and the values.

Pairing is the configuration model (shuffle the item stubs against the user
stubs); then each duplicate (user, item) pair trades its item with a random
partner edge, where the trade makes no pair that exists, until no duplicate
is left — which keeps both degree sequences exact.
(The sampling laws — lognormal activity, power-law popularity — are those of
``albedo_tpu/datasets/synthetic.py:generate_scale_dataset``; that generator
writes shards to disk chunk by chunk and dedupes by dropping, so it can hold
neither the counts nor the shapes fixed.)
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def degree_sequence(n: int, total: int, law: dict) -> np.ndarray:
    """``n`` integer degrees, descending, summing to ``total`` exactly, each
    in ``[law.min, law.max]``: the quantiles of the law, scaled."""
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

    def dealt(c: float) -> np.ndarray:
        return np.clip(np.floor(c * w), lo, hi).astype(np.int64)

    c_lo, c_hi = 0.0, 1.0
    while dealt(c_hi).sum() < total:
        c_hi *= 2.0
    for _ in range(200):  # largest scale that does not overshoot
        mid = 0.5 * (c_lo + c_hi)
        if dealt(mid).sum() <= total:
            c_lo = mid
        else:
            c_hi = mid
    deg = dealt(c_lo)
    rem = int(total - deg.sum())
    while rem > 0:  # the remainder goes to the largest rows still under the cap
        room = np.flatnonzero(deg < hi)[:rem]
        deg[room] += 1
        rem -= room.size
    return deg


def _sorted_keys_and_duplicates(
    u_stub: np.ndarray, items: np.ndarray, indptr: np.ndarray, n_items: int, workers: int
) -> tuple[np.ndarray, np.ndarray]:
    """All (user, item) keys, sorted, and the positions of every second and
    later occurrence of a pair. Duplicates live inside one user's segment, so
    user-aligned chunks are sorted independently (the sorts release the GIL)
    and their concatenation is sorted as a whole."""
    n = u_stub.shape[0]
    cuts = np.searchsorted(indptr, np.linspace(0, n, workers + 1)[1:-1])
    bounds = np.unique(np.concatenate([[0], indptr[cuts], [n]]))

    def chunk(i: int) -> tuple[np.ndarray, np.ndarray]:
        a, b = int(bounds[i]), int(bounds[i + 1])
        key = u_stub[a:b].astype(np.int64) * n_items + items[a:b]
        order = np.argsort(key, kind="stable")
        ks = key[order]
        return ks, a + order[1:][ks[1:] == ks[:-1]]

    with ThreadPoolExecutor(max_workers=workers) as pool:
        parts = list(pool.map(chunk, range(len(bounds) - 1)))
    return np.concatenate([p[0] for p in parts]), np.concatenate([p[1] for p in parts])


def _contains(sorted_keys: np.ndarray, x: np.ndarray, pool: ThreadPoolExecutor,
              workers: int) -> np.ndarray:
    """Which of ``x`` are in ``sorted_keys`` (random probes miss the cache, so
    the probes are split over the threads; ``searchsorted`` releases the GIL)."""
    if sorted_keys.size == 0:
        return np.zeros(x.shape, bool)

    def part(q):
        pos = np.minimum(np.searchsorted(sorted_keys, q), sorted_keys.size - 1)
        return sorted_keys[pos] == q

    return np.concatenate(list(pool.map(part, np.array_split(x, workers))))


def _trade_duplicates_away(dup, keys, u_stub, items, indptr, n_items, rng, workers) -> None:
    """Repairs, in place: each duplicate edge (u, i) trades items with a
    random partner edge (u', i') where that makes no pair that exists
    already or that another trade makes, round after round until none is
    left. ``keys`` is the sorted pair set BEFORE any trade; pairs that trades
    have since removed still count as existing, which only refuses some
    trades that would have been sound."""
    n = u_stub.shape[0]
    was_dup = np.zeros(n, bool)
    was_dup[dup] = True
    added = np.zeros(0, np.int64)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for _ in range(500):
            if dup.size == 0:
                return
            # a heavy user's surplus copies of a popular item find few partners
            # that fit, so when few duplicates are left each gets many offers
            offers = np.repeat(dup, max(1, min(256, 4_000_000 // dup.size)))
            # partners are a random edge of a random USER: light users, who
            # lack the popular items that duplicates are made of, come up
            # as often as heavy ones
            users = rng.integers(0, indptr.size - 1, size=offers.size)
            partner = indptr[users] + (rng.random(offers.size) * (indptr[users + 1] - indptr[users])).astype(np.int64)
            first = np.zeros(offers.size, bool)
            first[np.unique(partner, return_index=True)[1]] = True
            u_d, i_d = u_stub[offers].astype(np.int64), items[offers]
            u_p, i_p = u_stub[partner].astype(np.int64), items[partner]
            new_d, new_p = u_d * n_items + i_p, u_p * n_items + i_d
            ok = first & ~was_dup[partner]
            for new in (new_d, new_p):
                live = np.flatnonzero(ok)
                ok[live] = ~_contains(keys, new[live], pool, workers)
                live = np.flatnonzero(ok)
                ok[live] = ~_contains(added, new[live], pool, workers)
            live = np.flatnonzero(ok)
            live = live[np.unique(offers[live], return_index=True)[1]]  # one trade an edge
            made = np.concatenate([new_d[live], new_p[live]])
            uniq, counts = np.unique(made, return_counts=True)
            clash = uniq[counts > 1]
            if clash.size:
                live = live[~(np.isin(new_d[live], clash) | np.isin(new_p[live], clash))]
            items[offers[live]], items[partner[live]] = i_p[live], i_d[live]
            added = np.sort(np.concatenate([added, new_d[live], new_p[live]]))
            dup = np.setdiff1d(dup, offers[live], assume_unique=True)
    raise RuntimeError("duplicate pairs did not clear; the degrees are not dealable")


def generate_stars(config: dict, seed: int, workers: int | None = None) -> dict:
    """``rows, cols, vals`` (int32, int32, float32) of the configuration's
    matrix for this seed, rows sorted by user."""
    n_users, n_items, nnz = config["n_users"], config["n_items"], config["nnz"]
    workers = workers or min(16, os.cpu_count() or 1)
    rng = np.random.default_rng([int(seed), 0x5747])
    user_deg = degree_sequence(n_users, nnz, config["user_degrees"])[rng.permutation(n_users)]
    item_deg = degree_sequence(n_items, nnz, config["item_degrees"])[rng.permutation(n_items)]
    indptr = np.zeros(n_users + 1, np.int64)
    np.cumsum(user_deg, out=indptr[1:])
    u_stub = np.repeat(np.arange(n_users, dtype=np.int32), user_deg)
    items = np.repeat(np.arange(n_items, dtype=np.int32), item_deg)[rng.permutation(nnz)]
    keys, dup = _sorted_keys_and_duplicates(u_stub, items, indptr, n_items, workers)
    _trade_duplicates_away(dup, keys, u_stub, items, indptr, n_items, rng, workers)
    values = config["values"]
    levels = np.asarray(values["levels"], np.float32)
    if levels.size == 1:
        vals = np.full(nnz, levels[0], np.float32)
    else:
        vals = levels[rng.choice(levels.size, size=nnz, p=np.asarray(values["weights"]))]
    return {"rows": u_stub, "cols": items, "vals": vals,
            "n_users": n_users, "n_items": n_items}
