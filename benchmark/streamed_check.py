"""The comparison of a fit whose tables do not fit twice: the plain
reference and ``compare.py``'s numbers, unchanged in what they compute, in
memory that a 10M-row table leaves.

At ``gh10m-r128`` one factor table is 5.12 GB. ``reference/als_cg.py:fit``
lands a half-sweep by concatenating every solved block and gathering from the
pool, five tables' worth beside the blocks (a one-chip machine has 16.9 GB),
and ``compare.row_errors`` holds three float64 copies of a table, 30.7 GB
beside the two float32 pairs (the machine has 40 GiB). Neither file is
edited. Here:

- ``reference_fit`` runs THE REFERENCE'S OWN functions (``csr_of``,
  ``build_side``, ``init_factors``, ``solve_block``: its blocks, its init,
  its arithmetic) and lands each solved block into the donated table as soon
  as it is solved. A row sits in one block and its warm start is read before
  its block lands, so the tables are those of ``reference.fit`` bit for bit.
- ``compare_fit`` gives ``compare.compare_fit``'s eight numbers from row
  errors computed a block of rows at a time, in float64 buffers made once
  (``row_errors``; fresh 268 MB temporaries a block were 57 GB of first-touch
  page faults, 7 s to 70 s of a run on the chip's host).

``tests/perfbench/test_perfbench_streamed.py`` holds both to equality with
the originals on a seeded matrix.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 14


@functools.cache
def _land():
    import jax

    # rows of -1 (a block's padding slots) fall outside the table and drop
    return jax.jit(
        lambda table, rows, solved: table.at[rows].set(solved.astype(table.dtype), mode="drop"),
        donate_argnums=0,
    )


def _half_sweep(reference, source, target, side: dict, n_rows: dict, reg, alpha, cg_steps, dtype):
    import jax
    import jax.numpy as jnp

    yty = jnp.matmul(source.T, source, precision=jax.lax.Precision.HIGHEST)
    for (row_of, idx, val, mask), real in zip(side["blocks"], n_rows):
        per = idx.shape[1]
        for j in range(idx.shape[0]):
            solved = reference.solve_block(
                source, yty, target[row_of[j]], idx[j], val[j], mask[j],
                jnp.asarray(reg, dtype), jnp.asarray(alpha, dtype), cg_steps, dtype,
            )
            live = jnp.arange(per) < real - j * per      # the slots that hold a row
            target = _land()(target, jnp.where(live, row_of[j], target.shape[0]), solved)
    return target


def _rows_per_group(side: dict) -> list[int]:
    """How many of each width group's slots hold a row: ``build_side`` fills
    them first and numbers them in its landing map in that order."""
    landing = np.asarray(side["landing"])
    landed = np.sort(landing[landing >= 0])
    out, offset = [], 0
    for _, idx, _, _ in side["blocks"]:
        slots = idx.shape[0] * idx.shape[1]
        out.append(int(np.searchsorted(landed, offset + slots) - np.searchsorted(landed, offset)))
        offset += slots
    return out


def reference_fit(reference, stars: dict, config: dict, seed: int, sweeps: int, dtype=None):
    """``reference.fit(stars, config, seed, sweeps, dtype)`` in one table's
    worth of device memory beside the blocks."""
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    n_users, n_items = stars["n_users"], stars["n_items"]
    user_side = reference.build_side(
        *reference.csr_of(stars["rows"], stars["cols"], stars["vals"], n_users))
    item_side = reference.build_side(
        *reference.csr_of(stars["cols"], stars["rows"], stars["vals"], n_items))
    user_rows, item_rows = _rows_per_group(user_side), _rows_per_group(item_side)
    uf, vf = reference.init_factors(seed, n_users, n_items, config["rank"])
    uf, vf = uf.astype(dtype), vf.astype(dtype)
    args = (config["reg_param"], config["alpha"], config["cg_steps"], dtype)
    for _ in range(sweeps):
        vf = _half_sweep(reference, uf, vf, item_side, item_rows, *args)
        uf = _half_sweep(reference, vf, uf, user_side, user_rows, *args)
    return np.asarray(uf, np.float32), np.asarray(vf, np.float32)


def _blocks(n_rows: int):
    return (slice(a, a + BLOCK_ROWS) for a in range(0, n_rows, BLOCK_ROWS))


def row_errors(got: np.ndarray, want: np.ndarray, workers: int = 4) -> np.ndarray:
    """``compare.row_errors(got, want)``, a block of rows at a time on a few
    threads: ``np.linalg.norm(x, axis=1)`` is ``sqrt(add.reduce(x * x, axis=1))``."""
    n, k = want.shape
    norms, diff = np.empty(n, np.float64), np.empty(n, np.float64)
    blocks = list(_blocks(n))

    def part(mine) -> None:
        w, g = np.empty((BLOCK_ROWS, k), np.float64), np.empty((BLOCK_ROWS, k), np.float64)
        for rows in mine:
            m = len(range(*rows.indices(n)))
            np.copyto(w[:m], want[rows])
            np.copyto(g[:m], got[rows])
            np.subtract(g[:m], w[:m], out=g[:m])
            for x, out in ((w[:m], norms[rows]), (g[:m], diff[rows])):
                np.multiply(x, x, out=x)
                np.add.reduce(x, axis=1, out=out)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(part, [blocks[i::workers] for i in range(workers)]))
    np.sqrt(norms, out=norms)
    np.sqrt(diff, out=diff)
    return diff / np.maximum(norms, np.median(norms))


def _all_finite(table: np.ndarray) -> bool:
    flags = np.empty((BLOCK_ROWS,) + table.shape[1:], bool)
    return all(np.isfinite(table[rows], out=flags[:len(table[rows])]).all()
               for rows in _blocks(table.shape[0]))


def numbers_of(err: np.ndarray, degrees: np.ndarray, min_stars: int) -> dict:
    """One table's four numbers from its row errors and star counts."""
    heavy = err[degrees >= min_stars]
    if heavy.size == 0:
        raise ValueError(f"no row has {min_stars} stars: nothing would be compared")
    return {"rows_worst": float(heavy.max()), "rows_p99": float(np.percentile(heavy, 99)),
            "rows_median": float(np.median(heavy)), "all_rows_worst": float(err.max())}


def compare_fit(got_user, got_item, want_user, want_item, stars: dict, min_stars: int) -> dict:
    """``compare.compare_fit``'s numbers under the same names."""
    out = {}
    for side, got, want, ids in (("user", got_user, want_user, stars["rows"]),
                                 ("item", got_item, want_item, stars["cols"])):
        got = np.asarray(got)
        if got.shape != np.asarray(want).shape or not _all_finite(got):
            out.update({f"{side}_{n}": float("inf")
                        for n in ("rows_worst", "rows_p99", "rows_median", "all_rows_worst")})
            continue
        err = row_errors(got, want)
        degrees = np.bincount(ids, minlength=err.size)
        out.update({f"{side}_{k}": v for k, v in numbers_of(err, degrees, min_stars).items()})
    return out
