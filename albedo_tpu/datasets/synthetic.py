"""Synthetic star-matrix generator with planted low-rank structure.

The reference's dataset (``albedo.sql``, crawled from the GitHub API) is not
distributable with this repo, so tests and benchmarks use a generator that
reproduces its statistical shape: a power-law item popularity (GitHub stars),
power-law user activity, and a low-rank preference structure that implicit ALS
can recover — so ranking metrics behave like the reference's (ALS >> popularity
baseline >> random, cf. BASELINE.md).

Generation: scores S = signal_scale * U V^T / sqrt(rank) + popularity logit;
each user stars their Gumbel-top-k items, i.e. samples without replacement from
softmax(S / temperature). ``signal_scale`` sets how much personalization
dominates popularity + Gumbel noise — at the default, a tuned ALS beats the
popularity baseline by a wide margin, mirroring the reference's metric gap
(0.052 vs 0.002, BASELINE.md).
"""

from __future__ import annotations

import json
import os
from pathlib import Path

import numpy as np

from albedo_tpu.datasets.star_matrix import StarMatrix


def synthetic_stars(
    n_users: int = 2000,
    n_items: int = 1000,
    rank: int = 16,
    mean_stars: float = 30.0,
    popularity_alpha: float = 1.0,
    signal_scale: float = 4.0,
    temperature: float = 1.0,
    seed: int = 42,
    chunk: int = 2048,
) -> StarMatrix:
    """Sample an implicit-feedback star matrix.

    Returns a ``StarMatrix`` whose raw ids are offset from the dense indices
    (users +1_000_000, items +5_000_000) so tests exercise the reindex maps.
    """
    rng = np.random.default_rng(seed)
    # Unit-variance per-pair preference signal, scaled by signal_scale.
    scale = np.sqrt(signal_scale / np.sqrt(rank))
    u_fac = rng.normal(0.0, scale, size=(n_users, rank)).astype(np.float32)
    v_fac = rng.normal(0.0, scale, size=(n_items, rank)).astype(np.float32)

    # Zipf-ish popularity logit: item j gets -alpha * log(rank_j).
    pop_rank = rng.permutation(n_items) + 1
    pop_logit = (-popularity_alpha * np.log(pop_rank)).astype(np.float32)

    # Per-user activity: lognormal, clipped to [1, n_items // 2].
    n_stars = np.clip(
        rng.lognormal(np.log(mean_stars), 0.9, size=n_users).astype(np.int64),
        1,
        max(1, n_items // 2),
    )

    rows_parts: list[np.ndarray] = []
    cols_parts: list[np.ndarray] = []
    for lo in range(0, n_users, chunk):
        hi = min(lo + chunk, n_users)
        scores = (u_fac[lo:hi] @ v_fac.T + pop_logit) / temperature
        gumbel = rng.gumbel(size=scores.shape).astype(np.float32)
        noisy = scores + gumbel
        kmax = int(n_stars[lo:hi].max())
        # Gumbel-top-k == sampling w/o replacement from softmax(scores).
        # argpartition returns the top-kmax unordered; sort within it so the
        # per-user :k slice really is that user's top-k by noisy score.
        part = np.argpartition(-noisy, kmax - 1, axis=1)[:, :kmax]
        inner = np.argsort(np.take_along_axis(-noisy, part, axis=1), axis=1)
        top = np.take_along_axis(part, inner, axis=1)
        for r in range(hi - lo):
            k = int(n_stars[lo + r])
            cols_parts.append(top[r, :k].astype(np.int32))
            rows_parts.append(np.full(k, lo + r, dtype=np.int32))

    rows = np.concatenate(rows_parts)
    cols = np.concatenate(cols_parts)
    return StarMatrix.from_interactions(
        raw_users=rows.astype(np.int64) + 1_000_000,
        raw_items=cols.astype(np.int64) + 5_000_000,
        vals=np.ones(rows.shape[0], dtype=np.float32),
    )


# --- out-of-core scale harness -------------------------------------------------
#
# The ALX-scale sharded fit (parallel.als.ShardedALSFit) is built so the star
# matrix never needs to be device-resident whole; this generator makes sure it
# never needs to be HOST-resident whole either. Interactions are generated per
# user chunk (power-law activity, Zipf item popularity sampled by inverse
# CDF), spilled to per-item-range partition files on disk, and packed into the
# SAME fixed-shape padded buckets the training sweeps consume
# (``datasets.ragged``) — user side per generation chunk, item side per spill
# partition. Peak host memory is one chunk/partition, so the parameters scale
# to 10M users x 1M repos / 1B+ nnz (the spill is ~8 bytes/nnz on disk) while
# CI exercises the identical code path at toy sizes.


class ScaleDataset:
    """A disk-backed bucket-packed star matrix (see module comment above).

    Layout under ``root``: ``meta.json``, ``user-buckets/chunk-*.npz`` (one
    file per generation chunk, each holding that chunk's padded buckets),
    ``item-buckets/part-*.npz`` (one per item partition), and
    ``pairs/part-*.bin`` (the raw (row, col) int32 spill the item side was
    built from — kept for :meth:`to_star_matrix` and auditability).
    """

    def __init__(self, root: str | Path):
        self.root = Path(root)
        self.meta = json.loads((self.root / "meta.json").read_text())

    @property
    def n_users(self) -> int:
        return int(self.meta["n_users"])

    @property
    def n_items(self) -> int:
        return int(self.meta["n_items"])

    @property
    def nnz(self) -> int:
        return int(self.meta["nnz"])

    def _bucket_files(self, side: str) -> list[Path]:
        sub = {"user": "user-buckets", "item": "item-buckets"}[side]
        return sorted((self.root / sub).glob("*.npz"))

    @staticmethod
    def _load_bucket_file(path: Path) -> list:
        from albedo_tpu.datasets.ragged import Bucket

        with np.load(path) as z:
            n = int(z["n_buckets"])
            return [
                Bucket(
                    row_ids=z[f"b{i}_row_ids"],
                    idx=z[f"b{i}_idx"],
                    val=z[f"b{i}_val"],
                    mask=z[f"b{i}_mask"],
                )
                for i in range(n)
            ]

    def iter_buckets(
        self,
        side: str,
        readahead: bool = True,
        coalesce: bool = False,
    ):
        """Yield the stored padded buckets for one half-sweep, file by file.

        With ``readahead`` (the default) the
        NEXT file is read and parsed on a background thread while the
        current file's buckets are consumed — the disk I/O side of the
        pipelined sharded dataflow, feeding the device-side bucket
        prefetcher (``parallel.als._BucketPrefetcher``) without ever making
        it wait on a cold ``np.load``. Peak host memory is ONE file's
        buckets synchronous, TWO under readahead (the double-buffer's host
        half). ``readahead=False`` restores the strictly one-file-resident
        synchronous walk; bucket order is identical either way.

        ``coalesce`` stream-merges each length tier's per-chunk partial
        buckets into full ones (``datasets.ragged.coalesce_buckets``):
        chunked generation fragments every tier once per chunk file, so an
        n-chunk dataset otherwise dispatches ~n buckets where one would
        do. Raw (False) is the stored layout — what the meta shapes
        describe; :meth:`provider` turns coalescing on for fits.
        """
        if coalesce:
            from albedo_tpu.datasets.ragged import coalesce_buckets

            yield from coalesce_buckets(
                self.iter_buckets(side, readahead=readahead, coalesce=False),
                batch_size=int(self.meta.get("batch_size", 1024)),
                max_entries=self.meta.get("max_entries"),
            )
            return
        files = self._bucket_files(side)
        if not readahead:
            for path in files:
                yield from self._load_bucket_file(path)
            return
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="albedo-bucket-read"
        ) as pool:
            pending = pool.submit(self._load_bucket_file, files[0]) if files else None
            for i in range(len(files)):
                buckets = pending.result()
                pending = (
                    pool.submit(self._load_bucket_file, files[i + 1])
                    if i + 1 < len(files) else None
                )
                yield from buckets

    def provider(
        self,
        side: str,
        readahead: bool = True,
        coalesce: bool = True,
    ):
        """A re-callable bucket provider for ``ShardedALSFit.fit`` — each
        half-sweep re-streams the side's buckets from disk. By default with
        file readahead on a background
        thread AND per-tier bucket coalescing (see :meth:`iter_buckets`) —
        the host half of the pipelined sharded dataflow."""
        return lambda: self.iter_buckets(
            side, readahead=readahead, coalesce=coalesce
        )

    def bucket_shapes(self, side: str) -> list[tuple[int, int]]:
        return [tuple(s) for s in self.meta[f"{side}_bucket_shapes"]]

    def to_star_matrix(self) -> StarMatrix:
        """Materialize the whole matrix in memory (parity tests / small
        sizes only). Dense indices ARE the raw ids, so factors line up with
        the bucket row ids positionally."""
        parts = [
            np.fromfile(p, dtype=np.int32).reshape(-1, 2)
            for p in sorted((self.root / "pairs").glob("*.bin"))
        ]
        pairs = (
            np.concatenate(parts) if parts else np.zeros((0, 2), np.int32)
        )
        return StarMatrix(
            user_ids=np.arange(self.n_users, dtype=np.int64),
            item_ids=np.arange(self.n_items, dtype=np.int64),
            rows=pairs[:, 0],
            cols=pairs[:, 1],
            vals=np.ones(pairs.shape[0], dtype=np.float32),
        )


def _save_buckets(path: Path, buckets: list) -> None:
    arrays: dict[str, np.ndarray] = {"n_buckets": np.int64(len(buckets))}
    for i, b in enumerate(buckets):
        arrays[f"b{i}_row_ids"] = b.row_ids
        arrays[f"b{i}_idx"] = b.idx
        arrays[f"b{i}_val"] = b.val
        arrays[f"b{i}_mask"] = b.mask
    tmp = path.with_name(path.name + f".tmp{os.getpid()}")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


def generate_scale_dataset(
    root: str | Path,
    n_users: int = 10_000_000,
    n_items: int = 1_000_000,
    mean_stars: float = 100.0,
    popularity_alpha: float = 1.0,
    seed: int = 42,
    chunk_users: int = 262_144,
    n_partitions: int | None = None,
    batch_size: int = 8192,
    max_entries: int = 1 << 21,
    max_len: int | None = None,
) -> ScaleDataset:
    """Generate a power-law star matrix bucket-by-bucket out-of-core.

    Defaults parameterize the ROADMAP scale target (10M users x 1M repos,
    ~1B nnz at ``mean_stars=100``); tests and the CPU-smoke weak-scaling
    bench pass toy sizes through the identical path. Deterministic per
    ``seed`` (chunk-keyed child generators, so ``chunk_users`` only affects
    peak memory, not which user gets which stars... within one chunk size).
    """
    root = Path(root)
    for sub in ("user-buckets", "item-buckets", "pairs"):
        d = root / sub
        d.mkdir(parents=True, exist_ok=True)
        # Clear EVERYTHING from a previous generation: the loader globs, so
        # stale chunk/part files from a larger earlier run would silently
        # ride along under the new meta.json.
        for stale in d.iterdir():
            stale.unlink()
    from albedo_tpu.datasets.ragged import bucket_rows

    rng = np.random.default_rng(seed)
    # Zipf-ish popularity over a seeded permutation (mirrors synthetic_stars).
    pop_rank = rng.permutation(n_items) + 1
    p = pop_rank.astype(np.float64) ** (-popularity_alpha)
    cdf = np.cumsum(p / p.sum())

    n_parts = int(n_partitions) if n_partitions else max(1, -(-n_items // 131_072))
    items_per_part = -(-n_items // n_parts)
    part_files = [root / "pairs" / f"part-{pi:05d}.bin" for pi in range(n_parts)]
    for f in part_files:
        f.unlink(missing_ok=True)

    nnz_total = 0
    user_shapes: set[tuple[int, int]] = set()
    n_chunks = -(-n_users // chunk_users)
    for ci in range(n_chunks):
        lo = ci * chunk_users
        hi = min(lo + chunk_users, n_users)
        crng = np.random.default_rng((seed, ci))
        n_stars = np.clip(
            crng.lognormal(np.log(mean_stars), 0.9, size=hi - lo).astype(np.int64),
            1,
            max(1, n_items // 2),
        )
        total = int(n_stars.sum())
        # Inverse-CDF popularity sampling, deduped per user: sampling with
        # replacement then unique keeps the power-law item marginal while
        # matching StarMatrix's unique-(user, item) constraint.
        u = crng.random(total)
        # Clamp: float64 cumsum leaves cdf[-1] a hair below 1.0, so at ~1e9
        # draws some u lands above it and searchsorted returns n_items —
        # an out-of-range item that would corrupt the partition pass.
        cols = np.minimum(
            np.searchsorted(cdf, u).astype(np.int64), n_items - 1
        )
        rows = np.repeat(np.arange(lo, hi, dtype=np.int64), n_stars)
        key = rows * n_items + cols
        key = np.unique(key)  # sorts by (row, col) and dedups in one pass
        rows = (key // n_items).astype(np.int32)
        cols = (key % n_items).astype(np.int32)
        nnz_total += rows.shape[0]

        # User-side buckets for this chunk: a local CSR over [lo, hi), then
        # global row ids patched in (fill writes local ids; +lo restores).
        counts = np.bincount(rows - lo, minlength=hi - lo)
        indptr = np.zeros(hi - lo + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        vals = np.ones(cols.shape[0], dtype=np.float32)
        buckets = bucket_rows(
            indptr, cols.astype(np.int32), vals,
            batch_size=batch_size, max_entries=max_entries, max_len=max_len,
        )
        patched = []
        for b in buckets:
            rid = np.where(b.row_ids >= 0, b.row_ids + lo, -1).astype(np.int32)
            patched.append(type(b)(row_ids=rid, idx=b.idx, val=b.val, mask=b.mask))
        _save_buckets(root / "user-buckets" / f"chunk-{ci:05d}.npz", patched)
        user_shapes.update(b.shape for b in patched)

        # Spill (row, col) pairs into item-range partitions for the CSC pass.
        part_of = cols // items_per_part
        order = np.argsort(part_of, kind="stable")
        sorted_parts = part_of[order]
        bounds = np.searchsorted(
            sorted_parts, np.arange(n_parts + 1), side="left"
        )
        pair_block = np.stack([rows[order], cols[order]], axis=1)
        for pi in range(n_parts):
            s, e = bounds[pi], bounds[pi + 1]
            if s == e:
                continue
            with open(part_files[pi], "ab") as f:
                pair_block[s:e].tofile(f)

    # Item side: each partition independently sorted by item and packed.
    item_shapes: set[tuple[int, int]] = set()
    for pi, pf in enumerate(part_files):
        if not pf.exists():
            continue
        pairs = np.fromfile(pf, dtype=np.int32).reshape(-1, 2)
        base = pi * items_per_part
        width = min(items_per_part, n_items - base)
        local = pairs[:, 1] - base
        order = np.argsort(local, kind="stable")
        counts = np.bincount(local, minlength=width)
        indptr = np.zeros(width + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        urows = pairs[order, 0]
        vals = np.ones(urows.shape[0], dtype=np.float32)
        buckets = bucket_rows(
            indptr, urows, vals,
            batch_size=batch_size, max_entries=max_entries, max_len=max_len,
        )
        patched = []
        for b in buckets:
            rid = np.where(b.row_ids >= 0, b.row_ids + base, -1).astype(np.int32)
            patched.append(type(b)(row_ids=rid, idx=b.idx, val=b.val, mask=b.mask))
        _save_buckets(root / "item-buckets" / f"part-{pi:05d}.npz", patched)
        item_shapes.update(b.shape for b in patched)

    meta = {
        "n_users": int(n_users),
        "n_items": int(n_items),
        "nnz": int(nnz_total),
        "seed": int(seed),
        "mean_stars": float(mean_stars),
        "popularity_alpha": float(popularity_alpha),
        "chunk_users": int(chunk_users),
        "n_partitions": int(n_parts),
        "batch_size": int(batch_size),
        "max_entries": int(max_entries),
        "max_len": max_len,
        "user_bucket_shapes": sorted(user_shapes),
        "item_bucket_shapes": sorted(item_shapes),
    }
    (root / "meta.json").write_text(json.dumps(meta, indent=2))
    return ScaleDataset(root)
