"""Implicit-feedback ALS estimator and model.

Reference parity: Spark MLlib ``ALS`` as configured by
``ALSRecommenderBuilder.scala:46-58`` — implicitPrefs=true, rank=50,
regParam=0.5, alpha=40, maxIter=26, seed=42, coldStartStrategy="drop". The
north-star NDCG@30 (0.05209, BASELINE.md) comes from exactly those settings.

TPU-first architecture: instead of MLlib's shuffled in/out blocks, each
iteration is two bucketed half-sweeps of fixed-shape normal-equation solves on
device (``albedo_tpu.ops.als``); the ratings live on device as padded buckets
built once per fit. Iteration order matches MLlib: item factors update first,
then user factors.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from albedo_tpu.datasets.ragged import (
    Bucket,
    bucket_rows,
    device_bucket,
    balanced_shards,
    group_buckets,
    grouped_bucket_rows,
    rows_allowed,
    shard_grouped_bucket_rows,
    shard_rows,
    slot_tier_floor,
)
from albedo_tpu.datasets.star_matrix import StarMatrix
from albedo_tpu.ops.als import (
    als_fit_fused,
    als_init_fit_fused,
    cg_gram_entry_share,
    check_solver,
    chunked_bucket_update,
    exact_lane_systems,
    exact_systems,
    gather_packed_entry_share,
    gather_reformed_entry_share,
    gather_table,
    gramian,
    relayout_rows,
    scanned_shape,
    seeded_factors,
)
from albedo_tpu.ops.topk import topk_scores
from albedo_tpu.utils import capacity as capacity_mod
from albedo_tpu.utils import faults
from albedo_tpu.utils.aot import persistent_aot_executable
from albedo_tpu.utils.profiling import Timer

# Chaos hook for the chunked host-streamed fallback: fires ahead of every
# chunked half-sweep, so drills can kill/fail a degraded fit mid-stream
# exactly like they kill the resident path mid-checkpoint.
_CHUNKED_FAULT = faults.site("als.chunked")

# The spans EVERY chunked (host-streamed) fit publishes in
# ``last_fit_report["spans"]`` and, as ``albedo.<name>``, in a profiler trace
# (``ImplicitALS._fit_chunked`` says what each is around). Tests and the
# benchmark's ``fit_streamed`` driver import this tuple. Optional beside
# them: ``fit.stream.acquire`` with the AOT layer's branches under it (a cold
# estimator's one acquisition of every shape; the steady loop's look-up
# carries no span) and ``fit.gc`` (absent when no full collection ran).
CHUNKED_SPANS = (
    "fit", "fit.admission", "fit.prep", "fit.init", "fit.relayout",
    "fit.stream", "fit.stream.gramian", "fit.stream.upload",
    "fit.stream.dispatch", "fit.wait", "fit.report",
)

# The one length tier whose rows the chunked fit hands over more than
# ``batch_size`` at a time (``ImplicitALS._dispatch_rows``): rows of one padded
# entry. A MEASURED constant, not a derived rule - set on one v5e at rank 128,
# float32 gathers, ``batch_size`` 8192 and CG (10M x 1M x 100M stars; PERF.md
# section 6, PR 34), and to be measured again where one of those changes:
# ``ops.als.chunked_bucket_update`` alone, device ns a row at 8,192 rows a
# call -> at the priced cap, read 99 -> 107 at ``L`` = 1 but 160 -> 193 at
# ``L`` = 2, 167 -> 206 at 4, 186 -> 216 at 8, 230 -> 299 at 16, 485 -> 534 at
# 40 (already +7% at twice the rows), and a whole sweep 3,471 ms unmerged,
# 3,373-3,380 with this tier merged, 3,775 with every tier merged. What the
# compiled programs show beside it: at 8,192 rows the temporaries of an
# ``L`` <= 16 bucket are 0.7 MB (the block and the CG's row state stay in
# VMEM) and hundreds of MB at the cap (they stream from HBM) - at ``L`` = 1
# too, where it costs nothing because 70 of a row's 99 ns are the landing
# scatter, a cost a ROW at any call size. So no test of bytes against VMEM
# that the code could make separates the tier that gains from those that
# lose: (16384, 2) would pass it and is 7% slower a row. The 363 one-entry
# dispatches of a sweep's 1,455 (0.8 ms of device work each behind a
# millisecond of upload and dispatch) were where the chip waited.
STREAM_MERGED_LEN = 1

# The spans of a resident row-sharded fit (``sharded="resident"``,
# ``shard_mode="allgather"``; ``ImplicitALS._fit_sharded_resident`` says what
# each is around), for the tests and the benchmark's ``fit_sharded`` driver.
# Optional beside them: ``fit.gc`` (absent when no full collection ran).
SHARDED_SPANS = (
    "fit", "fit.prep", "fit.acquire", "fit.init",
    "fit.shard", "fit.shard.gramian", "fit.shard.assemble",
    "fit.shard.dispatch", "fit.relayout", "fit.wait", "fit.report",
)


def _cut(table, n_rows: int):
    """``table`` without its row padding, itself where it has none."""
    return table if table.shape[0] == n_rows else table[:n_rows]


class ALSModel:
    """Trained factor matrices, indexed by dense user/item indices.

    Factors may be device (jax) arrays straight out of the fused fit — the
    ``user_factors``/``item_factors`` properties materialize host copies
    lazily on first access, so training wall-clock doesn't pay a ~10 MB
    device->host transfer that evaluation may never need, and the retrieval
    path can keep scoring on device."""

    def __init__(self, user_factors, item_factors, rank: int,
                 n_users: int | None = None, n_items: int | None = None):
        # A row-sharded fit hands its tables back as they lie on the mesh:
        # rows padded with zeros to a shard-count multiple. ``n_users`` /
        # ``n_items`` are the logical row counts; the host copies are cut to
        # them on the host, and a device copy only where serving asks for
        # one (cutting a sharded table on the device to a count the mesh
        # does not divide gathers all of it onto every device).
        self._uf_raw = user_factors
        self._vf_raw = item_factors
        self.rank = int(rank)
        self.n_users = int(user_factors.shape[0] if n_users is None else n_users)
        self.n_items = int(item_factors.shape[0] if n_items is None else n_items)
        self._uf_np: np.ndarray | None = None
        self._vf_np: np.ndarray | None = None
        self._dev: tuple[jax.Array, jax.Array] | None = None
        self._vf_dev: jax.Array | None = None

    @property
    def user_factors(self) -> np.ndarray:  # (n_users, rank) float32
        if self._uf_np is None:
            self._uf_np = _cut(np.asarray(self._uf_raw, dtype=np.float32), self.n_users)
        return self._uf_np

    @property
    def item_factors(self) -> np.ndarray:  # (n_items, rank) float32
        if self._vf_np is None:
            self._vf_np = _cut(np.asarray(self._vf_raw, dtype=np.float32), self.n_items)
        return self._vf_np

    def device_factors(self) -> tuple[jax.Array, jax.Array]:
        """Device-resident ``(user_factors, item_factors)``, uploaded once
        and cached — the serving batcher's explicit opt-in: it scores every
        request against the same tables, so pinning the full user table on
        device is the right trade there. Offline ``recommend()`` callers do
        NOT pay this pin for host-backed models (see below)."""
        if self._dev is None:
            uf = (
                _cut(self._uf_raw, self.n_users)
                if isinstance(self._uf_raw, jax.Array)
                else jnp.asarray(self.user_factors)
            )
            self._dev = (uf, self._device_items())
        return self._dev

    def _device_items(self) -> jax.Array:
        """Device-resident item table only — cached so repeat ``recommend``
        calls stop re-uploading it (the seed paid that per call), without
        pinning the much larger user table for one-shot offline scoring."""
        if self._dev is not None:
            return self._dev[1]
        if self._vf_dev is None:
            self._vf_dev = (
                _cut(self._vf_raw, self.n_items)
                if isinstance(self._vf_raw, jax.Array)
                else jnp.asarray(self.item_factors)
            )
        return self._vf_dev

    def predict(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        u = self.user_factors[np.asarray(rows)]
        v = self.item_factors[np.asarray(cols)]
        return np.sum(u * v, axis=1)

    def recommend(
        self,
        user_indices: np.ndarray,
        k: int = 30,
        exclude_idx: np.ndarray | None = None,
        item_block: int = 4096,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k items for the given users: (scores (U, k), item_idx (U, k))."""
        ui = np.asarray(user_indices)
        n = self.n_users
        if ui.size and (int(ui.min()) < 0 or int(ui.max()) >= n):
            # Out-of-range indices (including negatives — dense user indices
            # have no wrap-around meaning here) are rejected on BOTH paths:
            # jnp.take's default clipping would silently score a wrong user.
            raise IndexError(f"user index out of range [0, {n}): {ui.min()}..{ui.max()}")
        if isinstance(self._uf_raw, jax.Array):
            # Factors already device-resident: gather on device.
            uf = jnp.take(self._uf_raw, jnp.asarray(ui), axis=0)
        else:
            # Host-backed (unpickled artifacts): upload only the requested
            # rows — offline evaluate/cv callers score a few hundred users
            # once, so pinning the full user table here would be pure waste.
            uf = jnp.asarray(self.user_factors[ui])
        vf = self._device_items()
        excl = None if exclude_idx is None else jnp.asarray(exclude_idx)
        vals, idx = topk_scores(uf, vf, k=k, exclude_idx=excl, item_block=item_block)
        return np.asarray(vals), np.asarray(idx)

    def to_arrays(self) -> dict[str, np.ndarray]:
        return {
            "user_factors": self.user_factors,
            "item_factors": self.item_factors,
            "rank": np.int64(self.rank),
        }

    @staticmethod
    def from_arrays(arrays: dict[str, np.ndarray]) -> "ALSModel":
        return ALSModel(
            user_factors=np.asarray(arrays["user_factors"], dtype=np.float32),
            item_factors=np.asarray(arrays["item_factors"], dtype=np.float32),
            rank=int(arrays["rank"]),
        )


def _landing_perm(buckets: list[Bucket], n_target: int) -> np.ndarray:
    """Host-side inverse permutation for the gather-based landing
    (``ops.als.scan_half_sweep``): position of each target row in the
    flattened solved blocks (group order, then bucket, then slot), with
    ``n_slots + r`` for rows in no bucket (keep the old factor)."""
    n_slots = sum(int(np.prod(b.row_ids.shape)) for b in buckets)
    landing = np.arange(n_slots, n_slots + n_target, dtype=np.int32)
    offset = 0
    for b in buckets:
        rid = b.row_ids.reshape(-1)
        pos = np.arange(rid.size, dtype=np.int32) + offset
        valid = rid >= 0
        landing[rid[valid]] = pos[valid]
        offset += rid.size
    return landing



def _dispatch_order(buckets: list[Bucket], n_rows: int) -> tuple[np.ndarray, np.ndarray]:
    """One side's dispatch order for the chunked fit: ``(order, pos)``.
    ``order[p]`` is the logical row at position ``p`` - every bucket's slots
    in half-sweep order, -1 on a padding slot (its own row, holding zeros),
    then the rows no bucket holds (a row with no stars keeps its factor) -
    and ``pos[r]`` the position of logical row ``r``."""
    slots = np.concatenate([b.row_ids for b in buckets] or [np.zeros(0, np.int32)])
    held = np.zeros(n_rows, bool)
    held[slots[slots >= 0]] = True
    order = np.concatenate([slots, np.flatnonzero(~held)]).astype(np.int32)
    valid = order >= 0
    pos = np.empty(n_rows, np.int32)
    pos[order[valid]] = np.flatnonzero(valid)
    return order, pos


@dataclasses.dataclass
class StreamLayout:
    """The chunked fit's layout with both factor tables held in DISPATCH
    ORDER (:func:`_dispatch_order`): a bucket's slots are one contiguous
    block of its target table, so ``ops.als.chunked_bucket_update`` warm
    starts from it with one slice and lands it with one block write. The
    buckets are ``_host_buckets(stream=True)``'s relabelled once: ``idx``
    into the source side's positions, ``row_ids`` into the target's (the
    block's offset + slot, -1 still on padding slots); ``val`` and ``mask``
    are the same arrays. ``rows`` holds the relayouts' index vectors on the
    device, per side ``(order, pos)``, uploaded once with the layout."""

    user_buckets: list[Bucket]
    item_buckets: list[Bucket]
    user_order: np.ndarray
    user_pos: np.ndarray
    item_order: np.ndarray
    item_pos: np.ndarray
    landed_in_place_share: float  # rows whose bucket is its block ÷ all rows landed
    rows: dict | None = None

    @classmethod
    def build(cls, user: list[Bucket], item: list[Bucket], n_users: int, n_items: int,
              workers: int | None) -> "StreamLayout":
        (u_order, u_pos), (i_order, i_pos) = (
            _dispatch_order(user, n_users), _dispatch_order(item, n_items))

        def relabel(buckets, source_pos):
            offsets = np.cumsum([0] + [b.shape[0] for b in buckets])

            def one(j: int) -> Bucket:
                b = buckets[j]
                block = offsets[j] + np.arange(b.shape[0], dtype=np.int32)
                return Bucket(row_ids=np.where(b.row_ids >= 0, block, -1).astype(np.int32),
                              idx=np.take(source_pos, b.idx), val=b.val, mask=b.mask)

            if workers and len(buckets) > 1:
                with ThreadPoolExecutor(max_workers=workers) as pool:
                    return list(pool.map(one, range(len(buckets))))
            return [one(j) for j in range(len(buckets))]

        user_d, item_d = relabel(user, i_pos), relabel(item, u_pos)

        def in_place(buckets, n_table):
            """Rows a bucket lands as the block its slots are."""
            rows = 0
            for b in buckets:
                valid = b.row_ids >= 0
                start = int(b.row_ids[0])
                if (valid[0] and start + b.shape[0] <= n_table
                        and np.array_equal(b.row_ids[valid], start + np.flatnonzero(valid))):
                    rows += int(valid.sum())
            return rows

        landed = sum(int((b.row_ids >= 0).sum()) for b in (*user, *item))
        share = (in_place(user_d, u_order.size) + in_place(item_d, i_order.size)) / max(1, landed)
        return cls(user_d, item_d, u_order, u_pos, i_order, i_pos, share)

    def slot_row_share(self) -> float:
        """Rows the dispatch-order tables hold ÷ logical rows."""
        return (self.user_order.size + self.item_order.size) / max(
            1, self.user_pos.size + self.item_pos.size)

    def device_rows(self) -> dict:
        """``{"user": (order, pos), "item": (order, pos)}`` on the device."""
        if self.rows is None:
            self.rows = {
                "user": (jnp.asarray(self.user_order), jnp.asarray(self.user_pos)),
                "item": (jnp.asarray(self.item_order), jnp.asarray(self.item_pos)),
            }
        return self.rows


def _shard_landing_perm(groups: list[Bucket], n_shards: int, rows_per: int) -> np.ndarray:
    """``_landing_perm`` of every shard of own-rows shape groups
    (``ragged.shard_grouped_bucket_rows``: shard ``d``'s slots of a bucket
    are ``[d * B, (d + 1) * B)``, its row ids local), one after another:
    the ``(n_shards * rows_per,)`` vector that, row-sharded, hands each
    device the landing permutation of its own solved blocks."""
    def slots(g: Bucket, d: int) -> Bucket:
        b = g.row_ids.shape[1] // n_shards
        return dataclasses.replace(g, row_ids=g.row_ids[:, d * b:(d + 1) * b])

    return np.concatenate([
        _landing_perm([slots(g, d) for g in groups], rows_per) for d in range(n_shards)
    ])

# Weakref-keyed per-matrix caches (ADVICE r5 #1): keyed by id() with a
# finalizer that drops the entry when the matrix is collected, so a
# long-lived process fitting many matrices releases each one's uploaded
# device buckets with the matrix instead of accumulating them. (A
# WeakKeyDictionary won't do: the frozen dataclass's field-tuple __hash__
# would try to hash ndarrays.)
_LAYOUT_CACHES: dict[int, tuple[Any, dict]] = {}


def _matrix_cache(matrix: StarMatrix) -> dict:
    """Per-matrix memo for bucket layouts and uploaded device groups.

    ``StarMatrix`` is an immutable (frozen) value and bucketing is a pure
    function of it + the layout knobs, so the same artifact-memoization
    philosophy as ``loadOrCreate*`` (``utils/ModelUtils.scala:7-21``) applies:
    a warmup fit leaves the layouts (and their one-time device upload) warm
    for the real fit. The cache lives exactly as long as the matrix (see
    ``_LAYOUT_CACHES``)."""
    key = id(matrix)
    entry = _LAYOUT_CACHES.get(key)
    # The ref check guards id reuse: a dead matrix's id can be recycled
    # before its finalizer has run on exotic GC interleavings.
    if entry is not None and entry[0]() is matrix:
        return entry[1]
    cache: dict = {}
    _LAYOUT_CACHES[key] = (weakref.ref(matrix), cache)
    weakref.finalize(matrix, _LAYOUT_CACHES.pop, key, None)
    return cache


def _bucket_workers() -> int | None:
    """Host fill-thread count: ``ALBEDO_BUCKET_WORKERS`` (0/1 = sequential),
    default = CPU count. The scatter fills are pure NumPy and release the
    GIL, so threads scale until memory bandwidth saturates."""
    raw = os.environ.get("ALBEDO_BUCKET_WORKERS")
    n = int(raw) if raw else (os.cpu_count() or 1)
    return n if n > 1 else None


@dataclasses.dataclass
class ImplicitALS:
    """Alternating least squares for implicit feedback on a device mesh.

    Defaults mirror the reference's flagship config
    (``ALSRecommenderBuilder.scala:46-58``).
    """

    rank: int = 50
    reg_param: float = 0.5
    alpha: float = 40.0
    max_iter: int = 26
    seed: int = 42
    # Normal-equation solver: "cholesky" = exact per-row solve, MLlib's
    # algorithm (the parity reference); "cg" = Jacobi-preconditioned
    # conjugate gradient warm-started from the previous sweep's factors
    # (``ops.als.bucket_cg_body``: matrix-free on short rows, on the row's
    # explicit Gramian on long ones) — the fast path: XLA's batched
    # small-matrix Cholesky runs at a few GF/s on TPU while the CG is
    # streaming passes and one MXU contraction; a few warm-started steps per
    # half-sweep match the exact solve's held-out ranking quality (the
    # ``implicit`` package's standard CG solver uses 3).
    solver: str = "cholesky"
    cg_steps: int = 3
    # Gathered-factor dtype for the sweeps: None = float32; "bfloat16" halves
    # the streamed bytes of the bandwidth-bound gather passes (contractions
    # still accumulate in f32 on the MXU). The factor TABLES and solves stay
    # f32 either way; held-out ranking parity vs f32 is test-pinned.
    gather_dtype: str | None = None
    batch_size: int = 8192
    max_entries: int = 1 << 21  # B*L budget per bucket (gather memory bound)
    max_len: int | None = None
    # Optional jax.sharding.Mesh: shard each bucket's batch dim over the mesh's
    # "data" axis (albedo_tpu.parallel.als) instead of single-device sweeps.
    mesh: Any | None = None
    # Optional (user_factors, item_factors) warm start — resume-from-checkpoint
    # (utils.checkpoint.checkpointed_als_fit) instead of the seeded init.
    init_factors: tuple | None = None
    # Memory-budget admission (utils.capacity): None = the admission verdict
    # decides (a `degrade` falls back to the chunked host-streamed path),
    # True/False force the chunked/resident path (bench A/B, tests).
    chunked: bool | None = None
    # Mesh-path admission (requires self.mesh): None = the admission LADDER
    # decides — replicated-resident -> sharded tables -> sharded + streamed
    # buckets (double-buffered prefetch) -> sharded + streamed synchronous
    # (single bucket in flight); False forces the replicated GSPMD path;
    # "resident"/True force row-sharded tables with resident buckets;
    # "streamed" additionally streams interaction buckets from the host per
    # half-sweep (the star matrix is never device-resident whole) through
    # the PIPELINED dataflow; "streamed_sync"
    # pins the synchronous streamed dataflow — the cheaper admission rung
    # and the A/B triage path. Checkpointed mesh fits run the ELASTIC
    # driver (parallel/elastic.py): mesh-portable sweep-boundary
    # checkpoints + mid-fit device-loss remesh-resume.
    sharded: Any | None = None
    # Source-factor assembly for the sharded path: "allgather" (the full
    # table: once a half-sweep with resident buckets, every device solving
    # its own rows; once a bucket with streamed ones) or "ring" (ppermute'd
    # 1/n shards, a bucket at a time, cholesky only).
    shard_mode: str = "allgather"

    def _layout_kwargs(self) -> dict:
        return dict(
            batch_size=self.batch_size,
            max_entries=self.max_entries,
            max_len=self.max_len,
        )

    def _host_buckets(self, matrix: StarMatrix, stream: bool = False) -> tuple[list, list]:
        """(user, item) bucket lists — the exact layouts ``fit`` trains on.

        Memoized per matrix (see ``_matrix_cache``): bucketing is a pure
        function of the immutable matrix + layout knobs, so a warmup fit
        leaves the layout warm for the timed fit. The CSR (user) and CSC
        (item) sides run concurrently and each side's per-bucket scatter
        fills shard across a thread pool (``_bucket_workers``) — output is
        byte-identical to the sequential build.

        ``stream`` asks for the chunked fit's own layout, where a bucket is
        an upload and a dispatch: the same length tiers, rows, order and pad
        widths under that path's row allowance (:meth:`_dispatch_rows`, from
        the planner's shapes that admission priced: no side is planned
        again for it), under a key that holds what the allowance depends on."""
        key = ("host", self.batch_size, self.max_entries, self.max_len)
        rows_of = (None, None)
        if stream:
            key += ("stream", self.rank, self.gather_dtype, self.solver)
        cache = _matrix_cache(matrix)
        if key not in cache:
            if stream:
                rows_of = tuple(self._dispatch_rows(side) for side in self._plan_shapes(matrix))
            workers = _bucket_workers()
            if workers:
                # Split the worker budget across the two concurrent sides so
                # the total fill-thread count stays at the host budget.
                kw = dict(self._layout_kwargs(), workers=max(1, workers // 2))
                with ThreadPoolExecutor(max_workers=2) as sides:
                    user_f = sides.submit(
                        lambda: bucket_rows(*matrix.csr(), rows_of=rows_of[0], **kw))
                    item_f = sides.submit(
                        lambda: bucket_rows(*matrix.csc(), rows_of=rows_of[1], **kw))
                    cache[key] = (user_f.result(), item_f.result())
            else:
                cache[key] = tuple(
                    bucket_rows(*csx, rows_of=allowance, **self._layout_kwargs())
                    for csx, allowance in zip((matrix.csr(), matrix.csc()), rows_of)
                )
        return cache[key]

    def _stream_layout(self, matrix: StarMatrix) -> StreamLayout:
        """The chunked fit's layout in dispatch order (:class:`StreamLayout`),
        built from ``_host_buckets(matrix, stream=True)`` once and kept with
        it, under the same settings."""
        key = ("stream_layout", self.batch_size, self.max_entries, self.max_len,
               self.rank, self.gather_dtype, self.solver)
        cache = _matrix_cache(matrix)
        if key not in cache:
            user, item = self._host_buckets(matrix, stream=True)
            cache[key] = StreamLayout.build(
                user, item, matrix.n_users, matrix.n_items, _bucket_workers())
        return cache[key]

    def _dispatch_rows(self, shapes: list[tuple[int, int]]):
        """``L -> rows`` that one dispatch of the chunked fit carries of a
        side's rows of padded length ``L`` (``ragged.plan_buckets``:
        ``rows_of``), given the side's planner shapes at ``batch_size`` rows
        (:meth:`_plan_shapes`): ``batch_size`` rows, or ``max_entries`` flat,
        as in every layout - but a dispatch of rows of ``STREAM_MERGED_LEN``
        entries is filled until the bytes it holds in flight
        (``capacity.chunked_row_bytes``) reach those of the side's worst
        bucket at ``batch_size`` rows, in whole slot tiers. So no dispatch is
        priced over that layout's worst, which admission's price of the rung
        covers (``capacity.plan_fit_chunked``), and HOW MANY rows the merged
        tier carries follows from the rank, the solver and the priced bytes
        alone (a row of the exact solve holds a ``(k, k)`` system: a tenth as
        many fit). WHICH tier merges does not: see ``STREAM_MERGED_LEN``.
        """
        def price(ln: int) -> int:
            return capacity_mod.chunked_row_bytes(ln, self.rank, self.gather_dtype, self.solver)

        worst = max((b * price(ln) for b, ln in shapes), default=0)

        def rows_of(pad_l: int) -> int:
            rows = rows_allowed(pad_l, self.batch_size, self.max_entries)
            if pad_l != STREAM_MERGED_LEN:
                return rows
            most = worst // price(pad_l)
            if self.max_entries is not None:
                most = min(most, self.max_entries // pad_l)
            return max(rows, slot_tier_floor(max(1, most)))

        return rows_of

    def _groups_cache_key(self) -> tuple:
        """Cache key for the uploaded device groups. ``Mesh`` is hashable and
        compared by value (keying on ``id(mesh)`` could alias a dead mesh's
        reused id to a new, differently-laid-out one)."""
        return (
            "device", self.batch_size, self.max_entries, self.max_len,
            self.mesh, jax.default_backend(),
        )

    def device_groups(
        self, matrix: StarMatrix, timer: Timer | None = None
    ) -> tuple[list[tuple], list[tuple], Any, Any]:
        """(user_groups, item_groups, user_landing, item_landing) on device, as
        ``als_fit_fused`` consumes them — shared by ``fit`` and the bench's
        phase breakdown so both always measure the same shapes. Memoized per
        (matrix, layout, mesh, backend): the upload happens once and the
        ratings stay device-resident across fits on the same matrix.

        With ``self.mesh`` set, each group's batch axis is laid out sharded
        over the mesh's data axis (buckets padded to a device-count multiple):
        the fused fit then runs under XLA's SPMD partitioner, which splits the
        per-row solves across devices and inserts the all-gather when solved
        rows land in the replicated factor tables — the compiler-inserted
        version of ``parallel.als.ShardedALSSweep``'s explicit shard_map.

        Cold-path pipeline (the r5 20.1 s single-threaded cliff): CSR and CSC
        sides bucket concurrently, per-bucket scatter fills shard across a
        thread pool, each finished shape group starts its (async)
        ``jax.device_put`` while later groups are still being packed, and the
        landing permutations are built while those transfers are in flight.
        ``self.last_prep_timings`` records the split: ``bucket_s`` (host
        planning + fills) and ``upload_s`` (upload dispatch of the slabs and
        the landing permutations; the transfers themselves overlap the
        packing). ``timer`` (the fit's own, fresh per fit) gets the same work
        as spans: ``fit.prep.index`` (the CSR + CSC build, with ``.csr``/``.csc`` inside
        the two worker threads), ``fit.prep.fill`` (packing both sides, with
        ``.user``/``.item`` likewise) — both wall-clock on the calling thread
        — and ``fit.prep.upload``, which is ``upload_s``: dispatch seconds
        summed over the side threads, inside ``fit.prep.fill`` in time.
        """
        key = self._groups_cache_key()
        cache = _matrix_cache(matrix)
        if key in cache:
            self.last_prep_timings = {"bucket_s": 0.0, "upload_s": 0.0}
            return cache[key]

        if self.mesh is not None:
            cache[key] = self._device_groups_mesh(matrix)
            return cache[key]

        timer = Timer() if timer is None else timer

        def spanned(name, fn, *args):
            with timer.section(name):
                return fn(*args)

        workers = _bucket_workers()
        t0 = time.perf_counter()
        with timer.section("fit.prep.index"), ThreadPoolExecutor(max_workers=2) as sides:
            csr_f = sides.submit(spanned, "fit.prep.index.csr", matrix.csr)
            csc_f = sides.submit(spanned, "fit.prep.index.csc", matrix.csc)
            csr, csc = csr_f.result(), csc_f.result()

        def put(g: Bucket) -> tuple:
            d = device_bucket(g)
            return (d.row_ids, d.idx, d.val, d.mask)

        # Both sides pack concurrently, so each gets half the fill-thread
        # budget — total threads stay at the host budget, not 2x it.
        side_workers = None if workers is None else max(1, workers // 2)

        def build_side(csx, n_target):
            """Pack one side's groups, uploading each as soon as it's full;
            returns (device groups, device landing)."""
            device_groups: list[tuple] = []

            def on_group(_i, g):
                # device_put is async: the transfer overlaps later packing
                with timer.section("fit.prep.upload"):
                    device_groups.append(put(g))
            grouped = grouped_bucket_rows(
                *csx, **self._layout_kwargs(), workers=side_workers, on_group=on_group
            )
            # Landing perm is pure host work — runs while H2D is in flight.
            landing = _landing_perm(grouped, n_target)
            with timer.section("fit.prep.upload"):
                landing_dev = jax.device_put(landing)
            return device_groups, landing_dev

        with timer.section("fit.prep.fill"):
            if workers:
                with ThreadPoolExecutor(max_workers=2) as sides:
                    user_f = sides.submit(
                        spanned, "fit.prep.fill.user", build_side, csr, matrix.n_users)
                    item_f = sides.submit(
                        spanned, "fit.prep.fill.item", build_side, csc, matrix.n_items)
                    ug, u_land = user_f.result()
                    ig, i_land = item_f.result()
            else:
                ug, u_land = spanned("fit.prep.fill.user", build_side, csr, matrix.n_users)
                ig, i_land = spanned("fit.prep.fill.item", build_side, csc, matrix.n_items)
        total = time.perf_counter() - t0
        upload = timer.totals["fit.prep.upload"]
        self.last_prep_timings = {
            "bucket_s": round(max(0.0, total - upload), 4),
            "upload_s": round(upload, 4),
        }
        cache[key] = (ug, ig, u_land, i_land)
        return cache[key]

    def _device_groups_mesh(self, matrix: StarMatrix) -> tuple:
        """Mesh layout path: pad buckets to a device-count multiple, then
        group/upload with the sharded layout. Host fills still run threaded
        via ``_host_buckets``; the per-group pipeline stays single-stream
        because ``pad_bucket`` operates on ungrouped buckets."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from albedo_tpu.parallel.als import pad_bucket
        from albedo_tpu.parallel.mesh import DATA_AXIS, replicated

        t0 = time.perf_counter()
        user_buckets, item_buckets = self._host_buckets(matrix)
        n_dev = self.mesh.shape[DATA_AXIS]
        user_buckets = [pad_bucket(b, n_dev) for b in user_buckets]
        item_buckets = [pad_bucket(b, n_dev) for b in item_buckets]
        # Leading axis = stacked same-shape buckets; batch axis sharded
        # (specs shorter than the rank replicate trailing dims).
        sharding = NamedSharding(self.mesh, P(None, DATA_AXIS))
        landing_sharding = replicated(self.mesh)

        user_grouped = group_buckets(user_buckets)
        item_grouped = group_buckets(item_buckets)
        user_landing = _landing_perm(user_grouped, matrix.n_users)
        item_landing = _landing_perm(item_grouped, matrix.n_items)
        t1 = time.perf_counter()

        def put(g):
            d = device_bucket(g, sharding)
            return (d.row_ids, d.idx, d.val, d.mask)

        out = (
            [put(g) for g in user_grouped],
            [put(g) for g in item_grouped],
            jax.device_put(user_landing, landing_sharding),
            jax.device_put(item_landing, landing_sharding),
        )
        t2 = time.perf_counter()
        self.last_prep_timings = {
            "bucket_s": round(t1 - t0, 4),
            "upload_s": round(t2 - t1, 4),
        }
        return out

    def _aot_key_parts(self, fn_name: str, matrix: StarMatrix, ug, ig) -> tuple:
        """Executable identity for the persistent AOT cache: everything the
        compiled program depends on beyond the dynamic argument values —
        bucket-shape signature, factor-table sizes, solver statics, mesh
        layout, and backend. Seed/reg/alpha/max_iter are traced arguments,
        so one executable serves any of their values."""
        dev = jax.devices()[0]
        groups_sig = tuple(tuple(g[1].shape) for g in ug) + ("|",) + tuple(
            tuple(g[1].shape) for g in ig
        )
        return (
            fn_name, jax.__version__, jax.default_backend(),
            getattr(dev, "device_kind", "?"), len(jax.devices()),
            None if self.mesh is None else repr(self.mesh),
            self.solver, self.cg_steps, self.gather_dtype, self.rank,
            matrix.n_users, matrix.n_items, groups_sig,
        )

    # ---------------------------------------------------- capacity admission

    def _plan_shapes(self, matrix: StarMatrix) -> tuple[list, list]:
        """(user, item) bucket shapes from the PLANNER alone: indptrs come
        from a bincount over the raw row/col ids — no slab filled, no byte
        uploaded, and none of the O(nnz log nnz) argsorts a full csr()/csc()
        view would redundantly pay before the real bucketing pays them."""
        kw = self._layout_kwargs()
        cache, key = _matrix_cache(matrix), ("plan_shapes", *kw.values())
        if key not in cache:
            cache[key] = (
                capacity_mod.bucket_plan_shapes(
                    capacity_mod.counts_indptr(matrix.rows, matrix.n_users), **kw
                ),
                capacity_mod.bucket_plan_shapes(
                    capacity_mod.counts_indptr(matrix.cols, matrix.n_items), **kw
                ),
            )
        return cache[key]

    def capacity_plan(self, matrix: StarMatrix, chunked: bool = False):
        """Static byte pricing of this fit's layout (``utils.capacity``)."""
        shapes_u, shapes_i = self._plan_shapes(matrix)
        args = (shapes_u, shapes_i, matrix.n_users, matrix.n_items,
                self.rank, self.gather_dtype)
        if chunked:
            return capacity_mod.plan_fit_chunked(*args, self.solver)
        return capacity_mod.plan_fit(*args, solver=self.solver)

    def admission(self, matrix: StarMatrix):
        """Admission verdict for fitting ``matrix`` on this estimator's
        layout: ``fit`` = resident path, ``degrade`` = chunked host-streamed
        fallback. When even the chunked plan (factor tables + one bucket in
        flight) busts the budget, raises :class:`~albedo_tpu.utils.capacity.
        CapacityExceeded` — that matrix needs the sharded mesh path, not a
        single device. One admission, one counted verdict: the chunked plan
        rides along as ``fallback_plan`` instead of a second admit()."""
        shapes_u, shapes_i = self._plan_shapes(matrix)
        args = (shapes_u, shapes_i, matrix.n_users, matrix.n_items,
                self.rank, self.gather_dtype)
        verdict = capacity_mod.admit(
            capacity_mod.plan_fit(*args, solver=self.solver), degradable=True,
            fallback_plan=capacity_mod.plan_fit_chunked(*args, self.solver),
        )
        if verdict.verdict == "refuse":
            raise capacity_mod.CapacityExceeded(verdict)
        return verdict

    def admission_mesh(self, matrix: StarMatrix):
        """Admission ladder for the mesh path (closes the PR 7 'mesh path
        exempt' blind spot): replicated-resident GSPMD fit -> row-sharded
        tables with resident sharded buckets -> sharded + host-streamed
        buckets under the pipelined dataflow (TWO bucket slabs in flight —
        the double-buffered prefetch) -> sharded + streamed SYNCHRONOUS
        (one slab in flight; the pipeline is worth a slab of HBM, so the
        ladder may trade it away before refusing). Each rung is priced PER
        DEVICE; the first rung that fits the budget wins
        (``verdict.chosen``). When even the synchronous streamed rung busts
        the budget, raises :class:`~albedo_tpu.utils.capacity.
        CapacityExceeded` — that matrix needs more chips, not more spilling.
        """
        from albedo_tpu.parallel.mesh import DATA_AXIS

        n_dev = int(self.mesh.shape[DATA_AXIS])
        shapes_u, shapes_i = self._plan_shapes(matrix)
        args = (shapes_u, shapes_i, matrix.n_users, matrix.n_items, self.rank)
        shard_kw = dict(
            gather_dtype=self.gather_dtype, mode=self.shard_mode,
            solver=self.solver,
        )
        verdict = capacity_mod.admit_ladder([
            capacity_mod.plan_fit(
                *args, gather_dtype=self.gather_dtype, n_devices=n_dev,
                solver=self.solver,
            ),
            capacity_mod.plan_fit_sharded(*args, n_dev, streamed=False, **shard_kw),
            capacity_mod.plan_fit_sharded(
                *args, n_dev, streamed=True, pipelined=True, **shard_kw
            ),
            capacity_mod.plan_fit_sharded(
                *args, n_dev, streamed=True, pipelined=False, **shard_kw
            ),
        ])
        if verdict.verdict == "refuse":
            raise capacity_mod.CapacityExceeded(verdict)
        return verdict

    # -------------------------------------------------------------- training

    def fit(self, matrix: StarMatrix, callback: Any | None = None) -> ALSModel:
        """Train factors on the default backend, or sharded over ``self.mesh``.

        ``callback(iteration, user_factors, item_factors)`` if given is invoked
        after each full sweep (host arrays; for monitoring/tests).

        Memory-budget admission runs first (:meth:`_choose_path`; cold layout
        cache only): a ``degrade`` verdict reroutes to the chunked
        host-streamed fallback (:meth:`_fit_chunked`) instead of dispatching a
        resident upload that would ``RESOURCE_EXHAUSTED``. ``self.chunked``
        forces either path; a warm groups cache implies the resident slabs
        already fit (they are on device now), and a ``degrade`` verdict is
        kept with the matrix's layout, so later fits of it take the chunked
        path under that verdict without pricing the matrix again.

        The returned model's factors are device arrays, fully computed on
        return (``block_until_ready``) — host copies materialize lazily via
        the ``ALSModel`` properties. ``self.last_fit_report`` records the
        wall-clock split (:meth:`_finish` builds it for every path):
        ``prep_s`` (bucket layout + one-time device upload;
        ~0 when the per-matrix cache is warm) with its ``bucket_s``/
        ``upload_s`` parts, ``compile_s`` (AOT executable acquisition — 0 on
        an in-memory hit; ``compile_source`` says memory/disk/compile),
        ``device_s`` (the fused training dispatch, synchronized),
        ``prep_cached`` (whether the layout cache was warm),
        ``cg_gram_entry_share`` (the share of padded entries in buckets whose
        CG ran on the explicit Gramian, ``ops.als.cg_uses_gramian``; 0 under
        Cholesky), ``gather_reformed_entry_share`` (the share in buckets
        gathered at a grown slot count, ``ops.als.gather_slots``) and
        ``gather_packed_entry_share`` (the share in buckets whose gather read
        a line table, ``ops.als.gather_packs_rows``),
        ``exact_systems_per_sweep`` (systems the exact solve factorises a
        sweep, ``ops.als.exact_systems``: every slot row, empty ones too; 0
        under CG) and ``exact_system_share`` (that over the logical rows of
        both tables), ``exact_lane_systems_per_sweep`` (the lanes those
        systems are solved at, a bucket's padded to whole lane tiles:
        ``ops.als.exact_lane_systems``) and ``exact_lane_share`` (that over
        the same rows). ``spans`` is the
        same call as a per-fit ``Timer`` snapshot (``{"totals", "counts"}``;
        each also an ``albedo.<name>`` host span in a profiler trace):
        ``fit`` > ``fit.admission``, ``fit.prep`` (children: see
        :meth:`device_groups`), ``fit.acquire`` (children: see
        ``utils.aot.persistent_aot_executable``), ``fit.dispatch`` (scalars,
        key and the compiled call until it returns), ``fit.wait`` (the
        health read that is the completion barrier), ``fit.report`` (this
        report's own making, after the barrier) and, only where one ran,
        ``fit.gc`` (the interpreter's full garbage collections inside this
        call, ``Timer.collections``: each also inside whichever span it
        interrupted). A span or a report key stays only with a reader
        (PERF.md section 3 names each one's).
        """
        # Before admission, bucketing and the upload are paid for a fit that
        # no kernel can run.
        check_solver(self.solver)
        timer = Timer()
        with timer.section("fit"), timer.collections("fit"):
            t0 = time.perf_counter()
            path, admission = self._choose_path(matrix, timer)
            if path == "chunked":
                run = self._fit_chunked(matrix, callback, timer)
            elif path == "resident":
                run = self._fit_resident(matrix, callback, timer, admission)
            else:
                run = self._fit_sharded(
                    matrix, callback, timer,
                    streamed=path != "sharded",
                    pipelined=path != "sharded_streamed_sync",
                )
            self.last_fit_report = self._finish(run, path, admission, t0, timer, matrix)
        self.last_fit_report["spans"] = timer.snapshot()
        return ALSModel(
            user_factors=run.user_f, item_factors=run.item_f, rank=self.rank,
            n_users=matrix.n_users, n_items=matrix.n_items,
        )

    def _choose_path(self, matrix: StarMatrix, timer: Timer) -> tuple[str, Any]:
        """Which fit runs, and the admission verdict that said so (``None``
        where a field forced the path or nothing was priced): ``"chunked"``
        (:meth:`_fit_chunked`), ``"resident"`` (:meth:`_fit_resident`, on one
        device or replicated over the mesh), or ``"sharded"`` /
        ``"sharded_streamed"`` / ``"sharded_streamed_sync"``
        (:meth:`_fit_sharded`: resident buckets, host-streamed buckets under
        the pipelined dataflow, the same under the synchronous one). From the
        estimator's fields (``chunked``, ``mesh``, ``sharded``) and from what
        admission observes, nothing else."""
        if self.chunked:
            return "chunked", None
        cache, groups_key = _matrix_cache(matrix), self._groups_cache_key()
        # A warm groups cache stands for a resident layout that fits: its
        # slabs are on the device now.
        priced = groups_key not in cache and capacity_mod.enabled()
        if self.mesh is None:
            if self.chunked is False or not priced:
                return "resident", None
            # A degrade verdict stays with the matrix's layout, as a warm
            # groups cache stands for a resident one: the next fit of this
            # layout does not price the same matrix again (two bincounts
            # over every entry).
            verdict_key = ("degrade_verdict", self.rank, self.gather_dtype, *groups_key)
            admission = cache.get(verdict_key)
            if admission is None:
                with timer.section("fit.admission"):
                    admission = self.admission(matrix)
                if admission.verdict == "degrade":
                    cache[verdict_key] = admission
            return ("chunked" if admission.verdict == "degrade" else "resident"), admission
        # The mesh path is not capacity-exempt: the admission LADDER picks
        # replicated-resident -> sharded -> sharded + streamed -> the same
        # with one slab in flight (or raises), unless self.sharded forces one.
        if self.sharded is None:
            if not priced:
                return "resident", None
            with timer.section("fit.admission"):
                admission = self.admission_mesh(matrix)
            return {
                "als_fit": "resident",
                "als_fit_sharded": "sharded",
                "als_fit_sharded_streamed": "sharded_streamed",
                "als_fit_sharded_streamed_sync": "sharded_streamed_sync",
            }[admission.chosen], admission
        if not self.sharded:
            return "resident", None
        if self.sharded in ("streamed", "streamed_sync"):
            return f"sharded_{self.sharded}", None
        return "sharded", None

    def _finish(
        self, run: "_PathRun", path: str, admission, t0: float, timer: Timer,
        matrix: StarMatrix,
    ) -> dict:
        """The completion barrier (``fit.wait``) and ``last_fit_report`` (less
        ``spans``; its making is ``fit.report``) of every path: the keys the
        benchmark's readers take from whichever path ran, then the keys that
        are the path's own (``run.own``)."""
        # Completion barrier: one ~12-byte device->host read of the
        # divergence watchdog's on-device health vector (nonfinite count /
        # max-abs / RMS over BOTH factor tables, utils.watchdog). It depends
        # on every factor element, so it orders after the whole fit exactly
        # as block_until_ready does (timed equal on a v5e, CHANGES.md PR 21)
        # AND surfaces per-fit solve sanity with zero added host syncs on
        # the happy path.
        from albedo_tpu.utils.watchdog import factor_health, health_dict

        with timer.section("fit.wait"):
            health = health_dict(factor_health(run.user_f, run.item_f))
        t2 = time.perf_counter()
        # What is left is host arithmetic over the bucket shapes (milliseconds
        # where a layout has a thousand of them), after the device is done.
        with timer.section("fit.report"):
            prep_s = round(run.t1 - t0, 4)
            # Systems the exact solve factorises a sweep, and the lanes it solves
            # them at, over every device: each slot row of each bucket, empty
            # slots among them (the ring mode hands back no shapes for the
            # gather's counters, and says the ones it solved apart).
            systems = lane_systems = 0
            if self.solver == "cholesky":
                solved = run.shapes if run.exact_shapes is None else run.exact_shapes
                devices = run.own.get("n_shards", 1)
                systems = devices * exact_systems(solved)
                lane_systems = devices * exact_lane_systems(solved)
            rows = max(1, matrix.n_users + matrix.n_items)
            return {
                "prep_s": prep_s,
                "bucket_s": prep_s if run.bucket_s is None else run.bucket_s,
                "upload_s": run.upload_s,
                "compile_s": round(run.compile_s, 4),
                "compile_source": run.compile_source,
                "device_s": round(t2 - run.t1 - run.compile_s, 4),
                "prep_cached": run.prep_cached,
                "health": health,
                # the synchronous dataflow is the streamed mode's, told apart by
                # the report's own ``pipelined``
                "mode": "sharded_streamed" if path == "sharded_streamed_sync" else path,
                "capacity": None if admission is None else admission.to_dict(),
                "cg_gram_entry_share": (
                    cg_gram_entry_share(run.shapes, self.rank) if self.solver == "cg" else 0.0
                ),
                "gather_reformed_entry_share": gather_reformed_entry_share(run.shapes),
                "gather_packed_entry_share": gather_packed_entry_share(run.shapes, self.rank),
                "exact_systems_per_sweep": systems,
                "exact_system_share": systems / rows,
                "exact_lane_systems_per_sweep": lane_systems,
                "exact_lane_share": lane_systems / rows,
                # the chunked fit's own, where its tables are held in
                # dispatch order (StreamLayout); no other path lands a block
                "landed_in_place_share": 0.0,
                "stream_slot_row_share": 0.0,
                **run.own,
            }

    def _fit_resident(
        self, matrix: StarMatrix, callback: Any | None, timer: Timer, admission
    ) -> "_PathRun":
        """The resident fit: every bucket group uploaded once
        (:meth:`device_groups`), the whole fit one device program — on one
        device, or with ``self.mesh`` under XLA's SPMD partitioner."""
        prep_cached = self._groups_cache_key() in _matrix_cache(matrix)
        with timer.section("fit.prep"):
            ug, ig, u_land, i_land = self.device_groups(matrix, timer)
        prep_split = dict(getattr(self, "last_prep_timings", {}))
        t1 = time.perf_counter()

        def acquire(jitted, args, dyn_kwargs, static_kwargs, name):
            with timer.section("fit.acquire"):
                return persistent_aot_executable(
                    jitted, args, dyn_kwargs, static_kwargs,
                    key_parts=self._aot_key_parts(name, matrix, ug, ig),
                    name=name, timer=timer, span="fit.acquire",
                )

        statics = dict(solver=self.solver, cg_steps=self.cg_steps,
                       gather_dtype=self.gather_dtype)
        landings = dict(user_landing=u_land, item_landing=i_land)
        cross = None
        with timer.section("fit.dispatch"):
            reg = jnp.float32(self.reg_param)
            alpha = jnp.float32(self.alpha)
        if self.init_factors is None and callback is None:
            # Seeded init fused into the training program: the whole fit is
            # ONE dispatch (ops.als.als_init_fit_fused), AOT-compiled through
            # the persistent executable cache (utils.aot) so a fresh process
            # with the same bucket layout skips the trace+compile entirely.
            with timer.section("fit.dispatch"):
                fused_args = (jax.random.PRNGKey(self.seed), ug, ig, reg, alpha,
                              jnp.int32(self.max_iter))
            compiled, compile_s, compile_source = acquire(
                als_init_fit_fused, fused_args, landings,
                dict(n_users=matrix.n_users, n_items=matrix.n_items,
                     rank=self.rank, **statics),
                "als_init_fit_fused",
            )
            with timer.section("fit.dispatch"):
                user_f, item_f = compiled(*fused_args, **landings)
            # Cross-check the static cost model against the compiler's own
            # memory analysis where a verdict and this program's handle are
            # both held — advisory (logged loudly on a >2x underestimate), so
            # a stale model surfaces before it mis-admits a real workload.
            if admission is not None:
                cross = capacity_mod.cross_check(admission.plan, compiled)
        else:
            user_f, item_f = self._initial_factors(matrix)
            if self.mesh is not None:
                from albedo_tpu.parallel.mesh import replicated

                user_f = jax.device_put(user_f, replicated(self.mesh))
                item_f = jax.device_put(item_f, replicated(self.mesh))
            # Without a callback one dispatch runs every sweep; with one, one
            # dispatch a sweep (same program: n_iter is traced), surfacing
            # factors to the host in between. The per-sweep executable is
            # acquired through the AOT layer under a name of its own: the
            # checkpointed chunks this serves are exactly what kill-resume
            # drills re-run in a fresh process, so their cross-process
            # executable reuse must be output-fingerprint verified too (a
            # plain jit call here rode the persistent XLA cache unguarded —
            # the source of the PR 3 drift).
            n_iter = jnp.int32(self.max_iter if callback is None else 1)
            compiled, compile_s, compile_source = acquire(
                als_fit_fused, (user_f, item_f, ug, ig, reg, alpha, n_iter),
                landings, statics,
                "als_fit_fused" if callback is None else "als_fit_step",
            )
            for it in range(1 if callback is None else self.max_iter):
                with timer.section("fit.dispatch"):
                    user_f, item_f = compiled(
                        user_f, item_f, ug, ig, reg, alpha, n_iter, **landings
                    )
                if callback is not None:
                    # The checkpoint callback's contract IS a host copy per
                    # chunk boundary (utils/checkpoint materializes exactly
                    # these) — an intentional, paid-for sync, not a hidden one.
                    # albedo: noqa[hidden-host-sync]
                    callback(it, np.asarray(user_f), np.asarray(item_f))
        return _PathRun(
            user_f, item_f, t1, [scanned_shape(g[1].shape, self.rank) for g in (*ug, *ig)],
            compile_s, compile_source,
            own={"capacity_cross_check": cross},
            bucket_s=prep_split.get("bucket_s", 0.0),
            upload_s=prep_split.get("upload_s", 0.0),
            prep_cached=prep_cached,
        )

    def _initial_factors(self, matrix: StarMatrix, as_array=jnp.asarray) -> tuple:
        """The tables a fit outside the fused program starts from: the warm
        start (``init_factors``, through ``as_array``: the sharded fit keeps
        it on the host until its shards are placed) or the seeded draw,
        computed eagerly."""
        if self.init_factors is not None:
            return tuple(as_array(f, jnp.float32) for f in self.init_factors[:2])
        return seeded_factors(
            jax.random.PRNGKey(self.seed), matrix.n_users, matrix.n_items, self.rank
        )

    def _chunked_executables(self, matrix: StarMatrix, kind: str = "shapes") -> dict:
        """The chunked path's per-shape executables, kept with the matrix's
        layout (``_matrix_cache``) under this estimator's compile-time
        settings and keyed by ``(n_source, n_target, bucket shape)``: a
        second fit of the same estimator and matrix dispatches them as they
        are, as the resident path finds its one program. ``kind=
        "relayouts"``: the tables' relayouts, keyed by ``(rows in, rows
        out)``."""
        key = (
            "chunked_executables", kind, self.solver, self.cg_steps, self.gather_dtype,
            self.rank, jax.default_backend(),
        )
        return _matrix_cache(matrix).setdefault(key, {})

    def _fit_chunked(
        self, matrix: StarMatrix, callback: Any | None, timer: Timer
    ) -> "_PathRun":
        """The degraded-capacity fit: host-streamed bucket groups.

        Only the factor tables stay device-resident; every half-sweep
        re-uploads each bucket's slab and solves it with the SAME kernels as
        the fused path (``ops.als.chunked_bucket_update`` wraps
        ``ops.als.solve_rows``), so the result is
        numerics-parity with the resident path (pinned by
        ``tests/test_als_chunked.py``) — slower, never dead. The layout is
        the path's own (``_host_buckets(matrix, stream=True)``): a bucket is
        a dispatch here, and both tables are held in the layout's DISPATCH
        ORDER (:meth:`_stream_layout`) from after the seeded draw to before
        the health read, so a bucket's rows are one block of its target
        table: the warm start is one slice and the landing one block write.
        Measured on one v5e at 10M x 1M x 100M stars, rank 128
        (``gh10m-r128.fit-streamed``, PERF.md sections 5 and 6, PR 38):
        2,797 ms a sweep over 1,107 dispatches against a resident plan that
        does not fit, where a row scatter landing 70 ns a row (736 ms a
        sweep) held it at 3,372; the block write lands a row in 4 ns (44 ms
        a sweep) and the relayouts cost 25 ms a sweep. The device's programs
        are 2,551 ms of a sweep, and the host now sets the pace: its uploads
        (1.56 s a sweep, 1.4 GB/s through ``jnp.asarray``) and calls (1.31
        s) come to more, and the chip waits 14% of a fit under the uploads. Per-shape
        executables are acquired through the
        persistent AOT layer, NOT bare jit: chunked fits run in exactly the
        kill-resume chaos that exposed the PR 4 XLA-cache custom-call
        corruption, so their cross-process executable reuse must stay
        fingerprint-verified too. Every shape of the layout, and the
        relayout of each table each way, is acquired
        ahead of the first sweep, side by side on a thread pool (abstract
        arguments: no table exists yet, so a probe's tables are the only
        ones on the device), and kept with the matrix for the estimator's
        later fits (:meth:`_chunked_executables`).

        Spans (``CHUNKED_SPANS``): ``fit.prep`` (host bucketing, the
        relabelling into dispatch order and the relayouts' index vectors'
        upload), ``fit.init`` (the seeded tables), ``fit.relayout`` (twice:
        the tables into dispatch order, and back into logical order after
        the last half-sweep), one ``fit.stream`` a half-sweep
        with one ``fit.stream.gramian`` and, a bucket, ``fit.stream.upload``
        (the slab's four arrays) and ``fit.stream.dispatch`` (the compiled
        call; the executable's look-up between them carries no span);
        ``fit.wait`` is the health read that ends the fit. On a cold
        estimator one more ``fit.stream`` comes first, holding one
        ``fit.stream.acquire`` around the acquisition of every program
        (``fit.acquire`` = ``compile_s`` repeats its wall-clock; the AOT
        layer's branches under it are thread-seconds). The host runs ahead
        of the device: a span is the host's time in the call, and what the
        device still owes is in ``fit.wait``.
        """
        with timer.section("fit.prep"):
            layout = self._stream_layout(matrix)
            rows = layout.device_rows()
        user_buckets, item_buckets = layout.user_buckets, layout.item_buckets
        t1 = time.perf_counter()

        statics = dict(
            solver=self.solver, cg_steps=self.cg_steps,
            gather_dtype=self.gather_dtype,
        )
        executables = self._chunked_executables(matrix)
        relayouts = self._chunked_executables(matrix, "relayouts")
        compile_sources: set[str] = set()
        dev = jax.devices()[0]
        f32, i32 = jnp.float32, jnp.int32
        sds = jax.ShapeDtypeStruct
        key_parts = ("als_chunked", jax.__version__, jax.default_backend(),
                     getattr(dev, "device_kind", "?"))

        def acquire(n_source: int, n_target: int, shape: tuple):
            """One shape's executable and where it came from, from abstract
            arguments."""
            args = (
                # the fixed side's table in the form the gather reads it
                jax.eval_shape(gather_table, sds((n_source, self.rank), f32)),
                sds((self.rank, self.rank), f32),
                sds((n_target, self.rank), f32), sds(shape[:1], i32),
                sds(shape, i32), sds(shape, f32), sds(shape, jnp.bool_),
                sds((), f32), sds((), f32),
            )
            compiled, _, source_tag = persistent_aot_executable(
                chunked_bucket_update, args, None, statics,
                key_parts=(
                    *key_parts, self.solver, self.cg_steps, self.gather_dtype,
                    self.rank, n_source, n_target, shape,
                ),
                name="als_chunked", timer=timer, span="fit.stream.acquire",
                donate_argnums=(2,),  # target, as chunked_bucket_update's own
            )
            return compiled, source_tag

        def acquire_relayout(n_rows: int, n_out: int):
            """A table of ``n_rows`` rows into ``n_out`` rows of the other order."""
            args = (sds((n_rows, self.rank), f32), sds((n_out,), i32))
            compiled, _, source_tag = persistent_aot_executable(
                relayout_rows, args, None, None,
                key_parts=(*key_parts, "relayout", self.rank, n_rows, n_out),
                name="als_chunked_relayout", timer=timer, span="fit.stream.acquire",
            )
            return compiled, source_tag

        n_user, n_item = layout.user_order.size, layout.item_order.size
        sides = (
            (n_user, n_item, item_buckets),
            (n_item, n_user, user_buckets),
        )
        missing = [(acquire, key) for key in dict.fromkeys(
            (n_source, n_target, b.shape) for n_source, n_target, buckets in sides
            for b in buckets) if key not in executables]
        missing += [(acquire_relayout, key) for key in dict.fromkeys((
            (matrix.n_users, n_user), (n_user, matrix.n_users),
            (matrix.n_items, n_item), (n_item, matrix.n_items),
        )) if key not in relayouts]
        compile_s = 0.0
        if missing:
            with timer.section("fit.stream"), timer.section("fit.stream.acquire"), \
                    ThreadPoolExecutor(max_workers=_bucket_workers() or 1) as pool:
                for (how, key), (compiled, source_tag) in zip(
                        missing, pool.map(lambda task: task[0](*task[1]), missing)):
                    (executables if how is acquire else relayouts)[key] = compiled
                    compile_sources.add(source_tag)
            compile_s = time.perf_counter() - t1
        timer.add("fit.acquire", compile_s)

        with timer.section("fit.init"):
            user_f, item_f = self._initial_factors(matrix)
            reg = jnp.float32(self.reg_param)
            alpha = jnp.float32(self.alpha)

        def relayout(table, side: str, back: bool = False):
            """``table`` into dispatch order, or ``back`` into logical order."""
            index = rows[side][1 if back else 0]
            return relayouts[table.shape[0], index.shape[0]](table, index)

        with timer.section("fit.relayout"):
            user_f = relayout(user_f, "user")
            item_f = relayout(item_f, "item")

        def half_sweep(source, target, buckets):
            # The chaos hook: an armed kill dies genuinely mid-stream; an
            # armed error/oom surfaces as a failed fit for the pipeline's
            # fail-fast (not retried: is_resource_exhausted) handling.
            _CHUNKED_FAULT.hit()
            with timer.section("fit.stream"):
                with timer.section("fit.stream.gramian"):
                    yty = gramian(source)
                    table = gather_table(source)
                for b in buckets:
                    with timer.section("fit.stream.upload"):
                        slab = (jnp.asarray(b.row_ids), jnp.asarray(b.idx),
                                jnp.asarray(b.val), jnp.asarray(b.mask))
                    compiled = executables[source.shape[0], target.shape[0], b.shape]
                    with timer.section("fit.stream.dispatch"):
                        target = compiled(table, yty, target, *slab, reg, alpha)
            return target

        for it in range(self.max_iter):
            # MLlib order: item factors first (from users), then users.
            item_f = half_sweep(user_f, item_f, item_buckets)
            user_f = half_sweep(item_f, user_f, user_buckets)
            if callback is not None:
                # Checkpoint-callback host copies, by contract (see fit()),
                # of the tables in logical order.
                user_l, item_l = relayout(user_f, "user", True), relayout(item_f, "item", True)
                # albedo: noqa[hidden-host-sync]
                callback(it, np.asarray(user_l), np.asarray(item_l))

        with timer.section("fit.relayout"):
            user_f = relayout(user_f, "user", back=True)
            item_f = relayout(item_f, "item", back=True)

        n_buckets = {"user": len(user_buckets), "item": len(item_buckets)}
        shapes = [b.shape for b in (*user_buckets, *item_buckets)]
        slots = sorted(b for b, _ in shapes) or [0]
        return _PathRun(
            user_f, item_f, t1, shapes,
            compile_s, "+".join(sorted(compile_sources)) or None,
            own={
                "chunked_shapes": len(executables),
                "dispatches": self.max_iter * sum(n_buckets.values()),
                "buckets": n_buckets,
                "streamed_bytes_per_sweep": sum(
                    capacity_mod.bucket_slab_bytes(*shape) for shape in shapes
                ),
                # slot rows a dispatch carries, and the share of the padded
                # entries that travel in a dispatch of more than batch_size
                # rows (_dispatch_rows: none does in the resident layout)
                "rows_per_dispatch": {"median": slots[len(slots) // 2], "largest": slots[-1]},
                "merged_entry_share": (
                    sum(b * ln for b, ln in shapes if b > self.batch_size)
                    / max(1, sum(b * ln for b, ln in shapes))
                ),
                "landed_in_place_share": layout.landed_in_place_share,
                "stream_slot_row_share": layout.slot_row_share(),
            },
            # Host seconds in the per-bucket uploads (inside device_s).
            upload_s=round(timer.totals.get("fit.stream.upload", 0.0), 4),
        )

    def _fit_sharded(
        self, matrix: StarMatrix, callback: Any | None, timer: Timer,
        streamed: bool, pipelined: bool,
    ) -> "_PathRun":
        """The ALX-layout fit: BOTH factor tables row-sharded over the
        mesh's data axis, per-device bucket blocks solved against
        all-gathered (or ring-passed) source shards inside shard_map, and —
        when ``streamed`` — interaction buckets uploaded per half-sweep so
        the star matrix is never device-resident whole. The dataflow is
        PIPELINED (double-buffered bucket prefetch, overlapped
        ring phases, fused landing scatter) unless ``pipelined`` is false,
        which is the synchronous PR 8 dataflow. Same
        kernels as every other path (``ops.als.solve_rows`` via
        ``parallel.als.ShardedALSFit``), per-shape
        executables through the persistent AOT layer, and the watchdog
        health reduction as the completion barrier — parity with the
        single-device resident fit is test-pinned at atol 1e-5.

        Resident buckets under ``shard_mode="allgather"`` (what ``train_als
        --mesh-devices n --sharded resident`` builds) take the dataflow of
        :meth:`_fit_sharded_resident` instead: the source table assembled
        ONCE a half-sweep, every device solving its own rows.
        """
        from albedo_tpu.parallel.als import (
            assembled_bytes_per_sweep,
            collective_bytes_per_sweep,
            sharded_fit_engine,
        )
        from albedo_tpu.parallel.mesh import DATA_AXIS

        engine = sharded_fit_engine(
            self.mesh, DATA_AXIS, self.solver, self.cg_steps,
            self.gather_dtype, self.shard_mode,
        )
        if not streamed and self.shard_mode == "allgather":
            return self._fit_sharded_resident(matrix, callback, timer, engine)
        with timer.section("fit.prep"):
            user_buckets, item_buckets = self._host_buckets(matrix)
        t1 = time.perf_counter()

        user_f, item_f = self._initial_factors(matrix, np.asarray)
        user_f, item_f, stats = engine.fit(
            user_f, item_f, user_buckets, item_buckets,
            self.reg_param, self.alpha, self.max_iter,
            streamed=streamed, callback=callback, pipelined=pipelined,
        )
        timer.add("fit.acquire", stats["compile_s"])
        # Each device gathers and solves its own slots of a bucket (padded to
        # a multiple of the shards).
        n, ring = engine.n_shards, self.shard_mode == "ring"
        local = [(-(-b.shape[0] // n), b.shape[1]) for b in (*user_buckets, *item_buckets)]
        # Every bucket's program moves whole tables: all-gathered (the source,
        # and under CG the target), or ring-passed shard by shard and never
        # assembled; then its solved rows, all-gathered to land.
        tables = assembled_bytes_per_sweep(
            matrix.n_users, matrix.n_items, self.rank, n,
            (len(user_buckets), len(item_buckets)), self.solver,
        )
        return _PathRun(
            user_f, item_f, t1,
            # The ring mode gathers phase by phase from a table shard, not
            # through ``ops.als._gather``, and is Cholesky only.
            [] if ring else local,
            stats["compile_s"], "+".join(sorted(stats["compile_sources"])) or None,
            own={
                "shard_mode": self.shard_mode,
                "n_shards": n,
                "streamed_buckets": stats["streamed_buckets"],
                "sharded_shapes": stats["n_shapes"],
                "assembled_bytes_per_sweep": 0 if ring else tables,
                "collective_bytes_per_sweep": collective_bytes_per_sweep(
                    self.rank, n, tables, landed_rows=n * sum(b for b, _ in local)),
                "dispatches": stats["dispatches"],
                "shard_padded_entries": sum(b * ln for b, ln in local),
                # Pipelined-dataflow accounting: upload_s accumulates inside the
                # background prefetch thread when pipelined+streamed, so it is
                # OFF the critical path there; prefetch_wait_s is the time the
                # sweep actually stalled waiting for a bucket — the visible
                # (un-hidden) remainder of the upload cost.
                "pipelined": stats["pipelined"],
                "prefetch_wait_s": stats["prefetch_wait_s"],
                # Elasticity cost surface: a bare sharded fit observed no mesh
                # events; the elastic driver (parallel/elastic.py) overwrites
                # this with its loss/resume/checkpoint record.
                "mesh_events": {
                    "losses": 0, "resumes": 0, "degradations": 0,
                    "checkpoint_s": 0.0, "n_shards": engine.n_shards,
                },
            },
            upload_s=stats["upload_s"],
            exact_shapes=local if ring else None,
        )

    def _sharded_groups_cache_key(self) -> tuple:
        return ("device_sharded", *self._groups_cache_key()[1:])

    def _device_groups_sharded(self, matrix: StarMatrix, timer: Timer, engine) -> tuple:
        """``(user_groups, item_groups, user_landing, item_landing,
        user_rows, item_rows)`` on the mesh for the resident row-sharded fit.
        Rows are dealt to the shards in turn by length
        (``datasets.ragged.balanced_shards``), so every shard holds the same
        lengths and the shapes are the degree sequence's alone; every
        shard's OWN rows are bucketed (``shard_grouped_bucket_rows``, source
        indices in the other side's dealt order), each group's slot axis
        laid over the mesh's data axis, each side's per-shard landing
        permutations (``_landing_perm`` of a shard's slots, local row ids)
        stacked under the row sharding. ``*_rows`` are a side's
        ``(logical_of_phys, phys_of_logical)`` on the mesh: the order the
        seeded tables are laid out in, and the way back. Memoized per
        (matrix, layout, mesh, backend) like :meth:`device_groups`, with the
        same spans: ``fit.prep.index`` (the CSR + CSC build and the deal),
        ``fit.prep.fill`` and ``fit.prep.upload`` (= ``upload_s``)."""
        key = self._sharded_groups_cache_key()
        cache = _matrix_cache(matrix)
        if key in cache:
            self.last_prep_timings = {"bucket_s": 0.0, "upload_s": 0.0}
            return cache[key]
        t0 = time.perf_counter()
        n = engine.n_shards
        with timer.section("fit.prep.index"), ThreadPoolExecutor(max_workers=2) as sides:
            csr_f, csc_f = sides.submit(matrix.csr), sides.submit(matrix.csc)
            csr, csc = csr_f.result(), csc_f.result()
            dealt = [balanced_shards(csx[0], n) for csx in (csr, csc)]

        def build_side(csx, rows, source_rows):
            indptr, indices, vals = csx
            with timer.section("fit.prep.fill"):
                groups = shard_grouped_bucket_rows(
                    indptr, source_rows[0][indices], vals, rows[1], n,
                    **self._layout_kwargs(), workers=_bucket_workers())
                landing = _shard_landing_perm(groups, n, shard_rows(indptr.shape[0] - 1, n))
            with timer.section("fit.prep.upload"):
                return ([engine.put_group(g) for g in groups], engine.put_rows(landing),
                        (engine.put_rows(rows[1]), engine.put_rows(rows[0])))

        ug, u_land, u_rows = build_side(csr, dealt[0], dealt[1])
        ig, i_land, i_rows = build_side(csc, dealt[1], dealt[0])
        upload = timer.totals["fit.prep.upload"]
        self.last_prep_timings = {
            "bucket_s": round(max(0.0, time.perf_counter() - t0 - upload), 4),
            "upload_s": round(upload, 4),
        }
        cache[key] = (ug, ig, u_land, i_land, u_rows, i_rows)
        return cache[key]

    def _fit_sharded_resident(
        self, matrix: StarMatrix, callback: Any | None, timer: Timer, engine
    ) -> "_PathRun":
        """The row-sharded fit with resident buckets (``sharded="resident"``,
        ``shard_mode="allgather"``: the ALX layout, arXiv:2112.02194). Both
        tables stay row-sharded over the mesh from the seeded draw to the
        returned model; every device buckets, solves and lands its OWN rows
        with the one-chip sweep's kernels (``ops.als.scan_group``), and the
        only bytes that cross the mesh are each source table's other shards
        ONCE a half-sweep and the ``(k, k)`` psum
        (``parallel/als.py``, "the resident dataflow").

        Spans (``SHARDED_SPANS``): ``fit.prep`` (bucketing each shard's rows
        and the one upload, ``.index`` / ``.fill`` / ``.upload`` inside),
        ``fit.acquire`` (every shape's executable ahead of the first sweep,
        on threads; = ``compile_s``), ``fit.init`` (the seeded tables, made
        on the mesh), one ``fit.shard`` a half-sweep holding
        ``fit.shard.gramian`` (the psum program's dispatch),
        ``fit.shard.assemble`` (the assembly's) and ``fit.shard.dispatch``
        (a program a shape group and the landing); ``fit.relayout`` (the
        fitted tables back into the logical row order) and ``fit.wait`` end
        the fit. Device scopes: ``als.shard.gramian``, ``als.shard.assemble``
        (the all-gather and the relayout ``gather_table`` needs),
        ``als.shard.land``, ``als.shard.relayout`` (the seeded tables into
        the order the shards own rows in, and the fitted ones back), and
        ``als.gather`` / ``als.cg`` inside the group programs. Counters: see
        the ``own`` keys below.
        """
        from albedo_tpu.parallel.als import collective_bytes_per_sweep

        prep_cached = self._sharded_groups_cache_key() in _matrix_cache(matrix)
        with timer.section("fit.prep"):
            ug, ig, u_land, i_land, u_rows, i_rows = self._device_groups_sharded(
                matrix, timer, engine)
        prep_split = dict(self.last_prep_timings)
        t1 = time.perf_counter()

        n = engine.n_shards
        shapes_u = [tuple(g[1].shape) for g in ug]
        shapes_i = [tuple(g[1].shape) for g in ig]
        stats = {"compile_s": 0.0, "compile_sources": set(), "dispatches": 0,
                 "assembled_bytes": 0}
        sizes = (matrix.n_users, matrix.n_items, self.rank)
        with timer.section("fit.acquire"):
            engine.acquire_local(*sizes, shapes_u, shapes_i, stats, timer,
                                 workers=_bucket_workers() or 1)
        compile_s = time.perf_counter() - t1

        with timer.section("fit.init"):
            if self.init_factors is None:
                user_sh, item_sh = engine.seeded_tables(
                    jax.random.PRNGKey(self.seed), u_rows[0], i_rows[0], *sizes, stats)
            else:
                # a warm start comes in the logical order, like the model goes out
                user_sh, item_sh = (
                    engine.relayout(engine.shard_table(f), rows[0], stats)
                    for f, rows in zip(self._initial_factors(matrix, np.asarray), (u_rows, i_rows)))

        def logical(user_sh, item_sh):
            return (engine.relayout(user_sh, u_rows[1], stats),
                    engine.relayout(item_sh, i_rows[1], stats))

        def after_sweep(it, user_sh, item_sh):
            # Checkpoint-callback host copies, by contract (see fit()).
            user_f, item_f = logical(user_sh, item_sh)
            callback(
                it,
                np.asarray(user_f)[:matrix.n_users],   # albedo: noqa[hidden-host-sync]
                np.asarray(item_f)[:matrix.n_items],   # albedo: noqa[hidden-host-sync]
            )

        user_sh, item_sh = engine.fit_local(
            user_sh, item_sh, ug, ig, u_land, i_land, self.reg_param, self.alpha,
            self.max_iter, stats, timer, after_sweep=None if callback is None else after_sweep,
        )
        with timer.section("fit.relayout"):
            user_sh, item_sh = logical(user_sh, item_sh)
        # each device's own slots of a group, in the pieces it scans them in
        local = [scanned_shape((s[0], s[1] // n, s[2]), self.rank)
                 for s in (*shapes_u, *shapes_i)]
        # what the compiled programs of the sweeps all-gathered, a chip a sweep
        assembled = stats["assembled_bytes"] // max(1, self.max_iter)
        return _PathRun(
            user_sh, item_sh, t1, local, compile_s,
            "+".join(sorted(stats["compile_sources"])) or None,
            own={
                "shard_mode": self.shard_mode,
                "n_shards": n,
                "streamed_buckets": 0,
                "sharded_shapes": len(set(shapes_u)) + len(set(shapes_i)),
                "pipelined": False,
                "prefetch_wait_s": 0.0,
                # counted from the executables the sweeps called (one assembly
                # of each table a sweep is the plan); what of it crosses the
                # mesh into a chip, with the two psums
                "assembled_bytes_per_sweep": assembled,
                "collective_bytes_per_sweep": collective_bytes_per_sweep(self.rank, n, assembled),
                "dispatches": stats["dispatches"],
                "shard_padded_entries": sum(math.prod(s) for s in local),
                "mesh_events": {
                    "losses": 0, "resumes": 0, "degradations": 0,
                    "checkpoint_s": 0.0, "n_shards": n,
                },
            },
            bucket_s=prep_split.get("bucket_s", 0.0),
            upload_s=prep_split.get("upload_s", 0.0),
            prep_cached=prep_cached,
        )


@dataclasses.dataclass
class _PathRun:
    """What a fit path hands back for the barrier and the report
    (``ImplicitALS._finish``)."""

    user_f: Any
    item_f: Any
    t1: float                     # perf_counter at the end of the host prep
    shapes: list                  # bucket shapes as the kernels were handed them
    compile_s: float
    compile_source: str | None
    own: dict                     # the report keys only this path has
    bucket_s: float | None = None  # None: all of prep_s (no upload in prep)
    upload_s: float = 0.0
    prep_cached: bool = False
    exact_shapes: list | None = None  # what the exact solve ran at, where ``shapes`` do not say
