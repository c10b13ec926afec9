"""Ragged -> dense bucketing for XLA-friendly sparse row access.

The ALS sweep needs, per user (or per item on the alternate sweep), the dense
gather indices and ratings of that row's nonzeros. Row lengths follow a power
law, so one global pad-to-max would waste most of the FLOPs. Instead rows are
sorted by length and chunked into fixed-size batches, each padded to its own
power-of-two-ish length: XLA compiles one kernel per distinct (batch, length)
shape, of which there are O(log max_len) (SURVEY.md section 7 hard part (a)).

This is the TPU-native replacement for Spark MLlib ALS's shuffled
user/item blocks, and for ``ALSRecommender.blockify`` (4096-row blocks,
``recommenders/ALSRecommender.scala:21-24``).
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

import numpy as np


def segment_positions(counts: np.ndarray) -> np.ndarray:
    """0..count-1 position indices within each segment of a flat ragged array.

    For ``counts = [3, 2]`` returns ``[0, 1, 2, 0, 1]``. The shared idiom for
    walking concatenated per-user / per-sentence segments without a Python
    loop (used by the negative balancer and the skip-gram pair builder).
    """
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    starts = np.cumsum(counts) - counts
    return np.arange(total, dtype=np.int64) - np.repeat(starts, counts)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A fixed-shape batch of padded rows.

    ``row_ids[b]`` is the dense row index this slot solves for; padding slots
    have ``row_ids == -1``. ``idx/val`` are ``(B, L)`` with ``val == 0`` on pads
    (so confidence weights vanish); ``idx`` points at row 0 on pads, which is
    harmless under a zero weight.
    """

    row_ids: np.ndarray  # (B,) int32, -1 for padding slots
    idx: np.ndarray      # (B, L) int32 column indices
    val: np.ndarray      # (B, L) float32 ratings, 0 on padding
    mask: np.ndarray     # (B, L) bool

    @property
    def shape(self) -> tuple[int, int]:
        return self.idx.shape  # type: ignore[return-value]


def _pad_len(n: int, multiple: int) -> int:
    """Round up to the next length tier.

    Tiers are powers of two up to ``2 * multiple``, then ~1.15x geometric
    steps rounded up to ``multiple``. Pure power-of-two tiers cost up to 2x
    padding per row (measured 2.7x overall on the bench matrix); 1.15x steps
    bound per-row waste at ~15% (bench-matrix total overhead 1.48x vs 1.52x
    at 1.25x steps) while keeping the distinct-shape count (and therefore
    XLA kernel count) logarithmic in max_len (~33 shapes per sweep).
    """
    t = 1
    while t < n and t < 2 * multiple:
        t *= 2
    while t < n:
        nxt = ((int(t * 1.15) + multiple - 1) // multiple) * multiple
        t = max(nxt, t + multiple)  # strict growth even when rounding truncates
    return t


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """One bucket's layout, decided before any array is filled.

    Splitting planning (a cheap sequential scan over the length-sorted rows)
    from filling (per-bucket NumPy scatters that release the GIL) is what lets
    the cold-path pipeline fill buckets on a thread pool and upload finished
    shape groups while later ones are still being packed — the plan fixes the
    exact same chunk boundaries and tier shapes the sequential path produces,
    so the filled buckets are byte-identical however they are scheduled.
    """

    rows: np.ndarray         # (n_take,) dense row ids, length-sorted chunk order
    shape: tuple[int, int]   # (B, L) allocated slot/length tiers
    cap: int                 # per-row entry cap (pad length or max_len)


def _slot_tier(n: int) -> int:
    """Quantize a bucket's slot count: powers of two up to 1024, then
    1024-multiples — the same tiers :func:`plan_buckets` allocates."""
    if n > 1024:
        return -(-n // 1024) * 1024
    return 1 << max(0, (n - 1).bit_length())


def rows_allowed(length: int, batch_size: int, max_entries: int | None) -> int:
    """Rows one bucket of padded length ``length`` may hold: ``batch_size``,
    or as many as keep its flat count at ``max_entries`` or under. The one
    allowance :func:`plan_buckets` and :func:`coalesce_buckets` chunk by."""
    if max_entries is None:
        return batch_size
    return max(1, min(batch_size, max_entries // max(1, length)))


def coalesce_buckets(
    buckets,
    batch_size: int = 1024,
    max_entries: int | None = None,
):
    """Stream-merge same-width partial buckets into full ones.

    Out-of-core generation (``datasets.synthetic.generate_scale_dataset``)
    packs each user chunk independently, so every length tier ends in a
    partial bucket PER CHUNK — at n chunks the half-sweep dispatches ~n
    buckets per tier where one would do, and per-dispatch overhead grows
    linearly with the user count. This generator merges valid rows of
    same-``L`` buckets as they stream past, emitting full
    ``min(batch_size, max_entries // L)``-row buckets and flushing the
    per-tier remainders at the end (slot counts re-quantized to the
    planner's own tiers, so the merged shapes come from the same shape
    universe the capacity model prices).

    Numerically invisible by construction: every row keeps its exact
    entries and pad width (only same-``L`` buckets merge), each row still
    appears in exactly one bucket, and within-half-sweep bucket order is
    already irrelevant to the solves — pinned by the scale-harness parity
    tests. Host cost is one concatenation pass (~bytes of the slabs);
    what it buys is an ~n-fold cut in dispatch count on chunked data.
    """
    pending: dict[int, list] = {}  # L -> [row_ids, idx, val, mask] valid-only

    def build(parts, n_lo, n_hi, length, allowed):
        """One padded bucket from pending[L] rows [n_lo:n_hi)."""
        n = n_hi - n_lo
        b = max(n, min(_slot_tier(n), allowed))
        out = Bucket(
            row_ids=np.full((b,), -1, dtype=np.int32),
            idx=np.zeros((b, length), dtype=np.int32),
            val=np.zeros((b, length), dtype=np.float32),
            mask=np.zeros((b, length), dtype=bool),
        )
        out.row_ids[:n] = parts[0][n_lo:n_hi]
        out.idx[:n] = parts[1][n_lo:n_hi]
        out.val[:n] = parts[2][n_lo:n_hi]
        out.mask[:n] = parts[3][n_lo:n_hi]
        return out

    for bk in buckets:
        length = int(bk.idx.shape[1])
        allowed = rows_allowed(length, batch_size, max_entries)
        valid = int((bk.row_ids >= 0).sum())  # fills front-pack valid rows
        if length not in pending and valid == bk.row_ids.shape[0] == allowed:
            yield bk  # already a full canonical bucket: pass through, no copy
            continue
        parts = pending.get(length)
        if parts is None:
            parts = pending[length] = [
                bk.row_ids[:valid], bk.idx[:valid], bk.val[:valid], bk.mask[:valid]
            ]
        else:
            for i, arr in enumerate(
                (bk.row_ids[:valid], bk.idx[:valid], bk.val[:valid], bk.mask[:valid])
            ):
                parts[i] = np.concatenate([parts[i], arr])
        n_have = parts[0].shape[0]
        lo = 0
        while n_have - lo >= allowed:
            yield build(parts, lo, lo + allowed, length, allowed)
            lo += allowed
        if lo:
            for i in range(4):
                parts[i] = parts[i][lo:]
            if parts[0].shape[0] == 0:
                del pending[length]
    for length, parts in sorted(pending.items()):
        n = parts[0].shape[0]
        if not n:
            continue
        yield build(parts, 0, n, length, rows_allowed(length, batch_size, max_entries))


def slot_tier_floor(n: int) -> int:
    """The largest slot count up to ``n`` that :func:`_slot_tier` leaves as
    it is (``n`` >= 1): what a bucket filled to the brim should hold, so that
    a length tier has two shapes at most, the full bucket and the remainder."""
    return n // 1024 * 1024 if n >= 1024 else 1 << (n.bit_length() - 1)


def plan_buckets(
    indptr: np.ndarray,
    batch_size: int = 1024,
    len_multiple: int = 8,
    max_len: int | None = None,
    max_entries: int | None = None,
    rows_of: Callable[[int], int] | None = None,
) -> list[BucketPlan]:
    """Chunk CSR rows into fixed-shape bucket layouts (no fills yet).

    Rows are sorted by nonzero count so batch-mates have similar lengths; each
    batch is padded to a power-of-two-ish length (bounded padding waste,
    bounded compile count). ``max_entries`` bounds ``B * L`` per bucket so the
    downstream ``(B, L, rank)`` factor gather fits in device memory. Empty
    rows are skipped: ALS leaves those factors at their current value,
    matching cold-start behavior.

    ``rows_of(L)`` stands in for :func:`rows_allowed` where a path has a row
    allowance of its own for buckets of padded length ``L`` (the chunked
    fit's, ``models.als.ImplicitALS._dispatch_rows``): the same tiers, the
    same rows in the same order, each at the pad width it had, chunked at
    another count.
    """
    lengths = np.diff(indptr)
    nonempty = np.nonzero(lengths > 0)[0]
    # Stable sort by length keeps determinism across runs.
    order = nonempty[np.argsort(lengths[nonempty], kind="stable")]
    eff = lengths[order]
    if max_len is not None:
        eff = np.minimum(eff, max_len)

    def tier(n: int) -> int:
        pad_l = _pad_len(n, len_multiple)
        if max_len is not None:
            # Don't let tier rounding blow past the explicit bound.
            pad_l = min(pad_l, -(-max_len // len_multiple) * len_multiple)
            pad_l = max(pad_l, n)
        return pad_l

    plans: list[BucketPlan] = []
    start = 0
    n_rows = order.shape[0]
    while start < n_rows:
        # One bucket = consecutive (length-sorted) rows within one length tier,
        # so no row pads more than one tier up (~15%); slots are allocated for
        # the rows actually present (next power of two), so a tail bucket of a
        # few very long rows doesn't burn batch_size slots of padding.
        pad_l = tier(int(eff[start]))
        allowed = (
            rows_allowed(pad_l, batch_size, max_entries) if rows_of is None
            else rows_of(pad_l)
        )
        # (eff ascends: the tier ends at the first longer row)
        end = min(start + allowed, int(np.searchsorted(eff, pad_l, side="right")))
        n_take = end - start
        # Slot-count tiers (`_slot_tier`, ONE definition — the streaming
        # coalescer re-quantizes merged buckets through the same rule):
        # powers of two up to 1024, then 1024-multiples. Pure pow-2
        # rounding wastes up to 2x SOLVE slots per bucket once batches are
        # wide (measured +20% padded entries at batch_size=8192);
        # 1024-steps bound slot waste at ~12% with a still-small shape count.
        b = _slot_tier(n_take)
        # Never exceed the caller's slot budget (or entry budget): tier
        # rounding quantizes shapes but must not grow the bucket past them.
        b = max(n_take, min(b, allowed))
        cap = pad_l if max_len is None else min(pad_l, max_len)
        plans.append(BucketPlan(rows=order[start:end], shape=(b, pad_l), cap=cap))
        start = end
    return plans


def fill_bucket(
    plan: BucketPlan,
    indptr: np.ndarray,
    indices: np.ndarray,
    vals: np.ndarray,
    out: Bucket | None = None,
) -> Bucket:
    """Execute one plan's scatter fill. ``out`` (zero-initialized arrays,
    ``row_ids`` pre-filled with -1 — possibly views into a preallocated group
    slab) lets the grouped builder fill stacked arrays in place, skipping the
    ``np.stack`` copy the group step used to pay."""
    b, pad_l = plan.shape
    if out is None:
        out = Bucket(
            row_ids=np.full((b,), -1, dtype=np.int32),
            idx=np.zeros((b, pad_l), dtype=np.int32),
            val=np.zeros((b, pad_l), dtype=np.float32),
            mask=np.zeros((b, pad_l), dtype=bool),
        )
    chunk = plan.rows
    n_take = chunk.shape[0]
    # Vectorized slot fill (one scatter per bucket, no per-row Python):
    # rows over cap keep their TAIL = most recent entries in insert order.
    hi = indptr[chunk + 1].astype(np.int64)
    take = np.minimum(hi - indptr[chunk].astype(np.int64), plan.cap)
    pos = segment_positions(take)
    slot_of = np.repeat(np.arange(n_take), take)
    flat = np.repeat(hi - take, take) + pos
    out.row_ids[:n_take] = chunk
    out.idx[slot_of, pos] = indices[flat]
    out.val[slot_of, pos] = vals[flat]
    out.mask[slot_of, pos] = True
    return out


def bucket_rows(
    indptr: np.ndarray,
    indices: np.ndarray,
    vals: np.ndarray,
    batch_size: int = 1024,
    len_multiple: int = 8,
    max_len: int | None = None,
    max_entries: int | None = None,
    workers: int | None = None,
    rows_of: Callable[[int], int] | None = None,
) -> list[Bucket]:
    """Chunk CSR rows into fixed-shape padded batches (plan + fill).

    Rows longer than ``max_len`` are truncated to their most recent
    ``max_len`` entries, mirroring the reference's ``maxStarredReposCount``
    cap (``LogisticRegressionRanker.scala:133``).

    With ``workers`` > 1 the per-bucket scatter fills run on a thread pool
    (they are pure NumPy and release the GIL); the bucket list is returned in
    plan order either way, so the output is byte-identical to the sequential
    path — enforced by the parity test. ``rows_of`` is the planner's
    (:func:`plan_buckets`: a path's own row allowance).
    """
    plans = plan_buckets(
        indptr, batch_size=batch_size, len_multiple=len_multiple,
        max_len=max_len, max_entries=max_entries, rows_of=rows_of,
    )

    def fill(p: BucketPlan) -> Bucket:
        return fill_bucket(p, indptr, indices, vals)

    if workers and workers > 1 and len(plans) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            return list(pool.map(fill, plans))
    return [fill(p) for p in plans]


def grouped_bucket_rows(
    indptr: np.ndarray,
    indices: np.ndarray,
    vals: np.ndarray,
    batch_size: int = 1024,
    len_multiple: int = 8,
    max_len: int | None = None,
    max_entries: int | None = None,
    workers: int | None = None,
    on_group: Callable[[int, Bucket], None] | None = None,
) -> list[Bucket]:
    """Plan, group by shape, and fill straight into the stacked group slabs.

    Byte-identical to ``group_buckets(bucket_rows(...))`` (parity-tested) but
    with one less full copy of the data: each bucket's scatter fill writes
    directly into its ``(N, B, L)`` group slab slice instead of filling a
    standalone bucket that ``np.stack`` then copies.

    ``on_group(i, group)`` fires in shape-sorted group order as soon as group
    ``i``'s fills complete — the hook the cold-path pipeline uses to start the
    (async) host->device upload of a finished group while the thread pool is
    still filling later ones.
    """
    plans = plan_buckets(
        indptr, batch_size=batch_size, len_multiple=len_multiple,
        max_len=max_len, max_entries=max_entries,
    )
    by_shape: dict[tuple[int, int], list[BucketPlan]] = {}
    for p in plans:
        by_shape.setdefault(p.shape, []).append(p)
    ordered = sorted(by_shape.items())

    groups: list[Bucket] = []
    tasks: list[tuple[int, int, BucketPlan]] = []
    for gi, ((b, pad_l), ps) in enumerate(ordered):
        n = len(ps)
        groups.append(
            Bucket(
                row_ids=np.full((n, b), -1, dtype=np.int32),
                idx=np.zeros((n, b, pad_l), dtype=np.int32),
                val=np.zeros((n, b, pad_l), dtype=np.float32),
                mask=np.zeros((n, b, pad_l), dtype=bool),
            )
        )
        tasks.extend((gi, si, p) for si, p in enumerate(ps))

    def fill(task: tuple[int, int, BucketPlan]) -> None:
        gi, si, p = task
        g = groups[gi]
        fill_bucket(
            p, indptr, indices, vals,
            out=Bucket(row_ids=g.row_ids[si], idx=g.idx[si], val=g.val[si], mask=g.mask[si]),
        )

    if workers and workers > 1 and len(tasks) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            futures: dict[int, list] = {}
            for task in tasks:
                futures.setdefault(task[0], []).append(pool.submit(fill, task))
            # Groups complete roughly in submission order; notifying in shape
            # order lets the caller upload group 0 while group N still fills.
            for gi in range(len(groups)):
                for f in futures.get(gi, []):
                    f.result()
                if on_group is not None:
                    on_group(gi, groups[gi])
    else:
        done = 0
        for gi in range(len(groups)):
            while done < len(tasks) and tasks[done][0] == gi:
                fill(tasks[done])
                done += 1
            if on_group is not None:
                on_group(gi, groups[gi])
    return groups


def shard_rows(n_rows: int, n_shards: int) -> int:
    """Rows a shard of a row-sharded ``n_rows``-row table: contiguous ranges
    of one size, the last padded (``parallel.mesh.pad_rows_to``)."""
    return -(-n_rows // n_shards)


def balanced_shards(indptr: np.ndarray, n_shards: int) -> tuple[np.ndarray, np.ndarray]:
    """Which shard owns which row, so that every shard holds the same row
    lengths to within one row: rows dealt to the shards in turn in order of
    their length (stable: by length, then by row id). Returns
    ``(phys_of_logical, logical_of_phys)``, two permutations of
    ``range(n_shards * shard_rows(n_rows, n_shards))``, inverse to one
    another: physical row ``d * rows_per + i`` is shard ``d``'s ``i``-th row,
    and the few ids from ``n_rows`` up are the table's zero padding rows on
    both sides.

    Ownership by contiguous id ranges would hand each shard whatever lengths
    its ids happen to have: a half-sweep then takes as long as the shard that
    drew the heaviest rows, its bucket shapes differ from matrix to matrix of
    the same degree sequence (every seed compiles anew), and its time from
    seed to seed. Dealt in turn, the shards' bucket plans are the same
    function of the degree sequence alone."""
    n_rows = indptr.shape[0] - 1
    rows_per = shard_rows(n_rows, n_shards)
    n_pad = rows_per * n_shards
    order = np.argsort(np.diff(indptr), kind="stable")
    rank = np.arange(n_rows, dtype=np.int64)
    logical_of_phys = np.full(n_pad, -1, dtype=np.int32)
    logical_of_phys[(rank % n_shards) * rows_per + rank // n_shards] = order
    # the padding rows, logical and physical, stand for one another
    logical_of_phys[logical_of_phys < 0] = np.arange(n_rows, n_pad, dtype=np.int32)
    phys_of_logical = np.empty(n_pad, dtype=np.int32)
    phys_of_logical[logical_of_phys] = np.arange(n_pad, dtype=np.int32)
    return phys_of_logical, logical_of_phys


def shard_grouped_bucket_rows(
    indptr: np.ndarray,
    indices: np.ndarray,
    vals: np.ndarray,
    logical_of_phys: np.ndarray,
    n_shards: int,
    batch_size: int = 1024,
    len_multiple: int = 8,
    max_len: int | None = None,
    max_entries: int | None = None,
    workers: int | None = None,
) -> list[Bucket]:
    """Bucket every shard's OWN rows (the ALX layout, arXiv:2112.02194):
    shape groups ``(N, n_shards * B, L)`` as ``grouped_bucket_rows`` makes
    them, in which slots ``[d * B, (d + 1) * B)`` of every bucket hold rows
    of shard ``d`` under row ids LOCAL to that shard. ``logical_of_phys``
    says which rows those are (:func:`balanced_shards`: shard ``d``'s
    ``i``-th row is CSR row ``logical_of_phys[d * rows_per + i]``);
    ``indices`` are written into the slabs as they are, so they come in the
    numbering of the OTHER side's table as the devices hold it.

    Each shard's rows are planned alone (``plan_buckets``: the same length
    tiers, chunked by the same slot and entry budgets), and bucket ``j`` of a
    length tier takes the slot count of the shard that has most rows in it,
    so that the batch axis splits evenly over a mesh axis of ``n_shards``
    and ``shard_map`` sees one shape. A shard with fewer rows there has
    empty slots (``row_ids == -1``, zero weight), and one with no bucket
    ``j`` at all an empty bucket. With the slot axis sharded over the mesh,
    a device solves its own rows from its own slots: warm starts are read
    from, and solved rows land in, its own shard of the table."""
    n_rows = indptr.shape[0] - 1
    rows_per = shard_rows(n_rows, n_shards)
    lengths = np.diff(indptr)
    owned: list[np.ndarray] = []          # a shard's rows, by local row id
    tiers: list[dict[int, list[BucketPlan]]] = []
    for d in range(n_shards):
        rows = logical_of_phys[d * rows_per:(d + 1) * rows_per]
        rows = rows[rows < n_rows]        # (padding rows come last)
        owned.append(rows)
        local_indptr = np.zeros(rows.shape[0] + 1, dtype=np.int64)
        np.cumsum(lengths[rows], out=local_indptr[1:])
        by_len: dict[int, list[BucketPlan]] = {}
        for p in plan_buckets(
            local_indptr, batch_size=batch_size, len_multiple=len_multiple,
            max_len=max_len, max_entries=max_entries,
        ):
            by_len.setdefault(p.shape[1], []).append(p)
        tiers.append(by_len)
    by_shape: dict[tuple[int, int], list[list[BucketPlan | None]]] = {}
    for length in sorted(set().union(*tiers)):
        lists = [t.get(length, []) for t in tiers]
        for j in range(max(map(len, lists))):
            plans = [ps[j] if j < len(ps) else None for ps in lists]
            b = max(p.shape[0] for p in plans if p is not None)
            by_shape.setdefault((b, length), []).append(plans)

    groups: list[Bucket] = []
    tasks: list[tuple[Bucket, int, BucketPlan]] = []
    for (b, pad_l), buckets in sorted(by_shape.items()):
        n = len(buckets)
        g = Bucket(
            row_ids=np.full((n, n_shards * b), -1, dtype=np.int32),
            idx=np.zeros((n, n_shards * b, pad_l), dtype=np.int32),
            val=np.zeros((n, n_shards * b, pad_l), dtype=np.float32),
            mask=np.zeros((n, n_shards * b, pad_l), dtype=bool),
        )
        groups.append(g)
        for si, plans in enumerate(buckets):
            for d, p in enumerate(plans):
                if p is not None:
                    mine = slice(d * b, (d + 1) * b)
                    out = Bucket(row_ids=g.row_ids[si, mine], idx=g.idx[si, mine],
                                 val=g.val[si, mine], mask=g.mask[si, mine])
                    tasks.append((out, d, p))

    def fill(task: tuple[Bucket, int, BucketPlan]) -> None:
        out, d, p = task
        # filled from the CSR rows the local ids stand for, kept under the local ids
        fill_bucket(dataclasses.replace(p, rows=owned[d][p.rows]), indptr, indices, vals, out=out)
        out.row_ids[:p.rows.shape[0]] = p.rows

    if workers and workers > 1 and len(tasks) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(fill, tasks))
    else:
        for task in tasks:
            fill(task)
    return groups


def padded_rows(
    indptr: np.ndarray, indices: np.ndarray, rows: np.ndarray, fill: int = -1
) -> np.ndarray:
    """Gather CSR rows into one ``(len(rows), max_len)`` dense array, padded
    with ``fill`` — fully vectorized (no per-row Python loop).

    The seen-item exclusion mask of the retrieval path (the PySpark track's
    ``recommend_items`` exclusion, ``albedo_toolkit/common.py:47-71``) is this
    gather over the requested users.
    """
    rows = np.asarray(rows)
    lens = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
    width = max(1, int(lens.max())) if rows.size else 1
    out = np.full((rows.size, width), fill, dtype=np.int32)
    pos = segment_positions(lens)
    out_rows = np.repeat(np.arange(rows.size), lens)
    flat = np.repeat(indptr[rows].astype(np.int64), lens) + pos
    out[out_rows, pos] = indices[flat]
    return out


def csr_row(indptr: np.ndarray, indices: np.ndarray, row: int) -> np.ndarray:
    """One CSR row's column indices as int32 — the single-user form of
    :func:`padded_rows` (no padding needed for one row). The serving layer's
    seen-item exclusion slices through here from both the plain batched path
    and the pipeline's ALS source, so exclusion semantics can't diverge."""
    lo, hi = indptr[row], indptr[row + 1]
    return indices[lo:hi].astype(np.int32)


def group_buckets(buckets: list[Bucket]) -> list[Bucket]:
    """Stack same-shape buckets along a new leading axis: ``(B, L)`` buckets
    become ``(N, B, L)`` "groups" (still ``Bucket``s, with ``row_ids`` of shape
    ``(N, B)``).

    A half-sweep over groups is one ``lax.scan`` per distinct shape instead of
    one dispatch per bucket — the layout that lets the whole ALS fit compile
    into a single XLA program (``ops.als.als_fit_fused``), where the reference
    pays a Spark shuffle per block per sweep.

    Stacked arrays are preallocated and filled slice-by-slice (no ``np.stack``
    temporaries); ``grouped_bucket_rows`` goes one step further and scatters
    fills directly into the slabs, never materializing per-bucket arrays.
    """
    by_shape: dict[tuple[int, int], list[Bucket]] = {}
    for b in buckets:
        by_shape.setdefault(b.shape, []).append(b)

    def stack(arrays: list[np.ndarray]) -> np.ndarray:
        out = np.empty((len(arrays),) + arrays[0].shape, dtype=arrays[0].dtype)
        for i, a in enumerate(arrays):
            out[i] = a
        return out

    return [
        Bucket(
            row_ids=stack([b.row_ids for b in bs]),
            idx=stack([b.idx for b in bs]),
            val=stack([b.val for b in bs]),
            mask=stack([b.mask for b in bs]),
        )
        for _, bs in sorted(by_shape.items())
    ]


def device_bucket(b: Bucket, sharding=None) -> Bucket:
    """One-time host->device upload of a bucket's arrays (optionally with a
    ``jax.sharding.Sharding`` layout, e.g. row-sharded over a mesh)."""
    import jax

    put = (lambda x: jax.device_put(x, sharding)) if sharding is not None else jax.device_put
    return Bucket(
        row_ids=put(b.row_ids), idx=put(b.idx), val=put(b.val), mask=put(b.mask)
    )


def bucket_shapes(buckets: list[Bucket]) -> list[tuple[int, int]]:
    """Distinct shapes (== number of XLA compilations the sweep will trigger)."""
    return sorted({b.shape for b in buckets})
