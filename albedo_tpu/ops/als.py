"""Implicit-feedback ALS normal-equation kernels.

The math (Hu-Koren-Volinsky implicit ALS, with Spark MLlib's conventions so the
reference's NDCG is reproducible — SURVEY.md section 7 hard part (b)):

- confidence ``c_ui = 1 + alpha * r_ui``; preference ``p_ui = 1`` where ``r > 0``
- user solve:  ``x_u = (YtY + Y_u^T diag(alpha r_u) Y_u + lambda n_u I)^-1
  Y_u^T (1 + alpha r_u)``
  where ``n_u`` is the user's nonzero count — MLlib scales ``regParam`` by the
  explicit rating count (ALS-WR scaling), see ``ALSRecommenderBuilder.scala:46-58``
  for the hyperparameters this must match.

The reference executes this inside Spark MLlib as shuffled user/item blocks
with per-block LAPACK Cholesky on executors. Here each half-sweep is a set of
fixed-shape bucket solves: gather ``Y[idx] -> (B, L, k)``, one fused einsum for
the Gramian correction (on the MXU: the Cholesky solver's every bucket, the
CG's long rows, ``cg_uses_gramian``; the CG's short rows stay matrix-free
multiply-reduce passes on the vector unit), batched solve (the exact one with
the bucket's rows in the lanes, a system a lane: ``solve_corrected``), land
solved rows by an inverse-permutation gather — no shuffle. Buckets come from
``albedo_tpu.datasets.bucket_rows``. The layout is the same family as ALX's
TPU matrix factorization (arXiv:2112.02194 — padded
dense gather blocks over sharded factor tables), and the warm-started-CG fast
path follows the iALS speedup literature (arXiv:2110.14044; the ``implicit``
package's CG solver).

Why XLA HLO and not a hand-written Pallas kernel: the sweep is a row gather
feeding batched contractions with static shapes, and on a v5e the gather is
bound by ROWS, not bytes (PERF.md section 5): a factor row is 512 B in the
(8, 128) tiling at rank 50 and rank 128 alike, and XLA's gather takes 1.6-2.4
ns a row from a table it can keep in VMEM (under ~115 MB), 4.3-5.8 ns a row
from a table in HBM when it stages 256 rows a step, and 10.5-11.9 ns when it
stages 128. A Pallas gather issues one DMA descriptor a row and has the same
bound, so the way to a faster gather is to be handed the compiler's fast form.
Which form it takes follows from the flat row count ``B * L`` alone - XLA
flattens any index array to one vector, so its blocking changes nothing
(measured: within 3.4%) and the order of its entries next to nothing - and
``gather_slots`` picks the slot count that gets the 256-row form. Where it
reads from follows from the table's padded size: up to rank 64 two factor rows
share a 128-lane line (``gather_table``), the table is half as large, and the
compiler keeps it in VMEM ahead of every gather that ``gather_pieces`` keeps
small enough.

Phases carry ``jax.named_scope`` names (HLO metadata only: the compiled code
does not move) so a profiler trace splits the one fused program by what the
source says and not by what kind of fusion XLA emitted: ``als.init``,
``als.gramian``, ``als.gather``, ``als.warm_start``, ``als.cg`` (``.rhs``,
``.gram`` — long rows only —, ``.precond``, ``.matvec``, ``.update`` inside
it), ``als.cholesky`` (``.build``, ``.factor``, ``.solve`` inside it),
``als.landing``. They sit in the shared bodies, so the
chunked and sharded paths inherit them; the chunked path's per-bucket landing
into the donated table - one block write since PR 38, its tables held in
dispatch order - is ``als.chunk.scatter`` (around its ``als.landing``), and
its relayout of a table into that order and back ``als.chunk.relayout``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from albedo_tpu.datasets.ragged import Bucket


def gramian(factors: jax.Array) -> jax.Array:
    """``F^T F`` in float32 — the shared ``YtY`` term of every implicit solve."""
    with jax.named_scope("als.gramian"):
        return factors.T @ factors


def scatter_solved(
    target: jax.Array, row_ids: jax.Array, solved: jax.Array
) -> jax.Array:
    """Land a solved block into ``target``: padding slots (``row_ids == -1``)
    scatter out of bounds and drop. One definition of the landing contract —
    shared by the per-bucket reference path and the scan fallback; the
    sharded landing (``parallel.als._landing_scatter``) is the owner-shard
    variant of the same rule, the chunked path's block write
    (``chunked_bucket_update``) the dispatch-order one."""
    with jax.named_scope("als.landing"):
        safe_rows = jnp.where(row_ids < 0, target.shape[0], row_ids)
        return target.at[safe_rows].set(solved, mode="drop")


def warm_start(target: jax.Array, row_ids: jax.Array) -> jax.Array:
    """The CG's starting iterates: the bucket's current rows of ``target``
    (padding slots read row 0; their solves are dropped at landing)."""
    with jax.named_scope("als.warm_start"):
        return target[jnp.where(row_ids < 0, 0, row_ids)]


# XLA's TPU row gather flattens its index array to one (B * L,) vector, tiles
# that by GATHER_INDEX_TILE entries, and sizes its staging step by the padding
# the tiling leaves: 256 rows a step when the count lies GATHER_MIN_PAD or more
# short of a tile boundary, 128 rows otherwise. From a table in HBM the
# 128-row form takes 10.5-11.9 ns a row and the 256-row form 4.3-5.8; from a
# table in VMEM 2.1-2.8 against 1.6-2.4 (one v5e, ranks 50 and 128, every
# bucket shape and index order tried: PERF.md section 5). The planner's slot
# tiers (powers of two, 1024-multiples) and length tiers (multiples of 8) make
# B * L a multiple of 1024 for most buckets, so most gathers took the slow form.
GATHER_INDEX_TILE = 1024
GATHER_MIN_PAD = 128
# A bucket grows by at most one slot row in GATHER_MAX_GROWTH: the fast form
# gains 2.2x on the gather, and an empty slot row costs its share of gather
# and solve alike.
GATHER_MAX_GROWTH = 8


def gather_slots(n_slots: int, length: int) -> int:
    """The slot count at which a ``(n_slots, length)`` bucket is gathered and
    solved: the least count from ``n_slots`` up whose flat row count gets the
    gather's 256-row form, or ``n_slots`` itself where none within an eighth
    more does (a ``length`` that is a multiple of 1024; buckets of a few
    slots). Static shapes only: the kernel and the fit report's counter share
    this choice."""
    # (the flat count modulo the tile repeats within GATHER_INDEX_TILE slots)
    most = n_slots + min(n_slots // GATHER_MAX_GROWTH, GATHER_INDEX_TILE)
    for slots in range(n_slots, most + 1):
        if -(slots * length) % GATHER_INDEX_TILE >= GATHER_MIN_PAD:
            return slots
    return n_slots


def gather_reformed_entry_share(shapes) -> float:
    """Share of a fit's padded entries, over the bucket shapes ``(..., B, L)``
    of both sides, in buckets that ``_gather`` grows to another slot count."""
    total = reformed = 0
    for shape in shapes:
        entries = math.prod(shape)
        total += entries
        reformed += entries * (gather_slots(*shape[-2:]) != shape[-2])
    return reformed / total if total else 0.0


def exact_systems(shapes) -> int:
    """Systems the exact solve factorises over the bucket shapes
    ``(..., B, L)`` of both sides: every slot row of every bucket, the
    planner's empty slots and a piece's among them (the rows ``gather_slots``
    grows a bucket by are cut before the solve)."""
    return sum(math.prod(shape[:-1]) for shape in shapes)


def exact_lanes(n_systems: int) -> int:
    """Lanes at which ``solve_corrected`` solves a block of ``n_systems``
    systems: one system a lane, whole ``LANES``-lane tiles. Static shapes
    only: the kernel and the fit report's counter share this rule."""
    return -(-n_systems // LANES) * LANES


def exact_lane_systems(shapes) -> int:
    """Lanes the exact solve pays for over the bucket shapes ``(..., B, L)``
    of both sides: each bucket's ``B`` systems at ``exact_lanes(B)``."""
    return sum(math.prod(shape[:-2]) * exact_lanes(shape[-2]) for shape in shapes)


# A factor row takes a whole 128-lane line of the (8, 128) tiling whatever its
# rank, so a table's padded size, and with it whether the compiler copies it
# into VMEM ahead of a gather (PERF.md section 5), is set by its row count
# alone. Where two rows fit a line they are handed to the gather as one, each
# from the start of its half: a whole line gathers at 1.6-2.5 ns a row from
# VMEM where a 100-lane one (rank 50's rows end to end) takes 3.1-3.9, and the
# sweep 613 ms against 654.
LANES = 128
HALF = LANES // 2


def gather_packs_rows(rank: int) -> bool:
    """Whether a rank-``rank`` table is gathered from its line table
    (``gather_table``). Static shapes only: the kernel and the fit report's
    counter share this choice."""
    return 2 * rank <= LANES


def gather_table(source: jax.Array) -> jax.Array:
    """The form in which ``_gather`` reads the ``(n, k)`` table ``source``:
    the table itself where two rows do not fit a line, else its line table
    ``(ceil(n / 2), LANES)`` - row ``2i`` in lanes ``0:k`` and row ``2i + 1``
    in lanes ``HALF:HALF + k`` of line ``i``, zeros between, an odd row count
    padded by one zero row - which is half the table's padded size. A relayout
    of the whole table, so built once a half-sweep, beside ``gramian(source)``,
    never once a bucket."""
    n, k = source.shape
    if not gather_packs_rows(k):
        return source
    with jax.named_scope("als.gather"):
        return jnp.pad(source, ((0, n % 2), (0, HALF - k))).reshape(-1, LANES)


def gather_packed_entry_share(shapes, rank: int) -> float:
    """Share of a fit's padded entries, over the bucket shapes ``(..., B, L)``
    of both sides, in buckets whose gather read a line table."""
    return float(gather_packs_rows(rank) and any(math.prod(shape) for shape in shapes))


# Inside the fused fit the compiler keeps a line table in VMEM ahead of a
# gather only while the gather is small, and copies it back to HBM ahead of a
# larger one. Compiled for a described v5e at albedo-r50's layout (76.8 and
# 115.2 MB of lines), the table is in VMEM (``S(1)``) for 53% of the gathered
# rows with every bucket gathered whole, 75% in pieces of at most 2^20 flat
# rows, 98.7% at 786,432 and for every gather at 2^19; on the chip (100-lane
# lines, a bucket's pieces as slices) the sweep took 851 ms whole, 695 at
# 786,432 and 645 at 2^19, against the parent's 961 (PERF.md section 6).
GATHER_VMEM_ROWS = 1 << 19


def gather_pieces(n_slots: int, length: int, packed: bool) -> tuple[int, int]:
    """``(pieces, slots a piece)`` in which the fused sweep scans a
    ``(n_slots, length)`` bucket: whole where its gather reads the table as it
    is (``packed`` false) or is small enough already, else in equal pieces of
    at most ``GATHER_VMEM_ROWS`` flat rows (one slot row, where a row is
    longer) - of the counts from the fewest that do to twice as many, the one
    that leaves the fewest empty slot rows in the last piece. Static shapes
    only: ``scan_half_sweep`` and the shapes a fit reports share this choice."""
    fewest = -(-n_slots // max(1, GATHER_VMEM_ROWS // length)) if packed else 1
    pieces = min(range(fewest, min(2 * fewest, n_slots) + 1), key=lambda n: (-n_slots % n, n))
    return pieces, -(-n_slots // pieces)


def scanned_shape(shape: tuple[int, int, int], rank: int) -> tuple[int, int, int]:
    """The ``(buckets, B, L)`` that ``scan_half_sweep`` solves a rank-``rank``
    fit's ``(N, B, L)`` group at: its buckets in their pieces."""
    n, n_slots, length = shape
    pieces, per = gather_pieces(n_slots, length, gather_packs_rows(rank))
    return n * pieces, per, length


def _fold(x: jax.Array, rank: int, axes=(-1,)) -> jax.Array:
    """A contraction of a line-table block back at ``rank`` lanes: the sum of
    its two halves' first ``rank`` lanes along ``axes`` (every entry lives in
    one half and is zero in the other; what two axes hold across the halves is
    dropped). The identity on what a plain block gave."""
    if x.shape[-1] == rank:
        return x
    lower = (Ellipsis,) + (slice(None, rank),) * len(axes)
    upper = (Ellipsis,) + (slice(HALF, HALF + rank),) * len(axes)
    return x[lower] + x[upper]


def _spread(p: jax.Array, width: int) -> jax.Array:
    """``(B, k)`` vectors at a gathered block's ``width``: once in each half
    of a line-table block's, so that an entry in either meets its vector."""
    if p.shape[-1] == width:
        return p
    p = jnp.pad(p, ((0, 0), (0, HALF - p.shape[-1])))
    return jnp.concatenate([p, p], axis=-1)


def _with_slots(a: jax.Array, n_slots: int) -> jax.Array:
    """``a`` with empty slot rows (zeros: no entry, no weight, a zero
    iterate) appended up to ``n_slots``."""
    if a.shape[0] == n_slots:
        return a
    return jnp.pad(a, ((0, n_slots - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _gather(
    source: jax.Array, idx: jax.Array, gather_dtype, rank: int
) -> tuple[jax.Array, jax.Array]:
    """Row-gather the fixed side's factors, optionally through a reduced-
    precision copy of the table, at the slot count XLA's gather runs fastest
    on. ``source`` is the ``(n, rank)`` table, or its line table
    (``gather_table``), told apart by their width.

    The block returned is ``(gather_slots(B, L), L, k)``: its first ``B`` slot
    rows are ``source[idx]``, and the few beyond them are empty slots like the
    ones every bucket's slot tier already carries (index 0, to be given no
    weight). From a line table it is ``(gather_slots(B, L), L, LANES)``: line
    ``idx >> 1`` with the half that is another row's set to zero, so row
    ``idx`` sits in lanes ``0:k`` (``idx`` even) or ``HALF:HALF + k`` (odd)
    and every contraction over the block is ``_fold``-ed back to ``k``.
    Choosing the half into a ``k``-wide block instead costs two passes over a
    block that a lane tile pads to the same 512 B a row either way (a
    ``where`` of two lane slices, 14.5-19.1 ms a bucket of 1.2-2.1M rows
    against 8.2-11.3 folded, gather and contractions: PERF.md section 5); the
    mask is an elementwise producer of whatever reads the block. Beside the
    block come the lines as they were fetched (the block itself, from a plain
    table): the second operand of a product whose first is already masked
    needs no mask, and leaves the other row's terms where ``_fold`` drops
    them (135 against 155 ms a sweep in ``als.cg.gram``).

    Growing the bucket is the one handle on the gather's form - a reshape of
    ``idx`` reaches the same flat gather - and it is all but free: the index
    padding fuses into the gather's own index clamp, the callers' padding of
    ``val``/``mask`` into the elementwise passes that read them, and the block
    is written once, where it is read (no slice of it, no copy).

    With ``gather_dtype="bfloat16"`` the (tiny) factor table is cast once and
    the (huge) gathered ``(B, L, k)`` blocks live in bf16 in HBM — halving the
    streamed bytes of the bandwidth-bound sweep. All contractions over the
    gathered blocks accumulate in float32 (``preferred_element_type``), the
    MXU's native bf16-in/f32-out mode."""
    with jax.named_scope("als.gather"):
        idx = _with_slots(idx, gather_slots(*idx.shape))
        if gather_dtype is not None:
            source = source.astype(jnp.dtype(gather_dtype))
        if source.shape[1] == rank:
            rows = source[idx]
            return rows, rows
        lines = source[idx >> 1]
        upper = jnp.arange(LANES) >= HALF
        return jnp.where(upper == (idx & 1).astype(bool)[..., None], lines, 0), lines


def _gdot(spec: str, gathered: jax.Array, other: jax.Array) -> jax.Array:
    """Einsum against the gathered block with f32 accumulation; the non-block
    operand is cast to the block's (possibly bf16) dtype so the MXU consumes
    both natively instead of upcasting the big block to f32 in HBM."""
    return jnp.einsum(
        spec, gathered, other.astype(gathered.dtype),
        preferred_element_type=jnp.float32,
    )


def bucket_solve_body(
    source: jax.Array,   # (n_source, k) fixed side's factors, or their gather_table
    yty: jax.Array,      # (k, k) gramian of the factors
    idx: jax.Array,      # (B, L) int32 indices into `source`
    val: jax.Array,      # (B, L) float32 ratings, 0 on padding
    mask: jax.Array,     # (B, L) bool
    reg: jax.Array,      # () float32 regParam
    alpha: jax.Array,    # () float32 confidence scale
    gather_dtype=None,   # None = f32 gathers; "bfloat16" halves streamed bytes
) -> jax.Array:
    """The normal-equation solve for a padded bucket: gather → fused Gramian
    correction → the exact solve, a system a lane (``solve_corrected``).
    Shared by the single-device and shard_map'd paths (``parallel.als``), so a
    parity fix lands in both.

    Scopes: the gather (and the padding of ``idx`` to the block's slot count)
    is ``als.gather``; everything after it is ``als.cholesky``, in three
    parts - ``als.cholesky.build`` holds the correction and the b-vector
    (``bucket_partial_terms``), from a line table the folds of its
    ``(B', LANES, LANES)`` / ``(B', LANES)`` results back to ``k``, and the
    regularised systems in the solve's ``(k, k + 1, lanes)`` order;
    ``als.cholesky.factor`` and ``als.cholesky.solve`` are the solve's two
    loops (``solve_corrected``). The padding of ``val`` and ``mask`` and the
    weights carry no scope; the compiler fuses them into the contractions
    that read them.

    The block is gathered and contracted at the gather's slot count ``B' >=
    B`` (``gather_slots``); the few rows beyond ``B`` are statically empty and
    ``solve_corrected`` drops them where it puts the systems into lanes, so
    ``B`` systems are solved - the planner's empty slots among them (``YtY``
    alone: positive definite) -, which the fit report's
    ``exact_systems_per_sweep`` counts. (Cutting the contraction's operand or
    its result by that odd row instead makes the TPU compiler emit code it
    takes minutes to generate and seconds to run: PERF.md section 6, PR 36.)"""
    k = yty.shape[0]
    gathered, _ = _gather(source, idx, gather_dtype, k)  # (B', L, k or LANES), B' >= B
    n_b = mask.sum(axis=1).astype(jnp.float32)  # (B,)
    val, mask = (_with_slots(a, gathered.shape[0]) for a in (val, mask))
    c1 = alpha * val                            # (B', L); 0 on padding
    w = jnp.where(mask, 1.0 + c1, 0.0)          # b-vector weights

    corr, b_vec = bucket_partial_terms(gathered, c1, w)
    with jax.named_scope("als.cholesky"), jax.named_scope("als.cholesky.build"):
        corr, b_vec = _fold(corr, k, axes=(-2, -1)), _fold(b_vec, k)
    return solve_corrected(yty, corr, b_vec, n_b, reg)


def bucket_partial_terms(
    gathered: jax.Array,  # (B, L, k) gathered source rows (zeros where absent)
    c1: jax.Array,        # (B, L) alpha * val, zeroed where the entry is absent
    w: jax.Array,         # (B, L) b-vector weights, zeroed where absent
) -> tuple[jax.Array, jax.Array]:
    """The Gramian correction and b-vector for one (partial) gathered block.

    The bucket solve's data-dependent terms are SUMS over a row's entries, so
    a ring-passed sharded sweep (``parallel.als`` with ``mode="ring"``) can
    accumulate them phase by phase — each phase zeroing the entries whose
    source rows live on a shard not yet seen — and the total equals the
    full-gather terms. Factored out so the ring path's math IS
    ``bucket_solve_body``'s math, not a reimplementation.
    """
    with jax.named_scope("als.cholesky"), jax.named_scope("als.cholesky.build"):
        # A_b correction = sum_l c1 * y y^T
        corr = jnp.einsum(
            "blk,bl,blm->bkm", gathered, c1.astype(gathered.dtype), gathered,
            preferred_element_type=jnp.float32,
        )
        # b-vector weights stay float32 even under bf16 gathers: w = 1 +
        # alpha*r spends ~8 significant bits on the integer part alone, so a
        # bf16 cast adds ~0.4% relative error per entry (ADVICE r5 #3). The
        # MXU consumes mixed bf16/f32 inputs with f32 accumulation natively —
        # only the big gathered block needs the reduced dtype to save
        # bandwidth.
        b_vec = jnp.einsum(
            "blk,bl->bk", gathered, w, preferred_element_type=jnp.float32
        )
        return corr, b_vec


# The exact solve works through a block's lanes a chunk at a time: a chunk of
# systems and its factor, 2 * k * ceil((k + 1) / 8) * 8 * LANES * 4 B a lane
# tile, as many tiles as fit EXACT_CHUNK_BYTES. Compiled for a v5e such a
# chunk and its factor are kept in VMEM (``S(1)``) through the k column
# steps, where a whole block of 8,192 systems (92 MB at rank 50, twice) is
# read from HBM at every column. One bucket's whole program, 8,192 x 8 rows
# at rank 50 on a v5e: 4.81 ms at 12 MiB (512 lanes), 4.67 at 24 (1,024),
# 4.92 at 48 (2,176), 5.57 unchunked, the library's 53.1; at rank 128 19.4 at
# 24 MiB (one tile), 19.5 at 36 (two), 23.5 at 72 (four). Inside the fused
# fit the chunk competes for VMEM with the gather's line table: albedo-r50-chol
# reads 962 ms a sweep at 24 MiB, 1,194 at 12 (the factorisation falls out of
# VMEM) and 1,303 at 48 (the table does) - PERF.md sections 5 and 6, PR 36.
EXACT_CHUNK_BYTES = 24 << 20


def exact_chunk_tiles(rank: int) -> int:
    """Lane tiles of systems a chunk of the exact solve holds at ``rank``
    (at least one). Static shapes only."""
    tile = 2 * rank * -(-(rank + 1) // 8) * 8 * LANES * 4
    return max(1, EXACT_CHUNK_BYTES // tile)


def _factor_and_solve(aug: jax.Array) -> jax.Array:
    """``x (k, R)`` of ``A x = b`` for ``R`` symmetric positive definite
    systems, one a lane: ``aug (k, k + 1, R)`` holds ``A`` (``aug[j, i] =
    A[i, j]``) with ``b`` as row ``k`` (``aug[j, k] = b[j]``).

    ``als.cholesky.factor``: the lower factor a column a step, left-looking
    (column ``j`` is ``A[:, j]`` less the earlier columns times their row
    ``j`` entries, over ``sqrt`` of its diagonal), kept transposed -
    ``up[m, i] = L[i, m]`` - so that every sum runs over the leading axis and
    every step is an elementwise float32 pass over whole lanes. Row ``k``
    rides along: the factor of ``[[A, b], [b^T, .]]`` has ``z = L^-1 b`` as
    its last row, so the forward substitution is the factorisation's own.
    ``als.cholesky.solve``: ``L^T x = z`` from the last row up, a row of
    ``up`` a step. Both loops are rolled (``fori_loop``): the fused fit
    instantiates this once a shape group."""
    k = aug.shape[0]
    at = functools.partial(jax.lax.dynamic_index_in_dim, keepdims=False)
    put = jax.lax.dynamic_update_index_in_dim

    with jax.named_scope("als.cholesky.factor"):
        below = jnp.arange(k + 1)[:, None]

        def column(j, carry):
            up, inv_diag = carry        # up[m] is final for m < j and zero from j on
            s = at(aug, j, 0) - jnp.sum(up * at(up, j, 1)[:, None], axis=0)  # (k + 1, R)
            inv = 1.0 / jnp.sqrt(at(s, j, 0))                                # (R,)
            col = jnp.where(below >= j, s * inv[None], 0.0)
            return put(up, col, j, 0), put(inv_diag, inv, j, 0)

        up, inv_diag = jax.lax.fori_loop(
            0, k, column, (jnp.zeros_like(aug), jnp.zeros_like(aug[:, 0])))

    with jax.named_scope("als.cholesky.solve"):
        lower, z = up[:, :k], up[:, k]

        def backward(i, x):             # x[m] is final for m > j and zero up to j
            j = k - 1 - i
            xj = (at(z, j, 0) - jnp.sum(at(lower, j, 0) * x, axis=0)) * at(inv_diag, j, 0)
            return put(x, xj, j, 0)

        return jax.lax.fori_loop(0, k, backward, jnp.zeros_like(z))


def solve_corrected(
    yty: jax.Array,    # (k, k)
    corr: jax.Array,   # (B', k, k) accumulated Gramian correction, B' >= B
    b_vec: jax.Array,  # (B', k)
    n_b: jax.Array,    # (B,) float32 per-row nonzero counts
    reg: jax.Array,    # () float32
) -> jax.Array:
    """The exact solve of ``(YtY + corr + reg n_b I) x = b`` for the ``B``
    rows ``n_b`` counts, by Cholesky with the BATCH IN THE LANES - the shared
    tail of the full-gather and ring-accumulated bucket solves, under
    ``als.cholesky``. ``corr`` and ``b_vec`` may carry rows beyond ``B`` (a
    bucket's statically empty growth rows): they never reach a lane.

    ``als.cholesky.build``: the systems are transposed once to ``(k, k + 1,
    rows)``, the block's row LAST and the b-vector as row ``k``
    (``_factor_and_solve``), cut to ``B`` and padded to ``exact_lanes(B)``
    lanes, and regularised there; a lane past ``B`` holds ``I x = 0``.
    ``als.cholesky.factor`` / ``.solve``: a chunk of ``exact_chunk_tiles(k)``
    lane tiles at a time, so that a chunk and its factor stay on chip through
    the ``k`` steps; the tiles left over are one more, smaller chunk. Float32
    elementwise throughout: no library factorisation, no matmul, no
    iteration. Every one of the ``B`` systems is solved, a bucket's empty
    slots too (their ``corr`` and ``b_vec`` are zero). Returns ``(B, k)``."""
    n, k = n_b.shape[0], yty.shape[0]
    lanes = exact_lanes(n)
    chunk = min(exact_chunk_tiles(k) * LANES, lanes)
    with jax.named_scope("als.cholesky"):
        with jax.named_scope("als.cholesky.build"):
            # (k, k + 1, B'): aug[j, i] = corr[i, j], row k the b-vector
            aug = jnp.concatenate([corr, b_vec[:, None, :]], axis=1).transpose(2, 1, 0)
            aug = jnp.pad(aug[:, :, :n], ((0, 0), (0, 0), (0, lanes - n)))
            live = jnp.arange(lanes) < n
            ridge = jnp.pad(reg * n_b, (0, lanes - n))
            shared, eye = (
                jnp.pad(m, ((0, 0), (0, 1)))[:, :, None] for m in (yty, jnp.eye(k, dtype=jnp.float32)))
            aug = aug + jnp.where(live, shared + eye * ridge, eye)

        def solve_chunk(start, size):
            return _factor_and_solve(jax.lax.dynamic_slice_in_dim(aug, start, size, axis=2))

        solved = []
        whole = lanes // chunk
        if whole:
            with jax.named_scope("als.cholesky.factor"):                 # the loop over chunks
                x = jax.lax.map(lambda c: solve_chunk(c * chunk, chunk), jnp.arange(whole))
            with jax.named_scope("als.cholesky.solve"):
                solved.append(x.transpose(0, 2, 1).reshape(whole * chunk, k))
        if lanes > whole * chunk:
            x = solve_chunk(whole * chunk, lanes - whole * chunk)
            with jax.named_scope("als.cholesky.solve"):
                solved.append(x.T)
        with jax.named_scope("als.cholesky.solve"):
            return jnp.concatenate(solved)[:n]


# A bucket's CG runs on its explicit (B, k, k) Gramian once its padded length
# L reaches this many ranks k. Building the Gramian is one MXU contraction
# over the gathered (B, L, k) block, and the Gramian is k/L of the block's
# size, so the matvecs stop streaming the block. On a v5e the explicit form
# loses or ties at L = k and wins by 22% (rank 128) and 29% (rank 50) at
# L = 2k, more beyond (PERF.md section 5).
CG_GRAM_LEN_PER_RANK = 2


def cg_uses_gramian(length: int, rank: int) -> bool:
    """Whether a bucket of padded length ``length`` solves its CG on the
    explicit Gramian (``bucket_cg_body``). Static shapes only: every caller of
    the one CG body, and the fit report's counter, share this choice."""
    return length >= CG_GRAM_LEN_PER_RANK * rank


def cg_gram_entry_share(shapes, rank: int) -> float:
    """Share of a CG fit's padded entries, over the bucket shapes ``(..., B, L)``
    of both sides, in buckets whose CG takes the explicit-Gramian form."""
    total = gram = 0
    for shape in shapes:
        entries = math.prod(shape)
        total += entries
        gram += entries * cg_uses_gramian(shape[-1], rank)
    return gram / total if total else 0.0


def bucket_cg_body(
    source: jax.Array,   # (n_source, k) fixed side's factors, or their gather_table
    yty: jax.Array,      # (k, k) gramian of the factors
    idx: jax.Array,      # (B, L) int32 indices into `source`
    val: jax.Array,      # (B, L) float32 ratings, 0 on padding
    mask: jax.Array,     # (B, L) bool
    x0: jax.Array,       # (B, k) warm-start iterates (current factors)
    reg: jax.Array,      # () float32 regParam
    alpha: jax.Array,    # () float32 confidence scale
    cg_steps: int,
    gather_dtype=None,   # None = f32 gathers; "bfloat16" halves streamed bytes
) -> jax.Array:
    """Jacobi-preconditioned conjugate gradient on the implicit normal
    equations, in the association the bucket's static shape makes cheaper.

    Short rows (``L < CG_GRAM_LEN_PER_RANK * k``) stay matrix-free: the matvec
    ``A p = YtY p + Y_u^T (alpha r (.) (Y_u p)) + reg n_u p`` is two passes
    over the gathered block and the (B, k, k) systems are never built. Long
    rows (``cg_uses_gramian``) build ``A`` once, one MXU contraction over the
    block, and iterate on it: the same iterates in exact arithmetic, without
    streaming the block from HBM for every matvec.

    Either way each CG step is cheap next to the exact path's k^3-shaped
    factorization (``solve_corrected``: k column steps over the systems of a
    block, a system a lane). Warm-starting from the previous
    sweep's factors makes a few CG steps per half-sweep converge to the same
    fixed point — the established fast implicit-ALS practice (e.g. the
    ``implicit`` package's conjugate-gradient solver, default 3 steps), while
    MLlib's exact per-block Cholesky (what ``bucket_solve_body`` mirrors)
    remains the parity reference.
    """
    n_slots = idx.shape[0]
    gathered, lines = _gather(source, idx, gather_dtype, yty.shape[0])  # (B', L, k or LANES), B' >= B
    with jax.named_scope("als.cg"):
        val, mask, x0 = (_with_slots(a, gathered.shape[0]) for a in (val, mask, x0))
        return _cg_solve(gathered, lines, yty, val, mask, x0, reg, alpha, cg_steps)[:n_slots]


def _cg_solve(gathered, lines, yty, val, mask, x0, reg, alpha, cg_steps):
    """``bucket_cg_body`` after its gather, under the ``als.cg`` scope. A
    line-table block (``_gather``) is contracted at its own ``LANES`` lanes,
    which the tiling pads a ``k``-wide one to anyway, and each result folded
    back to ``k`` where it is ``(B, LANES)`` or ``(B, LANES, LANES)`` small."""
    k, width = yty.shape[0], gathered.shape[-1]
    with jax.named_scope("als.cg.rhs"):
        c1 = alpha * val                            # (B, L); 0 on padding
        w = jnp.where(mask, 1.0 + c1, 0.0)
        n_b = mask.sum(axis=1).astype(jnp.float32)
        # f32 weights for the b-vector under bf16 gathers — see
        # bucket_partial_terms.
        b_vec = _fold(jnp.einsum(
            "blk,bl->bk", gathered, w, preferred_element_type=jnp.float32
        ), k)

    if cg_uses_gramian(gathered.shape[1], k):
        with jax.named_scope("als.cg.gram"):
            # A = YtY + sum_l c1 y y^T + reg n I. The scaling is an
            # elementwise producer of the contraction's operand, for XLA to
            # fuse into it rather than write a second (B, L, k) block.
            scaled = gathered * c1[..., None].astype(gathered.dtype)
            a_mat = (
                yty[None]
                + _fold(jnp.einsum(
                    "blk,blm->bkm", scaled, lines,
                    preferred_element_type=jnp.float32,
                ), k, axes=(-2, -1))
                + (reg * n_b)[:, None, None] * jnp.eye(k, dtype=jnp.float32)
            )
        with jax.named_scope("als.cg.precond"):
            diag = jnp.maximum(jnp.diagonal(a_mat, axis1=1, axis2=2), 1e-12)

        def matvec(p):
            with jax.named_scope("als.cg.matvec"):
                return jnp.einsum(
                    "bkm,bm->bk", a_mat, p, preferred_element_type=jnp.float32
                )
    else:
        # Jacobi preconditioner: diag(A) = diag(YtY) + sum_l c1 y_l^2 + reg n.
        with jax.named_scope("als.cg.precond"):
            diag = (
                jnp.diagonal(yty)[None]
                + _fold(_gdot("blk,bl->bk", gathered * gathered, c1), k)
                + (reg * n_b)[:, None]
            )
            diag = jnp.maximum(diag, 1e-12)

        def matvec(p):
            with jax.named_scope("als.cg.matvec"):
                t = c1 * _gdot("blk,bk->bl", gathered, _spread(p, width))
                return (
                    p @ yty
                    + _fold(_gdot("blk,bl->bk", gathered, t), k)
                    + (reg * n_b)[:, None] * p
                )

    return _pcg(matvec, diag, b_vec, x0, cg_steps)


def _pcg(matvec, diag, b_vec, x0, cg_steps: int):
    """``cg_steps`` Jacobi-preconditioned CG steps on ``A x = b`` from ``x0``:
    the one iteration both of ``_cg_solve``'s forms of ``A`` share."""
    tiny = jnp.float32(1e-30)
    x = x0
    ax = matvec(x)
    with jax.named_scope("als.cg.update"):
        r = b_vec - ax
        z = r / diag
        p = z
        rz = jnp.sum(r * z, axis=1)
    for _ in range(cg_steps):  # static unroll: fixed shapes, no host sync
        ap = matvec(p)
        with jax.named_scope("als.cg.update"):
            step = rz / (jnp.sum(p * ap, axis=1) + tiny)
            x = x + step[:, None] * p
            r = r - step[:, None] * ap
            z = r / diag
            rz_new = jnp.sum(r * z, axis=1)
            beta = rz_new / (rz + tiny)
            p = z + beta[:, None] * p
            rz = rz_new
    return x


def check_solver(solver: str) -> None:
    """Reject a solver name no kernel answers to. ``ImplicitALS.fit`` and
    ``ShardedALSFit`` call it before any layout is built; ``solve_rows``
    calls it again for whoever traces the programs below directly."""
    if solver not in ("cholesky", "cg"):
        raise ValueError(f"unknown solver {solver!r} (expected 'cholesky' or 'cg')")


def solve_rows(
    source, yty, target, row_ids, idx, val, mask, reg, alpha,
    solver: str, cg_steps: int, gather_dtype, x0=None,
) -> jax.Array:
    """One bucket's solved ``(B, k)`` block, by the kernel ``solver`` names:
    ``"cholesky"`` the exact MLlib-parity solve (``bucket_solve_body``),
    ``"cg"`` the CG warm-started from the bucket's current rows of ``target``
    (``bucket_cg_body``). Arguments as ``solve_bucket``'s: ``source``
    serves the gather alone, so it comes in the gather's form
    (``gather_table``, built where ``yty`` is); ``target`` and ``row_ids`` are
    read under ``"cg"`` only, by ``warm_start``, unless ``x0`` hands in the
    bucket's current rows already read (the chunked program reads them as
    one block). The one place a
    kernel is chosen: the fused sweep, the chunked per-bucket program, the
    eager reference and the mesh's assembled solve (``parallel.als``, which
    hands in its all-gathered tables) all trace this, so a change to either
    body reaches every path."""
    check_solver(solver)
    if solver == "cg":
        if x0 is None:
            x0 = warm_start(target, row_ids)
        return bucket_cg_body(
            source, yty, idx, val, mask, x0, reg, alpha, cg_steps,
            gather_dtype=gather_dtype,
        )
    return bucket_solve_body(
        source, yty, idx, val, mask, reg, alpha, gather_dtype=gather_dtype
    )


# Per-bucket eager reference path (als_half_sweep): parity tests and small
# interactive runs only — hot fits go through als_fit_fused/als_init_fit_fused,
# which ARE acquired via utils/aot.
# albedo: noqa[bare-jit]
@functools.partial(jax.jit, donate_argnames=("target",))
def solve_bucket(
    source: jax.Array,   # gather_table of the (n_source, k) fixed side's factors
    yty: jax.Array,      # (k, k) gramian of those factors
    target: jax.Array,   # (n_target, k) factors being updated (donated)
    row_ids: jax.Array,  # (B,) int32 target rows, -1 on padding slots
    idx: jax.Array,      # (B, L) int32 indices into `source`
    val: jax.Array,      # (B, L) float32 ratings, 0 on padding
    mask: jax.Array,     # (B, L) bool
    reg: jax.Array,      # () float32 regParam
    alpha: jax.Array,    # () float32 confidence scale
) -> jax.Array:
    """One normal-equation solve for a padded bucket of rows; returns updated
    ``target`` with solved rows scattered in."""
    solved = solve_rows(
        source, yty, target, row_ids, idx, val, mask, reg, alpha,
        solver="cholesky", cg_steps=0, gather_dtype=None,
    )
    return scatter_solved(target, row_ids, solved)


# The chunked fit's slab travels as ONE int32 vector a bucket, one host ->
# device transfer where four arrays took four: a transfer costs about a
# millisecond on a v5e whatever its bytes, 0.1 MB or 18.9 MB (PERF.md
# section 6). The indices with the mask folded in come first, then the values'
# float32 bits, then the row ids: the two large sections start on a whole
# 1,024 entries wherever ``B * L`` is a multiple of it, as the planner's
# slot tiers make it for most buckets.
def slab_entries(n_slots: int, length: int) -> int:
    """Entries of a ``(n_slots, length)`` bucket's slab (:func:`pack_slab`)."""
    return n_slots * (2 * length + 1)


def pack_slab(row_ids: np.ndarray, idx: np.ndarray, val: np.ndarray,
              mask: np.ndarray) -> np.ndarray:
    """A bucket's four host arrays as the one int32 slab the chunked fit
    uploads (:func:`unpack_slab` undoes it, bit for bit): ``idx`` with the
    mask folded in as ``~idx`` on a padding entry (every index is a row
    position, so ``>= 0``, and the sign alone is the mask); ``val`` as its
    float32 bit pattern; ``row_ids`` whole."""
    entries = idx.size
    slab = np.empty(slab_entries(*idx.shape), np.int32)
    packed = slab[:entries].reshape(idx.shape)
    packed[...] = idx
    np.invert(packed, out=packed, where=~mask)
    slab[entries:2 * entries] = np.ascontiguousarray(val, np.float32).view(np.int32).reshape(-1)
    slab[2 * entries:] = row_ids
    return slab


def slab_row_ids(slab, shape: tuple[int, int]):
    """The ``(B,)`` row ids of a ``shape`` bucket's slab (:func:`pack_slab`),
    on the host or in a program: its last section."""
    return slab[2 * shape[0] * shape[1]:]


def unpack_slab(slab: jax.Array, shape: tuple[int, int]) -> tuple[jax.Array, ...]:
    """``(row_ids, idx, val, mask)`` of a ``shape`` bucket's slab
    (:func:`pack_slab`), at the head of its program: slices, a bit cast, a
    compare and a ``where``, and each index - a padding entry's among them -
    the one the bucket was packed with."""
    entries = shape[0] * shape[1]
    packed = slab[:entries].reshape(shape)
    val = jax.lax.bitcast_convert_type(slab[entries:2 * entries], jnp.float32).reshape(shape)
    mask = packed >= 0
    return slab_row_ids(slab, shape), jnp.where(mask, packed, ~packed), val, mask


@functools.partial(
    jax.jit,
    donate_argnums=(2,),  # target: the chunked path must not double-buffer it
    static_argnames=("shape", "solver", "cg_steps", "gather_dtype"),
)
def chunked_bucket_update(
    source: jax.Array,   # gather_table of the fixed side's factors, dispatch order
    yty: jax.Array,      # (k, k) gramian of those factors
    target: jax.Array,   # (n_target, k) factors being updated, dispatch order (donated)
    slab: jax.Array,     # (slab_entries(*shape),) int32: the bucket, pack_slab
    reg: jax.Array,      # () float32 regParam
    alpha: jax.Array,    # () float32 confidence scale
    shape: tuple[int, int],  # the bucket's (B, L), static
    solver: str = "cholesky",
    cg_steps: int = 3,
    gather_dtype: str | None = None,
) -> jax.Array:
    """One bucket's solve for the **chunked host-streamed** fallback path
    (``models.als`` under a ``degrade`` capacity verdict): the bucket slab
    arrives fresh from the host per call, only the factor tables stay
    device-resident. Same kernels as the fused sweep (``solve_rows``) so the
    fallback is numerics-parity with the resident path.

    The slab is one int32 vector, one transfer (:func:`pack_slab`, done once
    a matrix when the layout is built); the program's first ops unpack it
    (:func:`unpack_slab`) into the ``(B,)`` row ids and ``(B, L)`` indices,
    values and mask it was packed from, bit for bit, so the gather's flat
    count ``B * L`` and every op after the unpacking are those of a bucket
    handed over as four arrays.

    The landing contract is this path's alone: both tables are held in the
    chunked fit's dispatch order (``models.als.StreamLayout``), where the
    bucket's ``B`` slots ARE the contiguous block ``[row_ids[0],
    row_ids[0] + B)`` of ``target`` (slot 0 always holds a row; a padding
    slot has a row of its own, holding zeros). So the warm start reads the
    block with one slice and the landing writes it back with one
    ``dynamic_update_slice`` into the donated table: 1.7-2.9 ns a row alone
    where a row scatter took 72-76 ns at any call size, and a bucket's whole
    program 27-31 ns a row where it took 105-107 at one entry a row (one
    v5e, rank 128, 10M-row table: PERF.md section 6, PR 38). A padding slot lands
    zeros, which keeps ``gramian`` of the table equal to the logical table's
    and no empty row's 0/0 in it. The block never reaches past the table's
    end, so the slice is never clamped.
    """
    row_ids, idx, val, mask = unpack_slab(slab, shape)
    n_slots = shape[0]
    offset = row_ids[0]
    x0 = None
    if solver == "cg":
        with jax.named_scope("als.warm_start"):
            x0 = jax.lax.dynamic_slice_in_dim(target, offset, n_slots)
    solved = solve_rows(
        source, yty, target, row_ids, idx, val, mask, reg, alpha,
        solver, cg_steps, gather_dtype, x0=x0,
    )
    with jax.named_scope("als.chunk.scatter"), jax.named_scope("als.landing"):
        block = jnp.where((row_ids >= 0)[:, None], solved, 0.0)
        return jax.lax.dynamic_update_slice_in_dim(target, block, offset, 0)


@jax.jit
def relayout_rows(table: jax.Array, rows: jax.Array) -> jax.Array:
    """``table[rows]`` with zeros where ``rows < 0``: a factor table into the
    chunked fit's dispatch order (``rows`` = the logical row at each
    position, -1 on a padding slot's), and back (``rows`` = each logical
    row's position). Once a table a fit, each way (span ``fit.relayout``)."""
    with jax.named_scope("als.chunk.relayout"):
        picked = table[jnp.maximum(rows, 0)]
        return jnp.where((rows >= 0)[:, None], picked, 0.0)


def als_half_sweep(
    source: jax.Array,
    target: jax.Array,
    buckets: list[Bucket],
    reg: float,
    alpha: float,
) -> jax.Array:
    """Update every (nonempty) row of ``target`` from fixed ``source`` factors.

    One compiled kernel per distinct bucket shape (O(log max_len) shapes).
    """
    yty = gramian(source)
    table = gather_table(source)
    reg_arr = jnp.float32(reg)
    alpha_arr = jnp.float32(alpha)
    for b in buckets:
        target = solve_bucket(
            table, yty, target,
            jnp.asarray(b.row_ids), jnp.asarray(b.idx),
            jnp.asarray(b.val), jnp.asarray(b.mask),
            reg_arr, alpha_arr,
        )
    return target


def scan_group(
    table: jax.Array,    # gather_table of the fixed side's factors
    yty: jax.Array,      # (k, k) gramian of those factors
    target: jax.Array,   # (n_target, k) pre-sweep factors (CG warm starts)
    g: Bucket,           # one stacked shape group: row_ids (N, B), idx (N, B, L), ...
    reg: jax.Array,
    alpha: jax.Array,
    solver: str,
    cg_steps: int,
    gather_dtype,
) -> tuple[jax.Array, jax.Array]:
    """One shape group's ``(N * B,)`` row ids and solved ``(N * B, k)`` rows,
    slot for slot: one ``lax.scan`` over its same-shape buckets, nothing
    landed. The body of
    ``scan_half_sweep``, and of the row-sharded fit's per-group program
    (``parallel.als``), whose devices each scan their own rows' buckets."""

    def body(_, xs):
        row_ids, idx, val, mask = xs
        return None, solve_rows(
            table, yty, target, row_ids, idx, val, mask, reg, alpha,
            solver, cg_steps, gather_dtype,
        )

    # A slot row's solve is its own, so a bucket scanned in pieces is the
    # bucket solved, with every piece's gather small enough for the
    # compiler to keep a line table in VMEM (GATHER_VMEM_ROWS); the last
    # piece's empty slot rows are cut from the solved block.
    k = target.shape[1]
    n, n_slots, _ = g.idx.shape
    pieces, per = gather_pieces(*g.idx.shape[1:], packed=gather_packs_rows(k))
    xs = (g.row_ids, g.idx, g.val, g.mask)
    if pieces > 1:
        grown = ((0, 0), (0, pieces * per - n_slots))
        xs = tuple(
            jnp.pad(a, grown + ((0, 0),) * (a.ndim - 2)).reshape(n * pieces, per, *a.shape[2:])
            for a in xs
        )
    _, solved = jax.lax.scan(body, None, xs)
    return g.row_ids.reshape(-1), solved.reshape(n, pieces * per, k)[:, :n_slots].reshape(-1, k)


def scan_half_sweep(
    source: jax.Array,
    target: jax.Array,
    groups: list[Bucket],
    reg: jax.Array,
    alpha: jax.Array,
    solver: str = "cholesky",
    cg_steps: int = 3,
    landing: jax.Array | None = None,
    gather_dtype=None,
) -> jax.Array:
    """Traceable half-sweep over stacked same-shape bucket groups
    (``ragged.group_buckets``): one ``lax.scan`` per distinct shape, so the
    whole sweep lives inside a single XLA program with no per-bucket dispatch.

    Each row appears in exactly one bucket, so scan order within a half-sweep
    is irrelevant. ``solver`` picks the kernel as everywhere (``solve_rows``).

    ``landing`` (``models.als`` precomputes it on host) is the inverse
    permutation that lands solved rows by a GATHER from
    ``concat(solved_blocks..., target)`` instead of a scatter into ``target``
    — TPU scatters serialize (measured ~0.03 s/iter, the largest single
    phase of the r4 CG iteration) while the equivalent gather streams.
    ``landing[r] = flat slot position of row r``, or ``n_slots + r`` to keep
    the old factor for rows in no bucket.
    """
    yty = gramian(source)
    table = gather_table(source)

    # Every target row appears in exactly one bucket, so the solves never
    # read rows written this half-sweep: solve all groups against the
    # PRE-SWEEP target (CG warm starts read it), collect the solved blocks,
    # and land them in ONE gather (or scatter, without `landing`) — keeping
    # the (n_target, k) table out of the scan carry.
    all_rows, all_solved = [], []
    for g in groups:
        rows, solved = scan_group(
            table, yty, target, g, reg, alpha, solver, cg_steps, gather_dtype
        )
        all_rows.append(rows)
        all_solved.append(solved)
    if landing is not None:
        with jax.named_scope("als.landing"):
            pool = jnp.concatenate(all_solved + [target])
            return pool[landing]
    rows = jnp.concatenate(all_rows)
    solved = jnp.concatenate(all_solved)
    return scatter_solved(target, rows, solved)


def _fit_loop(
    user_f, item_f, user_groups, item_groups, reg, alpha, n_iter,
    solver, cg_steps, user_landing=None, item_landing=None, gather_dtype=None,
):
    ug = [Bucket(*g) for g in user_groups]
    ig = [Bucket(*g) for g in item_groups]

    def iteration(_, carry):
        uf, vf = carry
        # MLlib order: item factors first (from user factors), then users.
        vf = scan_half_sweep(
            uf, vf, ig, reg, alpha, solver, cg_steps, item_landing, gather_dtype
        )
        uf = scan_half_sweep(
            vf, uf, ug, reg, alpha, solver, cg_steps, user_landing, gather_dtype
        )
        return uf, vf

    return jax.lax.fori_loop(0, n_iter, iteration, (user_f, item_f))


@functools.partial(
    jax.jit,
    donate_argnames=("user_f", "item_f"),
    static_argnames=("solver", "cg_steps", "gather_dtype"),
)
def als_fit_fused(
    user_f: jax.Array,
    item_f: jax.Array,
    user_groups: list[tuple],  # (row_ids, idx, val, mask) per stacked shape group
    item_groups: list[tuple],
    reg: jax.Array,
    alpha: jax.Array,
    n_iter: jax.Array,         # traced scalar: one executable for any iter count
    solver: str = "cholesky",
    cg_steps: int = 3,
    user_landing: jax.Array | None = None,
    item_landing: jax.Array | None = None,
    gather_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The entire ALS fit as ONE device dispatch.

    The reference runs 26 alternating sweeps as hundreds of Spark stages with a
    shuffle boundary each (``ALSRecommenderBuilder.scala:46-58``); the previous
    revision here still paid one host->device dispatch per bucket per sweep
    (~1.5k dispatches, each a host round-trip the device idles through). Now the
    ``max_iter`` loop is a ``lax.fori_loop`` whose body is two scanned
    half-sweeps, so dispatch overhead is paid once per *fit*. ``n_iter`` is a
    traced scalar: warmup with ``n_iter=1`` reuses the same executable as the
    real run.
    """
    return _fit_loop(
        user_f, item_f, user_groups, item_groups, reg, alpha, n_iter,
        solver, cg_steps, user_landing, item_landing, gather_dtype,
    )


def seeded_factors(
    key: jax.Array, n_users: int, n_items: int, rank: int
) -> tuple[jax.Array, jax.Array]:
    """The seeded ``(user, item)`` factor tables every fit starts from:
    normal draws scaled by ``1/sqrt(rank)``. Traced inside the fused program
    and called eagerly by the paths that keep their tables outside one
    (callback, chunked, sharded): the same PRNG ops on the same key, so the
    draws are the same bits and the eager paths' tables are each other's;
    inside a program the compiler may fold the scale into the draw's own last
    multiply, which moves a value by an ulp or two (``tests/test_als.py``).
    That is what lets the paths be compared with one another, and with the
    benchmark's reference, row by row."""
    with jax.named_scope("als.init"):
        ukey, ikey = jax.random.split(key)
        scale = 1.0 / jnp.sqrt(jnp.float32(rank))
        user_f = jax.random.normal(ukey, (n_users, rank), jnp.float32) * scale
        item_f = jax.random.normal(ikey, (n_items, rank), jnp.float32) * scale
    return user_f, item_f


@functools.partial(
    jax.jit,
    static_argnames=("n_users", "n_items", "rank", "solver", "cg_steps", "gather_dtype"),
)
def als_init_fit_fused(
    key: jax.Array,            # PRNG key for the seeded factor init
    user_groups: list[tuple],
    item_groups: list[tuple],
    reg: jax.Array,
    alpha: jax.Array,
    n_iter: jax.Array,
    n_users: int,
    n_items: int,
    rank: int,
    solver: str = "cholesky",
    cg_steps: int = 3,
    user_landing: jax.Array | None = None,
    item_landing: jax.Array | None = None,
    gather_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``als_fit_fused`` with the seeded factor init INSIDE the program.

    Creating the init factors eagerly costs ~6 separate device dispatches
    (PRNGKey, split, 2x normal, 2x scale). Fusing the init into the fit
    program makes the whole train ONE dispatch, on the values
    ``seeded_factors`` gives any other path.
    """
    user_f, item_f = seeded_factors(key, n_users, n_items, rank)
    return _fit_loop(
        user_f, item_f, user_groups, item_groups, reg, alpha, n_iter,
        solver, cg_steps, user_landing, item_landing, gather_dtype,
    )


def implicit_loss(
    user_factors: jax.Array,
    item_factors: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    reg: float,
    alpha: float,
) -> jax.Array:
    """The exact implicit-ALS objective (for tests/monitoring; O(U*I) — small
    data only).

    ``sum_ui c_ui (p_ui - x_u . y_i)^2 + reg * (sum_u n_u |x_u|^2 + sum_i n_i |y_i|^2)``
    """
    scores = user_factors @ item_factors.T
    conf = jnp.ones_like(scores)
    pref = jnp.zeros_like(scores)
    conf = conf.at[rows, cols].add(alpha * vals)
    pref = pref.at[rows, cols].set(jnp.where(vals > 0, 1.0, 0.0))
    data_term = (conf * (pref - scores) ** 2).sum()

    n_u = jnp.zeros(user_factors.shape[0]).at[rows].add(1.0)
    n_i = jnp.zeros(item_factors.shape[0]).at[cols].add(1.0)
    reg_term = (n_u * (user_factors**2).sum(axis=1)).sum() + (
        n_i * (item_factors**2).sum(axis=1)
    ).sum()
    return data_term + reg * reg_term
