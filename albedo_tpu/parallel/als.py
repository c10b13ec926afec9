"""Sharded implicit-ALS sweeps over a device mesh.

The reference's ALS scales by Spark MLlib's shuffled in/out factor blocks with
per-block LAPACK solves on executors (``ALSRecommenderBuilder.scala:46-58``
just calls ``als.fit``; the block machinery is inside MLlib). TPU-native
replacement, two composable pieces:

1. **Data-parallel bucket solves** (`make_sharded_solver`): each padded bucket's
   batch dimension is sharded over the mesh's ``data`` axis with ``shard_map``
   — every device runs the same fixed-shape gather → Gramian-correction einsum
   → batched-Cholesky pipeline on its slice of the rows, the direct analogue of
   MLlib's per-executor block solves but with no shuffle: the solved rows are
   re-assembled by XLA (an all-gather over ICI) and scattered into the factor
   table.

2. **psum Gramian** (`sharded_gramian`): when a factor table is stored sharded
   over devices (rows split on ``data``), the shared ``YtY`` term of every
   implicit solve is the sum of per-shard partial Gramians — one ``(k, k)``
   ``psum`` over ICI, the pattern SURVEY.md section 7 step 3 prescribes (ALX).

Factor tables are replicated by default: at albedo scale (≤ millions of rows ×
rank 50, float32) a full table is ≤ a few hundred MB — far below HBM — and
replication makes the per-bucket arbitrary-index gather local.

3. **The fully sharded fit** (`ShardedALSFit`, ALX arXiv:2112.02194) for
   larger-than-HBM factor tables: BOTH tables row-sharded over ``data``.
   With resident buckets under ``mode="allgather"`` every device buckets,
   solves and lands its OWN rows against the source table assembled ONCE a
   half-sweep ("the resident dataflow" below: `fit_local`). Otherwise —
   interaction buckets STREAMED from the host per half-sweep so the star
   matrix is never device-resident whole, or the ring — per-device bucket
   blocks are solved against source shards all-gathered or ring-passed
   inside every bucket's program, and solved rows land shard-locally from a
   small all-gathered block (`fit`). ``models.als.ImplicitALS`` dispatches
   here when the capacity admission ladder says the replicated layout no
   longer fits, or ``sharded`` names a rung (ARCHITECTURE.md "Sharded ALS").

The per-bucket dataflow is PIPELINED end to end by default (ARCHITECTURE.md
"Pipelined sharded dataflow"; ``ShardedALSFit.fit(pipelined=False)`` is the
synchronous one):
a background prefetcher (`_BucketPrefetcher`) uploads bucket i+1 while
bucket i's solve is dispatched (double-buffered — the mesh never waits on a
cold upload after the first bucket), ring phases issue phase p+1's
``ppermute`` ahead of phase p's Gramian-correction compute, and each
bucket's landing scatter is fused into the NEXT bucket's solve dispatch
(`make_pipelined_landsolve` + a final `make_landing_flush`). Same math,
parity-pinned at 1e-5 against the synchronous path.
"""

from __future__ import annotations

import functools
import math
import queue
import re
from concurrent.futures import ThreadPoolExecutor
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from albedo_tpu.datasets.ragged import Bucket, device_bucket, shard_rows
from albedo_tpu.ops.als import (
    bucket_partial_terms,
    check_solver,
    gather_table,
    scan_group,
    scatter_solved,
    seeded_factors,
    solve_corrected,
    solve_rows,
)
from albedo_tpu.parallel.mesh import DATA_AXIS, pad_rows_to, row_sharded
from albedo_tpu.utils import faults

# Chaos hooks for the fully sharded fit: `als.shard.gather` fires once per
# half-sweep ahead of the source-shard assembly (the all-gather / ring pass),
# `als.shard.stream` fires before every streamed bucket upload — so drills
# can fail or kill a sharded fit mid-collective or mid-stream, exactly like
# `als.chunked` does for the single-device degraded path. `als.shard.
# collective` is the ELASTIC surface: it fires at the head of every
# half-sweep's collective phase (the psum Gramian + the per-bucket
# all-gather/ring passes follow it), and its `loss` kind raises the
# device-loss-shaped error a dead shard surfaces as — the elastic driver
# (`parallel/elastic.py`) classifies it and runs the real checkpoint ->
# remesh -> resume machinery instead of crashing the fit.
SHARD_GATHER_FAULT = faults.site("als.shard.gather")
SHARD_STREAM_FAULT = faults.site("als.shard.stream")
SHARD_COLLECTIVE_FAULT = faults.site("als.shard.collective")
# `als.shard.prefetch` fires INSIDE the background prefetch uploader of a
# pipelined streamed fit, before each bucket's device_put — so drills can
# fail, wedge (delay), or kill the prefetch thread specifically. An error
# there is delivered to the consuming sweep and surfaces as a clean failed
# fit; a wedge is bounded by the collective deadline (`PrefetchStalled`),
# never a hang. The site never fires on the resident sharded path or on the
# synchronous streamed path.
SHARD_PREFETCH_FAULT = faults.site("als.shard.prefetch")


class PrefetchStalled(RuntimeError):
    """The pipelined sweep waited longer than the collective deadline for
    the background prefetch uploader to deliver the next bucket — the
    signature of a wedged prefetch thread (stuck disk read, stuck
    device_put). Deliberately NOT shaped like a device loss: remeshing
    cannot revive a host-side reader, so the elastic driver propagates this
    as a plain clean failure instead of burning its loss budget on it."""

    def __init__(self, deadline_s: float):
        super().__init__(
            f"sharded bucket prefetch exceeded the {deadline_s:g}s "
            f"collective deadline waiting for the background uploader"
        )
        self.deadline_s = float(deadline_s)


def pad_bucket(b: Bucket, multiple: int) -> Bucket:
    """Pad a bucket's batch dim to a device-count multiple (padding slots have
    ``row_ids == -1`` and zero weight, so they solve garbage that is dropped on
    scatter)."""
    if b.row_ids.shape[0] % multiple == 0:
        return b
    return Bucket(
        row_ids=pad_rows_to(b.row_ids, multiple, fill=-1),
        idx=pad_rows_to(b.idx, multiple),
        val=pad_rows_to(b.val, multiple),
        mask=pad_rows_to(b.mask, multiple),
    )


def sharded_gramian(mesh: Mesh, axis: str = DATA_AXIS):
    """``F^T F`` for a row-sharded factor table: local partial Gramian + psum."""

    # One (k, k) psum program per mesh, compiled once and memoized via
    # sharded_fit_engine — no per-shape ladder, no cross-process cold cost
    # worth an export; the bucket solves themselves go through utils/aot.
    # albedo: noqa[bare-jit]
    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(),
    )
    def gramian(local_factors: jax.Array) -> jax.Array:
        with jax.named_scope("als.shard.gramian"):
            return jax.lax.psum(local_factors.T @ local_factors, axis)

    return gramian


def make_sharded_solver(mesh: Mesh, axis: str = DATA_AXIS):
    """Build the jitted sharded bucket solver for this mesh.

    The returned function has the same signature/semantics as
    ``ops.als.solve_bucket`` but runs the per-row solves data-parallel across
    ``axis``. Bucket batch dims must be divisible by the axis size
    (see ``pad_bucket``).
    """
    n_shards = mesh.shape[axis]

    local_solve = shard_map(
        _local_bucket_solve,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis, None), P(axis, None), P(axis, None), P(), P()),
        out_specs=P(axis),
    )

    # Explicit-collectives REFERENCE implementation (ShardedALSSweep):
    # parity tests pin the fused path against it; it never runs in a fit job.
    # albedo: noqa[bare-jit]
    @functools.partial(jax.jit, donate_argnames=("target",))
    def solve_bucket_sharded(source, yty, target, row_ids, idx, val, mask, reg, alpha):
        if row_ids.shape[0] % n_shards:
            raise ValueError(
                f"bucket batch {row_ids.shape[0]} not divisible by {n_shards} shards"
            )
        solved = local_solve(source, yty, row_ids, idx, val, mask, reg, alpha)
        # Scatter back into the (replicated) target; XLA inserts the all-gather
        # of the row-sharded `solved` over ICI.
        return scatter_solved(target, row_ids, solved)

    return solve_bucket_sharded


def _local_bucket_solve(source, yty, row_ids, idx, val, mask, reg, alpha):
    """Per-device slice of a bucket solve; math shared with the single-device
    path via ``ops.als.solve_rows`` (exact solve: no target rows are read;
    ``row_ids`` serves the scatter, outside the shard)."""
    return solve_rows(
        source, yty, None, row_ids, idx, val, mask, reg, alpha,
        solver="cholesky", cg_steps=0, gather_dtype=None,
    )


# --- fully sharded fit (ALX layout) -------------------------------------------
#
# Both factor tables stored ROW-SHARDED over the mesh's data axis (1/n of each
# table resident per device), bucket batch dims sharded the same way, and the
# fixed side's factors assembled per bucket inside shard_map:
#
# ``mode="allgather"``  one tiled all-gather materializes the full (padded)
#                       source table transiently per bucket — minimal FLOPs,
#                       transient HBM = one full table. (Resident buckets
#                       assemble once a HALF-SWEEP instead: "the resident
#                       dataflow" further down.)
# ``mode="ring"``       the source shard rotates around the ring (ppermute);
#                       each of the n phases accumulates the Gramian
#                       correction and b-vector for the entries whose rows
#                       live on the visiting shard (``ops.als.
#                       bucket_partial_terms``) — n x the gather/einsum work,
#                       but NO array larger than a 1/n table shard ever
#                       materializes. Cholesky only: the CG matvec would need
#                       the gathered rows at every step.
#
# Solved rows land by all-gathering the (small) solved block + row ids and
# letting every device scatter the rows it owns into its target shard —
# row-sharded in, row-sharded out, no host round trip.


def _assembled_solve(
    source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, axis, solver, cg_steps, gather_dtype,
):
    """Per-device bucket solve against the all-gathered source table, in the
    form the gather reads it (``ops.als.gather_table``)."""
    source = gather_table(jax.lax.all_gather(source_l, axis, axis=0, tiled=True))
    target = None
    if solver == "cg":
        # Warm starts read the PRE-SWEEP target rows, which live on whatever
        # shard owns them — assemble the target too (priced by the cost
        # model as the CG mode's extra transient).
        target = jax.lax.all_gather(target_l, axis, axis=0, tiled=True)
    return solve_rows(
        source, yty, target, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
        solver, cg_steps, gather_dtype,
    )


def _ring_solve(
    source_l, yty, idx_l, val_l, mask_l, reg, alpha,
    *, axis, n_shards, gather_dtype, overlapped=False,
):
    """Per-device bucket solve with the source shard ring-passed: phase p
    holds the shard born on device ``(self - p) mod n`` and accumulates the
    normal-equation terms for entries whose global index falls in that
    shard's row range; after n phases every entry has been seen exactly
    once, so the accumulated terms equal the full-gather terms.

    ``overlapped`` software-pipelines the loop body: phase p+1's
    ``ppermute`` is ISSUED before phase p's gather/einsum compute, so the
    shard transfer rides the ICI while the MXU chews the current phase —
    same dataflow graph, same math (the permute reads the same ``src`` the
    compute does), only the issue order changes so the async-collective
    scheduler can hide the hop latency. The synchronous order (compute,
    then permute) is the synchronous dataflow's (``pipelined=False``)."""
    rows_per = source_l.shape[0]
    k = source_l.shape[1]
    shard = jax.lax.axis_index(axis)
    src0 = (
        source_l if gather_dtype is None
        else source_l.astype(jnp.dtype(gather_dtype))
    )
    c1_full = alpha * val_l                      # (B_l, L); 0 on padding
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    b_l = idx_l.shape[0]
    # The accumulators start as constants (unvarying over the mesh) but
    # come back device-varying: shard_map's varying-axes check needs the
    # loop carry's type fixed up front.
    corr0 = jax.lax.pcast(jnp.zeros((b_l, k, k), jnp.float32), axis, to="varying")
    bvec0 = jax.lax.pcast(jnp.zeros((b_l, k), jnp.float32), axis, to="varying")

    def phase(p, carry):
        src, corr, b_vec = carry
        if overlapped:
            # Phase p+1's shard starts moving before phase p's compute.
            src_next = jax.lax.ppermute(src, axis, perm)
        owner = jax.lax.rem(shard - p + n_shards, n_shards)
        lo = owner * rows_per
        rel = idx_l - lo
        valid = mask_l & (rel >= 0) & (rel < rows_per)
        g = jnp.where(
            valid[..., None],
            src[jnp.clip(rel, 0, rows_per - 1)],
            jnp.zeros((), src.dtype),
        )
        c1 = jnp.where(valid, c1_full, 0.0)
        w = jnp.where(valid, 1.0 + c1_full, 0.0)
        dc, db = bucket_partial_terms(g, c1, w)
        if not overlapped:
            src_next = jax.lax.ppermute(src, axis, perm)
        return src_next, corr + dc, b_vec + db

    _, corr, b_vec = jax.lax.fori_loop(
        0, n_shards, phase, (src0, corr0, bvec0)
    )
    n_b = mask_l.sum(axis=1).astype(jnp.float32)
    return solve_corrected(yty, corr, b_vec, n_b, reg)


def _landing_scatter(target_l, rows_g, solved_g, axis):
    """Owner-shard scatter of an all-gathered solved block: each device
    keeps the rows its target shard owns; padding slots (``row_ids == -1``)
    and foreign rows scatter out of range and drop."""
    shard = jax.lax.axis_index(axis)
    rows_per = target_l.shape[0]
    local = rows_g - shard * rows_per
    local = jnp.where(
        (rows_g >= 0) & (local >= 0) & (local < rows_per), local, rows_per
    )
    return target_l.at[local].set(solved_g, mode="drop")


def _solve_any(
    source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, axis, n_shards, mode, solver, cg_steps, gather_dtype, overlapped,
):
    if mode == "ring":
        return _ring_solve(
            source_l, yty, idx_l, val_l, mask_l, reg, alpha,
            axis=axis, n_shards=n_shards, gather_dtype=gather_dtype,
            overlapped=overlapped,
        )
    return _assembled_solve(
        source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg,
        alpha, axis=axis, solver=solver, cg_steps=cg_steps,
        gather_dtype=gather_dtype,
    )


def _sharded_update_body(
    source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, axis, n_shards, mode, solver, cg_steps, gather_dtype,
):
    solved_l = _solve_any(
        source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg,
        alpha, axis=axis, n_shards=n_shards, mode=mode, solver=solver,
        cg_steps=cg_steps, gather_dtype=gather_dtype, overlapped=False,
    )
    # Land: the solved block is small (B x k), so all-gather it with its row
    # ids and let each device keep the rows its target shard owns.
    rows_g = jax.lax.all_gather(row_ids_l, axis, axis=0, tiled=True)
    solved_g = jax.lax.all_gather(solved_l, axis, axis=0, tiled=True)
    return _landing_scatter(target_l, rows_g, solved_g, axis)


# --- pipelined dataflow program bodies ----------------------------------------
#
# The pipelined half-sweep splits each bucket's work so every cross-device
# transfer is issued AHEAD of compute it can hide behind (ARCHITECTURE.md
# "Pipelined sharded dataflow"):
#
#   solve      the first bucket: solve only, no landing yet (there is no
#              previous block to land). Ring phases run overlapped.
#   landsolve  every later bucket: the PREVIOUS bucket's solved-block
#              all-gather is issued first, this bucket's solve computes
#              while that (small) block is in flight, then the previous
#              block scatters into the target shard — the landing stops
#              being a separate synchronous tail on every bucket.
#   flush      after the last bucket: land the final pending block.
#
# Parity is exact by construction: each target row appears in exactly ONE
# bucket per half-sweep, so deferring bucket i's landing until bucket i+1's
# dispatch changes no value any solve reads — the CG warm start reads only
# its own bucket's rows (never landed earlier in the sweep), and padding
# rows solve garbage that drops on scatter either way.


def _pipelined_solve_body(
    source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, axis, n_shards, mode, solver, cg_steps, gather_dtype,
):
    return _solve_any(
        source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg,
        alpha, axis=axis, n_shards=n_shards, mode=mode, solver=solver,
        cg_steps=cg_steps, gather_dtype=gather_dtype, overlapped=True,
    )


def _pipelined_landsolve_body(
    source_l, yty, target_l, prev_rows_l, prev_solved_l,
    row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, axis, n_shards, mode, solver, cg_steps, gather_dtype,
):
    # Previous bucket's landing all-gathers issued FIRST: the (B_prev, k)
    # block transfer overlaps this bucket's gather/einsum/solve compute.
    prev_rows_g = jax.lax.all_gather(prev_rows_l, axis, axis=0, tiled=True)
    prev_solved_g = jax.lax.all_gather(prev_solved_l, axis, axis=0, tiled=True)
    solved_l = _solve_any(
        source_l, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg,
        alpha, axis=axis, n_shards=n_shards, mode=mode, solver=solver,
        cg_steps=cg_steps, gather_dtype=gather_dtype, overlapped=True,
    )
    target_l = _landing_scatter(target_l, prev_rows_g, prev_solved_g, axis)
    return target_l, solved_l


def _landing_flush_body(target_l, rows_l, solved_l, *, axis):
    rows_g = jax.lax.all_gather(rows_l, axis, axis=0, tiled=True)
    solved_g = jax.lax.all_gather(solved_l, axis, axis=0, tiled=True)
    return _landing_scatter(target_l, rows_g, solved_g, axis)


def make_sharded_update(mesh: Mesh, axis: str = DATA_AXIS, mode: str = "allgather"):
    """Jitted sharded bucket update: row-sharded source/target tables in,
    row-sharded target out. Bucket batch dims and both tables' row counts
    must be device-count multiples (``pad_bucket`` / ``pad_rows_to``)."""
    n_shards = mesh.shape[axis]

    def update(source, yty, target, row_ids, idx, val, mask, reg, alpha,
               solver="cholesky", cg_steps=3, gather_dtype=None):
        body = functools.partial(
            _sharded_update_body, axis=axis, n_shards=n_shards, mode=mode,
            solver=solver, cg_steps=cg_steps, gather_dtype=gather_dtype,
        )
        f = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(axis, None), P(), P(axis, None), P(axis),
                P(axis, None), P(axis, None), P(axis, None), P(), P(),
            ),
            out_specs=P(axis, None),
        )
        return f(source, yty, target, row_ids, idx, val, mask, reg, alpha)

    return jax.jit(
        update, donate_argnums=(2,),
        static_argnames=("solver", "cg_steps", "gather_dtype"),
    )


def make_pipelined_solve(mesh: Mesh, axis: str = DATA_AXIS, mode: str = "allgather"):
    """Solve-only program for the pipelined half-sweep's FIRST bucket:
    row-sharded solved block out, target untouched (read transiently for
    the CG warm start only — NOT donated, the landing comes later)."""
    n_shards = mesh.shape[axis]

    def solve(source, yty, target, row_ids, idx, val, mask, reg, alpha,
              solver="cholesky", cg_steps=3, gather_dtype=None):
        body = functools.partial(
            _pipelined_solve_body, axis=axis, n_shards=n_shards, mode=mode,
            solver=solver, cg_steps=cg_steps, gather_dtype=gather_dtype,
        )
        f = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(axis, None), P(), P(axis, None), P(axis),
                P(axis, None), P(axis, None), P(axis, None), P(), P(),
            ),
            out_specs=P(axis),
        )
        return f(source, yty, target, row_ids, idx, val, mask, reg, alpha)

    return jax.jit(solve, static_argnames=("solver", "cg_steps", "gather_dtype"))


def make_pipelined_landsolve(
    mesh: Mesh, axis: str = DATA_AXIS, mode: str = "allgather"
):
    """The pipelined half-sweep's steady-state program: land the PREVIOUS
    bucket's solved block (its all-gather issued ahead of compute) while
    solving THIS bucket — the fused landing scatter. Returns
    ``(target, solved_l)``; target is donated — the consumed previous
    block and the bucket slabs are NOT (slabs are reused by resident
    sweeps, and a (B, k) block is too small to be worth the
    shape-mismatched-alias donation warnings)."""
    n_shards = mesh.shape[axis]

    def landsolve(source, yty, target, prev_rows, prev_solved,
                  row_ids, idx, val, mask, reg, alpha,
                  solver="cholesky", cg_steps=3, gather_dtype=None):
        body = functools.partial(
            _pipelined_landsolve_body, axis=axis, n_shards=n_shards,
            mode=mode, solver=solver, cg_steps=cg_steps,
            gather_dtype=gather_dtype,
        )
        f = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(axis, None), P(), P(axis, None), P(axis), P(axis, None),
                P(axis), P(axis, None), P(axis, None), P(axis, None),
                P(), P(),
            ),
            out_specs=(P(axis, None), P(axis)),
        )
        return f(source, yty, target, prev_rows, prev_solved,
                 row_ids, idx, val, mask, reg, alpha)

    return jax.jit(
        landsolve, donate_argnums=(2,),
        static_argnames=("solver", "cg_steps", "gather_dtype"),
    )


def make_landing_flush(mesh: Mesh, axis: str = DATA_AXIS):
    """Land one pending solved block (the pipelined half-sweep's tail)."""

    def flush(target, rows, solved):
        f = shard_map(
            functools.partial(_landing_flush_body, axis=axis),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis, None)),
            out_specs=P(axis, None),
        )
        return f(target, rows, solved)

    return jax.jit(flush, donate_argnums=(0,))


# --- the resident dataflow: every device solves its OWN rows -------------------
#
# ``mode="allgather"`` with resident buckets (``ImplicitALS(sharded="resident")``,
# what ``train_als --mesh-devices n --sharded resident`` builds) is the ALX
# layout proper. A half-sweep is
#
#   gramian   one (k, k) psum over the source shards (``sharded_gramian``);
#   assemble  ONE all-gather of the source table, in the form the gather
#             reads it (``ops.als.gather_table``), handed to every bucket
#             program of the half-sweep — (n - 1) / n of the table into a
#             chip, once, where a program that assembles inside every bucket
#             moves it once a BUCKET (1,455 times a sweep at 10M x 1M);
#   solve     one program a shape group: each device scans its own rows'
#             buckets (``datasets.ragged.shard_grouped_bucket_rows``: slots
#             ``[d * B, (d + 1) * B)`` of a bucket hold rows of shard ``d``
#             under local row ids) with the one-chip sweep's own body
#             (``ops.als.scan_group``) — warm starts read from its own shard
#             of the target, no collective at all;
#   land      one gather a half-sweep from ``concat(solved blocks, shard)``
#             through the shard's landing permutation, as the fused one-chip
#             fit lands (``ops.als.scan_half_sweep``): no solved row leaves
#             its device.
#
# So a sweep's collective traffic into a chip is each table once and two
# (k, k) psums (``collective_bytes_per_sweep``), and the host dispatches one
# program a shape group and six more a sweep instead of one a bucket.


def assembled_bytes_per_sweep(
    n_users: int, n_items: int, rank: int, n_shards: int,
    bucket_programs: tuple[int, int] | None = None, solver: str = "cholesky",
) -> int:
    """The PLAN of assembled float32 table bytes a chip holds new in one
    sweep of a row-sharded all-gather fit, from the shapes alone. The
    resident dataflow is to assemble each (row-padded) table ONCE — the user
    table ahead of the item half-sweep, the item table ahead of the user
    one: ``(users + items) * rank * 4``, the layout's need; what its
    compiled programs did assemble is the fit report's counter of this name
    (:func:`all_gather_bytes` of every executable a sweep called).
    ``bucket_programs=(user buckets, item buckets)`` prices the dataflow
    whose every bucket program assembles for itself (streamed buckets;
    global buckets handed to ``ShardedALSFit.fit``): the source table once a
    BUCKET, and under ``solver="cg"`` the target table beside it, for the
    warm-start rows; there the plan is the report's counter too. The
    benchmark's ``fit_sharded`` driver asks for it before it generates a
    star: a program without one is refused."""
    n_u, n_i = pad_rows(n_users, n_shards), pad_rows(n_items, n_shards)
    if bucket_programs is None:
        return (n_u + n_i) * rank * 4
    user_buckets, item_buckets = bucket_programs
    both = solver == "cg"
    return (user_buckets * (n_i + both * n_u) + item_buckets * (n_u + both * n_i)) * rank * 4


def collective_bytes_per_sweep(
    rank: int, n_shards: int, table_bytes: int, landed_rows: int = 0
) -> int:
    """Bytes that arrive on a chip from the others in one sweep: the other
    shards of ``table_bytes`` of assembled (or ring-passed) tables, of
    ``landed_rows`` all-gathered solved rows with their row ids (none in the
    resident dataflow: solved rows do not travel), and the two psums'
    ``(k, k)`` partial Gramians."""
    moved = table_bytes + landed_rows * (rank * 4 + 4)
    return (moved // n_shards + 2 * rank * rank * 4) * (n_shards - 1)


_ALL_GATHER = re.compile(r"= (.+?) all-gather(?:-start)?\(")
_HLO_ARRAY = re.compile(r"\b([a-z]+)(\d*)\w*\[([\d,]*)\]")


def all_gather_bytes(compiled) -> int:
    """Bytes of all-gather results in one call of a compiled program, a
    device: every ``all-gather`` (or asynchronous ``all-gather-start``, whose
    result names the operand beside the gathered array: the larger counts)
    of its optimized HLO, each counted once — so one inside a loop's body is
    counted as a single call of it, which is enough to tell a program that
    gathers a table from one that does not."""
    return sum(
        max(int(bits or 8) // 8 * math.prod(int(d) for d in dims.split(",") if d)
            for _, bits, dims in _HLO_ARRAY.findall(result))
        for result in _ALL_GATHER.findall(compiled.as_text())
    )


def _sds(shape, dtype, sharding) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def pad_rows(n_rows: int, n_shards: int) -> int:
    """Rows of a table padded to a shard-count multiple."""
    return shard_rows(n_rows, n_shards) * n_shards


def _assemble_body(source_l, *, axis):
    with jax.named_scope("als.shard.assemble"):
        return gather_table(jax.lax.all_gather(source_l, axis, axis=0, tiled=True))


def _local_group_body(
    table, yty, target_l, row_ids_l, idx_l, val_l, mask_l, reg, alpha,
    *, solver, cg_steps, gather_dtype,
):
    _, solved = scan_group(
        table, yty, target_l, Bucket(row_ids_l, idx_l, val_l, mask_l),
        reg, alpha, solver, cg_steps, gather_dtype,
    )
    return solved


def _local_landing_body(target_l, landing_l, *solved_l):
    with jax.named_scope("als.shard.land"):
        return jnp.concatenate(list(solved_l) + [target_l])[landing_l]


def make_assemble(mesh: Mesh, axis: str = DATA_AXIS):
    """The half-sweep's one assembly: a row-sharded table in, the whole
    table in the gather's form on every device out."""

    def assemble(source):
        # every device holds the same rows after a tiled all-gather, which
        # the varying-axes check cannot see
        return shard_map(
            functools.partial(_assemble_body, axis=axis), mesh=mesh,
            in_specs=P(axis, None), out_specs=P(), check_vma=False,
        )(source)

    return jax.jit(assemble)


def make_local_solve(mesh: Mesh, axis: str = DATA_AXIS):
    """One shape group's program: the assembled table, the Gramian and the
    row-sharded target in, every device's solved ``(N * B, k)`` rows out
    (row-sharded: device ``d``'s block is its own). No collective."""

    def solve(table, yty, target, row_ids, idx, val, mask, reg, alpha,
              solver="cholesky", cg_steps=3, gather_dtype=None):
        body = functools.partial(
            _local_group_body, solver=solver, cg_steps=cg_steps,
            gather_dtype=gather_dtype,
        )
        return shard_map(
            body, mesh=mesh,
            in_specs=(
                P(), P(), P(axis, None), P(None, axis), P(None, axis, None),
                P(None, axis, None), P(None, axis, None), P(), P(),
            ),
            out_specs=P(axis, None),
        )(table, yty, target, row_ids, idx, val, mask, reg, alpha)

    return jax.jit(solve, static_argnames=("solver", "cg_steps", "gather_dtype"))


def make_local_landing(mesh: Mesh, axis: str = DATA_AXIS):
    """The half-sweep's one landing: every device gathers its new shard from
    its own solved blocks and its old shard. No collective."""

    def land(target, landing, *solved):
        return shard_map(
            _local_landing_body, mesh=mesh,
            in_specs=(P(axis, None), P(axis)) + (P(axis, None),) * len(solved),
            out_specs=P(axis, None),
        )(target, landing, *solved)

    return jax.jit(land)


def make_relayout(mesh: Mesh, axis: str = DATA_AXIS):
    """``table[rows]`` for a row-sharded table and a row-sharded permutation
    of its rows, row-sharded again: between the logical row order and the
    order the shards own rows in (``datasets.ragged.balanced_shards``). Rows
    change shards, so this moves a table's worth across the mesh — once a
    fit each way, never inside a sweep."""
    rows2d = NamedSharding(mesh, P(axis, None))

    def relayout(table, rows):
        with jax.named_scope("als.shard.relayout"):
            return table[rows]

    return jax.jit(relayout, out_shardings=rows2d)


def make_seeded_tables(mesh: Mesh, axis: str = DATA_AXIS):
    """``ops.als.seeded_factors`` made ON the mesh: both tables row-sharded,
    rows padded with zeros to a shard-count multiple (no bucket refers to
    them) and laid out in the order ``user_rows`` / ``item_rows`` give
    (``logical_of_phys`` of ``datasets.ragged.balanced_shards``). The draws
    are the single-device path's, row for row (the threefry implementation
    is partitionable: a value does not depend on the layout it is drawn
    under)."""
    n = int(mesh.shape[axis])
    rows2d = NamedSharding(mesh, P(axis, None))

    def seeded(key, user_rows, item_rows, n_users, n_items, rank):
        tables = seeded_factors(key, n_users, n_items, rank)
        with jax.named_scope("als.shard.relayout"):
            return tuple(
                jnp.pad(f, ((0, -f.shape[0] % n), (0, 0)))[rows]
                for f, rows in zip(tables, (user_rows, item_rows))
            )

    return jax.jit(
        seeded, static_argnames=("n_users", "n_items", "rank"),
        out_shardings=(rows2d, rows2d),
    )


class _BucketPrefetcher:
    """Double-buffered background bucket uploader for the streamed pipelined
    half-sweep (the ALX host-feeding pattern, arXiv:2112.02194).

    A daemon thread pulls HOST buckets from the provider's iterable — the
    disk read/parse runs off the critical path — pads them and issues the
    async ``device_put`` (``ShardedALSFit.put_bucket``), then parks the
    device bucket in a 1-deep queue. A slot semaphore keeps exactly TWO
    buckets in flight (the one the sweep is solving + the one just
    uploaded): that is the footprint ``utils.capacity.plan_fit_sharded``
    prices for the pipelined-streamed rung, so upload never runs ahead of
    the admission that approved it.

    Failure semantics: an exception in the thread (including the
    ``als.shard.prefetch`` fault site's kinds) is delivered to the
    consuming sweep at its next bucket and re-raised there — a clean failed
    fit. A wedged thread cannot hang the fit: the consumer's queue wait is
    bounded by the collective deadline (:class:`PrefetchStalled`). On ANY
    exit — normal, error, or an exception thrown by the sweep itself (a
    device loss mid-chunk) — the context manager stops the thread and
    drops whatever was in flight, so an elastic remesh-resume never sees a
    half-applied bucket: the chunk re-runs whole from the last boundary.
    """

    def __init__(self, engine: "ShardedALSFit", host_buckets, stats: dict,
                 deadline_s: float):
        self._engine = engine
        self._buckets = host_buckets
        self._stats = stats
        self._deadline = float(deadline_s)
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._slot = threading.Semaphore(1)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="albedo-shard-prefetch", daemon=True
        )

    def __enter__(self) -> "_BucketPrefetcher":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        try:  # unblock a put()-parked thread so it can observe the stop
            self._q.get_nowait()
        except queue.Empty:
            pass
        self._slot.release()
        self._thread.join(timeout=2.0)
        return False

    # ------------------------------------------------- background uploader
    def _run(self) -> None:
        try:
            for b in self._buckets:
                while not self._slot.acquire(timeout=0.1):
                    if self._stop.is_set():
                        return
                if self._stop.is_set():
                    return
                SHARD_STREAM_FAULT.hit()
                SHARD_PREFETCH_FAULT.hit()
                t0 = time.perf_counter()
                dev = self._engine.put_bucket(b)
                # Disjoint stats keys, one writer each: this thread owns
                # upload_s/streamed_buckets, the consumer owns
                # prefetch_wait_s; dict item stores are GIL-atomic.
                self._stats["upload_s"] += time.perf_counter() - t0   # albedo: noqa[shared-state-guard]
                self._stats["streamed_buckets"] += 1
                self._put(("bucket", dev))
        except BaseException as e:  # noqa: BLE001 — re-raised on the consumer
            self._put(("error", e))
            return
        self._put(("done", None))

    def _put(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    # ------------------------------------------------------------ consumer
    def __iter__(self) -> "_BucketPrefetcher":
        return self

    def __next__(self):
        t0 = time.perf_counter()
        try:
            kind, payload = self._q.get(
                timeout=self._deadline if self._deadline > 0 else None
            )
        except queue.Empty:
            raise PrefetchStalled(self._deadline) from None
        # Disjoint stats key: the consumer thread is the only writer of
        # prefetch_wait_s (the uploader owns upload_s/streamed_buckets);
        # dict item stores are GIL-atomic.
        self._stats["prefetch_wait_s"] += time.perf_counter() - t0  # albedo: noqa[shared-state-guard]
        if kind == "error":
            raise payload
        if kind == "done":
            raise StopIteration
        self._slot.release()  # free the slot: upload bucket i+2 while i+1 solves
        return payload


def _acquire_executable(
    engine: "ShardedALSFit", fn, kind: str, args, stats: dict, shape_key: tuple,
    statics: dict | None = None, timer=None,
):
    """Per-shape executable through the persistent AOT layer, memoized on
    the engine; ``kind`` names which of the sweep's programs this is
    (update / solve / landsolve / flush, and the resident dataflow's
    assemble / local_solve / local_land / seeded) — each gets its own key
    space and its own fingerprint-verified disk export. ``args`` may be
    abstract (``jax.ShapeDtypeStruct`` with the argument's sharding): the
    resident dataflow acquires every shape ahead of its first sweep, on
    threads, before a table exists. A module-level conduit (forwards ``fn``
    into ``persistent_aot_executable``) so graftlint R1 can prove every
    sharded program reaches the AOT layer."""
    from albedo_tpu.utils.aot import persistent_aot_executable

    key = (kind,) + shape_key
    compiled = engine._executables.get(key)
    if compiled is None:
        dev = jax.devices()[0]
        compiled, c_s, tag = persistent_aot_executable(
            fn, args, None, statics,
            key_parts=(
                "als_sharded", kind, jax.__version__,
                jax.default_backend(), getattr(dev, "device_kind", "?"),
                repr(engine.mesh), engine.mode, engine.solver,
                engine.cg_steps, engine.gather_dtype,
            ) + shape_key,
            name=f"als_sharded_{kind}", timer=timer, span="fit.acquire",
        )
        engine._executables[key] = compiled
        engine._gathered[key] = all_gather_bytes(compiled)
        stats["compile_s"] += c_s
        stats["compile_sources"].add(tag)
    return compiled


@functools.lru_cache(maxsize=8)
def sharded_fit_engine(
    mesh: Mesh,
    axis: str = DATA_AXIS,
    solver: str = "cholesky",
    cg_steps: int = 3,
    gather_dtype: str | None = None,
    mode: str = "allgather",
) -> "ShardedALSFit":
    """Memoized engine factory: ``Mesh`` is hashable and value-compared, so
    repeated fits on the same layout reuse the engine's jitted update /
    gramian closures and its per-shape executable handles instead of
    retracing per fit."""
    return ShardedALSFit(
        mesh, axis=axis, solver=solver, cg_steps=cg_steps,
        gather_dtype=gather_dtype, mode=mode,
    )


class ShardedALSFit:
    """The fully sharded ALS fit: both tables row-sharded, buckets resident
    (uploaded once, batch-sharded) or STREAMED from the host per half-sweep
    so the star matrix is never device-resident whole.

    Per-bucket-shape executables are acquired through the persistent AOT
    layer (``utils.aot``) — sharded fits run in the same kill-resume chaos
    as every other fit, so their cross-process executable reuse must stay
    fingerprint-verified; ``models.als.ImplicitALS`` drives this engine when
    the capacity admission ladder picks a sharded rung.
    """

    def __init__(
        self,
        mesh: Mesh,
        axis: str = DATA_AXIS,
        solver: str = "cholesky",
        cg_steps: int = 3,
        gather_dtype: str | None = None,
        mode: str = "allgather",
    ):
        check_solver(solver)
        if mode not in ("allgather", "ring"):
            raise ValueError(f"unknown shard mode {mode!r}")
        if mode == "ring" and solver == "cg":
            raise ValueError(
                "ring mode supports the cholesky solver only: the CG matvec "
                "re-reads the gathered rows every step, which would re-run "
                "the whole ring per step — use mode='allgather' with cg"
            )
        self.mesh = mesh
        self.axis = axis
        self.solver = solver
        self.cg_steps = int(cg_steps)
        self.gather_dtype = gather_dtype
        self.mode = mode
        self.n_shards = int(mesh.shape[axis])
        self._update = make_sharded_update(mesh, axis, mode)
        self._solve = make_pipelined_solve(mesh, axis, mode)
        self._landsolve = make_pipelined_landsolve(mesh, axis, mode)
        self._flush = make_landing_flush(mesh, axis)
        self._gramian = sharded_gramian(mesh, axis)
        self._assemble = make_assemble(mesh, axis)
        self._local_solve = make_local_solve(mesh, axis)
        self._local_land = make_local_landing(mesh, axis)
        self._seeded = make_seeded_tables(mesh, axis)
        self._relayout = make_relayout(mesh, axis)
        self._rows1d = row_sharded(mesh, axis)
        self._rows2d = NamedSharding(mesh, P(axis, None))
        self._replicated = NamedSharding(mesh, P())
        # a stacked shape group's arrays: the slot axis over the mesh
        self._slots2d = NamedSharding(mesh, P(None, axis))
        self._slots3d = NamedSharding(mesh, P(None, axis, None))
        self._executables: dict[tuple, object] = {}
        self._gathered: dict[tuple, int] = {}   # all_gather_bytes of each of them
        self._acquired_layouts: set[tuple] = set()   # (sizes, shapes) acquire_local has seen

    # ------------------------------------------------------------- layout
    def shard_table(self, factors) -> jax.Array:
        """Pad rows to a shard-count multiple (pad rows are zeros — no
        bucket references them) and lay the table out row-sharded."""
        f = np.asarray(factors, dtype=np.float32)
        f = pad_rows_to(f, self.n_shards)
        return jax.device_put(f, self._rows2d)

    def put_bucket(self, b: Bucket) -> Bucket:
        """Pad a host bucket's batch dim to the shard count and upload it
        batch-sharded over the mesh."""
        b = pad_bucket(b, self.n_shards)
        return Bucket(
            row_ids=jax.device_put(np.ascontiguousarray(b.row_ids), self._rows1d),
            idx=jax.device_put(b.idx, self._rows2d),
            val=jax.device_put(b.val, self._rows2d),
            mask=jax.device_put(b.mask, self._rows2d),
        )

    # ------------------------------------------------------------ running
    def _statics(self) -> dict:
        return dict(
            solver=self.solver, cg_steps=self.cg_steps,
            gather_dtype=self.gather_dtype,
        )

    def _run_bucket(self, source, yty, target, b: Bucket, reg, alpha, stats: dict):
        args = (source, yty, target, b.row_ids, b.idx, b.val, b.mask, reg, alpha)
        key = (source.shape[0], target.shape[0], tuple(b.idx.shape))
        stats["dispatches"] += 1
        return _acquire_executable(
            self, self._update, "update", args, stats, key, self._statics())(*args)

    # ------------------------------------------- the resident dataflow
    def put_group(self, g: Bucket) -> tuple:
        """Upload one own-rows shape group (``datasets.ragged.
        shard_grouped_bucket_rows``), its slot axis over the mesh: every
        device is sent its own rows' slots and nothing else."""
        return (
            jax.device_put(g.row_ids, self._slots2d),
            jax.device_put(g.idx, self._slots3d),
            jax.device_put(g.val, self._slots3d),
            jax.device_put(g.mask, self._slots3d),
        )

    def put_rows(self, rows: np.ndarray) -> jax.Array:
        """Upload one int32 entry a table row (``(n_shards * rows_per,)``: a
        side's stacked per-shard landing permutations, or a permutation of
        its rows), each shard's to its device."""
        return jax.device_put(rows, self._rows1d)

    def _local_programs(self, n_source: int, n_target: int, rank: int, shapes) -> list[tuple]:
        """``(kind, program, abstract arguments, key, statics)`` of one
        half-sweep of the resident dataflow: ``n_source`` / ``n_target``
        padded table rows, ``shapes`` the target side's group shapes ``(N,
        n_shards * B, L)``."""
        f32, i32, sds = jnp.float32, jnp.int32, _sds
        source = sds((n_source, rank), f32, self._rows2d)
        table = sds(jax.eval_shape(gather_table, source).shape, f32, self._replicated)
        yty = sds((rank, rank), f32, self._replicated)
        target = sds((n_target, rank), f32, self._rows2d)
        scalar = sds((), f32, self._replicated)
        programs = [("assemble", self._assemble, (source,), (n_source, rank), None)]
        solved = []
        for shape in dict.fromkeys(tuple(shape) for shape in shapes):
            n, slots, _ = shape
            args = (table, yty, target, sds((n, slots), i32, self._slots2d),
                    sds(shape, i32, self._slots3d), sds(shape, f32, self._slots3d),
                    sds(shape, jnp.bool_, self._slots3d), scalar, scalar)
            programs.append(("local_solve", self._local_solve, args,
                             (n_source, n_target, rank, shape), self._statics()))
        for n, slots, _ in shapes:
            solved.append(sds((n * slots, rank), f32, self._rows2d))
        landing = sds((n_target,), i32, self._rows1d)
        programs.append(("local_land", self._local_land, (target, landing, *solved),
                         (n_target, rank, tuple(tuple(shape) for shape in shapes)), None))
        return programs

    def acquire_local(self, n_users: int, n_items: int, rank: int, user_shapes, item_shapes,
                      stats: dict, timer=None, workers: int = 1) -> None:
        """Every executable of the resident dataflow for this layout, ahead
        of the first sweep and side by side on ``workers`` threads (abstract
        arguments: no table exists yet, so a probe's tables are the only
        ones on the device). A second fit finds them on the engine."""
        layout = (n_users, n_items, rank, tuple(user_shapes), tuple(item_shapes))
        if layout in self._acquired_layouts:
            return
        n_u, n_i = pad_rows(n_users, self.n_shards), pad_rows(n_items, self.n_shards)
        sds = _sds
        rows = {n: sds((n,), jnp.int32, self._rows1d) for n in (n_u, n_i)}
        programs = [("seeded", self._seeded,
                     (sds((2,), jnp.uint32, self._replicated), rows[n_u], rows[n_i]),
                     (n_users, n_items, rank), dict(n_users=n_users, n_items=n_items, rank=rank))]
        programs += [("relayout", self._relayout,
                      (sds((n, rank), jnp.float32, self._rows2d), rows[n]), (n, rank), None)
                     for n in dict.fromkeys((n_u, n_i))]
        programs += self._local_programs(n_u, n_i, rank, item_shapes)
        programs += self._local_programs(n_i, n_u, rank, user_shapes)
        missing = [p for p in programs if (p[0],) + p[3] not in self._executables]

        def one(program) -> dict:
            kind, fn, args, shape_key, statics = program
            mine = {"compile_s": 0.0, "compile_sources": set()}   # a thread's own: no shared update
            _acquire_executable(self, fn, kind, args, mine, shape_key, statics, timer)
            return mine

        with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
            for mine in pool.map(one, missing):
                stats["compile_s"] += mine["compile_s"]          # thread-seconds
                stats["compile_sources"] |= mine["compile_sources"]
        self._acquired_layouts.add(layout)

    def seeded_tables(self, key, user_rows, item_rows, n_users: int, n_items: int,
                      rank: int, stats: dict):
        """The seeded first tables, made on the mesh under the row sharding,
        in the row order ``user_rows`` / ``item_rows`` (:meth:`put_rows` of a
        side's ``logical_of_phys``)."""
        args = (jax.device_put(key, self._replicated), user_rows, item_rows)
        return _acquire_executable(
            self, self._seeded, "seeded", args, stats, (n_users, n_items, rank),
            dict(n_users=n_users, n_items=n_items, rank=rank),
        )(*args)

    def relayout(self, table, rows, stats: dict):
        """``table[rows]``, row-sharded (:func:`make_relayout`)."""
        args = (table, rows)
        return _acquire_executable(
            self, self._relayout, "relayout", args, stats, tuple(table.shape),
        )(*args)

    def half_sweep_local(self, source, target, groups, landing, reg, alpha, stats, timer):
        """One half-sweep of the resident dataflow (see the section comment
        above): psum Gramian, ONE assembly of the source table, a program a
        shape group, one landing. ``groups`` are device shape groups
        (:meth:`put_group`), ``landing`` the target side's stacked per-shard
        landing permutations. ``stats["assembled_bytes"]`` grows by what the
        programs it calls all-gather (:func:`all_gather_bytes`, a call each)."""
        SHARD_GATHER_FAULT.hit()
        SHARD_COLLECTIVE_FAULT.hit()
        rank = int(source.shape[1])
        n_source, n_target = int(source.shape[0]), int(target.shape[0])
        with timer.section("fit.shard"):
            with timer.section("fit.shard.gramian"):
                yty = self._gramian(source)
            with timer.section("fit.shard.assemble"):
                key = (n_source, rank)
                table = _acquire_executable(
                    self, self._assemble, "assemble", (source,), stats, key,
                )(source)
                gathered = self._gathered[("assemble", *key)]
            with timer.section("fit.shard.dispatch"):
                solved = []
                for g in groups:
                    args = (table, yty, target, *g, reg, alpha)
                    key = (n_source, n_target, rank, tuple(g[1].shape))
                    solved.append(_acquire_executable(
                        self, self._local_solve, "local_solve", args, stats, key, self._statics(),
                    )(*args))
                    gathered += self._gathered[("local_solve", *key)]
                args = (target, landing, *solved)
                key = (n_target, rank, tuple(tuple(g[1].shape) for g in groups))
                target = _acquire_executable(
                    self, self._local_land, "local_land", args, stats, key,
                )(*args)
                gathered += self._gathered[("local_land", *key)]
        stats["assembled_bytes"] += gathered
        stats["dispatches"] += len(groups) + 3
        return target

    def fit_local(self, user_sh, item_sh, user_groups, item_groups, user_landing,
                  item_landing, reg: float, alpha: float, n_iter: int, stats: dict,
                  timer, after_sweep=None):
        """``n_iter`` sweeps of the resident dataflow over row-sharded tables
        (:meth:`seeded_tables` / :meth:`shard_table`) in the row order the
        groups were bucketed in; returns them as they are: row-sharded, rows
        padded to the shard count, in that order (:meth:`relayout` brings
        the logical order back). ``after_sweep(it, user_sh, item_sh)`` runs
        between sweeps."""
        reg_arr = jax.device_put(np.float32(reg), self._replicated)
        alpha_arr = jax.device_put(np.float32(alpha), self._replicated)
        for it in range(int(n_iter)):
            # MLlib order: item factors first (from users), then users.
            item_sh = self.half_sweep_local(
                user_sh, item_sh, item_groups, item_landing, reg_arr, alpha_arr, stats, timer)
            user_sh = self.half_sweep_local(
                item_sh, user_sh, user_groups, user_landing, reg_arr, alpha_arr, stats, timer)
            if after_sweep is not None:
                after_sweep(it, user_sh, item_sh)
        return user_sh, item_sh

    def half_sweep(self, source, target, buckets, reg, alpha, stats,
                   streamed=False, pipelined=False):
        """One sharded half-sweep: psum Gramian, then every bucket's gather
        -> solve -> scatter. ``buckets`` yields HOST buckets when
        ``streamed`` (uploaded one at a time, ``als.shard.stream`` firing
        per upload) and device buckets otherwise. ``pipelined`` runs the
        software-pipelined dataflow instead (:meth:`_half_sweep_pipelined`)."""
        SHARD_GATHER_FAULT.hit()
        SHARD_COLLECTIVE_FAULT.hit()
        yty = self._gramian(source)
        if pipelined:
            return self._half_sweep_pipelined(
                source, yty, target, buckets, reg, alpha, stats, streamed
            )
        for b in buckets:
            if streamed:
                SHARD_STREAM_FAULT.hit()
                t0 = time.perf_counter()
                b = self.put_bucket(b)  # async dispatch; overlaps the solves
                stats["upload_s"] += time.perf_counter() - t0
                stats["streamed_buckets"] += 1
            target = self._run_bucket(source, yty, target, b, reg, alpha, stats)
        return target

    def _half_sweep_pipelined(
        self, source, yty, target, buckets, reg, alpha, stats, streamed
    ):
        """The pipelined driver loop (ARCHITECTURE.md "Pipelined sharded
        dataflow"): when ``streamed``, a background prefetcher uploads
        bucket i+1 while bucket i's solve is dispatched; every bucket after
        the first lands the PREVIOUS bucket's solved block inside its own
        solve dispatch (fused landing scatter, overlapped ring phases), and
        a final flush lands the last pending block."""
        pending = None  # (row_ids, solved_l) awaiting landing

        def run(device_buckets):
            nonlocal target, pending
            for b in device_buckets:
                stats["dispatches"] += 1
                if pending is None:
                    args = (source, yty, target, b.row_ids, b.idx, b.val,
                            b.mask, reg, alpha)
                    key = (source.shape[0], target.shape[0], tuple(b.idx.shape))
                    solved = _acquire_executable(
                        self, self._solve, "solve", args, stats, key, self._statics()
                    )(*args)
                else:
                    prev_rows, prev_solved = pending
                    args = (source, yty, target, prev_rows, prev_solved,
                            b.row_ids, b.idx, b.val, b.mask, reg, alpha)
                    key = (
                        source.shape[0], target.shape[0],
                        tuple(b.idx.shape), int(prev_rows.shape[0]),
                    )
                    target, solved = _acquire_executable(
                        self, self._landsolve, "landsolve", args, stats, key, self._statics()
                    )(*args)
                pending = (b.row_ids, solved)

        if streamed:
            from albedo_tpu.parallel.elastic import collective_deadline_s

            with _BucketPrefetcher(
                self, buckets, stats, collective_deadline_s()
            ) as prefetched:
                run(prefetched)
        else:
            run(buckets)
        if pending is not None:
            stats["dispatches"] += 1
            rows, solved = pending
            args = (target, rows, solved)
            key = (target.shape[0], int(rows.shape[0]))
            target = _acquire_executable(
                self, self._flush, "flush", args, stats, key
            )(*args)
        return target

    def fit(
        self,
        user_f,
        item_f,
        user_buckets,
        item_buckets,
        reg: float,
        alpha: float,
        n_iter: int,
        streamed: bool = False,
        callback=None,
        pipelined: bool = True,
    ) -> tuple[jax.Array, jax.Array, dict]:
        """Run ``n_iter`` full sweeps; returns ``(user_f, item_f, stats)``
        with the factor tables trimmed back to their unpadded row counts.

        ``user_buckets`` / ``item_buckets`` are lists of host buckets, or
        zero-arg callables returning a fresh iterable per half-sweep — the
        disk-backed scale harness streams each half-sweep's buckets from
        spill files through such a provider without ever holding the whole
        side in memory.

        ``pipelined`` (the default) runs the
        pipelined dataflow — double-buffered bucket prefetch when
        ``streamed``, overlapped ring phases, fused landing scatter —
        numerically identical to the synchronous path (parity-pinned at
        1e-5 in ``tests/test_sharded_als.py``); ``False`` is the
        synchronous A/B and triage path.
        """
        pipelined = bool(pipelined)
        n_users, n_items = int(user_f.shape[0]), int(item_f.shape[0])
        u_provider = user_buckets if callable(user_buckets) else (lambda: user_buckets)
        i_provider = item_buckets if callable(item_buckets) else (lambda: item_buckets)

        stats = {
            "compile_s": 0.0, "compile_sources": set(),
            "streamed_buckets": 0, "upload_s": 0.0,
            "prefetch_wait_s": 0.0, "pipelined": pipelined, "dispatches": 0,
        }
        user_sh = self.shard_table(user_f)
        item_sh = self.shard_table(item_f)
        if not streamed:
            t0 = time.perf_counter()
            user_dev = [self.put_bucket(b) for b in u_provider()]
            item_dev = [self.put_bucket(b) for b in i_provider()]
            stats["upload_s"] = round(time.perf_counter() - t0, 4)
        reg_arr = jnp.float32(reg)
        alpha_arr = jnp.float32(alpha)

        for it in range(int(n_iter)):
            # MLlib order: item factors first (from users), then users.
            item_sh = self.half_sweep(
                user_sh, item_sh,
                i_provider() if streamed else item_dev,
                reg_arr, alpha_arr, stats, streamed=streamed,
                pipelined=pipelined,
            )
            user_sh = self.half_sweep(
                item_sh, user_sh,
                u_provider() if streamed else user_dev,
                reg_arr, alpha_arr, stats, streamed=streamed,
                pipelined=pipelined,
            )
            if callback is not None:
                callback(
                    it,
                    # Checkpoint-callback host copies, by contract (the
                    # chunked refit journals exactly these per boundary).
                    np.asarray(user_sh)[:n_users],   # albedo: noqa[hidden-host-sync]
                    np.asarray(item_sh)[:n_items],   # albedo: noqa[hidden-host-sync]
                )
        stats["upload_s"] = round(stats["upload_s"], 4)
        stats["prefetch_wait_s"] = round(stats["prefetch_wait_s"], 4)
        stats["n_shapes"] = len(self._executables)
        return user_sh[:n_users], item_sh[:n_items], stats


class ShardedALSSweep:
    """Stateful wrapper: pre-pads buckets for a mesh and runs half-sweeps.

    The EXPLICIT shard_map variant of the sharded sweep, kept as the
    spelled-out-collectives reference implementation (and covered by its own
    parity test). ``ImplicitALS.fit`` itself now runs the fused single-dispatch
    path with batch-axis-sharded bucket groups, letting XLA's SPMD partitioner
    insert the equivalent collectives (``models/als.py device_groups``); both
    share the per-bucket math in ``ops.als.solve_rows``.
    """

    def __init__(self, mesh: Mesh, axis: str = DATA_AXIS):
        self.mesh = mesh
        self.axis = axis
        self._solver = make_sharded_solver(mesh, axis)
        self._n = mesh.shape[axis]

    def prepare(self, buckets: list[Bucket]) -> list[Bucket]:
        """Pad to the shard count and upload once, already laid out row-sharded
        over the mesh (no per-iteration transfer or reshard)."""
        rows = row_sharded(self.mesh, self.axis)
        return [device_bucket(pad_bucket(b, self._n), rows) for b in buckets]

    def half_sweep(self, source, target, buckets, reg, alpha):
        yty = source.T @ source
        reg_arr = jnp.float32(reg)
        alpha_arr = jnp.float32(alpha)
        for b in buckets:
            target = self._solver(
                source, yty, target,
                jnp.asarray(b.row_ids), jnp.asarray(b.idx),
                jnp.asarray(b.val), jnp.asarray(b.mask),
                reg_arr, alpha_arr,
            )
        return target
