"""Mesh-resident fold-in: the streaming solve sharded over a device mesh.

`streaming.foldin.FoldInEngine` solves touched user rows against a frozen
item table that is fully resident on ONE device — fine at smoke scale,
impossible on any catalog that needs the mesh (ROADMAP item 2: at the
out-of-core 10M x 1M parameterization the item side alone busts a single
device). This module is the mesh citizen of that solve: the frozen item
factors live ROW-SHARDED over the mesh (the ALX posture, arXiv:2112.02194),
their Gramian is the one-psum `sharded_gramian`, and each fold-in batch is
routed so every touched user lands on the device that owns their row shard
and is solved there against ring-passed or all-gathered item shards with
the SAME `bucket_partial_terms`/`solve_corrected` kernels the training
sweep uses (arXiv:1508.03110 composed with PR 8's ring factoring) — no
full item table ever resident on one device in ring mode.

Contracts carried over from the single-device engine, unchanged:

- **pow2 shape ladder through the persistent AOT layer** — the slab is
  ``n_shards * pow2(max per-shard users) x pow2(row length)``, each shape
  compiled once via `persistent_aot_executable` and the handle held;
  regularization and alpha stay traced so the damped watchdog re-solve
  reuses the same executable.
- **The health read is the completion barrier** — each shard reduces its
  solved block to `utils.watchdog.factor_health` partials which are
  psum/pmax'd into ONE replicated (3,) vector inside the same program; its
  single d2h read synchronizes every shard with zero added round-trips
  (bit-identical semantics to `factor_health(solved, solved)` on the
  assembled block).
- **Deadline-guarded collectives** — every dispatch (solve + health read)
  runs under `parallel.elastic.run_with_deadline`, so a dead shard
  surfaces as the same loss-shaped `CollectiveTimeout` the elastic fit
  classifies, never a hang. The streaming cycle (streaming/job.py) drains
  to its last sealed publish, remeshes down the ladder and re-solves.

The `stream.foldin.collective` fault site fires at the head of every
sharded batch dispatch: its `loss` kind raises the device-loss-shaped
error a dead shard surfaces as, which is how the chaos drill kills a
device mid-cycle and pins the 8 -> 4 remesh with fold-in parity.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from albedo_tpu.ops.als import bucket_solve_body
from albedo_tpu.parallel.als import _ring_solve, sharded_gramian
from albedo_tpu.parallel.mesh import DATA_AXIS, pad_rows_to
from albedo_tpu.utils import faults
from albedo_tpu.utils import pow2_at_least as _pow2

log = logging.getLogger(__name__)

# Chaos hook for the mesh-resident fold-in: fires at the head of every
# sharded batch dispatch (the all-gather / ring phases plus the fused
# health psum follow it). The `loss` kind raises the device-loss-shaped
# error a dead shard surfaces as — `utils.retry.is_collective_lost`
# classifies it and the streaming cycle's elastic path (streaming/job.py)
# drains to the last sealed publish, remeshes down the ladder, and
# re-solves the interrupted batch on the smaller rung.
FOLDIN_COLLECTIVE_FAULT = faults.site("stream.foldin.collective")


def _foldin_body(vf_l, yty, idx_l, val_l, mask_l, reg, alpha,
                 *, axis, n_shards, mode):
    """Per-shard fold-in solve + fused health partials.

    ``mode="ring"``: the item shard is ppermute'd around the ring and each
    phase accumulates the normal-equation terms for entries whose global
    item index falls in the visiting shard — `parallel.als._ring_solve`,
    the training sweep's own math, so fold-in/refit parity stays a theorem
    on the mesh too. ``mode="allgather"``: assemble the padded item table
    transient per batch and run `bucket_solve_body` directly (cheaper in
    collectives, priced higher in transient bytes by `plan_foldin`).

    The health tail is `utils.watchdog.factor_health(solved, solved)`
    decomposed into per-shard partials: nonfinite counts and sum-of-squares
    psum, max-abs pmax, finished into the same `[nonfinite, max_abs, rms]`
    layout — replicated, so the caller's single d2h read of the (3,)
    vector is the completion barrier across EVERY shard.
    """
    if mode == "ring":
        solved_l = _ring_solve(
            vf_l, yty, idx_l, val_l, mask_l, reg, alpha,
            axis=axis, n_shards=n_shards, gather_dtype=None, overlapped=True,
        )
    else:
        vf = jax.lax.all_gather(vf_l, axis, axis=0, tiled=True)
        solved_l = bucket_solve_body(vf, yty, idx_l, val_l, mask_l, reg, alpha)
    finite = jnp.isfinite(solved_l)
    safe = jnp.where(finite, solved_l, 0.0)
    nonfinite = jax.lax.psum(
        (solved_l.size - finite.sum()).astype(jnp.float32), axis
    )
    max_abs = jax.lax.pmax(jnp.max(jnp.abs(safe)), axis)
    sumsq = jax.lax.psum(jnp.sum(safe * safe), axis)
    rms = jnp.sqrt(sumsq / float(solved_l.size * n_shards))
    # factor_health(x, x) counts both "tables", hence the doubled count.
    health = jnp.stack([2.0 * nonfinite, max_abs, rms])
    return solved_l, health


def make_sharded_foldin(mesh: Mesh, axis: str = DATA_AXIS, mode: str = "allgather"):
    """Jitted sharded fold-in program: row-sharded item factors +
    replicated Gramian + batch-sharded user slab in, batch-sharded solved
    rows + replicated health vector out. Slab batch dims must be
    shard-count multiples (`ShardedFoldIn.build_slab` guarantees it)."""
    n_shards = mesh.shape[axis]

    def solve(vf, yty, idx, val, mask, reg, alpha):
        body = functools.partial(
            _foldin_body, axis=axis, n_shards=n_shards, mode=mode
        )
        f = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(axis, None), P(), P(axis, None), P(axis, None),
                P(axis, None), P(), P(),
            ),
            out_specs=(P(axis, None), P()),
        )
        return f(vf, yty, idx, val, mask, reg, alpha)

    return jax.jit(solve)


def _acquire_foldin_executable(engine: "ShardedFoldIn", fn, args, shape_key: tuple):
    """Per-shape executable through the persistent AOT layer, memoized on
    the engine. A module-level conduit (forwards ``fn`` into
    ``persistent_aot_executable``) so graftlint R1 can prove the sharded
    fold-in program reaches the AOT layer — same discipline as
    `parallel.als._acquire_executable`."""
    from albedo_tpu.utils.aot import persistent_aot_executable

    compiled = engine._executables.get(shape_key)
    if compiled is None:
        compiled, compile_s, source = persistent_aot_executable(
            fn, args, None, None,
            key_parts=(
                "stream_foldin_sharded", engine.n_shards,
                engine.rank, engine.padded_items, jax.__version__,
                jax.default_backend(), repr(engine.mesh),
            ) + shape_key,
            name="stream_foldin_sharded",
        )
        engine._executables[shape_key] = compiled
        engine.compile_s += compile_s
        if source != "memory":
            log.info(
                "sharded fold-in shape %s ready on %d shards (%s, %.2fs)",
                shape_key, engine.n_shards, source, compile_s,
            )
    return compiled


class ShardedFoldIn:
    """Holds the frozen item side row-sharded over the mesh and solves
    owner-routed fold-in slabs against it.

    The single-device `FoldInEngine` owns the stream-facing contract
    (admission, watchdog remediation, bank publish); this class is its
    mesh substrate: shard layout, routing geometry, the shard_map'd solve,
    and the deadline guard. ``n_users`` (the user table's row count) fixes
    the routing geometry — the same ``ceil(n/n_shards)`` row blocks
    `pad_rows_to` + `P(axis, None)` give every sharded table, so a folded
    row is solved on the device whose user shard (and whose slice of the
    sharded retrieval bank) will hold it.
    """

    def __init__(
        self,
        mesh: Mesh,
        item_factors,
        *,
        axis: str = DATA_AXIS,
        mode: str = "allgather",
        n_users: int = 0,
    ):
        self.mesh = mesh
        self.axis = axis
        self.mode = str(mode)
        self.n_shards = int(mesh.shape[axis])
        f = np.asarray(item_factors, dtype=np.float32)
        self.rank = int(f.shape[1])
        self.n_items = int(f.shape[0])
        f = pad_rows_to(f, self.n_shards)
        self.padded_items = int(f.shape[0])
        # Row-sharded frozen item side: each device holds 1/n of the padded
        # table; the Gramian is the one-psum sharded reduction, replicated.
        self._vf = jax.device_put(f, NamedSharding(mesh, P(axis, None)))
        self._yty = sharded_gramian(mesh, axis)(self._vf)
        # Both assembly programs up front (building the jit closure traces
        # nothing): the admission ladder picks per batch, so an over-budget
        # all-gather transient degrades to ring without rebuilding the
        # engine or re-uploading the item side.
        self._solve_allgather = make_sharded_foldin(mesh, axis, "allgather")
        self._solve_ring = make_sharded_foldin(mesh, axis, "ring")
        self._executables: dict[tuple, object] = {}
        self.n_users = int(n_users)
        self.compile_s = 0.0
        self.dispatches = 0

    # ------------------------------------------------------------- routing

    def owners(self, user_idx) -> np.ndarray:
        """Owner shard per touched user under the row-sharded user-table
        layout (``rows_per = ceil(n_users / n_shards)`` blocks). Without a
        known user-table size (or without addresses at all) routing falls
        back to round-robin — the per-row solves are independent, so
        placement changes no value, only locality."""
        u = np.asarray(user_idx, dtype=np.int64)
        if self.n_users <= 0:
            return u % self.n_shards
        rows_per = -(-self.n_users // self.n_shards)
        return np.minimum(u // rows_per, self.n_shards - 1)

    def build_slab(self, chunk, owners=None):
        """Owner-routed padded slab for one chunk of ``(item_idx,
        confidence)`` rows: user j of owner shard d lands in slice d's rows
        so shard_map's ``P(axis)`` split hands it to its owning device.
        Returns ``(idx, val, mask, pos)`` where ``pos[j]`` is row j's slab
        slot (un-permute the solved block with ``solved[pos]``)."""
        n = self.n_shards
        if owners is None:
            owners = np.arange(len(chunk), dtype=np.int64) % n
        counts = np.bincount(owners, minlength=n)
        b_per = _pow2(max(1, int(counts.max())))
        bucket = n * b_per
        length = _pow2(max(int(ri.size) for ri, _ in chunk))
        idx = np.zeros((bucket, length), dtype=np.int32)
        val = np.zeros((bucket, length), dtype=np.float32)
        mask = np.zeros((bucket, length), dtype=bool)
        pos = np.empty(len(chunk), dtype=np.int64)
        cursor = np.zeros(n, dtype=np.int64)
        for j, (ri, rv) in enumerate(chunk):
            d = int(owners[j])
            r = d * b_per + int(cursor[d])
            cursor[d] += 1
            pos[j] = r
            k = int(ri.size)
            idx[r, :k] = ri
            val[r, :k] = rv
            mask[r, :k] = True
        return idx, val, mask, pos

    # --------------------------------------------------------------- solve

    def warm(self, bucket: int, length: int, mode: str | None = None) -> None:
        args = (
            self._vf, self._yty,
            np.zeros((bucket, length), dtype=np.int32),
            np.zeros((bucket, length), dtype=np.float32),
            np.zeros((bucket, length), dtype=bool),
            jnp.float32(0.1), jnp.float32(1.0),
        )
        mode = self.mode if mode is None else str(mode)
        if mode == "ring":
            _acquire_foldin_executable(
                self, self._solve_ring, args, ("ring", bucket, length)
            )
        else:
            _acquire_foldin_executable(
                self, self._solve_allgather, args, ("allgather", bucket, length)
            )

    def solve(self, idx, val, mask, reg: float, alpha: float,
              mode: str | None = None):
        """Dispatch one padded slab; returns ``(solved, health)`` as host
        arrays. The replicated health vector's d2h read is the completion
        barrier across every shard, and the whole dispatch runs under the
        collective deadline so a dead shard raises loss-shaped instead of
        hanging the stream."""
        from albedo_tpu.parallel.elastic import (
            collective_deadline_s,
            run_with_deadline,
        )

        FOLDIN_COLLECTIVE_FAULT.hit()
        mode = self.mode if mode is None else str(mode)
        bucket, length = int(idx.shape[0]), int(idx.shape[1])
        args = (
            self._vf, self._yty, idx, val, mask,
            jnp.float32(reg), jnp.float32(alpha),
        )
        if mode == "ring":
            compiled = _acquire_foldin_executable(
                self, self._solve_ring, args, ("ring", bucket, length)
            )
        else:
            compiled = _acquire_foldin_executable(
                self, self._solve_allgather, args, ("allgather", bucket, length)
            )

        def dispatch():
            solved_dev, health_dev = compiled(*args)
            # Reading the replicated (3,) health synchronizes every shard;
            # the solved block copy rides the same barrier.
            health = np.asarray(health_dev, dtype=np.float32)
            return np.asarray(solved_dev, dtype=np.float32), health

        solved, health = run_with_deadline(
            dispatch, collective_deadline_s(),
            f"sharded fold-in batch {bucket}x{length} "
            f"({mode}, {self.n_shards} shards)",
        )
        self.dispatches += 1
        return solved, health
