"""The mesh-shardable embedding bank: registration, build, blocked MIPS query.

The reference's candidate generation is a fan-out: ALS block cross-join,
an external Elasticsearch More-Like-This query, curated/popular SQL views —
each a host thread with its own deadline (``serving/pipeline.py``). Every
embedding-backed source among them is the same computation wearing a
different costume: score a query vector against a row table, keep the
top-k. At albedo scale (~1M repos x rank <= 256) that is ONE bandwidth-bound
GEMM per batch — well within the measured 285 GB/s roofline — so the bank
collapses them into one device-resident table set served by a single fused
gather -> blocked GEMM -> top-k executable per batch shape.

**Sources.** A :class:`BankSourceSpec` registers one source:

- ``kind="user_rows"``: the query vector is a row of a user table aligned
  with the serving matrix's dense user indices (ALS user factors; or the
  user table itself scored against the user table — user-to-user
  similarity).
- ``kind="item_mean"``: the query vector is the L2-normalized mean of
  example rows of the source's OWN table (content/tfidf More-Like-This:
  query by the user's recently starred repos; the query rows themselves
  are excluded from the results, matching ES MLT semantics).

**Build.** ``build()`` is the versioned step: capacity admission
(``utils.capacity.plan_retrieval`` — resident generations are priced before
any byte moves), device upload (single device) or row padding for the mesh
layout (the ALX row-sharded serving layout from PR 8), per-source row-norm /
score **calibration** (a deterministic probe records the scale that maps
each source's raw top-1 scores onto ~1.0, so heterogeneous sources can fuse
on one scale; queries return RAW scores — calibration is metadata applied
only where a caller asks, which is what keeps bank-vs-host parity exact),
and a content-hash ``version``. ``save()`` seals the build like every other
artifact: pickle + ``.meta.json`` stamp (sources, calibration, lineage) +
the ``.sha256`` manifest written LAST.

**Query.** Single device: one fused executable per (batch bucket, k,
source-mask, query-width, exclusion-mode) shape, acquired through
``utils.aot.persistent_aot_executable`` and held — the hot path is
``compiled(tables, user_idx, q_idx, excl)`` with no tracing. Seen-item
exclusion gathers rows from the SAME device-resident ``-1``-padded
exclusion table the serving micro-batcher uploads (sources whose row space
differs from the matrix item space carry a device remap table). Mesh: each
source's table is row-sharded over the ``item`` axis and served by the
``parallel/topk.py`` per-shard top-k + k-per-device all-gather merge, now
routed through the persistent AOT layer.

**Overlay.** ``publish_user_rows`` lands freshly folded-in user rows
(``streaming/foldin.py``) into a ``user_rows`` source's table — the bank is
the natural overlay target for the minutes-stale loop: the next query batch
reads the new rows because tables are call-time arguments, not baked-in
constants.

Fault sites: ``retrieval.build`` (head of the build step) and
``retrieval.query`` (head of every query batch) — catalogued in
ARCHITECTURE.md; queries are counted per source in
``albedo_retrieval_queries_total{source=}``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import threading
import time
from typing import Callable

import numpy as np

from albedo_tpu.analysis.locksmith import named_lock
from albedo_tpu.utils import events, faults
from albedo_tpu.utils import pow2_at_least as _pow2

log = logging.getLogger(__name__)

BUILD_FAULT = faults.site("retrieval.build")
QUERY_FAULT = faults.site("retrieval.query")

KINDS = ("user_rows", "item_mean")

# Floor of the item_mean query-width ladder. Every More-Like-This provider in
# the tree answers with at most top_k = 30 recent items
# (``recommenders.base.recent_starred_provider``), so with the floor at
# pow2(30) every request-sized query pads to ONE width and the request path
# runs ONE fused program per source set — instead of compiling a new one for
# each (history length, history length) combination it meets, seconds apiece
# on a chip and each a ``bank_timeout``. Longer queries still ladder up.
_MIN_QUERY_WIDTH = 32


def bank_artifact_name(tag: str) -> str:
    """The bank artifact naming convention (one definition: build job,
    serve wiring, and the reload watcher glob all agree)."""
    return f"{tag}-retrievalBank-v1.pkl"


@dataclasses.dataclass
class BankSourceSpec:
    """One embedding source's registration.

    ``vectors`` is the scored table — (N, d) float32 host rows whose raw ids
    are ``item_ids``. ``user_vectors`` (``user_rows`` kind) is the query
    table, row-aligned with the serving matrix's dense user indices.
    ``query_items`` (``item_mean`` kind) maps a raw user id to the raw item
    ids whose rows form the query (e.g. the user's most recent stars); a
    spec without one uses the stage's shared provider. ``exclude_seen``
    opts the source into the shared seen-item exclusion table (meaningful
    for ``user_rows`` sources whose candidates are catalog items).
    ``owner`` keys shared device residency (``utils.devcache``) so a bank
    build and the host fallback path hold ONE device copy of the table.
    """

    name: str
    kind: str
    vectors: np.ndarray
    item_ids: np.ndarray
    user_vectors: np.ndarray | None = None
    query_items: Callable[[int], np.ndarray] | None = None
    exclude_seen: bool = False
    owner: object | None = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown bank source kind {self.kind!r} (not in {KINDS})")
        self.vectors = np.asarray(self.vectors, dtype=np.float32)
        self.item_ids = np.asarray(self.item_ids, dtype=np.int64)
        if self.vectors.ndim != 2 or self.vectors.shape[0] != self.item_ids.shape[0]:
            raise ValueError(
                f"source {self.name!r}: vectors {self.vectors.shape} do not "
                f"row-align with item_ids {self.item_ids.shape}"
            )
        if self.kind == "user_rows":
            if self.user_vectors is None:
                raise ValueError(f"user_rows source {self.name!r} needs user_vectors")
            self.user_vectors = np.asarray(self.user_vectors, dtype=np.float32)
            if self.user_vectors.shape[1] != self.vectors.shape[1]:
                raise ValueError(
                    f"source {self.name!r}: user rank {self.user_vectors.shape[1]} "
                    f"!= item rank {self.vectors.shape[1]}"
                )


def _calibration(spec: BankSourceSpec, probe_rows: int = 32) -> dict:
    """Deterministic per-source score calibration, recorded at build time.

    Probes the first ``probe_rows`` query vectors (user rows, or the
    source's own normalized rows for item_mean) against the full table and
    records ``scale`` = 1 / median top-1 score — multiplying a source's raw
    scores by its scale puts every source's best-match at ~1.0, one shared
    scale for cross-source fusion. Row-norm stats ride along so an operator
    inspecting a stamp can see WHY a scale is what it is. Pure f32 host
    arithmetic on a bounded probe: build-time cost, not query-time.
    """
    vf = spec.vectors
    norms = np.linalg.norm(vf, axis=1)
    if spec.kind == "user_rows":
        q = spec.user_vectors[: min(probe_rows, spec.user_vectors.shape[0])]
    else:
        q = vf[: min(probe_rows, vf.shape[0])]
        qn = np.linalg.norm(q, axis=1, keepdims=True)
        q = np.where(qn > 0, q / np.maximum(qn, 1e-9), 0.0)
    if q.shape[0] == 0 or vf.shape[0] == 0:
        scale = 1.0
    else:
        top1 = np.abs((q @ vf.T).max(axis=1))
        med = float(np.median(top1))
        scale = 1.0 / med if med > 1e-9 else 1.0
    return {
        "scale": round(float(scale), 8),
        "probe_rows": int(q.shape[0]),
        "row_norm_mean": round(float(norms.mean()) if norms.size else 0.0, 8),
        "row_norm_max": round(float(norms.max()) if norms.size else 0.0, 8),
    }


def mean_query_vectors(
    vectors: np.ndarray, q_mat: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side item_mean query assembly: masked mean of the query rows,
    L2-normalized; returns ``(queries (B, d) f32, has_query (B,) bool)``.

    ONE definition for every host-assembled path (the mesh query, similar-
    by-example on a mesh) — it must stay in lockstep with the device
    program's inlined copy in :func:`_make_query_program` AND with the host
    recommenders (``tfidf.similar_to_repos``/``content.more_like_this``):
    the candidate-parity contract is pinned against all of them."""
    valid = q_mat >= 0
    rows = vectors[np.clip(q_mat, 0, None)]
    w = valid.astype(np.float32)
    qv = (rows * w[..., None]).sum(axis=1)
    qv /= np.maximum(w.sum(axis=1, keepdims=True), 1.0)
    qv /= np.maximum(np.linalg.norm(qv, axis=1, keepdims=True), 1e-9)
    return qv.astype(np.float32), valid.any(axis=1)


def _make_query_program(
    kinds: tuple[str, ...],
    k_each: tuple[int, ...],
    use_excl: tuple[bool, ...],
    remap: tuple[bool, ...],
    k: int,
    item_block: int,
):
    """Build the fused all-sources query program for one static layout.

    One jitted function = one device dispatch per batch, whatever the
    source mask: per source, gather the query vectors (user-table rows, or
    the masked mean of example rows), run the blocked MIPS top-k
    (``ops.topk.topk_scores`` — the same streaming-merge kernel the
    micro-batcher serves ALS with), and pad every source's output to a
    uniform (B, k). The jitted callable is acquired exclusively through
    ``utils.aot.persistent_aot_executable`` (see ``RetrievalBank._executable``).
    """
    import jax
    import jax.numpy as jnp

    from albedo_tpu.ops.topk import topk_scores

    neg_inf = float("-inf")

    def run(tables, user_idx, q_idxs, excl_all):
        outs = []
        for i, kind in enumerate(kinds):
            tab = tables[i]
            if kind == "user_rows":
                uf, vf = tab[0], tab[1]
                qv = jnp.take(uf, user_idx, axis=0)
                e = None
                if use_excl[i]:
                    e = jnp.take(excl_all, user_idx, axis=0)
                    if remap[i]:
                        excl_map = tab[2]
                        e = jnp.where(
                            e < 0, -1, jnp.take(excl_map, jnp.clip(e, 0))
                        )
                vals, idx = topk_scores(
                    qv, vf, k=k_each[i], exclude_idx=e, item_block=item_block
                )
            else:
                vf = tab[0]
                q_idx = q_idxs[i]
                valid = q_idx >= 0
                rows = jnp.take(vf, jnp.clip(q_idx, 0), axis=0)   # (B, Q, d)
                w = valid.astype(vf.dtype)
                qv = (rows * w[..., None]).sum(axis=1)
                qv = qv / jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)
                qv = qv / jnp.maximum(
                    jnp.linalg.norm(qv, axis=1, keepdims=True), 1e-9
                )
                # The query rows themselves are excluded (ES MLT semantics:
                # "more like this", never "this").
                vals, idx = topk_scores(
                    qv, vf, k=k_each[i], exclude_idx=q_idx, item_block=item_block
                )
                has_q = valid.any(axis=1)
                vals = jnp.where(has_q[:, None], vals, neg_inf)
                idx = jnp.where(has_q[:, None], idx, -1)
            if k_each[i] < k:
                pad = k - k_each[i]
                vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=neg_inf)
                idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
            outs.append((vals, idx))
        return tuple(outs)

    return jax.jit(run)


class RetrievalBank:
    """Registered embedding sources, one device-resident bank, one query path.

    Lifecycle: ``register_source()`` (host arrays) -> ``build()`` (capacity
    admission, device upload / mesh layout, calibration, version stamp) ->
    ``query()`` / ``query_similar()`` / ``publish_user_rows()``. ``save()``
    persists the build; ``RetrievalBank.load()`` restores it (un-built —
    the loading process runs its own admission and upload). ``reshard()``
    re-lays a built bank onto a different mesh — the degraded-ladder rung a
    device loss leaves serving — with version/calibration/overlay intact
    (ARCHITECTURE.md "Elastic operation").
    """

    def __init__(self, item_block: int = 4096, max_batch: int = 64):
        self.item_block = int(item_block)
        self.max_batch = max(1, _pow2(int(max_batch)))
        self.specs: dict[str, BankSourceSpec] = {}
        self.calibration: dict[str, dict] = {}
        self.version: str | None = None
        self.built_at: float = 0.0
        self.overlay_generation = 0
        self.mesh = None
        self._built = False
        # Device state (single-device build): per-source tables + remaps.
        self._vf: dict[str, object] = {}
        self._uf: dict[str, object] = {}
        self._excl_map: dict[str, object] = {}
        self._rowmap: dict[str, dict[int, int]] = {}
        self._excl_map_np: dict[str, np.ndarray] = {}
        self._excl_np: np.ndarray | None = None
        self._excl_dev = None
        self._executables: dict[tuple, object] = {}
        self._exec_lock = named_lock("retrieval.bank.exec")
        self._overlay_owned: set[str] = set()
        self.admission = None

    # ------------------------------------------------------------ registration

    @property
    def source_names(self) -> tuple[str, ...]:
        return tuple(self.specs)

    def register(self, spec: BankSourceSpec) -> None:
        if self._built:
            raise RuntimeError(
                "bank already built — register sources first, then build(); "
                "a new source set is a new bank generation"
            )
        if spec.name in self.specs:
            raise ValueError(f"source {spec.name!r} already registered")
        self.specs[spec.name] = spec

    def register_source(self, name: str, **kwargs) -> None:
        self.register(BankSourceSpec(name=name, **kwargs))

    # ------------------------------------------------------------------- build

    def build(
        self,
        matrix=None,
        exclude_table: np.ndarray | None = None,
        mesh=None,
        budget: int | None = None,
        generations: int = 1,
    ) -> "RetrievalBank":
        """The versioned build step: admission -> upload -> calibration.

        ``matrix`` (the serving :class:`StarMatrix`) enables seen-item
        exclusion remaps for sources whose row space is not the matrix item
        space; ``exclude_table`` is the micro-batcher's device-resident
        ``-1``-padded seen-item table, reused verbatim. ``mesh`` selects the
        row-sharded layout served by ``parallel/topk.py``. A build that
        cannot fit ``generations`` resident copies raises
        :class:`~albedo_tpu.utils.capacity.CapacityExceeded` (the refusal is
        recorded; the host fan-out keeps serving).
        """
        from albedo_tpu.utils import capacity

        if not self.specs:
            raise ValueError("no sources registered")
        BUILD_FAULT.hit()
        t0 = time.perf_counter()
        verdict = capacity.admit(
            self._retrieval_plan(
                mesh,
                excl_entries=int(exclude_table.size) if exclude_table is not None else 0,
                generations=generations,
            ),
            degradable=False, budget=budget,
        )
        self.admission = verdict
        if verdict.verdict == "refuse":
            raise capacity.CapacityExceeded(verdict)

        matrix_item_ids = None if matrix is None else np.asarray(matrix.item_ids)
        for name in sorted(self.specs):
            spec = self.specs[name]
            self._rowmap[name] = {int(i): r for r, i in enumerate(spec.item_ids)}
            self.calibration[name] = _calibration(spec)
            # Seen-item exclusion remap: matrix dense item index -> source
            # row, -1 where the source does not carry the item. Identity
            # (the ALS case: source rows ARE the matrix item space) skips
            # the gather entirely.
            excl_map = None
            if (
                spec.kind == "user_rows"
                and spec.exclude_seen
                and matrix_item_ids is not None
                and not np.array_equal(spec.item_ids, matrix_item_ids)
            ):
                pos = np.searchsorted(spec.item_ids, matrix_item_ids)
                pos_c = np.clip(pos, 0, max(0, len(spec.item_ids) - 1))
                hit = (
                    (pos < len(spec.item_ids))
                    & (spec.item_ids[pos_c] == matrix_item_ids)
                )
                excl_map = np.where(hit, pos_c, -1).astype(np.int32)
                if not np.all(np.diff(spec.item_ids) > 0):
                    # searchsorted needs sorted ids; fall back to a dict map.
                    excl_map = np.array(
                        [self._rowmap[name].get(int(i), -1) for i in matrix_item_ids],
                        dtype=np.int32,
                    )
            if excl_map is not None:
                self._excl_map_np[name] = excl_map
        if exclude_table is not None:
            self._excl_np = np.asarray(exclude_table, dtype=np.int32)
        self._upload(mesh)
        self.version = self._content_hash()
        self.built_at = time.time()
        self._built = True
        log.info(
            "retrieval bank built: %d source(s), version %s, %.2fs%s",
            len(self.specs), self.version, time.perf_counter() - t0,
            f", mesh {dict(mesh.shape)}" if mesh is not None else "",
        )
        return self

    def _retrieval_plan(self, mesh, excl_entries: int, generations: int):
        """The bank's capacity plan for a given layout — PER DEVICE when a
        mesh is given (tables row-shard over the item axis)."""
        from albedo_tpu.parallel.mesh import ITEM_AXIS
        from albedo_tpu.utils import capacity

        return capacity.plan_retrieval(
            [
                shape
                for s in self.specs.values()
                for shape in (
                    [s.vectors.shape]
                    + ([s.user_vectors.shape] if s.user_vectors is not None else [])
                )
            ],
            excl_entries=excl_entries,
            generations=generations,
            max_batch=self.max_batch,
            item_block=self.item_block,
            n_devices=1 if mesh is None else int(mesh.shape[ITEM_AXIS]),
        )

    def _upload(self, mesh) -> None:
        """Device placement for the registered tables on ``mesh`` (or the
        single default device when None) — the mesh-dependent tail of
        ``build()``, shared with :meth:`reshard` so a built bank can re-lay
        itself onto whatever mesh the degraded ladder gives. Clears any
        previous layout's device state and shape-keyed executables (new
        padded shapes = new programs); host-side products (row maps,
        calibration, exclusion remaps) are layout-independent and kept."""
        import jax.numpy as jnp

        from albedo_tpu.utils.devcache import device_put_cached

        self.mesh = mesh
        self._vf.clear()
        self._uf.clear()
        self._excl_map.clear()
        self._excl_dev = None
        self._executables.clear()
        for name in sorted(self.specs):
            spec = self.specs[name]
            excl_map = self._excl_map_np.get(name)
            owner = spec.owner if spec.owner is not None else spec
            if mesh is None:
                self._vf[name] = device_put_cached(owner, spec.vectors)
                if spec.user_vectors is not None:
                    self._uf[name] = jnp.asarray(spec.user_vectors)
                if excl_map is not None:
                    self._excl_map[name] = jnp.asarray(excl_map)
            else:
                # Mesh layout: pre-pad to the item-axis multiple ONCE and
                # pin the device array — per-query calls pass the resident
                # table (the aligned fast path in ``sharded_topk_scores``)
                # instead of re-uploading the whole table per batch.
                from albedo_tpu.parallel.mesh import ITEM_AXIS, pad_rows_to

                padded = pad_rows_to(spec.vectors, int(mesh.shape[ITEM_AXIS]))
                self._vf[name] = (
                    device_put_cached(owner, spec.vectors)
                    if padded is spec.vectors else jnp.asarray(padded)
                )
                if excl_map is not None:
                    self._excl_map[name] = excl_map  # host: remapped on host
        if self._excl_np is not None:
            self._excl_dev = (
                self._excl_np if mesh is not None else jnp.asarray(self._excl_np)
            )

    def reshard(self, mesh, budget: int | None = None,
                generations: int = 1) -> "RetrievalBank":
        """Re-lay a BUILT bank onto a different mesh — the degraded-mesh
        serving move: after the ladder hands serving a smaller rung (or a
        single device), the SAME bank re-prices and re-shards onto it with
        its version, calibration, and overlay state intact. Admission runs
        against the NEW layout's per-device price first (shards double when
        the mesh halves); a refusal raises
        :class:`~albedo_tpu.utils.capacity.CapacityExceeded` and leaves the
        current layout serving — a recorded rejection, never a torn swap.
        """
        from albedo_tpu.utils import capacity

        self._require_built()
        verdict = capacity.admit(
            self._retrieval_plan(
                mesh,
                excl_entries=0 if self._excl_np is None else int(self._excl_np.size),
                generations=generations,
            ),
            degradable=False, budget=budget,
        )
        if verdict.verdict == "refuse":
            raise capacity.CapacityExceeded(verdict)
        self.admission = verdict
        old = None if self.mesh is None else dict(self.mesh.shape)
        self._upload(mesh)
        log.warning(
            "retrieval bank resharded: %s -> %s (version %s unchanged)",
            old or "single-device",
            dict(mesh.shape) if mesh is not None else "single-device",
            self.version,
        )
        return self

    def _content_hash(self) -> str:
        """Deterministic digest of every registered table — the bank's
        ``version``. Recomputed at build AND at save, so overlay publishes
        between the two stamp the content actually sealed."""
        h = hashlib.sha256()
        for name in sorted(self.specs):
            spec = self.specs[name]
            h.update(name.encode())
            h.update(spec.kind.encode())
            h.update(spec.vectors.tobytes())
            h.update(spec.item_ids.tobytes())
            if spec.user_vectors is not None:
                h.update(spec.user_vectors.tobytes())
        return h.hexdigest()[:16]

    def manifest(self) -> dict:
        """The build's inspectable record (also what ``save()`` stamps)."""
        return {
            "version": self.version,
            "built_at": self.built_at,
            "overlay_generation": self.overlay_generation,
            "sharded": self.mesh is not None,
            "sources": {
                name: {
                    "kind": s.kind,
                    "rows": int(s.vectors.shape[0]),
                    "dim": int(s.vectors.shape[1]),
                    "user_rows": (
                        int(s.user_vectors.shape[0])
                        if s.user_vectors is not None else 0
                    ),
                    "exclude_seen": bool(s.exclude_seen),
                    "calibration": self.calibration.get(name, {}),
                }
                for name, s in self.specs.items()
            },
        }

    # ----------------------------------------------------------------- queries

    def _require_built(self) -> None:
        if not self._built:
            raise RuntimeError("bank not built — call build() first")

    def _executable(self, names: tuple[str, ...], bucket: int, k_exec: int,
                    q_widths: tuple[int, ...], with_excl: bool):
        """(source-mask, batch bucket, k, query widths, exclusion) ->
        compiled fused program via the persistent AOT caches."""
        key = (names, bucket, k_exec, q_widths, with_excl)
        compiled = self._executables.get(key)
        if compiled is not None:
            return compiled
        with self._exec_lock:
            compiled = self._executables.get(key)
            if compiled is not None:
                return compiled
            return self._build_executable(key)

    def _build_executable(self, key):
        import jax

        from albedo_tpu.utils.aot import persistent_aot_executable

        names, bucket, k_exec, q_widths, with_excl = key
        kinds = tuple(self.specs[n].kind for n in names)
        k_each = tuple(
            min(k_exec, int(self.specs[n].vectors.shape[0])) for n in names
        )
        use_excl = tuple(
            with_excl and self.specs[n].exclude_seen and kinds[i] == "user_rows"
            for i, n in enumerate(names)
        )
        remap = tuple(n in self._excl_map for n in names)
        tables, user_idx, q_idxs, excl = self._program_args(
            names, np.zeros(bucket, dtype=np.int32),
            tuple(
                np.full((bucket, w), -1, dtype=np.int32) if w else None
                for w in q_widths
            ),
            with_excl,
        )
        fn = _make_query_program(
            kinds, k_each, use_excl, remap, k_exec, self.item_block
        )
        key_parts = (
            "retrieval_query", names, kinds, bucket, k_exec, q_widths,
            with_excl, use_excl, remap, self.item_block,
            tuple(tuple(self.specs[n].vectors.shape) for n in names),
            tuple(
                tuple(self.specs[n].user_vectors.shape)
                if self.specs[n].user_vectors is not None else ()
                for n in names
            ),
            () if self._excl_dev is None else tuple(np.asarray(self._excl_dev).shape),
            jax.default_backend(),
        )
        compiled, compile_s, source = persistent_aot_executable(
            fn, (tables, user_idx, q_idxs, excl), None, None,
            key_parts, name="retrieval_query",
        )
        if source != "memory":
            log.info(
                "retrieval shape (sources=%s, bucket=%d, k=%d, excl=%s) "
                "ready (%s, %.2fs)", ",".join(names), bucket, k_exec,
                with_excl, source, compile_s,
            )
        self._executables[key] = compiled
        return compiled

    def _program_args(self, names, user_idx, q_idxs, with_excl):
        """Assemble the call-time argument pytree: CURRENT device tables
        (overlay publishes swap the array, the executable is shape-keyed),
        the user-index gather rows, per-source query rows, exclusion table."""
        tables = []
        for n in names:
            spec = self.specs[n]
            if spec.kind == "user_rows":
                tab = [self._uf[n], self._vf[n]]
                if n in self._excl_map:
                    tab.append(self._excl_map[n])
                tables.append(tuple(tab))
            else:
                tables.append((self._vf[n],))
        excl = self._excl_dev if with_excl else None
        return tuple(tables), user_idx, q_idxs, excl

    def _q_rows(self, name: str, queries: list[np.ndarray]) -> tuple[np.ndarray, int]:
        """Raw query item ids -> padded (B, Q) source-row index matrix."""
        rowmap = self._rowmap[name]
        rows = [
            np.array(
                [rowmap[int(i)] for i in q if int(i) in rowmap], dtype=np.int32
            )
            for q in queries
        ]
        width = max(_MIN_QUERY_WIDTH, _pow2(max((r.size for r in rows), default=1)))
        out = np.full((len(queries), width), -1, dtype=np.int32)
        for b, r in enumerate(rows):
            out[b, : r.size] = r
        return out, width

    def query(
        self,
        user_dense: np.ndarray,
        k: int,
        raw_user_ids: np.ndarray | None = None,
        sources: tuple[str, ...] | None = None,
        exclude_seen: bool = False,
    ) -> dict[str, tuple[np.ndarray, np.ndarray]]:
        """One fused candidate pass for a batch of users.

        ``user_dense``: dense matrix user indices (``-1`` = unknown: user-row
        sources return no rows, item_mean sources still answer from
        ``query_items``). Returns per source ``(scores (B, k) f32, rows
        (B, k) int32)`` — rows index the source's ``item_ids``; ``-1`` marks
        an empty slot. Scores are RAW (host-path parity); apply
        ``calibration[name]["scale"]`` for cross-source fusion.
        """
        self._require_built()
        QUERY_FAULT.hit()
        names = tuple(sources) if sources is not None else self.source_names
        unknown = set(names) - set(self.specs)
        if unknown:
            raise KeyError(f"unregistered bank source(s): {sorted(unknown)}")
        user_dense = np.asarray(user_dense, dtype=np.int64)
        b = user_dense.shape[0]
        if raw_user_ids is not None and len(raw_user_ids) != b:
            # A short id list would silently serve empty candidates for the
            # tail users (and a long one a shape mismatch deep in dispatch).
            raise ValueError(
                f"raw_user_ids ({len(raw_user_ids)}) must align with "
                f"user_dense ({b})"
            )
        if b == 0:
            empty = (
                np.zeros((0, k), dtype=np.float32),
                np.full((0, k), -1, dtype=np.int32),
            )
            return {n: empty for n in names}
        # Per-source example-query rows (host dict lookups; tiny per batch).
        q_raw: dict[str, list[np.ndarray]] = {}
        for n in names:
            spec = self.specs[n]
            if spec.kind != "item_mean":
                continue
            fn = spec.query_items
            if fn is not None and raw_user_ids is None:
                # query_items providers are keyed by RAW user id; silently
                # feeding them dense indices would answer with some OTHER
                # user's candidates — refuse instead.
                raise ValueError(
                    f"source {n!r} needs raw_user_ids (its query_items "
                    f"provider is keyed by raw user id, not dense index)"
                )
            q_raw[n] = [
                (
                    np.asarray(fn(int(u)), dtype=np.int64)
                    if fn is not None
                    else np.zeros(0, dtype=np.int64)
                )
                for u in (raw_user_ids if fn is not None else user_dense)
            ]
        wants_excl = bool(exclude_seen) and any(
            self.specs[n].exclude_seen for n in names
        )
        if wants_excl and self._excl_dev is None:
            # Refuse rather than silently return seen items: the caller
            # asked for the exclusion contract and this build cannot honor
            # it (build() was not given the exclusion table).
            raise ValueError(
                "exclude_seen=True but the bank was built without an "
                "exclude_table; pass the batcher's exclusion table to build()"
            )
        with_excl = wants_excl
        known = user_dense >= 0
        if self.mesh is not None:
            out = self._query_sharded(names, user_dense, q_raw, k, with_excl)
        else:
            out = self._query_fused(names, user_dense, q_raw, k, with_excl, b)
        # Unknown users never answer from user-row sources (the host paths'
        # inner-join-on-userFactors semantics).
        for n in names:
            if self.specs[n].kind == "user_rows" and not known.all():
                vals, idx = out[n]
                vals = np.where(known[:, None], vals, np.float32(-np.inf))
                idx = np.where(known[:, None], idx, np.int32(-1))
                out[n] = (vals.astype(np.float32), idx.astype(np.int32))
            events.retrieval_queries.inc(b, source=n)
        return out

    def _query_fused(self, names, user_dense, q_raw, k, with_excl, b):
        bucket = _pow2(min(self.max_batch, max(1, b)))
        if b > bucket:  # batches beyond the ladder split (batcher discipline)
            out: dict[str, tuple[np.ndarray, np.ndarray]] = {}
            for start in range(0, b, bucket):
                part = self._query_fused(
                    names, user_dense[start:start + bucket],
                    {n: q[start:start + bucket] for n, q in q_raw.items()},
                    k, with_excl, min(bucket, b - start),
                )
                for n, (v, i) in part.items():
                    pv, pi = out.get(n, (np.zeros((0, k), np.float32),
                                         np.full((0, k), -1, np.int32)))
                    out[n] = (np.concatenate([pv, v]), np.concatenate([pi, i]))
            return out
        k_exec = _pow2(int(k))
        user_idx = np.zeros(bucket, dtype=np.int32)
        user_idx[:b] = np.clip(user_dense, 0, None).astype(np.int32)
        q_idxs, widths = [], []
        for n in names:
            if self.specs[n].kind == "item_mean":
                q_mat, w = self._q_rows(n, q_raw[n])
                if q_mat.shape[0] < bucket:
                    q_mat = np.pad(
                        q_mat, ((0, bucket - q_mat.shape[0]), (0, 0)),
                        constant_values=-1,
                    )
                q_idxs.append(q_mat)
                widths.append(w)
            else:
                q_idxs.append(None)
                widths.append(0)
        compiled = self._executable(
            names, bucket, k_exec, tuple(widths), with_excl
        )
        tables, user_idx, q_idxs, excl = self._program_args(
            names, user_idx, tuple(q_idxs), with_excl
        )
        results = compiled(tables, user_idx, q_idxs, excl)
        out = {}
        for n, (vals, idx) in zip(names, results):
            out[n] = (
                np.asarray(vals)[:b, :k],
                np.asarray(idx)[:b, :k],
            )
        return out

    def _query_sharded(self, names, user_dense, q_raw, k, with_excl):
        """Mesh path: per-source sharded MIPS through ``parallel/topk.py``
        (per-shard top-k -> cross-shard k-per-device merge) against the
        tables PINNED at build (pre-padded device residents — only the
        small query/exclusion rows move per batch). One dispatch per source
        rather than one fused pass — the tables are the big thing on a
        mesh, not the dispatch."""
        from albedo_tpu.parallel.topk import sharded_topk_scores

        b = user_dense.shape[0]
        out = {}
        for n in names:
            spec = self.specs[n]
            n_rows = int(spec.vectors.shape[0])
            if spec.kind == "user_rows":
                q = spec.user_vectors[np.clip(user_dense, 0, None)]
                excl = None
                if with_excl and spec.exclude_seen:
                    excl = np.asarray(self._excl_dev)[
                        np.clip(user_dense, 0, None)
                    ].astype(np.int32)
                    emap = self._excl_map.get(n)
                    if emap is not None:
                        emap = np.asarray(emap)
                        excl = np.where(
                            excl < 0, -1, emap[np.clip(excl, 0, None)]
                        ).astype(np.int32)
                vals, idx = sharded_topk_scores(
                    q, self._vf[n], k=k, mesh=self.mesh, exclude_idx=excl,
                    n_items=n_rows,
                )
            else:
                q_mat, _ = self._q_rows(n, q_raw[n])
                qv, has_q = mean_query_vectors(spec.vectors, q_mat)
                vals, idx = sharded_topk_scores(
                    qv, self._vf[n], k=k, mesh=self.mesh,
                    exclude_idx=q_mat, n_items=n_rows,
                )
                vals, idx = np.asarray(vals), np.asarray(idx)
                vals = np.where(has_q[:, None], vals, -np.inf)
                idx = np.where(has_q[:, None], idx, -1)
            out[n] = (
                np.asarray(vals, dtype=np.float32)[:b],
                np.asarray(idx, dtype=np.int32)[:b],
            )
        return out

    def query_similar(
        self, name: str, example_ids: list[np.ndarray] | np.ndarray, k: int
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Similar-by-example over any source ("similar repos": example =
        one repo id against ``als``/``content``/``tfidf``; user-to-user:
        register the user table as its own source). Returns per query
        ``(raw_item_ids, scores)`` with the example rows excluded."""
        self._require_built()
        QUERY_FAULT.hit()
        if isinstance(example_ids, np.ndarray) and example_ids.ndim == 1:
            example_ids = [np.asarray([i]) for i in example_ids]
        queries = [np.asarray(q, dtype=np.int64) for q in example_ids]
        spec = self.specs[name]
        events.retrieval_queries.inc(len(queries), source=name)
        if self.mesh is not None:
            out = self._query_sharded(
                (name,),
                np.full(len(queries), -1, dtype=np.int64),
                {name: queries}, k, False,
            )[name] if spec.kind == "item_mean" else None
            if out is None:
                # user_rows source queried by example: run it as item_mean
                # over its own table (host-assembled queries).
                from albedo_tpu.parallel.topk import sharded_topk_scores

                q_mat, _ = self._q_rows(name, queries)
                qv, has_q = mean_query_vectors(spec.vectors, q_mat)
                vals, idx = sharded_topk_scores(
                    qv, self._vf[name], k=k, mesh=self.mesh,
                    exclude_idx=q_mat, n_items=int(spec.vectors.shape[0]),
                )
                vals = np.where(has_q[:, None], np.asarray(vals), -np.inf)
                idx = np.where(has_q[:, None], np.asarray(idx), -1)
                out = (vals.astype(np.float32), idx.astype(np.int32))
            vals, idx = out
        else:
            vals, idx = self._similar_fused(name, queries, k)
        results = []
        for b in range(len(queries)):
            ok = (idx[b] >= 0) & np.isfinite(vals[b])
            results.append((spec.item_ids[idx[b][ok]], vals[b][ok].astype(np.float64)))
        return results

    def _similar_fused(self, name: str, queries: list[np.ndarray], k: int):
        """Single-device similar-by-example: the item_mean program over one
        source (user_rows sources included — their table is queried by its
        own rows), through the same AOT executable ladder."""
        import jax

        from albedo_tpu.utils.aot import persistent_aot_executable

        b = len(queries)
        bucket = _pow2(min(self.max_batch, max(1, b)))
        if b > bucket:
            parts = [
                self._similar_fused(name, queries[s:s + bucket], k)
                for s in range(0, b, bucket)
            ]
            return (
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
            )
        k_exec = _pow2(int(k))
        q_mat, width = self._q_rows(name, queries)
        if q_mat.shape[0] < bucket:
            q_mat = np.pad(
                q_mat, ((0, bucket - q_mat.shape[0]), (0, 0)), constant_values=-1
            )
        spec = self.specs[name]
        key = ("similar", name, bucket, k_exec, width)
        compiled = self._executables.get(key)
        if compiled is None:
            # Same cache discipline as _executable(): double-checked under
            # the lock so concurrent cold callers compile once.
            with self._exec_lock:
                compiled = self._executables.get(key)
                if compiled is None:
                    fn = _make_query_program(
                        ("item_mean",),
                        (min(k_exec, int(spec.vectors.shape[0])),),
                        (False,), (False,), k_exec, self.item_block,
                    )
                    key_parts = (
                        "retrieval_similar", name, bucket, k_exec, width,
                        tuple(spec.vectors.shape), self.item_block,
                        jax.default_backend(),
                    )
                    compiled, _, _ = persistent_aot_executable(
                        fn,
                        (
                            ((self._vf[name],),),
                            np.zeros(bucket, dtype=np.int32),
                            (q_mat,),
                            None,
                        ),
                        None, None, key_parts, name="retrieval_similar",
                    )
                    self._executables[key] = compiled
        ((vals, idx),) = compiled(
            ((self._vf[name],),), np.zeros(bucket, dtype=np.int32), (q_mat,), None
        )
        return np.asarray(vals)[:b, :k], np.asarray(idx)[:b, :k]

    # ----------------------------------------------------------------- overlay

    def publish_user_rows(
        self, name: str, dense_rows: np.ndarray, rows: np.ndarray
    ) -> int:
        """Land freshly solved user rows (the fold-in engine's output) into a
        ``user_rows`` source's query table — the streaming overlay target.
        Tables are call-time arguments of the query executables, so the next
        batch reads the new rows with no recompile. Returns the bank's new
        overlay generation."""
        import jax.numpy as jnp

        self._require_built()
        spec = self.specs[name]
        if spec.kind != "user_rows":
            raise ValueError(f"source {name!r} has no user-row table to overlay")
        dense_rows = np.asarray(dense_rows, dtype=np.int64)
        rows = np.asarray(rows, dtype=np.float32)
        if rows.shape != (dense_rows.shape[0], spec.user_vectors.shape[1]):
            raise ValueError(
                f"overlay rows {rows.shape} do not match "
                f"({dense_rows.shape[0]}, {spec.user_vectors.shape[1]})"
            )
        # Host copy first (the sharded path and a future save() read it),
        # then the device table (functional update; old array stays valid
        # for in-flight batches — the generation-snapshot discipline).
        if name not in self._overlay_owned:
            # The registered array may BE the model's own cached factors
            # (the adapters register no-copy views); mutating it in place
            # would rewrite the trained model under every other holder —
            # the overlay owns its copy from the first publish on.
            spec.user_vectors = spec.user_vectors.copy()
            self._overlay_owned.add(name)
        spec.user_vectors[dense_rows] = rows
        if self.mesh is None:
            self._uf[name] = self._uf[name].at[jnp.asarray(dense_rows)].set(
                jnp.asarray(rows)
            )
        self.overlay_generation += 1
        return self.overlay_generation

    # ----------------------------------------------------------- persistence

    def save(self, artifact_name: str, lineage: dict | None = None):
        """Persist the built bank: pickle + ``.meta.json`` stamp (the
        manifest() record + lineage) + the ``.sha256`` manifest written
        LAST — the same seal every publishable artifact carries, so a death
        mid-write leaves nothing a watcher would promote."""
        from albedo_tpu.datasets import artifacts as store

        self._require_built()
        path = store.artifact_path(artifact_name)
        # Overlay publishes since build() changed the sealed content; the
        # stamp must vouch for the bytes actually written.
        self.version = self._content_hash()
        payload = {
            "format": "retrieval-bank-v1",
            "version": self.version,
            "built_at": self.built_at,
            "item_block": self.item_block,
            "max_batch": self.max_batch,
            "calibration": self.calibration,
            "sources": [
                {
                    "name": s.name,
                    "kind": s.kind,
                    "exclude_seen": bool(s.exclude_seen),
                    "vectors": s.vectors,
                    "item_ids": s.item_ids,
                    "user_vectors": s.user_vectors,
                }
                for s in self.specs.values()
            ],
        }
        store.save_pickle(path, payload)
        store.write_meta(path, {
            "bank": self.manifest(),
            "lineage": dict(lineage or {}),
        })
        store.write_manifest(path)
        return path

    @classmethod
    def load(cls, artifact_name: str, verify: bool = True) -> "RetrievalBank":
        """Restore a saved bank (un-built: the loading process runs its own
        admission + upload via ``build()``). ``verify`` enforces the
        ``.sha256`` manifest — a mismatch raises rather than serving
        corrupted embeddings; reload-style quarantine is the stage's job.
        Query-item providers are live callables and do not persist — rebind
        them (``bind_query_items``) before serving item_mean sources."""
        from albedo_tpu.datasets import artifacts as store

        path = store.artifact_path(artifact_name)
        if verify and store.verify_manifest(path) is False:
            raise ValueError(f"bank artifact {path.name} fails its manifest")
        payload = store.load_pickle(path)
        if payload.get("format") != "retrieval-bank-v1":
            raise ValueError(f"not a retrieval bank artifact: {path.name}")
        bank = cls(
            item_block=int(payload.get("item_block", 4096)),
            max_batch=int(payload.get("max_batch", 64)),
        )
        for s in payload["sources"]:
            bank.register(BankSourceSpec(
                name=s["name"], kind=s["kind"], vectors=s["vectors"],
                item_ids=s["item_ids"], user_vectors=s["user_vectors"],
                exclude_seen=bool(s["exclude_seen"]),
            ))
        return bank

    def bind_query_items(self, name: str, fn: Callable[[int], np.ndarray]) -> None:
        """Re-attach a query-item provider after ``load()`` (providers are
        live callables over the serving tables; they never persist)."""
        self.specs[name].query_items = fn
