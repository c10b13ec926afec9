"""BankStage: the serving pipeline's bank-backed candidate stage.

Inside :class:`~albedo_tpu.serving.pipeline.TwoStagePipeline`, sources the
bank carries stop being threads: stage 1 submits ONE bank task that answers
every bank-resident source in a single fused device pass, while truly
external sources (and any source the bank does not carry) keep the
thread + breaker fan-out. The degradation contract gains one new edge —
a bank query that times out or raises falls back to the **host-side
per-source path** for exactly the sources it was covering (tagged
``bank_timeout`` / ``bank_error``, counted in
``albedo_retrieval_fallbacks_total{reason=}``), never a 500.

The stage also owns bank **generations**: ``reload()`` promotes a freshly
saved bank artifact through the same gate shape the model hot-swap uses
(manifest -> stamp -> load -> invariants -> capacity -> probe), atomically
swapping the served bank only after every gate passes. Outcomes land in
``albedo_retrieval_promotions_total{outcome=}``; a capacity refusal is a
recorded rejection, not a quarantine (the bytes are fine, the process is
full — the reload capacity-gate convention).
"""

from __future__ import annotations

import logging
import threading

import numpy as np
import pandas as pd

from albedo_tpu.analysis.locksmith import named_lock
from albedo_tpu.retrieval.bank import RetrievalBank
from albedo_tpu.utils import events

log = logging.getLogger(__name__)


class BankStage:
    """One served bank + the host fallbacks behind it.

    ``fallbacks`` maps source name -> host-side :class:`Recommender`; on a
    bank failure the pipeline fans those out exactly as it would have
    without a bank. ``calibrate=True`` multiplies each source's scores by
    its build-time calibration scale (cross-source fusion on one scale);
    the default serves RAW scores — bit-comparable with the host paths.
    """

    def __init__(
        self,
        bank: RetrievalBank,
        matrix,
        sources: tuple[str, ...] | None = None,
        fallbacks: dict | None = None,
        top_k: int = 30,
        calibrate: bool = False,
        timeout_s: float = 1.0,
    ):
        self._bank = bank
        self.matrix = matrix
        self._sources = tuple(sources) if sources is not None else bank.source_names
        self.fallbacks = dict(fallbacks or {})
        self.top_k = int(top_k)
        self.calibrate = bool(calibrate)
        # The bank's OWN wait budget inside stage 1 — strictly less than the
        # stage deadline by construction (the pipeline caps it at half the
        # remaining stage budget), so a timed-out bank always leaves the
        # host fallback real time to answer instead of a zero-budget collect.
        self.timeout_s = float(timeout_s)
        self._swap_lock = named_lock("retrieval.stage.swap")
        self.generation = 1

    @property
    def bank(self) -> RetrievalBank:
        return self._bank

    @property
    def source_names(self) -> tuple[str, ...]:
        return tuple(n for n in self._sources if n in self._bank.specs)

    def publish_user_rows(self, source: str, dense_rows, rows) -> int:
        """Forward a streaming overlay publish to the CURRENTLY SERVED bank.

        Fold-in subscribers attach the STAGE, not a bank object — a bank
        held directly would go stale at the first generation promotion and
        every later publish would land in the retired tables."""
        return self._bank.publish_user_rows(source, dense_rows, rows)

    def snapshot(self) -> dict:
        """The readiness probe's view of the stage."""
        return {
            "generation": self.generation,
            "version": self._bank.version,
            "overlay_generation": self._bank.overlay_generation,
            "sources": list(self.source_names),
            "sharded": self._bank.mesh is not None,
        }

    # ------------------------------------------------------------------ query

    def query_frames(
        self,
        user_id: int,
        k: int | None = None,
        exclude_seen: bool = True,
        sources: tuple[str, ...] | None = None,
    ) -> dict[str, pd.DataFrame]:
        """One user's candidates from the requested bank sources, as
        recommender frames (user_id, repo_id, score, source) — the
        fusion-ready shape ``recommenders.base`` produces, from one device
        pass. ``sources`` restricts the pass (the pipeline excludes names
        its generation snapshot already answers — a bank frame must never
        clobber the snapshot's). ``k`` overrides the stage's ``top_k`` —
        the brownout ladder's reduced-k tier passes its halved budget here;
        it is clamped to >= 1 so an aggressively-degraded request can never
        drive the device pass with an empty shape."""
        bank = self._bank  # snapshot: a concurrent reload must not tear us
        k = max(1, self.top_k if k is None else int(k))
        dense = self.matrix.users_of(np.asarray([int(user_id)], dtype=np.int64))
        # Filter against the SNAPSHOTTED bank — source_names reads the live
        # one, and a mid-request promote that adds a source would otherwise
        # ask the old bank for a name it never registered.
        wanted = self._sources if sources is None else tuple(sources)
        names = tuple(n for n in wanted if n in bank.specs)
        out = bank.query(
            dense, k,
            raw_user_ids=np.asarray([int(user_id)], dtype=np.int64),
            sources=names, exclude_seen=exclude_seen,
        )
        frames: dict[str, pd.DataFrame] = {}
        for name, (vals, idx) in out.items():
            spec = bank.specs[name]
            ok = (idx[0] >= 0) & np.isfinite(vals[0])
            scores = vals[0][ok].astype(np.float64)
            if self.calibrate:
                scores = scores * float(
                    bank.calibration.get(name, {}).get("scale", 1.0)
                )
            frames[name] = pd.DataFrame({
                "user_id": np.full(int(ok.sum()), int(user_id), dtype=np.int64),
                "repo_id": spec.item_ids[idx[0][ok]],
                "score": scores,
                "source": name,
            })
        return frames

    def warm(self) -> None:
        """Answer one real query OFF the request path so the fused program
        behind ``query_frames`` is compiled before a client's stage budget
        is on the clock (the bank's wait budget is ~1 s; a cold compile on
        a chip is longer, and would surface as ``bank_timeout`` on the first
        requests). Raises on failure: a bank that cannot answer at boot is
        a boot failure, not a degraded first request."""
        if self.matrix.n_users:
            self.query_frames(int(self.matrix.user_ids[0]), exclude_seen=True)

    # ----------------------------------------------------------- generations

    _INCUMBENT_MESH = object()  # sentinel: "build on the incumbent's mesh"

    def reload(
        self,
        artifact_name: str,
        require_stamp: bool = False,
        probe_users: int = 4,
        probe_k: int = 10,
        mesh=_INCUMBENT_MESH,
    ) -> dict:
        """Promote a bank artifact through the validation gates.

        Gates, in order (any failure = recorded rejection, incumbent keeps
        serving): **manifest** (``.sha256`` verifies), **stamp**
        (``.meta.json`` present when required), **load** (unpickle +
        format), **invariants** (finite tables; source names/dims cover the
        incumbent's — a shrunken bank is a restart, not a swap),
        **capacity** (candidate priced ALONGSIDE the incumbent,
        ``generations=2``), **probe** (probe users answer with finite
        scores and in-range rows through the candidate's real query path).

        ``mesh`` overrides the layout the candidate builds onto; the
        default is the incumbent's own mesh. This is the degraded-serving
        seam: the shard count is a per-process LAYOUT choice, not part of
        the artifact — a bank saved by an 8-shard builder promotes onto
        whatever rung the ladder gave THIS process (4, 2, 1, or a plain
        single device), and a candidate too big for the smaller rung is a
        recorded capacity rejection, never a quarantine.
        """
        from albedo_tpu.datasets import artifacts as store
        from albedo_tpu.utils.capacity import CapacityExceeded

        def reject(gate: str, why: str) -> dict:
            events.retrieval_promotions.inc(outcome="rejected")
            log.warning("bank reload rejected at gate %s: %s", gate, why)
            return {"outcome": "rejected", "gate": gate, "why": why}

        path = store.artifact_path(artifact_name)
        if store.verify_manifest(path) is not True:
            return reject("manifest", f"{path.name}: missing or failing manifest")
        meta = store.read_meta(path)
        if require_stamp and meta is None:
            return reject("stamp", f"{path.name}: unstamped bank artifact")
        try:
            candidate = RetrievalBank.load(artifact_name)
        except Exception as e:  # noqa: BLE001 — any unreadable candidate rejects
            return reject("load", f"{type(e).__name__}: {e}")

        incumbent = self._bank
        for name in incumbent.specs:
            if name not in candidate.specs:
                return reject(
                    "invariants",
                    f"candidate drops source {name!r} — a changed source set "
                    f"is a restart, not a swap",
                )
            if candidate.specs[name].vectors.shape[1] != incumbent.specs[name].vectors.shape[1]:
                return reject(
                    "invariants",
                    f"source {name!r} rank changed "
                    f"{incumbent.specs[name].vectors.shape[1]} -> "
                    f"{candidate.specs[name].vectors.shape[1]}",
                )
        for name, spec in candidate.specs.items():
            if not np.all(np.isfinite(spec.vectors)) or (
                spec.user_vectors is not None
                and not np.all(np.isfinite(spec.user_vectors))
            ):
                return reject("invariants", f"source {name!r} carries non-finite rows")
            # Live query-item providers never persist; inherit the
            # incumbent's bindings so item_mean sources keep answering (a
            # GROWN source set is legal — an added source the incumbent
            # never carried simply has no binding to inherit).
            if spec.kind == "item_mean" and spec.query_items is None:
                inc_spec = incumbent.specs.get(name)
                if inc_spec is not None:
                    spec.query_items = inc_spec.query_items

        try:
            candidate.build(
                matrix=self.matrix,
                exclude_table=(
                    np.asarray(incumbent._excl_dev)
                    if incumbent._excl_dev is not None else None
                ),
                mesh=incumbent.mesh if mesh is self._INCUMBENT_MESH else mesh,
                generations=2,  # incumbent + candidate resident through the swap
            )
        except CapacityExceeded as e:
            # Recorded rejection, NOT a quarantine: the artifact is fine,
            # this process is full (the reload capacity-gate convention).
            return reject("capacity", str(e))
        except Exception as e:  # noqa: BLE001
            return reject("load", f"build failed: {type(e).__name__}: {e}")

        try:
            n = min(int(probe_users), max(1, self.matrix.n_users))
            probe = candidate.query(
                np.arange(n, dtype=np.int64), int(probe_k),
                raw_user_ids=self.matrix.user_ids[:n],
                sources=tuple(candidate.source_names),
                exclude_seen=False,
            )
            for name, (vals, idx) in probe.items():
                live = idx >= 0  # filled slots; -1 = legitimately empty
                if np.any(idx[live] >= candidate.specs[name].item_ids.shape[0]):
                    return reject("probe", f"source {name!r} returned out-of-range rows")
                if np.any(~np.isfinite(vals[live])):
                    return reject("probe", f"source {name!r} returned non-finite scores")
        except Exception as e:  # noqa: BLE001
            return reject("probe", f"{type(e).__name__}: {e}")

        with self._swap_lock:
            self._bank = candidate
            self.generation += 1
        events.retrieval_promotions.inc(outcome="promoted")
        log.info(
            "bank generation %d promoted (version %s, %d source(s))",
            self.generation, candidate.version, len(candidate.specs),
        )
        return {
            "outcome": "promoted",
            "generation": self.generation,
            "version": candidate.version,
        }

    def reshard(self, mesh) -> dict:
        """Re-lay the LIVE bank onto a different mesh — the in-place
        degraded-serving move after a device loss halves the serving slice
        mid-flight (promotion-shaped swaps go through :meth:`reload`).
        Re-admission runs first (per-device shards double when the mesh
        halves); a refusal leaves the current layout serving and is a
        recorded rejection, not a quarantine. Returns the stage snapshot.
        """
        from albedo_tpu.utils.capacity import CapacityExceeded

        with self._swap_lock:
            try:
                self._bank.reshard(mesh)
            except CapacityExceeded as e:
                events.retrieval_promotions.inc(outcome="rejected")
                log.warning("bank reshard refused: %s", e)
                return {"outcome": "rejected", "gate": "capacity", "why": str(e)}
        log.info(
            "bank resharded onto %s",
            dict(mesh.shape) if mesh is not None else "single-device",
        )
        return dict(self.snapshot(), outcome="resharded")
