"""Artifact-backed serving engine: batcher + two-stage pipeline + cache.

Promoted from the seed's single-module serving layer (Django ``views/admin``
parity): :class:`RecommendationService` still answers id-mapped top-k and
admin search from trained artifacts, but requests now flow through the
online engine:

1. **TTL result cache** (``serving.cache``) — hot users skip the device.
2. **Two-stage pipeline** (``serving.pipeline``) when candidate sources are
   registered: fan-out -> fuse -> LR re-rank with per-stage deadlines and
   graceful degradation.
3. **Micro-batcher** (``serving.batcher``) — all ALS scoring, both the plain
   ``/recommend`` path and the pipeline's stage-1 source, coalesces into
   fixed-shape device batches. ``batching=False`` keeps the seed's direct
   single-request path (the parity baseline).
4. **Metrics** (``serving.metrics``) — every outcome is counted; the HTTP
   layer renders the registry at ``/metrics``.

Degradation contract (tested): ranker deadline exceeded -> raw ALS scores;
missing/cold ALS artifacts (``model=None``) -> popularity fallback; queue
overflow -> :class:`~albedo_tpu.serving.batcher.QueueOverflow` (HTTP 429).
Every degraded response carries ``"degraded": [reasons]`` and bumps
``albedo_degraded_total{reason=...}``.

Live operations (PR 4): the model state a request reads is an immutable
:class:`ModelGeneration` snapshot — model + batcher + pipeline ALS source,
captured ONCE at request entry — so the hot-swap manager
(``serving.reload``) can atomically promote a freshly validated generation
(or roll one back) under live traffic without a request ever seeing half of
each. Every response carries ``"generation"``; ``/healthz/ready`` reports
the promoted generation, batcher warm state, and breaker states.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np
import pandas as pd

from albedo_tpu.analysis.locksmith import named_lock
from albedo_tpu.datasets.ragged import csr_row, padded_rows
from albedo_tpu.datasets.star_matrix import StarMatrix
from albedo_tpu.models.als import ALSModel
from albedo_tpu.serving.batcher import (
    BatcherClosed,
    DeadlineExceeded,
    MicroBatcher,
    QueueOverflow,
)
from albedo_tpu.serving.cache import TTLCache
from albedo_tpu.serving.metrics import MetricsRegistry
from albedo_tpu.serving.overload import (
    LEVEL_SHED,
    OverloadConfig,
    OverloadController,
    tier_name,
)
from albedo_tpu.serving.pipeline import (
    BatchedALSSource,
    StageDeadlines,
    TwoStagePipeline,
)


@dataclasses.dataclass(frozen=True)
class ModelGeneration:
    """One immutable serving state: everything a request needs that a hot
    swap replaces. Requests snapshot the CURRENT generation once at entry
    and use only its members — items, scores, and the ``"generation"`` tag
    in a response always come from the same model (no torn reads).
    """

    number: int
    model: ALSModel | None
    batcher: MicroBatcher | None
    als_source: object | None  # BatchedALSSource/ALSRecommender for the pipeline
    origin: str                # "boot" or the artifact path it was loaded from
    validated: bool            # passed the reload validation gates (or boot)
    promoted_at: float = 0.0


class RecommendationService:
    """Read-only online engine over trained artifacts.

    Seed-compatible construction (``RecommendationService(model, matrix,
    repo_info, user_info)``) serves the plain ALS path; the engine features
    are opt-in keywords. ``model=None`` declares the ALS artifacts missing —
    the service stays up and answers from the ``popularity`` source (the
    cold-artifact degradation path).
    """

    def __init__(
        self,
        model: ALSModel | None,
        matrix: StarMatrix | None,
        repo_info: pd.DataFrame | None = None,
        user_info: pd.DataFrame | None = None,
        *,
        recommenders: dict | None = None,
        ranker=None,
        metrics: MetricsRegistry | None = None,
        batching: bool = True,
        batch_window_ms: float = 2.0,
        max_batch: int = 64,
        max_queue: int = 256,
        cache_ttl: float = 0.0,
        cache_size: int = 4096,
        deadlines: StageDeadlines | None = None,
        default_k: int = 30,
        max_k: int = 500,
        item_block: int = 4096,
        warm: bool = False,
        breaker_config=None,
        breakers_enabled: bool = True,
        bank_stage=None,  # retrieval.stage.BankStage — fused candidate stage
        overload_enabled: bool = True,
        overload_config: OverloadConfig | None = None,
    ):
        self.matrix = matrix
        self.repo_info = repo_info if repo_info is not None else pd.DataFrame()
        self.user_info = user_info if user_info is not None else pd.DataFrame()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.default_k = int(default_k)
        self.max_k = int(max_k)
        self.item_block = int(item_block)
        self._closed = False
        self._close_lock = named_lock("serving.service.close")
        # Batcher construction parameters, kept so the hot-swap manager can
        # build a candidate generation's batcher identically configured.
        self._batching = bool(batching)
        self._max_batch = int(max_batch)
        self._max_queue = int(max_queue)
        self._batch_window_ms = float(batch_window_ms)
        self._warm = bool(warm)
        self.reload_manager = None  # set by serving.reload.HotSwapManager
        # Overload-resilience layer (serving.overload): ONE controller for
        # the whole service, shared by every generation's batcher, so a hot
        # swap under pressure inherits the brownout state. The default AIMD
        # ceiling is the legacy queue bound — an unstressed service behaves
        # exactly like the static bounded queue it replaced.
        self.overload: OverloadController | None = None
        if overload_enabled:
            self.overload = OverloadController(
                overload_config or OverloadConfig(max_limit=int(max_queue)),
                metrics=self.metrics,
            )

        if matrix is not None:
            self._indptr, self._cols, _ = matrix.csr()
            max_hist = int((self._indptr[1:] - self._indptr[:-1]).max()) if matrix.n_users else 0
        else:
            self._indptr = self._cols = None
            max_hist = 0
        self._max_hist = max_hist
        self._repo_names = (
            self.repo_info.set_index("repo_id")["repo_full_name"].to_dict()
            if "repo_full_name" in self.repo_info.columns
            else {}
        )

        # Device-side exclusion table: the users' seen-item rows, -1-padded,
        # computed once on the host and re-uploaded per generation's batcher
        # (the matrix does not change across a model hot-swap). Skewed
        # datasets (one power user -> huge padded width) fall back to host
        # rows; the cap is entries, i.e. 4 bytes each.
        self._exclude_table: np.ndarray | None = None
        if batching and matrix is not None and max_hist:
            cap = int(os.environ.get("ALBEDO_SERVE_EXCL_TABLE_MAX", str(32 << 20)))
            if matrix.n_users * max_hist <= cap:
                self._exclude_table = padded_rows(
                    self._indptr, self._cols, np.arange(matrix.n_users)
                )

        self.cache: TTLCache | None = (
            TTLCache(maxsize=cache_size, ttl=cache_ttl) if cache_ttl > 0 else None
        )

        self.pipeline: TwoStagePipeline | None = None
        self._pipeline_owns_als = False
        self.bank_stage = bank_stage
        if recommenders or bank_stage is not None:
            sources = dict(recommenders or {})
            # The live ALS source rides each ModelGeneration and joins the
            # fan-out per request (pipeline extra_sources) — unless the
            # caller registered an "als" source explicitly, which then wins.
            self._pipeline_owns_als = "als" in sources
            self.pipeline = TwoStagePipeline(
                sources, ranker=ranker, deadlines=deadlines, metrics=self.metrics,
                breaker_config=breaker_config, breakers_enabled=breakers_enabled,
                bank_stage=bank_stage,
            )

        # Retired generations' batchers that have not been stopped yet: the
        # incumbent stays fully serviceable after a promote (rollback target
        # + in-flight requests holding its snapshot) until the manager
        # retires it; close() sweeps whatever is left.
        self._zombie_batchers: list[MicroBatcher] = []
        self._gen_lock = named_lock("serving.service.gen")
        self._generation = self.build_generation(
            model,
            number=1 if model is not None else 0,
            origin="boot",
            validated=model is not None,
            warm=warm,
        )
        self.metrics.model_generation.set(self._generation.number)
        self._max_generation = self._generation.number
        if warm and self.pipeline is not None and matrix is not None and matrix.n_users:
            # The batcher warmed its ladder in build_generation; the
            # two-stage path's other device programs (bank query, ranker
            # logits) compile here, not under a client's stage deadline.
            als_source = self._generation.als_source
            self.pipeline.warm(
                int(matrix.user_ids[0]),
                extra_sources={"als": als_source} if als_source is not None else None,
            )

    # ------------------------------------------------- generation plumbing

    @property
    def exclude_table(self) -> np.ndarray | None:
        """The device-exclusion source table (host copy) — shared with the
        retrieval bank so seen-item exclusion has ONE definition."""
        return self._exclude_table

    @property
    def generation(self) -> ModelGeneration:
        return self._generation

    def next_generation_number(self) -> int:
        """A number no generation has ever carried. Candidate numbers must
        never derive from the CURRENT generation: after a rollback
        (2 -> back to 1) the next candidate would be "2" again, and a slow
        request still holding the first gen-2 snapshot could write its model's
        body under the second gen-2's cache key — the exact staleness the
        generation-tagged key exists to make structurally impossible."""
        with self._gen_lock:
            return self._max_generation + 1

    @property
    def model(self) -> ALSModel | None:
        return self._generation.model

    @property
    def batcher(self) -> MicroBatcher | None:
        return self._generation.batcher

    def build_generation(
        self,
        model: ALSModel | None,
        number: int,
        origin: str,
        validated: bool,
        warm: bool = False,
    ) -> ModelGeneration:
        """Assemble a serving state for ``model`` WITHOUT promoting it: the
        batcher (same config as the incumbent's, warm-compiled off the
        request path — same factor shapes reuse the incumbent's executables
        via the AOT cache) and the pipeline ALS source."""
        batcher = None
        if self._batching and model is not None:
            batcher = MicroBatcher(
                model,
                exclude_table=self._exclude_table,
                excl_width=self._max_hist,
                item_block=self.item_block,
                max_batch=self._max_batch,
                max_queue=self._max_queue,
                window_ms=self._batch_window_ms,
                metrics=self.metrics,
                overload=self.overload,
            )
            if warm:
                batcher.warm(ks=(self.default_k,))
        als_source = None
        if (
            self.pipeline is not None
            and not self._pipeline_owns_als
            and model is not None
            and self.matrix is not None
        ):
            if batcher is not None:
                als_source = BatchedALSSource(
                    batcher, self.matrix, exclude_seen=True, top_k=self.default_k
                )
            else:
                from albedo_tpu.recommenders import ALSRecommender

                als_source = ALSRecommender(
                    model, self.matrix, exclude_seen=True, top_k=self.default_k
                )
        return ModelGeneration(
            number=int(number),
            model=model,
            batcher=batcher,
            als_source=als_source,
            origin=origin,
            validated=validated,
            promoted_at=time.time(),
        )

    def promote(self, gen: ModelGeneration) -> ModelGeneration:
        """Atomically make ``gen`` the serving generation; returns the
        displaced incumbent (left fully alive — it is the rollback target
        and in-flight requests may still hold its snapshot). The result
        cache is flushed: cached bodies carry the old generation tag."""
        with self._gen_lock:
            old = self._generation
            self._generation = gen
            self._max_generation = max(self._max_generation, gen.number)
            if gen.batcher is not None and gen.batcher in self._zombie_batchers:
                self._zombie_batchers.remove(gen.batcher)  # rollback revival
            if old.batcher is not None and old.batcher is not gen.batcher:
                self._zombie_batchers.append(old.batcher)
        self.metrics.model_generation.set(gen.number)
        if self.cache is not None:
            self.cache.invalidate_all()
        return old

    def retire_batcher(self, batcher: MicroBatcher | None) -> None:
        """Stop a displaced generation's batcher (drains in-flight work).
        Called by the hot-swap manager once its post-swap checks pass."""
        if batcher is None:
            return
        batcher.stop(drain=True)
        with self._gen_lock:
            if batcher in self._zombie_batchers:
                self._zombie_batchers.remove(batcher)

    def readiness(self) -> tuple[bool, dict]:
        """(ready?, report) for ``/healthz/ready``: ready only once a
        validated model generation is promoted. The report carries what an
        operator needs to see first: generation, batcher warmth, breakers."""
        gen = self._generation
        ready = gen.model is not None and gen.validated
        batcher = gen.batcher
        report = {
            "ready": ready,
            "generation": gen.number,
            "model_loaded": gen.model is not None,
            "validated": gen.validated,
            "origin": gen.origin,
            "batcher": (
                {
                    "active": True,
                    "warm": bool(batcher.warmed),
                    "queue_depth": batcher._queue.qsize(),
                    "mean_batch_size": round(batcher.mean_batch_size, 3),
                }
                if batcher is not None
                else {"active": False}
            ),
            "breakers": (
                self.pipeline.breaker_states() if self.pipeline is not None else {}
            ),
        }
        if self.bank_stage is not None:
            report["retrieval_bank"] = self.bank_stage.snapshot()
        if self.cache is not None:
            report["cache"] = self.cache.stats()
        if self.overload is not None:
            report["overload"] = self.overload.snapshot()
        return ready, report

    # ----------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Stop the batcher (draining in-flight work) and the pipeline pool.
        Idempotent; the HTTP layer calls it from ``ServerHandle.shutdown``."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        if self.reload_manager is not None:
            self.reload_manager.stop()
        gen = self._generation
        if gen.batcher is not None:
            gen.batcher.stop(drain=True)
        with self._gen_lock:
            zombies, self._zombie_batchers = self._zombie_batchers, []
        for batcher in zombies:
            batcher.stop(drain=True)
        if self.pipeline is not None:
            self.pipeline.close()

    def __enter__(self) -> "RecommendationService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------- helpers

    def clamp_k(self, k) -> int:
        """Harden ``k``: junk/absurd values become sane bounds, never an
        index error deep inside the model."""
        try:
            k = int(k)
        except (TypeError, ValueError):
            return self.default_k
        return max(1, min(k, self.max_k))

    def _named_items(self, repo_ids, scores, sources=None) -> list[dict]:
        items = []
        for i, (repo_id, score) in enumerate(zip(repo_ids, scores)):
            item = {
                "repo_id": int(repo_id),
                "repo_full_name": self._repo_names.get(int(repo_id)),
                "score": float(score),
            }
            if sources is not None:
                item["source"] = sources[i]
            items.append(item)
        return items

    def _exclude_row(self, dense_user: int) -> np.ndarray:
        return csr_row(self._indptr, self._cols, dense_user)

    def invalidate(self, user_id: int | None = None) -> int:
        """Explicit cache invalidation (e.g. after a star ingest)."""
        if self.cache is None:
            return 0
        if user_id is None:
            return self.cache.invalidate_all()
        return self.cache.invalidate_user(int(user_id))

    # ------------------------------------------------------- request paths

    def recommend(self, user_id: int, k: int = 30, exclude_seen: bool = True) -> dict:
        """The seed's direct single-request path: one blocking GEMM + top-k.

        Kept verbatim as the parity baseline for the micro-batcher (and the
        ``batching=False`` serving mode)."""
        gen = self._generation
        dense = self.matrix.users_of(np.array([user_id], dtype=np.int64))
        if dense[0] < 0:
            return {"user_id": user_id, "error": "unknown user", "items": []}
        excl = padded_rows(self._indptr, self._cols, dense) if exclude_seen else None
        vals, idx = gen.model.recommend(
            dense, k=k, exclude_idx=excl, item_block=self.item_block
        )
        ok = (idx[0] >= 0) & np.isfinite(vals[0])
        repo_ids = self.matrix.item_ids[idx[0][ok]]
        return {
            "user_id": user_id,
            "k": k,
            "generation": gen.number,
            "items": self._named_items(repo_ids, vals[0][ok]),
        }

    def _recommend_batched(
        self,
        gen: ModelGeneration,
        user_id: int,
        k: int,
        exclude_seen: bool,
        deadline: float | None = None,
    ) -> dict:
        dense = self.matrix.users_of(np.array([user_id], dtype=np.int64))
        if dense[0] < 0:
            return {"user_id": user_id, "error": "unknown user", "items": []}
        exclude = None
        if exclude_seen:
            exclude = (
                True if gen.batcher.device_exclusion
                else self._exclude_row(int(dense[0]))
            )
        fut = gen.batcher.submit(int(dense[0]), k, exclude, deadline=deadline)
        timeout = 30.0
        if deadline is not None:
            timeout = max(0.05, deadline - time.monotonic())
        try:
            vals, idx = fut.result(timeout=timeout)
        except FutureTimeout:
            if deadline is None:
                raise
            # The client's deadline lapsed while the request queued: shed it
            # here. A successful cancel keeps the worker from computing it
            # AND means this side owns the accounting; a failed cancel means
            # the worker already resolved it (its own shed counted there, a
            # too-late success counts nowhere — the work was done).
            if fut.cancel():
                self.metrics.shed.inc()
                self.metrics.deadline_shed.inc()
            raise DeadlineExceeded(
                "request deadline expired while queued",
                retry_after_s=gen.batcher.retry_after_s(),
            ) from None
        ok = (idx >= 0) & np.isfinite(vals)
        repo_ids = self.matrix.item_ids[idx[ok]]
        return {
            "user_id": user_id,
            "k": k,
            "generation": gen.number,
            "items": self._named_items(repo_ids, vals[ok]),
        }

    def handle_recommend(
        self,
        user_id: int,
        k=None,
        exclude_seen: bool = True,
        deadline: float | None = None,
    ) -> tuple[int, dict]:
        """Full engine path: cache -> (two-stage | batched ALS | fallback).

        Returns ``(http_status, body)``; raises
        :class:`~albedo_tpu.serving.batcher.QueueOverflow` for the HTTP
        layer's 429. Never returns a half-built body: every path ends in a
        well-formed dict. ``deadline`` (monotonic timestamp) opts the
        batched path into admission control.
        """
        user_id = int(user_id)
        k = self.clamp_k(k if k is not None else self.default_k)
        if self.pipeline is not None:
            # Two-stage k is bounded by the stage-1 candidate budget (each
            # source generates default_k candidates, the reference's top-30
            # product shape) — clamp and SAY so, rather than claiming a k
            # the fusion cannot fill.
            k = min(k, self.default_k)
        gen = self._generation

        def cache_key(g):
            # The generation tag is part of the cache key: a promoted swap
            # must never answer from the displaced model's cached bodies
            # (promote() also flushes, but the key makes staleness
            # structurally impossible).
            return ("rec", user_id, k, bool(exclude_seen),
                    self.pipeline is not None, g.number)

        key = cache_key(gen)
        if self.cache is not None:
            hit = self.cache.get(key)
            if hit is not None:
                self.metrics.cache_hits.inc()
                return hit
            self.metrics.cache_misses.inc()

        try:
            status, body = self._compute(gen, user_id, k, exclude_seen, deadline)
        except BatcherClosed:
            # The snapshot lost a race with a retirement (its batcher was
            # stopped between our read and the submit). The CURRENT
            # generation is alive by construction — retry once against it,
            # and re-key the cache write to the generation that actually
            # answered (a body cached under the displaced key could outlive
            # a later rollback to that very generation number).
            gen = self._generation
            key = cache_key(gen)
            status, body = self._compute(gen, user_id, k, exclude_seen, deadline)
        self.metrics.generation_requests.inc(generation=str(gen.number))
        if (
            self.cache is not None and status == 200
            and not body.get("degraded") and not body.get("brownout")
        ):
            # Degraded OR brownout-tagged bodies never enter the cache: a
            # reduced-quality answer must not outlive the incident (the TTL
            # cache is what the cache_popularity tier leans on for quality).
            self.cache.put(key, (status, body), user_id=user_id)
        return status, body

    def _compute(
        self,
        gen: ModelGeneration,
        user_id: int,
        k: int,
        exclude_seen: bool,
        deadline: float | None = None,
    ) -> tuple[int, dict]:
        # Admission control, every path: a request whose deadline lapsed
        # before compute started (queued in the HTTP pool, or retried across
        # a generation swap) is shed here rather than computed-then-late.
        # Nothing was submitted yet, so this side owns the accounting.
        if deadline is not None and time.monotonic() >= deadline:
            self.metrics.shed.inc()
            self.metrics.deadline_shed.inc()
            raise DeadlineExceeded(
                "request deadline expired while queued",
                retry_after_s=(
                    gen.batcher.retry_after_s() if gen.batcher is not None else None
                ),
            )
        # Brownout ladder, every path: at the shed tier nothing is computed —
        # a 429 with honest Retry-After pricing, tagged with the tier, never
        # a 5xx. Below it the level degrades the pipeline plan instead.
        blevel = 0
        if self.overload is not None:
            blevel = self.overload.brownout_level
            if blevel >= LEVEL_SHED:
                self.overload.count_shed()
                self.metrics.shed.inc()
                raise QueueOverflow(
                    "brownout shed tier active",
                    retry_after_s=(
                        gen.batcher.retry_after_s()
                        if gen.batcher is not None
                        else self.overload.price_retry_after(1.0, 0)
                    ),
                    tier=tier_name(blevel),
                    level=blevel,
                )
        # Cold/missing ALS artifacts: the popularity fallback keeps answering.
        # The degraded counter counts ANSWERED degraded requests only — the
        # no-fallback 503 below is an error, not a degradation.
        if gen.model is None:
            # Any registered sources (popularity and friends) live in the
            # pipeline — a recommenders dict always constructs one, so the
            # pipeline IS the fallback plane. Degraded counts answered
            # requests only; the no-source 503 is an error, not degradation.
            if self.pipeline is None:
                return 503, {
                    "user_id": user_id,
                    "error": "no model loaded and no fallback source",
                    "items": [],
                }
            self.metrics.degraded.inc(reason="cold_artifacts")
            out = self.pipeline.recommend(
                user_id, k, exclude_seen=exclude_seen, deadline=deadline,
                brownout_level=blevel,
            )
            out.setdefault("degraded", []).insert(0, "cold_artifacts")
            return 200, self._pipeline_body(gen, user_id, k, out)

        if self.pipeline is not None:
            extra = {"als": gen.als_source} if gen.als_source is not None else None
            out = self.pipeline.recommend(
                user_id, k, exclude_seen=exclude_seen, extra_sources=extra,
                deadline=deadline, brownout_level=blevel,
            )
            return 200, self._pipeline_body(gen, user_id, k, out)

        if gen.batcher is not None:
            body = self._recommend_batched(gen, user_id, k, exclude_seen, deadline)
        else:
            body = self.recommend(user_id, k=k, exclude_seen=exclude_seen)
        if blevel > 0 and self.overload is not None and not body.get("error"):
            # No pipeline to degrade — the plain path answers at full quality
            # until the shed tier, but the response still carries the tier
            # tag so clients and the harness see the brownout state.
            body["brownout"] = {
                "level": blevel, "tier": tier_name(blevel),
            }
        return (404 if body.get("error") else 200), body

    def _pipeline_body(self, gen: ModelGeneration, user_id: int, k: int, out: dict) -> dict:
        items = out.get("items", [])
        body = {
            "user_id": user_id,
            "k": k,
            "generation": gen.number,
            "stage": out.get("stage"),
            "degraded": out.get("degraded", []),
            "items": [
                {**item, "repo_full_name": self._repo_names.get(item["repo_id"])}
                for item in items
            ],
        }
        if out.get("brownout_level"):
            body["brownout"] = {
                "level": out["brownout_level"],
                "tier": out.get("brownout_tier"),
            }
        return body

    # -------------------------------------------------------- admin search

    def search_repos(self, q: str = "", limit: int = 20) -> list[dict]:
        """RepoInfoAdmin parity: search full_name/description, list language +
        stars + description (``app/admin.py:19-21``)."""
        df = self.repo_info
        if df.empty:
            return []
        if q:
            mask = df["repo_full_name"].fillna("").str.contains(q, case=False, regex=False)
            if "repo_description" in df.columns:
                mask |= df["repo_description"].fillna("").str.contains(q, case=False, regex=False)
            df = df[mask]
        cols = [
            c for c in ("repo_id", "repo_full_name", "repo_language",
                        "repo_stargazers_count", "repo_description")
            if c in df.columns
        ]
        return json.loads(df[cols].head(limit).to_json(orient="records"))

    def search_users(self, q: str = "", limit: int = 20) -> list[dict]:
        """UserInfoAdmin parity: search login/name/company, list name/company/
        location/bio (``app/admin.py:11-13``)."""
        df = self.user_info
        if df.empty:
            return []
        if q:
            mask = pd.Series(False, index=df.index)
            for col in ("user_login", "user_name", "user_company"):
                if col in df.columns:
                    mask |= df[col].fillna("").str.contains(q, case=False, regex=False)
            df = df[mask]
        cols = [
            c for c in ("user_id", "user_login", "user_name", "user_company",
                        "user_location", "user_bio")
            if c in df.columns
        ]
        return json.loads(df[cols].head(limit).to_json(orient="records"))
