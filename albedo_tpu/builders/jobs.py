"""CLI jobs: one per reference entry point.

Reference parity: the L4 ``object ... main`` builders and their Makefile
targets (``make train_als``, ``make train_lr``, ..., ``Makefile:131-218``).
Each job loads the raw tables (file/sqlite source via ``--tables``, else the
synthetic generator), runs its workload, and prints params + metrics the way
the reference ``println``s them; expensive products memoize through the
date-keyed artifact store.

Evaluation protocol matches the builders: train on the FULL star matrix,
sample test users (+ the canary user), recommend top-30, and score NDCG@30
against each user's most recent 30 stars (``ALSRecommenderBuilder.scala:60-105``,
``loadUserActualItemsDF``)."""

from __future__ import annotations

import argparse
import itertools
import threading
import time

import numpy as np

from albedo_tpu.cli import EXIT_FAILURE, EXIT_REFUSED, register_job
from albedo_tpu.datasets import (
    load_or_create_raw_tables,
    load_raw_tables,
    sample_test_users,
    synthetic_tables,
)
from albedo_tpu.datasets.artifacts import load_or_create_pickle
from albedo_tpu.datasets.tables import RawTables, popular_repos
from albedo_tpu.evaluators import RankingEvaluator, UserItems, user_actual_items, user_items_from_pairs
from albedo_tpu.builders.profiles import VINTA_USER_ID, build_repo_profile, build_user_profile

TOP_K = 30

# The flagship ALS artifact's hyperparameter defaults — re-exported from
# the estimator itself (ONE definition), shared with the streaming fold-in
# engine, which must solve with the SAME regularization/alpha the base
# artifact was trained with (a mismatch would bias every folded row
# relative to the refit path).
from albedo_tpu.models.als import ImplicitALS as _ImplicitALS  # noqa: E402

ALS_REG = _ImplicitALS.reg_param
ALS_ALPHA = _ImplicitALS.alpha


class JobContext:
    """Shared lazily-built artifacts for one CLI invocation."""

    def __init__(
        self,
        args: argparse.Namespace,
        tables: RawTables | None = None,
        tag: str | None = None,
    ):
        """``tables``/``tag`` inject a pre-built dataset (and its artifact
        identity) without going through ``--tables`` — used by the bench."""
        self.args = args
        self.small = bool(getattr(args, "small", False))
        now = getattr(args, "now", None)
        self.now = float(now) if now is not None else time.time()
        # Dataset identity tag baked into every artifact name, so a run
        # against different --tables (or synthetic vs real) on the same day
        # can never resume another dataset's cached model.
        from albedo_tpu.settings import md5

        source = str(getattr(args, "tables", None) or f"synthetic-{self.small}")
        if (tables is None) != (tag is None):
            # A tag without its dataset (or vice versa) would stamp artifacts
            # with the wrong identity and resume another dataset's models.
            raise ValueError("inject tables and tag together, or neither")
        self.tag = tag if tag is not None else md5(source)[:10]
        self._cache: dict[str, object] = {}
        # Checkpoint dirs this process has already initialized: a retry of a
        # failed stage must RESUME from this run's own steps, not wipe them.
        self._ckpt_initialized: set[str] = set()
        if tables is not None:
            self._cache["tables"] = tables
        # Persistent executable reuse by default, even when a JobContext is
        # built directly (bench, notebooks) rather than through cli.main —
        # idempotent, and a no-op under --no-compilation-cache /
        # ALBEDO_JAX_CACHE=0.
        if not bool(getattr(args, "no_compilation_cache", False)):
            from albedo_tpu.utils.compilation_cache import (
                enable_persistent_compilation_cache,
            )

            enable_persistent_compilation_cache()

    def artifact_name(self, base: str) -> str:
        return f"{self.tag}-{base}"

    def tables(self) -> RawTables:
        if "tables" not in self._cache:
            path = getattr(self.args, "tables", None)
            if path:
                self._cache["tables"] = load_or_create_raw_tables(
                    lambda: load_raw_tables(path), key=self.artifact_name("raw_tables.pkl")
                )
            else:
                n_users, n_items = (400, 300) if self.small else (5000, 3000)
                self._cache["tables"] = synthetic_tables(
                    n_users=n_users, n_items=n_items, mean_stars=20, seed=42
                )
        return self._cache["tables"]  # type: ignore[return-value]

    def curators(self) -> tuple[int, ...] | None:
        """Single curator policy for curation_job AND the ranker's curation
        source: the reference's hard-coded ids on real tables, the five most
        active users on synthetic data (where those ids don't exist)."""
        if getattr(self.args, "tables", None):
            return None  # CurationRecommender's default CURATOR_IDS
        star = self.tables().starring
        return tuple(star["user_id"].value_counts().index[:5].tolist())

    def data_policy(self) -> str:
        """The ingest firewall policy (``--data-policy strict|repair|off``;
        default ``repair``): strict fails the job on any bad star row,
        repair drops/quarantines bad rows, off is the bare seed path."""
        from albedo_tpu.datasets.validate import default_policy

        return getattr(self.args, "data_policy", None) or default_policy()

    def matrix(self):
        if "matrix" not in self._cache:
            policy = self.data_policy()
            matrix, report = self.tables().validated_star_matrix(
                policy=policy,
                quarantine_name=(
                    self.artifact_name("starring") if policy == "repair" else None
                ),
                now=self.now,
            )
            self._cache["matrix"] = matrix
            self._cache["data_report"] = report
        return self._cache["matrix"]

    def data_report(self):
        """The ingest :class:`~albedo_tpu.datasets.validate.ValidationReport`
        (building the matrix on first call)."""
        self.matrix()
        return self._cache["data_report"]

    def als_solver(self) -> tuple[str, int]:
        """(solver, cg_steps) from the CLI ``--solver``/``--cg-steps`` flags."""
        steps = getattr(self.args, "cg_steps", None)
        return (
            getattr(self.args, "solver", "cholesky") or "cholesky",
            3 if steps is None else int(steps),
        )

    def mesh(self):
        """The training mesh from ``--mesh-devices`` (None = single device),
        built once per context. Fewer visible devices than requested remesh
        down the degraded ladder (loudly, counted) — the same call path a
        checkpointed sharded fit resumes through on a smaller slice."""
        n = int(getattr(self.args, "mesh_devices", 0) or 0)
        if n <= 0:
            return None
        if "mesh" not in self._cache:
            from albedo_tpu.parallel.mesh import make_mesh

            self._cache["mesh"] = make_mesh(n)
        return self._cache["mesh"]

    def mesh_opts(self) -> dict:
        """Estimator kwargs for the mesh fit: ``--sharded`` maps auto ->
        None (the admission ladder decides); ``--shard-mode`` passes
        through. Empty when no mesh is configured."""
        mesh = self.mesh()
        if mesh is None:
            return {}
        sharded = getattr(self.args, "sharded", "auto") or "auto"
        return dict(
            mesh=mesh,
            sharded=None if sharded == "auto" else sharded,
            shard_mode=getattr(self.args, "shard_mode", "allgather") or "allgather",
        )

    def checkpoint_opts(self) -> tuple[int, bool, int | None]:
        """(checkpoint_every, resume, keep_last) from the CLI flags;
        ``--keep-last 0`` means keep every step (maps to None)."""
        keep = getattr(self.args, "keep_last", 3)
        keep = 3 if keep is None else int(keep)
        return (
            int(getattr(self.args, "checkpoint_every", 0) or 0),
            bool(getattr(self.args, "resume", False)),
            keep if keep > 0 else None,
        )

    def checkpointed_als(self, est, matrix, key: str):
        """Preemption-safe ALS fit: checkpoints every ``--checkpoint-every``
        iterations under ``checkpoint_dir/<tag>-<key>``, resumes from the
        newest readable step under ``--resume``, and converts SIGTERM/SIGINT
        into a checkpoint + :class:`~albedo_tpu.utils.checkpoint.Preempted`
        clean exit (the CLI maps it to exit code 75).

        A MESH estimator routes to the ELASTIC driver
        (:func:`~albedo_tpu.parallel.elastic.elastic_sharded_fit`): the
        same preemption/journal/retention contract, plus mesh-portable
        sharded checkpoints (a fit checkpointed on 8 devices resumes on a
        4/2/1-device rung) and mid-fit device-loss remesh-resume."""
        import shutil

        from albedo_tpu.settings import get_settings
        from albedo_tpu.utils.checkpoint import (
            PreemptionHandler,
            checkpointed_als_fit,
        )

        from albedo_tpu.utils.watchdog import DivergenceWatchdog

        every, resume, keep_last = self.checkpoint_opts()
        ckdir = get_settings().checkpoint_dir / self.artifact_name(key)
        if not resume and key not in self._ckpt_initialized and ckdir.exists():
            # A fresh (non-resume) run must not silently adopt stale factors —
            # but only on the FIRST fit per key: an in-process retry (e.g.
            # run_pipeline's stage retry after a transient checkpoint-write
            # error) resumes from the steps this very run just saved instead
            # of deleting them and restarting from iteration 0.
            shutil.rmtree(ckdir)
        self._ckpt_initialized.add(key)
        watchdog = DivergenceWatchdog()
        try:
            with PreemptionHandler() as preemption:
                if est.mesh is not None:
                    from albedo_tpu.parallel.elastic import elastic_sharded_fit

                    return elastic_sharded_fit(
                        est, matrix, ckdir, every=every, keep_last=keep_last,
                        preemption=preemption, watchdog=watchdog,
                    )
                return checkpointed_als_fit(
                    est, matrix, ckdir, every=every, keep_last=keep_last,
                    preemption=preemption, watchdog=watchdog,
                )
        finally:
            # Trips (with remediation outcomes) feed the publish stamp's
            # quality record, even when the fit ultimately diverged.
            if watchdog.trips:
                self._cache.setdefault("watchdog_trips", []).extend(watchdog.trips)

    def star_range(self) -> tuple[int, int]:
        # The reference's popular/profile star windows assume GitHub-scale
        # counts; synthetic tables are smaller.
        if getattr(self.args, "tables", None):
            return (1000, 290_000)
        return (1, 10**9)

    def als_key(self, rank=50, reg=ALS_REG, alpha=ALS_ALPHA, iters=26) -> str:
        """The flagship ALS artifact's base key (hyperparams baked into the
        name, solver-tagged when not the parity default) — one definition
        shared by training, the canary publish gate, and the serve watcher."""
        if self.small:
            rank, iters = 16, 8
        solver, cg_steps = self.als_solver()
        key = f"alsModel-{rank}-{reg}-{alpha}-{iters}"
        if solver != "cholesky":
            key += f"-{solver}{cg_steps}"  # solver-tagged artifact, no mixups
        return key

    def als_artifact_name(self, **kw) -> str:
        return self.artifact_name(self.als_key(**kw) + ".pkl")

    def als_model(self, rank=50, reg=ALS_REG, alpha=ALS_ALPHA, iters=26, on_fit=None):
        """The flagship ALS model, trained (or loaded from today's artifact).

        ``on_fit(estimator, model)`` if given is called right after a REAL
        fit — never on an artifact-store hit — with the estimator (its
        ``last_fit_report``) and the still device-resident model, before the
        factors round-trip through the store. ``chip_smoke.py`` reads the
        admission verdict, compile source and shard placement there."""
        from albedo_tpu.models.als import ImplicitALS

        key = self.als_key(rank=rank, reg=reg, alpha=alpha, iters=iters)
        if self.small:
            rank, iters = 16, 8
        solver, cg_steps = self.als_solver()

        def train():
            est = ImplicitALS(
                rank=rank, reg_param=reg, alpha=alpha, max_iter=iters,
                solver=solver, cg_steps=cg_steps, **self.mesh_opts(),
            )
            every, _, _ = self.checkpoint_opts()
            if every > 0:
                model = self.checkpointed_als(est, self.matrix(), key)
            else:
                # Non-checkpointed fits still run under the divergence
                # watchdog (check-final + one damped re-fit;
                # utils.watchdog.guarded_fit).
                from albedo_tpu.utils.watchdog import guarded_fit

                model, trips = guarded_fit(est, self.matrix())
                if trips:
                    self._cache.setdefault("watchdog_trips", []).extend(trips)
            if on_fit is not None:
                on_fit(est, model)
            return model

        if "als" not in self._cache:
            from albedo_tpu.models.als import ALSModel

            arrays = load_or_create_pickle(
                self.artifact_name(key + ".pkl"), lambda: train().to_arrays()
            )
            self._cache["als"] = ALSModel.from_arrays(arrays)
        return self._cache["als"]

    def profiles(self):
        if "profiles" not in self._cache:
            lo, hi = self.star_range()
            up, uc = build_user_profile(self.tables(), now=self.now)
            rp, rc = build_repo_profile(
                self.tables(), now=self.now, min_stars=max(1, lo // 30), max_stars=hi,
                language_bin_threshold=3 if not getattr(self.args, "tables", None) else 30,
            )
            self._cache["profiles"] = (up, uc, rp, rc)
        return self._cache["profiles"]

    def word2vec_corpus(self) -> list[list[str]]:
        """The reference's W2V corpus (``Word2VecCorpusBuilder.scala:47-69``):
        ``concat_ws(", ", login/name/bio/company/location)`` per user union
        ``concat_ws(", ", owner/name/language/description/topics)`` per repo,
        then the SAME Tokenizer -> StopWordsRemover stages the ranker's
        inference pipeline applies, so corpus vocab and inference tokens
        can never diverge (no punctuation-OOV)."""
        import pandas as pd

        from albedo_tpu.features.text import StopWordsRemover, Tokenizer

        tables = self.tables()

        def concat_ws(df, cols: list[str]):
            parts = [df[c].fillna("").astype(str) for c in cols]
            out = parts[0]
            for p in parts[1:]:
                out = out + ", " + p
            return out

        user_text = concat_ws(
            tables.user_info,
            ["user_login", "user_name", "user_bio", "user_company", "user_location"],
        )
        repo_text = concat_ws(
            tables.repo_info,
            ["repo_owner_username", "repo_name", "repo_language", "repo_description", "repo_topics"],
        )
        corpus_df = pd.DataFrame({"text": list(user_text) + list(repo_text)})
        staged = StopWordsRemover("text__words", "text__filtered").transform(
            Tokenizer("text", "text__words", remove_stop_words=False).transform(corpus_df)
        )
        return list(staged["text__filtered"])

    def word2vec_estimator(self):
        """The configured (untrained) Word2Vec — also what the
        ``train_word2vec`` job's explainParams dump prints.

        Reference config (dim=200, maxIter=30, Word2VecCorpusBuilder.scala:74-83)
        on real ``--tables`` runs or when ``args.w2v_full`` is set (the bench
        sets it so its wall-clock compares apples-to-apples against the 38m58s
        baseline); the small config keeps synthetic/laptop runs snappy."""
        from albedo_tpu.models.word2vec import Word2Vec

        full = bool(getattr(self.args, "w2v_full", False)) or (
            bool(getattr(self.args, "tables", None)) and not self.small
        )
        dim, iters = (200, 30) if full else (16, 3)
        return Word2Vec(
            dim=dim, min_count=3 if self.small else 10, max_iter=iters, subsample=0.0
        )

    def word2vec_artifact_name(self) -> str:
        """The trained-w2v artifact name (one definition — the run_pipeline
        journal records the same name this cache writes)."""
        est = self.word2vec_estimator()
        return self.artifact_name(f"word2VecModel-v2-{est.dim}-{est.max_iter}.pkl")

    def word2vec(self):
        from albedo_tpu.models.word2vec import Word2VecModel

        if "w2v" not in self._cache:
            est = self.word2vec_estimator()

            def train():
                # Corpus built lazily inside the closure: a cache hit on the
                # trained model skips the full-table tokenization pass.
                return est.fit_corpus(self.word2vec_corpus())

            arrays = load_or_create_pickle(
                self.word2vec_artifact_name(), lambda: train().to_arrays()
            )
            self._cache["w2v"] = Word2VecModel(
                vocab=list(arrays["vocab"]), vectors=np.asarray(arrays["vectors"], np.float32)
            )
        return self._cache["w2v"]

    def ranker_model(self):
        """Trained LR :class:`~albedo_tpu.builders.ranker.RankerModel` for
        online re-ranking (``serve --two-stage``). Trained in-process and
        cached per context — the model holds live pipeline stages (w2v, LR
        device arrays), so it memoizes here rather than through the pickle
        store; its ingredients (ALS factors, w2v vectors) still come from
        their date-keyed artifacts."""
        if "ranker" not in self._cache:
            from albedo_tpu.builders.ranker import RankerConfig, train_ranker

            up, uc, rp, rc = self.profiles()
            lo, hi = self.star_range()
            config = RankerConfig(
                popular_min_stars=lo, popular_max_stars=hi,
                min_df=3 if self.small else 10,
            )
            if self.small:
                config = config.small()
            result = train_ranker(
                self.tables(), up, uc, rp, rc, self.als_model(), self.matrix(),
                self.word2vec(), now=self.now, config=config,
            )
            print(f"[serve] ranker trained: AUC = {result.auc:.4f}")
            self._cache["ranker"] = result.model
            self._cache["ranker_auc"] = float(result.auc)
        return self._cache["ranker"]

    def test_user_dense(self, n=250) -> np.ndarray:
        matrix = self.matrix()
        canary = matrix.users_of(np.array([VINTA_USER_ID]))
        extra = canary[canary >= 0]
        return sample_test_users(matrix, n=n, always_include=extra if extra.size else None)

    def evaluate_topk(self, frame) -> float:
        """NDCG@30 of a (user_id, repo_id, score) candidate frame."""
        matrix = self.matrix()
        predicted = user_items_from_pairs(
            matrix.users_of(frame["user_id"].to_numpy(np.int64)),
            matrix.items_of(frame["repo_id"].to_numpy(np.int64)),
            order_key=frame["score"].to_numpy(np.float64),
            k=TOP_K,
        )
        actual = user_actual_items(matrix, k=TOP_K)
        return RankingEvaluator(metric_name="ndcg@k", k=TOP_K).evaluate(predicted, actual)


def _report(job: str, metric_name: str, value: float, t0: float) -> None:
    print(f"[{job}] {metric_name} = {value}")
    print(f"[{job}] wall-clock = {time.time() - t0:.1f}s")


# The counters of ``ImplicitALS.last_fit_report`` the operator's table prints
# beside the spans, with every ``*_share`` key the path's report carries.
_FIT_COUNTERS = (
    "mode", "compile_source", "prep_cached", "dispatches", "rows_per_dispatch",
    "stream_transfers_per_dispatch", "exact_systems_per_sweep", "exact_lane_systems_per_sweep",
)


def _log_fit_table(job: str, estimators: list) -> None:
    """The operator's table (ARCHITECTURE.md "Observability"), on standard
    error after the job's REAL fits: where their seconds went, by span (each
    estimator's ``last_fit_report["spans"]`` in one ``Timer``; of a
    checkpointed fit, its last chunk's), then the counters of the last
    fit's report. An estimator that ran no fit (a checkpoint that was
    already complete) has no report and adds nothing."""
    import sys

    from albedo_tpu.utils.profiling import Timer

    reports = [r for r in (getattr(e, "last_fit_report", None) for e in estimators)
               if r and "spans" in r]
    if not reports:
        return

    def log(row: str) -> None:
        print(f"[{job}] {row}", file=sys.stderr)

    spans = Timer()
    for r in reports:
        spans.absorb(r["spans"])
    log(f"fit spans of {len(reports)} fit(s): seconds, calls, self seconds")
    spans.report(log)
    report = reports[-1]
    counters = {k: report[k] for k in _FIT_COUNTERS if k in report}
    counters.update((k, round(v, 4)) for k, v in report.items() if k.endswith("_share"))
    log("fit counters: " + ", ".join(f"{k}={v}" for k, v in counters.items()))


@register_job("popularity")
def popularity_job(args) -> None:
    """``PopularityRecommenderBuilder`` (NDCG@30 gate 0.00202)."""
    from albedo_tpu.recommenders import PopularityRecommender

    t0 = time.time()
    ctx = JobContext(args)
    lo, hi = ctx.star_range()
    pop = popular_repos(ctx.tables().repo_info, lo, hi)
    rec = PopularityRecommender(pop, top_k=TOP_K)
    users = ctx.matrix().user_ids[ctx.test_user_dense()]
    ndcg = ctx.evaluate_topk(rec.recommend_for_users(users))
    _report("popularity", "NDCG@30", ndcg, t0)


@register_job("curation")
def curation_job(args) -> None:
    """``CurationRecommenderBuilder`` (NDCG@30 gate 0.00319)."""
    from albedo_tpu.recommenders import CurationRecommender

    t0 = time.time()
    ctx = JobContext(args)
    star = ctx.tables().starring
    curators = ctx.curators()
    rec = (
        CurationRecommender(star, curator_ids=curators, top_k=TOP_K)
        if curators
        else CurationRecommender(star, top_k=TOP_K)
    )
    users = ctx.matrix().user_ids[ctx.test_user_dense()]
    ndcg = ctx.evaluate_topk(rec.recommend_for_users(users))
    _report("curation", "NDCG@30", ndcg, t0)


@register_job("content")
def content_job(args) -> None:
    """``ContentRecommenderBuilder`` — embedding MLT backend."""
    from albedo_tpu.recommenders import ContentRecommender, EmbeddingSearchBackend

    t0 = time.time()
    ctx = JobContext(args)
    backend = EmbeddingSearchBackend(ctx.tables().repo_info, ctx.word2vec())
    rec = ContentRecommender(
        backend, ctx.tables().starring, top_k=TOP_K, enable_evaluation_mode=True
    )
    users = ctx.matrix().user_ids[ctx.test_user_dense(100)]
    ndcg = ctx.evaluate_topk(rec.recommend_for_users(users))
    _report("content", "NDCG@30", ndcg, t0)


@register_job("train_als")
def train_als_job(args) -> None:
    """``ALSRecommenderBuilder`` — the flagship (NDCG@30 gate 0.05209)."""
    from albedo_tpu.recommenders import ALSRecommender

    t0 = time.time()
    ctx = JobContext(args)
    # Sparsity print: the PySpark track's calculate_sparsity parity
    # (albedo_toolkit/common.py).
    print(f"[train_als] star-matrix sparsity = {ctx.matrix().sparsity():.6f}")
    # the span table of the real fit; nothing on an artifact-store hit
    model = ctx.als_model(on_fit=lambda est, _model: _log_fit_table("train_als", [est]))
    rec = ALSRecommender(model, ctx.matrix(), top_k=TOP_K)
    users = ctx.matrix().user_ids[ctx.test_user_dense()]
    ndcg = ctx.evaluate_topk(rec.recommend_for_users(users))
    _report("train_als", "NDCG@30", ndcg, t0)


@register_job("cv_als")
def cv_als_job(args) -> None:
    """``ALSRecommenderCV`` — 2-fold grid over rank x regParam x alpha."""
    from albedo_tpu.cv import cross_validate, param_grid
    from albedo_tpu.models.als import ImplicitALS
    from albedo_tpu.recommenders import ALSRecommender

    t0 = time.time()
    ctx = JobContext(args)
    fitted = []   # every estimator of the grid, for one span table
    grid = (
        param_grid(rank=[8, 16], reg_param=[0.1, 0.5], alpha=[1.0, 40.0])
        if ctx.small or not getattr(args, "tables", None)
        else param_grid(rank=[50, 100], reg_param=[0.01, 0.5], alpha=[0.01, 40.0])
    )
    iters = 6 if ctx.small else 13

    solver, cg_steps = ctx.als_solver()

    fit_no = itertools.count()

    def fit(params, train):
        est = ImplicitALS(max_iter=iters, solver=solver, cg_steps=cg_steps, **params)
        fitted.append(est)
        every, _, _ = ctx.checkpoint_opts()
        if every > 0:
            # Per-(params, fold) checkpoint identity. cross_validate iterates
            # params x folds in a deterministic order, so the sequential fit
            # number is stable across reruns and -- unlike shape/nnz alone --
            # can never collide between folds (two folds with equal nnz would
            # otherwise share a dir and --resume would hand fold 2 fold 1's
            # trained factors).
            from albedo_tpu.settings import md5

            key = md5(f"{sorted(params.items())}-fit{next(fit_no)}")[:12]
            return ctx.checkpointed_als(est, train, f"cvALS-{key}")
        return est.fit(train)

    def evaluate(model, train, test):
        users = sample_test_users(test, n=150)
        rec_frame = ALSRecommender(model, train, top_k=TOP_K).recommend_for_users(
            train.user_ids[users]
        )
        predicted = user_items_from_pairs(
            train.users_of(rec_frame["user_id"].to_numpy(np.int64)),
            train.items_of(rec_frame["repo_id"].to_numpy(np.int64)),
            order_key=rec_frame["score"].to_numpy(np.float64),
            k=TOP_K,
        )
        return RankingEvaluator(metric_name="ndcg@k", k=TOP_K).evaluate(
            predicted, user_actual_items(test, k=TOP_K)
        )

    results = cross_validate(fit, evaluate, ctx.matrix(), grid, n_folds=2, verbose=True)
    best = results[0]
    _log_fit_table("cv_als", fitted)
    print(f"[cv_als] best params = {best.params}")
    _report("cv_als", "NDCG@30", best.mean_metric, t0)


@register_job("build_user_profile")
def build_user_profile_job(args) -> None:
    from albedo_tpu.datasets.artifacts import load_or_create_df

    t0 = time.time()
    ctx = JobContext(args)
    df = load_or_create_df(
        ctx.artifact_name("userProfileDF.parquet"), lambda: ctx.profiles()[0]
    )
    _report("build_user_profile", "rows", float(len(df)), t0)


@register_job("build_repo_profile")
def build_repo_profile_job(args) -> None:
    from albedo_tpu.datasets.artifacts import load_or_create_df

    t0 = time.time()
    ctx = JobContext(args)
    df = load_or_create_df(
        ctx.artifact_name("repoProfileDF.parquet"), lambda: ctx.profiles()[2]
    )
    _report("build_repo_profile", "rows", float(len(df)), t0)


@register_job("train_word2vec")
def train_word2vec_job(args) -> None:
    """``Word2VecCorpusBuilder`` (explainParams dump parity, :85)."""
    from albedo_tpu.utils.params import explain_params

    t0 = time.time()
    ctx = JobContext(args)
    print(f"[train_word2vec] {explain_params(ctx.word2vec_estimator())}")
    model = ctx.word2vec()
    _report("train_word2vec", "vocab", float(len(model.vocab)), t0)


@register_job("train_lr")
def train_lr_job(args) -> None:
    """``LogisticRegressionRanker`` (AUC gate 0.9425, NDCG@30 gate 0.0211)."""
    from albedo_tpu.builders.ranker import RankerConfig, train_ranker
    from albedo_tpu.recommenders import ALSRecommender, CurationRecommender, PopularityRecommender

    t0 = time.time()
    ctx = JobContext(args)
    up, uc, rp, rc = ctx.profiles()
    als = ctx.als_model()
    lo, hi = ctx.star_range()
    config = RankerConfig(popular_min_stars=lo, popular_max_stars=hi, min_df=3 if ctx.small else 10)
    if ctx.small:
        config = config.small()
    star = ctx.tables().starring
    curators = ctx.curators()
    recs = [
        ALSRecommender(als, ctx.matrix(), top_k=60),
        CurationRecommender(star, curator_ids=curators, top_k=TOP_K)
        if curators
        else CurationRecommender(star, top_k=TOP_K),
        PopularityRecommender(popular_repos(ctx.tables().repo_info, lo, hi), top_k=TOP_K),
    ]
    result = train_ranker(
        ctx.tables(), up, uc, rp, rc, als, ctx.matrix(), ctx.word2vec(),
        now=ctx.now, config=config, recommenders=recs,
    )
    print(f"[train_lr] areaUnderROC = {result.auc}")
    _report("train_lr", "NDCG@30", result.ndcg or 0.0, t0)


def _holdout_cf_ndcg(ctx: JobContext, rec_cls) -> float:
    """NDCG@30 for the memory-based CFs under a held-out split.

    The CF recommenders drop the user's own stars from the ranked list
    (``train_item_cf.py:38`` behavior), so the full-matrix protocol the other
    builders use (actual = recent stars the model trained on) would score an
    exact 0 by construction; they are evaluated on held-out stars instead:
    fit on the train split, recommend with train stars excluded, score
    against each user's held-out items."""
    from albedo_tpu.datasets import random_split_by_user

    matrix = ctx.matrix()
    train, test = random_split_by_user(matrix, test_ratio=0.1, seed=42)
    rec = rec_cls(train, top_k=TOP_K)
    users_dense = sample_test_users(test, n=250, seed=42)
    frame = rec.recommend_for_users(matrix.user_ids[users_dense])
    predicted = user_items_from_pairs(
        matrix.users_of(frame["user_id"].to_numpy(np.int64)),
        matrix.items_of(frame["repo_id"].to_numpy(np.int64)),
        order_key=frame["score"].to_numpy(np.float64),
        k=TOP_K,
    )
    actual = user_actual_items(test, k=TOP_K)
    return RankingEvaluator(metric_name="ndcg@k", k=TOP_K).evaluate(predicted, actual)


@register_job("item_cf")
def item_cf_job(args) -> None:
    """``train_item_cf`` legacy-trainer parity: item-item cosine CF, NDCG@30
    on a held-out split."""
    from albedo_tpu.recommenders.cf import ItemCFRecommender

    t0 = time.time()
    ndcg = _holdout_cf_ndcg(JobContext(args), ItemCFRecommender)
    _report("item_cf", "NDCG@30", ndcg, t0)


@register_job("user_cf")
def user_cf_job(args) -> None:
    """``train_user_cf`` legacy-trainer parity: user-user dice CF, NDCG@30 on
    a held-out split."""
    from albedo_tpu.recommenders.cf import UserCFRecommender

    t0 = time.time()
    ndcg = _holdout_cf_ndcg(JobContext(args), UserCFRecommender)
    _report("user_cf", "NDCG@30", ndcg, t0)


@register_job("ranking_mf")
def ranking_mf_job(args) -> None:
    """``train_graphlab`` legacy-trainer parity: ranking factorization on the
    binary star matrix (binary_target=True, split by user, top-50 with known
    items excluded — ``train_graphlab.py:23-34``), with repo side features
    (log-stars/forks) as the linear side-data term; NDCG@30 on the held-out
    split plus the canary user's top list."""
    from albedo_tpu.datasets import random_split_by_user
    from albedo_tpu.datasets.ragged import padded_rows
    from albedo_tpu.models.ranking_factorization import RankingFactorization

    t0 = time.time()
    ctx = JobContext(args)
    matrix = ctx.matrix()
    train, test = random_split_by_user(matrix, test_ratio=0.2, seed=42)

    # Side data: per-repo activity features, standardized (the reference's
    # side-data path; its own invocation passes none, so these are additive).
    repo = ctx.tables().repo_info.set_index("repo_id").reindex(matrix.item_ids)
    side = np.stack(
        [
            np.log1p(repo["repo_stargazers_count"].fillna(0).to_numpy(np.float64)),
            np.log1p(repo["repo_forks_count"].fillna(0).to_numpy(np.float64)),
        ],
        axis=1,
    )
    side = (side - side.mean(axis=0)) / np.maximum(side.std(axis=0), 1e-9)

    mf = RankingFactorization(
        rank=16 if ctx.small else 32, epochs=5 if ctx.small else 10,
        batch_size=1024 if ctx.small else 8192,
    )
    model = mf.fit(train, item_side=side.astype(np.float32))

    users_dense = sample_test_users(test, n=250, seed=42)
    indptr, cols_arr, _ = train.csr()
    excl = padded_rows(indptr, cols_arr, users_dense)
    _, idx = model.recommend(users_dense, k=TOP_K, exclude_idx=excl)
    predicted = UserItems(users=users_dense, items=idx.astype(np.int32))
    ndcg = RankingEvaluator(metric_name="ndcg@k", k=TOP_K).evaluate(
        predicted, user_actual_items(test, k=TOP_K)
    )
    _report("ranking_mf", "NDCG@30", ndcg, t0)


@register_job("tfidf_content")
def tfidf_content_job(args) -> None:
    """``train_content_based`` legacy-trainer parity: tf-idf similar-repo
    search. Prints the most-similar repos for the most-starred repo (the
    reference prints a query's top-49, ``train_content_based.py:62-66``) and
    reports indexed-corpus size."""
    from albedo_tpu.recommenders.tfidf import TfidfSimilaritySearch

    t0 = time.time()
    ctx = JobContext(args)
    repo = ctx.tables().repo_info
    search = TfidfSimilaritySearch(min_df=2).fit(repo)
    top_repo = repo.sort_values("repo_stargazers_count", ascending=False).iloc[0]
    for score, name in search.similar(str(top_repo["repo_full_name"]), k=10):
        print(f"[tfidf_content] {score:.4f} {name}")
    _report("tfidf_content", "indexed_repos", float(len(search.doc_ids)), t0)


def _context_bank(ctx, with_user_sim: bool = False, with_als: bool = True):
    """Assemble the default retrieval bank from this context's trained
    artifacts (ALS factors + the Word2Vec content index + the TF-IDF
    projection) — one definition shared by ``build_bank`` and
    ``serve --bank`` (which passes ``with_als=False``: its stage serves
    only the MLT sources, so the factor tables must not be pinned or
    capacity-priced twice)."""
    from albedo_tpu.recommenders import EmbeddingSearchBackend
    from albedo_tpu.recommenders.tfidf import TfidfSimilaritySearch
    from albedo_tpu.retrieval.build import build_default_bank

    tables = ctx.tables()
    backend = EmbeddingSearchBackend(tables.repo_info, ctx.word2vec())
    search = TfidfSimilaritySearch(min_df=2).fit(tables.repo_info)
    bank = build_default_bank(
        ctx.als_model(), ctx.matrix(),
        starring_df=tables.starring,
        content_backend=backend, tfidf_search=search,
        with_user_sim=with_user_sim, with_als=with_als, top_k=TOP_K,
    )
    return bank, backend, search


@register_job("build_bank")
def build_bank_job(args) -> int | None:
    """Build (or inspect) the unified retrieval bank: every embedding-backed
    candidate source — ALS factors, Word2Vec content embeddings, the TF-IDF
    projection, optionally the user-similarity table — sealed into ONE
    stamped, manifest-sealed device-servable artifact
    (``albedo_tpu.retrieval``; see the README retrieval runbook).

    Extra flags: --user-sim (register the user-to-user source),
    --inspect (print the existing artifact's stamp and exit).
    """
    from albedo_tpu.datasets.artifacts import read_meta, artifact_path
    from albedo_tpu.retrieval import bank_artifact_name

    t0 = time.time()
    extra = argparse.ArgumentParser()
    extra.add_argument("--user-sim", action="store_true")
    extra.add_argument("--inspect", action="store_true")
    ns, _ = extra.parse_known_args(getattr(args, "_rest", []))

    ctx = JobContext(args)
    name = bank_artifact_name(ctx.tag)
    if ns.inspect:
        meta = read_meta(artifact_path(name))
        if meta is None:
            print(f"[build_bank] no stamped bank at {name}")
            return EXIT_FAILURE
        import json as _json

        print(_json.dumps(meta.get("bank", meta), indent=2))
        return None
    bank, _, _ = _context_bank(ctx, with_user_sim=ns.user_sim)
    bank.save(name, lineage={
        "als_artifact": ctx.als_artifact_name(),
        "word2vec_artifact": ctx.word2vec_artifact_name(),
        "tag": ctx.tag,
    })
    for sname, info in bank.manifest()["sources"].items():
        print(
            f"[build_bank] {sname}: {info['rows']} rows x {info['dim']} dims "
            f"(calibration scale {info['calibration'].get('scale')})"
        )
    print(f"[build_bank] sealed {name} (version {bank.version})")
    _report("build_bank", "sources", float(len(bank.specs)), t0)


def serve_options(rest: list[str]) -> argparse.Namespace:
    """The ``serve`` job's own flags (see :func:`serve_job`)."""
    extra = argparse.ArgumentParser()
    extra.add_argument("--port", type=int, default=8080)
    extra.add_argument("--host", default="127.0.0.1")
    extra.add_argument("--duration", type=float, default=0.0)
    extra.add_argument("--no-batch", action="store_true")
    extra.add_argument("--no-warm", action="store_true")
    extra.add_argument("--two-stage", action="store_true")
    extra.add_argument("--cache-ttl", type=float, default=30.0)
    extra.add_argument("--max-batch", type=int, default=64)
    extra.add_argument("--window-ms", type=float, default=2.0)
    extra.add_argument("--reload-watch", action="store_true")
    extra.add_argument("--reload-interval", type=float, default=10.0)
    extra.add_argument("--reload-require-stamp", action="store_true")
    extra.add_argument("--bank", action="store_true")
    ns, _ = extra.parse_known_args(rest)
    return ns


def build_serving(ctx: JobContext, ns: argparse.Namespace):
    """Assemble the online engine the way ``serve`` runs it: the
    :class:`~albedo_tpu.serving.RecommendationService` over this context's
    trained artifacts (two-stage sources, LR ranker and bank stage per
    ``ns``) plus its hot-swap manager. One definition shared by
    :func:`serve_job` and ``chip_smoke.py``; the caller starts the HTTP
    server (``serving.serve``) and owns shutdown."""
    from albedo_tpu.recommenders import CurationRecommender, PopularityRecommender
    from albedo_tpu.serving import HotSwapManager, RecommendationService

    recommenders = None
    ranker = None
    bank_stage = None
    if ns.bank and not ns.two_stage:
        import sys

        print(
            "[serve] --bank requires --two-stage (the bank is a stage-1 "
            "candidate plane); ignoring --bank", file=sys.stderr,
        )
    if ns.two_stage:
        lo, hi = ctx.star_range()
        recommenders = {
            "popularity": PopularityRecommender(
                popular_repos(ctx.tables().repo_info, lo, hi), top_k=TOP_K
            ),
            "curation": CurationRecommender(
                ctx.tables().starring,
                **({"curator_ids": ctx.curators()} if ctx.curators() else {}),
                top_k=TOP_K,
            ),
        }
        ranker = ctx.ranker_model()
        if ns.bank:
            # The bank-backed candidate stage: content + tfidf answered in
            # one fused device pass (the "als" rows stay on the generation-
            # snapshot batcher source, so hot swaps keep their invariant).
            from albedo_tpu.recommenders import ContentRecommender, TfidfRecommender
            from albedo_tpu.retrieval import BankStage

            bank, content_backend, search = _context_bank(ctx, with_als=False)
            tables = ctx.tables()
            fallbacks = {
                "content": ContentRecommender(
                    content_backend, tables.starring, top_k=TOP_K
                ),
                "tfidf": TfidfRecommender(search, tables.starring, top_k=TOP_K),
            }
            bank_stage = BankStage(
                bank, ctx.matrix(),
                sources=("content", "tfidf"), fallbacks=fallbacks, top_k=TOP_K,
            )
    service = RecommendationService(
        ctx.als_model(), ctx.matrix(),
        repo_info=ctx.tables().repo_info, user_info=ctx.tables().user_info,
        recommenders=recommenders, ranker=ranker,
        batching=not ns.no_batch, warm=not ns.no_batch and not ns.no_warm,
        cache_ttl=ns.cache_ttl, max_batch=ns.max_batch,
        batch_window_ms=ns.window_ms, bank_stage=bank_stage,
    )
    # Live-ops plane: the hot-swap manager always exists (SIGHUP and
    # POST /admin/reload work out of the box); --reload-watch additionally
    # polls the store so the compose ingest->train->serve loop picks up
    # fresh artifacts with no restart and no signal.
    manager = HotSwapManager(
        service,
        artifact_glob=f"{ctx.tag}-alsModel-*.pkl",
        watch_interval_s=ns.reload_interval,
        require_stamp=ns.reload_require_stamp,
    )
    return service, manager


@register_job("serve")
def serve_job(args) -> None:
    """The online inference engine over trained artifacts: micro-batched
    top-k, optional two-stage candidate fan-out + LR re-rank, TTL result
    cache, and the `/metrics` Prometheus plane (``albedo_tpu.serving``).

    Extra flags: --port N (default 8080), --host ADDR (default 127.0.0.1;
    use 0.0.0.0 inside containers), --duration SECONDS (0 = forever),
    --no-batch (direct per-request GEMMs, the seed path), --no-warm (skip
    pre-compiling the batch-shape ladder), --two-stage (register the
    popularity + curation candidate sources and train/load the LR ranker
    for online re-ranking), --cache-ttl SECONDS (default 30; 0 disables),
    --max-batch N (default 64), --window-ms MS (batching window, default 2),
    --reload-watch (poll the artifact store and hot-swap fresh run_pipeline
    outputs through the validation gates), --reload-interval SECONDS (watch
    poll period, default 10). SIGHUP triggers one validated reload
    immediately (watched or not), and POST /admin/reload does the same over
    HTTP — see the README live-ops runbook.
    """
    import signal

    from albedo_tpu.serving import serve

    ns = serve_options(getattr(args, "_rest", []))
    ctx = JobContext(args)
    service, manager = build_serving(ctx, ns)
    if ns.reload_watch:
        manager.start_watch()
    if hasattr(signal, "SIGHUP"):
        def _sighup(_sig, _frame):
            # Reload on a worker thread: gates + batcher warm are seconds of
            # work and a signal handler must not block the main thread.
            threading.Thread(
                target=manager.request_reload, name="albedo-sighup-reload",
                daemon=True,
            ).start()

        signal.signal(signal.SIGHUP, _sighup)

    server = serve(service, host=ns.host, port=ns.port)
    host, port = server.server_address[:2]
    mode = "two-stage" if ns.two_stage else "als"
    print(f"[serve] listening on http://{host}:{port}/ "
          f"(/recommend/<user_id>, /admin/repos, /admin/users, /metrics, "
          f"/healthz/ready; POST /admin/reload) "
          f"[{mode}, batching={'off' if ns.no_batch else 'on'}, "
          f"cache_ttl={ns.cache_ttl:g}s, "
          f"reload={'watch' if ns.reload_watch else 'on-demand'}]")
    # Signal-interruptible foreground wait: SIGTERM/SIGINT set the stop
    # event instead of tearing the process down mid-batch, and the finally
    # block runs the full drain (reload watcher stopped, batcher drained,
    # pipeline pool shut down, server thread joined) — a scheduler
    # terminating the job gets the same clean shutdown as Ctrl-C.
    stop = threading.Event()

    def _sigstop(_sig, _frame):
        stop.set()
        # First signal starts the clean drain; hand the handlers back to
        # the defaults so a SECOND Ctrl-C/SIGTERM can still kill a wedged
        # shutdown instead of being swallowed by an already-set event.
        for s in (signal.SIGTERM, signal.SIGINT):
            signal.signal(s, signal.SIG_DFL)

    for _sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(_sig, _sigstop)
    try:
        stop.wait(ns.duration if ns.duration > 0 else None)
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()


@register_job("play")
def play_job(args) -> None:
    """``Playground`` parity (``Playground.scala:44-75``): the manual
    scratchpad — load raw tables, fit a quick ALS, save it through the
    artifact store, and print the canary user's top repos."""
    from albedo_tpu.models.als import ALSModel, ImplicitALS

    t0 = time.time()
    ctx = JobContext(args)
    matrix = ctx.matrix()
    arrays = load_or_create_pickle(
        ctx.artifact_name("playgroundALS.pkl"),
        lambda: ImplicitALS(rank=16, max_iter=8).fit(matrix).to_arrays(),
    )
    model = ALSModel.from_arrays(arrays)
    users = ctx.test_user_dense(n=1)
    _, idx = model.recommend(users[:1], k=10)
    for rank, item in enumerate(idx[0], 1):
        print(f"[play] {rank}. repo {matrix.item_ids[int(item)]}")
    _report("play", "rank", float(model.rank), t0)


@register_job("collect_data")
def collect_data_job(args) -> None:
    """``collect_data`` Django command parity: crawl GitHub into a sqlite
    store. Requires network unless a fake transport is injected in tests.

    Extra flags (parsed here): --db PATH, --seed-users a,b,c, --token T[,T2].
    """
    from albedo_tpu.store import EntityStore, GitHubCrawler

    t0 = time.time()
    extra = argparse.ArgumentParser()
    extra.add_argument("--db", default="albedo-crawl.db")
    extra.add_argument("--seed-users", default="vinta")
    extra.add_argument("--token", default="")
    ns, _ = extra.parse_known_args(getattr(args, "_rest", []))
    with EntityStore(ns.db) as store:
        with GitHubCrawler(store, tokens=ns.token.split(",")) as crawler:
            stats = crawler.collect([u for u in ns.seed_users.split(",") if u])
        print(f"[collect_data] {stats}")
    _report("collect_data", "requests", float(stats.requests), t0)


@register_job("drop_data")
def drop_data_job(args) -> None:
    """``drop_data`` Django command parity: truncate the crawl store's tables
    (``drop_data.py:11-13``). Extra flags: --db PATH, --yes (required)."""
    from albedo_tpu.store import EntityStore

    t0 = time.time()
    extra = argparse.ArgumentParser()
    extra.add_argument("--db", default="albedo-crawl.db")
    extra.add_argument("--yes", action="store_true",
                       help="required confirmation; refuses to truncate without it")
    ns, _ = extra.parse_known_args(getattr(args, "_rest", []))
    if not ns.yes:
        import sys

        print("[drop_data] refusing to truncate without --yes", file=sys.stderr)
        return EXIT_REFUSED  # automation must not mistake a refusal for success
    with EntityStore(ns.db) as store:
        before = store.counts()
        store.drop_data()
        print(f"[drop_data] truncated {before}")
    _report("drop_data", "rows_dropped", float(sum(before.values())), t0)


@register_job("sync_index")
def sync_index_job(args) -> None:
    """``sync_data_to_es`` parity: build the content embedding index."""
    from albedo_tpu.store import build_content_index

    t0 = time.time()
    ctx = JobContext(args)
    lo, hi = (10, 290_000) if getattr(args, "tables", None) else (1, 10**9)
    backend = build_content_index(
        ctx.tables().repo_info, ctx.word2vec(), min_stars=lo, max_stars=hi,
        artifact_name=ctx.artifact_name("contentIndex-v2.npz"),
    )
    _report("sync_index", "indexed_repos", float(len(backend.item_ids)), t0)


@register_job("datacheck")
def datacheck_job(args) -> int | None:
    """Standalone run of the ingest data-quality firewall (``make datacheck``):
    evaluates every rule in ``datasets.validate`` against the configured
    dataset (``--tables`` or synthetic), prints per-rule counts, mutates and
    quarantines NOTHING, and exits 1 when violations exist so CI can gate on
    dataset health before a training run spends accelerator time."""
    from albedo_tpu.datasets.validate import validate_starring

    t0 = time.time()
    ctx = JobContext(args)
    tables = ctx.tables()
    s = tables.starring.sort_values("starred_at", kind="stable")
    _, report = validate_starring(
        s,
        user_vocab=tables.user_info["user_id"].to_numpy(np.int64)
        if len(tables.user_info) else None,
        repo_vocab=tables.repo_info["repo_id"].to_numpy(np.int64)
        if len(tables.repo_info) else None,
        now=ctx.now,
        policy="repair",  # evaluate + count every rule; report-only, no sidecar
        quarantine_name=None,
    )
    for rule, count in sorted(report.violations.items()):
        print(f"[datacheck] {rule}: {count}")
    print(f"[datacheck] rows = {report.rows_in} -> {report.rows_out} "
          f"(policy would drop {report.total})")
    _report("datacheck", "violations", float(report.total), t0)
    return EXIT_FAILURE if report.total else None


@register_job("cv_lr")
def cv_lr_job(args) -> None:
    """``LogisticRegressionRankerCV`` — grid over instance-weight columns.

    The featurized set is built ONCE and the five weight-column LR fits run
    as a single vmapped L-BFGS solve (``LogisticRegression.fit_many``), the
    reference CV's materialize-once-then-grid structure
    (``LogisticRegressionRankerCV.scala:275-288,326-332``)."""
    from albedo_tpu.builders.ranker import RankerConfig, train_ranker
    from albedo_tpu.features.weights import WEIGHT_COLUMNS

    t0 = time.time()
    ctx = JobContext(args)
    up, uc, rp, rc = ctx.profiles()
    als = ctx.als_model()
    lo, hi = ctx.star_range()
    config = RankerConfig(
        popular_min_stars=lo, popular_max_stars=hi,
        min_df=3 if ctx.small else 10, lr_max_iter=60 if ctx.small else 300,
    )
    if ctx.small:
        config = config.small()
    r = train_ranker(
        ctx.tables(), up, uc, rp, rc, als, ctx.matrix(), ctx.word2vec(),
        now=ctx.now, config=config, weight_cols=WEIGHT_COLUMNS,
    )
    for weight_col, auc in r.grid:
        print(f"[cv_lr] {weight_col} -> AUC {auc:.6f}")
    best = r.grid[0]
    print(f"[cv_lr] best weight column = {best[0]}")
    _report("cv_lr", "AUC", best[1], t0)
