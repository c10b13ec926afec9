"""The bank-backed candidate stage inside the serving plane: fused stage-1
answers, the bank-failure -> host-fallback edge of the degradation matrix
(tags + counters over real HTTP), snapshot precedence, and readiness."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from albedo_tpu.datasets import synthetic_tables  # noqa: E402
from albedo_tpu.datasets.ragged import padded_rows  # noqa: E402
from albedo_tpu.datasets.tables import popular_repos  # noqa: E402
from albedo_tpu.models.als import ImplicitALS  # noqa: E402
from albedo_tpu.recommenders import (  # noqa: E402
    ALSRecommender,
    PopularityRecommender,
    TfidfRecommender,
    TfidfSimilaritySearch,
)
from albedo_tpu.retrieval import BankStage, RetrievalBank  # noqa: E402
from albedo_tpu.serving import RecommendationService, serve  # noqa: E402
from albedo_tpu.serving.overload import OverloadConfig  # noqa: E402
from albedo_tpu.serving.pipeline import StageDeadlines, TwoStagePipeline  # noqa: E402
from albedo_tpu.utils import events, faults  # noqa: E402

K = 10
# For the tests that assert an UNDEGRADED answer: stage budgets, a bank wait
# and an overload SLO that no machine's load can reach. The defaults (2 s of
# candidates, 0.5 s of ranker, a 1 s bank wait, a 0.25 s batch SLO) are real
# deadlines, and a first request that compiles, on cores the rest of the
# suite is loading, misses them and is answered degraded — rightly.
PATIENT_S = 600.0
PATIENT_DEADLINES = StageDeadlines(candidates_s=PATIENT_S, ranker_s=PATIENT_S)


@pytest.fixture(scope="module")
def world():
    tables = synthetic_tables(n_users=120, n_items=90, mean_stars=8, seed=5)
    matrix = tables.star_matrix()
    model = ImplicitALS(rank=8, max_iter=3, seed=0).fit(matrix)
    als = ALSRecommender(model, matrix, exclude_seen=True, top_k=K)
    search = TfidfSimilaritySearch(min_df=1).fit(tables.repo_info)
    tfidf = TfidfRecommender(search, tables.starring, top_k=K)
    pop = PopularityRecommender(
        popular_repos(tables.repo_info, 1, 10**9), top_k=K
    )
    return tables, matrix, model, als, tfidf, pop


def _stage(world, timeout_s=1.0):
    tables, matrix, model, als, tfidf, _pop = world
    indptr, cols, _ = matrix.csr()
    excl = padded_rows(indptr, cols, np.arange(matrix.n_users))
    bank = RetrievalBank()
    bank.register(als.bank_registration())
    bank.register(tfidf.bank_registration())
    bank.build(matrix=matrix, exclude_table=excl)
    return BankStage(
        bank, matrix, fallbacks={"als": als, "tfidf": tfidf}, top_k=K,
        timeout_s=timeout_s,
    )


def test_bank_serves_its_sources_threaded_sources_stay(world):
    _tables, matrix, _model, als, tfidf, pop = world
    pipe = TwoStagePipeline(
        {"als": als, "tfidf": tfidf, "popularity": pop},
        bank_stage=_stage(world, timeout_s=PATIENT_S),
        # this first request compiles the bank's fused query
        deadlines=PATIENT_DEADLINES,
    )
    try:
        out = pipe.recommend(int(matrix.user_ids[0]), 30)
        assert out["degraded"] == []
        sources = {i["source"] for i in out["items"]}
        assert {"als", "popularity"} <= sources
        # No breaker exists for bank-served sources — they never ran on the
        # threaded path; popularity (threaded) gets one on first use.
        assert "als" not in pipe.breakers and "tfidf" not in pipe.breakers
        assert "popularity" in pipe.breakers
    finally:
        pipe.close()


def test_bank_error_falls_back_to_host_per_source_path(world):
    _tables, matrix, _model, als, tfidf, pop = world
    pipe = TwoStagePipeline(
        {"als": als, "tfidf": tfidf, "popularity": pop}, bank_stage=_stage(world)
    )
    try:
        uid = int(matrix.user_ids[0])
        baseline = pipe.recommend(uid, 30)
        faults.arm("retrieval.query", "error", at=1)
        out = pipe.recommend(uid, 30)
        assert "bank_error" in out["degraded"]
        assert events.retrieval_fallbacks.value(reason="bank_error") == 1
        # The fallback really ran the host path: same sources still answer.
        assert {i["source"] for i in out["items"]} == {
            i["source"] for i in baseline["items"]
        }
        # The next request (fault exhausted) is clean again.
        after = pipe.recommend(uid, 30)
        assert after["degraded"] == []
    finally:
        pipe.close()


def test_bank_timeout_tagged_and_host_path_answers(world):
    _tables, matrix, _model, als, tfidf, pop = world
    pipe = TwoStagePipeline(
        {"als": als, "tfidf": tfidf, "popularity": pop},
        bank_stage=_stage(world),
        deadlines=StageDeadlines(candidates_s=2.0),
    )
    try:
        uid = int(matrix.user_ids[0])
        baseline = pipe.recommend(uid, 30)  # warm every executable first
        faults.arm("retrieval.query", "delay", at=1, param=3.0)
        out = pipe.recommend(uid, 30)
        assert "bank_timeout" in out["degraded"]
        assert events.retrieval_fallbacks.value(reason="bank_timeout") == 1
        # Not a 500 — and the HOST fallback really answered the covered
        # sources (the bank's wait is capped at half the stage budget, so
        # the fallback had real time, not a zero-budget collect).
        assert {i["source"] for i in out["items"]} == {
            i["source"] for i in baseline["items"]
        }
        assert not any(d.startswith("candidate_timeout") for d in out["degraded"])
    finally:
        pipe.close()


def test_generation_snapshot_als_wins_over_bank_als(world):
    import pandas as pd

    _tables, matrix, _model, als, tfidf, pop = world
    # asserts an undegraded answer, and its first request may compile
    stage = _stage(world, timeout_s=PATIENT_S)
    pipe = TwoStagePipeline({"popularity": pop}, bank_stage=stage, deadlines=PATIENT_DEADLINES)

    calls = {"n": 0}
    marker_repo = int(matrix.item_ids[0])

    class SnapshotALS(ALSRecommender):
        """Returns a DISTINCTIVE frame — if the bank's als rows clobbered
        the snapshot's, the marker would vanish from the response."""

        def recommend_for_users(self, user_ids, **kw):
            calls["n"] += 1
            return pd.DataFrame({
                "user_id": np.asarray(user_ids, np.int64),
                "repo_id": np.full(len(user_ids), marker_repo, np.int64),
                "score": np.full(len(user_ids), 999.0),
                "source": "als",
            })

    snap = SnapshotALS(als.model, matrix, exclude_seen=True, top_k=K)
    try:
        out = pipe.recommend(
            int(matrix.user_ids[0]), 30, extra_sources={"als": snap}
        )
        assert calls["n"] == 1  # the snapshot source answered, not the bank
        assert out["degraded"] == []
        als_items = [i for i in out["items"] if i["source"] == "als"]
        assert als_items and als_items[0]["repo_id"] == marker_repo, (
            "the bank's als frame clobbered the generation snapshot's"
        )
    finally:
        pipe.close()


def test_stage_forwards_overlay_to_promoted_bank(world):
    """Fold-in subscribers attach the STAGE: publishes after a promotion
    must land in the newly promoted bank, not the retired one."""
    _tables, matrix, model, als, _tfidf, _pop = world
    stage = _stage(world)
    old_bank = stage.bank
    old_bank.save("test-stage-forward.pkl")
    assert stage.reload("test-stage-forward.pkl")["outcome"] == "promoted"
    new_bank = stage.bank
    assert new_bank is not old_bank
    fresh = np.random.default_rng(1).normal(size=(1, model.rank)).astype(np.float32)
    stage.publish_user_rows("als", np.array([0]), fresh)
    assert new_bank.overlay_generation == 1
    assert old_bank.overlay_generation == 0


def test_end_to_end_ndcg_unchanged_by_bank(world):
    """The acceptance bound: candidate NDCG@30 through the full pipeline is
    the same whether stage 1 fans out host threads or queries the bank —
    candidate parity per source implies end-to-end quality parity, and this
    pins it on the actual recommend() path."""
    from albedo_tpu.evaluators import (
        RankingEvaluator,
        user_actual_items,
        user_items_from_pairs,
    )

    _tables, matrix, _model, als, tfidf, pop = world
    sources = {"als": als, "tfidf": tfidf, "popularity": pop}
    fanout = TwoStagePipeline(dict(sources))
    banked = TwoStagePipeline(dict(sources), bank_stage=_stage(world))
    try:
        probe = np.arange(0, matrix.n_users, 4, dtype=np.int64)[:40]
        scores = {}
        for tag, pipe in (("fanout", fanout), ("bank", banked)):
            users, items, vals = [], [], []
            for du in probe:
                uid = int(matrix.user_ids[int(du)])
                out = pipe.recommend(uid, 30)
                assert out["degraded"] == [], (tag, out["degraded"])
                for rank, item in enumerate(out["items"]):
                    users.append(uid)
                    items.append(item["repo_id"])
                    vals.append(-rank)  # served order IS the ranking
            predicted = user_items_from_pairs(
                matrix.users_of(np.asarray(users, np.int64)),
                matrix.items_of(np.asarray(items, np.int64)),
                order_key=np.asarray(vals, np.float64),
                k=30,
            )
            scores[tag] = RankingEvaluator(metric_name="ndcg@k", k=30).evaluate(
                predicted, user_actual_items(matrix, k=30)
            )
        assert scores["bank"] == pytest.approx(scores["fanout"], abs=1e-6), scores
    finally:
        fanout.close()
        banked.close()


# --- over real HTTP -----------------------------------------------------------


def _get(handle, path):
    host, port = handle.server_address[:2]
    try:
        with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=30) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture()
def server(world):
    tables, matrix, model, als, tfidf, pop = world
    svc = RecommendationService(
        model, matrix,
        repo_info=tables.repo_info, user_info=tables.user_info,
        recommenders={"popularity": pop},
        bank_stage=_stage(world),
    )
    with serve(svc, port=0) as handle:
        yield handle, matrix


def test_bank_failure_over_http_degrades_not_500(server):
    handle, matrix = server
    uid = int(matrix.user_ids[1])
    status, body = _get(handle, f"/recommend/{uid}")
    assert status == 200 and body["degraded"] == []
    faults.arm("retrieval.query", "error", at=1)
    status, body = _get(handle, f"/recommend/{uid}?k=7")
    assert status == 200, body
    assert "bank_error" in body["degraded"]
    assert body["items"], "fallback must still answer"
    # Tags AND counters: the metrics page shows both planes.
    status, _ = _get(handle, f"/recommend/{uid}")
    host, port = handle.server_address[:2]
    with urllib.request.urlopen(f"http://{host}:{port}/metrics", timeout=30) as r:
        page = r.read().decode()
    assert 'albedo_retrieval_fallbacks_total{reason="bank_error"} 1' in page
    assert 'albedo_degraded_total{reason="bank_error"} 1' in page
    assert "albedo_retrieval_queries_total" in page


def test_readiness_reports_bank_snapshot(server):
    handle, _matrix = server
    status, body = _get(handle, "/healthz/ready")
    assert status == 200
    snap = body["retrieval_bank"]
    assert snap["sources"] == ["als", "tfidf"]
    assert snap["generation"] == 1 and snap["version"]


def test_query_width_floor_keeps_the_request_path_on_one_program(world):
    """More-Like-This queries are a user's <= top_k recent stars, so history
    length differs per request. The bank pads every such query to one floor
    width: users with 1 star and users with 20 run the SAME fused program —
    the request path must not compile one per history length (seconds each
    on a chip, each a ``bank_timeout``)."""
    from albedo_tpu.utils import aot

    _tables, matrix, _model, _als, _tfidf, _pop = world
    stage = _stage(world)
    indptr, _cols, _ = matrix.csr()
    lens = np.diff(indptr)
    users = [int(matrix.user_ids[i]) for i in (np.argmin(lens), np.argmax(lens))]
    assert lens.min() != lens.max()
    stage.query_frames(users[0], exclude_seen=True)
    n = len([r for r in aot.branch_log() if r["name"] == "retrieval_query"])
    for uid in users + [int(u) for u in matrix.user_ids[:20]]:
        frames = stage.query_frames(uid, exclude_seen=True)
        assert set(frames) == {"als", "tfidf"}
    assert len([r for r in aot.branch_log() if r["name"] == "retrieval_query"]) == n


def test_warm_service_compiles_bank_and_ranker_before_the_first_request(world):
    """``warm=True`` is the promise that no request pays a trace+compile.
    For the two-stage path that covers the bank's fused query and the
    ranker's logits as well as the batcher ladder: the first request after a
    warm boot is a clean ``two_stage`` answer and acquires no executable."""
    from albedo_tpu.utils import aot

    _tables, matrix, model, _als, _tfidf, pop = world

    class CountingRanker:
        calls = 0

        def score(self, candidates):
            CountingRanker.calls += 1
            out = candidates.copy()
            out["probability"] = np.linspace(0.9, 0.1, len(out))
            return out

    service = RecommendationService(
        model, matrix, recommenders={"popularity": pop},
        ranker=CountingRanker(), bank_stage=_stage(world, timeout_s=PATIENT_S),
        warm=True, default_k=K, deadlines=PATIENT_DEADLINES,
        overload_config=OverloadConfig(slo_s=PATIENT_S, codel_target_s=PATIENT_S),
    )
    try:
        assert service.batcher.warmed
        assert CountingRanker.calls == 1  # the warm pass ran the ranker once
        before = len(aot.branch_log())
        status, body = service.handle_recommend(int(matrix.user_ids[3]))
        assert status == 200 and body["stage"] == "two_stage"
        assert body["degraded"] == []
        assert len(aot.branch_log()) == before  # nothing compiled on the path
        assert events.retrieval_fallbacks.total() == 0
    finally:
        service.close()


def test_cold_service_does_not_warm(world):
    _tables, matrix, model, _als, _tfidf, pop = world

    class Ranker:
        calls = 0

        def score(self, candidates):
            Ranker.calls += 1
            out = candidates.copy()
            out["probability"] = 0.5
            return out

    service = RecommendationService(
        model, matrix, recommenders={"popularity": pop}, ranker=Ranker(),
        bank_stage=_stage(world), warm=False, default_k=K,
    )
    try:
        assert Ranker.calls == 0
    finally:
        service.close()
