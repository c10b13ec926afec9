"""chip_smoke.py — the quickest proof that the train -> serve path still
starts on the chip.

One process (a chip belongs to one process at a time: no child imports JAX,
the HTTP clients are threads) drives the two-stage recommender end to end
through the objects the CLI jobs use — ``cli.build_parser`` namespaces,
``JobContext``, ``build_serving`` + ``serving.serve`` — at the reference
width, on seeded synthetic data, into a fresh artifact directory:

- **Leg A, trainer**: ``train_als`` rank 50 / reg 0.5 / alpha 40 / 26 sweeps
  on 30,000 x 20,000 x mean 60 stars, once with the CLI default solver
  (Cholesky) and once with ``--solver cg``. Gated on the resident path, a
  ``fit`` admission against the device's own ``bytes_limit``, finite factor
  health, no watchdog trip, and the f64 normal-equation residual.
- **Leg B, server** over the artifact Leg A wrote (store hit asserted):
  batching and ladder warm-up on, >= 64 ``/recommend/<uid>?k=30`` requests in
  concurrent bursts; every answer 200 / 30 items / untagged, and the served
  top-30 checked against a plain-numpy f32 top-30 of the host factors.
- **Leg C, two-stage**: ``serve --two-stage --bank`` on 8,000 x 5,000 x mean
  20 — ALS, Word2Vec at the reference width (dim 200, 30 epochs), the LR
  ranker (L-BFGS), the retrieval bank, ``BankStage``; no degraded tag, no
  retrieval fallback, bank/host candidate parity, ranker AUC.
- **Leg D, mesh** (only when >= 4 devices are visible): ``train_als
  --mesh-devices 4`` resident / streamed / ring and a mesh fold-in in both
  modes, with shard placement and agreement with Leg A's single-chip fit.

It refuses any platform but ``tpu`` (CPU correctness is tier-1's job; a tiny
CPU rehearsal of the same legs lives in ``tests/test_chip_smoke.py``), exits
non-zero if any check of any leg failed, and prints as the LAST line of
stdout ``{"ok": true, "device": {"platform", "kind", "count"}}``. Everything
it writes goes under ``chiprun_out/chip_smoke/`` (emptied at start) and the
compile-cache directory (``JAX_COMPILATION_CACHE_DIR`` or ``.jax-cache/``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
OUT_DIR = HERE / "chiprun_out" / "chip_smoke"
DEADLINE_S = 1140.0  # the contract is 1200 s, compilation included
TOP_K = 30


@dataclasses.dataclass(frozen=True)
class SmokeShape:
    """The sizes of one run. ``FULL`` is the only shape ``main`` runs; the
    test suite rehearses the same legs on CPU with a tiny one."""

    als_users: int = 30_000
    als_items: int = 20_000
    als_mean_stars: float = 60.0
    two_stage_users: int = 8_000
    two_stage_items: int = 5_000
    two_stage_mean_stars: float = 20.0
    small: bool = False       # --small: rank 16 / 8 sweeps (rehearsal only)
    w2v_full: bool = True     # Word2Vec dim 200 / 30 epochs
    bursts: tuple[int, ...] = (1, 2, 5, 12, 24, 40)   # Leg B, 84 requests
    two_stage_requests: int = 24
    foldin_rows: int = 96
    residual_max: float = 5e-3
    # Served scores vs a numpy f32 reference: the serving GEMM runs at TPU
    # default matmul precision (bf16 passes); measured 2.6e-3 max on a v5e
    # over rank-50 dot products of magnitude ~1 (CHANGES.md PR 21).
    served_score_atol: float = 5e-3
    bank_parity_atol: float = 1e-3
    # Four-chip fit vs the single-chip fit after 26 sweeps: measured 3.9e-3
    # (resident, streamed) and 4.1e-3 (ring) on 4 x v5e — reduction order and
    # default matmul precision, amplified sweep over sweep (1e-5 is a CPU
    # number). One fold-in solve has nothing to amplify: measured 1.4e-6.
    mesh_factor_atol: float = 1e-2
    mesh_foldin_atol: float = 1e-4


FULL = SmokeShape()


class Checks:
    """Collects every failed expectation of a leg instead of stopping at the
    first, so one run reports all that is wrong."""

    def __init__(self, leg: str):
        self.leg = leg
        self.failures: list[str] = []

    def expect(self, ok: bool, what: str) -> bool:
        tag = "ok  " if ok else "FAIL"
        print(f"[{self.leg}] {tag} {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return bool(ok)


class CompileLedger:
    """Per-leg compile accounting: the AOT layer's branch records plus JAX's
    own persistent-compilation-cache hit/miss events."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        self._mark = (0, 0, 0)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)

    def start(self) -> None:
        from albedo_tpu.utils import aot

        self._mark = (len(aot.branch_log()), self.hits, self.misses)

    def report(self, leg: str) -> dict:
        from albedo_tpu.utils import aot

        n0, h0, m0 = self._mark
        records = aot.branch_log()[n0:]
        by_name: dict[str, dict] = {}
        for r in records:
            agg = by_name.setdefault(r["name"], {
                "n": 0, "compile_s": 0.0, "sources": set(), "branches": set(),
                "custom_calls": set(),
            })
            agg["n"] += 1
            agg["compile_s"] += r["compile_s"]
            agg["sources"].add(r["source"])
            agg["branches"].add(r["branch"])
            agg["custom_calls"].update(r["custom_calls"] or [])
        programs = {
            name: {
                "n": a["n"], "compile_s": round(a["compile_s"], 2),
                "compile_source": "+".join(sorted(a["sources"])),
                "branch": " | ".join(sorted(a["branches"])),
                "custom_calls": sorted(a["custom_calls"]),
            }
            for name, a in sorted(by_name.items())
        }
        for name, p in programs.items():
            print(f"[{leg}] aot {name}: {json.dumps(p)}", flush=True)
        out = {
            "compile_s": round(sum(p["compile_s"] for p in programs.values()), 2),
            "xla_cache_hits": self.hits - h0,
            "xla_cache_misses": self.misses - m0,
            "programs": programs,
        }
        print(
            f"[{leg}] compile_s={out['compile_s']} xla_cache_hits="
            f"{out['xla_cache_hits']} xla_cache_misses={out['xla_cache_misses']}",
            flush=True,
        )
        return out


# --------------------------------------------------------------------- helpers


def cli_namespace(*argv: str, **attrs):
    """The namespace ``albedo-tpu <argv>`` would hand its job, from the CLI's
    own parser; ``attrs`` are the programmatic extras (``w2v_full``, ``now``)
    the bench injects the same way."""
    from albedo_tpu.cli import build_parser

    args, rest = build_parser().parse_known_args(list(argv))
    args._rest = rest
    for k, v in attrs.items():
        setattr(args, k, v)
    return args


def http_get(url: str, timeout: float = 60.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def burst(base: str, user_ids: list[int]) -> list[tuple[int, int, dict]]:
    """Fire one request per user at the same instant (threads behind a
    barrier); returns ``(user_id, status, body)`` per request. A client
    thread that raises fails the burst — a silently dead client would thin
    the load."""
    barrier = threading.Barrier(len(user_ids))
    out: list = [None] * len(user_ids)
    errors: list[str] = []

    def client(i: int, uid: int) -> None:
        try:
            barrier.wait(timeout=30)
            status, raw = http_get(f"{base}/recommend/{uid}?k={TOP_K}")
            out[i] = (uid, status, json.loads(raw))
        except Exception as e:  # noqa: BLE001 — reported, fails the leg
            errors.append(f"user {uid}: {e!r}")

    threads = [
        threading.Thread(target=client, args=(i, uid), name=f"smoke-client-{i}")
        for i, uid in enumerate(user_ids)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    if errors or any(t.is_alive() for t in threads) or any(o is None for o in out):
        raise RuntimeError(f"burst of {len(user_ids)} lost requests: {errors[:3]}")
    return out


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text page -> ``{"name{labels}": value}``."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def metric_total(metrics: dict[str, float], name: str, **labels: str) -> float:
    total = 0.0
    for key, val in metrics.items():
        base, _, rest = key.partition("{")
        if base != name:
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += val
    return total


def numpy_top_k(model, matrix, dense_user: int, k: int):
    """Plain-numpy f32 top-k of one user over the host factors, seen items
    excluded: ``(raw item ids, scores)``, score-descending."""
    uf = np.asarray(model.user_factors, np.float32)
    vf = np.asarray(model.item_factors, np.float32)
    scores = vf @ uf[dense_user]
    indptr, cols, _ = matrix.csr()
    scores[cols[indptr[dense_user]:indptr[dense_user + 1]]] = -np.inf
    top = np.argsort(-scores, kind="stable")[:k]
    return matrix.item_ids[top], scores[top]


def response_problems(uid: int, status: int, body: dict) -> list[str]:
    """Why one /recommend answer is not a clean full-quality 200."""
    bad = []
    if status != 200:
        bad.append(f"user {uid}: status {status} {str(body)[:120]}")
        return bad
    if len(body.get("items", [])) != TOP_K:
        bad.append(f"user {uid}: {len(body.get('items', []))} items")
    if body.get("degraded"):
        bad.append(f"user {uid}: degraded {body['degraded']}")
    if body.get("brownout"):
        bad.append(f"user {uid}: brownout {body['brownout']}")
    return bad


def expect_clean_answers(ck: Checks, answers: list, metrics: dict, minimum: int) -> None:
    """The serving gates Legs B and C share: enough requests, every one a
    full-quality 200, and a /metrics page that saw no degradation or shed."""
    problems = [p for uid, st, body in answers for p in response_problems(uid, st, body)]
    ck.expect(len(answers) >= minimum, f"{len(answers)} requests sent (>= {minimum})")
    ck.expect(not problems, f"every answer 200 / {TOP_K} items / untagged {problems[:3]}")
    ck.expect(metric_total(metrics, "albedo_degraded_total") == 0, "albedo_degraded_total == 0")
    ck.expect(
        metric_total(metrics, "albedo_requests_total", status="429") == 0,
        "no 429 on /metrics",
    )


# ------------------------------------------------------------------------ legs


def leg_a(shape: SmokeShape, state: dict) -> dict:
    """Trainer at full width, both solvers."""
    import jax

    from albedo_tpu.builders.jobs import JobContext
    from albedo_tpu.datasets import synthetic_tables
    from albedo_tpu.datasets.artifacts import artifact_path
    from albedo_tpu.evaluators import normal_eq_residual
    from albedo_tpu.recommenders import ALSRecommender
    from albedo_tpu.utils import capacity, events

    ck = Checks("leg A")
    t0 = time.perf_counter()
    tables = synthetic_tables(
        n_users=shape.als_users, n_items=shape.als_items,
        mean_stars=shape.als_mean_stars, seed=42,
    )
    print(f"[leg A] synthetic tables built in {time.perf_counter() - t0:.1f}s "
          f"({len(tables.starring):,} stars)", flush=True)
    state["als_tables"] = tables
    stats = jax.local_devices()[0].memory_stats() or {}
    out: dict = {"solvers": {}}
    small = ["--small"] if shape.small else []
    for solver in ("cholesky", "cg"):
        flags = [] if solver == "cholesky" else ["--solver", "cg"]
        args = cli_namespace("train_als", *small, *flags, now=1.52e9)
        ctx = JobContext(args, tables=tables, tag="smokeA")
        seen: dict = {}

        def on_fit(est, model, seen=seen):
            seen["report"] = dict(est.last_fit_report)
            seen["est"] = est

        t0 = time.perf_counter()
        model = ctx.als_model(on_fit=on_fit)
        fit_wall = time.perf_counter() - t0
        if not ck.expect("report" in seen, f"{solver}: the fit ran (no stale artifact)"):
            continue
        rep = seen["report"]
        cap = rep.get("capacity") or {}
        print(f"[leg A] {solver} fit report: {json.dumps(rep, default=str)}", flush=True)
        ck.expect(rep.get("mode") == "resident", f"{solver}: mode == resident ({rep.get('mode')})")
        ck.expect(cap.get("verdict") == "fit", f"{solver}: capacity verdict == fit ({cap.get('verdict')})")
        if stats.get("bytes_limit"):
            want = int(stats["bytes_limit"] * capacity.headroom())
            ck.expect(
                cap.get("budget_bytes") == want,
                f"{solver}: budget {cap.get('budget_bytes'):,} == device "
                f"bytes_limit x headroom {want:,}",
            )
        health = rep.get("health") or {}
        ck.expect(
            health.get("nonfinite") == 0 and np.isfinite(health.get("rms", np.nan)),
            f"{solver}: factor health finite {health}",
        )
        ck.expect(
            not ctx._cache.get("watchdog_trips"),
            f"{solver}: no watchdog trip (guarded_fit did not re-fit damped)",
        )
        ck.expect(
            artifact_path(ctx.als_artifact_name()).exists(),
            f"{solver}: artifact {ctx.als_artifact_name()} written",
        )
        matrix = ctx.matrix()
        rec = ALSRecommender(model, matrix, top_k=TOP_K)
        users = matrix.user_ids[ctx.test_user_dense()]
        ndcg = float(ctx.evaluate_topk(rec.recommend_for_users(users)))
        est = seen["est"]
        res = normal_eq_residual(matrix, model, est.reg_param, est.alpha)
        print(f"[leg A] {solver}: NDCG@30 = {ndcg:.5f}  residual = {json.dumps(res)}  "
              f"fit wall = {fit_wall:.1f}s", flush=True)
        ck.expect(np.isfinite(ndcg) and ndcg > 0, f"{solver}: NDCG@30 finite and > 0")
        ck.expect(
            res["rel_residual_max"] <= shape.residual_max,
            f"{solver}: f64 normal-equation residual max "
            f"{res['rel_residual_max']:.2e} <= {shape.residual_max:g}",
        )
        out["solvers"][solver] = {
            "ndcg30": round(ndcg, 5), "residual": res, "fit_wall_s": round(fit_wall, 2),
            "report": rep,
        }
        if solver == "cholesky":
            state["als_model"] = model
            state["als_matrix"] = matrix
    ck.expect(events.watchdog_trips.total() == 0, "albedo_watchdog_trips_total == 0")
    out["failures"] = ck.failures
    return out


def leg_b(shape: SmokeShape, state: dict) -> dict:
    """The server over Leg A's artifact."""
    from albedo_tpu.builders.jobs import JobContext, build_serving, serve_options
    from albedo_tpu.retrieval import candidate_parity
    from albedo_tpu.serving import serve

    ck = Checks("leg B")
    small = ["--small"] if shape.small else []
    args = cli_namespace("serve", *small, "--port", "0", now=1.52e9)
    ctx = JobContext(args, tables=state["als_tables"], tag="smokeA")
    refit: list = []
    model = ctx.als_model(on_fit=lambda est, m: refit.append(est))
    ck.expect(not refit, "ALS model came from the artifact store (no re-fit)")
    ck.expect(
        np.array_equal(model.user_factors, state["als_model"].user_factors),
        "store round-trip returns Leg A's factors bit-exact",
    )
    ns = serve_options(args._rest)
    t0 = time.perf_counter()
    service, _manager = build_serving(ctx, ns)
    print(f"[leg B] service built + ladder warmed in {time.perf_counter() - t0:.1f}s "
          f"(device_exclusion={service.batcher.device_exclusion})", flush=True)
    ck.expect(bool(service.batcher and service.batcher.warmed), "batcher ladder warmed")
    matrix = ctx.matrix()
    rng = np.random.default_rng(7)
    n_req = sum(shape.bursts)
    dense = rng.choice(matrix.n_users, size=n_req, replace=False)
    uids = [int(u) for u in matrix.user_ids[dense]]
    answers: list = []
    with serve(service, host=ns.host, port=ns.port) as server:
        base = "http://%s:%d" % server.server_address[:2]
        at = 0
        for size in shape.bursts:
            answers.extend(burst(base, uids[at:at + size]))
            at += size
        _, raw = http_get(f"{base}/metrics")
    metrics = parse_metrics(raw.decode())
    expect_clean_answers(ck, answers, metrics, minimum=min(64, n_req))
    # Non-cumulative occupancy of the batch-size histogram: how many distinct
    # size bins the coalesced device batches fell into.
    bins = sorted(
        (float(key.split('le="')[1].split('"')[0]), val)
        for key, val in metrics.items()
        if key.startswith("albedo_serving_batch_size_bucket") and "+Inf" not in key
    )
    occupied = [le for (le, c), prev in zip(bins, [0.0] + [c for _, c in bins]) if c > prev]
    print(f"[leg B] batch-size histogram bins occupied: {occupied}", flush=True)
    ck.expect(len(occupied) > 1, "more than one batch bucket executed")
    worst = 0.0
    checked = 0
    for (uid, _st, body), du in list(zip(answers, dense))[-16:]:
        host = numpy_top_k(model, matrix, int(du), TOP_K)
        served = (
            np.array([it["repo_id"] for it in body["items"]], np.int64),
            np.array([it["score"] for it in body["items"]], np.float64),
        )
        rep = candidate_parity(host, served, atol=shape.served_score_atol)
        worst = max(worst, rep.get("max_score_err", 0.0))
        checked += 1
        if not rep["ok"]:
            ck.expect(False, f"user {uid}: served top-{TOP_K} != numpy f32 top-{TOP_K}: {rep}")
    ck.expect(checked >= 16, f"{checked} users checked against the numpy reference")
    print(f"[leg B] served vs numpy f32 top-{TOP_K}: max score error {worst:.2e} "
          f"over {checked} users", flush=True)
    return {"requests": len(answers), "max_score_err": worst, "failures": ck.failures}


def leg_c(shape: SmokeShape, state: dict) -> dict:
    """Two-stage serving: every north-star module on the chip."""
    from albedo_tpu.builders.jobs import JobContext, build_serving, serve_options
    from albedo_tpu.datasets import synthetic_tables
    from albedo_tpu.retrieval import candidate_parity
    from albedo_tpu.retrieval.parity import frame_to_pairs
    from albedo_tpu.serving import serve
    from albedo_tpu.utils import events

    ck = Checks("leg C")
    tables = synthetic_tables(
        n_users=shape.two_stage_users, n_items=shape.two_stage_items,
        mean_stars=shape.two_stage_mean_stars, seed=42,
    )
    small = ["--small"] if shape.small else []
    # `now` pinned just after the synthetic tables' fixed t_now (1.51e9):
    # date features are functions of (now - timestamp).
    args = cli_namespace(
        "serve", *small, "--two-stage", "--bank", "--port", "0",
        w2v_full=shape.w2v_full, now=1.52e9,
    )
    ctx = JobContext(args, tables=tables, tag="smokeC")
    ns = serve_options(args._rest)
    t0 = time.perf_counter()
    service, _manager = build_serving(ctx, ns)
    print(f"[leg C] two-stage service built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    w2v = ctx.word2vec()
    want_dim = 200 if shape.w2v_full else 16
    ck.expect(w2v.vectors.shape[1] == want_dim, f"Word2Vec dim == {want_dim}")
    auc = ctx._cache.get("ranker_auc", float("nan"))
    print(f"[leg C] ranker AUC = {auc:.4f}", flush=True)
    ck.expect(np.isfinite(auc) and auc > 0.5, "ranker AUC finite and > 0.5")
    ck.expect(not ctx._cache.get("watchdog_trips"), "no watchdog trip")

    matrix = ctx.matrix()
    rng = np.random.default_rng(11)
    dense = rng.choice(matrix.n_users, size=shape.two_stage_requests, replace=False)
    uids = [int(u) for u in matrix.user_ids[dense]]
    answers: list = []
    with serve(service, host=ns.host, port=ns.port) as server:
        base = "http://%s:%d" % server.server_address[:2]
        # One request at a time: each two-stage request featurizes its
        # candidates in pandas on the host, under the GIL, and four in
        # flight were seen to queue past the 0.5 s ranker deadline — host
        # queueing (ROADMAP S5/D3), which this leg, a proof that the device
        # programs run, must not mistake for a chip failure.
        for uid in uids:
            answers.extend(burst(base, [uid]))
        _, raw = http_get(f"{base}/metrics")
        metrics = parse_metrics(raw.decode())
        stage_s = {
            stage: round(
                metric_total(metrics, "albedo_stage_seconds", stage=stage)
                / max(1.0, metric_total(metrics, "albedo_stage_calls", stage=stage)), 4
            )
            for stage in ("stage1_candidates", "stage2_rank")
        }
        print(f"[leg C] mean seconds per request by stage: {json.dumps(stage_s)}",
              flush=True)

        expect_clean_answers(ck, answers, metrics, minimum=min(16, len(uids)))
        stages = sorted({body.get("stage") for _u, _s, body in answers})
        ck.expect(stages == ["two_stage"], f"every answer re-ranked (stages {stages})")
        ck.expect(
            events.retrieval_fallbacks.total() == 0,
            "albedo_retrieval_fallbacks_total == 0",
        )
        stage = service.bank_stage
        worst = 0.0
        for uid in uids[:4]:
            frames = stage.query_frames(uid, k=TOP_K, exclude_seen=True)
            for name in stage.source_names:
                host_frame = stage.fallbacks[name].recommend_for_users(np.array([uid]))
                rep = candidate_parity(
                    frame_to_pairs(host_frame, uid),
                    (
                        frames[name]["repo_id"].to_numpy(np.int64),
                        frames[name]["score"].to_numpy(np.float64),
                    ),
                    atol=shape.bank_parity_atol,
                )
                worst = max(worst, rep.get("max_score_err", 0.0))
                if not rep["ok"]:
                    ck.expect(False, f"bank parity {name} user {uid}: {rep}")
        print(f"[leg C] bank vs host candidates: max score error {worst:.2e} "
              f"(4 users x {list(stage.source_names)})", flush=True)
    return {
        "requests": len(answers), "auc": float(auc), "bank_max_score_err": worst,
        "failures": ck.failures,
    }


def leg_d(shape: SmokeShape, state: dict) -> dict:
    """Four chips: the sharded fits and the mesh fold-in."""
    import jax

    from albedo_tpu.builders.jobs import ALS_ALPHA, ALS_REG, JobContext
    from albedo_tpu.streaming.foldin import FoldInEngine
    from albedo_tpu.utils import events

    ck = Checks("leg D")
    devices = jax.devices()[:4]
    ref = state["als_model"]
    matrix = state["als_matrix"]
    small = ["--small"] if shape.small else []
    out: dict = {"fits": {}, "foldin": {}}
    modes = (
        ("resident", ["--sharded", "resident"]),
        ("streamed", ["--sharded", "streamed"]),
        ("ring", ["--sharded", "resident", "--shard-mode", "ring"]),
    )
    ctx = None
    for name, flags in modes:
        args = cli_namespace(
            "train_als", *small, "--mesh-devices", "4", *flags, now=1.52e9
        )
        ctx = JobContext(args, tables=state["als_tables"], tag=f"smokeD{name}")
        ck.expect(ctx.mesh().devices.size == 4, f"{name}: mesh.devices.size == 4")
        seen: dict = {}

        def on_fit(est, model, seen=seen):
            seen["report"] = dict(est.last_fit_report)
            seen["tables"] = (model._uf_raw, model._vf_raw)

        t0 = time.perf_counter()
        model = ctx.als_model(on_fit=on_fit)
        wall = time.perf_counter() - t0
        if not ck.expect("report" in seen, f"{name}: the fit ran"):
            continue
        rep = seen["report"]
        print(f"[leg D] {name} fit report: {json.dumps(rep, default=str)}", flush=True)
        want_mode = "sharded_streamed" if name == "streamed" else "sharded"
        ck.expect(rep.get("mode") == want_mode, f"{name}: mode == {want_mode} ({rep.get('mode')})")
        ck.expect(rep.get("n_shards") == 4, f"{name}: n_shards == 4")
        if name == "ring":
            ck.expect(rep.get("shard_mode") == "ring", "ring: shard_mode == ring")
        ck.expect(
            (rep.get("mesh_events") or {}).get("losses") == 0,
            f"{name}: mesh_events.losses == 0",
        )
        placement = {}
        for label, arr in zip(("user_factors", "item_factors"), seen["tables"]):
            shards = arr.addressable_shards
            on = {s.device.id for s in shards}
            rows = [int(s.data.shape[0]) for s in shards]
            n = int(arr.shape[0])
            # The fit keeps both tables row-sharded (padded to a shard
            # multiple) and returns them trimmed to n rows: a multiple of 4
            # comes back sharded 1/4 each; any other n can only come back
            # as the full table on every device (the trim gathers it).
            if n % 4 == 0:
                layout, ok = "sharded", rows == [n // 4] * 4
            else:
                layout, ok = "replicated by the unpad trim", rows == [n] * 4
            placement[label] = {"devices": sorted(on), "rows": rows, "layout": layout}
            ck.expect(
                len(on) == 4 and ok,
                f"{name}: {label} ({n} rows) on 4 distinct devices, {layout} "
                f"(devices {sorted(on)}, rows {rows})",
            )
        seen.pop("tables")
        diff = max(
            float(np.max(np.abs(model.user_factors - ref.user_factors))),
            float(np.max(np.abs(model.item_factors - ref.item_factors))),
        )
        print(f"[leg D] {name}: max |factor - single-chip factor| = {diff:.3e} "
              f"(fit wall {wall:.1f}s)", flush=True)
        ck.expect(
            diff <= shape.mesh_factor_atol,
            f"{name}: factors match Leg A's single-chip fit within {shape.mesh_factor_atol:g}",
        )
        out["fits"][name] = {
            "max_factor_diff": diff, "fit_wall_s": round(wall, 2),
            "placement": placement, "report": rep,
        }
    per_device = []
    for d in devices:
        s = d.memory_stats() or {}
        per_device.append({
            "id": d.id, "bytes_in_use": s.get("bytes_in_use"),
            "peak_bytes_in_use": s.get("peak_bytes_in_use"),
        })
    print(f"[leg D] per-device memory: {json.dumps(per_device)}", flush=True)
    if devices[0].platform == "cpu":
        # The rehearsal's virtual CPU devices report no memory_stats.
        print("[leg D] per-device memory: not measured (cpu backend)", flush=True)
    else:
        ck.expect(
            all((p["peak_bytes_in_use"] or 0) > (1 << 20) for p in per_device),
            "peak_bytes_in_use non-trivial on all four devices",
        )
    out["per_device_memory"] = per_device

    # Mesh fold-in over Leg A's model, both source-assembly modes, against
    # the single-device engine on the same rows.
    rng = np.random.default_rng(13)
    indptr, cols, vals = matrix.csr()
    users = rng.choice(matrix.n_users, size=min(shape.foldin_rows, matrix.n_users), replace=False)
    rows = [
        (cols[indptr[u]:indptr[u + 1]].astype(np.int64),
         vals[indptr[u]:indptr[u + 1]].astype(np.float32))
        for u in users if indptr[u + 1] > indptr[u]
    ]
    want = FoldInEngine(ref, reg_param=ALS_REG, alpha=ALS_ALPHA).fold_in(rows)
    for mode in ("allgather", "ring"):
        engine = FoldInEngine(
            ref, reg_param=ALS_REG, alpha=ALS_ALPHA, mesh=ctx.mesh(), shard_mode=mode,
        )
        got = engine.fold_in(rows)
        diff = float(np.max(np.abs(got - want)))
        print(f"[leg D] fold-in {mode}: max |mesh - single-device| = {diff:.3e} "
              f"admission {json.dumps(engine.last_admission, default=str)}", flush=True)
        ck.expect(
            np.isfinite(got).all() and diff <= shape.mesh_foldin_atol,
            f"fold-in {mode}: matches the single-device engine within "
            f"{shape.mesh_foldin_atol:g}",
        )
        out["foldin"][mode] = {"max_diff": diff, "rows": len(rows)}
    ck.expect(events.mesh_degraded.total() == 0, "albedo_mesh_degraded_total == 0")
    ck.expect(events.mesh_losses.total() == 0, "albedo_mesh_losses_total == 0")
    out["failures"] = ck.failures
    return out


# ------------------------------------------------------------------ the runner


def run_legs(shape: SmokeShape, out_dir: Path, n_devices: int, legs: str = "ABCD") -> dict:
    """Run every leg against a fresh artifact directory; returns the summary
    (``failed`` lists every failed check, prefixed by its leg). ``legs``
    narrows the plan for a caller that pays per chip-minute (the four-chip
    record needs only A and D); ``main`` always runs them all."""
    from albedo_tpu import settings
    from albedo_tpu.utils import events

    if out_dir.exists():
        shutil.rmtree(out_dir)  # a same-day model would otherwise skip the fit
    out_dir.mkdir(parents=True)
    settings.set_settings(settings.Settings(
        data_dir=out_dir / "data", checkpoint_dir=out_dir / "data" / "checkpoints",
    ))
    ledger = CompileLedger()
    state: dict = {}
    summary: dict = {"legs": {}, "failed": []}
    plan = [("A", leg_a), ("B", leg_b), ("C", leg_c)]
    if n_devices >= 4:
        plan.append(("D", leg_d))
    else:
        print(f"mesh leg: not run ({n_devices} device)", flush=True)
    plan = [(name, fn) for name, fn in plan if name in legs]
    try:
        for name, fn in plan:
            needs_a = name in ("B", "D")
            t0 = time.perf_counter()
            ledger.start()
            try:
                if needs_a and "als_model" not in state:
                    raise RuntimeError("needs Leg A's model, and Leg A did not produce one")
                result = fn(shape, state)
            except Exception:  # noqa: BLE001 — any exception fails the run, loudly
                traceback.print_exc()
                result = {
                    "failures": [f"raised: {traceback.format_exc(limit=1).strip()[-300:]}"]
                }
            result["compile"] = ledger.report(f"leg {name}")
            result["wall_s"] = round(time.perf_counter() - t0, 1)
            print(f"[leg {name}] {'PASSED' if not result['failures'] else 'FAILED'} "
                  f"in {result['wall_s']}s", flush=True)
            summary["legs"][name] = result
            summary["failed"] += [f"leg {name}: {f}" for f in result["failures"]]
    finally:
        ledger.close()
    counters = {
        "albedo_aot_fingerprint_mismatches_total": events.aot_fingerprint_mismatches.total(),
        "albedo_retrieval_fallbacks_total": events.retrieval_fallbacks.total(),
        "albedo_mesh_degraded_total": events.mesh_degraded.total(),
        "albedo_watchdog_trips_total": events.watchdog_trips.total(),
    }
    print(f"counters: {json.dumps(counters)}", flush=True)
    summary["counters"] = counters
    summary["failed"] += [f"{k} == {v}" for k, v in counters.items() if v]
    summary["compile_s"] = round(sum(r["compile"]["compile_s"] for r in summary["legs"].values()), 2)
    summary["wall_s"] = round(sum(r["wall_s"] for r in summary["legs"].values()), 1)
    return summary


def warm_gate(summary: dict) -> list[str]:
    """On a run whose compile cache this same code already filled: no fresh
    compile of the ALS and LR programs (export deserialized, or the
    XLA-cache reuse verified)."""
    bad = []
    for leg, result in summary["legs"].items():
        for name, p in result["compile"]["programs"].items():
            if not name.startswith(("als_", "lr_")):
                continue
            reused = p["compile_source"] == "disk" or "xla-cache-verified" in p["branch"]
            if not reused:
                bad.append(f"leg {leg}: warm cache, yet {name} took {p['branch']!r}")
    return bad


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU — this script proves the path on the "
              "chip and has no CPU mode (tier-1 covers CPU correctness)",
              file=sys.stderr)
        return 2

    def abort():
        print(f"chip_smoke: exceeded its {DEADLINE_S:.0f}s deadline", file=sys.stderr,
              flush=True)
        os._exit(3)

    timer = threading.Timer(DEADLINE_S, abort)
    timer.daemon = True
    timer.start()

    from albedo_tpu.utils import aot
    from albedo_tpu.utils.compilation_cache import (
        cache_dir,
        enable_persistent_compilation_cache,
    )
    from albedo_tpu.utils.log import configure_logging

    configure_logging()
    enable_persistent_compilation_cache()
    print(f"memory_stats: {json.dumps(devices[0].memory_stats())}", flush=True)
    # Warm means: THIS code, on this device kind and JAX, already ran to a
    # green end against this cache directory (a cache filled by another
    # commit holds other programs and proves nothing).
    identity = {
        "code": aot._code_fingerprint(), "kind": device["kind"],
        "count": device["count"], "jax": jax.__version__,
    }
    marker = cache_dir() / "chip_smoke.warm.json"
    warm = marker.exists() and json.loads(marker.read_text()) == identity
    print(f"compile cache: {cache_dir()} ({'warm' if warm else 'cold'})", flush=True)

    summary = run_legs(FULL, OUT_DIR, device["count"])
    if warm:
        summary["failed"] += warm_gate(summary)
    summary.update(device=device, jax=jax.__version__, cache="warm" if warm else "cold",
                   total_s=round(time.perf_counter() - t_start, 1))
    (OUT_DIR / "summary.json").write_text(json.dumps(summary, indent=2, default=str))
    print(f"set-up: cache={summary['cache']} compile_s={summary['compile_s']} "
          f"legs_wall_s={summary['wall_s']} total_s={summary['total_s']}", flush=True)
    timer.cancel()
    ok = not summary["failed"]
    for f in summary["failed"]:
        print(f"FAILED {f}", flush=True)
    if ok:
        marker.write_text(json.dumps(identity))
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": False, "device": device, "failed": len(summary["failed"])}),
          flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
