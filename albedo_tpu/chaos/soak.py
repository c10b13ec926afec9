"""Full-loop chaos soak: seeded fault schedules over the whole fault-site
inventory, driven through repeated ingest -> train -> publish -> serve ->
stream cycles, with the standing invariants checked every cycle.

PRs 3-6 built fault tolerance one subsystem at a time, each with its own
drills; this is the missing INTEGRATION test over all of it at once. One
soak run:

1. draws a deterministic fault schedule (``--soak-seed``) over the
   catalogued site inventory — every kind (error/ioerror/corrupt/delay/
   kill/term/oom/loss) appears at least once, placed where its effect is
   observable; the ``loss`` cycle is the DEVICE-LOSS cycle: its mesh leg
   runs the elastic fit drill (a shard dies mid-sweep, the fit must
   checkpoint -> remesh -> resume to parity) plus the degraded-serving
   drill (a bank sealed at the full rung promotes onto the halved rung),
   and its stream leg arms ``stream.foldin.collective:loss`` on a forced
   mesh stream (remesh-and-complete in the subprocess flavor, clean
   ``MeshLost`` on the in-process 1-device rung);
2. runs ``--soak-cycles`` full loops, each: a **mesh boot** (degraded-remesh
   ladder), the **offline pipeline** (ingest -> train_als -> canary publish,
   a real CLI subprocess so kill/term faults genuinely kill something), a
   **serve leg** (validated hot-swap of the published artifact through the
   real reload gates + live probes + a short open-loop under-load burst
   that must hold the overload contract: zero 5xx, offered/completed
   parity, sheds priced as tier-tagged 429s), a **stream leg** (validated delta
   ingest -> fold-in -> stamped publish), and a **scoring leg** (the
   ``score_all`` batch sweep under drawn ``score.*`` faults; one pinned
   cycle per soak — the 2-cycle smoke included — runs it as a real CLI
   subprocess pair killed mid-spill (``score.spill:kill`` -> exit 137)
   then resumed, with the sealed manifest checked to cover exactly the
   scored shards);
3. checks the standing invariants after every cycle:

   - **no unstamped artifact served** — a promoted generation's origin
     passed the manifest + quality-stamp gates (``require_stamp``);
   - **no half-applied delta / torn publish** — every artifact carrying a
     ``.sha256`` manifest verifies against it, and every journal parses
     (atomic writes);
   - **exit codes honor the contract** — subprocess legs exit 0 (ok),
     1 (stage failure), 3 (fold-in diverged), 4 (canary refusal),
     75 (preempted) or 137 (killed by an injected ``kill``); anything else
     is a harness bug;
   - **factors finite** — the newest manifest-verified model artifact loads
     to finite factor tables;
   - **capacity rejections never quarantine** — a ``gate=capacity`` reload
     rejection leaves the artifact bytes in place.

A one-time **capacity drill** precedes the cycles: an over-budget fit must
complete via the ``degrade`` verdict (chunked host-streamed path) and match
the resident path's factors — the acceptance bar for the guardrail layer.

The report (``<tag>-soak-report.json``, artifact dir) records every cycle's
legs, exit codes, fired-fault evidence per kind, and invariant verdicts;
the job exits 1 on the first broken invariant (after finishing the report).

``make soak`` runs the subprocess flavor; ``tests/test_soak.py`` runs the
fast in-process ``soak-smoke`` subset (kill/term excluded — they would kill
the test runner) under the ``chaos`` marker.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import random
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from albedo_tpu.cli import register_job
from albedo_tpu.utils import events, faults

log = logging.getLogger(__name__)

REPORT_NAME = "soak-report.json"

# Exit codes the offline contract allows a subprocess leg to report. 137 is
# the injected-kill signature (os._exit(137), the preempted-pod code) — legal
# only on a cycle that armed a kill.
CONTRACT_CODES = {0, 1, 3, 4, 75}
KILL_CODE = 137

# --- the schedulable inventory -------------------------------------------------
# (site, kind) pairs the seeded scheduler draws extra chaos from, keyed by the
# leg that must arm them. Kill/term only ever land in subprocess legs (they
# would kill the soak driver itself); in-process legs stick to raising kinds
# whose firing the driver can read back from the fault registry.

PIPELINE_FAULTS = (
    ("pipeline.stage.ingest", "error"),
    ("pipeline.stage.train_als", "error"),
    ("pipeline.stage.canary", "delay"),
    ("pipeline.canary", "error"),
    ("data.validate", "error"),
    ("train.watchdog", "error"),
    ("artifact.load", "ioerror"),
    ("artifact.load", "corrupt"),
    ("artifact.save", "delay"),
    ("capacity.admit", "oom"),
)
STREAM_FAULTS = (
    ("stream.ingest", "error"),
    ("stream.drift", "error"),
    ("stream.foldin", "error"),
    ("stream.foldin.collective", "loss"),
    ("capacity.admit", "oom"),
)
SERVE_FAULTS = (
    ("reload.load", "ioerror"),
    ("reload.load", "corrupt"),
    ("reload.load", "delay"),
    ("reload.validate", "error"),
    ("capacity.admit", "oom"),
)
MESH_FAULTS = (
    ("mesh.devices", "error"),
    ("als.shard.gather", "delay"),
    ("als.shard.stream", "error"),
    ("als.shard.prefetch", "error"),
)
SCORE_FAULTS = (
    ("score.shard", "error"),
    ("score.spill", "ioerror"),
    ("score.publish", "error"),
)

# Canonical per-kind evidence placements: where each kind is armed so its
# firing is OBSERVABLE regardless of what else the cycle draws. The mesh and
# serve legs always run in-process (fired counters are readable); the serve
# leg ends with an explicit admission probe, so `capacity.admit` is reachable
# even when an earlier reload gate rejected the candidate first. kill/term
# are subprocess-only (their evidence is the exit code): term at
# checkpoint.save on the FIRST cycle (the only one guaranteed to train from
# scratch, where the preemption handler is installed -> exit 75), kill at the
# stage wrapper, which fires on every cycle -> exit 137. `loss` is the
# ELASTIC surface: its cycle's mesh leg swaps the plain sharded drill for
# the elastic one (`_elastic_fit_drill` — the injected device loss must be
# survived via checkpoint -> remesh -> resume, or fail CLEANLY as MeshLost
# on a 1-device rung), plus the degraded-serving drill (a bank sealed at
# the full rung promotes onto the halved rung through the real gates).
KIND_EVIDENCE = {
    "error": ("mesh", "mesh.devices", "error"),
    "delay": ("mesh", "mesh.devices", "delay"),
    "ioerror": ("serve", "reload.load", "ioerror"),
    "corrupt": ("serve", "reload.load", "corrupt"),
    "oom": ("serve", "capacity.admit", "oom"),
    "loss": ("mesh", "als.shard.collective", "loss"),
    "term": ("pipeline", "checkpoint.save", "term"),
    "kill": ("pipeline", "pipeline.stage.train_als", "kill"),
}


def build_schedule(
    cycles: int, seed: int, include_kill_term: bool
) -> list[dict]:
    """The deterministic soak schedule: per cycle, which (leg, site, kind)
    faults arm. Random draws from the inventory add breadth; a coverage
    pass then pins every kind's canonical evidence placement onto a
    concrete cycle — displacing any random draw on the same site, because
    only the FIRST matching armed spec fires at a given hit."""
    if cycles < 2:
        raise ValueError("the soak needs at least 2 cycles for kind coverage")
    rng = random.Random(seed)
    schedule: list[dict] = [
        {"pipeline": [], "stream": [], "serve": [], "mesh": [], "score": []}
        for _ in range(cycles)
    ]
    pools = {
        "pipeline": PIPELINE_FAULTS,
        "stream": STREAM_FAULTS,
        "serve": SERVE_FAULTS,
        "mesh": MESH_FAULTS,
        "score": SCORE_FAULTS,
    }
    for c in range(cycles):
        for leg, pool in pools.items():
            if rng.random() < (0.6 if leg not in ("mesh", "score") else 0.3):
                site, kind = rng.choice(pool)
                schedule[c][leg].append((site, kind, 1))
    kinds = [
        k for k in KIND_EVIDENCE
        if include_kill_term or k not in ("kill", "term")
    ]
    for i, kind in enumerate(kinds):
        leg, site, k = KIND_EVIDENCE[kind]
        if kind == "term":
            cycle, at = 0, 2  # checkpoint 2 of the from-scratch training fit
        elif kind == "kill":
            cycle, at = 1, 1
        elif kind == "loss":
            # The device-loss cycle: pinned to cycle 1 so the 2-cycle smoke
            # always runs it, and kept OFF the last cycle (which pins the
            # plain sharded drill's als.shard.gather coverage).
            cycle, at = 0, 1
        else:
            cycle, at = i % cycles, 1
        # Same-site displacement: two armed specs on one site race for the
        # same hit; the canonical evidence spec must be the one that fires.
        schedule[cycle][leg] = [
            (s, kd, a) for s, kd, a in schedule[cycle][leg] if s != site
        ] + [(site, k, at)]
    # Sharded-fit coverage: the mesh leg runs a tiny row-sharded ALS fit
    # every cycle; pin one cycle to arm its `als.shard.gather` site (delay =
    # observable and benign) so every soak — the 2-cycle smoke included —
    # drills the sharded path's chaos surface, not just mesh boot. The same
    # cycle pins `als.shard.prefetch:error` — the fault fires INSIDE the
    # pipelined fit's background uploader thread and must surface on the
    # consuming sweep as a CLEAN failed fit (recorded, never a hang; the
    # wedged-thread variant is deadline-bounded and unit-drilled in
    # tests/test_sharded_als.py).
    schedule[cycles - 1]["mesh"] = [
        (s, k, a) for s, k, a in schedule[cycles - 1]["mesh"]
        if s not in ("als.shard.gather", "als.shard.prefetch")
    ] + [("als.shard.gather", "delay", 1), ("als.shard.prefetch", "error", 1)]
    # The device-loss cycle's elastic drill must complete via remesh-resume:
    # strip any OTHER raising als.shard.* draw from its mesh leg (the same
    # reason kill/term cycles carry only the preemption — a second injected
    # failure would mask the drill's verdict).
    for c in range(cycles):
        legs = schedule[c]["mesh"]
        if any(s == "als.shard.collective" and k == "loss" for s, k, _ in legs):
            schedule[c]["mesh"] = [
                (s, k, a) for s, k, a in legs
                if s == "als.shard.collective"
                or not (s.startswith("als.shard.") and k in ("error", "ioerror", "oom", "loss"))
            ]
    # The device-loss cycle ALSO pins the STREAMING loss surface: its stream
    # leg arms `stream.foldin.collective:loss` so every soak drills a device
    # dying mid-fold-in, not just mid-refit. Replacing the whole leg strips
    # any random raising draw that would fail the stream before the armed
    # loss fires (the same reason the elastic mesh leg runs alone). The leg
    # forces a mesh stream (see the stream-leg dispatch): the subprocess
    # flavor boots 2 virtual host devices and must remesh 2 -> 1 and
    # COMPLETE the cycle (rc 0); the in-process smoke is stuck on the one
    # real CPU device, where the contract is a CLEAN MeshLost (rc 1) —
    # mirroring `_elastic_fit_drill`'s 1-device branch. `loss` evidence
    # stays canonical on the mesh leg (KIND_EVIDENCE).
    for c in range(cycles):
        if any(
            s == "als.shard.collective" and k == "loss"
            for s, k, _ in schedule[c]["mesh"]
        ):
            schedule[c]["stream"] = [("stream.foldin.collective", "loss", 1)]
    # A kill/term pipeline leg must not ALSO carry raising faults that could
    # fail the stage before the preemption fires.
    for c in range(cycles):
        legs = schedule[c]["pipeline"]
        if any(k in ("kill", "term") for _, k, _ in legs):
            schedule[c]["pipeline"] = [
                (s, k, a) for s, k, a in legs if k in ("kill", "term")
            ][:1]
    # The batch-scoring kill cycle: every soak — the 2-cycle smoke included —
    # pins one `score.spill:kill` on the LAST cycle's scoring leg. The leg
    # always runs as a real CLI subprocess pair (kill -> --resume), even in
    # the in-process smoke flavor, so the kill genuinely kills a process; the
    # resume must walk the cursor, re-score exactly the unsealed shards, and
    # seal a manifest covering every shard (``check_score_invariants``).
    # Replacing the whole leg also strips any random raising draw that could
    # fail the sweep before the armed kill fires.
    schedule[cycles - 1]["score"] = [("score.spill", "kill", 2)]
    return schedule


def faults_env(specs: list[tuple[str, str, int]]) -> str:
    return ",".join(f"{site}:{kind}@{at}" for site, kind, at in specs)


# --- invariants -----------------------------------------------------------------


def check_invariants(art_dir: Path) -> list[str]:
    """Host-side sweep of the standing invariants; returns violations."""
    from albedo_tpu.datasets import artifacts as store

    violations: list[str] = []
    # Concurrency invariant: when the soak runs with ALBEDO_LOCKCHECK=1
    # (`make sanitize`), every lock-order inversion / unguarded shared
    # access the sanitizer observed during the cycle is a violation — this
    # is what validates the static ARCHITECTURE.md catalog against the
    # behavior the chaos legs actually drive.
    from albedo_tpu.analysis import locksmith

    if locksmith.enabled():
        # violations() is cumulative since process start; report each one
        # in the cycle that observed it, not again in every later cycle.
        # The cursor rides the monotonic per-violation `seq` (which
        # survives locksmith.reset()), not list length.
        seen = getattr(check_invariants, "_lockcheck_seen", 0)
        recorded = locksmith.violations()
        for v in recorded:
            if v.get("seq", 0) > seen:
                violations.append(f"locksmith {v['kind']}: {v['message']}")
        if recorded:
            check_invariants._lockcheck_seen = max(
                seen, *(v.get("seq", 0) for v in recorded)
            )
    if not art_dir.exists():
        return violations
    for p in sorted(art_dir.glob("*")):
        name = p.name
        if ".corrupt-" in name or ".quarantine-" in name or name.endswith(".tmp"):
            continue
        if name.endswith(store.MANIFEST_SUFFIX):
            target = p.with_name(name[: -len(store.MANIFEST_SUFFIX)])
            if target.exists() and store.verify_manifest(target) is False:
                violations.append(f"torn publish: {target.name} fails its manifest")
        if name.endswith("journal.json"):
            try:
                json.loads(p.read_text())
            except ValueError:
                violations.append(f"unparseable journal (non-atomic write?): {name}")
    # The newest manifest-verified model artifact must load to finite factors.
    candidates = [
        p for p in sorted(
            art_dir.glob("*alsModel*.pkl"), key=lambda q: q.stat().st_mtime
        )
        if ".corrupt-" not in p.name
        and store.manifest_path(p).exists()
        and store.verify_manifest(p) is not False
    ]
    if candidates:
        newest = candidates[-1]
        try:
            import pickle

            arrays = pickle.loads(newest.read_bytes())
            for key in ("user_factors", "item_factors"):
                if not np.isfinite(np.asarray(arrays[key])).all():
                    violations.append(f"non-finite factors in {newest.name}")
        except Exception as e:  # noqa: BLE001
            violations.append(f"unloadable sealed artifact {newest.name}: {e!r}")
    return violations


# --- the one-time capacity drill ------------------------------------------------


def capacity_drill() -> dict:
    """An over-budget fit must complete via `degrade` (chunked path) and
    match the resident path — the guardrail layer's acceptance bar, run
    once per soak on a small synthetic matrix."""
    from albedo_tpu.datasets.synthetic import synthetic_stars
    from albedo_tpu.models.als import ImplicitALS

    from albedo_tpu.utils import capacity

    matrix = synthetic_stars(n_users=96, n_items=64, mean_stars=6, seed=5)
    kw = dict(rank=8, max_iter=3, seed=0, batch_size=32)
    resident = ImplicitALS(**kw, chunked=False).fit(matrix)
    est = ImplicitALS(**kw)
    plan = est.capacity_plan(matrix)
    chunked_plan = est.capacity_plan(matrix, chunked=True)
    # A budget squarely between the resident and chunked plans: the resident
    # path must not fit, the chunked one must (headroom un-scaled back out).
    target = (plan.required_bytes + chunked_plan.required_bytes) // 2
    before = faults.FAULTS.hits("als.chunked")
    prev = os.environ.get("ALBEDO_DEVICE_MEM_BYTES")
    os.environ["ALBEDO_DEVICE_MEM_BYTES"] = str(
        max(1, int(target / capacity.headroom()))
    )
    try:
        matrix2 = synthetic_stars(n_users=96, n_items=64, mean_stars=6, seed=5)
        degraded = est.fit(matrix2)
    finally:
        if prev is None:
            os.environ.pop("ALBEDO_DEVICE_MEM_BYTES", None)
        else:
            os.environ["ALBEDO_DEVICE_MEM_BYTES"] = prev
    mode = est.last_fit_report.get("mode")
    max_delta = float(
        max(
            np.abs(resident.user_factors - degraded.user_factors).max(),
            np.abs(resident.item_factors - degraded.item_factors).max(),
        )
    )
    ok = mode == "chunked" and max_delta < 1e-4 and (
        faults.FAULTS.hits("als.chunked") > before
    )
    return {
        "ok": bool(ok),
        "mode": mode,
        "max_factor_delta": max_delta,
        "verdict": (est.last_fit_report.get("capacity") or {}).get("verdict"),
    }


# --- legs -----------------------------------------------------------------------


def _cli_env(specs, extra_env=None) -> dict:
    env = dict(os.environ)
    env.pop("ALBEDO_FAULTS", None)
    if specs:
        env["ALBEDO_FAULTS"] = faults_env(specs)
    # Drill children run on CPU, whatever platform the parent's environment
    # names: a chip belongs to one process at a time, and a parent that has
    # touched JAX holds it — a child that needed the chip would fail or
    # hang. Only an explicit ``extra_env`` can place a child elsewhere.
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return env


def _run_cli(job: str, cli_args: list[str], specs, timeout: float,
             extra_env=None, keep_stdout: bool = False) -> dict:
    cmd = [sys.executable, "-m", "albedo_tpu.cli", job, *cli_args]
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True,
            env=_cli_env(specs, extra_env), timeout=timeout,
        )
        rc: int | str = proc.returncode
        stdout = proc.stdout
        tail = (proc.stdout + proc.stderr)[-2000:]
    except subprocess.TimeoutExpired:
        rc, stdout, tail = "timeout", "", ""
    rec = {
        "job": job, "rc": rc, "faults": [f"{s}:{k}@{a}" for s, k, a in specs],
        "wall_s": round(time.time() - t0, 1), "tail": tail,
    }
    if keep_stdout:
        # For callers that look up a job marker: the 2000-char tail of
        # stdout+stderr loses it whenever the runtime is chatty on stderr
        # (one XLA:CPU loader notice is longer than the window).
        rec["stdout"] = stdout
    return rec


class _InProcessArm:
    """Arm faults through the registry for an in-process leg, recording the
    per-site fired deltas on exit (the smoke mode's evidence channel)."""

    def __init__(self, specs):
        self.specs = specs
        self.fired: dict[str, int] = {}

    def __enter__(self):
        self._before = {s: faults.FAULTS.fired(s) for s, _, _ in self.specs}
        for site, kind, at in self.specs:
            faults.arm(site, kind=kind, at=at)
        return self

    def __exit__(self, *exc):
        for site, _, _ in self.specs:
            faults.disarm(site)
            self.fired[site] = faults.FAULTS.fired(site) - self._before[site]
        return False


def _pipeline_in_process(ctx_factory, specs, resume: bool) -> dict:
    from albedo_tpu.builders.pipeline import (
        PipelineStageFailed, PublishRejected, run_pipeline,
    )
    from albedo_tpu.utils.checkpoint import Preempted

    rc, err = 0, None
    with _InProcessArm(specs) as armed:
        try:
            run_pipeline(
                ctx_factory(), resume=resume,
                stages=["ingest", "train_als", "canary"],
                sleeper=lambda s: None, verbose=False,
            )
        except PublishRejected as e:
            rc, err = 4, repr(e)
        except Preempted as e:
            rc, err = 75, repr(e)
        except PipelineStageFailed as e:
            rc, err = 1, repr(e)
        except Exception as e:  # noqa: BLE001 — the CLI would exit 1 too
            rc, err = 1, repr(e)
    return {"job": "run_pipeline", "rc": rc, "fired": armed.fired,
            "error": err, "faults": [f"{s}:{k}@{a}" for s, k, a in specs]}


def _stream_in_process(ctx_factory, args, specs, cycle_seed: int) -> dict:
    from albedo_tpu.builders.pipeline import PipelineStageFailed, PublishRejected
    from albedo_tpu.parallel.elastic import MeshLost
    from albedo_tpu.streaming.foldin import FoldInDiverged
    from albedo_tpu.streaming.job import run_stream

    opts = argparse.Namespace(
        cycles=1, delta_batch=60, stream_seed=cycle_seed, deltas="",
        drift_tolerance=0.05, drift_floor=0.0, drift_every=1,
        half_life_days=7.0, recency_boost=1.0, foldout_limit=0,
        max_foldin_batch=16, probe_users=40, no_publish=False,
        keep_stream=3, refit_checkpoint_every=2,
    )
    # The device-loss cycle forces a MESH stream so the armed fold-in loss
    # has a collective to kill. In-process the mesh is pinned at the one
    # real CPU device: no rung below exists, so the contract is a CLEAN
    # MeshLost (rc 1) — the same 1-device branch `_elastic_fit_drill`
    # validates for the refit path.
    run_args = args
    ctx = ctx_factory()
    if any(s == "stream.foldin.collective" for s, _, _ in specs):
        run_args = argparse.Namespace(**vars(args))
        run_args.mesh_devices = 1
        ctx.args = run_args
    rc, err = 0, None
    with _InProcessArm(specs) as armed:
        try:
            run_stream(ctx, run_args, opts)
        except FoldInDiverged as e:
            rc, err = 3, repr(e)
        except PublishRejected as e:
            rc, err = 4, repr(e)
        except MeshLost as e:
            rc, err = 1, repr(e)
        except PipelineStageFailed as e:
            rc, err = 1, repr(e)
        except Exception as e:  # noqa: BLE001 — the CLI would exit 1 too
            rc, err = 1, repr(e)
    return {"job": "run_stream", "rc": rc, "fired": armed.fired,
            "error": err, "faults": [f"{s}:{k}@{a}" for s, k, a in specs]}


def _score_in_process(ctx_factory, specs) -> dict:
    """The scoring leg (non-kill cycles): one in-process ``score_all`` sweep
    over the soak dataset with the drawn ``score.*`` faults armed. A raising
    kind must surface as a contract exit code (never a hang or a torn seal);
    whatever happens, a SEALED manifest must still pass the scoring
    invariants."""
    from albedo_tpu.builders.pipeline import PublishRejected
    from albedo_tpu.parallel.elastic import MeshLost
    from albedo_tpu.scoring.sweep import (
        MANIFEST_NAME, check_score_invariants, run_score_all,
        score_output_root,
    )
    from albedo_tpu.utils.capacity import CapacityExceeded
    from albedo_tpu.utils.checkpoint import Preempted

    ctx = ctx_factory()
    rc, err = 0, None
    with _InProcessArm(specs) as armed:
        try:
            run_score_all(ctx, shard_users=48, k=10)
        except PublishRejected as e:
            rc, err = 4, repr(e)
        except Preempted as e:
            rc, err = 75, repr(e)
        except (MeshLost, CapacityExceeded) as e:
            rc, err = 1, repr(e)
        except Exception as e:  # noqa: BLE001 — the CLI would exit 1 too
            rc, err = 1, repr(e)
    out_root = score_output_root(ctx.tag)
    score_violations = (
        check_score_invariants(out_root)
        if (out_root / MANIFEST_NAME).exists()
        else []
    )
    return {"job": "score_all", "rc": rc, "fired": armed.fired, "error": err,
            "score_violations": score_violations,
            "faults": [f"{s}:{k}@{a}" for s, k, a in specs]}


def _export_score_tables(ctx) -> Path:
    """The smoke flavor's injected in-memory tables, exported once per soak
    so the scoring kill cycle's SUBPROCESS pair scores the same dataset —
    and, because both runs pass the same ``--tables`` string, shares one
    artifact tag between the killed sweep and its resume."""
    dest = ctx_artifact_dir() / "score-tables"
    if not (dest / "user_info.parquet").exists():
        dest.mkdir(parents=True, exist_ok=True)
        t = ctx.tables()
        for key in ("user_info", "repo_info", "starring", "relation"):
            getattr(t, key).to_parquet(dest / f"{key}.parquet", index=False)
    return dest


def _score_kill_resume_leg(
    args, ctx_factory, specs, timeout: float, injected_tables: bool
) -> dict:
    """The pinned ``score.spill:kill`` cycle: a real CLI ``score_all``
    subprocess is killed mid-spill (exit 137, an unsealed shard on disk),
    then a second subprocess resumes the cursor and must seal a manifest
    covering exactly the scored shards. Runs as a subprocess pair in EVERY
    soak flavor — an in-process kill would take the driver down with it."""
    from albedo_tpu.scoring.sweep import (
        MANIFEST_NAME, check_score_invariants, score_output_root,
    )
    from albedo_tpu.settings import md5

    base = ["--small", "--score-shard-users", "48", "--score-k", "10"]
    tables_src = getattr(args, "tables", None)
    if injected_tables:
        tables_src = str(_export_score_tables(ctx_factory()))
    if tables_src:
        base += ["--tables", str(tables_src)]
    # The subprocess's dataset identity tag (JobContext's computation): where
    # on disk the pair's sealed output lands.
    source = str(tables_src or f"synthetic-{bool(getattr(args, 'small', False))}")
    tag = md5(source)[:10]
    kill = _run_cli("score_all", base, specs, timeout)
    resume = _run_cli("score_all", [*base, "--resume"], [], timeout, keep_stdout=True)
    out_root = score_output_root(tag)
    violations: list[str] = []
    if kill["rc"] != KILL_CODE:
        violations.append(
            f"score kill leg exited {kill['rc']}, wanted {KILL_CODE}"
        )
    resumed = "resume:" in resume["stdout"]
    if resume["rc"] != 0:
        violations.append(f"score resume leg exited {resume['rc']}")
    elif not resumed:
        violations.append("score resume leg never walked the cursor")
    if (out_root / MANIFEST_NAME).exists():
        violations.extend(check_score_invariants(out_root))
    elif resume["rc"] == 0:
        violations.append("score resume exited 0 without sealing a manifest")
    return {
        "job": "score_all", "rc": resume["rc"], "kill_rc": kill["rc"],
        "resumed": resumed, "score_violations": violations,
        "faults": kill["faults"],
        "wall_s": round(kill["wall_s"] + resume["wall_s"], 1),
        "tail": resume["tail"][-400:],
    }


def _mesh_leg(specs, ctx_factory=None) -> dict:
    """The boot leg: a mesh request that may exceed the visible devices (or
    lose half of them to a mesh.devices fault) must remesh down the ladder,
    never assert-crash. The leg then drives a tiny ROW-SHARDED fit on the
    booted mesh (``parallel.als.ShardedALSFit`` streamed), so the
    ``als.shard.gather``/``als.shard.stream`` chaos surface is exercised
    every cycle: an armed raising kind must surface as a failed fit (the
    pipeline's fail-fast contract), never a hang or a wrong result.

    A cycle arming ``als.shard.collective:loss`` is the DEVICE-LOSS cycle:
    the fit runs through the elastic driver instead (the injected loss must
    be survived via checkpoint -> remesh -> resume to parity, or fail
    cleanly as ``MeshLost`` when no smaller rung exists), and the leg
    additionally drives the degraded-serving drill — a retrieval bank
    sealed at the full rung must promote onto the halved rung through the
    real gates and answer with single-device parity."""
    import jax

    from albedo_tpu.parallel.mesh import make_mesh

    elastic_cycle = any(
        s == "als.shard.collective" and k == "loss" for s, k, _ in specs
    )
    before = events.mesh_degraded.total()
    with _InProcessArm(specs) as armed:
        mesh = make_mesh(8)  # more than a 1-device CPU soak box has
        if elastic_cycle:
            shard_rec = _elastic_fit_drill(mesh)
        else:
            shard_rec = _sharded_fit_drill(mesh, specs)
    n = int(np.prod(list(mesh.shape.values())))
    rc = 0 if (n >= 1 and shard_rec.pop("ok")) else 1
    out = {
        "job": "mesh_boot", "rc": rc,
        "devices": n, "visible": len(jax.devices()),
        "degraded": events.mesh_degraded.total() - before,
        "sharded_fit": shard_rec,
        "fired": armed.fired,
        "faults": [f"{s}:{k}@{a}" for s, k, a in specs],
    }
    if elastic_cycle and ctx_factory is not None:
        serving_rec = _degraded_serving_drill(ctx_factory())
        if not serving_rec.pop("ok"):
            out["rc"] = 1
        out["degraded_serving"] = serving_rec
    return out


def _sharded_fit_drill(mesh, specs) -> dict:
    """One streamed sharded fit on ``mesh``. A raising kind armed on an
    ``als.shard.*`` site makes the fit fail CLEANLY (recorded, ok=True);
    any other exception, non-finite factors, or an injected fault that
    neither fired nor failed is a violation."""
    from albedo_tpu.datasets.synthetic import synthetic_stars
    from albedo_tpu.models.als import ImplicitALS

    matrix = synthetic_stars(n_users=48, n_items=32, mean_stars=5, seed=21)
    est = ImplicitALS(
        rank=4, max_iter=1, batch_size=16, seed=0, mesh=mesh,
        sharded="streamed",
    )
    shard_specs = {s for s, _, _ in specs if s.startswith("als.shard.")}
    raising = {
        s for s, k, _ in specs
        if s.startswith("als.shard.") and k in ("error", "ioerror", "oom")
    }
    try:
        model = est.fit(matrix)
    except Exception as e:  # noqa: BLE001
        injected = any(faults.FAULTS.fired(s) for s in shard_specs)
        return {
            "ok": bool(injected), "outcome": "failed",
            "error": repr(e)[-200:], "injected": injected,
        }
    finite = bool(np.isfinite(model.user_factors).all())
    # An armed RAISING shard fault that neither fired nor failed the fit is
    # zero coverage wearing a green checkmark — flag it.
    unfired = sorted(s for s in raising if not faults.FAULTS.fired(s))
    return {
        "ok": finite and not unfired,
        "outcome": "completed",
        "mode": est.last_fit_report.get("mode"),
        "streamed_buckets": est.last_fit_report.get("streamed_buckets"),
        "unfired_faults": unfired,
    }


def _elastic_fit_drill(mesh) -> dict:
    """The device-loss cycle's fit drill: an armed ``als.shard.collective``
    ``loss`` fires mid-sweep inside an elastic checkpointed fit. On a mesh
    with a rung below, the driver must checkpoint, remesh down the ladder,
    resume, and land factors matching a clean single-device fit at 1e-5 —
    with the loss journaled and counted. On a 1-device mesh (a bare CPU
    soak box) there is no rung left: the contract is a CLEAN ``MeshLost``
    with journal status ``mesh_lost`` — never a hang, never a wrong
    result."""
    import json as _json
    import tempfile

    from albedo_tpu.datasets.synthetic import synthetic_stars
    from albedo_tpu.models.als import ImplicitALS
    from albedo_tpu.parallel.elastic import MeshLost, elastic_sharded_fit
    from albedo_tpu.parallel.mesh import DATA_AXIS

    matrix = synthetic_stars(n_users=48, n_items=32, mean_stars=5, seed=21)
    kw = dict(rank=4, max_iter=2, batch_size=16, seed=0)
    reference = ImplicitALS(**kw, chunked=False).fit(matrix)
    est = ImplicitALS(**kw, mesh=mesh, sharded="streamed")
    n_start = int(mesh.shape[DATA_AXIS])
    losses_before = events.mesh_losses.total()
    with tempfile.TemporaryDirectory() as d:
        try:
            model = elastic_sharded_fit(est, matrix, d, every=1)
        except MeshLost:
            journal = _json.loads((Path(d) / "journal.json").read_text())
            ok = (
                n_start == 1
                and journal.get("status") == "mesh_lost"
                and "cause" in journal
                and events.mesh_losses.total() > losses_before
            )
            return {"ok": ok, "outcome": "mesh_lost", "n_shards": n_start,
                    "journal_status": journal.get("status")}
        except Exception as e:  # noqa: BLE001
            return {"ok": False, "outcome": "failed", "error": repr(e)[-200:]}
        journal = _json.loads((Path(d) / "journal.json").read_text())
    me = est.last_fit_report.get("mesh_events", {})
    delta = float(max(
        np.abs(model.user_factors - reference.user_factors).max(),
        np.abs(model.item_factors - reference.item_factors).max(),
    ))
    ok = (
        me.get("losses", 0) >= 1
        and me.get("resumes", 0) >= 1
        and events.mesh_losses.total() > losses_before
        and events.elastic_resumes.value(outcome="resumed") >= 1
        and journal.get("status") == "complete"
        and journal.get("mesh_events", {}).get("losses", 0) >= 1
        and delta < 1e-5
    )
    return {
        "ok": ok, "outcome": "resumed",
        "losses": me.get("losses"), "resumes": me.get("resumes"),
        "n_shards": f"{n_start} -> {me.get('n_shards')}",
        "max_factor_delta": delta,
        "journal_status": journal.get("status"),
    }


def _degraded_serving_drill(ctx) -> dict:
    """Degraded-mesh serving acceptance: a retrieval bank built and SEALED
    at the full rung (N item shards) promotes through the real BankStage
    gates onto the halved rung — the mesh a device loss leaves serving —
    and answers queries with single-device parity. A capacity refusal at
    the smaller rung would stay a recorded non-quarantine rejection (the
    reload convention); anything else is a violation."""
    import jax

    from albedo_tpu.parallel.mesh import make_mesh
    from albedo_tpu.retrieval.bank import RetrievalBank
    from albedo_tpu.retrieval.stage import BankStage

    n = len(jax.devices())
    if n <= 1:
        # No smaller rung exists to promote onto: claiming "promoted" here
        # would overstate chaos coverage — the elastic fit drill already
        # validates the explicit 1-device (MeshLost) contract.
        return {"ok": True, "outcome": "skipped (single device)"}

    matrix = ctx.matrix()
    model = ctx.als_model()

    def mk_bank() -> RetrievalBank:
        bank = RetrievalBank(max_batch=8)
        bank.register_source(
            "als", kind="user_rows", vectors=model.item_factors,
            item_ids=np.asarray(matrix.item_ids),
            user_vectors=model.user_factors,
        )
        return bank

    full = make_mesh(n, data=1, item=n)
    rung = make_mesh(max(1, n // 2), data=1, item=max(1, n // 2))
    name = f"{ctx.tag}-elasticBank-drill.pkl"
    try:
        sealed = mk_bank().build(matrix=matrix, mesh=full)
        sealed.save(name, lineage={"drill": "degraded-serving"})
        stage = BankStage(mk_bank().build(matrix=matrix, mesh=full), matrix)
        report = stage.reload(name, require_stamp=True, mesh=rung)
        if report.get("outcome") != "promoted":
            return {"ok": False, "outcome": report.get("outcome"),
                    "gate": report.get("gate"), "why": report.get("why")}
        # Parity: the promoted degraded-rung bank vs a single-device build.
        q = np.arange(min(4, matrix.n_users), dtype=np.int64)
        got = stage.bank.query(q, k=5, sources=("als",))["als"]
        ref = mk_bank().build(matrix=matrix).query(q, k=5, sources=("als",))["als"]
        delta = float(np.abs(got[0] - ref[0]).max()) if got[0].size else 0.0
        ok = delta < 1e-5 and bool(np.array_equal(got[1], ref[1]))
        return {
            "ok": ok, "outcome": "promoted",
            "built_at_shards": n, "promoted_on_shards": max(1, n // 2),
            "max_score_delta": delta,
        }
    except Exception as e:  # noqa: BLE001
        return {"ok": False, "outcome": "failed", "error": repr(e)[-200:]}


def _serve_leg(ctx, specs) -> dict:
    """In-process serving leg: boot a service on the current model, drive
    one validated reload of the newest published candidate through the REAL
    gates (require_stamp on), then probe live traffic and run a short
    open-loop under-load burst (PR 20's overload contract: zero 5xx,
    offered/completed parity). The incumbent must keep answering whatever
    the gates decide."""
    from albedo_tpu.serving import HotSwapManager, RecommendationService

    out: dict = {"job": "serve", "rc": 0, "fired": {}, "probes": 0,
                 "faults": [f"{s}:{k}@{a}" for s, k, a in specs]}
    service = RecommendationService(
        ctx.als_model(), ctx.matrix(),
        repo_info=ctx.tables().repo_info, user_info=ctx.tables().user_info,
        batching=True, batch_window_ms=0.0, warm=False,
    )
    try:
        manager = HotSwapManager(
            service, artifact_glob=f"{ctx.tag}-alsModel-*.pkl",
            require_stamp=True,
        )
        with _InProcessArm(specs) as armed:
            report = manager.request_reload()
        out["fired"] = armed.fired
        out["reload_outcome"] = report.get("outcome")
        out["reload_gate"] = report.get("gate")
        # Invariant: a capacity rejection is recorded, never quarantined.
        if report.get("gate") == "capacity":
            art = report.get("artifact")
            if art and not (
                Path(ctx_artifact_dir() / art).exists()
            ):
                out["rc"] = 1
                out["error"] = "capacity rejection quarantined the artifact"
        # Invariant: whatever happened above, live traffic still answers.
        matrix = ctx.matrix()
        users = matrix.user_ids[np.linspace(
            0, matrix.n_users - 1, 3, dtype=np.int64
        )]
        for uid in users:
            status, body = service.handle_recommend(int(uid), k=5)
            if status == 200 and all(
                np.isfinite(i["score"]) for i in body.get("items", [])
            ):
                out["probes"] += 1
            else:
                out["rc"] = 1
                out["error"] = f"probe user {uid}: status {status}"
        # Invariant: no unstamped artifact served — require_stamp guarantees
        # a promoted candidate passed the stamp gate; assert the record.
        if out["reload_outcome"] == "promoted":
            stamp = report["gates"].get("stamp")
            if not isinstance(stamp, dict):
                out["rc"] = 1
                out["error"] = "promoted without a stamp-gate record"
        # Admission probe: one explicit degradable admission, so the
        # capacity.admit site is reachable this leg even when an earlier
        # reload gate rejected the candidate before its capacity gate. An
        # armed oom must convert to a `degrade` verdict, never a crash.
        from albedo_tpu.utils import capacity

        with _InProcessArm(
            [s for s in specs if s[0] == "capacity.admit"]
        ) as probe_armed:
            verdict = capacity.admit(
                capacity.plan_foldin(8, 8, 8, 64), degradable=True
            )
        out["admission_probe"] = verdict.verdict
        for site, n in probe_armed.fired.items():
            out["fired"][site] = out["fired"].get(site, 0) + n
        if verdict.verdict == "refuse":
            out["rc"] = 1
            out["error"] = "degradable admission probe refused"
        # Under-load leg (the overload contract in the soak loop, not just
        # the dedicated bench): a short open-loop burst through the live
        # service must hold zero 5xx and strict offered/completed parity —
        # shed requests come back as priced, tier-tagged 429s.
        from albedo_tpu.loadgen import OpenLoopLoadGen
        from albedo_tpu.serving import QueueOverflow
        from albedo_tpu.serving.batcher import DeadlineExceeded

        def load_fn(i: int):
            uid = int(users[i % len(users)])
            try:
                return service.handle_recommend(uid, k=5)
            except (QueueOverflow, DeadlineExceeded) as e:
                body = {"error": str(e)}
                tier = getattr(e, "tier", None)
                if tier is not None:
                    body["brownout"] = {
                        "level": getattr(e, "level", None), "tier": tier,
                    }
                return 429, body
            except Exception as e:  # noqa: BLE001 — the contract under test
                return 500, {"error": repr(e)}

        load = OpenLoopLoadGen(
            load_fn, rate_hz=60.0, duration_s=1.0, budget_s=0.25, workers=8,
        ).run()
        out["load"] = {
            "offered": load["offered"], "completed": load["completed"],
            "n_5xx": load["n_5xx"],
            "transport_errors": load["transport_errors"],
            "parity_ok": load["parity_ok"],
            "p99_s": load["latency_s"]["p99"],
            "brownout_tiers_seen": load["brownout_tiers_seen"],
        }
        if load["n_5xx"] or load["transport_errors"] or not load["parity_ok"]:
            out["rc"] = 1
            out["error"] = f"under-load leg broke the overload contract: {out['load']}"
    finally:
        service.close()
    return out


def ctx_artifact_dir() -> Path:
    from albedo_tpu.datasets import artifacts as store

    return store.get_settings().artifact_dir


# --- the driver -----------------------------------------------------------------


def run_soak(
    args,
    cycles: int = 10,
    seed: int = 42,
    subprocess_legs: bool = True,
    leg_timeout: float = 560.0,
    ctx_kwargs: dict | None = None,
) -> dict:
    """Drive the soak; returns the report dict (also written to the store).

    ``subprocess_legs=False`` is the smoke flavor: pipeline/stream run
    in-process (kill/term excluded — they would kill the caller), every
    fired fault is read back from the in-process registry. ``ctx_kwargs``
    (e.g. ``tables=``/``tag=``) shrink the in-process dataset for smoke runs.
    """
    from albedo_tpu.builders.jobs import JobContext

    # Pin ONE date for the whole run (today's, unless the caller pinned
    # their own): the in-process legs and every subprocess leg must key the
    # same artifact tag even across a midnight boundary.
    os.environ.setdefault("ALBEDO_TODAY", time.strftime("%Y%m%d"))
    t0 = time.time()
    schedule = build_schedule(cycles, seed, include_kill_term=subprocess_legs)

    def ctx_factory():
        return JobContext(args, **(ctx_kwargs or {}))

    report: dict = {
        "seed": seed,
        "cycles_planned": cycles,
        "subprocess_legs": subprocess_legs,
        "capacity_drill": capacity_drill(),
        "cycles": [],
        "kinds_observed": {},
        "violations": [],
    }
    kinds_observed: dict[str, str] = {}
    resume_next = False

    def observe_in_process(leg_record, specs):
        for site, kind, _ in specs:
            if leg_record.get("fired", {}).get(site, 0) > 0:
                kinds_observed.setdefault(
                    kind, f"fired in-process at {site} "
                    f"(cycle {len(report['cycles']) + 1})"
                )

    for c, plan in enumerate(schedule):
        cycle: dict = {"cycle": c + 1, "legs": []}

        mesh_rec = _mesh_leg(plan["mesh"], ctx_factory=ctx_factory)
        cycle["legs"].append(mesh_rec)
        observe_in_process(mesh_rec, plan["mesh"])
        if mesh_rec["rc"] != 0:
            report["violations"].append(
                f"cycle {c + 1} mesh leg: "
                f"{mesh_rec.get('sharded_fit', mesh_rec)}"
            )

        pipeline_args = [
            "--small", "--checkpoint-every", "2",
            "--stages", "ingest,train_als,canary",
        ]
        if subprocess_legs:
            rec = _run_cli(
                "run_pipeline",
                pipeline_args + (["--resume"] if resume_next else []),
                plan["pipeline"], leg_timeout,
            )
        else:
            rec = _pipeline_in_process(ctx_factory, plan["pipeline"], resume_next)
            observe_in_process(rec, plan["pipeline"])
        cycle["legs"].append(rec)
        armed_kinds = {k for _, k, _ in plan["pipeline"]}
        if rec["rc"] == KILL_CODE and "kill" in armed_kinds:
            kinds_observed.setdefault("kill", f"exit 137 (cycle {c + 1})")
        if rec["rc"] == 75 and "term" in armed_kinds:
            kinds_observed.setdefault("term", f"exit 75 (cycle {c + 1})")
        allowed = CONTRACT_CODES | ({KILL_CODE} if "kill" in armed_kinds else set())
        if rec["rc"] not in allowed:
            report["violations"].append(
                f"cycle {c + 1} pipeline exit code {rec['rc']} outside the "
                f"contract {sorted(allowed)}"
            )
        resume_next = rec["rc"] in (75, KILL_CODE)

        serve_rec = _serve_leg(ctx_factory(), plan["serve"])
        cycle["legs"].append(serve_rec)
        observe_in_process(serve_rec, plan["serve"])
        if serve_rec["rc"] != 0:
            report["violations"].append(
                f"cycle {c + 1} serve leg: {serve_rec.get('error', 'failed')}"
            )

        if subprocess_legs:
            stream_args = [
                "--small", "--cycles", "1", "--delta-batch", "60",
                "--stream-seed", str(seed + c), "--probe-users", "40",
            ]
            stream_env = None
            if any(s == "stream.foldin.collective" for s, _, _ in plan["stream"]):
                # The device-loss cycle's stream leg: 2 virtual host devices,
                # so the injected fold-in loss has a rung below to remesh
                # onto — the cycle must COMPLETE on 1 shard (rc 0), with the
                # loss on the journal's mesh_events trail.
                stream_args += ["--mesh-devices", "2"]
                stream_env = {
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                }
            stream_rec = _run_cli(
                "run_stream", stream_args, plan["stream"], leg_timeout,
                extra_env=stream_env,
            )
        else:
            stream_rec = _stream_in_process(
                ctx_factory, args, plan["stream"], seed + c
            )
            observe_in_process(stream_rec, plan["stream"])
        cycle["legs"].append(stream_rec)
        s_kinds = {k for _, k, _ in plan["stream"]}
        s_allowed = CONTRACT_CODES | ({KILL_CODE} if "kill" in s_kinds else set())
        if stream_rec["rc"] not in s_allowed:
            report["violations"].append(
                f"cycle {c + 1} stream exit code {stream_rec['rc']} outside "
                f"the contract {sorted(s_allowed)}"
            )

        score_specs = plan.get("score", [])
        if any(k == "kill" for _, k, _ in score_specs):
            score_rec = _score_kill_resume_leg(
                args, ctx_factory, score_specs, leg_timeout,
                injected_tables="tables" in (ctx_kwargs or {}),
            )
            if score_rec.get("kill_rc") == KILL_CODE:
                kinds_observed.setdefault(
                    "kill", f"score_all exit 137 (cycle {c + 1})"
                )
        else:
            score_rec = _score_in_process(ctx_factory, score_specs)
            observe_in_process(score_rec, score_specs)
        cycle["legs"].append(score_rec)
        if score_rec["rc"] not in CONTRACT_CODES:
            report["violations"].append(
                f"cycle {c + 1} score exit code {score_rec['rc']} outside "
                f"the contract {sorted(CONTRACT_CODES)}"
            )
        report["violations"].extend(
            f"cycle {c + 1} score leg: {v}"
            for v in score_rec.get("score_violations", [])
        )

        cycle["invariant_violations"] = check_invariants(ctx_artifact_dir())
        report["violations"].extend(
            f"cycle {c + 1}: {v}" for v in cycle["invariant_violations"]
        )
        report["cycles"].append(cycle)
        log.info(
            "soak cycle %d/%d: rcs=%s violations=%d", c + 1, cycles,
            [leg["rc"] for leg in cycle["legs"]],
            len(cycle["invariant_violations"]),
        )

    if not report["capacity_drill"]["ok"]:
        report["violations"].append(
            f"capacity drill failed: {report['capacity_drill']}"
        )
    expected_kinds = set(KIND_EVIDENCE)
    if not subprocess_legs:
        # `kill` stays expected: the pinned scoring kill cycle runs as a
        # real subprocess pair even in the in-process smoke flavor.
        expected_kinds -= {"term"}
    missing = expected_kinds - set(kinds_observed)
    if missing:
        report["violations"].append(
            f"fault kinds never observed firing: {sorted(missing)}"
        )
    report["kinds_observed"] = kinds_observed
    report["wall_clock_s"] = round(time.time() - t0, 1)
    report["ok"] = not report["violations"]

    from albedo_tpu.utils.jsonio import atomic_write_json

    out_path = ctx_artifact_dir() / REPORT_NAME
    out_path.parent.mkdir(parents=True, exist_ok=True)
    atomic_write_json(out_path, report, indent=2)
    report["report_path"] = str(out_path)
    return report


@register_job("soak")
def soak_job(args) -> int | None:
    """The full-loop chaos soak (see module docstring).

    Extra flags: --soak-cycles N (default 10), --soak-seed N (default 42),
    --in-process (the smoke flavor: pipeline/stream legs run in-process and
    kill/term kinds are excluded), --leg-timeout SECONDS (default 560).
    Honors the global --small (recommended) and --tables. Exit codes:
    0 every invariant green, 1 otherwise.
    """
    extra = argparse.ArgumentParser()
    extra.add_argument("--soak-cycles", type=int, default=10)
    extra.add_argument("--soak-seed", type=int, default=42)
    extra.add_argument("--in-process", action="store_true")
    extra.add_argument("--leg-timeout", type=float, default=560.0)
    ns, _ = extra.parse_known_args(getattr(args, "_rest", []))

    report = run_soak(
        args, cycles=ns.soak_cycles, seed=ns.soak_seed,
        subprocess_legs=not ns.in_process, leg_timeout=ns.leg_timeout,
    )
    print(f"[soak] {report['cycles_planned']} cycle(s) in "
          f"{report['wall_clock_s']}s; kinds observed: "
          f"{sorted(report['kinds_observed'])}")
    for v in report["violations"]:
        print(f"[soak] INVARIANT VIOLATED: {v}")
    print(f"[soak] report: {report['report_path']}")
    print(f"[soak] {'ALL INVARIANTS GREEN' if report['ok'] else 'FAILED'}")
    return None if report["ok"] else 1
