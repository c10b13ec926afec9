"""CLI entry point: ``albedo-tpu <job> [options]``.

Replaces the reference's Makefile targets (``make train_als``, ``make train_lr``,
... each wrapping ``spark-submit --class ws.vinta.albedo.X``, ``Makefile:131-218``).
Jobs are registered by the builder modules as they land.
"""

from __future__ import annotations

import argparse
import sys
from typing import Callable

# The process exit-code contract — ONE definition, used by every job module
# and enforced both directions (code <-> ARCHITECTURE.md table) by
# graftlint's contract-drift rule (albedo_tpu/analysis). Automation keys off
# these: a scheduler reruns 75 with --resume, treats 3/4 as verdicts (the
# same input produces the same answer), and pages on 1.
EXIT_OK = 0
EXIT_FAILURE = 1       # crash / stage failure / datacheck violations
EXIT_USAGE = 2         # bad invocation (argparse convention)
EXIT_REFUSED = 3       # verdict: training/fold-in diverged, or an explicit refusal
EXIT_REJECTED = 4      # verdict: canary/publish gate rejected the artifact
EXIT_PREEMPTED = 75    # EX_TEMPFAIL: checkpointed under SIGTERM; rerun --resume
EXIT_KILLED = 137      # SIGKILL (preempted pod / injected kill fault)

_JOBS: dict[str, Callable[[argparse.Namespace], None]] = {}


def register_job(name: str):
    def deco(fn: Callable[[argparse.Namespace], None]):
        _JOBS[name] = fn
        return fn

    return deco


def build_parser() -> argparse.ArgumentParser:
    """The CLI's argument parser — one definition, so anything that drives
    the jobs' objects without ``main`` (``chip_smoke.py``) builds its
    namespace from the same flags and defaults a user's command line gets."""
    _load_builders()
    parser = argparse.ArgumentParser(prog="albedo-tpu")
    parser.add_argument("job", choices=sorted(_JOBS) or ["none"], help="job to run")
    parser.add_argument("--small", action="store_true", help="laptop-scale run")
    parser.add_argument(
        "--tables",
        default=None,
        help="raw-table source: CSV/parquet directory or sqlite db "
        "(default: deterministic synthetic tables)",
    )
    parser.add_argument(
        "--now", type=float, default=None,
        help="epoch seconds for date features (default: wall clock). "
        "score_all pins this into its sweep cursor at generation start, and "
        "--resume restores the pinned instant so resumed shards re-rank "
        "with the same featurization the sealed shards used",
    )
    parser.add_argument(
        "--data-policy",
        choices=("strict", "repair", "off"),
        default=None,
        help="ingest data-quality firewall (datasets/validate.py): strict = "
        "any bad star row fails the job, repair (default) = drop bad rows "
        "and quarantine them to a reviewable sidecar, off = trust the data "
        "(the seed path). Violations are counted per rule in "
        "albedo_data_violations_total on /metrics",
    )
    parser.add_argument(
        "--solver",
        choices=("cholesky", "cg"),
        default="cholesky",
        help="ALS normal-equation solver: exact Cholesky (MLlib parity, "
        "default) or matrix-free warm-started CG (fast path)",
    )
    parser.add_argument(
        "--cg-steps", type=int, default=3, help="CG steps per half-sweep (--solver cg)"
    )
    parser.add_argument(
        "--checkpoint-every",
        type=int,
        default=0,
        help="checkpoint ALS factors every N iterations (0 = off); a killed "
        "run rerun with --resume continues from the latest readable step",
    )
    parser.add_argument(
        "--resume",
        action="store_true",
        help="resume from existing checkpoints / completed pipeline stages "
        "instead of starting over (train_als, cv_als, run_pipeline)",
    )
    parser.add_argument(
        "--keep-last",
        type=int,
        default=3,
        help="checkpoint retention: keep the newest N steps (default 3; "
        "0 = keep every step)",
    )
    parser.add_argument(
        "--mesh-devices",
        type=int,
        default=0,
        help="train ALS on a device mesh of N devices (0 = single device). "
        "Fewer visible devices than requested remesh down the degraded "
        "8 -> 4 -> 2 -> 1 ladder (parallel/mesh.py) — which is also how a "
        "checkpointed sharded fit resumes on a smaller slice",
    )
    parser.add_argument(
        "--sharded",
        choices=("auto", "resident", "streamed", "streamed_sync"),
        default="auto",
        help="mesh-fit shard layout (--mesh-devices > 0): auto = the "
        "capacity admission ladder picks, resident = row-sharded factor "
        "tables with device-resident buckets, streamed = additionally "
        "stream interaction buckets from the host per half-sweep (the "
        "PIPELINED dataflow — double-buffered prefetch, overlapped ring "
        "phases, fused landing), "
        "streamed_sync = pin the synchronous single-slab streamed dataflow "
        "(the cheapest admission rung and the A/B triage path). With "
        "--checkpoint-every the fit runs the ELASTIC driver "
        "(parallel/elastic.py): mesh-portable sweep-boundary checkpoints, "
        "mid-fit device-loss detection, remesh-resume",
    )
    parser.add_argument(
        "--shard-mode",
        choices=("allgather", "ring"),
        default="allgather",
        help="sharded-fit source assembly: allgather (the full table, "
        "assembled once a half-sweep with resident buckets and every device "
        "solving its own rows; once a bucket with streamed ones) or ring "
        "(ppermute'd 1/n shards, a bucket at a time, cholesky only)",
    )
    parser.add_argument(
        "--no-compilation-cache",
        action="store_true",
        help="disable the persistent XLA executable cache (on by default; "
        "directory = JAX_COMPILATION_CACHE_DIR when set, else the fixed "
        "<checkout>/.jax-cache; ALBEDO_JAX_CACHE=0 is the env "
        "equivalent of this flag). Cached-executable reuse is "
        "output-fingerprint verified (utils/aot.py; ALBEDO_AOT_FINGERPRINT=0 "
        "to skip the check): an executable that cannot reproduce the "
        "exporting process's probe output is discarded and recompiled",
    )
    parser.add_argument(
        "--platform",
        default=None,
        help="force a jax platform (e.g. 'cpu') — the laptop-mode switch "
        "(reference RUN_WITH_INTELLIJ local master), applied before any "
        "backend use. How tests and drills run on CPU; equivalent to "
        "JAX_PLATFORMS in the environment.",
    )
    return parser


def main(argv: list[str] | None = None) -> int:
    args, _rest = build_parser().parse_known_args(argv)
    # log4j.properties analogue: WARN root / quiet backends / app at INFO
    # (ALBEDO_LOG_LEVEL overrides).
    from albedo_tpu.utils.log import configure_logging

    configure_logging()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    args._rest = _rest  # job-specific flags (e.g. collect_data --db/--token)
    if args.job not in _JOBS:
        print(f"no such job: {args.job}", file=sys.stderr)
        return EXIT_USAGE
    # After arg validation: persistent executable cache, so repeat job
    # submissions skip XLA compile. Env-var-based when jax isn't imported
    # yet — host-only jobs never pay the jax import for this. Opt out with
    # --no-compilation-cache (or ALBEDO_JAX_CACHE=0).
    if not args.no_compilation_cache:
        from albedo_tpu.utils.compilation_cache import enable_persistent_compilation_cache

        enable_persistent_compilation_cache()
    # Join the multi-host world (launcher env-configured; single-process runs
    # are a no-op) BEFORE any job touches jax.devices()/make_mesh, so meshes
    # span every host's devices (parallel/mesh.py init_distributed).
    from albedo_tpu.parallel.mesh import init_distributed

    n_proc = init_distributed()
    if n_proc > 1:
        print(f"[cli] joined distributed world: {n_proc} processes")
    # init_distributed imported jax: re-invoke the cache enabler so the
    # torn-write hardening patch (harden_jax_cache_writes) is applied — the
    # first call above ran before jax existed and could only set env vars.
    if not args.no_compilation_cache:
        from albedo_tpu.utils.compilation_cache import enable_persistent_compilation_cache

        enable_persistent_compilation_cache()
    from albedo_tpu.utils.checkpoint import Preempted

    try:
        rc = _JOBS[args.job](args)
    except Preempted as e:
        # SIGTERM/SIGINT landed mid-fit and the loop checkpointed: exit
        # clean-but-incomplete (EX_TEMPFAIL) so schedulers rerun with --resume.
        print(f"[cli] {e}; rerun with --resume to continue", file=sys.stderr)
        return EXIT_PREEMPTED
    # Jobs may return an int exit code (e.g. drop_data's refusal); None = ok.
    return int(rc) if isinstance(rc, int) else EXIT_OK


def _load_builders() -> None:
    try:
        import albedo_tpu.builders  # noqa: F401  (registers jobs on import)
    except ImportError:
        # Surface the real failure — a swallowed import error would otherwise
        # masquerade as "no such job".
        import traceback

        print("warning: failed to load builder jobs:", file=sys.stderr)
        traceback.print_exc()


if __name__ == "__main__":
    # Under `python -m albedo_tpu.cli` this file runs as `__main__`, but jobs
    # register into the canonical `albedo_tpu.cli` module — delegate to it.
    from albedo_tpu.cli import main as _canonical_main

    sys.exit(_canonical_main())
