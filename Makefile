# Launcher parity with the reference's Makefile targets (reference
# Makefile:131-218 wraps spark-submit; here each target wraps the CLI).
# Usage: make train_als [ARGS="--small --tables path/to/tables"]

PY ?= python
ARGS ?=

JOBS = popularity curation content train_als cv_als build_user_profile \
       build_repo_profile train_word2vec train_lr cv_lr item_cf user_cf \
       tfidf_content ranking_mf collect_data drop_data sync_index serve play \
       run_pipeline datacheck run_stream build_bank

.PHONY: $(JOBS) test test-all bench chip-smoke serve-bench datacheck-bench chaos \
        chaos-serve chaos-stream chaos-elastic stream stream-bench dryrun \
        soak soak-smoke capacity-bench retrieval-bench lint lint-baseline \
        sanitize score score-bench loadgen chaos-load

$(JOBS):
	$(PY) -m albedo_tpu.cli $@ $(ARGS)

# Tier-1: the slow-marked load tests run via test-all, not here.
test:
	$(PY) -m pytest tests/ -q -m 'not slow'

# graftlint (albedo_tpu/analysis): the repo's JAX-aware static analysis —
# bare-jit, hidden-host-sync, contract-drift, dtype-discipline,
# retrace-hazard. Exits 0 only when every finding is fixed, pragma'd with a
# reason, or baselined (see ARCHITECTURE.md "Static analysis"). Never
# imports jax — safe anywhere.
lint:
	$(PY) -m albedo_tpu.analysis

# Regenerate .graftlint-baseline.json from the current findings. Review the
# diff: shrinking is progress, growth needs a reason in the PR.
lint-baseline:
	$(PY) -m albedo_tpu.analysis --write-baseline

# The runtime complement of graftlint's concurrency tier (R6-R8): re-run
# the threaded suites (micro-batcher, hot-swap reload, breakers, elastic,
# locksmith's own drills) plus the soak smoke leg with the locksmith
# lock-order sanitizer armed (ALBEDO_LOCKCHECK=1). Every lock created via
# analysis.locksmith.named_lock is tracked per thread; an ABBA inversion,
# a self-deadlock, or an unguarded shared access fails the run and counts
# in albedo_lockcheck_violations_total{kind=}. See ARCHITECTURE.md
# "Concurrency".
sanitize:
	JAX_PLATFORMS=cpu ALBEDO_LOCKCHECK=1 $(PY) -m pytest \
	  tests/test_locksmith.py tests/test_serving_batcher.py \
	  tests/test_serving_reload.py tests/test_serving_breaker.py \
	  tests/test_elastic.py -q -m 'not slow'
	JAX_PLATFORMS=cpu ALBEDO_LOCKCHECK=1 $(PY) -m pytest \
	  tests/test_soak.py -q -m chaos

test-all:
	$(PY) -m pytest tests/ -q

# One process, JAX initialized once in it, no child process (a chip belongs
# to one process at a time). Exits non-zero if ANY phase it ran failed.
bench:
	$(PY) bench.py

# The quickest proof that the train -> serve path still starts on the chip
# (README "Running on CPU and on the chip"). Needs a TPU: exits non-zero and
# prints no result where JAX finds none. From the builders' sandbox:
#   chiprun --chips 1 -- python chip_smoke.py
chip-smoke:
	$(PY) chip_smoke.py

# Online-engine scenario: micro-batched vs per-request throughput/p50/p99
# under concurrent load (env knobs: ALBEDO_SERVE_USERS/ITEMS/CONCURRENCY/
# DURATION/TRIALS/K).
serve-bench:
	$(PY) bench.py serving

# Ingest-validation overhead scenario: firewall off vs repair over the same
# tables, interleaved trials, median overhead fraction (<5% budget).
datacheck-bench:
	$(PY) bench.py datacheck

# Fault-injection drills: the full chaos matrix (corrupt-artifact healing,
# kill/SIGTERM-resume parity through the real CLI, fault-injected serving
# degradation over HTTP). CPU-safe; includes the slow subprocess drills.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos

# Serving-plane chaos only (fast; no CLI subprocess drills): corrupt-artifact
# hot-swap quarantine, swap-under-load parity, breaker trip/recovery, and
# overload shedding through real HTTP.
chaos-serve:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos -k "serving or reload or breaker"

# Streaming chaos: kill mid-fold-in through the real CLI — the served
# generation must never be a half-applied delta.
chaos-stream:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos_stream.py -q -m chaos

# The minutes-stale loop: validated delta ingest -> fold-in -> drift check
# -> stamped hot-swap publish (see README "Streaming runbook").
stream:
	$(PY) -m albedo_tpu.cli run_stream $(ARGS)

# Streaming scenario: fold-in latency per touched-user batch, sustained
# deltas/sec, and the fold-in-vs-full-refit wall-clock ratio (interleaved
# trials, medians — per the bench-box throttling policy).
stream-bench:
	$(PY) bench.py foldin

# Full-loop chaos soak: seeded random fault schedules over the whole
# catalogued site inventory, driven through repeated ingest -> train ->
# publish -> serve -> stream cycles with the standing invariants checked
# every cycle (albedo_tpu/chaos/soak.py). Bounded: 10 cycles, seeded.
# Exit 1 on the first broken invariant; report lands in the artifact dir.
soak:
	JAX_PLATFORMS=cpu $(PY) -m albedo_tpu.cli soak --small $(ARGS)

# The fast in-process subset (kill/term excluded) — also runs in tier-1
# under the chaos marker.
soak-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_soak.py -q -m chaos

# Elastic-operation chaos: mesh-portable checkpoint roundtrips, the
# mid-fit device-loss remesh-resume drill, the degraded-mesh serving
# (bank reshard/promote) parity checks, and the cross-mesh kill-resume
# drill through the real CLI (8 virtual devices -> resume on 4). Runs the
# WHOLE elastic suite (no marker filter): the in-process drills are the
# tier-1 flavor, the chaos-marked CLI drill is the subprocess acceptance.
chaos-elastic:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_elastic.py -q

# Open-loop load-harness smoke: the scheduled-tick latency, parity
# accounting, and loadgen.tick hole-punch tests — seconds, no device work
# (albedo_tpu/loadgen/; see README "Overload runbook").
loadgen:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_loadgen.py tests/test_overload.py -q

# Chaos under load: calibrate closed-loop capacity, then offer 2x open-loop
# while firing hot-swap / reshard / fold-in publish / breaker-trip legs
# mid-surge. Gates: zero 5xx, brownout engaged AND recovered, p999 bounded,
# every chaos leg observed, request parity -> SERVING_r02.json (env knobs:
# ALBEDO_OVERLOAD_USERS/ITEMS/SURGE_S/SLO/WORKERS/P999_BOUND).
chaos-load:
	JAX_PLATFORMS=cpu $(PY) bench.py overload

# Capacity scenario: chunked-fallback overhead vs the device-resident fit
# (interleaved trials, medians — per the bench-box throttling policy).
capacity-bench:
	$(PY) bench.py capacity

# Retrieval scenario: the bank-backed fused candidate stage vs the threaded
# per-source fan-out over identical sources — candidate-set parity gate
# first, then interleaved closed-loop trials (sustained candidate rps,
# p50/p99, achieved GB/s) -> RETRIEVAL_r01.json.
retrieval-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py retrieval

# Full-catalog batch scoring: every user through bank MIPS + the LR
# re-rank, per-shard top-k parquet sealed under a canary-gated manifest
# (albedo_tpu/scoring/). Preemptible (exit 75 + --resume), elastic
# (--mesh-devices N remeshes down the ladder on device loss), admission-
# priced before any byte moves. See README "Batch-scoring runbook".
score:
	JAX_PLATFORMS=cpu $(PY) -m albedo_tpu.cli score_all $(ARGS)

# Scoring scenario: sweep throughput (users/s per chip, chip-seconds per
# million users) plus the 10M-user x 1M-item out-of-core admission pricing
# (resident vs streamed rung) -> SCORING_r01.json.
score-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py scoring

# ALX-scale weak scaling: the fully sharded PIPELINED streamed fit at
# 1 -> 2 -> 4 -> 8 chips with fixed work per chip (out-of-core synthetic
# star matrices), per-sweep wall-clock + achieved GB/s per chip vs roofline
# + per-stage overlap accounting (interleaved sync-dataflow trials) + the
# largest-fittable-matrix estimate -> MULTICHIP_r07.json (see README
# "Scale runbook").
scale-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py scale

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"
