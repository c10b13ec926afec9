# Container parity with the reference's ops layer (Dockerfile + the django
# service of docker-compose.yml:4-32). One image serves every Makefile target:
#
#   docker build -t albedo-tpu .
#   docker run --rm -p 8080:8080 albedo-tpu
#   docker run --rm albedo-tpu make bench
#   docker run --rm albedo-tpu make test
#
# The default CPU jax wheel runs everything (tests, dryrun, serving, CPU
# bench). On Cloud TPU VMs, build with the TPU extra instead:
#   docker build --build-arg JAX_EXTRA=tpu -t albedo-tpu-tpu .
# and run with the TPU runtime mounted (--privileged --net=host on the VM).
#
# NOTE (build environment): this repository's CI image has zero network
# egress, so `docker build` cannot be executed there; the Dockerfile is
# validated by inspection and mirrors the exact dependency set the baked-in
# environment provides (jax, flax, optax, orbax, chex, einops, pytest).

FROM python:3.12-slim

ARG JAX_EXTRA=cpu
# Optional-dependency extras baked into the image (comma-separated names from
# [project.optional-dependencies]): mysql makes the compose `ingest` profile's
# mysql:// table source work from the app container; checkpoint enables the
# Orbax-backed resumable ALS fit.
ARG PIP_EXTRAS=mysql,checkpoint

WORKDIR /app

# Dependency layer first (stable across source edits), RESOLVED FROM
# pyproject.toml — a hard-coded pip list here silently drifts the moment the
# project gains a dependency (ADVICE r5 #2). pytest rides along for
# `docker run ... make test`.
COPY pyproject.toml ./
RUN python -c "import os, tomllib; \
proj = tomllib.load(open('pyproject.toml', 'rb'))['project']; \
extras = [e for e in os.environ.get('PIP_EXTRAS', '').split(',') if e]; \
deps = proj['dependencies'] + [d for e in extras for d in proj['optional-dependencies'][e]]; \
open('/tmp/requirements.txt', 'w').write('\n'.join(deps) + '\n')" \
 && pip install --no-cache-dir "jax[${JAX_EXTRA}]" pytest -r /tmp/requirements.txt

COPY albedo_tpu ./albedo_tpu
COPY tests ./tests
COPY bench.py chip_smoke.py __graft_entry__.py Makefile ./

RUN pip install --no-cache-dir --no-deps -e .

# Artifacts (loadOrCreate parquet/npz cache, Orbax checkpoints) live under one
# mountable volume, the dataDir convention (settings/package.scala:12-13).
# The executable caches (XLA's persistent cache + the jax.export blobs) are
# placed by JAX_COMPILATION_CACHE_DIR — on the same volume here, so they
# survive the container; unset, they would land in /app/.jax-cache and die
# with it (utils/compilation_cache.py: the code never derives the cache
# directory from ALBEDO_DATA_DIR).
ENV ALBEDO_DATA_DIR=/data
ENV JAX_COMPILATION_CACHE_DIR=/data/jax-cache
VOLUME /data

# HTTP recommendation serving (app's web layer parity).
EXPOSE 8080

CMD ["make", "serve", "ARGS=--small --host 0.0.0.0"]
