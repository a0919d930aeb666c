# fluidframework-tpu service image — the `image:` every service in
# deploy/compose.yaml runs (reference analog:
# server/routerlicious/Dockerfile behind its docker-compose.yml).
#
#   docker build -t fluidframework-tpu:latest .
#
# One image serves every tier; the compose file picks the process:
#   netserver shards   python -m fluidframework_tpu.server.netserver
#   pipeline workers   python -m fluidframework_tpu.server.partition_manager
#   device fleet       python -m fluidframework_tpu.server.fleet_main
#
# The TPU fleet tier additionally needs the accelerator runtime
# (libtpu/jax[tpu]) layered on top — deployment-environment specific, so
# the base image stays CPU-jax and the compose device reservation selects
# the host.
FROM python:3.12-slim

# g++ backs the on-demand native builds (native/*.cpp: sequencer, ingest
# encoder); build-essential keeps the image able to rebuild them when the
# sources change under a bind mount.
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY fluidframework_tpu ./fluidframework_tpu
COPY native ./native
COPY deploy ./deploy

# Editable install keeps the repo-rooted native/ directory resolvable for
# the ctypes loaders (fluidframework_tpu/native/*_native.py).
RUN pip install --no-cache-dir -e .

# Static-analysis gate: the image FAILS TO BUILD on any unbaselined
# fftpu-check finding (all 11 passes — layering, jit/donation safety,
# determinism, thread/lock discipline, blocking-under-lock, mesh safety).
# Pure AST, no JAX import, ~10s; a hazardous tree never becomes a
# deployable service image.
RUN python -m fluidframework_tpu.analysis.cli fluidframework_tpu --json

# Pre-build the native libraries so containers start warm; failure is
# non-fatal (the ctypes loaders rebuild on demand at first use).
RUN (g++ -O2 -shared -fPIC -std=c++17 -o native/libtpusequencer.so native/sequencer.cpp \
     && g++ -O2 -shared -fPIC -std=c++17 -o native/libtpuingest.so native/ingest.cpp) \
    || echo "native pre-build failed; loaders will build on demand"

EXPOSE 7070 7071
CMD ["python", "-m", "fluidframework_tpu.server.netserver", "--port", "7070", "--http-port", "7071"]
