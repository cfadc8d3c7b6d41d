# authorino-tpu serving image (parity: ref Dockerfile:8 — the reference
# builds a static Go binary; here the image carries the Python package, the
# native C++ batch encoder prebuilt from source, and jax from the base
# image).
#
# Build from a jax-enabled base so the TPU runtime libraries match the host
# (on GKE TPU node pools use the cloud-tpu base; for CPU-only serving any
# python base works with JAX_PLATFORM=cpu).
ARG BASE_IMAGE=python:3.11-slim
FROM ${BASE_IMAGE} AS build

RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY pyproject.toml README.md ./
COPY authorino_tpu ./authorino_tpu
COPY native ./native

RUN pip install --no-cache-dir .

# Prebuild the native batch encoder at the path the loader probes
# (authorino_tpu/native/__init__.py: <pkg>/native/_build/_atpuenc.so, with
# sources expected at <site-packages>/native for the staleness check).
# The loader falls back to the pure-Python encoder if any of this is absent.
# Stage into a fixed path: site-packages' real location depends on the base
# image's Python version, so the final stage re-derives it via sysconfig.
RUN SITE=$(python -c "import sysconfig; print(sysconfig.get_paths()['purelib'])") && \
    cp -r native "$SITE/native" && \
    mkdir -p "$SITE/authorino_tpu/native/_build" && \
    g++ -O2 -std=c++17 -shared -fPIC -pthread \
        -I "$(python -c "import sysconfig; print(sysconfig.get_paths()['include'])")" \
        "$SITE/native/pymod.cpp" \
        -o "$SITE/authorino_tpu/native/_build/_atpuenc.so" && \
    touch "$SITE/authorino_tpu/native/_build/_atpuenc.so" && \
    mkdir -p /staged && cp -a "$SITE" /staged/site-packages && \
    touch /staged/site-packages/authorino_tpu/native/_build/_atpuenc.so && \
    cp /usr/local/bin/authorino-tpu /staged/authorino-tpu

FROM ${BASE_IMAGE}
# the native gRPC frontend frames HTTP/2 itself (native/frontend.cpp): the
# runtime image needs no HTTP/2 library
RUN groupadd -r authorino && useradd -r -g authorino -u 1001 authorino
COPY --from=build /staged /staged
RUN python -c "import shutil, sysconfig; \
shutil.copytree('/staged/site-packages', sysconfig.get_paths()['purelib'], dirs_exist_ok=True)" && \
    python -c "import os, sysconfig; \
os.utime(sysconfig.get_paths()['purelib'] + '/authorino_tpu/native/_build/_atpuenc.so')" && \
    install -m 0755 /staged/authorino-tpu /usr/local/bin/authorino-tpu && \
    rm -rf /staged
# the utime keeps the prebuilt .so newer than the staged sources — the
# loader's mtime staleness check must not trigger a rebuild in the
# runtime image (no g++, non-root site-packages → permanent Python fallback)
USER 1001
ENTRYPOINT ["authorino-tpu"]
CMD ["server"]
