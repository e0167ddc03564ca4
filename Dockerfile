# Container image for pilosa-tpu (analog of the reference's Dockerfile:
# builder stage + minimal runtime, server-on-/data entrypoint).
#
# The compute path runs on JAX; inside a container that is the CPU
# backend unless a TPU runtime is mounted in (set JAX_PLATFORMS and the
# libtpu env per your TPU platform).  The C++ codec compiles at build
# time so first boot doesn't need the toolchain.
#
#   docker build -t pilosa-tpu .
#   docker run -p 10101:10101 -v pilosa-data:/data pilosa-tpu

FROM python:3.12-slim AS builder

RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

COPY pyproject.toml README.md /src/
COPY pilosa_tpu /src/pilosa_tpu

# Pre-build the native codec into the installed package so the runtime
# image needs no compiler (lib() compiles next to the source on first
# use).
RUN pip install --no-cache-dir /src \
    && python -c "from pilosa_tpu import native; assert native.available(), 'native codec failed to build'"

FROM python:3.12-slim

COPY --from=builder /usr/local/lib/python3.12/site-packages /usr/local/lib/python3.12/site-packages
COPY --from=builder /usr/local/bin/pilosa-tpu /usr/local/bin/pilosa-tpu

EXPOSE 10101
VOLUME /data
# The compile cache's directory is part of its key: one fixed path on
# the volume, so a restarted container finds its programs again.
ENV JAX_COMPILATION_CACHE_DIR=/data/.jax-compile-cache

ENTRYPOINT ["pilosa-tpu"]
CMD ["server", "--data-dir", "/data", "--bind", "0.0.0.0:10101"]
