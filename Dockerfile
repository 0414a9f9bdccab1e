# TPU-native DSS server image (the analog of the reference's
# single-binary Dockerfile).  The CPU jax wheel is installed by
# default and the image names that backend (JAX_PLATFORMS=cpu); on TPU
# hosts swap in the libtpu wheel AND name the tpu backend at build
# time — `--storage tpu` never falls back to the CPU on its own:
#   docker build --build-arg JAX_EXTRA="jax[tpu]" \
#       --build-arg JAX_PLATFORMS=tpu .

# Stage 1: compile the native host kernels (covering, host query,
# window pack/decode).  The runtime image is slim (no toolchain), so
# relying on the lazy in-process g++ build would silently fall back
# to the numpy paths — a 3-26x slowdown on the serving hot paths.
FROM python:3.12-slim AS native-build
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*
COPY dss_tpu/native /src/native
# _buildlib is the same stdlib-only builder the lazy in-process path
# uses: one source list, and it writes the content-digest sidecar the
# runtime loader validates (mtimes don't survive pip installs)
RUN python /src/native/_buildlib.py /src/native

FROM python:3.12-slim

ARG JAX_EXTRA=""
ARG JAX_PLATFORMS=cpu
ENV JAX_PLATFORMS=${JAX_PLATFORMS}

WORKDIR /app
COPY pyproject.toml README.md ./
COPY dss_tpu ./dss_tpu
COPY --from=native-build /src/native/libdsscover.so \
    /src/native/libdsscover.so.sha ./dss_tpu/native/
RUN pip install --no-cache-dir . ${JAX_EXTRA}

# build info (the reference's -ldflags -X injection, pkg/build) — after
# the install layers so a changing commit never busts the pip cache
ARG BUILD_COMMIT=unknown
ARG BUILD_TIME=unknown
ENV DSS_BUILD_COMMIT=${BUILD_COMMIT} DSS_BUILD_TIME=${BUILD_TIME}

# flags mirror cmds/grpc-backend (see dss_tpu/cmds/server.py --help)
EXPOSE 8082
ENTRYPOINT ["dss-server"]
CMD ["--addr", ":8082", "--enable_scd", "--storage", "tpu", \
     "--insecure_no_auth"]
