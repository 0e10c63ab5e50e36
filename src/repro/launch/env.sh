#!/usr/bin/env bash
# Host profile for serving runs: wrap any launcher command to get a
# reproducible host environment (docs/benchmarks.md "Host profile").
#
#   src/repro/launch/env.sh python -m repro.launch.serve --arch llada-8b --hbm-gb 16 ...
#   REPRO_HOST_DEVICES=4 src/repro/launch/env.sh python -m benchmarks.run ...
#
# Everything here is a host-side knob, not a numerics knob: result JSONs
# record host_profile=1 (serve.py reads REPRO_HOST_PROFILE) so benchmark
# diffs can refuse to compare profiled against unprofiled runs, but token
# output is bit-identical either way.
set -euo pipefail

# --- allocator -------------------------------------------------------------
# The pipelined engine's host side is allocation-heavy (per-iteration plan +
# pack buffers built while the device runs). tcmalloc's thread caches cut the
# malloc tail; probe the usual locations and silently keep glibc malloc when
# absent (the container does not ship it).
for _tc in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
           /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
           /usr/lib/libtcmalloc_minimal.so.4; do
  if [[ -e "${_tc}" ]]; then
    export LD_PRELOAD="${_tc}${LD_PRELOAD:+:${LD_PRELOAD}}"
    # only giant allocations are worth a report line (default warns at 1GiB
    # and the packed KV pool legitimately allocates bigger arenas)
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=$((8 << 30))
    break
  fi
done

# --- XLA / jax -------------------------------------------------------------
# CPU repro of an N-device mesh: REPRO_HOST_DEVICES=N splits the host into
# N XLA devices (the same flag the mesh docs tell you to set by hand).
if [[ -n "${REPRO_HOST_DEVICES:-}" ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=${REPRO_HOST_DEVICES}${XLA_FLAGS:+ ${XLA_FLAGS}}"
fi

# Pin default dtypes: fp32/int32 everywhere, no x64 promotion — the modeled
# clock and the packed layouts assume 32-bit widths, and an ambient
# JAX_ENABLE_X64 would silently double every buffer in the footprint ledger.
export JAX_ENABLE_X64=0
export JAX_DEFAULT_DTYPE_BITS=32

# Keep TF/XLA's C++ logging out of benchmark stdout (JSON goes there).
export TF_CPP_MIN_LOG_LEVEL=${TF_CPP_MIN_LOG_LEVEL:-4}

# Mark the run so result JSONs can assert the profile was active.
export REPRO_HOST_PROFILE=1

exec "$@"
