#!/usr/bin/env bash
# Reproducible compiled-backend bench launcher (DESIGN.md §13).
#
# Pins the XLA launch environment so committed numbers carry a repro
# recipe: host device count, tcmalloc preload, f32 dtype pinning, quiet
# logs.  The pinned env is dumped into every bench JSON by
# bench_config.launch_env().
#
# Usage:
#   benchmarks/launch_bench.sh                    # dslash + solvers, CPU
#   BENCH_BACKEND=tpu benchmarks/launch_bench.sh  # device run
#   benchmarks/launch_bench.sh --only dslash      # extra run.py args pass through
#
# Produces BENCH_dslash.json / BENCH_solvers.json in the CWD.
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

export BENCH_BACKEND="${BENCH_BACKEND:-cpu}"
export BENCH_COMPILED=1

# fixed host device count: results must not depend on the runner's cores
HOSTDEV="${BENCH_HOST_DEVICES:-1}"
case " ${XLA_FLAGS:-} " in
  *xla_force_host_platform_device_count*) ;;
  *) export XLA_FLAGS="--xla_force_host_platform_device_count=${HOSTDEV}${XLA_FLAGS:+ ${XLA_FLAGS}}" ;;
esac

# dtype pinning + quiet C++ logs
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# tcmalloc when the host has it (allocator noise dominates small-kernel
# timings on glibc malloc); silently skipped when absent
if [ -z "${LD_PRELOAD:-}" ]; then
  for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
            /usr/lib/libtcmalloc.so.4; do
    if [ -f "$so" ]; then export LD_PRELOAD="$so"; break; fi
  done
fi

export PYTHONPATH="${REPO}/src${PYTHONPATH:+:$PYTHONPATH}"

ARGS=("--backend" "${BENCH_BACKEND}" "--compiled")
if [ "$#" -eq 0 ]; then
  ARGS+=("--only" "dslash" "solvers")
fi
python "${REPO}/benchmarks/run.py" "${ARGS[@]}" "$@"
