#!/bin/bash
# Pod/multi-host launch wrapper (analog of reference scripts/launch.sh:1-58,
# which wires torchrun + NVSHMEM env). On TPU the process model is one
# python process per HOST (not per chip) and jax.distributed.initialize()
# picks the cluster up from environment variables, so this script only has
# to pin the env and exec python once per host.
#
# Usage (run the same command on EVERY host of the pod):
#
#   # single host (one chip or one slice):
#   scripts/launch.sh python -m tutorials.t05_ag_gemm --case perf
#
#   # multi-host pod, explicit coordinator (host 0's address):
#   JAX_COORDINATOR_ADDRESS=10.0.0.1:8476 \
#   JAX_NUM_PROCESSES=4 JAX_PROCESS_ID=<this host's index> \
#   scripts/launch.sh python -m tutorials.t05_ag_gemm --case perf
#
# ShmemContext.initialize_distributed() calls jax.distributed.initialize()
# when any of JAX_COORDINATOR_ADDRESS / COORDINATOR_ADDRESS /
# MEGASCALE_COORDINATOR_ADDRESS is set (shmem/context.py), so no per-op
# launcher changes are needed. TPU_WORKER_ID alone does NOT trigger it:
# single-host TPU machines export it too.

set -euo pipefail

SCRIPT_DIR=$(cd -- "$(dirname -- "${BASH_SOURCE[0]}")" &>/dev/null && pwd)
REPO_DIR=$(dirname -- "$SCRIPT_DIR")

# repo importable from anywhere (reference pins PYTHONPATH the same way)
case ":${PYTHONPATH:-}:" in
    *:"${REPO_DIR}":*) ;;
    *) export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:${PYTHONPATH}}" ;;
esac

# persistent XLA compile cache: first compiles are ~20-40 s on TPU; cached
# afterwards (the analog of the reference's TRITON_CACHE_DIR pinning)
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-"$REPO_DIR/.jax_cache"}
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

# quieter default logs on pods (reference sets NCCL_DEBUG=ERROR)
export TPU_STDERR_LOG_LEVEL=${TPU_STDERR_LOG_LEVEL:-3}
export TF_CPP_MIN_LOG_LEVEL=${TF_CPP_MIN_LOG_LEVEL:-2}

# map generic coordinator env to jax's spelling if only the generic one is
# set (lets one launch line serve ad-hoc clusters)
if [ -n "${COORDINATOR_ADDRESS:-}" ] && [ -z "${JAX_COORDINATOR_ADDRESS:-}" ]; then
  export JAX_COORDINATOR_ADDRESS="$COORDINATOR_ADDRESS"
fi

echo "[launch] repo=$REPO_DIR" \
     "coordinator=${JAX_COORDINATOR_ADDRESS:-<single-host/auto>}" \
     "process=${JAX_PROCESS_ID:-0}/${JAX_NUM_PROCESSES:-1}" >&2

exec "$@"
