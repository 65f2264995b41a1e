#!/usr/bin/env bash
# Env-var driven worker launcher (analogue of the reference's
# scripts/start_worker.sh; the reference also exports CUDA/NCCL
# LD_LIBRARY_PATH — not needed on TPU/JAX).
#   COORDINATOR_ADDR (default 127.0.0.1:50052)  WORKER_ID (default 0)
#   ITERATIONS (default 10)  WORKER_PORT (default 50060+WORKER_ID)
#   CHECKPOINT_PATH (optional restore-at-start)
#   MODEL (default mnist_mlp)  BATCH (default 32)  EXTRA_FLAGS
#   LOG_FILE (default ./worker_${WORKER_ID}.log)  PID_DIR (default ./run)
# The worker takes whatever JAX gives it (JAX_PLATFORMS unset = the
# accelerator).  One process owns a chip: start ONE unpinned worker per
# host and give it the host's chips with --mesh; a second worker on the
# same host needs JAX_PLATFORMS=cpu, or it fails or hangs reaching for
# the chip the first one holds.
set -euo pipefail
COORDINATOR_ADDR="${COORDINATOR_ADDR:-127.0.0.1:50052}"
WORKER_ID="${WORKER_ID:-0}"
ITERATIONS="${ITERATIONS:-10}"
WORKER_PORT="${WORKER_PORT:-$((50060 + WORKER_ID))}"
CHECKPOINT_PATH="${CHECKPOINT_PATH:-}"
MODEL="${MODEL:-mnist_mlp}"
BATCH="${BATCH:-32}"
EXTRA_FLAGS="${EXTRA_FLAGS:-}"
LOG_FILE="${LOG_FILE:-./worker_${WORKER_ID}.log}"
PID_DIR="${PID_DIR:-./run}"
mkdir -p "$PID_DIR"
# shellcheck disable=SC2086
nohup python -m parameter_server_distributed_tpu.cli.worker_main \
  "${COORDINATOR_ADDR}" "${WORKER_ID}" "${ITERATIONS}" "127.0.0.1" \
  "${WORKER_PORT}" "${CHECKPOINT_PATH}" \
  --model="${MODEL}" --batch="${BATCH}" ${EXTRA_FLAGS} >"$LOG_FILE" 2>&1 &
echo $! > "${PID_DIR}/worker_${WORKER_ID}.pid"
echo "worker ${WORKER_ID} started (pid $(cat "${PID_DIR}/worker_${WORKER_ID}.pid"))"
