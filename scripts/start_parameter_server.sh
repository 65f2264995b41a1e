#!/usr/bin/env bash
# Env-var driven PS launcher (analogue of the reference's
# scripts/start_parameter_server.sh).
#   PS_PORT (default 50051)  TOTAL_WORKERS (default 2)
#   CHECKPOINT_INTERVAL (default 10)  CHECKPOINT_DIR (default .)
#   EXTRA_FLAGS (e.g. "--lr=0.1 --optimizer=adam --staleness=4 --elastic
#   --coordinator=127.0.0.1:50052")
#   LOG_FILE (default ./parameter_server.log)  PID_DIR (default ./run)
set -euo pipefail
PS_PORT="${PS_PORT:-50051}"
TOTAL_WORKERS="${TOTAL_WORKERS:-2}"
CHECKPOINT_INTERVAL="${CHECKPOINT_INTERVAL:-10}"
CHECKPOINT_DIR="${CHECKPOINT_DIR:-.}"
EXTRA_FLAGS="${EXTRA_FLAGS:-}"
LOG_FILE="${LOG_FILE:-./parameter_server.log}"
# default the PS to the host backend (control plane + host optimizers).
# A device-resident optimizer (--optimizer=device_*/pallas_* in
# EXTRA_FLAGS) needs JAX_PLATFORMS=tpu and a chip of its own: one process
# owns a chip, so the PS cannot share one with a worker on the same host.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
PID_DIR="${PID_DIR:-./run}"
mkdir -p "$PID_DIR"
# shellcheck disable=SC2086
nohup python -m parameter_server_distributed_tpu.cli.ps_main \
  "0.0.0.0:${PS_PORT}" "${TOTAL_WORKERS}" "${CHECKPOINT_INTERVAL}" \
  --ckpt-dir="${CHECKPOINT_DIR}" ${EXTRA_FLAGS} >"$LOG_FILE" 2>&1 &
echo $! > "${PID_DIR}/parameter_server.pid"
echo "parameter server started (pid $(cat "${PID_DIR}/parameter_server.pid"), port ${PS_PORT}, workers ${TOTAL_WORKERS})"
