#!/usr/bin/env bash
# Env-var driven coordinator launcher (analogue of the reference's
# scripts/start_coordinator.sh: nohup daemonization + PID file).
#   COORDINATOR_PORT (default 50052)  PS_ADDR (default 127.0.0.1:50051)
#   LOG_FILE (default ./coordinator.log)  PID_DIR (default ./run)
set -euo pipefail
COORDINATOR_PORT="${COORDINATOR_PORT:-50052}"
PS_ADDR="${PS_ADDR:-127.0.0.1:50051}"
LOG_FILE="${LOG_FILE:-./coordinator.log}"
PID_DIR="${PID_DIR:-./run}"
# the control plane is device-free: pin it to the host backend so the
# coordinator never takes the chip a worker on this host needs
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
mkdir -p "$PID_DIR"
nohup python -m parameter_server_distributed_tpu.cli.coordinator_main \
  "0.0.0.0:${COORDINATOR_PORT}" "${PS_ADDR}" >"$LOG_FILE" 2>&1 &
echo $! > "${PID_DIR}/coordinator.pid"
echo "coordinator started (pid $(cat "${PID_DIR}/coordinator.pid"), port ${COORDINATOR_PORT})"
