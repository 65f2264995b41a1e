#!/usr/bin/env bash
# The parameter-server topology the reference implements — coordinator +
# PS + workers as separate gRPC processes — run locally with this
# framework's extensions: an ELASTIC barrier (a worker joining mid-run
# widens the sync barrier without restarting the PS — the reference's
# scale script kills and restarts it, losing in-memory params) and the
# pst-status observability CLI.
#
#   bash examples/ps_cluster.sh
set -euo pipefail
cd "$(dirname "$0")/.."
# One process owns a chip.  This script starts three workers on one host,
# so every process defaults to the CPU; on a one-chip host at most ONE
# worker may be left unpinned (launch it by hand with JAX_PLATFORMS unset).
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONUNBUFFERED=1

PORT_BASE="${PORT_BASE:-15750}"
PS_PORT=$((PORT_BASE + 1))
COORD_PORT=$((PORT_BASE + 2))
WORK="$(mktemp -d)"
trap 'kill $(jobs -p) 2>/dev/null || true; rm -rf "$WORK"' EXIT

echo "== 1. parameter server: sync barrier, SGD lr 0.05, elastic width =="
python -m parameter_server_distributed_tpu.cli.ps_main \
  "127.0.0.1:${PS_PORT}" 2 5 --lr=0.05 --elastic \
  --coordinator="127.0.0.1:${COORD_PORT}" --ckpt-dir="$WORK" \
  >"$WORK/ps.log" 2>&1 &

echo "== 2. coordinator: registry + heartbeats + stale-worker reaper =="
python -m parameter_server_distributed_tpu.cli.coordinator_main \
  "127.0.0.1:${COORD_PORT}" "127.0.0.1:${PS_PORT}" \
  >"$WORK/coordinator.log" 2>&1 &

for i in $(seq 1 50); do
  grep -q "listening" "$WORK/ps.log" 2>/dev/null && \
  grep -q "listening" "$WORK/coordinator.log" 2>/dev/null && break
  sleep 0.2
done

echo "== 3. two workers training mnist_mlp (real grads, not the"
echo "      reference's 0.01 stub) =="
python -m parameter_server_distributed_tpu.cli.worker_main \
  "127.0.0.1:${COORD_PORT}" 0 8 127.0.0.1 15760 "" --batch=16 \
  >"$WORK/w0.log" 2>&1 &
W0=$!
python -m parameter_server_distributed_tpu.cli.worker_main \
  "127.0.0.1:${COORD_PORT}" 1 8 127.0.0.1 15761 "" --batch=16 \
  >"$WORK/w1.log" 2>&1 &
W1=$!

sleep 8
echo "== 4. elastic scale-up: worker 2 joins MID-RUN (barrier widens"
echo "      2 -> 3 live; no PS restart, no params lost) =="
python -m parameter_server_distributed_tpu.cli.worker_main \
  "127.0.0.1:${COORD_PORT}" 2 5 127.0.0.1 15762 "" --batch=16 \
  >"$WORK/w2.log" 2>&1 &
W2=$!

echo "== 5. cluster status while training (ListWorkers + sync state) =="
python -m parameter_server_distributed_tpu.cli.status_main \
  "127.0.0.1:${COORD_PORT}" || true

wait $W0 $W1 $W2
echo "== final status and worker tails =="
python -m parameter_server_distributed_tpu.cli.status_main \
  "127.0.0.1:${COORD_PORT}" || true
tail -n 2 "$WORK"/w*.log
ls "$WORK"/*.ckpt >/dev/null 2>&1 && echo "checkpoints written in $WORK"
echo "example complete"
