#!/usr/bin/env bash
# Speculative continuous batching with a TRAINED draft: train the target
# (small_lm) and a 1-layer draft (tiny_lm) on the same corpus, then serve
# the target with draft/verify rounds — each request advances
# 1..draft_len+1 tokens per target forward at its measured acceptance
# rate, and greedy output stays token-exact vs plain serving.
#
#   bash examples/speculative_serving.sh [workdir]
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

WORK="${1:-/tmp/psdt_spec_example}"
STEPS="${STEPS:-60}"
mkdir -p "$WORK"

CORPUS="$WORK/corpus.txt"
if [ ! -s "$CORPUS" ]; then
  cat parameter_server_distributed_tpu/models/*.py > "$CORPUS"
fi

echo "== 1. train the target (small_lm) and the draft (tiny_lm) on the"
echo "      SAME corpus — acceptance comes from distribution match =="
python -m parameter_server_distributed_tpu.cli.train_main \
  --model=small_lm --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=3e-3 --ckpt-dir="$WORK/target" --ckpt-every="$STEPS"
python -m parameter_server_distributed_tpu.cli.train_main \
  --model=tiny_lm --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=3e-3 --ckpt-dir="$WORK/draft" --ckpt-every="$STEPS"

echo "== 2. serve the target with the draft (depth CAP 4 — the server"
echo "      ADAPTS the per-round depth from the measured accept rate,"
echo "      disabling speculation if this draft cannot pay on this host;"
echo "      add --no-adaptive-draft to pin the depth) =="
python -m parameter_server_distributed_tpu.cli.serve_main \
  --model=small_lm --ckpt-dir="$WORK/target" \
  --draft-model=tiny_lm --draft-ckpt="$WORK/draft" --draft-len=4 \
  --slots=4 <<'REQS'
{"id": "a", "prompt": "def forward", "max_new": 32}
{"id": "b", "prompt": "import jax", "max_new": 32}
REQS

echo "example complete; acceptance stats are logged by the server on exit"
