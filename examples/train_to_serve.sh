#!/usr/bin/env bash
# End-to-end walkthrough: train a byte-level LM on a real text corpus,
# checkpoint it, sample from the checkpoint, then serve it as a
# continuous-batching process with per-request sampling controls.
#
#   bash examples/train_to_serve.sh [workdir]
#
# Runs in a few minutes on a laptop CPU (JAX_PLATFORMS defaults to cpu
# below); on a TPU VM run with JAX_PLATFORMS=tpu and raise STEPS/--batch
# — the commands run one after another, so each owns the chip in turn.
# Every command is the
# installed console-script surface — nothing here imports the package
# directly, so this is exactly what a user types.
set -euo pipefail
cd "$(dirname "$0")/.."

WORK="${1:-/tmp/psdt_example}"
STEPS="${STEPS:-60}"
mkdir -p "$WORK"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# -- 1. corpus: this package's own source is a fine byte-level dataset
CORPUS="$WORK/corpus.txt"
if [ ! -s "$CORPUS" ]; then
  cat parameter_server_distributed_tpu/models/*.py > "$CORPUS"
fi

# -- 2. train small_lm on it (byte tokenizer: .txt is tokenized to a
#    cached shard on first use), checkpointing every 20 steps.
#    --mesh=data:1 keeps it single-device; on an 8-chip host try
#    --mesh=data:4,fsdp:2 — same command, sharded by GSPMD.
python -m parameter_server_distributed_tpu.cli.train_main \
  --model=small_lm --batch=8 --steps="$STEPS" \
  --data="$CORPUS" --optimizer=adamw --lr=3e-3 --schedule=cosine \
  --warmup=10 --ckpt-dir="$WORK/ckpt" --ckpt-every=20 --ckpt-keep=2 \
  --metrics="$WORK/metrics.jsonl"

# -- 3. sample from the latest checkpoint (greedy and nucleus)
python -m parameter_server_distributed_tpu.cli.generate_main \
  --model=small_lm --ckpt-dir="$WORK/ckpt" \
  --prompt="def forward" --max-new=48
python -m parameter_server_distributed_tpu.cli.generate_main \
  --model=small_lm --ckpt-dir="$WORK/ckpt" \
  --prompt="def forward" --max-new=48 --temperature=0.8 --top-p=0.9

# -- 4. serve it: JSONL line protocol on stdin/stdout.  One greedy
#    request, one hot-temperature request, one with a stop token (10 =
#    '\n' under the byte tokenizer) — all decoded in the same batch.
python -m parameter_server_distributed_tpu.cli.serve_main \
  --model=small_lm --ckpt-dir="$WORK/ckpt" --slots=4 <<'REQS'
{"id": "greedy", "prompt": "def forward", "max_new": 32}
{"id": "hot", "prompt": "def forward", "max_new": 32, "temperature": 0.9}
{"id": "one_line", "prompt": "def forward", "max_new": 32, "stop": [10]}
REQS

echo "example complete; artifacts in $WORK"
