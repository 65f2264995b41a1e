#!/usr/bin/env bash
# Parameter-efficient fine-tuning: pretrain a base LM, LoRA-fine-tune it
# with the base frozen (only rank-4 adapters train), then serve the
# adapted model both ways — merged on load by the serving CLI, and as a
# dense export.
#
#   bash examples/finetune_lora.sh [workdir]
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

WORK="${1:-/tmp/psdt_lora_example}"
STEPS="${STEPS:-40}"
mkdir -p "$WORK"

CORPUS="$WORK/corpus.txt"
if [ ! -s "$CORPUS" ]; then
  cat parameter_server_distributed_tpu/models/*.py > "$CORPUS"
fi

echo "== 1. pretrain the base model (dense, all parameters) =="
python -m parameter_server_distributed_tpu.cli.train_main \
  --model=small_lm --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=3e-3 --ckpt-dir="$WORK/base" --ckpt-every="$STEPS"

echo "== 2. LoRA fine-tune FROM that checkpoint: rank-4 adapters on the"
echo "      attention q/v projections are the only trainable parameters"
echo "      (the log line confirms the frozen base) =="
python -m parameter_server_distributed_tpu.cli.train_main \
  --model=small_lm --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=1e-2 --lora=4:8 --init-ckpt-dir="$WORK/base" \
  --ckpt-dir="$WORK/lora" --ckpt-every="$STEPS"

echo "== 3. serve the adapted model: the CLI folds the adapters into"
echo "      dense weights on load (--lora-alpha must match training) =="
python -m parameter_server_distributed_tpu.cli.generate_main \
  --model=small_lm --ckpt-dir="$WORK/lora" --lora-alpha=8 \
  --prompt="def forward" --max-new=48

echo "== 4. or export a permanent dense checkpoint (merge_lora) =="
python - "$WORK" <<'EOF'
import sys
from parameter_server_distributed_tpu.checkpoint import codec, sharded
from parameter_server_distributed_tpu.models.lora import merge_lora

import numpy as np

work = sys.argv[1]
step, state = sharded.restore_latest(f"{work}/lora")
params = state["params"] if isinstance(state, dict) else state.params
merged = {k: np.asarray(v) for k, v in merge_lora(params, alpha=8.0).items()}
codec.save(f"{work}/merged.ckpt", epoch=0, iteration=step, params=merged)
print(f"dense export: {work}/merged.ckpt ({len(merged)} tensors)")
EOF
python -m parameter_server_distributed_tpu.cli.generate_main \
  --model=small_lm --ckpt="$WORK/merged.ckpt" \
  --prompt="def forward" --max-new=24

echo "example complete; artifacts in $WORK"
