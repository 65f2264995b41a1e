#!/usr/bin/env bash
# Converted-checkpoint flow: take a transformers GPT-2 checkpoint and run
# the WHOLE CLI suite on it — evaluate, fine-tune (full and LoRA, with a
# pipeline mesh), evaluate the fine-tune, generate, serve.  No
# intermediate export: every command takes the checkout directly and the
# conversion (models/hf.from_hf_gpt2) happens in-process.
#
#   bash examples/hf_checkpoint.sh [workdir]
#
# Uses a tiny randomly-initialized GPT-2 so the example runs anywhere in
# minutes; point HF_CKPT at a real checkout (e.g. a downloaded gpt2) to
# run the same flow at full scale.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

WORK="${1:-/tmp/psdt_hf_example}"
STEPS="${STEPS:-30}"
mkdir -p "$WORK"

CORPUS="$WORK/corpus.txt"
if [ ! -s "$CORPUS" ]; then
  cat parameter_server_distributed_tpu/models/*.py > "$CORPUS"
fi

HF_CKPT="${HF_CKPT:-$WORK/hf_gpt2}"
if [ ! -d "$HF_CKPT" ]; then
  echo "== 0. make a tiny GPT-2 checkpoint (stand-in for a real checkout) =="
  python - "$HF_CKPT" <<'EOF'
import sys
import torch
import transformers

torch.manual_seed(0)
cfg = transformers.GPT2Config(vocab_size=512, n_positions=128, n_embd=64,
                              n_layer=2, n_head=2)
transformers.GPT2LMHeadModel(cfg).save_pretrained(sys.argv[1])
print(f"saved tiny GPT-2 to {sys.argv[1]}")
EOF
fi

echo "== 1. baseline evaluation of the raw converted checkpoint =="
python -m parameter_server_distributed_tpu.cli.eval_main \
  --hf-gpt2="$HF_CKPT" --data="$CORPUS" --batch=8 --steps=8

echo "== 2. fine-tune the converted model (the checkout IS the"
echo "      initializer; composes with --lora/--ema/pipe meshes) =="
python -m parameter_server_distributed_tpu.cli.train_main \
  --hf-gpt2="$HF_CKPT" --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=3e-3 --ckpt-dir="$WORK/ft" --ckpt-every="$STEPS"

echo "== 3. or LoRA-fine-tune it on a 2-stage pipeline mesh (GPipe"
echo "      handles the GPT-2 arch; adapters are the only trainables)."
echo "      On this CPU host the 2 'chips' are virtual devices =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
python -m parameter_server_distributed_tpu.cli.train_main \
  --hf-gpt2="$HF_CKPT" --batch=8 --steps="$STEPS" --data="$CORPUS" \
  --optimizer=adamw --lr=1e-2 --lora=4:8 \
  --mesh=pipe:2,data:1 --ckpt-dir="$WORK/lora" --ckpt-every="$STEPS"

echo "== 4. generate from the raw converted checkpoint and serve it."
echo "      (The tiny stand-in ships no tokenizer files, so this uses"
echo "      raw token ids; a real checkout serves --prompt text with"
echo "      its own tokenizer) =="
python -m parameter_server_distributed_tpu.cli.generate_main \
  --hf-gpt2="$HF_CKPT" --tokens=11,22,33 --max-new=24
printf '{"id": 1, "tokens": [11, 22, 33], "max_new": 16}\n' | \
  python -m parameter_server_distributed_tpu.cli.serve_main \
    --hf-gpt2="$HF_CKPT" --slots=2 --max-len=128

echo "example complete; artifacts in $WORK"
