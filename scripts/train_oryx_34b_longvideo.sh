#!/usr/bin/env bash
# 34B long-video SFT on a v5e-64 (BASELINE config 5: 256-frame records,
# ZeRO-3 at pod scale): ring attention over sp=4 with the ZeRO state
# sharded over the COMBINED fsdp x sp width, vision patch shards riding
# sp, bf16 moments, block remat, grad_accum 8 — sized with
# scripts/estimate_7b_mesh_memory.py
# (AOT_CONFIG=scripts/configs/oryx_34b_longvideo.json AOT_FRAMES=256).
# Not run on the current chip.
set -euo pipefail
cd "$(dirname "$0")/.."

DATA=${DATA:?path to conversation-records json}
TOKENIZER=${TOKENIZER:?path to Yi/Qwen tokenizer dir}

python -m oryx_tpu.train.cli \
  --config scripts/configs/oryx_34b_longvideo.json \
  --data "$DATA" \
  --tokenizer-path "$TOKENIZER" \
  --template yi_34b \
  --video-frames 256 \
  --sharding fsdp \
  --metrics-path logs/oryx34b_video_metrics.jsonl \
  --output-dir models/oryx34b-longvideo \
  "$@"
