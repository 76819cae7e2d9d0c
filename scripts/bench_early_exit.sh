#!/bin/bash
# Trained-model early-exit A/B on the current backend: the beam-search
# while_loop's exact early exit (sat_tpu/ops/beam_search.py run_search)
# only pays off when the model actually terminates captions, which a
# random init never does — so train a quick flagship-shape model on the
# self-contained corpus, then run scripts/bench_eval.py on its checkpoint
# with and without the exit.  Artifact = two JSON lines on stdout
# (early_exit true/false).
#
# Usage: bash scripts/bench_early_exit.sh [outdir]
# Env knobs (CPU smoke: EE_CPU=1 EE_IMAGE_SIZE=64 EE_STEPS=30 EE_BATCH=4):
#   EE_IMAGE_SIZE (default 224), EE_STEPS (400), EE_BATCH (bench batch,
#   32), EE_CPU=1 (pin the CPU backend everywhere).
set -u
OUT=${1:-runs/early_exit}
IMG=${EE_IMAGE_SIZE:-224}
STEPS=${EE_STEPS:-400}
# cache dir keyed on EVERY knob that shapes corpus + checkpoint —
# including the backend, so a CPU smoke run with default sizes can't be
# mistaken for the production (TPU-trained) artifacts
BACKEND=$([ "${EE_CPU:-0}" = "1" ] && echo cpu || echo dev)
DIR="$OUT/ee_run_${IMG}px_${STEPS}s_${BACKEND}"
BATCH=${EE_BATCH:-32}
CPU_FLAG=""
[ "${EE_CPU:-0}" = "1" ] && { CPU_FLAG="--cpu"; export JAX_PLATFORMS=cpu; }
cd "$(dirname "$0")/.."
mkdir -p "$OUT"

if [ ! -f "$DIR/captions.json" ]; then
  timeout 300 python scripts/quality_run.py --corpus-only \
    --image-size "$IMG" --out "$DIR" \
    >"$OUT/ee_corpus.log" 2>&1 || { echo "corpus gen failed" >&2; exit 1; }
fi

if ! ls "$DIR"/models/*.npz >/dev/null 2>&1; then
  timeout 700 python -m sat_tpu.cli --phase=train \
    --set train_image_dir="$DIR/images" \
    --set train_caption_file="$DIR/captions.json" \
    --set vocabulary_file="$DIR/vocabulary_basic.csv" \
    --set temp_annotation_file="$DIR/anns_basic.csv" \
    --set temp_data_file="$DIR/data_basic.npy" \
    --set save_dir="$DIR/models" \
    --set summary_dir="$DIR/summary" \
    --set image_size="$IMG" \
    --set max_train_ann_num=none --set batch_size=16 --set num_epochs=200 \
    --set max_steps="$STEPS" --set save_period=0 \
    --set initial_learning_rate=3e-4 \
    >"$OUT/ee_train.log" 2>&1 || { echo "train failed" >&2; exit 1; }
fi

CKPT=$(ls -t "$DIR"/models/*.npz | head -1)
for arm in "" "--no-early-exit"; do
  timeout 400 python scripts/bench_eval.py --batch "$BATCH" --iters 10 \
    --image-size "$IMG" $CPU_FLAG \
    --params "$CKPT" --vocab "$DIR/vocabulary_basic.csv" $arm \
    2>>"$OUT/ee_bench.log" || { echo "bench arm failed" >&2; exit 1; }
done
