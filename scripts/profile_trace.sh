#!/bin/bash
# Capture one real jax.profiler trace of the PrefetchLoader-fed train hot
# loop on the current backend (round-1 ask #8: back the
# "loader-hides-decode" claim with a trace, PERF.md §host-input-pipeline).
# Writes <outdir>/profile_done.txt on success.
#
# Live-capture mode (ISSUE 9): point it at an already-running caption
# server and it opens an on-demand profiler window over HTTP instead of
# launching a fresh training run — no restart, no config edit:
#
#   bash scripts/profile_trace.sh --live HOST:PORT [duration_ms]
#
# The server answers 200 with the capture dir, or 409 if a window is
# already open (single-capture latch).  For a *training* process, send
# SIGUSR2 instead (`kill -USR2 <pid>`); the run opens a window of
# profile_window_ms at the next log boundary.  See OBSERVABILITY.md.
#
# Usage: bash scripts/profile_trace.sh [outdir]
#        bash scripts/profile_trace.sh --live HOST:PORT [duration_ms]
set -u
if [ "${1:-}" = "--live" ]; then
  ADDR=${2:?usage: profile_trace.sh --live HOST:PORT [duration_ms]}
  DUR=${3:-2000}
  BODY=$(curl -s -X POST "http://$ADDR/profile?duration_ms=$DUR") || {
    echo "live capture failed: server at $ADDR unreachable"; exit 1; }
  echo "$BODY"
  case "$BODY" in
    *profile_dir*) echo "profiler window open for ${DUR} ms"; exit 0 ;;
    *"in progress"*) echo "capture already in progress (409)"; exit 1 ;;
    *) echo "live capture refused"; exit 1 ;;
  esac
fi
OUT=${1:-runs/profile}
cd "$(dirname "$0")/.."
mkdir -p "$OUT"

if [ ! -f "$OUT/profile_run/captions.json" ]; then
  timeout 300 python scripts/quality_run.py --corpus-only --out "$OUT/profile_run" \
    >"$OUT/profile_corpus.log" 2>&1 || { echo "corpus gen failed"; exit 1; }
fi

PROF="$OUT/profile_run_trace"
timeout 700 python -m sat_tpu.cli --phase=train \
  --set train_image_dir="$OUT/profile_run/images" \
  --set train_caption_file="$OUT/profile_run/captions.json" \
  --set vocabulary_file="$OUT/profile_run/vocabulary_basic.csv" \
  --set temp_annotation_file="$OUT/profile_run/anns_basic.csv" \
  --set temp_data_file="$OUT/profile_run/data_basic.npy" \
  --set save_dir="$OUT/profile_run/models2" \
  --set summary_dir="$OUT/profile_run/summary2" \
  --set max_train_ann_num=none --set batch_size=32 --set num_epochs=30 \
  --set max_steps=25 --set save_period=0 \
  --set profile_dir="$PROF" --set profile_start_step=8 \
  --set profile_num_steps=5 >"$OUT/profile_train.log" 2>&1
rc=$?
# a COMPLETE trace only: partial dirs from a mid-trace kill don't count
if [ "$rc" -eq 0 ] && { ls "$PROF"/plugins/profile/*/*.xplane.pb >/dev/null 2>&1 || \
     ls "$PROF"/plugins/profile/*/*.trace.json.gz >/dev/null 2>&1; }; then
  echo "trace captured under $PROF" | tee "$OUT/profile_done.txt"
else
  echo "trace capture failed (rc=$rc) — see $OUT/profile_train.log"
  exit 1
fi
