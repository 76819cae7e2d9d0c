"""Bulk offline captioning benchmark: steady throughput + resume cost.

Times the real ``--phase bulk`` CLI end-to-end (docs/BULK.md) against a
procedurally generated corpus and a tiny blessed checkpoint:

* ``bulk_throughput_captions_s`` — steady-state captions/second of the
  decode loop, read from the run's final heartbeat (the gauge clock
  starts after AOT warmup, so compile time is excluded — that cost is
  bench_serve's ``serve_warmup_s`` territory);
* ``bulk_resume_overhead_s`` — wall seconds of a relaunch over a fully
  completed output dir: corpus walk + manifest load + per-shard crc
  verification, and NO jax boot (the resume fast path exits before the
  device runtime loads).  This is the fixed tax every ``--supervise``
  restart pays before new work starts.

The run is rejected (exit 1) if the job reports any steady-state XLA
recompile — the zero-recompile guarantee is the premise of the
throughput number.

Prints BENCH-contract JSON rows on stdout ({"metric", "value", "unit",
"vs_baseline", ...}; schema via ``telemetry.bench_stamp``) so
``scripts/check_regression.py`` gates the trajectory.

Usage: python scripts/bench_bulk.py [--images 24] [--shard-rows 6]
       [--workdir DIR] [--timeout 420]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from sat_tpu import telemetry

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench_bulk +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    return env


def _make_corpus(corpus_dir: str, n: int, size: int) -> None:
    """n procedural JPEGs — deterministic, no dataset download."""
    import cv2
    import numpy as np

    os.makedirs(corpus_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(n):
        img = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
        cv2.imwrite(os.path.join(corpus_dir, f"corpus_{i:05d}.jpg"), img)


_SEED_CHILD = r'''
import os, sys
import jax
import numpy as np
from sat_tpu.config import Config
from sat_tpu.resilience import lineage
from sat_tpu.train.checkpoint import save_checkpoint
from sat_tpu.train.step import create_train_state

config = Config.load(sys.argv[1])
os.makedirs(config.save_dir, exist_ok=True)
state = create_train_state(jax.random.PRNGKey(0), config)
save_checkpoint(state, config)
lineage.mark_last_good(config.save_dir, int(np.asarray(state.step)))
'''


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--images", type=int, default=24)
    ap.add_argument("--shard-rows", type=int, default=6)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--timeout", type=int, default=420,
                    help="per-child-run timeout, seconds")
    args = ap.parse_args()

    workdir = args.workdir or tempfile.mkdtemp(prefix="bench_bulk_")
    made_workdir = args.workdir is None
    try:
        from sat_tpu.config import Config
        from sat_tpu.data.vocabulary import Vocabulary

        corpus = os.path.join(workdir, "corpus")
        _make_corpus(corpus, args.images, 32)
        vocab_file = os.path.join(workdir, "vocabulary.csv")
        vocabulary = Vocabulary(size=30)
        vocabulary.build(["a man riding a horse.", "a cat on a table."])
        vocabulary.save(vocab_file)
        out_dir = os.path.join(workdir, "out")
        config = Config(
            phase="bulk", image_size=32, dim_embedding=16,
            num_lstm_units=16, dim_initialize_layer=16,
            dim_attend_layer=16, dim_decode_layer=32,
            compute_dtype="float32", vocabulary_size=vocabulary.size,
            vocabulary_file=vocab_file, beam_size=2,
            serve_slot_pages=2, serve_page_width=2,
            telemetry=True, heartbeat_interval=0.1,
            shard_cache="off",
            save_dir=os.path.join(workdir, "models"),
            summary_dir=os.path.join(workdir, "summary"),
            bulk_input=corpus, bulk_output=out_dir,
            bulk_shard_rows=args.shard_rows,
        )
        cfg_path = os.path.join(workdir, "bulk.json")
        config.save(cfg_path)

        log("blessing a tiny checkpoint (init-only, no train steps)")
        proc = subprocess.run(
            [sys.executable, "-c", _SEED_CHILD, cfg_path],
            capture_output=True, text=True, cwd=REPO, env=_child_env(),
            timeout=args.timeout,
        )
        if proc.returncode != 0:
            log(f"seed child failed rc {proc.returncode}:\n{proc.stderr}")
            return 1

        log(f"decode run: {args.images} images, shards of {args.shard_rows}")
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "sat_tpu.cli", "--config", cfg_path],
            capture_output=True, text=True, cwd=REPO, env=_child_env(),
            timeout=args.timeout,
        )
        decode_wall_s = time.perf_counter() - t0
        if proc.returncode != 0 or "bulk: complete" not in proc.stderr:
            log(f"bulk run failed rc {proc.returncode}:\n{proc.stderr}")
            return 1
        hb_path = os.path.join(config.summary_dir, "telemetry",
                               "heartbeat.json")
        with open(hb_path) as f:
            bulk = json.load(f).get("bulk", {})
        throughput = bulk.get("captions_per_s", 0.0)
        steady = bulk.get("steady_compiles")
        log(f"decode: {throughput:.1f} captions/s steady "
            f"({decode_wall_s:.1f}s wall incl. boot), "
            f"{steady} steady-state recompiles")
        if steady != 0:
            log(f"REJECTED: {steady} steady-state XLA recompiles "
                "(a shape leaked past the AOT warmup)")
            return 1
        if bulk.get("images_done") != args.images:
            log(f"REJECTED: {bulk.get('images_done')} of {args.images} "
                "images captioned")
            return 1

        log("resume run over the completed output dir")
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "sat_tpu.cli", "--config", cfg_path],
            capture_output=True, text=True, cwd=REPO, env=_child_env(),
            timeout=args.timeout,
        )
        resume_s = time.perf_counter() - t0
        if proc.returncode != 0 or "nothing to do" not in proc.stderr:
            log(f"resume run failed rc {proc.returncode}:\n{proc.stderr}")
            return 1
        log(f"resume: {resume_s:.2f}s (verified + skipped every shard, "
            "no jax boot)")

        rows = [
            {
                "metric": "bulk_throughput_captions_s",
                "value": round(throughput, 3),
                "unit": "captions/s",
                "vs_baseline": 1.0,
                "images": args.images,
                "shard_rows": args.shard_rows,
                "decode_wall_s": round(decode_wall_s, 2),
                **telemetry.bench_stamp(),
            },
            {
                "metric": "bulk_resume_overhead_s",
                "value": round(resume_s, 3),
                "unit": "s",
                "vs_baseline": 1.0,
                "shards_verified": (args.images + args.shard_rows - 1)
                // args.shard_rows,
                **telemetry.bench_stamp(),
            },
        ]
        print(json.dumps(rows, indent=1), flush=True)
        return 0
    finally:
        if made_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
