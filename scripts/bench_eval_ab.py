"""Controlled A/B for the eval-decode throughput discrepancy.

Round 3 left two numbers for the same metric (PERF.md): 802 img/s from a
dedicated decode process (scripts/bench_eval.py) vs 619-620 from bench.py's
in-process window measured right after the train program ran.  The offered
explanation ("chip state shared with the train program") was a conjecture;
this script turns it into a measured mechanism by varying ONE factor at a
time, with everything else held identical:

* arm "fresh":    a new process measures decode only;
* arm "resident": the SAME process first builds and runs the training
  program for 10 steps (bench.py's shape), keeps the sharded train state
  alive, then measures decode with byte-identical measurement code.

Each arm runs in its own subprocess, repeated --repeats times,
interleaved (fresh, resident, fresh, ...) so slow chip-state drift
cannot masquerade as an arm effect.  Within a run, decode time is
measured over --windows consecutive windows of --iters batches each, so
warm-up drift inside a process is visible separately from the
resident-program effect.  The parent writes one summary JSON line:
the per-arm mean images/sec of the LAST window (steady state), the
resident/fresh ratio, and the raw per-run rows.

Usage:
  python scripts/bench_eval_ab.py [--repeats 3] [--batch 32] [--beam 3]
                                  [--iters 10] [--windows 3] [--out FILE]
  (--cpu --image-size 64 --steps 2 for an off-TPU smoke run)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--beam", type=int, default=3)
    ap.add_argument("--iters", type=int, default=10, help="batches per window")
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--steps", type=int, default=10,
                    help="train steps the resident arm runs first")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default=None, help="summary JSON path (parent)")
    ap.add_argument("--arm", choices=["fresh", "resident"], default=None,
                    help="internal: run one measurement in this process")
    ap.add_argument("--budget-s", type=float, default=420.0,
                    help="parent per-subprocess timeout")
    return ap


def run_arm(args) -> int:
    """One measurement process; prints a single JSON row on stdout."""
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from sat_tpu.config import Config
    from sat_tpu.models.captioner import init_variables
    from sat_tpu.utils.benchmarking import (
        make_chained_decode,
        time_decode_windows,
    )

    config = Config(
        batch_size=args.batch, beam_size=args.beam, image_size=args.image_size
    )
    B = args.batch
    rng = np.random.default_rng(0)
    host_images = rng.normal(
        size=(B, args.image_size, args.image_size, 3)
    ).astype(np.float32)

    resident_state = None
    if args.arm == "resident":
        # bench.py's shape of the world: the full train program compiled
        # and executed in this process, its state left alive on device
        from sat_tpu.train.step import create_train_state, make_jit_train_step

        state = create_train_state(jax.random.PRNGKey(0), config)
        train_step = make_jit_train_step(config)
        t_batch = {
            "images": jax.device_put(host_images),
            "word_idxs": jax.device_put(
                rng.integers(
                    0, config.vocabulary_size,
                    (B, config.max_caption_length),
                ).astype(np.int32)
            ),
            "masks": jax.device_put(
                np.ones((B, config.max_caption_length), np.float32)
            ),
        }
        rkey = jax.random.key(1, impl=config.rng_impl)
        for i in range(args.steps):
            state, _ = train_step(state, t_batch, jax.random.fold_in(rkey, i))
        jax.block_until_ready(state.params)
        resident_state = state  # keep it alive through the decode windows

    variables = init_variables(jax.random.PRNGKey(0), config)
    images = jax.device_put(host_images)

    decode = make_chained_decode(config, eos=1, beam_size=args.beam)
    compile_s, windows_ms, _ = time_decode_windows(
        decode, variables, images, args.iters, args.windows
    )

    dev = jax.devices()[0]
    row = {
        "arm": args.arm,
        "batch": B,
        "beam": args.beam,
        "windows_batch_ms": [round(ms, 2) for ms in windows_ms],
        "images_per_sec_last_window": round(1e3 * B / windows_ms[-1], 2),
        "compile_s": round(compile_s, 1),
        "device_kind": getattr(dev, "device_kind", dev.platform),
    }
    from sat_tpu.telemetry import bench_stamp

    row.update(bench_stamp())
    del resident_state
    print(json.dumps(row), flush=True)
    return 0


def _emit_error(row: dict) -> None:
    # both streams, so the diagnostics survive a wrapper that keeps
    # only one of them
    print(json.dumps(row), flush=True)
    print(json.dumps(row), file=sys.stderr, flush=True)


def main() -> int:
    ap = build_parser()
    args = ap.parse_args()
    if args.arm:
        return run_arm(args)

    child_flags = [
        "--batch", str(args.batch), "--beam", str(args.beam),
        "--iters", str(args.iters), "--windows", str(args.windows),
        "--steps", str(args.steps), "--image-size", str(args.image_size),
    ] + (["--cpu"] if args.cpu else [])

    rows = []
    # interleaved arms: chip-state drift over the session averages out of
    # the arm comparison instead of into it
    order = []
    for r in range(args.repeats):
        order += [("fresh", r), ("resident", r)]
    for arm, rep in order:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--arm", arm]
                + child_flags,
                capture_output=True, text=True, timeout=args.budget_s,
            )
        except subprocess.TimeoutExpired as e:
            # a child that overran its budget must produce the same
            # structured error row as a nonzero exit, not an uncaught
            # traceback
            _emit_error({
                "error": "arm_timeout", "arm": arm, "repeat": rep,
                "budget_s": args.budget_s,
                "stderr": ((e.stderr or "")[-500:] if isinstance(
                    e.stderr, str) else ""),
            })
            return 3
        if proc.returncode != 0:
            _emit_error({
                "error": "arm_failed", "arm": arm, "repeat": rep,
                "rc": proc.returncode, "stderr": proc.stderr[-500:],
            })
            return 3
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        row["repeat"] = rep
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    def arm_mean(arm):
        v = [r["images_per_sec_last_window"] for r in rows if r["arm"] == arm]
        return sum(v) / len(v)

    fresh, resident = arm_mean("fresh"), arm_mean("resident")
    summary = {
        "metric": "eval_images_per_sec",
        "value": round(fresh, 2),          # the clean-process number
        "unit": f"images/sec @ beam={args.beam}",
        "protocol": (
            f"B={args.batch}, {args.windows} windows x {args.iters} "
            f"batches, last window, {args.repeats} interleaved repeats "
            "per arm, fresh subprocess each"
        ),
        "fresh_mean": round(fresh, 2),
        "resident_mean": round(resident, 2),
        "resident_over_fresh": round(resident / fresh, 4),
        "rows": rows,
    }
    from sat_tpu.telemetry import bench_stamp

    summary.update(bench_stamp())
    line = json.dumps(summary)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
