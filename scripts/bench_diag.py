"""Diag-tap overhead accounting: train-step cost with --diag_level off/basic/full.

docs/OBSERVABILITY.md claims the in-graph model-health taps
(telemetry/device.py) are cheap enough to leave on: a handful of scalar
reductions fused into the step program, fetched on the existing log sync.
This bench puts a number on it — the measured wall-clock delta between a
``diag_level=off`` and a ``diag_level=basic`` (and ``full``) train step
on a small synthetic model, expressed as percent of a ``--step-ms``
(default 30 ms) production device step.  The acceptance bar is
``basic < 1%`` (ISSUE 4).

Methodology: the three step variants are compiled up front, then timed in
INTERLEAVED rounds (off/basic/full, off/basic/full, ...) with a device
sync per timed block, taking the per-round minimum block time —
interleaving cancels drift (thermal, CI noisy neighbors) that
back-to-back arms would alias into the delta.

Prints a BENCH-contract JSON row ({"metric","value","unit",
"vs_baseline",...}) stamped with the shared provenance header
(``sat_tpu.telemetry.bench_stamp``), so ``scripts/check_regression.py``
can gate it across sessions.

Usage: python scripts/bench_diag.py [--batch 8] [--iters 30] [--rounds 5]
       [--step-ms 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench_diag +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=30,
                    help="steps per timed block")
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved measurement rounds per arm")
    ap.add_argument("--step-ms", type=float, default=30.0,
                    help="production device step the overhead is scored "
                         "against (~30 ms; not measured on the "
                         "current machine)")
    args = ap.parse_args()

    log("importing jax")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sat_tpu import telemetry
    from sat_tpu.config import Config
    from sat_tpu.train.step import create_train_state, make_jit_train_step

    base = Config(
        phase="train",
        batch_size=args.batch,
        image_size=32,
        dim_embedding=16,
        num_lstm_units=16,
        dim_initialize_layer=16,
        dim_attend_layer=16,
        dim_decode_layer=32,
        vocabulary_size=200,
        compute_dtype="float32",
    )
    rng = jax.random.PRNGKey(0)
    B, T = args.batch, base.max_caption_length
    batch = {
        "images": jnp.asarray(
            np.random.default_rng(0).integers(
                0, 255, (B, base.image_size, base.image_size, 3), np.uint8
            )
        ),
        "word_idxs": jnp.asarray(
            np.random.default_rng(1).integers(0, 200, (B, T), np.int32)
        ),
        "masks": jnp.ones((B, T), jnp.float32),
    }
    step_rng = jax.random.key(1, impl=base.rng_impl)

    arms = {}
    for level in ("off", "basic", "full"):
        config = base.replace(diag_level=level)
        step_fn = make_jit_train_step(config)
        state = create_train_state(rng, config)
        # steady state: compile + a couple of dispatches outside the timer
        for _ in range(3):
            state, metrics = step_fn(state, batch, step_rng)
        jax.block_until_ready(metrics)
        arms[level] = (step_fn, state)
        log(f"{level}: compiled, {len(metrics)} metric outputs")

    times = {level: [] for level in arms}
    for r in range(args.rounds):
        for level, (step_fn, state) in arms.items():
            t0 = time.perf_counter()
            metrics = None
            for _ in range(args.iters):
                state, metrics = step_fn(state, batch, step_rng)
            jax.block_until_ready(metrics)
            times[level].append((time.perf_counter() - t0) / args.iters)
            arms[level] = (step_fn, state)
    ms = {level: 1e3 * min(samples) for level, samples in times.items()}
    log(f"per-step: off {ms['off']:.4f} ms, basic {ms['basic']:.4f} ms, "
        f"full {ms['full']:.4f} ms")

    # the gated quantity: what basic taps add to a production step budget
    basic_delta_ms = max(0.0, ms["basic"] - ms["off"])
    full_delta_ms = max(0.0, ms["full"] - ms["off"])
    overhead_pct = 100.0 * basic_delta_ms / args.step_ms
    log(f"basic taps: +{basic_delta_ms:.4f} ms/step = {overhead_pct:.4f}% "
        f"of a {args.step_ms:.0f} ms step (bar: 1%)")

    result = {
        "metric": "diag_tap_overhead",
        "value": round(overhead_pct, 4),
        "unit": "%_of_step",
        "vs_baseline": 1.0,  # the acceptance bar (ISSUE 4: < 1%)
        "off_ms_per_step": round(ms["off"], 4),
        "basic_ms_per_step": round(ms["basic"], 4),
        "full_ms_per_step": round(ms["full"], 4),
        "basic_delta_ms": round(basic_delta_ms, 4),
        "full_delta_ms": round(full_delta_ms, 4),
        "step_ms_assumed": args.step_ms,
        "iters": args.iters,
        "rounds": args.rounds,
        "batch_size": args.batch,
        **telemetry.bench_stamp(),
    }
    print(json.dumps(result), flush=True)
    return 0 if overhead_pct < 1.0 else 1


if __name__ == "__main__":
    sys.exit(main())
