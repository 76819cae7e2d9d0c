"""Benchmark: training throughput + MFU of the flagship caption model.

Measures steady-state captions/sec of the jitted train step — VGG16
encoder forward (frozen CNN, the reference's published configuration,
/root/reference/config.py:8-43 + README.md:85-89), 20-step scan decoder,
backward, global-norm clip 5.0, Adam — on the first device JAX provides.

One process: it runs the bench and exits with its own status.  Anything
that raises — a bad knob, a failed compile, an out-of-memory sweep size,
a device whose peak FLOP/s is not in the table below — ends the run with
a traceback and a non-zero exit code.

Prints JSON lines on stdout of the shape
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": 1.0,
   "platform": ..., "device_kind": ..., "device_count": N, ...extras}
The first line comes from a minimal timed window; later lines re-emit
the same schema with fuller numbers (full window, batch sweep, then
eval-decode extras).  The reference publishes no throughput numbers
(SURVEY.md §6) and the repo records no baseline yet, so ``vs_baseline``
is 1.0.

Env knobs: BENCH_BATCH (default 32), BENCH_STEPS (default 10),
BENCH_MIN_STEPS (minimal first-emit window, default 3),
BENCH_IMAGE_SIZE (override config.image_size), BENCH_WARMUP (default 2),
BENCH_TRAIN_CNN=1 (joint CNN+RNN training instead of the default
frozen-CNN reference configuration), BENCH_RNG_IMPL (override
config.rng_impl, e.g. threefry2x32 for the dropout-PRNG A/B),
BENCH_CNN=resnet50 (bench the second encoder family), BENCH_REMAT=1 /
BENCH_REMAT_CNN=1 (decoder / encoder rematerialization A/Bs),
BENCH_CE_DTYPE (bf16-CE A/B), BENCH_EVAL=0 (skip the additive
eval-decode metric; BENCH_EVAL_ITERS sizes its window), BENCH_SWEEP
(comma list of extra batch sizes tried after the primary windows land —
default "64,128,256" for the frozen-CNN config, "0" disables; the final
line reports the best measured config with the per-batch sweep results
attached).
"""

from __future__ import annotations

import json
import os
import sys
import time

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# bf16 peak FLOP/s per chip, keyed by a substring of jax's device_kind
# with blanks removed (Google Cloud TPU documentation, per-generation
# system-architecture pages).
_PEAK_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5lite": 197.0,   # JAX reports v5e as device_kind "TPU v5 lite"
    "v5p": 459.0,
    "v6e": 918.0,
    "v6lite": 918.0,
}


def _peak_flops(device) -> float:
    kind = device.device_kind.lower().replace(" ", "")
    for key, tf in _PEAK_TFLOPS.items():
        if key in kind:
            return tf * 1e12
    raise ValueError(
        f"bench.py: no peak FLOP/s recorded for device_kind "
        f"{device.device_kind!r} (platform {device.platform!r}); MFU needs "
        f"a known accelerator — add it to _PEAK_TFLOPS with its source"
    )


def _program_flops(compiled) -> float:
    """FLOPs/step from XLA's cost analysis of the compiled program."""
    return float(compiled.cost_analysis()["flops"])


def _config_from_env():
    """The benched Config, from the BENCH_* env knobs."""
    from sat_tpu.config import Config

    config = Config(
        batch_size=int(os.environ.get("BENCH_BATCH", "32")),
        train_cnn=os.environ.get("BENCH_TRAIN_CNN", "0") == "1",
        cnn=os.environ.get("BENCH_CNN", "vgg16"),
    )
    if "BENCH_IMAGE_SIZE" in os.environ:  # smoke/micro runs off-reference
        config = config.replace(image_size=int(os.environ["BENCH_IMAGE_SIZE"]))
    if "BENCH_RNG_IMPL" in os.environ:  # e.g. threefry2x32, the dropout A/B
        config = config.replace(rng_impl=os.environ["BENCH_RNG_IMPL"])
    if os.environ.get("BENCH_REMAT") == "1":  # decoder-remat A/B
        config = config.replace(remat_decoder=True)
    if os.environ.get("BENCH_REMAT_CNN") == "1":  # encoder-remat A/B (joint)
        config = config.replace(remat_cnn=True)
    if "BENCH_CE_DTYPE" in os.environ:  # bf16-CE A/B
        config = config.replace(ce_dtype=os.environ["BENCH_CE_DTYPE"])
    return config


def _host_batch(config, rng, B=None):
    import numpy as np

    B = config.batch_size if B is None else B
    T = config.max_caption_length
    S = config.image_size
    return {
        "images": rng.normal(size=(B, S, S, 3)).astype(np.float32),
        "word_idxs": rng.integers(0, config.vocabulary_size, size=(B, T)).astype(
            np.int32
        ),
        "masks": (np.arange(T)[None, :] < rng.integers(8, T + 1, size=(B, 1))).astype(
            np.float32
        ),
    }


def run_bench() -> None:
    import numpy as np

    # knobs first: a bad one fails before any device work
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    n_steps = int(os.environ.get("BENCH_STEPS", "10"))
    n_min = max(1, int(os.environ.get("BENCH_MIN_STEPS", "3")))
    config = _config_from_env()
    B = config.batch_size
    train_cnn = config.train_cnn
    cnn = config.cnn
    T = config.max_caption_length

    log("importing jax")
    import jax

    from sat_tpu.utils.compile_cache import enable as enable_compile_cache

    log(f"compile cache: {enable_compile_cache(jax)}")

    from sat_tpu.train.step import create_train_state, make_jit_train_step

    device = jax.devices()[0]
    device_facts = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }
    log(" ".join(f"{k}={v}" for k, v in device_facts.items()))
    peak = _peak_flops(device)  # unknown accelerator: fail before compiling

    rng = np.random.default_rng(0)
    log(f"building host batch B={B} T={T}")
    host_batch = _host_batch(config, rng)

    log("initializing model state")
    state = create_train_state(jax.random.PRNGKey(0), config)
    step_rng = jax.random.key(1, impl=config.rng_impl)
    log("transferring batch + state to device")
    batch = jax.device_put(host_batch, device)
    state = jax.device_put(state, device)
    jax.block_until_ready((batch, state))

    train_step = make_jit_train_step(config)
    log("lowering + compiling train step")
    t_c = time.perf_counter()
    compiled = train_step.lower(state, batch, step_rng).compile()
    compile_s = time.perf_counter() - t_c
    log(f"compiled in {compile_s:.1f}s")
    flops_per_step = _program_flops(compiled)

    def emit(elapsed: float, steps: int, window: str) -> dict:
        captions_per_sec = steps * B / elapsed
        step_ms = 1e3 * elapsed / steps
        log(f"[{window}] {captions_per_sec:.2f} captions/sec ({step_ms:.1f} ms/step)")
        achieved = flops_per_step * steps / elapsed
        result = {
            "metric": "train_captions_per_sec",
            "value": round(captions_per_sec, 2),
            "unit": "captions/sec/chip",
            "vs_baseline": 1.0,
            "step_time_ms": round(step_ms, 2),
            "batch_size": B,
            "train_cnn": train_cnn,
            "cnn": cnn,
            "compile_s": round(compile_s, 1),
            **device_facts,
            "window": window,
            "steps_measured": steps,
            "tflops_per_sec": round(achieved / 1e12, 2),
            "mfu": round(achieved / peak, 4),
        }
        print(json.dumps(result), flush=True)
        return result

    log(f"warmup x{warmup}")
    for _ in range(warmup):
        state, metrics = compiled(state, batch, step_rng)
        loss = float(metrics["total_loss"])  # hard host sync barrier
        log(f"warmup step done, loss={loss:.4f}")

    log(f"minimal timing window x{n_min}")
    t0 = time.perf_counter()
    for _ in range(n_min):
        state, metrics = compiled(state, batch, step_rng)
    float(metrics["total_loss"])  # sync
    emit(time.perf_counter() - t0, n_min, "minimal")

    log(f"full timing window x{n_steps}")
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, batch, step_rng)
    float(metrics["total_loss"])  # sync
    result = emit(time.perf_counter() - t0, n_steps, "full")

    # Batch-size sweep: the chip's best operating point is usually a
    # bigger batch than the B=32 default (the MXU tiles 128 rows); the
    # final line reports the best measured config.  Off for the joint-CNN
    # variant, whose activations outgrow the chip at B=128 without remat
    # (BENCH_SWEEP names other sizes; "0" disables).
    sweep_env = os.environ.get(
        "BENCH_SWEEP", "64,128,256" if not train_cnn else "0"
    )
    sweep_batches = [
        int(x) for x in sweep_env.split(",") if x.strip() and x.strip() != "0"
    ]
    if sweep_batches:
        result["sweep"] = {str(B): result["value"]}
    for B2 in sweep_batches:
        if B2 == B:
            continue
        log(f"sweep: building + compiling B={B2}")
        batch2 = jax.device_put(_host_batch(config, rng, B2), device)
        state2 = jax.device_put(jax.device_get(state), device)
        cfg2 = config.replace(batch_size=B2)
        step2 = make_jit_train_step(cfg2)
        t_c = time.perf_counter()
        compiled2 = step2.lower(state2, batch2, step_rng).compile()
        log(f"sweep B={B2}: compiled in {time.perf_counter() - t_c:.1f}s")
        flops2 = _program_flops(compiled2)
        for _ in range(warmup):
            state2, m2 = compiled2(state2, batch2, step_rng)
            float(m2["total_loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state2, m2 = compiled2(state2, batch2, step_rng)
        float(m2["total_loss"])
        el2 = time.perf_counter() - t0
        cps2 = n_steps * B2 / el2
        log(f"sweep B={B2}: {cps2:.2f} captions/sec ({1e3*el2/n_steps:.1f} ms/step)")
        result["sweep"][str(B2)] = round(cps2, 2)
        if cps2 > result["value"]:
            achieved = flops2 * n_steps / el2
            result.update(
                value=round(cps2, 2),
                step_time_ms=round(1e3 * el2 / n_steps, 2),
                batch_size=B2,
                window="full",
                tflops_per_sec=round(achieved / 1e12, 2),
                mfu=round(achieved / peak, 4),
            )
        print(json.dumps(result), flush=True)

    # Eval-decode throughput (encode + on-device batched beam search) in
    # the same artifact, re-emitted as a fuller line (BENCH_EVAL=0
    # disables).
    if os.environ.get("BENCH_EVAL", "1") == "1":
        from sat_tpu.utils.benchmarking import (
            make_chained_decode,
            time_decode_windows,
        )

        log("eval decode: compiling encoder+beam program (beam=3)")
        eval_iters = int(os.environ.get("BENCH_EVAL_ITERS", "5"))

        # BN encoders (resnet50) need running statistics at inference
        eval_variables = {"params": state.params}
        if state.batch_stats:
            eval_variables["batch_stats"] = state.batch_stats

        # the SAME measurement core as scripts/bench_eval{,_ab}.py —
        # cross-vehicle deltas are process state, never harness drift
        decode = make_chained_decode(config, eos=1, beam_size=3)
        compile_s, windows_ms, _ = time_decode_windows(
            decode, eval_variables, batch["images"], eval_iters, windows=1
        )
        log(f"eval decode compiled+first in {compile_s:.1f}s")
        result["eval_images_per_sec"] = round(1e3 * B / windows_ms[0], 2)
        result["eval_batch_ms"] = round(windows_ms[0], 1)
        log(f"eval decode: {result['eval_images_per_sec']} images/sec @ beam=3")
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    run_bench()
