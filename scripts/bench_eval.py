"""Eval-side decode throughput: images/sec at beam_size=3.

BASELINE.md declares this a to-be-measured metric (the reference publishes
none; its host-side beam loop does ~beam×20 sess.run round-trips per image,
/root/reference/base_model.py:184-212).  Measures the full on-device
pipeline per batch: VGG16 encode + batched beam-search scan, one dispatch.

Usage: python scripts/bench_eval.py [--batch 32] [--beam 3] [--iters 20]
       (add --cpu --image-size 64 for a smoke run off-TPU)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_stamp() -> dict:
    # imported lazily: the stamp reads jax device facts only when the
    # bench already initialized a backend (sat_tpu.telemetry.bench_stamp)
    from sat_tpu.telemetry import bench_stamp

    return bench_stamp()

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--beam", type=int, default=3)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--cpu", action="store_true")
    # A/B control for the search's exact early exit (ops/beam_search.py);
    # note the random-init model here never emits eos from the top-K set,
    # so both arms measure the full-T worst case — the flag exists for
    # trained-checkpoint measurements via --params
    ap.add_argument("--no-early-exit", action="store_true")
    ap.add_argument(
        "--params",
        default=None,
        help="checkpoint .npz to decode with (a trained model terminates "
        "early; random init is the worst case)",
    )
    ap.add_argument(
        "--vocab",
        default=None,
        help="vocabulary CSV of the checkpoint's run — required with "
        "--params: derives the real '.' eos id and the valid_size mask "
        "the production decode applies (runtime.py decode_dataset)",
    )
    ap.add_argument(
        "--vocab-size",
        type=int,
        default=None,
        help="the checkpoint run's config.vocabulary_size (logit width) "
        "when it differs from the default",
    )
    ap.add_argument(
        "--encoder-quant",
        choices=("off", "bf16", "int8"),
        default="off",
        help="A/B the PTQ encoder (sat_tpu/nn/quant.py): measures the "
        "fp32 arm first, then the quantized arm over the SAME weights, "
        "emitting a second eval_images_per_sec_<mode> row",
    )
    args = ap.parse_args()
    if args.params and not args.vocab:
        ap.error("--params requires --vocab (eos id + valid_size must come "
                 "from the run's vocabulary, not a fixed index)")

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from sat_tpu.config import Config
    from sat_tpu.models.captioner import init_variables

    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}", file=sys.stderr, flush=True)

    config = Config(
        batch_size=args.batch, beam_size=args.beam, image_size=args.image_size
    )
    B = args.batch
    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.normal(size=(B, args.image_size, args.image_size, 3)).astype(np.float32)
    )
    eos = 1  # any fixed vocab index; random init never tops it → worst case
    valid_size = None
    if args.params:
        from sat_tpu.data.vocabulary import Vocabulary
        from sat_tpu.runtime import _eos_id
        from sat_tpu.train.step import create_train_state

        if args.vocab_size:
            config = config.replace(vocabulary_size=args.vocab_size)
        # width set BEFORE loading: Vocabulary clamps its word list to
        # size, so the default width would truncate a larger run's CSV
        vocab = Vocabulary(config.vocabulary_size, save_file=args.vocab)
        eos = _eos_id(vocab)
        valid_size = len(vocab.words)
        skeleton = create_train_state(jax.random.PRNGKey(0), config)
        # partial restore guard: a shape-skipped decoder would silently
        # benchmark random weights as "trained" (restore skips
        # mismatches), so count the params group by itself — the total
        # from restore_checkpoint also includes optimizer slots, which
        # would mask a skipped leaf
        from sat_tpu.train.checkpoint import _assign_leaves, load_flat

        flat = load_flat(args.params)
        params, n_p = _assign_leaves(skeleton.params, "params/", flat)
        n_params = len(jax.tree_util.tree_leaves(skeleton.params))
        if n_p < n_params:
            print(
                f"checkpoint covered {n_p}/{n_params} param leaves — wrong "
                "config/--vocab-size for this checkpoint?",
                file=sys.stderr,
            )
            return 2
        batch_stats, _ = _assign_leaves(skeleton.batch_stats, "batch_stats/", flat)
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
    else:
        variables = init_variables(jax.random.PRNGKey(0), config)

    from sat_tpu.utils.benchmarking import (
        make_chained_decode,
        time_decode_windows,
    )

    decode = make_chained_decode(
        config, eos=eos, beam_size=args.beam, valid_size=valid_size,
        early_exit=not args.no_early_exit,
    )
    compile_s, windows_ms, _ = time_decode_windows(
        decode, variables, images, args.iters, windows=1
    )
    print(f"compile+first: {compile_s:.1f}s", file=sys.stderr, flush=True)

    images_per_sec = 1e3 * B / windows_ms[0]
    common = {
        "unit": f"images/sec @ beam={args.beam}",
        "batch_size": B,
        "early_exit": not args.no_early_exit,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        **_bench_stamp(),
    }
    print(
        json.dumps(
            {
                "metric": "eval_images_per_sec",
                "value": round(images_per_sec, 2),
                "batch_ms": round(windows_ms[0], 1),
                "encoder_quant": "off",
                **common,
            }
        ),
        flush=True,
    )

    if args.encoder_quant != "off":
        # quantized arm: same weights through the PTQ pass, same decode —
        # the row pair is the encode-path A/B the PERF table quotes
        import time as _time

        from sat_tpu.nn import quant

        qconfig = config.replace(encoder_quant=args.encoder_quant)
        t0 = _time.perf_counter()
        qcnn = quant.quantize_encoder(variables, qconfig)
        quantize_s = _time.perf_counter() - t0
        qvars = {
            "params": {"decoder": variables["params"]["decoder"]},
            "qcnn": qcnn,
        }
        qdecode = make_chained_decode(
            qconfig, eos=eos, beam_size=args.beam, valid_size=valid_size,
            early_exit=not args.no_early_exit,
        )
        q_compile_s, q_windows_ms, _ = time_decode_windows(
            qdecode, qvars, images, args.iters, windows=1
        )
        print(
            f"quant arm ({args.encoder_quant}) compile+first: "
            f"{q_compile_s:.1f}s (quantize {quantize_s:.2f}s)",
            file=sys.stderr, flush=True,
        )
        print(
            json.dumps(
                {
                    "metric": f"eval_images_per_sec_{args.encoder_quant}",
                    "value": round(1e3 * B / q_windows_ms[0], 2),
                    "batch_ms": round(q_windows_ms[0], 1),
                    "encoder_quant": args.encoder_quant,
                    "quantize_seconds": round(quantize_s, 3),
                    "fp32_images_per_sec": round(images_per_sec, 2),
                    **common,
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
