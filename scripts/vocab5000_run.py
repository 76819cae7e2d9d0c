"""Full-width vocabulary exercise: train + decode at V≈5000, with TP parity.

The reference's published configuration runs vocabulary_size=5000
(/root/reference/config.py:66-67), but the richest fixture corpus to date
builds ~1,100 words — so the full-width [5000, 512] embedding/softmax
(the tensors vocab-TP exists for) had never been trained at published
width on data, nor sharded at that width (VERDICT r04 missing #5 /
next-round #4).  This script closes that:

1. builds a rich-style corpus large enough that the top-5000 vocabulary
   cap BINDS (3 unique words/image: ~1700 images → >5000 distinct words),
2. builds the vocabulary (asserting the cap bound at exactly 5000),
3. trains the flagship decoder at V=5000 single-device for a bounded
   number of steps on real corpus batches,
4. repeats the identical run under vocab-TP on a (2,4) mesh of 8 virtual
   CPU devices (embedding + softmax + their Adam moments sharded 4-way
   over 'model': 5000 % 4 == 0 → 1250-row shards),
5. asserts per-step loss parity between the two trajectories,
6. beam-decodes (beam=3) a capped eval subset at V=5000 through the full
   eval pipeline (both single-device and on the mesh), and
7. writes runs/vocab5000/result.json with the parity numbers and scores.

CPU-only by design: the parity evidence needs an 8-device mesh, which
here is 8 virtual CPU devices.  Usage:
    python scripts/vocab5000_run.py [--out runs/vocab5000] [--steps 48]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# 8 virtual CPU devices BEFORE jax import (mirrors tests/conftest.py)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (sat_tpu)
sys.path.insert(0, _HERE)                   # sibling scripts

import numpy as np  # noqa: E402

from quality_run import make_rich_corpus  # noqa: E402


def _losses(summary_dir: str) -> np.ndarray:
    path = os.path.join(summary_dir, "metrics.jsonl")
    with open(path) as f:
        return np.array([json.loads(x)["total_loss"] for x in f])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="runs/vocab5000")
    ap.add_argument("--num-images", type=int, default=1700,
                    help="3 unique words/image; 1700 → >5100 distinct "
                    "words, so the top-5000 cap binds")
    ap.add_argument("--steps", type=int, default=48,
                    help="bounded train steps per arm (the exercise is "
                    "width + parity, not convergence)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=64,
                    help="small edge keeps the CPU VGG16 forward cheap; "
                    "the vocab-width tensors are full-size regardless")
    ap.add_argument("--eval-anns", type=int, default=24,
                    help="eval-subset cap for the beam=3 decode stage")
    args = ap.parse_args()

    t0 = time.time()

    def log(msg: str) -> None:
        print(f"[v5000 +{time.time()-t0:6.1f}s] {msg}", flush=True)

    root = os.path.abspath(args.out)
    os.makedirs(root, exist_ok=True)

    img_dir, caption_file, _ = make_rich_corpus(
        root, num_images=args.num_images, image_edge=args.image_size
    )
    log(f"corpus: {args.num_images} images, 2 captions each")

    import jax

    from sat_tpu.utils.compile_cache import enable as _enable_cache

    _enable_cache(jax)
    assert len(jax.devices()) >= 8, jax.devices()

    from sat_tpu.cli import build_config
    from sat_tpu.data.dataset import build_vocabulary

    # ~steps*B annotations so one epoch is exactly the bounded run
    ann_cap = args.steps * args.batch_size
    common = [
        f"train_image_dir={img_dir}",
        f"train_caption_file={caption_file}",
        f"eval_image_dir={img_dir}",
        f"eval_caption_file={caption_file}",
        f"vocabulary_file={root}/vocabulary.csv",
        f"temp_annotation_file={root}/anns.csv",
        f"temp_data_file={root}/data.npy",
        f"eval_result_dir={root}/results",
        "vocabulary_size=5000",
        f"batch_size={args.batch_size}",
        f"image_size={args.image_size}",
        "num_epochs=1",
        f"max_train_ann_num={ann_cap}",
        f"max_eval_ann_num={args.eval_anns}",
        "save_period=0",
        "log_every=8",
        # deterministic trajectories for the parity comparison
        "fc_drop_rate=0.0",
        "lstm_drop_rate=0.0",
    ]

    def cfg(phase: str, *extra: str):
        set_args = [x for o in (*common, *extra) for x in ("--set", o)]
        config, _ = build_config([f"--phase={phase}"] + set_args)
        return config

    # 1) vocabulary from the FULL corpus (no ann cap) — the 5000 cap must
    # bind, which is the point of the exercise
    vocab_cfg = cfg("train", "max_train_ann_num=none")
    if not os.path.exists(vocab_cfg.vocabulary_file):
        vocabulary = build_vocabulary(vocab_cfg)
    else:
        from sat_tpu.data.vocabulary import Vocabulary

        vocabulary = Vocabulary(5000, vocab_cfg.vocabulary_file)
    vocab_words = len(vocabulary.words)
    log(f"vocabulary built: {vocab_words} words (cap 5000)")
    assert vocab_words == 5000, (
        f"corpus must overflow the top-5000 cap, built {vocab_words}"
    )

    from sat_tpu import runtime

    # 2) single-device trajectory
    single_cfg = cfg(
        "train",
        f"save_dir={root}/models_single",
        f"summary_dir={root}/summary_single",
        "mesh_shape=1,1",
    )
    log("training single-device at V=5000")
    state_single = runtime.train(single_cfg, seed=0)
    single_losses = _losses(f"{root}/summary_single")
    log(f"single-device done: {int(state_single.step)} steps, "
        f"loss {single_losses[0]:.4f} -> {single_losses[-1]:.4f}")

    # 3) vocab-TP (2 data × 4 model) trajectory, same seed and data
    tp_cfg = cfg(
        "train",
        f"save_dir={root}/models_tp",
        f"summary_dir={root}/summary_tp",
        "mesh_shape=2,4",
    )
    # guard against silently-replicated "TP": the embedding/softmax rows
    # must actually shard 4-way at this width
    from sat_tpu.parallel import make_mesh
    from sat_tpu.parallel.sharding import param_partition_specs

    specs = param_partition_specs(
        {"params": state_single.params}, tp_cfg, make_mesh(tp_cfg)
    )
    n_sharded = sum(
        "model" in str(s) for s in jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(str, specs)
        )
    )
    assert n_sharded > 0, "no parameter sharded over 'model' at V=5000"
    log(f"TP shards {n_sharded} param tensors over 'model'")

    log("training dp=2 x tp=4 mesh at V=5000 (same seed, same batches)")
    state_tp = runtime.train(tp_cfg, seed=0)
    tp_losses = _losses(f"{root}/summary_tp")
    log(f"mesh done: {int(state_tp.step)} steps, "
        f"loss {tp_losses[0]:.4f} -> {tp_losses[-1]:.4f}")

    # 4) per-step loss parity.  fp32 CPU, identical data order (the
    # per-process shard view is layout-invariant), dropout off: the only
    # divergence source is collective/matmul reduction order, which Adam
    # amplifies step over step — tolerance covers the measured multihost
    # demo band (tp 1.8e-7 first step) with growth room.
    assert single_losses.shape == tp_losses.shape and len(single_losses) > 0
    rel = np.abs(tp_losses - single_losses) / np.maximum(single_losses, 1e-9)
    log(f"loss parity: max rel diff {rel.max():.3e} over {len(rel)} records")
    # hard gate at the suite's trajectory band (test_parallel_runtime
    # uses rtol 5e-2 over 6 steps); the artifact records the exact value
    assert rel.max() < 5e-2, f"TP trajectory diverged: {rel.max()}"

    # 5) beam=3 decode at V=5000 through the full eval pipeline, both ways
    log("beam=3 eval decode, single-device")
    eval_single = runtime.evaluate(
        cfg("eval", f"summary_dir={root}/summary_single",
            f"eval_result_file={root}/results_single.json",
            "beam_size=3", "mesh_shape=1,1"),
        state=state_single,
    )
    log(f"single-device scores: { {k: round(v, 4) for k, v in eval_single.items()} }")
    log("beam=3 eval decode on the (2,4) mesh")
    eval_tp = runtime.evaluate(
        cfg("eval", f"summary_dir={root}/summary_tp",
            f"eval_result_file={root}/results_tp.json",
            "beam_size=3", "mesh_shape=2,4"),
        state=state_tp,
    )
    log(f"mesh scores: { {k: round(v, 4) for k, v in eval_tp.items()} }")

    payload = {
        "vocab_words": vocab_words,
        "vocabulary_cap_bound": True,
        "num_images": args.num_images,
        "image_size": args.image_size,
        "train_steps": int(state_single.step),
        "loss_single_first_last": [float(single_losses[0]), float(single_losses[-1])],
        "loss_tp_first_last": [float(tp_losses[0]), float(tp_losses[-1])],
        "loss_parity_max_rel": float(rel.max()),
        "mesh_shape": [2, 4],
        "tp_sharded_tensors": n_sharded,
        "scores_single": eval_single,
        "scores_tp": eval_tp,
        "total_seconds": round(time.time() - t0, 1),
    }
    with open(os.path.join(root, "result.json"), "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
