"""Continue a finished quality_run from its final checkpoint.

The overfit-protocol runs save one checkpoint at end of training
(runtime.train's final ckpt_save); this script restores it, extends
num_epochs, trains the additional steps, re-evaluates (beam=3 and
optionally greedy), and rewrites scores.json — so a run that ended
short of saturation continues instead of being repaid from scratch
(the 1-core box prices a 1600-step rich run at ~100 min).

Usage:
  python scripts/continue_quality_run.py --out runs/quality_rich_joint \
      --corpus rich [--extra-epochs 39] [--beam-compare] [...]
Flags mirror the original quality_run invocation where relevant; the
config is rebuilt the same way, only num_epochs grows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--corpus", default="rich", choices=["basic", "rich"])
    ap.add_argument("--extra-epochs", type=int, default=39)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--frozen-cnn", action="store_true")
    ap.add_argument("--beam-compare", action="store_true")
    ap.add_argument("--cnn", default="vgg16")
    ap.add_argument("--extra-set", action="append", default=[])
    args = ap.parse_args()

    t0 = time.time()

    def log(msg: str) -> None:
        print(f"[cont +{time.time()-t0:6.1f}s] {msg}", flush=True)

    import jax

    from sat_tpu.utils.compile_cache import enable as _enable_cache

    _enable_cache(jax)

    from sat_tpu.cli import build_config
    from sat_tpu.train.checkpoint import latest_checkpoint, restore_checkpoint
    from sat_tpu.train.step import create_train_state
    from sat_tpu import runtime

    root = os.path.abspath(args.out)
    img_dir = os.path.join(root, "images")
    caption_file = os.path.join(root, "captions.json")
    assert os.path.isdir(img_dir), f"no corpus at {root} — run quality_run first"

    overrides = [
        f"train_image_dir={img_dir}",
        f"train_caption_file={caption_file}",
        f"eval_image_dir={img_dir}",
        f"eval_caption_file={caption_file}",
        f"vocabulary_file={root}/vocabulary_{args.corpus}.csv",
        f"temp_annotation_file={root}/anns_{args.corpus}.csv",
        f"temp_data_file={root}/data_{args.corpus}.npy",
        f"save_dir={root}/models",
        f"summary_dir={root}/summary",
        f"eval_result_dir={root}/results",
        f"eval_result_file={root}/results.json",
        "max_train_ann_num=none",
        "max_eval_ann_num=none",
        f"batch_size={args.batch_size}",
        "vocabulary_size=5000" if args.corpus == "rich" else "vocabulary_size=200",
        "fc_drop_rate=0.1",
        "lstm_drop_rate=0.1",
        "initial_learning_rate=0.0003",
        "save_period=0",
        "log_every=10",
        f"image_size={args.image_size}",
        f"cnn={args.cnn}",
    ] + args.extra_set
    set_args = [x for o in overrides for x in ("--set", o)]
    train_flags = [] if args.frozen_cnn else ["--train_cnn"]

    ckpt = latest_checkpoint(os.path.join(root, "models"))
    assert ckpt, f"no checkpoint under {root}/models"
    log(f"restoring {ckpt}")

    config, _ = build_config(["--phase=train"] + train_flags + set_args)
    state = create_train_state(jax.random.PRNGKey(0), config)
    state, n = restore_checkpoint(state, model_file=ckpt)
    assert n > 0, "restore matched no tensors"
    start_step = int(state.step)

    # steps/epoch from the cached dataset size; extend num_epochs so the
    # loop runs --extra-epochs past wherever the checkpoint stopped
    from sat_tpu.data.dataset import prepare_train_data

    dataset = prepare_train_data(config)
    steps_per_epoch = dataset.num_batches
    done_epochs = start_step // steps_per_epoch
    config = config.replace(num_epochs=done_epochs + args.extra_epochs)
    log(f"continuing from step {start_step} (epoch {done_epochs}) for "
        f"{args.extra_epochs} more epochs x {steps_per_epoch} steps")

    state = runtime.train(config, state=state, dataset=dataset)
    log(f"training done at step {int(state.step)}")

    eval_config, _ = build_config(["--phase=eval", "--beam_size=3"] + set_args)
    scores = runtime.evaluate(eval_config, state=state)
    log(f"beam=3 scores: { {k: round(v, 4) for k, v in scores.items()} }")

    greedy_scores = None
    if args.beam_compare:
        greedy_config, _ = build_config(["--phase=eval", "--beam_size=1"] + set_args)
        greedy_config = greedy_config.replace(
            eval_result_file=f"{root}/results_greedy.json"
        )
        greedy_scores = runtime.evaluate(greedy_config, state=state)
        log(f"greedy scores: { {k: round(v, 4) for k, v in greedy_scores.items()} }")

    # merge into the original quality_run payload: its provenance fields
    # (corpus, protocol, train_cnn, vocab_words, length histogram) must
    # survive the continuation — RESULTS.md comparisons key on them
    scores_path = os.path.join(root, "scores.json")
    payload = {}
    try:
        with open(scores_path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        pass
    payload.update(
        scores=scores,
        greedy_scores=greedy_scores,
        steps=int(state.step),
        continued_from_step=start_step,
        continuation_seconds=round(time.time() - t0, 1),
    )
    with open(scores_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
