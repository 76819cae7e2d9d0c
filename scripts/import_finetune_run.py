"""Import-then-finetune evidence run (VERDICT r2 §next-round #4).

Proves the reference-checkpoint migration path END TO END, not just by
leaf-placement counts: train phase A on the fixture corpus, export its
state into the reference's flat TF1 ``{var.name: value}`` npy layout
(base_model.py:242-249) via export_reference_checkpoint, import that
file into a freshly-initialized model with import_reference_checkpoint,
and show that

* the imported model's starting loss equals phase A's final loss (the
  weights survived the round trip through the foreign layout — a silent
  gate-order or orientation mismatch would send it back to scratch), and
* finetuning continues DOWN from there, beating phase A's final loss.

A from-scratch control trained for the same phase-B budget quantifies
the head start.  Results land in RESULTS.md's ``import-finetune``
section (marker-delimited; quality_run.py owns the main body).

Usage: python scripts/import_finetune_run.py [--cpu] [--steps-a N]
       [--steps-b N] [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from quality_run import make_corpus, read_loss_curve, update_results_sections


def mean_first_losses(metrics_path: str, n: int = 5):
    curve = read_loss_curve(metrics_path, samples=10**9)
    return float(np.mean([loss for _, loss in curve[:n]])), curve


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps-a", type=int, default=300, help="phase-A steps")
    ap.add_argument("--steps-b", type=int, default=150, help="finetune steps")
    ap.add_argument("--num-images", type=int, default=48)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="runs/import_finetune")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    t0 = time.time()
    root = os.path.abspath(args.out)
    os.makedirs(root, exist_ok=True)
    img_dir, caption_file = make_corpus(
        root, num_images=args.num_images, image_edge=args.image_size
    )

    import jax

    from sat_tpu import runtime
    from sat_tpu.cli import build_config
    from sat_tpu.train.checkpoint import (
        export_reference_checkpoint,
        import_reference_checkpoint,
    )
    from sat_tpu.train.step import create_train_state

    from sat_tpu.utils.compile_cache import enable as _enable_cache

    _enable_cache(jax)

    steps_per_epoch = -(-2 * args.num_images // args.batch_size)

    def cfg(tag: str, steps: int):
        overrides = [
            f"train_image_dir={img_dir}",
            f"train_caption_file={caption_file}",
            f"vocabulary_file={root}/vocabulary.csv",
            f"temp_annotation_file={root}/anns.csv",
            f"temp_data_file={root}/data.npy",
            f"save_dir={root}/models_{tag}",
            f"summary_dir={root}/summary_{tag}",
            "max_train_ann_num=none",
            f"batch_size={args.batch_size}",
            f"num_epochs={-(-steps // steps_per_epoch)}",
            "vocabulary_size=200",
            "fc_drop_rate=0.1",
            "lstm_drop_rate=0.1",
            "initial_learning_rate=0.0003",
            "save_period=0",
            "log_every=5",
            f"image_size={args.image_size}",
        ]
        set_args = [x for o in overrides for x in ("--set", o)]
        config, _ = build_config(["--phase=train", "--train_cnn"] + set_args)
        return config

    device = jax.devices()[0]
    print(f"[import-ft +{time.time()-t0:5.1f}s] device: {device.device_kind}")

    # ---- phase A: train the donor model -------------------------------
    cfg_a = cfg("a", args.steps_a)
    state_a = runtime.train(cfg_a)
    curve_a = read_loss_curve(f"{root}/summary_a/metrics.jsonl", samples=10**9)
    final_a = float(np.mean([l for _, l in curve_a[-3:]]))
    print(f"[import-ft +{time.time()-t0:5.1f}s] phase A done: "
          f"step {int(state_a.step)}, final loss ~{final_a:.3f}")

    # ---- export to the reference's flat layout ------------------------
    ref_path = f"{root}/reference_layout.npy"
    n_exported = export_reference_checkpoint(state_a, ref_path)
    print(f"[import-ft +{time.time()-t0:5.1f}s] exported {n_exported} tensors "
          f"in reference layout -> {ref_path}")

    # ---- import into a FRESH model and finetune -----------------------
    cfg_b = cfg("b", args.steps_b)
    fresh = create_train_state(jax.random.PRNGKey(123), cfg_b)
    imported, n_loaded = import_reference_checkpoint(fresh, ref_path)
    print(f"[import-ft +{time.time()-t0:5.1f}s] imported {n_loaded} tensors")

    state_b = runtime.train(cfg_b, state=imported)
    first_b, curve_b = mean_first_losses(f"{root}/summary_b/metrics.jsonl")
    final_b = float(np.mean([l for _, l in curve_b[-3:]]))

    # ---- from-scratch control over the same phase-B budget ------------
    cfg_c = cfg("c", args.steps_b)
    runtime.train(cfg_c)
    first_c, curve_c = mean_first_losses(f"{root}/summary_c/metrics.jsonl")
    final_c = float(np.mean([l for _, l in curve_c[-3:]]))

    verdicts = {
        # imported start ~ phase-A end: the weights survived the layout
        # round trip (gate order, kernel orientation, name translation)
        "import_resumes_phase_a": first_b < final_a + 0.5,
        # ...and is far below a cold start
        "import_beats_scratch_start": first_b < 0.6 * first_c,
        # finetuning continues DOWN from the imported point
        "finetune_improves": final_b < first_b,
        "finetune_beats_scratch": final_b < final_c,
    }
    summary = {
        "device": device.device_kind,
        "steps_a": int(args.steps_a),
        "steps_b": int(args.steps_b),
        "tensors_exported": n_exported,
        "tensors_imported": n_loaded,
        "phase_a_final_loss": round(final_a, 4),
        "imported_start_loss": round(first_b, 4),
        "finetuned_final_loss": round(final_b, 4),
        "scratch_start_loss": round(first_c, 4),
        "scratch_final_loss": round(final_c, 4),
        "verdicts": verdicts,
        "total_seconds": round(time.time() - t0, 1),
    }
    with open(f"{root}/summary.json", "w") as f:
        json.dump(summary, f, indent=2)

    ok = all(verdicts.values())
    section = "\n".join([
        "## Import-then-finetune: the reference-checkpoint migration path, end to end",
        "",
        f"Produced by `python scripts/import_finetune_run.py "
        f"{' '.join(sys.argv[1:])}`".rstrip() + f" on **{device.device_kind}**.",
        "",
        "A donor model trained on the fixture corpus is **exported into the "
        "reference's flat TF1 checkpoint layout** "
        "(`export_reference_checkpoint`, the inverse of the importer; "
        "`/root/reference/base_model.py:242-255` format), then **imported "
        "into a freshly-initialized model** with "
        "`import_reference_checkpoint` and finetuned. If any of the "
        "TF1-compatibility details were silently wrong — (i,j,f,o) LSTM "
        "gate order, concatenated kernel, HWIO conv orientation, scope "
        "name translation — the imported model would start back at the "
        "from-scratch loss. It does not:",
        "",
        "| Quantity | Loss |",
        "|---|---|",
        f"| phase-A donor, final | {final_a:.3f} |",
        f"| **imported** model, first steps | **{first_b:.3f}** |",
        f"| from-scratch control, first steps | {first_c:.3f} |",
        f"| imported + {args.steps_b} finetune steps | {final_b:.3f} |",
        f"| from-scratch control after {args.steps_b} steps | {final_c:.3f} |",
        "",
        f"{n_exported} tensors exported / {n_loaded} imported (decoder + CNN; "
        "optimizer slots correctly dropped). Checks: "
        + ", ".join(f"{k}={'PASS' if v else 'FAIL'}" for k, v in verdicts.items())
        + f". Artifacts: `{args.out}/summary.json`.",
    ])
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    update_results_sections(
        os.path.join(repo_root, "RESULTS.md"),
        section="import-finetune",
        section_text=section,
    )
    print(f"[import-ft +{time.time()-t0:5.1f}s] RESULTS.md section written; "
          f"all checks {'PASS' if ok else 'FAIL'}")
    for k, v in verdicts.items():
        print(f"  {k}: {'PASS' if v else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
