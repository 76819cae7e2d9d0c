"""End-to-end quality-evidence run: train -> checkpoint -> beam-search eval
-> BLEU/METEOR/ROUGE/CIDEr, on a self-contained fixture corpus.

The reference's north-star is BLEU-4 = 29.5 on COCO val2014
(/root/reference/README.md:85-89).  This environment has no network access,
so COCO itself cannot be fetched; this script instead runs the *entire*
pipeline (data prep -> vocab build -> prefetch-fed jitted training ->
checkpoint save/restore -> on-device beam search -> PTB tokenize -> four
scorers) on a procedurally generated caption corpus where each image has a
distinct, learnable caption.  A model that actually learns drives BLEU-4
from ~0 to near-saturation; a broken pipeline stays at 0.  Results land in
RESULTS.md at the repo root.

Usage:  python scripts/quality_run.py  [--steps N] [--out DIR]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COLORS = ["red", "blue", "green", "yellow", "black", "white", "brown", "orange"]
ANIMALS = ["cat", "dog", "horse", "bird", "rabbit", "sheep"]
PLACES = ["park", "beach", "kitchen", "street", "garden", "field", "harbor", "station"]

# rich-corpus pools (VERDICT r2 §next-round #4: 1000+ word vocabulary,
# full caption-length distribution)
ADJS = [
    "big", "small", "tiny", "huge", "fluffy", "sleepy", "playful", "spotted",
    "striped", "muddy", "shiny", "elderly", "young", "swift", "sluggish",
    "quiet", "noisy", "gentle", "curious", "clever", "lazy", "hungry",
    "cheerful", "grumpy",
]
VERBS = [
    "sitting", "standing", "sprinting", "sleeping", "playing", "eating",
    "drinking", "jumping", "strolling", "resting", "hiding", "waiting",
    "watching", "climbing", "digging", "paddling",
]
WEATHER = ["sunny", "rainy", "cloudy", "windy", "foggy", "snowy", "stormy", "hazy"]
TIMES = ["morning", "afternoon", "evening", "midday"]

# pronounceable fake words, deterministic and collision-free: base-70
# syllable triples.  Each rich-corpus image carries THREE unique tokens
# (a name, a toy, a landmark) so vocabulary grows 3/image past the
# ~60-word common pools — 336 images -> 1000+ distinct words.
_SYLLABLES = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]


def _fake_word(i: int) -> str:
    a, rest = i % 70, i // 70
    b, c = rest % 70, rest // 70
    return _SYLLABLES[c % 70] + _SYLLABLES[b] + _SYLLABLES[a]


def make_rich_corpus(root: str, num_images: int = 336, image_edge: int = 64):
    """Few-hundred-image corpus with a 1000+ word vocabulary and the full
    caption-length distribution up to the 20-token cap.

    Per image: a unique (color, animal, place) scene like make_corpus plus
    three unique fake-word tokens, and TWO reference captions whose length
    band cycles short (7 tokens) / medium (12) / long (19) / max (20)
    so masking, the scan decoder, and scoring see every length.  Every
    41st image carries a third, 29-token caption that filter_by_cap_len
    must drop (reference coco.py:323-339).  Images get a distinctive
    color block + a unique per-image texture so the mapping is learnable
    by memorization."""
    import cv2

    img_dir = os.path.join(root, "images")
    os.makedirs(img_dir, exist_ok=True)
    rng = np.random.default_rng(1)

    images, annotations = [], []
    lengths = []
    ann_id = itertools.count(1000)
    for i in range(num_images):
        fname = f"rich_{i:06d}.jpg"
        img = rng.integers(0, 90, (image_edge, image_edge, 3), dtype=np.uint8)
        hue = np.zeros(3, dtype=np.uint8)
        hue[i % 3] = 120 + (i * 7) % 130
        img[: image_edge // 2, :, :] = hue
        img[image_edge // 2:, : image_edge // 2, (i // 3) % 3] = 210
        cv2.imwrite(os.path.join(img_dir, fname), img)
        images.append({"id": i + 1, "file_name": fname})

        color = COLORS[i % len(COLORS)]
        animal = ANIMALS[(i // 3) % len(ANIMALS)]
        place = PLACES[(i // 7) % len(PLACES)]
        adj = ADJS[(i // 2) % len(ADJS)]
        verb = VERBS[(i // 5) % len(VERBS)]
        weather = WEATHER[(i // 11) % len(WEATHER)]
        daytime = TIMES[(i // 13) % len(TIMES)]
        name, toy, mark = _fake_word(3 * i), _fake_word(3 * i + 1), _fake_word(3 * i + 2)

        # Every image's caption pair must surface all three unique tokens
        # (name + toy + mark) or the vocabulary undershoots 1000 words.
        band = i % 4
        if band == 0:      # short: 7 tokens incl. '.'
            caps = [
                f"{name} the {color} {animal} is {verb}.",
                f"{name} has the {toy} and {mark}.",
            ]
        elif band == 1:    # medium: 12 tokens
            caps = [
                f"the {adj} {color} {animal} named {name} is {verb} in the {place}.",
                f"a {adj} {color} {animal} named {name} guards the {toy} and {mark}.",
            ]
        elif band == 2:    # long: 19 tokens
            caps = [
                f"on a {weather} {daytime} the {adj} {color} {animal} named "
                f"{name} is {verb} near the {place} with a {toy}.",
                f"on one {weather} {daytime} a {adj} {color} {animal} named "
                f"{name} was {verb} near the {place} with the {mark}.",
            ]
        else:              # max: exactly 20 tokens
            caps = [
                f"on a {weather} {daytime} the {adj} {color} {animal} named "
                f"{name} is {verb} by the old {mark} near the {place}.",
                f"on a {weather} {daytime} a {adj} {color} {animal} named "
                f"{name} was {verb} by the old {toy} near the {place}.",
            ]
        if i % 41 == 0:    # over-cap caption: filter_by_cap_len must drop it
            caps.append(
                f"this is a deliberately very long extra caption about the {adj} "
                f"{color} {animal} named {name} that keeps {verb} near the "
                f"{place} with a {toy} by the {mark} today."
            )
        for cap in caps:
            lengths.append(len(cap.replace(".", " .").split()))
            annotations.append(
                {"id": next(ann_id), "image_id": i + 1, "caption": cap}
            )

    caption_file = os.path.join(root, "captions.json")
    with open(caption_file, "w") as f:
        json.dump({"images": images, "annotations": annotations}, f)
    return img_dir, caption_file, lengths


def make_corpus(root: str, num_images: int = 48, image_edge: int = 96):
    """Procedural COCO-format corpus: image i shows a color-coded pattern and
    carries two reference captions with identical content words (the learnable
    target) and one function-word variation (so scoring vs 2 refs is
    non-degenerate, like real COCO)."""
    import cv2

    img_dir = os.path.join(root, "images")
    os.makedirs(img_dir, exist_ok=True)
    combos = list(itertools.product(range(len(COLORS)), range(len(ANIMALS)), range(len(PLACES))))
    if num_images > len(combos):
        raise SystemExit(
            f"--num-images must be <= {len(combos)} (distinct caption combos)"
        )
    rng = np.random.default_rng(0)
    rng.shuffle(combos)

    images, annotations = [], []
    for i in range(num_images):
        ci, ai, pi = combos[i]
        fname = f"fixture_{i:06d}.jpg"
        # visually distinctive image: color block keyed to the caption's
        # color word + unique per-image texture, so the mapping is learnable
        img = rng.integers(0, 80, (image_edge, image_edge, 3), dtype=np.uint8)
        hue = np.zeros(3, dtype=np.uint8)
        hue[ci % 3] = 250 - 20 * (ci // 3)
        img[: image_edge // 2, :, :] = hue
        img[image_edge // 2 :, : image_edge // 2, (ai % 3)] = 200
        img[image_edge // 2 :, image_edge // 2 :, (pi % 3)] = 120
        cv2.imwrite(os.path.join(img_dir, fname), img)
        images.append({"id": i + 1, "file_name": fname})
        color, animal, place = COLORS[ci], ANIMALS[ai], PLACES[pi]
        caps = [
            f"a {color} {animal} in the {place}.",
            f"the {color} {animal} is in the {place}.",
        ]
        for j, cap in enumerate(caps):
            annotations.append({"id": 1000 + 2 * i + j, "image_id": i + 1, "caption": cap})

    caption_file = os.path.join(root, "captions.json")
    with open(caption_file, "w") as f:
        json.dump({"images": images, "annotations": annotations}, f)
    return img_dir, caption_file


def read_loss_curve(metrics_path: str, samples: int = 12):
    """(step, total_loss) rows of the FINAL run in a metrics.jsonl,
    downsampled to ~``samples`` rows (last row always kept).  A step that
    does not increase marks the start of a newer run appended to the same
    --out dir; earlier segments are discarded."""
    curve = []
    with open(metrics_path) as f:
        for line in f:
            rec = json.loads(line)
            if "total_loss" in rec:
                if curve and rec["step"] <= curve[-1][0]:
                    curve = []
                curve.append((rec["step"], rec["total_loss"]))
    sampled = curve[:: max(1, len(curve) // samples)]
    if curve and sampled[-1][0] != curve[-1][0]:
        sampled.append(curve[-1])
    return sampled


def update_results_sections(md_path: str, main_text: str = None,
                            section: str = None, section_text: str = None) -> None:
    """RESULTS.md is assembled from a main body plus marker-delimited
    sections (``<!-- section:NAME -->…<!-- /section:NAME -->``) owned by
    other evidence scripts (import-finetune).  Rewriting the main body
    preserves existing sections; a section writer replaces just its own."""
    import re

    old = ""
    if os.path.exists(md_path):
        with open(md_path) as f:
            old = f.read()
    pat = re.compile(r"<!-- section:(\S+) -->\n.*?<!-- /section:\1 -->", re.S)
    sections = {m.group(1): m.group(0) for m in pat.finditer(old)}
    body = main_text if main_text is not None else pat.sub("", old).rstrip() + "\n"
    if section is not None:
        sections[section] = (
            f"<!-- section:{section} -->\n{section_text.rstrip()}\n"
            f"<!-- /section:{section} -->"
        )
    parts = [body.rstrip()] + [sections[k] for k in sorted(sections)]
    with open(md_path, "w") as f:
        f.write("\n\n".join(parts) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600, help="target train steps")
    ap.add_argument("--out", default="runs/quality")
    ap.add_argument(
        "--corpus", default="basic", choices=["basic", "rich"],
        help="rich = few-hundred images, 1000+ word vocab, caption lengths "
        "7-20 plus over-cap captions the length filter must drop",
    )
    ap.add_argument("--num-images", type=int, default=None,
                    help="default 48 (basic) / 336 (rich)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument(
        "--frozen-cnn", action="store_true",
        help="reference-published configuration (RNN-only training); "
        "default trains the CNN jointly",
    )
    ap.add_argument(
        "--beam-compare", action="store_true",
        help="also decode greedily (beam=1) and record the beam-3 deltas",
    )
    ap.add_argument(
        "--corpus-only", action="store_true",
        help="generate the fixture corpus under --out and exit (for runs "
        "that only need the inputs, e.g. the profiler stage)",
    )
    ap.add_argument(
        "--image-size", type=int, default=224,
        help="input edge; 224 = flagship, smaller for CPU runs",
    )
    ap.add_argument(
        "--cpu", action="store_true",
        help="pin the CPU backend",
    )
    ap.add_argument(
        "--cnn", default="vgg16", choices=["vgg16", "resnet50"],
        help="encoder family (resnet50 exercises the BN/bottleneck path)",
    )
    ap.add_argument(
        "--no-results-md", action="store_true",
        help="write scores.json only; leave RESULTS.md untouched (for "
        "secondary-evidence runs, e.g. the resnet50 variant)",
    )
    ap.add_argument(
        "--extra-set", action="append", default=[], metavar="KEY=VALUE",
        help="extra Config overrides appended AFTER the protocol defaults "
        "(e.g. fc_drop_rate=0.0 for a saturation run — memorization-"
        "protocol dropout caps teacher-forced accuracy)",
    )
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    t0 = time.time()
    root = os.path.abspath(args.out)
    os.makedirs(root, exist_ok=True)
    if args.num_images is None:
        args.num_images = 336 if args.corpus == "rich" else 48
    cap_lengths = None
    if args.corpus == "rich":
        img_dir, caption_file, cap_lengths = make_rich_corpus(
            root, num_images=args.num_images
        )
    else:
        img_dir, caption_file = make_corpus(root, num_images=args.num_images)
    print(f"[quality +{time.time()-t0:5.1f}s] corpus: {args.num_images} images at {img_dir}")
    if args.corpus_only:
        return 0

    from sat_tpu.cli import build_config

    steps_per_epoch = -(-2 * args.num_images // args.batch_size)  # 2 captions/image
    num_epochs = -(-args.steps // steps_per_epoch)
    overrides = [
        f"train_image_dir={img_dir}",
        f"train_caption_file={caption_file}",
        f"eval_image_dir={img_dir}",
        f"eval_caption_file={caption_file}",
        # corpus-keyed cache/vocab names: a rerun with a different
        # --corpus into the same --out must not silently train on the
        # previous corpus's cached anns/data/vocab
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
        f"num_epochs={num_epochs}",
        # rich corpus: top-5000 cap like the reference's published config;
        # the corpus itself supplies 1000+ distinct words
        "vocabulary_size=5000" if args.corpus == "rich" else "vocabulary_size=200",
        # overfit protocol: mild dropout + slightly hotter Adam so ~600
        # steps saturate; documented in RESULTS.md
        "fc_drop_rate=0.1",
        "lstm_drop_rate=0.1",
        "initial_learning_rate=0.0003",
        "save_period=0",
        "log_every=10",
        f"image_size={args.image_size}",
        f"cnn={args.cnn}",
    ]
    overrides += args.extra_set    # caller overrides win (later --set)
    set_args = [x for o in overrides for x in ("--set", o)]

    train_flags = [] if args.frozen_cnn else ["--train_cnn"]
    config, _ = build_config(["--phase=train"] + train_flags + set_args)

    import jax

    from sat_tpu import runtime

    # Persistent compilation cache (the CLI's directory): the resnet50
    # CPU-XLA compile in particular runs tens of minutes cold on this
    # 1-core host; a rerun must not pay it twice.
    from sat_tpu.utils.compile_cache import enable as _enable_cache

    _enable_cache(jax)

    device = jax.devices()[0]
    print(f"[quality +{time.time()-t0:5.1f}s] device: {device.device_kind} ({device.platform})")
    print(f"[quality +{time.time()-t0:5.1f}s] training {num_epochs} epochs x {steps_per_epoch} steps")
    state = runtime.train(config)
    train_s = time.time() - t0
    print(f"[quality +{train_s:5.1f}s] training done at step {int(state.step)}")

    eval_config, _ = build_config(["--phase=eval", "--beam_size=3"] + set_args)
    scores = runtime.evaluate(eval_config, state=state)

    greedy_scores = None
    if args.beam_compare:
        greedy_config, _ = build_config(
            ["--phase=eval", "--beam_size=1"] + set_args
        )
        greedy_config = greedy_config.replace(
            eval_result_file=f"{root}/results_greedy.json"
        )
        greedy_scores = runtime.evaluate(greedy_config, state=state)
    total_s = time.time() - t0

    sampled = read_loss_curve(os.path.join(root, "summary", "metrics.jsonl"))

    vocab_words = None
    try:
        with open(f"{root}/vocabulary_{args.corpus}.csv") as f:
            vocab_words = sum(1 for _ in f) - 1      # header row
    except OSError:
        pass

    payload = {
        "scores": scores,
        "steps": int(state.step),
        "device": device.device_kind,
        "train_seconds": round(train_s, 1),
        "total_seconds": round(total_s, 1),
        "num_images": args.num_images,
        "corpus": args.corpus,
        "train_cnn": not args.frozen_cnn,
        "vocab_words": vocab_words,
        "protocol": "overfit-fixture",
    }
    if greedy_scores is not None:
        payload["greedy_scores"] = greedy_scores
    if cap_lengths is not None:
        hist = {}
        for n in cap_lengths:
            hist[n] = hist.get(n, 0) + 1
        payload["caption_token_length_histogram"] = {
            str(k): hist[k] for k in sorted(hist)
        }
    with open(os.path.join(root, "scores.json"), "w") as f:
        json.dump(payload, f, indent=2)

    argv = " ".join(sys.argv[1:])
    lines = [
        "# RESULTS — quality evidence (fixture-scale end-to-end run)",
        "",
        f"Produced by `python scripts/quality_run.py {argv}`".rstrip() + " "
        f"on **{device.device_kind}** ({device.platform}); total wall-clock "
        f"{total_s:.0f}s (train {train_s:.0f}s for {int(state.step)} steps "
        "including compiles, the rest is eval-side beam search + scoring).",
        "",
    ]
    if device.platform != "tpu":
        lines += [
            "*Backend note:* this run used a non-TPU backend. The pipeline "
            "under test is identical on every backend: same jitted "
            "programs, same on-device beam search.",
            "",
        ]
    cnn_mode = (
        "frozen randomly-initialized CNN — RNN-only training like the "
        "reference's published mode, though without its pretrained VGG16 "
        "weights (unavailable offline)"
        if args.frozen_cnn else "`--train_cnn`"
    )
    corpus_desc = (
        f"self-contained {args.num_images}-image corpus with a "
        f"**{vocab_words}-word built vocabulary**, caption lengths spanning "
        "7-20 tokens (plus over-cap captions the length filter drops)"
        if args.corpus == "rich"
        else f"self-contained {args.num_images}-image corpus"
    )
    lines += [
        "**Protocol.** This environment has no network egress, so COCO val2014 "
        "(the reference's BLEU-4 = 29.5 benchmark, `/root/reference/README.md:85-89`) "
        "cannot be fetched. Instead this run drives the complete pipeline — COCO-format "
        "ingestion, vocabulary build, prefetch-fed jitted training of the full "
        f"{args.cnn}+attention-LSTM model ({cnn_mode}), checkpointing, on-device batched "
        "beam search (beam=3), PTB tokenization, and all four scorers — on a "
        f"{corpus_desc} where every image carries a "
        "distinct learnable caption (content words correlated with image pixels). "
        "The memorization protocol turns caption quality into a pipeline-integrity "
        "test: a model that learns saturates BLEU; any break in the chain "
        "(preprocessing, attention, decoding, tokenization, scoring) keeps it near 0.",
        "",
        "## Scores (beam_size=3, eval over all corpus images)",
        "",
        "| Metric | Score |" if greedy_scores is None
        else "| Metric | beam=3 | greedy (beam=1) | Δ |",
        "|---|---|" if greedy_scores is None else "|---|---|---|---|",
    ]
    for k, v in scores.items():
        if greedy_scores is None:
            lines.append(f"| {k} | {v:.4f} |")
        else:
            g = greedy_scores.get(k, float("nan"))
            lines.append(f"| {k} | {v:.4f} | {g:.4f} | {v - g:+.4f} |")
    lines += [
        "",
        f"Raw artifacts: `{args.out}/scores.json`, `{args.out}/results.json` "
        "(per-image captions).",
        "",
    ]
    if cap_lengths is not None:
        bands = {"7 (short)": 0, "12 (medium)": 0, "19 (long)": 0,
                 "20 (max)": 0, ">20 (filtered)": 0}
        for n in cap_lengths:
            if n > 20: bands[">20 (filtered)"] += 1
            elif n >= 20: bands["20 (max)"] += 1
            elif n >= 15: bands["19 (long)"] += 1
            elif n >= 10: bands["12 (medium)"] += 1
            else: bands["7 (short)"] += 1
        lines += [
            "## Caption length distribution (tokens incl. terminator)",
            "",
            "| Band | Captions |",
            "|---|---|",
        ] + [f"| {k} | {v} |" for k, v in bands.items()] + [""]
    lines += [
        "## Training loss curve (total_loss from metrics.jsonl)",
        "",
        "| Step | Total loss |",
        "|---|---|",
    ]
    for step, loss in sampled:
        lines.append(f"| {step} | {loss:.3f} |")
    vocab_note = "vocabulary_size=5000 (top-5000 cap)" if args.corpus == "rich" \
        else "`vocabulary_size=200`"
    lines += [
        "",
        "## Config deltas vs flagship defaults",
        "",
        f"{'frozen randomly-initialized CNN (RNN-only training)' if args.frozen_cnn else '`--train_cnn`'}, "
        f"`batch_size={args.batch_size}`, {vocab_note}, "
        "`fc_drop_rate=0.1`, `lstm_drop_rate=0.1`, `initial_learning_rate=3e-4` "
        f"(overfit protocol), `num_epochs={num_epochs}`, "
        f"`image_size={args.image_size}`. Everything else — {args.cnn} "
        "encoder, 512-unit attention LSTM, Adam, global-norm clip 5.0, "
        "doubly-stochastic attention penalty — is the reference-published "
        "configuration (`/root/reference/config.py:8-43`).",
        "",
    ]
    if args.no_results_md:
        print(f"[quality +{time.time()-t0:5.1f}s] scores.json written "
              "(--no-results-md)")
    else:
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        md = os.path.join(repo_root, "RESULTS.md")
        if args.corpus == "rich":
            # the rich run is supplementary evidence: it owns its marked
            # section and must not replace the flagship main body
            update_results_sections(
                md, section="rich-corpus",
                section_text="\n".join(lines[1:]),  # drop the H1
            )
        else:
            update_results_sections(md, main_text="\n".join(lines))
        print(f"[quality +{time.time()-t0:5.1f}s] RESULTS.md written")
    for k, v in scores.items():
        print(f"  {k}: {v:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
