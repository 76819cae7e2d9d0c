"""Synthetic smoke-scale fixtures.

The reference ships no annotation JSONs (only 11 val JPEGs); its de-facto
fast test mode is the max_*_ann_num config caps (SURVEY.md §4).  We go one
step further: generate a fully self-contained COCO-format dataset with
procedurally drawn JPEG images, so end-to-end train/eval tests run with no
network and no external assets.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

# The step that host-side overhead bars are judged against: the train
# cell's device step on the chip, `train_step_device_ms` of
# `vgg16-train-b256` in PERF_LEDGER.jsonl (PR 27: 103.49 / 103.59).  The
# overhead itself is host work, timed in the test's own process.
LEDGER_TRAIN_STEP_MS = 103.5

CAPTIONS = [
    "a man riding a horse on the beach.",
    "a group of people standing around a kitchen.",
    "two dogs playing with a red ball in the grass.",
    "a plate of food with rice and vegetables.",
    "a bus driving down a city street.",
    "a cat sitting on top of a wooden table.",
    "a woman holding an umbrella in the rain.",
    "a young boy throwing a frisbee in the park.",
    "several boats floating in the harbor near the dock.",
    "a train traveling down the tracks near a station.",
    "a bird perched on a branch of a tree.",
    "a pizza with cheese and tomatoes on a plate.",
]


def _write_jpeg(path: str, seed: int, size: int = 64) -> None:
    import cv2

    rng = np.random.default_rng(seed)
    img = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
    # some structure so resize interpolation is non-trivial
    img[:, : size // 2, 0] = 200
    img[size // 2 :, :, 2] = 60
    cv2.imwrite(path, img)


def make_coco_fixture(
    root: str, num_images: int = 12, image_size: int = 64, seed: int = 0
) -> Dict:
    """Create train/val image dirs + caption JSONs under `root`:
    ``num_images`` JPEGs of ``image_size`` px drawn from ``seed``.
    Returns a dict of paths plus a ready Config."""
    from sat_tpu.config import Config

    train_img_dir = os.path.join(root, "train", "images")
    val_img_dir = os.path.join(root, "val", "images")
    os.makedirs(train_img_dir, exist_ok=True)
    os.makedirs(val_img_dir, exist_ok=True)

    images: List[Dict] = []
    annotations: List[Dict] = []
    for i in range(num_images):
        fname = f"COCO_fixture_{i:012d}.jpg"
        images.append({"id": i + 1, "file_name": fname})
        for img_dir in (train_img_dir, val_img_dir):
            _write_jpeg(
                os.path.join(img_dir, fname),
                seed=seed * num_images + i, size=image_size,
            )
        # two captions per image, cycling the pool
        for j in range(2):
            annotations.append(
                {
                    "id": 1000 + 2 * i + j,
                    "image_id": i + 1,
                    "caption": CAPTIONS[(i + j) % len(CAPTIONS)],
                }
            )

    train_json = os.path.join(root, "train", "captions_train.json")
    val_json = os.path.join(root, "val", "captions_val.json")
    payload = {"images": images, "annotations": annotations}
    for p in (train_json, val_json):
        with open(p, "w") as f:
            json.dump(payload, f)

    config = Config(
        batch_size=4,
        vocabulary_size=200,
        max_train_ann_num=None,
        max_eval_ann_num=8,
        num_epochs=1,
        train_image_dir=train_img_dir,
        train_caption_file=train_json,
        eval_image_dir=val_img_dir,
        eval_caption_file=val_json,
        vocabulary_file=os.path.join(root, "vocabulary.csv"),
        temp_annotation_file=os.path.join(root, "train", "anns.csv"),
        temp_data_file=os.path.join(root, "train", "data.npy"),
        eval_result_dir=os.path.join(root, "val", "results"),
        eval_result_file=os.path.join(root, "val", "results.json"),
        test_image_dir=val_img_dir,
        test_result_dir=os.path.join(root, "test_results"),
        test_result_file=os.path.join(root, "test_results.csv"),
        save_dir=os.path.join(root, "models"),
        summary_dir=os.path.join(root, "summary"),
        save_eval_result_as_image=False,
    )
    return {
        "root": root,
        "train_json": train_json,
        "val_json": val_json,
        "train_img_dir": train_img_dir,
        "val_img_dir": val_img_dir,
        "config": config,
    }


def plain_moe_ffn(p, config, x, sum_eps):
    """The serial expert layer with EVERY expert held, written out plainly
    (PR 41's lines of ``lm_common.moe_ffn``): the reference the one expert
    layer is held against, to the bit off the chip.  x [T, H] -> (x + the
    experts' weighted sum (+ the shared SwiGLU), tokens per expert [E],
    experts chosen [T, k]).  ``p["feed_forward"]`` holds all ``num_experts``
    maps, whatever ``config.experts_held`` says.  jax and the program are
    imported here: this module is also the jax-free tests'."""
    import jax.numpy as jnp

    from sat_tpu.models import lm_common

    c = config
    T, H = x.shape
    k, E = c.num_experts_per_tok, c.num_experts
    h = lm_common.rms_norm(x, p["ffn_norm"], c.norm_eps).astype(jnp.bfloat16)
    experts, weights = lm_common.route(p["feed_forward"], c, h, sum_eps)
    f = p["feed_forward"]
    flat = experts.reshape(T * k)
    order = jnp.argsort(flat, stable=True)
    rows = h[order // k]
    sizes = jnp.sum(flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
    hidden = lm_common.swiglu(
        lm_common.grouped_matmul(rows, f["w1"], sizes), lm_common.grouped_matmul(rows, f["w3"], sizes))
    out = lm_common.grouped_matmul(hidden, f["w2"], sizes)
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(jnp.arange(T * k, dtype=jnp.int32))
    picked = out[back].reshape(T, k, H).astype(jnp.float32)
    y = jnp.sum(picked * weights[..., None], axis=1)
    if "shared" in f:
        s = f["shared"]
        y = y + lm_common.mm(lm_common.swiglu(lm_common.mm(h, s["w1"]), lm_common.mm(h, s["w3"])),
                             s["w2"]).astype(jnp.float32)
    return x + y.astype(x.dtype), sizes, experts
