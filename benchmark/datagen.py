"""Generated inputs: a vocabulary, captions, JPEG images and COCO-format
annotation files, all from the seed.  No jax, nothing of the program.

Images are 28x28 seeded noise enlarged to the target size (cubic) plus a
little per-pixel noise: enough structure for a JPEG of ~15-25 KB that no
two requests share, and every pixel of every image differs.
"""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence

import numpy as np

_CONSONANTS = "bdfghjklmnprstvz"
_VOWELS = "aeiou"


def words(vocabulary_size: int) -> List[str]:
    """['<start>', '.', then consonant-vowel words], in index order.  The
    words are tokenizer-stable (lower-case letters only) and the list does
    not depend on the seed: index i is the same word in every run."""
    syll = [c + v for c in _CONSONANTS for v in _VOWELS]          # 80
    pool = [a + b for a in syll for b in syll]                     # 6400
    if vocabulary_size - 2 > len(pool):
        raise ValueError(f"vocabulary_size {vocabulary_size} exceeds the word pool")
    return ["<start>", "."] + pool[: vocabulary_size - 2]


def write_vocabulary(path: str, vocabulary_size: int) -> List[str]:
    """The program's vocabulary.csv (pandas CSV: word, index, frequency)."""
    import pandas as pd

    w = words(vocabulary_size)
    freq = np.log(np.full(len(w), 1.0 / len(w)))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    pd.DataFrame({"word": w, "index": list(range(len(w))), "frequency": freq - freq.max()}).to_csv(path)
    return w


def detokenize(tokens: Sequence[int], vocab: List[str]) -> str:
    """What the program prints for a row of token ids (source
    vocabulary.py get_sentence): words up to the first '.', index 0 and
    out-of-range ids dropped, ' w1 w2.'"""
    out = []
    for t in tokens:
        t = int(t)
        if t <= 0 or t >= len(vocab):
            continue
        if vocab[t] == ".":
            break
        out.append(vocab[t])
    return (" ".join(out) + ".") if out else ""


def tokenize_caption(text: str, index: Dict[str, int]) -> List[int]:
    """Word ids of a caption the program returned (without the final '.')."""
    body = text.strip()
    if body.endswith("."):
        body = body[:-1]
    return [index[w] for w in body.split()]


def make_captions(rng: np.random.Generator, vocab: List[str], n: int,
                  min_words: int, max_words: int) -> List[str]:
    lengths = rng.integers(min_words, max_words + 1, size=n)
    ids = rng.integers(2, len(vocab), size=(n, max_words))
    return [" ".join(vocab[j] for j in ids[i, : lengths[i]]) + "." for i in range(n)]


def _jpeg(path: str, seed: int, index: int, size: int) -> None:
    import cv2

    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    low = rng.integers(0, 256, (28, 28, 3), dtype=np.uint8)
    img = cv2.resize(low, (size, size), interpolation=cv2.INTER_CUBIC).astype(np.int16)
    img += rng.integers(-6, 7, img.shape, dtype=np.int16)
    cv2.imwrite(path, np.clip(img, 0, 255).astype(np.uint8), [cv2.IMWRITE_JPEG_QUALITY, 90])


def make_images(directory: str, n: int, size: int, seed: int, threads: int = 8) -> List[str]:
    """n distinct JPEGs; returns their file names (not paths)."""
    os.makedirs(directory, exist_ok=True)
    names = [f"img_{i:06d}.jpg" for i in range(n)]
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(lambda i: _jpeg(os.path.join(directory, names[i]), seed, i, size), range(n)))
    return names


def write_coco(path: str, file_names: Sequence[str], image_ids: Sequence[int],
               captions: Sequence[Sequence[str]]) -> None:
    """image_ids[i] shows file_names[i % len(file_names)] with captions[i]."""
    images, anns = [], []
    for i, image_id in enumerate(image_ids):
        images.append({"id": int(image_id), "file_name": file_names[i % len(file_names)]})
        for cap in captions[i]:
            anns.append({"id": len(anns) + 1, "image_id": int(image_id), "caption": cap})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"images": images, "annotations": anns}, f)


def read_rgb(path: str) -> np.ndarray:
    """A JPEG as the uint8 RGB tensor a decoder hands the model."""
    import cv2

    img = cv2.imread(path)
    if img is None:
        raise FileNotFoundError(path)
    return np.ascontiguousarray(img[:, :, ::-1])
