"""Host-side image decoding and the async device-feed pipeline.

The reference loads images synchronously inside the train loop
(/root/reference/utils/misc.py:6-36 and base_model.py:53), stalling the
device every step.  Here the same preprocessing (decode → BGR→RGB → resize
224×224 → subtract ILSVRC-2012 per-channel mean) runs in a thread pool that
stays ``prefetch_depth`` batches ahead and hands ready numpy batches to the
device while the previous step is still running.

Preprocessing parity notes (utils/misc.py:13-28):
* cv2 decodes BGR; the reference flips channels to RGB via an axis-swap;
* the per-channel mean is the spatial mean of the Caffe ILSVRC-2012 mean
  image, [104.00698793, 116.66876762, 122.67891434] in (B,G,R) npy order —
  the reference subtracts this vector *as-is* from the RGB image
  (utils/misc.py:27), and we reproduce that exactly since pretrained
  weights were trained against it;
* "center crop" is 224→224, a no-op kept only for shape clarity.
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Iterator, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..resilience.faultinject import consume_caption_fault, consume_decode_fault
from ..resilience.quarantine import QuarantineManager, SystemicCorruption

# Spatial mean of the Caffe ILSVRC-2012 mean image (BGR npy channel order);
# matches np.load('ilsvrc_2012_mean.npy').mean(1).mean(1) in the reference.
ILSVRC_2012_MEAN = np.array([104.00698793, 116.66876762, 122.67891434], np.float32)

# Suffixes cv2.imread is expected to decode; everything else in a walked
# directory (READMEs, .DS_Store, sidecar JSONs) is skipped, not an error.
IMAGE_SUFFIXES = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def walk_images(root: str) -> List[str]:
    """Deterministic recursive walk of ``root`` returning every image file
    (by suffix, case-insensitive) in sorted absolute-path order.

    Real corpora directories are mixed-content — checksum manifests,
    thumbnails databases, editor droppings live next to the JPEGs — and a
    bulk job that raises on the first ``README.txt`` three hours in is
    useless.  Non-image files are skipped and counted on the named
    ``data/skipped_nonimage`` counter so the skip volume is observable
    (heartbeat/bench) instead of silent.  The sort is over the final
    absolute paths, so the corpus order — and hence the bulk manifest
    fingerprint (bulk.manifest) — is independent of os.walk's directory
    visit order.
    """
    import os

    files: List[str] = []
    skipped = 0
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()  # deterministic descent (cosmetic; final sort rules)
        for name in filenames:
            if name.lower().endswith(IMAGE_SUFFIXES):
                files.append(os.path.abspath(os.path.join(dirpath, name)))
            else:
                skipped += 1
    if skipped:
        telemetry.get().count("data/skipped_nonimage", skipped)
    return sorted(files)


class ImageLoader:
    """raw=True defers the astype(float32)−mean step to the accelerator
    (models.captioner.encode mean-subtracts uint8 inputs on device):
    numerically IDENTICAL — the resize already happens on the uint8 image,
    mean-sub is the final op either way — but the host skips a float32
    allocation per image and the host→device feed shrinks 4×.  The config
    knob is ``device_preprocess`` (on by default)."""

    def __init__(
        self, mean: Optional[np.ndarray] = None, size: int = 224,
        raw: bool = False,
    ):
        if raw and mean is not None:
            raise ValueError(
                "raw=True defers mean subtraction to the device, which "
                "hardcodes ILSVRC_2012_MEAN (captioner.encode) — a custom "
                "mean would be silently ignored; use raw=False with it"
            )
        self.mean = ILSVRC_2012_MEAN if mean is None else np.asarray(mean, np.float32)  # sync-ok: host constant
        self.size = size
        self.raw = raw

    def _finish_decode(self, image: np.ndarray) -> np.ndarray:
        """Shared post-codec tail: BGR → RGB, resize, contiguous uint8."""
        import cv2

        image = image[:, :, ::-1]  # BGR → RGB
        image = cv2.resize(image, (self.size, self.size))
        return np.ascontiguousarray(image)

    def load_raw(self, image_file: str) -> np.ndarray:
        """Decode → RGB → resize, stopping at the uint8 tensor.  This is
        the canonical post-resize row format the shard cache persists
        (data.shards): both preprocessing modes finish from it — raw=True
        feeds it to the device as-is, raw=False applies the float32 mean
        subtraction — so a cached row is bitwise-interchangeable with a
        live decode in either mode."""
        import cv2

        consume_decode_fault(image_file)  # SAT_FI_BAD_IMAGE_EVERY
        image = cv2.imread(image_file)
        if image is None:
            raise FileNotFoundError(f"cannot decode image: {image_file}")
        return self._finish_decode(image)

    def decode_raw(self, data: bytes) -> np.ndarray:
        """In-memory twin of load_raw for the serving frontend
        (sat_tpu/serve): cv2.imdecode of POSTed bytes runs the identical
        BGR→RGB→resize tail, so a JPEG uploaded over HTTP preprocesses
        bitwise-identically to the same file read from disk."""
        import cv2

        image = cv2.imdecode(np.frombuffer(data, np.uint8), cv2.IMREAD_COLOR)
        if image is None:
            raise ValueError("cannot decode image bytes (not a JPEG/PNG?)")
        return self._finish_decode(image)

    def load_image(self, image_file: str) -> np.ndarray:
        image = self.load_raw(image_file)
        if self.raw:
            return image  # uint8 RGB, device finishes
        return image.astype(np.float32) - self.mean

    def load_bytes(self, data: bytes) -> np.ndarray:
        """decode_raw + this loader's preprocessing mode (see load_image)."""
        image = self.decode_raw(data)
        if self.raw:
            return image
        return image.astype(np.float32) - self.mean

    def load_images(self, image_files: Sequence[str]) -> np.ndarray:
        return np.stack([self.load_image(f) for f in image_files])


class PrefetchDecodeError(RuntimeError):
    """A prefetch worker failed to decode an image.  The bare codec
    error surfaces on the consumer side at an unrelated later batch
    with no clue WHICH record broke; this wrapper carries the file and
    batch coordinates (the original error rides ``__cause__``)."""

    def __init__(
        self, image_file: str, batch_index: int, row: int,
        cause: Optional[BaseException] = None,
    ):
        detail = f": {cause}" if cause is not None else ""
        super().__init__(
            f"cannot decode {image_file!r} "
            f"(batch {batch_index}, row {row}){detail}"
        )
        self.image_file = image_file
        self.batch_index = batch_index
        self.row = row


class PrefetchLoader:
    """Wraps a batch iterator; assembles image batches ahead of the
    consumer in a ring of ``prefetch_depth`` ready slots (a bounded queue
    the producer thread fills and the step loop drains), so the
    accelerator never waits on host-side batch assembly.

    Two assembly paths:

    * **live decode** (default): images run through the thread-pool JPEG
      decode (``ImageLoader``) — 2.5-4.5 ms/image of codec work;
    * **shard gather** (``shard_cache`` given, see ``data.shards``): the
      batch is one fancy-index read per shard out of mmap'd preprocessed
      uint8 tensors — no codec, no per-image allocation; files absent
      from the cache fall back to live decode per image, so a partial
      cache degrades instead of failing.  Bitwise-identical to the live
      path in both preprocessing modes (the shard row IS the live path's
      post-resize uint8 intermediate).

    Yields dicts with 'images' [B,S,S,3] — float32 mean-subtracted, or
    uint8 RGB when the loader runs raw=True (device finishes the
    preprocessing; see ImageLoader) — plus any extra arrays the source
    iterator produced ('word_idxs', 'masks', 'files')."""

    def __init__(
        self,
        dataset,
        image_loader: Optional[ImageLoader] = None,
        num_workers: int = 8,
        prefetch_depth: int = 2,
        shard_cache=None,
        quarantine: Optional[QuarantineManager] = None,
    ):
        self.dataset = dataset
        self.loader = image_loader or ImageLoader()
        self.num_workers = num_workers
        self.prefetch_depth = max(1, prefetch_depth)
        self.shard_cache = shard_cache
        # quarantine=None (default, and every direct construction in
        # tests): failures raise, as they always did.  runtime wires a
        # run-level QuarantineManager in, flipping the data plane to
        # contain-and-substitute (resilience.quarantine)
        self.quarantine = quarantine
        self._pass = 0  # __iter__ count: caption quarantine coordinates
        if shard_cache is not None and shard_cache.image_size != self.loader.size:
            raise ValueError(
                f"shard cache rows are {shard_cache.image_size}px but the "
                f"loader resizes to {self.loader.size}px — the cache was "
                "opened for a different preprocessing"
            )

    def _decode_batch(
        self, batch, pool: ThreadPoolExecutor, pass_idx: int = 0,
        batch_idx: int = 0,
    ):
        with telemetry.span("data/decode_batch", batch_idx):
            return self._decode_batch_inner(batch, pool, pass_idx, batch_idx)

    def _decode_batch_inner(
        self, batch, pool: ThreadPoolExecutor, pass_idx: int = 0,
        batch_idx: int = 0,
    ):
        if isinstance(batch, tuple):
            files, word_idxs, masks = batch
            out = {
                "word_idxs": np.asarray(word_idxs, np.int32),  # sync-ok: host numpy
                "masks": np.asarray(masks, np.float32),  # sync-ok: host numpy
            }
        else:
            files, out = batch, {}
        files = [str(f) for f in files]
        q = self.quarantine
        # (row, file, reason, exc, kind) — everything that must not be
        # trained on as-is; filled by the replay pre-pass, the gather,
        # the live decode, and the caption anomaly scan below
        bad: List[tuple] = []
        flagged: set = set()
        if q is not None:
            q.note_rows(len(files))
            # replayed ledger: substitute known-bad files proactively,
            # never re-attempting the decode — a file repaired since the
            # original run must not change the replay (bitwise rule)
            for i, f in enumerate(files):
                if q.known_bad_file(f):
                    bad.append((i, f, "replayed_ledger", None, "image"))
                    flagged.add(i)
        if self.shard_cache is not None:
            gather_bad = None if q is None else []
            raw = self.shard_cache.gather(
                files, fallback=self.loader.load_raw, bad_rows=gather_bad,
                index=batch_idx,
            )
            if gather_bad:
                for i, f, reason, exc in gather_bad:
                    if i not in flagged:
                        bad.append((i, f, reason, exc, "image"))
                        flagged.add(i)
            # the final float32−mean step runs batch-wise here; elementwise
            # it is the exact op the live path applies per image, so the
            # two paths stay bitwise-identical
            out["images"] = (
                raw if self.loader.raw
                else raw.astype(np.float32) - self.loader.mean
            )
        else:
            S = self.loader.size
            dtype = np.uint8 if self.loader.raw else np.float32
            images = np.zeros((len(files), S, S, 3), dtype)
            def _load_one(i):
                if i in flagged:
                    return i, None, None
                try:
                    return i, self.loader.load_image(files[i]), None
                except Exception as e:
                    return i, None, e
            for i, img, exc in pool.map(_load_one, range(len(files))):
                if img is not None:
                    images[i] = img
                elif exc is not None:
                    if q is None:
                        raise PrefetchDecodeError(
                            files[i], batch_idx, i, exc
                        ) from exc
                    bad.append((i, files[i], "decode_failed", exc, "image"))
                    flagged.add(i)
            out["images"] = images
        out["files"] = list(files)
        if "word_idxs" in out:
            for i in range(len(files)):
                if consume_caption_fault():  # SAT_FI_BAD_CAPTION_AT
                    out["word_idxs"][i] = 0
                    out["masks"][i] = 0.0
            if q is not None:
                masks = out["masks"]
                cap = masks.shape[1] if masks.ndim == 2 else 0
                for i in range(len(files)):
                    if i in flagged:
                        continue
                    n_tok = float(masks[i].sum())  # sync-ok: host numpy
                    if q.known_bad_pos(pass_idx, batch_idx, i):
                        reason = "replayed_ledger"
                    elif n_tok == 0:
                        reason = "caption_all_oov"
                    elif cap and n_tok >= cap:
                        reason = "caption_overlength"
                    else:
                        continue
                    bad.append((i, files[i], reason, None, "caption"))
                    flagged.add(i)
        if q is not None and bad:
            self._quarantine_and_substitute(
                out, bad, len(files), pass_idx, batch_idx
            )
        return out

    def _quarantine_and_substitute(
        self, out, bad, n_rows, pass_idx, batch_idx
    ):
        """Ledger every newly bad row, then overwrite each bad row
        wholesale with a deterministically chosen healthy row of the
        same batch — geometry never changes, a replay with the same
        ledger substitutes identically."""
        q = self.quarantine
        bad_set = {b[0] for b in bad}
        healthy = [i for i in range(n_rows) if i not in bad_set]
        for i, f, reason, exc, kind in sorted(bad, key=lambda b: b[0]):
            pos = (pass_idx, batch_idx, i) if kind == "caption" else None
            if reason != "replayed_ledger":
                # may raise SystemicCorruption (the ceiling)
                q.quarantine(f, reason, kind=kind, pos=pos, exc=exc)
            if not healthy:
                raise SystemicCorruption(
                    f"every row of batch {batch_idx} is quarantined "
                    f"(last: {f!r}, {reason}) — no healthy row to "
                    "substitute; the input data is systemically corrupt"
                )
            key = (
                f"image:{f}" if kind == "image"
                else f"caption:{pass_idx}:{batch_idx}:{i}"
            )
            j = healthy[QuarantineManager.substitute_index(key, len(healthy))]
            for k in ("images", "word_idxs", "masks"):
                if k in out:
                    out[k][i] = out[k][j]
            out["files"][i] = out["files"][j]

    def __iter__(self) -> Iterator[dict]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_depth)
        sentinel = object()
        stop = threading.Event()
        error: List[BaseException] = []

        pass_idx = self._pass
        self._pass += 1

        def producer():
            try:
                with ThreadPoolExecutor(max_workers=self.num_workers) as pool:
                    for batch_idx, batch in enumerate(self.dataset):
                        item = self._decode_batch(
                            batch, pool, pass_idx, batch_idx
                        )
                        # Bounded put that aborts if the consumer went away,
                        # so an abandoned iterator can't pin a thread.
                        while not stop.is_set():
                            try:
                                q.put(item, timeout=0.1)
                                break
                            except queue.Full:
                                continue
                        if stop.is_set():
                            return
            except BaseException as e:  # surfaced on the consumer side
                error.append(e)
            finally:
                while not stop.is_set():
                    try:
                        q.put(sentinel, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                # depth AFTER the take: 0 = consumer outran the producers
                # (data-starved), maxsize = producers ahead (healthy)
                telemetry.get().gauge("data/prefetch_qsize", q.qsize())
                if item is sentinel:
                    if error:
                        raise error[0]
                    return
                yield item
        finally:
            stop.set()
