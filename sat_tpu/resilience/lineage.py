"""Checkpoint lineage: integrity sidecars, LAST_GOOD pointer, retention.

The atomic tmp+rename write (``utils.fileio.atomic_write``) guarantees a
checkpoint file is never *torn by us* — but it cannot protect against
bit-rot, a truncating copy, a misbehaving network filesystem, or a
checkpoint written from an already-diverged state.  This module adds the
lineage layer on top:

* every ``<step>.npz`` gets a ``<step>.npz.sha256`` **integrity sidecar**
  written right after the rename;
* a ``LAST_GOOD`` pointer file names the newest checkpoint that passed a
  **post-write verify** (bytes re-read and hashed against the sidecar)
  while the run was **healthy** (finite metrics at the anomaly sentinel's
  last check) — the rollback target that is safe by construction;
* a **retention policy** keeps the newest N checkpoints plus whatever
  ``LAST_GOOD`` names, so bounded disk can't silently delete the one
  checkpoint that still verifies;
* :func:`verify_checkpoint` is the shared detector for torn / corrupt /
  unreadable files, used by the post-write verify, the restore walk-back
  (``train.checkpoint.restore_checkpoint``), and ``train()``'s final-save
  confirmation.

Directory layout::

    save_dir/
      1500.npz  1500.npz.sha256
      1550.npz  1550.npz.sha256
      LAST_GOOD          # text: "1550\n"
      config.json        # step-stamped Config sidecar (train.checkpoint)

No jax at module level: lineage is pure host IO, shared with the jax-free
``--supervise`` parent and ``scripts/chaos_campaign.py``.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys
import zipfile
from typing import List, Optional, Tuple

from ..utils.fileio import atomic_write
from .retry import retry_io

LAST_GOOD_NAME = "LAST_GOOD"
REJECTED_NAME = "REJECTED"
SIDECAR_SUFFIX = ".sha256"

_STEP_RE = re.compile(r"(\d+)\.npz")


class CheckpointWriteError(RuntimeError):
    """A checkpoint the training loop depended on did not land (queued
    async write failed, or the final save failed verification)."""


def checkpoint_steps(save_dir: str) -> List[int]:
    """Sorted steps of the real ``<step>.npz`` checkpoints under
    ``save_dir`` — regular files with non-zero size only.  Temp files from
    in-flight atomic writes (``*.tmp``), sidecars, trimmed exports
    (``slim.npz``), zero-byte husks left by a full disk, and directories
    that merely look like checkpoints are all skipped rather than
    mis-parsed (the ``latest_checkpoint`` hardening)."""
    steps = []
    if not os.path.isdir(save_dir):
        return steps
    for fn in os.listdir(save_dir):
        m = _STEP_RE.fullmatch(fn)
        if not m:
            continue
        path = os.path.join(save_dir, fn)
        try:
            if not os.path.isfile(path) or os.path.getsize(path) == 0:
                continue
        except OSError:
            continue
        steps.append(int(m.group(1)))
    return sorted(set(steps))


# ---------------------------------------------------------------------------
# integrity sidecars + verification
# ---------------------------------------------------------------------------


def sidecar_path(ckpt_path: str) -> str:
    return ckpt_path + SIDECAR_SUFFIX


def file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def write_sidecar(
    ckpt_path: str,
    topology: Optional[dict] = None,
    vocab: Optional[dict] = None,
    dtypes: Optional[dict] = None,
) -> str:
    """Hash the landed checkpoint and record it; the sidecar is what makes
    later verification a byte-for-byte statement instead of a guess.

    ``dtypes`` (optional) names the entries stored by a view of another
    dtype — ``{entry: "bfloat16"}`` for leaves written as uint16, since
    numpy's format has no bfloat16 (train.checkpoint.load_flat views them
    back).

    ``topology`` (optional) is the device topology the checkpoint was
    written under — ``{"device_count", "mesh_shape", "mesh_axes",
    "platform"}``; ``vocab`` (optional) is the content identity of the
    vocabulary the model was trained against — ``{"sha256", "size"}``
    (data.vocabulary.vocab_fingerprint).  Both ride one JSON line
    appended AFTER the digest line.  :func:`verify_checkpoint` reads
    only the first whitespace-delimited token, so the extension is
    invisible to every existing sidecar consumer;
    :func:`read_sidecar_meta` is the reader.  Elastic resume
    (docs/RESILIENCE.md) uses the topology to report changes — the
    saved state itself is always host-flat full arrays, so restoring
    onto a different mesh is a re-placement, not a data transform.  The
    vocab record lets restore fail fast on a vocabulary swap instead of
    silently skipping the mismatched embedding."""
    digest = retry_io(
        lambda: file_sha256(ckpt_path), desc=f"hash checkpoint {ckpt_path}"
    )
    lines = f"{digest}  {os.path.basename(ckpt_path)}\n"
    meta = {}
    if topology:
        meta["topology"] = topology
    if vocab:
        meta["vocab"] = vocab
    if dtypes:
        meta["dtypes"] = dtypes
    if meta:
        lines += json.dumps(meta, sort_keys=True) + "\n"
    atomic_write(sidecar_path(ckpt_path), "w", lambda f: f.write(lines))
    return digest


def read_sidecar_meta(ckpt_path: str) -> dict:
    """The JSON metadata record from ``ckpt_path``'s sidecar (topology,
    vocab, ...), or {} when the sidecar is missing or predates the
    extension."""
    sc = sidecar_path(ckpt_path)
    try:
        with open(sc) as f:
            for line in f.read().splitlines()[1:]:
                line = line.strip()
                if line.startswith("{"):
                    return json.loads(line)
    except (OSError, ValueError):
        return {}
    return {}


def read_sidecar_topology(ckpt_path: str) -> Optional[dict]:
    """Topology record from ``ckpt_path``'s sidecar, or None when the
    sidecar is missing or predates the topology extension."""
    return read_sidecar_meta(ckpt_path).get("topology")


def verify_checkpoint(ckpt_path: str) -> Tuple[bool, str]:
    """Is ``ckpt_path`` a restorable checkpoint?  Returns (ok, reason).

    With a sidecar: re-read and compare the sha256 — catches truncation
    and bit-rot exactly.  Without one (legacy / foreign checkpoints):
    fall back to structural verification — the zip central directory must
    parse and every member's CRC must check out (``testzip`` decompresses
    everything), which catches torn and corrupt files, just without the
    byte-exactness of the hash.
    """
    if not os.path.isfile(ckpt_path):
        return False, "missing"
    try:
        if os.path.getsize(ckpt_path) == 0:
            return False, "empty file"
        sc = sidecar_path(ckpt_path)
        if os.path.isfile(sc):
            with open(sc) as f:
                want = f.read().split()[0] if f else ""
            got = retry_io(
                lambda: file_sha256(ckpt_path), desc=f"hash checkpoint {ckpt_path}"
            )
            if got != want:
                return False, f"sha256 mismatch (sidecar {want[:12]}…, file {got[:12]}…)"
            return True, "sha256 ok"
        with zipfile.ZipFile(ckpt_path) as z:
            bad = z.testzip()
            if bad is not None:
                return False, f"corrupt member {bad}"
        return True, "zip crc ok (no sidecar)"
    except (OSError, zipfile.BadZipFile, ValueError) as e:
        return False, f"unreadable: {e}"


# ---------------------------------------------------------------------------
# LAST_GOOD pointer
# ---------------------------------------------------------------------------


def mark_last_good(save_dir: str, step: int) -> None:
    """Advance the pointer — callers do this ONLY after the post-write
    verify passed and the run was healthy at its last metrics check."""
    atomic_write(
        os.path.join(save_dir, LAST_GOOD_NAME), "w", lambda f: f.write(f"{int(step)}\n")
    )


def last_good_step(save_dir: str) -> Optional[int]:
    path = os.path.join(save_dir, LAST_GOOD_NAME)
    try:
        with open(path) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def last_good_checkpoint(save_dir: str) -> Optional[str]:
    """Path of the newest VERIFIABLE known-good checkpoint: the pointer
    target if it still verifies, else the walk-back from the pointer
    through older checkpoints (the pointer file itself may be stale or its
    target rotted since it was written)."""
    pointed = last_good_step(save_dir)
    candidates = checkpoint_steps(save_dir)
    if pointed is not None:
        # older-or-equal to the pointer: checkpoints past it were never
        # blessed (unverified, or written while the sentinel was unhealthy)
        candidates = [s for s in candidates if s <= pointed]
    for step in sorted(candidates, reverse=True):
        path = os.path.join(save_dir, f"{step}.npz")
        ok, reason = verify_checkpoint(path)
        if ok:
            return path
        print(
            f"sat_tpu: last-good candidate {path} rejected ({reason}); walking back",
            file=sys.stderr,
            flush=True,
        )
    return None


# ---------------------------------------------------------------------------
# rejection ledger
# ---------------------------------------------------------------------------


def _rejected_path(save_dir: str) -> str:
    return os.path.join(save_dir, REJECTED_NAME)


def rejected_steps(save_dir: str) -> set:
    """Steps the lifecycle controller has permanently rejected (failed
    canary, vocab mismatch, shape drift).  A rejected step is never
    re-canaried even if LAST_GOOD still points at it."""
    steps = set()
    try:
        with open(_rejected_path(save_dir)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    steps.add(int(json.loads(line)["step"]))
                except (ValueError, KeyError, TypeError):
                    continue
    except OSError:
        pass
    return steps


def is_rejected(save_dir: str, step: int) -> bool:
    return int(step) in rejected_steps(save_dir)


def mark_rejected(save_dir: str, step: int, reason: str) -> bool:
    """Append ``step`` to the rejection ledger (one JSON line per entry).
    Exactly-once: returns False without writing when the step is already
    in the ledger, so a rollback raced with a re-poll records a single
    rejection.  Append (not atomic rewrite) keeps earlier entries intact
    even if this write is torn — a torn tail line is skipped by the
    reader."""
    step = int(step)
    if is_rejected(save_dir, step):
        return False
    record = json.dumps({"step": step, "reason": str(reason)}, sort_keys=True)
    path = _rejected_path(save_dir)
    # a torn tail from a crashed append has no newline: start fresh so
    # this record parses instead of gluing onto the garbage
    prefix = ""
    try:
        with open(path, "rb") as f:
            f.seek(-1, os.SEEK_END)
            if f.read(1) != b"\n":
                prefix = "\n"
    except (OSError, ValueError):
        pass
    with open(path, "a") as f:
        f.write(prefix + record + "\n")
        f.flush()
        os.fsync(f.fileno())
    return True


# ---------------------------------------------------------------------------
# retention
# ---------------------------------------------------------------------------


def apply_retention(save_dir: str, keep: int) -> List[str]:
    """Keep the newest ``keep`` checkpoints plus the ``LAST_GOOD`` target;
    delete the rest (files + sidecars).  ``keep<=0`` keeps everything.
    Returns the deleted paths."""
    if keep <= 0:
        return []
    steps = checkpoint_steps(save_dir)
    protect = set(steps[-keep:])
    pointed = last_good_step(save_dir)
    if pointed is not None:
        protect.add(pointed)
    deleted = []
    for step in steps:
        if step in protect:
            continue
        path = os.path.join(save_dir, f"{step}.npz")
        for victim in (path, sidecar_path(path)):
            try:
                os.unlink(victim)
                deleted.append(victim)
            except FileNotFoundError:
                pass
            except OSError as e:  # retention must never kill training
                print(f"sat_tpu: retention could not delete {victim}: {e}",
                      file=sys.stderr, flush=True)
    return deleted


def finalize_save(save_dir: str, path: str, step: int, healthy: bool, keep: int) -> bool:
    """The lineage tail of every checkpoint save: sidecar → post-write
    verify → (healthy?) LAST_GOOD advance → retention.  Returns whether
    the file verified; a failed verify is reported, never raised — the
    previous LAST_GOOD remains the recovery point, which is exactly the
    degradation this layer exists to provide.

    An existing sidecar is trusted, not rewritten: the npz save hashes
    the file immediately after the rename (train.checkpoint._write_flat),
    and re-hashing here would faithfully fingerprint any rot that
    happened since — blessing exactly the corruption the verify exists
    to catch.  The fallback write covers standalone callers only."""
    if not os.path.isfile(sidecar_path(path)):
        write_sidecar(path)
    # verify AFTER any injected corruption so the injection proves the
    # detector (the env knob flips a byte between write and verify)
    ok, reason = verify_checkpoint(path)
    if not ok:
        print(
            f"sat_tpu: checkpoint {path} FAILED post-write verification "
            f"({reason}); LAST_GOOD not advanced",
            file=sys.stderr,
            flush=True,
        )
    elif not healthy:
        print(
            f"sat_tpu: checkpoint {path} written while metrics were "
            "anomalous; LAST_GOOD not advanced",
            file=sys.stderr,
            flush=True,
        )
    else:
        mark_last_good(save_dir, step)
    apply_retention(save_dir, keep)
    return ok
