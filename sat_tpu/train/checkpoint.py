"""Checkpoint save / restore / import, npy-lineage compatible.

The reference persists a flat ``{variable_name: ndarray}`` dict via
``np.save`` plus a pickled Config carrying ``global_step``
(/root/reference/base_model.py:242-255), restores per-variable and skips
missing names (partial restore, base_model.py:257-278), imports pretrained
CNNs from a *nested* ``{op_name: {param_name: ndarray}}`` npy
(base_model.py:280-297), and ships a trim tool that strips optimizer slots
(/root/reference/data/models/trim_model.py:11-18).

This module reproduces all four capabilities on the JAX pytree state:

* ``save_checkpoint``   — flat name→array ``<step>.npz`` + ``config.json``
  sidecar holding global_step (the config.pickle equivalent);
* ``restore_checkpoint`` — by explicit file or latest-in-dir, per-leaf
  assignment tolerant of missing/mismatched entries;
* ``load_pretrained_cnn`` — reads the reference's nested npy formats
  (``vgg16_no_fc.npy`` / ``resnet50_no_fc.npy``); module names match the
  reference's TF scopes 1:1 (conv1_1…conv5_3, res2a_branch2a…), so the map
  is name-table-driven, ignore-missing like the reference;
* ``trim_checkpoint``   — drops ``optimizer/*`` entries for slim
  inference checkpoints.

Checkpoints are written atomically (tmp + rename) so a preempted host
never leaves a torn file — the failure-recovery story the reference lacks.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..resilience import faultinject, lineage
from ..resilience.lineage import CheckpointWriteError
from ..resilience.retry import retry_io
from .. import telemetry
from ..utils.dist import gather_tree_replicated
from ..utils.fileio import atomic_write

# ---------------------------------------------------------------------------
# pytree <-> flat name dict
# ---------------------------------------------------------------------------


def _key_to_str(entry: Any) -> str:
    """One path entry → a stable string segment."""
    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return entry.name
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    if isinstance(entry, jax.tree_util.FlattenedIndexKey):
        return str(entry.key)
    return str(entry)


def _path_name(prefix: str, path) -> str:
    """Leaf path → checkpoint entry name (single definition shared by save
    and restore so the two can never disagree)."""
    name = "/".join(_key_to_str(e) for e in path)
    return prefix + name if name else prefix.rstrip("/")


def flatten_with_names(tree: Any, prefix: str = "") -> Dict[str, Any]:
    """Pytree → {slash/joined/path: leaf}.  Works on dicts, NamedTuples
    (optax states), and lists alike."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path_name(prefix, path): leaf for path, leaf in leaves}


_BFLOAT16 = np.dtype(jnp.bfloat16)


def _bfloat16_as_uint16(flat: Dict[str, np.ndarray]):
    """numpy's file format has no bfloat16 (it would write the leaf as an
    opaque 2-byte void): such leaves are stored by an explicit uint16 view
    of the same bytes.  Returns (what to write, {name: "bfloat16"} for the
    sidecar's dtype note)."""
    noted = {k: "bfloat16" for k, v in flat.items() if v.dtype == _BFLOAT16}
    if not noted:
        return flat, noted
    return {k: v.view(np.uint16) if k in noted else v for k, v in flat.items()}, noted


def _assign_leaves(tree: Any, prefix: str, data: Dict[str, np.ndarray]):
    """Rebuild ``tree`` with any leaf whose name appears in ``data`` (same
    shape) replaced.  Returns (new_tree, loaded_count) — the per-variable
    tolerant assignment of the reference's load (base_model.py:272-277)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    new_leaves = []
    count = 0
    for path, leaf in paths:
        name = _path_name(prefix, path)
        if name in data:
            value = np.asarray(data[name])
            if hasattr(leaf, "shape") and tuple(value.shape) == tuple(leaf.shape):
                if leaf.dtype == _BFLOAT16 and value.dtype == np.uint16:
                    # a view whose dtype note (the sidecar) was lost:
                    # casting would turn bit patterns into numbers
                    raise ValueError(
                        f"checkpoint entry {name} holds raw uint16 for a "
                        "bfloat16 leaf and its sidecar's dtype note is "
                        "missing; restore the .sha256 file beside the npz"
                    )
                # jnp.array, not the raw numpy value: the CPU backend turns
                # an aligned numpy argument into a ZERO-COPY device buffer
                # that borrows the host memory, and train_step's
                # donate_argnums then lets XLA free/reuse a buffer it never
                # owned — a use-after-free that shows up as heap pointers in
                # restored Adam slots on resume (timing-dependent; the
                # persistent compile cache makes it reproducible).  An
                # explicit device copy gives every restored leaf an
                # XLA-owned buffer, same as fresh-init jit outputs.
                new_leaves.append(jnp.array(value.astype(leaf.dtype)))
                count += 1
                continue
        new_leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, new_leaves), count


def state_to_flat(state: Any) -> Dict[str, np.ndarray]:
    """TrainState → flat dict.  Optimizer slots live under ``optimizer/`` so
    the trim tool (reference trim_model.py:14) can drop them by prefix.
    Works on mesh-sharded states (single- or multi-process): shards held
    by other hosts are all-gathered first so every process can materialize
    full values (the distributed save path)."""
    with telemetry.span("ckpt/snapshot"):
        flat: Dict[str, np.ndarray] = {}
        flat.update(flatten_with_names(state.params, "params/"))
        if state.batch_stats:
            flat.update(flatten_with_names(state.batch_stats, "batch_stats/"))
        flat.update(flatten_with_names(state.opt_state, "optimizer/"))
        flat["global_step"] = np.asarray(state.step)
        flat = gather_tree_replicated(flat)
        # One batched D2H transfer for the whole dict, not one per leaf.  The
        # snapshot must OWN its bytes: on the CPU backend device_get returns
        # zero-copy views of the live device buffers, and those buffers are
        # donated into the next dispatched step (train/step.py donate_argnums)
        # — an async writer serializing a view after donation would persist
        # whatever XLA wrote over it (observed as denormal garbage in Adam mu
        # slots of resumed runs).  OWNDATA is False exactly for such views, so
        # TPU-path arrays (device_get already copied) aren't copied twice.
        host = jax.device_get(flat)
        return {
            k: v if isinstance(v, np.ndarray) and v.flags["OWNDATA"] else np.array(v)
            for k, v in host.items()
        }


# ---------------------------------------------------------------------------
# save / restore
# ---------------------------------------------------------------------------


class AsyncCheckpointWriter:
    """Overlaps checkpoint disk writes with training.

    The reference stalls its hot loop every ``save_period=50`` steps while
    every variable is pulled to host AND written out
    (/root/reference/base_model.py:61-62,242-255).  On TPU the
    device→host snapshot is the only part that must synchronize with the
    step stream — the state is donated into the next dispatched step
    (train/step.py donate_argnums), so its buffers must be materialized
    on host before training proceeds — but npz serialization + disk I/O
    (hundreds of MB with Adam slots) have no such constraint.  ``save``
    therefore snapshots synchronously and hands the numpy tree to a
    single worker thread; saves serialize in submission order, worker
    failures surface on the next ``save``/``close`` (the PrefetchLoader
    error contract), and ``close`` drains the queue.

    Single-process only: the multi-host save path needs a cross-host
    barrier in line with the step stream, so ``save`` falls back to the
    synchronous writer when ``jax.process_count() > 1``.
    """

    def __init__(self) -> None:
        import queue
        import threading

        # bounded like PrefetchLoader's queue (data/images.py): each item
        # is a full host snapshot (hundreds of MB with Adam slots), so a
        # slow disk must apply backpressure on save() — degrading toward
        # sync-save speed — rather than stack snapshots until OOM
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._error: Optional[BaseException] = None
        # _error crosses the worker/caller thread boundary; the lock makes
        # that handoff explicit rather than leaning on CPython's per-ref
        # atomicity.  It does NOT close the save()-time window between
        # _check and put() — a failure landing there surfaces on the NEXT
        # call, which is what the permanent-failure contract in _check
        # guarantees (the actual ADVICE r3 fix).
        self._error_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, name="sat-ckpt-writer", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        import threading

        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, threading.Event):  # flush() barrier
                item.set()
                continue
            flat, path, config, save_dir, healthy = item
            try:
                _write_flat(flat, path, config, save_dir, healthy=healthy)
            except BaseException as e:  # surfaced on next save/close
                with self._error_lock:
                    if self._error is None:  # keep the FIRST failure (root cause)
                        self._error = e

    def _check(self) -> None:
        # the failure is permanent: a writer that lost a snapshot cannot
        # promise anything about later ones, so every subsequent
        # save()/close() re-raises the same root cause rather than
        # silently resuming
        with self._error_lock:
            e = self._error
        if e is not None:
            # CheckpointWriteError subclasses RuntimeError, so callers
            # matching the long-standing message keep working while the
            # CLI can map the typed failure to a non-zero exit
            raise CheckpointWriteError("async checkpoint write failed") from e

    def save(
        self,
        state: Any,
        config: Config,
        save_dir: Optional[str] = None,
        healthy: bool = True,
    ) -> str:
        self._check()
        if jax.process_count() > 1:
            return save_checkpoint(state, config, save_dir, healthy=healthy)
        save_dir = save_dir or config.save_dir
        flat = state_to_flat(state)  # the synchronous part
        step = int(flat["global_step"])
        path = os.path.join(save_dir, f"{step}.npz")
        self._q.put((flat, path, config, save_dir, healthy))
        return path

    def flush(self) -> None:
        """Block until every save queued so far is on disk (with its
        lineage tail applied), then surface any worker failure.  The
        rollback path needs this: LAST_GOOD is only readable after the
        write that blesses it has drained."""
        import threading

        barrier = threading.Event()
        self._q.put(barrier)
        barrier.wait()
        self._check()

    def close(self) -> None:
        """Drain pending writes; re-raise the first worker failure."""
        self._q.put(None)
        self._thread.join()
        self._check()

    def __enter__(self) -> "AsyncCheckpointWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _topology_snapshot(config: Config) -> Dict[str, Any]:
    """Device topology the checkpoint is being written under, recorded in
    the lineage sidecar so elastic resume (docs/RESILIENCE.md) can report
    a topology change.  Informational only: the saved state is host-flat
    full arrays, so a restore onto fewer (or more) chips is a
    re-placement (``parallel.sharding.reshard_train_state``), never a
    data transform — the snapshot exists so the change is visible, not
    because it gates anything."""
    devices = jax.devices()
    return {
        "device_count": len(devices),
        "platform": devices[0].platform if devices else "unknown",
        "process_count": jax.process_count(),
        "mesh_shape": list(config.mesh_shape),
        "mesh_axes": list(config.mesh_axes),
    }


def _write_flat(
    flat: Dict[str, np.ndarray],
    path: str,
    config: Config,
    save_dir: str,
    healthy: bool = True,
) -> None:
    """The disk half of a checkpoint save (shared by the sync and async
    paths): atomic npz + config.json sidecar, then the lineage tail —
    sha256 sidecar, post-write verify, LAST_GOOD advance (only when the
    verify passed AND the run was ``healthy`` at its last metrics check),
    and keep-N retention (docs/RESILIENCE.md)."""
    step = int(flat["global_step"])
    stored, dtypes = _bfloat16_as_uint16(flat)
    # write through the file object: np.savez(path) appends '.npz' itself
    with telemetry.span("ckpt/write"):
        retry_io(
            lambda: atomic_write(path, "wb", lambda f: np.savez(f, **stored)),
            desc=f"write checkpoint {path}",
        )
    del stored
    # hash NOW, while the file is still exactly what we serialized: a
    # sidecar computed later would faithfully fingerprint whatever rot
    # happened in between and the verify would bless corrupt bytes
    with telemetry.span("ckpt/sidecar"):
        try:
            from ..data.vocabulary import vocab_fingerprint

            vocab = vocab_fingerprint(
                config.vocabulary_file, config.vocabulary_size
            )
        except Exception:
            vocab = None  # attestation is best-effort; the save is not
        lineage.write_sidecar(
            path, topology=_topology_snapshot(config), vocab=vocab, dtypes=dtypes
        )
    retry_io(
        lambda: config.replace(global_step=step).save(
            os.path.join(save_dir, "config.json")
        ),
        desc=f"write checkpoint config {save_dir}",
    )
    # injection point: bit-rot between the rename and the verify — the
    # post-write verify below must catch it and refuse to bless the file
    faultinject.FaultPlan.from_env().maybe_corrupt_checkpoint(path, step)
    # verify + LAST_GOOD advance + retention, timed as one phase
    with telemetry.span("ckpt/finalize"):
        lineage.finalize_save(
            save_dir, path, step, healthy=healthy, keep=config.keep_checkpoints
        )
    telemetry.count("ckpt/saves")
    telemetry.gauge("ckpt/last_save_step", step)
    telemetry.gauge("ckpt/last_save_unix", time.time())


def save_checkpoint(
    state: Any,
    config: Config,
    save_dir: Optional[str] = None,
    healthy: bool = True,
) -> str:
    """Write ``<global_step>.npz`` + ``config.json`` under save_dir.

    Mirrors the reference's save (base_model.py:242-255): everything —
    params, BN stats, optimizer slots, global step — in one flat archive,
    with the config (embedding global_step) alongside for
    resume-from-latest.  Atomic via tmp+rename; ``healthy=False`` (the
    anomaly sentinel saw non-finite metrics) still writes the file but
    withholds the ``LAST_GOOD`` blessing.
    """
    save_dir = save_dir or config.save_dir
    flat = state_to_flat(state)
    step = int(flat["global_step"])
    path = os.path.join(save_dir, f"{step}.npz")
    if jax.process_index() == 0:
        # process 0 writes; other hosts only participated in the gather
        # (the reference's chief-writes checkpointing, main_distributed.py:64)
        _write_flat(flat, path, config, save_dir, healthy=healthy)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"sat_tpu_ckpt_{step}")
    return path


def latest_checkpoint(save_dir: str) -> Optional[str]:
    """Resolve the newest checkpoint like the reference's config.pickle
    lookup (base_model.py:262-269), falling back to a directory scan.

    The scan (``resilience.lineage.checkpoint_steps``) accepts only real,
    non-empty ``<step>.npz`` regular files — in-flight atomic-write temps,
    sidecars, ``slim.npz`` exports, zero-byte husks from a full disk, and
    lookalike directories are never mis-parsed into a candidate."""
    steps = set(lineage.checkpoint_steps(save_dir))
    cfg_path = os.path.join(save_dir, "config.json")
    # The config.json pointer can name a step the scan rejected (e.g. its
    # npz truncated to zero bytes) — intersect, don't trust.
    if os.path.exists(cfg_path):
        try:
            pointed = int(Config.load(cfg_path).global_step)
        except (ValueError, KeyError, TypeError):
            pass  # torn config.json → rely on the directory scan
        else:
            path = os.path.join(save_dir, f"{pointed}.npz")
            try:
                if os.path.isfile(path) and os.path.getsize(path) > 0:
                    steps.add(pointed)
            except OSError:
                pass
    if steps:
        return os.path.join(save_dir, f"{max(steps)}.npz")
    return None


def load_flat(path: str) -> Dict[str, np.ndarray]:
    """The archive's entries, with the leaves the sidecar's dtype note
    names viewed back as what they are (bit-exact: no cast)."""
    noted = lineage.read_sidecar_meta(path).get("dtypes") or {}

    def _read() -> Dict[str, np.ndarray]:
        with np.load(path, allow_pickle=False) as z:
            return {
                k: z[k].view(_BFLOAT16) if noted.get(k) == "bfloat16" else z[k]
                for k in z.files
            }

    return retry_io(_read, desc=f"read checkpoint {path}")


def _note_elastic_restore(path: str) -> None:
    """Report when a checkpoint written under one device topology is being
    restored under another (elastic resume).  Purely informational — the
    host-flat checkpoint format makes the restore itself topology-free —
    but an operator resuming an 8-chip run on 1 chip should see it said
    out loud, and ``ckpt/elastic_restores`` makes it greppable in
    heartbeat/bench artifacts."""
    recorded = lineage.read_sidecar_topology(path)
    if not recorded:
        return
    now = len(jax.devices())
    then = recorded.get("device_count")
    if then is not None and int(then) != now:
        telemetry.count("ckpt/elastic_restores")
        print(
            f"sat_tpu: elastic resume — checkpoint {os.path.basename(path)} "
            f"was written on {then} device(s) "
            f"(mesh {recorded.get('mesh_shape')}), restoring onto {now}; "
            "state will be re-placed on the current mesh",
            file=sys.stderr,
            flush=True,
        )


class VocabMismatchError(RuntimeError):
    """The checkpoint's lineage sidecar attests a different vocabulary
    than the one this run is configured with.  Without this check the
    word-embedding rows would be silently skipped by the shape-tolerant
    partial restore and the model would decode gibberish."""


def _check_vocab(path: str, expect: Optional[dict]) -> None:
    """Compare the run's vocabulary fingerprint against the sidecar's.
    Both sides optional: a legacy sidecar (no vocab record) or a run
    that could not fingerprint its vocabulary checks nothing."""
    if not expect:
        return
    recorded = lineage.read_sidecar_meta(path).get("vocab")
    if not recorded:
        return
    if (
        recorded.get("sha256") != expect.get("sha256")
        or int(recorded.get("size", 0)) != int(expect.get("size", 0))
    ):
        raise VocabMismatchError(
            f"vocab mismatch (got {expect.get('size')} words, sha "
            f"{str(expect.get('sha256'))[:12]}…; checkpoint "
            f"{os.path.basename(path)} expects {recorded.get('size')} "
            f"words, sha {str(recorded.get('sha256'))[:12]}…) — the "
            "vocabulary file changed since this checkpoint was trained; "
            "restore with the original vocabulary.csv or retrain"
        )


def restore_checkpoint(
    state: Any,
    model_file: Optional[str] = None,
    save_dir: Optional[str] = None,
    expect_vocab: Optional[dict] = None,
) -> Tuple[Any, int]:
    """Restore into an existing state skeleton.

    ``model_file`` explicit, else latest under ``save_dir`` — the
    reference's two load modes (base_model.py:258-269).  Missing /
    shape-mismatched entries are skipped (partial restore), so trimmed
    inference checkpoints load cleanly into a full train state.
    Returns (new_state, tensors_loaded).

    ``expect_vocab`` (``data.vocabulary.vocab_fingerprint`` of the
    run's configured vocabulary) is compared against the candidate's
    lineage sidecar; a mismatch raises :class:`VocabMismatchError`
    IMMEDIATELY — it is a configuration error, not file rot, so the
    save_dir mode does NOT walk back past it (every older checkpoint of
    the run was trained against the same vocabulary).

    In ``save_dir`` mode a torn / corrupt / unreadable newest checkpoint
    is not fatal: each candidate is integrity-checked
    (``resilience.lineage.verify_checkpoint`` — sha256 sidecar when
    present, zip CRC otherwise) and the restore walks back to the newest
    checkpoint that verifies AND loads.  An explicit ``model_file`` is
    the operator saying "this file" — it is loaded as-is and failures
    propagate.
    """
    if model_file:
        _check_vocab(model_file, expect_vocab)
        flat = load_flat(model_file)
        _note_elastic_restore(model_file)
    else:
        if not save_dir:
            raise FileNotFoundError(f"no checkpoint found (save_dir={save_dir!r})")
        flat = None
        rejected = []
        for step in sorted(lineage.checkpoint_steps(save_dir), reverse=True):
            path = os.path.join(save_dir, f"{step}.npz")
            ok, reason = lineage.verify_checkpoint(path)
            if ok:
                try:
                    _check_vocab(path, expect_vocab)
                    flat = load_flat(path)
                    _note_elastic_restore(path)
                    break
                except (OSError, ValueError) as e:  # verified yet unloadable
                    reason = f"load failed: {e}"
            rejected.append(f"{os.path.basename(path)} ({reason})")
            telemetry.count("ckpt/walkbacks")
            print(
                f"sat_tpu: checkpoint {path} rejected ({reason}); "
                "walking back to an older checkpoint",
                file=sys.stderr,
                flush=True,
            )
        if flat is None:
            detail = f"; rejected: {', '.join(rejected)}" if rejected else ""
            raise FileNotFoundError(
                f"no verifiable checkpoint found (save_dir={save_dir!r}{detail})"
            )

    params, n_p = _assign_leaves(state.params, "params/", flat)
    batch_stats, n_b = _assign_leaves(state.batch_stats, "batch_stats/", flat)
    opt_state, n_o = _assign_leaves(state.opt_state, "optimizer/", flat)
    step = state.step
    if "global_step" in flat:
        step = jnp.array(np.asarray(flat["global_step"], dtype=np.int32))
    new_state = state._replace(
        params=params, batch_stats=batch_stats, opt_state=opt_state, step=step
    )
    # global_step deliberately not counted: count==0 must mean "nothing
    # usable restored" so callers can treat it as a hard error.
    return new_state, n_p + n_b + n_o


def trim_checkpoint(in_path: str, out_path: str) -> int:
    """Strip optimizer slots (reference trim_model.py:11-18).  Returns the
    number of entries kept."""
    flat = load_flat(in_path)
    kept = {k: v for k, v in flat.items() if not k.startswith("optimizer/")}
    stored, dtypes = _bfloat16_as_uint16(kept)
    atomic_write(out_path, "wb", lambda f: np.savez(f, **stored))
    if dtypes:  # the views need their note to be read back
        lineage.write_sidecar(out_path, dtypes=dtypes)
    return len(kept)


# ---------------------------------------------------------------------------
# pretrained-CNN import (reference nested-npy formats)
# ---------------------------------------------------------------------------

# Param-name aliases across the caffe-converted npy files and TF scopes.
_KERNEL_NAMES = {"kernel", "weights", "W", "w"}
_BIAS_NAMES = {"bias", "biases", "b", "offset", "beta"}
_SCALE_NAMES = {"scale", "gamma"}
_MEAN_NAMES = {"mean", "moving_mean", "mu"}
_VAR_NAMES = {"variance", "moving_variance", "var"}


def _nested_npy(data_path: str) -> Dict[str, Dict[str, np.ndarray]]:
    raw = np.load(data_path, allow_pickle=True, encoding="latin1")
    d = raw.item() if hasattr(raw, "item") and raw.dtype == object else dict(raw)
    return {str(k): {str(p): np.asarray(a) for p, a in v.items()} for k, v in d.items()}


def _find_op(tree: Any, op: str) -> Optional[Dict[str, Any]]:
    """Locate the dict node named ``op`` at any depth — Flax nests block
    submodules (cnn/res2a/res2a_branch2a/...) one level deeper than the
    reference's flat TF scopes."""
    if not isinstance(tree, dict):
        return None
    if op in tree and isinstance(tree[op], dict):
        return tree[op]
    for child in tree.values():
        hit = _find_op(child, op)
        if hit is not None:
            return hit
    return None


def _set_key(dest: Dict[str, Any], key: str, value: np.ndarray) -> bool:
    """Assign ``key`` within the op's subtree; our nn.Conv wrapper nests
    an inner 'conv' module, so descend through child dicts if needed."""
    if key in dest and not isinstance(dest[key], dict):
        if tuple(dest[key].shape) != tuple(value.shape):
            return False
        dest[key] = value.astype(dest[key].dtype)
        return True
    for child in dest.values():
        if isinstance(child, dict) and _set_key(child, key, value):
            return True
    return False


def _place_nested(
    cnn_params: Dict[str, Any],
    batch_stats: Dict[str, Any],
    nested: Dict[str, Dict[str, np.ndarray]],
) -> int:
    """Place ``{op: {param: arr}}`` entries into the (numpy, mutated
    in-place) CNN param / batch-stat trees, alias-mapping param names.
    Unknown ops/params are skipped, matching the reference's
    ignore_missing=True (base_model.py:295-296).  Returns tensors placed."""
    count = 0

    def place(tree: Dict[str, Any], op: str, key: str, value: np.ndarray) -> bool:
        dest = _find_op(tree, op)
        return dest is not None and _set_key(dest, key, value)

    for op_name, entries in nested.items():
        for param_name, value in entries.items():
            if param_name in _KERNEL_NAMES:
                key, tree = "kernel", cnn_params
            elif param_name in _SCALE_NAMES:
                key, tree = "scale", cnn_params
            elif param_name in _BIAS_NAMES:
                key, tree = "bias", cnn_params
            elif param_name in _MEAN_NAMES:
                key, tree = "mean", batch_stats
            elif param_name in _VAR_NAMES:
                key, tree = "var", batch_stats
            else:
                continue
            if place(tree, op_name, key, value):
                count += 1
    return count


def load_pretrained_cnn(
    variables: Dict[str, Any], data_path: str
) -> Tuple[Dict[str, Any], int]:
    """Import a reference-format pretrained CNN npy into the variable tree.

    The file is ``{op_name: {param_name: array}}`` (base_model.py:286-289);
    op names are the TF scopes our Flax modules reuse verbatim (conv1_1 …,
    res2a_branch2a …, bn_conv1 …).  Conv kernels arrive HWIO (TF layout =
    ours).  BN stats land in ``batch_stats``; scale/offset in params.
    Returns (new_variables, tensors_loaded).
    """
    return _import_cnn_nested(variables, _nested_npy(data_path))


# ---------------------------------------------------------------------------
# full reference-checkpoint import (TF1 flat-name format)
# ---------------------------------------------------------------------------

_DECODER_SCOPES = ("word_embedding", "initialize", "attend", "decode")


def import_reference_checkpoint(
    state: Any, path: str, restore_step: bool = False
) -> Tuple[Any, int]:
    """Ingest a checkpoint written by the reference's own save():
    a flat ``{var.name: value}`` npy (base_model.py:242-249).

    Name translation, not weight surgery — the decoder was designed with
    TF1-compatible layouts so every tensor drops in unchanged:

    * ``<scope>/<fc>/kernel:0`` → ``params/decoder/<scope>/<fc>/kernel``
      for the word_embedding / initialize / attend / decode scopes
      (reference model.py:219-225,358-459);
    * ``lstm/lstm_cell/{kernel,bias}:0`` → ``params/decoder/lstm/*`` —
      the single concatenated [(D+E+H), 4H] matrix with TF1's (i, j, f, o)
      gate order, which lstm_step consumes natively (the +1.0 forget bias
      is a runtime constant on both sides, never stored);
    * CNN scopes (``conv1_1/kernel:0``, ``res2a_branch2a/...``,
      BN gamma/beta/moving_mean/moving_variance) place through the same
      alias machinery as the nested pretrained import;
    * optimizer slots (``OptimizeLoss/...``) are dropped — the reference's
      Adam state has no meaning for our optax chain.  ``global_step:0`` is
      only adopted with ``restore_step=True``: a foreign step count would
      otherwise drive the train loop's resume fast-forward (skipping
      epochs, or no-opping entirely when it exceeds the epoch budget) —
      fine-tuning an imported model starts a fresh optimization at step 0.

    Returns (new_state, tensors_loaded).
    """
    raw = np.load(path, allow_pickle=True, encoding="latin1").item()

    decoder_flat: Dict[str, np.ndarray] = {}
    cnn_nested: Dict[str, Dict[str, np.ndarray]] = {}
    step: Optional[np.ndarray] = None
    for name, value in raw.items():
        name = name.split(":")[0]
        parts = name.split("/")
        if parts[0] == "global_step":
            step = np.asarray(value, dtype=np.int32)
        elif parts[0].startswith("OptimizeLoss") or "optimizer" in parts[0].lower():
            continue
        elif parts[0] == "lstm":
            decoder_flat[f"params/decoder/lstm/{parts[-1]}"] = np.asarray(value)
        elif parts[0] in _DECODER_SCOPES:
            decoder_flat["params/decoder/" + "/".join(parts)] = np.asarray(value)
        elif len(parts) >= 2:
            cnn_nested.setdefault(parts[0], {})[parts[-1]] = np.asarray(value)

    params, n_dec = _assign_leaves(state.params, "params/", decoder_flat)
    new_state, n_cnn = apply_cnn_import(state._replace(params=params), cnn_nested)
    if restore_step and step is not None:
        new_state = new_state._replace(step=step)
    return new_state, n_dec + n_cnn


def apply_cnn_import(state: Any, nested_or_path: Any) -> Tuple[Any, int]:
    """Import a nested CNN dict (or its npy path) into a TrainState —
    the variables-wrap/unwrap shared by the reference-checkpoint import
    and runtime.setup_state's --load_cnn branch."""
    variables: Dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    if isinstance(nested_or_path, str):
        nested_or_path = _nested_npy(nested_or_path)
    variables, count = _import_cnn_nested(variables, nested_or_path)
    return (
        state._replace(
            params=variables["params"],
            batch_stats=variables.get("batch_stats", state.batch_stats),
        ),
        count,
    )


def _import_cnn_nested(
    variables: Dict[str, Any], nested: Dict[str, Dict[str, np.ndarray]]
) -> Tuple[Dict[str, Any], int]:
    """load_pretrained_cnn body for an already-loaded nested dict."""
    cnn_params = jax.tree_util.tree_map(np.asarray, variables["params"]["cnn"])
    batch_stats = jax.tree_util.tree_map(
        np.asarray, variables.get("batch_stats", {})
    )
    count = _place_nested(cnn_params, batch_stats, nested)
    new_variables = dict(variables)
    new_params = dict(variables["params"])
    new_params["cnn"] = cnn_params
    new_variables["params"] = new_params
    if batch_stats:
        new_variables["batch_stats"] = batch_stats
    return new_variables, count


# ---------------------------------------------------------------------------
# reference-checkpoint EXPORT (migration in the other direction)
# ---------------------------------------------------------------------------

_BN_EXPORT_NAMES = {
    "scale": "gamma", "bias": "beta", "mean": "moving_mean", "var": "moving_variance",
}


def _export_cnn_tree(tree: Any, out: Dict[str, np.ndarray]) -> None:
    """Walk a CNN param/batch-stat tree emitting reference TF-scope names:
    a node holding our Conv wrapper's inner 'conv' module becomes
    ``<op>/{kernel,bias}``; a node of BN leaves becomes
    ``<op>/{gamma,beta}`` (params) / ``<op>/{moving_mean,moving_variance}``
    (stats); anything else (res2a block containers) recurses."""
    if not isinstance(tree, dict):
        return
    for op, sub in tree.items():
        if not isinstance(sub, dict):
            continue
        inner = sub.get("conv")
        if isinstance(inner, dict) and "kernel" in inner:
            for leaf, arr in inner.items():
                out[f"{op}/{leaf}:0"] = np.asarray(arr)
        elif any(k in sub and not isinstance(sub[k], dict) for k in _BN_EXPORT_NAMES):
            for leaf, arr in sub.items():
                if leaf in _BN_EXPORT_NAMES and not isinstance(arr, dict):
                    out[f"{op}/{_BN_EXPORT_NAMES[leaf]}:0"] = np.asarray(arr)
        else:
            _export_cnn_tree(sub, out)


def export_reference_checkpoint(state: Any, path: str) -> int:
    """Inverse of :func:`import_reference_checkpoint`: write the
    reference's flat ``{var.name: value}`` npy (base_model.py:242-249), so
    a sat_tpu-trained model migrates BACK into the reference (its load()
    assigns by var name with missing-key tolerance, base_model.py:270-277)
    — and so the import path can be proven end-to-end offline by
    round-tripping a real trained state (RESULTS.md import-finetune run).

    Same name conventions the import consumes: decoder scopes verbatim
    (``word_embedding/weights:0``, ``attend/fc_1a/kernel:0``, …), the TF1
    LSTMCell under ``lstm/lstm_cell/`` with its concatenated (i,j,f,o)
    kernel unchanged, conv kernels HWIO as stored, BN as
    gamma/beta/moving_mean/moving_variance.  Optimizer slots are not
    exported (our optax state has no meaning to the reference's Adam).
    Returns the tensor count written."""
    # Mesh-sharded states (single- or multi-process): gather shards held
    # by other hosts first, then one batched D2H transfer — the same
    # discipline as state_to_flat; per-leaf np.asarray would crash on
    # non-addressable arrays and pay one transfer per tensor.
    gathered = jax.device_get(
        gather_tree_replicated(
            {"params": state.params, "batch_stats": state.batch_stats or {}}
        )
    )
    state = state._replace(
        params=gathered["params"], batch_stats=gathered["batch_stats"]
    )
    flat: Dict[str, np.ndarray] = {}
    dec = state.params.get("decoder", {})
    for scope, sub in dec.items():
        if scope == "lstm":
            for leaf, arr in sub.items():
                flat[f"lstm/lstm_cell/{leaf}:0"] = np.asarray(arr)
            continue
        for name, node in sub.items():
            if isinstance(node, dict):
                for leaf, arr in node.items():
                    flat[f"{scope}/{name}/{leaf}:0"] = np.asarray(arr)
            else:
                flat[f"{scope}/{name}:0"] = np.asarray(node)

    _export_cnn_tree(state.params.get("cnn", {}), flat)
    if getattr(state, "batch_stats", None):
        _export_cnn_tree(state.batch_stats, flat)

    flat["global_step:0"] = np.asarray(int(state.step), np.int64)
    atomic_write(
        path, "wb",
        lambda f: np.save(f, np.array(flat, dtype=object), allow_pickle=True),
    )
    return len(flat) - 1  # global_step is bookkeeping, not a tensor
