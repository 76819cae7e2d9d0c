"""``--phase bulk`` orchestrator: corpus → stepped decode → sharded JSONL.

Composes five existing planes into one crash-only offline workload
(docs/BULK.md):

* corpus walk + shard plan (:mod:`.corpus`) — pure functions of the
  input, never of chip count or restart history;
* the serve engine's AOT-warmed decode (``serve.engine`` lineage param
  load + quantize-once, ``serve.slot_pool`` continuous stepped decode)
  embedded headless — no HTTP, the zero-steady-state-recompile
  guarantee carried over unchanged;
* the quarantine plane (``resilience.quarantine``, and the shard
  cache's crc32c row integrity when one resolves): poison images are
  ledgered and deterministically substituted within their output shard,
  never fatal below the systemic ceiling (exit 87 above it);
* durable output (:mod:`.writer`) + the resume manifest
  (:mod:`.manifest`): kill -9 anywhere and relaunch (``--supervise``) —
  completed shards are verified and skipped, the interrupted shard is
  re-decoded from its first row, and the final corpus of output files
  is bitwise-identical to an uninterrupted run;
* observability: ``bulk/*`` gauges (images done, captions/s, ETA,
  quarantined count, steady-state compiles) on the heartbeat, the
  watchdog's phase guards over assembly/decode/write, and the black-box
  flight recorder when ``--blackbox`` is on.

Module-level imports stay jax-free (the jax-free import test covers
this module); jax and the serve stack load lazily inside
:func:`run_bulk`.
"""

from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import telemetry
from ..config import Config
from ..telemetry.metering import measured_busy_ms
from ..resilience.faultinject import FaultPlan
from ..resilience.preempt import GracefulShutdown
from ..resilience.quarantine import (
    QuarantineManager,
    SystemicCorruption,
    ledger_path_for,
)
from ..resilience.watchdog import Watchdog, deadlines_from_config
from .corpus import plan_shards, resolve_corpus
from .manifest import (
    corpus_fingerprint,
    load_manifest,
    manifest_path_for,
    mark_completed,
    new_manifest,
    write_manifest,
)
from .writer import ShardWriter, verify_shard


def _log(msg: str) -> None:
    print(f"sat_tpu: {msg}", file=sys.stderr, flush=True)


def _assemble_rows(
    shard_files: List[str],
    engine,
    cache,
    quarantine: QuarantineManager,
    num_workers: int,
) -> Tuple[np.ndarray, Dict[int, dict]]:
    """Decode one output shard's images into a [n,S,S,3] batch in the
    engine's input dtype, containing poison rows exactly like the train
    feed does (``data.images.PrefetchLoader``): ledger each newly bad
    row, then overwrite it with a deterministically chosen healthy row
    OF THE SAME OUTPUT SHARD.  Keying the substitution to the shard —
    not the pool geometry or admission timing — is what makes it stable
    across restarts and chip-count changes (the bitwise-resume rule).

    Returns ``(batch, meta)`` where ``meta[i]`` marks substituted rows
    for the output writer.  The marker deliberately omits the detection
    reason: a first run sees ``decode_failed`` where a resumed run sees
    ``replayed_ledger`` for the same file, and output bytes must not
    depend on which run wrote them.
    """
    n = len(shard_files)
    loader = engine.loader
    q = quarantine
    q.note_rows(n)
    bad: List[tuple] = []  # (row, file, reason, exc)
    flagged: set = set()
    # replayed ledger: substitute known-bad files proactively — a file
    # repaired since the original run must not change the replay
    for i, f in enumerate(shard_files):
        if q.known_bad_file(f):
            bad.append((i, f, "replayed_ledger", None))
            flagged.add(i)
    if cache is not None:
        gather_bad: List[tuple] = []
        raw = cache.gather(
            shard_files, fallback=loader.load_raw, bad_rows=gather_bad
        )
        for i, f, reason, exc in gather_bad:
            if i not in flagged:
                bad.append((i, f, reason, exc))
                flagged.add(i)
    else:
        size = loader.size
        raw = np.zeros((n, size, size, 3), np.uint8)

        def _load_one(i):
            if i in flagged:
                return i, None, None
            try:
                return i, loader.load_raw(shard_files[i]), None
            except Exception as e:
                return i, None, e

        with ThreadPoolExecutor(max_workers=max(1, num_workers)) as tp:
            for i, img, exc in tp.map(_load_one, range(n)):
                if img is not None:
                    raw[i] = img
                elif exc is not None:
                    bad.append((i, shard_files[i], "decode_failed", exc))
                    flagged.add(i)
    meta: Dict[int, dict] = {}
    if bad:
        bad_set = {b[0] for b in bad}
        healthy = [i for i in range(n) if i not in bad_set]
        for i, f, reason, exc in sorted(bad, key=lambda b: b[0]):
            if reason != "replayed_ledger":
                # may raise SystemicCorruption (the run-level ceiling)
                q.quarantine(f, reason, kind="image", exc=exc)
            if not healthy:
                raise SystemicCorruption(
                    f"every row of output shard holding {f!r} is "
                    "quarantined — no healthy row to substitute; the "
                    "corpus is systemically corrupt"
                )
            j = healthy[
                QuarantineManager.substitute_index(f"image:{f}", len(healthy))
            ]
            raw[i] = raw[j]
            meta[i] = {"quarantined": True, "substituted_from": shard_files[j]}
    # final preprocessing step, batch-wise — elementwise identical to the
    # live path's per-image version (see data.images)
    batch = raw if loader.raw else raw.astype(np.float32) - loader.mean
    return batch, meta


def _decode_shard(
    engine, pool, batch: np.ndarray, fp: FaultPlan, wd: Watchdog,
    step_counter: int,
) -> Tuple[List[Any], int]:
    """Run one assembled shard through the continuous stepped decode:
    admit rows as slots free up, run one fused ``decode_multi_step``
    window over the whole pool, harvest finished beams early.  The
    window depth rides the same queue-pressure policy as the serve loop
    (``batcher.choose_decode_depth``): K=1 while corpus rows are still
    waiting for a slot (a freed slot reseeds at the very next dispatch),
    the deepest warmed lane once everything is submitted (the tail
    amortizes one host round-trip over K device steps).  Returns per-row
    caption lists (row order) and the advanced pool-step counter (the
    fault-injection clock — ``SAT_FI_DIE_AT_STEP`` counts decode steps
    across shards, so the counter advances by the steps actually run in
    each window, keeping the chaos clock step-denominated).  With the
    quality plane on (``--serve_quality on``) each row also gets the
    flywheel's curation signals (margin / normalized log-prob / unk
    rate / coverage deviation) — pure host arithmetic on the already-
    drained harvest arrays, rounded so output stays bitwise
    deterministic; off leaves the output bytes untouched."""
    from ..serve.batcher import choose_decode_depth
    from ..telemetry.quality import extract_signals

    want_quality = engine.config.serve_quality == "on"
    vocab_size = len(engine.vocabulary.words)
    n = batch.shape[0]
    results: List[Any] = [None] * n
    quality_rows: List[Any] = [None] * n
    submitted = 0
    harvested = 0
    while harvested < n:
        fp.maybe_kill(step_counter)
        fp.maybe_wedge(step_counter)
        fp.maybe_slow(step_counter)
        free = pool.free_count()
        if free and submitted < n:
            take = min(free, n - submitted)
            items = [(batch[i], i) for i in range(submitted, submitted + take)]
            with wd.phase("dispatch"):
                submitted += pool.admit(items)
        k = choose_decode_depth(pool.decode_depths, n - submitted, 0)
        with wd.phase("dispatch"):
            done, steps_dev = pool.multi_step(k)
        # whole [S] flag drain, decisions on the HOST — a device-side
        # reduction at varying occupancy would recompile (slot_pool rule)
        done_host = np.asarray(done)  # sync-ok: stepped-decode drain boundary, whole-array transfer
        step_counter += int(np.asarray(steps_dev))  # sync-ok: same drain boundary as the done flags
        if done_host.any():
            payloads, words, lengths, scores, _steps, alphas = pool.harvest(
                done_host
            )
            if payloads:
                rows = engine.detok_rows((words, lengths, scores), len(payloads))
                for j, (payload, row) in enumerate(zip(payloads, rows)):
                    results[payload] = row["captions"]
                    if want_quality:
                        sig = extract_signals(
                            words[j], lengths[j], scores[j],
                            vocab_size=vocab_size, eos_id=engine.eos_id,
                            alphas=None if alphas is None else alphas[j],
                        )
                        quality_rows[payload] = {
                            k: round(sig[k], 6)
                            for k in (
                                "margin", "norm_logprob", "unk_rate",
                                "coverage_dev",
                            )
                            if k in sig
                        }
                    harvested += 1
    return results, quality_rows, step_counter


def run_bulk(config: Config, model_file: Optional[str] = None) -> int:
    """CLI entry point: ``python -m sat_tpu.cli --phase bulk``."""
    if not config.bulk_output:
        raise ValueError("--bulk_output is required for --phase bulk")
    files = resolve_corpus(config.bulk_input)
    shards = plan_shards(files, config.bulk_shard_rows)
    out_dir = config.bulk_output
    os.makedirs(out_dir, exist_ok=True)

    # ---- resume frontier: manifest + output-file verification --------
    mpath = manifest_path_for(out_dir)
    sha = corpus_fingerprint(files, config.bulk_shard_rows, config.image_size)
    manifest = load_manifest(mpath)
    if manifest is not None and manifest.get("corpus_sha") != sha:
        _log(
            "bulk: corpus or shard geometry changed since the last run — "
            "restarting from an empty frontier"
        )
        manifest = None
    if manifest is None:
        manifest = new_manifest(files, config.bulk_shard_rows, config.image_size)
    completed = manifest["completed"]
    for k in sorted(list(completed), key=int):
        entry = completed[k]
        path = os.path.join(out_dir, entry["file"])
        if not verify_shard(
            path, expect_rows=entry["rows"], expect_crc=entry["crc32c"]
        ):
            _log(f"bulk: completed shard {k} failed verification — re-decoding")
            del completed[k]
    # a kill -9 mid-shard leaves only a .tmp orphan; resume re-decodes
    # that shard from its first row, so the orphan is just garbage
    for name in os.listdir(out_dir):
        if name.endswith(".jsonl.tmp"):
            os.unlink(os.path.join(out_dir, name))
    pending = [i for i in range(len(shards)) if str(i) not in completed]
    write_manifest(mpath, manifest)  # persist the verified frontier
    resumed_rows = sum(len(shards[i]) for i in range(len(shards)) if str(i) in completed)
    _log(
        f"bulk: {len(files)} images in {len(shards)} output shards of "
        f"{config.bulk_shard_rows} ({len(shards) - len(pending)} already "
        f"complete, {len(pending)} to decode) -> {out_dir}"
    )
    if not pending:
        _log("bulk: nothing to do — all output shards verified complete")
        return 0

    # ---- decode-plane boot (mirrors serve.server.serve) --------------
    tel = telemetry.get()
    if not tel.enabled:
        # bulk always records: the zero-recompile assertion and the
        # bulk/* progress gauges ride the counter/gauge plane
        tel = telemetry.enable(capacity=config.telemetry_buffer)
    from ..runtime import _install_compile_listener

    _install_compile_listener()
    from ..data.shards import resolve_shard_cache
    from ..data.vocabulary import Vocabulary
    from ..serve.engine import ServeEngine, load_serving_state
    from ..serve.slot_pool import PagedSlotPool

    vocabulary = Vocabulary(config.vocabulary_size, config.vocabulary_file)
    state, source = load_serving_state(config, model_file=model_file)
    engine = ServeEngine(config, state, vocabulary, tel=tel)
    _log(f"bulk: captioning with params from {source} (step {engine.step})")
    # the slot pool warms its own programs; the engine's bucket ladder
    # (engine.warmup) is dead weight here, exactly as in continuous serve
    pool = PagedSlotPool(engine, tel=tel)
    pool.warmup()

    quarantine = QuarantineManager(
        ledger_path_for(config), max_fraction=config.quarantine_max_fraction
    )
    cache = resolve_shard_cache(config, files)

    tdir = config.telemetry_dir or os.path.join(config.summary_dir, "telemetry")
    wd = Watchdog(
        deadlines_from_config(config),
        poll_s=config.watchdog_interval or 1.0,
        grace_s=config.watchdog_grace_s,
        dump_path=os.path.join(tdir, "watchdog_stacks.txt"),
        tel=tel,
    )
    bb = None
    if config.blackbox:
        from ..telemetry import blackbox as _blackbox

        bb = _blackbox.BlackBox(os.path.join(tdir, "blackbox"), tel)
        _blackbox.install(bb, telemetry_dir=tdir, config_snapshot=config.to_dict())
        bb.event(
            "bulk_start",
            total_images=len(files),
            pending_shards=len(pending),
            model_step=engine.step,
        )
    hb = None
    if config.heartbeat_interval > 0:
        from ..telemetry.heartbeat import Heartbeat

        hb = Heartbeat(
            os.path.join(tdir, "heartbeat.json"),
            config.heartbeat_interval,
            tel,
            static={"phase": "bulk", "bulk_output": out_dir},
        )
        hb.start()
    if config.watchdog_interval > 0:
        wd.start()

    fp = FaultPlan.from_env()
    total = len(files)
    images_done = resumed_rows
    decoded_this_run = 0
    step_counter = 0
    t0 = time.perf_counter()

    def _progress_gauges() -> None:
        elapsed = time.perf_counter() - t0
        rate = decoded_this_run / elapsed if elapsed > 0 else 0.0
        tel.gauge("bulk/images_done", images_done)
        tel.gauge("bulk/images_total", total)
        tel.gauge("bulk/shards_done", len(completed))
        tel.gauge("bulk/shards_total", len(shards))
        tel.gauge("bulk/captions_per_s", round(rate, 3))
        if rate > 0:
            tel.gauge("bulk/eta_s", round((total - images_done) / rate, 1))
        tel.gauge("bulk/quarantined", quarantine.total)
        # the fault-injection clock, exported: a chaos harness reads the
        # control run's total to aim SAT_FI_DIE_AT_STEP mid-corpus
        tel.gauge("bulk/decode_steps", step_counter)
        tel.gauge(
            "bulk/steady_compiles",
            tel.counters().get("jax/compiles", 0) - engine.compiles_at_ready,
        )
        # unit cost for capacity planning: measured device-busy ms
        # (encode + decode spans) over images finished this run — the
        # same busy-span definition the serve-side metering reconciles
        # its per-request attribution against
        if decoded_this_run > 0:
            tel.gauge(
                "bulk/device_ms_per_image",
                round(measured_busy_ms(tel) / decoded_this_run, 3),
            )

    _progress_gauges()
    interrupted = False
    try:
        with GracefulShutdown() as shutdown:
            for shard_idx in pending:
                if shutdown.stop_requested:
                    # graceful SIGTERM/SIGINT: stop at the shard boundary —
                    # the manifest already records everything completed
                    interrupted = True
                    break
                with wd.phase("step"):
                    shard_files = shards[shard_idx]
                    with wd.phase("data_wait"):
                        batch, meta = _assemble_rows(
                            shard_files, engine, cache, quarantine,
                            config.num_data_workers,
                        )
                    results, qrows, step_counter = _decode_shard(
                        engine, pool, batch, fp, wd, step_counter
                    )
                    with wd.phase("checkpoint"):
                        writer = ShardWriter(out_dir, shard_idx)
                        try:
                            for i, f in enumerate(shard_files):
                                row = {"file": f, "captions": results[i]}
                                if qrows[i] is not None:
                                    # flywheel curation signals; keyed
                                    # fields only, rounded at extraction
                                    # so the bytes stay deterministic
                                    row["quality"] = qrows[i]
                                row.update(meta.get(i, ()))
                                writer.write_row(row)
                            fname, rows, crc = writer.finish()
                        except BaseException:
                            writer.abort()
                            raise
                        mark_completed(manifest, shard_idx, fname, rows, crc)
                        write_manifest(mpath, manifest)
                images_done += len(shard_files)
                decoded_this_run += len(shard_files)
                _progress_gauges()
                if bb is not None:
                    bb.event(
                        "bulk_shard_done", shard=shard_idx, rows=len(shard_files)
                    )
    except Exception as e:
        if bb is not None:
            bb.event("bulk_failed", error=repr(e))
        raise
    finally:
        if hb is not None:
            hb.stop()
        wd.stop()

    steady = tel.counters().get("jax/compiles", 0) - engine.compiles_at_ready
    tel.gauge("bulk/steady_compiles", steady)
    if steady:
        _log(
            f"bulk: WARNING — {steady} steady-state XLA recompiles after "
            "warmup (expected 0; a shape leaked past the AOT programs)"
        )
    if interrupted:
        _log(
            f"bulk: drained at shard boundary on {shutdown.signal_name or 'signal'} "
            f"— {images_done}/{total} images captioned; relaunch to resume"
        )
        if bb is not None:
            bb.event("bulk_drained", images_done=images_done)
        return 0
    elapsed = time.perf_counter() - t0
    rate = decoded_this_run / elapsed if elapsed > 0 else 0.0
    unit_ms = (
        measured_busy_ms(tel) / decoded_this_run if decoded_this_run else 0.0
    )
    _log(
        f"bulk: complete — {images_done}/{total} images in "
        f"{len(shards)} shards ({decoded_this_run} decoded this run, "
        f"{rate:.1f} captions/s, {unit_ms:.1f} device-ms/image, "
        f"{quarantine.total} quarantined)"
    )
    if bb is not None:
        bb.event("bulk_complete", images=images_done, quarantined=quarantine.total)
    return 0
