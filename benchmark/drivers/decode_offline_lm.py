"""``decode_offline_lm``: ``--phase=eval --beam_size=K`` over a generated
val set with a LANGUAGE-MODEL caption decoder (``Config.decoder`` other
than the LSTM): the loader, the encoder, the prefill of the image prefix,
``ops/beam_search.py`` over a cache of two kinds, the drain and
detokenisation.  README-lm.md beside the other READMEs describes it.

The control flow is ``decode_offline``'s (one process; the program's
``runtime.beam_search_jit`` wrapped so that the benchmark reads its clock
at every dispatch and keeps every batch's tokens and scores; the wrapper
raises when the window has closed).  What differs is the set-up and the
check, which ``drivers/common.py`` binds to the LSTM captioner's weights
and reference:

* refuses at once, before any weight is made, where the program has no
  ``decoder`` field (a program from before this configuration);
* weights from ``reference/params_lfm2.py`` (host numpy, gigabytes), with
  the connector's bias and every ``expert_bias`` fitted by the reference on
  a seeded calibration batch (``lfm2_captioner.calibrate``: the routing a
  trained router has, every expert taking its share; kept beside the
  checkpoint as ``fitted.npz`` for the check); vocabulary from this file's
  word generator (``datagen.words`` ends at 6,402); all written ONCE per
  seed under ``models0/`` and read by the run in place (eval writes no
  checkpoint); ``.work/<cell>/`` keeps ONE seed;
* ``correct`` against ``reference/lfm2_captioner.py``: the eval mix's
  numbers (``score_gap``, ``score_gap_mean``, ``rank_gap``, no compile in
  the window, captions well formed) plus ``route_agreement``: the share of
  sampled (position, layer) choices of experts on which the TIMED beam
  program (the record it returns with its results: every prefix position
  of its prefill, every step of the served beam's own ancestry) and the
  reference agree, floor in the mix's ``limits.route_agreement_min``.

Mix parameters: distinct_images, image_ids, calibration_images,
warm_batches, sample_batches, sample_rows, trace_seconds, limits, program.
Sabotage (tests): "token" alters one served token; "no_expert_bias" zeroes
``expert_bias`` in the checkpoint the program loads, and only there.
"""

from __future__ import annotations

import dataclasses
import gc
import itertools
import json
import os
import shutil
import threading
import time

import numpy as np

import datagen
import harness
from drivers import common
from drivers.decode_offline import WindowClosed
from reference import check as refcheck

_CONSONANTS, _VOWELS = "bdfghjklmnprstvz", "aeiou"
_EOS = 1                                  # '.' in ``words``: the caption's terminator


def words(vocabulary_size: int):
    """['<start>', '.', then consonant-vowel words of two, then three
    syllables], in index order: ``datagen.words`` continued past its
    6,402.  Lower-case letters only, the same list in every run."""
    syll = [c + v for c in _CONSONANTS for v in _VOWELS]                 # 80
    out = ["<start>", "."]
    for n_syll in (2, 3):
        more = itertools.product(syll, repeat=n_syll)
        out += ["".join(w) for w in itertools.islice(more, vocabulary_size - len(out))]
    if len(out) < vocabulary_size:
        raise ValueError(f"vocabulary_size {vocabulary_size} exceeds the word pool")
    return out


def write_vocabulary(path: str, vocabulary_size: int):
    """The program's vocabulary.csv (pandas CSV: word, index, frequency)."""
    import pandas as pd

    w = words(vocabulary_size)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    pd.DataFrame({"word": w, "index": list(range(len(w))),
                  "frequency": np.zeros(len(w))}).to_csv(path)
    return w


class LMBeamRecorder(common.Recorder):
    """``decode_offline.BeamRecorder`` for a program whose beam results
    carry the decoder's own report (``BeamResult.decoder_stats``: expert
    counts and chosen experts), kept on the device until the check."""

    def __init__(self, fn, sabotage=None) -> None:
        super().__init__(fn)
        self.sabotage = sabotage
        self.times, self.live, self.outs = [], [], []
        self.stop = threading.Event()

    def __call__(self, *a, **kw):
        if self.stop.is_set():
            raise WindowClosed()
        self.times.append(time.perf_counter_ns())
        out = self._fn(*a, **kw)
        self.live.append(harness.memory_stats()["bytes_in_use"])    # this batch in flight
        searched = out.words                      # what the search returned (the record of routes is its)
        if self.sabotage == "token":
            out = out._replace(words=out.words.at[:, 0, 3].add(1))
        self.outs.append((out.words, out.lengths, out.log_scores, out.decoder_stats, searched))
        return out


def _calibration_batch(cell: harness.Cell, kept: str, seed: int):
    """The seeded batch the reference fits the router's balance on: images
    of the traffic's generator that the run never serves, and captions of
    words drawn evenly from the vocabulary."""
    n, T, V = int(cell.mix["calibration_images"]), cell.model["max_caption_length"], cell.model["vocabulary_size"]
    folder = os.path.join(kept, "calibration")
    files = datagen.make_images(folder, n, cell.model["image_size"], seed + 1)
    images = np.stack([datagen.read_rgb(os.path.join(folder, f)) for f in files])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5])
    return images, rng.integers(2, V, size=(n, T)).astype(np.int32)


def _setup(cell: harness.Cell, kept: str, work: str, reused: bool, seed: int, sabotage):
    """The program's Config and, once per seed, its inputs under ``kept``:
    vocabulary, JPEGs, COCO file, and the step-0 checkpoint of the seeded
    weights under ``models0/``, which the run reads in place."""
    from reference import lfm2_captioner, params_lfm2

    models0 = os.path.join(kept, "models0")
    config = harness.program_config(cell, kept, work, seed, phase="eval", save_dir=models0)
    n_files = int(cell.mix["distinct_images"])
    files = [f"img_{i:06d}.jpg" for i in range(n_files)]
    ids = list(range(1, int(cell.mix["image_ids"]) + 1))
    if not reused:
        datagen.make_images(os.path.join(kept, "val", "images"), n_files,
                            cell.model["image_size"], seed)
        datagen.write_coco(os.path.join(kept, "val", "captions.json"), files, ids,
                           [["a generated image."]] * len(ids))
        write_vocabulary(config.vocabulary_file, cell.model["vocabulary_size"])
        t0 = time.perf_counter()
        weights = params_lfm2.make_weights(cell.model, seed)
        t1 = time.perf_counter()
        fitted = lfm2_captioner.calibrate(
            cell.model, weights, *_calibration_batch(cell, kept, seed),
            block=int(cell.mix["sample_batches"]) * int(cell.mix["sample_rows"]))
        np.savez(os.path.join(kept, "fitted.npz"), **fitted)
        weights.update(fitted)
        t2 = time.perf_counter()
        if sabotage == "no_expert_bias":
            weights = {k: np.zeros_like(v) if k.endswith("/expert_bias") else v
                       for k, v in weights.items()}
        harness.write_checkpoint(config, weights, models0)
        print(f"benchmark: weights made in {t1 - t0:.1f} s, router balance fitted in {t2 - t1:.1f} s, "
              f"checkpoint written and verified in {time.perf_counter() - t2:.1f} s", flush=True)
        del weights
        gc.collect()
        harness.mark_complete(kept)
    path = os.path.join(work, "config.json")
    config.save(path)
    with np.load(os.path.join(kept, "fitted.npz")) as z:
        fitted = {k: z[k] for k in z.files}
    return config, path, files, ids, fitted


def _keep_one_seed(kept: str) -> None:
    """This driver's own rule: a seed's checkpoint is gigabytes, so
    ``.work/<cell>/`` holds the current seed's directory and no other."""
    base = os.path.dirname(kept)
    for d in os.listdir(base):
        if os.path.join(base, d) != kept:
            shutil.rmtree(os.path.join(base, d), ignore_errors=True)


def route_agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Share of (layer, row, position) whose sets of chosen experts agree."""
    return float((np.sort(a, axis=-1) == np.sort(b, axis=-1)).all(axis=-1).mean())


def limit_checks(numbers: dict, limits: dict):
    """The mix's limits over one set of numbers (the program's, or the
    control's in its place): ceilings through ``common.limit_checks``;
    ``route_agreement`` is held to its floor ``route_agreement_min``."""
    limits = dict(limits)
    floor = float(limits.pop("route_agreement_min"))
    checks = common.limit_checks({k: numbers[k] for k in limits}, limits)
    checks.append({"name": "route_agreement", "value": bool(numbers["route_agreement"] >= floor), "limit": None})
    return checks


def failed(checks) -> list:
    return [c["name"] for c in checks
            if not (c["value"] <= c["limit"] if c.get("limit") is not None else c["value"])]


def run(cell: harness.Cell, args, env) -> common.Outcome:
    from sat_tpu.config import Config

    if "decoder" not in {f.name for f in dataclasses.fields(Config)}:
        raise harness.BenchError(
            f"the program has no Config.decoder: it cannot run configuration {cell.entry['config']!r}")
    from sat_tpu import cli, runtime, telemetry
    from reference import lfm2_captioner

    if cell.rehearsal:
        cell.model.update(cell.config["rehearsal_model"])
    mix, seed = cell.mix, args.seed
    sabotage = getattr(args, "sabotage", None)
    kept, work, reused = cell.workdir(seed, sabotage)
    _keep_one_seed(kept)
    config, cfg_path, files, ids, fitted = _setup(cell, kept, work, reused, seed, sabotage)
    beam, B, T = config.beam_size, config.batch_size, config.max_caption_length

    rec = LMBeamRecorder(runtime.beam_search_jit, sabotage)
    original, runtime.beam_search_jit = runtime.beam_search_jit, rec
    warm = int(mix["warm_batches"])
    window, done = {}, threading.Event()
    tracer = (harness.TraceWindow(os.path.join(work, "trace"), float(mix["trace_seconds"]))
              if args.trace else None)

    def control() -> None:
        common.wait_for(lambda: len(rec.times) > warm, 3000.0, "the decode loop's warm-up",
                        alive=lambda: not done.is_set())
        t0 = rec.times[warm]
        window["ns"] = (t0, t0 + int(args.seconds * 1e9))
        common.sleep_until(window["ns"][1] - (int(tracer.seconds * 1e9) if tracer else 0))
        if tracer:                    # the window's last stretch; stop_trace's cost falls after it
            tracer.run()
        rec.stop.set()

    controller = threading.Thread(target=control, name="bench-controller", daemon=True)
    controller.start()
    closed = False
    try:
        cli.main(["--phase=eval", f"--beam_size={beam}", "--config", cfg_path, "--telemetry"])
    except WindowClosed:
        closed = True
    finally:
        done.set()
        runtime.beam_search_jit = original
    controller.join(timeout=30.0)
    if not closed or "ns" not in window:
        raise harness.BenchError("the val set ran out before the window closed: raise image_ids")

    run_ = harness.RunData(cell, common.span_window(window["ns"], tracer), env.peaks)
    run_.take_spans(telemetry.get())
    inside = [i for i, t in enumerate(rec.times) if window["ns"][0] <= t <= window["ns"][1]]
    memory = harness.memory_peak([rec.live[i] for i in inside if i < len(rec.live)],
                                 harness.program_temps("decode/encode", "decode/beam_search"))
    if len(inside) < 4:
        raise harness.BenchError(f"only {len(inside)} batches were dispatched inside the window")
    a, b = inside[0], inside[-1]
    run_.measured["captions_per_s"] = (b - a) * B / ((rec.times[b] - rec.times[a]) / 1e9)
    run_.measured["setup_s"] = (window["ns"][0] - env.t_start_ns) / 1e9
    done_batches = inside[:-1]                      # the last may not have been drained
    loads, visited, visited_first = [], [], []
    for bi in done_batches:                         # the program's counters, batch by batch
        stats = rec.outs[bi][3]
        counts = np.asarray(stats["moe_counts"], np.float64)
        loads.append(float((counts.max(axis=1) / counts.mean(axis=1)).max()))
        visits = np.asarray(stats["moe_step_visits"])           # [expert layers, T]
        visited.append(int(visits[:, 1:].min())), visited_first.append(int(visits[:, 0].min()))
    run_.extras.update(compile_s=env.meter.seconds_before(window["ns"][0]),
                       batches_in_window=b - a, batch_size=B, trace_dir=os.path.join(work, "trace"),
                       moe_load_max_over_mean=loads, beam_size=beam, caption_steps=T)
    if tracer:
        common.take_trace(run_, tracer)

    # ---- correct: a seeded sample of the captions the window produced
    checks = [{"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window["ns"])}]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    pick = [done_batches[j] for j in common.sample_indices(rng, len(done_batches), int(mix["sample_batches"]))]
    rows = int(mix["sample_rows"])
    k, N = config.num_experts_per_tok, config.num_ctx
    tokens, lengths, scores, paths, routes_p = [], [], [], [], []
    well_formed, ended_early = True, []
    for bi in pick:
        words_, lens, logp = (np.asarray(x) for x in rec.outs[bi][:3])
        ended_early.append(float((lens[:, 0] < T).mean()))
        well_formed &= bool((lens[:, 0] >= 1).all() and (lens[:, 0] <= T).all()
                            and (words_ >= 0).all() and (words_ < cell.model["vocabulary_size"]).all())
        longest = int(np.argmax(lens[:, 0]))
        chosen = common.sample_indices(rng, B, rows, must=longest)
        stats, searched = rec.outs[bi][3], np.asarray(rec.outs[bi][4])
        prefix = np.asarray(stats["prefix_routes"][np.asarray(chosen)])           # [rows, N, layers * k]
        steps = np.asarray(stats["step_routes"][np.asarray(chosen), 0])          # [rows, T, layers * k]
        for j, r in enumerate(chosen):
            tokens.append(words_[r, 0]), lengths.append(int(lens[r, 0])), scores.append(float(logp[r, 0]))
            image_id = ids[bi * B + r]
            paths.append(os.path.join(kept, "val", "images", files[(image_id - 1) % len(files)]))
            # the record is a LIVE beam's: the served caption is live beam 0
            # where it never ended (then no caption of the image did)
            live = lengths[-1] == T and _EOS not in searched[r, 0]
            routes_p.append(np.concatenate([prefix[j], steps[j]]).reshape(N + T, -1, k).swapaxes(0, 1)
                            if live else None)
    checks.append({"name": "captions_well_formed", "value": well_formed, "limit": None})
    tokens = np.stack(tokens).astype(np.int32)
    rec.outs = []                                    # the records leave the chip
    gc.collect()
    images = np.stack([datagen.read_rgb(p) for p in paths])
    t0 = time.perf_counter()
    ref_logits, routes_r = lfm2_captioner.served_logits(cell.model, seed, images, tokens, fitted=fitted)
    reference_s = time.perf_counter() - t0
    live = [i for i, r in enumerate(routes_p) if r is not None]
    if not live:
        raise harness.BenchError("no sampled caption ran all its steps: there is no record of routes to compare")
    got = refcheck.served_numbers(ref_logits, tokens, lengths, scores, beam)
    got["route_agreement"] = route_agreement(np.stack([routes_p[i] for i in live], axis=1), routes_r[:, live])
    print(json.dumps({"route_agreement": got["route_agreement"], "floor": mix["limits"]["route_agreement_min"],
                      "choices": int(routes_r[:, live, :, 0].size)}), flush=True)
    checks += limit_checks(got, mix["limits"])
    control = None
    if getattr(args, "control", 0):               # the nearest precision below, in the program's place
        low_logits, low_routes = lfm2_captioner.served_logits(cell.model, seed, images, tokens, mode="fp8",
                                                              fitted=fitted)
        low = {**refcheck.control_numbers(ref_logits, low_logits, tokens, lengths, beam),
               "route_agreement": route_agreement(low_routes, routes_r)}
        control = {"fp8": {**low, "fails": failed(limit_checks(low, mix["limits"]))}}
    return common.Outcome(run_, checks, attempted=(b - a) * B, failed=0,
                          memory_peak_bytes=memory["peak"],
                          notes={"control": control, "memory": memory, "reused": reused,
                                 "score_gap_mean": got["score_gap_mean"], "route_agreement": got["route_agreement"],
                                 "route_captions": len(live), "experts_visited_a_step": min(visited),
                                 "experts_visited_at_step_0": min(visited_first),
                                 "captions_ended_early": float(np.mean(ended_early)),
                                 "reference_s": reference_s,
                                 "trace_timing": run_.extras.get("trace_timing"),
                                 "served_tokens": int(sum(lengths)), "captions": len(lengths)})
