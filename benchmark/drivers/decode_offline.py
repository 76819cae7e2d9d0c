"""``decode_offline``: ``--phase=eval --beam_size=K`` over a generated val
set — the loader, the encoder, ``ops/beam_search.py`` with the Pallas
kernel, the drain and detokenisation.

One process (see train_job).  ``runtime.beam_search_jit`` is wrapped: the
benchmark reads its clock at every dispatch (dispatch b follows the drain
of batch b-2, so dispatches and drains keep step) and keeps every batch's
tokens and scores.  When the window has closed the wrapper raises: the
val set lists its ``distinct_images`` JPEGs under ``image_ids`` ids, more
than any window can drain, and the COCO scorers are never reached (they
are not part of what is timed).

Mix parameters: distinct_images, image_ids, warm_batches, sample_batches,
sample_rows, trace_seconds, limits, program.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

import datagen
import harness
from drivers import common


class WindowClosed(Exception):
    pass


class BeamRecorder(common.Recorder):
    sabotage = None          # tests: "token" alters one token where it is produced

    def __init__(self, fn) -> None:
        super().__init__(fn)
        self.times, self.live, self.outs = [], [], []
        self.stop = threading.Event()

    def __call__(self, *a, **kw):
        if self.stop.is_set():
            raise WindowClosed()
        self.times.append(time.perf_counter_ns())
        out = self._fn(*a, **kw)
        self.live.append(harness.memory_stats()["bytes_in_use"])    # this batch in flight
        if self.sabotage == "token":
            out = out._replace(words=out.words.at[:, 0, 3].add(1))
        self.outs.append((out.words, out.lengths, out.log_scores))
        return out


def run(cell: harness.Cell, args, env) -> common.Outcome:
    from sat_tpu import cli, runtime, telemetry

    mix, seed = cell.mix, args.seed
    kept, work, reused = cell.workdir(seed)
    n_files = int(mix["distinct_images"])
    files = [f"img_{i:06d}.jpg" for i in range(n_files)]
    ids = list(range(1, int(mix["image_ids"]) + 1))
    if not reused:
        files = datagen.make_images(os.path.join(kept, "val", "images"), n_files,
                                    cell.model["image_size"], seed)
        datagen.write_coco(os.path.join(kept, "val", "captions.json"), files, ids,
                           [["a generated image."]] * len(ids))
    config, cfg_path, _ = common.seeded_setup(cell, kept, work, reused, seed, phase="eval")
    beam, B, T = config.beam_size, config.batch_size, config.max_caption_length

    rec = BeamRecorder(runtime.beam_search_jit)
    rec.sabotage = getattr(args, "sabotage", None)
    original, runtime.beam_search_jit = runtime.beam_search_jit, rec
    warm = int(mix["warm_batches"])
    window, done = {}, threading.Event()
    tracer = (harness.TraceWindow(os.path.join(work, "trace"), float(mix["trace_seconds"]))
              if args.trace else None)

    def control() -> None:
        common.wait_for(lambda: len(rec.times) > warm, 1500.0, "the decode loop's warm-up",
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
    run_.extras.update(compile_s=env.meter.seconds_before(window["ns"][0]),
                       batches_in_window=b - a, batch_size=B, trace_dir=os.path.join(work, "trace"))
    if tracer:
        common.take_trace(run_, tracer)

    # ---- correct: a seeded sample of the captions the window produced
    checks = [{"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window["ns"])}]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    done_batches = inside[:-1]                      # the last may not have been drained
    pick = common.sample_indices(rng, len(done_batches), int(mix["sample_batches"]))
    rows = int(mix["sample_rows"])
    tokens, lengths, scores, paths = [], [], [], []
    well_formed = True
    for j in pick:
        bi = done_batches[j]
        words, lens, logp = (np.asarray(x) for x in rec.outs[bi])
        well_formed &= bool((lens[:, 0] >= 1).all() and (lens[:, 0] <= T).all()
                            and (words >= 0).all() and (words < cell.model["vocabulary_size"]).all())
        longest = int(np.argmax(lens[:, 0]))
        for r in common.sample_indices(rng, B, rows, must=longest):
            tokens.append(words[r, 0]), lengths.append(int(lens[r, 0])), scores.append(float(logp[r, 0]))
            image_id = ids[bi * B + r]
            paths.append(os.path.join(kept, "val", "images", files[(image_id - 1) % len(files)]))
    checks.append({"name": "captions_well_formed", "value": well_formed, "limit": None})
    rec.outs = []
    images = np.stack([datagen.read_rgb(p) for p in paths])
    got = common.check_served(cell, seed, images, np.stack(tokens).astype(np.int32),
                              lengths, scores, beam)
    checks += common.limit_checks({k: got[k] for k in mix["limits"]}, mix["limits"])
    control = (common.control_served(cell, seed, images, np.stack(tokens).astype(np.int32), lengths,
                                     got["logits"], beam) if getattr(args, "control", 0) else None)
    return common.Outcome(run_, checks, attempted=(b - a) * B, failed=0,
                          memory_peak_bytes=memory["peak"],
                          notes={"control": control, "memory": memory, "reused": reused, "score_gap_mean": got["score_gap_mean"], "trace_timing": run_.extras.get("trace_timing"), "served_tokens": int(sum(lengths)), "captions": len(lengths)})
