"""``decode_offline_gqa``: ``--phase=eval --beam_size=K`` over a generated
val set of 1,536-px images with the command-a-plus caption decoder
(``Config.decoder = "cohere2_moe"``: a PARALLEL block, one LayerNorm for
attention and feed-forward alike, over grouped-query attention of 128 query
/ 8 key-value heads: rotary sliding layers over the last 4,096 of
9,217-9,236 positions beside a full layer with no positional term; 16 of
128 routed experts held beside four shared experts AVERAGED; a tied head
over the vocabulary's slice): ``decode_offline_swa``'s control flow, bound
to this configuration's weights (``reference/params_cohere2.py``) and
reference (``reference/cohere2_captioner.py``); the recorder, the word
generator, the route comparison and ``limit_checks`` are imported from
``decode_offline_lm``, the rule that no seed is kept from
``decode_offline_dsa``.  README-gqa.md describes it.  This is the FOURTH
copy of ``_run`` (lm, dsa, swa, gqa): PERF.md section 7 (j) has the debt.

Its own:

* refuses at once, before any weight is made, where the program's
  ``Config.decoder`` does not take ``"cohere2_moe"`` (a program from before
  this configuration);
* ``correct``: the TIMED beam program's served captions (its prefill: the
  grouped kernel, the sliding layers under its window bound; + 20 steps
  over the kept tails, the full layer's whole prefix and the rows' own
  suffixes) against the reference's full forward, which repeats keys and
  values over their group, bounds the window by a comparison of positions
  and applies the four shared experts apart; ``moe_pairs_over`` limit 0; no
  selection, so no ``select_agreement``;
* the router's balance is fitted in the WEIGHTS (the source has no
  selection bias): ``cohere2_captioner.calibrate`` returns every layer's
  ``feed_forward/gate`` with its columns' component along the calibration
  batch's mean normed input taken out, and the connector's bias; both go
  into checkpoint and reference alike;
* ``run.extras``: ``lm_state_mb`` and ``lm_swa_state_mb`` (the search's
  state a batch, and the sliding layers' leaves of it: the kept tails per
  image and the suffixes per beam), ``lm_swa_attended_share``,
  ``lm_moe_held_pair_share``, ``step_held_pairs`` and
  ``step_experts_visited``, each per batch as the program reports them;
* keeps NO seed: the step-0 checkpoint (9.5 GB) is deleted when the check
  has run;
* sabotage (tests; ``no_window`` and ``shared_sum`` one chip run each), each
  a change of the PROGRAM alone, the reference keeps the configuration's:
  "token" as ``decode_offline_lm``; "no_window" gives it a window of the
  whole sequence (its sliding layers attend all they see and keep the whole
  prefix); "rope_in_full" turns the full layer's queries and keys too;
  "serial_block" makes the feed-forward read a norm of the stream AFTER
  attention (the block of every other decoder here); "shared_sum" adds the
  four shared experts' SUM; "rms_norm" leaves the mean in (RMSNorm with
  LayerNorm's weights).

Mix parameters: as ``decode_offline_lm``'s.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time

import numpy as np

import datagen
import harness
from drivers import common
from drivers.decode_offline import WindowClosed
from drivers.decode_offline_lm import (
    _EOS,
    LMBeamRecorder,
    _calibration_batch,
    _keep_one_seed,
    failed,
    limit_checks,
    route_agreement,
    write_vocabulary,
)
from drivers.decode_offline_dsa import _keep_no_seed
from reference import check as refcheck

DECODER = "cohere2_moe"


def _refuse_unless_the_program_has_the_decoder() -> None:
    from sat_tpu.config import Config

    try:
        Config(decoder=DECODER, num_hidden_layers=1, num_dense_layers=0, layer_types=("sliding_attention",))
    except (TypeError, ValueError) as e:
        raise harness.BenchError(f"the program's Config.decoder does not take {DECODER!r}: {e}")


def _parts_ms(run_: harness.RunData):
    """notes.gqa_parts_ms of a traced run: device ms a decoded batch of each
    scope under ``decoder/lm/attn`` (the full layer's) and
    ``decoder/lm/attn/window`` (the sliding layers') by phase, and of the
    block's other parts by phase (the metrics' buckets, cut finer)."""
    from reducers import trace_scope_ms

    rules = [[f"{phase}/{part}", f"beam/{scope}.*decoder/lm/{where}"]
             for phase, scope in (("prefill", "prefill"), ("step", "loop"))
             for part, where in (*((f"window/{part}", f"attn/window/{part}") for part in
                                   ("qkv", "rope", "scores", "out")),
                                 ("qkv", "attn/qkv"), ("scores", "attn/scores"), ("out", "attn/out"),
                                 ("norm", "norm"), ("residual", "residual"), ("shared", "moe/shared"),
                                 ("experts", "moe/experts"), ("route", "moe/route"),
                                 ("dispatch", "moe/dispatch"), ("combine", "moe/combine"),
                                 ("head", "head"), ("embed", "(embed|prefix)"))]
    out = {}
    for bucket, _ in rules:
        ms = trace_scope_ms.read(run_, "decode/beam_search", "^jit_beam_search", rules, bucket)
        if ms:
            out[bucket] = round(ms, 2)
    return out or None


def _sabotaged_config(cell: harness.Cell, sabotage) -> dict:
    """What a sabotage changes of the PROGRAM's configuration (the
    reference keeps the file's)."""
    from reference.params import context_shape

    if sabotage == "no_window":
        return {"sliding_window_size": context_shape(cell.model)[0] + int(cell.model["max_caption_length"]) + 1}
    return {}


@contextlib.contextmanager
def _sabotaged_program(sabotage):
    """What a sabotage changes of the PROGRAM's code, for as long as it
    runs: one function of ``models/cohere2_moe.py`` or ``lm_common`` put in
    another's place."""
    from sat_tpu.models import cohere2_moe, lm_common

    def serial_block(p, config, x, attend):
        a, kept = attend(cohere2_moe.layer_norm(x, p["input_norm"], config.norm_eps).astype(x.dtype))
        x = x + a                       # the feed-forward reads a norm of the stream AFTER attention
        u = cohere2_moe.layer_norm(x, p["input_norm"], config.norm_eps).astype(x.dtype)
        y, sizes, experts, pairs = cohere2_moe._experts(p, config, u)
        return x + y.astype(x.dtype), kept, sizes, experts, pairs

    summed = lm_common.shared_experts
    swaps = {"rope_in_full": (cohere2_moe, "_turns", lambda config, layer: True),
             "serial_block": (cohere2_moe, "_block", serial_block),
             "shared_sum": (lm_common, "shared_experts", lambda f, h, mean_of=1: summed(f, h, 1)),
             "rms_norm": (cohere2_moe, "layer_norm", lm_common.rms_norm)}
    if sabotage not in swaps:
        yield
        return
    module, name, other = swaps[sabotage]
    original = getattr(module, name)
    setattr(module, name, other)
    try:
        yield
    finally:
        setattr(module, name, original)


def _setup(cell: harness.Cell, kept: str, work: str, reused: bool, seed: int, sabotage):
    """The program's Config and, once per seed, its inputs under ``kept``:
    vocabulary, JPEGs, COCO file, and the step-0 checkpoint of the seeded
    weights under ``models0/``, which the run reads in place."""
    from reference import cohere2_captioner, params_cohere2

    models0 = os.path.join(kept, "models0")
    config = harness.program_config(cell, kept, work, seed, phase="eval", save_dir=models0,
                                    **_sabotaged_config(cell, sabotage))
    n_files = int(cell.mix["distinct_images"])
    files = [f"img_{i:06d}.jpg" for i in range(n_files)]
    ids = list(range(1, int(cell.mix["image_ids"]) + 1))
    if not reused:
        t_data = time.perf_counter()
        datagen.make_images(os.path.join(kept, "val", "images"), n_files,
                            cell.model["image_size"], seed)
        datagen.write_coco(os.path.join(kept, "val", "captions.json"), files, ids,
                           [["a generated image."]] * len(ids))
        write_vocabulary(config.vocabulary_file, cell.model["vocabulary_size"])
        t0 = time.perf_counter()
        weights = params_cohere2.make_weights(cell.model, seed)
        t1 = time.perf_counter()
        fitted = cohere2_captioner.calibrate(
            cell.model, weights, *_calibration_batch(cell, kept, seed),
            block=int(cell.mix["reference_block"]))
        np.savez(os.path.join(kept, "fitted.npz"), **{k: v.astype(np.float32) for k, v in fitted.items()})
        weights.update(fitted)
        t2 = time.perf_counter()
        harness.write_checkpoint(config, weights, models0)
        print(f"benchmark: images and words made in {t0 - t_data:.1f} s, weights made in {t1 - t0:.1f} s, "
              f"router balance fitted in {t2 - t1:.1f} s, "
              f"checkpoint written and verified in {time.perf_counter() - t2:.1f} s", flush=True)
        del weights
        gc.collect()
        harness.mark_complete(kept)
    path = os.path.join(work, "config.json")
    config.save(path)
    with np.load(os.path.join(kept, "fitted.npz")) as z:
        fitted = {k: z[k] for k in z.files}       # float32 on disk; every value bfloat16-exact
    return config, path, files, ids, fitted


def run(cell: harness.Cell, args, env) -> common.Outcome:
    _refuse_unless_the_program_has_the_decoder()
    if cell.rehearsal:
        cell.model.update(cell.config["rehearsal_model"])
    sabotage = getattr(args, "sabotage", None)
    kept, work, reused = cell.workdir(args.seed, sabotage)
    _keep_one_seed(kept)
    try:
        with _sabotaged_program(sabotage):
            return _run(cell, args, env, kept, work, reused, sabotage)
    finally:
        _keep_no_seed(kept)


def _run(cell: harness.Cell, args, env, kept: str, work: str, reused: bool, sabotage) -> common.Outcome:
    from sat_tpu import cli, runtime, telemetry
    from reference import cohere2_captioner

    mix, seed = cell.mix, args.seed
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
    loads, visited, visited_first, state_mb, held, over = [], [], [], [], [], 0
    window_mb, in_window = [], []
    step_pairs, step_visits = [], []      # a batch's steps: pairs held here, experts visited (layers x steps)
    first = rec.outs[done_batches[0]][3]
    fused_by_kind = np.asarray(first["prefill_fused_blocks_by_kind"]).tolist()
    combine = np.asarray(first["moe_combine"]).tolist()
    for bi in done_batches:                         # the program's counters, batch by batch
        stats = rec.outs[bi][3]
        counts = np.asarray(stats["moe_counts"], np.float64)
        loads.append(float((counts.max(axis=1) / counts.mean(axis=1)).max()))
        visits = np.asarray(stats["moe_step_visits"])           # [expert layers, T]
        visited.append(int(visits[:, 1:].min())), visited_first.append(int(visits[:, 0].min()))
        state_mb.append(float(stats["state_bytes"]) / 1e6)
        pairs = np.asarray(stats["moe_pairs"], np.float64)      # [prefill | steps, held | routed | over]
        held.append(float(pairs[:, 0].sum() / pairs[:, 1].sum()))
        over += int(pairs[:, 2].sum())
        window_mb.append(float(stats["state_bytes_window"]) / 1e6)
        attended, visible = (float(x) for x in np.asarray(stats["swa_attended"]))
        in_window.append(attended / visible)
        step_pairs.append(float(pairs[1, 0])), step_visits.append(float(visits.sum()))
    run_.extras.update(compile_s=env.meter.seconds_before(window["ns"][0]),
                       batches_in_window=b - a, batch_size=B, trace_dir=os.path.join(work, "trace"),
                       moe_load_max_over_mean=loads, lm_state_mb=state_mb, beam_size=beam, caption_steps=T,
                       lm_moe_held_pair_share=held,
                       lm_swa_state_mb=window_mb, lm_swa_attended_share=in_window,
                       step_held_pairs=step_pairs, step_experts_visited=step_visits)
    parts = None
    if tracer:
        common.take_trace(run_, tracer)
        parts = _parts_ms(run_)

    # ---- correct: a seeded sample of the captions the window produced
    checks = [{"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window["ns"])},
              {"name": "moe_pairs_over", "limit": 0, "value": over}]
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
        picked = common.sample_indices(rng, B, rows, must=longest)
        stats, searched = rec.outs[bi][3], np.asarray(rec.outs[bi][4])
        prefix = np.asarray(stats["prefix_routes"][np.asarray(picked)])           # [rows, N, layers * k]
        steps = np.asarray(stats["step_routes"][np.asarray(picked), 0])          # [rows, T, layers * k]
        for j, r in enumerate(picked):
            tokens.append(words_[r, 0]), lengths.append(int(lens[r, 0])), scores.append(float(logp[r, 0]))
            image_id = ids[bi * B + r]
            paths.append(os.path.join(kept, "val", "images", files[(image_id - 1) % len(files)]))
            # the records are a LIVE beam's: the served caption is live beam 0
            # where it never ended (then no caption of the image did)
            live = lengths[-1] == T and _EOS not in searched[r, 0]
            routes_p.append(np.concatenate([prefix[j], steps[j]]).reshape(N + T, -1, k).swapaxes(0, 1)
                            if live else None)
    checks.append({"name": "captions_well_formed", "value": well_formed, "limit": None})
    tokens = np.stack(tokens).astype(np.int32)
    rec.outs = []                                    # the records leave the chip
    gc.collect()
    images = np.stack([datagen.read_rgb(p) for p in paths])
    block = int(mix["reference_block"])
    t0 = time.perf_counter()
    ref_logits, routes_r = cohere2_captioner.served_logits(
        cell.model, seed, images, tokens, fitted=fitted, block=block)
    reference_s = time.perf_counter() - t0
    live = [i for i, r in enumerate(routes_p) if r is not None]
    if not live:
        raise harness.BenchError("no sampled caption ran all its steps: there is no record to compare")
    got = refcheck.served_numbers(ref_logits, tokens, lengths, scores, beam)
    got["route_agreement"] = route_agreement(np.stack([routes_p[i] for i in live], axis=1), routes_r[:, live])
    print(json.dumps({"route_agreement": got["route_agreement"], "floor": mix["limits"]["route_agreement_min"],
                      "choices": int(routes_r[:, live, :, 0].size)}), flush=True)
    checks += limit_checks(got, mix["limits"])
    control = None
    if getattr(args, "control", 0):               # the nearest precision below, in the program's place
        low_logits, low_routes = cohere2_captioner.served_logits(
            cell.model, seed, images, tokens, mode="fp8", fitted=fitted, block=block)
        low = {**refcheck.control_numbers(ref_logits, low_logits, tokens, lengths, beam),
               "route_agreement": route_agreement(low_routes, routes_r)}
        control = {"fp8": {**low, "fails": failed(limit_checks(low, mix["limits"]))}}
    return common.Outcome(run_, checks, attempted=(b - a) * B, failed=0,
                          memory_peak_bytes=memory["peak"],
                          notes={"control": control, "memory": memory, "reused": reused,
                                 "score_gap": got["score_gap"], "rank_gap": got["rank_gap"],
                                 "score_gap_mean": got["score_gap_mean"], "route_agreement": got["route_agreement"],
                                 "route_captions": len(live), "experts_visited_a_step": min(visited),
                                 "experts_visited_at_step_0": min(visited_first),
                                 "experts_visited_mean_a_step": float(np.mean(step_visits)) / (T * (routes_r.shape[0] or 1)),
                                 "captions_ended_early": float(np.mean(ended_early)),
                                 "moe_load_max_over_mean": float(np.median(loads)),
                                 "lm_state_mb": float(np.median(state_mb)),
                                 "lm_swa_state_mb": float(np.median(window_mb)),
                                 "lm_swa_attended_share": float(np.median(in_window)),
                                 "prefill_fused_blocks_by_kind": fused_by_kind, "moe_combine": combine,
                                 "lm_moe_held_pair_share": float(np.median(held)), "moe_pairs_over": over,
                                 "batches_in_window": b - a,
                                 "gqa_parts_ms": parts, "reference_s": reference_s,
                                 "trace_timing": run_.extras.get("trace_timing"),
                                 "served_tokens": int(sum(lengths)), "captions": len(lengths)})
