"""``decode_offline_gdn``: ``--phase=eval --beam_size=K`` over a generated
val set of 224-px images with the Qwen3-Next caption decoder
(``Config.decoder = "qwen3_next"``: three Gated DeltaNet layers, each a
float32 matrix state ``[32, 128, 128]`` a beam that every token decays and
rewrites whole, after a causal depthwise conv; one gated grouped-query
layer of 16 query / 2 key-value heads of 256 with rope on a quarter of the
head; 256 of 512 experts held under a softmax router beside one gated
shared expert; an untied head over the vocabulary's slice):
``decode_offline_gqa``'s control flow, bound to this configuration's
weights (``reference/params_qwen3next.py``) and reference
(``reference/qwen3next_captioner.py``); the recorder, the word generator,
the route comparison and ``limit_checks`` are imported from
``decode_offline_lm``, the rule that no seed is kept from
``decode_offline_dsa``.  README-gdn.md describes it.  No accepted ``_run``
takes its reference as an argument, so this is the FIFTH (PERF.md section 7
(j) has the debt); it takes nothing it could import.

Its own:

* refuses at once, before any weight is made, where the program's
  ``Config.decoder`` does not take ``"qwen3_next"`` (a program from before
  this configuration);
* ``correct``: the TIMED beam program's served captions (its prefill: the
  chunked delta rule; + 20 steps of the recurrence through state, taps,
  keys and values, and the search's reorder of all of them) against the
  reference's full forward, which runs the recurrence a token at a time:
  ``score_gap``, ``score_gap_mean``, ``rank_gap``, ``route_agreement`` as
  the other lm cells, ``moe_pairs_over`` limit 0, PLUS two numbers of the
  final S of the sampled rows' live beams (the program hands back the S of
  live beam 0 of each batch's first ``qwen3_next.REPORT_STATE_IMAGES``
  images with its results; the recorder keeps those of the batches
  dispatched in the window's last ``_KEEP_STATES_S`` seconds and the sample
  is drawn among those), because with random
  weights a caption's score hardly sees the state: ``state_gap``, against
  the reference's S after the same words, relative, the worst DeltaNet
  layer (a state without its decay, or another beam's, reads of the order
  of 1); and ``state_bf16_share``, the share of its values on the bfloat16
  grid (what the state was KEPT in: bfloat16 inputs move S by as much as
  rounding S does, so no gap tells a bfloat16 state from a float32 one;
  the values' own low bits do);
* the router's balance is fitted in the WEIGHTS (the source has no
  selection bias): ``qwen3next_captioner.calibrate``;
* ``--control 1``: two references in the program's place, the float8
  control of the other cells and ``state_bf16`` (S rounded to bfloat16
  after every token: the precision the configuration does NOT state),
  which must fail ``state_bf16_share``;
* ``run.extras``: ``lm_state_mb``, ``lm_gdn_state_mb`` (S and the conv taps
  of the per-beam tree), ``lm_moe_held_pair_share``, ``step_held_pairs``
  and ``step_experts_visited``, each per batch as the program reports them;
* keeps NO seed: the step-0 checkpoint (7.4 GB) is deleted when the check
  has run;
* sabotage (tests), each a change of the PROGRAM alone: "token" as
  ``decode_offline_lm``; "no_decay" runs the recurrence with g = 0; "rope_whole_head" turns
  all of a full layer's head; "state_bf16" keeps S in bfloat16 between
  steps; "ungated_shared" leaves the shared expert's gate out.

Mix parameters: as ``decode_offline_lm``'s, and ``reference_block``.
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

DECODER = "qwen3_next"
_KEEP_STATES_S = 8.0     # the window's last seconds whose batches' handed-back states stay on the device (50 MB each)


def _refuse_unless_the_program_has_the_decoder() -> None:
    from sat_tpu.config import Config

    try:
        Config(decoder=DECODER, num_hidden_layers=1, num_dense_layers=0, layer_types=("linear_attention",),
               tie_word_embeddings=False, use_expert_bias=False, n_shared_experts=1, head_dim=16,
               linear_num_key_heads=1, linear_num_value_heads=1, linear_key_head_dim=8, linear_value_head_dim=8,
               linear_conv_kernel_dim=4, shared_expert_intermediate_size=8)
    except (TypeError, ValueError) as e:
        raise harness.BenchError(f"the program's Config.decoder does not take {DECODER!r}: {e}")


class GDNBeamRecorder(LMBeamRecorder):
    """``LMBeamRecorder`` that keeps the states a batch hands back
    (``decoder_stats['final_state']``) only of the batches dispatched in the
    measured window's last ``_KEEP_STATES_S`` seconds (``window``: the
    controller's dict, its ``"ns"`` set once the warm-up is over); every
    other record stays, as the other drivers keep them."""

    def __init__(self, fn, sabotage, window: dict) -> None:
        super().__init__(fn, sabotage)
        self.window = window

    def __call__(self, *a, **kw):
        out = super().__call__(*a, **kw)
        ns = self.window.get("ns")
        if not (ns and ns[1] - int(_KEEP_STATES_S * 1e9) <= self.times[-1] <= ns[1]):
            words, lens, logp, stats, searched = self.outs[-1]
            self.outs[-1] = (words, lens, logp, {k: v for k, v in stats.items() if k != "final_state"}, searched)
        return out


def _parts_ms(run_: harness.RunData):
    """notes.gdn_parts_ms of a traced run: device ms a decoded batch of each
    scope under ``decoder/lm/attn/gdn`` and ``decoder/lm/attn/full`` by
    phase, and of the block's other parts by phase (the metrics' buckets,
    cut finer)."""
    from reducers import trace_scope_ms

    rules = [[f"{phase}/{part}", f"beam/{scope}.*decoder/lm/{where}"]
             for phase, scope in (("prefill", "prefill"), ("step", "loop"))
             for part, where in (*((f"gdn/{part}", f"attn/gdn/{part}") for part in
                                   ("proj", "conv", "gates", "scan", "state", "norm")),
                                 *((f"full/{part}", f"attn/full/{part}") for part in
                                   ("qkv", "rope", "scores", "gate")),
                                 ("norm", "attn/(norm|residual)"), ("shared", "moe/shared"),
                                 ("experts", "moe/experts"), ("route", "moe/route"),
                                 ("dispatch", "moe/dispatch"), ("combine", "moe/combine"),
                                 ("head", "head"), ("embed", "(embed|prefix)"))]
    rules += [["step/reorder", "beam/loop.*beam/tile"], ["step/topk", "beam/loop.*beam/topk"]]
    out = {}
    for bucket, _ in rules:
        ms = trace_scope_ms.read(run_, "decode/beam_search", "^jit_beam_search", rules, bucket)
        if ms:
            out[bucket] = round(ms, 2)
    return out or None


@contextlib.contextmanager
def _sabotaged_program(sabotage):
    """What a sabotage changes of the PROGRAM's code, for as long as it
    runs: one name of ``models/qwen3_next.py`` or ``lm_common`` bound to
    another value."""
    import jax.numpy as jnp

    from sat_tpu.models import lm_common, qwen3_next

    inputs = qwen3_next._gdn_inputs

    def no_decay(m, config, u):
        mixed, z, beta, g = inputs(m, config, u)
        return mixed, z, beta, jnp.zeros_like(g)

    swaps = {"no_decay": (qwen3_next, "_gdn_inputs", no_decay),
             "rope_whole_head": (qwen3_next, "_rotary", lambda config: config.head_dim),
             "state_bf16": (qwen3_next, "STATE_DTYPE", jnp.bfloat16),
             "ungated_shared": (lm_common, "shared_gate", lambda f, h: jnp.ones((h.shape[0], 1), jnp.float32))}
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


def _setup(cell: harness.Cell, kept: str, work: str, reused: bool, seed: int):
    """The program's Config and, once per seed, its inputs under ``kept``:
    vocabulary, JPEGs, COCO file, and the step-0 checkpoint of the seeded
    weights under ``models0/``, which the run reads in place."""
    from reference import params_qwen3next, qwen3next_captioner

    models0 = os.path.join(kept, "models0")
    config = harness.program_config(cell, kept, work, seed, phase="eval", save_dir=models0)
    n_files = int(cell.mix["distinct_images"])
    files = [f"img_{i:06d}.jpg" for i in range(n_files)]
    ids = list(range(1, int(cell.mix["image_ids"]) + 1))
    if not reused:
        t_data = time.perf_counter()
        datagen.make_images(os.path.join(kept, "val", "images"), n_files, cell.model["image_size"], seed)
        datagen.write_coco(os.path.join(kept, "val", "captions.json"), files, ids,
                           [["a generated image."]] * len(ids))
        write_vocabulary(config.vocabulary_file, cell.model["vocabulary_size"])
        t0 = time.perf_counter()
        weights = params_qwen3next.make_weights(cell.model, seed)
        t1 = time.perf_counter()
        fitted = qwen3next_captioner.calibrate(
            cell.model, weights, *_calibration_batch(cell, kept, seed), block=int(cell.mix["reference_block"]))
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
    from reference import qwen3next_captioner

    mix, seed = cell.mix, args.seed
    config, cfg_path, files, ids, fitted = _setup(cell, kept, work, reused, seed)
    beam, B, T = config.beam_size, config.batch_size, config.max_caption_length

    window, done = {}, threading.Event()
    rec = GDNBeamRecorder(runtime.beam_search_jit, sabotage, window)
    original, runtime.beam_search_jit = runtime.beam_search_jit, rec
    warm = int(mix["warm_batches"])
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
    loads, visited, visited_first, state_mb, recurrent_mb, held, over = [], [], [], [], [], [], 0
    step_pairs, step_visits = [], []      # a batch's steps: pairs held here, experts visited (layers x steps)
    combine = np.asarray(rec.outs[done_batches[0]][3]["moe_combine"]).tolist()
    for bi in done_batches:                         # the program's counters, batch by batch
        stats = rec.outs[bi][3]
        counts = np.asarray(stats["moe_counts"], np.float64)
        loads.append(float((counts.max(axis=1) / counts.mean(axis=1)).max()))
        visits = np.asarray(stats["moe_step_visits"])           # [expert layers, T]
        visited.append(int(visits[:, 1:].min())), visited_first.append(int(visits[:, 0].min()))
        state_mb.append(float(stats["state_bytes"]) / 1e6)
        recurrent_mb.append(float(stats["state_bytes_recurrent"]) / 1e6)
        pairs = np.asarray(stats["moe_pairs"], np.float64)      # [prefill | steps, held | routed | over]
        held.append(float(pairs[:, 0].sum() / pairs[:, 1].sum()))
        over += int(pairs[:, 2].sum())
        step_pairs.append(float(pairs[1, 0])), step_visits.append(float(visits.sum()))
    run_.extras.update(compile_s=env.meter.seconds_before(window["ns"][0]),
                       batches_in_window=b - a, batch_size=B, trace_dir=os.path.join(work, "trace"),
                       moe_load_max_over_mean=loads, lm_state_mb=state_mb, beam_size=beam, caption_steps=T,
                       lm_moe_held_pair_share=held, lm_gdn_state_mb=recurrent_mb,
                       step_held_pairs=step_pairs, step_experts_visited=step_visits)
    parts = None
    if tracer:
        common.take_trace(run_, tracer)
        parts = _parts_ms(run_)

    # ---- correct: a seeded sample of the captions the window produced, among the batches whose states are kept
    checks = [{"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window["ns"])},
              {"name": "moe_pairs_over", "limit": 0, "value": over}]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    with_state = [bi for bi in done_batches if "final_state" in rec.outs[bi][3]]
    if not with_state:
        raise harness.BenchError(f"no batch of the window's last {_KEEP_STATES_S:.0f} s was drained: nothing to compare")
    pick = [with_state[j] for j in common.sample_indices(rng, len(with_state), int(mix["sample_batches"]))]
    rows = int(mix["sample_rows"])
    k, N = config.num_experts_per_tok, config.num_ctx
    tokens, lengths, scores, paths, routes_p, states_p = [], [], [], [], [], []
    well_formed, ended_early = True, []
    for bi in pick:
        words_, lens, logp = (np.asarray(x) for x in rec.outs[bi][:3])
        ended_early.append(float((lens[:, 0] < T).mean()))
        well_formed &= bool((lens[:, 0] >= 1).all() and (lens[:, 0] <= T).all()
                            and (words_ >= 0).all() and (words_ < cell.model["vocabulary_size"]).all())
        stats, searched = rec.outs[bi][3], np.asarray(rec.outs[bi][4])
        final = np.asarray(stats["final_state"])                # [images, DeltaNet layers, nv, dk, dv]
        if final.shape[0] < rows:
            raise harness.BenchError(f"the program hands back the states of {final.shape[0]} images a batch; "
                                     f"the mix samples {rows}")
        picked = list(range(rows))          # the images whose live beam's state came back with the results
        prefix = np.asarray(stats["prefix_routes"][:rows])                        # [rows, N, layers * k]
        steps = np.asarray(stats["step_routes"][:rows, 0])                       # [rows, T, layers * k]
        for j, r in enumerate(picked):
            tokens.append(words_[r, 0]), lengths.append(int(lens[r, 0])), scores.append(float(logp[r, 0]))
            image_id = ids[bi * B + r]
            paths.append(os.path.join(kept, "val", "images", files[(image_id - 1) % len(files)]))
            # the records are a LIVE beam's: the served caption is live beam 0
            # where it never ended (then no caption of the image did)
            live = lengths[-1] == T and _EOS not in searched[r, 0]
            routes_p.append(np.concatenate([prefix[j], steps[j]]).reshape(N + T, -1, k).swapaxes(0, 1)
                            if live else None)
            states_p.append(final[r])
    checks.append({"name": "captions_well_formed", "value": well_formed, "limit": None})
    tokens = np.stack(tokens).astype(np.int32)
    rec.outs = []                                    # the records leave the chip
    gc.collect()
    images = np.stack([datagen.read_rgb(p) for p in paths])
    block = int(mix["reference_block"])
    t0 = time.perf_counter()
    ref_logits, routes_r, states_r = qwen3next_captioner.served_logits(
        cell.model, seed, images, tokens, fitted=fitted, block=block)
    reference_s = time.perf_counter() - t0
    live = [i for i, r in enumerate(routes_p) if r is not None]
    if not live:
        raise harness.BenchError("no sampled caption ran all its steps: there is no record to compare")
    got = refcheck.served_numbers(ref_logits, tokens, lengths, scores, beam)
    got["route_agreement"] = route_agreement(np.stack([routes_p[i] for i in live], axis=1), routes_r[:, live])
    states_live = np.stack([states_p[i] for i in live], axis=1)
    got["state_gap"] = qwen3next_captioner.state_gap(states_live, states_r[:, live])
    got["state_bf16_share"] = qwen3next_captioner.state_bf16_share(states_live)
    print(json.dumps({"route_agreement": got["route_agreement"], "floor": mix["limits"]["route_agreement_min"],
                      "choices": int(routes_r[:, live, :, 0].size), "state_gap": got["state_gap"],
                      "state_gap_by_layer": [qwen3next_captioner.state_gap(states_live[i:i + 1], states_r[i:i + 1, live])
                                             for i in range(len(states_r))],
                      "state_bf16_share": got["state_bf16_share"], "states": len(live)}), flush=True)
    checks += limit_checks(got, mix["limits"])
    control = None
    if getattr(args, "control", 0):               # a lower precision, in the program's place
        control = {}
        for mode in ("fp8", "state_bf16"):
            low_logits, low_routes, low_states = qwen3next_captioner.served_logits(
                cell.model, seed, images, tokens, mode=mode, fitted=fitted, block=block)
            low = {**refcheck.control_numbers(ref_logits, low_logits, tokens, lengths, beam),
                   "route_agreement": route_agreement(low_routes, routes_r),
                   "state_gap": qwen3next_captioner.state_gap(low_states, states_r),
                   "state_bf16_share": qwen3next_captioner.state_bf16_share(low_states)}
            control[mode] = {**low, "fails": failed(limit_checks(low, mix["limits"]))}
    return common.Outcome(run_, checks, attempted=(b - a) * B, failed=0,
                          memory_peak_bytes=memory["peak"],
                          notes={"control": control, "memory": memory, "reused": reused,
                                 "score_gap": got["score_gap"], "rank_gap": got["rank_gap"],
                                 "score_gap_mean": got["score_gap_mean"], "route_agreement": got["route_agreement"],
                                 "state_gap": got["state_gap"], "state_bf16_share": got["state_bf16_share"],
                                 "route_captions": len(live), "experts_visited_a_step": min(visited),
                                 "experts_visited_at_step_0": min(visited_first),
                                 "experts_visited_mean_a_step": float(np.mean(step_visits)) / (T * (routes_r.shape[0] or 1)),
                                 "captions_ended_early": float(np.mean(ended_early)),
                                 "moe_load_max_over_mean": float(np.median(loads)),
                                 "lm_state_mb": float(np.median(state_mb)),
                                 "lm_gdn_state_mb": float(np.median(recurrent_mb)),
                                 "moe_combine": combine,
                                 "lm_moe_held_pair_share": float(np.median(held)), "moe_pairs_over": over,
                                 "batches_in_window": b - a,
                                 "gdn_parts_ms": parts, "reference_s": reference_s,
                                 "trace_timing": run_.extras.get("trace_timing"),
                                 "served_tokens": int(sum(lengths)), "captions": len(lengths)})
