"""``train_job``: ``--phase=train`` as a trainer starts it, resumed from a
step-0 checkpoint of the benchmark's seeded weights, over a generated
COCO-format set; ended by SIGTERM after the window.

One process: this one holds the chip and calls ``sat_tpu.cli.main`` on its
main thread.  The only hand laid on the program is that the factory of its
jitted train step (``runtime.make_jit_train_step``) is wrapped, so that the
benchmark can (a) read its own clock at every call — call k*log_every comes
right after the loop's log sync, when the device has caught up — and (b)
keep what ``correct`` needs from the first steps of the very object the
window then drives: the batches as fed, each step's loss, Adam's first
moment after step 1 and the decoder after step ``check_steps``.

Mix parameters: images, captions_per_image, caption_words [lo, hi],
warm_log_boundaries, check_steps, trace_seconds, limits, program.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time

import numpy as np

import datagen
import flops
import harness
from drivers import common
from reference import check as refcheck
from reference import model as refmodel
from reference.params import make_weights


class StepRecorder(common.Recorder):
    sabotage = None          # tests: "identity" returns the state unchanged

    def __init__(self, fn, log_every: int, check_steps: int) -> None:
        super().__init__(fn)
        self.log_every, self.check_steps = log_every, check_steps
        self.calls = 0
        self.caught_up = []            # (perf_counter_ns, steps done) after each log sync
        self.live = []                 # the runtime's bytes_in_use at each of them
        self.batches, self.losses = [], []
        self.mu = self.after = None

    def __call__(self, state, batch, rng):
        import jax
        import jax.numpy as jnp

        n = self.calls
        if n % self.log_every == 0:
            self.caught_up.append((time.perf_counter_ns(), n))
            self.live.append(harness.memory_stats()["bytes_in_use"])
        if n >= self.check_steps and self.sabotage is None:
            self.calls = n + 1
            return self._fn(state, batch, rng)
        saved = jax.tree_util.tree_map(jnp.copy, state) if self.sabotage else None
        new_state, metrics = self._fn(state, batch, rng)
        if self.sabotage == "identity":
            new_state = saved
        if n < self.check_steps:
            self.batches.append(batch)
            self.losses.append(metrics["total_loss"])
            if n == 0:
                adam = next(s for s in jax.tree_util.tree_leaves(
                    new_state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu"))
                self.mu = jax.tree_util.tree_map(jnp.copy, adam.mu)
            if n == self.check_steps - 1:
                self.after = jax.tree_util.tree_map(jnp.copy, new_state.params["decoder"])
        self.calls = n + 1
        return new_state, metrics


def hyper(config) -> dict:
    return dict(fc_drop_rate=config.fc_drop_rate, lstm_drop_rate=config.lstm_drop_rate,
                attention_loss_factor=config.attention_loss_factor,
                fc_kernel_regularizer_scale=config.fc_kernel_regularizer_scale,
                beta1=config.beta1, beta2=config.beta2, epsilon=config.epsilon,
                learning_rate=config.initial_learning_rate,
                clip_gradients=config.clip_gradients, rng_impl=config.rng_impl)


def program_numbers(rec: StepRecorder, weights, beta1: float):
    """(losses, first clipped gradient, decoder change) as the program made
    them, as host arrays keyed like the reference's."""
    losses = [float(x) for x in rec.losses]
    grad = {k: v / (1.0 - beta1) for k, v in refmodel.flatten(rec.mu, "params").items()}
    after = refmodel.flatten(rec.after, "params/decoder")
    delta = {k: v - np.asarray(weights[k]) for k, v in after.items()}
    return losses, grad, delta


def run(cell: harness.Cell, args, env) -> common.Outcome:
    from sat_tpu import cli, runtime, telemetry

    mix, seed = cell.mix, args.seed
    kept, work, reused = cell.workdir(seed)
    phases = {"start_to_driver": (time.perf_counter_ns() - env.t_start_ns) / 1e9, "reused": reused}
    tp = time.perf_counter()
    if not reused:
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
        vocab = datagen.words(cell.model["vocabulary_size"])
        n_img, per = int(mix["images"]), int(mix["captions_per_image"])
        files = datagen.make_images(os.path.join(kept, "train", "images"), n_img,
                                    cell.model["image_size"], seed)
        lo, hi = mix["caption_words"]
        hi = min(hi, cell.model["max_caption_length"] - 1)
        caps = datagen.make_captions(rng, vocab, n_img * per, min(lo, hi), hi)
        datagen.write_coco(os.path.join(kept, "train", "captions.json"), files,
                           range(1, n_img + 1), [caps[i * per:(i + 1) * per] for i in range(n_img)])
    phases["data"] = time.perf_counter() - tp
    tp = time.perf_counter()
    config, cfg_path, _ = common.seeded_setup(cell, kept, work, reused, seed, phase="train")
    phases["weights_checkpoint"] = time.perf_counter() - tp

    rec_box = {}
    make_step = runtime.make_jit_train_step

    def wrapped_factory(cfg):
        rec_box["rec"] = StepRecorder(make_step(cfg), cfg.log_every, int(mix["check_steps"]))
        rec_box["rec"].sabotage = getattr(args, "sabotage", None)
        return rec_box["rec"]

    runtime.make_jit_train_step = wrapped_factory
    warm = int(mix["warm_log_boundaries"])
    window, tracer, done = {}, None, threading.Event()
    if args.trace:
        tracer = harness.TraceWindow(os.path.join(work, "trace"), float(mix["trace_seconds"]))

    def control() -> None:
        common.wait_for(lambda: "rec" in rec_box and len(rec_box["rec"].caught_up) > warm,
                        1500.0, "the train loop's warm-up", alive=lambda: not done.is_set())
        t0 = rec_box["rec"].caught_up[warm][0]
        window["ns"] = (t0, t0 + int(args.seconds * 1e9))
        common.sleep_until(window["ns"][1] - (int(tracer.seconds * 1e9) if tracer else 0))
        if tracer:                    # the window's last stretch; stop_trace's cost falls after it
            tracer.run()

    controller = harness.self_sigterm_after(control)
    try:
        rc = cli.main(["--phase=train", "--config", cfg_path, "--load", "--telemetry"])
    finally:
        done.set()
        runtime.make_jit_train_step = make_step
    controller.join(timeout=30.0)
    if rc != 0 or "ns" not in window:
        raise harness.BenchError(f"--phase=train exited {rc} (window opened: {'ns' in window})")

    rec: StepRecorder = rec_box["rec"]
    run_ = harness.RunData(cell, common.span_window(window["ns"], tracer), env.peaks)
    run_.take_spans(telemetry.get())
    marks = [(t, n) for t, n in rec.caught_up if window["ns"][0] <= t <= window["ns"][1]]
    memory = harness.memory_peak(
        [b for (t, _), b in zip(rec.caught_up, rec.live) if window["ns"][0] <= t <= window["ns"][1]],
        harness.program_temps("train_step"))
    if len(marks) < 3:
        raise harness.BenchError(f"only {len(marks)} log boundaries fell inside the window")
    (ta, na), (tb, nb) = marks[0], marks[-1]
    B = config.batch_size
    run_.measured["captions_per_s"] = (nb - na) * B / ((tb - ta) / 1e9)
    run_.measured["setup_s"] = (window["ns"][0] - env.t_start_ns) / 1e9
    gaps_ms = np.diff([t for t, _ in marks]) / 1e6
    run_.extras.update(
        boundary_ms={"median": float(np.median(gaps_ms)), "max": float(gaps_ms.max()),
                     "argmax": int(gaps_ms.argmax()), "n": len(gaps_ms)},
        compile_s=env.meter.seconds_before(window["ns"][0]), trace_dir=os.path.join(work, "trace"),
        flops_per_caption=flops.train_flops_per_caption(cell.model, config.train_cnn),
        steps_in_window=nb - na, batch_size=B,
    )
    if tracer:
        common.take_trace(run_, tracer)

    # ---- correct: the first steps of the object the window drove, against
    # the reference on the same rows (after the program's state is freed)
    checks = [{"name": "compiles_in_window", "limit": 0,
               "value": env.meter.count_between(*window["ns"])}]
    batches = [(np.asarray(b["images"]), np.asarray(b["word_idxs"]), np.asarray(b["masks"]))
               for b in rec.batches]
    # a row is an (image, caption) pair; five captions share an image, so
    # it is the captions that must all differ
    rows_differ = all(len(np.unique(tok, axis=0)) == len(tok) for _img, tok, _m in batches)
    with open(os.path.join(config.summary_dir, "metrics.jsonl")) as f:
        logged = [json.loads(line)["total_loss"] for line in f]
    checks.append({"name": "rows_all_differ", "value": rows_differ, "limit": None})
    checks.append({"name": "logged_losses_finite",
                   "value": bool(logged and np.isfinite(logged).all()), "limit": None})
    weights = make_weights(cell.model, seed)
    got = program_numbers(rec, weights, config.beta1)
    rec.batches, rec.mu, rec.after = [], None, None
    gc.collect()
    want = refmodel.train_steps(weights, cell.model, hyper(config), batches, config.seed)
    numbers = refcheck.train_numbers(got[0], want[0], got[1], want[1], got[2], want[2])
    checks += common.limit_checks(numbers, mix["limits"])
    control = None
    if getattr(args, "control", 0):
        control = {}
        for mode in common.CONTROL_MODES:
            low = refmodel.train_steps(weights, cell.model, hyper(config), batches, config.seed, mode=mode)
            control[mode] = refcheck.train_numbers(low[0], want[0], low[1], want[1], low[2], want[2])
    return common.Outcome(run_, checks, attempted=nb - na, failed=0, memory_peak_bytes=memory["peak"],
                          notes={"control": control, "memory": memory, "losses_program": got[0], "losses_reference": want[0],
                                 "trace_timing": run_.extras.get("trace_timing"),
                                 "setup_phases_s": phases, "boundary_ms": run_.extras["boundary_ms"]})
