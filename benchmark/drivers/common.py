"""Pieces the drivers share: the outcome record, the seeded set-up of a
cell's vocabulary, weights and checkpoint, and the served-caption check."""

from __future__ import annotations

import gc
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import datagen
import harness
from reference import check as refcheck
from reference import model as refmodel
from reference.params import make_weights


@dataclass
class Outcome:
    run: harness.RunData
    checks: List[dict]
    attempted: int
    failed: int
    memory_peak_bytes: int
    notes: Dict[str, Any] = field(default_factory=dict)


class Recorder:
    """Wraps one of the program's jitted callables: the window's own call
    goes through ``__call__`` (which a driver overrides to take its clock
    readings and keep outputs), everything else (``.lower`` for the
    program's compile accounting) falls through to the callable."""

    def __init__(self, fn) -> None:
        self._fn = fn

    def __getattr__(self, name):
        return getattr(self._fn, name)


def seeded_setup(cell: harness.Cell, kept: str, run: str, reused: bool, seed: int, **config_extra):
    """The program's Config (inputs under ``kept``, outputs under ``run``)
    and a step-0 checkpoint of the benchmark's seeded weights in the run's
    own save_dir.  The weights are made and written once per seed, to
    ``<kept>/models0``; every run gets a copy, because a run leaves later
    checkpoints behind in the save_dir it resumes from.  Marks ``kept``
    complete: call it after the seed's other data is made.  Returns
    (config, path of config.json, vocabulary word list)."""
    vocab = datagen.words(cell.model["vocabulary_size"])
    config = harness.program_config(cell, kept, run, seed, **config_extra)
    models0 = os.path.join(kept, "models0")
    if not reused:
        datagen.write_vocabulary(config.vocabulary_file, cell.model["vocabulary_size"])
        weights = make_weights(cell.model, seed)
        harness.write_checkpoint(config, weights, models0)
        del weights
        gc.collect()
        harness.mark_complete(kept)
    shutil.copytree(models0, config.save_dir)
    path = os.path.join(run, "config.json")
    config.save(path)
    return config, path, vocab


def limit_checks(numbers: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    missing = [k for k in numbers if k not in limits]
    if missing:
        raise harness.BenchError(f"the traffic mix gives no limit for {missing}")
    return [{"name": k, "value": float(v), "limit": float(limits[k])} for k, v in numbers.items()]


def check_served(cell: harness.Cell, seed: int, images_u8: np.ndarray, tokens: np.ndarray,
                 lengths: Sequence[int], reported: Sequence[float], beam: int,
                 mode: str = "f32") -> Dict[str, Any]:
    """Reference over each sampled prompt with its served tokens; returns
    check.served_numbers' dict plus the reference logits."""
    weights = make_weights(cell.model, seed)
    logits = refmodel.served_logits(weights, cell.model, images_u8, tokens, mode=mode)
    out = refcheck.served_numbers(logits, tokens, lengths, reported, beam)
    out["logits"] = logits
    return out


CONTROL_MODES = ("fp8", "fp8enc")


def control_served(cell: harness.Cell, seed: int, images_u8, tokens, lengths, ref_logits, beam: int):
    """The lower-precision references in the program's place, same prompts
    and tokens: {mode: check.control_numbers}."""
    weights = make_weights(cell.model, seed)
    out = {}
    for mode in CONTROL_MODES:
        low = refmodel.served_logits(weights, cell.model, images_u8, tokens, mode=mode)
        out[mode] = refcheck.control_numbers(ref_logits, low, tokens, lengths, beam)
    return out


def wait_for(cond, timeout_s: float, what: str, alive=lambda: True, poll_s: float = 0.005) -> None:
    t0 = time.time()
    while not cond():
        if not alive():
            raise harness.BenchError(f"the program ended while the benchmark waited for {what}")
        if time.time() - t0 > timeout_s:
            raise harness.BenchError(f"timed out after {timeout_s:.0f}s waiting for {what}")
        time.sleep(poll_s)


def sample_indices(rng: np.random.Generator, n: int, k: int, must: Optional[int] = None) -> List[int]:
    pick = list(rng.choice(n, size=min(k, n), replace=False))
    if must is not None and must not in pick:
        pick[0] = must
    return [int(i) for i in pick]


def trace_timing(tracer, reduced) -> dict:
    """Host-clock seconds of the profiler calls (relative to the call of
    start_trace) beside the device span the trace holds."""
    t = tracer.timing
    out = {"start_returned_s": (t[1] - t[0]) / 1e9, "stop_called_s": (t[2] - t[0]) / 1e9,
           "stop_returned_s": (t[3] - t[0]) / 1e9} if len(t) == 4 else {}
    if reduced:
        out.update(device_span_s=reduced["span_s"], busy_s=reduced["busy_s"],
                   first_ns=reduced["first_ns"], last_ns=reduced["last_ns"],
                   unix_now=time.time(), perf_now_ns=time.perf_counter_ns())
    return out


def sleep_until(t_ns: int) -> None:
    time.sleep(max(0.0, (t_ns - time.perf_counter_ns()) / 1e9))


def span_window(window_ns, tracer):
    """The stretch of the measured window that host spans and counters are
    read from: all of it, or in a traced run the part before the profiler
    started (starting, and above all stopping, the profiler stalls the
    host for seconds; what it disturbs is left out)."""
    if tracer is None or not tracer.timing:
        return window_ns
    return (window_ns[0], min(window_ns[1], tracer.timing[0]))


def take_trace(run: harness.RunData, tracer) -> None:
    """The reduced trace of the run's traced stretch; a profiler that failed
    fails the run."""
    if tracer.error:
        raise harness.BenchError(f"the profiler failed: {tracer.error}")
    run.trace, run.trace_ns = tracer.reduced(), (tracer.timing[0], tracer.t1_ns)
    run.extras["trace_timing"] = trace_timing(tracer, run.trace)
