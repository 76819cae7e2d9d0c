"""Shared decode-throughput measurement core.

One implementation of the eval-decode benchmark harness, used by every
vehicle that reports `eval_images_per_sec` — `scripts/bench_eval.py`
(dedicated process), `scripts/bench_eval_ab.py` (the fresh-vs-resident
controlled A/B), and bench.py's additive eval window.  Round 3's 802-vs-620
discrepancy between vehicles could not be adjudicated while each carried
its own copy of the measurement code; sharing it here makes the remaining
differences (process state, window placement) the ONLY variables.

Methodology notes (PERF.md):
* the decode program returns a chained image tensor carrying a
  score-derived term too small to perturb fp32 pixels — each timed call
  consumes the previous call's output, so the wall window measures the
  device-bound dispatch chain, closed by one sync;
* timing is per-window: one device sync per window of `iters` batches.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..config import Config
from ..models.captioner import encode
from ..ops.beam_search import beam_search_jit


def make_chained_decode(
    config: Config,
    eos: int,
    beam_size: int,
    valid_size: Optional[int] = None,
    early_exit: bool = True,
):
    """Jitted (variables, images) -> (BeamResult, chained_images)."""

    @jax.jit
    def decode(variables: Dict[str, Any], images: jax.Array):
        contexts, _ = encode(variables, config, images, train=False)
        out = beam_search_jit(
            variables["params"]["decoder"], config, contexts, eos,
            beam_size=beam_size, valid_size=valid_size,
            early_exit=early_exit,
        )
        # serializing dependency for chained timing (see module docstring)
        return out, images + 1e-30 * out.log_scores.sum()

    return decode


def time_decode_windows(
    decode,
    variables: Dict[str, Any],
    images: jax.Array,
    iters: int,
    windows: int = 1,
) -> Tuple[float, List[float], jax.Array]:
    """Compile+first call, then `windows` timed windows of `iters` batches.

    Returns (compile_s, per-window mean batch ms, final chained images).
    """
    t0 = time.perf_counter()
    out, images_c = decode(variables, images)
    jax.device_get(out.log_scores[0, 0])
    compile_s = time.perf_counter() - t0

    windows_ms: List[float] = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out, images_c = decode(variables, images_c)
        jax.device_get(out.log_scores[0, 0])
        # raw ms — callers derive images/sec from this, so rounding happens
        # only at presentation/serialization time (ADVICE r04)
        windows_ms.append(1e3 * (time.perf_counter() - t0) / iters)
    return compile_s, windows_ms, images_c
