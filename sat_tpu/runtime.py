"""Train / eval / test runtimes — the framework's driving loops.

Equivalent of the reference ``BaseModel.train/eval/test``
(/root/reference/base_model.py:39-161) redesigned TPU-first:

* the train loop consumes an async prefetch pipeline (the reference decodes
  images synchronously inside the loop, base_model.py:53) and runs ONE
  compiled XLA program per step;
* eval/test drive the on-device batched beam search (one device dispatch
  per batch, vs the reference's ~beam×20 sess.run round-trips per image,
  base_model.py:184-212);
* checkpoints every ``save_period`` steps (base_model.py:61-62), summaries
  via the TensorBoard-compatible writer (base_model.py:46-47,63);
* artifact parity: ``results.json`` + COCO scoring for eval
  (base_model.py:109-117), ``results.csv`` + captioned images for test
  (base_model.py:144-160).
"""

from __future__ import annotations

import collections
import gc
import itertools
import os
import resource
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from .config import Config
from .data.dataset import DataSet, prepare_eval_data, prepare_test_data, prepare_train_data
from .data.images import ImageLoader, PrefetchLoader
from .data.vocabulary import Vocabulary
from .evalcap.eval import CocoEvalCap
from .models.captioner import encode
from .ops.beam_search import beam_search_jit
from .train.checkpoint import (
    AsyncCheckpointWriter,
    apply_cnn_import,
    import_reference_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from .resilience import AnomalySentinel, FaultPlan, GracefulShutdown, lineage
from .resilience import retry as _retry
from .resilience.lineage import CheckpointWriteError
from .resilience.supervisor import RESTARTS_ENV
from .resilience.watchdog import Watchdog, deadlines_from_config
from .train.step import TrainState, create_train_state, make_jit_train_step
from . import telemetry
from .utils.fileio import atomic_write
from .utils.progress import Progress, track
from .utils.summary import SummaryWriter


# ---------------------------------------------------------------------------
# input feed shared by all three phases
# ---------------------------------------------------------------------------


# One QuarantineManager per ledger path (i.e. per run): the systemic-
# corruption ceiling is a run-level judgement, so the train and eval
# loaders of one run must share the bookkeeping.
_QUARANTINES: Dict[str, "object"] = {}


def _quarantine_for(config: Config):
    from .resilience.quarantine import QuarantineManager, ledger_path_for

    path = ledger_path_for(config)
    q = _QUARANTINES.get(path)
    if q is None:
        q = QuarantineManager(
            path, max_fraction=config.quarantine_max_fraction
        )
        _QUARANTINES[path] = q
    return q


def make_loader(config: Config, dataset: DataSet) -> PrefetchLoader:
    """The host-side input pipeline for a dataset: shard-cache resolution
    (build-or-load per ``config.shard_cache``; falls back to live JPEG
    decode when no valid cache exists — see data.shards) + the prefetching
    batch assembler, with the run's quarantine wired in (bad records are
    contained and substituted instead of crashing the run — see
    resilience.quarantine; direct PrefetchLoader construction without a
    quarantine keeps the old raise-through behavior).  All three phase
    loops build their feed here so the cache policy is applied
    uniformly."""
    from .data.shards import resolve_shard_cache

    return PrefetchLoader(
        dataset,
        ImageLoader(size=config.image_size, raw=config.device_preprocess),
        num_workers=config.num_data_workers,
        prefetch_depth=config.prefetch_depth,
        shard_cache=resolve_shard_cache(config, dataset.image_files),
        quarantine=_quarantine_for(config),
    )


def device_prefetch(loader, ahead: int = 1):
    """Double-buffered host→device feed: dispatch batch k+1's transfer
    before the consumer syncs on step k.

    ``jax.device_put`` is asynchronous — it enqueues the host→HBM copy and
    returns immediately — so holding ``ahead`` already-dispatched batches
    in a ring overlaps every batch's transfer with the previous step's
    device compute; the step dispatch then consumes an array that is
    already (or almost) resident instead of paying the copy on its
    critical path.  Array leaves are transferred; everything else
    ('files') passes through.  Single-device feed only: the mesh paths
    place batches through ``make_global_batch``, which owns its own
    per-device placement.
    """
    from collections import deque

    def put(batch, index):
        # the span times only the (async) transfer DISPATCH — it runs
        # inside the feed's data wait, so the breakdown reports it as a
        # nested interval, not a phase of its own
        with telemetry.span("feed/device_put", index):
            return {
                k: jax.device_put(v) if isinstance(v, np.ndarray) else v
                for k, v in batch.items()
            }

    buf = deque()
    for index, batch in enumerate(loader):  # index within this pass
        buf.append(put(batch, index))
        if len(buf) > ahead:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def _watched_iter(it, wd, name: str):
    """Bracket every fetch from ``it`` with a watchdog phase guard, so a
    feed that stops producing (dead worker pool, wedged host IO) trips the
    ``data_wait`` deadline instead of hanging the loop silently."""
    it = iter(it)
    while True:
        with wd.phase(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


# ---------------------------------------------------------------------------
# state setup shared by all three phases
# ---------------------------------------------------------------------------


def setup_state(
    config: Config,
    load: bool = False,
    model_file: Optional[str] = None,
    load_cnn: bool = False,
    cnn_model_file: Optional[str] = None,
    seed: Optional[int] = None,
) -> TrainState:
    """Initialize the train state, optionally restoring a checkpoint and/or
    importing a pretrained CNN — the main.py load sequence
    (/root/reference/main.py:49-53)."""
    if seed is None:
        seed = config.seed
    # set-up spans land in the run's telemetry where the CLI began it
    # before this call (cli.main); elsewhere they hit the null object
    restoring = bool(load or model_file)
    # where a checkpoint of this program is about to fill the tree, build
    # its shapes only: nothing is initialised (small programs compiled and
    # run, gigabytes for a language-model decoder) to be overwritten at
    # once, and the device never holds an initialised copy beside the
    # restored one.  (The reference's own .npy is imported leaf by leaf
    # INTO an initialised state.)
    shapes_only = restoring and not (model_file or "").endswith(".npy")
    with telemetry.span("setup/state"):
        if shapes_only:
            state = jax.eval_shape(
                lambda: create_train_state(jax.random.PRNGKey(seed), config)
            )
        else:
            state = create_train_state(jax.random.PRNGKey(seed), config)
    if restoring:
        if model_file and model_file.endswith(".npy"):
            # a checkpoint written by the *reference* itself (flat TF1
            # var.name dict, base_model.py:242-249) — imported via the
            # name-translation path so reference-trained models run here
            state, count = import_reference_checkpoint(state, model_file)
        else:
            from .data.vocabulary import vocab_fingerprint

            with telemetry.span("setup/restore"):  # read + verify
                state, count = restore_checkpoint(
                    state,
                    model_file=model_file,
                    save_dir=config.save_dir,
                    # fail fast on a vocabulary swap instead of silently
                    # skipping the mismatched embedding (partial restore)
                    expect_vocab=vocab_fingerprint(
                        config.vocabulary_file, config.vocabulary_size
                    ),
                )
        if count == 0:
            raise ValueError(
                f"checkpoint {model_file or config.save_dir} restored 0 tensors"
            )
        is_shape = lambda x: isinstance(x, jax.ShapeDtypeStruct)  # noqa: E731
        if shapes_only and any(map(is_shape, jax.tree_util.tree_leaves(state))):
            # a partial checkpoint (trimmed of its optimizer slots, say):
            # what it did not fill is initialised after all
            fresh = create_train_state(jax.random.PRNGKey(seed), config)
            state = jax.tree_util.tree_map(
                lambda got, new: new if is_shape(got) else got, state, fresh
            )
        print(f"{count} tensors loaded from checkpoint (step {int(state.step)}).")
    if load_cnn and cnn_model_file:
        state, count = apply_cnn_import(state, cnn_model_file)
        print(f"{count} pretrained CNN tensors loaded.")
    return state


class ProfilerWindow:
    """Shared ``jax.profiler`` trace-window bookkeeping for the step loops
    (train and both decode paths): trigger once at step >= start — resume-
    aware, like train always was — capture ``profile_num_steps`` steps,
    block on a sync target before stopping, and guarantee closure on loop
    exit.  A window left open would poison the process's NEXT
    ``start_trace`` (evaluate_sweep re-enters decode repeatedly), so
    callers close() in a finally/ExitStack."""

    def __init__(self, config: Config, max_start: Optional[int] = None) -> None:
        self._dir = config.profile_dir
        self._start = config.profile_start_step
        if max_start is not None:
            # decode loops pass their batch count: profile_start_step is a
            # train-step knob (default 5), and a short eval must still
            # trace rather than silently never opening the window
            self._start = min(self._start, max(max_start, 0))
        self._num = max(config.profile_num_steps, 1)
        self._on = False
        self._fired = False
        self._stop_at = -1
        self._last_sync = None

    def before_step(self, i: int) -> None:
        """Call before dispatching step ``i``; opens the window once.

        ``start_trace`` raises when another trace is already live in the
        process — e.g. an outer ``jax.profiler`` session running
        alongside ``--trace_export``'s host-side export, or a sweep whose
        previous window leaked.  The window must not take the run down
        for that: it marks itself fired FIRST (so a failed open is never
        retried every subsequent step) and degrades the collision to a
        warning, leaving ``_on`` false so ``after_step``/``__exit__``
        never issue the double ``stop_trace`` that would close the OUTER
        trace and leak this window's dir."""
        if self._dir and not self._fired and i >= self._start:
            self._fired = True
            self._stop_at = i + self._num
            try:
                jax.profiler.start_trace(self._dir)
            except Exception as e:
                print(
                    f"sat_tpu: profiler window skipped — start_trace failed "
                    f"(another trace active?): {e}",
                    file=sys.stderr,
                    flush=True,
                )
                return
            self._on = True

    def _stop(self) -> None:
        """Close the trace this window opened; a stop failure (the trace
        was stopped under us) degrades to a warning but still marks the
        window closed so it is never double-stopped."""
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            print(
                f"sat_tpu: profiler stop_trace failed ({e})",
                file=sys.stderr,
                flush=True,
            )
        self._on = False
        self._last_sync = None

    def after_step(self, i: int, sync) -> None:
        """Call after dispatching step ``i``; closes the window when the
        configured step count has been captured (blocks on ``sync`` so
        the trace contains completed device work)."""
        self._last_sync = sync  # __exit__'s sync target if the loop ends early
        if self._on and i + 1 >= self._stop_at:
            jax.block_until_ready(sync)  # sync-ok: trace-window close only
            self._stop()

    def __enter__(self) -> "ProfilerWindow":
        return self

    def __exit__(self, *exc) -> None:
        """Idempotent tail/error-path stop (loop ended inside the window,
        or an exception fired mid-window) — blocks on the last
        ``after_step`` sync target so the trace holds completed work."""
        if self._on:
            if self._last_sync is not None:
                try:
                    jax.block_until_ready(self._last_sync)  # sync-ok: window close
                except Exception:
                    pass  # sync target may be poisoned on the error path
            self._stop()
        self._last_sync = None


# ---------------------------------------------------------------------------
# telemetry wiring (docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------

# train-loop phase decomposition: disjoint sub-intervals of "train/step"
# (their totals + the "other" residual reconstruct measured wall time) and
# the nested spans that occur INSIDE a phase (reported, not summed)
_TRAIN_PHASES = (
    "train/data_wait", "train/place", "train/dispatch", "train/log_sync",
    "train/log_io", "train/summary", "train/checkpoint",
)
_TRAIN_NESTED = ("feed/device_put", "ckpt/write", "ckpt/snapshot")
_DECODE_PHASES = ("decode/data_wait", "decode/dispatch", "decode/drain")
_DECODE_NESTED = (
    "feed/device_put",
    "decode/dispatch/encode", "decode/dispatch/beam",
    "decode/drain/wait", "decode/drain/detok",
)

_compile_listener_installed = False


def _install_compile_listener() -> None:
    """Feed XLA compile count/seconds into the active telemetry recorder.

    ``jax.monitoring`` listeners cannot be unregistered, so install ONE
    process-wide callback that dispatches through ``telemetry.get()`` —
    re-running train() in the same process (tests, sweeps) never stacks a
    second listener, and with telemetry off the callback hits the null
    object."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    try:
        from jax import monitoring

        def _cb(event: str, duration: float, **kw) -> None:
            if "compil" in event:
                tel = telemetry.get()
                tel.count("jax/compiles")
                tel.count("jax/compile_s", duration)

        monitoring.register_event_duration_secs_listener(_cb)
    except Exception:
        pass  # observability never takes the run down


def _timed_iter(it, tel, name: str, first: int = 0):
    """Yield from ``it``, recording each ``next()`` wait as a ``name``
    span — the feed-starvation phase of the consuming loop — numbered
    from ``first`` (the step or batch the item is for)."""
    it = iter(it)
    for k in itertools.count(first):
        span = tel.span(name, k)
        span.__enter__()
        try:
            item = next(it)
        except StopIteration:
            span.drop()  # the end of the feed is no wait for data
            return
        span.__exit__(None, None, None)
        yield item


class StallWatch:
    """Evidence for the host stalls of PERF.md (0.2-4 s, cause unknown):
    when an iteration the loop already timed takes over ``factor`` times
    the running median, record one ``host/stall`` span (``arg`` = the
    step) and gauge what the process did since the last boundary:
    involuntary context switches (descheduled), major page faults
    (paging), garbage collections, and its own CPU time (little of it in
    a long stall = blocked: waiting on the device or on IO).  One
    comparison an iteration; the median and the baseline are refreshed
    every ``every`` iterations; no thread.

    ``synced`` keeps the iterations that end in the loop's device sync
    apart from those that only enqueue: the unsynced train loop runs
    ``log_every`` - 1 steps ahead at the loader's pace and then waits out
    the device at the boundary, so its step times have two medians, an
    order of magnitude apart."""

    def __init__(self, tel, factor: float = 3.0, every: int = 8) -> None:
        self._tel, self._factor, self._every = tel, factor, every
        # per kind of iteration (enqueue only, synced): durations seen,
        # how many, the limit in force
        self._lanes = [
            [collections.deque(maxlen=4 * every), 0, np.inf] for _ in range(2)
        ]
        self._base = self._sample()

    @staticmethod
    def _sample():
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (
            time.perf_counter_ns(), time.process_time_ns(), ru.ru_nivcsw,
            ru.ru_majflt, sum(g["collections"] for g in gc.get_stats()),
        )

    def iteration(self, k: int, t0_ns: int, dur_ns: int, synced: bool = False) -> None:
        lane = self._lanes[synced]
        if dur_ns > lane[2]:
            now = self._sample()
            wall, cpu, nivcsw, majflt, collections_ = (
                n - b for n, b in zip(now, self._base)
            )
            tel = self._tel
            tel.record("host/stall", t0_ns, dur_ns, k)
            tel.count("host/stalls")
            tel.gauge("host/stall_step", k)
            tel.gauge("host/stall_ms", dur_ns / 1e6)
            tel.gauge("host/stall_since_boundary_ms", wall / 1e6)
            tel.gauge("host/stall_cpu_ms", cpu / 1e6)
            tel.gauge("host/stall_nivcsw", nivcsw)
            tel.gauge("host/stall_majflt", majflt)
            tel.gauge("host/stall_gc", collections_)
            self._base = now
        lane[0].append(dur_ns)
        lane[1] += 1
        if lane[1] % self._every == 0:
            lane[2] = self._factor * np.median(lane[0])  # host durations
            self._base = self._sample()


class DeviceOccupancy:
    """The loop's own account of the device standing empty: every stretch in
    which the host KNOWS that nothing is enqueued on the chip, recorded as
    one ``<family>/device_empty`` span (``family`` = ``train`` | ``decode``;
    ``arg`` = the step or batch whose dispatch ended the stretch) on the
    clock, thread and ring of the loop's phase spans, so that a reader
    intersects the two: ``device_empty`` x ``decode/drain/detok`` is the
    detokenise time that ran with the device empty.

    ``enqueued(handle, k)`` is called when dispatch k has returned, with an
    array that program computes; an open stretch ends there.  ``observe()``
    is called at the phase boundaries the loop already has: if no stretch
    is open and the newest handle's ``is_ready()`` is true (the runtime's
    own word that the newest enqueued work is finished: non-blocking, NOT a
    sync, and no in-order stream is assumed), one opens.  With a stretch
    open ``observe()`` is one comparison.

    So a stretch is a LOWER bound of a device gap: it opens at the first
    boundary at which the host could know and closes when the next program
    is enqueued, not when it starts.  Behind a blocking sync
    (``train/log_sync``, ``decode/drain/wait``) it opens when the sync
    returns, so the sync's own tail after the device ran dry (its copies
    to the host, the drain's slice programs) is not in it; between syncs
    its resolution is the phase it falls in (PERF.md section 6 has the
    measured ratio to a trace's gaps).  The stretch is also entered as the
    profiler's annotation, by hand as ``_Span`` does, with the index it is
    expected to end at: a trace taken with the host tracer on shows the
    program's account of the gap on the device's clock beside the gap.

    ``publish()`` sets the gauge ``<family>/device_empty_share``: empty
    time over the time since the loop's first dispatch (a fraction).
    Telemetry off: ``NULL_OCCUPANCY``, which never calls ``is_ready()``."""

    def __init__(self, tel, family: str, clock=time.perf_counter_ns) -> None:
        self._tel, self._clock = tel, clock
        self._span = family + "/device_empty"
        self._gauge = family + "/device_empty_share"
        self._ready = None        # is_ready of the newest handle enqueued
        self._next = 0            # the index after it: where an open stretch should end
        self._t_open: Optional[int] = None
        self._ann = None          # the open stretch's annotation
        self._t_first: Optional[int] = None
        self._empty_ns = 0

    def enqueued(self, handle, k: int) -> None:
        if self._t_open is not None:
            now = self._clock()
            t_open = self._end_stretch()
            self._tel.record(self._span, t_open, now - t_open, k)
            self._empty_ns += now - t_open
        elif self._t_first is None:
            self._t_first = self._clock()
        self._ready = getattr(handle, "is_ready", None)
        self._next = k + 1

    def observe(self) -> None:
        if self._t_open is not None or self._ready is None or not self._ready():
            return
        annotate = self._tel.annotate
        if annotate is not None:
            self._ann = annotate(self._span, i=self._next)
            self._ann.__enter__()
        self._t_open = self._clock()

    def publish(self) -> None:
        if self._t_first is None:
            return
        now = self._clock()
        empty = self._empty_ns + (0 if self._t_open is None else now - self._t_open)
        self._tel.gauge(self._gauge, empty / max(now - self._t_first, 1))

    def close(self) -> None:
        """The loop is over: a stretch that no dispatch ended is dropped."""
        self._end_stretch()
        self._ready = None

    def _end_stretch(self) -> Optional[int]:
        t_open, self._t_open = self._t_open, None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return t_open


class _NullOccupancy:
    """``DeviceOccupancy`` with telemetry off: nothing is asked of any handle."""

    __slots__ = ()

    def enqueued(self, handle, k: int) -> None:
        pass

    def observe(self) -> None:
        pass

    def publish(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_OCCUPANCY = _NullOccupancy()


def _telemetry_dir(config: Config) -> str:
    return config.telemetry_dir or os.path.join(config.summary_dir, "telemetry")


def _telemetry_begin(config: Config):
    """Install the run's telemetry implementation (fresh buffers when on,
    the null object when off) and the process-wide compile listener."""
    if config.telemetry:
        tel = telemetry.enable(config.telemetry_buffer)
        # every ``with tel.span`` also enters the profiler's annotation, so
        # a trace taken with the host tracer on (/profile, SIGUSR2,
        # --profile_steps) shows the host phases on the device's clock
        tel.annotate = jax.profiler.TraceAnnotation
        from .telemetry import xla as xla_acct

        xla_acct.reset()  # per-run compile accounting (compile_report.json)
        # its op_scopes reads the named scopes out of the executables'
        # metadata, which the persistent cache's key leaves out by default:
        # a cache filled by a build with other scope names would hand back
        # theirs.  With telemetry on, metadata is part of the key.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    else:
        tel = telemetry.disable()
    _install_compile_listener()
    return tel


def _device_static() -> dict:
    """Heartbeat ``static`` device facts: backend plus the first local
    device's kind/platform."""
    d0 = jax.local_devices()[0]
    return {
        "backend": jax.default_backend(),
        "num_devices": jax.device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "device_kind": d0.device_kind,
        "device_platform": d0.platform,
    }


def _fleet_gather(vec):
    """Collective transport for ``FleetPlane.tick``: all-gather one ~6
    float64 host vector across processes.  The fleet module is jax-free,
    so the runtime injects this; any failure (no distributed init, mixed
    topologies mid-teardown) returns None and the plane falls back to
    reading sidecar files.  Single-process runs skip the collective
    entirely — the sidecar path is already exact."""
    if jax.process_count() == 1:
        return None
    try:
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(vec)
        return np.asarray(out, dtype=np.float64)  # sync-ok: ~6 host scalars/process at the log boundary
    except Exception:
        return None


def _device_memory_sampler():
    """Heartbeat sampler: per-device HBM bytes-in-use via the backend's
    ``memory_stats()``.  CPU devices return None — the sampler then
    contributes nothing, per docs/OBSERVABILITY.md."""

    def sample() -> dict:
        per: dict = {}
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats and "bytes_in_use" in stats:
                per[str(d.id)] = int(stats["bytes_in_use"])
        return {"hbm_bytes_in_use": per} if per else {}

    return sample


def _telemetry_finish(tel, config: Config, phase: str) -> None:
    """End-of-run exports: Chrome trace JSON, the per-phase step-time
    breakdown (printed + saved), run from an ExitStack callback so an
    interrupted run still leaves its trace behind."""
    from .telemetry import exporters

    tdir = _telemetry_dir(config)
    trace_path = config.trace_export or os.path.join(
        tdir, "trace.json" if phase == "train" else f"trace-{phase}.json"
    )
    exporters.export_chrome_trace(tel, trace_path)
    step_span, phases, nested = (
        ("train/step", _TRAIN_PHASES, _TRAIN_NESTED)
        if phase == "train"
        else ("decode/batch", _DECODE_PHASES, _DECODE_NESTED)
    )
    report = exporters.step_breakdown(tel, step_span, phases, nested)
    if report is not None:
        print(exporters.format_breakdown(report), flush=True)
        exporters.save_breakdown(
            report,
            os.path.join(
                tdir,
                "breakdown.json" if phase == "train" else f"breakdown-{phase}.json",
            ),
        )
    # compile-time cost/memory accounting (telemetry/xla.py): one report
    # per phase, surfaced in the end-of-run printout next to the breakdown
    from .telemetry import xla as xla_acct

    summary = xla_acct.format_summary()
    if summary is not None:
        print(summary, flush=True)
        xla_acct.write_report(
            os.path.join(
                tdir,
                "compile_report.json"
                if phase == "train"
                else f"compile_report-{phase}.json",
            )
        )


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train(
    config: Config,
    state: Optional[TrainState] = None,
    dataset: Optional[DataSet] = None,
    seed: Optional[int] = None,
    tel=None,
) -> TrainState:
    """Epoch × batch training loop (reference base_model.py:39-68).

    With ``mesh_shape`` spanning more than one device the same loop runs
    SPMD: state sharded per the (data, model) placement rules, batches
    data-sharded, XLA inserting the gradient all-reduce — the synchronous
    upgrade of the reference's async PS strategy (SURVEY.md §2.13).

    tel: the telemetry the caller began for this run (cli.main does, so
    that the set-up before this call is inside it); None begins it here."""
    if seed is None:
        seed = config.seed
    # host-side tracing (docs/OBSERVABILITY.md): fresh ring buffers when
    # config.telemetry, the null object otherwise — the off path leaves
    # run behavior bit-for-bit unchanged
    if tel is None:
        tel = _telemetry_begin(config)
    if dataset is None:
        # the explicit kwarg must drive the WHOLE run — shuffle order
        # included — not just init/dropout (batch order is f(seed, epoch))
        with tel.span("setup/data"):
            dataset = prepare_train_data(
                config if seed == config.seed else config.replace(seed=seed)
            )
    if dataset.count == 0:
        raise ValueError(
            "training dataset is empty after preparation — every caption was "
            "filtered out (cap-length <= max_caption_length and vocab "
            "filters, reference coco.py:323-361) or the caption file has no "
            "annotations; check train_caption_file/max_caption_length"
        )
    if state is None:
        state = setup_state(config, seed=seed)

    if int(np.prod(config.mesh_shape)) > 1:
        from .parallel import make_mesh, make_parallel_train_step, sync_processes
        from .parallel.collectives import make_global_batch
        from .parallel.data import mesh_data_shard, process_local_dataset
        from .parallel.sharding import shard_train_state

        mesh = make_mesh(config)
        if config.context_parallel > 1:
            # 'model' axis spent on the context grid (distributed-softmax
            # attention) instead of vocab TP; params stay replicated
            from .parallel.context import (
                make_context_parallel_train_step,
                validate_cp_mesh,
            )

            validate_cp_mesh(config, mesh)
            # realign before the sharded placement: its cross-host
            # assert_equal opens a fresh communicator rendezvous
            sync_processes("sat_tpu:shard_state")
            placement_config = config.replace(vocabulary_size=-1)
            state = shard_train_state(
                state, placement_config, mesh
            )  # vocab rule disabled → fully replicated placement
            train_step = make_context_parallel_train_step(config, mesh)
        else:
            sync_processes("sat_tpu:shard_state")
            placement_config = config
            state = shard_train_state(state, config, mesh)
            train_step = make_parallel_train_step(config, mesh)
        # sentinel rollback restores host-side numpy leaves; mesh runs must
        # re-place them with the same sharding rules as the initial state
        reshard_state = lambda s: shard_train_state(s, placement_config, mesh)  # noqa: E731
        # feed keyed on the DATA-axis layout: processes along the model
        # axis (CP / cross-host TP) share a data row and feed identical
        # replicas of it (mesh_data_shard docstring)
        shard_idx, n_shards = mesh_data_shard(mesh)
        dataset = process_local_dataset(
            dataset, process_index=shard_idx, process_count=n_shards
        )
        place_batch = lambda b: make_global_batch(mesh, b)  # noqa: E731
        wrap_feed = lambda l: l  # noqa: E731 — make_global_batch places
    else:
        train_step = make_jit_train_step(config)
        place_batch = lambda b: b  # noqa: E731
        reshard_state = lambda s: s  # noqa: E731 — jit re-places on dispatch
        # async device slot: batch k+1's host→HBM transfer is dispatched
        # while step k still runs, so the step never pays the copy
        wrap_feed = device_prefetch
    with tel.span("setup/data"):  # opens (or builds) the shard cache
        loader = make_loader(config, dataset)
    # Typed key with the configured bit-generator impl: dropout-mask
    # generation is ~40% of the flagship train step under threefry (the
    # decoder draws ~130M mask bits/step); config.rng_impl="rbg" routes it
    # to the TPU hardware generator instead.  Param init (above) stays on
    # threefry so weights are impl-independent.
    root_rng = jax.random.key(seed + 1, impl=config.rng_impl)

    # Host-side step counter: fetching int(state.step) every iteration would
    # block the host on the just-dispatched device step, serializing the loop
    # with the device and defeating async dispatch + prefetch.  Sync once
    # here (resume-aware), then count locally; device_get only when logging.
    step = int(state.step)
    stopped = False
    # resilience wiring (docs/RESILIENCE.md): process-wide IO-retry knobs,
    # the env-armed fault plan (inert in production — every hook is a
    # host-side compare), the log-boundary anomaly sentinel, and graceful
    # SIGTERM/SIGINT draining
    _retry.configure(config.io_retries, config.io_retry_base_s)
    plan = FaultPlan.from_env()
    sentinel = AnomalySentinel(config.anomaly_policy, config.anomaly_spike_factor)
    # async checkpointing: the step loop pays only the device→host
    # snapshot; serialization + disk write overlap the following steps
    # (AsyncCheckpointWriter docstring; sync fallback multi-host/off)
    async_writer = (
        AsyncCheckpointWriter()
        if config.async_checkpoint and jax.process_count() == 1
        else None
    )
    ckpt_save = async_writer.save if async_writer else save_checkpoint
    # incarnation number under `--supervise`: the restart loop exports it
    # so heartbeat.json can show how many times this run has come back
    tel.gauge("supervisor/restarts", int(os.environ.get(RESTARTS_ENV, "0") or 0))
    # hang/wedge watchdog (docs/RESILIENCE.md): a side thread observing the
    # phase guards below, escalating gauges → stack dump → abort with exit
    # code 86 when a tracked phase stops completing.  Constructed always so
    # the guards are uniform; the observer thread only runs when
    # config.watchdog_interval > 0 (unstarted, a guard is two dict writes).
    wd = Watchdog(
        deadlines_from_config(config),
        poll_s=config.watchdog_interval or 1.0,
        grace_s=config.watchdog_grace_s,
        dump_path=os.path.join(_telemetry_dir(config), "watchdog_stacks.txt"),
        pre_abort=async_writer.flush if async_writer else None,
        tel=tel,
    )
    compile_probed = False  # train_step analyzed once, on the first batch
    # the first call of train_step is part of set-up: trace + compile, or
    # the load from the cache
    first_dispatch = tel.span("setup/first_dispatch")
    stall = StallWatch(tel) if tel.enabled else None
    occupancy = DeviceOccupancy(tel, "train") if tel.enabled else NULL_OCCUPANCY
    import contextlib

    final_path: Optional[str] = None
    # on-demand live profiler window (telemetry/profwin.py): armed below
    # when telemetry is on; SIGUSR2 latches a flag the log boundary drains
    profile_trigger = None
    profile_latch = None
    # fleet telemetry plane + black-box flight recorder (docs/
    # OBSERVABILITY.md "Fleet & Postmortem"): built below when configured
    fleet_plane = None
    bb = None
    # the ExitStack drains the async writer LAST (after SummaryWriter
    # closes), on success and on exception alike — queued checkpoint
    # writes survive an interrupt and worker failures surface
    with contextlib.ExitStack() as _stack, SummaryWriter(
        config.summary_dir
    ) as writer, GracefulShutdown() as shutdown:
        if tel.enabled:
            # LIFO: trace/breakdown export runs last, after the heartbeat's
            # final beat, which itself runs after the async writer drains —
            # the artifacts see the final step and the final checkpoint
            _stack.callback(_telemetry_finish, tel, config, "train")
            _stack.callback(occupancy.close)
            if config.heartbeat_interval > 0:
                from .telemetry.heartbeat import Heartbeat

                hb = Heartbeat(
                    os.path.join(_telemetry_dir(config), "heartbeat.json"),
                    config.heartbeat_interval,
                    tel,
                    static={"phase": "train", **_device_static()},
                    sampler=_device_memory_sampler(),
                )
                _stack.callback(hb.stop)
                hb.start()
            else:
                hb = None
            # read-only Prometheus scrape endpoint (telemetry/promtext.py)
            # riding the heartbeat payload — zero new syncs, a bind
            # failure degrades to a warning
            if config.metrics_port > 0:
                from .telemetry.promtext import MetricsListener

                ml = MetricsListener(
                    "127.0.0.1",
                    config.metrics_port,
                    tel,
                    payload_fn=hb.payload if hb is not None else None,
                )
                if ml.start():
                    _stack.callback(ml.stop)
            # SLO engine (telemetry/slo.py): declared train objectives
            # (captions/s floor, checkpoint-age ceiling) evaluated on a
            # side thread; transitions land in slo.jsonl and slo/* gauges
            # surface in heartbeat.json
            from .telemetry.slo import SLOEngine, objectives_from_config

            slo_objectives = objectives_from_config(config, "train")
            if slo_objectives:
                slo_engine = SLOEngine(
                    tel,
                    slo_objectives,
                    jsonl_path=os.path.join(
                        _telemetry_dir(config), "slo.jsonl"
                    ),
                    cap_bytes=int(config.telemetry_log_cap_mb * 1e6),
                    fast_s=config.slo_window_fast_s,
                    slow_s=config.slo_window_slow_s,
                ).start(
                    interval_s=max(
                        0.1, min(5.0, config.slo_window_fast_s / 4)
                    )
                )
                _stack.callback(slo_engine.stop)
            # SIGUSR2 → bounded live profiler capture, drained at the log
            # boundary (signals are async; profiler starts are not)
            import signal as _signal

            from .telemetry.profwin import ProfileLatch, SignalTrigger

            profile_latch = ProfileLatch(_telemetry_dir(config))
            _stack.callback(profile_latch.stop_now)
            profile_trigger = SignalTrigger()
            if hasattr(_signal, "SIGUSR2"):
                profile_trigger.install(_signal.SIGUSR2)
            # fleet plane (telemetry/fleet.py): every process writes a
            # heartbeat_p<i>.json sidecar at the log boundary; process 0
            # merges the fleet view into fleet.json + fleet/* gauges.
            # finish() is registered so the terminal step is recorded
            # even when the loop dies between boundaries.
            if config.fleet_telemetry:
                from .telemetry.fleet import FleetPlane

                fleet_dir = config.fleet_dir or _telemetry_dir(config)
                fleet_plane = FleetPlane(
                    fleet_dir,
                    jax.process_index(),
                    jax.process_count(),
                    tel,
                    straggler_factor=config.straggler_factor,
                    history_cap_bytes=int(config.telemetry_log_cap_mb * 1e6),
                )
                _stack.callback(fleet_plane.finish)
        # black-box flight recorder (telemetry/blackbox.py): bounded
        # on-disk ring journaling recent state; abnormal exits (watchdog
        # 86, corruption 87, sentinel trip, uncaught exception, SIGTERM
        # mid-checkpoint) dump a postmortem bundle from it.  The ExitStack
        # runs the finalizer chain on clean teardown; the atexit hook
        # covers paths that unwind without reaching it.
        if config.blackbox:
            from .resilience.quarantine import ledger_path_for
            from .telemetry import blackbox as _blackbox

            _bb_tdir = _telemetry_dir(config)
            bb = _blackbox.BlackBox(os.path.join(_bb_tdir, "blackbox"), tel)
            _blackbox.install(
                bb,
                telemetry_dir=_bb_tdir,
                fleet_dir=(
                    fleet_plane.fleet_dir if fleet_plane is not None else ""
                ),
                config_snapshot=config.to_dict(),
                quarantine_ledger=ledger_path_for(config),
            )
            _stack.callback(_blackbox.run_finalizers)
            bb.event("train_start", step=step)
        if async_writer:
            _stack.callback(async_writer.close)
        if config.watchdog_interval > 0:
            # LIFO: the observer stops BEFORE the writer drain above runs,
            # so a slow final drain is never mistaken for a wedge
            _stack.callback(wd.stop)
            wd.start()
        # resume-aware trace window (>= start, once); the ExitStack exit
        # keeps an exception mid-window from leaving the profiler open
        prof = _stack.enter_context(ProfilerWindow(config))
        if int(np.prod(config.mesh_shape)) > 1:
            # realign before the first step dispatch: its execution opens
            # the per-axis communicators (fresh rendezvous windows), and
            # loader startup / executable cache loads drift processes
            # apart (sync_processes docstring; imported with the mesh
            # machinery above under this same condition)
            sync_processes("sat_tpu:first_step")
        while True:  # re-entered only by a sentinel rollback
            rollback = False
            # Mid-epoch resume: batch order is a pure function of (seed,
            # epoch) (DataSet._set_epoch), so the cursor IS the global step
            # — fast-forward to exactly where the checkpointed run stopped
            # and the resumed run replays the identical batch + dropout-key
            # sequence.  A rollback re-enters here with restored weights
            # and the cursor already PAST the poison step.
            start_epoch, skip_batches = divmod(step, dataset.num_batches)
            if start_epoch < config.num_epochs:
                dataset.seek(start_epoch, skip_batches)
            for epoch in range(start_epoch, config.num_epochs):
                # per-batch visibility, tqdm-style (reference
                # base_model.py:49-50); metric-free so the async dispatch
                # chain never syncs for it
                bar = Progress(
                    dataset.num_batches,
                    desc=f"epoch {epoch + 1}/{config.num_epochs}",
                    initial=skip_batches if epoch == start_epoch else 0,
                )
                # step span boundary: each iteration records data_wait
                # (inside _timed_iter) + body phases, and the step total
                # from the previous boundary — no extra syncs, ~1 µs/step
                step_t0 = time.perf_counter_ns()
                for batch in _timed_iter(
                    _watched_iter(wrap_feed(loader), wd, "data_wait"),
                    tel,
                    "train/data_wait",
                    first=step,
                ):
                  # watchdog net around the whole body: a wedge landing
                  # between the finer-grained guards still trips the
                  # 'step' deadline (deadlines_from_config docstring)
                  with wd.phase("step"):
                    occupancy.observe()  # after train/data_wait
                    if config.max_steps and step >= config.max_steps:
                        stopped = True
                        break
                    plan.maybe_kill(step)  # injected preemption (inert unarmed)
                    plan.maybe_wedge(step)  # injected silent hang (inert unarmed)
                    plan.maybe_slow(step)  # injected slow-but-alive step
                    if shutdown.stop_requested:
                        # stop at the step boundary: the final save below
                        # flushes through the writer and train() returns
                        # cleanly so the CLI can exit 0 for the supervisor
                        stopped = True
                        break
                    prof.before_step(step)
                    with tel.span("train/place", step):
                        placed = place_batch(
                            {
                                "images": batch["images"],
                                "word_idxs": batch["word_idxs"],
                                "masks": batch["masks"],
                            }
                        )
                        step_rng = jax.random.fold_in(root_rng, step)
                    occupancy.observe()
                    if tel.enabled and not compile_probed:
                        # AOT cost/memory accounting BEFORE the first
                        # dispatch: lowering reads only avals (donated
                        # buffers stay intact) and seeds the same
                        # lower/compile caches the call below hits, so
                        # the step is not compiled twice
                        compile_probed = True
                        from .telemetry import xla as xla_acct

                        xla_acct.analyze(
                            "train_step", train_step, state, placed,
                            step_rng, tel=tel,
                        )
                    with tel.span("train/dispatch", step), wd.phase(
                        "dispatch"
                    ), first_dispatch:
                        state, metrics = train_step(state, placed, step_rng)
                    occupancy.enqueued(state.step, step)
                    first_dispatch = telemetry.NULL_SPAN
                    prof.after_step(step, state)
                    done = step  # the step just dispatched: its spans' arg
                    step += 1  # == int(state.step), without a device sync
                    tel.gauge("train/step", step)
                    # injected NaN gradient (inert unarmed): poisons params
                    # and metrics exactly as a diverged update would
                    state, metrics = plan.maybe_poison(step, state, metrics)
                    if step % config.log_every == 0:
                        # the loop's ONE host sync — the sentinel reads
                        # these already-fetched floats, adding no syncs
                        with tel.span("train/log_sync", done):
                            host = {
                                k: float(v)  # sync-ok: the loop's ONE log-boundary fetch
                                for k, v in jax.device_get(metrics).items()
                            }
                        occupancy.observe()
                        # host IO of the boundary: nothing is queued on
                        # the device behind the sync above while it runs
                        # (train/device_empty, opened just above, shows it)
                        with tel.span("train/log_io", done):
                            writer.scalars(step, host)
                            if tel.enabled:
                                from .telemetry import exporters

                                # diag taps (telemetry/device.py) ride the
                                # host dict just fetched: gauging them here
                                # lands the last-known snapshot in
                                # telemetry.jsonl and heartbeat.json without
                                # touching the device again
                                for k, v in host.items():
                                    if k.startswith("diag/"):
                                        tel.gauge(k, v)
                                occupancy.publish()
                                exporters.append_jsonl(
                                    tel,
                                    os.path.join(
                                        _telemetry_dir(config), "telemetry.jsonl"
                                    ),
                                    step,
                                    cap_bytes=int(
                                        config.telemetry_log_cap_mb * 1e6
                                    ),
                                )
                                # SIGUSR2 since the last boundary → start a
                                # bounded live profiler window (refusals —
                                # capture already running — just log)
                                if (
                                    profile_trigger is not None
                                    and profile_trigger.pop()
                                ):
                                    ok, info = profile_latch.start(
                                        config.profile_window_ms
                                    )
                                    print(
                                        "sat_tpu: live profiler window "
                                        + (f"-> {info}" if ok else f"refused ({info})"),
                                        file=sys.stderr,
                                        flush=True,
                                    )
                            # fleet tick: every process writes its sidecar
                            # (and joins the gather when available); only
                            # process 0 aggregates.  Black-box journal rides
                            # the same boundary — both are pure host IO.
                            if fleet_plane is not None:
                                fleet_plane.tick(step, gather_fn=_fleet_gather)
                            if bb is not None:
                                bb.journal(step)
                            if sentinel.check(step, host) == "rollback":
                                if bb is not None:
                                    from .telemetry import blackbox as _bbx

                                    bb.event(
                                        "anomaly_rollback",
                                        step=step,
                                        reason=sentinel.last_reason,
                                    )
                                    _bbx.dump(
                                        "anomaly_rollback",
                                        step=step,
                                        reason_detail=sentinel.last_reason,
                                    )
                                rollback = True
                                break
                        occupancy.observe()
                    if (
                        config.var_summary_period
                        and step % config.var_summary_period == 0
                    ):
                        with tel.span("train/summary", done):
                            writer.variable_stats(step, state.params)
                    if (
                        config.save_period
                        and step % config.save_period == 0
                        and not sentinel.suppress_save
                    ):
                        with tel.span("train/checkpoint", done), wd.phase("checkpoint"):
                            ckpt_save(state, config, healthy=sentinel.healthy)
                    bar.update()
                    now = time.perf_counter_ns()
                    tel.record("train/step", step_t0, now - step_t0, done)
                    if stall is not None:
                        stall.iteration(
                            done, step_t0, now - step_t0,
                            synced=step % config.log_every == 0,
                        )
                    step_t0 = now
                bar.close()
                if stopped or rollback:
                    break
                print(f"epoch {epoch + 1}/{config.num_epochs} done (step {int(state.step)})")
            if rollback:
                if async_writer:
                    # the save that blessed LAST_GOOD may still be queued;
                    # the pointer is only readable once it drains
                    async_writer.flush()
                restored = _restore_last_good(state, config, step)
                if restored is None:
                    # nothing verifiable to roll back to — degrade to warn
                    # and keep training rather than dying here
                    sentinel.policy = "warn"
                else:
                    state = reshard_state(restored)
                    sentinel.note_rolled_back()
                continue
            break
        # the final save rides the same queue: submission order guarantees
        # it lands AFTER any still-draining periodic write (config.json
        # must end at the final step), and the ExitStack close joins the
        # worker before train() returns
        if sentinel.suppress_save:
            print(
                "sat_tpu: final checkpoint suppressed — metrics were "
                f"anomalous under anomaly_policy=skip ({sentinel.last_reason})",
                file=sys.stderr,
                flush=True,
            )
        else:
            # defer(): a second (force-kill) SIGTERM arriving while the
            # final write is in flight is held until the flush below has
            # landed AND verified — the one window where the old behavior
            # could kill the run between rename and verify
            with shutdown.defer():
                final_path = ckpt_save(state, config, healthy=sentinel.healthy)
                if async_writer:
                    async_writer.flush()
        if shutdown.stop_requested:
            print(
                f"sat_tpu: stopped on {shutdown.signal_name} at step {step}; "
                "final checkpoint flushed — relaunch with --load to resume",
                file=sys.stderr,
                flush=True,
            )
            if bb is not None:
                # the stop raced the final checkpoint (defer() held the
                # force-kill window open) — leave a bundle so a later
                # "did the tail land?" question has an answer
                from .telemetry import blackbox as _bbx

                bb.event(
                    "sigterm_stop", step=step, signal=shutdown.signal_name
                )
                _bbx.dump(
                    "sigterm_during_checkpoint",
                    exit_code=0,
                    step=step,
                    signal=shutdown.signal_name,
                    final_checkpoint=final_path or "",
                )
    # the writer is drained here; the final save must actually be on disk
    # and restorable before train() reports success (a lost final
    # checkpoint silently discards the training tail)
    if final_path is not None and jax.process_index() == 0:
        ok, reason = lineage.verify_checkpoint(final_path)
        if not ok:
            raise CheckpointWriteError(
                f"final checkpoint {final_path} did not land: {reason}"
            )
    return state


def _restore_last_good(
    state: TrainState, config: Config, step: int
) -> Optional[TrainState]:
    """Sentinel-rollback restore: load the newest verifiable ``LAST_GOOD``
    checkpoint into the (poisoned) state skeleton, keeping the HOST step
    counter — the loader then fast-forwards PAST the poison step instead
    of replaying it (with deterministic dropout keys a replay would just
    reproduce the same divergence).  Returns None when nothing verifiable
    exists (caller degrades to warn)."""
    path = lineage.last_good_checkpoint(config.save_dir)
    if path is None:
        print(
            "sat_tpu: rollback requested but save_dir holds no verifiable "
            f"LAST_GOOD checkpoint ({config.save_dir})",
            file=sys.stderr,
            flush=True,
        )
        return None
    restored, count = restore_checkpoint(state, model_file=path)
    if count == 0:
        print(
            f"sat_tpu: rollback restore from {path} loaded 0 tensors",
            file=sys.stderr,
            flush=True,
        )
        return None
    print(
        f"sat_tpu: rolled back to {path} "
        f"(step {int(np.asarray(restored.step))}); resuming forward at "  # sync-ok: rollback epilogue, off the hot path
        f"step {step}, skipping the poison window",
        file=sys.stderr,
        flush=True,
    )
    # device-owned copy, not a numpy scalar: the step leaf is donated into
    # train_step along with the rest of the state (see _assign_leaves)
    return restored._replace(step=jax.numpy.array(np.asarray(step, np.int32)))  # sync-ok: host int, not a device value


# ---------------------------------------------------------------------------
# shared decoding driver
# ---------------------------------------------------------------------------


def _eos_id(vocabulary: Vocabulary) -> int:
    """Vocabulary index of the '.' terminator (reference base_model.py:229)."""
    return vocabulary.word2idx["."]


def decode_dataset(
    config: Config,
    state: TrainState,
    dataset: DataSet,
    vocabulary: Vocabulary,
    tel=None,
) -> List[Dict[str, Any]]:
    """Beam-search every image; returns [{image_id, image_file, caption,
    prob}] with last-batch padding dropped and per-image dedup — the
    reference's fake_count/set handling (base_model.py:83-88).

    tel: the telemetry the caller began for this run (see train); None
    begins it here, so every decode of a sweep starts fresh."""
    # host tracing over the decode loop: data_wait / dispatch / drain per
    # batch, and the stretches in which the device stood empty meanwhile
    # (the drain of batch n was meant to overlap batch n+1's beam search;
    # see the comment above ``prev`` for what it does)
    if tel is None:
        tel = _telemetry_begin(config)
    occupancy = DeviceOccupancy(tel, "decode") if tel.enabled else NULL_OCCUPANCY
    variables: Dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats

    eos = _eos_id(vocabulary)

    # Mesh-parallel decoding: encoder + beam search in one jitted program
    # with the image batch sharded over 'data' — eval/test scale over the
    # mesh exactly like training does (reference capability:
    # base_model.py:70-117, which is strictly single-device).  Multi-host:
    # each process feeds its shard of the dataset and the beam results are
    # all-gathered so every host assembles the full result list.
    if int(np.prod(config.mesh_shape)) > 1:
        from .parallel import make_mesh, sync_processes
        from .parallel.collectives import make_global_batch
        from .parallel.data import mesh_data_shard, process_local_dataset
        from .parallel.sharding import named_shardings
        from .parallel.train import make_parallel_beam_search

        mesh = make_mesh(config)
        dp = mesh.shape.get("data", 1)
        if config.batch_size % dp != 0:
            raise ValueError(
                f"batch_size={config.batch_size} not divisible by the "
                f"data-axis size {dp} for mesh decoding"
            )
        # Placement mirrors training's (docs/PARALLELISM.md):
        # * vocab-TP runs: embedding table + softmax projection shard over
        #   'model' instead of idling it, and GSPMD compiles the TP decode
        #   (sharded logits, collective softmax/top-k) from the shardings
        #   alone;
        # * context-parallel runs trained with params REPLICATED
        #   (train() above, the 'model' axis was spent on the context
        #   grid) — eval keeps that placement AND spends the 'model' axis
        #   the same way: shard_map context-parallel beam search with the
        #   grid sharded and the distributed-softmax attend
        #   (parallel/context.py cp_beam_search).
        if config.context_parallel > 1:
            from .parallel.context import (
                make_context_parallel_beam_search,
                validate_cp_mesh,
            )

            validate_cp_mesh(config, mesh)
            placement_config = config.replace(vocabulary_size=-1)  # replicated
            make_caption_fn = make_context_parallel_beam_search
        else:
            placement_config = config
            make_caption_fn = make_parallel_beam_search
        # realign before the sharded placement (fresh communicator
        # rendezvous — see sync_processes): eval is reached after
        # unsynchronized host work (data prep, training epilogue)
        sync_processes("sat_tpu:shard_eval_variables")
        variables = jax.device_put(
            variables, named_shardings(variables, placement_config, mesh)
        )
        caption_fn = make_caption_fn(
            config, mesh, eos,
            beam_size=config.beam_size,
            valid_size=len(vocabulary.words),
            return_alphas=config.save_attention_maps,
        )

        def run_batch(batch, b):
            images = make_global_batch(mesh, {"images": batch["images"]})
            return caption_fn(variables, images["images"])

        pc = jax.process_count()
        if pc > 1:
            # split keyed on the data axis, not the process count: under
            # CP the model-axis processes all feed (and decode) the same
            # rows, so a pure-CP mesh gives (0, 1) — no split at all
            shard_idx, n_shards = mesh_data_shard(mesh)
            local_ds = process_local_dataset(
                dataset, process_index=shard_idx, process_count=n_shards
            )
            loader = make_loader(config, local_ds)
            from .utils.dist import gather_tree_replicated

            gathered = []
            # realign before the first decode dispatch (fresh per-axis
            # communicator windows — see the train-loop twin)
            sync_processes("sat_tpu:first_decode")
            # same knobs as the other loops; start clamped to batch count
            with ProfilerWindow(
                config, max_start=local_ds.num_batches - 1
            ) as prof:
                for b, batch in enumerate(
                    track(loader, local_ds.num_batches, desc="decode(mesh)")
                ):
                    prof.before_step(b)
                    out = run_batch(batch, b)
                    prof.after_step(b, out.words)
                    # assembly only consumes beam 0: slice on device, then
                    # one batched cross-host gather for the whole tuple
                    # (the beam-0 [B,T,N] alphas ride the same gather when
                    # attention maps are requested — VERDICT r2 weak #5)
                    best = jax.tree_util.tree_map(
                        lambda x: x[:, 0],
                        (out.words, out.lengths, out.log_scores)
                        + ((out.alphas,) if out.alphas is not None else ()),
                    )
                    gathered.append(
                        tuple(
                            np.asarray(x) for x in gather_tree_replicated(best)  # sync-ok: decode drain boundary (gathered beam-0)
                        )
                    )
            return _assemble_mesh_results(dataset, vocabulary, gathered)

    else:

        @jax.jit
        def encode_fn(variables, images):
            contexts, _ = encode(variables, config, images, train=False)
            return contexts

        def first_dispatch(b):
            # the first call of each program is part of set-up: trace +
            # compile, or the load from the cache
            return tel.span("setup/first_dispatch") if b == 0 else telemetry.NULL_SPAN

        def run_batch(batch, b):
            with tel.span("decode/dispatch/encode", b), first_dispatch(b):
                contexts = encode_fn(variables, batch["images"])
            occupancy.enqueued(contexts, b)  # the device has work again
            beam_kwargs = dict(
                beam_size=config.beam_size,
                valid_size=len(vocabulary.words),
                return_alphas=config.save_attention_maps,
            )
            if b == 0 and tel.enabled:
                # compile accounting fires once, on batch 0
                from .telemetry import xla as xla_acct

                xla_acct.analyze(
                    "decode/encode", encode_fn, variables,
                    batch["images"], tel=tel,
                )
                xla_acct.analyze(
                    "decode/beam_search", beam_search_jit,
                    state.params["decoder"], config, contexts, eos,
                    tel=tel, **beam_kwargs,
                )
            with tel.span("decode/dispatch/beam", b), first_dispatch(b):
                return beam_search_jit(
                    state.params["decoder"], config, contexts, eos, **beam_kwargs
                )

    with tel.span("setup/data"):
        loader = make_loader(config, dataset)

    results: List[Dict[str, Any]] = []
    seen = set()
    emitted = 0
    # meant as a depth-1 pipeline (dispatch batch n+1 before fetching batch
    # n's results, so that the host-side decode of words/captions overlaps
    # the device-side beam search); on the device it is depth 0: the drain's
    # slices of batch n queue BEHIND batch n+1, np.asarray waits both out,
    # and detokenise, decode/data_wait and the next dispatch then run with
    # the device empty.  The decode/device_empty spans show it (ROADMAP A6).
    prev: Optional[Tuple[Any, List[str], int]] = None

    def drain(out, files, b):
        nonlocal emitted
        # until the outputs are on the host: the wait for the device and
        # the device-to-host copy
        with tel.span("decode/drain/wait", b):
            words = np.asarray(out.words[:, 0])        # best caption per image  # sync-ok: decode drain boundary
            lengths = np.asarray(out.lengths[:, 0])  # sync-ok: decode drain boundary
            scores = np.asarray(out.log_scores[:, 0])  # sync-ok: decode drain boundary
            alphas = (
                np.asarray(out.alphas[:, 0]) if out.alphas is not None else None  # sync-ok: decode drain boundary
            )
            if out.decoder_stats and "moe_counts" in out.decoder_stats:
                # tokens of the fullest expert over the mean, worst layer:
                # the counts came back with the results, no sync of their own
                counts = np.asarray(out.decoder_stats["moe_counts"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge(
                    "decode/moe_load_max_over_mean",
                    float((counts.max(axis=1) / counts.mean(axis=1)).max()),  # sync-ok: host numpy, already drained
                )
            if out.decoder_stats and "state_bytes" in out.decoder_stats:
                # the search's state a batch (prefix per image + per-beam
                # tree), as the decoder counted it from the shapes
                tel.gauge(
                    "decode/lm_state_mb",
                    float(out.decoder_stats["state_bytes"]) / 1e6,  # sync-ok: decode drain boundary
                )
            if out.decoder_stats and "dsa_attended" in out.decoder_stats:
                # a decoder that selects positions: positions attended /
                # positions visible over the steps; the prefill's query
                # blocks whose scores stayed in the fused kernel / all of
                # them (1.0 on the chip, 0.0 where the lax form ran: a
                # silent fall-back shows here)
                attended, visible = np.asarray(out.decoder_stats["dsa_attended"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_dsa_selected_share", float(attended / max(visible, 1.0)))  # sync-ok: host numpy, already drained
                fused, blocks = np.asarray(out.decoder_stats["prefill_fused_blocks"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_dsa_prefill_fused_share", float(fused / max(blocks, 1.0)))  # sync-ok: host numpy, already drained
            if out.decoder_stats and "moe_pairs" in out.decoder_stats:
                # a decoder that holds a share of its experts: pairs
                # computed here / pairs routed.  The expert layers' combine:
                # the prefill's calls through ops/moe_combine.py's kernel /
                # all of them (1.0 on the chip, 0.0 where the lax form ran),
                # and the rows of the grouped products it fetched, prefill
                # and steps / pairs routed (the kernel fetches the pairs
                # held, the lax form every pair)
                pairs = np.asarray(out.decoder_stats["moe_pairs"], np.float64).sum(axis=0)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_moe_held_pair_share", float(pairs[0] / max(pairs[1], 1.0)))  # sync-ok: host numpy, already drained
                combine = np.asarray(out.decoder_stats["moe_combine"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_moe_combine_fused_share", float(combine[0, 1] / max(combine[0, 2], 1.0)))  # sync-ok: host numpy, already drained
                tel.gauge("decode/lm_moe_combine_rows_share", float(combine[:, 0].sum() / max(pairs[1], 1.0)))  # sync-ok: host numpy, already drained
            if out.decoder_stats and "swa_attended" in out.decoder_stats:
                # a decoder with window layers: the bytes of their leaves
                # of the state (the kept tail per image + the suffix per
                # beam), and positions attended / positions visible over
                # the steps' sliding layers (1.0: nothing slides)
                tel.gauge("decode/lm_swa_state_mb", float(out.decoder_stats["state_bytes_window"]) / 1e6)  # sync-ok: decode drain boundary
                attended, visible = np.asarray(out.decoder_stats["swa_attended"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_swa_attended_share", float(attended / max(visible, 1.0)))  # sync-ok: host numpy, already drained
            if out.decoder_stats and "state_bytes_recurrent" in out.decoder_stats:
                # a decoder whose layers keep a recurrent state: the bytes
                # of those leaves of the per-beam tree (the matrix state
                # and the conv's taps), which every step rewrites whole
                tel.gauge("decode/lm_gdn_state_mb", float(out.decoder_stats["state_bytes_recurrent"]) / 1e6)  # sync-ok: decode drain boundary
                # of the steps' updates of that state (a layer and step),
                # those made in place by ops/gdn_step.py's kernel, the
                # search's reorder folded into their read (1.0 on the chip,
                # 0.0 where the lax form gathered the state first)
                fused, updates, _ = np.asarray(out.decoder_stats["gdn_fold"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_gdn_fold_share", float(fused / max(updates, 1.0)))  # sync-ok: host numpy, already drained
                # of the prefill's DeltaNet layers, those whose chunked rule
                # ran in ops/gdn_chunk.py's kernel (1.0 on the chip, 0.0
                # where the lax form ran)
                fused, layers = np.asarray(out.decoder_stats["gdn_chunk"], np.float64)  # sync-ok: decode drain boundary
                tel.gauge("decode/lm_gdn_chunk_share", float(fused / max(layers, 1.0)))  # sync-ok: host numpy, already drained
        occupancy.observe()
        occupancy.publish()
        with tel.span("decode/drain/detok", b):  # host work after it
            for i, image_file in enumerate(files):
                if emitted >= dataset.count:           # fake_count padding
                    break
                # eval/test DataSets are unshuffled, so batch order is
                # image_ids order (reference drops fake_count the same way,
                # base_model.py:86-88)
                image_id = int(dataset.image_ids[emitted])
                emitted += 1
                if image_id in seen:                   # reference's set() dedup
                    continue
                seen.add(image_id)
                length = max(1, int(lengths[i]))
                caption = vocabulary.get_sentence(words[i, :length])
                row = {
                    "image_id": image_id,
                    "image_file": str(image_file),
                    "caption": caption,
                    "prob": float(np.exp(scores[i])),  # sync-ok: host numpy, already drained
                }
                if alphas is not None:
                    row["words"] = [
                        vocabulary.words[w] for w in words[i, :length]
                    ]
                    row["alphas"] = alphas[i, :length]    # [len, N]
                results.append(row)
        occupancy.observe()

    # profiler window over the decode loop — same knobs and semantics as
    # train's (shared ProfilerWindow), start clamped to the batch count so
    # a short eval still traces; the trace shows how much of the batch
    # time is the beam program vs encode vs dispatch
    # single-device decode gets the async device slot too (the mesh paths
    # place batches through make_global_batch inside run_batch)
    feed = (
        device_prefetch(loader)
        if int(np.prod(config.mesh_shape)) == 1
        else loader
    )
    stall = StallWatch(tel) if tel.enabled else None
    # black-box flight recorder for decode (same contract as train's):
    # journal per batch so an uncaught exception mid-eval still leaves a
    # postmortem bundle behind via the CLI's exception handler
    dec_bb = None
    if config.blackbox:
        from .resilience.quarantine import ledger_path_for
        from .telemetry import blackbox as _blackbox

        _dec_tdir = _telemetry_dir(config)
        dec_bb = _blackbox.BlackBox(os.path.join(_dec_tdir, "blackbox"), tel)
        _blackbox.install(
            bb=dec_bb,
            telemetry_dir=_dec_tdir,
            config_snapshot=config.to_dict(),
            quarantine_ledger=ledger_path_for(config),
        )
        dec_bb.event("decode_start", batches=dataset.num_batches)
    try:
        with ProfilerWindow(config, max_start=dataset.num_batches - 1) as prof:
            # per-batch visibility during decode (reference
            # base_model.py:82,131 tqdm-bars eval/test; a full-COCO eval
            # would otherwise run silent)
            batch_t0 = time.perf_counter_ns()
            for b, batch in enumerate(
                track(
                    _timed_iter(feed, tel, "decode/data_wait"),
                    dataset.num_batches,
                    desc="decode",
                )
            ):
                occupancy.observe()  # after decode/data_wait
                prof.before_step(b)
                with tel.span("decode/dispatch", b):
                    out = run_batch(batch, b)      # async dispatch
                occupancy.enqueued(out.words, b)
                prof.after_step(b, out.words)
                if prev is not None:
                    with tel.span("decode/drain", prev[2]):  # batch b-1
                        drain(*prev)
                prev = (out, batch["files"], b)
                now = time.perf_counter_ns()
                tel.record("decode/batch", batch_t0, now - batch_t0, b)
                if stall is not None:
                    stall.iteration(b, batch_t0, now - batch_t0)
                batch_t0 = now
                if dec_bb is not None:
                    dec_bb.journal(b)
        if prev is not None:
            with tel.span("decode/drain", prev[2]):
                drain(*prev)
    finally:
        occupancy.close()
        if dec_bb is not None:
            from .telemetry import blackbox as _blackbox

            _blackbox.run_finalizers()
        if tel.enabled:
            _telemetry_finish(tel, config, "decode")
    return results


def _assemble_mesh_results(
    dataset: DataSet,
    vocabulary: Vocabulary,
    gathered: List[Tuple[np.ndarray, ...]],
) -> List[Dict[str, Any]]:
    """Merge all-gathered multi-host beam-0 results back into dataset order.

    ``gathered[b]`` = (words [B,T], lengths [B], scores [B][, alphas
    [B,T,N] when attention maps were requested]) for global batch ``b`` —
    the best beam per image, already gathered to every host.
    Row layout: each process's shard view holds the contiguous block of
    the global batch its data row owns, and ``make_global_batch`` places
    block ``r`` at global rows ``[r*Bl, (r+1)*Bl)`` — so gathered batch
    ``b`` row ``m`` IS position ``b*B + m`` of the global order, which
    for the unshuffled eval set is dataset row ``b*B + m``.  Positions at
    or past ``dataset.count`` are the trailing fake_count padding and are
    dropped; then the usual per-image dedup applies (reference
    base_model.py:83-88).
    """
    by_row: Dict[int, Tuple] = {}
    for b, batch_arrays in enumerate(gathered):
        B = batch_arrays[0].shape[0]
        for m in range(B):
            g = b * B + m
            if g < dataset.count:                # trailing fake_count pad
                by_row[g] = tuple(a[m] for a in batch_arrays)

    results: List[Dict[str, Any]] = []
    seen = set()
    for g in sorted(by_row):                     # dataset order + dedup
        image_id = int(dataset.image_ids[g])
        if image_id in seen:
            continue
        seen.add(image_id)
        word_row, length, score, *rest = by_row[g]
        length = max(1, int(length))
        row: Dict[str, Any] = {
            "image_id": image_id,
            "image_file": str(dataset.image_files[g]),
            "caption": vocabulary.get_sentence(word_row[:length]),
            "prob": float(np.exp(score)),  # sync-ok: host numpy, already drained
        }
        if rest:                                 # gathered beam-0 alphas
            row["words"] = [vocabulary.words[w] for w in word_row[:length]]
            row["alphas"] = rest[0][:length]     # [len, N]
        results.append(row)
    return results


def _render_attention_panel(
    image_file: str,
    words: List[str],
    alphas: np.ndarray,
    out_file: str,
) -> None:
    """Per-word attention figure (Xu et al. fig. 5): the image, then one
    tile per generated word with its soft-attention map α upsampled from
    the context grid and overlaid.  alphas: [len(words), N], N a square
    grid (196 → 14×14 for VGG16, 49 → 7×7 for ResNet50).

    Composited directly with cv2 (colormap + blend + grid + putText)
    rather than matplotlib: measured ~20x faster per panel on this host
    (matplotlib's tight_layout alone dominated), which matters because
    eval renders one panel per image."""
    import cv2

    bgr = cv2.imread(image_file, cv2.IMREAD_COLOR)
    if bgr is None:
        raise FileNotFoundError(image_file)
    h, w = bgr.shape[:2]
    tile_w = max(180, min(w, 360))
    tile_h = int(round(tile_w * h / w))
    base = cv2.resize(bgr, (tile_w, tile_h), interpolation=cv2.INTER_AREA)
    g = int(round(np.sqrt(alphas.shape[1])))
    # one shared color scale across the caption: per-tile autoscaling
    # would stretch a near-uniform map to the same contrast as a sharply
    # peaked one, faking localization
    vmax = float(alphas.max()) or 1.0  # sync-ok: host numpy, render path

    label_h = 22
    pad = 6

    def tile(image, label):
        canvas = np.full(
            (label_h + tile_h, tile_w, 3), 255, dtype=np.uint8
        )
        cv2.putText(
            canvas, label[:24], (4, label_h - 7),
            cv2.FONT_HERSHEY_SIMPLEX, 0.45, (0, 0, 0), 1, cv2.LINE_AA,
        )
        canvas[label_h:, :, :] = image
        return canvas

    tiles = [tile(base, "input")]
    for t, word in enumerate(words):
        amap = cv2.resize(
            alphas[t].reshape(g, g).astype(np.float32), (tile_w, tile_h),
            interpolation=cv2.INTER_CUBIC,
        )
        amap_u8 = np.clip(amap / vmax * 255.0, 0.0, 255.0).astype(np.uint8)
        heat = cv2.applyColorMap(amap_u8, cv2.COLORMAP_JET)
        blend = cv2.addWeighted(base, 0.4, heat, 0.6, 0.0)
        tiles.append(tile(blend, word))

    cols = min(5, len(tiles))
    rows = -(-len(tiles) // cols)
    cell_h, cell_w = tiles[0].shape[:2]
    panel = np.full(
        (rows * (cell_h + pad) + pad, cols * (cell_w + pad) + pad, 3),
        255, dtype=np.uint8,
    )
    for idx, t_img in enumerate(tiles):
        r, c = divmod(idx, cols)
        y = pad + r * (cell_h + pad)
        x = pad + c * (cell_w + pad)
        panel[y:y + cell_h, x:x + cell_w] = t_img
    cv2.imwrite(out_file, panel)


def _local_render_rows(results: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Partition artifact rendering across processes: every host holds the
    full (all-gathered) result list after a mesh decode, so without this
    N hosts would render N copies of every panel — duplicated work and
    racing non-atomic cv2.imwrite calls on shared storage.  The
    interleaved slice is disjoint; hosts without shared image storage
    skip rows whose source image they can't read (the render helpers
    raise FileNotFoundError only in single-process runs)."""
    pc = jax.process_count()
    if pc == 1:
        return results
    return results[jax.process_index()::pc]


def _save_attention_panels(results: List[Dict[str, Any]], out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    multiproc = jax.process_count() > 1
    for r in _local_render_rows(results):
        if "alphas" not in r:
            continue
        stem = os.path.splitext(os.path.basename(r["image_file"]))[0]
        try:
            _render_attention_panel(
                r["image_file"], r["words"], r["alphas"],
                os.path.join(out_dir, f"{stem}_attention.jpg"),
            )
        except FileNotFoundError:
            if not multiproc:
                raise  # single-process: a missing image is a real error
            # multi-host without shared image storage: this host only has
            # its own data shard's images; another host renders the rest


def _export_attention_artifacts(
    results: List[Dict[str, Any]], out_dir: str
) -> None:
    """Machine-readable attention introspection next to the JPG panels:
    attn.jsonl (per-caption alpha grids + entropy/coverage stats) and the
    self-contained HTML contact sheet (telemetry/exporters.py).  Process
    0 only — every host holds the full result list after a mesh decode,
    and these artifacts are whole-run files, not per-image renders."""
    if jax.process_index() != 0:
        return
    from .telemetry import exporters as tel_exporters

    os.makedirs(out_dir, exist_ok=True)
    n = tel_exporters.export_attention_jsonl(
        results, os.path.join(out_dir, "attn.jsonl")
    )
    sheet = tel_exporters.render_attention_sheet(
        results, os.path.join(out_dir, "attn.html")
    )
    if n:
        print(
            f"attention introspection: {n} captions -> "
            f"{os.path.join(out_dir, 'attn.jsonl')}"
            + (f", contact sheet {sheet}" if sheet else "")
        )


def _render_caption_images(results: List[Dict[str, Any]], out_dir: str) -> None:
    """Captioned-JPG artifacts for this process's render slice (same
    multi-host partition/skip rules as _save_attention_panels)."""
    multiproc = jax.process_count() > 1
    for r in _local_render_rows(results):
        stem = os.path.splitext(os.path.basename(r["image_file"]))[0]
        try:
            _render_caption_image(
                r["image_file"], r["caption"],
                os.path.join(out_dir, f"{stem}_result.jpg"),
            )
        except FileNotFoundError:
            if not multiproc:
                raise


def _render_caption_image(image_file: str, caption: str, out_file: str) -> None:
    """Captioned-JPG artifact (reference base_model.py:96-107), composited
    with cv2 (caption banner above the image) — same ~100x-per-artifact
    speedup story as _render_attention_panel."""
    import cv2

    img = cv2.imread(image_file, cv2.IMREAD_COLOR)
    if img is None:
        raise FileNotFoundError(image_file)
    h, w = img.shape[:2]
    out_w = max(320, min(w, 640))
    out_h = int(round(out_w * h / w))
    img = cv2.resize(img, (out_w, out_h), interpolation=cv2.INTER_AREA)

    # wrap the caption into lines that fit the banner
    font, scale, thick = cv2.FONT_HERSHEY_SIMPLEX, 0.5, 1
    words = caption.split()
    lines, cur = [], ""
    for word in words:
        cand = (cur + " " + word).strip()
        if cv2.getTextSize(cand, font, scale, thick)[0][0] > out_w - 12 and cur:
            lines.append(cur)
            cur = word
        else:
            cur = cand
    if cur:
        lines.append(cur)

    line_h = 20
    banner_h = 8 + line_h * max(1, len(lines))
    canvas = np.full((banner_h + out_h, out_w, 3), 255, dtype=np.uint8)
    for k, line in enumerate(lines):
        cv2.putText(
            canvas, line, (6, 8 + line_h * k + 12),
            font, scale, (0, 0, 0), thick, cv2.LINE_AA,
        )
    canvas[banner_h:, :, :] = img
    cv2.imwrite(out_file, canvas)


# ---------------------------------------------------------------------------
# eval
# ---------------------------------------------------------------------------


def evaluate(
    config: Config,
    state: Optional[TrainState] = None,
    model_file: Optional[str] = None,
    prepared: Optional[Tuple[Any, DataSet, Any]] = None,
    tel=None,
) -> Dict[str, float]:
    """Scored beam-search decoding over the eval split
    (reference base_model.py:70-117): results.json + BLEU/METEOR/ROUGE/CIDEr.

    prepared: an existing ``(coco, dataset, vocabulary)`` triple from
    :func:`prepare_eval_data` — callers scoring many checkpoints against
    the same split (evaluate_sweep) pass it so the caption JSON is read
    and indexed once, not once per checkpoint.

    tel: the run's telemetry where the caller began it (cli.main), so
    that set-up is inside it; a sweep passes none and every decode of it
    starts fresh."""
    if prepared is None:
        with telemetry.span("setup/data"):
            prepared = prepare_eval_data(config)
    coco, dataset, vocabulary = prepared
    if state is None:
        state = setup_state(config, load=True, model_file=model_file)

    results = decode_dataset(config, state, dataset, vocabulary, tel=tel)
    payload = [
        {"image_id": r["image_id"], "caption": r["caption"]} for r in results
    ]
    import json

    atomic_write(
        config.eval_result_file, "w", lambda f: json.dump(payload, f)
    )

    if config.save_eval_result_as_image:
        os.makedirs(config.eval_result_dir, exist_ok=True)
        _render_caption_images(results, config.eval_result_dir)
    if config.save_attention_maps:
        _save_attention_panels(results, config.eval_result_dir)
        _export_attention_artifacts(results, config.eval_result_dir)

    coco_res = coco.load_results(payload)
    scorer = CocoEvalCap(coco, coco_res, eval_data=dataset)
    return scorer.evaluate()


def evaluate_sweep(config: Config) -> Dict[int, Dict[str, float]]:
    """Score every checkpoint under save_dir — the reference's eval.sh
    sweep (/root/reference/eval.sh:1-9), in-process.  Writes per-step
    ``<step>.txt`` score dumps next to the checkpoints and returns
    {step: scores} for model selection.

    The reference's sweep launches one full process per checkpoint; the
    in-process upgrade this exists for means the expensive invariants are
    paid ONCE across the sweep — the eval split is prepared a single time
    and every checkpoint restores into one initialized state skeleton, so
    sweep cost is O(prep) + N×O(restore + decode)."""
    # the lineage scan skips temp/partial/zero-byte files, so an in-flight
    # or torn write never enters the sweep
    steps = lineage.checkpoint_steps(config.save_dir)
    prepared = prepare_eval_data(config)
    skeleton = create_train_state(jax.random.PRNGKey(config.seed), config)
    sweep: Dict[int, Dict[str, float]] = {}
    for step in steps:
        path = os.path.join(config.save_dir, f"{step}.npz")
        state, count = restore_checkpoint(skeleton, model_file=path)
        if count == 0:
            raise ValueError(f"checkpoint {path} restored 0 tensors")
        scores = evaluate(config, state=state, prepared=prepared)
        sweep[step] = scores
        atomic_write(
            os.path.join(config.save_dir, f"{step}.txt"),
            "w",
            lambda f: f.writelines(f"{k}: {v:.4f}\n" for k, v in scores.items()),
        )
    return sweep


# ---------------------------------------------------------------------------
# test
# ---------------------------------------------------------------------------


def test(
    config: Config,
    state: Optional[TrainState] = None,
    model_file: Optional[str] = None,
    tel=None,
) -> List[Dict[str, Any]]:
    """Caption arbitrary JPEGs (reference base_model.py:119-161):
    captioned images + results.csv.  ``tel``: as in :func:`evaluate`."""
    with telemetry.span("setup/data"):
        dataset, vocabulary = prepare_test_data(config)
    if dataset.count == 0:
        print(f"no images found in {config.test_image_dir}")
        return []
    if state is None:
        state = setup_state(config, load=True, model_file=model_file)

    results = decode_dataset(config, state, dataset, vocabulary, tel=tel)

    os.makedirs(config.test_result_dir, exist_ok=True)
    _render_caption_images(results, config.test_result_dir)
    if config.save_attention_maps:
        _save_attention_panels(results, config.test_result_dir)
        _export_attention_artifacts(results, config.test_result_dir)

    import pandas as pd

    pd.DataFrame(
        {
            "image_files": [r["image_file"] for r in results],
            "caption": [r["caption"] for r in results],
            "prob": [r["prob"] for r in results],
        }
    ).to_csv(config.test_result_file)
    return results
