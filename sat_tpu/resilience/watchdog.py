"""Zero-sync progress watchdog: detect a wedged run, dump, abort.

The one failure mode PR 2's resilience layer cannot touch is the run
wedging *silently*: a hung dispatch makes no progress and raises
nothing.  This module watches the run from a side thread and escalates
when a tracked phase stops completing:

1. **gauges** — ``watchdog/state`` flips to ``STALLED`` and
   ``watchdog/stalled_s`` starts counting, so ``heartbeat.json`` (and
   ``/healthz``) show the stall while it is still recoverable;
2. **dump** — ``faulthandler`` writes an all-thread stack dump to the
   ``dump_path`` artifact and the telemetry ring flushes a Chrome trace
   next to it, preserving *where* every thread was parked;
3. **abort** — after ``grace_s`` more seconds the ``pre_abort`` hook runs
   (bounded — the train loop passes the async checkpoint writer's flush
   so ``LAST_GOOD`` lands) and the process exits with
   ``WATCHDOG_EXIT_CODE`` so a supervisor (``resilience.supervisor``)
   can tell "wedged, restart me" from every other failure.

Observation is **zero-sync by design**: the watchdog thread reads host
clocks and host dicts only — never a device value, never jax (the
no-hidden-sync lint in tests/test_device_diag.py covers this package).
The observed signal is phase *guards*: the instrumented thread brackets
each potentially-wedging region with ``with wd.phase("dispatch"):`` —
entry records a host timestamp, exit clears it.  A phase's deadline is
enforced only after that phase has completed at least once, so a cold
first step (XLA compiling for minutes) never false-trips a steady-state
deadline.

``SAT_FI_SLOW_STEP_MS`` (a degraded-but-alive device) keeps completing
phases and must never fire; ``SAT_FI_WEDGE_AT_STEP`` parks the loop
inside its step guard and must always fire.  Both are pinned by
tests/test_supervisor.py.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time
from typing import Callable, Dict, Optional

from .. import telemetry

# Distinct from every exit code already in the fleet's vocabulary:
# 0 clean, 1 checkpoint-write/preemption failure, 2 pytest/argparse,
# 3 unmeasured (a measurement that could not be taken: neither a pass
# nor a regression), 87 systemic data corruption (the
# quarantine ceiling — resilience/quarantine.py; the supervisor must NOT
# restart it).  The supervisor treats this one as "wedged, state on disk
# is good, restart me".
WATCHDOG_EXIT_CODE = 86

# watchdog/state gauge values (heartbeat.json renders the raw number)
OK, STALLED, DUMPED, ABORTING = 0, 1, 2, 3
STATE_NAMES = {OK: "ok", STALLED: "stalled", DUMPED: "dumped", ABORTING: "aborting"}


class _PhaseGuard:
    """Context manager bracketing one instrumented region."""

    __slots__ = ("_wd", "_name")

    def __init__(self, wd: "Watchdog", name: str):
        self._wd = wd
        self._name = name

    def __enter__(self):
        self._wd._enter(self._name)
        return self

    def __exit__(self, *exc):
        self._wd._exit(self._name)
        return False


class Watchdog:
    """Observer thread enforcing per-phase progress deadlines.

    Parameters
    ----------
    deadlines: phase name -> seconds the phase may stay open once it has
        completed at least once.  Phases without an entry are tracked
        (visible in the stack dump) but never enforced.
    poll_s: observer wake-up cadence; detection latency is one poll.
    dump_path: where the faulthandler all-thread stack dump lands; the
        telemetry trace flushes next to it as ``<stem>_trace.json``.
    pre_abort: best-effort callable run (bounded by ``grace_s``) before
        the abort — the train loop passes the async checkpoint writer's
        ``flush`` so LAST_GOOD lands before the process dies.
    abort: the final rung.  Defaults to ``os._exit(WATCHDOG_EXIT_CODE)``;
        tests inject a recorder.
    """

    def __init__(
        self,
        deadlines: Dict[str, float],
        *,
        poll_s: float = 1.0,
        grace_s: float = 2.0,
        dump_path: Optional[str] = None,
        pre_abort: Optional[Callable[[], None]] = None,
        abort: Optional[Callable[[int], None]] = None,
        tel=None,
    ) -> None:
        self.deadlines = {k: v for k, v in deadlines.items() if v and v > 0}
        self.poll_s = max(0.05, poll_s)
        self.grace_s = max(0.0, grace_s)
        self.dump_path = dump_path
        self.pre_abort = pre_abort
        self._abort = abort if abort is not None else self._default_abort
        self._tel = tel if tel is not None else telemetry.get()
        # phase name -> monotonic entry time; written by instrumented
        # threads, read by the observer.  Plain dict ops are atomic under
        # the GIL and a torn read here costs one poll of latency, not
        # correctness, so no lock on the hot path.
        self._active: Dict[str, float] = {}
        self._completed: Dict[str, bool] = {}
        self.state = OK
        self.stalled_phase: Optional[str] = None
        self.aborted_rc: Optional[int] = None  # set when abort is injected
        self._dumped_at: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # every deadline comparison reads this clock; tests swap in a
        # fake (use_clock) so stall/no-stall scenarios are exact instead
        # of racing wall time under suite load (the TenantRegistry
        # injectable-clock pattern)
        self._clock = time.monotonic
        self._tel.gauge("watchdog/state", OK)

    def use_clock(self, clock: Callable[[], float]) -> "Watchdog":
        """Swap the monotonic time source (tests only)."""
        self._clock = clock
        return self

    # -- instrumentation (called from watched threads) ---------------------

    def phase(self, name: str) -> _PhaseGuard:
        return _PhaseGuard(self, name)

    def _enter(self, name: str) -> None:
        self._active[name] = self._clock()

    def _exit(self, name: str) -> None:
        self._active.pop(name, None)
        self._completed[name] = True
        if self.state != OK and self.stalled_phase == name:
            # the phase the ladder was climbing on just completed after
            # all — stand down (a dump may already have landed; that is
            # evidence, not damage)
            self.state = OK
            self.stalled_phase = None
            self._dumped_at = None
            self._tel.gauge("watchdog/state", OK)
            self._tel.gauge("watchdog/stalled_s", 0.0)

    # -- observer ----------------------------------------------------------

    def start(self) -> "Watchdog":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="sat-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _overdue(self) -> Optional[tuple]:
        """(phase, seconds overdue) of the worst enforced open phase."""
        now = self._clock()
        worst = None
        for name, t0 in list(self._active.items()):
            deadline = self.deadlines.get(name)
            if deadline is None or not self._completed.get(name):
                continue
            over = (now - t0) - deadline
            if over > 0 and (worst is None or over > worst[1]):
                worst = (name, over)
        return worst

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            self.check()

    def check(self) -> None:
        """One observer tick (public so tests and the bench can drive the
        ladder without waiting on the poll clock)."""
        worst = self._overdue()
        if worst is None:
            if self.state != OK:
                self.state = OK
                self.stalled_phase = None
                self._dumped_at = None
                self._tel.gauge("watchdog/state", OK)
                self._tel.gauge("watchdog/stalled_s", 0.0)
            return
        name, over = worst
        self._tel.gauge("watchdog/stalled_s", over)
        if self.state == OK:
            self.state = STALLED
            self.stalled_phase = name
            self._tel.gauge("watchdog/state", STALLED)
            self._tel.count("watchdog/stalls")
            print(
                f"sat_tpu watchdog: phase {name!r} exceeded its "
                f"{self.deadlines[name]:g}s deadline by {over:.1f}s — "
                "escalating (stack dump next tick, then abort)",
                file=sys.stderr,
                flush=True,
            )
            return
        if self.state == STALLED:
            self.state = DUMPED
            self._dumped_at = self._clock()
            self._tel.gauge("watchdog/state", DUMPED)
            self._dump(name, over)
            return
        if self.state == DUMPED and (
            self._clock() - (self._dumped_at or 0.0) >= self.grace_s
        ):
            self.state = ABORTING
            self._tel.gauge("watchdog/state", ABORTING)
            print(
                f"sat_tpu watchdog: phase {name!r} still wedged "
                f"{over:.1f}s past deadline — landing LAST_GOOD and "
                f"aborting with exit code {WATCHDOG_EXIT_CODE}",
                file=sys.stderr,
                flush=True,
            )
            self._run_pre_abort()
            self._postmortem(name, over)
            self._abort(WATCHDOG_EXIT_CODE)

    # -- escalation rungs --------------------------------------------------

    def _dump(self, name: str, over: float) -> None:
        """Rung 2: all-thread stacks + telemetry trace, best-effort."""
        if not self.dump_path:
            return
        try:
            os.makedirs(os.path.dirname(self.dump_path) or ".", exist_ok=True)
            with open(self.dump_path, "w") as f:
                f.write(
                    f"sat_tpu watchdog stack dump: phase={name} "
                    f"overdue={over:.1f}s deadline={self.deadlines[name]:g}s "
                    f"pid={os.getpid()}\n"
                )
                f.flush()
                faulthandler.dump_traceback(file=f, all_threads=True)
            print(
                f"sat_tpu watchdog: stack dump written to {self.dump_path}",
                file=sys.stderr,
                flush=True,
            )
        except Exception as e:
            print(f"sat_tpu watchdog: stack dump failed: {e!r}", file=sys.stderr)
        try:
            if telemetry.enabled():
                from ..telemetry import exporters

                stem, _ = os.path.splitext(self.dump_path)
                exporters.export_chrome_trace(telemetry.get(), stem + "_trace.json")
        except Exception as e:
            print(f"sat_tpu watchdog: trace flush failed: {e!r}", file=sys.stderr)

    def _run_pre_abort(self) -> None:
        """Rung 3 prologue: run ``pre_abort`` in a helper thread bounded
        by ``grace_s`` — the hook itself may be wedged (a checkpoint
        flush stuck on the same dead device), and the abort must not be."""
        if self.pre_abort is None:
            return
        done = threading.Event()

        def _run():
            try:
                self.pre_abort()
            except Exception as e:
                print(
                    f"sat_tpu watchdog: pre-abort hook failed: {e!r}",
                    file=sys.stderr,
                    flush=True,
                )
            finally:
                done.set()

        t = threading.Thread(target=_run, name="sat-watchdog-preabort", daemon=True)
        t.start()
        if not done.wait(timeout=max(self.grace_s, 2.0)):
            print(
                "sat_tpu watchdog: pre-abort hook wedged too — aborting anyway",
                file=sys.stderr,
                flush=True,
            )

    def _postmortem(self, name: str, over: float) -> None:
        """Rung 3 epilogue: black-box bundle BEFORE ``os._exit`` (atexit
        never runs on the abort path, so this is the only window).  Same
        bounded-helper-thread discipline as the pre-abort hook — the dump
        is pure host file IO, but a dead network mount must not turn the
        abort into a second wedge.  No-op unless the run installed a
        recorder (``--blackbox``)."""
        done = threading.Event()

        def _run():
            try:
                from ..telemetry import blackbox

                bb = blackbox.installed()
                if bb is not None:
                    bb.event(
                        "watchdog_abort", phase=name, overdue_s=round(over, 1)
                    )
                blackbox.dump(
                    "watchdog_wedge",
                    exit_code=WATCHDOG_EXIT_CODE,
                    phase=name,
                    overdue_s=round(over, 1),
                    deadline_s=self.deadlines.get(name),
                )
            except Exception as e:
                print(
                    f"sat_tpu watchdog: postmortem dump failed: {e!r}",
                    file=sys.stderr,
                    flush=True,
                )
            finally:
                done.set()

        t = threading.Thread(
            target=_run, name="sat-watchdog-postmortem", daemon=True
        )
        t.start()
        if not done.wait(timeout=max(self.grace_s, 2.0)):
            print(
                "sat_tpu watchdog: postmortem dump wedged — aborting anyway",
                file=sys.stderr,
                flush=True,
            )

    def _default_abort(self, code: int) -> None:
        self.aborted_rc = code
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(code)


def deadlines_from_config(config) -> Dict[str, float]:
    """The per-phase deadline map the train loop arms (seconds; a value
    of 0 disables that phase).  ``step`` brackets the whole loop body —
    the net that catches a wedge landing *between* finer-grained phases."""
    return {
        "step": config.watchdog_step_s,
        "data_wait": config.watchdog_data_wait_s,
        "dispatch": config.watchdog_dispatch_s,
        "checkpoint": config.watchdog_checkpoint_s,
    }
