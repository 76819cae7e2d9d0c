"""Retrying host IO: jittered exponential backoff with error classification.

On shared cluster filesystems the common failure is not "the file is
gone" but "the mount hiccuped for 200 ms" — EIO/EAGAIN/ESTALE-class
errors that a second attempt clears.  ``retry_io`` wraps the durable-IO
call sites (checkpoint read/write, shard manifest + shard mmap opens,
caption-file reads — see the callers in ``utils.fileio``,
``train.checkpoint``, ``data.shards``, ``data.coco``) with bounded
retries, exponential backoff, and jitter so a fleet of preempted workers
relaunching together doesn't hammer the filesystem in lockstep.

Classification is deliberate, not blanket: errors that signal a *wrong
program or environment* (missing file, permission, a path that is a
directory, corrupt archive contents) fail immediately — retrying them
only hides the real bug — while errors that signal *transient transport
trouble* back off and retry.  Everything that is not an OSError at all
propagates untouched.

No jax, and no sat_tpu imports beyond ``faultinject`` (the injection
point ``SAT_FI_IO_FAILURES`` lands here) and the equally jax-free
``telemetry`` (each retry ticks the ``io/retries`` counter), so the
wrapper is usable from processes that must not hold an accelerator.
"""

from __future__ import annotations

import errno
import random
import sys
import time
from typing import Callable, Optional, Tuple, TypeVar

from .faultinject import consume_io_fault
from .. import telemetry

T = TypeVar("T")

# Transient-transport errnos: worth a second attempt.
RETRYABLE_ERRNOS = frozenset(
    getattr(errno, name)
    for name in (
        "EIO", "EAGAIN", "EBUSY", "EINTR", "ETIMEDOUT", "ESTALE",
        "ENETDOWN", "ENETUNREACH", "ENETRESET", "ECONNRESET",
        "ECONNABORTED", "EREMOTEIO",
    )
    if hasattr(errno, name)
)

# Wrong-program/environment OSError subclasses: never retried, even though
# they share the OSError base with the transient family.
FATAL_OSERROR_TYPES = (
    FileNotFoundError,
    PermissionError,
    IsADirectoryError,
    NotADirectoryError,
    FileExistsError,
)

# Process-wide defaults, set once from Config (``configure`` below) so
# deep call sites (fileio, shards) honor --io_retries without threading a
# config through every layer.
_defaults = {"retries": 3, "base_delay_s": 0.05}

# Module-level PRNG: jitter is decorrelation across processes, not
# cryptography; a fixed seed keeps single-process test runs deterministic
# while PIDs decorrelate a real fleet.
_jitter_rng = random.Random(0x5A7)


def configure(retries: Optional[int] = None, base_delay_s: Optional[float] = None) -> None:
    """Install process-wide retry defaults (called with Config values at
    runtime entry; explicit ``retry_io`` kwargs always win)."""
    if retries is not None:
        _defaults["retries"] = max(0, int(retries))
    if base_delay_s is not None:
        _defaults["base_delay_s"] = float(base_delay_s)  # sync-ok: host config scalar


def backoff_delay(
    attempt: int,
    *,
    base_delay_s: float,
    max_delay_s: float = 2.0,
    jitter: Tuple[float, float] = (0.5, 1.5),
    rng: Optional[random.Random] = None,
) -> float:
    """Jittered exponential backoff for retry ``attempt`` (0-based):
    ``base * 2**attempt`` capped at ``max_delay_s``, scaled by a uniform
    draw from ``jitter``.  Shared by :func:`retry_io` and the crash-only
    supervisor (``resilience.supervisor``) so every retry loop in the
    fleet decorrelates the same way."""
    delay = min(float(base_delay_s) * (2.0 ** attempt), max_delay_s)  # sync-ok: host arithmetic
    return delay * (rng or _jitter_rng).uniform(*jitter)


def is_retryable(exc: BaseException) -> bool:
    """Transient vs fatal: the classification ``retry_io`` applies."""
    if not isinstance(exc, OSError):
        return False
    if isinstance(exc, FATAL_OSERROR_TYPES):
        return False
    if isinstance(exc, (TimeoutError, BlockingIOError, InterruptedError, ConnectionError)):
        return True
    return exc.errno in RETRYABLE_ERRNOS


def retry_io(
    fn: Callable[[], T],
    *,
    desc: str,
    retries: Optional[int] = None,
    base_delay_s: Optional[float] = None,
    max_delay_s: float = 2.0,
    jitter: Tuple[float, float] = (0.5, 1.5),
    sleep: Callable[[float], None] = time.sleep,
) -> T:
    """Run ``fn()`` with up to ``retries`` retries on transient IO errors.

    Backoff before retry k (0-based) is ``base * 2**k`` capped at
    ``max_delay_s``, scaled by a uniform jitter draw from ``jitter``.
    Fatal errors (see :func:`is_retryable`) raise immediately; the final
    transient failure raises with the full retry history behind it.
    ``desc`` names the operation in warnings and is what
    ``SAT_FI_IO_FAILURES=n:substr`` matches against.
    """
    budget = _defaults["retries"] if retries is None else max(0, int(retries))
    base = _defaults["base_delay_s"] if base_delay_s is None else float(base_delay_s)  # sync-ok: host config scalar
    for attempt in range(budget + 1):
        try:
            consume_io_fault(desc)
            return fn()
        except BaseException as e:
            if not is_retryable(e) or attempt == budget:
                raise
            telemetry.count("io/retries")
            delay = backoff_delay(
                attempt,
                base_delay_s=base,
                max_delay_s=max_delay_s,
                jitter=jitter,
            )
            print(
                f"sat_tpu: transient IO error on {desc} "
                f"(attempt {attempt + 1}/{budget + 1}): {e} — "
                f"retrying in {delay * 1e3:.0f} ms",
                file=sys.stderr,
                flush=True,
            )
            sleep(delay)
    raise AssertionError("unreachable")  # loop always returns or raises
