"""sat_tpu.telemetry — always-on host-side tracing and run-health metrics.

Complements ``jax.profiler`` (deep, short-windowed, device-centric) with a
cheap, whole-run, host-centric layer: ring-buffered spans + counters +
gauges (``spans``), Chrome-trace / JSONL / breakdown exporters
(``exporters``), and the pollable ``heartbeat.json`` writer
(``heartbeat``).  See docs/OBSERVABILITY.md.

This package is deliberately jax-free so the processes that must never
hold an accelerator (the ``--supervise`` parent, the router) can use it
(``tests/test_device_diag.py::test_telemetry_core_is_jax_free``).  Only
``spans`` is imported eagerly; runtime imports the exporters and
heartbeat directly.
"""

from __future__ import annotations

import os
import time

from .spans import (  # noqa: F401
    NULL_SPAN,
    NULL_TELEMETRY,
    NullTelemetry,
    Telemetry,
    count,
    disable,
    enable,
    enabled,
    gauge,
    get,
    record,
    span,
)

# One id per process lifetime: every artifact a run writes (metrics.jsonl,
# telemetry.jsonl, heartbeat.json, trace JSON) carries it, so post-hoc
# joins never depend on file mtimes or directory layout.
RUN_ID = f"{int(time.time()):x}-{os.getpid()}"

# Version of the report artifact contract (compile_report.json,
# heartbeat.json, slo.jsonl, the fleet files, a chaos campaign's rows).
# A reader refuses artifacts stamped with another version, as
# scripts/check_slo.py does; bump it when a field changes meaning (not
# when fields are added).
SCHEMA_VERSION = 1


def run_id() -> str:
    return RUN_ID


def process_identity() -> tuple:
    """(process_index, process_count) for multi-host artifact stamping.

    Same contract as :func:`bench_stamp`: never imports jax — the facts
    are read via ``sys.modules`` only when the caller already initialized
    a backend, and a single-process / host-only caller gets (0, 1).  The
    fleet plane (telemetry/fleet.py), heartbeat.json, and report rows all
    stamp through here so cross-host artifacts agree on who wrote them."""
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index()), int(jax.process_count())
        except Exception:
            pass
    return 0, 1


def bench_stamp() -> dict:
    """Provenance stamp of a report row (``scripts/chaos_campaign.py``
    writes it on every row): artifact schema version, git SHA, and a
    device/host descriptor — what a reader needs to decide whether two
    artifacts are comparable at all.

    Deliberately import-light: no jax import ever (this package is
    jax-free); device facts are read only when the caller already
    initialized jax, and only via ``sys.modules`` so a host-only caller
    never drags a backend in.  Callers stamp at emit time — after their
    device work — so touching ``local_devices()`` here never triggers a
    fresh backend init."""
    import platform
    import subprocess
    import sys

    sha = None
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            capture_output=True,
            text=True,
            timeout=5,
        )
        sha = out.stdout.strip() or None
    except Exception:
        pass
    device = {
        "host": platform.node(),
        "machine": platform.machine(),
        "python": platform.python_version(),
    }
    if "jax" in sys.modules:
        try:
            jax = sys.modules["jax"]
            d0 = jax.local_devices()[0]
            device.update(
                platform=d0.platform,
                kind=d0.device_kind,
                device_count=jax.device_count(),
            )
        except Exception:
            pass
    process_index, process_count = process_identity()
    return {
        "schema_version": SCHEMA_VERSION,
        "git_sha": sha,
        "run_id": RUN_ID,
        "stamp_unix": round(time.time(), 3),
        "process_index": process_index,
        "process_count": process_count,
        "device": device,
    }
