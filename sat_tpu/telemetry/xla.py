"""Compile-time XLA cost/memory accounting → ``compile_report.json``.

Step time tells you a PR got slower; it cannot tell you *why*.  XLA
already knows: every compiled executable carries a cost analysis (FLOPs,
bytes accessed, transcendentals) and a memory analysis (temp / argument
/ output / alias HBM bytes).  This module snapshots those per jitted
function — ``train_step``, the eval encoder, the beam program — into one
JSON artifact per run, so the regression gate
(``scripts/check_regression.py``) can catch a silent FLOP or HBM
regression even when wall-clock noise hides it, and a post-mortem can
answer "did the working set grow" without a profiler window.

``analyze()`` uses the AOT path (``fn.lower(*args).compile()``) *before*
the loop's first dispatch: lowering against live arguments does not
consume donated buffers, and the lower/compile caches (plus the
persistent compile cache the CLI enables) are shared with
the normal call path, so the real first step reuses the executable
instead of compiling twice.

Like ``device.py`` this module imports jax and is therefore NOT imported
eagerly by the package ``__init__`` (the core telemetry package stays
jax-free); runtime imports it directly and only when telemetry is on.
A program that fails to compile here is reported and skipped — the real
dispatch right after raises the same error where it belongs.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, Optional

from ..utils.fileio import atomic_write
from . import SCHEMA_VERSION, run_id

# per-run accumulator: reset by runtime._telemetry_begin, written by
# runtime._telemetry_finish — one entry per analyzed jitted function
_entries: Dict[str, Dict[str, Any]] = {}

_COST_KEYS = ("flops", "transcendentals", "bytes accessed")
_MEMORY_ATTRS = (
    "temp_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def reset() -> None:
    _entries.clear()


def entries() -> Dict[str, Dict[str, Any]]:
    return dict(_entries)


def _arg_bytes(args, kwargs) -> Optional[int]:
    """Host-side argument footprint from shape/dtype metadata only (valid
    even for donated buffers — metadata survives donation)."""
    import jax

    total = 0
    try:
        for leaf in jax.tree_util.tree_leaves((args, kwargs)):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            total += n * getattr(dtype, "itemsize", 0)
        return int(total)
    except Exception:
        return None


def analyze(name: str, jitted, *args, tel=None, **kwargs) -> Optional[Dict]:
    """AOT lower+compile ``jitted`` on ``args``' shapes and record its
    cost/memory/donation facts under ``name``.  Never raises; returns the
    entry dict (None when the probe failed).  Safe to call with live
    donated arguments — lowering reads only avals."""
    t0 = time.perf_counter()
    try:
        lowered = jitted.lower(*args, **kwargs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    except Exception as e:
        print(
            f"sat_tpu: compile accounting skipped for {name}: {e}",
            file=sys.stderr,
            flush=True,
        )
        return None

    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    entry: Dict[str, Any] = {
        "lower_seconds": round(t1 - t0, 3),
        "compile_seconds": round(t2 - t1, 3),
        "argument_bytes_host_estimate": _arg_bytes(args, kwargs),
        "cost": {
            k.replace(" ", "_"): float(ca[k]) for k in _COST_KEYS if k in ca
        },
        "memory": {
            attr.replace("_size_in_bytes", "_bytes"): int(getattr(ma, attr))
            for attr in _MEMORY_ATTRS
        },
        "donation": None,
    }

    try:
        import jax

        infos = jax.tree_util.tree_leaves(lowered.args_info)
        donated = sum(1 for i in infos if getattr(i, "donated", False))
        entry["donation"] = {"donated_args": donated, "total_args": len(infos)}
    except Exception:
        pass

    _entries[name] = entry
    if tel is not None and getattr(tel, "enabled", False):
        cost = entry.get("cost") or {}
        mem = entry.get("memory") or {}
        if "flops" in cost:
            tel.gauge(f"xla/{name}/gflops", round(cost["flops"] / 1e9, 3))
        if "temp_bytes" in mem:
            tel.gauge(f"xla/{name}/temp_mb", round(mem["temp_bytes"] / 2**20, 2))
        tel.gauge(f"xla/{name}/compile_s", entry["compile_seconds"])
    return entry


def report() -> Optional[Dict[str, Any]]:
    """The compile_report.json document (None when nothing was analyzed)."""
    if not _entries:
        return None
    doc: Dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "run_id": run_id(),
        "time_unix": round(time.time(), 3),
        "functions": dict(_entries),
    }
    try:
        if "jax" in sys.modules:  # never trigger backend init from here
            jax = sys.modules["jax"]
            doc["backend"] = jax.default_backend()
            doc["device_kind"] = jax.local_devices()[0].device_kind
    except Exception:
        pass
    return doc


def write_report(path: str) -> Optional[str]:
    """Atomically write the report; returns the path (None when empty or
    the write failed — warned, never raised)."""
    doc = report()
    if doc is None:
        return None
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        atomic_write(path, "w", lambda f: json.dump(doc, f, indent=1))
        return path
    except (OSError, ValueError) as e:
        print(
            f"sat_tpu: compile report export failed ({path}): {e}",
            file=sys.stderr,
            flush=True,
        )
        return None


def format_summary() -> Optional[str]:
    """One human line per analyzed function for the end-of-run printout."""
    if not _entries:
        return None
    lines = ["compile report:"]
    for name, e in _entries.items():
        cost = e.get("cost") or {}
        mem = e.get("memory") or {}
        parts = [f"  {name:<18} compile {e['compile_seconds']:.2f}s"]
        if "flops" in cost:
            parts.append(f"{cost['flops'] / 1e9:.3f} GFLOP/call")
        if "temp_bytes" in mem:
            parts.append(f"temp {mem['temp_bytes'] / 2**20:.1f} MB")
        if "output_bytes" in mem:
            parts.append(f"out {mem['output_bytes'] / 2**20:.1f} MB")
        don = e.get("donation")
        if don and don.get("donated_args"):
            parts.append(f"donated {don['donated_args']}/{don['total_args']} args")
        lines.append("  ".join(parts))
    return "\n".join(lines)
