"""Reduction of a jax.profiler trace (``*.xplane.pb``) to numbers.

What a TPU trace looks like (read by hand on a v5e, PERF.md §6): one plane
per chip named ``/device:TPU:<i>``; on it the line ``XLA Ops`` holds one
event per executed HLO op (fusion, convolution, custom-call ...), the line
``XLA Modules`` one event per executed program, named
``jit_<function>(<fingerprint>)``; ``Steps`` groups modules.  Host threads
are on ``/host:CPU``.  Times are nanoseconds on one clock per trace.

``reduce_trace`` returns, averaged over the device planes:
  busy_s      union of the op intervals (falls back to module intervals)
  window_s    the span from the first to the last device event (the
              profiler starts and stops seconds away from the calls that
              ask it to, so the host's clock around them says nothing)
  modules     {module name without fingerprint: [durations_s ...]}
  ops         {op name: total seconds}, the top ones
  gaps        the longest idle gaps as (start_s, seconds) from trace start
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """(covered ns, gaps between covered stretches) of [start, end) pairs."""
    busy, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def plane_events(plane, line_name: str) -> List[Tuple[str, int, int]]:
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            out.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def reduce_planes(planes, window_s: Optional[float] = None, top: int = 10) -> Optional[dict]:
    """``planes``: iterable of objects with .name and .lines[].events[]
    (jax.profiler.ProfileData planes, or the recorded stand-ins of the
    tests).  None when no device plane holds an op."""
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    per_device = []
    for plane in devices:
        ops = plane_events(plane, "XLA Ops")
        modules = plane_events(plane, "XLA Modules")
        timed = ops or modules
        if not timed:
            continue
        busy_ns, gaps = _union([(s, s + d) for _, s, d in timed])
        first = min(s for _, s, _ in timed)
        last = max(s + d for _, s, d in timed)
        per_device.append(dict(ops=ops, modules=modules, busy_ns=busy_ns, gaps=gaps,
                               first=first, last=last))
    if not per_device:
        return None
    n = len(per_device)
    busy_s = sum(d["busy_ns"] for d in per_device) / n / 1e9
    span_s = sum(d["last"] - d["first"] for d in per_device) / n / 1e9
    lead = per_device[0]
    modules: Dict[str, List[float]] = {}
    for name, _s, dur in lead["modules"]:
        modules.setdefault(_FINGERPRINT.sub("", name), []).append(dur / 1e9)
    op_totals: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    for name, _s, dur in lead["ops"]:
        op_totals[name] = op_totals.get(name, 0.0) + dur / 1e9
        op_counts[name] = op_counts.get(name, 0) + 1
    top_ops = sorted(op_totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(lead["gaps"], key=lambda g: -g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": float(window_s) if window_s else span_s,
        "span_s": span_s,
        "devices": n,
        "modules": modules,
        "ops": [[k, v] for k, v in top_ops],
        "op_totals": op_totals,
        "op_counts": op_counts,
        "gaps": [((s - lead["first"]) / 1e9, d / 1e9) for s, d in gaps],
        "first_ns": lead["first"],
        "last_ns": lead["last"],
    }


def reduce_trace(trace_dir: str, window_s: Optional[float] = None) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s)


def describe(trace_dir: str, limit: int = 12) -> str:
    """Planes, lines and the commonest event names of a trace, for reading
    one by hand."""
    path = find_xplane(trace_dir)
    if path is None:
        return f"no xplane under {trace_dir}"
    from jax.profiler import ProfileData

    out = [path]
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names: Dict[str, List[int]] = {}
            for ev in events:
                names.setdefault(ev.name, []).append(int(ev.duration_ns))
            out.append(f"  line {line.name!r}: {len(events)} events, {len(names)} names")
            ranked = sorted(names.items(), key=lambda kv: -sum(kv[1]))[:limit]
            for name, durs in ranked:
                out.append(f"    {sum(durs) / 1e6:10.3f} ms  x{len(durs):<6d} {name[:110]}")
            for name, durs in names.items():
                if "custom-call" in name and line.name == "XLA Ops":
                    out.append(f"    CUSTOM {sum(durs) / 1e6:10.3f} ms  x{len(durs):<6d} {name[:2500]}")
    return "\n".join(out)


def record(trace_dir: str, out_path: str, per_line: int = 120) -> None:
    """Write a small extract of a trace as JSON (the first ``per_line``
    events of every device line) — what ``tests/data/`` keeps, so that the
    reduction is checked against names and planes as the chip wrote them."""
    import json

    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    planes = []
    for plane in ProfileData.from_file(path).planes if path else []:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for _, ev in zip(range(per_line), line.events)]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    with open(out_path, "w") as f:
        json.dump({"planes": planes}, f)


class _Recorded:
    """Stand-in with the attributes reduce_planes reads, built from record()'s JSON."""

    def __init__(self, d: dict) -> None:
        self.__dict__.update(d)


def load_recorded(path: str):
    import json

    with open(path) as f:
        data = json.load(f)
    planes = []
    for p in data["planes"]:
        lines = [_Recorded({"name": ln["name"],
                            "events": [_Recorded({"name": n, "start_ns": s, "duration_ns": d})
                                       for n, s, d in ln["events"]]}) for ln in p["lines"]]
        planes.append(_Recorded({"name": p["name"], "lines": lines}))
    return planes
