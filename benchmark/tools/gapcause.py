"""What was the host doing in each idle gap of the device?  Exactly, once.

    python3 benchmark/tools/gapcause.py --workload <cell> --seed <n> --seconds <s>

A builder's instrument like ``memprobe.py``: never the driver's command, and
it edits nothing.  It runs the cell's driver in-process, as ``run.py
--trace 1`` does, but with a ``TraceWindow`` of its own whose profiler
options set ``host_tracer_level = 1`` (``python_tracer_level = 0``), so that
the ``jax.profiler.TraceAnnotation`` the program enters with every host
span (``sat_tpu/telemetry/spans.py``) lands in the trace, ON THE DEVICE'S
CLOCK, with the step or batch it worked on.  It reads the raw xplane before
the run's directory is removed and prints, for every gap of ``--min-gap-ms``
(0.2) or more between the programs of the device's ``XLA Modules`` line, the
annotated span(s) of the loop's thread that CONTAIN the gap, innermost
first, with their index: containment on one clock, not the nearest match
that ``harness.breakdown`` has to make (host clock against the trace's
session start, good to ~40 ms).  Where no span contains a gap, the spans
that overlap it are listed with the milliseconds they share.  It also
prints the harness's own labels of the longest gaps, for comparison, and
what ``stop_trace`` cost at this tracer level.  The whole report goes to
``chiprun_out/gapcause_<cell>_<seed>.json``.

``--trace-seconds`` (default: the mix's) takes a longer stretch than the
benchmark's: starting the profiler with the host tracer on stalls the loop
for some hundred milliseconds, and the first gaps of a trace are that
stall's.  ``stop_trace`` runs on a thread of its own here, so that the
driver ends the program's loop at once and a val set sized for the
benchmark's stop (10-27 s) does not run out under this one's.

A program without annotations (one from before it had them) gives gaps
with no cause: the tool says so and exits 0.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import threading
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TOOLS)
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

LOOP_SPANS = ("train/dispatch", "decode/dispatch")     # the thread that holds them is the loop's


def annotated(planes):
    """{thread: [(name, index, start_ns, end_ns)]} of the program's
    annotations on the host plane: events whose name is a span's
    (``family/phase``) and that carry the ``i`` stat."""
    out = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for n, line in enumerate(plane.lines):          # threads share names ("python"): number them
            for ev in line.events:
                stats = dict(ev.stats)
                if "i" not in stats or "/" not in ev.name:
                    continue
                out.setdefault(f"{line.name}.{n}", []).append(
                    (ev.name, int(stats["i"]), int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    return out


def device_modules(planes):
    """[(name, start_ns, end_ns)] of the first device plane's ``XLA
    Modules`` line, by start."""
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                events = [(ev.name.split("(")[0], int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                          for ev in line.events]
                if events:
                    return sorted(events, key=lambda e: e[1])
    return []


def gap_report(planes, min_gap_ns: int) -> dict:
    planes = list(planes)
    modules = device_modules(planes)
    threads = annotated(planes)
    loop = next((t for t, evs in threads.items() if any(e[0] in LOOP_SPANS for e in evs)), None)
    on_loop = threads.get(loop, [])
    gaps = []
    for (prev, _s, prev_end), (nxt, nxt_start, _e) in zip(modules, modules[1:]):
        if nxt_start - prev_end < min_gap_ns:
            continue
        lo, hi = prev_end, nxt_start
        inside = sorted((e for e in on_loop if e[2] <= lo and e[3] >= hi), key=lambda e: e[3] - e[2])
        overlap = [] if inside else sorted(
            ((e, min(hi, e[3]) - max(lo, e[2])) for e in on_loop if e[2] < hi and e[3] > lo),
            key=lambda x: -x[1])
        meanwhile = sorted({e[0] for t, evs in threads.items() if t != loop
                            for e in evs if e[2] < hi and e[3] > lo})
        gaps.append({
            "ms": (hi - lo) / 1e6, "at_s": (lo - modules[0][1]) / 1e9, "after": prev, "before": nxt,
            "inside": [f"{e[0]}#{e[1]}" for e in inside],
            "overlaps": [[f"{e[0]}#{e[1]}", ns / 1e6] for e, ns in overlap[:4]],
            "other_threads": meanwhile,
        })
    return {"modules": len(modules), "loop_thread": loop, "annotated_spans": sum(map(len, threads.values())),
            "gaps": gaps}


def summary(gaps) -> list:
    """One row per (program before, program after, innermost cause):
    count, median and total ms."""
    groups = {}
    for g in gaps:
        cause = g["inside"][0].split("#")[0] if g["inside"] else (
            "overlaps " + g["overlaps"][0][0].split("#")[0] if g["overlaps"] else "no annotated span")
        groups.setdefault((g["after"], g["before"], cause), []).append(g["ms"])
    return sorted(({"after": a, "before": b, "cause": c, "n": len(ms), "median_ms": statistics.median(ms),
                    "total_ms": sum(ms)} for (a, b, c), ms in groups.items()), key=lambda r: -r["total_ms"])


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--min-gap-ms", type=float, default=0.2)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ns = ap.parse_args(argv)

    import harness
    import run as bench_run

    min_gap_ns = int(ns.min_gap_ms * 1e6)
    reports = []

    class HostTraceWindow(harness.TraceWindow):
        """The harness's window with the host tracer at level 1, the stop
        on a thread of its own, and the gaps read from the raw xplane
        while it is still there."""

        def __init__(self, directory: str, seconds: float) -> None:
            super().__init__(directory, ns.trace_seconds or seconds)
            self.stopper = None

        def run(self) -> None:
            import jax

            def stop() -> None:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    self.error = repr(e)
                self.timing += [self.t0_ns, self.t1_ns, time.perf_counter_ns()]

            try:
                shutil.rmtree(self.directory, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.host_tracer_level = 1
                options.python_tracer_level = 0
                self.timing = [time.perf_counter_ns()]
                jax.profiler.start_trace(self.directory, profiler_options=options)
                self.t0_ns = time.perf_counter_ns()
                time.sleep(self.seconds)
                self.t1_ns = time.perf_counter_ns()
                self.stopper = threading.Thread(target=stop, name="gapcause-stop", daemon=True)
                self.stopper.start()
            except Exception as e:
                self.error = repr(e)

        def reduced(self):
            import xtrace
            from jax.profiler import ProfileData

            if self.stopper is not None:
                self.stopper.join(timeout=900.0)
            path = xtrace.find_xplane(self.directory)
            if path is not None:
                reports.append(gap_report(ProfileData.from_file(path).planes, min_gap_ns))
            return super().reduced()

    harness.TraceWindow = HostTraceWindow
    args = bench_run.parse(["--workload", ns.workload, "--seed", str(ns.seed), "--seconds", str(ns.seconds),
                            "--trace", "1"] + (["--cpu-rehearsal"] if ns.cpu_rehearsal else []))
    try:
        cell, facts, outcome = bench_run.run_cell(args)
    except harness.BenchError as e:
        print(f"gapcause: {e}", file=sys.stderr, flush=True)
        return 2
    correct = harness.print_checks(outcome.checks)
    report = reports[-1] if reports else {"gaps": [], "modules": 0, "loop_thread": None, "annotated_spans": 0}
    timing = outcome.notes.get("trace_timing") or {}
    report.update(
        cell=cell.name, seed=ns.seed, device=facts, correct=correct, host_tracer_level=1,
        stop_trace_s=timing.get("stop_returned_s", 0.0) - timing.get("stop_called_s", 0.0),
        harness_labels=(harness.breakdown(outcome.run) or {}).get("idle_gaps", []),
        summary=summary(report["gaps"]),
    )
    shutil.rmtree(os.path.dirname(outcome.run.extras["trace_dir"]), ignore_errors=True)     # <kept>/run
    for g in report["gaps"]:
        if g["ms"] >= 1.0:
            cause = " < ".join(g["inside"]) or ("overlaps " + ", ".join(f"{n} {ms:.2f} ms" for n, ms in g["overlaps"])
                                                if g["overlaps"] else "no annotated span")
            print(f"gap {g['ms']:9.3f} ms at +{g['at_s']:.4f} s  {g['after']} -> {g['before']}  | {cause}"
                  f"  | other threads: {', '.join(g['other_threads']) or '-'}")
    for row in report["summary"]:
        print(json.dumps({"cause": row}))
    print(json.dumps({k: report[k] for k in ("cell", "seed", "device", "correct", "modules", "loop_thread",
                                             "annotated_spans", "stop_trace_s", "harness_labels")}))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"gapcause_{cell.name}_{ns.seed}.json"), "w") as f:
        json.dump(report, f)
    if not report["annotated_spans"]:
        print("gapcause: the trace holds no annotated host span: the program sets no "
              "telemetry annotate hook, so no gap has a cause", flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # as run.py: loader pools and telemetry threads must not hold the exit
