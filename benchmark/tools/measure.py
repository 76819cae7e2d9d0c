"""Run a cell several times and reduce the runs to what a bound is set
from: for each metric the median and the spread (inter-quartile distance
by ``statistics.quantiles(n=4)`` as a share of the median) of each set.

    python3 benchmark/tools/measure.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --seconds 20 [--sets 2] [--trace 0] [--extra "--describe-trace"]

This parent never imports jax: each run is ``benchmark/run.py`` in a child
of its own, one after another.  Full output of each run goes to
``chiprun_out/<cell>/``; the check lines and the result line are echoed.
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--extra", default="")
    ap.add_argument("--control-first", type=int, default=0,
                    help="give the first N runs of the first set --control 1 (the check's "
                         "lower-precision readings; they fall after the window)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    sets = []
    for k in range(args.sets):
        rows = []
        for j, seed in enumerate(seeds):
            cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                   args.workload, "--seed", str(seed), "--seconds", str(args.seconds),
                   "--trace", str(args.trace)] + shlex.split(args.extra)
            if k == 0 and j < args.control_first:
                cmd += ["--control", "1"]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            wall = time.time() - t0
            tag = f"t{args.trace}_set{k}_seed{seed}"
            with open(os.path.join(out_dir, tag + ".log"), "w") as f:
                f.write(proc.stdout + "\n==== stderr ====\n" + proc.stderr)
            lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
            for ln in lines:
                if ln.startswith(('{"check"', '{"notes"', '{"sweep"')):
                    print("   ", ln[:600])
            last = lines[-1] if lines else ""
            print(f"[{tag}] rc={proc.returncode} wall={wall:.1f}s {last[:1500]}", flush=True)
            if proc.returncode != 0:
                print(proc.stderr[-3000:], flush=True)
                continue
            try:
                res = json.loads(last)
            except ValueError:
                continue
            if "metrics" in res:
                rows.append(res)
        sets.append(rows)
    summary = {}
    for k, rows in enumerate(sets):
        names = sorted({n for r in rows for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            summary.setdefault(n, []).append(
                {"set": k, "n": len(vals), "median": statistics.median(vals), "spread": spread(vals),
                 "min": min(vals), "max": max(vals)})
        print(json.dumps({"set": k, "correct": [r["correct"] for r in rows],
                          "failed": [r["failed"] for r in rows],
                          "memory_peak_bytes": [r["device"]["memory_peak_bytes"] for r in rows]}))
    for n, per_set in summary.items():
        print(json.dumps({"metric": n, "sets": per_set}))
    with open(os.path.join(out_dir, f"summary_t{args.trace}_{int(time.time())}.json"), "w") as f:
        json.dump({"args": vars(args), "sets": sets, "summary": summary}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
