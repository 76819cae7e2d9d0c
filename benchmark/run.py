"""benchmark/run.py — one cell of BENCHMARK.json, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process holds the chip: this one.  It checks that jax's first device is
a TPU that ``peaks.json`` knows (else it exits non-zero before any work),
makes data and weights from the seed, and calls ``sat_tpu.cli.main`` for
the cell's phase on its own main thread, as ``python -m sat_tpu.cli``
would; the plain reference runs in the same process after the program has
returned.  The serve cells' load generator is a child that never imports
jax.  The last line of stdout is the contract's JSON object and nothing
else; every number that decided ``correct`` is printed before it, beside
its limit.

``--cpu-rehearsal`` drives the same control flow at a toy size on the CPU
and never prints a result line.  ``--rates`` (serve cells) is the knee
sweep and prints no result line either.
"""

from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()
T_START_UNIX = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--rates", default=None, help="serve cells: knee sweep, comma-separated req/s")
    ap.add_argument("--control", type=int, default=0,
                    help="builder's option: also put the lower-precision references (fp8 throughout, "
                         "fp8 in the encoder alone) in the program's place and print their numbers "
                         "under notes.control")
    ap.add_argument("--program", action="append", default=[], metavar="KEY=VALUE",
                    help="builder's option: one switch of the program over the mix's (JSON, or a bare "
                         "word for a string), e.g. encoder_quant=int8: the program's own "
                         "lower-precision path as the control")
    ap.add_argument("--bench-json", default=None, metavar="FILE",
                    help="builder's option: resolve the cell from FILE (BENCHMARK.json's schema) and not "
                         "from BENCHMARK.json: the tests keep a serve cell in a file of their own")
    ap.add_argument("--describe-trace", action="store_true",
                    help="with --trace 1: also write the trace's planes and lines to chiprun_out/")
    return ap.parse_args(argv)


def run_cell(args, sabotage=None):
    """Everything but the device check's verdict and the printing: returns
    (cell, facts, outcome).  The tests call this with ``sabotage`` set."""
    import harness

    switches = {}
    for pair in getattr(args, "program", []):
        key, _, value = pair.partition("=")
        try:
            switches[key] = json.loads(value)
        except ValueError:
            switches[key] = value                     # a bare word is a string
    cell = harness.Cell(args.workload, rehearsal=args.cpu_rehearsal, switches=switches,
                        bench_json=getattr(args, "bench_json", None))
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import sat_tpu  # noqa: F401 — only to fail early where the program is absent
    except ImportError as e:
        raise harness.BenchError(f"the program is not in this checkout: {e}")
    import jax

    facts, peaks = harness.require_device(cell.chips, args.cpu_rehearsal)
    from sat_tpu.utils.compile_cache import enable

    enable(jax)
    env = types.SimpleNamespace(t_start_ns=T_START_NS, t_start_unix=T_START_UNIX,
                                facts=facts, peaks=peaks, meter=harness.CompileMeter())
    args.sabotage = sabotage
    driver = importlib.import_module("drivers." + cell.mix["driver"])
    return cell, facts, driver.run(cell, args, env)


def main(argv=None) -> int:
    args = parse(argv)
    import harness

    try:
        cell, facts, outcome = run_cell(args)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    correct = harness.print_checks(outcome.checks)
    print(json.dumps({"notes": outcome.notes}, default=str), flush=True)
    if args.describe_trace and args.trace:
        import xtrace

        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        trace_dir = outcome.run.extras["trace_dir"]
        with open(os.path.join(ROOT, "chiprun_out", f"trace_{cell.name}.txt"), "w") as f:
            f.write(xtrace.describe(trace_dir))
        xtrace.record(trace_dir, os.path.join(ROOT, "chiprun_out", f"trace_{cell.name}.json"))
    shutil.rmtree(os.path.dirname(outcome.run.extras["trace_dir"]), ignore_errors=True)   # <kept>/run
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": "passed" if correct else "failed", "device": facts,
                          "e2e_names": sorted(outcome.run.e2e),
                          "per_layer_names": sorted(harness.read_per_layer(cell, outcome.run))}),
              flush=True)
        return 0 if correct else 1
    print(harness.result_line(cell, bool(args.trace), correct, outcome.attempted, outcome.failed,
                              facts, outcome.run, outcome.memory_peak_bytes), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # loader pools and telemetry threads must not hold the exit
