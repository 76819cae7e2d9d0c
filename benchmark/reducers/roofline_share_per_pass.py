"""``roofline_share`` for work that lives inside a LOOP of the program (the
caption steps of a search; the images of a prefill that goes image by
image), made independent of where the trace cuts a run.

``roofline_share`` divides a bucket's device time by the runs of the module
that the trace holds, a cut run counting as the part it lasted.  That is
right for a bucket spread evenly over the run.  A bucket that lies in ONE
phase is not: a 4-s trace of a 1.44-s period holds 2.78 runs but may hold
two of their step phases or three, so the steps' time a run read 138 or
191 ms in two traces of one program and a share of its roofline 122% (my
chip runs, PR 32).  Here the bucket's time is divided by the PASSES of the
loop that the trace holds, read off the trace itself: every leaf
instruction of a loop's body runs once a pass, so its event count
(``run.trace["op_counts"]``) is the number of passes (instructions of a
loop nested inside run a multiple of that: the count taken is the median
of the counts under 1.5 times the least).  The work a run does
(``counts``: ``<module>.<function>(run) -> {"flops", "bytes"}``) is that of
``run.extras[passes_per_run]`` passes (``caption_steps``; ``batch_size``).

Nothing to read (no trace, a program that keeps no scopes, a trace from
before ``op_counts``, the bucket empty): None.
"""

import importlib
import statistics

from reducers import trace_scope_ms


def bucket_ops(run, program: str, rules, pick: str):
    """[(seconds, events)] of the leaf instructions of ``program`` that the
    rules put in bucket ``pick``, or None where there is nothing to read."""
    if not run.trace or "op_counts" not in run.trace:
        return None
    try:
        from sat_tpu.telemetry import xla
    except ImportError:
        return None
    entries = xla.entries()
    scopes = entries.get(program, {}).get("op_scopes")
    if not scopes:
        return None
    own = trace_scope_ms._by_key(scopes)
    shared = set().union(*(trace_scope_ms._by_key(e["op_scopes"]) for name, e in entries.items()
                           if name != program and e.get("op_scopes")))
    rules = trace_scope_ms.load_rules(rules)
    out = []
    for line, seconds in run.trace["op_totals"].items():
        key = trace_scope_ms.head(line)
        row = own.get(key)
        if row is None or row["container"] or key in shared:
            continue
        if next((b for b, rx in rules if rx.search(row["op_name"])), None) == pick:
            out.append((seconds, run.trace["op_counts"].get(line, 0)))
    return out or None


def passes(ops) -> float:
    """Passes of the loop the trace holds: the median event count of the
    instructions that run once a pass."""
    counts = [c for _, c in ops if c > 0]
    if not counts:
        return 0.0
    return float(statistics.median(c for c in counts if c < 1.5 * min(counts)))


def read(run, program: str, rules, pick: str, counts: str, passes_per_run: str,
         flops_peak: str = "bf16_flops_per_s", bytes_peak: str = "hbm_bytes_per_s"):
    ops = bucket_ops(run, program, rules, pick)
    if not ops or not run.peaks:
        return None
    held = passes(ops)
    if not held:
        return None
    seconds_a_run = sum(s for s, _ in ops) / held * float(run.extras[passes_per_run])
    mod, _, fn = counts.rpartition(".")
    work = getattr(importlib.import_module(mod), fn)(run)
    least_s = max(work["flops"] / run.peaks[flops_peak], work["bytes"] / run.peaks[bytes_peak])
    return 100.0 * least_s / seconds_a_run
