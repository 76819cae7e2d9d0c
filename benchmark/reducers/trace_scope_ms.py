"""Device self time (ms) of one named layer per run of a program: the
``jax.named_scope`` buckets of a train step or of a decoded batch.

The trace names a device op by its whole HLO line (``%fusion.503 = ...``),
and the number after the dot changes with every compile; the PROGRAM keeps,
in its compile accounting (``sat_tpu.telemetry.xla.entries()[program]
["op_scopes"]``), the map from each instruction of the optimized module to
the ``op_name`` it was traced under.  This reader sums
``run.trace["op_totals"]`` over that program's LEAF instructions (a
``while`` is its body's time over again: containers are skipped), puts each
into the bucket of the first of ``rules`` (ordered ``[bucket, regular
expression]`` pairs, searched in ``op_name``) that matches, and divides by
the number of runs of ``module`` (pattern of the ``XLA Modules`` name) in
the trace.  The trace starts and ends inside a run, and the ops of a run
that is cut are in ``op_totals`` like any other's, so a cut run counts as
the part of a whole one that its module event lasted (on the chip: nine
events of a train step, one of 56.6 and one of 94.2 ms among seven of
103.5, are 8.46 runs; counted as nine, every bucket read 6% low).  What no
rule claims is the bucket ``unscoped``; ``pick: "unscoped"`` reads its
share (%) of the program's leaf time.

An instruction is found by the head of its line as the trace prints it:
name and result shape (layouts dropped: printers differ on them).  A decode
trace holds two programs in one ``op_totals`` and both may own a
``%fusion.4``: a key that two programs of the run share is counted as
unscoped, never in a bucket.

``rules`` is the list itself or the name of a file under ``scopes/`` that
holds it, so that the metrics of one program share one list and their
buckets add up.  Where the program keeps no map (a program from before it
did), there is nothing to read: None.
"""

import json
import os
import re
import statistics

_LAYOUT = re.compile(r"/\*.*?\*/|\{[^{}]*\}|\s+")
_HEAD = re.compile(r"^(%[^\s=]+) = ")


def head(line: str):
    """``(name, result shape without layouts)`` of a traced op's name (its
    HLO line), or None where the line has no such head."""
    m = _HEAD.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):                    # a tuple shape: to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[: i + 1]
                break
    else:
        rest = rest.partition(" ")[0]
    return m.group(1), _LAYOUT.sub("", rest)


def load_rules(rules):
    if isinstance(rules, str):
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                            "scopes", rules + ".json")
        with open(path) as f:
            rules = json.load(f)["rules"]
    return [(bucket, re.compile(pattern)) for bucket, pattern in rules]


def _by_key(scopes: dict) -> dict:
    """{(name, shape): {column: value}} of one program's ``op_scopes``."""
    rows = (dict(zip(scopes["columns"], row)) for row in scopes["rows"])
    return {(r["name"], r["shape"]): r for r in rows}


def buckets(op_totals: dict, scopes: dict, others, rules) -> dict:
    """{bucket: seconds} of the leaf instructions of ``scopes`` (one
    program's ``op_scopes``) found in ``op_totals``; ``others``: the
    ``op_scopes`` of the run's other programs."""
    own = _by_key(scopes)
    shared = set().union(*(_by_key(o) for o in others))
    out = {}
    for line, seconds in op_totals.items():
        key = head(line)
        row = own.get(key)
        if row is None or row["container"]:
            continue
        bucket = "unscoped"
        if key not in shared:
            bucket = next((b for b, rx in rules if rx.search(row["op_name"])), "unscoped")
        out[bucket] = out.get(bucket, 0.0) + seconds
    return out


def runs_of(modules: dict, module: str) -> float:
    """How many runs of the modules that ``module`` matches the trace
    holds: their device time over the duration of a whole run (the median
    of the events within a tenth of the longest)."""
    durations = [d for name, ds in modules.items() if re.search(module, name) for d in ds]
    if not durations:
        return 0.0
    whole = statistics.median(d for d in durations if d >= 0.9 * max(durations))
    return sum(durations) / whole if whole > 0 else 0.0


def read(run, program: str, module: str, rules, pick: str):
    if not run.trace:
        return None
    try:
        from sat_tpu.telemetry import xla
    except ImportError:
        return None
    entries = xla.entries()
    scopes = entries.get(program, {}).get("op_scopes")
    if not scopes:
        return None
    runs = runs_of(run.trace["modules"], module)
    others = [e["op_scopes"] for name, e in entries.items() if name != program and e.get("op_scopes")]
    found = buckets(run.trace["op_totals"], scopes, others, load_rules(rules))
    total = sum(found.values())
    if not runs or total <= 0.0:
        return None
    if pick == "unscoped":
        return 100.0 * found.get("unscoped", 0.0) / total
    return 1e3 * found.get(pick, 0.0) / runs
