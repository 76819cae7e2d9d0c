"""Compile-time XLA cost/memory accounting → ``compile_report.json``.

Step time tells you a PR got slower; it cannot tell you *why*.  XLA
already knows: every compiled executable carries a cost analysis (FLOPs,
bytes accessed, transcendentals) and a memory analysis (temp / argument
/ output / alias HBM bytes).  This module snapshots those per jitted
function — ``train_step``, the eval encoder, the beam program — into one
JSON artifact per run, so the benchmark's compile accounting
(``entries()`` -> ``memory_peak_bytes``, ``train_mfu``) can catch a
silent FLOP or HBM regression even when wall-clock noise hides it, and a
post-mortem can answer "did the working set grow" without a profiler
window.

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

``op_scopes`` (per program): the map from each instruction of the
optimized module that a device trace can show — ``%fusion.503`` says
nothing, and its number changes with every compile — to the ``op_name``
the compiler kept in its metadata, which holds the ``jax.named_scope``
path the op was traced under (``.../while/body/decoder/lstm/dot_general``).
Read a trace by layer with it: docs/OBSERVABILITY.md "XLA accounting".
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional

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


# ---------------------------------------------------------------------------
# op_scopes: optimized-HLO instruction -> named-scope path
# ---------------------------------------------------------------------------

OP_SCOPE_COLUMNS = ("name", "shape", "container", "op_name", "inherited")

# their time in a trace is their children's: a reader that sums device time
# by scope skips them, or a loop and its body are counted twice
_CONTAINERS = {
    "while": ("condition", "body"),
    "conditional": ("true_computation", "false_computation", "branch_computations"),
    "call": ("to_apply",),
}
# never a device event of their own (no work is scheduled for them)
_NO_EVENT = frozenset(
    ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")
)

_COMPUTATION = re.compile(r"^(ENTRY )?(%[^\s(]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%[^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%[^\s,(){}]+")
_SHAPE_NOISE = re.compile(r"/\*.*?\*/|\{[^{}]*\}|\s+")


def normal_shape(shape: str) -> str:
    """A result shape without what printers disagree on: layouts
    (``{1,0:T(8,128)}``), ``/*index=5*/`` comments and spaces."""
    return _SHAPE_NOISE.sub("", shape)


def _split_shape(rest: str):
    """(result shape, what follows it) of an instruction's right-hand
    side; a tuple shape is a balanced parenthesis."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[: i + 1], rest[i + 2:]
    return rest, ""


def parse_op_scopes(hlo_text: str) -> List[list]:
    """Rows of :data:`OP_SCOPE_COLUMNS` for every instruction of the
    optimized module that can appear as a device event: the entry
    computation and, recursively, the computations its containers run
    (loop bodies and conditions, branches, calls) — not the insides of
    fusions, which the device runs as one op.

    The compiler makes instructions of its own and gives them no
    ``op_name``: layout copies, the asynchronous ``copy-start`` /
    ``slice-start`` pairs that move an operand to faster memory, dtype
    conversions of weights.  They are data movement FOR another op, so
    such a row takes the ``op_name`` of the first named instruction that
    uses its result (through other unnamed ones), else of the first named
    one it reads, else of the loop it only feeds, and says so in
    ``inherited``."""
    computations: Dict[str, List[str]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line) if line[:1] in ("%", "E") else None
            if m and line.endswith("{"):
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif line.startswith("}"):
            current = None
        else:
            current.append(line)
    rows: List[list] = []
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        # name -> [opcode, shape, op_name, operands], in program order
        parsed: Dict[str, list] = {}
        for line in computations[comp]:
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            shape, tail = _split_shape(m.group(2))
            opcode, _, rest = tail.partition("(")
            name = _OP_NAME.search(rest)
            body = rest.partition(", metadata=")[0]
            parsed[m.group(1)] = [opcode, shape, name.group(1) if name else "", _OPERAND.findall(body), body]
        users: Dict[str, List[str]] = {}
        for inst, (_o, _s, _n, operands, _b) in parsed.items():
            for operand in operands:
                if operand in parsed:
                    users.setdefault(operand, []).append(inst)

        def named(inst: str, edges, skip, depth: int = 0, visited=None) -> str:
            """op_name of the nearest named instruction along ``edges``,
            not counting (nor passing through) the opcodes in ``skip``."""
            visited = visited if visited is not None else set()
            for nxt in edges(inst):
                if nxt in visited or nxt not in parsed or parsed[nxt][0] in skip:
                    continue
                visited.add(nxt)
                found = parsed[nxt][2] or (
                    named(nxt, edges, skip, depth + 1, visited) if depth < 8 else ""
                )
                if found:
                    return found
            return ""

        def inherit(inst: str) -> str:
            uses = lambda i: users.get(i, ())  # noqa: E731
            reads = lambda i: parsed[i][3]  # noqa: E731
            return (
                named(inst, uses, _CONTAINERS)
                # a parameter's op_name is its argument's path, not a scope
                or named(inst, reads, ("parameter",))
                # what only feeds a loop is that loop's set-up
                or named(inst, uses, ())
            )

        for inst, (opcode, shape, op_name, _operands, body) in parsed.items():
            if opcode in _NO_EVENT:
                continue
            container = opcode in _CONTAINERS
            if container:
                for attr in _CONTAINERS[opcode]:
                    called = re.search(attr + r"=(\{[^}]*\}|%[^\s,]+)", body)
                    if called:
                        todo += _OPERAND.findall(called.group(1))
            inherited = "" if op_name else inherit(inst)
            rows.append([inst, normal_shape(shape), container, op_name or inherited, bool(inherited)])
    return rows


def _op_scopes(compiled, tel) -> Optional[Dict[str, Any]]:
    """The op map of one compiled program, timed as the
    ``setup/compile_accounting`` span.  None where the executable gives no
    text (never raises: the map is an aid, the run goes on without it)."""
    t0 = time.perf_counter_ns()
    try:
        rows = parse_op_scopes(compiled.as_text())
    except Exception as e:
        print(
            f"sat_tpu: op_scopes skipped: {e!r}", file=sys.stderr, flush=True
        )
        return None
    finally:
        if tel is not None:
            tel.record("setup/compile_accounting", t0, time.perf_counter_ns() - t0)
    if not rows:
        return None
    return {"columns": list(OP_SCOPE_COLUMNS), "rows": rows}


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
    scopes = _op_scopes(compiled, tel)
    if scopes is not None:
        entry["op_scopes"] = scopes

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
