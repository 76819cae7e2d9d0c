"""Mean device time (microseconds) of one call of the ops whose name in the
trace's ``XLA Ops`` line matches ``pattern`` (a kernel is a custom-call
named after its function: ``%fused_attend.7``)."""

import re


def read(run, pattern: str):
    if not run.trace:
        return None
    total = count = 0.0
    for name, seconds in run.trace["op_totals"].items():
        if re.search(pattern, name):
            total += seconds
            count += run.trace["op_counts"][name]
    return 1e6 * total / count if count else None
