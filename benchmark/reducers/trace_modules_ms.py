"""Device time (ms) of XLA modules per unit of work: for each regular
expression in ``patterns`` the ``stat`` of the durations of the modules it
matches, summed over the patterns (e.g. encoder + beam search per batch)."""

import re

from reducers._stats import stat as _stat


def read(run, patterns, stat: str = "median"):
    if not run.trace:
        return None
    total, found = 0.0, False
    for pattern in patterns:
        durs = [d for name, ds in run.trace["modules"].items() if re.search(pattern, name) for d in ds]
        value = _stat(durs, stat)
        if value is not None:
            total, found = total + value, True
    return 1e3 * total if found else None
