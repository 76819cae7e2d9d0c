"""A number (or a statistic of an array) the driver measured itself and
left under ``run.extras[key]``: compile seconds, the generator's lateness."""

import numpy as np

from reducers._stats import stat as _stat


def read(run, key: str, stat: str = "value", scale: float = 1.0):
    if key not in run.extras:
        return None
    value = run.extras[key]
    if stat != "value":
        value = _stat(np.asarray(value), stat)
    return None if value is None else float(value) * scale
