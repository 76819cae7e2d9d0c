"""A statistic of a host span's durations.  ``scale`` converts ns (1e-6:
ms); spans that record a count in place of a duration use scale 1 and
``windowed: false`` (the program stamps them with no start time)."""

from reducers._stats import stat as _stat


def read(run, span: str, stat: str = "median", scale: float = 1e-6, windowed: bool = True):
    _t0s, durs = run.spans(span) if windowed else run.spans(span, within="all")
    value = _stat(durs, stat)
    return None if value is None else value * scale
