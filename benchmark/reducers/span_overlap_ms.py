"""Milliseconds that the host spans called ``span`` share with the spans
called ``within``, per span called ``per``: the three are of one thread,
clock and ring, so ``train/device_empty`` x ``train/data_wait`` per
``train/step`` is the data wait a step that ran with the device empty.
Over the spans that start inside the window (``RunData.spans``).  None
where the program records no ``span`` (one from before it had it) or the
window holds no ``per``."""

import numpy as np


def read(run, span: str, within: str, per: str):
    a0, a_dur = run.spans(span)
    b0, b_dur = run.spans(within)
    n = len(run.spans(per)[1])
    if len(a0) == 0 or n == 0:
        return None
    shared = np.minimum((a0 + a_dur)[:, None], (b0 + b_dur)[None, :]) - np.maximum(a0[:, None], b0[None, :])
    return float(np.clip(shared, 0, None).sum()) / n * 1e-6
