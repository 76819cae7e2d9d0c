"""Share (%) of the measured window that a host span of the program was open."""


def read(run, span: str):
    t0s, durs = run.spans(span)
    if len(durs) == 0:
        return None
    lo, hi = run.window_ns
    return 100.0 * float(durs.sum()) / float(hi - lo)
