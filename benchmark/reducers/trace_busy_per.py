"""Device busy time (ms) per unit counted by the driver in the traced
sub-window (``run.extras[per]``, e.g. replies finished inside it)."""


def read(run, per: str):
    n = run.extras.get(per)
    if not run.trace or not n:
        return None
    return 1e3 * run.trace["busy_s"] / float(n)
