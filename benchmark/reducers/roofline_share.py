"""A kernel's share (%) of the chip's roofline: the least time the chip
could take for the kernel's work per run (the larger of operations over
peak FLOP/s and bytes over peak HBM bytes/s, both from shapes:
``counts`` names ``<module>.<function>(run) -> {"flops", "bytes"}`` under
benchmark/) over its measured device self time per run, which
``trace_scope_ms`` reads by named scope (``program``, ``module``,
``rules``, ``pick`` are that reader's).  Nothing to read (no trace, a
program that keeps no scopes, the bucket empty): None.
"""

import importlib

from reducers import trace_scope_ms


def read(run, program: str, module: str, rules, pick: str, counts: str,
         flops_peak: str = "bf16_flops_per_s", bytes_peak: str = "hbm_bytes_per_s"):
    ms = trace_scope_ms.read(run, program, module, rules, pick)
    if not ms or not run.peaks:
        return None
    mod, _, fn = counts.rpartition(".")
    work = getattr(importlib.import_module(mod), fn)(run)
    least_s = max(work["flops"] / run.peaks[flops_peak], work["bytes"] / run.peaks[bytes_peak])
    return 100.0 * least_s / (ms * 1e-3)
