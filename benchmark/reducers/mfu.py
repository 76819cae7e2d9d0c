"""Model FLOP/s utilisation (%): operations per unit of work FROM SHAPES
(``run.extras[flops]``, set from benchmark/flops.py) x the run's own rate
(the driver's quantity ``run.measured[rate]``) / the device's peak in
benchmark/peaks.json x chips."""


def read(run, rate: str, flops: str, peak: str = "bf16_flops_per_s"):
    if not run.peaks or rate not in run.measured or flops not in run.extras:
        return None
    return 100.0 * run.measured[rate] * run.extras[flops] / (run.peaks[peak] * run.cell.chips)
