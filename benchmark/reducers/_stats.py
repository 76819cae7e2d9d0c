import numpy as np

STATS = {
    "median": np.median, "mean": np.mean, "max": np.max, "sum": np.sum,
    "p95": lambda x: np.percentile(x, 95), "p99": lambda x: np.percentile(x, 99),
}


def stat(values, name: str):
    values = np.asarray(values, dtype=np.float64)
    if values.size == 0:
        return None
    return float(STATS[name](values))
