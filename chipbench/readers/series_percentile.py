"""A percentile of one of the run's series of samples."""
from chipbench.harness.stats import percentile


def read(obs, series, q):
    values = obs.get("series", {}).get(series)
    return percentile(values, q) if values else None
