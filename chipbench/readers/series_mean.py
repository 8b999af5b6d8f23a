"""The mean of one of the run's series of samples."""
from chipbench.harness.stats import mean


def read(obs, series):
    values = obs.get("series", {}).get(series)
    return mean(values) if values else None
