"""A percentile of one of the run's series of samples, each sample
standing as many times as its entry in a second series says."""
from chipbench.harness.stats import weighted_percentile


def read(obs, series, weights, q):
    values = obs.get("series", {}).get(series)
    if not values:
        return None
    return weighted_percentile(values, obs["series"][weights], q)
