"""One of the run's counters, scaled."""


def read(obs, name, scale=1.0):
    value = obs.get("counters", {}).get(name)
    return None if value is None else value * scale
