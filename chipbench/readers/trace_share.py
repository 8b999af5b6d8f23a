"""One share from the device trace's summary (harness/trace.py); nothing
where the run was not traced or the trace holds no device operation."""


def read(obs, key):
    summary = obs.get("trace")
    return None if summary is None else summary.get(key)
