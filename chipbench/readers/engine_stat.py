"""One figure of the program's own ``engine.stats()`` as the driver left
it in ``obs["notes"]["engine"]``, scaled: ``name`` is a key, or a
dotted path into a nested dict (``counters.moe_local_pair_share``).

Nothing where the driver left no such notes, or the program's engine
reports no such figure (a program from before the figure existed)."""


def read(obs, name, scale=1.0):
    value = obs.get("notes", {}).get("engine")
    for key in name.split("."):
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return None if value is None else value * scale
