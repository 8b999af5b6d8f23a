"""A named kernel's share of its memory roofline in the traced segment:
the bytes its calls had to move over the time they took, over the
chip's published bandwidth, in percent.

The bytes are the algorithm's, not the implementation's: ``bytes_fn`` of
the configuration's family (``models/<family>.py``) gives what ONE slot
of one call must move, computed from the configuration's shapes; the
driver's series ``slots_series`` says how many slots each step inside
the trace advanced.  Calls are the Mosaic custom calls whose
instruction name contains ``match``, on every device plane; their time
is the sum of their durations.  So the share reads the same work
whatever implements the kernel, and a kernel that moves no more than it
must cannot pass 100.

Nothing where the run was not traced, the trace holds no such call (a
rehearsal on the CPU; a program without the kernel), the driver left no
series, or the family has no such function."""
from chipbench.harness.cli import load_module
from chipbench.harness.peaks import peak


def read(obs, match, bytes_fn, slots_series):
    parsed = load_module("readers", "trace_named")._parsed(obs)
    if parsed is None:
        return None
    seconds = [b - a for dev in parsed["devices"]
               for a, b, name in dev["kernels"] if match in name]
    slots = obs.get("series", {}).get(slots_series) or []
    family = load_module("models", obs["config"]["family"])
    per_slot = getattr(family, bytes_fn, None)
    if not seconds or not slots or per_slot is None:
        return None
    moved = len(seconds) * per_slot(obs["config"], 1) \
        * sum(slots) / len(slots)
    return 100.0 * moved / sum(seconds) / peak(obs["device"]["kind"],
                                               "hbm_bytes_per_s")
