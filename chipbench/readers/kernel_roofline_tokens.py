"""A named kernel's share of its memory roofline in the traced segment,
for a kernel whose bytes follow the TOKENS it reads (an attention over a
paged cache) and not the slots it advances: the bytes its calls had to
move over the time they took, over the chip's published bandwidth, in
percent.

The bytes are the algorithm's: ``bytes_fn`` of the configuration's
family (``models/<family>.py``) gives what one call must move for
``rows`` live rows.  The rows a call read are the program's own count,
``rows_stat`` of ``engine.stats()`` (a dotted path, read by
``readers/engine_stat.py``): ``traced.live_tokens_mean`` is the mean,
over the decode steps the program dispatched while the profiler's
capture ran, of the decoding slots' summed context lengths, which the
host knows without a read: the same steps whose kernel calls the trace
holds, give or take the one in flight when the capture began.  (The
engine's ``live_tokens_mean`` beside it runs over its whole life, the
lead-in's ramp and the drain included: PR 33's first runs read 26% by
it where the traced steps' own rows give 36%.)  Calls are the Mosaic
custom calls whose instruction name contains ``match``; their time is
the sum of their durations.

Nothing where the run was not traced, the trace holds no such call, the
engine reports no such figure, or the family has no such function."""
from chipbench.harness.cli import load_module
from chipbench.harness.peaks import peak


def read(obs, match, bytes_fn, rows_stat):
    parsed = load_module("readers", "trace_named")._parsed(obs)
    if parsed is None:
        return None
    seconds = [b - a for dev in parsed["devices"]
               for a, b, name in dev["kernels"] if match in name]
    rows = load_module("readers", "engine_stat").read(obs, rows_stat)
    family = load_module("models", obs["config"]["family"])
    per_call = getattr(family, bytes_fn, None)
    if not seconds or not rows or per_call is None:
        return None
    moved = len(seconds) * per_call(obs["config"], rows)
    return 100.0 * moved / sum(seconds) / peak(obs["device"]["kind"],
                                               "hbm_bytes_per_s")
