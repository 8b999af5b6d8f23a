"""One number from the traced segment under the program's own names
(harness/named.py): a named kernel's share of the device's busy time,
a named executable's run time or runs, one of the program's host spans,
the device's idle time inside a span.

The trace is the one the driver left in ``out/trace_<cell>``; it is read
once per run, and the whole table under every name goes out as one
``named`` line.  Nothing where the run was not traced, or where the
trace holds nothing under the name asked for: a rehearsal on the CPU has
no device plane (its spans still report), and the parent of the commit
that named the kernels has none of the names."""
import os
import time

from chipbench.harness import named
from chipbench.harness.cli import OUT_DIR, say
from chipbench.harness.trace import xplane_in


def _parsed(obs):
    if "_named" not in obs:
        obs["_named"] = None
        path = xplane_in(os.path.join(OUT_DIR,
                                      "trace_" + obs["cell"]["name"]))
        # the drivers trace after their window: a file older than the
        # window's start is an earlier run's
        opened = time.time() - (time.monotonic() - obs["t_window_start"])
        if path and os.path.getmtime(path) >= opened:
            obs["_named"] = named.load(path)
            say("named", **named.table(obs["_named"]))
    return obs["_named"]


def read(obs, what, **args):
    parsed = _parsed(obs)
    return None if parsed is None else named.MODES[what](parsed, **args)
