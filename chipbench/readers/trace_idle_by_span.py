"""The device's idle time by the program span that covers it, as a share
of the traced window, with the host's spans first moved onto the
device's clock by an offset measured from the trace itself.

``span`` names a span of the scheduler's thread (``decode.empty``: the
wait between turns when nothing is to run; ``decode.step``: a turn, so
also every span inside one, which is every other ``decode.*`` span).
The number is the idle time of the first device whose covering span, by
``harness/trace.py:_covering``'s rule (the shortest ``decode.*`` span
over the middle of the gap; a gap under a microsecond lies between two
operations), is of that kind, over ``harness/named.py:window``, in
percent.  With the other name's share and what lies under neither, it
sums to ``serve_device_idle_share``.

The offset (host clock minus device clock, seconds) is bounded from two
things that cannot happen: a run of ``jit_mxtpu_decode`` beginning
before the ``decode.decode`` span that dispatched it begins (a lower
bound, its maximum over the turns), and a ``decode.sync`` ending before
the run it waited for ends (an upper bound, its minimum over the
turns: a turn's read waits for the run the turn before dispatched).
Spans and runs are matched in order.  The first runs of a trace may
have been dispatched before the capture began (or by a turn whose own
span began before it), so the runs' sequence is tried shifted by none
to three; of the shifts whose bounds do not cross, the one whose
midpoint is nearest zero is taken (where every turn is like the next,
clocks further apart than half a turn cannot be told from a
neighbouring turn; a server that idles now and then leaves one shift
uncrossed).  The host's spans are moved by the midpoint before any gap is
booked; where the bounds cross under every shift nothing is booked.
The lower bound is tight only where the device stood idle when the
dispatch came, which is where there is idle time to book.

Nothing where the run was not traced, the trace holds no device
operation, the bounds cross, or the program has no such span: a span
that never occurred in a program that has it reads 0, and the reader
knows the program has it by ``known_by``, a figure of
``engine.stats()`` that came with the span.
"""
import re

from chipbench.harness import named
from chipbench.harness.cli import say
from chipbench.harness.trace import _covering
from chipbench.readers import engine_stat, trace_named

DISPATCH, SYNC, TURN = "decode.decode", "decode.sync", "decode.step"
EMPTY = "decode.empty"
DECODE_RUN = re.compile(r"^jit_mxtpu_decode\(")
SHIFTS = (0, 1, 2, 3)


def _turns(parsed):
    """Per ``decode.step`` span, in order: (start of the dispatch span
    inside it or None, end of the sync span inside it or None)."""
    prefix = parsed["span_prefix"]
    by_name = {n: sorted((a, b, t) for a, b, name, t in parsed["spans"]
                         if name == prefix + n)
               for n in (TURN, DISPATCH, SYNC)}
    out = []
    for lo, hi, thread in by_name[TURN]:
        inside = {n: [(a, b) for a, b, t in by_name[n]
                      if t == thread and lo <= a and b <= hi]
                  for n in (DISPATCH, SYNC)}
        out.append((inside[DISPATCH][0][0] if inside[DISPATCH] else None,
                    inside[SYNC][-1][1] if inside[SYNC] else None))
    return out


def offset_bounds(parsed):
    """``(lower, upper, shift)`` of host clock minus device clock in
    seconds, or None where no shift of the runs leaves the bounds
    uncrossed (or the trace holds no turn that dispatched)."""
    if not parsed["devices"]:
        return None
    runs = sorted((a, b) for a, b, name in parsed["devices"][0]["modules"]
                  if DECODE_RUN.search(name))
    turns = _turns(parsed)
    found = []
    for shift in SHIFTS:
        lower, upper, k, waited = [], [], shift, None
        for dispatched, synced in turns:
            # this turn's read waits for the run the turn before dispatched
            if synced is not None and waited is not None:
                upper.append(synced - waited[1])
            waited = None
            if dispatched is not None and k < len(runs):
                lower.append(dispatched - runs[k][0])
                waited = runs[k]
                k += 1
        if lower and upper and max(lower) <= min(upper):
            found.append((max(lower), min(upper), shift))
    return min(found, key=lambda f: abs(f[0] + f[1])) if found else None


def idle_shares(parsed, offset):
    """Percent of the window the first device idled under
    ``decode.empty`` and under ``decode.step`` (a turn or any other
    ``decode.*`` span: a turn that began before the capture leaves its
    phases in the trace without itself), the host's spans moved
    ``offset`` seconds earlier."""
    prefix = parsed["span_prefix"]
    mine = [(a - offset, b - offset, n) for a, b, n, _ in parsed["spans"]
            if n.startswith(prefix + "decode.")]
    idle = {EMPTY: 0.0, TURN: 0.0}
    for a, b in named.idle_intervals(parsed):
        name = _covering(mine, a, b)
        if name.startswith(prefix):     # not "no_span", "between_ops"
            idle[EMPTY if name == prefix + EMPTY else TURN] += b - a
    win = named.window(parsed)
    return {name: 100.0 * s / (win[1] - win[0]) for name, s in idle.items()}


def booked(parsed):
    """The idle shares of one parsed trace under the offset found in it
    (None where there is none to find), and the ``clock_offset`` line."""
    if not parsed["devices"]:
        return None
    bounds = offset_bounds(parsed)
    shares = bounds and idle_shares(parsed, (bounds[0] + bounds[1]) / 2.0)
    say("clock_offset", bounds_ms=bounds and [1e3 * b for b in bounds[:2]],
        runs_shifted=bounds and bounds[2], idle_share=shares)
    return shares


def read(obs, span, known_by):
    if "_idle_by_span" not in obs:
        parsed = trace_named._parsed(obs)
        obs["_idle_by_span"] = parsed and booked(parsed)
    shares = obs["_idle_by_span"]
    if shares is None or engine_stat.read(obs, known_by) is None:
        return None
    return shares[span]


if __name__ == "__main__":
    # by hand, from the repo's root:
    #   python3 -m chipbench.readers.trace_idle_by_span chipbench/out/trace_<cell>
    import sys
    from chipbench.harness.trace import xplane_in
    booked(named.load(xplane_in(sys.argv[1]) or sys.argv[1]))
