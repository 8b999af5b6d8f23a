"""The device trace under the program's own names: time by named Pallas
kernel, runs by named executable, the program's host spans, and the
device's idle time by the span that covers it.

What a TPU v5e trace lets a reader tell apart, and what it does not
(found in this benchmark's recorded ``tests/data/v5e_1chip.xplane.pb``;
PERF.md, Layers, has the longer note):

- an ``XLA Ops`` event is the text of its HLO instruction and three
  timing stats, nothing else: no ``op_name`` metadata, no ``tf_op``
  stat.  ``jax.named_scope`` reaches no reader;
- a Pallas kernel shows the ``name`` its ``pallas_call`` was given as
  its instruction's name, wrapped in JAX's transforms and numbered
  (``%transpose_jvp_mxtpu_flash_dq__.1``): kernels are matched by
  substring, never by equality;
- an executable is one ``XLA Modules`` event per run, named
  ``jit_<function>(<fingerprint>)``: matched by pattern;
- the program's spans (``mxnet_tpu.tracing.span`` while a capture
  runs) are events named ``mxtpu.<span>`` on the line of the thread
  that ran them, on ``/host:CPU``, on the device planes' clock (the
  device's ran about 1 ms ahead of the host's in the recorded trace, so
  idle time is attributed to spans that loosely).

Everything here is a pure function of one ``.xplane.pb``; times are
seconds unless a name says ``ms``.  A trace without what is asked for
gives None, never an error: the parent of the commit that named the
kernels has none of these names.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from .stats import percentile
from .trace import (DEVICE_PLANE, HOST_PLANE, OP_LINE, Interval, _covering,
                    instruction, is_container, is_pallas, length, subtract,
                    union)

MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "mxtpu."

# start, end, name (prefix and all), the thread's line
Span = Tuple[float, float, str, str]


def load(path: str, span_prefix: str = SPAN_PREFIX) -> dict:
    """One trace, read once:

    devices   per ``/device:TPU:<n>`` plane that ran an operation, in
              order of ``n``: ``ops`` (the intervals of every operation
              but the control-flow containers), ``kernels`` (``(start,
              end, instruction name)`` of the Mosaic custom calls) and
              ``modules`` (``(start, end, name)`` of the executables'
              runs)
    spans     the host's events whose name starts with ``span_prefix``
    """
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"plane": plane.name, "n": int(m.group(1)), "ops": [],
                   "kernels": [], "modules": []}
            kind: Dict[str, Optional[str]] = {}   # text -> kernel's name
            for line in plane.lines:
                if line.name == OP_LINE:
                    for ev in line.events:
                        text = ev.name
                        if text not in kind:
                            kind[text] = (
                                None if is_container(text) else
                                instruction(text) if is_pallas(text) else "")
                        if kind[text] is None:
                            continue
                        lo = ev.start_ns * 1e-9
                        hi = lo + ev.duration_ns * 1e-9
                        dev["ops"].append((lo, hi))
                        if kind[text]:
                            dev["kernels"].append((lo, hi, kind[text]))
                elif line.name == MODULE_LINE:
                    for ev in line.events:
                        lo = ev.start_ns * 1e-9
                        dev["modules"].append(
                            (lo, lo + ev.duration_ns * 1e-9, ev.name))
            if dev["ops"]:
                devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        lo = ev.start_ns * 1e-9
                        spans.append((lo, lo + ev.duration_ns * 1e-9,
                                      ev.name, line.name))
    devices.sort(key=lambda d: d["n"])
    spans.sort()
    return {"devices": devices, "spans": spans, "span_prefix": span_prefix}


# -- the device ---------------------------------------------------------------

def kernel_share(parsed: dict, match: str) -> Optional[float]:
    """The Mosaic custom calls whose instruction name contains
    ``match``: their union over the busy union, in percent, both summed
    over the device planes (the arithmetic of ``trace.reduce``'s
    ``pallas_share``).  None where no such call ran."""
    busy = kern = 0.0
    found = False
    for dev in parsed["devices"]:
        mine = [(a, b) for a, b, name in dev["kernels"] if match in name]
        found = found or bool(mine)
        kern += length(union(mine))
        busy += length(union(dev["ops"]))
    return 100.0 * kern / busy if found else None


def module_runs(parsed: dict, pattern: str) -> List[float]:
    """Device seconds of each run of the executables whose ``XLA
    Modules`` name matches ``pattern`` (``re.search``), all planes."""
    rx = re.compile(pattern)
    return [b - a for dev in parsed["devices"]
            for a, b, name in dev["modules"] if rx.search(name)]


def module_ms(parsed: dict, pattern: str, q: float) -> Optional[float]:
    return percentile([1e3 * s for s in module_runs(parsed, pattern)], q)


def module_ratio(parsed: dict, pattern: str, per: str) -> Optional[float]:
    """Runs matching ``pattern`` for each run matching ``per``; None
    where nothing matches ``per``."""
    base = len(module_runs(parsed, per))
    return len(module_runs(parsed, pattern)) / base if base else None


def window(parsed: dict) -> Optional[Interval]:
    """First start to last end of any device operation."""
    if not parsed["devices"]:
        return None
    return (min(a for d in parsed["devices"] for a, _ in d["ops"]),
            max(b for d in parsed["devices"] for _, b in d["ops"]))


def idle_intervals(parsed: dict) -> List[Interval]:
    """Where the first device ran nothing, inside the window."""
    win = window(parsed)
    if win is None:
        return []
    return subtract([win], union(parsed["devices"][0]["ops"]))


# -- the host -----------------------------------------------------------------

def _named(parsed: dict, name: str) -> List[Span]:
    full = parsed["span_prefix"] + name
    return [s for s in parsed["spans"] if s[2] == full]


def span_durations(parsed: dict, name: str,
                   minus: Sequence[str] = ()) -> List[float]:
    """Seconds of each span ``name``, less the spans named in ``minus``
    that lie inside it on the same thread."""
    inner = [s for other in minus for s in _named(parsed, other)]
    out = []
    for lo, hi, _, thread in _named(parsed, name):
        inside = union((a, b) for a, b, _, t in inner
                       if t == thread and lo <= a and b <= hi)
        out.append((hi - lo) - length(inside))
    return out


def span_ms(parsed: dict, name: str, q: float,
            minus: Sequence[str] = ()) -> Optional[float]:
    return percentile(
        [1e3 * s for s in span_durations(parsed, name, minus)], q)


def idle_in_span_ms(parsed: dict, name: str) -> Optional[float]:
    """Idle time of the first device inside spans ``name``, in ms per
    span, over the spans that lie wholly inside the device's window."""
    win = window(parsed)
    if win is None:
        return None
    inside = [(a, b) for a, b, _, _ in _named(parsed, name)
              if win[0] <= a and b <= win[1]]
    if not inside:
        return None
    whole = union(inside)
    busy = subtract(whole, idle_intervals(parsed))
    return 1e3 * (length(whole) - length(busy)) / len(inside)


def idle_by_span(parsed: dict) -> Dict[str, float]:
    """Idle seconds of the first device by the program's span that
    covers the middle of each gap: the shortest, since they nest
    (``trace._covering``'s rule, which ``trace.reduce`` applies to the
    benchmark's own spans)."""
    spans = [(a, b, name) for a, b, name, _ in parsed["spans"]]
    out: Dict[str, float] = {}
    for a, b in idle_intervals(parsed):
        name = _covering(spans, a, b)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


# -- the whole table, and one number of it -------------------------------------

_NUMBERED = re.compile(r"\.\d+$")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def table(parsed: dict) -> dict:
    """Everything under a name, for the run's ``named`` line: seconds
    per device of every Mosaic kernel (by instruction name, its number
    dropped), runs and median of every executable (its fingerprint
    dropped), count and median of every span, idle seconds by span."""
    k = float(len(parsed["devices"])) or 1.0
    kernels: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for dev in parsed["devices"]:
        for a, b, name in dev["kernels"]:
            stem = _NUMBERED.sub("", name)
            kernels[stem] = kernels.get(stem, 0.0) + (b - a) / k
        for a, b, name in dev["modules"]:
            modules.setdefault(_FINGERPRINT.sub("", name), []).append(
                1e3 * (b - a))
    spans: Dict[str, List[float]] = {}
    for a, b, name, _ in parsed["spans"]:
        spans.setdefault(name, []).append(1e3 * (b - a))
    win = window(parsed)
    return {
        "devices": len(parsed["devices"]),
        "window_s": win[1] - win[0] if win else None,
        "kernels_s": dict(sorted(kernels.items(), key=lambda kv: -kv[1])),
        "modules": {n: {"runs": len(v), "ms_p50": percentile(v, 50)}
                    for n, v in sorted(modules.items())},
        "spans": {n: {"count": len(v), "ms_p50": percentile(v, 50)}
                  for n, v in sorted(spans.items())},
        "idle_s_by_span": dict(sorted(idle_by_span(parsed).items(),
                                      key=lambda kv: -kv[1])),
    }


# one number of the trace, by the ``what`` of a metric's file
MODES = {"kernel_share": kernel_share, "module_ms": module_ms,
         "module_ratio": module_ratio, "span_ms": span_ms,
         "idle_in_span_ms": idle_in_span_ms}
