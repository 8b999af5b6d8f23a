"""Device trace: capture with the JAX profiler, and the reduction from
its ``.xplane.pb`` to busy and idle time, shares by kind of operation,
the operations that took most time and the idle gaps by host span.

The reduction reads the trace with ``jax.profiler.ProfileData`` alone.
What a TPU v5e trace of this installation looks like, as found by hand
in this benchmark's own traces (PERF.md, Layers, has the longer note):

- a device is a plane named ``/device:TPU:<n>``.  Its line ``XLA Ops``
  holds one event per executed HLO instruction; ``XLA Modules`` one per
  executable run; ``Steps`` one per run of the largest; ``Async XLA
  Ops`` the in-flight spans of ``copy-start``/``slice-start`` pairs,
  which overlap the ops and are not read.  Host threads are lines of
  ``/host:CPU``, on the same clock;
- an event's name is the whole text of its HLO instruction,
  ``%fusion.570 = bf16[..]{..} fusion(...), kind=kOutput, calls=...``:
  the instruction's name (unstable: ``fusion.N`` renumbers with any
  change to the program), its opcode, and for a custom call its
  ``custom_call_target``;
- a Pallas kernel is a custom call whose target is ``tpu_custom_call``
  (Mosaic).  Other custom calls reach the line too (``ConcatBitcast``),
  so the opcode alone does not do;
- a collective is recognised by its opcode (``all-reduce``,
  ``all-gather``, ``reduce-scatter``, ``all-to-all``,
  ``collective-permute``, with ``-start`` and ``-done`` for the
  asynchronous forms);
- control-flow instructions (``while``, ``conditional``, ``call``) span
  the instructions of their bodies and are left out, or everything
  inside them would count twice.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CONTAINER_OPCODES = ("while", "conditional", "call")
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace what runs inside the ``with`` into ``log_dir``, in place of
    whatever trace was there.  The Python tracer stays off: it slows the
    host far more than the rest."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span of the benchmark's own on the profiler's clock."""
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def xplane_in(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_dir(log_dir: str) -> Optional[dict]:
    """The summary of the trace ``capture`` left in ``log_dir``."""
    path = xplane_in(log_dir)
    return reduce(path) if path else None


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that ``b`` does not cover (both merged)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- classification ----------------------------------------------------------

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
MOSAIC_TARGET = "tpu_custom_call"


def instruction(name: str) -> str:
    """``%fusion.570 = bf16[..] fusion(...)`` -> ``fusion.570``."""
    return name.lstrip("%").split(" ", 1)[0]


def opcode(name: str) -> str:
    """The HLO opcode of an event: from the instruction's text where the
    name is one (the first lower-case word followed by ``(`` after the
    ``=``: shapes and layouts hold none), else the name's stem."""
    if " = " in name:
        m = _OPCODE.search(name, name.index(" = "))
        if m:
            return m.group(1)
    return instruction(name).split(".", 1)[0]


def is_container(name: str) -> bool:
    return opcode(name) in CONTAINER_OPCODES


def is_collective(name: str) -> bool:
    op = opcode(name)
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in COLLECTIVE_OPCODES


def is_pallas(name: str) -> bool:
    """A Mosaic custom call: opcode ``custom-call``, target
    ``tpu_custom_call``.  An event that does not say its target cannot
    be told from other custom calls and does not count."""
    if opcode(name) != "custom-call":
        return False
    m = _TARGET.search(name)
    return bool(m) and m.group(1) == MOSAIC_TARGET


def label(name: str) -> str:
    """A short name for the breakdown: instruction and opcode."""
    op = opcode(name)
    if op == "custom-call":
        m = _TARGET.search(name)
        op += ":" + (m.group(1) if m else "?")
    return f"{instruction(name)} {op}"[:96]


# -- reading -----------------------------------------------------------------

def _kind(name: str) -> Optional[str]:
    if is_container(name):
        return None
    if is_collective(name):
        return "collective"
    return "pallas" if is_pallas(name) else "compute"


def read_planes(path: str, span_prefix: str):
    """``(devices, spans)``: per device plane the op events as
    ``(start_s, end_s, label, kind)`` with kind ``pallas``,
    ``collective`` or ``compute``; and the host's events whose name
    starts with ``span_prefix`` as ``(start_s, end_s, name)``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: List[Tuple[float, float, str]] = []
    known: Dict[str, tuple] = {}       # an event's text -> (label, kind)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if name not in known:
                        known[name] = (label(name), _kind(name))
                    tag, kind = known[name]
                    if kind is not None:
                        lo = ev.start_ns * 1e-9
                        events.append((lo, lo + ev.duration_ns * 1e-9,
                                       tag, kind))
            devices[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        lo = ev.start_ns * 1e-9
                        spans.append((lo, lo + ev.duration_ns * 1e-9,
                                      ev.name[len(span_prefix):]))
    return devices, spans


# a gap shorter than this lies between two back-to-back operations and
# is no host's doing
BETWEEN_OPS_S = 1e-6


def _covering(spans, lo: float, hi: float) -> str:
    """Which of the benchmark's own host spans covers the middle of the
    gap ``[lo, hi)``: the shortest, since they nest."""
    if hi - lo < BETWEEN_OPS_S:
        return "between_ops"
    t = (lo + hi) / 2.0
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else "no_span"


def reduce(path: str, span_prefix: str = "chipbench.") -> Optional[dict]:
    """The summary of one trace, or None where no device operation is
    in it.  Times in seconds; shares in percent of busy time, idle in
    percent of the window; everything averaged over the device planes.

    window     first start to last end of any device operation
    busy       union of the operations' intervals on a device
    *_time     union of the intervals of that kind on a device
    exposed    the part of the collectives' union that no compute or
               Pallas operation on the same device covers
    """
    devices, spans = read_planes(path, span_prefix)
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    lo = min(e[0] for evs in devices.values() for e in evs)
    hi = max(e[1] for evs in devices.values() for e in evs)
    window = hi - lo
    per_dev = []
    by_name: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for n, (plane, evs) in enumerate(sorted(devices.items())):
        busy = union((a, b) for a, b, _, _ in evs)
        coll = union((a, b) for a, b, _, k in evs if k == "collective")
        pallas = union((a, b) for a, b, _, k in evs if k == "pallas")
        work = union((a, b) for a, b, _, k in evs if k != "collective")
        per_dev.append({
            "busy": length(busy), "collective": length(coll),
            "pallas": length(pallas),
            "exposed": length(subtract(coll, work))})
        for a, b, name, _ in evs:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        if n == 0:
            gaps = subtract([(lo, hi)], busy)
    k = float(len(per_dev))
    busy_s = sum(d["busy"] for d in per_dev) / k

    def share(key):
        return 100.0 * sum(d[key] for d in per_dev) / k / busy_s

    idle_by_span: Dict[str, float] = {}
    for a, b in gaps:
        name = _covering(spans, a, b)
        idle_by_span[name] = idle_by_span.get(name, 0.0) + (b - a)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    return {
        "devices": len(per_dev),
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": 100.0 * (1.0 - busy_s / window),
        "pallas_share": share("pallas"),
        "collective_share": share("collective"),
        "collective_exposed_share": share("exposed"),
        # seconds per device: summed over the planes, over their number
        "device_ops": [[name, t / k] for name, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        # idle seconds on the first device by the host span covering
        # the middle of each gap
        "idle_gaps": [[name, t] for name, t in sorted(
            idle_by_span.items(), key=lambda kv: -kv[1])[:10]],
        "longest_gaps": [[_covering(spans, a, b), b - a]
                         for a, b in longest],
    }
