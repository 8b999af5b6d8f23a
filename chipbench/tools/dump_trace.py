#!/usr/bin/env python3
"""Look at a trace by hand: planes, lines, the names that take most
time on each line and every stat of one event of each.

    python chipbench/tools/dump_trace.py <file.xplane.pb> [top]
"""
import json
import sys


def dump(path, top=25):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            names, first, n = {}, {}, 0
            lo, hi = float("inf"), float("-inf")
            for ev in line.events:
                n += 1
                lo = min(lo, ev.start_ns)
                hi = max(hi, ev.start_ns + ev.duration_ns)
                tot = names.setdefault(ev.name, [0, 0.0])
                tot[0] += 1
                tot[1] += ev.duration_ns
                if ev.name not in first:
                    try:
                        first[ev.name] = {k: str(v)[:160]
                                          for k, v in ev.stats}
                    except Exception as e:   # unreadable stat
                        first[ev.name] = {"error": str(e)}
            ranked = sorted(names.items(), key=lambda kv: -kv[1][1])[:top]
            p["lines"].append({
                "line": line.name, "events": n,
                "start_ms": lo / 1e6 if n else None,
                "end_ms": hi / 1e6 if n else None,
                "top": [{"name": k[:120], "count": c, "total_ms": t / 1e6,
                         "stats": first[k] if i < 10 else None}
                        for i, (k, (c, t)) in enumerate(ranked)]})
        out.append(p)
    return out


if __name__ == "__main__":
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    json.dump(dump(sys.argv[1], top), sys.stdout, indent=1)
    print()
