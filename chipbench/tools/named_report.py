#!/usr/bin/env python3
"""A trace by the program's own names, by hand: the ``named`` table and
every per-layer metric that the ``trace_named`` reader computes.

    python chipbench/tools/named_report.py <file.xplane.pb | trace dir> \
        [--workload <cell>] [--span-prefix mxtpu.]

``--workload`` keeps the metrics whose file lists that cell under
``workloads``; without it every ``trace_named`` metric is tried, and
what the trace holds nothing for is left out.  After a ``--trace 1`` run
of a cell its trace is ``chipbench/out/trace_<cell>``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness import named  # noqa: E402
from chipbench.harness.cli import BENCH_DIR, layer_metrics, say  # noqa: E402
from chipbench.harness.trace import xplane_in  # noqa: E402


def metric_names(workload=None):
    """The metric files read by ``trace_named``, for one cell or all."""
    out = []
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    for f in sorted(os.listdir(folder)):
        with open(os.path.join(folder, f)) as fh:
            spec = json.load(fh)
        if spec["reader"] == "trace_named" and (
                workload is None or workload in spec.get("workloads", ())):
            out.append(spec["name"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--workload")
    ap.add_argument("--span-prefix", default=named.SPAN_PREFIX)
    args = ap.parse_args(argv)
    path = xplane_in(args.trace) if os.path.isdir(args.trace) \
        else args.trace
    if not path or not os.path.isfile(path):
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 2
    parsed = named.load(path, args.span_prefix)
    say("named", trace=path, **named.table(parsed))
    say("named_metrics", workload=args.workload, metrics=layer_metrics(
        {"_named": parsed}, metric_names(args.workload)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
