#!/usr/bin/env python3
"""Price the benchmark's telemetry sink: one run of a decode cell with
the in-memory sink and one without, client-side request times compared.

    python chipbench/tools/price_sink.py --workload gpt2_decode_chat \
        --seconds 30 --seed 0 --sink 0|1

One run per process (the second would find the chip taken); call it
twice.  Without the sink there are no step records, so only the
client's side of each request is reported.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness import stats  # noqa: E402
from chipbench.harness.cli import prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sink", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    job, drv, device = prepare(args.workload, args.seed, args.seconds,
                               False, args.rehearse)
    job.cell["telemetry_sink"] = bool(args.sink)
    obs = drv.run(job)
    req = obs["series"]["request_ms"]
    print(json.dumps({
        "workload": args.workload, "sink": bool(args.sink), "device": device,
        "requests": len(req), "failed": obs["failed"],
        "request_ms_p25": stats.percentile(req, 25),
        "request_ms_p50": stats.percentile(req, 50),
        "request_ms_p75": stats.percentile(req, 75),
        "request_ms_p95": stats.percentile(req, 95)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
