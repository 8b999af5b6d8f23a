#!/usr/bin/env python3
"""Find the highest request rate a decode cell sustains, once, on the chip.

    python chipbench/tools/find_knee.py --workload gpt2_decode_chat \
        --start 3.0 --steps 10 [--seconds 30] [--seed 0]

One server for the whole sweep.  Rates rise in steps of 10% from
``--start``; each is offered for the cell's lead-in plus ``--seconds``,
then the server drains before the next.  A rate is sustained when no
request of its window was refused or failed and the queue depth at the
end of the window is no larger than at its middle.  The sweep stops
after the second rate that is not sustained.  The knee is the highest
sustained rate; the cell's ``rate_rps`` is 0.8 of it, written into the
cell's file by hand with the table this prints (and ``PERF.md`` keeps).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness import stats  # noqa: E402
from chipbench.harness.cli import OUT_DIR, prepare, say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    job, drv, device = prepare(args.workload, args.seed, args.seconds,
                               False, args.rehearse)
    cell = job.cell
    lead_in = float(job.size(cell)["lead_in_s"])
    served = drv.Served(job)
    table, misses = [], 0
    try:
        for k in range(args.steps):
            rate = args.start * 1.1 ** k
            n0 = len(served.sink.records)
            offered = drv.offer(job, served, rate, args.seed + k,
                                lead_in + args.seconds, tag=f"knee{k}")
            try:
                w_lo = offered.t0 + lead_in
                w_hi = w_lo + args.seconds
                time.sleep(max(0.0, w_hi - time.monotonic()))
                records = list(served.sink.records[n0:])
                offered.wait_for(
                    [r["i"] for r in offered.due_in(w_lo, w_hi)],
                    timeout=240.0)
                num = drv.window_numbers(records, offered, w_lo, w_hi)
            finally:
                offered.stop()
            # drain: the server finishes what it admitted, client or no
            deadline = time.monotonic() + 240.0
            while (served.scheduler.active() or served.scheduler.pending()) \
                    and time.monotonic() < deadline:
                time.sleep(0.2)
            sustained = (num["failed"] == 0
                         and num["queue_end"] <= max(num["queue_mid"], 0))
            row = {
                "rate_rps": rate, "requests": len(num["due"]),
                "refused": num["refused"], "failed": num["failed"],
                "queue_mid": num["queue_mid"], "queue_end": num["queue_end"],
                "first_answer_ms_p50": stats.percentile(num["ttft_ms"], 50),
                "first_answer_ms_p95": stats.percentile(num["ttft_ms"], 95),
                "token_gap_ms_p50": stats.weighted_percentile(
                    num["gap_ms"], num["gap_weights"], 50),
                "token_gap_ms_p95": stats.weighted_percentile(
                    num["gap_ms"], num["gap_weights"], 95),
                "request_ms_p95": stats.percentile(num["request_ms"], 95),
                "gen_late_ms_p95": stats.percentile(num["gen_late_ms"], 95),
                "slots_active_mean": stats.mean(num["slots_active"]),
                "decode_step_ms_p50": stats.percentile(
                    num["decode_step_ms"], 50),
                "tokens_per_s": num["tokens"] / num["window_s"],
                "sustained": sustained}
            table.append(row)
            say("knee_row", **row)
            misses += 0 if sustained else 1
            if misses >= 2:
                break
    finally:
        served.close()
    ok = [r["rate_rps"] for r in table if r["sustained"]]
    knee = max(ok) if ok else None
    result = {"workload": args.workload, "device": device, "knee_rps": knee,
              "rate_rps_at_0.8": None if knee is None else 0.8 * knee,
              "table": table}
    with open(os.path.join(OUT_DIR, f"knee_{args.workload}.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
