#!/usr/bin/env python3
"""How far windows of one decode cell disagree under a traffic mix: one
server, one unbroken stream per mix, cut into consecutive windows.

    python chipbench/tools/window_spread.py --workload gpt2_decode_chat \
        --mixes issue,cell --window 30 --windows 7 [--also 50] [--seed 0]

A window of a stationary stream is what one run of the cell measures,
without the run's set-up, so a few chip-minutes give the spread (the
distance between the quartiles over the median) that a dozen runs
would.  ``cell`` is the cell's own traffic file; ``issue`` is ISSUE
24's wording of it: plain Poisson arrivals, every length a draw of its
own.  The streams follow each other without a drain; each starts with
the cell's lead-in, which is not counted.  Everything read is written to
``chipbench/out/window_spread_<workload>.json`` with the step records
themselves, so another window length or another statistic can be worked
out later without the chip.
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
from chipbench.harness.traffic import make_schedule  # noqa: E402

READ = ("token_gap_ms_p50", "token_gap_ms_p95", "token_gap_ms_mean",
        "first_answer_ms_p50", "first_answer_ms_p95", "tokens_per_s",
        "requests", "asked_tokens", "slots_active_mean", "queue_end")


def window_row(drv, records, offered, lo, hi) -> dict:
    num = drv.window_numbers(records, offered, lo, hi)
    by_i = {r["i"]: r for r in offered.schedule}
    gaps, weights = num["gap_ms"], num["gap_weights"]
    total = sum(weights)
    return {
        "token_gap_ms_p50": stats.weighted_percentile(gaps, weights, 50),
        "token_gap_ms_p95": stats.weighted_percentile(gaps, weights, 95),
        "token_gap_ms_mean": sum(g * w for g, w in zip(gaps, weights))
        / total if total else None,
        "first_answer_ms_p50": stats.percentile(num["ttft_ms"], 50),
        "first_answer_ms_p95": stats.percentile(num["ttft_ms"], 95),
        "tokens_per_s": num["tokens"] / num["window_s"],
        "requests": len(num["due"]),
        "asked_tokens": sum(by_i[i]["max_new_tokens"] for i in num["due"]),
        "slots_active_mean": stats.mean(num["slots_active"]),
        "queue_end": num["queue_end"], "refused": num["refused"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mixes", default="issue,cell")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--windows", type=int, default=7)
    ap.add_argument("--also", type=float, default=0.0,
                    help="cut the same streams into windows of this "
                         "length as well")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    job, drv, device = prepare(args.workload, args.seed, args.window,
                               False, args.rehearse)
    cell = job.size(job.cell)
    lead_in = float(cell["lead_in_s"])
    length = lead_in + args.windows * args.window
    served = drv.Served(job)
    streams = []
    try:
        t0 = time.monotonic() + 1.0
        for k, mix in enumerate(args.mixes.split(",")):
            traffic = job.size(job.traffic)
            if mix == "issue":
                traffic = dict(traffic, arrivals={"process": "poisson"},
                               lengths_block=0)
            schedule = make_schedule(traffic, cell["rate_rps"],
                                     args.seed + k, length,
                                     served.cfg["vocab_size"])
            # every child starts now and sleeps until its stream is due
            streams.append((mix, drv.Offered(job, served, schedule, t0,
                                             tag=f"spread_{mix}")))
            t0 += length
        time.sleep(max(0.0, t0 - time.monotonic()))
        records = list(served.sink.records)
    finally:
        for _, offered in streams:
            offered.stop()
        served.close()

    out = {"workload": args.workload, "device": device,
           "rate_rps": cell["rate_rps"], "lead_in_s": lead_in, "mixes": {}}
    for mix, offered in streams:
        entry = {}
        for w in sorted({args.window, args.also} - {0.0}):
            lo, rows = offered.t0 + lead_in, []
            while lo + w <= offered.t0 + length + 1e-6:
                rows.append(window_row(drv, records, offered, lo, lo + w))
                lo += w
            spread = {k: stats.spread([r[k] for r in rows
                                       if r[k] is not None])
                      for k in READ}
            entry[f"window_{w:g}s"] = {"rows": rows, "spread": spread}
            say("window_spread", mix=mix, window_s=w, windows=len(rows),
                spread=spread,
                token_gap_ms_p95=[r["token_gap_ms_p95"] for r in rows],
                first_answer_ms_p95=[r["first_answer_ms_p95"]
                                     for r in rows],
                tokens_per_s=[r["tokens_per_s"] for r in rows],
                queue_end=[r["queue_end"] for r in rows])
        entry["t0"] = offered.t0
        entry["requests"] = [
            {"i": r["i"], "due": offered.t0 + r["due_s"],
             "prompt_len": len(r["prompt"]),
             "max_new_tokens": r["max_new_tokens"],
             **{k: offered.results.get(r["i"], {}).get(k)
                for k in ("sent", "done", "status", "n_tokens")}}
            for r in offered.schedule]
        out["mixes"][mix] = entry
    out["records"] = [
        [t, d["tokens"], d.get("ttft_ms") or [], d["step_ms"],
         d["slots_active"], d["queue_depth"]] for t, d in records]
    with open(os.path.join(OUT_DIR,
                           f"window_spread_{args.workload}.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
