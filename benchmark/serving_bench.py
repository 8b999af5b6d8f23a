#!/usr/bin/env python
"""Serving throughput microbench: dynamic batching vs batch-size-1.

Drives the queue → DynamicBatcher → bucketed InferenceEngine path
(mxnet_tpu/serving/) over a small MLP with two load generators:

- **closed loop**: T client threads, each submitting R synchronous
  ``predict()`` calls back-to-back — batch occupancy converges to T,
  so throughput measures dispatches amortized over coalesced requests;
- **open loop**: Poisson arrivals at a fixed rate from one submitter
  thread (futures resolved at the end) — measures latency under a
  target offered load instead of at saturation.

The baseline is the same stack pinned to ``max_batch_size=1`` (one
XLA dispatch per request).  Dispatch count is backend-independent, so
CPU is fine; the acceptance gate is ``--min-speedup`` (default 3.0)
on the best closed-loop configuration vs that baseline.

Prints one JSON line per configuration:
  {"mode", "max_delay_ms", "threads", "requests", "throughput_rps",
   "mean_occupancy", "p50_ms", "p95_ms", "dispatches", "compiles"}
and a final {"speedup", "min_speedup", "pass"} summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build(units, layers):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    onp.random.seed(0)
    net = nn.Sequential()
    for _ in range(layers):
        net.add(nn.Dense(units, in_units=units, activation="relu"))
    net.add(nn.Dense(units, in_units=units))
    net.initialize()
    return net


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   round(q / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[k]


def _make_server(net, units, max_batch, max_delay_ms):
    from mxnet_tpu import serving
    srv = serving.ServingServer(
        net,
        engine_args={"example_shape": (units,), "dtype": "float32"},
        batcher_args={"max_batch_size": max_batch,
                      "max_delay_ms": max_delay_ms,
                      "queue_depth": 4096})
    # warm every power-of-two bucket the run can hit, so the measured
    # window is steady state (0 new compiles)
    b = 1
    sizes = []
    while b <= max_batch:
        sizes.append(b)
        b *= 2
    srv.warmup(sizes)
    return srv


def _snapshot():
    from mxnet_tpu import telemetry
    return {
        "dispatches": telemetry.counter("dispatch.count").value,
        "compiles": telemetry.counter("compile.count").value,
        "requests": telemetry.counter("serving.requests").value,
        "batches": telemetry.counter("serving.batches").value,
    }


def _delta(before):
    after = _snapshot()
    return {k: after[k] - before[k] for k in before}


def run_closed(net, units, max_batch, max_delay_ms, threads, requests):
    srv = _make_server(net, units, max_batch, max_delay_ms)
    x = onp.random.RandomState(2).randn(units).astype("float32")
    latencies = [[] for _ in range(threads)]
    errors = []

    def client(i):
        try:
            for _ in range(requests):
                t0 = time.perf_counter()
                srv.predict(x)
                latencies[i].append((time.perf_counter() - t0) * 1e3)
        except Exception as e:    # surface, don't hang the join
            errors.append(repr(e))

    # one untimed round so every client thread is alive and the first
    # straggler window isn't billed to the measurement
    srv.predict(x)
    before = _snapshot()
    workers = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(threads)]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    d = _delta(before)
    srv.stop(drain=True)
    if errors:
        raise SystemExit(f"closed-loop client failed: {errors[0]}")
    lat = sorted(ms for per in latencies for ms in per)
    total = threads * requests
    return {
        "mode": "closed",
        "max_delay_ms": max_delay_ms,
        "threads": threads,
        "requests": total,
        "throughput_rps": round(total / wall, 1),
        "mean_occupancy": round(d["requests"] / d["batches"], 2)
        if d["batches"] else 0.0,
        "p50_ms": round(_percentile(lat, 50), 3),
        "p95_ms": round(_percentile(lat, 95), 3),
        "dispatches": d["dispatches"],
        "compiles": d["compiles"],
    }


def run_open(net, units, max_batch, max_delay_ms, rate_rps, requests):
    srv = _make_server(net, units, max_batch, max_delay_ms)
    x = onp.random.RandomState(3).randn(units).astype("float32")
    gaps = onp.random.RandomState(4).exponential(1.0 / rate_rps,
                                                 size=requests)
    srv.predict(x)
    before = _snapshot()
    done_ms = []
    done_lock = threading.Lock()

    def waiter(ts, fut):
        # stamp completion when the future resolves, not when the
        # submission loop happens to get around to it
        fut.result(60.0)
        ms = (time.perf_counter() - ts) * 1e3
        with done_lock:
            done_ms.append(ms)

    waiters = []
    t0 = time.perf_counter()
    t_next = t0
    for gap in gaps:
        t_next += gap
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        ts = time.perf_counter()
        w = threading.Thread(target=waiter,
                             args=(ts, srv.batcher.submit(x)), daemon=True)
        w.start()
        waiters.append(w)
    for w in waiters:
        w.join(60.0)
    lat = sorted(done_ms)
    wall = time.perf_counter() - t0
    d = _delta(before)
    srv.stop(drain=True)
    return {
        "mode": "open",
        "max_delay_ms": max_delay_ms,
        "offered_rps": rate_rps,
        "requests": requests,
        "throughput_rps": round(requests / wall, 1),
        "mean_occupancy": round(d["requests"] / d["batches"], 2)
        if d["batches"] else 0.0,
        "p50_ms": round(_percentile(lat, 50), 3),
        "p95_ms": round(_percentile(lat, 95), 3),
        "dispatches": d["dispatches"],
        "compiles": d["compiles"],
    }


def main():
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--threads", type=int, default=16)
    ap.add_argument("--requests", type=int, default=100,
                    help="closed-loop requests per thread")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--delays", type=float, nargs="*",
                    default=[0.0, 1.0, 2.0, 5.0],
                    help="max_delay_ms sweep for the dynamic batcher")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--open-requests", type=int, default=300)
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="gate: best dynamic closed-loop throughput must "
                         "beat the batch-1 baseline by this factor")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (fewer requests, "
                         "two delay points, no open loop)")
    args = ap.parse_args()
    if args.smoke:
        # keep the thread count — occupancy (and thus the measured
        # speedup) scales with it; just shorten the run
        args.requests = min(args.requests, 30)
        args.delays = [d for d in args.delays if d > 0][:1] or [2.0]
        args.open_requests = min(args.open_requests, 150)

    net = _build(args.units, args.layers)

    baseline = run_closed(net, args.units, max_batch=1, max_delay_ms=0.0,
                          threads=args.threads, requests=args.requests)
    baseline["mode"] = "closed-batch1-baseline"
    print(json.dumps(baseline))
    sys.stdout.flush()

    best = 0.0
    for delay in args.delays:
        r = run_closed(net, args.units, args.max_batch, delay,
                       args.threads, args.requests)
        best = max(best, r["throughput_rps"])
        print(json.dumps(r))
        sys.stdout.flush()

    if args.open_requests:
        for delay in args.delays:
            r = run_open(net, args.units, args.max_batch, delay,
                         args.rate, args.open_requests)
            print(json.dumps(r))
            sys.stdout.flush()

    speedup = best / baseline["throughput_rps"] \
        if baseline["throughput_rps"] else 0.0
    verdict = {"speedup": round(speedup, 2),
               "min_speedup": args.min_speedup,
               "pass": bool(speedup >= args.min_speedup)}
    print(json.dumps(verdict))
    if not verdict["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
