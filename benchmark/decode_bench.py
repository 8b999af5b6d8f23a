#!/usr/bin/env python
"""Decode serving microbench: continuous batching vs sequential batch-1.

Drives the paged-KV decode plane (mxnet_tpu/serving/decode/) over a
small autoregressive transformer with two load generators:

- **sequential baseline**: one request in flight at a time — submit,
  wait for the full completion, repeat.  Occupancy is 1, so every
  ``decode_step`` dispatch yields one token;
- **open loop**: Poisson arrivals at a multiple of the baseline's
  sustained request rate (default 10x) from one submitter thread,
  futures resolved at the end.  The continuous batcher packs the
  fixed ``max_slots`` grid, so one dispatch yields up to
  ``max_slots`` tokens.

Both phases run against a warmed engine; the fixed-shape contract
means admission and eviction never recompile, which the open-loop
phase asserts (``compiles == 0`` in the measured window).  A third
phase checks that greedy speculative decode (same-weights draft) is
token-identical to the non-speculative path.

Prints one JSON line per phase:
  {"mode", "requests", "tokens", "tokens_per_s", "wall_s",
   "p50_ms", "p95_ms", "compiles", ...}
and a final {"speedup", "min_speedup", "open_compiles",
"spec_identical", "pass"} summary line.
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


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   round(q / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[k]


def _build(vocab, dim, heads, layers, seed=0):
    from mxnet_tpu.serving.decode import DecodeModel
    return DecodeModel(vocab, dim=dim, n_heads=heads, n_layers=layers,
                       seed=seed)


def _make(model, *, slots, pages, page_size, draft=None, spec_k=0,
          queue_depth=4096):
    from mxnet_tpu.serving.decode import DecodeEngine, DecodeScheduler
    eng = DecodeEngine(model, draft_model=draft, spec_k=spec_k,
                      max_slots=slots, num_pages=pages,
                      page_size=page_size)
    sch = DecodeScheduler(eng, queue_depth=queue_depth, start=True)
    return eng, sch


def _prompts(n, vocab, lo, hi, seed):
    rs = onp.random.RandomState(seed)
    return [[int(t) for t in rs.randint(0, vocab, size=rs.randint(lo, hi + 1))]
            for _ in range(n)]


def run_sequential(eng, sch, prompts, max_new):
    # warm the prefill bucket + decode executable outside the window
    sch.submit(prompts[0], max_new_tokens=max_new).result(120.0)
    c0 = eng.compiles
    lat = []
    tokens = 0
    t0 = time.perf_counter()
    for p in prompts:
        ts = time.perf_counter()
        out = sch.submit(p, max_new_tokens=max_new).result(120.0)
        lat.append((time.perf_counter() - ts) * 1e3)
        tokens += len(out)
    wall = time.perf_counter() - t0
    lat.sort()
    return {
        "mode": "sequential-batch1-baseline",
        "requests": len(prompts),
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall, 1),
        "wall_s": round(wall, 3),
        "p50_ms": round(_percentile(lat, 50), 3),
        "p95_ms": round(_percentile(lat, 95), 3),
        "compiles": eng.compiles - c0,
    }


def run_open(eng, sch, prompts, max_new, rate_rps):
    sch.submit(prompts[0], max_new_tokens=max_new).result(120.0)
    c0 = eng.compiles
    gaps = onp.random.RandomState(11).exponential(
        1.0 / rate_rps, size=len(prompts))
    done_ms = []
    done_tokens = []
    done_lock = threading.Lock()

    def waiter(ts, fut):
        out = fut.result(300.0)
        ms = (time.perf_counter() - ts) * 1e3
        with done_lock:
            done_ms.append(ms)
            done_tokens.append(len(out))

    waiters = []
    t0 = time.perf_counter()
    t_next = t0
    for p, gap in zip(prompts, gaps):
        t_next += gap
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        ts = time.perf_counter()
        w = threading.Thread(
            target=waiter,
            args=(ts, sch.submit(p, max_new_tokens=max_new)), daemon=True)
        w.start()
        waiters.append(w)
    for w in waiters:
        w.join(300.0)
    wall = time.perf_counter() - t0
    lat = sorted(done_ms)
    return {
        "mode": "open",
        "offered_rps": round(rate_rps, 2),
        "requests": len(prompts),
        "tokens": sum(done_tokens),
        "tokens_per_s": round(sum(done_tokens) / wall, 1),
        "wall_s": round(wall, 3),
        "p50_ms": round(_percentile(lat, 50), 3),
        "p95_ms": round(_percentile(lat, 95), 3),
        "compiles": eng.compiles - c0,
    }


def run_spec_identity(model, prompts, max_new, *, slots, pages, page_size,
                      spec_k):
    # same-weights draft: every proposal is accepted, and greedy output
    # must match the non-speculative path token for token
    eng_ns, sch_ns = _make(model, slots=slots, pages=pages,
                           page_size=page_size)
    base = [sch_ns.submit(p, max_new_tokens=max_new).result(120.0)
            for p in prompts]
    sch_ns.close(drain=True)

    eng_sp, sch_sp = _make(model, slots=slots, pages=pages,
                           page_size=page_size, draft=model, spec_k=spec_k)
    spec = [sch_sp.submit(p, max_new_tokens=max_new).result(120.0)
            for p in prompts]
    st = sch_sp.stats()
    sch_sp.close(drain=True)
    identical = all(a == b for a, b in zip(base, spec))
    return {
        "mode": "spec-identity",
        "requests": len(prompts),
        "spec_k": spec_k,
        "spec_proposed": st.get("spec_proposed", 0),
        "spec_accepted": st.get("spec_accepted", 0),
        "identical": bool(identical),
    }


def main():
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-lo", type=int, default=9)
    ap.add_argument("--prompt-hi", type=int, default=16,
                    help="keep all prompts in one pow2 prefill bucket so "
                         "the warmup request covers every executable")
    ap.add_argument("--load-factor", type=float, default=10.0,
                    help="open-loop offered rate as a multiple of the "
                         "sequential baseline's sustained request rate")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="gate: open-loop tokens/s must beat the "
                         "sequential baseline by this factor")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (smaller model, fewer "
                         "requests)")
    args = ap.parse_args()
    if args.smoke:
        args.vocab = min(args.vocab, 64)
        args.dim = min(args.dim, 32)
        args.requests = min(args.requests, 16)
        args.max_new = min(args.max_new, 12)

    model = _build(args.vocab, args.dim, args.heads, args.layers)
    prompts = _prompts(args.requests, args.vocab,
                       args.prompt_lo, args.prompt_hi, seed=5)

    eng, sch = _make(model, slots=args.slots, pages=args.pages,
                     page_size=args.page_size)
    baseline = run_sequential(eng, sch, prompts, args.max_new)
    print(json.dumps(baseline))
    sys.stdout.flush()

    base_rps = baseline["requests"] / baseline["wall_s"]
    opened = run_open(eng, sch, prompts, args.max_new,
                      rate_rps=args.load_factor * base_rps)
    print(json.dumps(opened))
    sys.stdout.flush()
    sch.close(drain=True)

    spec = run_spec_identity(
        model, prompts[:max(4, args.requests // 4)], args.max_new,
        slots=args.slots, pages=args.pages, page_size=args.page_size,
        spec_k=args.spec_k)
    print(json.dumps(spec))
    sys.stdout.flush()

    speedup = opened["tokens_per_s"] / baseline["tokens_per_s"] \
        if baseline["tokens_per_s"] else 0.0
    verdict = {
        "speedup": round(speedup, 2),
        "min_speedup": args.min_speedup,
        "open_compiles": opened["compiles"],
        "spec_identical": spec["identical"],
        "pass": bool(speedup >= args.min_speedup
                     and opened["compiles"] == 0
                     and spec["identical"]),
    }
    print(json.dumps(verdict))
    if not verdict["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
