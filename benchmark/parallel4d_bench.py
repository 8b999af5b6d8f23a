#!/usr/bin/env python
"""Composed-mesh (dp×tp) training bench: memory, step-time and
dispatch gates for the 4-D parallelism plan.

Trains the same transformer LM twice on 4 devices with AMP bf16 on:

- **baseline**: ``MeshPlan(dp=4)`` — pure data parallelism, replicated
  params and optimizer state (``zero_stage=0``); the configuration a
  dp-only fleet would run.
- **composed**: ``MeshPlan(dp=2, tp=2)`` — the SAME device count, with
  attention/FFN weights tensor-sharded over ``tp`` and the ZeRO-1
  optimizer shard composed onto the free axis (``zero_stage=1``), so
  optimizer state lands at ~1/(dp·tp) per device.

Gates (the acceptance criteria of the composable-4D PR):

- **memory**: per-device param + optimizer-state bytes under the
  composed plan must be <= ``--max-mem-ratio`` (default 0.55) of the
  dp-only baseline.  tp halves the sharded weights, ZeRO-over-(dp·tp)
  quarters their optimizer state; 0.55 leaves headroom for the
  replicated remainder (embeddings, norms, biases).
- **time**: median steady-state per-step time (run_steps windows,
  window cost / n_steps) must be <= ``--max-time-ratio`` (default
  1.15) of baseline.  On real ICI the tp collectives overlap; on the
  CPU backend they are memcpy shuffles and the gate bounds regression.
- **dispatch**: every ``run_steps`` window must execute as ONE device
  program — each telemetry record's ``dispatches`` delta is exactly 1
  — and the composed run's record must attribute collective bytes to
  BOTH mesh axes (``collective_split.by_axis`` dp and tp > 0).

Prints one JSON summary line:
  {"mem_baseline", "mem_composed", "mem_ratio", "step_ms_baseline",
   "step_ms_composed", "time_ratio", "dispatch_per_window", "pass"}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the 4-device mesh needs multiple devices; on the single-device CPU
# backend expose virtual ones (must happen before jax initializes)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


def _build_trainer(plan, zero_stage, shard_tp, vocab, units, layers,
                   max_len):
    import mxnet_tpu as mx
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer
    mx.random.seed(0)
    net = get_transformer_lm(vocab, units=units, num_layers=layers,
                             num_heads=4, max_len=max_len)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 8), onp.int32)))
    if shard_tp:
        # Megatron layout: column-parallel into the block, row-parallel
        # out — XLA inserts the partial-sum all-reduce on tp
        for k, p in net.collect_params().items():
            if k.endswith("weight") and p.shape is not None \
                    and len(p.shape) == 2:
                if "ffn1" in k or "qkv" in k:
                    p.shard(P("tp", None))
                elif "ffn2" in k or "out_proj" in k:
                    p.shard(P(None, "tp"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    return SPMDTrainer(
        net, lambda o, l: ce(o.reshape((-1, vocab)), l.reshape((-1,))),
        optimizer="adam", optimizer_params={"learning_rate": 1e-3},
        mesh=plan, zero_stage=zero_stage, dtype="bfloat16")


def _param_bytes_per_device(tr) -> int:
    """Actual parameter bytes resident on the busiest mesh device,
    summed over each param's addressable shards (replicated leaves
    count full size per device, tp-sharded ones 1/tp)."""
    per_dev: dict = {}
    for k in tr._pkeys:
        arr = tr._params[k].data()._data
        for sh in arr.addressable_shards:
            key = repr(sh.device)
            per_dev[key] = per_dev.get(key, 0) + sh.data.nbytes
    return max(per_dev.values()) if per_dev else 0


def _window(tr, data, label, wsteps, records):
    """One timed run_steps window: per-step ms; appends the window's
    telemetry record to ``records``."""
    from mxnet_tpu import telemetry
    t0 = time.perf_counter()
    losses = tr.run_steps(data, label, n_steps=wsteps)
    losses.asnumpy()                # sync: time the whole window
    records.append(telemetry.last_record())
    return (time.perf_counter() - t0) * 1e3 / wsteps


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def main(argv=None):
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--windows", type=int, default=12)
    ap.add_argument("--window-steps", type=int, default=4)
    ap.add_argument("--skip", type=int, default=3)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--max-mem-ratio", type=float, default=0.55)
    ap.add_argument("--max-time-ratio", type=float, default=1.15)
    # CPU CI: tp collectives are thread-pool memcpys, so allow
    # scheduler noise on top of the 1.15x acceptance ratio
    ap.add_argument("--time-eps", type=float, default=0.15)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast configuration for CI")
    args = ap.parse_args(argv)
    if args.smoke:
        args.windows, args.units, args.layers = 8, 32, 2

    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import MeshPlan

    # the step-record stream (dispatches / collective_split.by_axis)
    # only runs when a sink is attached; the gates read last_record()
    class _NullSink:
        def emit(self, record):
            pass
    telemetry.add_sink(_NullSink())

    rs = onp.random.RandomState(0)
    toks = rs.randint(0, args.vocab,
                      (args.batch, args.seq + 1)).astype("int32")
    data, label = toks[:, :-1], toks[:, 1:].astype("float32")

    # build both, warm both (compile + skip windows), then time in
    # ALTERNATING windows — paired sampling cancels the load drift a
    # shared-core CI box injects into back-to-back runs
    trainers, results = {}, {}
    for name, plan, stage, tp in (
            ("baseline", MeshPlan(dp=4), 0, False),
            ("composed", MeshPlan(dp=2, tp=2), 1, True)):
        tr = _build_trainer(plan, stage, tp, args.vocab, args.units,
                            args.layers, 2 * args.seq)
        trainers[name] = (tr, plan, stage)
        for _ in range(args.skip):
            _window(tr, data, label, args.window_steps, [])
    times = {"baseline": [], "composed": []}
    recs: dict = {"baseline": [], "composed": []}
    for _ in range(max(1, args.windows - args.skip)):
        for name in ("baseline", "composed"):
            times[name].append(_window(trainers[name][0], data, label,
                                       args.window_steps, recs[name]))
    for name in ("baseline", "composed"):
        tr, plan, stage = trainers[name]
        med = _median(times[name])
        mem = (_param_bytes_per_device(tr)
               + tr.opt_state_bytes_per_device())
        results[name] = (med, mem, recs[name])
        print(json.dumps({
            "run": name, "mesh": plan.describe(), "zero_stage": stage,
            "step_ms": round(med, 3), "param_opt_bytes_per_device": mem,
        }), flush=True)

    t0, m0, recs0 = results["baseline"]
    t1, m1, recs1 = results["composed"]
    mem_ratio = m1 / m0 if m0 else 1.0
    time_ratio = t1 / t0 if t0 else 1.0
    # one device program per window, on every timed window of both runs
    dispatches = sorted({int(r.get("dispatches", -1))
                         for r in recs0 + recs1 if r})
    one_dispatch = dispatches == [1]
    by_axis = (recs1[-1] or {}).get("collective_split", {}) \
        .get("by_axis", {})
    axes_attributed = (by_axis.get("dp", 0) > 0
                       and by_axis.get("tp", 0) > 0)
    ok = (mem_ratio <= args.max_mem_ratio
          and time_ratio <= args.max_time_ratio + args.time_eps
          and one_dispatch and axes_attributed)
    print(json.dumps({
        "mem_baseline": m0, "mem_composed": m1,
        "mem_ratio": round(mem_ratio, 4),
        "step_ms_baseline": round(t0, 3),
        "step_ms_composed": round(t1, 3),
        "time_ratio": round(time_ratio, 4),
        "dispatch_per_window": dispatches,
        "by_axis_bytes": {k: v for k, v in by_axis.items() if v},
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
