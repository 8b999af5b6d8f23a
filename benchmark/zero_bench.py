#!/usr/bin/env python
"""ZeRO-1 sharded optimizer update bench: memory and step-time gates.

Runs the same SPMD training loop twice on a dp=2 mesh — once
replicated (``zero_stage=0``), once with the sharded optimizer update
(``zero_stage=1``) — and gates on the two acceptance criteria of the
sharded-update PR:

- **memory**: per-device optimizer-state residency under ZeRO must be
  <= ``--max-mem-ratio`` (default 0.6) of the replicated trainer's.
  ZeRO-1 shards every dp-divisible state tensor 1/dp per device, so at
  dp=2 the ideal is ~0.5 plus padding and any non-shardable state
  (BatchNorm-style stats); 0.6 leaves that headroom.
- **time**: median steady-state step time under ZeRO must be
  <= ``--max-time-ratio`` (default 1.15) of replicated.  The sharded
  update replaces one allreduce with reduce-scatter + all-gather at
  identical ring wire volume and computes the update on 1/dp of the
  elements, so on real interconnects it is neutral-to-faster; on the
  CPU backend the collectives are memcpy shuffles and the gate only
  bounds regression.

Both runs reuse one compiled step (dispatch stays 1/step); the first
``--skip`` steps (compile + warmup) are excluded.  Prints one JSON
summary line:
  {"mem_replicated", "mem_zero", "mem_ratio", "step_ms_replicated",
   "step_ms_zero", "time_ratio", "pass"}
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

# the dp=2 mesh needs multiple devices; on the single-device CPU
# backend expose virtual ones (must happen before jax initializes)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


def _build_trainer(units, layers, zero_stage, dp):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(units, activation="relu"))
    net.add(nn.Dense(8))
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((2, units), "float32")))
    return SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                       optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3},
                       mesh=make_mesh({"dp": dp}),
                       zero_stage=zero_stage)


def _run(tr, data, label, steps, skip):
    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = tr.step(data, label)
        loss.asnumpy()                  # sync: time the whole step
        if i >= skip:
            times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]       # median


def main(argv=None):
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--skip", type=int, default=5)
    ap.add_argument("--units", type=int, default=256)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--max-mem-ratio", type=float, default=0.6)
    # CPU CI: collectives are thread-pool memcpys, so allow scheduler
    # noise on top of the 1.15x acceptance ratio
    ap.add_argument("--time-eps", type=float, default=0.10)
    ap.add_argument("--max-time-ratio", type=float, default=1.15)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast configuration for CI")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps, args.units, args.layers = 15, 128, 2

    rs = onp.random.RandomState(0)
    data = rs.randn(args.batch, args.units).astype("float32")
    label = rs.randint(0, 8, (args.batch,)).astype("float32")

    results = {}
    for name, stage in (("replicated", 0), ("zero", 1)):
        tr = _build_trainer(args.units, args.layers, stage, args.dp)
        med = _run(tr, data, label, args.steps, args.skip)
        results[name] = (med, tr.opt_state_bytes_per_device())
        print(json.dumps({"run": name, "zero_stage": stage,
                          "step_ms": round(med, 3),
                          "opt_state_bytes_per_device": results[name][1]}),
              flush=True)

    t0, m0 = results["replicated"]
    t1, m1 = results["zero"]
    mem_ratio = m1 / m0 if m0 else 1.0
    time_ratio = t1 / t0 if t0 else 1.0
    ok = (mem_ratio <= args.max_mem_ratio
          and time_ratio <= args.max_time_ratio + args.time_eps)
    print(json.dumps({
        "mem_replicated": m0, "mem_zero": m1,
        "mem_ratio": round(mem_ratio, 4),
        "step_ms_replicated": round(t0, 3),
        "step_ms_zero": round(t1, 3),
        "time_ratio": round(time_ratio, 4),
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
