#!/usr/bin/env python
"""AMP end-to-end bench: wire-bytes and numerics gates vs fp32.

Runs the same dp=2 ZeRO-1 SPMD training loop twice — once fp32, once
under the AMP execution policy (bf16 compute, fp32 master weights) —
and gates on the acceptance criteria of the low-precision PR:

- **wire**: gradient bytes on the reduce-scatter leg under AMP must be
  <= ``--max-wire-ratio`` (default 0.55) of the fp32 run's.  The
  sharded update casts the gradient to the policy storage dtype BEFORE
  the reduce-scatter point, so the ring carries bf16 — the ideal is
  0.5 plus non-shardable stragglers; 0.55 leaves that headroom.
- **numerics**: per-step losses of the AMP run must match fp32 within
  ``--rtol`` (default 1e-2) over the measured window.  bf16 shares
  f32's exponent range, so the compute-dtype casts perturb mantissa
  only — 1e-2 is generous for a few-layer MLP.
- **masters**: parameters must stay float32 under AMP (the compute
  casts are traced into the step, never materialized into storage),
  and per-device optimizer-state residency must be within
  ``--max-mem-ratio`` (default 1.05) of fp32 — AMP must not silently
  inflate the ZeRO memory win.

Prints one JSON summary line:
  {"wire_fp32", "wire_amp", "wire_ratio", "loss_rel_err",
   "mem_ratio", "pass"}
"""
from __future__ import annotations

import argparse
import json
import os
import sys

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


def _build_trainer(units, layers, dp):
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
    # momentum-SGD: a weight-shaped state slot for the ZeRO shard to
    # carve, without adam's adaptive normalization amplifying bf16
    # mantissa noise into trajectory divergence (the numerics gate
    # measures the AMP casts, not optimizer chaos)
    return SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                       optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9},
                       mesh=make_mesh({"dp": dp}),
                       zero_stage=1)


def _run(units, layers, dp, data, label, steps, skip):
    from mxnet_tpu import telemetry
    tr = _build_trainer(units, layers, dp)
    losses = []
    rs0 = None
    ctr = telemetry.counter("comm.reduce_scatter.bytes")
    for i in range(steps):
        if i == skip:
            rs0 = ctr.value
        loss = tr.step(data, label)
        losses.append(float(loss.asnumpy()))
    wire = ctr.value - (rs0 if rs0 is not None else 0)
    pdt = str(next(iter(
        tr.net.collect_params().values())).data().dtype)
    return losses[skip:], wire, tr.opt_state_bytes_per_device(), pdt


def main(argv=None):
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--skip", type=int, default=2)
    ap.add_argument("--units", type=int, default=256)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--max-wire-ratio", type=float, default=0.55)
    ap.add_argument("--max-mem-ratio", type=float, default=1.05)
    ap.add_argument("--rtol", type=float, default=1e-2)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast configuration for CI")
    args = ap.parse_args(argv)
    if args.smoke:
        args.units, args.layers = 128, 2

    rs = onp.random.RandomState(0)
    data = rs.randn(args.batch, args.units).astype("float32")
    label = rs.randint(0, 8, (args.batch,)).astype("float32")

    from mxnet_tpu import amp

    l_fp32, w_fp32, m_fp32, dt_fp32 = _run(
        args.units, args.layers, args.dp, data, label,
        args.steps, args.skip)
    print(json.dumps({"run": "fp32", "wire_bytes": w_fp32,
                      "opt_state_bytes_per_device": m_fp32,
                      "param_dtype": dt_fp32}), flush=True)

    amp.init("bfloat16")
    try:
        l_amp, w_amp, m_amp, dt_amp = _run(
            args.units, args.layers, args.dp, data, label,
            args.steps, args.skip)
    finally:
        amp.reset()
    print(json.dumps({"run": "amp", "wire_bytes": w_amp,
                      "opt_state_bytes_per_device": m_amp,
                      "param_dtype": dt_amp}), flush=True)

    wire_ratio = w_amp / w_fp32 if w_fp32 else 1.0
    mem_ratio = m_amp / m_fp32 if m_fp32 else 1.0
    rel = max(abs(a - b) / max(abs(b), 1e-6)
              for a, b in zip(l_amp, l_fp32))
    ok = (wire_ratio <= args.max_wire_ratio
          and mem_ratio <= args.max_mem_ratio
          and rel <= args.rtol
          and dt_amp == "float32")
    print(json.dumps({
        "wire_fp32": w_fp32, "wire_amp": w_amp,
        "wire_ratio": round(wire_ratio, 4),
        "loss_rel_err": round(rel, 6),
        "mem_ratio": round(mem_ratio, 4),
        "masters_fp32": dt_amp == "float32",
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
