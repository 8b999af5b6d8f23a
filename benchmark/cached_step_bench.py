#!/usr/bin/env python
"""Whole-step graph-capture microbench: XLA dispatches per training
step and host step time through record->backward->step, cached vs eager.

The cached step (mxnet_tpu/imperative/cached_step.py) replays the
autograd tape, the vjp chain, and the fused optimizer update as ONE
donated XLA executable: an N-op forward goes from ~2N+1 dispatches per
step (N forward + N backward + 1 fused update) to exactly 1.  This
bench measures that claim on an 8- and a 32-layer MLP (CPU is fine —
dispatch count is backend-independent) and checks the two paths agree
on the final weights and optimizer state to 1e-6.

Prints one JSON line per configuration:
  {"n_layers", "n_params", "dispatches_per_step_cached",
   "dispatches_per_step_eager", "step_ms_cached", "step_ms_eager",
   "max_abs_err", "match"}
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


def _build(n_layers, units, optimizer, opt_args):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Trainer, nn
    mx.random.seed(0)
    onp.random.seed(0)
    net = nn.Sequential()
    for _ in range(n_layers):
        net.add(nn.Dense(units, in_units=units, activation="relu"))
    net.add(nn.Dense(1, in_units=units))
    net.initialize()
    trainer = Trainer(net.collect_params(), optimizer, dict(opt_args),
                      kvstore=None)
    x = nd.array(onp.random.RandomState(1).randn(8, units)
                 .astype("float32"))
    return net, trainer, x


def _run(n_layers, units, optimizer, opt_args, steps, cached):
    from mxnet_tpu import autograd, telemetry
    os.environ["MXNET_CACHED_STEP"] = "1" if cached else "0"
    net, trainer, x = _build(n_layers, units, optimizer, opt_args)
    disp = telemetry.counter("dispatch.count")

    def one_step():
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        trainer.step(batch_size=8)

    # warm twice: step 0 observes eagerly, step 1 captures + compiles;
    # after that the cache is steady
    one_step()
    one_step()
    d0 = disp.value
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    for p in net.collect_params().values():
        p._data_nd()._data.block_until_ready()
    dt = (time.perf_counter() - t0) / steps
    dispatches = (disp.value - d0) / steps
    weights = [p._data_nd().asnumpy() for p in net.collect_params().values()]
    states = trainer._updaters[0].states
    states = {k: tuple(s.asnumpy() for s in v) for k, v in states.items()}
    return dispatches, dt * 1e3, weights, states


def main():
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--layers", type=int, nargs="*", default=[8, 32])
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--tol", type=float, default=1e-6)
    args = ap.parse_args()
    opt_args = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}

    ok = True
    for n_layers in args.layers:
        dc, tc, wc, sc = _run(n_layers, args.units, args.optimizer,
                              opt_args, args.steps, cached=True)
        de, te, we, se = _run(n_layers, args.units, args.optimizer,
                              opt_args, args.steps, cached=False)
        err = max(
            [float(onp.abs(a - b).max()) for a, b in zip(wc, we)]
            + [float(onp.abs(a - b).max()) for k in sc
               for a, b in zip(sc[k], se[k])])
        match = sc.keys() == se.keys() and err <= args.tol
        ok = ok and match and dc == 1.0
        print(json.dumps({
            "n_layers": n_layers,
            "n_params": 2 * (n_layers + 1),
            "dispatches_per_step_cached": dc,
            "dispatches_per_step_eager": de,
            "step_ms_cached": round(tc, 3),
            "step_ms_eager": round(te, 3),
            "max_abs_err": err,
            "match": bool(match),
        }))
        sys.stdout.flush()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
