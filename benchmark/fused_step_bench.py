#!/usr/bin/env python
"""Fused optimizer-step microbench: per-step dispatch count and host
dispatch time through ``Trainer.step()``, fused vs per-param.

The fused whole-parameter-set step (mxnet_tpu/optimizer/fused_step.py)
replaces the eager Trainer's O(n_params) per-step optimizer dispatches
with ONE jitted pytree update.  This bench measures exactly that claim
on any backend (CPU is fine — dispatch count is backend-independent)
and checks the two paths produce bitwise-identical weights and states.

Prints one JSON line per configuration:
  {"n_params", "dispatches_per_step_fused", "dispatches_per_step_eager",
   "step_ms_fused", "step_ms_eager", "identical"}
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
        net.add(nn.Dense(units, in_units=units))
    net.initialize()
    trainer = Trainer(net.collect_params(), optimizer, dict(opt_args))
    x = nd.array(onp.random.RandomState(1).randn(8, units)
                 .astype("float32"))
    return net, trainer, x


def _run(n_layers, units, optimizer, opt_args, steps, fused):
    from mxnet_tpu import autograd
    from mxnet_tpu.optimizer import optimizer as opt_mod
    os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
    net, trainer, x = _build(n_layers, units, optimizer, opt_args)

    def one_step():
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        trainer.step(batch_size=8)

    # warm twice: the second step retraces once more (post-update
    # weights lose weak_type), after which the cache is steady
    one_step()
    one_step()
    d0 = opt_mod.dispatch_count()
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    for p in net.collect_params().values():
        p._data_nd()._data.block_until_ready()
    dt = (time.perf_counter() - t0) / steps
    dispatches = (opt_mod.dispatch_count() - d0) / steps
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
    ap.add_argument("--layers", type=int, nargs="*", default=[4, 16, 64])
    ap.add_argument("--optimizer", default="sgd")
    args = ap.parse_args()
    opt_args = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}

    for n_layers in args.layers:
        df, tf, wf, sf = _run(n_layers, args.units, args.optimizer,
                              opt_args, args.steps, fused=True)
        de, te, we, se = _run(n_layers, args.units, args.optimizer,
                              opt_args, args.steps, fused=False)
        identical = (
            all((a == b).all() for a, b in zip(wf, we))
            and sf.keys() == se.keys()
            and all((a == b).all() for k in sf
                    for a, b in zip(sf[k], se[k])))
        print(json.dumps({
            "n_params": 2 * n_layers,
            "dispatches_per_step_fused": df,
            "dispatches_per_step_eager": de,
            "step_ms_fused": round(tf, 3),
            "step_ms_eager": round(te, 3),
            "identical": bool(identical),
        }))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
