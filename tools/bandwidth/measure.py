#!/usr/bin/env python
"""Collective-bandwidth microbenchmark.

Parity: tools/bandwidth/measure.py (times kvstore push/pull of large
tensors across devices).  TPU-native: times an all-reduce (`psum`) over
the device mesh — the collective every data-parallel step rides — and
reports algorithmic bus bandwidth like nccl-tests:
bus_bw = 2*(n-1)/n * bytes / time.
"""
from __future__ import annotations

import argparse
import sys
import time


def measure(size_mb: float, repeat: int, devices=None):
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = devices or jax.devices()
    n = len(devs)
    mesh = Mesh(onp.array(devs), ("x",))
    elems = int(size_mb * 1e6 / 4)
    elems = max(n, elems - elems % n)
    x = jnp.ones((elems,), jnp.float32)

    @jax.jit
    def allreduce(v):
        return shard_map(lambda s: jax.lax.psum(s, "x"), mesh=mesh,
                         in_specs=P("x"), out_specs=P())(v)

    allreduce(x).block_until_ready()   # compile + warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / repeat
    nbytes = elems * 4
    alg_bw = nbytes / dt / 1e9
    bus_bw = alg_bw * 2 * (n - 1) / n if n > 1 else alg_bw
    return {"devices": n, "size_mb": nbytes / 1e6,
            "time_ms": dt * 1e3, "alg_bw_GBps": alg_bw,
            "bus_bw_GBps": bus_bw}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--repeat", type=int, default=10)
    args = ap.parse_args()
    r = measure(args.size_mb, args.repeat)
    print(f"devices={r['devices']} size={r['size_mb']:.1f}MB "
          f"time={r['time_ms']:.3f}ms alg_bw={r['alg_bw_GBps']:.2f}GB/s "
          f"bus_bw={r['bus_bw_GBps']:.2f}GB/s")


if __name__ == "__main__":
    sys.exit(main())
