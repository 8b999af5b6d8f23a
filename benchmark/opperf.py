"""Registry-driven operator micro-benchmark harness.

Parity: ``benchmark/opperf`` in the reference (opperf.py
run_all_mxnet_operator_benchmarks + utils/benchmark_utils.py
run_performance_test) re-designed for the TPU build: instead of 18
hand-curated category modules, the harness walks the live op registry
(`mxnet_tpu.ops.registry`), synthesizes default inputs per op from a
small rules table with a probing fallback, and times

- **eager forward** — the `invoke` funnel, device-synced per call
  (what the reference's engine-push timing measures), and
- **jit forward** — the same fn under `jax.jit`, steady-state (the
  regime real training runs in; no reference analogue, TPU-specific),
- **eager forward+backward** — tape + vjp, where the op is
  differentiable.

Usage::

    python -m benchmark.opperf                     # every benchmarkable op
    python -m benchmark.opperf --ops exp,dot,Convolution
    python -m benchmark.opperf --runs 50 --warmup 10 --output-json r.json
"""
from __future__ import annotations

import argparse
import json
import re
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as onp

__all__ = ["default_inputs", "benchmark_op", "run_op_benchmarks",
           "benchmarkable_ops", "format_table"]

_RNG = onp.random.RandomState(17)


def _nd(shape, dtype="float32", positive=False, low=None, high=None):
    import mxnet_tpu as mx
    if dtype in ("int32", "int64"):
        arr = _RNG.randint(low if low is not None else 0,
                           high if high is not None else 8,
                           size=shape).astype(dtype)
    else:
        arr = _RNG.uniform(0.5 if positive else -1.0, 1.0,
                           size=shape).astype(dtype)
    return mx.nd.array(arr)


# --------------------------------------------------------------------------
# default-input rules (parity: opperf/utils/op_registry_utils.py
# DEFAULTS_INPUTS — here a pattern table instead of a per-op dict)
# --------------------------------------------------------------------------

# Each rule: (regex on op name, builder() -> (inputs, params)).
# First match wins.  Shapes are modest so the sweep finishes on small
# hosts; pass --large for reference-opperf-sized tensors.
_SMALL = {"vec": (1024,), "mat": (64, 64), "batch4d": (4, 8, 16, 16),
          "gemm": (64, 64)}
_LARGE = {"vec": (2 ** 20,), "mat": (1024, 1024),
          "batch4d": (32, 3, 224, 224), "gemm": (1024, 1024)}
_SHAPES = dict(_SMALL)


def _rule_conv():
    x = _nd(_SHAPES["batch4d"])
    c = x.shape[1]
    w = _nd((16, c, 3, 3))
    b = _nd((16,))
    return [x, w, b], {"kernel": (3, 3), "num_filter": 16}


def _rule_deconv():
    x = _nd(_SHAPES["batch4d"])
    c = x.shape[1]
    w = _nd((c, 16, 3, 3))
    return [x, w], {"kernel": (3, 3), "num_filter": 16, "no_bias": True}


def _rule_fc():
    x = _nd(_SHAPES["gemm"])
    w = _nd((128, x.shape[1]))
    b = _nd((128,))
    return [x, w, b], {"num_hidden": 128}


def _rule_pool():
    return [_nd(_SHAPES["batch4d"])], {"kernel": (2, 2), "pool_type": "max",
                                       "stride": (2, 2)}


def _rule_bn():
    x = _nd(_SHAPES["batch4d"])
    c = x.shape[1]
    one, zero = _nd((c,), positive=True), _nd((c,))
    return [x, one, zero, zero, one], {}


def _rule_norm_affine():
    x = _nd(_SHAPES["mat"])
    return [x, _nd((x.shape[-1],), positive=True), _nd((x.shape[-1],))], {}


def _rule_rmsnorm():
    x = _nd(_SHAPES["mat"])
    return [x, _nd((x.shape[-1],), positive=True)], {}


def _rule_embedding():
    return [_nd((32, 16), dtype="int32", high=100), _nd((100, 32))], \
        {"input_dim": 100, "output_dim": 32}


def _rule_act():
    return [_nd(_SHAPES["mat"])], {"act_type": "relu"}


def _rule_gemm():
    return [_nd(_SHAPES["gemm"]), _nd(_SHAPES["gemm"])], {}


def _rule_lrn():
    return [_nd(_SHAPES["batch4d"])], {"nsize": 3}


def _rule_unary():
    return [_nd(_SHAPES["vec"], positive=True)], {}


def _rule_binary():
    return [_nd(_SHAPES["vec"], positive=True),
            _nd(_SHAPES["vec"], positive=True)], {}


_RULES: List[Tuple[str, Callable]] = [
    (r"^(Convolution|convolution|DeformableConvolution)$", _rule_conv),
    (r"^(Deconvolution|deconvolution)$", _rule_deconv),
    (r"^(FullyConnected|fully_connected)$", _rule_fc),
    (r"^(Pooling|pooling)$", _rule_pool),
    (r"^(BatchNorm|batch_norm|SyncBatchNorm)$", _rule_bn),
    (r"^(LayerNorm|layer_norm|GroupNorm|group_norm|InstanceNorm)$",
     _rule_norm_affine),
    (r"^(RMSNorm|rms_norm)$", _rule_rmsnorm),
    (r"^(Embedding|embedding)$", _rule_embedding),
    (r"^(Activation|activation)$", _rule_act),
    (r"^(dot|batch_dot|_npi_matmul|_npi_dot)$", _rule_gemm),
    (r"^LRN$", _rule_lrn),
    (r"^(adaptive_avg_pool2d|BilinearResize2D|UpSampling|L2Normalization"
     r"|Flatten|flatten)$", lambda: ([_nd(_SHAPES["batch4d"])], {})),
    (r"^(softmax|log_softmax|softmin)$",
     lambda: ([_nd(_SHAPES["mat"])], {})),
]

# ops that need stateful/special handling and are covered by the macro
# benchmarks instead (bench.py / tests) — excluded from the sweep
_SKIP = re.compile(
    r"^(_backward|_foreach|_while_loop|_cond|_cached_op|RNN|rnn"
    r"|Dropout|dropout|_npi_.*(seed|key)|Custom|_rtc"
    r"|IdentityAttachKLSparseReg|MakeLoss|BlockGrad"
    r"|_contrib_(count_sketch|fft|ifft))")


def benchmarkable_ops() -> List[str]:
    """Unique op names (canonical, no aliases) eligible for the sweep."""
    from mxnet_tpu.ops import registry
    seen, out = set(), []
    for name in registry.list_ops():
        op = registry.get(name)
        if op.name != name or id(op) in seen:   # alias row
            continue
        seen.add(id(op))
        if _SKIP.match(name):
            continue
        out.append(name)
    return out


def default_inputs(op_name: str):
    """(inputs, params) for an op: rules table, then probing fallback.

    Returns None if no synthesized inputs run the op successfully.
    """
    from mxnet_tpu.ops import registry
    for pat, builder in _RULES:
        if re.match(pat, op_name):
            try:
                inputs, params = builder()
                registry.invoke(op_name, inputs, **params)
                return inputs, params
            except Exception:
                return None
    # probe: unary, binary, ternary on float vecs; then int vec (indices)
    candidates = [
        lambda: ([_nd(_SHAPES["vec"], positive=True)], {}),
        lambda: ([_nd(_SHAPES["mat"], positive=True)], {}),
        lambda: (_rule_binary()[0], {}),
        lambda: ([_nd(_SHAPES["vec"], positive=True)] * 3, {}),
        lambda: ([_nd(_SHAPES["vec"], dtype="int32")], {}),
    ]
    for cand in candidates:
        try:
            inputs, params = cand()
            out = registry.invoke(op_name, inputs, **params)
            del out
            return inputs, params
        except Exception:
            continue
    return None


def _sync(out):
    outs = out if isinstance(out, (list, tuple)) else [out]
    for o in outs:
        o.wait_to_read()


def _time_loop(fn, warmup: int, runs: int) -> float:
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2] * 1e3     # median, ms


def measure_device_time(op_name: str, runs: int = 10) -> Optional[Dict]:
    """Per-op DEVICE time via an xplane capture around the jitted replay
    (parity: the reference profiler's aggregate device-time table,
    aggregate_stats.cc — dispatch wall time says nothing about the
    kernel under async dispatch)."""
    import functools
    import shutil
    import tempfile

    import jax
    from mxnet_tpu import xplane
    from mxnet_tpu.ops import registry

    synth = default_inputs(op_name)
    if synth is None:
        return None
    inputs, params = synth
    op = registry.get(op_name)
    fn = functools.partial(op.fn, **params) if params else op.fn
    arrays = [x._data for x in inputs]
    jfn = jax.jit(fn)
    try:
        jax.block_until_ready(jfn(*arrays))    # compile outside the trace
    except Exception:
        return None
    tmp = tempfile.mkdtemp(prefix="opperf_xplane_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(runs):
            jax.block_until_ready(jfn(*arrays))
        jax.profiler.stop_trace()
        table = xplane.device_op_table(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if not table:
        return None
    total_us = sum(r["total_us"] for r in table.values())
    return {"op": op_name, "dev_us_per_call": round(total_us / runs, 3),
            "kernels": {k: round(v["total_us"] / runs, 3)
                        for k, v in sorted(table.items(),
                                           key=lambda kv: -kv[1]["total_us"])
                        [:8]}}


def benchmark_op(op_name: str, warmup: int = 3, runs: int = 10,
                 slow_ms: float = 25.0) -> Optional[Dict]:
    """Benchmark one op; returns a result row or None if not runnable.

    Ops whose eager forward exceeds ``slow_ms`` get the eager number
    only — compiling + differentiating a pathological op would dominate
    the whole sweep's wall-clock (e.g. box_nms through vjp).
    """
    import jax
    from mxnet_tpu import autograd
    from mxnet_tpu.ops import registry
    import functools

    synth = default_inputs(op_name)
    if synth is None:
        return None
    inputs, params = synth
    op = registry.get(op_name)

    def eager():
        _sync(registry.invoke(op_name, inputs, **params))

    fwd_ms = _time_loop(eager, warmup, runs)
    if fwd_ms > slow_ms:
        return {"op": op_name, "inputs": [tuple(x.shape) for x in inputs],
                "fwd_eager_ms": round(fwd_ms, 4), "fwd_jit_ms": None,
                "fwd_bwd_ms": None}

    # jit steady-state on the raw arrays (the training regime)
    fn = functools.partial(op.fn, **params) if params else op.fn
    arrays = [x._data for x in inputs]
    jfn = jax.jit(fn)
    try:
        jax.block_until_ready(jfn(*arrays))     # compile outside the clock

        def jitted():
            jax.block_until_ready(jfn(*arrays))

        jit_ms = _time_loop(jitted, warmup, runs)
    except Exception:
        jit_ms = None

    # forward+backward where differentiable
    bwd_ms = None
    try:
        grad_inputs = [x for x in inputs if "float" in str(x.dtype)]
        for x in grad_inputs:
            x.attach_grad()

        def train_step():
            with autograd.record():
                out = registry.invoke(op_name, inputs, **params)
                outs = out if isinstance(out, (list, tuple)) else [out]
                head = outs[0]
            head.backward()
            # block on the *gradients* — syncing only the head would let
            # the async backward escape the clock
            for x in grad_inputs:
                if x.grad is not None:
                    x.grad.wait_to_read()

        bwd_ms = _time_loop(train_step, warmup, runs)
    except Exception:
        bwd_ms = None

    return {"op": op_name,
            "inputs": [tuple(x.shape) for x in inputs],
            "fwd_eager_ms": round(fwd_ms, 4),
            "fwd_jit_ms": round(jit_ms, 4) if jit_ms is not None else None,
            "fwd_bwd_ms": round(bwd_ms, 4) if bwd_ms is not None else None}


def run_op_benchmarks(ops: Optional[Sequence[str]] = None, warmup: int = 3,
                      runs: int = 10, large: bool = False,
                      verbose: bool = False) -> List[Dict]:
    """Sweep ops (default: all benchmarkable); returns result rows.

    Parity: run_all_mxnet_operator_benchmarks (opperf.py:57).
    """
    global _SHAPES
    _SHAPES = dict(_LARGE if large else _SMALL)
    names = list(ops) if ops else benchmarkable_ops()
    rows, skipped = [], []
    for name in names:
        if verbose:
            print(f"{name:40s} ", end="", flush=True)
        row = benchmark_op(name, warmup=warmup, runs=runs)
        if row is None:
            skipped.append(name)
            if verbose:
                print("(no default inputs)")
            continue
        rows.append(row)
        if verbose:
            print(f"{row['fwd_eager_ms']:>9.3f} ms eager")
    if skipped and verbose:
        print(f"# no default inputs for {len(skipped)} ops: "
              f"{', '.join(skipped[:20])}{' …' if len(skipped) > 20 else ''}")
    return rows


def measure_dispatch_overhead(runs: int = 300) -> Dict:
    """Eager-dispatch overhead in µs/op above raw compiled replay.

    The reference hides per-op cost behind engine worker threads (a
    PushFCompute is a few µs, imperative_utils.h:448); our synchronous
    eager funnel pays Python dispatch + jit-cache lookup + NDArray
    wrapping per op.  Measured directly: a tiny elemwise_add (device
    work ≈ 0) through the funnel vs replaying the same compiled
    executable on raw arrays — the difference IS the funnel.
    """
    import jax

    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.ops import registry

    x = NDArray(onp.ones((8, 8), onp.float32))
    y = NDArray(onp.ones((8, 8), onp.float32))

    def funnel():
        registry.invoke("elemwise_add", [x, y]).wait_to_read()

    funnel_ms = _time_loop(funnel, 20, runs)

    op = registry.get("elemwise_add")
    jfn = jax.jit(op.fn)
    a, b = x._data, y._data
    jax.block_until_ready(jfn(a, b))

    def raw():
        jax.block_until_ready(jfn(a, b))

    raw_ms = _time_loop(raw, 20, runs)
    return {"funnel_us": round(funnel_ms * 1e3, 2),
            "raw_jit_us": round(raw_ms * 1e3, 2),
            "overhead_us": round((funnel_ms - raw_ms) * 1e3, 2)}


def lenet_step_benchmark(warmup: int = 5, runs: int = 30) -> Dict:
    """Eager vs whole-step-compiled LeNet training step.

    'Eager' is the imperative gluon loop (record/backward/Trainer.step,
    one funnel dispatch per op); 'hybrid' is SPMDTrainer.step (forward+
    backward+update in ONE XLA executable — the CachedOp analogue).
    The ratio is the repo's measured answer to the reference's
    imperative-vs-symbolic gap (commit ba672e6's claim, now pinned by
    tests/test_eager_dispatch.py::test_lenet_eager_vs_hybrid_ratio).
    """
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, loss as gloss, nn
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    def build():
        net = nn.HybridSequential()
        net.add(nn.Conv2D(20, kernel_size=5, activation="tanh"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Conv2D(50, kernel_size=5, activation="tanh"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Flatten(),
                nn.Dense(500, activation="tanh"),
                nn.Dense(10))
        net.initialize(init=mx.initializer.Xavier())
        return net

    rng = onp.random.RandomState(0)
    data = rng.randn(32, 1, 28, 28).astype("float32")
    label = rng.randint(0, 10, (32,)).astype("float32")
    ce = gloss.SoftmaxCrossEntropyLoss()

    mx.random.seed(0)
    net_e = build()
    d, l = NDArray(data), NDArray(label)
    trainer = Trainer(net_e.collect_params(), "sgd",
                      {"learning_rate": 0.01})

    def eager_step():
        with autograd.record():
            out = net_e(d)
            loss = ce(out, l).mean()
        loss.backward()
        trainer.step(1)
        loss.wait_to_read()

    eager_ms = _time_loop(eager_step, warmup, runs)

    mx.random.seed(0)
    net_h = build()
    net_h(NDArray(data[:1]))
    st = SPMDTrainer(net_h, ce, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.01},
                     mesh=make_mesh({"dp": 1}))

    def hybrid_step():
        st.step(data, label).wait_to_read()

    hybrid_ms = _time_loop(hybrid_step, warmup, runs)
    return {"eager_ms": round(eager_ms, 3),
            "hybrid_ms": round(hybrid_ms, 3),
            "ratio": round(eager_ms / hybrid_ms, 2)}


def format_table(rows: List[Dict]) -> str:
    hdr = (f"{'op':40s} {'fwd eager(ms)':>14s} {'fwd jit(ms)':>12s} "
           f"{'fwd+bwd(ms)':>12s}  inputs")
    lines = [hdr, "-" * len(hdr)]
    for r in sorted(rows, key=lambda r: -r["fwd_eager_ms"]):
        jit = f"{r['fwd_jit_ms']:.4f}" if r["fwd_jit_ms"] is not None else "-"
        bwd = f"{r['fwd_bwd_ms']:.4f}" if r["fwd_bwd_ms"] is not None else "-"
        lines.append(f"{r['op']:40s} {r['fwd_eager_ms']:>14.4f} {jit:>12s} "
                     f"{bwd:>12s}  {r['inputs']}")
    return "\n".join(lines)


def main(argv=None):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ops", default="",
                   help="comma-separated op names (default: all)")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--runs", type=int, default=10)
    p.add_argument("--large", action="store_true",
                   help="reference-opperf-sized tensors")
    p.add_argument("--output-json", default="",
                   help="write result rows as JSON")
    p.add_argument("--dispatch", action="store_true",
                   help="measure eager dispatch overhead + LeNet "
                        "eager-vs-hybrid step ratio instead of the "
                        "op sweep")
    p.add_argument("--device-time", action="store_true",
                   help="report per-op DEVICE time from an xplane "
                        "capture (kernel truth) instead of wall time")
    p.add_argument("--tune", action="store_true",
                   help="autotune registered Pallas kernels over their "
                        "shape grids (--ops filters by kernel name) and "
                        "commit winners to the persistent cache "
                        "(MXNET_KERNEL_CACHE_DIR)")
    args = p.parse_args(argv)

    if args.tune:
        # tuning is an explicit request here, whatever MXNET_KERNEL_TUNE
        # says — the cache file this emits is what makes training/serving
        # starts measurement-free
        os.environ["MXNET_KERNEL_TUNE"] = "1"
        from mxnet_tpu import kernels
        names = [s for s in args.ops.split(",") if s] or None
        rows = kernels.tune_registered(names=names, warmup=args.warmup,
                                       runs=args.runs, verbose=True)
        winners = [r for r in rows if "winner" in r]
        hdr = (f"{'kernel':<22s}{'shape sig':<22s}{'dtype':<10s}"
               f"{'winner config':<34s}{'ms':>9s}")
        print()
        print(hdr)
        print("-" * len(hdr))
        for r in winners:
            print(f"{r['kernel']:<22s}{r['sig']:<22s}{r['dtype']:<10s}"
                  f"{str(r['winner']):<34s}{r['ms']:>9.4f}")
        path = kernels.cache_path()
        if path:
            print(f"# cache written: {path}")
        else:
            print("# MXNET_KERNEL_CACHE_DIR unset: winners kept "
                  "in-process only (not persisted)")
        if args.output_json:
            with open(args.output_json, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"# wrote {len(rows)} rows to {args.output_json}")
        return rows

    if args.device_time:
        ops = [s for s in args.ops.split(",") if s] or \
            ["dot", "Convolution", "softmax", "elemwise_add"]
        rows = []
        for name in ops:
            row = measure_device_time(name, runs=args.runs)
            if row:
                rows.append(row)
                print(f"{row['op']:<24}{row['dev_us_per_call']:>12.1f} "
                      f"us/call (device)")
        if args.output_json:
            with open(args.output_json, "w") as f:
                json.dump(rows, f, indent=1)
        return rows

    if args.dispatch:
        ov = measure_dispatch_overhead(runs=max(args.runs, 50))
        print(f"eager dispatch: funnel {ov['funnel_us']}us/op, raw jit "
              f"replay {ov['raw_jit_us']}us/op, overhead "
              f"{ov['overhead_us']}us/op")
        ln = lenet_step_benchmark(warmup=args.warmup, runs=args.runs)
        print(f"LeNet step: eager {ln['eager_ms']}ms, whole-step-jit "
              f"{ln['hybrid_ms']}ms, ratio {ln['ratio']}x")
        if args.output_json:
            with open(args.output_json, "w") as f:
                json.dump({"dispatch_overhead": ov, "lenet": ln}, f,
                          indent=1)
        return {"dispatch_overhead": ov, "lenet": ln}

    ops = [s for s in args.ops.split(",") if s] or None
    rows = run_op_benchmarks(ops=ops, warmup=args.warmup, runs=args.runs,
                             large=args.large, verbose=True)
    print(format_table(rows))
    if args.output_json:
        with open(args.output_json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.output_json}")
    return rows


if __name__ == "__main__":
    main()
