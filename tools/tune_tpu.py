"""One-command on-chip tuning sweep.

Sweep the knobs that set the bf16 MFU ceiling and print JSON
recommendations to bake into bench.py / model defaults:

1. flash-attention block sizes (block_q x block_k) on a training-shaped
   attention problem;
2. ResNet-50 bf16 fused-window training step over candidate batch
   sizes (MXU utilization vs HBM pressure);
3. buffer donation on/off for the training window.

All timings use bench.py's methodology: fused device-side windows,
device_get sync, marginal (slope) rate between two window lengths.
A platform other than a TPU is an error unless MXNET_TPU_BENCH_DRYRUN=1.

Usage:  python tools/tune_tpu.py [--quick]
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as onp

import bench  # the methodology lives there; reuse, don't re-derive


def tune_flash_blocks(quick=False):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops.attention import flash_attention

    B, H, S, D = (1, 2, 256, 64) if bench.DRYRUN else (4, 16, 4096, 128)
    q = jnp.asarray(onp.random.RandomState(0)
                    .randn(B, H, S, D).astype("float32")).astype(
                        jnp.bfloat16)
    sizes = [128, 256] if bench.DRYRUN else (
        [256, 512, 1024] if not quick else [512, 1024])
    rows = []
    for bq, bk in itertools.product(sizes, sizes):
        if bq > S or bk > S:
            continue

        def run(n, bq=bq, bk=bk):
            def loop(x):
                def body(acc, i):
                    xi = x * (1 + i.astype(x.dtype) * 1e-6)
                    o = flash_attention(xi, xi, xi, causal=True,
                                        block_q=bq, block_k=bk)
                    return acc + o.astype(jnp.float32).sum(), None
                acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(n))
                return acc
            bench._materialize(jax.jit(loop)(q))

        try:
            t = bench._marginal(run)
        except Exception as e:
            print(f"# flash {bq}x{bk} failed: {e}", flush=True)
            continue
        # causal flash ≈ half the dense FLOPs: 2 matmuls, S^2/2 each
        flops = 2 * 2 * B * H * S * S * D / 2
        rows.append({"block_q": bq, "block_k": bk,
                     "ms": round(t * 1e3, 3),
                     "tflops": round(flops / t / 1e12, 1)})
        print(f"# flash {bq}x{bk}: {rows[-1]['ms']} ms "
              f"{rows[-1]['tflops']} TFLOP/s", flush=True)
    best = min(rows, key=lambda r: r["ms"]) if rows else None
    return {"sweep": rows, "best": best}


def _train_step_rate(bs, donate=True):
    """bf16 fused-window training rate at batch ``bs`` (bench.py's
    model + methodology), returning (img_s, mfu or None)."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    net = get_resnet(1, 50, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 3, bench.IMAGE, bench.IMAGE),
                          onp.float32)))
    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.05,
                                            "momentum": 0.9,
                                            "wd": 1e-4},
                          mesh=make_mesh({"dp": -1}),
                          dtype="bfloat16", donate=donate)
    rng = onp.random.RandomState(0)
    data = NDArray(jnp.asarray(
        rng.randn(bs, 3, bench.IMAGE, bench.IMAGE).astype("float32")))
    label = NDArray(jnp.asarray(
        rng.randint(0, 1000, size=(bs,)).astype("float32")))

    def run(n):
        bench._materialize(trainer.run_steps(data, label, n)._data)

    step_t = bench._marginal(run)
    # analytic model FLOPs (bench.py's corrected MFU convention — XLA
    # cost_analysis counts a scan body once and misses pallas calls)
    mfu = None
    if not bench.DRYRUN:
        import jax
        peak = bench._peak_flops(jax.devices()[0].device_kind)
        mfu = bench._RESNET50_TRAIN_FLOPS_PER_IMG * bs / step_t / peak
    return bs / step_t, mfu


def tune_train_batch(quick=False):
    rows = []
    batches = [2, 4] if bench.DRYRUN else (
        [128, 256] if quick else [128, 256, 384, 512])
    for bs in batches:
        try:
            img_s, mfu = _train_step_rate(bs)
        except Exception as e:
            print(f"# bs {bs} failed: {e}", flush=True)
            continue
        rows.append({"batch": bs, "img_s": round(img_s, 1),
                     "mfu": round(mfu, 4) if mfu else None})
        print(f"# train bf16 bs={bs}: {rows[-1]['img_s']} img/s "
              f"mfu {rows[-1]['mfu']}", flush=True)
    best = max(rows, key=lambda r: r["img_s"]) if rows else None
    return {"sweep": rows, "best": best}


def tune_conv_layout(quick=False, bs=None):
    """Sweep #4 (VERDICT r2 weak #1): NCHW (XLA-chosen layouts) vs the
    explicit NHWC compute path (MXNET_TPU_CONV_LAYOUT=NHWC) for the
    ResNet-50 bf16 training step."""
    if bs is None:
        bs = 4 if bench.DRYRUN else 256
    rows = []
    for mode in ("", "NHWC"):
        os.environ["MXNET_TPU_CONV_LAYOUT"] = mode
        try:
            img_s, mfu = _train_step_rate(bs)
        except Exception as e:
            print(f"# layout={mode or 'NCHW'} failed: {e}", flush=True)
            continue
        finally:
            os.environ.pop("MXNET_TPU_CONV_LAYOUT", None)
        rows.append({"layout": mode or "NCHW",
                     "img_s": round(img_s, 1),
                     "mfu": round(mfu, 4) if mfu else None})
        print(f"# conv layout {rows[-1]['layout']}: "
              f"{rows[-1]['img_s']} img/s", flush=True)
    best = max(rows, key=lambda r: r["img_s"]) if rows else None
    return {"sweep": rows, "best": best}


def tune_donation(quick=False, bs=None):
    """Sweep #3: buffer donation on/off for the fused train window —
    donation lets XLA alias param/state buffers in place (HBM
    headroom), occasionally at the cost of a layout copy."""
    if bs is None:
        bs = 4 if bench.DRYRUN else 256
    rows = []
    for donate in (True, False):
        try:
            img_s, mfu = _train_step_rate(bs, donate=donate)
        except Exception as e:
            print(f"# donate={donate} failed: {e}", flush=True)
            continue
        rows.append({"donate": donate, "img_s": round(img_s, 1),
                     "mfu": round(mfu, 4) if mfu else None})
        print(f"# donate={donate}: {rows[-1]['img_s']} img/s",
              flush=True)
    best = max(rows, key=lambda r: r["img_s"]) if rows else None
    return {"sweep": rows, "best": best}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--skip-flash", action="store_true")
    p.add_argument("--skip-train", action="store_true")
    args = p.parse_args(argv)

    import jax

    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not bench.DRYRUN:
        raise SystemExit(
            f"tune_tpu: platform is {dev.platform!r}, not 'tpu' "
            f"(MXNET_TPU_BENCH_DRYRUN=1 rehearses at toy sizes)")
    out = {"device": dev.device_kind, "platform": dev.platform}
    if bench.DRYRUN:
        out["dryrun"] = True
    if not args.skip_flash:
        out["flash"] = tune_flash_blocks(args.quick)
    if not args.skip_train:
        out["train"] = tune_train_batch(args.quick)
        out["donation"] = tune_donation(args.quick)
        out["conv_layout"] = tune_conv_layout(args.quick)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
