"""Benchmark: ResNet-50 on one chip — bf16 training (headline), fp32
training, and batch inference, with MFU accounting.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Baselines (BASELINE.md, from reference docs perf.md):
- training  fp32 1xV100 bs=64  ~343 img/s (perf.md:252-254; the only
  published training anchor — no fp16 training row exists, so the bf16
  headline is also reported against it; perf.md:199-211 says low
  precision roughly doubles V100 numbers).
- inference fp32 1xV100 bs=128 1233.15 img/s (perf.md:196)
- inference fp16 1xV100 bs=128 2355.04 img/s (perf.md:210)

Methodology:
- work runs DEVICE-SIDE in fused windows — `SPMDTrainer.run_steps`
  (lax.scan over full train steps) and a scanned inference loop;
- every timing is synchronized by materializing a scalar reduction of
  the result via device_get (cannot complete before the work does);
- throughput is the MARGINAL rate between a short and a long window:
  (T(n2) - T(n1)) / (n2 - n1), which cancels launch latency and any
  constant dispatch overhead.  That is the steady-state per-step time
  a real training loop sees, the same regime the V100 baselines report.
A row that fails raises: the process exits non-zero and prints no
result line.  A platform other than a TPU is an error unless
MXNET_TPU_BENCH_DRYRUN=1, and a dry run labels itself.
MFU uses ANALYTIC model FLOPs (the standard convention): ResNet-50
train ~= 3 x 4.089 GFLOP/img; transformer train ~= (6P + 12*L*d*S)
per token — divided by marginal step time and the chip's peak bf16
FLOP/s (by device kind).  XLA cost_analysis is NOT the numerator: it
counts a lax.scan body once regardless of trip count, reports zero
FLOPs for Pallas custom calls, and reports tile-padded hardware FLOPs
for convs.
"""
from __future__ import annotations

import json
import os
import time

import numpy as onp

TRAIN_BASE_FP32 = 343.0
INFER_BASE_FP32 = 1233.15
INFER_BASE_FP16 = 2355.04
IMAGE = 224
TRAIN_BS_FP32 = 64
TRAIN_BS_BF16 = 256
INFER_BS = 128
N1, N2 = 4, 24          # fused-window sizes for marginal timing
REPS = 3

# MXNET_TPU_BENCH_DRYRUN=1: run EVERY row end to end at toy scale on
# whatever backend is available (CPU included) — validates the whole
# bench program without a TPU.  Numbers produced this way are tagged
# and meaningless as perf.
def _envbool(name):
    return os.environ.get(name, "").strip().lower() in (
        "1", "true", "yes", "on")


DRYRUN = _envbool("MXNET_TPU_BENCH_DRYRUN")
if DRYRUN:
    IMAGE = 32
    TRAIN_BS_FP32 = 4
    TRAIN_BS_BF16 = 4
    INFER_BS = 4
    N1, N2 = 2, 4
    REPS = 1

# Analytic model FLOPs for MFU (standard convention: model FLOPs over
# peak, NOT hardware/padded FLOPs).  ResNet-50 v1 @224 forward is the
# conventional ~4.089 GFLOP/img; training fwd+bwd ~= 3x forward.  Conv
# FLOPs scale with spatial area, so the dry-run's IMAGE=32 scales the
# figure (dry-run numbers are tagged meaningless anyway).
_RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.089e9 * (IMAGE / 224) ** 2

# Parity grids: the reference's published perf page beyond ResNet-50
# (model zoo name, batch, input px, V100 anchor img/s or None).
# Single source of truth — tests/test_bench_parity_grid.py constructs
# every model here so a zoo rename fails on CPU, not mid-bench on a chip.
TRAIN_PARITY_GRID = [
    ("inceptionv3", 128, 299, 253.68),     # perf.md:254
    ("alexnet", 512, 224, 2585.61),        # perf.md:252
]
INFER_PARITY_GRID = [
    ("resnet152_v1", 128, 224),            # perf.md:196/210
    ("inceptionv3", 128, 299),             # perf.md:196/210
    ("vgg16", 64, 224),                    # perf.md:195
    ("alexnet", 256, 224),                 # perf.md:197
]

# peak dense bf16 FLOP/s of one chip, keyed by the exact
# ``device_kind`` JAX reports.  A kind that is not here is an error,
# never a default and never a missing MFU.
_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def _peak_flops(kind: str) -> float:
    try:
        return _PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s recorded for device kind {kind!r}; add it "
            f"to bench._PEAKS with its source") from None


RESULTS: dict = {}


def _phase(name):
    print(f"# bench: {name}", flush=True)


def _emit():
    """Print the single JSON line from RESULTS."""
    headline = RESULTS["train_bf16_bs%d_img_s" % TRAIN_BS_BF16]
    out = {
        "metric": "resnet50_train_bf16_bs%d_images_per_sec"
                  % TRAIN_BS_BF16,
        "value": round(headline, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(headline / TRAIN_BASE_FP32, 3),
        "extra": dict(RESULTS),
    }
    print(json.dumps(out), flush=True)


def _materialize(x):
    """Full synchronization: fetch a value derived from x."""
    import jax
    return jax.device_get(x)


def _marginal(run, n1=N1, n2=N2, reps=REPS):
    """Steady-state per-unit time via the slope between two window
    sizes (constant launch overhead cancels)."""
    run(n1)   # compile + warm
    run(n2)
    t1 = min(_timed(run, n) for n in [n1] * reps)
    t2 = min(_timed(run, n) for n in [n2] * reps)
    return max((t2 - t1) / (n2 - n1), 1e-9)


def _timed(run, n):
    t0 = time.perf_counter()
    run(n)
    return time.perf_counter() - t0


def _train_bench(dtype, batch, model=None, image=None,
                 flops_per_img=None):
    """Training rate for ``model`` (zoo name; default the flagship
    ResNet-50).  ``flops_per_img``: analytic train FLOPs for the MFU
    numerator (None -> no TFLOP/s figure for that model)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import get_model, get_resnet
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import make_mesh, SPMDTrainer
    from mxnet_tpu.ndarray import NDArray

    image = image or IMAGE
    if model is None:
        net = get_resnet(1, 50, classes=1000)
        flops_per_img = _RESNET50_TRAIN_FLOPS_PER_IMG
    else:
        net = get_model(model, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 3, image, image), onp.float32)))

    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.05,
                                            "momentum": 0.9, "wd": 1e-4},
                          mesh=make_mesh({"dp": -1}), dtype=dtype)

    # synthetic batch generated ON DEVICE (no ~154 MB host->device
    # transfer of bs=256 fp32 imagenet in the timed path)
    import jax
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    data = NDArray(jax.random.normal(
        k1, (batch, 3, image, image), jnp.float32))
    label = NDArray(jax.random.randint(
        k2, (batch,), 0, 1000).astype(jnp.float32))

    def run(n):
        losses = trainer.run_steps(data, label, n)
        _materialize(losses._data)

    step_t = _marginal(run)
    img_s = batch / step_t
    # MFU accounting uses ANALYTIC model FLOPs (the standard MFU
    # definition; see module docstring for why XLA cost_analysis is
    # the wrong numerator)
    flops_s = (flops_per_img * batch / step_t) if flops_per_img else None

    def capture_kernel_table():
        """One short profiled window parsed into the top kernels by
        device time (aggregate_stats.cc analogue); run after the rate
        is measured so tracing cannot perturb it."""
        import shutil

        from mxnet_tpu import profiler as _prof
        dt_name = dtype or "float32"   # NOT 'label' (the labels array)
        if _prof.is_running():
            return     # don't disturb a user/autostart trace
        _prof.set_config(filename=f"/tmp/bench_{dt_name}.json")
        _prof.start()
        try:
            run(2)
        finally:
            _prof.stop()
            tdir = _prof.trace_dir()
        table = _prof.device_op_table()
        if table:
            top = sorted(table.items(),
                         key=lambda kv: -kv[1]["total_us"])[:5]
            RESULTS[f"top_kernels_{dt_name}"] = {
                k: round(v["total_us"], 1) for k, v in top}
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    return img_s, flops_s, capture_kernel_table


def _infer_bench(dtype, batch, model=None, image=None):
    """Batch-inference rate for ``model`` (zoo name; default the
    flagship ResNet-50) at the reference table's input size.  Parity
    table: perf.md:189-211 measures ResNet-50/152, Inception-v3,
    VGG-16 and AlexNet at their own batch sizes — `main` runs the same
    grid so one bench run answers the full published-inference page."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon.model_zoo.vision import get_model, get_resnet
    from mxnet_tpu.gluon.block import _TraceContext, _trace_scope
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.ops.random import next_key

    image = image or IMAGE
    if model is None:
        net = get_resnet(1, 50, classes=1000)
    else:
        net = get_model(model, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 3, image, image), onp.float32)))
    if dtype != "float32":
        net.cast(dtype)

    params = net.collect_params()
    pvals = [params[k] for k in params]
    p_arrays = [p.data()._data for p in pvals]

    key0 = next_key()   # fetched OUTSIDE any trace (inference: unused
                        # entropy; splitting inside a scan would leak a
                        # tracer into the global key chain)

    def fwd(x):
        tc = _TraceContext(key0)
        saved = [p._data for p in pvals]
        try:
            for p, a in zip(pvals, p_arrays):
                p._data = NDArray(a)
            with _trace_scope(tc), ag.pause(train_mode=False):
                out = net.forward(NDArray(x))
            return out._data
        finally:
            for p, s in zip(pvals, saved):
                p._data = s

    x = jax.random.normal(jax.random.PRNGKey(0),
                          (batch, 3, image, image), jnp.float32)
    if dtype != "float32":
        x = x.astype(jnp.dtype(dtype))

    loops = {}

    def run(n):
        f = loops.get(n)
        if f is None:
            def loop(xin):
                def body(acc, i):
                    # per-iteration input perturbation defeats
                    # loop-invariant hoisting of the whole forward
                    xi = xin * (1 + i.astype(xin.dtype) * 1e-6)
                    out = fwd(xi)
                    return acc + out.astype(jnp.float32).sum(), None
                acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(n))
                return acc
            f = jax.jit(loop)
            loops[n] = f
        _materialize(f(x))

    batch_t = _marginal(run)
    return batch / batch_t


def _transformer_bench(dtype="bfloat16", batch=8, seq=2048,
                       units=512, layers=8, heads=8, vocab=32000):
    """Transformer-LM training rate (tokens/s + MFU): decoder-only LM
    with the Pallas flash-attention kernel, trained via the same fused
    run_steps windows as the ResNet rows.  A GPT-2-medium-ish shape
    sized for one chip; covers the long-context/transformer capability
    the SURVEY adds beyond the reference."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    net = TransformerLM(vocab, units=units, num_layers=layers,
                        num_heads=heads, max_len=seq, tie_weights=True)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 8), onp.float32)))
    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          optimizer="adam",
                          optimizer_params={"learning_rate": 3e-4},
                          mesh=make_mesh({"dp": -1}), dtype=dtype)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    data = NDArray(jax.random.randint(
        k1, (batch, seq), 0, vocab).astype(jnp.float32))
    label = NDArray(jax.random.randint(
        k2, (batch, seq), 0, vocab).astype(jnp.float32))

    def run(n):
        _materialize(trainer.run_steps(data, label, n)._data)

    step_t = _marginal(run, n1=2, n2=8)
    tok_s = batch * seq / step_t
    # analytic model FLOPs (standard MFU convention; see _train_bench
    # for why cost_analysis is the wrong numerator): training ~6*P
    # FLOPs per token for the matmul core plus the attention term
    # 12*L*H*S per token (scores + value matmuls, fwd+bwd)
    n_params = sum(
        int(onp.prod(p.shape))
        for p in net.collect_params().values())
    flops_tok = 6 * n_params + 12 * layers * units * seq
    flops_s = flops_tok * tok_s
    return tok_s, flops_s


def _make_rec(path, n=512, hw=IMAGE):
    from mxnet_tpu import recordio
    from mxnet_tpu.io import native

    rng = onp.random.RandomState(0)
    blobs = [rng.randint(0, 255, (hw, hw, 3), onp.uint8)
             for _ in range(8)]
    with native.NativeRecordWriter(path) as w:
        for i in range(n):
            hdr = recordio.IRHeader(flag=0, label=float(i % 10), id=i,
                                    id2=0)
            w.write(recordio.pack_img(hdr, blobs[i % 8], quality=90))
    return path


def _pipeline_bench(path, batch=64):
    """Uncontended native input-pipeline rate (decode+augment+batch;
    reference baseline 3,000 img/s, note_data_loading.md:181).

    Measured in a CLEAN SUBPROCESS: by this point the bench process
    carries a multi-GB jax heap and its compiled executables' thread
    pools, which contend with the decode threads.  The row documents
    the pipeline, so it gets a clean process.  The child is host-only
    and this process holds the chip, so the child's jax is pinned to
    the CPU through its environment.  The existing record file is
    passed down (no second 512-JPEG encode)."""
    import subprocess
    import sys
    _phase("pipeline row: clean-subprocess measure")
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "bench_pipeline_scaling.py"),
         "--one-rate", "--rec", path, "--threads",
         str(min(8, os.cpu_count() or 4)),
         "--hw", str(IMAGE), "--batch", str(batch)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    for line in out.stdout.strip().splitlines()[::-1]:
        if line.startswith("{"):
            return json.loads(line)["img_s"]
    raise RuntimeError(f"pipeline child printed no JSON "
                       f"(rc={out.returncode}): {out.stderr[-2000:]}")


def _train_bench_datafed(path, dtype, batch, window=8, windows=3,
                         pipe_img_s=None):
    """Data-FED training rate: ImageRecordIter batches staged into
    (window, batch, ...) arrays, trained via run_steps(per_step_data=
    True) — one transfer + one launch per window.  End-to-end img/s
    including decode/augment/staging; the delta vs the synthetic-tensor
    row is the input-pipeline cost (round-1 'can the framework feed the
    chip' question).

    TPU-first wire format: pixels cross host->device as UINT8 (1/4 the
    f32 bytes) and normalization runs device-side via
    SPMDTrainer(data_transform=...), where XLA fuses it into the first
    conv.

    ``pipe_img_s``: measured host decode rate; the BATCH SIZE halves
    until the row's decode time fits ~5 minutes on slow hosts (a
    1-core container cannot feed bs-256 windows).  Returns
    ``(img_s, effective_batch)`` and the caller records both — a
    datafed rate at a reduced batch is NOT comparable to the synthetic
    bs-256 row (staging amortization differs)."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.io import native
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    if pipe_img_s:
        # keep decode time for warmup + measured windows under ~5 min.
        # The clean-process rate overstates what decoding inside this
        # jax-heavy process achieves, so budget at rate/4.
        eff = pipe_img_s / 4
        while (windows + 1) * window * batch / eff > 300 \
                and batch > 32:
            batch //= 2

    def normalize(d):
        # (window, batch, 3, H, W) uint8 -> f32 in ~[-1, 1]; fused on
        # device into the first conv
        return d.astype(jnp.float32) / 127.5 - 1.0

    net = get_resnet(1, 50, classes=1000)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 3, IMAGE, IMAGE), onp.float32)))
    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.05,
                                            "momentum": 0.9, "wd": 1e-4},
                          mesh=make_mesh({"dp": -1}), dtype=dtype,
                          data_transform=normalize)

    it = native.ImageRecordUInt8Iter(
        path, batch_size=batch, data_shape=(3, IMAGE, IMAGE),
        rand_mirror=True, rand_crop=True,
        preprocess_threads=min(8, os.cpu_count() or 4),
        prefetch_buffer=4)

    def next_window():
        ds, ls = [], []
        while len(ds) < window:
            for b in it:
                ds.append(b.data[0].asnumpy())
                ls.append(b.label[0].asnumpy().astype("float32"))
                if len(ds) == window:
                    break
            else:
                it.reset()
        return (jnp.asarray(onp.stack(ds)), jnp.asarray(onp.stack(ls)))

    # warm-up: compile + first transfer
    d, l = next_window()
    _materialize(trainer.run_steps(d, l, window,
                                   per_step_data=True)._data)
    t0 = time.perf_counter()
    for i in range(windows):
        d, l = next_window()
        _materialize(trainer.run_steps(d, l, window,
                                       per_step_data=True)._data)
        _phase(f"datafed window {i + 1}/{windows} (bs={batch})")
    dt = time.perf_counter() - t0
    it.close()
    return windows * window * batch / dt, batch


def main():
    import jax

    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not DRYRUN:
        raise SystemExit(
            f"bench: platform is {dev.platform!r}, not 'tpu' "
            f"(MXNET_TPU_BENCH_DRYRUN=1 rehearses at toy sizes)")
    kind = dev.device_kind
    RESULTS["device_kind"] = kind
    RESULTS["platform"] = dev.platform
    RESULTS["device_count"] = len(jax.devices())
    if DRYRUN:
        RESULTS["dryrun"] = True   # toy shapes; numbers meaningless
        peak = None                # no MFU for a rehearsal
    else:
        peak = _peak_flops(kind)
    RESULTS["method_note"] = (
        "marginal (slope) timing over fused device-side windows with "
        "device_get sync — steady-state per-step rate; launch latency "
        "excluded")
    RESULTS["baseline_note"] = (
        "vs_baseline anchors the bf16 headline to the only published "
        "training row (1xV100 fp32 343 img/s); ref fp16 roughly "
        "doubles V100 (perf.md:199-211)")

    _phase(f"device {kind}, starting bf16 train (headline)")
    bf16_img_s, bf16_flops_s, bf16_capture = _train_bench(
        "bfloat16", TRAIN_BS_BF16)
    RESULTS["train_bf16_bs%d_img_s" % TRAIN_BS_BF16] = round(bf16_img_s, 2)
    RESULTS["train_bf16_tflops"] = round(bf16_flops_s / 1e12, 2)
    if peak:
        RESULTS["train_bf16_mfu"] = round(bf16_flops_s / peak, 4)
    _phase("bf16 headline recorded; capturing kernel table")
    bf16_capture()

    _phase(f"bf16 {bf16_img_s:.1f} img/s; starting fp32 train")
    fp32_img_s, _, fp32_capture = _train_bench(None, TRAIN_BS_FP32)
    RESULTS["train_fp32_bs%d_img_s" % TRAIN_BS_FP32] = round(fp32_img_s, 2)
    RESULTS["train_fp32_vs_v100_343"] = round(fp32_img_s / TRAIN_BASE_FP32,
                                              3)
    fp32_capture()

    _phase(f"fp32 {fp32_img_s:.1f} img/s; starting inference")
    infer32 = _infer_bench("float32", INFER_BS)
    RESULTS["infer_fp32_bs%d_img_s" % INFER_BS] = round(infer32, 2)
    RESULTS["infer_fp32_vs_v100_1233"] = round(infer32 / INFER_BASE_FP32, 3)
    infer16 = _infer_bench("bfloat16", INFER_BS)
    RESULTS["infer_bf16_bs%d_img_s" % INFER_BS] = round(infer16, 2)
    RESULTS["infer_bf16_vs_v100_fp16_2355"] = round(
        infer16 / INFER_BASE_FP16, 3)

    if not os.environ.get("MXNET_TPU_BENCH_SKIP_TRANSFORMER"):
        _phase("starting transformer-LM row")
        tok_s, tf_flops_s = (_transformer_bench(
            batch=2, seq=64, units=32, layers=1, heads=2,
            vocab=128) if DRYRUN else _transformer_bench())
        RESULTS["transformer_lm_bf16_tok_s"] = round(tok_s, 1)
        RESULTS["transformer_lm_bf16_tflops"] = round(
            tf_flops_s / 1e12, 2)
        if peak:
            RESULTS["transformer_lm_bf16_mfu"] = round(
                tf_flops_s / peak, 4)

    if not os.environ.get("MXNET_TPU_BENCH_SKIP_PARITY_TABLE"):
        # the reference's published TRAINING rows beyond ResNet-50
        # (perf.md:252-254): Inception-v3 bs128 (253.68 img/s V100)
        # and AlexNet bs512 (2585.61 img/s V100), fp32 like the page.
        _train_grid = ([("alexnet", 4, 32, 2585.61)] if DRYRUN
                       else TRAIN_PARITY_GRID)
        for name, bs, hw, anchor in _train_grid:
            _phase(f"train parity: {name} fp32 bs={bs}")
            key = f"train_{name}_fp32_bs{bs}_img_s"
            rate, _, _ = _train_bench(None, bs, model=name, image=hw)
            RESULTS[key] = round(rate, 2)
            RESULTS[key.replace("_img_s", "_vs_v100")] = \
                round(rate / anchor, 3)

        # the reference's full published inference page (perf.md:
        # 189-211): same models, same batch sizes, fp32 + low precision
        _grid = ([("alexnet", 8, 32)] if DRYRUN
                 else INFER_PARITY_GRID)
        _anchors = {  # V100 img/s rows from perf.md:189-211
            ("resnet152_v1", "float32"): 511.79,
            ("inceptionv3", "float32"): 904.33,
            ("vgg16", "float32"): 701.59,
            ("alexnet", "float32"): 10990.46,
            ("resnet152_v1", "bfloat16"): 1046.98,   # vs V100 fp16
            ("inceptionv3", "bfloat16"): 1818.26,
        }
        for name, bs, hw in _grid:
            for dt in ("float32", "bfloat16"):
                _phase(f"parity table: {name} {dt} bs={bs}")
                key = f"infer_{name}_{dt}_bs{bs}_img_s"
                rate = _infer_bench(dt, bs, model=name, image=hw)
                RESULTS[key] = round(rate, 2)
                anchor = _anchors.get((name, dt))
                if anchor:
                    RESULTS[key.replace("_img_s", "_vs_v100")] = \
                        round(rate / anchor, 3)

    _phase("inference done; starting feed-the-chip rows")
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        rec = _make_rec(os.path.join(tmp, "bench.rec"),
                        n=64 if DRYRUN else 512)
        pipe_img_s = _pipeline_bench(rec)
        RESULTS["pipeline_img_s_vs_ref_3000"] = round(pipe_img_s, 1)
        datafed_img_s, datafed_bs = _train_bench_datafed(
            rec, "bfloat16", TRAIN_BS_BF16,
            window=2 if DRYRUN else 8, windows=1 if DRYRUN else 3,
            pipe_img_s=pipe_img_s)
        RESULTS["train_bf16_datafed_img_s"] = round(datafed_img_s, 2)
        RESULTS["train_bf16_datafed_bs"] = datafed_bs

    _emit()


if __name__ == "__main__":
    main()
