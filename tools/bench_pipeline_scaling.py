"""Measure native image-pipeline throughput vs preprocess_threads.

Parity target: the reference's threaded ImageRecordIter hits ~3,000
img/s decode+augment on a multi-core machine (docs
note_data_loading.md:181).  This tool measures img/s at several thread
counts on THIS host and emits one JSON line; on a single-core container
the curve documents the 1-core ceiling (per-thread rate x 1) and the
cost model extrapolates the core count needed for the reference rate.

Usage: python tools/bench_pipeline_scaling.py [--n 512] [--hw 224]
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def make_rec(tmp, n, hw):
    import numpy as onp
    from mxnet_tpu import recordio
    from mxnet_tpu.io import native

    rec = os.path.join(tmp, "bench.rec")
    rng = onp.random.RandomState(0)
    blobs = [rng.randint(0, 255, (hw, hw, 3), onp.uint8)
             for _ in range(8)]
    with native.NativeRecordWriter(rec) as w:
        for i in range(n):
            hdr = recordio.IRHeader(flag=0, label=float(i % 10), id=i,
                                    id2=0)
            w.write(recordio.pack_img(hdr, blobs[i % 8], quality=90))
    return rec


def measure(rec, threads, batch, hw, epochs=2, rand_crop=False,
            prefetch_buffer=4, shuffle=True):
    from mxnet_tpu.io.native import ImageRecordIter as NativeImageRecordIter

    it = NativeImageRecordIter(
        path_imgrec=rec, batch_size=batch,
        data_shape=(3, hw, hw), shuffle=shuffle, rand_mirror=True,
        rand_crop=rand_crop, prefetch_buffer=prefetch_buffer,
        preprocess_threads=threads)
    # warm-up epoch: thread spin-up + page cache
    for _ in it:
        pass
    it.reset()
    seen = 0
    t0 = time.perf_counter()
    for _ in range(epochs):
        for batch_data in it:
            seen += batch_data.data[0].shape[0]
        it.reset()
    dt = time.perf_counter() - t0
    return seen / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--threads", default="1,2,4")
    ap.add_argument("--one-rate", action="store_true",
                    help="measure only the FIRST thread count and print "
                         "one {'img_s': N} JSON line (clean-subprocess "
                         "mode for bench.py's pipeline row)")
    ap.add_argument("--rec", default=None,
                    help="existing .rec file to read (skips the encode)")
    args = ap.parse_args()
    # the pipeline never touches the accelerator, and a chip belongs to
    # one process: pin jax to the CPU before anything imports it
    os.environ["JAX_PLATFORMS"] = "cpu"

    if args.one_rate:
        # bench.py's pipeline-row config (rand_crop + prefetch, no
        # shuffle), comparable to the 3,000 img/s reference row
        t = int(args.threads.split(",")[0])
        kw = dict(rand_crop=True, prefetch_buffer=4, shuffle=False)
        if args.rec:
            rate = measure(args.rec, t, args.batch, args.hw, **kw)
        else:
            with tempfile.TemporaryDirectory() as tmp:
                rec = make_rec(tmp, args.n, args.hw)
                rate = measure(rec, t, args.batch, args.hw, **kw)
        print(json.dumps({"img_s": round(rate, 1)}))
        return

    ncores = os.cpu_count() or 1
    with tempfile.TemporaryDirectory() as tmp:
        rec = make_rec(tmp, args.n, args.hw)
        rows = {}
        for t in [int(x) for x in args.threads.split(",")]:
            rate = measure(rec, t, args.batch, args.hw)
            rows[str(t)] = round(rate, 1)
            print(f"threads={t}: {rate:.1f} img/s", file=sys.stderr)

    per_thread = rows.get("1", 0.0)
    reference = 3000.0
    result = {
        "metric": "pipeline_img_s_vs_threads",
        "host_cores": ncores,
        "img_s": rows,
        "per_thread_img_s": per_thread,
        "reference_img_s": reference,
        "cores_needed_for_reference": (
            round(reference / per_thread, 1) if per_thread else None),
        "note": ("single-core host: thread scaling is flat by "
                 "construction; the cost model extrapolates the "
                 "multi-core rate as threads x per-thread rate up to "
                 "memory bandwidth" if ncores == 1 else
                 "multi-core host: measured curve"),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
