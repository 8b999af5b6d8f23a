#!/usr/bin/env python
"""DLRM-style sharded-embedding bench: the planet-scale recommender path.

Proves the four claims the embedding subsystem makes, end to end, on a
generated LibSVM click log:

1. **Capacity** — the logical table's total bytes EXCEED one device's
   memory allotment, but each of the 2 shards' local subtables fits:
   the table only exists sharded, which is the point of the subsystem.
2. **Wire** — training moves only touched rows: the sparse wire bytes
   accumulated by the ``embedding.sparse_bytes`` counter stay at or
   under 0.2x the dense-push equivalent (``embedding.
   dense_equiv_bytes``) for a realistically skewed id stream.
3. **Kill-and-resume** — the table checkpoints per shard (each shard
   one manifest-listed SHA-256 artifact), the servers are killed, and
   a FRESH table at a DIFFERENT shard count restores bitwise equal to
   the pre-kill table (``assert_array_equal``).
4. **Serving** — a repeated-user inference batch through the
   LRU lookup tier + InferenceEngine admission hook scores cache
   hits >= 1 and matches the direct dense forward.

The model is a toy CTR predictor: mean-pooled embedding of each
example's categorical ids -> logistic regression.  The dense side
trains host-side (it is not what is being measured); the embedding side
trains through the real kvstore/PS sparse path with a server-side SGD.

Prints one JSON line:
  {"table_nbytes", "device_allotment_bytes", "per_shard_nbytes",
   "num_shards", "steps", "loss_first", "loss_last", "wire_ratio",
   "rows_pulled", "rows_pushed", "restore_match", "serving_cache_hits",
   "discarded_rows", "ok"}

Usage:
    python benchmark/embedding_bench.py            # full
    python benchmark/embedding_bench.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def gen_libsvm(path, rows, vocab, feats_per_row, seed=0):
    """Synthetic click log: each row draws ``feats_per_row`` ids from a
    zipf-skewed distribution over ``vocab`` (repeat-heavy, like real
    traffic) and a label correlated with the lowest id (so the model
    has signal to learn)."""
    rng = onp.random.RandomState(seed)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = onp.unique(rng.zipf(1.3, feats_per_row) % vocab)
            label = int(ids.min() < vocab // 8)
            f.write(str(label) + " "
                    + " ".join(f"{i}:1.0" for i in sorted(ids)) + "\n")


def batch_ids(csr):
    """Per-example id lists + the flat (example index, id) pairs of one
    CSR LibSVM batch — the categorical ids ARE the column indices."""
    indptr = onp.asarray(csr.indptr)
    cols = onp.asarray(csr.indices, onp.int64)
    return indptr, cols


def train(emb, it, w, b, lr, steps_cap):
    """Mean-pooled-embedding logistic regression: pull touched rows,
    dense compute on host, push row-sparse grads back through the PS.
    Each step runs inside a telemetry step funnel, so a JSONL sink gets
    one record per step with the ``embedding`` delta section."""
    from mxnet_tpu import telemetry
    losses = []
    it.reset()
    steps = 0
    for batch in it:
        if steps >= steps_cap:
            break
        tok = telemetry.begin_step()
        indptr, cols = batch_ids(batch.data[0])
        labels = batch.label[0].asnumpy().reshape(-1)
        n = labels.size
        rows = emb.pull_rows(cols)                  # sparse pull
        counts = onp.maximum(indptr[1:] - indptr[:-1], 1)
        seg = onp.repeat(onp.arange(n), indptr[1:] - indptr[:-1])
        pooled = onp.zeros((n, emb.dim), onp.float32)
        onp.add.at(pooled, seg, rows)
        pooled /= counts[:, None]
        logits = pooled @ w + b
        p = 1.0 / (1.0 + onp.exp(-logits))
        eps = 1e-7
        losses.append(float(-onp.mean(
            labels * onp.log(p + eps)
            + (1 - labels) * onp.log(1 - p + eps))))
        dlogit = (p - labels) / n
        # dense side updates host-side; embedding side goes on the wire
        w -= lr * (pooled.T @ dlogit)
        b -= lr * float(dlogit.sum())
        dpooled = onp.outer(dlogit, w)
        demb = dpooled[seg] / counts[seg][:, None]
        emb.push_grad(cols, demb)                   # row-sparse push
        telemetry.end_step(tok, "embedding_bench")
        steps += 1
    return steps, losses


def main(argv=None):
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (smaller table, fewer steps)")
    ap.add_argument("--rows", type=int, default=None,
                    help="training examples to generate")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None,
                    help="embedding rows (table height)")
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--device-allotment-bytes", type=int, default=None,
                    help="one CPU 'device' memory allotment the whole "
                         "table must NOT fit in (each shard must)")
    args = ap.parse_args(argv)
    vocab = args.vocab or (8192 if args.smoke else 32768)
    dim = args.dim or (16 if args.smoke else 32)
    n_rows = args.rows or (512 if args.smoke else 4096)
    steps_cap = args.steps or (6 if args.smoke else 40)
    allot = args.device_allotment_bytes or \
        (3 * vocab * dim * 4) // 4      # 0.75x the table: 2 shards fit

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.embedding import EmbeddingLookupCache, ShardedEmbedding
    from mxnet_tpu.io import LibSVMIter

    workdir = tempfile.mkdtemp(prefix="emb_bench_")
    data = os.path.join(workdir, "clicks.svm")
    gen_libsvm(data, n_rows, vocab, feats_per_row=12)
    d0 = telemetry.counter("io.libsvm.discarded_rows").value
    it = LibSVMIter(data, data_shape=vocab, batch_size=args.batch_size,
                    last_batch_handle="discard")
    discarded = telemetry.counter("io.libsvm.discarded_rows").value - d0

    sb0 = telemetry.counter("embedding.sparse_bytes").value
    db0 = telemetry.counter("embedding.dense_equiv_bytes").value
    rp0 = telemetry.counter("embedding.rows_pulled").value
    rq0 = telemetry.counter("embedding.rows_pushed").value

    emb = ShardedEmbedding("ctr", vocab, dim, num_shards=2, seed=0)
    emb.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    per_shard = max(emb.part.local_count(s) * dim * 4
                    for s in range(emb.num_shards))
    rng = onp.random.RandomState(7)
    w = (rng.randn(dim) * 0.01).astype(onp.float32)
    b = 0.0

    steps, losses = train(emb, it, w, b, lr=0.1, steps_cap=steps_cap)
    # re-read the discard counter: the iterator ticks it per epoch end
    discarded = telemetry.counter("io.libsvm.discarded_rows").value - d0
    sparse_bytes = telemetry.counter("embedding.sparse_bytes").value - sb0
    dense_equiv = telemetry.counter(
        "embedding.dense_equiv_bytes").value - db0
    wire_ratio = sparse_bytes / dense_equiv if dense_equiv else None

    # -- kill-and-resume: 2-shard save -> kill -> 1-shard restore ----------
    ckdir = os.path.join(workdir, "ckpt")
    emb.save_checkpoint(ckdir, block=True)
    pre_kill = emb.dump()
    emb.close()                                    # kill the shard servers
    emb2 = ShardedEmbedding("ctr", vocab, dim, num_shards=1, seed=123)
    emb2.load_checkpoint(ckdir)
    onp.testing.assert_array_equal(emb2.dump(), pre_kill)
    restore_match = True

    # -- serving leg: repeated-user batch through the lookup tier ----------
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.serving import InferenceEngine
    net = gluon.nn.Dense(1, in_units=dim)
    net.initialize()
    cache = EmbeddingLookupCache(emb2, capacity=256)
    eng = InferenceEngine(net, example_shape=(dim,), dtype="float32")
    eng.attach_embedding(cache)
    repeat_user = onp.int64(3)                     # the same user, 4 hits
    got = None
    for _ in range(5):
        got = eng.infer(onp.array(repeat_user))
    want = net(nd.array(pre_kill[int(repeat_user)][None])).asnumpy()[0]
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    cache_hits = cache.stats()["hits"]
    emb2.close()

    table_nbytes = vocab * dim * 4
    ok = (table_nbytes > allot
          and per_shard <= allot
          and wire_ratio is not None and wire_ratio <= 0.2
          and restore_match
          and cache_hits >= 1
          and losses[-1] <= losses[0])
    result = {
        "table_nbytes": table_nbytes,
        "device_allotment_bytes": allot,
        "per_shard_nbytes": per_shard,
        "num_shards": 2,
        "steps": steps,
        "loss_first": round(losses[0], 6),
        "loss_last": round(losses[-1], 6),
        "wire_ratio": round(wire_ratio, 6) if wire_ratio else None,
        "rows_pulled":
            telemetry.counter("embedding.rows_pulled").value - rp0,
        "rows_pushed":
            telemetry.counter("embedding.rows_pushed").value - rq0,
        "restore_match": restore_match,
        "serving_cache_hits": cache_hits,
        "discarded_rows": discarded,
        "ok": ok,
    }
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
