"""Transformer LM family (gluon/model_zoo/transformer.py).

The TPU build's long-context flagship: causal flash attention in a
gluon model, trainable eagerly and under SPMDTrainer.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import (MultiHeadAttention, TransformerLM,
                                       get_transformer_lm)


def _toks(rng, b, s, vocab=50):
    return mx.nd.array(rng.randint(0, vocab, (b, s)).astype(onp.int32))


def _lm(units=32, layers=2, heads=4, vocab=50, use_flash=False, **kw):
    net = get_transformer_lm(vocab_size=vocab, units=units,
                             num_layers=layers, num_heads=heads,
                             max_len=64, use_flash=use_flash, **kw)
    net.initialize(init=mx.initializer.Xavier())
    return net


def test_causality():
    """Logits at position t must not change when future tokens change."""
    rng = onp.random.RandomState(0)
    net = _lm()
    a = rng.randint(0, 50, (1, 12)).astype(onp.int32)
    b = a.copy()
    b[0, 8:] = rng.randint(0, 50, 4)        # perturb the future
    out_a = net(mx.nd.array(a)).asnumpy()
    out_b = net(mx.nd.array(b)).asnumpy()
    onp.testing.assert_allclose(out_a[0, :8], out_b[0, :8],
                                rtol=1e-4, atol=1e-5)
    assert abs(out_a[0, 8:] - out_b[0, 8:]).max() > 1e-3


def test_flash_matches_reference_attention():
    rng = onp.random.RandomState(1)
    toks = _toks(rng, 2, 16)
    net_ref = _lm(use_flash=False)
    net_flash = _lm(use_flash=True)
    net_ref(toks)                      # materialize deferred params
    net_flash(toks)
    # same params
    ref_params = net_ref.collect_params()
    for k, p in net_flash.collect_params().items():
        p.set_data(ref_params[k].data())
    onp.testing.assert_allclose(net_flash(toks).asnumpy(),
                                net_ref(toks).asnumpy(),
                                rtol=1e-3, atol=1e-3)


def test_training_reduces_loss():
    rng = onp.random.RandomState(2)
    net = _lm(units=32, layers=1)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    toks = _toks(rng, 4, 12)
    inp = toks.slice_axis(axis=1, begin=0, end=11)
    tgt = toks.slice_axis(axis=1, begin=1, end=12)
    first = last = None
    for _ in range(15):
        with autograd.record():
            logits = net(inp)
            L = loss_fn(logits.reshape((-1, 50)), tgt.reshape((-1,)))
        L.backward()
        tr.step(4)
        v = float(L.mean().asnumpy())
        first = first if first is not None else v
        last = v
    assert last < first * 0.8, (first, last)


def test_spmd_trainer_on_mesh():
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    rng = onp.random.RandomState(3)
    net = _lm(units=32, layers=1)
    net(_toks(rng, 1, 11))             # materialize deferred params
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return loss_fn(logits.reshape((-1, 50)), labels.reshape((-1,)))

    trainer = SPMDTrainer(net, lm_loss, optimizer="adam",
                          optimizer_params={"learning_rate": 1e-2},
                          mesh=make_mesh({"dp": 4}))
    toks = rng.randint(0, 50, (8, 12)).astype(onp.int32)
    first = last = None
    for _ in range(6):
        loss = trainer.step(toks[:, :11], toks[:, 1:].astype(onp.float32))
        v = float(loss.asnumpy())
        first = first if first is not None else v
        last = v
    assert last < first, (first, last)


def test_tied_weights_and_limits():
    rng = onp.random.RandomState(4)
    net = _lm(tie_weights=True)
    out = net(_toks(rng, 1, 8))
    assert out.shape == (1, 8, 50)
    with pytest.raises(MXNetError, match="exceeds max_len"):
        net(_toks(rng, 1, 65))
    with pytest.raises(MXNetError, match="divisible"):
        MultiHeadAttention(30, 4)


def test_spmd_trainer_dp_x_tp_matches_replicated():
    """Combined data + tensor parallel training of the Transformer LM:
    dp2×tp2 with column/row-sharded FFN and attention projections must
    match the replicated-dp numerics (GSPMD inserts the collectives)."""
    from jax.sharding import PartitionSpec
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return loss_fn(logits.reshape((-1, 50)), labels.reshape((-1,)))

    def build(seed):
        mx.random.seed(seed)
        net = _lm(units=32, layers=2)
        net(_toks(onp.random.RandomState(0), 1, 11))
        return net

    rng = onp.random.RandomState(7)
    toks = rng.randint(0, 50, (8, 12)).astype(onp.int32)

    def train(net, mesh, shard_tp):
        if shard_tp:
            for k, p in net.collect_params().items():
                # column-parallel: first FFN / qkv projections (out, in)
                if p._sharding is None and k.endswith("weight") \
                        and p.shape is not None and len(p.shape) == 2:
                    if "ffn1" in k or "qkv" in k:
                        p.shard(PartitionSpec("tp", None))
                    elif "ffn2" in k or "out_proj" in k:
                        p.shard(PartitionSpec(None, "tp"))
        tr = SPMDTrainer(net, lm_loss, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         mesh=mesh)
        return [float(tr.step(toks[:, :11],
                              toks[:, 1:].astype(onp.float32)).asnumpy())
                for _ in range(3)]

    ref = train(build(5), make_mesh({"dp": 4}), shard_tp=False)
    tp = train(build(5), make_mesh({"dp": 2, "tp": 2}), shard_tp=True)
    onp.testing.assert_allclose(tp, ref, rtol=2e-4, atol=2e-5)


def test_multi_head_attention_gqa_block():
    """MultiHeadAttention(num_kv_heads=...) — GQA projections with
    shared KV heads; flash and reference paths agree."""
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention
    from mxnet_tpu.ndarray import NDArray

    rng = onp.random.RandomState(0)
    x = NDArray(rng.randn(2, 16, 32).astype("float32"))
    mx.random.seed(0)
    att = MultiHeadAttention(32, 8, causal=True, num_kv_heads=2,
                             use_flash=True)
    att.initialize(init=mx.initializer.Xavier())
    out = att(x)
    assert out.shape == (2, 16, 32)
    # kv projection is group-sized: units + 2 * (units/heads * kv_heads)
    assert att.qkv.weight.shape[0] == 32 + 2 * (32 // 8) * 2

    att_ref = MultiHeadAttention(32, 8, causal=True, num_kv_heads=2,
                                 use_flash=False)
    att_ref.initialize()
    # copy params by position
    pa = list(att.collect_params().values())
    pb = list(att_ref.collect_params().values())
    for a, b in zip(pa, pb):
        b.set_data(a.data())
    onp.testing.assert_allclose(att_ref(x).asnumpy(), out.asnumpy(),
                                rtol=2e-4, atol=2e-4)


def test_generate_device_side_decode():
    """generate(): one-jit lax.scan decode — greedy deterministic,
    matches per-step eager argmax decoding exactly."""
    from mxnet_tpu.gluon.model_zoo.transformer import generate
    from mxnet_tpu.ndarray import NDArray

    mx.random.seed(0)
    net = _lm(units=32, layers=1)
    net(_toks(onp.random.RandomState(0), 1, 8))
    prompt = onp.array([[3, 7, 11]], onp.int32)

    out = generate(net, prompt, max_new_tokens=5, temperature=0)
    arr = out.asnumpy()
    assert arr.shape == (1, 8)
    onp.testing.assert_array_equal(arr[0, :3], prompt[0])

    # oracle: eager greedy loop re-running the full forward per step
    seq = list(prompt[0])
    for _ in range(5):
        logits = net(NDArray(onp.asarray([seq], onp.int32))).asnumpy()
        seq.append(int(logits[0, -1].argmax()))
    onp.testing.assert_array_equal(arr[0], seq)

    # sampling path runs and respects the prompt
    out2 = generate(net, prompt, max_new_tokens=4, temperature=1.0,
                    top_k=5, seed=0)
    assert out2.shape == (1, 7)
    onp.testing.assert_array_equal(out2.asnumpy()[0, :3], prompt[0])
    # seeded sampling is reproducible
    out3 = generate(net, prompt, max_new_tokens=4, temperature=1.0,
                    top_k=5, seed=0)
    onp.testing.assert_array_equal(out2.asnumpy(), out3.asnumpy())


@pytest.mark.parametrize("sp_mode", ["ring", "ring_flash", "ulysses",
                                     "ulysses_flash"])
def test_sequence_parallel_training(sp_mode):
    """Long-context path end to end: MultiHeadAttention(ring_mesh=...,
    sp_mode=...) + SPMDTrainer(seq_axis=1) trains with the sequence
    axis sharded over 'sp' under BOTH context-parallel schemes;
    numerics match the replicated (flashless) run."""
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    V, E, S, B = 16, 16, 8, 4
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return loss_fn(logits.reshape((-1, V)), labels.reshape((-1,)))

    # "ulysses_flash" = sp_mode "ulysses" with use_flash=True: the MHA
    # wiring that routes the local post-all-to-all attention through
    # the Pallas kernel
    layer_mode = "ulysses" if sp_mode == "ulysses_flash" else sp_mode
    layer_flash = sp_mode == "ulysses_flash"

    def build(ring_mesh):
        mx.random.seed(3)
        net = gnn.HybridSequential()
        net.add(gnn.Embedding(V, E),
                MultiHeadAttention(E, 4, causal=True,
                                   use_flash=layer_flash,
                                   ring_mesh=ring_mesh,
                                   sp_mode=layer_mode),
                gnn.Dense(V, flatten=False))
        net.initialize(init=mx.initializer.Xavier())
        net(NDArray(onp.zeros((1, S), onp.int32)))
        return net

    rng = onp.random.RandomState(0)
    toks = rng.randint(0, V, (B, S + 1)).astype(onp.int32)

    # replicated reference (dp only)
    ref_net = build(None)
    ref_tr = SPMDTrainer(ref_net, lm_loss, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         mesh=make_mesh({"dp": 2}))
    ref_losses = [float(ref_tr.step(
        toks[:, :S], toks[:, 1:].astype(onp.float32)).asnumpy())
        for _ in range(3)]

    # sequence-parallel run: dp2×sp4, sequence axis sharded
    sp_mesh = make_mesh({"dp": 2, "sp": 4})
    sp_net = build(sp_mesh)
    sp_tr = SPMDTrainer(sp_net, lm_loss, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1},
                        mesh=sp_mesh, seq_axis=1)
    sp_losses = [float(sp_tr.step(
        toks[:, :S], toks[:, 1:].astype(onp.float32)).asnumpy())
        for _ in range(3)]

    onp.testing.assert_allclose(sp_losses, ref_losses, rtol=2e-4,
                                atol=2e-5)


def test_vision_transformer_trains():
    """ViT: patch-embed + encoder + CLS head; trains on separable
    synthetic images via SPMDTrainer."""
    from mxnet_tpu.gluon.model_zoo.transformer import get_vit
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mx.random.seed(0)
    vit = get_vit(image_size=16, patch_size=4, classes=4, units=32,
                  num_layers=2, num_heads=4)
    vit.initialize(init=mx.initializer.Xavier())
    vit(NDArray(onp.zeros((1, 3, 16, 16), onp.float32)))

    rng = onp.random.RandomState(0)
    Y = rng.randint(0, 4, size=64).astype("float32")
    X = rng.rand(64, 3, 16, 16).astype("float32") * 0.1
    for i, y in enumerate(Y.astype(int)):
        X[i, 0, y * 4:y * 4 + 4, :] += 0.9

    tr = SPMDTrainer(vit, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3},
                     # two devices: a step of the interpreted flash
                     # kernel takes 0.4 s there and 1.7 s on all eight,
                     # which share this host's cores
                     mesh=make_mesh({"dp": 2}))
    first = last = None
    for epoch in range(8):
        for i in range(0, 64, 16):
            loss = tr.step(X[i:i + 16], Y[i:i + 16])
            v = float(loss.asnumpy())
            first = v if first is None else first
            last = v
    assert last < first * 0.7, (first, last)


def test_generate_cached_matches_uncached():
    """KV-cached decode (O(L) per token) must reproduce the full
    re-forward greedy decode exactly, for flash and plain nets, batched."""
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.ndarray import NDArray

    for use_flash in (False, True):
        mx.random.seed(0)
        net = get_transformer_lm(50, units=32, num_layers=2, num_heads=4,
                                 max_len=24, use_flash=use_flash)
        net.initialize(init=mx.initializer.Xavier())
        net(NDArray(onp.zeros((1, 4), onp.int32)))
        prompt = onp.array([[3, 7, 11], [1, 2, 9]], onp.int32)
        a = net.generate(prompt, 6, temperature=0).asnumpy()
        b = net.generate_cached(prompt, 6, temperature=0).asnumpy()
        onp.testing.assert_array_equal(a, b)

    # seeded sampling reproducible through the cached path
    out1 = net.generate_cached(prompt, 5, temperature=1.0, top_k=5,
                               seed=0).asnumpy()
    out2 = net.generate_cached(prompt, 5, temperature=1.0, top_k=5,
                               seed=0).asnumpy()
    onp.testing.assert_array_equal(out1, out2)


def test_generate_seeded_sampling_cached_matches_uncached():
    """Same seed → same sampled tokens on both decode paths (the cached
    path must not consume entropy during prefill)."""
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.ndarray import NDArray

    mx.random.seed(1)
    net = get_transformer_lm(50, units=32, num_layers=1, num_heads=4,
                             max_len=24, use_flash=False)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 4), onp.int32)))
    prompt = onp.array([[3, 7, 11]], onp.int32)
    a = net.generate(prompt, 6, temperature=1.0, top_k=8,
                     seed=42).asnumpy()
    b = net.generate_cached(prompt, 6, temperature=1.0, top_k=8,
                            seed=42).asnumpy()
    onp.testing.assert_array_equal(a, b)


def test_generate_cached_gqa():
    """Cached decode through GQA blocks: matches the full re-forward
    decode exactly (cache stores only hkv shared heads)."""
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.ndarray import NDArray

    mx.random.seed(2)
    net = get_transformer_lm(50, units=32, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_len=24, use_flash=False)
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 4), onp.int32)))
    prompt = onp.array([[5, 9, 2]], onp.int32)
    a = net.generate(prompt, 6, temperature=0).asnumpy()
    b = net.generate_cached(prompt, 6, temperature=0).asnumpy()
    onp.testing.assert_array_equal(a, b)
