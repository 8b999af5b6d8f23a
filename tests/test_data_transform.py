"""SPMDTrainer(data_transform=...): device-side input preprocessing
(uint8 wire format) applies identically in step(), run_steps(), and
predict().  Shipping f32 pixels host->device costs 4x the bytes of
uint8 + on-device normalize (bench.py datafed row)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import loss as gloss, nn
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import SPMDTrainer, make_mesh


def _net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.initializer.Xavier())
    net(NDArray(onp.zeros((1, 8), onp.float32)))
    return net


def test_transform_matches_host_preprocessing():
    import jax.numpy as jnp
    rng = onp.random.RandomState(0)
    raw = rng.randint(0, 256, (16, 8)).astype(onp.uint8)
    label = rng.randint(0, 4, (16,)).astype(onp.float32)

    def tf(d):
        return d.astype(jnp.float32) / 127.5 - 1.0

    net_a = _net()
    net_b = _net()
    # identical init (fresh host copies: step() donates param buffers,
    # so the two trainers must not share arrays)
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        pb.set_data(NDArray(pa.data().asnumpy().copy()))
    ta = SPMDTrainer(net_a, gloss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     mesh=make_mesh({"dp": 1}), data_transform=tf)
    tb = SPMDTrainer(net_b, gloss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     mesh=make_mesh({"dp": 1}))
    host = (raw.astype(onp.float32) / 127.5 - 1.0)
    la = ta.step(raw, label)
    lb = tb.step(host, label)
    onp.testing.assert_allclose(la.asnumpy(), lb.asnumpy(), rtol=1e-6)
    # predict applies the SAME transform (a uint8-wire trainer must not
    # see raw pixels at inference)
    pa = ta.predict(raw).asnumpy()
    pb = tb.predict(host).asnumpy()
    onp.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)


def test_transform_in_fused_window():
    import jax.numpy as jnp
    rng = onp.random.RandomState(1)
    raw = rng.randint(0, 256, (3, 8, 8)).astype(onp.uint8)   # (W,B,F)
    label = rng.randint(0, 4, (3, 8)).astype(onp.float32)

    def tf(d):
        return d.astype(jnp.float32) / 127.5 - 1.0

    net = _net()
    tr = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     mesh=make_mesh({"dp": 1}), data_transform=tf)
    losses = tr.run_steps(raw, label, 3, per_step_data=True)
    assert losses.shape == (3,)
    assert bool(onp.all(onp.isfinite(losses.asnumpy())))
