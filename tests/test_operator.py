"""Operator correctness against numpy oracle (parity:
tests/python/unittest/test_operator.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def test_fully_connected():
    x = nd.array(onp.random.randn(4, 8).astype("float32"))
    w = nd.array(onp.random.randn(3, 8).astype("float32"))
    b = nd.array(onp.random.randn(3).astype("float32"))
    out = nd.FullyConnected(x, w, b, num_hidden=3)
    expect = x.asnumpy() @ w.asnumpy().T + b.asnumpy()
    assert_almost_equal(out, expect, rtol=1e-4)
    out2 = nd.FullyConnected(x, w, None, num_hidden=3, no_bias=True)
    assert_almost_equal(out2, x.asnumpy() @ w.asnumpy().T, rtol=1e-4)


def test_convolution_shapes():
    x = nd.array(onp.random.randn(2, 3, 8, 8).astype("float32"))
    w = nd.array(onp.random.randn(4, 3, 3, 3).astype("float32"))
    b = nd.array(onp.zeros(4, "float32"))
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4)
    assert out.shape == (2, 4, 6, 6)
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4, pad=(1, 1))
    assert out.shape == (2, 4, 8, 8)
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4, stride=(2, 2),
                         pad=(1, 1))
    assert out.shape == (2, 4, 4, 4)


def test_convolution_vs_manual():
    # 1x1 conv == matmul over channels
    x = onp.random.randn(2, 3, 5, 5).astype("float32")
    w = onp.random.randn(4, 3, 1, 1).astype("float32")
    out = nd.Convolution(nd.array(x), nd.array(w), None, kernel=(1, 1),
                         num_filter=4, no_bias=True)
    expect = onp.einsum("nchw,oc->nohw", x, w[:, :, 0, 0])
    assert_almost_equal(out, expect, rtol=1e-4)


def test_grouped_and_depthwise_conv():
    x = nd.array(onp.random.randn(1, 4, 6, 6).astype("float32"))
    w = nd.array(onp.random.randn(4, 1, 3, 3).astype("float32"))
    out = nd.Convolution(x, w, None, kernel=(3, 3), num_filter=4,
                         num_group=4, no_bias=True)
    assert out.shape == (1, 4, 4, 4)
    # each output channel = conv of corresponding input channel
    from scipy.signal import correlate2d
    for c in range(4):
        expect = correlate2d(x.asnumpy()[0, c], w.asnumpy()[c, 0], "valid")
        assert_almost_equal(out.asnumpy()[0, c], expect, rtol=1e-3, atol=1e-4)


def test_deconvolution():
    x = nd.array(onp.random.randn(1, 2, 4, 4).astype("float32"))
    w = nd.array(onp.random.randn(2, 3, 3, 3).astype("float32"))
    out = nd.Deconvolution(x, w, None, kernel=(3, 3), num_filter=3,
                           stride=(2, 2), no_bias=True)
    # out = (i-1)*s - 2p + k = 3*2 + 3 = 9
    assert out.shape == (1, 3, 9, 9)
    out = nd.Deconvolution(x, w, None, kernel=(3, 3), num_filter=3,
                           stride=(2, 2), pad=(1, 1), adj=(1, 1),
                           no_bias=True)
    assert out.shape == (1, 3, 8, 8)


def test_pooling():
    x = nd.array(onp.arange(16, dtype="float32").reshape(1, 1, 4, 4))
    out = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    assert_almost_equal(out, [[[[5, 7], [13, 15]]]])
    out = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    assert_almost_equal(out, [[[[2.5, 4.5], [10.5, 12.5]]]])
    out = nd.Pooling(x, kernel=(2, 2), global_pool=True, pool_type="max")
    assert_almost_equal(out, [[[[15.0]]]])
    out = nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pool_type="max",
                     pooling_convention="full")
    assert out.shape == (1, 1, 2, 2)


def test_batchnorm():
    x = onp.random.randn(4, 3, 5, 5).astype("float32")
    gamma = onp.random.rand(3).astype("float32") + 0.5
    beta = onp.random.randn(3).astype("float32")
    mean = onp.zeros(3, "float32")
    var = onp.ones(3, "float32")
    out, m, v = nd.BatchNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                             nd.array(mean), nd.array(var), fix_gamma=False,
                             use_batch_stats=True, eps=1e-5)
    bm = x.mean(axis=(0, 2, 3))
    bv = x.var(axis=(0, 2, 3))
    expect = (x - bm[None, :, None, None]) / onp.sqrt(
        bv[None, :, None, None] + 1e-5) * gamma[None, :, None, None] \
        + beta[None, :, None, None]
    assert_almost_equal(out, expect, rtol=1e-3, atol=1e-4)
    assert_almost_equal(m, bm, rtol=1e-4)


# -- BatchNorm's training path: one pass, float32 sums, its own gradient.
#    The formulation it replaced stays as the reference
#    (batch_norm_two_pass.py).

def _bn_two_pass(x, gamma, beta, *, eps, axis, fix_gamma):
    from batch_norm_two_pass import two_pass_batch_norm
    return two_pass_batch_norm(x, gamma, beta, eps, axis, fix_gamma)


def _bn_op(x, gamma, beta, **kw):
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _batch_norm
    c = gamma.shape[0]
    return _batch_norm(x, gamma, beta, jnp.zeros(c, x.dtype),
                       jnp.ones(c, x.dtype), use_batch_stats=True, **kw)


def _bn_case(axis, c=16, seed=0):
    import jax
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (8, c, 14, 14) if axis == 1 else (8, 14, 14, c)
    x = jax.random.normal(k[0], shape) * 1.5 + 0.7
    gamma = jax.random.uniform(k[1], (c,)) + 0.5
    beta = jax.random.normal(k[2], (c,))
    # cotangents of out, mean and var
    w = (jax.random.normal(k[3], shape), jax.random.normal(k[4], (c,)),
         jax.random.normal(k[5], (c,)))
    return x, gamma, beta, w


def _bn_all(fn, args, w):
    """(out, mean, var, dx, dgamma, dbeta) of ``fn`` in float32, the
    gradients those of ``sum(w_o out) + sum(w_m mean) + sum(w_v var)``."""
    import jax
    import jax.numpy as jnp

    def loss(x, gamma, beta):
        return sum((o.astype(jnp.float32) * wi).sum()
                   for o, wi in zip(fn(x, gamma, beta), w))

    outs = list(fn(*args)) + list(jax.grad(loss, (0, 1, 2))(*args))
    return [o.astype(jnp.float32) for o in outs]


def _scaled_errs(got, ref):
    import jax.numpy as jnp
    return [float(jnp.abs(g - r).max() / jnp.maximum(jnp.abs(r).max(), 1e-30))
            for g, r in zip(got, ref)]


@pytest.mark.parametrize("fix_gamma", [False, True])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batchnorm_train_matches_two_pass_reference(dtype, axis, fix_gamma):
    """Output, returned mean and var, dx, dgamma and dbeta against the
    two-pass formulation evaluated in float32 on the same (rounded)
    inputs: float32 to 1e-5 of each reference's largest value, bfloat16
    no worse than the two-pass formulation itself does in bfloat16."""
    import jax.numpy as jnp
    x, gamma, beta, w = _bn_case(axis)
    kw = dict(eps=1e-5, axis=axis, fix_gamma=fix_gamma)
    low = [a.astype(dtype) for a in (x, gamma, beta)]
    ref = _bn_all(lambda *a: _bn_two_pass(*a, **kw),
                  [a.astype(jnp.float32) for a in low], w)
    got_raw = _bn_op(*low, **kw)
    assert [o.dtype for o in got_raw] == [jnp.dtype(dtype)] * 3
    assert got_raw[1].shape == got_raw[2].shape == gamma.shape
    got = _bn_all(lambda *a: _bn_op(*a, **kw), low, w)
    errs = _scaled_errs(got, ref)
    if dtype == "float32":
        assert max(errs) < 1e-5, errs
    else:
        old = _scaled_errs(
            _bn_all(lambda *a: _bn_two_pass(*a, **kw), low, w), ref)
        # one bfloat16 rounding of the result itself is 2**-9
        for name, new_e, old_e in zip(
                ("out", "mean", "var", "dx", "dgamma", "dbeta"), errs, old):
            assert new_e <= max(old_e, 2.0 ** -8), (name, new_e, old_e)
    if fix_gamma:
        assert float(jnp.abs(got[4]).max()) == 0.0
    assert float(got[2].min()) >= 0.0


def test_batchnorm_one_pass_variance_survives_a_large_mean():
    """A channel whose mean is 30 times its deviation: s2/n - mean^2
    cancels five digits of float32's seven; a constant channel cancels
    all of them and must not come out negative."""
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4, 14, 14))
    x = x * jnp.array([1.0, 0.1, 2.0, 0.0]).reshape(1, 4, 1, 1) \
        + jnp.array([30.0, -3.0, 0.5, 3.3]).reshape(1, 4, 1, 1)
    out, mean, var = _bn_op(x, jnp.ones(4), jnp.zeros(4), eps=1e-5, axis=1,
                            fix_gamma=False)
    ref = onp.asarray(x, "float64").var(axis=(0, 2, 3))
    got = onp.asarray(var, "float64")
    assert (got >= 0).all()
    onp.testing.assert_allclose(got[:3], ref[:3], rtol=1e-3)
    assert got[3] <= 1e-5
    assert bool(jnp.isfinite(out).all())
    onp.testing.assert_allclose(
        onp.asarray(out[:, :3]).std(axis=(0, 2, 3)), 1.0, rtol=2e-3)


@pytest.mark.parametrize("which", ["mean", "var"])
def test_batchnorm_grad_through_returned_statistics(which):
    """``output_mean_var`` users differentiate the returned statistics:
    d mean / dx = 1/n and d var / dx = 2 (x - mean) / n."""
    import jax
    import jax.numpy as jnp
    x, gamma, beta, _ = _bn_case(1, c=4, seed=1)
    pick = 1 if which == "mean" else 2
    kw = dict(eps=1e-5, axis=1, fix_gamma=False)
    wv = jnp.arange(1.0, 5.0)

    def loss(fn):
        return lambda x_: (fn(x_, gamma, beta, **kw)[pick] * wv).sum()

    got = jax.grad(loss(_bn_op))(x)
    ref = jax.grad(loss(_bn_two_pass))(x)
    n = x.size // 4
    mean = x.mean(axis=(0, 2, 3)).reshape(1, 4, 1, 1)
    by_hand = wv.reshape(1, 4, 1, 1) * (
        jnp.ones_like(x) / n if which == "mean" else 2 * (x - mean) / n)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(got - ref).max()) < 1e-5 * scale
    assert float(jnp.abs(got - by_hand).max()) < 1e-5 * scale


def test_batchnorm_fix_gamma_gives_no_gamma_gradient():
    x = nd.array(onp.random.randn(4, 3, 5, 5).astype("float32"))
    gamma = nd.array(onp.random.rand(3).astype("float32") + 0.5)
    beta = nd.array(onp.random.randn(3).astype("float32"))
    for a in (x, gamma, beta):
        a.attach_grad()
    with autograd.record():
        out, _, _ = nd.BatchNorm(x, gamma, beta, nd.zeros((3,)),
                                 nd.ones((3,)), fix_gamma=True,
                                 use_batch_stats=True)
        loss = (out * out * out).sum()
    loss.backward()
    assert (gamma.grad.asnumpy() == 0).all()
    assert onp.abs(beta.grad.asnumpy()).max() > 0
    assert onp.abs(x.grad.asnumpy()).max() > 0
    # gamma is ignored on the way in as well
    assert_almost_equal(out.asnumpy().std(axis=(0, 2, 3)), onp.ones(3),
                        rtol=1e-2)


@pytest.mark.parametrize("axis", [1, -1])
def test_batchnorm_global_stats_path_is_unchanged(axis):
    """Inference and ``use_global_stats`` keep the old expression, bit
    for bit."""
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops.nn import _batch_norm
    x, gamma, beta, _ = _bn_case(axis)
    mm = jnp.linspace(-1.0, 1.0, 16)
    mv = jnp.linspace(0.5, 2.0, 16)
    shape = [1] * x.ndim
    shape[axis] = -1
    for fix_gamma in (False, True):
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        want = (x - mm.reshape(shape)) * (
            lax.rsqrt(mv + 1e-3) * g).reshape(shape) + beta.reshape(shape)
        for flags in (dict(use_batch_stats=False),
                      dict(use_batch_stats=True, use_global_stats=True)):
            out, mean, var = _batch_norm(x, gamma, beta, mm, mv, eps=1e-3,
                                         axis=axis, fix_gamma=fix_gamma,
                                         **flags)
            assert onp.array_equal(onp.asarray(out), onp.asarray(want))
            assert mean is mm and var is mv


def test_gluon_batchnorm_last_axis_keeps_per_channel_statistics():
    from mxnet_tpu.gluon import nn
    bn = nn.BatchNorm(axis=-1, in_channels=3)
    bn.initialize()
    x = onp.random.randn(4, 5, 5, 3).astype("float32") * [1.0, 2.0, 3.0] \
        + [0.0, 5.0, -5.0]
    with autograd.record():
        out = bn(nd.array(x.astype("float32")))
    assert_almost_equal(out.asnumpy().mean(axis=(0, 1, 2)), onp.zeros(3),
                        atol=1e-4)
    assert_almost_equal(out.asnumpy().std(axis=(0, 1, 2)), onp.ones(3),
                        rtol=1e-3)
    assert_almost_equal(bn.running_mean.data().asnumpy(),
                        0.1 * x.mean(axis=(0, 1, 2)), rtol=1e-3, atol=1e-5)


def test_layernorm():
    x = onp.random.randn(4, 10).astype("float32")
    g = onp.ones(10, "float32")
    b = onp.zeros(10, "float32")
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b))
    mu = x.mean(-1, keepdims=True)
    sd = onp.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out, (x - mu) / sd, rtol=1e-4, atol=1e-5)


def test_softmax_family():
    x = onp.random.randn(3, 5).astype("float32")
    out = nd.softmax(nd.array(x))
    e = onp.exp(x - x.max(-1, keepdims=True))
    assert_almost_equal(out, e / e.sum(-1, keepdims=True), rtol=1e-5)
    lout = nd.log_softmax(nd.array(x))
    assert_almost_equal(lout, onp.log(e / e.sum(-1, keepdims=True)),
                        rtol=1e-4, atol=1e-5)
    length = nd.array([2, 5, 3])
    mout = nd.softmax(nd.array(x), length, use_length=True, axis=-1)
    mnp = mout.asnumpy()
    assert mnp[0, 2:].sum() == 0
    assert abs(mnp[0, :2].sum() - 1) < 1e-5


def test_activations():
    x = onp.array([-2.0, -0.5, 0.0, 0.5, 2.0], "float32")
    assert_almost_equal(nd.Activation(nd.array(x), act_type="relu"),
                        onp.maximum(x, 0))
    assert_almost_equal(nd.Activation(nd.array(x), act_type="sigmoid"),
                        1 / (1 + onp.exp(-x)), rtol=1e-5)
    assert_almost_equal(nd.Activation(nd.array(x), act_type="tanh"),
                        onp.tanh(x), rtol=1e-5)
    assert_almost_equal(nd.Activation(nd.array(x), act_type="softrelu"),
                        onp.log1p(onp.exp(x)), rtol=1e-5)
    assert_almost_equal(nd.LeakyReLU(nd.array(x), act_type="leaky",
                                     slope=0.1),
                        onp.where(x > 0, x, 0.1 * x), rtol=1e-5)
    assert_almost_equal(nd.LeakyReLU(nd.array(x), act_type="elu", slope=1.0),
                        onp.where(x > 0, x, onp.expm1(x)), rtol=1e-5)


def test_dropout_op():
    x = nd.ones((1000,))
    with autograd.record():  # train mode
        from mxnet_tpu.ops.random import next_key
        out = nd.Dropout(x, nd.NDArray(next_key()), p=0.5)
    kept = (out.asnumpy() != 0).mean()
    assert 0.4 < kept < 0.6
    assert_almost_equal(out.asnumpy()[out.asnumpy() != 0],
                        onp.full((out.asnumpy() != 0).sum(), 2.0))


def test_elementwise_broadcast():
    a = onp.random.randn(3, 1, 4).astype("float32")
    b = onp.random.randn(1, 5, 4).astype("float32")
    out = nd.broadcast_add(nd.array(a), nd.array(b))
    assert_almost_equal(out, a + b, rtol=1e-5)
    out = nd.broadcast_mul(nd.array(a), nd.array(b))
    assert_almost_equal(out, a * b, rtol=1e-5)
    out = nd.broadcast_maximum(nd.array(a), nd.array(b))
    assert_almost_equal(out, onp.maximum(a, b))


def test_dot_batchdot():
    a = onp.random.randn(3, 4).astype("float32")
    b = onp.random.randn(4, 5).astype("float32")
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)), a @ b, rtol=1e-4)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b.T), transpose_b=True),
                        a @ b, rtol=1e-4)
    ba = onp.random.randn(2, 3, 4).astype("float32")
    bb = onp.random.randn(2, 4, 5).astype("float32")
    assert_almost_equal(nd.batch_dot(nd.array(ba), nd.array(bb)), ba @ bb,
                        rtol=1e-4)


def test_topk_sort():
    x = onp.array([[3.0, 1.0, 2.0], [0.0, 5.0, 4.0]], "float32")
    idx = nd.topk(nd.array(x), k=2)
    assert_almost_equal(idx, [[0, 2], [1, 2]])
    vals = nd.topk(nd.array(x), k=2, ret_typ="value")
    assert_almost_equal(vals, [[3, 2], [5, 4]])
    s = nd.sort(nd.array(x), axis=1)
    assert_almost_equal(s, onp.sort(x, 1))
    a = nd.argsort(nd.array(x), axis=1)
    assert_almost_equal(a, onp.argsort(x, 1).astype("f"))


def test_sequence_ops():
    x = onp.arange(24, dtype="float32").reshape(4, 2, 3)  # (T, N, C)
    length = nd.array([2, 4])
    out = nd.SequenceMask(nd.array(x), length, use_sequence_length=True,
                          value=-1.0)
    outn = out.asnumpy()
    assert (outn[2:, 0] == -1).all()
    assert (outn[:, 1] == x[:, 1]).all()
    last = nd.SequenceLast(nd.array(x), length, use_sequence_length=True)
    assert_almost_equal(last, onp.stack([x[1, 0], x[3, 1]]))
    rev = nd.SequenceReverse(nd.array(x), length, use_sequence_length=True)
    revn = rev.asnumpy()
    assert_almost_equal(revn[0, 0], x[1, 0])
    assert_almost_equal(revn[1, 0], x[0, 0])
    assert_almost_equal(revn[0, 1], x[3, 1])


def test_embedding():
    w = onp.random.randn(10, 4).astype("float32")
    idx = nd.array([1, 3, 1])
    out = nd.Embedding(idx, nd.array(w), input_dim=10, output_dim=4)
    assert_almost_equal(out, w[[1, 3, 1]])


def test_grad_of_conv_pool_dense():
    x = nd.array(onp.random.randn(2, 3, 6, 6).astype("float32") * 0.5)
    w = nd.array(onp.random.randn(4, 3, 3, 3).astype("float32") * 0.3)

    def f(x_, w_):
        c = nd.Convolution(x_, w_, None, kernel=(3, 3), num_filter=4,
                           no_bias=True)
        p = nd.Pooling(c, kernel=(2, 2), stride=(2, 2), pool_type="avg")
        return p * p

    check_numeric_gradient(f, [x, w], eps=1e-2, rtol=5e-2, atol=1e-2)


def test_ctc_loss_smoke():
    T, N, C = 10, 2, 5
    data = nd.array(onp.random.randn(T, N, C).astype("float32"))
    label = nd.array(onp.array([[1, 2], [2, 3]], dtype="float32"))
    loss = nd.CTCLoss(data, label)
    assert loss.shape == (N,)
    assert (loss.asnumpy() > 0).all()


def test_clip_norm_misc():
    x = onp.random.randn(4, 4).astype("float32")
    assert_almost_equal(nd.clip(nd.array(x), -0.5, 0.5),
                        onp.clip(x, -0.5, 0.5))
    assert_almost_equal(nd.norm(nd.array(x)),
                        onp.sqrt((x ** 2).sum()), rtol=1e-4)
    assert_almost_equal(nd.norm(nd.array(x), axis=1),
                        onp.sqrt((x ** 2).sum(1)), rtol=1e-4)


def test_conv_nhwc_env_path_matches_nchw(monkeypatch):
    """MXNET_TPU_CONV_LAYOUT=NHWC computes the same result as a direct
    NCHW lax reference (the knob only changes layout, never numerics).
    Fresh (unseen) shapes force a genuine NHWC-path compile — same
    shapes through the funnel twice would replay the cached
    executable and compare it to itself."""
    import jax.numpy as jnp
    from jax import lax

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    def lax_ref(x, w, b, stride, pad, groups=1):
        out = lax.conv_general_dilated(
            jnp.asarray(x), jnp.asarray(w), window_strides=stride,
            padding=[(p, p) for p in pad],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups)
        if b is not None:
            out = out + jnp.asarray(b).reshape(1, -1, 1, 1)
        return onp.asarray(out)

    rng = onp.random.RandomState(0)
    monkeypatch.setenv("MXNET_TPU_CONV_LAYOUT", "NHWC")
    x = rng.randn(2, 3, 13, 13).astype("float32")
    w = rng.randn(8, 3, 3, 3).astype("float32")
    b = rng.randn(8).astype("float32")
    got = mx.nd.Convolution(
        NDArray(x), NDArray(w), NDArray(b), kernel=(3, 3),
        stride=(2, 2), pad=(1, 1), num_filter=8).asnumpy()
    onp.testing.assert_allclose(
        got, lax_ref(x, w, b, (2, 2), (1, 1)), rtol=2e-5, atol=2e-5)
    # grouped conv through the forced-NHWC path
    xg = rng.randn(2, 6, 9, 9).astype("float32")
    wg = rng.randn(6, 2, 3, 3).astype("float32")
    got_g = mx.nd.Convolution(
        NDArray(xg), NDArray(wg), kernel=(3, 3), num_filter=6,
        num_group=3, no_bias=True).asnumpy()
    onp.testing.assert_allclose(
        got_g, lax_ref(xg, wg, None, (1, 1), (0, 0), groups=3),
        rtol=2e-5, atol=2e-5)


def test_channels_last_pooling_and_deconv():
    """NHWC/NWC layouts through Pooling and Deconvolution match the
    channels-first reference (regression: NHWC pooling reduced the
    wrong axes)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    rng = onp.random.RandomState(0)
    x = rng.randn(2, 10, 10, 3).astype("float32")
    got = mx.nd.Pooling(NDArray(x), kernel=(2, 2), stride=(2, 2),
                        pool_type="max", layout="NHWC").asnumpy()
    ref = mx.nd.Pooling(NDArray(onp.transpose(x, (0, 3, 1, 2))),
                        kernel=(2, 2), stride=(2, 2),
                        pool_type="max").asnumpy()
    onp.testing.assert_allclose(got, onp.transpose(ref, (0, 2, 3, 1)),
                                rtol=1e-6)
    gavg = mx.nd.Pooling(NDArray(x), pool_type="avg", global_pool=True,
                         layout="NHWC").asnumpy()
    onp.testing.assert_allclose(gavg.reshape(2, 3), x.mean((1, 2)),
                                rtol=1e-5)
    # deconv: channels-last weights follow the data layout
    # ((I, *k, O/g) for NWC; (I, O/g, *k) channels-first)
    xs = rng.randn(2, 8, 4).astype("float32")      # NWC
    w_nwc = rng.randn(4, 3, 5).astype("float32")   # (in, k, out)
    b = rng.randn(5).astype("float32")
    got_d = mx.nd.Deconvolution(NDArray(xs), NDArray(w_nwc),
                                NDArray(b), kernel=(3,), num_filter=5,
                                no_bias=False, layout="NWC").asnumpy()
    ref_d = mx.nd.Deconvolution(
        NDArray(onp.transpose(xs, (0, 2, 1))),
        NDArray(onp.transpose(w_nwc, (0, 2, 1))), NDArray(b),
        kernel=(3,), num_filter=5, no_bias=False).asnumpy()
    onp.testing.assert_allclose(got_d,
                                onp.transpose(ref_d, (0, 2, 1)),
                                rtol=1e-4, atol=1e-4)
    # conv: NHWC layout kwarg expects (O, *k, I) weights — asymmetric
    # kernel catches axis misinterpretation
    xh = rng.randn(2, 9, 9, 3).astype("float32")
    w_oihw = rng.randn(8, 3, 2, 4).astype("float32")
    got_c = mx.nd.Convolution(
        NDArray(xh), NDArray(onp.transpose(w_oihw, (0, 2, 3, 1))),
        kernel=(2, 4), num_filter=8, no_bias=True,
        layout="NHWC").asnumpy()
    ref_c = mx.nd.Convolution(
        NDArray(onp.transpose(xh, (0, 3, 1, 2))), NDArray(w_oihw),
        kernel=(2, 4), num_filter=8, no_bias=True).asnumpy()
    onp.testing.assert_allclose(got_c,
                                onp.transpose(ref_c, (0, 2, 3, 1)),
                                rtol=1e-4, atol=1e-4)
    # and the gluon layer allocates layout-consistent weights: a
    # training-shaped forward matches a transposed NCHW twin
    from mxnet_tpu.gluon import nn as gnn
    mx.random.seed(11)
    lay = gnn.Conv2D(6, (2, 3), layout="NHWC", in_channels=3)
    lay.initialize()
    out_l = lay(NDArray(xh))
    assert lay.weight.shape == (6, 2, 3, 3)    # (O, kH, kW, I)
    assert out_l.shape == (2, 8, 7, 6)


def test_deconv_target_shape():
    """target_shape overrides the deconv output size by inferring adj
    (parity: DeconvolutionParam)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    rng = onp.random.RandomState(0)
    x = rng.randn(1, 4, 8).astype("float32")       # NCW
    w = rng.randn(4, 5, 3).astype("float32")
    out = mx.nd.Deconvolution(NDArray(x), NDArray(w), kernel=(3,),
                              stride=(2,), num_filter=5,
                              target_shape=(15,)).asnumpy()
    assert out.shape == (1, 5, 15)
    # default formula gives 17; 15 is valid because adj range is [0, s)
    out17 = mx.nd.Deconvolution(NDArray(x), NDArray(w), kernel=(3,),
                                stride=(2,), num_filter=5).asnumpy()
    assert out17.shape == (1, 5, 17)
    # odd excess exercises the adj remainder
    out16 = mx.nd.Deconvolution(NDArray(x), NDArray(w), kernel=(3,),
                                stride=(2,), num_filter=5,
                                target_shape=(16,)).asnumpy()
    assert out16.shape == (1, 5, 16)
    with pytest.raises(Exception):
        mx.nd.Deconvolution(NDArray(x), NDArray(w), kernel=(3,),
                            stride=(2,), num_filter=5,
                            target_shape=(30,))


def test_eager_dropout_modes():
    """mx.nd.Dropout works standalone: identity in inference,
    stochastic under record(), unconditional with mode='always'
    (regression: the raw binding lacked the PRNG key)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray import NDArray

    import mxnet_tpu as mx

    ones = NDArray(onp.ones((1000,), "float32"))
    d = mx.nd.Dropout(ones, p=0.5, mode="always").asnumpy()
    assert 0.35 < float((d == 0).mean()) < 0.65
    assert (d[d != 0] == 2.0).all()          # inverted scaling
    assert (mx.nd.Dropout(ones, p=0.5).asnumpy() == 1).all()
    with autograd.record():
        y = mx.nd.Dropout(ones, p=0.5)
    z = float((y.asnumpy() == 0).mean())
    assert 0.3 < z < 0.7


def test_numeric_gradients_layout_ops():
    """Finite-difference gradient checks for the layout-sensitive ops
    (NHWC conv wrt weight, NWC deconv wrt input, InstanceNorm
    axis=-1 wrt input) — the kernel-oracle discipline of
    check_numeric_gradient (test_utils.py:1039) applied to the
    channels-last paths."""
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray import NDArray

    import mxnet_tpu as mx

    def num_grad(f, x, eps=1e-3):
        g = onp.zeros_like(x)
        it = onp.nditer(x, flags=["multi_index"])
        while not it.finished:
            i = it.multi_index
            xp = x.copy(); xp[i] += eps
            xm = x.copy(); xm[i] -= eps
            g[i] = (f(xp) - f(xm)) / (2 * eps)
            it.iternext()
        return g

    rng = onp.random.RandomState(0)
    x = rng.randn(1, 5, 5, 2).astype("float32")
    w = rng.randn(3, 2, 2, 2).astype("float32")

    def f_w(wv):
        return float(mx.nd.Convolution(
            NDArray(x), NDArray(wv.astype("float32")), kernel=(2, 2),
            num_filter=3, no_bias=True,
            layout="NHWC").asnumpy().sum())

    wn = NDArray(w)
    wn.attach_grad()
    with autograd.record():
        out = mx.nd.Convolution(NDArray(x), wn, kernel=(2, 2),
                                num_filter=3, no_bias=True,
                                layout="NHWC")
    out.backward(NDArray(onp.ones(out.shape, "float32")))
    onp.testing.assert_allclose(wn.grad.asnumpy(),
                                num_grad(f_w, w.astype("float64")),
                                rtol=2e-2, atol=2e-2)

    xd = rng.randn(1, 4, 2).astype("float32")
    wd = rng.randn(2, 3, 3).astype("float32")
    xn = NDArray(xd)
    xn.attach_grad()
    with autograd.record():
        o = mx.nd.Deconvolution(xn, NDArray(wd), kernel=(3,),
                                num_filter=3, layout="NWC")
        loss = (o * o).sum()
    loss.backward()

    def f_x(xv):
        return float((mx.nd.Deconvolution(
            NDArray(xv.astype("float32")), NDArray(wd), kernel=(3,),
            num_filter=3, layout="NWC").asnumpy() ** 2).sum())

    onp.testing.assert_allclose(xn.grad.asnumpy(),
                                num_grad(f_x, xd.astype("float64")),
                                rtol=2e-2, atol=2e-2)
