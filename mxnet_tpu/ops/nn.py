"""Neural-network ops.

Parity target: ``src/operator/nn/`` (convolution.cc:399, pooling,
batch_norm, fully_connected, softmax family, dropout, layer_norm —
SURVEY.md §2.2).  TPU-first choices: convolutions/matmuls go straight to
``lax.conv_general_dilated``/``jnp.dot`` so XLA tiles them onto the MXU;
normalizations are unfused jnp graphs XLA fuses into the surrounding
matmuls; everything is rank-polymorphic over 1D/2D/3D spatial dims
(the reference maintains separate cuDNN descriptors per rank).
"""
from __future__ import annotations

import functools
import os
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

# -- helpers ---------------------------------------------------------------

def _safe_acc(x):
    """Upcast low-precision inputs to f32 for accumulation when
    ``MXNET_SAFE_ACCUMULATION=1`` (parity: the reference's safe-
    accumulation switch in softmax/norm kernels, env_var.md; read at
    trace time — the dispatch cache keys on the switch via the same
    shared helper, so toggling it is honored)."""
    from .registry import safe_accumulation_enabled
    if safe_accumulation_enabled() and \
            x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32), x.dtype
    return x, None


def _tup(v, n) -> Tuple[int, ...]:
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(int(x) for x in v)
    return t if len(t) == n else t * n


def _conv_dnums(nspatial: int, layout: str | None):
    sp = "DHW"[-nspatial:]
    if layout and layout.endswith("C"):  # NHWC-family: TPU-preferred layout
        return ("N" + sp + "C", "O" + sp + "I", "N" + sp + "C")
    return ("NC" + sp, "OI" + sp, "NC" + sp)


# -- FullyConnected (parity: src/operator/nn/fully_connected.cc) -----------

@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(x, weight, bias=None, *, num_hidden=None, no_bias=False,
                     flatten=True):
    if flatten:
        x = x.reshape(x.shape[0], -1)
    out = jnp.dot(x, weight.T)
    if bias is not None:
        out = out + bias
    return out


# -- Convolution (parity: src/operator/nn/convolution.cc:399) --------------

@register("Convolution", aliases=("convolution",))
def _convolution(x, weight, bias=None, *, kernel, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 layout=None, **_ignored):
    n = len(kernel)
    stride, dilate = _tup(stride, n), _tup(dilate, n)
    pad = _tup(pad, n) if pad is not None else (0,) * n
    # MXNET_TPU_CONV_LAYOUT=NHWC: compute logically-NCHW 2-D convs in
    # the TPU-native channels-last layout (transpose in/out; weights
    # stay OIHW — lax dimension_numbers handle the mixed spec).  XLA
    # usually picks good layouts itself; this knob makes the choice
    # explicit and sweepable (tools/tune_tpu.py).  Read at trace time.
    force_nhwc = (n == 2 and (layout is None or layout == "NCHW")
                  and os.environ.get("MXNET_TPU_CONV_LAYOUT", "")
                  .upper() == "NHWC")
    if force_nhwc:
        x = jnp.transpose(x, (0, 2, 3, 1))
        dnums = ("NHWC", "OIHW", "NHWC")
    else:
        dnums = _conv_dnums(n, layout)
    out = lax.conv_general_dilated(
        x, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dnums,
        feature_group_count=num_group)
    if bias is not None:
        if dnums[2].endswith("C"):
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * n)
    if force_nhwc:
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


# -- Deconvolution (parity: src/operator/nn/deconvolution.cc).  MXNet weight
#    layout is (in, out/g, *k); out = (i-1)*s - 2p + dilate*(k-1) + 1 + adj.
@register("Deconvolution", aliases=("deconvolution",))
def _deconvolution(x, weight, bias=None, *, kernel, stride=None, dilate=None,
                   pad=None, adj=None, target_shape=None, num_filter=None,
                   num_group=1, no_bias=True, layout=None, **_ignored):
    n = len(kernel)
    stride, dilate = _tup(stride, n), _tup(dilate, n)
    pad = _tup(pad, n) if pad is not None else (0,) * n
    adj = _tup(adj, n) if adj is not None else (0,) * n
    if target_shape:
        # target_shape overrides pad/adj to hit the requested output
        # exactly (parity: deconvolution-inl.h DeconvolutionParam —
        # out = (i-1)*s - 2p + d*(k-1) + 1 + adj, solved for p, adj)
        tgt = _tup(target_shape, n)
        spatial_in = (x.shape[2:2 + n]
                      if not (layout and layout.endswith("C"))
                      else x.shape[1:1 + n])
        new_pad, new_adj = [], []
        for i in range(n):
            nopad = ((spatial_in[i] - 1) * stride[i]
                     + dilate[i] * (kernel[i] - 1) + 1)
            excess = nopad - tgt[i]
            if excess < 0:
                raise ValueError(
                    f"Deconvolution target_shape {tgt} larger than "
                    f"the maximum unpadded output for input "
                    f"{tuple(spatial_in)}")
            a = excess % 2
            new_pad.append((excess + a) // 2)
            new_adj.append(a)
        pad, adj = tuple(new_pad), tuple(new_adj)
    g = num_group
    cin = weight.shape[0]
    channels_last = bool(layout) and layout.endswith("C")
    if channels_last:
        # weight follows the data layout (reference convention):
        # (I, *k, O/g) -> (g*O/g, *k, I/g) with spatial flip
        og = weight.shape[-1]
        ksp = tuple(weight.shape[1:-1])
        w = weight.reshape((g, cin // g) + ksp + (og,))
        w = jnp.moveaxis(w, -1, 1)            # (g, O/g, I/g, *k)
        w = w.reshape((g * og, cin // g) + ksp)
        w = jnp.moveaxis(w, 1, -1)            # (g*O/g, *k, I/g)
        w = jnp.flip(w, axis=tuple(range(1, 1 + n)))
    else:
        og = weight.shape[1]
        # (I, O/g, *k) -> (g*O/g, I/g, *k) with spatial flip
        w = weight.reshape((g, cin // g, og) + tuple(weight.shape[2:]))
        w = jnp.swapaxes(w, 1, 2).reshape(
            (g * og, cin // g) + tuple(weight.shape[2:]))
        w = jnp.flip(w, axis=tuple(range(2, 2 + n)))
    padding = []
    for i in range(n):
        lo = dilate[i] * (kernel[i] - 1) - pad[i]
        padding.append((lo, lo + adj[i]))
    dnums = _conv_dnums(n, layout)
    out = lax.conv_general_dilated(
        x, w,
        window_strides=(1,) * n,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dnums,
        feature_group_count=g)
    if bias is not None:
        if dnums[2].endswith("C"):
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * n)
    return out


# -- Pooling (parity: src/operator/nn/pooling.cc) --------------------------

@register("Pooling", aliases=("pooling",))
def _pooling(x, *, kernel=(), pool_type="max", global_pool=False, stride=None,
             pad=None, pooling_convention="valid", count_include_pad=True,
             p_value=2, cudnn_off=False, layout=None, **_ignored):
    # channels-last layouts (NWC/NHWC/NDHWC): normalize to
    # channels-first for the window math, restore on the way out
    channels_last = bool(layout) and layout.endswith("C")
    if channels_last:
        out = _pooling(jnp.moveaxis(x, -1, 1), kernel=kernel,
                       pool_type=pool_type, global_pool=global_pool,
                       stride=stride, pad=pad,
                       pooling_convention=pooling_convention,
                       count_include_pad=count_include_pad,
                       p_value=p_value, layout=None)
        return jnp.moveaxis(out, 1, -1)
    nsp = x.ndim - 2
    if global_pool:
        axes = tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(x, axis=axes, keepdims=True)
        if pool_type == "lp":
            return jnp.sum(jnp.abs(x) ** p_value, axis=axes,
                           keepdims=True) ** (1.0 / p_value)
        return jnp.mean(x, axis=axes, keepdims=True)

    k = _tup(kernel, nsp)
    s = _tup(stride, nsp) if stride is not None else k
    p = _tup(pad, nsp) if pad is not None else (0,) * nsp
    window = (1, 1) + k
    strides = (1, 1) + s
    if pooling_convention == "full":
        # ceil division semantics: pad high side enough for a final window
        pads = [(0, 0), (0, 0)]
        for i in range(nsp):
            inp = x.shape[2 + i] + 2 * p[i]
            out_sz = -(-(inp - k[i]) // s[i]) + 1  # ceil
            need = (out_sz - 1) * s[i] + k[i] - inp
            pads.append((p[i], p[i] + max(need, 0)))
    elif pooling_convention == "same":
        pads = [(0, 0), (0, 0)]
        for i in range(nsp):
            out_sz = -(-x.shape[2 + i] // s[i])
            need = max((out_sz - 1) * s[i] + k[i] - x.shape[2 + i], 0)
            pads.append((need // 2, need - need // 2))
    else:
        pads = [(0, 0), (0, 0)] + [(pi, pi) for pi in p]

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum", "lp"):
        src = jnp.abs(x) ** p_value if pool_type == "lp" else x
        summed = lax.reduce_window(src, 0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                                   else 0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if pool_type == "lp":
            return summed ** (1.0 / p_value)
        if count_include_pad:
            denom = 1
            for ki in k:
                denom *= ki
            return summed / denom
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return summed / counts
    raise ValueError(f"unknown pool_type {pool_type}")


@register("adaptive_avg_pool2d", aliases=("_contrib_AdaptiveAvgPooling2D",))
def _adaptive_avg_pool2d(x, *, output_size=1):
    os = _tup(output_size, 2)
    n, c, h, w = x.shape
    x = x.reshape(n, c, os[0], h // os[0], os[1], w // os[1])
    return x.mean(axis=(3, 5))


@register("BilinearResize2D", aliases=("_contrib_BilinearResize2D",))
def _bilinear_resize(x, *, height=None, width=None, scale_height=None,
                     scale_width=None, mode="size", align_corners=True):
    n, c, h, w = x.shape
    oh = height if height else int(h * scale_height)
    ow = width if width else int(w * scale_width)
    return jax.image.resize(x, (n, c, oh, ow), method="linear")


@register("UpSampling")
def _upsampling(x, *args, scale=2, sample_type="nearest", num_args=1, **_ignored):
    n, c, h, w = x.shape
    method = "nearest" if sample_type == "nearest" else "linear"
    return jax.image.resize(x, (n, c, h * scale, w * scale), method=method)


# -- activations (parity: src/operator/nn/activation.cc, leaky_relu.cc) ----

@register("Activation", aliases=("activation",))
def _activation(x, *, act_type):
    if act_type == "relu":
        return jnp.maximum(x, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(x)
    if act_type == "mish":
        return x * jnp.tanh(jax.nn.softplus(x))
    raise ValueError(f"unknown act_type {act_type}")


@register("LeakyReLU")
def _leaky_relu(x, gamma=None, *, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, **_ignored):
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if act_type == "rrelu":  # eval mode: use mean slope
        return jnp.where(x > 0, x, 0.5 * (lower_bound + upper_bound) * x)
    raise ValueError(f"unknown act_type {act_type}")


# -- softmax family (parity: src/operator/nn/softmax.cc, log_softmax.cc) ---

@register("softmax")
def _softmax(x, length=None, *, axis=-1, temperature=None, use_length=False,
             dtype=None):
    x, low = _safe_acc(x)
    if dtype is None and low is not None:
        dtype = low
    if temperature and temperature != 1.0:
        x = x / temperature
    if use_length and length is not None:
        steps = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = -1
        mask = steps.reshape(shape) < jnp.expand_dims(length, axis=axis)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        out = jnp.where(mask, out, 0.0)
    else:
        out = jax.nn.softmax(x, axis=axis)
    return out.astype(dtype) if dtype else out


@register("log_softmax")
def _log_softmax(x, *, axis=-1, temperature=None, dtype=None):
    x, low = _safe_acc(x)
    if dtype is None and low is not None:
        dtype = low
    if temperature and temperature != 1.0:
        x = x / temperature
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(dtype) if dtype else out


@register("softmin")
def _softmin(x, *, axis=-1, temperature=None, dtype=None):
    return _softmax(-x, axis=axis, temperature=temperature, dtype=dtype)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(logp * oh)


@register("SoftmaxOutput", aliases=("softmax_output",))
def _softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    # forward is plain softmax; the custom backward of the reference
    # (softmax - onehot(label)) falls out of autograd on the CE loss.
    return jax.nn.softmax(data, axis=1 if multi_output else -1)


# -- normalization (parity: batch_norm.cc, layer_norm.cc, group_norm.cc) ---

def _channel_shape(x, axis):
    shape = [1] * x.ndim
    shape[axis] = -1
    return shape


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _batch_norm_train(x, gamma, beta, eps, axis, fix_gamma):
    return _batch_norm_train_fwd(x, gamma, beta, eps, axis, fix_gamma)[0]


def _batch_norm_train_fwd(x, gamma, beta, eps, axis, fix_gamma):
    acc = jnp.promote_types(x.dtype, jnp.float32)
    red = tuple(i for i in range(x.ndim) if i != axis)
    shape = _channel_shape(x, axis)
    n = x.size // x.shape[axis]
    xf = x.astype(acc)
    # neither sum needs the other: XLA makes them one multi-output reduce
    # inside the fusion that writes x (a convolution), and nothing reads x
    # again for its statistics.  (An explicit variadic lax.reduce does not
    # fuse into a convolution and costs the pass back.)
    mean = jnp.sum(xf, axis=red) / n
    var = jnp.maximum(jnp.sum(xf * xf, axis=red) / n - mean * mean, 0)
    inv = lax.rsqrt(var + eps)
    scale = inv if fix_gamma else inv * gamma.astype(acc)
    shift = beta.astype(acc) - mean * scale
    out = xf * scale.reshape(shape) + shift.reshape(shape)
    out = out.astype(jnp.result_type(x, gamma, beta))
    return ((out, mean.astype(x.dtype), var.astype(x.dtype)),
            (x, gamma, beta, mean, inv))


def _batch_norm_train_bwd(eps, axis, fix_gamma, res, cts):
    x, gamma, beta, mean, inv = res
    dy, dmean, dvar = cts
    acc = mean.dtype
    red = tuple(i for i in range(x.ndim) if i != axis)
    shape = _channel_shape(x, axis)
    n = x.size // x.shape[axis]
    xc = x.astype(acc) - mean.reshape(shape)
    dyf = dy.astype(acc)
    # dbeta and dgamma / inv: again one multi-output reduce, over (dy, x)
    dbeta = jnp.sum(dyf, axis=red)
    dyxc = jnp.sum(dyf * xc, axis=red)
    scale = inv if fix_gamma else inv * gamma.astype(acc)
    # dx = scale (dy - dbeta/n - xhat sum(dy xhat)/n), plus what the
    # returned mean and var owe x: dmean/n + 2 dvar (x - mean)/n
    k_x = (2 * dvar.astype(acc) - scale * inv * inv * dyxc) / n
    k_0 = (dmean.astype(acc) - scale * dbeta) / n
    dx = dyf * scale.reshape(shape) + xc * k_x.reshape(shape) \
        + k_0.reshape(shape)
    dgamma = jnp.zeros_like(gamma) if fix_gamma \
        else (dyxc * inv).astype(gamma.dtype)
    return dx.astype(x.dtype), dgamma, dbeta.astype(beta.dtype)


_batch_norm_train.defvjp(_batch_norm_train_fwd, _batch_norm_train_bwd)


@register("BatchNorm", aliases=("batch_norm",), multi_out=True)
def _batch_norm(x, gamma, beta, moving_mean, moving_var, *, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                use_batch_stats=False, **_ignored):
    """Returns (out, mean, var): mean/var are the stats used, so the Gluon
    layer can fold them into moving averages (the reference mutates aux
    states inside the kernel, src/operator/nn/batch_norm.cc).

    With batch statistics (training) the op reads ``x`` once on the way
    in and ``x`` and ``dy`` once on the way back, because a training step
    of a convolutional net is bound by the bytes of its activations:

    - one pass: ``sum(x)`` and ``sum(x*x)`` come out of one reduce, with
      float32 accumulators (the input's dtype where that is wider);
      ``mean = s1/n``, ``var = max(s2/n - mean^2, 0)``, biased.  ``var`` of
      the centred values would need ``mean`` first, a second read.
    - ``gamma*rsqrt(var+eps)`` and ``beta - mean*scale`` fold into one
      per-channel multiply-add in the accumulator's dtype, cast at the end
      (``x - mean`` is never rounded to bfloat16).
    - the gradient is derived by hand (``jax.custom_vjp``): residuals
      ``x, mean, inv``; ``dbeta = sum(dy)`` and ``dgamma = sum(dy*xhat)``
      in one reduce; ``dx = scale*(dy - dbeta/n - xhat*dgamma/n)``.
      Autodiff of ``mean((x - mean(x))^2)`` keeps a term that is zero and
      costs a read of ``x``, and runs each sum as a pass of its own.
    - the cotangents of the returned ``mean`` and ``var`` are honoured
      (``dx += dmean/n + 2 dvar (x - mean)/n``, in the same pass).

    ``axis`` may be negative.  Inference and ``use_global_stats`` keep the
    plain expression over the moving statistics.
    """
    axis = axis % x.ndim
    if use_batch_stats and not use_global_stats:
        return _batch_norm_train(x, gamma, beta, eps, axis, bool(fix_gamma))
    mean, var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = _channel_shape(x, axis)
    inv = lax.rsqrt(var + eps)
    out = (x - mean.reshape(shape)) * (inv * g).reshape(shape) + beta.reshape(shape)
    return out, mean, var


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(x, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    # safe accumulation: the whole normalization runs in f32, only the
    # outputs are cast back (casting the statistics early would rounder
    # away the benefit)
    xa, low = _safe_acc(x)
    mean = jnp.mean(xa, axis=axis, keepdims=True)
    var = jnp.var(xa, axis=axis, keepdims=True)
    xn = (xa - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = -1
    out = xn * gamma.reshape(shape) + beta.reshape(shape)
    if low is not None:
        out = out.astype(low)
        mean, var = mean.astype(low), var.astype(low)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("GroupNorm", aliases=("group_norm",))
def _group_norm(x, gamma, beta, *, num_groups=1, eps=1e-5, output_mean_var=False):
    n, c = x.shape[:2]
    g = num_groups
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.var(xg, axis=red, keepdims=True)
    xn = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return xn * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def _lrn(x, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(x)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(padded[:, i:i + x.shape[1]] for i in range(nsize))
    return x / jnp.power(knorm + (alpha / nsize) * acc, beta)


@register("RMSNorm", aliases=("rms_norm",))
def _rms_norm(x, gamma, *, axis=-1, eps=1e-6):
    ms = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    return x * lax.rsqrt(ms + eps) * gamma


# -- dropout (parity: src/operator/nn/dropout.cc).  Takes the PRNG key as an
#    array input — TPU-first: stateless randomness threads through jit.
@register("Dropout", aliases=("dropout",), train_identity=True)
def _dropout(x, key, *, p=0.5, mode="training", axes=(), **_ignored):
    if p <= 0.0:
        return x
    shape = list(x.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# -- losses implemented as ops in the reference ----------------------------

@register("MakeLoss", aliases=("make_loss",))
def _make_loss(x, *, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return x


@register("BlockGrad", aliases=("stop_gradient",))
def _block_grad(x):
    return lax.stop_gradient(x)


@register("CTCLoss", aliases=("ctc_loss",))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
              use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC forward loss via dynamic-programming in log space.

    data: (T, N, C) activations (pre-softmax); label: (N, L) int labels.
    Parity: src/operator/nn/ctc_loss.cc (warp-ctc); computed here with a
    lax.scan over time — compiler-friendly, no host loop.
    """
    T, N, C = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else C - 1
    lab = label.astype(jnp.int32)
    L = lab.shape[1]
    # extended label seq: blank l1 blank l2 ... blank lL blank  (len 2L+1)
    ext = jnp.full((N, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # padding convention: entries < 0 (or == blank) are padding
        lab_len = jnp.sum(lab >= 0, axis=1).astype(jnp.int32)
    ext_len = 2 * lab_len + 1
    data_len = (data_lengths.astype(jnp.int32) if use_data_lengths and
                data_lengths is not None else jnp.full((N,), T, jnp.int32))

    neg_inf = -1e30
    S = 2 * L + 1
    probs_ext = jnp.take_along_axis(
        logp, jnp.broadcast_to(ext[None], (T, N, S)), axis=2)  # (T,N,S)

    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(probs_ext[0, :, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(ext_len > 1, probs_ext[0, :, 1], neg_inf))

    same = ext == jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :-2]
    can_skip = (jnp.arange(S)[None, :] % 2 == 1) & (~same)

    def step(alpha, t):
        a_shift1 = jnp.pad(alpha, ((0, 0), (1, 0)), constant_values=neg_inf)[:, :-1]
        a_shift2 = jnp.pad(alpha, ((0, 0), (2, 0)), constant_values=neg_inf)[:, :-2]
        a = jnp.logaddexp(alpha, a_shift1)
        a = jnp.where(can_skip, jnp.logaddexp(a, a_shift2), a)
        new = a + probs_ext[t]
        new = jnp.where(t < data_len[:, None], new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    idx_last = jnp.clip(ext_len - 1, 0, S - 1)
    idx_prev = jnp.clip(ext_len - 2, 0, S - 1)
    ll = jnp.logaddexp(
        jnp.take_along_axis(alpha, idx_last[:, None], axis=1)[:, 0],
        jnp.take_along_axis(alpha, idx_prev[:, None], axis=1)[:, 0])
    return -ll
