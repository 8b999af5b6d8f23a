"""BatchNorm's training pass as it was before the one-pass op of
``mxnet_tpu/ops/nn.py``: the mean, then the variance of the centred
values, autodiff for the gradient.  The reference of
``test_operator.py`` (values and gradients, in float32) and of
``test_tpu_compile.py`` (what it makes a compiled block read); same
signature as ``ops.nn._batch_norm_train``."""
import jax.numpy as jnp
from jax import lax


def two_pass_batch_norm(x, gamma, beta, eps, axis, fix_gamma):
    axis = axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != axis)
    mean = jnp.mean(x, axis=red)
    var = jnp.var(x, axis=red)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * x.ndim
    shape[axis] = -1
    out = (x - mean.reshape(shape)) * (lax.rsqrt(var + eps) * g).reshape(
        shape) + beta.reshape(shape)
    return out, mean, var
