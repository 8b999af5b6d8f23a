"""Fused LayerNorm + residual-add Pallas kernel.

The transformer block's ``LayerNorm(x + residual)`` is two HBM round
trips when left to separate ops (materialize the sum, re-read it to
normalize).  This kernel fuses them: one pass over row blocks in VMEM
computes the sum, the row statistics (f32), and the affine output —
the residual sum never hits HBM.

Second registrant of the kernel registry (``mxnet_tpu.kernels``): the
tunable config is the row-block size; the XLA lowering below is the
numerics oracle the parity tests pin the kernel against, and runs in
production only when a caller asks for it (``use_pallas=False``, which
ticks ``kernel.fallbacks``).  A Pallas path that cannot build is an
error, never a quiet switch to XLA.  Backward recomputes through
``jax.vjp`` of the oracle — the standard recompute-from-inputs
flash-style trade.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import kernels as _kernels
from .registry import register

__all__ = ["layer_norm_residual"]


def _lnr_reference(x, residual, gamma, beta, eps):
    """Unfused XLA lowering — fallback and numerics oracle.  Statistics
    accumulate in f32 regardless of input dtype (matching the kernel's
    in-VMEM f32 accumulators), outputs cast back."""
    y = x.astype(jnp.float32) + residual.astype(jnp.float32)
    mean = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.var(y, axis=-1, keepdims=True)
    yn = (y - mean) * lax.rsqrt(var + eps)
    out = yn * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


def _lnr_kernel(x_ref, r_ref, g_ref, b_ref, o_ref, *, eps):
    y = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mean = jnp.mean(y, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
    yn = (y - mean) * lax.rsqrt(var + eps)
    out = yn * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _lnr_pallas(x, residual, gamma, beta, eps, block_rows):
    """x, residual: (rows, F); gamma, beta: (F,).  Grid over row
    blocks; the feature axis stays whole per block (block dim == array
    dim satisfies the TPU lane-tiling rule for any F)."""
    rows, f = x.shape
    block_rows = min(int(block_rows), max(8, rows))
    pr = (-rows) % block_rows
    if pr:
        pad = ((0, pr), (0, 0))
        x = jnp.pad(x, pad)
        residual = jnp.pad(residual, pad)
    nr = x.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_lnr_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=jax.default_backend() != "tpu",
        name="mxtpu_layernorm_residual",
    )(x, residual, gamma.reshape(1, f), beta.reshape(1, f))
    return out[:rows] if pr else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _lnr(x, residual, gamma, beta, eps, block_rows):
    return _lnr_pallas(x, residual, gamma, beta, eps, block_rows)


def _lnr_fwd(x, residual, gamma, beta, eps, block_rows):
    out = _lnr_pallas(x, residual, gamma, beta, eps, block_rows)
    return out, (x, residual, gamma, beta)


def _lnr_bwd(eps, block_rows, res, g):
    x, residual, gamma, beta = res
    _, vjp = jax.vjp(
        lambda x_, r_, g_, b_: _lnr_reference(x_, r_, g_, b_, eps),
        x, residual, gamma, beta)
    return vjp(g)


_lnr.defvjp(_lnr_fwd, _lnr_bwd)


# -- kernel-registry spec ---------------------------------------------------

def _lnr_signature(x, residual, gamma, beta, eps=1e-5):
    # the dtype leg resolves through the AMP policy (see
    # attention._flash_signature): an fp32 call under AMP runs on
    # policy-cast operands, so the cache key names the compute dtype
    from ..amp import policy as _amp_policy
    from .attention import _pow2_bucket
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    return (f"rows{_pow2_bucket(rows, floor=8)}_f{x.shape[-1]}",
            _amp_policy.kernel_key_dtype(str(x.dtype)))


def _lnr_kernel_run(config, x, residual, gamma, beta, eps=1e-5):
    rows = x.shape[:-1]
    f = x.shape[-1]
    x2 = x.reshape(-1, f)
    r2 = residual.reshape(-1, f)
    out = _lnr(x2, r2, gamma, beta, float(eps),
               int(config["block_rows"]))
    return out.reshape(rows + (f,))


def _lnr_kernel_fallback(x, residual, gamma, beta, eps=1e-5):
    return _lnr_reference(x, residual, gamma, beta, float(eps))


def _lnr_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(13)
    rows, f = case["rows"], case["f"]
    dtype = case.get("dtype", "float32")
    x = jnp.asarray(rng.randn(rows, f), dtype)
    r = jnp.asarray(rng.randn(rows, f), dtype)
    gamma = jnp.asarray(rng.rand(f) + 0.5, dtype)
    beta = jnp.asarray(rng.randn(f) * 0.1, dtype)
    return (x, r, gamma, beta), {}


_kernels.register_kernel(_kernels.KernelSpec(
    "layer_norm_residual", version=1,
    run=_lnr_kernel_run, fallback=_lnr_kernel_fallback,
    config_space={"block_rows": (8, 16, 32, 64, 128)},
    default_config={"block_rows": 32},
    signature=_lnr_signature, make_args=_lnr_make_args,
    tune_grid=({"rows": 256, "f": 256}, {"rows": 512, "f": 128}),
))


@register("layer_norm_residual", aliases=("_npx_layer_norm_residual",))
def layer_norm_residual(x, residual, gamma, beta, *, eps=1e-5,
                        use_pallas=True):
    """``LayerNorm(x + residual)`` over the last axis, fused.

    Shapes: ``x``/``residual`` (..., F), ``gamma``/``beta`` (F,).
    The Pallas path resolves its row-block size through the kernel
    registry and raises if it cannot build; ``use_pallas=False`` runs
    the XLA lowering instead and ticks ``kernel.fallbacks``.
    """
    if x.shape != residual.shape:
        raise ValueError(
            f"x {x.shape} and residual {residual.shape} must match")
    if not use_pallas:
        _kernels.record_fallback("layer_norm_residual")
        return _lnr_kernel_fallback(x, residual, gamma, beta, eps=eps)
    sig, dt = _lnr_signature(x, residual, gamma, beta)
    args = (x, residual, gamma, beta)
    cfg = _kernels.resolve("layer_norm_residual", sig, dt,
                           tune_args=(args, {"eps": eps}))
    return _lnr_kernel_run(cfg, x, residual, gamma, beta, eps=eps)
