"""Fused rotary position embedding (RoPE) — kernel-registry phase 2.

One Pallas kernel applies the NeoX-style half-split rotation in place:
for head-dim pairs ``(i, i + D/2)`` the rotation angle at position
``p`` is ``p * base**(-2i/D)``, so

    out[..., :D/2] = x1 * cos - x2 * sin
    out[..., D/2:] = x2 * cos + x1 * sin

with ``x1/x2`` the two halves.  The fused path computes angles from an
in-kernel iota (no host-materialized cos/sin tables) and streams
``(block_r, H, D)`` row blocks through VMEM; positions cross the
boundary lane-broadcast like flash attention's lse (attention.py
``_LSE_LANES``).  The XLA lowering (:func:`rope_reference`) is the
numerics oracle tests pin against; no call site switches to it.

A model whose frequencies are no power law of one ``base`` (YaRN
blends them pair by pair, :func:`yarn_frequencies`) rotates by a table
of them in XLA (:func:`rope_table`): the kernel computes its angles from
``base`` inside, and the one model that needs a table rotates 64 lanes
of a head, half a lane tile, which XLA fuses into the projection's
epilogue where the kernel would be a launch of its own on a block padded
to twice its size.

Registered through ``mxnet_tpu.kernels`` as ``rope`` with a block-size
config space; the decode serving plane (serving/decode/) applies it to
every q/k projection, and training attention stacks can call
:func:`rope` on (B, S, H, D) activations directly.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import kernels as _kernels
from .registry import register

__all__ = ["rope", "rope_reference", "rope_table", "yarn_frequencies"]

# positions cross the pallas boundary lane-broadcast (TPU (8, 128)
# block-tiling rule — see attention.py _LSE_LANES)
_POS_LANES = 128

_ROPE_ENV_KEY = "MXNET_TPU_ROPE_BLOCK_R"
_rope_env_snapshot: tuple = (False,)          # impossible sentinel


def rope_reference(x, positions, base=10000.0):
    """XLA RoPE on ``x (..., H, D)`` with ``positions`` shaped like
    ``x.shape[:-2]`` (or scalar) — the oracle."""
    d = x.shape[-1]
    half = d // 2
    xf = x.astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.asarray(positions), x.shape[:-2])
    pos = pos.astype(jnp.float32)[..., None, None]        # (..., 1, 1)
    k = jnp.arange(half, dtype=jnp.float32)
    inv = jnp.exp(k * (-math.log(base) / half))           # base^(-2i/D)
    ang = pos * inv                                       # (..., 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # the halves as a (2, half) axis pair, not a slice + concatenate on
    # the lane axis: for float32 (r, 8, 64) that form aborts the TPU
    # compiler (libtpu 0.0.34, "Check failed: IsFusibleUnalignedDUS")
    xr = xf.reshape(xf.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return out.reshape(x.shape).astype(x.dtype)


def yarn_frequencies(dim, base, *, factor, original_max_position_embeddings,
                     beta_fast=32.0, beta_slow=1.0, **_):
    """YaRN's rotation frequencies of ``dim // 2`` pairs, as a numpy
    float32 vector.  Pair ``i`` turns ``base**(-2i/dim)`` radians a
    position; one that completes more than ``beta_fast`` turns inside
    ``original_max_position_embeddings`` positions keeps that, one that
    completes fewer than ``beta_slow`` is slowed by ``factor``, and
    between the two pair indices where those turn counts fall (the
    lower rounded down, the upper up) the blend is linear in the pair
    index.  Further keys of a ``rope_scaling`` dict (``mscale``,
    ``type``) are not this function's."""
    import numpy as onp
    half = dim // 2
    plain = float(base) ** (-onp.arange(half, dtype=onp.float64) * 2 / dim)

    def pair_at(turns):
        return dim * math.log(original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_at(beta_fast)), 0)
    high = min(math.ceil(pair_at(beta_slow)), dim - 1)
    ramp = onp.clip((onp.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(onp.float32)


def rope_table(x, positions, inv_freq, scale=1.0):
    """Split-half rotation of ``x (..., H, D)`` at ``positions`` (shaped
    like ``x.shape[:-2]``) by a table ``inv_freq (D/2,)`` of radians a
    position; ``scale`` multiplies cos and sin.  XLA, float32 inside."""
    half = x.shape[-1] // 2
    pos = jnp.broadcast_to(jnp.asarray(positions), x.shape[:-2])
    ang = pos.astype(jnp.float32)[..., None, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (2, half))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_kernel(x_ref, pos_ref, o_ref, *, base, half):
    x = x_ref[...].astype(jnp.float32)        # (block_r, H, D)
    pos = pos_ref[:, :1]                      # (block_r, 1): lane 0
    # the TPU iota is integer-only; cast after
    k = lax.broadcasted_iota(jnp.int32, (1, 1, half), 2).astype(jnp.float32)
    inv = jnp.exp(k * (-math.log(base) / half))
    ang = pos[:, :, None] * inv               # (block_r, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    o_ref[...] = out.astype(o_ref.dtype)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


# one f32 (block_r, H, D) temporary, tile-padded, may take this much of
# the 16 MiB scoped VMEM: the kernel keeps about ten alive (x, halves,
# cos/sin, out, double-buffered blocks).  Found by compiling for a v5e:
# 2 MiB is refused, 1 MiB passes at every (H, D) tried.
_BLOCK_BYTES_MAX = 1 << 20


def _max_block_r(h, d):
    row_bytes = 4 * _ceil_to(h, 8) * _ceil_to(d, 128)
    return max(8, _BLOCK_BYTES_MAX // row_bytes // 8 * 8)


def _rope_pallas(x, positions, base, block_r):
    """x (R, H, D), positions (R,) → rotated (R, H, D)."""
    return _rope_jit(x, jnp.asarray(positions), float(base), int(block_r),
                     jax.default_backend() != "tpu")


# Jitted on everything but the arrays: the layers of a step share ONE
# trace of the kernel (PERF.md, PR 40).
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _rope_jit(x, positions, base, block_r, interpret):
    r, h, d = x.shape
    if d % 2:
        raise ValueError(f"rope requires an even head_dim, got {d}")
    block_r = max(1, min(block_r, _ceil_to(r, 8), _max_block_r(h, d)))
    pad = _ceil_to(r, block_r) - r
    pos = positions.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        pos = jnp.pad(pos, (0, pad))
    pos = jnp.broadcast_to(pos[:, None], (pos.shape[0], _POS_LANES))
    out = pl.pallas_call(
        functools.partial(_rope_kernel, base=float(base), half=d // 2),
        grid=(x.shape[0] // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, h, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_r, _POS_LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="mxtpu_rope",
    )(x, pos)
    return out[:r] if pad else out


# -- kernel-registry integration -------------------------------------------

def _rope_signature(x, positions, base=10000.0):
    from ..amp import policy as _amp_policy
    from .attention import _pow2_bucket
    return (f"r{_pow2_bucket(x.shape[0], floor=64)}"
            f"_h{x.shape[1]}_d{x.shape[2]}",
            _amp_policy.kernel_key_dtype(str(x.dtype)))


def _rope_kernel_run(config, x, positions, base=10000.0):
    return _rope_pallas(x, positions, base, int(config["block_r"]))


def _rope_kernel_fallback(x, positions, base=10000.0):
    return rope_reference(x, jnp.asarray(positions), base=base)


def _rope_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(23)
    r, h, d = case["r"], case["h"], case["d"]
    x = jnp.asarray(rng.randn(r, h, d) * 0.5,
                    dtype=case.get("dtype", "float32"))
    pos = jnp.asarray(rng.randint(0, 4096, size=(r,)), jnp.int32)
    return (x, pos), {"base": float(case.get("base", 10000.0))}


_kernels.register_kernel(_kernels.KernelSpec(
    "rope", version=1,
    run=_rope_kernel_run, fallback=_rope_kernel_fallback,
    config_space={"block_r": (32, 64, 128, 256)},
    default_config={"block_r": 128},
    signature=_rope_signature, make_args=_rope_make_args,
    tune_grid=({"r": 128, "h": 4, "d": 64},
               {"r": 512, "h": 8, "d": 128}),
))


def _resolve_rope_block(xf, pos, base):
    """block_r for one call: env override > registry (memo/disk/tune/
    default), snapshot-invalidated like attention's flash blocks."""
    global _rope_env_snapshot
    env = (os.environ.get(_ROPE_ENV_KEY),)
    if env != _rope_env_snapshot:
        _rope_env_snapshot = env
        _kernels.invalidate("rope")
    if env[0] is not None:
        try:
            v = int(env[0])
        except ValueError:
            v = 0
        if v > 0:
            return v
    sig, dt = _rope_signature(xf, pos, base)
    cfg = _kernels.resolve("rope", sig, dt,
                           tune_args=((xf, pos), {"base": base}))
    return int(cfg["block_r"])


def rope(x, positions, *, base=10000.0, block_r=None):
    """Rotary embedding on ``x (..., H, D)`` at integer ``positions``
    shaped like ``x.shape[:-2]`` (scalars broadcast).  Leading axes are
    flattened into row blocks for the kernel and restored after."""
    x = jnp.asarray(x)
    lead = x.shape[:-2]
    r = 1
    for n in lead:
        r *= n
    if r == 0:
        return x
    xf = x.reshape((r,) + x.shape[-2:])
    pos = jnp.broadcast_to(jnp.asarray(positions), lead).reshape(r)
    if block_r is None:
        block_r = _resolve_rope_block(xf, pos, float(base))
    out = _rope_pallas(xf, pos, float(base), int(block_r))
    return out.reshape(x.shape)


register("rope", aliases=("_npx_rope",))(
    lambda x, positions, base=10000.0, block_r=None:
    rope(x, positions, base=base, block_r=block_r))
