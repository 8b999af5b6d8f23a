"""Selective state-space (Mamba-2 / SSD) recurrence for the decode plane.

Per head ``h`` (of group ``g = h // (H // G)``) the state is a
``(P, N)`` matrix (head dim x state size) and one token does

    S_t = exp(dt_t * a) * S_{t-1} + (dt_t * x_t) (outer) B_t
    y_t = S_t C_t + d * x_t

with ``a < 0`` and ``d`` per head, ``dt_t > 0`` per head and token
(already through its softplus), ``x_t (P,)`` per head and ``B_t, C_t
(N,)`` per group.

Three forms of the same arithmetic:

- :func:`ssm_update` — the decode step.  One token for every slot of
  the serving grid against the per-layer state buffer ``(slots, H, P,
  N)``; a Pallas kernel (``mxtpu_ssm_update``) that reads and writes the
  state of the ACTIVE slots once, in place (the buffer is aliased to
  the output), and never touches an inactive slot's state: the active
  slot ids are compacted and scalar-prefetched, the grid steps past the
  last active slot repeat its last block index, so the pipeline moves
  nothing for them.  :func:`ssm_update_reference` is its XLA oracle,
  the numerics baseline the parity tests pin it to; no call site
  switches to it.
- :func:`ssm_chunk_scan` — prefill.  One chunk of one sequence from a
  given initial state in the chunked (SSD) form: the tokens of the
  chunk meet through one masked ``(T, T)`` decay matrix and the carried
  state enters and leaves through two einsums.  Plain XLA.
- :func:`ssm_scan_reference` — the recurrence itself, one token at a
  time under ``lax.scan``: the oracle of the chunked form and the
  whole-sequence path of a model's dense forward.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels as _kernels
from .registry import register

__all__ = ["ssm_update", "ssm_update_reference", "ssm_chunk_scan",
           "ssm_scan_reference"]


def _per_head(v, heads):
    """``v (..., G, N)`` per group -> ``(..., H, N)`` per head."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssm_update_reference(state, x, dt, a, b, c, d, active):
    """``state (S, H, P, N)`` float32, ``x (S, H, P)``, ``dt (S, H)``,
    ``a, d (H,)``, ``b, c (S, G, N)``, ``active (S,)`` bool ->
    ``(state, y (S, H, P) float32)``.  Inactive slots keep their state
    and yield zeros."""
    f32 = jnp.float32
    h = x.shape[1]
    xf, dtf = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dtf * a.astype(f32))                      # (S, H)
    bh = _per_head(b.astype(f32), h)                          # (S, H, N)
    ch = _per_head(c.astype(f32), h)
    new = (state * decay[..., None, None]
           + (dtf[..., None] * xf)[..., None] * bh[:, :, None, :])
    y = (new * ch[:, :, None, :]).sum(-1) + d.astype(f32)[:, None] * xf
    on = active[:, None, None]
    return (jnp.where(on[..., None], new, state),
            jnp.where(on, y, 0.0))


def ssm_scan_reference(state, x, dt, a, b, c, d):
    """The recurrence over time for one sequence: ``state (H, P, N)``,
    ``x (T, H, P)``, ``dt (T, H)``, ``b, c (T, G, N)`` -> ``(state,
    y (T, H, P))``, all float32.  A row with ``dt == 0`` leaves the
    state as it was."""
    f32 = jnp.float32
    h = x.shape[1]
    af, df = a.astype(f32), d.astype(f32)

    def step(s, row):
        xt, dtt, bt, ct = row
        s = (s * jnp.exp(dtt * af)[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * _per_head(bt, h)[:, None, :])
        y = (s * _per_head(ct, h)[:, None, :]).sum(-1) + df[:, None] * xt
        return s, y

    return lax.scan(step, state.astype(f32),
                    (x.astype(f32), dt.astype(f32), b.astype(f32),
                     c.astype(f32)))


def ssm_chunk_scan(state, x, dt, a, b, c, d):
    """One chunk in the SSD form; arguments and result as
    :func:`ssm_scan_reference`.  With ``cum_t`` the running sum of
    ``dt * a`` inside the chunk,

        y_t = exp(cum_t) C_t S_0
              + sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s + d x_t
        S_T = exp(cum_T) S_0 + sum_s exp(cum_T - cum_s) dt_s x_s (outer) B_s

    so a padded row (``dt == 0``) adds nothing and decays nothing."""
    f32 = jnp.float32
    t_, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    r = h // g
    xf, dtf = x.astype(f32), dt.astype(f32)
    bf, cf = b.astype(f32), c.astype(f32)
    cum = jnp.cumsum(dtf * a.astype(f32), axis=0)             # (T, H)
    seg = cum[:, None, :] - cum[None, :, :]                   # (t, s, H)
    tri = jnp.tril(jnp.ones((t_, t_), bool))[:, :, None]
    # mask before the exponential: above the diagonal seg is positive
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("tgn,sgn->tsg", cf, bf)                   # (t, s, G)
    w = (decay.reshape(t_, t_, g, r) * cb[..., None]
         * dtf.reshape(1, t_, g, r))
    xg = xf.reshape(t_, g, r, p)
    y = jnp.einsum("tsgr,sgrp->tgrp", w, xg)
    s0 = state.astype(f32).reshape(g, r, p, n)
    y = y + (jnp.einsum("tgn,grpn->tgrp", cf, s0)
             * jnp.exp(cum).reshape(t_, g, r, 1))
    y = y + d.astype(f32).reshape(1, g, r, 1) * xg
    tail = (jnp.exp(cum[-1][None] - cum) * dtf).reshape(t_, g, r)
    new = (s0 * jnp.exp(cum[-1]).reshape(g, r, 1, 1)
           + jnp.einsum("tgr,tgrp,tgn->grpn", tail, xg, bf))
    return new.reshape(h, p, n), y.reshape(t_, h, p)


# -- the decode step's kernel -------------------------------------------------

def _ssm_kernel(idx_ref, n_ref, s_ref, dx_ref, dec_ref, b_ref, c_ref,
                so_ref, y_ref, *, block_h, p):
    """One grid step is ``block_h`` heads of one slot: state tiles ``(P,
    N)`` with the state size on the lanes.  ``dx (1, P)`` arrives with P
    on the lanes and the update needs it down the sublanes; the masked
    lane reduction against the identity is that transpose in operations
    every TPU generation lowers (a select, a broadcast, a reduction),
    and the way back for ``y`` is the same with the axes swapped."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_active = n_ref[0]

    @pl.when(i < n_active)
    def _update():
        eye = (lax.broadcasted_iota(jnp.int32, (p, p), 0)
               == lax.broadcasted_iota(jnp.int32, (p, p), 1))
        b_row = b_ref[0, 0]                                   # (1, N)
        c_row = c_ref[0, 0]
        for k in range(block_h):
            dx_col = jnp.sum(jnp.where(eye, dx_ref[0, k:k + 1, :], 0.0),
                             axis=1, keepdims=True)           # (P, 1)
            new = s_ref[0, k] * dec_ref[0, k:k + 1, :] + dx_col * b_row
            so_ref[0, k] = new
            y_col = jnp.sum(new * c_row, axis=1, keepdims=True)
            y_ref[0, k:k + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0),
                                           axis=0, keepdims=True)

    # no slot active at all: every step maps to one block, which must go
    # back as it came
    @pl.when((n_active == 0) & (i == 0) & (j == 0))
    def _keep():
        so_ref[...] = s_ref[...]


def _ssm_update_pallas(state, x, dt, a, b, c, active, block_h):
    """The kernel's part: ``(state, y)`` without the ``d * x`` skip;
    ``y`` rows of inactive slots are whatever the output buffer held."""
    return _ssm_update_jit(state, x, dt, a, b, c, active, int(block_h),
                           jax.default_backend() != "tpu")


# Jitted on everything but the arrays: the layers of a step, and every
# executable that updates the same grid of slots, share ONE trace of the
# kernel (traced anew at each call site, the kernels cost a fused
# Falcon-H1 turn's warm set-up seconds: PERF.md, PR 40).
@functools.partial(jax.jit, static_argnums=(7, 8))
def _ssm_update_jit(state, x, dt, a, b, c, active, block_h, interpret):
    f32 = jnp.float32
    s_, h, p, n = state.shape
    g = b.shape[1]
    block_h = math.gcd(max(1, int(block_h)), h // g)   # inside one group
    nj = h // block_h
    per_group = (h // g) // block_h                    # head blocks a group
    dtf = dt.astype(f32)
    dx = dtf[..., None] * x.astype(f32)                       # (S, H, P)
    dec = jnp.broadcast_to(jnp.exp(dtf * a.astype(f32))[..., None],
                           (s_, h, n))
    # active slot ids first, in order; the tail repeats the last one so
    # that its steps ask for the block that is already there
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    n_active = active.sum().astype(jnp.int32)
    last = order[jnp.maximum(n_active - 1, 0)]
    idx = jnp.where(jnp.arange(s_) < n_active, order, last)

    def slot_block(i, j, idx_ref, n_ref):
        return idx_ref[i], jnp.where(i < n_ref[0], j, nj - 1)

    def state_map(i, j, idx_ref, n_ref):
        return slot_block(i, j, idx_ref, n_ref) + (0, 0)

    def head_map(i, j, idx_ref, n_ref):
        return slot_block(i, j, idx_ref, n_ref) + (0,)

    def group_map(i, j, idx_ref, n_ref):
        slot, jj = slot_block(i, j, idx_ref, n_ref)
        return slot, jj // per_group, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, nj),
        in_specs=[
            pl.BlockSpec((1, block_h, p, n), state_map),
            pl.BlockSpec((1, block_h, p), head_map),
            pl.BlockSpec((1, block_h, n), head_map),
            pl.BlockSpec((1, 1, 1, n), group_map),
            pl.BlockSpec((1, 1, 1, n), group_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, p, n), state_map),
            pl.BlockSpec((1, block_h, p), head_map),
        ],
    )
    new, y = pl.pallas_call(
        functools.partial(_ssm_kernel, block_h=block_h, p=p),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((s_, h, p), f32)],
        # operands count the two prefetched scalars: the state is the third
        input_output_aliases={2: 0},
        interpret=interpret,
        name="mxtpu_ssm_update",
    )(idx.astype(jnp.int32), n_active.reshape(1), state, dx, dec,
      b.astype(f32).reshape(s_, g, 1, n), c.astype(f32).reshape(s_, g, 1, n))
    return new, y


# -- kernel-registry integration -------------------------------------------

def _ssm_signature(state, x, dt, a, b, c, d, active):
    """The serving grid fixes every extent; which slots are active is
    data, not shape."""
    s_, h, p, n = state.shape
    return f"s{s_}_h{h}_p{p}_n{n}_g{b.shape[1]}", str(state.dtype)


def _ssm_kernel_run(config, state, x, dt, a, b, c, d, active):
    new, y = _ssm_update_pallas(state, x, dt, a, b, c, active,
                                int(config["block_h"]))
    y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return new, jnp.where(active[:, None, None], y, 0.0)


def _ssm_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(29)
    s_, h, p, n, g = (case["slots"], case["h"], case["p"], case["n"],
                      case["g"])
    dtype = case.get("dtype", "float32")
    state = jnp.asarray(rng.randn(s_, h, p, n) * 0.5, jnp.float32)
    x = jnp.asarray(rng.randn(s_, h, p) * 0.5, dtype=dtype)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(s_, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, size=(h,)), jnp.float32)
    b = jnp.asarray(rng.randn(s_, g, n) * 0.5, dtype=dtype)
    c = jnp.asarray(rng.randn(s_, g, n) * 0.5, dtype=dtype)
    d = jnp.ones((h,), jnp.float32)
    # a ragged set of active slots, the first one idle
    active = rng.rand(s_) < 0.6
    active[0], active[-1] = False, True
    return (state, x, dt, a, b, c, d, jnp.asarray(active)), {}


_kernels.register_kernel(_kernels.KernelSpec(
    "ssm_update", version=1,
    run=_ssm_kernel_run, fallback=ssm_update_reference,
    config_space={"block_h": (8, 16)},
    default_config={"block_h": 8},
    signature=_ssm_signature, make_args=_ssm_make_args,
    tune_grid=({"slots": 6, "h": 4, "p": 16, "n": 32, "g": 2},
               {"slots": 4, "h": 8, "p": 32, "n": 64, "g": 1}),
))


def ssm_update(state, x, dt, a, b, c, d, active, *, block_h=None):
    """One token per slot against the layer's state buffer; arguments
    and result as :func:`ssm_update_reference`.  Hand it the whole
    donated buffer: the kernel writes it in place."""
    if block_h is None:
        sig, dt_key = _ssm_signature(state, x, dt, a, b, c, d, active)
        block_h = _kernels.resolve(
            "ssm_update", sig, dt_key,
            tune_args=((state, x, dt, a, b, c, d, active), {}))["block_h"]
    return _ssm_kernel_run({"block_h": block_h}, state, x, dt, a, b, c, d,
                           active)


register("ssm_update", aliases=("_npx_ssm_update",))(
    lambda state, x, dt, a, b, c, d, active, block_h=None:
    ssm_update(state, x, dt, a, b, c, d, active, block_h=block_h))
