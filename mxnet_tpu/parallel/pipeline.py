"""Pipeline parallelism: GPipe-style stage execution over a mesh axis.

TPU-native design (no reference counterpart to translate: the
reference's "model parallelism" is per-layer ctx placement,
`group2ctxs` in graph_executor.cc — a host-scheduled form the compiler
replaces here): stages live one-per-device along a ``pp`` mesh axis,
microbatches stream through, and stage outputs hop to the next device
with `lax.ppermute` (XLA collective-permute over ICI).  Expressed so
`jax.grad` differentiates straight through — the transpose of ppermute
is the reverse ppermute, so the backward pipeline runs automatically in
the opposite direction.

Layout: stage parameters are stacked on a leading axis sharded over
``pp``; inside `shard_map` each device sees only its own stage's
params.  The schedule is the classic GPipe fill-drain: with S stages
and M microbatches the loop runs S+M-1 ticks at 1/S bubble overhead.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec
from jax import shard_map

__all__ = ["gpipe_apply", "pipeline_forward", "interleaved_apply",
           "pipeline_forward_interleaved", "pipeline_forward_1f1b",
           "interleave_params", "interleaved_ticks", "gpipe_ticks",
           "one_f_one_b_apply", "pipeline_value_and_grad_1f1b",
           "one_f_one_b_ticks"]


def gpipe_apply(stage_fn: Callable, n_stages: int, axis_name: str = "pp"):
    """Build the per-device pipeline body; call inside shard_map.

    ``stage_fn(stage_params, x) -> y`` is one stage's computation; every
    stage must map shape (mb, ...) -> (mb, ...) identically (uniform
    pipelines — the GPipe assumption).

    Returns ``apply(stage_params, x_microbatches)`` where
    ``stage_params`` is this device's stage slice and
    ``x_microbatches`` has shape (M, mb, ...).  The result is the
    last stage's outputs, (M, mb, ...), replicated over the axis.
    """
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def apply(stage_params, x_mb):
        idx = lax.axis_index(axis_name)
        M = x_mb.shape[0]
        carry = jnp.zeros_like(x_mb[0])
        out = jnp.zeros_like(x_mb)
        for t in range(n_stages + M - 1):
            feed = x_mb[min(t, M - 1)]
            inp = jnp.where(idx == 0, feed, carry)
            y = stage_fn(stage_params, inp)
            # collect on the last stage: at tick t it finishes
            # microbatch t-(S-1)
            m = t - (n_stages - 1)
            if m >= 0:
                write = jnp.where(idx == n_stages - 1, y, out[m])
                out = out.at[m].set(write)
            carry = lax.ppermute(y, axis_name, perm)
        # replicate the collected outputs (they live on the last stage)
        mask = (idx == n_stages - 1).astype(out.dtype)
        return lax.psum(out * mask, axis_name)

    return apply


def pipeline_forward(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                     n_microbatches: int, axis_name: str = "pp",
                     batch_axis_name: Optional[str] = "dp"):
    """Run a full pipeline forward over a mesh (convenience wrapper).

    ``stacked_params``: pytree whose leaves have a leading stage axis of
    size mesh.shape[axis_name] (sharded over it).  ``x``: (B, ...) batch
    — split into ``n_microbatches`` along axis 0; if the mesh also has
    ``batch_axis_name``, the batch dim is additionally sharded over it
    (dp×pp).  Returns (B, ...) outputs with the same sharding as x.
    """
    S = mesh.shape[axis_name]
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"pipeline_forward: param leading (stage) axis "
                f"{leaf.shape[0]} != pp mesh size {S} — one stage per "
                f"device (stack multiple layers inside stage_fn instead)")
    body = gpipe_apply(stage_fn, S, axis_name)
    dp = (batch_axis_name
          if batch_axis_name and batch_axis_name in mesh.axis_names
          else None)
    n_dp = mesh.shape[dp] if dp else 1
    if x.shape[0] % (n_dp * n_microbatches):
        raise ValueError(
            f"pipeline_forward: batch {x.shape[0]} not divisible by "
            f"dp({n_dp}) x n_microbatches({n_microbatches})")

    def full(params, xb):
        # shard_map keeps the sharded stage axis at local size 1 — drop it
        local = jax.tree.map(lambda a: a[0], params)
        M = n_microbatches
        xmb = xb.reshape((M, xb.shape[0] // M) + xb.shape[1:])
        out = body(local, xmb)
        return out.reshape(xb.shape[0:1] + out.shape[2:])

    pspec = jax.tree.map(lambda _: PartitionSpec(axis_name), stacked_params)
    xspec = PartitionSpec(dp)
    return shard_map(full, mesh=mesh, in_specs=(pspec, xspec),
                     out_specs=xspec, check_vma=False)(stacked_params, x)


# --------------------------------------------------------------------------
# Interleaved 1F1B-style schedule (virtual stages).  The reference has no
# pipeline parallelism at all (its model parallelism is per-layer ctx
# placement, docs model_parallel_lstm.md) — this is north-star scaling
# work per SURVEY §7.
#
# Device d holds V *virtual* stages: layers {j*S + d, j=0..V-1}.  A
# microbatch circulates V times around the pp ring, so the fill/drain
# bubble shrinks from GPipe's (S-1)/(S+M-1) of step time to
# (S-1)/(V*S+M-1) — at M=S=4, V=2 that is 27% vs 43%.  Because the
# whole schedule is one differentiable loop of ppermutes, jax.grad
# produces the mirrored backward schedule automatically (the transpose
# of ppermute is the reverse ppermute).
# --------------------------------------------------------------------------

def interleaved_ticks(n_stages: int, n_virtual: int,
                      n_microbatches: int) -> int:
    """Total schedule ticks (per-device time in single-layer units)."""
    return n_virtual * n_stages + n_microbatches - 1


def gpipe_ticks(n_stages: int, n_virtual: int, n_microbatches: int) -> int:
    """GPipe per-device time in the same units: each of the S+M-1 ticks
    runs all V layers the device owns."""
    return n_virtual * (n_stages + n_microbatches - 1)


def interleaved_apply(stage_fn: Callable, n_stages: int, n_virtual: int,
                      axis_name: str = "pp"):
    """Per-device body of the interleaved pipeline; call inside shard_map.

    ``stage_fn(layer_params, x) -> y`` is ONE layer (virtual stage);
    uniform shapes.  Returns ``apply(vstage_params, x_microbatches)``
    where ``vstage_params`` has leading axis V (this device's virtual
    stages, ring order: global layer j*S + d) and ``x_microbatches`` is
    (M, mb, ...) with M <= S (the small-microbatch regime interleaving
    exists for; larger M would collide two microbatches on one device
    in the same tick).
    """
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def apply(vstage_params, x_mb):
        idx = lax.axis_index(axis_name)
        M = x_mb.shape[0]
        if M > n_stages:
            raise ValueError(
                f"interleaved schedule needs M <= S (got M={M}, "
                f"S={n_stages}); use gpipe_apply for deep microbatching")
        V = n_virtual
        T = interleaved_ticks(n_stages, V, M)
        carry = jnp.zeros_like(x_mb[0])
        out = jnp.zeros_like(x_mb)
        for t in range(T):
            # device d at tick t serves round j = (t - d) // S; clip to
            # the valid range (out-of-range ticks are bubble — the
            # computed garbage is never routed into an output)
            j = jnp.clip((t - idx) // n_stages, 0, V - 1)
            params_t = jax.tree.map(lambda a: a[j], vstage_params)
            feed = x_mb[min(t, M - 1)]
            inp = jnp.where((idx == 0) & (t < M), feed, carry)
            y = stage_fn(params_t, inp)
            m = t - (V * n_stages - 1)
            if m >= 0:
                write = jnp.where(idx == n_stages - 1, y, out[m])
                out = out.at[m].set(write)
            carry = lax.ppermute(y, axis_name, perm)
        mask = (idx == n_stages - 1).astype(out.dtype)
        return lax.psum(out * mask, axis_name)

    return apply


def interleave_params(layer_params, n_stages: int):
    """Rearrange a (L, ...) layer stack into the interleaved layout
    (S, V, ...): device d's round j applies global layer j*S + d."""
    def rearrange(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(
                f"layer count {L} not divisible by pp size {n_stages}")
        V = L // n_stages
        # index [d, j] -> layer j*S + d
        idx = (jnp.arange(V)[None, :] * n_stages
               + jnp.arange(n_stages)[:, None])
        return a[idx.reshape(-1)].reshape((n_stages, V) + a.shape[1:])
    return jax.tree.map(rearrange, layer_params)


def pipeline_forward_interleaved(stage_fn: Callable, layer_params, x,
                                 mesh: Mesh, n_microbatches: int,
                                 axis_name: str = "pp",
                                 batch_axis_name: Optional[str] = "dp"):
    """Interleaved-GPipe pipeline forward (virtual stages, fill-drain).

    Cuts the schedule bubble from GPipe's (S-1)/(S+M-1) to
    (S-1)/(V*S+M-1) by circulating each microbatch V times around the
    ring.  NOTE: this is a *forward* whose backward (under ``jax.grad``)
    replays after the whole forward, so all M microbatches' activations
    stay live — it does NOT have true 1F1B's O(S) activation bound.  For
    the activation-bounded schedule use
    :func:`pipeline_value_and_grad_1f1b`.

    ``layer_params``: pytree with leading axis L = V*S (the plain layer
    stack, in network order); rearranged internally to the interleaved
    placement.  Same contract as :func:`pipeline_forward` otherwise.
    """
    S = mesh.shape[axis_name]
    L = jax.tree.leaves(layer_params)[0].shape[0]
    V = L // S
    if L % S:
        raise ValueError(
            f"interleaved: layer count {L} not divisible by S={S}")
    inter = interleave_params(layer_params, S)
    body = interleaved_apply(stage_fn, S, V, axis_name)
    dp = (batch_axis_name
          if batch_axis_name and batch_axis_name in mesh.axis_names
          else None)
    n_dp = mesh.shape[dp] if dp else 1
    if x.shape[0] % (n_dp * n_microbatches):
        raise ValueError(
            f"interleaved: batch {x.shape[0]} not divisible by dp({n_dp}) "
            f"x n_microbatches({n_microbatches})")

    def full(params, xb):
        local = jax.tree.map(lambda a: a[0], params)   # drop sharded S
        M = n_microbatches
        xmb = xb.reshape((M, xb.shape[0] // M) + xb.shape[1:])
        out = body(local, xmb)
        return out.reshape(xb.shape[0:1] + out.shape[2:])

    pspec = jax.tree.map(lambda _: PartitionSpec(axis_name), inter)
    xspec = PartitionSpec(dp)
    return shard_map(full, mesh=mesh, in_specs=(pspec, xspec),
                     out_specs=xspec, check_vma=False)(inter, x)


def pipeline_forward_1f1b(*args, **kwargs):
    """Deprecated alias for :func:`pipeline_forward_interleaved`.

    The schedule it runs is interleaved fill-drain (smaller bubble), not
    activation-bounded 1F1B; the honest name is ``interleaved``.  For
    the true 1F1B training step see :func:`pipeline_value_and_grad_1f1b`.
    """
    warnings.warn(
        "pipeline_forward_1f1b is renamed pipeline_forward_interleaved "
        "(it is an interleaved fill-drain schedule, not activation-"
        "bounded 1F1B); for true 1F1B use pipeline_value_and_grad_1f1b",
        DeprecationWarning, stacklevel=2)
    return pipeline_forward_interleaved(*args, **kwargs)


# --------------------------------------------------------------------------
# True 1F1B: activation-bounded forward/backward interleaving.
#
# The defining property of 1F1B (PipeDream-flush / Megatron-LM's
# schedule) is that backward work for microbatch m starts as soon as its
# forward clears the last stage, so each device holds activations for at
# most O(S) in-flight microbatches — NOT O(M) as in GPipe-under-
# ``jax.grad`` (whose backward replays only after the entire forward).
#
# SPMD formulation: one `lax.scan` over T = M + 2S - 2 ticks.  Every
# tick each device runs one forward slot (microbatch  mf = t - s  when
# valid) and one backward slot (microbatch  mb = t - (2S-2) + s).
# Activations hop +1 on the ring after the F slot, cotangents hop -1
# after the B slot.  The last stage seeds each microbatch's cotangent
# from the loss the same tick its forward lands (B(S-1,m) shares tick
# m+S-1 with F(S-1,m)).
#
# Memory: the only cross-tick activation state is a stash of *stage
# inputs*, one slot per in-flight microbatch — a ring buffer of
# W = min(2S-1, M) entries (stage s holds at most 2S-1-2s in flight;
# entry m is written at tick m+s and read at tick m+2S-2-s, so W=2S-1
# slots never collide).  The backward slot recomputes its stage forward
# from the stashed input (``jax.vjp`` at backward time) — the standard
# remat trade: each tick costs 2f+b instead of f+b, identical to what
# GPipe-under-grad pays once ``jax.checkpoint`` is on, but with the
# activation working set O(S·|input|) instead of O(M·|residuals|).
# This is what unlocks deep microbatching (M >> S): bubble fraction
# (2S-2)/(M+2S-2) -> 0 while memory stays flat in M
# (pinned by tests/test_parallel_extra.py memory-growth test).
#
# The reference has no pipeline parallelism at all (its model
# parallelism is per-layer ctx placement, docs model_parallel_lstm.md);
# this is north-star scaling work per SURVEY §7.
# --------------------------------------------------------------------------

def one_f_one_b_ticks(n_stages: int, n_microbatches: int) -> int:
    """Total 1F1B schedule ticks; each tick is one F slot + one B slot."""
    return n_microbatches + 2 * n_stages - 2


def one_f_one_b_apply(stage_fn: Callable, loss_fn: Callable, n_stages: int,
                      n_microbatches: int, axis_name: str = "pp",
                      return_input_grad: bool = False):
    """Per-device 1F1B training-step body; call inside shard_map.

    ``stage_fn(stage_params, x) -> y`` is one stage (uniform shapes);
    ``loss_fn(y, target) -> scalar`` is applied to the last stage's
    output per microbatch.  Returns ``apply(stage_params, x_mb, t_mb)``
    -> ``(mean_loss, grads)`` where ``x_mb``/``t_mb`` are (M, mb, ...)
    microbatches and ``grads`` matches ``stage_params`` (this device's
    stage only; loss is replicated over the axis).  With
    ``return_input_grad`` the result is ``(loss, grads, dx_mb)`` where
    ``dx_mb`` is d(loss)/d(x_mb) — stage 0 collects its backward-slot
    input cotangents per microbatch (for chaining e.g. an embedding
    lookup in front of the pipeline).
    """
    S, M = n_stages, n_microbatches
    W = min(2 * S - 1, M)          # stash ring-buffer slots (O(S), not O(M))
    T = one_f_one_b_ticks(S, M)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def apply(stage_params, x_mb, t_mb):
        idx = lax.axis_index(axis_name)
        carry_f = jnp.zeros_like(x_mb[0])
        stash = jnp.zeros((W,) + x_mb.shape[1:], x_mb.dtype)
        # probe the output/cotangent shape once (abstract eval only)
        y_shape = jax.eval_shape(stage_fn, stage_params, x_mb[0])
        carry_b = jnp.zeros(y_shape.shape, y_shape.dtype)
        grads0 = jax.tree.map(jnp.zeros_like, stage_params)
        loss0 = jnp.zeros((), jnp.float32)
        dx0 = jnp.zeros_like(x_mb) if return_input_grad else \
            jnp.zeros((), x_mb.dtype)

        def tick(carry, t):
            carry_f, carry_b, stash, grads, loss_acc, dx_acc = carry
            # ---- F slot: microbatch mf = t - idx flows GPipe-style
            mf = t - idx
            valid_f = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            feed = lax.dynamic_index_in_dim(x_mb, mf_c, 0, keepdims=False)
            inp = jnp.where(idx == 0, feed, carry_f)
            slot_f = mf_c % W
            old = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(valid_f, inp, old), slot_f, 0)
            y = stage_fn(stage_params, inp)
            new_carry_f = lax.ppermute(y, axis_name, fwd_perm)
            # ---- B slot: microbatch mb = t - (2S-2) + idx drains the ring
            mb = t - (2 * S - 2) + idx
            valid_b = (mb >= 0) & (mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            xin = lax.dynamic_index_in_dim(stash, mb_c % W, 0,
                                           keepdims=False)
            y2, vjp_fn = jax.vjp(stage_fn, stage_params, xin)
            tgt = lax.dynamic_index_in_dim(t_mb, mb_c, 0, keepdims=False)
            loss_m, dldy = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt))(y2)
            # last stage seeds from the loss; others consume the ring
            cot = jnp.where(idx == S - 1, (dldy / M).astype(y2.dtype),
                            carry_b)
            dparams, dx = vjp_fn(cot)
            grads = jax.tree.map(
                lambda g, d: g + jnp.where(valid_b, d, jnp.zeros_like(d)),
                grads, dparams)
            loss_acc = loss_acc + jnp.where(
                valid_b & (idx == S - 1), loss_m / M, 0.0).astype(
                    jnp.float32)
            if return_input_grad:
                # stage 0's backward-slot dx IS d(loss)/d(x_mb[mb])
                slot = mb_c
                old_dx = lax.dynamic_index_in_dim(dx_acc, slot, 0,
                                                  keepdims=False)
                dx_acc = lax.dynamic_update_index_in_dim(
                    dx_acc,
                    jnp.where(valid_b & (idx == 0),
                              dx.astype(dx_acc.dtype), old_dx),
                    slot, 0)
            new_carry_b = lax.ppermute(dx, axis_name, bwd_perm)
            return (new_carry_f, new_carry_b, stash, grads, loss_acc,
                    dx_acc), None

        (_, _, _, grads, loss_acc, dx_acc), _ = lax.scan(
            tick, (carry_f, carry_b, stash, grads0, loss0, dx0),
            jnp.arange(T))
        mask = (idx == S - 1).astype(loss_acc.dtype)
        loss = lax.psum(loss_acc * mask, axis_name)
        if return_input_grad:
            # dx lives on stage 0 only; replicate over the pp axis
            m0 = (idx == 0).astype(dx_acc.dtype)
            return loss, grads, lax.psum(dx_acc * m0, axis_name)
        return loss, grads

    return apply


def pipeline_value_and_grad_1f1b(stage_fn: Callable, loss_fn: Callable,
                                 stacked_params, x, targets, mesh: Mesh,
                                 n_microbatches: int, axis_name: str = "pp",
                                 batch_axis_name: Optional[str] = "dp",
                                 param_specs=None,
                                 return_input_grad: bool = False):
    """True 1F1B pipeline training step: ``(mean_loss, grads)``.

    Unlike :func:`pipeline_forward` (+ ``jax.grad``), backward work is
    interleaved per microbatch, so activation memory is bounded by the
    stage count S, not the microbatch count M — use this for deep
    microbatching (no ``M <= S`` restriction).  ``stacked_params`` has a
    leading stage axis of size mesh.shape[axis_name] (sharded over it);
    ``x``/``targets`` are (B, ...) batches split into ``n_microbatches``
    (and over ``batch_axis_name`` if present; grads/loss are averaged
    over it).  Returned grads carry the same stacked layout as
    ``stacked_params``.

    ``param_specs``: optional pytree of PartitionSpecs matching
    ``stacked_params`` for additional intra-stage sharding (e.g. tensor
    parallelism: P('pp', None, 'tp') on a column-parallel weight — the
    stage_fn is then responsible for its own 'tp' collectives).
    Defaults to P(axis_name) on every leaf.  ``return_input_grad``
    additionally returns d(mean_loss)/dx with x's sharding — already
    scaled for the dp-mean, so a caller chains it directly (e.g. into
    an embedding scatter; summing each shard's rows yields the global
    gradient).
    """
    S = mesh.shape[axis_name]
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"1f1b: param leading (stage) axis {leaf.shape[0]} != pp "
                f"mesh size {S} — one stage per device")
    dp = (batch_axis_name
          if batch_axis_name and batch_axis_name in mesh.axis_names
          else None)
    n_dp = mesh.shape[dp] if dp else 1
    if x.shape[0] % (n_dp * n_microbatches):
        raise ValueError(
            f"1f1b: batch {x.shape[0]} not divisible by dp({n_dp}) x "
            f"n_microbatches({n_microbatches})")
    if targets.shape[0] != x.shape[0]:
        raise ValueError(
            f"1f1b: targets batch {targets.shape[0]} != x batch "
            f"{x.shape[0]} (a mismatch would silently broadcast in "
            f"loss_fn)")
    body = one_f_one_b_apply(stage_fn, loss_fn, S, n_microbatches,
                             axis_name,
                             return_input_grad=return_input_grad)

    def full(params, xb, tb):
        local = jax.tree.map(lambda a: a[0], params)   # drop sharded S
        M = n_microbatches
        xmb = xb.reshape((M, xb.shape[0] // M) + xb.shape[1:])
        tmb = tb.reshape((M, tb.shape[0] // M) + tb.shape[1:])
        res = body(local, xmb, tmb)
        loss, grads = res[0], res[1]
        if dp:
            loss = lax.pmean(loss, dp)
            grads = jax.tree.map(lambda g: lax.pmean(g, dp), grads)
        grads = jax.tree.map(lambda g: g[None], grads)
        if return_input_grad:
            dx = res[2].reshape(xb.shape)
            if dp:
                # dx rows live only on their own dp shard (a pmean
                # would mix different batch rows); the global-mean loss
                # scales each shard's contribution by 1/n_dp
                dx = (dx / n_dp).astype(dx.dtype)
            return loss, grads, dx
        return loss, grads

    if param_specs is None:
        pspec = jax.tree.map(lambda _: PartitionSpec(axis_name),
                             stacked_params)
    else:
        pspec = param_specs
    xspec = PartitionSpec(dp)
    out_specs = (PartitionSpec(), pspec) + \
        ((xspec,) if return_input_grad else ())
    return shard_map(full, mesh=mesh, in_specs=(pspec, xspec, xspec),
                     out_specs=out_specs,
                     check_vma=False)(stacked_params, x, targets)
