"""Ring attention: sequence/context parallelism over a mesh axis.

Each device holds a sequence shard of Q, K, V.  K/V shards rotate
around the ring (`lax.ppermute` → XLA collective-permute riding ICI)
while every device folds the visiting block into flash-attention
online-softmax accumulators — attention over sequences far larger than
one chip's HBM, with compute/communication overlap handled by XLA's
async collectives.

The reference has no equivalent (SURVEY.md §5: "Long-context / sequence
parallelism: absent"); this is the capability the TPU build adds.
Expressed with `lax.scan` over ring steps so it is differentiable
(the transpose of ppermute is the reverse ppermute — backward runs the
ring the other way for free).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec
from jax import shard_map

from ..ops.attention import online_block_update, _NEG_INF

__all__ = ["ring_attention", "ring_self_attention",
           "ring_flash_attention", "ring_flash_self_attention",
           "seq_shard_call"]


def seq_shard_call(body, mesh: Mesh, axis_name: str, q, k, v,
                   check_vma: bool = False):
    """Shared wrapper for the sequence-parallel attention schemes:
    shard the S axis of (B, H, S, D) tensors over ``axis_name`` and run
    ``body(q, k, v)`` under shard_map.  The device_put is a sharding
    constraint under jit; eagerly (e.g. a deferred-init warm-up
    forward) it moves single-device arrays onto the mesh so shard_map
    accepts them either way."""
    spec = PartitionSpec(None, None, axis_name, None)
    sh = jax.sharding.NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(q, sh), jax.device_put(k, sh),
               jax.device_put(v, sh))
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=check_vma)(q, k, v)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Per-shard ring attention body; call inside shard_map/pjit.

    q: (B, H, S_local, D); k, v: (B, Hkv, S_local, D) — this device's
    sequence shard.  GQA/MQA: with Hkv < H the SMALL K/V blocks rotate
    around the ring (minimal collective-permute traffic) and are
    broadcast to the query groups only at each local block update.
    Returns the local output shard (B, H, S_local, D).
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    if hkv <= 0 or h % hkv:
        raise ValueError(f"q heads ({h}) not divisible by kv heads "
                         f"({hkv})")
    group = h // hkv
    sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    q32 = q.astype(jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, ring_step):
        o, m, l, kc, vc = carry
        kv_idx = (my - ring_step) % n

        def update(o, m, l):
            mask = None
            if causal:
                qpos = (my * sq
                        + lax.broadcasted_iota(jnp.int32, (b, h, sq, sk), 2))
                kpos = (kv_idx * sk
                        + lax.broadcasted_iota(jnp.int32, (b, h, sq, sk), 3))
                mask = qpos >= kpos
            ke = jnp.repeat(kc, group, axis=1) if group > 1 else kc
            ve = jnp.repeat(vc, group, axis=1) if group > 1 else vc
            return online_block_update(o, m, l, q32, ke, ve, scale, mask)

        if causal:
            # shards strictly above the diagonal contribute nothing —
            # skip both matmuls, keep only the ring rotation
            o, m, l = lax.cond(kv_idx <= my, update,
                               lambda o, m, l: (o, m, l), o, m, l)
        else:
            o, m, l = update(o, m, l)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o, m, l, kc, vc), None

    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l).astype(q.dtype)


# --------------------------------------------------------------------------
# Ring FLASH attention: the visiting K/V shard is consumed by the
# Pallas flash kernel (scores never materialize in HBM — VMEM-blocked),
# and per-shard (out, lse) pairs merge in log-sum-exp space.  The
# backward is the ring-flash scheme: re-run the ring with the FINAL lse
# (flash semantics: p = exp(s_block - lse_final)), accumulate dq
# locally while dk/dv accumulators ride the rotating K/V buffers so
# each shard's gradient arrives home after the full cycle.
#
# vs `ring_attention` above: that path materializes each local
# (S_q x S_k) f32 score block per ring step; this one keeps the block
# math inside the flash kernel.  GQA note: K/V are expanded to the
# query head count BEFORE the ring here, so rotation traffic is
# group x larger than ring_attention's small-KV rotation — prefer
# ring_attention for extreme GQA ratios, ring_flash_attention for
# long-context dense/moderate-GQA attention.
# --------------------------------------------------------------------------

def _merge_lse(o, lse, ob, lseb):
    """Combine two normalized partial attentions in logsumexp space."""
    new = jnp.logaddexp(lse, lseb)
    w1 = jnp.exp(lse - new)[..., None]
    w2 = jnp.exp(lseb - new)[..., None]
    return o * w1 + ob.astype(o.dtype) * w2, new


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, bq, bk):
    from ..ops.attention import _fa_forward_pallas

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    o0 = jnp.zeros((b * h, sq, d), jnp.float32)
    lse0 = jnp.full((b * h, sq), _NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        o, lse, kc, vc = carry
        kf = kc.reshape(b * h, sk, d)
        vf = vc.reshape(b * h, sk, d)

        def full_block(o, lse):
            ob, lb = _fa_forward_pallas(qf, kf, vf, False, scale, bq, bk)
            return _merge_lse(o, lse, ob, lb)

        def diag_block(o, lse):
            ob, lb = _fa_forward_pallas(qf, kf, vf, True, scale, bq, bk)
            return _merge_lse(o, lse, ob, lb)

        if causal:
            kv_idx = (my - t) % n
            o, lse = lax.cond(
                kv_idx > my, lambda o, l: (o, l),
                lambda o, l: lax.cond(kv_idx == my, diag_block,
                                      full_block, o, l), o, lse)
        else:
            o, lse = full_block(o, lse)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o, lse, kc, vc), None

    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                                 jnp.arange(n))
    return o.reshape(b, h, sq, d).astype(q.dtype), lse


def _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name, causal,
                         scale, bq, bk):
    from ..ops.attention import _fa_backward_pallas

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    outf = out.reshape(b * h, sq, d)
    dof = do.reshape(b * h, sq, d)
    perm = [(i, (i + 1) % n) for i in range(n)]
    dq0 = jnp.zeros((b * h, sq, d), jnp.float32)
    # delta is loop-invariant (do/out fixed across ring steps): hoist
    delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                    axis=-1)

    def step(carry, t):
        dq, dkc, dvc, kc, vc = carry
        kf = kc.reshape(b * h, sk, d)
        vf = vc.reshape(b * h, sk, d)

        def grads(block_causal):
            def run(_):
                # flash backward against the GLOBAL lse: per-block
                # p = exp(s_b - lse_final) is exactly this block's
                # share of the final attention
                return _fa_backward_pallas(
                    block_causal, scale, bq, bk,
                    (qf, kf, vf, outf, lse), dof, delta=delta)
            return run

        zero = lambda _: (jnp.zeros_like(qf), jnp.zeros_like(kf),
                          jnp.zeros_like(vf))
        if causal:
            kv_idx = (my - t) % n
            dqb, dkb, dvb = lax.cond(
                kv_idx > my, zero,
                lambda u: lax.cond(kv_idx == my, grads(True),
                                   grads(False), u), 0)
        else:
            dqb, dkb, dvb = grads(False)(0)
        dq = dq + dqb.astype(jnp.float32)
        dkc = dkc + dkb.astype(jnp.float32).reshape(dkc.shape)
        dvc = dvc + dvb.astype(jnp.float32).reshape(dvc.shape)
        # gradients ride home with their shards
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        dkc = lax.ppermute(dkc, axis_name, perm)
        dvc = lax.ppermute(dvc, axis_name, perm)
        return (dq, dkc, dvc, kc, vc), None

    init = (dq0, jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32), k, v)
    (dq, dk, dv, _, _), _ = lax.scan(step, init, jnp.arange(n))
    return (dq.reshape(b, h, sq, d).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, scale, bq, bk):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                  bq, bk)
    return out


def _ring_flash_f(q, k, v, axis_name, causal, scale, bq, bk):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                    bq, bk)
    return out, (q, k, v, out, lse)


def _ring_flash_b(axis_name, causal, scale, bq, bk, res, do):
    q, k, v, out, lse = res
    return _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name,
                                causal, scale, bq, bk)


_ring_flash.defvjp(_ring_flash_f, _ring_flash_b)


def ring_flash_attention(q, k, v, axis_name: str = "sp",
                         causal: bool = False,
                         sm_scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None):
    """Per-shard ring attention with the Pallas flash kernel as the
    local block engine; call inside shard_map/pjit.  Same contract as
    :func:`ring_attention` for equal q/k shard lengths (GQA K/V are
    expanded to the query head count first — see the traffic note
    above); causal mode requires sq == sk per shard (the shard-index
    classification assumes aligned positions — use ring_attention for
    causal cross-attention over unequal shards).  Block sizes default
    to the env-tunable MXNET_TPU_FLASH_BLOCK_Q/_K like
    flash_attention."""
    from ..ops.attention import _flash_block_default

    b, h, sq, d = q.shape
    hkv = k.shape[1]
    if hkv <= 0 or h % hkv:
        raise ValueError(f"q heads ({h}) not divisible by kv heads "
                         f"({hkv})")
    if causal and sq != k.shape[2]:
        raise ValueError(
            f"ring_flash_attention(causal=True) needs equal per-shard "
            f"q/k lengths (got {sq} vs {k.shape[2]}); ring_attention "
            f"handles causal cross-attention over unequal shards")
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if block_q is None:
        block_q = _flash_block_default("Q")
    if block_k is None:
        block_k = _flash_block_default("K")
    return _ring_flash(q, k, v, axis_name, causal, scale, block_q,
                       block_k)


def ring_flash_self_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                              causal: bool = False,
                              sm_scale: Optional[float] = None,
                              block_q: Optional[int] = None,
                              block_k: Optional[int] = None):
    """shard_map wrapper for :func:`ring_flash_attention`."""
    fn = functools.partial(ring_flash_attention, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k)
    return seq_shard_call(fn, mesh, axis_name, q, k, v)


def ring_self_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                        causal: bool = False,
                        sm_scale: Optional[float] = None):
    """shard_map wrapper: shards the sequence axis of (B,H,S,D) over
    ``axis_name`` and runs ring attention across the mesh."""
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    return seq_shard_call(fn, mesh, axis_name, q, k, v)
