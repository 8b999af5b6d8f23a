"""Ulysses-style sequence parallelism: all-to-all context parallelism.

The second of the two context-parallel schemes (the other is
ring_attention.py).  Each device holds a sequence shard of Q/K/V
(B, H, S/P, D).  One `lax.all_to_all` re-shards from sequence to
HEADS: afterwards every device holds the FULL sequence for H/P of the
heads and runs ordinary attention locally — no per-step ring latency —
then a second all-to-all restores sequence sharding on the output.

Trade-off vs the ring (public technique, DeepSpeed-Ulysses,
arXiv:2309.14509): communication is two all-to-alls of activations
(O(B·S·E/P) per device) instead of (P-1) K/V collective-permutes;
attention compute is a single dense local call (flash-friendly).
Prefer Ulysses when heads ≥ devices and the per-step latency of the
ring matters; prefer the ring when heads < devices or K/V are small
(GQA) so rotating them is cheaper than re-sharding activations.

The reference has no equivalent (SURVEY.md §5: long-context /
sequence parallelism absent) — this is TPU-native capability, the
all-to-alls ride ICI.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

from ..ops.attention import attention_reference

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def ulysses_attention(q, k, v, axis_name: str = "sp",
                      causal: bool = False,
                      sm_scale: Optional[float] = None,
                      use_flash: bool = False):
    """Per-shard Ulysses body; call inside shard_map/pjit.

    q: (B, H, S_local, D); k, v: (B, Hkv, S_local, D) — this device's
    sequence shard.  Q heads must divide by the axis size.  GQA K/V
    whose head count divides the axis ride the all-to-all SMALL
    (1/group of the traffic) and expand locally afterwards; a head
    count that doesn't divide is pre-expanded (full traffic).
    """
    p = lax.psum(1, axis_name)
    b, h, s_loc, d = q.shape
    if h % p:
        raise ValueError(
            f"ulysses: num_heads {h} not divisible by axis size {p}")
    hkv = k.shape[1]
    if hkv <= 0 or h % hkv:
        raise ValueError(f"ulysses: q heads ({h}) not divisible by kv "
                         f"heads ({hkv})")
    group = h // hkv
    if hkv % p:
        # grouped K/V don't re-shard evenly: pre-expand to full head
        # count (pays group x the K/V all-to-all traffic)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        group = 1
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    def seq_to_heads(x):
        # (B, H, S/P, D) -> (B, H/P, S, D): split the head axis across
        # the mesh, concatenate the sequence axis
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    if group > 1:
        # GQA with hkv % p == 0: the SMALL K/V rode the all-to-all
        # (1/group of the traffic); device i's kv heads
        # [i·hkv/p, (i+1)·hkv/p) are exactly the groups its q heads
        # [i·h/p, (i+1)·h/p) consume, so a local repeat aligns them
        kh = jnp.repeat(kh, group, axis=1)
        vh = jnp.repeat(vh, group, axis=1)
    # full local sequence for a head subset: ordinary single-device
    # attention — with use_flash the Pallas flash kernel (VMEM-blocked
    # scores + custom-vjp backward) replaces the materialized-scores
    # path for long-context memory behavior
    if use_flash:
        from ..ops.attention import flash_attention
        out = flash_attention(qh, kh, vh, causal=causal,
                              sm_scale=scale)
    else:
        out = attention_reference(qh, kh, vh, causal=causal,
                                  sm_scale=scale)
    # (B, H/P, S, D) -> (B, H, S/P, D)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_self_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                           causal: bool = False,
                           sm_scale: Optional[float] = None,
                           use_flash: bool = False):
    """shard_map wrapper: shards the sequence axis of (B,H,S,D) over
    ``axis_name`` and runs Ulysses all-to-all attention across the
    mesh (mirror of ring_self_attention's contract)."""
    from .ring_attention import seq_shard_call

    def fn(qq, kk, vv):
        return ulysses_attention(qq, kk, vv, axis_name=axis_name,
                                 causal=causal, sm_scale=sm_scale,
                                 use_flash=use_flash)

    # pallas_call outputs (the use_flash local engine) carry no vma
    # annotation, so the checker must be off for flash; the dense path
    # keeps the shard_map vma validation it always had
    return seq_shard_call(fn, mesh, axis_name, q, k, v,
                          check_vma=not use_flash)
