"""Paged attention: one query token per slot over a paged KV pool.

The decode serving plane (serving/decode/) keeps every slot's KV
history in pre-allocated page pools ``(num_pages, page_size, Hkv*D)`` —
one whole buffer per layer for K and one for V, which is what this
kernel is handed (the KV heads folded into the lane axis; a
``(num_pages, page_size, Hkv, D)`` pool is accepted and viewed the same
way) — plus a per-slot page table
``(max_slots, pages_per_slot)`` — sequence state lives behind traced
integer indices, so one compiled ``decode_step`` serves any mix of
lengths (the fixed-shape-executable invariant, docs/ARCHITECTURE.md
"Decode serving").

The Pallas path rides ``PrefetchScalarGridSpec``: the page table and
per-slot lengths are scalar-prefetched, and the K/V BlockSpec index
maps dereference ``table[slot, page]`` directly, so the pipeline DMAs
exactly the pages each slot owns — no gather materialization.  Grid is
``(slots, pages_per_slot, page_size // block_k)`` with online-softmax
f32 accumulators in VMEM scratch persisting across the two inner
dims; pages wholly past a slot's length are skipped via ``pl.when``.
Slots with length 0 (inactive) produce exact zeros, matching the
oracle.

Grouped-query attention: ``q`` may carry ``R`` times the pool's KV
heads (query head ``h`` reads KV head ``h // R``).  The pool is sized
by the KV heads and nothing is repeated in HBM: the query heads are
dealt into ``R`` rows of ``Hkv*D`` lanes (row ``r`` holds query head
``g*R + r`` over KV head ``g``'s lanes), each K/V block is fetched once
and every row runs the multi-head arithmetic against it.  ``R == 1``
is multi-head attention, one row.

Heads of a whole number of lane tiles (``D % 128 == 0``) take a second
form of the kernel (:func:`_pa_kernel_lanes`): a KV head's ``D`` lanes
are an aligned slice of the block, so the scores of its ``R`` query
heads are one small matmul against that slice and the values another,
on the MXU in the pool's dtype, where the first form multiplies a
``(block_k, Hkv*D)`` tile on the vector unit once per row.  Its index
maps also stop at the slot's last live block: a grid step past it asks
for the block that is already there and moves nothing.

The XLA lowering (:func:`paged_attention_reference`) gathers
``pool[tables]`` and runs a masked softmax — the numerics oracle the
parity tests pin the kernel against across ragged lengths.  No call
site switches to it.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels as _kernels
from .registry import register

__all__ = ["paged_attention", "paged_attention_reference"]

_NEG_INF = -1e30

_PAGED_ENV_KEY = "MXNET_TPU_PAGED_BLOCK_K"
_paged_env_snapshot: tuple = (False,)          # impossible sentinel


def _kv_heads(q, k_pool) -> int:
    """KV heads of a pool in either layout, checked against ``q``."""
    h, d = q.shape[-2:]
    width = k_pool.shape[2] * (k_pool.shape[3] if k_pool.ndim == 4 else 1)
    kvh = width // d
    if kvh * d != width or kvh < 1 or h % kvh:
        raise ValueError(
            f"pool width {width} is not a whole number of heads of {d} "
            f"that divides the {h} query heads")
    return kvh


def paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                              sm_scale=None):
    """Gather-based oracle: q (S, H, D), pools (pages, ps, Hkv*D) or
    (pages, ps, Hkv, D) with H a multiple of Hkv, tables (S, P) int32,
    lengths (S,) int32 → (S, H, D).  Positions at
    or past a slot's length are masked; length-0 slots yield zeros."""
    s_, h, d = q.shape
    ps = k_pool.shape[1]
    p_ = tables.shape[1]
    rep = h // _kv_heads(q, k_pool)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    k = k_pool[tables].reshape(s_, p_ * ps, h // rep, d).astype(jnp.float32)
    v = v_pool[tables].reshape(s_, p_ * ps, h // rep, d).astype(jnp.float32)
    if rep > 1:         # the oracle may repeat; the kernel does not
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32), k) * scale
    kpos = lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    mask = kpos < lengths[:, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("shk,skhd->shd", p / l, v)
    return out.astype(q.dtype)


def _pa_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, seg_ref, o_ref,
               acc_ref, m_ref, l_ref, *, sm_scale, block_k, page_size, rep):
    """Heads stay folded into the lane axis: every operand is a 2-D
    ``(rows, H*D)`` or ``(rows, H)`` tile, ``H`` the KV heads; ``q``,
    the output and the accumulators hold one such row for each of the
    ``rep`` query heads a KV head serves.  ``seg (H, H*D)`` is the 0/1
    head-membership matrix; a matmul against it is the per-head lane
    reduction (scores) or lane broadcast (probabilities, running
    statistics).  The TPU compiler refuses the batched-over-heads form
    (no free lhs dim for one query row; ``(block_k, H, D)`` tiles need
    a sublane<->major shape cast that bf16 packing rules out), and this
    form needs no transpose or reshape for any (H, D, dtype)."""
    s_i = pl.program_id(0)
    p_i = pl.program_id(1)
    b_i = pl.program_id(2)
    np_ = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((p_i == 0) & (b_i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[s_i]
    start = p_i * page_size + b_i * block_k

    def per_head(x, contract_lanes):
        # contract_lanes: (rows, H*D) -> (rows, H); else (rows, H) ->
        # (rows, H*D).  HIGHEST keeps the f32 operands exact on the MXU.
        dims = (((1,), (1 if contract_lanes else 0,)), ((), ()))
        return lax.dot_general(x, seg_ref[...], dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    @pl.when(start < length)
    def _body():
        k = k_ref[0].astype(jnp.float32)          # (block_k, H*D)
        v = v_ref[0].astype(jnp.float32)
        for r in range(rep):
            row = slice(r, r + 1)
            q = q_ref[0, row].astype(jnp.float32)     # (1, H*D)
            s = per_head(k * q, True) * sm_scale      # (block_k, H)
            kpos = start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = kpos < length
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[row]                       # (1, H)
            m_cur = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[row] = l_ref[row] * corr + p.sum(axis=0, keepdims=True)
            m_ref[row] = m_cur
            pv = (per_head(p, False) * v).sum(axis=0, keepdims=True)
            acc_ref[row] = acc_ref[row] * per_head(corr, False) + pv

    @pl.when((p_i == np_ - 1) & (b_i == nb - 1))
    def _finish():
        for r in range(rep):
            row = slice(r, r + 1)
            l = l_ref[row]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, row] = (acc_ref[row]
                             / per_head(l, False)).astype(o_ref.dtype)


def _pa_kernel_lanes(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, sm_scale, block_k,
                     page_size, heads, d):
    """Lane-aligned heads: KV head ``g`` is the lanes ``[g*d, (g+1)*d)``
    of every operand.  ``q``, ``o`` and ``acc`` hold the head's query
    rows (padded to 8) on the sublanes; the running maximum and sum are
    kept lane-broadcast, ``(rows, 128)`` a head.  Matmuls take their
    operands in the pool's dtype with float32 accumulation (bfloat16
    products are exact in float32; a float32 pool asks for the MXU's
    exact passes)."""
    s_i = pl.program_id(0)
    p_i = pl.program_id(1)
    b_i = pl.program_id(2)
    np_ = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((p_i == 0) & (b_i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[s_i]
    start = p_i * page_size + b_i * block_k
    exact = (lax.Precision.HIGHEST if k_ref.dtype == jnp.float32
             else lax.Precision.DEFAULT)

    @pl.when(start < length)
    def _body():
        for g in range(heads):
            lanes = slice(g * d, (g + 1) * d)
            q = q_ref[0, :, lanes].astype(k_ref.dtype)        # (rows, d)
            k = k_ref[0, :, lanes]                            # (block_k, d)
            v = v_ref[0, :, lanes]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32)
            s = s * sm_scale                                  # (rows, block_k)
            kpos = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos < length
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[g]                                 # (rows, 128)
            m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur[:, :1]), 0.0)
            l_ref[g] = l_ref[g] * corr + p.sum(axis=1, keepdims=True)
            m_ref[g] = m_cur
            pv = lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())), precision=exact,
                                 preferred_element_type=jnp.float32)
            acc_ref[:, lanes] = acc_ref[:, lanes] * corr[:, :1] + pv

    @pl.when((p_i == np_ - 1) & (b_i == nb - 1))
    def _finish():
        for g in range(heads):
            lanes = slice(g * d, (g + 1) * d)
            l = l_ref[g][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, lanes] = (acc_ref[:, lanes] / l).astype(o_ref.dtype)


def _paged_attention_lanes(q, k_pool, v_pool, tables, lengths, sm_scale,
                           block_k, h, rep):
    """The lane-aligned form: ``q (S, rep, h*d)`` dealt as in
    :func:`_paged_attention_pallas`, rows padded to a sublane tile."""
    s_, _, hd = q.shape
    d = hd // h
    num_pages, page_size = k_pool.shape[:2]
    p_ = tables.shape[1]
    nb = page_size // block_k
    rows = -(-rep // 8) * 8
    if rows != rep:
        q = jnp.pad(q, ((0, 0), (0, rows - rep), (0, 0)))

    def kv_map(s, p, b, tbl, ln):
        # the slot's last live block, for every step past it
        live = jnp.maximum(ln[s] - 1, 0) // block_k
        at = jnp.minimum(p * nb + b, live)
        return tbl[s, at // nb], at % nb, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, p_, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda s, p, b, tbl, ln: (s, 0, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, rows, hd),
                               lambda s, p, b, tbl, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((h, rows, 128), jnp.float32),
            pltpu.VMEM((h, rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel_lanes, sm_scale=float(sm_scale),
                          block_k=block_k, page_size=page_size, heads=h,
                          d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, rows, hd), q.dtype),
        interpret=jax.default_backend() != "tpu",
        name="mxtpu_paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      k_pool.reshape(num_pages, page_size, hd),
      v_pool.reshape(num_pages, page_size, hd))
    return out[:, :rep]


def _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                            sm_scale, block_k):
    s_, hq, d = q.shape
    num_pages, page_size = k_pool.shape[:2]
    p_ = tables.shape[1]
    h = _kv_heads(q, k_pool)
    rep = hq // h
    hd = h * d
    block_k = math.gcd(max(1, int(block_k)), page_size)   # tiles the page
    if block_k % 8:
        # a block's rows are a multiple of the 8-sublane tile or the
        # whole page (the TPU block-shape rule)
        block_k = page_size
    if d % 128 == 0:
        qr = q.reshape(s_, h, rep, d).swapaxes(1, 2).reshape(s_, rep, hd)
        out = _paged_attention_lanes(qr, k_pool, v_pool, tables, lengths,
                                     sm_scale, block_k, h, rep)
        return out.reshape(s_, rep, h, d).swapaxes(1, 2).reshape(s_, hq, d)
    kernel = functools.partial(
        _pa_kernel, sm_scale=float(sm_scale), block_k=block_k,
        page_size=page_size, rep=rep)
    seg = (jnp.arange(hd, dtype=jnp.int32)[None, :] // d
           == jnp.arange(h, dtype=jnp.int32)[:, None]).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, p_, page_size // block_k),
        in_specs=[
            pl.BlockSpec((1, rep, hd), lambda s, p, b, tbl, ln: (s, 0, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda s, p, b, tbl, ln: (tbl[s, p], b, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda s, p, b, tbl, ln: (tbl[s, p], b, 0)),
            pl.BlockSpec((h, hd), lambda s, p, b, tbl, ln: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, hd),
                               lambda s, p, b, tbl, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, h), jnp.float32),
            pltpu.VMEM((rep, h), jnp.float32),
        ],
    )
    if rep > 1:     # query head g*rep + r -> row r, KV head g's lanes
        q = q.reshape(s_, h, rep, d).swapaxes(1, 2)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, rep, hd), q.dtype),
        interpret=jax.default_backend() != "tpu",
        name="mxtpu_paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(s_, rep, hd),
      k_pool.reshape(num_pages, page_size, hd),
      v_pool.reshape(num_pages, page_size, hd), seg)
    if rep > 1:
        out = out.reshape(s_, rep, h, d).swapaxes(1, 2)
    return out.reshape(s_, hq, d)


# -- kernel-registry integration -------------------------------------------

def _paged_signature(q, k_pool, v_pool, tables, lengths, sm_scale=None):
    """Slots/pages/page-size are fixed by the serving deployment, so
    they key exactly; ragged per-slot lengths deliberately share one
    entry (they are data, not shape)."""
    from ..amp import policy as _amp_policy
    kvh = _kv_heads(q, k_pool)
    return (f"s{q.shape[0]}_h{q.shape[1]}_d{q.shape[2]}"
            + (f"_kv{kvh}" if kvh != q.shape[1] else "")
            + f"_ps{k_pool.shape[1]}_p{tables.shape[1]}",
            _amp_policy.kernel_key_dtype(str(q.dtype)))


def _paged_kernel_run(config, q, k_pool, v_pool, tables, lengths,
                      sm_scale=None):
    scale = (sm_scale if sm_scale is not None
             else 1.0 / math.sqrt(q.shape[-1]))
    return _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                   float(scale), int(config["block_k"]))


def _paged_kernel_fallback(q, k_pool, v_pool, tables, lengths,
                           sm_scale=None):
    return paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                                     sm_scale=sm_scale)


def _paged_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(17)
    slots, pps = case["slots"], case["pages_per_slot"]
    ps, h, d = case["page_size"], case["h"], case["d"]
    dtype = case.get("dtype", "float32")
    kvh = case.get("kv_h", h)       # fewer than h: grouped-query
    num_pages = slots * pps + 1
    q = jnp.asarray(rng.randn(slots, h, d) * 0.5, dtype=dtype)
    # the serving pool's layout: KV heads folded into the lane axis
    k_pool = jnp.asarray(rng.randn(num_pages, ps, kvh * d) * 0.5,
                         dtype=dtype)
    v_pool = jnp.asarray(rng.randn(num_pages, ps, kvh * d) * 0.5,
                         dtype=dtype)
    tables = jnp.asarray(
        rng.permutation(num_pages - 1)[:slots * pps].reshape(slots, pps),
        jnp.int32)
    # ragged lengths, a zero (inactive slot) included
    lengths = rng.randint(0, pps * ps + 1, size=(slots,))
    lengths[0] = 0
    return (q, k_pool, v_pool, tables,
            jnp.asarray(lengths, jnp.int32)), {}


_kernels.register_kernel(_kernels.KernelSpec(
    "paged_attention", version=2,
    run=_paged_kernel_run, fallback=_paged_kernel_fallback,
    config_space={"block_k": (16, 32, 64, 128)},
    default_config={"block_k": 64},
    signature=_paged_signature, make_args=_paged_make_args,
    tune_grid=({"slots": 8, "pages_per_slot": 4, "page_size": 64,
                "h": 4, "d": 64},
               {"slots": 4, "pages_per_slot": 8, "page_size": 128,
                "h": 8, "d": 64},
               {"slots": 5, "pages_per_slot": 4, "page_size": 32,
                "h": 10, "kv_h": 2, "d": 32},
               {"slots": 5, "pages_per_slot": 3, "page_size": 32,
                "h": 10, "kv_h": 2, "d": 128}),
))


def _resolve_paged_block(q, k_pool, v_pool, tables, lengths, scale):
    global _paged_env_snapshot
    env = (os.environ.get(_PAGED_ENV_KEY),)
    if env != _paged_env_snapshot:
        _paged_env_snapshot = env
        _kernels.invalidate("paged_attention")
    if env[0] is not None:
        try:
            v = int(env[0])
        except ValueError:
            v = 0
        if v > 0:
            return v
    sig, dt = _paged_signature(q, k_pool, v_pool, tables, lengths)
    cfg = _kernels.resolve(
        "paged_attention", sig, dt,
        tune_args=((q, k_pool, v_pool, tables, lengths),
                   {"sm_scale": scale}))
    return int(cfg["block_k"])


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    sm_scale=None, block_k=None):
    """One attention step per slot against its paged KV history.

    ``q (slots, H, D)`` — one query token per slot; ``k_pool/v_pool
    (num_pages, page_size, Hkv*D)`` or ``(num_pages, page_size, Hkv,
    D)``, ``H`` a multiple of ``Hkv`` (grouped-query attention: query
    head ``h`` reads KV head ``h // (H // Hkv)``);
    ``tables (slots, pages_per_slot)``
    int32 page ids; ``lengths (slots,)`` int32 valid context lengths
    (0 = inactive slot → zero output)."""
    scale = (sm_scale if sm_scale is not None
             else 1.0 / math.sqrt(q.shape[-1]))
    if block_k is None:
        block_k = _resolve_paged_block(q, k_pool, v_pool, tables,
                                       lengths, float(scale))
    return _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                   float(scale), int(block_k))


register("paged_attention", aliases=("_npx_paged_attention",))(
    lambda q, k_pool, v_pool, tables, lengths, sm_scale=None,
    block_k=None:
    paged_attention(q, k_pool, v_pool, tables, lengths,
                    sm_scale=sm_scale, block_k=block_k))
