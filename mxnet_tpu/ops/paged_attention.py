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

The Pallas kernel's work follows the live lengths, not the table's
size.  Its grid is one step a LIVE slot: the wrapper lists the slots of
a length in slot order and counts them, both are scalar-prefetched
beside the page table and the lengths, and the count bounds the grid (a
dynamic bound), so an idle slot costs no grid step at all; a call with
no live slot takes one step that walks nothing.  The pools stay whole
operands in HBM.  Inside a slot's step (:func:`_pa_walker`) a loop runs
over the slot's ``cdiv(length, block_k)`` live blocks and no further:
each block's pages are copied from ``pool[table[slot, page]]`` into one
of two VMEM buffers while the block before it is worked on, and a
slot's last block starts the first block of the next live slot on the
list, so the copies do not drain at a slot's end.  The output is one
VMEM block for the whole call, zeroed at the first step and copied out
after the last, so the idle slots' rows, which no step visits, are
exact zeros, matching the oracle.  A block is ``block_k`` rows: several
whole pages (``block_k // page_size``, one copy a page) or a part of
one page; online-softmax float32 accumulators live in VMEM scratch
across a slot's blocks, and the tail block's rows past the length are
masked.

Grouped-query attention: ``q`` may carry ``R`` times the pool's KV
heads (query head ``h`` reads KV head ``h // R``).  The pool is sized
by the KV heads and nothing is repeated in HBM: the query heads are
dealt into ``R`` rows of ``Hkv*D`` lanes (row ``r`` holds query head
``g*R + r`` over KV head ``g``'s lanes), each K/V block is fetched once
and every row runs against it.  ``R == 1`` is multi-head attention,
one row.

What is done with a block comes in two bodies under the one walker,
chosen by the heads' shape.  Multi-head attention with heads narrower
than a lane tile stays folded into the lane axis
(:func:`_folded_body`): a ``(block_k, Hkv*D)`` tile is multiplied on
the vector unit once per row, and 0/1 matmuls reduce and broadcast by
head.  Heads of a whole number of lane tiles (``D % 128 == 0``,
:func:`_lanes_body`): a KV head's ``D`` lanes are an aligned slice of
the block, so the scores of its ``R`` query heads are one small matmul
against that slice and the values another, on the MXU in the pool's
dtype.  GROUPED-QUERY heads narrower than a tile (``R > 1``, ``128 %
D == 0``) take the same body with the ``128 // D`` KV heads of a lane
tile PACKED as one: every query head is a row of its tile with zeros in
its neighbours' lanes, so a row's scores are its own KV head's and
nothing else, and of a row's ``128`` output lanes its own ``D`` are
kept (:func:`_pack_queries`, :func:`_unpack_outputs`).  At 4 query
heads over a KV head of 64 the tile's 8 rows are exactly a sublane
tile, where the folded form multiplies every block four times on the
vector unit (PERF.md section 6, PR 35).

A copy moves whole lane tiles: on a TPU a pool whose ``Hkv*D`` is no
multiple of 128 is gathered by XLA instead (the oracle below).

The XLA lowering (:func:`paged_attention_reference`) gathers
``pool[tables]`` and runs a masked softmax — the numerics oracle the
parity tests pin the kernel against across ragged lengths.  No call
site chooses it.

A latent cache (:func:`latent_attention`, ``mxtpu_latent_attention`` in
a device trace) is a third body on the same walker and ONE paged buffer
a layer: a row ``[c_kv | k_rope | padding]`` is the key of every query
head and its first ``rank`` lanes are every head's value, so a block is
copied once and read for the scores and for the values.  The queries
come absorbed (``q_nope`` through the key half of the up-projection),
``rank + rope`` lanes a head; the output stays in the latent space and
the caller takes it through the value half.  At 64 heads a block's two
products keep the MXU as long as its copy keeps the DMA engine, so this
body is staged: the walker runs the next block's scores beside this
block's softmax, over four copy buffers (:func:`_pa_walker`; the other
bodies, bound by their copies, keep two).
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

__all__ = ["paged_attention", "paged_attention_reference",
           "latent_attention", "latent_attention_reference",
           "latent_overlap_share"]

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


def _folded_body(q_ref, seg_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 sm_scale, rep):
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

    def per_head(x, contract_lanes):
        # contract_lanes: (rows, H*D) -> (rows, H); else (rows, H) ->
        # (rows, H*D).  HIGHEST keeps the f32 operands exact on the MXU.
        dims = (((1,), (1 if contract_lanes else 0,)), ((), ()))
        return lax.dot_general(x, seg_ref[...], dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    def block(k_ref, v_ref, start, length):
        k = k_ref[...].astype(jnp.float32)        # (block_k, H*D)
        v = v_ref[...].astype(jnp.float32)
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

    def finish(slot):
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[slot] = (acc_ref[...]
                       / per_head(l, False)).astype(o_ref.dtype)

    return block, finish


def _lanes_body(q_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale, heads, d):
    """Lane-aligned heads: KV head ``g`` is the lanes ``[g*d, (g+1)*d)``
    of every operand.  ``q``, ``o`` and ``acc`` hold the head's query
    rows (padded to 8) on the sublanes; the running maximum and sum are
    kept lane-broadcast, ``(rows, 128)`` a head.  Matmuls take their
    operands in the pool's dtype with float32 accumulation (bfloat16
    products are exact in float32; a float32 pool asks for the MXU's
    exact passes)."""

    def block(k_ref, v_ref, start, length):
        exact = (lax.Precision.HIGHEST if k_ref.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
        for g in range(heads):
            lanes = slice(g * d, (g + 1) * d)
            q = q_ref[0, :, lanes].astype(k_ref.dtype)        # (rows, d)
            k = k_ref[:, lanes]                               # (block_k, d)
            v = v_ref[:, lanes]
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

    def finish(slot):
        for g in range(heads):
            lanes = slice(g * d, (g + 1) * d)
            l = l_ref[g][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[slot, :, lanes] = (acc_ref[:, lanes]
                                     / l).astype(o_ref.dtype)

    return block, finish


def _latent_body(q_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale, rank):
    """One shared key row for every query head (a latent cache): the
    heads are the rows of ``q``, ``o`` and ``acc`` (padded to 8), a
    block's ``(block_k, width)`` tile is their keys whole and their
    values in its first ``rank`` lanes.  Two matmuls a block on the MXU
    in the pool's dtype with float32 accumulation; the running maximum
    and sum are kept lane-broadcast, ``(rows, 128)``.

    At 64 heads a block's two products take the MXU as long as its copy
    takes the DMA engine, so the body is STAGED (``depth`` 4,
    :func:`_pa_walker`): ``score`` is a block's first product alone,
    issued while the block before it is in ``update`` (the softmax, the
    values, the accumulators).  Only the block that holds a slot's last
    row (``tail``) is masked: every other is whole."""

    def score(q, kv_ref):
        exact = (lax.Precision.HIGHEST if kv_ref.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
        s = lax.dot_general(q.astype(kv_ref.dtype), kv_ref[...],
                            (((1,), (1,)), ((), ())), precision=exact,
                            preferred_element_type=jnp.float32)
        return s * sm_scale                                   # (rows, block_k)

    def update(s, kv_ref, start, length, tail):
        exact = (lax.Precision.HIGHEST if kv_ref.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
        if tail:
            kpos = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos < length
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]                                   # (rows, 128)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        if tail:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_cur
        pv = lax.dot_general(p.astype(kv_ref.dtype), kv_ref[:, :rank],
                             (((1,), (0,)), ((), ())), precision=exact,
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    def finish(slot):
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[slot] = (acc_ref[...] / l).astype(o_ref.dtype)

    return score, update, finish


# four buffers: with three, only one block's copies were in flight while
# a stage computed: 259 us a call against 236 at axk1_decode_reasoning's
# geometry on a v5e, and 236 at five (PERF.md section 6)
_latent_body.depth = 4


def _pa_walker(tbl_ref, len_ref, live_ref, count_ref, q_ref, *refs, body,
               block_k, pools=2):
    """One grid step a live slot: the grid is bounded by ``count_ref[0]``
    and step ``i`` works on slot ``live_ref[i]`` (the slots of a length,
    in slot order).  Inside a step a loop runs over the slot's live
    blocks alone, each copied from the ``pools`` paged buffers (K and V;
    one for a latent cache; whole operands, in HBM) into one of
    ``depth`` VMEM buffers a pool while the blocks before it are worked
    on.  Copies run ahead across slots: a slot's last blocks start the
    first blocks of the next slots on the list, ``live_ref[i + 1]`` and
    on, so the copy engine does not drain at a slot's end.
    ``at_ref`` carries that hand-over from step to step: the buffer the
    slot's first block is in.  ``o_ref`` is the whole output, zeroed at
    the first step; a slot's ``finish`` writes its rows, so an idle
    slot, which has no step, keeps zeros.

    The depth is the body's (its ``depth`` attribute, else 2).  A body
    of depth 2 returns ``(block, finish)``: two buffers, block ``j + 1``
    copied while ``block`` works on block ``j``.  A STAGED body (depth
    3 or more) returns ``(score, update, finish)``: ``depth`` buffers,
    and while ``update`` takes block ``j`` through its softmax and
    values, ``score`` already runs block ``j + 1``'s first product and
    the copies of blocks up to ``j + depth - 1`` are in flight (those
    may belong to the next ``depth - 1`` live slots).  The scores wait
    in ``s_ref`` for the next stage, and a slot's last stage scores the
    next slot's first block with that slot's queries, so the stages do
    not drain at a slot's end either.  For that the staged walk is
    handed the queries whole, in HBM (``q_ref``), and copies each live
    slot's once, one step ahead, into one of two buffers (``q_bufs``,
    slot ``live_ref[i]``'s in buffer ``i % 2``).

    A block is ``block_k`` rows: whole pages (one copy a page) or a
    part of one page.  Its rows past the length are masked by the body;
    a page index past the table's width reads the last column, whose
    rows are all masked."""
    depth = getattr(getattr(body, "func", body), "depth", 2)
    hbm, refs = refs[:pools], refs[pools:]
    if depth > 2:   # a block's scores, two slots' queries, their copies
        *refs, s_ref, q_bufs, q_sems = refs
    *consts, o_ref, acc_ref, m_ref, l_ref = refs[:-(pools + 2)]
    *bufs, sems, at_ref = refs[-(pools + 2):]
    slots, pages = tbl_ref.shape
    page_size = hbm[0].shape[1]
    rows = min(block_k, page_size)                # rows of one copy
    i = pl.program_id(0)
    s_i = live_ref[i]
    length = len_ref[s_i]
    n = pl.cdiv(length, block_k)
    later = i + 1 < count_ref[0]                  # a live slot follows
    then = live_ref[jnp.minimum(i + 1, slots - 1)]
    *stages, finish = body(q_ref, *consts, o_ref, acc_ref, m_ref, l_ref)

    def copies(slot, blk, buf):
        made = []
        for j in range(block_k // rows):
            if rows == page_size:
                page, src = blk * (block_k // rows) + j, slice(None)
            else:
                per_page = page_size // rows
                page = blk // per_page
                src = pl.ds(pl.multiple_of((blk % per_page) * rows, rows),
                            rows)
            page = tbl_ref[slot, jnp.minimum(page, pages - 1)]
            dst = pl.ds(j * rows, rows)
            for kind, (pool, buffer) in enumerate(zip(hbm, bufs)):
                made.append(pltpu.make_async_copy(
                    pool.at[page, src], buffer.at[buf, dst],
                    sems.at[kind, buf]))
        return made

    def start(slot, blk, buf):
        for copy in copies(slot, blk, buf):
            copy.start()

    def wait(slot, blk, buf):
        for copy in copies(slot, blk, buf):
            copy.wait()

    if depth > 2:
        score, update = stages

        def q_copy(slot, buf):
            return pltpu.make_async_copy(q_ref.at[slot], q_bufs.at[buf],
                                         q_sems.at[buf])

        mine, theirs = lax.rem(i, 2), lax.rem(i + 1, 2)
        lead = depth - 1                          # blocks copied ahead
        # the live slots after this one that a copy can reach, and their
        # blocks; a slot past the list is padding (never copied)
        nexts = [(live_ref[jnp.minimum(i + d, slots - 1)],
                  i + d < count_ref[0]) for d in range(1, lead + 1)]
        sizes = [pl.cdiv(len_ref[slot], block_k) for slot, _ in nexts]

        def ahead(j):
            """Slot and block of the walk's block ``j`` counted from this
            slot's first (``j < n + lead``), and whether it is: this
            slot's, or one of the next live slots'."""
            slot, blk, real, rest = s_i, j, j < n, j - n
            for (nxt, live), size in zip(nexts, sizes):
                here = (rest >= 0) & (rest < size)
                slot = jnp.where(here, nxt, slot)
                blk = jnp.where(here, rest, blk)
                real = real | (here & live)
                rest = rest - size
            return slot, blk, real

        def stage(j, buf, tail):
            """Block ``j`` (in ``buf``, its scores in ``s_ref``) through
            ``update`` beside the next block's ``score``; block ``j +
            lead`` copied.  ``tail``: the slot's last block, whose next
            is the next slot's first."""
            slot, blk, real = ahead(j + lead)
            nxt = lax.rem(buf + 1, depth)

            @pl.when(real)
            def _():
                start(slot, blk, lax.rem(buf + lead, depth))

            if tail:
                @pl.when(later)
                def _():
                    wait(then, 0, nxt)
                    q_copy(then, theirs).wait()
                q = q_bufs[theirs]
            else:
                wait(s_i, j + 1, nxt)
                q = q_bufs[mine]
            s_next = score(q, *(b.at[nxt] for b in bufs))
            update(s_ref[...], *(b.at[buf] for b in bufs), j * block_k,
                   length, tail)
            s_ref[...] = s_next
            return nxt

    @pl.when(i == 0)                  # the first live slot of the call
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)
        at_ref[0] = 0

        @pl.when(n > 0)               # else no slot is live
        def _():
            start(s_i, 0, 0)
            if depth > 2:             # its first block scored here
                q_copy(s_i, 0).start()
                for j in range(1, lead):
                    slot, blk, real = ahead(j)

                    @pl.when(real)
                    def _():
                        start(slot, blk, j)

                q_copy(s_i, 0).wait()
                wait(s_i, 0, 0)
                s_ref[...] = score(q_bufs[0], *(b.at[0] for b in bufs))

    if depth > 2:
        @pl.when(later)
        def _():
            q_copy(then, theirs).start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    if depth > 2:
        buf = lax.fori_loop(0, n - 1, lambda j, b: stage(j, b, False),
                            at_ref[0])

        @pl.when(n > 0)
        def _():
            at_ref[0] = stage(n - 1, buf, True)

        finish(s_i)
        return

    block, = stages

    def step(j, buf):
        more = j + 1 < n              # else: the next live slot's first

        @pl.when(more | later)
        def _():
            start(jnp.where(more, s_i, then), jnp.where(more, j + 1, 0),
                  1 - buf)

        for copy in copies(s_i, j, buf):
            copy.wait()
        block(*(b.at[buf] for b in bufs), j * block_k, length)
        return 1 - buf

    at_ref[0] = lax.fori_loop(0, n, step, at_ref[0])
    finish(s_i)


def _slot_rows(q):
    """The block of ``q`` a grid step reads: its live slot's rows."""
    return pl.BlockSpec((1, *q.shape[1:]),
                        lambda i, tbl, ln, live, n: (live[i], 0, 0))


def _walk(kernel, lengths, tables, *operands, specs, out_shape, scratch,
          name, on_tpu):
    """``kernel`` over the live slots of ``lengths``, one grid step each,
    and one step where none is live (it zeroes the output).  The slots
    of a length are listed in slot order, padded with slot 0, and
    counted; both are scalar-prefetched after the table and the
    lengths.  The output is one VMEM block for the whole call,
    single-buffered: it is copied out once, after the last step."""
    live = jnp.nonzero(lengths > 0, size=lengths.shape[0],
                       fill_value=0)[0].astype(jnp.int32)
    count = (lengths > 0).sum(dtype=jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(count[0], 1),),
        in_specs=specs,
        out_specs=pl.BlockSpec(out_shape.shape, lambda *_: (0, 0, 0),
                               pipeline_mode=pl.Buffered(1)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=not on_tpu,
        name=name,
    )(tables, lengths, live, count, *operands)


def _block_rows(block_k, page_size, pages):
    """The rows of a block the walker can copy, nearest ``block_k``."""
    block_k = max(1, int(block_k))
    if block_k >= page_size and page_size % 8 == 0:
        # whole pages, as many as the block holds and a slot has
        return min(block_k // page_size, pages) * page_size
    # a part of a page: its rows tile the page and are a multiple of
    # the 8-sublane tile, or the block is the page
    block_k = math.gcd(block_k, page_size)
    return page_size if block_k % 8 else block_k


def packs_heads(hq: int, h: int, d: int) -> bool:
    """Whether ``hq`` query heads over ``h`` K/V heads of ``d`` lanes
    are packed: grouped-query heads that share a lane tile, whole tiles
    of them."""
    return hq > h and d < 128 and 128 % d == 0 and h % (128 // d) == 0


def _pack_queries(q, h: int, rep: int, d: int):
    """Grouped-query heads narrower than a lane tile, ``pack = 128 //
    d`` KV heads a tile: ``q (slots, h * rep, d)`` as ``(slots, pack *
    rep, h * d)``, row ``i * rep + r`` holding, in every tile ``t``, the
    query head ``(t * pack + i) * rep + r`` in the lanes of KV head
    ``t * pack + i`` and zeros in the tile's other lanes."""
    pack = 128 // d
    eye = jnp.eye(pack, dtype=q.dtype)
    qh = q.reshape(q.shape[0], h // pack, pack, rep, d)
    return jnp.einsum("stird,ij->sirtjd", qh, eye).reshape(
        q.shape[0], pack * rep, h * d)


def _unpack_outputs(out, h: int, rep: int, d: int):
    """:func:`_pack_queries` undone for the outputs ``(slots, pack *
    rep, h * d)``: of a row's 128 lanes a tile, the ``d`` of its own KV
    head: ``(slots, h * rep, d)``."""
    pack = 128 // d
    eye = jnp.eye(pack, dtype=out.dtype)
    oh = out.reshape(out.shape[0], pack, rep, h // pack, pack, d)
    return jnp.einsum("sirtjd,ij->stird", oh, eye).reshape(
        out.shape[0], h * rep, d)


def _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                            sm_scale, block_k):
    return _paged_attention_jit(q, k_pool, v_pool, tables, lengths,
                                float(sm_scale), int(block_k),
                                jax.default_backend() == "tpu")


# Jitted on everything but the arrays: the layers of a step, and every
# executable that attends over the same grid of slots, share ONE trace
# of the kernel (PERF.md, PR 40).
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _paged_attention_jit(q, k_pool, v_pool, tables, lengths, sm_scale,
                         block_k, on_tpu):
    s_, hq, d = q.shape
    num_pages, page_size = k_pool.shape[:2]
    p_ = tables.shape[1]
    h = _kv_heads(q, k_pool)
    rep = hq // h
    hd = h * d
    if hd % 128 and on_tpu:
        # Mosaic copies whole lane tiles: a page of a pool narrower than
        # one, or of one and a part, is no source of a copy.  Such a pool
        # is gathered by XLA; the interpreter walks it like any other.
        return paged_attention_reference(q, k_pool, v_pool, tables,
                                         lengths, sm_scale=sm_scale)
    block_k = _block_rows(block_k, page_size, p_)
    packed = packs_heads(hq, h, d)
    used = rep * (128 // d) if packed else rep
    if packed:
        q = _pack_queries(q, h, rep, d)
    else:
        # query head g*rep + r -> row r, KV head g's lanes
        q = q.reshape(s_, h, rep, d).swapaxes(1, 2).reshape(s_, rep, hd)
    if packed or d % 128 == 0:
        # what the body takes for a head: a KV head, or a packed tile
        heads, width = (hd // 128, 128) if packed else (h, d)
        rows = -(-used // 8) * 8
        q = jnp.pad(q, ((0, 0), (0, rows - used), (0, 0)))
        body = functools.partial(_lanes_body, sm_scale=float(sm_scale),
                                 heads=heads, d=width)
        consts, const_specs = (), []
        stats = (heads, rows, 128)
    else:
        rows = rep
        body = functools.partial(_folded_body, sm_scale=float(sm_scale),
                                 rep=rep)
        seg = (jnp.arange(hd, dtype=jnp.int32)[None, :] // d
               == jnp.arange(h, dtype=jnp.int32)[:, None])
        consts = (seg.astype(jnp.float32),)
        const_specs = [pl.BlockSpec((h, hd), lambda *_: (0, 0))]
        stats = (rep, h)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    out = _walk(
        functools.partial(_pa_walker, body=body, block_k=block_k),
        lengths.astype(jnp.int32), tables.astype(jnp.int32), q,
        k_pool.reshape(num_pages, page_size, hd),
        v_pool.reshape(num_pages, page_size, hd), *consts,
        specs=[_slot_rows(q), whole, whole, *const_specs],
        out_shape=jax.ShapeDtypeStruct((s_, rows, hd), q.dtype),
        scratch=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM((2, block_k, hd), k_pool.dtype),
            pltpu.VMEM((2, block_k, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
        name="mxtpu_paged_attention", on_tpu=on_tpu)
    if packed:
        return _unpack_outputs(out[:, :used], h, rep, d)
    return out[:, :rep].reshape(s_, rep, h, d).swapaxes(1, 2).reshape(
        s_, hq, d)


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
    "paged_attention", version=4,       # 4: the grid walks the live slots
    run=_paged_kernel_run, fallback=_paged_kernel_fallback,
    config_space={"block_k": (16, 32, 64, 128)},
    default_config={"block_k": 64},
    signature=_paged_signature, make_args=_paged_make_args,
    # pages of 16 (a block of 1 to 8 pages), 64 and 128 (a part of a
    # page, or the page), 32 under grouped-query heads of both bodies
    tune_grid=({"slots": 6, "pages_per_slot": 8, "page_size": 16,
                "h": 4, "d": 64},
               {"slots": 8, "pages_per_slot": 4, "page_size": 64,
                "h": 4, "d": 64},
               {"slots": 4, "pages_per_slot": 8, "page_size": 128,
                "h": 8, "d": 64},
               {"slots": 5, "pages_per_slot": 4, "page_size": 32,
                "h": 20, "kv_h": 4, "d": 32},
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


# -- the latent cache ----------------------------------------------------------

def latent_attention_reference(q, pool, tables, lengths, *, rank, sm_scale):
    """Gather-based oracle of :func:`latent_attention`: ``q (S, H, W)``
    absorbed queries, ``pool (pages, ps, W)`` rows ``[c_kv | k_rope |
    padding]``, ``tables (S, P)``, ``lengths (S,)`` -> ``(S, H, rank)``:
    every head scores the whole row and sums its first ``rank`` lanes.
    Length-0 slots yield zeros."""
    s_, _, w = q.shape
    ps, p_ = pool.shape[1], tables.shape[1]
    kv = pool[tables].reshape(s_, p_ * ps, w).astype(jnp.float32)
    scores = jnp.einsum("shw,skw->shk", q.astype(jnp.float32), kv,
                        precision=lax.Precision.HIGHEST) * sm_scale
    kpos = lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    mask = kpos < lengths[:, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("shk,skr->shr", p / l, kv[..., :rank],
                     precision=lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def _latent_attention_pallas(q, pool, tables, lengths, rank, sm_scale,
                             block_k):
    return _latent_attention_jit(q, pool, tables, lengths, int(rank),
                                 float(sm_scale), int(block_k),
                                 jax.default_backend() == "tpu")


# Jitted on everything but the arrays, as _paged_attention_jit: the seven
# layers of a decode step share ONE trace of the staged kernel's body.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _latent_attention_jit(q, pool, tables, lengths, rank, sm_scale, block_k,
                          on_tpu):
    s_, h, w = q.shape
    page_size, p_ = pool.shape[1], tables.shape[1]
    if on_tpu and (w % 128 or rank % 128):
        # a copy moves whole lane tiles and the values are a lane-aligned
        # slice of the row: serving/decode/paged_kv.py pads its rows so
        raise ValueError(
            f"latent_attention: a row of {w} lanes with {rank} of values "
            f"is no whole number of 128-lane tiles; pad the row "
            f"(paged_kv.latent_width)")
    block_k = _block_rows(block_k, page_size, p_)
    rows = -(-h // 8) * 8
    q = jnp.pad(q, ((0, 0), (0, rows - h), (0, 0)))
    body = functools.partial(_latent_body, sm_scale=float(sm_scale),
                             rank=rank)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    depth = _latent_body.depth
    out = _walk(
        functools.partial(_pa_walker, body=body, block_k=block_k, pools=1),
        # the queries whole: the staged walk copies each slot's by hand
        lengths.astype(jnp.int32), tables.astype(jnp.int32), q, pool,
        specs=[whole, whole],
        out_shape=jax.ShapeDtypeStruct((s_, rows, rank), q.dtype),
        scratch=[
            pltpu.VMEM((rows, rank), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((depth, block_k, w), pool.dtype),
            pltpu.SemaphoreType.DMA((1, depth)),
            pltpu.SMEM((1,), jnp.int32),
            # the staged walk's: a block's scores, two slots' queries
            pltpu.VMEM((rows, block_k), jnp.float32),
            pltpu.VMEM((2, rows, w), q.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        name="mxtpu_latent_attention", on_tpu=on_tpu)
    return out[:, :h]


def _latent_signature(q, pool, tables, lengths, *, rank, sm_scale):
    from ..amp import policy as _amp_policy
    return (f"s{q.shape[0]}_h{q.shape[1]}_w{q.shape[2]}_r{rank}"
            f"_ps{pool.shape[1]}_p{tables.shape[1]}",
            _amp_policy.kernel_key_dtype(str(q.dtype)))


def _latent_kernel_run(config, q, pool, tables, lengths, *, rank, sm_scale):
    return _latent_attention_pallas(q, pool, tables, lengths, int(rank),
                                    float(sm_scale), int(config["block_k"]))


def _latent_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(29)
    slots, pps, ps = case["slots"], case["pages_per_slot"], case["page_size"]
    h, rank, rope = case["h"], case["rank"], case["rope"]
    dtype = case.get("dtype", "float32")
    w = -(-(rank + rope) // 128) * 128
    num_pages = slots * pps + 1
    lanes = onp.arange(w) < rank + rope          # the padding holds zeros
    q = jnp.asarray(rng.randn(slots, h, w) * 0.5 * lanes, dtype=dtype)
    pool = jnp.asarray(rng.randn(num_pages, ps, w) * 0.5 * lanes,
                       dtype=dtype)
    tables = jnp.asarray(
        rng.permutation(num_pages - 1)[:slots * pps].reshape(slots, pps),
        jnp.int32)
    lengths = rng.randint(0, pps * ps + 1, size=(slots,))
    lengths[0] = 0
    return ((q, pool, tables, jnp.asarray(lengths, jnp.int32)),
            {"rank": rank, "sm_scale": (rank + rope) ** -0.5})


_kernels.register_kernel(_kernels.KernelSpec(
    "latent_attention", version=3,      # 3: the scores are staged
    run=_latent_kernel_run, fallback=latent_attention_reference,
    config_space={"block_k": (128, 256, 512)},
    default_config={"block_k": 512},
    signature=_latent_signature, make_args=_latent_make_args,
    # pages of 8 and 16 (several a block), 128 (the page is the block or
    # four make one); heads fewer than a sublane tile and more
    tune_grid=({"slots": 5, "pages_per_slot": 6, "page_size": 8,
                "h": 4, "rank": 32, "rope": 8},
               {"slots": 4, "pages_per_slot": 8, "page_size": 16,
                "h": 16, "rank": 128, "rope": 64},
               {"slots": 3, "pages_per_slot": 5, "page_size": 128,
                "h": 8, "rank": 128, "rope": 64}),
))


def _latent_block(q, pool, tables, lengths, rank, sm_scale):
    """The registry's block for this call's signature."""
    sig, dt = _latent_signature(q, pool, tables, lengths, rank=rank,
                                sm_scale=sm_scale)
    return _kernels.resolve(
        "latent_attention", sig, dt,
        tune_args=((q, pool, tables, lengths),
                   {"rank": rank, "sm_scale": sm_scale}))["block_k"]


def latent_attention(q, pool, tables, lengths, *, rank, sm_scale,
                     block_k=None):
    """One attention step per slot over a paged latent cache.

    ``q (slots, H, W)``: a slot's absorbed queries, ``[q_nope W_k |
    q_rope | zeros]`` a head; ``pool (num_pages, page_size, W)``: a
    token's row ``[c_kv (rank) | k_rope | zeros]``, one for all heads;
    ``tables (slots, pages_per_slot)``, ``lengths (slots,)`` as
    :func:`paged_attention`'s.  Returns ``(slots, H, rank)``: the
    softmax-weighted sum of the rows' first ``rank`` lanes, for the
    caller to up-project.  ``W`` and ``rank`` are multiples of 128 on a
    TPU (else the XLA gather runs)."""
    if block_k is None:
        block_k = _latent_block(q, pool, tables, lengths, rank, sm_scale)
    return _latent_attention_pallas(q, pool, tables, lengths, int(rank),
                                    float(sm_scale), int(block_k))


def latent_overlap_share(q, pool, tables, lengths, *, rank, sm_scale,
                         block_k=None):
    """Of the blocks :func:`latent_attention` walks for these
    arguments, the share whose scores are issued while another block's
    softmax runs: every block but the call's first, since the staged
    walk carries its stages across slots (:func:`_pa_walker`); 0 where
    no slot is live.  A float32 scalar, counted from ``lengths``."""
    if block_k is None:
        block_k = _latent_block(q, pool, tables, lengths, rank, sm_scale)
    rows = _block_rows(block_k, pool.shape[1], tables.shape[1])
    blocks = ((lengths + rows - 1) // rows).sum()
    return jnp.where(blocks > 0, (blocks - 1) / jnp.maximum(blocks, 1),
                     0.0).astype(jnp.float32)


register("latent_attention", aliases=("_npx_latent_attention",))(
    lambda q, pool, tables, lengths, rank, sm_scale, block_k=None:
    latent_attention(q, pool, tables, lengths, rank=rank, sm_scale=sm_scale,
                     block_k=block_k))
