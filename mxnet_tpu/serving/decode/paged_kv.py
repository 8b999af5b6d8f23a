"""Paged KV cache: pre-allocated device pool + host page allocator, and
what an executable does with a pool it was handed.

The device side is ONE pytree per engine, ``pool[layer] = (*paged,
*state)``, laid out BY LAYER: the model says of each layer which paged
buffers and which per-slot state it keeps (``layout[layer] =
(page_widths, state_spec)``; :func:`uniform_layout` for a model whose
layers are all of one kind), and a layer gets exactly those.  A layer
that keeps K and V has a buffer of its own for each,
``(num_pages, page_size, heads * head_dim)`` (``heads`` the KV heads —
fewer than the query heads under grouped-query attention — folded into
the lane axis, the layout the ``paged_attention`` kernel reads without
a relayout on the TPU); a layer that attends over nothing (a
convolution, a recurrence) keeps no paged buffer and costs no page
memory.  All of it is allocated once at construction and threaded,
donated, through every compiled decode/prefill executable — sequence
state never changes a shape.

A layer that carries recurrent state (a state-space layer's state
matrix, a convolution's tail) names each kind in its ``state_spec``
and gets, after its paged buffers, one more buffer per kind,
``(max_slots, *shape)``: addressed by the slot itself, not
through pages, its size does not grow with the sequence.  It lives
and dies with the slot: :meth:`PagedKVCache.acquire` counts the slot
as live (the engine zeroes its rows then, on the device),
:meth:`release` gives it back, and the executables leave an inactive
slot's rows as they are.  A buffer is the unit an executable works
on: it scatters the step's rows into it in place and hands it whole to
the kernel, which a slice of one larger array would not allow without
a copy (a custom call's operand is a whole buffer).  The host
side is a free-list page allocator with per-slot page tables: slots
acquire pages at admission, the tables are passed to the executables
as traced ``(max_slots, pages_per_slot)`` int32 arrays, and eviction
returns pages to the free list for the next request (recycling — no
device traffic on either path).

Page ``num_pages`` — one past a buffer — is the scatter sentinel: KV
writes for inactive slots / padded prefill rows are directed there and
dropped by XLA (``mode="drop"``), so masking never needs a branch.

A layer's paged buffers come in one of two kinds, and the model says
which (the layer's ``page_widths``, the lanes of a row of each):
K and V, two buffers of ``heads * head_dim`` lanes, or a LATENT page,
one buffer whose row ``[c_kv | k_rope | zeros]`` is the key of every
query head and, in its first ``rank`` lanes, every head's value
(multi-head latent attention; :func:`latent_width` pads the row to
whole lane tiles, which the kernel's copies move).  Pages, tables, the
sentinel and the allocator are the same for both, and ONE table a slot
serves every layer that keeps pages: a page id names the same rows of
each of their buffers.

The traced side (the second half of this module) is the only code
besides the ``paged_attention`` kernel that knows any of the above.  A
model's core builds ONE attention for the executable it is traced into
(:func:`slot_attention` for decode, :func:`chunk_attention` for
prefill, whose rows are lanes, several slots' chunks in one dispatch,
:func:`turn_attention` for a decode step that carries such lanes,
:func:`window_attention` for verify) from the positions and
page tables the executable was handed, and calls it in every layer as
``attend(q, k, v, kbuf, vbuf)``: the projected heads before rotation
and the layer's own buffers in, the attention output ``(..., query
heads, head_dim)`` and the buffers' successors out.  Query heads may be
a multiple of the pool's KV heads (grouped-query attention).  Over a
latent page the three are :func:`latent_slot_attention`,
:func:`latent_chunk_attention` and the oracle's
:func:`latent_dense_attention`, called as ``attend(q_nope, q_rope,
c_kv, k_rope, w_kvb, buf)``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from ... import telemetry
from ...base import MXNetError
from ...ops.paged_attention import (latent_attention, latent_overlap_share,
                                    packs_heads, paged_attention)
from ...ops.rope import rope, rope_reference, rope_table

__all__ = ["PageAllocator", "PagedKVCache", "OutOfPagesError",
           "uniform_layout", "slot_conv", "slot_rows", "chunk_conv",
           "dense_conv", "slot_attention", "chunk_attention", "last_rows",
           "turn_attention", "window_attention", "dense_attention",
           "latent_width", "latent_slot_attention", "latent_chunk_attention",
           "latent_dense_attention"]

_NEG_INF = -1e30
# a prefill chunk gathers a slot's whole table for one softmax up to
# this many positions, and walks the slot's live pages beyond it
_GATHER_ROWS = 2048


class OutOfPagesError(MXNetError):
    """The pool has no free pages for the attempted allocation."""


class PageAllocator:
    """Free-list page allocator (host-side, O(1) alloc/free)."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._lock = threading.Lock()

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> List[int]:
        with self._lock:
            if n > len(self._free):
                raise OutOfPagesError(
                    f"requested {n} pages, {len(self._free)} free "
                    f"of {self.num_pages}")
            pages = [self._free.pop() for _ in range(n)]
        return pages

    def free(self, pages: List[int]) -> None:
        with self._lock:
            self._free.extend(pages)


def uniform_layout(layers: int, page_widths: Sequence[int],
                   state_spec: Sequence[Tuple[str, tuple, str]] = ()):
    """The layout of a model whose ``layers`` layers all keep the same
    paged buffers and the same per-slot state."""
    return ((tuple(page_widths), tuple(state_spec)),) * int(layers)


class PagedKVCache:
    """One engine's KV state: device pool + slot page tables.

    ``pool`` is the device state, a tuple over layers of ``(*paged,
    *state)`` buffers; an executable that was given it returns its
    successor, which the engine stores back.  ``layout`` says what each
    layer keeps, ``(page_widths, state_spec)`` a layer: ``page_widths``
    the lanes of a row of each paged buffer (K and V are two of
    ``kv_heads * head_dim``, a latent page is one, a layer that attends
    over no cache has none), ``state_spec`` the kinds of per-slot
    recurrent state, ``(name, shape of one slot, dtype)`` each (empty:
    none).  Only what a layer names is allocated.

    ``pages_per_slot`` bounds a single slot's table width (the traced
    table shape); a slot's token capacity is
    ``pages_per_slot * page_size``."""

    def __init__(self, *, layout, num_pages: int, page_size: int,
                 max_slots: int, pages_per_slot: Optional[int] = None,
                 dtype="float32"):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.pages_per_slot = int(
            pages_per_slot if pages_per_slot is not None
            else max(1, num_pages // max(1, max_slots)))
        self.layout = tuple(
            (tuple(int(w) for w in widths),
             tuple((str(n), tuple(int(d) for d in sh), str(dt))
                   for n, sh, dt in spec))
            for widths, spec in layout)
        self.layers = len(self.layout)
        self.pool = tuple(
            tuple(jnp.zeros((self.num_pages, self.page_size, w), dtype=dtype)
                  for w in widths)
            + tuple(jnp.zeros((self.max_slots,) + sh, dtype=dt)
                    for _, sh, dt in spec)
            for widths, spec in self.layout)
        self.state_resets = 0
        # how many of a layer's buffers are paged (the rest are state),
        # and how many layers hold any of either kind
        self.paged = tuple(len(widths) for widths, _ in self.layout)
        self.page_layers = sum(1 for widths, _ in self.layout if widths)
        self.state_layers = sum(1 for _, spec in self.layout if spec)

        def nbytes(bufs):
            return sum(buf.size * buf.dtype.itemsize for buf in bufs)

        # bytes on the device, all layers: the pages, and the recurrent
        # state of every slot
        self.page_bytes = sum(nbytes(layer[:n])
                              for layer, n in zip(self.pool, self.paged))
        self.state_bytes = sum(nbytes(layer[n:])
                               for layer, n in zip(self.pool, self.paged))
        self.allocator = PageAllocator(self.num_pages)
        # traced inputs: page-table rows + a scratch row of zeros for
        # freed slots (page 0 ids are fine — masked by length 0)
        self.tables = onp.zeros((self.max_slots, self.pages_per_slot),
                                onp.int32)
        self._slot_pages: Dict[int, List[int]] = {}

    def split(self):
        """``(paged, state)`` of the pool: each a tuple over layers of
        that layer's buffers of the one kind, empty where the layer
        keeps none."""
        return (tuple(layer[:n] for layer, n in zip(self.pool, self.paged)),
                tuple(layer[n:] for layer, n in zip(self.pool, self.paged)))

    @property
    def slot_capacity(self) -> int:
        """Max tokens (prompt + generated) one slot can hold."""
        return self.pages_per_slot * self.page_size

    def pages_used(self) -> int:
        return self.allocator.used

    def state_slots_live(self) -> int:
        """Slots whose recurrent state belongs to a request."""
        return len(self._slot_pages) if self.state_layers else 0

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    def acquire(self, slot: int, tokens: int) -> None:
        """Allocate pages covering ``tokens`` positions for ``slot``
        and write its table row.  Raises :class:`OutOfPagesError`
        (leaving the slot untouched) when the free list is short."""
        if slot in self._slot_pages:
            raise MXNetError(f"slot {slot} already holds pages")
        need = self.pages_for(tokens)
        if need > self.pages_per_slot:
            raise MXNetError(
                f"{tokens} tokens need {need} pages > pages_per_slot "
                f"{self.pages_per_slot}")
        pages = self.allocator.alloc(need)
        self._slot_pages[slot] = pages
        row = onp.zeros((self.pages_per_slot,), onp.int32)
        row[:need] = pages
        self.tables[slot] = row
        telemetry.gauge("decode.pages_used").set(self.pages_used())
        if self.state_layers:
            self.state_resets += 1
            telemetry.counter("decode.state_resets").inc()
            telemetry.gauge("decode.state_slots_live").set(
                self.state_slots_live())

    def release(self, slot: int) -> int:
        """Return ``slot``'s pages to the free list; returns the count
        recycled (0 when the slot held none)."""
        pages = self._slot_pages.pop(slot, None)
        if not pages:
            return 0
        self.allocator.free(pages)
        self.tables[slot] = 0
        telemetry.gauge("decode.pages_used").set(self.pages_used())
        if self.state_layers:
            telemetry.gauge("decode.state_slots_live").set(
                self.state_slots_live())
        return len(pages)

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, ()))


# -- the traced side -----------------------------------------------------------

# A convolution's tail: per-slot state, addressed by the slot.  A causal
# depthwise convolution of ``taps`` positions reads, beside the rows it
# is given, the ``taps - 1`` rows before them: a layer keeps those as a
# state buffer ``(slots, taps - 1, width)``.  ``w (taps, width)``, tap
# ``j`` weighing the row ``taps - 1 - j`` positions back; ``bias
# (width,)`` or None.

def slot_conv(tail, x, w, active, bias=None):
    """Decode: one row a slot, ``x (slots, width)`` behind the slot's
    tail ``tail (slots, taps - 1, width)``: ``(the convolution's row
    (slots, width), the tail's successor)``.  An inactive slot's tail
    stays as it is."""
    taps = jnp.concatenate([tail, x[:, None, :]], axis=1)
    conv = (taps * w).sum(axis=1)
    if bias is not None:
        conv = conv + bias
    return conv, jnp.where(active[:, None, None], taps[:, 1:], tail)


def slot_rows(buf, slot):
    """The rows ``slot (lanes,)`` of a per-slot state buffer, ``(lanes,
    *one slot's shape)``, a slice a lane: a gather over the buffer
    would have the TPU compiler copy the buffer whole first.  An index
    past the buffer (a padding lane's) reads the last slot's rows."""
    return jnp.stack([lax.dynamic_index_in_dim(buf, slot[i], 0, False)
                      for i in range(slot.shape[0])])


def chunk_conv(tail, x, w, slot, chunk_len, bias=None):
    """Prefill: ``x (lanes * bucket, width)`` rows, ``bucket`` a lane,
    lane ``i`` the rows of slot ``slot[i]`` with the first
    ``chunk_len[i]`` (traced) of them real, behind that slot's row of
    ``tail``: the convolution sees the tokens before the chunk, and the
    new tail is the last ``taps - 1`` rows before the padding, rows of
    the old tail among them where the chunk is shorter than it.  The
    tails go back in one scatter (a slot rides once in a dispatch); a
    padding lane carries a slot index past the buffer and its tail is
    dropped."""
    lanes, n_tail = slot.shape[0], tail.shape[1]
    xl = x.reshape(lanes, -1, x.shape[-1])
    bucket = xl.shape[1]
    taps = jnp.concatenate([slot_rows(tail, slot), xl], axis=1)
    conv = sum(taps[:, j:j + bucket] * w[j] for j in range(n_tail + 1))
    if bias is not None:
        conv = conv + bias
    last = jnp.stack([lax.dynamic_slice_in_dim(taps[i], chunk_len[i],
                                               n_tail, 0)
                      for i in range(lanes)])
    return conv.reshape(x.shape), tail.at[slot].set(last, mode="drop")


def dense_conv(x, w, bias=None):
    """The oracles': the whole sequence ``x (T, width)`` from a zero
    tail, no state."""
    n_tail = w.shape[0] - 1
    taps = jnp.concatenate(
        [jnp.zeros((n_tail, x.shape[1]), x.dtype), x], axis=0)
    conv = sum(taps[j:j + x.shape[0]] * w[j] for j in range(n_tail + 1))
    return conv if bias is None else conv + bias


def _rotate_write(q, k, v, kbuf, vbuf, pos, page, offset, rope_base):
    """Rotate q and k at ``pos`` and scatter this step's K/V rows into
    ONE layer's own K and V buffers, each ``(num_pages, page_size,
    Hkv*D)``: ``(q, kbuf, vbuf)``.  ``page``/``offset`` address one
    position per row; masked rows carry the sentinel page ``num_pages``
    — one past the buffer — and are dropped (mode='drop').  Each buffer
    is a donated argument with this scatter as its only writer and
    nothing left that reads the old value, so XLA updates it in place:
    no copy of a buffer exists."""
    q = rope(q, pos, base=rope_base)
    k = rope(k, pos, base=rope_base)
    hd = kbuf.shape[-1]
    kbuf = kbuf.at[page, offset].set(
        k.reshape(-1, hd).astype(kbuf.dtype), mode="drop")
    vbuf = vbuf.at[page, offset].set(
        v.reshape(-1, hd).astype(vbuf.dtype), mode="drop")
    return q, kbuf, vbuf


# rows of a block where grouped-query heads are packed into lane tiles
# (``ops/paged_attention.py``): eight query rows a tile make a block's
# matmuls small beside its fixed cost.  On the chip at 32 query heads
# over 8 K/V heads of 64, pages of 128: 0.813 / 0.736 / 0.604 ms a call
# at blocks of 128 / 256 / 512 rows over 120 live slots of 1,036 rows,
# 1.375 / 1.222 / 0.893 over 192 of 1,139 (PERF.md section 6, PR 35)
_PACKED_BLOCK_ROWS = 512


def _kernel(q, kbuf, vbuf, tables, lengths):
    """One query a slot over its ``lengths`` rows.  A page of 128 rows
    or more is walked 128 rows a block (half a page a block cost 159 us
    a call against 103: PERF.md section 6, PR 30, finding 2), or
    ``_PACKED_BLOCK_ROWS`` where the heads are packed; how many smaller
    pages make a block is the kernel registry's choice (by default 64
    rows, four pages of 16)."""
    heads, d = q.shape[-2:]
    block_k = None
    if kbuf.shape[1] >= 128:
        packed = packs_heads(heads, kbuf.shape[-1] // d, d)
        block_k = _PACKED_BLOCK_ROWS if packed else 128
    return paged_attention(q, kbuf, vbuf, tables, lengths, block_k=block_k)


def _slot_places(pool, positions, tables, active):
    """Where a decode step's rows go, one a slot: ``(rows live a slot
    (its position's included), page, offset)``, an inactive slot's page
    the sentinel."""
    num_pages, ps = pool[0][0].shape[:2]
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    pagerow = jnp.take_along_axis(
        tables, (positions // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(active, pagerow, num_pages).astype(jnp.int32)
    return lengths, page, positions % ps


def slot_attention(pool, positions, tables, active, *, rope_base):
    """Decode: one token per slot at ``positions (slots,)``, written
    there through ``tables (slots, pages_per_slot)``, attending over
    the slot's ``positions + 1`` rows.  An inactive slot writes nothing
    and yields zeros."""
    lengths, page, offset = _slot_places(pool, positions, tables, active)

    def attend(q, k, v, kbuf, vbuf):
        q, kbuf, vbuf = _rotate_write(q, k, v, kbuf, vbuf, positions, page,
                                      offset, rope_base)
        return _kernel(q, kbuf, vbuf, tables, lengths), (kbuf, vbuf)

    return attend


def window_attention(pool, base_pos, width: int, tables, active, *,
                     rope_base):
    """Verify: ``width`` consecutive positions per slot from ``base_pos
    (slots,)`` on, heads ``(slots, width, heads, head_dim)``.  The whole
    window is written first; offset ``j`` then attends over ``base_pos
    + j + 1`` rows through the SAME kernel call as the decode step, so
    an accepted token is bitwise the one that step would have emitted."""
    num_pages, ps = pool[0][0].shape[:2]
    pos = base_pos[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    pagerow = jnp.take_along_axis(tables, pos // ps, axis=1)
    page = jnp.where(active[:, None], pagerow,
                     num_pages).astype(jnp.int32).reshape(-1)
    offset = (pos % ps).reshape(-1)
    lengths = [jnp.where(active, base_pos + j + 1, 0).astype(jnp.int32)
               for j in range(width)]

    def attend(q, k, v, kbuf, vbuf):
        q, kbuf, vbuf = _rotate_write(q, k, v, kbuf, vbuf, pos, page,
                                      offset, rope_base)
        cols = [_kernel(q[:, j], kbuf, vbuf, tables, lengths[j])
                for j in range(width)]
        return jnp.stack(cols, axis=1), (kbuf, vbuf)

    return attend


def _chunk_rows(pool, start, chunk_len, tables, bucket: int):
    """Where the rows of a prefill dispatch go: lane ``i`` holds
    ``bucket`` rows, the first ``chunk_len[i]`` of them positions
    ``start[i]`` on of the slot whose page row is ``tables[i]``.
    ``(positions (lanes, bucket), page and offset of every row, flat,
    the padding's page the sentinel, rows live a lane (lanes,))``."""
    num_pages, ps = pool[0][0].shape[:2]
    pos = start[:, None] + jnp.arange(bucket, dtype=jnp.int32)[None, :]
    valid = jnp.arange(bucket)[None, :] < chunk_len[:, None]
    pagerow = jnp.take_along_axis(tables, pos // ps, axis=1, mode="clip")
    page = jnp.where(valid, pagerow, num_pages).astype(jnp.int32)
    return pos, page.reshape(-1), (pos % ps).reshape(-1), start + chunk_len


def last_rows(x, chunk_len):
    """Of a prefill dispatch's rows ``x (lanes * bucket, dim)`` each
    lane's last valid one, ``(lanes, dim)``: the row a head reads."""
    lanes = chunk_len.shape[0]
    xl = x.reshape(lanes, -1, x.shape[-1])
    at = jnp.maximum(chunk_len - 1, 0)
    return jnp.stack([lax.dynamic_index_in_dim(xl[i], at[i], 0, False)
                      for i in range(lanes)])


def chunk_attention(pool, start, chunk_len, tables, bucket: int, *,
                    rope_base):
    """Prefill: ``lanes * bucket`` rows, ``bucket`` a lane, lane ``i``
    the next chunk of ONE slot: its first ``chunk_len[i]`` (traced)
    rows a prompt's positions from ``start[i]`` on, the rest padding
    that writes nothing (a lane of length 0 is all padding);
    ``tables (lanes, pages_per_slot)`` the slots' page rows.  Every
    lane's K and V rows are rotated and written in one scatter a
    buffer; then each lane attends its own causal prefix, earlier
    chunks included, over its own slot's pages: the chunk itself was
    just written, so one mask covers intra- and cross-chunk keys.
    Float32 softmax; each K/V head serves its ``rep`` query heads.  A
    table of up to ``_GATHER_ROWS`` positions is gathered whole for one
    softmax; a longer one is walked a page at a time over the slot's
    LIVE pages only (``start + chunk_len`` rows, a traced count) under
    an online softmax: the whole-table form's scores, ``(bucket, heads,
    table positions)`` in float32, are what a chunk's attention costs
    whatever the prompt's length (134 MB a layer at 4,096 positions and
    256 rows: PERF.md section 6, PR 35)."""
    pos, page, offset, total = _chunk_rows(pool, start, chunk_len, tables,
                                           bucket)

    def attend(q, k, v, kbuf, vbuf):
        q, kbuf, vbuf = _rotate_write(q, k, v, kbuf, vbuf, pos.reshape(-1),
                                      page, offset, rope_base)
        return (_lanes_attend(q, k.shape[1], kbuf, vbuf, tables, pos, total),
                (kbuf, vbuf))

    return attend


def _lanes_attend(q, kvh, kbuf, vbuf, tables, pos, total):
    """Each lane's rotated queries ``q (lanes * bucket, heads, hd)`` over
    its own causal prefix, through its slot's page row ``tables[i]``,
    once the rows are written: ``(lanes * bucket, heads, hd)`` float32."""
    (lanes, bucket), (heads, hd) = pos.shape, q.shape[1:]
    one_slot = (_walk_live_pages
                if tables.shape[1] * kbuf.shape[1] > _GATHER_ROWS
                else _gather_pages)
    qg = q.reshape(lanes, bucket, kvh, heads // kvh, hd).astype(jnp.float32)
    o = [one_slot(kbuf, vbuf, qg[i], tables[i], pos[i], total[i])
         for i in range(lanes)]
    return jnp.concatenate(o).reshape(lanes * bucket, heads, hd)


def turn_attention(pool, positions, tables, active, start, chunk_len,
                   lane_tables, bucket: int, *, rope_base):
    """A decode step with a dispatch's lanes inside it: ``slots`` rows
    (``positions.shape[0]``, one a slot, as :func:`slot_attention`'s),
    then ``lanes * bucket`` rows (as :func:`chunk_attention`'s, their
    slots' page rows ``lane_tables``).  Every row is rotated and written
    in ONE scatter a buffer first; then the slots' queries go through
    the paged kernel and the lanes' over their gathered pages, both
    reading the buffer that scatter left (a read of a buffer before a
    write into it had the TPU compiler copy the buffer whole).  A
    filling slot does not decode: the rows land on disjoint pages."""
    slots = positions.shape[0]
    lengths, page, offset = _slot_places(pool, positions, tables, active)
    pos, lane_page, lane_offset, total = _chunk_rows(
        pool, start, chunk_len, lane_tables, bucket)
    at = jnp.concatenate([positions, pos.reshape(-1)])
    page = jnp.concatenate([page, lane_page])
    offset = jnp.concatenate([offset, lane_offset])

    def attend(q, k, v, kbuf, vbuf):
        q, kbuf, vbuf = _rotate_write(q, k, v, kbuf, vbuf, at, page, offset,
                                      rope_base)
        o_slots = _kernel(q[:slots], kbuf, vbuf, tables, lengths)
        o_lanes = _lanes_attend(q[slots:], k.shape[1], kbuf, vbuf,
                                lane_tables, pos, total)
        return (jnp.concatenate([o_slots.astype(o_lanes.dtype), o_lanes]),
                (kbuf, vbuf))

    return attend


def _gather_pages(kbuf, vbuf, qg, table, pos, total):
    """Causal attention of the rotated queries ``qg (bucket, kv heads,
    rep, head_dim)`` float32 at positions ``pos`` over the first
    ``total`` rows of ONE slot's pages, the slot's whole table gathered
    for one softmax: ``(bucket, kv heads, rep, head_dim)`` float32."""
    kvh, hd = qg.shape[1], qg.shape[3]
    kctx = kbuf[table].reshape(-1, kvh, hd)
    vctx = vbuf[table].reshape(-1, kvh, hd)
    s = jnp.einsum("bgrd,kgd->bgrk", qg,
                   kctx.astype(jnp.float32)) * (1.0 / (hd ** 0.5))
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
    mask = (kpos <= pos[:, None, None, None]) & (kpos < total)
    s = jnp.where(mask, s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    pr = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = pr.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bgrk,kgd->bgrd", pr / l, vctx.astype(jnp.float32))


def _walk_live_pages(kbuf, vbuf, qg, table, pos, total):
    """Causal attention of the rotated queries ``qg (bucket, kv heads,
    rep, head_dim)`` float32 at positions ``pos`` over the first
    ``total`` rows of ONE slot's pages, a page an iteration under an
    online softmax: ``(bucket, kv heads, rep, head_dim)`` float32.
    The slot's pages are gathered out of the buffers first (4 MB each
    at 4,096 positions of 512 lanes; the scores were what the
    whole-table form cost) and the loop reads those: two lanes' loops
    reading the page buffers themselves had the TPU compiler copy each
    buffer into the layout its product prefers, 806 MB a layer at
    ``lfm2_decode_reasoning``'s geometry (PERF.md section 6, PR 38)."""
    bucket, kvh, reps, hd = qg.shape
    ps = kbuf.shape[1]
    sm_scale = 1.0 / (hd ** 0.5)
    kctx, vctx = kbuf[table], vbuf[table]

    def one_page(i, carry):
        m_prev, l, acc = carry
        k = kctx[i].reshape(ps, kvh, hd).astype(jnp.float32)
        v = vctx[i].reshape(ps, kvh, hd).astype(jnp.float32)
        s = jnp.einsum("bgrd,kgd->bgrk", qg, k) * sm_scale
        kpos = i * ps + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        mask = (kpos <= pos[:, None, None, None]) & (kpos < total)
        s = jnp.where(mask, s, _NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        return (m_cur, l * corr + p.sum(axis=-1, keepdims=True),
                acc * corr + jnp.einsum("bgrk,kgd->bgrd", p, v))

    init = (jnp.full((bucket, kvh, reps, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bucket, kvh, reps, 1), jnp.float32),
            jnp.zeros((bucket, kvh, reps, hd), jnp.float32))
    _, l, acc = lax.fori_loop(0, (total + ps - 1) // ps, one_page, init)
    return acc / jnp.where(l == 0.0, 1.0, l)


def dense_attention(length: int, *, rope_base):
    """The oracles' ``attend(q, k, v)``: causal attention of one
    sequence over its own K and V.  No pool, page or kernel
    (``rope_reference``, XLA's softmax): it shares nothing with the
    paged paths that tests pin to it."""
    pos = jnp.arange(length, dtype=jnp.int32)

    def attend(q, k, v):
        (heads, hd), kvh = q.shape[1:], k.shape[1]
        q = rope_reference(q, pos, base=rope_base)
        k = rope_reference(k, pos, base=rope_base)
        qg = q.reshape(length, kvh, heads // kvh, hd).astype(jnp.float32)
        s = jnp.einsum("qgrd,kgd->grqk", qg,
                       k.astype(jnp.float32)) * (1.0 / (hd ** 0.5))
        qp = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        kp = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        pr = jax.nn.softmax(jnp.where(qp >= kp, s, _NEG_INF), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", pr, v.astype(jnp.float32))
        return o.reshape(length, heads, hd), ()

    return attend


# -- the latent page -------------------------------------------------------------

def latent_width(rank: int, rope_dim: int) -> int:
    """Lanes of a latent page's row: ``[c_kv (rank) | k_rope (rope_dim)
    | zeros]``, padded to whole lane tiles of 128 (the kernel copies a
    page by lane tiles; 576 lanes are stored as 640)."""
    return -(-(int(rank) + int(rope_dim)) // 128) * 128


def _latent_write(q_nope, q_rope, c_kv, k_rope, w_kvb, buf, pos, page,
                  offset, inv_freq):
    """Rotate the rope lanes of the queries and of the one shared key
    at ``pos``, scatter the rows ``[c_kv | k_rope | 0]`` into the
    layer's one buffer at ``page``/``offset`` (the sentinel page drops a
    masked row), and absorb the up-projection's key half into the
    queries: ``(q (rows, heads, width), the value half (rank, heads,
    v_dim), buf)``.  ``w_kvb (rank, heads, nope + v_dim)``."""
    rank, nope = c_kv.shape[-1], q_nope.shape[-1]
    width, dtype = buf.shape[-1], buf.dtype
    q_rope = rope_table(q_rope, pos, inv_freq)
    k_rope = rope_table(k_rope[:, None, :], pos, inv_freq)[:, 0]
    pad = width - rank - k_rope.shape[-1]
    row = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)],
        axis=-1).astype(dtype)
    buf = buf.at[page, offset].set(row, mode="drop")
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kvb[..., :nope])
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,), q_lat.dtype)],
        axis=-1).astype(dtype)
    return q, w_kvb[..., nope:], buf


def latent_slot_attention(pool, positions, tables, active, *, inv_freq,
                          sm_scale):
    """Decode over a latent page: one token per slot at ``positions``,
    its row written through ``tables``, every head attending over the
    slot's ``positions + 1`` rows in the absorbed form (scores ``q_nope
    W_k . c_kv + q_rope . k_rope``, the output summed in the latent
    space and taken through ``W_v``): the ``latent_attention`` kernel
    reads each page once for both.  An inactive slot writes nothing and
    yields zeros.  ``attend.overlap`` collects, a call a layer, the
    kernel's ``latent_overlap_share`` (a counter of the decode step)."""
    num_pages, ps = pool[0][0].shape[:2]
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    pagerow = jnp.take_along_axis(
        tables, (positions // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(active, pagerow, num_pages).astype(jnp.int32)
    offset = positions % ps

    def attend(q_nope, q_rope, c_kv, k_rope, w_kvb, buf):
        q, w_v, buf = _latent_write(q_nope, q_rope, c_kv, k_rope, w_kvb,
                                    buf, positions, page, offset, inv_freq)
        kw = dict(rank=c_kv.shape[-1], sm_scale=sm_scale)
        o = latent_attention(q, buf, tables, lengths, **kw)
        attend.overlap.append(latent_overlap_share(q, buf, tables, lengths,
                                                   **kw))
        return jnp.einsum("bhr,rhd->bhd", o, w_v), (buf,)

    attend.overlap = []
    return attend


def latent_chunk_attention(pool, start, chunk_len, tables, bucket: int, *,
                           inv_freq, sm_scale):
    """Prefill over a latent page: ``lanes * bucket`` rows, ``bucket``
    a lane and a lane ONE slot's next chunk, as
    :func:`chunk_attention`'s.  The absorbed form again (the
    absorption, the rows' one scatter and the way back through ``W_v``
    are one product each over every lane's rows), each lane walked a
    page at a time over its slot's LIVE pages only (``start +
    chunk_len`` rows, a traced count) under an online softmax: the
    plain form would up-project every gathered row to ``heads x (nope +
    v)`` and a slot's table spans positions a prompt never reaches,
    where this reads what the decode step will read and keeps a page's
    scores, ``(bucket, heads, page_size)``, as its largest temporary."""
    lanes, ps = tables.shape[0], pool[0][0].shape[1]
    pos, page, offset, total = _chunk_rows(pool, start, chunk_len, tables,
                                           bucket)

    def attend(q_nope, q_rope, c_kv, k_rope, w_kvb, buf):
        rank, heads = c_kv.shape[-1], q_nope.shape[1]
        q, w_v, buf = _latent_write(q_nope, q_rope, c_kv, k_rope, w_kvb,
                                    buf, pos.reshape(-1), page, offset,
                                    inv_freq)

        def one_slot(q, table, pos, total):
            def one_page(i, carry):
                m_prev, l, acc = carry
                rows = buf[table[i]]                          # (ps, width)
                s = jnp.einsum("bhw,kw->bhk", q, rows,
                               preferred_element_type=jnp.float32) * sm_scale
                kpos = i * ps + lax.broadcasted_iota(jnp.int32, s.shape, 2)
                mask = (kpos <= pos[:, None, None]) & (kpos < total)
                s = jnp.where(mask, s, _NEG_INF)
                m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_cur)
                p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
                pv = jnp.einsum("bhk,kr->bhr", p.astype(rows.dtype),
                                rows[:, :rank],
                                preferred_element_type=jnp.float32)
                return (m_cur, l * corr + p.sum(axis=-1, keepdims=True),
                        acc * corr + pv)

            init = (jnp.full((bucket, heads, 1), _NEG_INF, jnp.float32),
                    jnp.zeros((bucket, heads, 1), jnp.float32),
                    jnp.zeros((bucket, heads, rank), jnp.float32))
            _, l, acc = lax.fori_loop(0, (total + ps - 1) // ps, one_page,
                                      init)
            return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)

        ql = q.reshape((lanes, bucket) + q.shape[1:])
        o = jnp.concatenate([one_slot(ql[i], tables[i], pos[i], total[i])
                             for i in range(lanes)])
        return jnp.einsum("bhr,rhd->bhd", o, w_v), (buf,)

    return attend


def latent_dense_attention(length: int, *, inv_freq, sm_scale):
    """The oracle's ``attend(q_nope, q_rope, c_kv, k_rope, w_kvb)`` over
    one whole sequence, in the PLAIN form: every row up-projected to
    its heads' keys and values, causal softmax in float32.  No pool,
    page, kernel or absorption: what the cached paths are pinned to."""
    pos = jnp.arange(length, dtype=jnp.int32)
    f32 = jnp.float32

    def attend(q_nope, q_rope, c_kv, k_rope, w_kvb):
        nope = q_nope.shape[-1]
        q_r = rope_table(q_rope.astype(f32), pos, inv_freq)
        k_r = rope_table(k_rope.astype(f32)[:, None, :], pos, inv_freq)[:, 0]
        kv = jnp.einsum("kr,rhd->khd", c_kv.astype(f32), w_kvb.astype(f32))
        s = (jnp.einsum("qhd,khd->hqk", q_nope.astype(f32), kv[..., :nope])
             + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * sm_scale
        qp = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kp = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        pr = jax.nn.softmax(jnp.where(qp >= kp, s, _NEG_INF), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, kv[..., nope:]), ()

    return attend
