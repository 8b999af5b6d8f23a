"""Paged KV cache: pre-allocated device pool + host page allocator.

The device side is ONE pytree per engine, ``pool[layer] = (k, v,
*state)``: a buffer of its own for every layer's K and for its V, each
``(num_pages, page_size, heads * head_dim)`` (``heads`` the KV heads —
fewer than the query heads under grouped-query attention — folded into
the lane axis, the layout the ``paged_attention`` kernel reads without
a relayout on the TPU), allocated once at construction and threaded,
donated, through every compiled decode/prefill executable — sequence
state never changes a shape.

A model whose layers also carry recurrent state (a state-space
layer's state matrix, a convolution's tail) names each kind in
``state_spec`` and gets, after K and V, one more buffer per kind and
layer, ``(max_slots, *shape)``: addressed by the slot itself, not
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
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as onp

from ... import telemetry
from ...base import MXNetError

__all__ = ["PageAllocator", "PagedKVCache", "OutOfPagesError"]


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


class PagedKVCache:
    """One engine's KV state: device pool + slot page tables.

    ``pool`` is the device state, a tuple over layers of ``(k, v,
    *state)`` buffers; an executable that was given it returns its
    successor, which the engine stores back.  ``heads`` counts KV
    heads.  ``state_spec`` lists the kinds of per-slot recurrent state
    a layer holds, ``(name, shape of one slot, dtype)`` each; empty for
    a model that has none.

    ``pages_per_slot`` bounds a single slot's table width (the traced
    table shape); a slot's token capacity is
    ``pages_per_slot * page_size``."""

    def __init__(self, *, layers: int, num_pages: int, page_size: int,
                 heads: int, head_dim: int, max_slots: int,
                 pages_per_slot: Optional[int] = None,
                 dtype="float32",
                 state_spec: Sequence[Tuple[str, tuple, str]] = ()):
        import jax.numpy as jnp
        self.layers = int(layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.max_slots = int(max_slots)
        self.pages_per_slot = int(
            pages_per_slot if pages_per_slot is not None
            else max(1, num_pages // max(1, max_slots)))
        shape = (self.num_pages, self.page_size,
                 self.heads * self.head_dim)
        self.state_spec = tuple((str(n), tuple(int(d) for d in sh), str(dt))
                                for n, sh, dt in state_spec)
        self.pool = tuple(
            (jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype))
            + tuple(jnp.zeros((self.max_slots,) + sh, dtype=dt)
                    for _, sh, dt in self.state_spec)
            for _ in range(self.layers))
        self.state_resets = 0
        # bytes of recurrent state on the device, all layers and slots
        self.state_bytes = sum(buf.size * buf.dtype.itemsize
                               for layer in self.pool for buf in layer[2:])
        self.allocator = PageAllocator(self.num_pages)
        # traced inputs: page-table rows + a scratch row of zeros for
        # freed slots (page 0 ids are fine — masked by length 0)
        self.tables = onp.zeros((self.max_slots, self.pages_per_slot),
                                onp.int32)
        self._slot_pages: Dict[int, List[int]] = {}

    @property
    def slot_capacity(self) -> int:
        """Max tokens (prompt + generated) one slot can hold."""
        return self.pages_per_slot * self.page_size

    def pages_used(self) -> int:
        return self.allocator.used

    def state_slots_live(self) -> int:
        """Slots whose recurrent state belongs to a request."""
        return len(self._slot_pages) if self.state_spec else 0

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
        if self.state_spec:
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
        if self.state_spec:
            telemetry.gauge("decode.state_slots_live").set(
                self.state_slots_live())
        return len(pages)

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, ()))
