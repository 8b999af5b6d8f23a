"""Autoregressive decode serving: continuous batching over a paged KV
cache with optional speculative decode.

- :mod:`paged_kv` — pre-allocated device page pool + host free-list
  allocator with per-slot page tables;
- :mod:`engine` — the model protocol, the built-in small causal LM and
  the fixed-shape compiled decode / prefill / draft / verify
  executables;
- :mod:`falcon_h1` — a second model behind the same protocol: grouped-
  query attention beside Mamba-2 heads, with per-slot recurrent state;
- :mod:`scheduler` — the continuous batcher (``DecodeScheduler``):
  per-step admission/eviction, chunked prefill, speculative accept.

See docs/ARCHITECTURE.md "Decode serving".
"""
from .paged_kv import OutOfPagesError, PageAllocator, PagedKVCache
from .engine import DecodeEngine, DecodeModel, DecodePlaneModel
from .falcon_h1 import FalconH1
from .scheduler import DecodeScheduler

__all__ = ["PageAllocator", "PagedKVCache", "OutOfPagesError",
           "DecodePlaneModel", "DecodeModel", "FalconH1", "DecodeEngine",
           "DecodeScheduler"]
