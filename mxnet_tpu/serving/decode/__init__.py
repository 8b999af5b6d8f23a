"""Autoregressive decode serving: continuous batching over a paged KV
cache with optional speculative decode.

- :mod:`paged_kv` — pre-allocated device page pool + host free-list
  allocator with per-slot page tables, and the traced side of the same
  format: how a token's K/V reaches a pool and how a query attends over
  it (decode, prefill chunk, verify window);
- :mod:`engine` — the model protocol and the fixed-shape compiled
  decode / prefill / draft / verify executables;
- :mod:`decode_model` — the built-in small causal LM, one block;
- :mod:`falcon_h1` — a second model behind the same protocol: grouped-
  query attention beside Mamba-2 heads, with per-slot recurrent state;
- :mod:`axk1` — a third: latent attention over a latent page, and a
  share of sigmoid-routed experts beside a shared one;
- :mod:`lfm2` — a fourth: layers of different kinds (a gated short
  convolution with a per-slot tail, or grouped-query attention over
  pages) under a cache laid out by layer, and a routed layer held whole;
- :mod:`scheduler` — the continuous batcher (``DecodeScheduler``):
  per-step admission/eviction, chunked prefill, speculative accept.

See docs/ARCHITECTURE.md "Decode serving".
"""
from .paged_kv import OutOfPagesError, PageAllocator, PagedKVCache
from .engine import DecodeEngine, DecodePlaneModel
from .decode_model import DecodeModel
from .falcon_h1 import FalconH1
from .axk1 import AXK1
from .lfm2 import LFM2
from .scheduler import DecodeScheduler

__all__ = ["PageAllocator", "PagedKVCache", "OutOfPagesError",
           "DecodePlaneModel", "DecodeModel", "FalconH1", "AXK1", "LFM2",
           "DecodeEngine", "DecodeScheduler"]
