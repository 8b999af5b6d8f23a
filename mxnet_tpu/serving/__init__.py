"""mxnet_tpu.serving — the inference serving subsystem.

queue → :class:`DynamicBatcher` → shape-bucketed
:class:`InferenceEngine` (AOT-compiled executable per bucket) →
per-request futures; :class:`ServingServer` fronts the pair with an
in-process ``predict()`` API and an optional stdlib HTTP JSON endpoint.
The ``slo`` submodule adds the SLO plane on top: request identity,
sliding-window burn-rate objectives, saturation-attributed clustermon
incidents, and the ``/slo`` + ``/requestz`` views.  The ``decode``
subpackage is the autoregressive plane: continuous batching
(:class:`DecodeScheduler`) over a paged KV cache with chunked prefill
and speculative decode, served through the same server's
``/generate``.  See docs/ARCHITECTURE.md (Serving, Serving SLOs,
Decode serving) for the dataflow and the admission/reject/timeout
contract.
"""
from . import slo
from .engine import (InferenceEngine, BadRequestError, QueueFullError,
                     RequestTimeoutError, ServingClosedError,
                     serving_enabled)
from .batcher import DynamicBatcher
from .server import ServingServer
from . import decode
from .decode import (AXK1, LFM2, DecodeEngine, DecodeModel, DecodeScheduler,
                     FalconH1, OutOfPagesError, PagedKVCache)

__all__ = ["InferenceEngine", "DynamicBatcher", "ServingServer",
           "BadRequestError", "QueueFullError", "RequestTimeoutError",
           "ServingClosedError", "serving_enabled", "slo", "decode",
           "DecodeEngine", "DecodeModel", "DecodeScheduler", "FalconH1",
           "AXK1", "LFM2", "OutOfPagesError", "PagedKVCache"]
