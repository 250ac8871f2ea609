"""Serving runtime: continuous-batching engine over a paged, optionally
INT8-quantized KV pool, with per-request sampling, shared-prefix reuse
and preemption-aware scheduling.

`kv_cache` / `sampling` / `scheduler` / `paging` / `prefix_cache` are
model-free and import eagerly (``models/layers.py`` depends on `kv_cache`
for the quantized-cache hook); the `Engine` itself imports the model
stack, so it loads lazily — keeping `repro.serving.kv_cache` importable
from inside `repro.models` without a cycle.
"""
from repro.serving.faults import (ALLOC_FAIL, KINDS, NAN_LOGITS, SPILL_FAIL,
                                  FaultPlan, InjectedFault)
from repro.serving.kv_cache import (QuantizedKV, cache_bytes,
                                    cache_is_finite, init_paged_storage,
                                    kv_dequantize, kv_quantize, paged_view,
                                    write_pages)
from repro.serving.paging import (PageAllocator, pow2_at_least,
                                  restore_pages, spill_pages)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import SamplingParams, sample_tokens
from repro.serving.scheduler import (DuplicateRequestError,
                                     EngineError, EngineInvariantError,
                                     EngineStalledError, GenerationRequest,
                                     GenerationResult, InvalidRequestError,
                                     QueueFullError, RequestStatus,
                                     ResumeTicket, Scheduler)

_LAZY = ("Engine", "EngineConfig", "batch_buckets")


def __getattr__(name):
    if name in _LAZY:
        from repro.serving import engine
        return getattr(engine, name)
    raise AttributeError(name)


__all__ = ["ALLOC_FAIL", "DuplicateRequestError", "Engine", "EngineConfig",
           "EngineError", "EngineInvariantError", "EngineStalledError",
           "FaultPlan", "GenerationRequest", "GenerationResult",
           "InjectedFault", "InvalidRequestError", "KINDS", "NAN_LOGITS",
           "PageAllocator", "PrefixCache", "QuantizedKV", "QueueFullError",
           "RequestStatus", "ResumeTicket", "SPILL_FAIL", "SamplingParams",
           "Scheduler", "batch_buckets", "cache_bytes", "cache_is_finite",
           "init_paged_storage", "kv_dequantize", "kv_quantize",
           "paged_view", "pow2_at_least", "restore_pages", "sample_tokens",
           "spill_pages", "write_pages"]
