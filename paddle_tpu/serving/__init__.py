"""Continuous-batching serving for decoder-only LMs (Orca/vLLM-style).

The decode matmuls of a cached autoregressive model are batch-starved
when requests are served one at a time: `generate()` runs [1, hidden]
GEMMs no matter how many requests are waiting. Continuous batching keeps
a fixed number of sequence *slots* over one pool of K/V pages and
admits/retires requests per decode step, so the compiled step always
runs at full occupancy with ONE static shape — no retrace across request
churn.

    engine = PagedContinuousBatchingEngine(model, num_seqs=8)
    req = engine.add_request([1, 2, 3], max_new_tokens=16)
    engine.run()                 # or step() / stream(req) / serve threads
    req.tokens                   # generated ids, identical to generate()

One engine: a block-granular K/V pool with prefix sharing, priority
preemption and optional speculative decoding.

Layering, each arrow pointing one way: front doors (Predictor,
gateway/, fabric/, registry/) -> engine.py (the jitted programs —
chunked prefill, fixed-K decode burst, spec verify — and the thread-safe
front door) -> scheduler.py (the request queue + admission/prefill
policy) and kv_cache.py (slot/page bookkeeping, the device state built
from a model's `cache_specs()`) -> the model's `cache_specs()` and
text/models/cache.py:paged_attention. metrics.py turns step timestamps
into tok/s + latency percentiles. See docs/serving.md.
"""
from .engine import NGramProposer, PagedContinuousBatchingEngine
from .fabric import (PrefixAffinityRouter, ReplicaWorker, SocketReplica,
                     spawn_worker)
from .gateway import (AutoscalePolicy, GatewayRequest, ModelAffinityRouter,
                      QosPolicy, ServingGateway, TenantClass)
from .kv_cache import (PageAllocator, PrefixCache, SlotAllocator,
                       build_paged_pools)
from .metrics import ServingMetrics
from .registry import ModelHost, ModelRegistry, RegistryEntry
from .scheduler import PagedScheduler, Request

__all__ = ['PagedContinuousBatchingEngine',
           'SlotAllocator', 'PageAllocator', 'PrefixCache',
           'NGramProposer', 'build_paged_pools',
           'ServingMetrics', 'Request', 'PagedScheduler',
           'ServingGateway', 'GatewayRequest', 'AutoscalePolicy',
           'QosPolicy', 'TenantClass', 'ModelAffinityRouter',
           'ModelRegistry', 'RegistryEntry', 'ModelHost',
           'SocketReplica', 'ReplicaWorker', 'PrefixAffinityRouter',
           'spawn_worker']
