"""mxnet_tpu.serving — dynamic-batching in-process inference service.

The layer between "a jitted forward" and "traffic" (ROADMAP north star:
serve heavy traffic from millions of users). TPU serving economics invert
the eager story: throughput comes from coalescing many small concurrent
requests into a few fixed-shape batched XLA executions, so every serve
hits a warm jit cache entry and the steady state never recompiles.

Pieces
------
* :mod:`~mxnet_tpu.serving.buckets`  — the fixed batch-size ladder
  (default ``1/4/16/32``) and zero-padding up to the next bucket;
* :mod:`~mxnet_tpu.serving.engine`   — the ``Engine`` interface hiding
  *what* executes a batch: a live Gluon block (:class:`BlockEngine`) or a
  loaded ``aot`` StableHLO artifact (:class:`StableHLOEngine`);
* :mod:`~mxnet_tpu.serving.batcher`  — :class:`Server`: bounded submit
  queue, deadline-driven micro-batcher, load shedding, per-request
  timeout, error isolation, graceful drain — plus engine-level
  resilience (retry under the ``mxnet_tpu.resilience`` policy, a circuit
  breaker per engine, AOT→Block fallback, engine load-shed);
* :mod:`~mxnet_tpu.serving.stats`    — counters + latency reservoir
  behind ``Server.stats()``, bridged to ``profiler`` Counters/Markers;
* :mod:`~mxnet_tpu.serving.kvcache`  — paged KV cache for autoregressive
  decode: static device pools, host free-list allocator, per-sequence
  page tables;
* :mod:`~mxnet_tpu.serving.decode`   — :class:`DecodeEngine`: token-level
  continuous batching over fixed decode slots, one jitted step per tick,
  prefill through a bucket ladder, ragged paged-attention reads
  (:mod:`mxnet_tpu.ops.pallas_kernels`) — the LLM serving plane;
* :mod:`~mxnet_tpu.serving.fleet`    — :class:`FleetRouter`: N decode
  replicas behind the single-engine surface — prefix-affinity placement,
  tenant-aware spillover, replica lifecycle (rolling swap, drain),
  failure containment with exactly-once re-routing, and SLO-driven
  autoscaling;
* :mod:`~mxnet_tpu.serving.speculative` — draft proposers for
  speculative decoding: the model-free prompt-lookup (n-gram) draft and
  a pluggable registry (``MXNET_DECODE_SPEC_DRAFT``); the engine
  verifies k+1 positions per slot in ONE widened ragged tick, greedy
  rejection keeps output bit-exact, and the static K+1 width keeps the
  steady state recompile-free;
* :mod:`~mxnet_tpu.serving.tenancy`  — the multi-tenant control plane
  both servers thread through: tenant registry (``MXNET_TENANTS``),
  weighted-fair queueing with priority classes, per-tenant circuit
  breakers / KV page quotas / token-rate budgets, and the live weight
  swap (:meth:`DecodeEngine.swap_params` /
  :meth:`Server.refresh_params`).

Typical use::

    from mxnet_tpu import serving
    srv = serving.serve_block(net, sample_shape=(3, 224, 224))
    srv.warmup()                      # compile every bucket up front
    fut = srv.submit(image)           # thread-safe, from any thread
    probs = fut.result(timeout=1.0)
    print(srv.stats())                # p50/p99, batch fill, shed, ...
    srv.close()                       # graceful drain

Every ``MXNET_SERVING_*`` knob flows through ``base.get_env``
(``cache=False`` — servers are constructed long after import); the
registry lives in ``docs/env_var.md`` and ``docs/serving.md``.
"""
from __future__ import annotations

from .batcher import (EngineUnavailableError, QueueFullError,
                      RequestTimeoutError, Server, ServerClosedError,
                      ServingError)
from .buckets import bucket_ladder, pad_to_bucket, select_bucket
from .afmoe import AfmoeDecoder
from .decode import DecodeEngine, PagedDecodeModel, TinyDecoder
from .engine import BlockEngine, Engine, StableHLOEngine
from .fleet import FleetRouter
from .kvcache import (OutOfPagesError, PagedKVCache, PrefixMatch,
                      RingKVCache)
from .ling import LingDecoder
from .speculative import (DraftProposer, ModelDraft, PromptLookupDraft,
                          available_drafts, make_draft, register_draft)
from .stats import ServingStats, TenantStats
from .tenancy import (PRIORITY_CLASSES, Tenant, TenantBreaker,
                      TenantRegistry, TenantUnavailableError,
                      WeightedFairQueue)

__all__ = [
    "Engine", "BlockEngine", "StableHLOEngine",
    "Server", "ServingError", "QueueFullError", "RequestTimeoutError",
    "ServerClosedError", "EngineUnavailableError",
    "ServingStats", "TenantStats",
    "bucket_ladder", "select_bucket", "pad_to_bucket",
    "serve_block", "serve_stablehlo",
    "DecodeEngine", "PagedDecodeModel", "TinyDecoder", "AfmoeDecoder",
    "LingDecoder", "RingKVCache", "FleetRouter",
    "PagedKVCache", "OutOfPagesError", "PrefixMatch",
    "DraftProposer", "PromptLookupDraft", "ModelDraft",
    "register_draft", "make_draft", "available_drafts",
    "Tenant", "TenantRegistry", "TenantBreaker",
    "TenantUnavailableError", "WeightedFairQueue", "PRIORITY_CLASSES",
]


def serve_block(block, sample_shape, dtype="float32", **kwargs) -> Server:
    """Serve a live (initialized) Gluon block.

    ``sample_shape`` is the per-request shape *without* the batch axis —
    the server stacks requests along a new leading axis before running
    the block, so a block exported for ``(batch, *sample_shape)`` inputs
    serves unchanged.
    """
    return Server(BlockEngine(block, dtype=dtype), sample_shape,
                  dtype=dtype, **kwargs)


def serve_stablehlo(out_dir: str, fallback_block=None, **kwargs) -> Server:
    """Serve a loaded ``aot.export_model`` artifact.

    Reads ``manifest.json`` for the sample shape/dtype. Artifacts exported
    with ``poly_batch=True`` serve every bucket from one serialization;
    fixed-shape artifacts serve only the bucket equal to their exported
    batch size (pass ``buckets=[that_size]``).

    ``fallback_block`` (a live initialized Gluon block) arms degraded
    mode: if the artifact engine's circuit breaker trips, traffic falls
    to a :class:`BlockEngine` over that block — the AOT→Block fallback
    chain — before the server load-sheds.
    """
    import json
    import os

    with open(os.path.join(out_dir, "manifest.json")) as f:
        manifest = json.load(f)
    sample_shape = tuple(manifest["input_shape"][1:])
    dtype = manifest.get("input_dtype", "float32")
    if not manifest.get("poly_batch") and kwargs.get("buckets") is None:
        # a fixed-shape artifact runs exactly one batch size: serve it as
        # the single bucket instead of failing every other rung
        kwargs["buckets"] = [int(manifest["input_shape"][0])]
    if fallback_block is not None and kwargs.get("fallback_engine") is None:
        kwargs["fallback_engine"] = BlockEngine(fallback_block, dtype=dtype)
    return Server(StableHLOEngine(out_dir), sample_shape, dtype=dtype,
                  **kwargs)
