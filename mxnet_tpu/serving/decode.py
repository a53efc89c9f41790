"""Token-level continuous batching: the LLM decode plane.

The PR-2 :class:`~mxnet_tpu.serving.batcher.Server` batches at *request*
granularity — right for CNNs, structurally wrong for autoregressive
decode, where a finished sequence strands its batch slot until the whole
batch drains and padded KV wastes HBM. This module batches at *token*
granularity instead: a fixed number of decode **slots** each hold one live
sequence, every engine tick runs ONE jitted decode step over all slots
(one new token per active slot), and the moment a sequence finishes its
slot is re-admitted from the queue — in the same tick, without retracing,
because every array in the step is statically shaped in
``(num_slots, max_pages, page_size)`` (:mod:`~mxnet_tpu.serving.kvcache`,
Ragged Paged Attention per PAPERS.md).

Anatomy of a request:

1. **submit** — prompt validated on the caller's thread; bounded queue
   (shed with :class:`~mxnet_tpu.serving.batcher.QueueFullError`) and
   per-request deadline, exactly the PR-2 policy surface;
2. **admission** — a free slot + a full worst-case page reservation
   (prompt + ``max_new_tokens``; an admitted sequence can always finish);
3. **prefill** — the prompt runs once through a fixed ladder of padded
   lengths (:func:`~mxnet_tpu.serving.buckets.select_bucket` over
   ``MXNET_DECODE_PREFILL_BUCKETS``), writes its KV into the reserved
   pages, and produces the first output token (the TTFT mark). Prompts of
   ``MXNET_DECODE_RING_PREFILL_LEN`` tokens or more route their attention
   through :func:`mxnet_tpu.sequence_parallel.ring_attention` — the
   long-context path, sequence axis sharded over the local mesh;
4. **decode ticks** — one jitted step per tick regardless of membership
   churn: paged-attention over the page table, in-graph greedy sampling,
   and exactly TWO host<->device crossings per tick — one packed
   operand put (tokens/positions/lengths/write slots travel together;
   the page table rides a version-keyed device cache re-put only when
   admission or completion mutates it) and ONE fetch of the sampled
   tokens (the per-token sync the ``decode-host-sync`` tpulint pass
   audits). One step is kept IN FLIGHT: step N+1 is dispatched before
   step N is fetched and reads N's tokens on the device, so the fetch,
   the commit and the next pass's admission run while the device works
   (with a draft in play a step is fetched before the next is packed);
5. **completion** — EOS or the token budget frees the pages (LIFO reuse)
   and the freed slot admits the next queued sequence on the same tick.

Resilience (PR-4 wiring, chaos sites ``serving.decode`` /
``serving.decode.prefill`` / ``serving.decode.tenant.<id>``): prefill
runs per sequence under the retry policy, so a poisoned/unlucky prompt
fails ONLY its own future; the decode step retries transients, and a
step that still fails evicts exactly the sequences in flight (fresh
pools, slots reset) while the engine keeps answering later traffic —
all under one circuit breaker whose open state sheds with
:class:`EngineUnavailableError` instead of hanging.

Prefix caching (``MXNET_DECODE_PREFIX_CACHE``, default on): admission
walks the cache's rolling-hash prefix index and maps a matching system
prompt's pages straight into the new slot's page table — refcounted,
read-only, prefilled once per fleet instead of once per request; the
first divergent/partial page is shared copy-on-write (a jitted device
copy into a page charged to the writer), and only the non-shared tail is
reserved against the tenant's budget (shared pages belong to the
``shared`` pseudo-tenant). The tail — or, on a full hit, a one-token
recompute of the last prompt position — runs through a *chunk* jit that
attends over the sequence's pages, so a hit's prefill cost is the tail,
not the prompt. Outputs stay exactly equal to the no-cache oracle: hits
are token-verified against the stored runs, the index is flushed on
weight swaps and pool re-zeros, and CoW means no sequence ever observes
another's writes.

Chunked prefill (``MXNET_DECODE_PREFILL_CHUNK`` = chunk size, default
off): prefill splits into fixed-size chunks interleaved with decode
ticks inside the same one-jitted-step regime — one statically-shaped
chunk rung pre-compiled at :meth:`DecodeEngine.warmup`, each chunk
carrying the KV written so far through the page table — so a long
prompt stops monopolizing the tick loop and TTFT p99 stops tracking the
longest prompt in the queue.

Observability (:mod:`~mxnet_tpu.telemetry`): a sampled request
(``MXNET_TRACE_SAMPLE``) carries a trace minted at :meth:`submit`
through every hop — enqueue, admission-guard deferral verdicts,
admission, prefill chunks, prefix hits/CoW, every decode tick, the
terminal — queryable by ``trace_id``; the flight recorder keeps each
tick's in-flight request set plus evictions/swaps/faults so a mid-tick
death leaves a readable black box (the worker catch-all dumps it), and
``stats()["alerts"]`` carries the live SLO engine's verdicts.

Multi-tenancy (:mod:`~mxnet_tpu.serving.tenancy`): every request
belongs to a tenant (``submit(..., tenant=)``; untagged = ``default``).
The single FIFO is replaced by per-tenant bounded sub-queues drained by
weighted-fair deficit-round-robin, KV **page quotas** and token-rate
budgets are enforced at admission (a tenant at budget *defers* without
blocking other tenants — the FIFO's head-of-line coupling is gone), a
request-level failure feeds that tenant's own sliding-window breaker
(``mxnet_tenant_breaker_state``) so a misbehaving tenant is shed alone
while the engine breaker stays reserved for tick-level engine faults,
per-request deadlines now also cover generation (an expired sequence is
evicted at the next tick boundary, its pages freed), and
:meth:`DecodeEngine.swap_params` hot-swaps the model weights between
ticks — an A/B rollout or fleet upgrade drops zero in-flight requests
and recompiles nothing (same pytree signature = same jit signature).
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..telemetry import flightrec as _flightrec
from ..telemetry import slo as _slo
from ..telemetry import tracing as _tracing
from ..base import MXNetError, fetch_host, get_env
from ..resilience import CircuitBreaker, chaos
from ..resilience import hbm as _hbm
from .batcher import (EngineUnavailableError, QueueFullError,
                      RequestTimeoutError, ServerClosedError)
from .buckets import select_bucket
from .kvcache import (OutOfPagesError, PrefixMatch, layer_states,
                      make_cache, write_kv)
from .stats import ServingStats
from .tenancy import (PRIORITY_CLASSES, SHARED_TENANT, Tenant,
                      TenantRegistry, TenantUnavailableError,
                      WeightedFairQueue)

__all__ = ["DecodeEngine", "PagedDecodeModel", "TinyDecoder"]

_DEFAULT_SLOTS = 8
_DEFAULT_MAX_SEQ_LEN = 256
_DEFAULT_PREFILL_BUCKETS = "16,64"
_DEFAULT_TIMEOUT_MS = 10000.0
_DEFAULT_QUEUE_DEPTH = 256
_DEFAULT_PREFIX_CACHE = 1  # sharing is exact by construction: default on
_DEFAULT_PREFILL_CHUNK = 0  # 0 = monolithic prefill (one rung per prompt)

#: category of the worker's ``telemetry.span`` regions (``mx.decode.*`` in
#: a ``jax.profiler`` trace; docs/observability.md lists them)
_SPAN_CAT = "serving"
#: an empty engine waits for work in slices of this many seconds, each under
#: its own ``mx.decode.idle`` span: a trace that starts inside the wait
#: loses at most one slice of it
_IDLE_SLICE_S = 0.05

_T_TOKENS = telemetry.counter(
    "mxnet_decode_tokens_total",
    "output tokens generated by the decode plane",
    labels=("server",))
_T_OCCUPANCY = telemetry.gauge(
    "mxnet_decode_slot_occupancy",
    "active decode slots over total slots, most recent tick",
    labels=("server",))
_T_MOE_ROWS = telemetry.counter(
    "mxnet_moe_rows_total",
    "(token, pick) rows the router sent to experts held on this chip "
    "(where=held) and to experts held elsewhere (where=absent), summed "
    "over the expert layers; prefills and decode ticks",
    labels=("server", "where"))
_T_KV_COLS_LIVE = telemetry.counter(
    "mxnet_decode_kv_cols_live_total",
    "page-table columns the decode ticks' paged-attention walks ran: the "
    "columns that hold a live key (ops.pallas_kernels.live_columns), over "
    "slots and layers",
    labels=("server", "group"))
_T_KV_COLS_GRID = telemetry.counter(
    "mxnet_decode_kv_cols_grid_total",
    "page-table columns those walks have to choose from: the tables' "
    "columns x slots x layers (live / grid = the share of a table a tick "
    "fetches and multiplies)",
    labels=("server", "group"))
_T_KV_COLS_WALKED = telemetry.counter(
    "mxnet_decode_kv_cols_walked_total",
    "grid steps those walks' launches ran: a launch visits each slot's "
    "live columns and no other's (at least one step a launch), so walked "
    "- live = steps that fetched and multiplied nothing",
    labels=("server", "group"))
_T_OVERLAPPED = telemetry.counter(
    "mxnet_decode_steps_overlapped_total",
    "decode steps dispatched while the step before them was still "
    "un-fetched (the host's work for that step ran behind the device)",
    labels=("server",))
_T_PREFILL_HELD = telemetry.counter(
    "mxnet_decode_prefill_held_slot_ms_total",
    "milliseconds decoding slots waited behind somebody else's prefill: "
    "each prefill's duration times the slots that were decoding when it "
    "was launched (over the tokens decoded: what a token loses to prefills)",
    labels=("server",))
_T_PREFILL_ATTN_BLOCKS = telemetry.counter(
    "mxnet_decode_prefill_attn_blocks_total",
    "(query block, kv block) pairs of the prefills' blocked attention, a kv "
    "head, over the model's layers: kind=rung what the band holds over the "
    "padded rung, kind=live over the prompt's real tokens — what the kernel "
    "multiplies (a model that declares prefill_attn_blocks)",
    labels=("server", "kind"))
_T_PREFILL_ROWS = telemetry.counter(
    "mxnet_decode_prefill_rows_total",
    "rows of the whole-prompt prefills' row-wise passes (norms, projections, "
    "router, shared expert): kind=rung the padded rungs' rows, "
    "kind=computed those of the row blocks the prompts reach — what is "
    "computed (a model that declares prefill_rows)",
    labels=("server", "kind"))
_T_STEP_TEMP = telemetry.gauge(
    "mxnet_decode_step_temp_bytes",
    "temporaries of the compiled decode step (its memory_analysis), set by "
    "warmup(): activations only while the step updates the KV pools in "
    "place, the pools' own size or more once it copies, converts or slices "
    "them",
    labels=("server",))
_T_MOE_LOAD = telemetry.gauge(
    "mxnet_moe_expert_load_max_over_mean",
    "rows of the busiest held expert over the mean of the held experts, "
    "over everything served so far (1.0 = even load)",
    labels=("server",))
_T_EVENTS = telemetry.counter(
    "mxnet_decode_events_total",
    "decode engine lifecycle events (prefill, admitted, completed, "
    "evicted, shed_open_breaker, shed_tenant_breaker, deadline_evicted, "
    "weight_swap, cow_copy)",
    labels=("server", "event"))


def _tree_sig(tree):
    """(shape, dtype) signature of a param pytree: two pytrees with equal
    signatures produce identical jit avals, so swapping one for the other
    between ticks can never recompile the decode step."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: (tuple(getattr(x, "shape", ())),
                   str(getattr(x, "dtype", type(x).__name__))), tree)


def _placed_alike(a, b) -> bool:
    """Whether a jit takes the two arrays for one signature: both left to
    the default device, or both committed to equivalent shardings."""
    if not (a.committed or b.committed):
        return True
    return a.committed == b.committed and \
        a.sharding.is_equivalent_to(b.sharding, a.ndim)


class PagedDecodeModel:
    """Contract a model serves decode through. Pure functions over the
    paged cache — the engine jits them once and the shapes never move.

    Attributes the engine sizes the cache from: ``num_layers``,
    ``num_heads``, ``num_kv_heads``, ``head_dim``, ``vocab_size``.

    What every method is handed as ``pools`` and ``state``, and returns in
    those places, is the two operands of the model's cache
    (:class:`~mxnet_tpu.serving.kvcache.DecodeCache`):

    ``pools``
        what is paged, one GROUP of pools a paged kind among the model's
        layers. A K/V group is ``(k layers, v layers)``: sequences of
        per-layer arrays ``(P, page, KH, Dw)``, ``k[layer]`` the array that
        layer's kernel reads; :func:`~mxnet_tpu.serving.kvcache.write_kv`
        replaces exactly that element. ``Dw`` is ``head_dim`` or, where the
        device holds narrow rows otherwise (a TPU, head_dim under 128), the
        lane tile above it: ``write_kv`` zero-pads the rows it is handed and
        the ``ops.pallas_kernels.paged_*`` functions take ``(.., head_dim)``
        queries and return ``(.., head_dim)``. A latent group is one
        sequence of per-layer arrays ``(P, page, row width)``, no V pool.
        A model with ONE group (:class:`TinyDecoder`: every layer paged;
        ``LingDecoder``: its latent layers) is handed that group's pools,
        its page table and its write pages bare; a model with several
        (``AfmoeDecoder``: full, then window) a tuple a group of each, in
        the cache's order (:func:`~mxnet_tpu.serving.kvcache.place_layers`).
    ``state``
        what a slot keeps whole: a tuple a ``slot`` layer of ``(num_slots,)
        + shape`` float32 arrays, ``()`` for a model with no such layer.
        ``decode`` row ``s`` IS slot ``s`` and a row with ``seq_len`` 0
        must leave its slot's state bit for bit; ``prefill`` takes ``slot=``
        (a traced int32) and writes that slot's state whole.

    Four optional declarations (a model without them, like
    :class:`TinyDecoder`, is served exactly as before):

    ``layer_state``
        what each layer keeps between tokens, one entry a layer
        (:func:`~mxnet_tpu.serving.kvcache.layer_states`: ``("paged",)``,
        ``("ring", window)``, ``("latent", width)``, ``("slot", shapes)``);
        :func:`~mxnet_tpu.serving.kvcache.make_cache` allocates per entry
        and for no layer that owns nothing of a kind. A model whose layers
        are not all ``paged`` is served with ``prefix_cache=False``,
        ``prefill_chunk=0``, ``spec_k=0``.
    ``moe_counters``
        ``(expert layers, held experts + 1)`` — ``decode`` and ``prefill``
        return a fourth value, an int32 array of that shape: the (token,
        pick) rows each held expert received in each expert layer and, in
        the last column, the rows routed to experts held elsewhere. It
        rides the tick's one fetch behind the sampled tokens.
    ``prefill_attn_blocks(tokens, rung)``
        the (query block, kv block) pairs its blocked prefill attention
        multiplies for a prompt of ``tokens`` padded to ``rung``, a kv head,
        over its layers (host arithmetic): the engine writes them on the
        ``mx.decode.prefill`` span and sums them in ``stats()``.
    ``prefill_rows(tokens, rung)``
        the rows of ``rung`` its prefill's row-wise passes compute for a
        prompt of ``tokens`` (host arithmetic: those of the row blocks the
        prompt reaches, :mod:`mxnet_tpu.ops.row_blocks`): on the span as
        ``rows_rung`` / ``rows_computed``, summed in ``stats()`` likewise.
    """

    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int

    def decode(self, params, tokens, positions, pools, state,
               page_tables, seq_lens, write_pages, write_offsets):
        """One query ROW per table row: ``tokens``/``positions``/
        ``write_*``/``seq_lens`` are ``(S*W,)`` where ``page_tables`` is
        ``(S, max_pages)`` — W is a static per-slot query width the model
        derives at trace time (``tokens.shape[0] // page_tables.shape[0]``).
        The classic decode tick is W=1: one token per slot. The
        speculative verify tick is W=K+1: slot s's rows sit at
        ``s*W .. s*W+W-1`` in position order (committed token, then
        draft tokens), sharing the slot's page-table row. ``seq_lens``
        is per ROW and INCLUDES the row's own token (it attends to
        itself and every position below — which covers the earlier draft
        rows, written before attention reads). Inactive/padded rows
        carry ``seq_len 0`` and the null write page; their logits are
        garbage the engine ignores. Returns
        ``(logits (S*W, vocab), pools, state)``."""
        raise NotImplementedError

    def prefill(self, params, tokens, length, pools, state,
                write_pages, write_offsets, attn=None):
        """Whole prompt in one pass: ``tokens`` ``(T,)`` padded to a
        ladder rung, ``length`` the real token count (traced — one
        compile per rung, not per length), ``write_*`` ``(T,)`` (padding
        rows target the null page). ``attn`` overrides the in-graph
        causal attention (the ring-attention long-context path). Returns
        ``(last_token_logits (vocab,), pools, state)``."""
        raise NotImplementedError

    def prefill_chunk(self, params, tokens, start, length, pools, state,
                      page_table_row, write_pages, write_offsets):
        """One prefill chunk of one sequence, attending THROUGH the page
        table: ``tokens`` ``(C,)`` padded to the chunk rung at absolute
        positions ``start .. start+C-1`` (``start``/``length`` traced
        int32 scalars — one compile per rung, not per prompt or chunk
        index), ``page_table_row`` ``(max_pages,)`` the slot's row.
        Writes the chunk's K/V at ``write_*`` ``(C,)`` (padding and
        already-cached positions target the null page), then attends
        each chunk query over the sequence's pages — the prefix written
        by earlier chunks or mapped from the prefix cache included.
        Returns ``(last_real_token_logits (vocab,), pools, state)``.
        Both chunked prefill and the prefix-cache tail/recompute path
        run through this."""
        raise NotImplementedError


#: process-wide request ids for the flight recorder's per-tick in-flight
#: set — ALWAYS minted (unlike trace ids, which are sampled): the black
#: box must identify every sequence on the failing tick, not just the
#: sampled ones. itertools.count.__next__ is GIL-atomic — no lock.
_RID = itertools.count(1)


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "future", "t_submit",
                 "deadline", "tokens", "last_t", "slot", "tenant",
                 "match", "kv_cached", "filled", "prefilling", "seq",
                 "epoch", "rid", "trace")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos_id: Optional[int], deadline: Optional[float],
                 tenant: Tenant):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.deadline = deadline
        self.tokens: List[int] = []
        self.last_t = 0.0
        self.slot = -1
        self.tenant = tenant
        # prefix-cache / chunked-prefill state: the admission-time match
        # (stashed by the guard), how many prompt tokens' KV came from
        # shared pages, the next position the chunk scheduler processes,
        # whether prefill is still in flight, and the admission order
        # the chunk lane round-robins over
        self.match: Optional[PrefixMatch] = None
        self.kv_cached = 0
        self.filled = 0
        self.prefilling = False
        self.seq = 0
        self.epoch = 0  # weight-swap epoch at prefill start (stale guard)
        self.rid = next(_RID)
        # the sampled request trace (None = unsampled: every hop's
        # tracing.event() is then a single `is None` check)
        self.trace: Optional[_tracing.Trace] = None


class _StepInFlight:
    """A decode step the device has been handed and the host has not
    fetched: its output array (the sampled tokens, a model's counters
    behind them; the device->host copy already started), the ``(slot,
    request)`` pairs and the per-row lengths as dispatched, the drafts by
    slot. ``reqs`` is the requests by identity: a slot may have changed
    hands by the time the step is retired."""

    __slots__ = ("out", "active", "drafts", "lens", "reqs")

    def __init__(self, out, active, drafts, lens):
        self.out = out
        self.active = active
        self.drafts = drafts
        self.lens = lens
        self.reqs = frozenset(req for _slot, req in active)


class DecodeEngine:
    """Continuous-batching decode service over one :class:`PagedDecodeModel`.

    ``submit(prompt, max_new_tokens)`` from any thread returns a Future of
    the generated token ids (``np.int32``, EOS included when hit). One
    engine thread runs the admit/step/complete loop; sampling is greedy
    argmax in-graph.

    Construction compiles nothing — call :meth:`warmup` to pre-compile
    the decode step and every prefill rung before traffic, after which a
    steady-state serve performs zero compiles no matter how sequences
    churn (``stats()['steady_state_recompiles']``, gauge-gated like the
    PR-2 server). ``name`` keys the stats series, the breaker site and
    the kv-cache gauge; keep it unique among live engines.
    """

    def __init__(self, model: PagedDecodeModel, params,
                 num_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 queue_depth: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 ring_prefill_len: Optional[int] = None,
                 name: str = "decode", retry_policy=None,
                 breaker_threshold: Optional[int] = None,
                 breaker_reset_s: Optional[float] = None,
                 dtype="float32", tenants=None,
                 prefix_cache: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_draft=None):
        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        self._model = model
        self._params = params
        if num_slots is None:
            num_slots = get_env("MXNET_DECODE_SLOTS", _DEFAULT_SLOTS, int,
                                cache=False)
        if max_seq_len is None:
            max_seq_len = get_env("MXNET_DECODE_MAX_SEQ_LEN",
                                  _DEFAULT_MAX_SEQ_LEN, int, cache=False)
        if queue_depth is None:
            queue_depth = get_env("MXNET_SERVING_QUEUE_DEPTH",
                                  _DEFAULT_QUEUE_DEPTH, int, cache=False)
        if timeout_ms is None:
            timeout_ms = get_env("MXNET_SERVING_TIMEOUT_MS",
                                 _DEFAULT_TIMEOUT_MS, float, cache=False)
        if ring_prefill_len is None:
            ring_prefill_len = get_env("MXNET_DECODE_RING_PREFILL_LEN", 0,
                                       int, cache=False)
        if prefix_cache is None:
            prefix_cache = bool(get_env("MXNET_DECODE_PREFIX_CACHE",
                                        _DEFAULT_PREFIX_CACHE, int,
                                        cache=False))
        if prefill_chunk is None:
            prefill_chunk = get_env("MXNET_DECODE_PREFILL_CHUNK",
                                    _DEFAULT_PREFILL_CHUNK, int,
                                    cache=False)
        if spec_k is None:
            spec_k = get_env("MXNET_DECODE_SPEC_K", 0, int, cache=False)
        self.num_slots = max(1, int(num_slots))
        self.max_seq_len = int(max_seq_len)
        self._queue_depth = max(1, int(queue_depth))
        self._timeout_s = float(timeout_ms) / 1e3
        self._ring_len = max(0, int(ring_prefill_len))
        self._prefix_cache = bool(prefix_cache)
        self._chunk = max(0, min(int(prefill_chunk), self.max_seq_len))
        # speculative decoding: the step carries a STATIC width of
        # spec_k+1 query rows per slot (committed token + up to k draft
        # rows). k=0 keeps the classic 1-row tick bit-for-bit (the
        # packed operand is then one column a slot). The width is
        # a compile-time constant — per-tick draft depth, acceptance and
        # per-tenant caps vary only the DATA inside it.
        self._spec_k = max(0, int(spec_k))
        self._spec_w = self._spec_k + 1
        if self._spec_k == 0:
            self._draft = None
        elif spec_draft is not None and not isinstance(spec_draft, str):
            self._draft = spec_draft   # a DraftProposer instance
        else:
            from .speculative import make_draft
            if spec_draft is None:
                spec_draft = get_env("MXNET_DECODE_SPEC_DRAFT",
                                     "prompt_lookup", str, cache=False)
            self._draft = make_draft(spec_draft, model, params)
        self._ladder = self._prefill_ladder(prefill_buckets)
        # the chunk jit's statically-shaped rungs: chunked prefill uses
        # ONE rung (the chunk size); with chunking off the prefix-cache
        # tail pads to the prefill ladder instead
        if self._chunk:
            self._chunk_rungs: tuple = (self._chunk,)
        elif self._prefix_cache:
            self._chunk_rungs = self._ladder
        else:
            self._chunk_rungs = ()
        kinds = {st[0] for st in layer_states(model)}
        if kinds != {"paged"} and (self._prefix_cache or self._chunk
                                   or self._spec_k or self._ring_len):
            why = {
                "ring": "a window layer's pages are a ring that is "
                        "rewritten in place (no page to share, no chunk or "
                        "draft row to read back through it)",
                "slot": "a slot's state is no page to share, a chunk would "
                        "have to hand it on and a rejected draft row cannot "
                        "be taken out of a recurrence",
                "latent": "its prefill attends in expanded form, not "
                          "through the pool"}
            raise MXNetError(
                "a model that declares %s layers (layer_state) "
                "is served with prefix_cache=False, prefill_chunk=0, "
                "spec_k=0 and no ring prefill: %s"
                % (" and ".join(sorted(kinds - {"paged"})),
                   "; ".join(why[k] for k in sorted(kinds) if k in why)))
        self._cache = make_cache(
            model, self.num_slots, self.max_seq_len, page_size=page_size,
            num_pages=num_pages, dtype=dtype, name=name,
            prefix_cache=self._prefix_cache)
        n_groups = self._cache.num_groups
        # rows of a prefill's packed operand: tokens, the first group's
        # write pages, offsets, a row of write pages a further group and,
        # for a cache that holds slot state, the slot (every column)
        self._prefill_rows = 2 + n_groups + bool(self._cache.state)
        # ... and of the step's: tokens, positions, seq_lens, the first
        # group's write pages, offsets, a row a further group, `from_prev`
        self._packed_rows = 4 + n_groups + 1
        # the tables a decode tick's paged attention walks: (cache group,
        # columns, layers that walk it)
        self._walk_groups = self._cache.walk_groups()
        self._kv_cols_live = 0
        self._kv_cols_grid = 0
        self._kv_cols_walked = 0
        # a model with experts returns its load counters beside the tokens
        moe_shape = getattr(model, "moe_counters", None)
        self._moe_rows = (np.zeros(moe_shape, np.int64)
                          if moe_shape else None)
        self._stats = ServingStats(name)
        self._name = name
        self._retry = retry_policy
        self._breaker = CircuitBreaker(
            "serving.%s.decode" % name, failure_threshold=breaker_threshold,
            reset_timeout_s=breaker_reset_s)
        # multi-tenant control plane: registry (tenants= is a
        # TenantRegistry, a MXNET_TENANTS-style spec string, or None =
        # the env spec), weighted-fair sub-queues costed in worst-case
        # tokens so weights apportion token throughput
        if isinstance(tenants, TenantRegistry):
            self._tenants = tenants
        else:
            self._tenants = TenantRegistry(
                server=name, spec=tenants,
                max_cost=float(self.max_seq_len),
                default_queue_depth=self._queue_depth)
        self._wfq = WeightedFairQueue(
            self._tenants,
            cost_fn=lambda r: float(int(r.prompt.size) + r.max_new))
        # the SLO engine's burn ratios divide by bounds the registry
        # cannot carry — register this engine's queue capacity
        _slo.note_bound("queue_depth", name, self._queue_depth)
        # HBM pressure governor: register this engine's worst-case byte
        # bounds and consult the degradation ladder at admission (see
        # _admit/_admit_guard). The KV pool is statically allocated, so
        # its bound is a constant; pending prefill is a callable bound —
        # every queued request may reserve up to max_seq_len of pages
        # (total_queued() reads one int, safe from any thread).
        self._governor = _hbm.governor()
        pools = jax.tree_util.tree_leaves(self._cache.operands)
        #: arrays a step is handed as the pools: one a layer for K and V
        self._kv_pool_leaves = len(pools)
        self._step_temp_bytes: Optional[int] = None  # set by warmup()
        #: telemetry.program_parts of every model program warmup() compiles
        #: (the step; each prefill and chunk rung, with its `rung`), each
        #: read from the object compiled for the warm-up call, with
        #: `seconds`: what the text and the pass over it cost
        self._programs: List[dict] = []
        #: worker-confined: the rows the live trace has (None: no trace)
        self._programs_traced: Optional[List[dict]] = None
        self._prefill_held_slot_ms = 0.0
        self._attn_blocks_rung = 0
        self._attn_blocks_live = 0
        self._rows_of_rungs = 0
        self._rows_of_blocks = 0
        pool_bytes = int(sum(x.nbytes for x in pools))
        self._governor.register_bound("serving.%s.kv_pool" % name,
                                      pool_bytes)
        # (what is not paged, a slot's state, no reservation takes)
        page_bytes = self._cache.paged_bytes // max(1, self._cache.num_pages)
        worst_pages = self._cache.pages_for(self.max_seq_len)
        self._governor.register_bound(
            "serving.%s.pending_prefill" % name,
            lambda: self._wfq.total_queued() * worst_pages * page_bytes)
        #: post-OOM governed re-admission cap (admit FEWER sequences at
        #: the same static slot shapes); None = ungoverned. Worker-only.
        self._governed_limit: Optional[int] = None
        #: the tier _admit observed this pass; _admit_guard (same worker
        #: pass, under _cv) reads it for the orange batch-defer rung
        self._tick_tier = "green"
        self._params_sig = _tree_sig(params)
        self._pending_swaps: List[tuple] = []
        self._variants = {}
        self._active_variant: Optional[str] = None
        self._swaps = 0
        self._deadline_evictions = 0

        donate = self._donate_argnums()

        # the tick's five (S*W,) int32 operands (tokens, positions,
        # seq_lens, write pages, write offsets; W = spec_k+1 query rows
        # per slot, 1 when speculation is off) travel as ONE packed
        # array — one host->device put per tick instead of five — with a
        # further group's write pages and, last, `from_prev`: the rows
        # whose token the host has not seen yet, taken on the device from
        # the previous step's output. The page tables ride a version-keyed
        # device cache (below), so a steady tick pays exactly one put +
        # one fetch. What a program hands its model a group, the cache
        # shapes (one group's bare, several as a tuple)
        per_group = self._cache.per_group

        def head():
            """The scope of a program's last operations (``mx_head``: what
            is not the model's own is under that part of the program,
            ``telemetry.PROGRAM_PARTS``), marked with the layout of the
            parts the program was traced with — what keeps a compile cache
            that another layout filled from serving this one its names."""
            from jax.experimental import xla_metadata

            stack = contextlib.ExitStack()
            stack.enter_context(jax.named_scope("mx_head"))
            stack.enter_context(xla_metadata.set_xla_metadata(
                mx_parts=telemetry.PROGRAM_PARTS_VERSION))
            return stack

        def with_counters(sampled, out):
            """The model's counters (if it returns any) behind the sampled
            token(s): one array, one fetch."""
            if len(out) == 3:
                return sampled
            return jnp.concatenate([sampled.reshape(-1),
                                    out[3].reshape(-1).astype(jnp.int32)])

        def mx_decode_step(params, packed, prev, pools, state, page_tables):
            # `prev`: the previous step's own output, whole (or zeros of
            # its shape before any step); its first S*W values are tokens
            with jax.named_scope("mx_head"):
                from_prev = packed[-1]
                tokens, positions, seq_lens, first_pages, write_offsets, \
                    *further_pages = packed[:-1]
                write_pages = per_group((first_pages, *further_pages))
                tokens = jnp.where(from_prev != 0, prev[:tokens.shape[0]],
                                   tokens)
            out = model.decode(
                params, tokens, positions, pools, state, page_tables,
                seq_lens, write_pages, write_offsets)
            logits, pools, state = out[:3]
            with head():
                sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return with_counters(sampled, out), pools, state

        # same packing for prefill: tokens + write pages + offsets share
        # the rung shape, so they travel as one (3, rung) array
        # (one jit, one program a rung: the scope names the rung's ops)
        def mx_prefill(params, packed, length, pools, state):
            more = {}
            with jax.named_scope("mx_head"):
                tokens, first_pages, write_offsets, *further_pages = packed
                if state:   # its prefill writes one slot's: the last row
                    *further_pages, slots = further_pages
                    more["slot"] = slots[0]
                write_pages = per_group((first_pages, *further_pages))
            with jax.named_scope("mx_prefill_%d" % tokens.shape[0]):
                out = model.prefill(
                    params, tokens, length, pools, state, write_pages,
                    write_offsets, **more)
            last, pools, state = out[:3]
            with head():
                first = jnp.argmax(last).astype(jnp.int32)
                return with_counters(first, out), pools, state

        # one prefill CHUNK: same (3, rung) packing plus the absolute
        # start position and the slot's page-table row — the chunk
        # attends through the pages (earlier chunks' and shared prefix
        # KV included), so start/length are traced and one compile
        # serves every chunk of a rung
        def mx_prefill_chunk(params, packed, start, length, page_row,
                             pools, state):
            tokens, write_pages, write_offsets = packed
            last, pools, state = model.prefill_chunk(
                params, tokens, start, length, pools, state, page_row,
                write_pages, write_offsets)
            with head():
                return jnp.argmax(last).astype(jnp.int32), pools, state

        # the copy-on-write copy: duplicate one page's K/V (all layers)
        # into a fresh page so a sequence diverging inside a shared page
        # writes into its own copy; src/dst are traced scalars — ONE
        # compile, pre-warmed against the null page. Pages are what is
        # paged: `state` passes through
        def mx_kv_cow(pools, state, src, dst):
            return jax.tree_util.tree_map(
                lambda pool: pool.at[dst].set(pool[src]), pools), state

        # pools are donated through the jits (they are dead the moment
        # the step returns — swap_pools rebinds to the outputs), so the
        # cache costs ONE pool of HBM, not two per step
        # the functions' names are the XLA modules' (jit_mx_decode_step,
        # ...): what a device trace is searched for
        # (`prev` is not donated: the host has yet to fetch it)
        self._step = jax.jit(mx_decode_step,
                             donate_argnums=(3, 4) if donate else ())
        self._prefill_jit = jax.jit(mx_prefill, donate_argnums=donate)
        self._chunk_jit = jax.jit(
            mx_prefill_chunk, donate_argnums=(5, 6) if donate else ())
        self._cow_jit = jax.jit(
            mx_kv_cow, donate_argnums=(0, 1) if donate else ())
        # what a step with no step before it is handed as `prev`: the
        # shape, dtype and placement of a step's output, so every mix of
        # host-fed and device-fed rows runs ONE executable. Weights
        # committed to one device commit the output there; across several
        # warmup() asks the step where it puts it
        n_out = self.num_slots * self._spec_w + (
            int(np.prod(moe_shape)) if moe_shape else 0)
        self._no_prev = jnp.zeros((n_out,), jnp.int32)
        leaf = jax.tree_util.tree_leaves(params)[0]
        if getattr(leaf, "committed", False) and \
                len(leaf.sharding.device_set) == 1:
            self._no_prev = jax.device_put(
                self._no_prev, next(iter(leaf.sharding.device_set)))
        #: the step dispatched and not yet fetched (worker-confined)
        self._inflight: Optional[_StepInFlight] = None
        self._steps_overlapped = 0
        # version-keyed device page tables: [version, table] a group
        self._pt_dev = [[-1, None] for _ in range(n_groups)]

        self._warm_compiles: Optional[int] = None
        self._slots: List[Optional[_DecodeRequest]] = \
            [None] * self.num_slots
        self._cv = threading.Condition()
        self._closed = False
        self._tokens_total = 0
        self._prefills = 0
        self._evictions = 0
        self._ticks = 0       # decode steps run
        self._slot_ticks = 0  # decoding slots, summed over those steps
        # speculation accounting (worker-confined): draft tokens
        # proposed/accepted, and the accepted-per-tick numerator/
        # denominator over SPECULATING slot-ticks only
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_new = 0         # tokens committed by speculating slots
        self._spec_slot_ticks = 0  # slot-ticks where a draft was in play
        self._cow_copies = 0   # written/read under _cv only
        self._admit_seq = 0    # admission order among prefilling slots
        self._rr_last = 0      # round-robin cursor over that order
        self._swap_epoch = 0   # worker-confined; bumps per applied swap
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="mxnet-decode-" + name)
        self._thread.start()

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    @staticmethod
    def _donate_argnums():
        from .. import fastpath

        if fastpath.donation_argnums_ok():
            return (3, 4)  # pools, state in the prefill signature
        return ()

    def _pools_dead(self) -> bool:
        """Whether a failed jitted execution consumed the donated pools
        (TPU/GPU donation only; always False on CPU where donation is
        off). A retry must not re-pass dead buffers, and a prefill
        failure that killed the pools has destroyed EVERY live sequence's
        KV — the caller escalates to a full eviction + fresh pools."""
        import jax

        dead = getattr(jax.tree_util.tree_leaves(self._cache.operands)[0],
                       "is_deleted", None)
        return bool(dead and dead())

    def _device_page_table(self):
        """The page tables' device copies as the model is handed them, one
        a group, each re-put only when ITS allocator mutated it
        (admission/free) — steady ticks with stable membership skip the
        transfer entirely."""
        for held, (ver, table) in zip(self._pt_dev, self._cache.tables):
            if held[0] != ver:
                held[:] = [ver, self._jnp.asarray(table)]
        return self._cache.per_group([held[1] for held in self._pt_dev])

    def _prefill_ladder(self, buckets):
        if buckets is None:
            raw = get_env("MXNET_DECODE_PREFILL_BUCKETS",
                          _DEFAULT_PREFILL_BUCKETS, str, cache=False)
            try:
                buckets = [int(t) for t in str(raw).split(",") if t.strip()]
            except ValueError:
                raise MXNetError("MXNET_DECODE_PREFILL_BUCKETS must be "
                                 "comma-separated ints, got %r" % (raw,))
        ladder = sorted({int(b) for b in buckets if int(b) > 0})
        if not ladder:
            raise MXNetError("empty prefill bucket ladder")
        # the top rung must cover every admissible prompt: cap the ladder
        # with max_seq_len so select_bucket never under-sizes a pad
        ladder = [b for b in ladder if b < self.max_seq_len]
        ladder.append(self.max_seq_len)
        return tuple(ladder)

    # ------------------------------------------------------------------
    # client side
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               timeout_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one sequence; returns a Future resolving to the
        generated ``np.int32`` token ids. Thread-safe. ``timeout_ms``
        bounds the WHOLE request — queue wait and generation: a sequence
        whose deadline expires mid-decode is evicted at the next tick
        boundary (pages freed, future fails with
        :class:`RequestTimeoutError`); ``<= 0`` disables. ``tenant``
        names the submitting tenant (:mod:`~mxnet_tpu.serving.tenancy`);
        untagged callers ride the ``default`` tenant."""
        arr = np.asarray(prompt, np.int32).ravel()
        if arr.size < 1:
            raise MXNetError("decode submit needs >= 1 prompt token")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if arr.size + max_new > self.max_seq_len:
            raise MXNetError(
                "prompt %d + max_new %d exceeds max_seq_len %d"
                % (arr.size, max_new, self.max_seq_len))
        # a worst-case reservation larger than the WHOLE pool — or than
        # the tenant's own page budget / rate burst — could never be
        # admitted: it would sit at its sub-queue head deferring forever,
        # so reject it at the door instead
        total = int(arr.size) + max_new
        need = self._cache.pages_for(total)
        capacity = self._cache.num_pages - 1
        tobj = self._tenants.resolve(tenant)
        # the trace is minted HERE — at submit(), the contract — so
        # EVERY door-reject (pool capacity, budgets, breaker) and shed
        # leaves a queryable chain too
        trace = _tracing.start_trace("decode", self._name, tobj.tenant_id)
        _tracing.event(trace, "submit", prompt_tokens=int(arr.size),
                       max_new=max_new)
        if need > capacity:
            _tracing.finish(trace, "rejected", reason="pool_capacity")
            raise MXNetError(
                "prompt %d + max_new %d needs %d KV pages but the pool "
                "only has %d: raise MXNET_KVCACHE_PAGES or shrink the "
                "request" % (arr.size, max_new, need, capacity))
        if tobj.page_budget is not None and need > tobj.page_budget:
            _tracing.finish(trace, "rejected", reason="page_budget")
            raise MXNetError(
                "request needs %d KV pages but tenant %r's page budget "
                "is %d: it could never be admitted"
                % (need, tobj.tenant_id, tobj.page_budget))
        if tobj.rate > 0.0 and total > tobj.burst:
            _tracing.finish(trace, "rejected", reason="burst_budget")
            raise MXNetError(
                "request costs %d tokens but tenant %r's burst budget "
                "is %.0f: it could never be admitted"
                % (total, tobj.tenant_id, tobj.burst))
        state = tobj.breaker.state
        if state == "open":
            # the tenant's own breaker is open: shed THIS tenant at the
            # door while every other tenant keeps flowing
            tobj.stats.on_shed(breaker=True)
            _T_EVENTS.inc(server=self._name, event="shed_tenant_breaker")
            _tracing.finish(trace, "shed", reason="tenant_breaker")
            raise TenantUnavailableError(tobj.tenant_id, state)
        timeout_s = (self._timeout_s if timeout_ms is None
                     else float(timeout_ms) / 1e3)
        deadline = (None if timeout_s <= 0
                    else time.perf_counter() + timeout_s)
        req = _DecodeRequest(arr, max_new, eos_id, deadline, tobj)
        req.trace = trace
        shed = None
        depth = 0
        with self._cv:
            if self._closed:
                raise ServerClosedError("submit() on a closed DecodeEngine")
            if len(tobj.queue) >= tobj.queue_depth:
                # per-tenant shed: one tenant's backlog fills ITS bound
                # before it can crowd the global queue
                shed = "tenant %r queue full (depth %d): request shed " \
                       "before the global queue" \
                       % (tobj.tenant_id, tobj.queue_depth)
            elif self._wfq.total_queued() >= self._queue_depth:
                shed = "decode queue full (depth %d): request shed" \
                       % self._queue_depth
            else:
                depth = self._wfq.push(tobj, req)
                gdepth = self._wfq.total_queued()
                self._cv.notify_all()
        if shed:
            self._stats.on_shed()
            tobj.stats.on_shed()
            _tracing.finish(trace, "shed", reason="queue_full")
            raise QueueFullError(shed)
        _tracing.event(trace, "enqueue", rid=req.rid, tenant_depth=depth,
                       queue_depth=gdepth)
        self._stats.on_submit(gdepth)
        tobj.stats.on_submit(depth)
        return req.future

    def generate(self, prompt, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None,
                 tenant: Optional[str] = None) -> np.ndarray:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, max_new_tokens, eos_id=eos_id,
                           tenant=tenant).result(timeout)

    # ------------------------------------------------------------------
    # live weight swap
    # ------------------------------------------------------------------
    def swap_params(self, params, variant: Optional[str] = None,
                    wait: bool = True,
                    timeout: Optional[float] = None) -> Future:
        """Hot-swap the served weights between ticks — zero dropped
        requests, zero recompiles.

        The new pytree must carry the SAME (treedef, shape, dtype)
        signature as the current one: the params enter the decode/prefill
        jits as a traced operand, so an equal signature is structurally
        guaranteed not to retrace (the steady-state-recompile gauge stays
        at 0 across the swap — asserted by the live-swap tests).
        In-flight sequences keep their slots and KV pages and continue
        under the new weights from the next tick — the
        fleet-upgrade/A-B-rollout semantic: nothing is evicted, nothing
        re-prefills. Returns a Future resolving True once a tick boundary
        applied the swap (``wait=True`` blocks on it)."""
        sig = _tree_sig(params)
        if sig != self._params_sig:
            raise MXNetError(
                "swap_params: new param pytree signature differs from the "
                "served one (tree structure, leaf shape or dtype) — a "
                "mismatched swap would retrace every rung; export the "
                "variant with identical architecture")
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise ServerClosedError("swap_params() on a closed engine")
            self._pending_swaps.append((params, variant, fut))
            self._cv.notify_all()
        if wait:
            fut.result(timeout)
        return fut

    def register_variant(self, name: str, params) -> None:
        """Register a named fine-tuned variant (same architecture) for
        :meth:`use_variant` — N variants served from ONE engine, swapped
        between ticks."""
        sig = _tree_sig(params)
        if sig != self._params_sig:
            raise MXNetError(
                "variant %r: param signature differs from the served "
                "model" % name)
        self._variants[str(name)] = params

    def use_variant(self, name: str, wait: bool = True,
                    timeout: Optional[float] = None) -> Future:
        """Swap a registered variant live (see :meth:`swap_params`)."""
        if name not in self._variants:
            raise MXNetError("unknown variant %r (registered: %s)"
                             % (name, sorted(self._variants) or "none"))
        return self.swap_params(self._variants[name], variant=str(name),
                                wait=wait, timeout=timeout)

    @property
    def active_variant(self) -> Optional[str]:
        with self._cv:
            return self._active_variant

    def warmup(self) -> int:
        """Compile the decode step, every prefill rung, every chunk rung
        and the CoW copy jit before traffic (dummy passes writing only
        to the null page); anchors the steady-state-recompile gauge at 0
        — a cold first shared-prefix request compiles NOTHING. Returns
        the compile count."""
        import jax

        jnp = self._jnp
        s = self.num_slots
        with self._cv:
            # snapshot: a live swap_params() may rebind between rungs
            params = self._params
        # the step's packed operand carries W = spec_k+1 rows per slot;
        # warming at that width anchors the widened tick too
        packed = np.zeros((self._packed_rows, s * self._spec_w), np.int32)
        # every row of write pages stays 0, the null page
        packed[4] = self._cache.null_write_slots(s * self._spec_w)[1]
        def step_args():
            return (params, jnp.asarray(packed), self._no_prev,
                    *self._cache.operands, self._device_page_table())

        # every model program is compiled ahead of its warm-up call, which
        # finds that lowering and that executable again (the jit holds one
        # of each for operands of one type: nothing is lowered or compiled
        # twice, with a persistent cache or without); what the compiled
        # object has to say is read behind the dispatch
        programs = []

        def mapped(compiled, **row):
            """The map of a compiled program's parts, with what reading it
            cost (``seconds``: the text and one pass over it)."""
            t0 = time.perf_counter()
            parts = telemetry.program_parts(compiled.as_text())
            programs.append(
                dict(parts, seconds=time.perf_counter() - t0, **row))

        for _ in range(2):
            args = step_args()
            compiled = self._step.lower(*args).compile()
            sampled, kp, vp = self._step(*args)
            self._cache.swap_pools(kp, vp)
            if _placed_alike(self._no_prev, sampled):
                break
            # weights sharded over several devices: the step's output is
            # placed by them, so the stand-in takes that placement and the
            # second pass warms the call every later step makes
            self._no_prev = jax.device_put(
                np.zeros(sampled.shape, sampled.dtype), sampled.sharding)
        mem = compiled.memory_analysis()
        if mem is not None:
            self._step_temp_bytes = int(mem.temp_size_in_bytes)
            _T_STEP_TEMP.set(self._step_temp_bytes, server=self._name)
        mapped(compiled)
        if not self._chunk:
            # chunked mode never dispatches the monolithic rungs — every
            # prompt runs through the one chunk rung compiled below
            for rung in self._ladder:
                pre = np.zeros((self._prefill_rows, rung), np.int32)
                pre[2] = self._cache.null_write_slots(rung)[1]
                args = (params, jnp.asarray(pre), jnp.asarray(1, jnp.int32),
                        *self._cache.operands)
                compiled = self._prefill_jit.lower(*args).compile()
                _tok, kp, vp = self._prefill_jit(*args)
                self._cache.swap_pools(kp, vp)
                mapped(compiled, rung=rung)
        null_row = np.zeros((self._cache.max_pages,), np.int32)
        for rung in self._chunk_rungs:
            pre = np.zeros((3, rung), np.int32)
            pre[2] = self._cache.null_write_slots(rung)[1]
            args = (params, jnp.asarray(pre), jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32), jnp.asarray(null_row),
                    *self._cache.operands)
            compiled = self._chunk_jit.lower(*args).compile()
            _tok, kp, vp = self._chunk_jit(*args)
            self._cache.swap_pools(kp, vp)
            mapped(compiled, rung=rung)
        if self._prefix_cache:
            # null -> null: harmless, and the CoW copy is compiled
            kp, vp = self._cow_jit(
                *self._cache.operands,
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
            self._cache.swap_pools(kp, vp)
        with self._cv:
            self._programs = programs
        count = self.compile_count
        self._warm_compiles = count if count >= 0 else None
        if self._warm_compiles is not None:
            telemetry.set_steady_state_recompiles("serving." + self._name, 0)
        return count

    def _trace_programs(self):
        """One zero-length ``mx.decode.programs`` span a program a
        ``jax.profiler`` trace, carrying the map from its instructions'
        names to its parts (``{part: [instruction]}`` as one JSON string)
        for whoever reads the device's events of that trace: written by the
        worker's first look (a pass, or a slice of an empty wait) that finds
        the trace live. Without a trace: one compare a look."""
        rows = self._programs if telemetry.trace_live() else None
        if rows is not None and rows is not self._programs_traced:
            for row in rows:
                by_part = {}
                for inst, part in row["parts"].items():
                    by_part.setdefault(part, []).append(inst)
                args = {"program": row["program"], "mixed": row["mixed"],
                        "unnamed": len(row["unnamed"]),
                        "parts": json.dumps(by_part, sort_keys=True),
                        "map_us": int(row["seconds"] * 1e6)}
                if "rung" in row:
                    args["rung"] = row["rung"]
                elif self._step_temp_bytes is not None:
                    args["step_temp_bytes"] = self._step_temp_bytes
                with telemetry.span("decode.programs", _SPAN_CAT, **args):
                    pass
        self._programs_traced = rows

    @property
    def compile_count(self) -> int:
        sizes = [telemetry.jit_cache_size(self._step),
                 telemetry.jit_cache_size(self._prefill_jit),
                 telemetry.jit_cache_size(self._chunk_jit),
                 telemetry.jit_cache_size(self._cow_jit)]
        if any(s < 0 for s in sizes):
            return -1
        return sum(sizes)

    def queue_depth(self) -> int:
        """Requests queued but not yet slotted — the cheap read behind
        the fleet's ``/debug/state`` view (``stats()`` evaluates SLOs;
        this doesn't)."""
        with self._cv:
            return self._wfq.total_queued()

    def kvcache_stats(self) -> dict:
        """The paged pool's counters alone (pages in use/free, prefix
        hit ratio) — the cheap subset of :meth:`stats`."""
        return self._cache.stats()

    def stats(self) -> dict:
        out = self._stats.snapshot()
        with self._cv:
            active = sum(1 for r in self._slots if r is not None)
            out.update({
                "slots": self.num_slots,
                "active_slots": active,
                "queued": self._wfq.total_queued(),
                "tokens_generated": self._tokens_total,
                "prefills": self._prefills,
                "evictions": self._evictions,
                "deadline_evictions": self._deadline_evictions,
                "slot_occupancy": (
                    self._slot_ticks / float(self._ticks * self.num_slots)
                    if self._ticks else 0.0),
                # monotonic: two scrapes give a windowed occupancy,
                # d(slot_ticks) / (d(ticks) * slots)
                "ticks": self._ticks,
                "slot_ticks": self._slot_ticks,
                # steps dispatched while the one before was un-fetched
                "steps_overlapped": self._steps_overlapped,
                # page-table columns the ticks' attention walks ran, of
                # the tables' columns x slots x layers (the share that
                # ran), and the grid steps their launches took to run them
                "kv_cols_live": self._kv_cols_live,
                "kv_cols_grid": self._kv_cols_grid,
                "kv_cols_walked": self._kv_cols_walked,
                "kv_pool_leaves": self._kv_pool_leaves,
                "decode_step_temp_bytes": self._step_temp_bytes,
                # per program, per part, the instructions the compiled
                # text puts there: a refactor that lost a scope shows here
                "program_parts": {
                    row["program"] + ("/%d" % row["rung"]
                                      if "rung" in row else ""):
                    dict(collections.Counter(row["parts"].values()),
                         unnamed=len(row["unnamed"]), mixed=row["mixed"])
                    for row in self._programs},
                # decoding slots x the prefills they waited behind, counted
                # while telemetry or a jax.profiler trace is on
                "prefill_held_slot_ms": self._prefill_held_slot_ms,
                # block pairs of the prefills' attention band: over the
                # padded rungs, and over the prompts' real tokens (what
                # the kernel multiplies)
                "attn_blocks_rung": self._attn_blocks_rung,
                "attn_blocks_live": self._attn_blocks_live,
                # rows of the prefills' row-wise passes: the padded rungs',
                # and those of the row blocks the prompts reached
                "prefill_rows_rung": self._rows_of_rungs,
                "prefill_rows_computed": self._rows_of_blocks,
                "prefill_buckets": list(self._ladder),
                "prefill_chunk": self._chunk,
                "cow_copies": self._cow_copies,
                "breaker": self._breaker.state,
                "weight_swaps": self._swaps,
                "active_variant": self._active_variant,
                "speculative": {
                    "k": self._spec_k,
                    "draft": (getattr(self._draft, "name", None)
                              if self._draft is not None else None),
                    "proposed_tokens": self._spec_proposed,
                    "accepted_tokens": self._spec_accepted,
                    "acceptance_rate": (self._spec_accepted /
                                        self._spec_proposed
                                        if self._spec_proposed else 0.0),
                    # tokens committed per SPECULATING slot-tick (1.0 =
                    # drafts never helped; k+1 = every draft accepted)
                    "accepted_per_tick": (self._spec_new /
                                          self._spec_slot_ticks
                                          if self._spec_slot_ticks else 0.0),
                },
            })
            governed = self._governed_limit
            moe_rows = None if self._moe_rows is None \
                else self._moe_rows.copy()
        if moe_rows is not None:
            held = moe_rows[:, :-1]
            out["moe"] = {
                "expert_layers": int(held.shape[0]),
                "experts_held": int(held.shape[1]),
                "rows_held": int(held.sum()),
                "rows_absent": int(moe_rows[:, -1].sum()),
                "rows_by_expert": [[int(n) for n in row] for row in held],
                "load_max_over_mean": self._moe_load(held),
            }
        out["tenants"] = self._tenants.snapshot()
        out["kvcache"] = self._cache.stats()
        if "state" in out["kvcache"]:   # a cache that holds slot state
            out["state"] = out["kvcache"].pop("state")
        # the governor's verdict rides every stats snapshot (the fleet's
        # replica rows and /debug/state read it from here)
        hv = self._governor.healthz_view()
        hv["governed_limit"] = governed
        hv["pressure_sheds"] = self._cache.pressure_sheds
        out["hbm"] = hv
        out["prefix_cache_enabled"] = self._prefix_cache
        if self._prefix_cache:
            out["prefix_hit_ratio"] = out["kvcache"]["prefix_hit_ratio"]
            # refcount>1 pages belong to the `shared` pseudo-tenant: no
            # real tenant's budget is charged for them (a sharer pays
            # only its exclusive tail + CoW copies)
            out["tenants"][SHARED_TENANT] = {
                "pseudo": True,
                "pages_in_use_now": out["kvcache"]["shared_pages"],
                "pages_cached": out["kvcache"]["pages_cached"],
            }
        count = self.compile_count
        out["compile_count"] = count
        if self._warm_compiles is not None and count >= 0:
            steady = count - self._warm_compiles
            out["steady_state_recompiles"] = steady
            telemetry.set_steady_state_recompiles(
                "serving." + self._name, steady)
        # live SLO verdicts over the series this snapshot just refreshed
        out["alerts"] = _slo.evaluate()
        return out

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> int:
        """Stop intake; ``drain=True`` finishes every queued AND admitted
        sequence first, ``drain=False`` fails them with
        :class:`ServerClosedError` now. Idempotent.

        Returns the number of requests that *completed during the drain*
        (0 for ``drain=False`` and for repeat closes) — the number a
        zero-drop replica drain / rolling upgrade asserts against; also
        published as ``mxnet_serving_drain_completed_total{server=}``."""
        before = self._stats.completed
        with self._cv:
            self._closed = True
            dropped: List[_DecodeRequest] = []
            if not drain:
                dropped = [req for _t, req in self._wfq.drain()]
                for i, req in enumerate(self._slots):
                    if req is not None:
                        dropped.append(req)
                        self._slots[i] = None
                        self._release_slot(i, req)
            self._cv.notify_all()
        exc = ServerClosedError("engine closed before completion")
        for req in dropped:
            self._fail(req, exc)
        if self._thread is not threading.current_thread():
            self._thread.join(timeout)
        # the governor outlives the engine (process-global): replace the
        # live-state bounds with zeros so a closed engine neither skews
        # pressure nor stays pinned through the pending-prefill closure
        self._governor.register_bound(
            "serving.%s.kv_pool" % self._name, 0)
        self._governor.register_bound(
            "serving.%s.pending_prefill" % self._name, 0)
        if not drain:
            return 0
        drained = max(0, self._stats.completed - before)
        self._stats.on_drain(drained)
        return drained

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def name(self) -> str:
        """The engine's server name — keys its stats series, breaker
        site and kv-cache gauges (and the fleet router's replica map)."""
        return self._name

    @property
    def page_size(self) -> int:
        """Tokens per KV page — the chunk granularity of the prefix
        cache's rolling hash (the fleet router hashes prompts at the
        same granularity to route for affinity)."""
        return self._cache.page_size

    @property
    def tenants(self) -> TenantRegistry:
        """The engine's tenant registry — register tenants with explicit
        weights/quotas before (or while) traffic flows."""
        return self._tenants

    # ------------------------------------------------------------------
    # engine thread
    # ------------------------------------------------------------------
    def _any_active(self) -> bool:
        return any(r is not None for r in self._slots)

    def _worker(self):
        while True:
            with self._cv:
                # (a step in flight is retired by the next pass: never
                # waited on, never left behind at the exit)
                while not self._wfq.total_queued() \
                        and not self._any_active() and not self._closed \
                        and not self._pending_swaps \
                        and self._inflight is None:
                    with telemetry.span("decode.idle", _SPAN_CAT,
                                        why="empty"):
                        self._cv.wait(_IDLE_SLICE_S)
                    self._trace_programs()
                if self._closed and not self._wfq.total_queued() \
                        and not self._any_active() \
                        and self._inflight is None:
                    swaps, self._pending_swaps = self._pending_swaps, []
                    break
            with telemetry.span("decode.tick", _SPAN_CAT) as tick:
                self._tick(tick)
        # drained close: resolve any swap still pending so its waiter
        # does not hang on a dead worker
        exc = ServerClosedError("engine closed before the swap applied")
        for _params, _variant, fut in swaps:
            if fut.set_running_or_notify_cancel():
                fut.set_exception(exc)

    def _tick(self, tick):
        """One pass of the worker: housekeeping, admission, at most one
        prefill chunk, then the decode step — dispatched BEFORE the step
        of the pass before is fetched and committed, so that fetch, that
        commit and the next pass's housekeeping and admission run while
        the device computes. ``tick`` is the pass's span."""
        with telemetry.span("decode.housekeep", _SPAN_CAT):
            self._apply_pending_swaps()
            self._expire_queued()
            self._evict_expired()
            self._shed_tenant_breakers()
            with self._cv:
                has_work = bool(self._wfq.total_queued()) \
                    or self._any_active()
        self._trace_programs()
        try:
            if not has_work:
                self._step_pass(())     # (a step whose rows all left)
                return
            if not self._breaker.allow():
                # open ENGINE breaker: answer all queued work explicitly
                # (the PR-2 engine load-shed) instead of letting it age
                # out; the reset timeout admits a half-open probe later
                self._step_pass(())
                self._shed_open_breaker()
                with telemetry.span("decode.idle", _SPAN_CAT, why="breaker"):
                    time.sleep(0.005)
                return
            with telemetry.span("decode.admit", _SPAN_CAT):
                self._admit()
            prefilling = [(i, r) for i, r in enumerate(self._slots)
                          if r is not None and r.prefilling]
            decoding = self._decoding()
            with self._cv:
                queued = self._wfq.total_queued()
            tick.set_args(active=len(decoding), prefilling=len(prefilling),
                          queued=queued)
            if prefilling:
                # ONE chunk per tick, ROUND-ROBIN over prefilling
                # slots (admission order, wrapping), then the tick
                # goes back to decoding. Round-robin — not oldest-
                # first — is what decouples TTFT from the longest
                # prompt: a 1-chunk prompt lands on its next turn
                # instead of waiting out a 100-chunk neighbour.
                cands = sorted(prefilling, key=lambda t: t[1].seq)
                slot, req = next(
                    (t for t in cands if t[1].seq > self._rr_last),
                    cands[0])
                self._rr_last = req.seq
                with self._prefill_span(chunk=self._chunk):
                    self._advance_prefill(slot, req)
            if decoding or self._inflight is not None:
                self._step_pass(decoding)
            elif not prefilling:
                # every queued tenant deferred (pages/rate/breaker)
                # with nothing in flight: yield instead of spinning
                with telemetry.span("decode.idle", _SPAN_CAT,
                                    why="deferred"):
                    time.sleep(0.001)
        except Exception as exc:  # noqa: BLE001 - engine must survive
            # belt-and-braces (the PR-2 batcher discipline): NO
            # exception may kill the engine thread — that would hang
            # every in-flight and queued future forever. Evict
            # whatever was in flight and keep serving. This is also a
            # black-box moment: something unexpected reached the
            # catch-all, so commit the ring before state is torn down.
            _flightrec.record("decode.engine_exception",
                              server=self._name, error=repr(exc))
            _flightrec.dump("decode engine catch-all: %r" % (exc,))
            self._breaker.on_failure()
            self._evict([(i, r) for i, r in enumerate(self._slots)
                         if r is not None], exc)

    def _decoding(self):
        """The (slot, request) pairs the step decodes: all but a sequence
        whose LAST token (by its budget) is the one in flight."""
        return [(i, r) for i, r in enumerate(self._slots)
                if r is not None and not r.prefilling
                and len(r.tokens) + self._ahead(r) < r.max_new]

    @contextlib.contextmanager
    def _prefill_span(self, **args):
        """``mx.decode.prefill`` around one prefill (a rung or a chunk).
        While somebody reads it (a ``jax.profiler`` trace, the registry) it
        carries ``held``, the slots that are decoding as it is launched —
        the sequences whose next token waits for it — and its duration
        times ``held`` is added to what decoding slots lost to prefills."""
        if not (telemetry.trace_live() or telemetry.enabled()):
            with telemetry.span("decode.prefill", _SPAN_CAT, **args) as span:
                yield span
            return
        held = len(self._decoding())
        t0 = time.perf_counter()
        try:
            with telemetry.span("decode.prefill", _SPAN_CAT, held=held,
                                **args) as span:
                yield span
        finally:
            lost_ms = held * (time.perf_counter() - t0) * 1e3
            with self._cv:
                self._prefill_held_slot_ms += lost_ms
            _T_PREFILL_HELD.inc(lost_ms, server=self._name)

    def _apply_pending_swaps(self):
        """Tick-boundary weight swap: rebind ``self._params`` between
        jitted executions. In-flight sequences continue on the new
        weights next tick; nothing is evicted and (same pytree
        signature) nothing retraces."""
        with self._cv:
            if not self._pending_swaps:
                return
            swaps, self._pending_swaps = self._pending_swaps, []
            for params, variant, _fut in swaps:
                self._params = params
                self._active_variant = variant
                self._swaps += 1
            self._swap_epoch += len(swaps)
            if swaps and self._prefix_cache:
                # cached KV was computed under the OLD weights: a prompt
                # prefilled under the new ones must not match it — flush
                # the index (in-flight sequences keep their pages and
                # continue, the documented rollout semantic)
                self._cache.clear_prefix_index()
        for _params, variant, fut in swaps:
            _T_EVENTS.inc(server=self._name, event="weight_swap")
            _flightrec.record("decode.weight_swap", server=self._name,
                              variant=variant)
            if fut.set_running_or_notify_cancel():
                fut.set_result(True)

    def _expire_queued(self):
        now = time.perf_counter()
        with self._cv:
            expired = self._wfq.expire(now)
        for tenant, req in expired:
            self._stats.on_timeout()
            tenant.stats.on_timeout()
            _tracing.finish(req.trace, "timeout", where="queued")
            self._fail(req, RequestTimeoutError(
                "request spent > its deadline queued"))

    def _evict_expired(self):
        """Deadline propagation into the tick loop: a sequence whose
        deadline passed mid-decode is evicted at the tick boundary —
        pages freed for waiting tenants, future failed — instead of
        holding its slot to the token budget."""
        now = time.perf_counter()
        victims: List[tuple] = []
        with self._cv:
            for i, req in enumerate(self._slots):
                if req is not None and req.deadline is not None \
                        and now > req.deadline:
                    victims.append((i, req))
                    self._slots[i] = None
                    self._release_slot(i, req)
        if victims:
            with self._cv:
                self._deadline_evictions += len(victims)
        for i, req in victims:
            self._stats.on_timeout()
            req.tenant.stats.on_timeout()
            _T_EVENTS.inc(server=self._name, event="deadline_evicted")
            _tracing.finish(req.trace, "timeout", where="mid_decode",
                            tokens=len(req.tokens))
            _flightrec.record("decode.deadline_evict", server=self._name,
                              rid=req.rid, tenant=req.tenant.tenant_id)
            self._fail(req, RequestTimeoutError(
                "deadline expired mid-decode after %d generated tokens: "
                "evicted at the tick boundary" % len(req.tokens)))

    def _shed_tenant_breakers(self):
        """A tenant whose breaker is open has its QUEUED work answered
        now with :class:`TenantUnavailableError` — that tenant alone;
        the engine keeps serving everyone else."""
        dropped: List[tuple] = []
        for tenant in self._tenants:
            if not tenant.queue:
                continue
            if tenant.breaker.state == "open":
                with self._cv:
                    dropped.extend(self._wfq.drain(tenant))
        for tenant, req in dropped:
            tenant.stats.on_shed(breaker=True)
            _T_EVENTS.inc(server=self._name, event="shed_tenant_breaker")
            _tracing.finish(req.trace, "shed", reason="tenant_breaker")
            self._fail(req, TenantUnavailableError(tenant.tenant_id,
                                                   "open"))

    def _shed_open_breaker(self):
        with self._cv:
            dropped = self._wfq.drain()
        if not dropped:
            return
        exc = EngineUnavailableError(
            "decode breaker is %s: request shed" % self._breaker.state)
        for tenant, req in dropped:
            self._stats.on_unavailable(1)
            tenant.stats.on_shed()
            _tracing.finish(req.trace, "shed", reason="engine_breaker")
            self._fail(req, exc)
            _T_EVENTS.inc(server=self._name, event="shed_open_breaker")

    # -- admission ------------------------------------------------------
    def _admit_guard(self, tenant: Tenant, req: "_DecodeRequest") -> bool:
        """Per-tenant admission veto, called by the weighted-fair pick
        under ``self._cv``. False = defer THIS tenant (its turn passes;
        other tenants' smaller/cheaper heads still admit this round —
        the anti-head-of-line property)."""
        # non-consuming open-state check FIRST, so a deferred tenant's
        # tokens are never charged for an admission its breaker would
        # refuse anyway (the worker's shed pass drains it shortly)
        if tenant.breaker.state == "open":
            _tracing.event(req.trace, "defer", reason="breaker")
            return False
        # orange-tier ladder rung: batch-class tenants defer while the
        # governor reports pressure — a deferral, not a shed (the
        # request stays queued and admits when the tier recedes), and
        # it NEVER touches interactive/standard heads: anti-head-of-line
        # means the batch head's turn simply passes to them
        if self._tick_tier in ("orange", "red") \
                and tenant.priority >= PRIORITY_CLASSES["batch"]:
            tenant.stats.on_defer("pressure")
            _tracing.event(req.trace, "defer", reason="pressure")
            return False
        total = int(req.prompt.size) + req.max_new
        # the admission walk: map-able shared prefix pages reduce both
        # the global reservation AND the tenant's charge — reserve()
        # only pays for the non-shared tail (+ the CoW copy). Stashed on
        # the request; _prefill consumes it on the same worker pass, so
        # the index cannot change in between.
        match = (self._cache.match_prefix(req.prompt)
                 if self._prefix_cache
                 and not (self._ring_len
                          and req.prompt.size >= self._ring_len)
                 else None)
        req.match = match
        need = self._cache.pages_for(total)
        if match is not None:
            need -= len(match.full)
        if not self._cache.can_admit_prefix(total, match):
            # global page pressure: this head defers, a cheaper tenant
            # behind it may still fit
            tenant.stats.on_defer("pages")
            _tracing.event(req.trace, "defer", reason="pages_global")
            return False
        if not tenant.within_page_budget(need):
            # the tenant is at ITS quota (shared pages charge the
            # `shared` pseudo-tenant, not this budget) — only its own
            # completions can unblock it, everyone else keeps flowing
            tenant.stats.on_defer("pages")
            _tracing.event(req.trace, "defer", reason="pages_budget")
            return False
        if not tenant.take_tokens(total):
            tenant.stats.on_defer("rate")
            _tracing.event(req.trace, "defer", reason="rate")
            return False
        # allow() LAST: it may consume the half-open probe, so it must
        # only run when the pop — and therefore the prefill that reports
        # the probe's outcome — really happens next. A veto here refunds
        # the tokens just taken: the request never ran.
        if not tenant.breaker.allow():
            tenant.refund_tokens(total)
            _tracing.event(req.trace, "defer", reason="breaker")
            return False
        _tracing.event(req.trace, "admission_verdict", pages_needed=need,
                       matched_pages=len(match.full) if match else 0)
        return True

    def _admit(self):
        # the governor's degradation ladder, consulted once per
        # admission pass (observe() is pure host arithmetic over the
        # bound registry — tick-rate cheap):
        #   yellow+  shed cached-LRU ref-0 prefix pages proactively
        #   orange   shrink the admission quantum to 1/pass and defer
        #            batch-class tenants (_admit_guard, never interactive)
        #   red      stop new admissions entirely; in-flight sequences
        #            keep decoding — completion is what drains pressure
        tier = self._governor.observe(source="decode.admit")
        with self._cv:
            # _cv guards both governor fields: _admit_guard reads
            # _tick_tier under the pop's lock, stats() reads
            # _governed_limit from caller threads
            # the only reader, _admit_guard, is a callback invoked through
            # _wfq.pop() inside this same worker's `with self._cv` block —
            # lock-guarded on both sides, just through an indirection the
            # analyzer cannot follow
            self._tick_tier = tier  # tpulint: disable=shared-state-race
            if self._governed_limit is not None and tier == "green" \
                    and not self._governor.latched:
                self._governed_limit = None
            governed = self._governed_limit
        if tier != "green":
            shed = self._cache.shed_cached()
            if shed:
                self._governor.note_shed(shed, self._cache.name)
                _T_EVENTS.inc(server=self._name, event="pressure_shed")
        if tier == "red":
            return
        limit = self.num_slots
        if governed is not None:
            # post-OOM governed re-admission: fewer sequences, same
            # static slot shapes, until the governor recovers green
            limit = min(limit, governed)
        quantum = 1 if tier == "orange" else self.num_slots
        admitted = 0
        while True:
            if sum(1 for r in self._slots if r is not None) >= limit:
                return
            slot = next((i for i, r in enumerate(self._slots)
                         if r is None), None)
            if slot is None:
                return
            with self._cv:
                picked = self._wfq.pop(self._admit_guard)
            if picked is None:
                return
            tenant, req = picked
            tenant.stats.set_depth(len(tenant.queue))
            try:
                self._prefill(req, slot)
            except Exception as exc:  # noqa: BLE001 - isolate to request
                # per-request isolation: a prefill failure (poisoned
                # prompt, tenant-scoped fault, exhausted retries) answers
                # ONLY this future — and feeds the TENANT breaker, not
                # the engine one (request-level vs tick-level faults)
                self._release_slot(slot, req)
                tenant.on_request_failure()
                self._stats.on_error()
                self._fail(req, exc)
                if self._on_oom("serving.decode.prefill", exc) \
                        or self._pools_dead():
                    # ...unless the failure classified as an OOM (an
                    # allocation died — every pool byte is suspect, and
                    # the governor just latched red) or the failed
                    # execution consumed the donated pools: every live
                    # sequence's KV died with them, so evict them all
                    # onto fresh pools (empty `active` still re-zeroes —
                    # reset_pools runs either way)
                    self._evict([(i, r) for i, r
                                 in enumerate(self._slots)
                                 if r is not None], exc)
                    return
            admitted += 1
            if admitted >= quantum:
                # orange's shrunk admission quantum: one admission per
                # pass keeps new prefill load trickling while pressure
                # is worked off
                return

    def _on_oom(self, plane: str, exc: BaseException) -> bool:
        """OOM classification at a failure site: False (untouched) for a
        non-OOM exception. A classified OOM — real ``RESOURCE_EXHAUSTED``
        out of XLA or the chaos harness's ``action=oom`` — runs the
        shared survival routine (``hbm.oom_survival``: diagnostic into
        the flight recorder, governor latched red, per-plane counter)
        and arms governed re-admission: after the caller's full
        eviction, ``_admit`` re-admits at half the sequence count that
        was in flight (``MXNET_HBM_RED_ADMIT`` overrides) until the
        governor recovers green. Slot shapes never change — fewer
        sequences, same jit signatures, zero recompiles."""
        if not _hbm.oom_survival(plane, exc, dump=False):
            return False
        active = sum(1 for r in self._slots if r is not None)
        with self._cv:
            self._governed_limit = self._governor.governed_admit(
                max(1, active))
        _T_EVENTS.inc(server=self._name, event="oom")
        return True

    def _prefill(self, req: _DecodeRequest, slot: int):
        # tenant-scoped chaos site, OUTSIDE the retry policy: a fault
        # scheduled against this tenant models the tenant's own traffic
        # being poisoned — it fails this request (feeding the tenant's
        # breaker via _admit's handler), it is not an engine transient
        # to be retried away. Site: serving.decode.tenant.<id>.
        chaos.maybe_fail("serving.decode.tenant.%s" % req.tenant.tenant_id)
        p = int(req.prompt.size)
        total = p + req.max_new
        req.epoch = self._swap_epoch  # worker-confined read
        ring = bool(self._ring_len and p >= self._ring_len)
        if self._prefix_cache and not ring:
            # the admission walk's match (stashed by the guard on this
            # same worker pass): shared full pages map refcounted into
            # the slot, the divergent/partial page gets a private CoW
            # copy, and reserve() pays only for the non-shared tail
            matched, cow_src, cow_dst = self._cache.admit_prefix(
                slot, total, req.match)
        else:
            self._cache.reserve(slot, total)
            matched, cow_src, cow_dst = 0, None, None
        # shared pages charge the `shared` pseudo-tenant (i.e. nobody):
        # the tenant's budget pays for its exclusive tail + CoW copies
        req.tenant.charge_pages(self._cache.exclusive_pages(slot))
        if cow_src is not None:
            with telemetry.span("decode.prefill", _SPAN_CAT, cow=1):
                self._run_cow(cow_src, cow_dst)
        req.kv_cached = matched
        _tracing.event(req.trace, "admit", slot=slot, ring=ring,
                       queue_wait_ms=round(
                           (time.perf_counter() - req.t_submit) * 1e3, 3))
        if matched:
            _tracing.event(req.trace, "prefix_hit", tokens_cached=matched)
        if cow_src is not None:
            _tracing.event(req.trace, "cow_copy", src_page=cow_src,
                           dst_page=cow_dst)
        # at least the LAST prompt position always runs through the
        # model: its logits are the first output token — a full-prompt
        # hit recomputes that one position (null writes) over the
        # shared/CoW pages instead of re-prefilling anything
        req.filled = min(matched, p - 1)
        if self._chunk and not ring:
            req.prefilling = True
            with self._cv:
                self._admit_seq += 1
                req.seq = self._admit_seq
            self._slots[slot] = req
            _T_EVENTS.inc(server=self._name, event="admitted")
            return
        rung = select_bucket(p - req.filled, self._ladder)
        with self._prefill_span(rung=rung) as span:
            if matched == 0:
                tok = self._run_full_prefill(req, slot, ring=ring)
                span.set_args(**self._attn_blocks(rung, p),
                              **self._rows_computed(rung, p))
            else:
                tok = self._run_chunk(slot, req, req.filled, p, rung)
            self._finish_prefill(req, slot, tok, span)

    def _run_full_prefill(self, req: _DecodeRequest, slot: int,
                          ring: bool = False):
        """The monolithic prefill: whole prompt padded to a ladder rung,
        attention in-graph (or routed through ring attention for
        long-context prompts). The cold-cache path — a prefix hit runs
        :meth:`_run_chunk` over the tail instead."""
        from .. import resilience

        jnp = self._jnp
        p = int(req.prompt.size)
        rung = select_bucket(p, self._ladder)
        _tracing.event(req.trace, "prefill", rung=rung, tokens=p,
                       ring=ring)
        # tokens, write pages, offsets (+ a further group's write pages,
        # + the slot whose state it writes); the padding's pages stay 0,
        # the null page
        pre = np.zeros((self._prefill_rows, rung), np.int32)
        pre[0, :p] = req.prompt
        wpg, woff = self._cache.write_slots(slot, 0, p)
        pre[1, :p] = wpg[0]
        pre[3:2 + len(wpg), :p] = wpg[1:]
        if self._cache.state:
            pre[-1] = slot
        pre[2] = np.concatenate(
            [woff, self._cache.null_write_slots(rung - p)[1]])
        policy = self._retry or resilience.default_policy()

        def attempt():
            chaos.maybe_fail("serving.decode.prefill")
            if self._pools_dead():
                raise MXNetError(  # not transient: stop the retry loop
                    "KV pools consumed by a failed prefill (donation); "
                    "eviction required")
            if ring:
                return self._run_ring_prefill(pre[0], p, pre[1], pre[2])
            return telemetry.jit_call(
                "serving.decode_prefill", self._prefill_jit, self._params,
                jnp.asarray(pre), jnp.asarray(p, jnp.int32),
                *self._cache.operands)

        tok, kp, vp = policy.call(attempt, site="serving.decode.prefill")
        self._cache.swap_pools(kp, vp)
        return tok

    def _run_chunk(self, slot: int, req: _DecodeRequest, start: int,
                   end: int, rung: int):
        """One jitted prefill chunk over prompt positions ``[start,
        end)`` of ``slot``, padded to ``rung``. Positions below
        ``req.kv_cached`` are only *recomputed* (their KV already sits
        in shared/CoW pages — writes redirect to the null page); the
        rest scatter into the slot's reserved pages. Attention runs over
        the slot's page row, so each chunk sees everything written
        before it. Returns the device argmax token of position
        ``end - 1``."""
        from .. import resilience

        jnp = self._jnp
        n = end - start
        _tracing.event(req.trace, "prefill_chunk", start=start, end=end,
                       rung=rung)
        pre = np.zeros((3, rung), np.int32)
        pre[0, :n] = req.prompt[start:end]
        cached_n = max(0, min(req.kv_cached, end) - start)
        pages, offs = [], []
        if cached_n:
            npg, noff = self._cache.null_write_slots(cached_n)
            pages.append(npg)
            offs.append(noff)
        if n - cached_n:
            wpg, woff = self._cache.write_slots(slot, start + cached_n,
                                                n - cached_n)
            pages.append(wpg)
            offs.append(woff)
        if rung - n:
            npg, noff = self._cache.null_write_slots(rung - n)
            pages.append(npg)
            offs.append(noff)
        pre[1] = np.concatenate(pages, axis=-1)[0]   # (the one group's)
        pre[2] = np.concatenate(offs)
        row = np.ascontiguousarray(self._cache.page_table[slot])
        policy = self._retry or resilience.default_policy()

        def attempt():
            chaos.maybe_fail("serving.decode.prefill")
            if self._pools_dead():
                raise MXNetError(  # not transient: stop the retry loop
                    "KV pools consumed by a failed prefill (donation); "
                    "eviction required")
            return telemetry.jit_call(
                "serving.decode_prefill_chunk", self._chunk_jit,
                self._params, jnp.asarray(pre),
                jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32),
                jnp.asarray(row), *self._cache.operands)

        tok, kp, vp = policy.call(attempt, site="serving.decode.prefill")
        self._cache.swap_pools(kp, vp)
        self._stats.on_prefill_chunk()
        return tok

    def _run_cow(self, src: int, dst: int):
        """The copy-on-write device copy (jitted, precompiled at
        warmup): the divergent/partial page's K/V duplicated into the
        writer's own page BEFORE any of its writes can land there —
        sharers never observe each other's tokens."""
        jnp = self._jnp
        kp, vp = telemetry.jit_call(
            "serving.decode_cow", self._cow_jit, *self._cache.operands,
            jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))
        self._cache.swap_pools(kp, vp)
        with self._cv:
            self._cow_copies += 1
        _T_EVENTS.inc(server=self._name, event="cow_copy")

    def _advance_prefill(self, slot: int, req: _DecodeRequest):
        """Chunked prefill: ONE chunk for ``slot``, then the tick yields
        back to decoding. Completion delivers the first token (the TTFT
        mark). A chunk failure is request-level — exactly this future
        fails (feeding the TENANT breaker), the engine keeps ticking —
        unless donation consumed the pools, which escalates to the full
        eviction like any pool death."""
        p = int(req.prompt.size)
        end = min(req.filled + self._chunk, p)
        try:
            tok = self._run_chunk(slot, req, req.filled, end, self._chunk)
        except Exception as exc:  # noqa: BLE001 - isolate to request
            self._slots[slot] = None
            self._release_slot(slot, req)
            req.tenant.on_request_failure()
            self._stats.on_error()
            self._fail(req, exc)
            if self._on_oom("serving.decode.prefill", exc) \
                    or self._pools_dead():
                self._evict([(i, r) for i, r in enumerate(self._slots)
                             if r is not None], exc)
            return
        req.filled = end
        if end >= p:
            self._finish_prefill(req, slot, tok)

    def _finish_prefill(self, req: _DecodeRequest, slot: int, tok,
                        span=None):
        """Prefill complete (monolithic, tail or final chunk): index the
        prompt's pages for future sharers, deliver the first token and
        hand the slot to the decode tick."""
        p = int(req.prompt.size)
        self._breaker.on_success()
        req.tenant.breaker.on_success()
        self._cache.seq_lens[slot] = p
        if not (self._ring_len and p >= self._ring_len) \
                and req.epoch == self._swap_epoch:
            # a swap that landed mid-prefill (between chunks) flushed
            # the index AND left this sequence's earlier pages holding
            # old-weight KV: serving the request is the documented
            # in-flight rollout semantic, but RE-INDEXING those pages
            # would hand stale KV to future prompts — skip the insert
            self._cache.insert_prefix(slot, req.prompt)
        self._prefills += 1
        _T_EVENTS.inc(server=self._name, event="prefill")
        # first token: ONE scalar fetch per admitted sequence (prefill
        # rate, not token rate — outside the decode-host-sync budget)
        fetched = fetch_host([tok])[0].reshape(-1)
        first = int(fetched[0])    # a model's counters ride behind it
        if span is not None:
            args = self._layer_args(
                fetched[1:] if self._moe_rows is not None else None, [p],
                prefill=True)
            if args:
                span.set_args(**args)
        now = time.perf_counter()
        ttft = (now - req.t_submit) * 1e3
        _tracing.event(req.trace, "first_token", ttft_ms=round(ttft, 3))
        self._stats.on_first_token(ttft)
        req.tenant.stats.on_first_token(ttft)
        req.tokens.append(first)
        req.last_t = now
        self._tokens_total += 1
        _T_TOKENS.inc(server=self._name)
        req.slot = slot
        req.prefilling = False
        if not self._chunk:
            _T_EVENTS.inc(server=self._name, event="admitted")
        if self._finished(req, first):
            self._slots[slot] = None
            self._complete(req, slot, now)
        else:
            self._slots[slot] = req

    def _run_ring_prefill(self, tokens, length, wpg, woff):
        """Long-context prefill: same model function, attention swapped
        for ring attention over the local device mesh. Runs eagerly (the
        collective path device_puts shardings jit can't trace), so it
        trades the compile-once guarantee for sequence-sharded memory —
        the documented long-context trade (docs/serving.md)."""
        import jax

        from .. import sequence_parallel

        jnp = self._jnp
        model = self._model
        n_dev = jax.local_device_count()
        groups = model.num_heads // model.num_kv_heads

        def ring_attn(q, k, v, scale):
            # (T, H, D) -> ring layout (1, H, T, D); GQA expands kv
            if groups > 1:
                k = jnp.repeat(k, groups, axis=1)
                v = jnp.repeat(v, groups, axis=1)
            out = sequence_parallel.ring_attention(
                q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
                v.transpose(1, 0, 2)[None], causal=True, scale=scale)
            return out[0].transpose(1, 0, 2)

        use_ring = n_dev > 1 and tokens.shape[0] % n_dev == 0
        last, kp, vp = model.prefill(
            self._params, jnp.asarray(tokens),
            jnp.asarray(length, jnp.int32), *self._cache.operands,
            jnp.asarray(wpg), jnp.asarray(woff),
            attn=ring_attn if use_ring else None)
        return jnp.argmax(last).astype(jnp.int32), kp, vp

    # -- the decode tick ------------------------------------------------
    def _ahead(self, req: _DecodeRequest) -> int:
        """1 while a token of ``req`` is in flight (sampled on the device,
        not fetched): with no draft in play a decoding slot commits exactly
        one token a step, so its next position is host arithmetic."""
        rec = self._inflight
        return 1 if rec is not None and req in rec.reqs else 0

    def _step_pass(self, active):
        """The decode half of a worker pass: dispatch the step for the
        ``active`` (slot, request) pairs, THEN fetch and commit the step
        the pass before left in flight — at most one step un-fetched. With
        a draft in play the tokens a slot commits are only known after the
        fetch, so the step just dispatched is retired at once: today's
        order, through the same two halves."""
        prev = self._inflight
        # (a prefill that failed this pass may have evicted them all)
        active = [(i, r) for i, r in active if self._slots[i] is r]
        if active:
            self._inflight = self._dispatch_step(active)
            if self._inflight is None:
                return      # failed: everything in flight was evicted
        else:
            self._inflight = None
        if prev is not None:
            self._retire_step(prev)     # (a failure clears _inflight)
        if self._draft is not None and self._inflight is not None:
            rec, self._inflight = self._inflight, None
            self._retire_step(rec)

    def _dispatch_step(self, active) -> Optional[_StepInFlight]:
        """Pack and launch one decode step; its device->host copy starts at
        once. Rows whose last token is still on the device read it from the
        un-fetched step's output there. Returns the step in flight, or
        None after a failure (which evicted every sequence)."""
        from .. import resilience

        jnp = self._jnp
        prev = self._inflight
        with telemetry.span("decode.pack", _SPAN_CAT):
            packed, drafts = self._pack_step(active)
        policy = self._retry or resilience.default_policy()
        fed = self._no_prev if prev is None else prev.out

        def attempt():
            chaos.maybe_fail("serving.decode")
            if self._pools_dead():
                raise MXNetError(  # not transient: stop the retry loop
                    "KV pools consumed by a failed step (donation); "
                    "eviction required")
            return telemetry.jit_call(
                "serving.decode_step", self._step, self._params,
                jnp.asarray(packed), fed, *self._cache.operands,
                self._device_page_table())

        try:
            with telemetry.span("decode.dispatch", _SPAN_CAT,
                                overlapped=int(prev is not None)):
                out, kp, vp = policy.call(attempt, site="serving.decode")
                self._cache.swap_pools(kp, vp)
                out.copy_to_host_async()
        except Exception as exc:  # noqa: BLE001 - evict, don't die
            self._on_oom("serving.decode", exc)
            self._step_failed(exc)
            return None
        if prev is not None:
            # stats() reads it from caller threads: tpulint's
            # shared-state-race wants the writer under the same lock
            with self._cv:
                self._steps_overlapped += 1
            _T_OVERLAPPED.inc(server=self._name)
        return _StepInFlight(out, active, drafts, packed[2])

    def _retire_step(self, rec: _StepInFlight):
        """Fetch a dispatched step's tokens and commit them, each under its
        span. A row whose request has left its slot since the dispatch
        (finished on the token before, evicted, timed out) is dropped."""
        try:
            with telemetry.span("decode.fetch", _SPAN_CAT):
                # the one per-token device->host sync of the plane: the
                # sampled token ids must reach the host for EOS/stop
                # checks and feedback. Inside the try: a wedged transfer
                # evicts the tick like a failed step instead of killing
                # the worker.
                toks = fetch_host([rec.out])[0]
                counters = None
                if self._moe_rows is not None:
                    n_rows = self.num_slots * self._spec_w
                    toks, counters = toks[:n_rows], toks[n_rows:]
        except Exception as exc:  # noqa: BLE001 - evict, don't die
            self._on_oom("serving.decode", exc)
            self._step_failed(exc)
            return
        with telemetry.span("decode.commit", _SPAN_CAT) as span:
            args = self._walk_args(rec.lens)
            args.update(self._layer_args(
                counters, [int(rec.lens[slot * self._spec_w])
                           for slot, _req in rec.active]))
            span.set_args(**args)
            self._commit_step(rec.active, toks, rec.drafts)

    def _step_failed(self, exc: BaseException):
        """A dispatch or a fetch failed after retries (the caller ran
        ``_on_oom`` first: a classified RESOURCE_EXHAUSTED or injected
        action=oom additionally latches the governor red and arms governed
        re-admission before the full eviction reclaims every page). The
        pool re-zero kills EVERY in-flight sequence's KV — chunked-
        prefilling slots included, not just this step's — and a step still
        in flight is forgotten with them."""
        self._breaker.on_failure()
        self._evict([(i, r) for i, r in enumerate(self._slots)
                     if r is not None], exc)

    def _pack_step(self, active):
        """The step's packed operand and the drafts by slot."""
        s = self.num_slots
        w = self._spec_w
        ps = self._cache.page_size
        # rows: tokens, positions, seq_lens, write pages, write offsets
        # (a further group's write pages), from_prev — ONE packed put per
        # tick, W = spec_k+1 query rows per slot (slot s owns rows s*W ..
        # s*W+W-1: row 0 the committed token, rows 1..k its draft guesses
        # at the next positions). W is static — draft depth, acceptance
        # and per-tenant caps vary only the data, so speculation can never
        # retrace the step. Inactive slots and unused draft rows keep
        # seq_len 0 and the null write page (rows of pages stay 0); their
        # offsets cycle the page so scatter indices stay in range.
        packed = np.zeros((self._packed_rows, s * w), np.int32)
        packed[4] = np.arange(s * w) % ps
        # the groups' page lookups, resolved once a tick: the first group's,
        # and (row of the operand, lookup) of each further group
        first_page_at, *further_pages_at = self._cache.page_lookups()
        further_pages_at = list(enumerate(further_pages_at, 5))
        drafts: dict = {}
        for slot, req in active:
            # a token in flight is one position the host has not seen:
            # the row reads it on the device (from_prev), one place on
            ahead = self._ahead(req)
            pos = int(req.prompt.size) + len(req.tokens) - 1 + ahead
            base = slot * w
            draft = (self._propose(req, slot, pos)
                     if self._draft is not None else ())
            drafts[slot] = draft
            row_toks = [0 if ahead else req.tokens[-1]]
            row_toks.extend(int(t) for t in draft)
            packed[-1, base] = ahead
            for j, row_tok in enumerate(row_toks):
                # row j carries the token at absolute position pos+j and
                # attends up to itself (per-row seq_len) — rows below it
                # in the same tick write their KV before attention reads,
                # so draft rows see each other causally. Admission's
                # worst-case reserve() plus the _propose clamp guarantee
                # pos+j is covered, so the page tables are indexed directly.
                packed[0, base + j] = row_tok
                packed[1, base + j] = pos + j
                packed[2, base + j] = pos + j + 1
                packed[3, base + j] = first_page_at(slot, pos + j)
                packed[4, base + j] = (pos + j) % ps
                for row, page_at in further_pages_at:
                    packed[row, base + j] = page_at(slot, pos + j)
        # black box: the in-flight set BEFORE the step executes, so a
        # mid-tick death's dump names the failing tick's sequences and
        # their tenants (the post-mortem acceptance contract). One event
        # per tick, one deque append — the enabled() guard keeps even
        # the reqs-list BUILD off the MXNET_TELEMETRY=0 hot path.
        if telemetry.enabled():
            _flightrec.record(
                "decode.tick", server=self._name, tick=self._ticks,
                reqs=[[req.rid, req.tenant.tenant_id,
                       "prefill" if req.prefilling else "decode"]
                      for req in self._slots if req is not None])
        return packed, drafts

    def _commit_step(self, active, toks, drafts):
        """Accept the step's tokens slot by slot, complete what finished,
        and book the tick."""
        s = self.num_slots
        w = self._spec_w
        # (under the KV audit: admission may have taken pages since the
        # dispatch, the commit itself may not)
        pages_before = self._cache.pages_in_use if self._cache.audit else 0
        self._breaker.on_success()
        now = time.perf_counter()
        tpots = []
        tenant_tpots: dict = {}
        tenant_slots: dict = {}
        tenant_spec: dict = {}
        total_new = 0
        tick_proposed = 0
        tick_accepted = 0
        for slot, req in active:
            if self._slots[slot] is not req or req.future.done():
                # gone since the dispatch (its token before this one was
                # EOS, or it was evicted, timed out, closed): the row's
                # token is dropped; its K/V write went to a page that was
                # the request's own, before any later program's
                continue
            base = slot * w
            draft = drafts.get(slot, ())
            k_eff = len(draft)
            # greedy rejection: accept the longest draft prefix that
            # equals the model's own argmax chain — committed token j is
            # the model's prediction from row j, and draft[j] rode row
            # j+1, so draft[j] was a correct guess iff it equals
            # toks[base+j]. The committed tokens are ALWAYS the model's
            # outputs (never the draft's), so output == sequential
            # greedy decode bit-for-bit whatever the draft proposed.
            a = 0
            while a < k_eff and int(draft[a]) == int(toks[base + a]):
                a += 1
            n_new = 0
            for j in range(a + 1):
                tok = int(toks[base + j])
                req.tokens.append(tok)
                n_new += 1
                if self._finished(req, tok):
                    break
            # commit = advance seq_lens past the rows that verified;
            # rejected rows' KV (positions >= the new seq_len) is the
            # ROLLBACK: never committed, masked by the ragged attention
            # bound, and overwritten by the next tick's rows — no page
            # alloc/free happened mid-tick, so there is nothing else to
            # unwind and no bystander is touched.
            self._cache.seq_lens[slot] += n_new
            accepted = min(a, n_new)
            total_new += n_new
            ms = (now - req.last_t) * 1e3
            # every decode tick the sequence participates in is a hop of
            # its (sampled) trace — the None path is one pointer check.
            # A multi-token tick amortizes the wall interval over its
            # commits so TPOT keeps meaning time-per-OUTPUT-token.
            per_tok = ms / n_new
            _tracing.event(req.trace, "tick",
                           token_index=len(req.tokens),
                           tpot_ms=round(per_tok, 3),
                           **({"drafted": k_eff, "accepted": accepted}
                              if self._spec_k else {}))
            tpots.extend([per_tok] * n_new)
            tenant_tpots.setdefault(req.tenant, []).extend(
                [per_tok] * n_new)
            tenant_slots[req.tenant] = tenant_slots.get(req.tenant, 0) + 1
            if k_eff:
                self._spec_slot_ticks += 1
                self._spec_new += n_new
                tick_proposed += k_eff
                tick_accepted += accepted
                row = tenant_spec.setdefault(req.tenant, [0, 0])
                row[0] += k_eff
                row[1] += accepted
            req.last_t = now
            if self._finished(req, int(req.tokens[-1])):
                self._slots[slot] = None
                tenant_slots[req.tenant] -= 1
                self._complete(req, slot, now)
        # per-TICK accounting, not per token: one reservoir extend + one
        # counter bump per tick keeps host bookkeeping off the token path
        # (and one per tenant that was active this tick)
        self._stats.on_output_tokens(tpots)
        for tenant, ms_batch in tenant_tpots.items():
            tenant.stats.on_output_tokens(ms_batch)
            tenant.stats.set_slots(tenant_slots.get(tenant, 0))
        if tick_proposed or tick_accepted:
            self._spec_proposed += tick_proposed
            self._spec_accepted += tick_accepted
            self._stats.on_spec(tick_proposed, tick_accepted)
            for tenant, (p_cnt, a_cnt) in tenant_spec.items():
                tenant.stats.on_spec(p_cnt, a_cnt)
        self._tokens_total += total_new
        _T_TOKENS.inc(total_new, server=self._name)
        self._ticks += 1
        self._slot_ticks += len(active)
        _T_OCCUPANCY.set(len(active) / float(s), server=self._name)
        # MXNET_KVCACHE_AUDIT: re-prove the page refcount invariant at
        # every tick boundary, not just on cache mutations — seq_lens
        # advances and slot completion both ran above without a page-map
        # change, and the audit contract is "per tick"
        if self._cache.audit:
            self._cache.audit_check()
            # the speculation-specific tick invariants: a verify tick
            # allocates NOTHING (completions above can only free), and
            # no speculating tenant stands over the page budget it was
            # admitted under — the gauge-proven form of "k+1 writes fit
            # the admission-time reservation".
            if self._cache.pages_in_use > pages_before:
                raise MXNetError(
                    "kvcache %r audit: decode tick grew pages_in_use "
                    "%d -> %d — a speculative write escaped its "
                    "admission-time reservation" %
                    (self._name, pages_before, self._cache.pages_in_use))
            if self._spec_k:
                for tenant in {req.tenant for _slot, req in active}:
                    if tenant.page_budget is not None and \
                            tenant.pages_in_use > tenant.page_budget:
                        raise MXNetError(
                            "tenant %r audit: pages_in_use %d exceeds "
                            "page_budget %d after a speculative tick"
                            % (tenant.tenant_id, tenant.pages_in_use,
                               tenant.page_budget))

    @staticmethod
    def _moe_load(held) -> float:
        """Busiest expert's rows over the mean (0.0 before any row)."""
        total = held.sum()
        return float(held.max() * held.size / total) if total else 0.0

    def _walk_args(self, row_lens) -> dict:
        """Span arguments of a decode tick's page walk, and its counters'
        bookkeeping: the table columns the paged-attention kernel ran
        (``kv_cols_live``; the host's form of
        ``ops.pallas_kernels.live_columns`` for a tick: the columns up to
        the slot's longest row, in a ring at most all of them) of the
        tables' columns x slots (``kv_cols_grid``), over the layers of
        every cache group, and the grid steps the launches took
        (``kv_cols_walked``, the extent of
        ``ops.pallas_kernels.walk_schedule``: each slot's own live columns,
        one step at the least a launch). ``row_lens``: the step operand's
        per-row lengths."""
        cols = -(-row_lens.reshape(self.num_slots, -1).max(axis=1)
                 // self._cache.page_size)
        live = grid = walked = 0
        for group, columns, layers in self._walk_groups:
            launch = sum(min(int(c), columns) for c in cols)
            n_live = layers * launch
            n_grid = layers * self.num_slots * columns
            n_walked = layers * max(launch, 1)
            _T_KV_COLS_LIVE.inc(n_live, server=self._name, group=group)
            _T_KV_COLS_GRID.inc(n_grid, server=self._name, group=group)
            _T_KV_COLS_WALKED.inc(n_walked, server=self._name, group=group)
            live += n_live
            grid += n_grid
            walked += n_walked
        with self._cv:      # stats() reads them from caller threads
            self._kv_cols_live += live
            self._kv_cols_grid += grid
            self._kv_cols_walked += walked
        return {"kv_cols_live": live, "kv_cols_grid": grid,
                "kv_cols_walked": walked,
                "kv_pool_leaves": self._kv_pool_leaves}

    def _attn_blocks(self, rung: int, tokens: int) -> dict:
        """Span arguments of a whole-prompt prefill of a model whose
        attention there is blocked (it declares ``prefill_attn_blocks``):
        the band's block pairs over the rung and over the prompt's
        ``tokens`` — host arithmetic — and their running sums."""
        blocks = getattr(self._model, "prefill_attn_blocks", None)
        if blocks is None:
            return {}
        n_rung, n_live = blocks(rung, rung), blocks(tokens, rung)
        _T_PREFILL_ATTN_BLOCKS.inc(n_rung, server=self._name, kind="rung")
        _T_PREFILL_ATTN_BLOCKS.inc(n_live, server=self._name, kind="live")
        with self._cv:      # stats() reads them from caller threads
            self._attn_blocks_rung += n_rung
            self._attn_blocks_live += n_live
        return {"attn_blocks_rung": n_rung, "attn_blocks_live": n_live}

    def _rows_computed(self, rung: int, tokens: int) -> dict:
        """Span arguments of a whole-prompt prefill of a model whose
        row-wise passes follow the prompt's length (it declares
        ``prefill_rows``): the rung's rows and those computed — host
        arithmetic — and their running sums."""
        rows = getattr(self._model, "prefill_rows", None)
        if rows is None:
            return {}
        computed = rows(tokens, rung)
        _T_PREFILL_ROWS.inc(rung, server=self._name, kind="rung")
        _T_PREFILL_ROWS.inc(computed, server=self._name, kind="computed")
        with self._cv:      # stats() reads them from caller threads
            self._rows_of_rungs += rung
            self._rows_of_blocks += computed
        return {"rows_rung": rung, "rows_computed": computed}

    def _layer_args(self, counters, live, prefill=False) -> dict:
        """Span arguments of a prefill or a decode tick beyond the page
        walk: what the cache says of its groups or its state
        (``span_args``) and what a model that declares experts counted (what
        the benchmark's per-layer readers are handed), and the counters'
        bookkeeping.
        ``counters``: the model's flat ``moe_counters`` of this program run
        (or None); ``live``: tokens each sequence of the run holds."""
        args = self._cache.span_args(live, prefill)
        if counters is not None:
            rows = np.asarray(counters, np.int64).reshape(
                self._moe_rows.shape)
            held = rows[:, :-1]
            with self._cv:      # stats() reads it from caller threads
                self._moe_rows += rows
                load = self._moe_load(self._moe_rows[:, :-1])
            n_held, n_absent = int(held.sum()), int(rows[:, -1].sum())
            _T_MOE_ROWS.inc(n_held, server=self._name, where="held")
            _T_MOE_ROWS.inc(n_absent, server=self._name, where="absent")
            _T_MOE_LOAD.set(load, server=self._name)
            args.update(moe_rows_held=n_held,
                        moe_experts_hit=int(np.count_nonzero(held)),
                        moe_load_max=int(held.max()))
        return args

    def _propose(self, req: _DecodeRequest, slot: int, pos: int):
        """Draft tokens for one slot's verify tick, clamped so the tick
        can NEVER outgrow what admission reserved:

        * the engine k (the static width bound — more would change the
          compiled shape);
        * the tenant's ``spec_k`` cap, if set (can only lower);
        * the request's remaining output budget (a tick commits at most
          k+1 tokens; committing past ``max_new`` would over-generate);
        * the slot's page reservation (every row writes KV at pos+j,
          and ``write_slots`` hard-faults past the reserved run — the
          PR-13 tenant page budget was charged for exactly that run at
          admission, so staying inside it keeps the budget invariant
          mid-tick with zero page traffic).
        """
        from .speculative import sanitize

        k = self._spec_k
        cap = req.tenant.spec_k
        if cap is not None:
            k = min(k, cap)
        k = min(k, req.max_new - len(req.tokens) - 1)
        k = min(k, self._cache.reserved_tokens(slot) - (pos + 1))
        if k <= 0:
            return ()
        history = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        try:
            proposed = self._draft.propose(history, k)
        except Exception:  # noqa: BLE001 - a draft bug must not kill ticks
            # drafts are hints: a failing proposer degrades this slot to
            # the classic single-token tick instead of faulting the tick
            # (which would evict every in-flight sequence)
            return ()
        return sanitize(proposed, k, self._model.vocab_size)

    def set_tenant_spec_k(self, tenant_id: str, spec_k: Optional[int]):
        """Set (or clear, with ``None``) one tenant's speculative draft
        cap at runtime. Caps only LOWER the engine's ``spec_k`` — the
        verify width K+1 is a compile-time shape — so a slow-accepting
        tenant can be throttled to 0 without touching anyone's compiled
        step. The fleet router forwards this to every replica."""
        tenant = self._tenants.resolve(tenant_id)
        tenant.spec_k = None if spec_k is None else max(0, int(spec_k))

    @staticmethod
    def _finished(req: _DecodeRequest, tok: int) -> bool:
        return (len(req.tokens) >= req.max_new
                or (req.eos_id is not None and tok == req.eos_id))

    def _release_slot(self, slot: int, req: _DecodeRequest):
        """Free a slot's page mappings AND return its EXCLUSIVE pages to
        the owning tenant's budget — shared prefix pages were never
        charged to it (they belong to the ``shared`` pseudo-tenant) and
        live on for other sharers / the prefix index. Idempotent (a slot
        already freed owns 0 pages), so the close()/worker race can
        double-call it harmlessly."""
        freed = self._cache.exclusive_pages(slot)
        self._cache.free(slot)
        req.tenant.release_pages(freed)

    def _complete(self, req: _DecodeRequest, slot: int, now: float):
        self._release_slot(slot, req)
        _T_EVENTS.inc(server=self._name, event="completed")
        _tracing.finish(req.trace, "complete", tokens=len(req.tokens),
                        latency_ms=round((now - req.t_submit) * 1e3, 3))
        if req.future.done():
            # close(drain=False) raced the in-flight tick and already
            # failed this future; completing it now would raise
            return
        if req.future.set_running_or_notify_cancel():
            req.future.set_result(np.asarray(req.tokens, np.int32))
            lat = (now - req.t_submit) * 1e3
            self._stats.on_complete(lat)
            req.tenant.stats.on_complete(lat)

    def _evict(self, active, exc: BaseException):
        """A decode step failed after retries: only the sequences in
        flight are affected — fail exactly their futures, reset their
        slots and re-zero the pools (donation may have consumed the old
        buffers mid-failure), and keep serving new traffic. This is a
        TICK-level fault: it feeds the engine breaker (the caller), not
        the tenants' — the victims were bystanders of an engine failure,
        not misbehaving traffic."""
        # a step still un-fetched computed on the pools that go below: its
        # rows' requests fail here, its tokens are nobody's
        self._inflight = None
        _flightrec.record(
            "decode.evict", server=self._name, error=repr(exc),
            reqs=[[req.rid, req.tenant.tenant_id]
                  for _slot, req in active])
        for slot, req in active:
            self._slots[slot] = None
            self._release_slot(slot, req)
            self._stats.on_error()
            req.tenant.stats.on_error()
            self._evictions += 1
            _T_EVENTS.inc(server=self._name, event="evicted")
            _tracing.finish(req.trace, "evict",
                            tokens=len(req.tokens), error=repr(exc))
            self._fail(req, exc)
        self._cache.reset_pools()

    @staticmethod
    def _fail(req: _DecodeRequest, exc: BaseException):
        # generic terminal fallback: paths with a more specific verdict
        # (evict/timeout/shed) finish the trace first and this no-ops
        _tracing.finish(req.trace, "error", error=type(exc).__name__)
        if req.future.done():
            return
        if req.future.set_running_or_notify_cancel():
            req.future.set_exception(exc)


# ---------------------------------------------------------------------------
# Reference model: a tiny pre-norm transformer over the paged cache
# ---------------------------------------------------------------------------

class TinyDecoder(PagedDecodeModel):
    """Small causal transformer implementing the paged-decode contract.

    The reference workload of the decode plane (bench soak + tests) and
    the template for wiring a real model: per layer — RMSNorm, QKV
    projections, :func:`~mxnet_tpu.serving.kvcache.write_kv` of the new
    K/V rows, :func:`~mxnet_tpu.ops.pallas_kernels.paged_attention` over
    the page table, output projection, RMSNorm + ReLU MLP; weights ride
    a plain dict pytree. ``embed_dim == num_heads * head_dim``;
    positions are sinusoidal (no learned table to size).
    """

    def __init__(self, vocab_size=128, num_layers=2, num_heads=4,
                 head_dim=16, num_kv_heads=None, mlp_ratio=2):
        if num_kv_heads is None:
            num_kv_heads = num_heads
        if num_heads % num_kv_heads:
            raise MXNetError("num_heads %d %% num_kv_heads %d != 0"
                             % (num_heads, num_kv_heads))
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.embed_dim = self.num_heads * self.head_dim
        self.mlp_dim = self.embed_dim * int(mlp_ratio)
        self.scale = 1.0 / float(self.head_dim) ** 0.5
        self._reference_jit = None  # built on first reference_generate

    # -- params ---------------------------------------------------------
    def init_params(self, seed: int = 0):
        import jax.numpy as jnp

        rng = np.random.RandomState(seed)
        e, v, h, kh, d, m = (self.embed_dim, self.vocab_size,
                             self.num_heads, self.num_kv_heads,
                             self.head_dim, self.mlp_dim)

        def w(*shape):
            return jnp.asarray(rng.randn(*shape).astype(np.float32)
                               * (1.0 / np.sqrt(shape[0])))

        layers = []
        for _ in range(self.num_layers):
            layers.append({
                "ln1": jnp.ones((e,), jnp.float32),
                "wq": w(e, h * d), "wk": w(e, kh * d), "wv": w(e, kh * d),
                "wo": w(h * d, e),
                "ln2": jnp.ones((e,), jnp.float32),
                "w1": w(e, m), "w2": w(m, e),
            })
        return {"embed": w(v, e), "layers": layers,
                "lnf": jnp.ones((e,), jnp.float32), "unembed": w(e, v)}

    # -- shared pieces --------------------------------------------------
    @staticmethod
    def _norm(x, scale):
        import jax.numpy as jnp

        return x * scale / jnp.sqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def _pe(self, positions):
        import jax.numpy as jnp

        e = self.embed_dim
        half = e // 2
        freq = 1.0 / (10000.0 ** (jnp.arange(half) / float(half)))
        ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

    def _dense_causal(self, q, k, v, scale):
        """(T, H, D) causal attention oracle: the prefill in-graph path
        and the no-cache reference."""
        import jax
        import jax.numpy as jnp

        groups = self.num_heads // self.num_kv_heads
        if groups > 1:
            k = jnp.repeat(k, groups, axis=1)
            v = jnp.repeat(v, groups, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        t = q.shape[0]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    def _mlp(self, x, layer):
        import jax

        return jax.nn.relu(x @ layer["w1"]) @ layer["w2"]

    def _forward(self, params, tokens, positions, pools, state,
                 write_pages, write_offsets, attend):
        """The layers over ``tokens`` rows, each piece under its part of the
        program (``telemetry.PROGRAM_PARTS``); ``attend(li, q, k, v, k_pool,
        v_pool)`` is the one thing prefill, chunk and decode do
        differently. Returns the logits of every row and the two operands
        (``pools``: its one group's ``(k layers, v layers)``; ``state``: as
        handed, ``()``)."""
        import jax

        part = jax.named_scope
        k_pool, v_pool = pools
        n = tokens.shape[0]
        h, kh, d = self.num_heads, self.num_kv_heads, self.head_dim
        with part("mx_embed"):
            x = params["embed"][tokens] + self._pe(positions)
        for li, layer in enumerate(params["layers"]):
            with part("mx_qkv"):
                hx = self._norm(x, layer["ln1"])
                q = (hx @ layer["wq"]).reshape(n, h, d)
                k = (hx @ layer["wk"]).reshape(n, kh, d)
                v = (hx @ layer["wv"]).reshape(n, kh, d)
            # scatter FIRST so a chunk's positions read their own K/V back
            # through the pages like every earlier chunk's (already-cached
            # positions write to the null page — their KV is in the
            # shared/CoW pages, that pass only recomputes activations)
            with part("mx_kv_write"):
                k_pool, v_pool = write_kv(k_pool, v_pool, li, k, v,
                                          write_pages, write_offsets)
            with part("mx_attn"):
                att = attend(li, q, k, v, k_pool, v_pool)
            with part("mx_attn_out"):
                x = x + att.reshape(n, h * d) @ layer["wo"]
            with part("mx_mlp"):
                x = x + self._mlp(self._norm(x, layer["ln2"]), layer)
        with part("mx_head"):
            logits = self._norm(x, params["lnf"]) @ params["unembed"]
        return logits, (k_pool, v_pool), state

    # -- contract -------------------------------------------------------
    def prefill(self, params, tokens, length, pools, state,
                write_pages, write_offsets, attn=None):
        import jax.numpy as jnp

        attn = attn or self._dense_causal
        logits, pools, state = self._forward(
            params, tokens, jnp.arange(tokens.shape[0], dtype=jnp.int32),
            pools, state, write_pages, write_offsets,
            lambda li, q, k, v, kp, vp: attn(q, k, v, self.scale))
        return logits[length - 1], pools, state

    def prefill_chunk(self, params, tokens, start, length, pools, state,
                      page_table_row, write_pages, write_offsets):
        import jax
        import jax.numpy as jnp

        from ..ops import pallas_kernels

        with jax.named_scope("mx_embed"):
            positions = start.astype(jnp.int32) \
                + jnp.arange(tokens.shape[0], dtype=jnp.int32)
        logits, pools, state = self._forward(
            params, tokens, positions, pools, state, write_pages,
            write_offsets,
            lambda li, q, k, v, kp, vp:
            pallas_kernels.paged_prefill_attention(
                q, kp[li], vp[li], page_table_row, start, length,
                scale=self.scale))
        return logits[length - 1], pools, state

    def decode(self, params, tokens, positions, pools, state,
               page_tables, seq_lens, write_pages, write_offsets):
        from ..ops import pallas_kernels

        # the per-slot query width (1 = classic tick, K+1 = speculative
        # verify tick) falls out of trace-time shapes — the contract's
        # operands widen, the signature doesn't
        w = tokens.shape[0] // page_tables.shape[0]
        paged = pallas_kernels.paged_spec_attention if w > 1 \
            else pallas_kernels.paged_attention
        return self._forward(
            params, tokens, positions, pools, state, write_pages,
            write_offsets,
            lambda li, q, k, v, kp, vp: paged(
                q, kp[li], vp[li], page_tables, seq_lens, scale=self.scale))

    # -- oracle ---------------------------------------------------------
    def _reference_next(self, params, arr):
        """Greedy next token of the dense no-cache forward over ``arr``."""
        import jax.numpy as jnp

        t = arr.shape[0]
        h, kh, d = self.num_heads, self.num_kv_heads, self.head_dim
        positions = jnp.arange(t, dtype=jnp.int32)
        x = params["embed"][arr] + self._pe(positions)
        for layer in params["layers"]:
            hx = self._norm(x, layer["ln1"])
            q = (hx @ layer["wq"]).reshape(t, h, d)
            k = (hx @ layer["wk"]).reshape(t, kh, d)
            v = (hx @ layer["wv"]).reshape(t, kh, d)
            att = self._dense_causal(q, k, v, self.scale)
            x = x + att.reshape(t, h * d) @ layer["wo"]
            x = x + self._mlp(self._norm(x, layer["ln2"]), layer)
        logits = self._norm(x, params["lnf"]) @ params["unembed"]
        return jnp.argmax(logits[-1])

    def reference_generate(self, params, prompt, max_new_tokens,
                           eos_id=None):
        """No-cache greedy decode: re-runs the full dense forward per
        token. O(T^2) per token — the correctness oracle the engine's
        paged path is tested against, never a serving path. The forward
        is ONE jitted program per sequence length: run op by op it cost a
        set of per-op compiles for every new length, 24 minutes for 100
        tokens on a v5e chip (PR 22)."""
        import jax
        import jax.numpy as jnp

        if self._reference_jit is None:
            self._reference_jit = jax.jit(self._reference_next)
        toks = [int(t) for t in np.asarray(prompt).ravel()]
        out: List[int] = []
        for _ in range(int(max_new_tokens)):
            arr = jnp.asarray(np.asarray(toks, np.int32))
            # the batched-fetch idiom even for one value: the transfer is
            # explicit, and greedy decode is inherently per-token (the
            # fetched token IS the next input)
            nxt = int(fetch_host([self._reference_jit(params, arr)])[0])  # tpulint: disable=decode-host-sync,unattributed-dispatch -- correctness oracle, never a serving path: per-token fetch is the point, and it stays off the chaos/attribution plane
            out.append(nxt)
            toks.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        return np.asarray(out, np.int32)
