"""Paged KV cache: static device pools + a host-side page allocator.

The HBM discipline of autoregressive decode. A contiguous per-sequence KV
buffer must be sized for the longest sequence it might ever hold, so a
batch of mixed lengths strands most of its HBM in padding; and growing a
buffer changes its shape, which retraces. Paging fixes both at once
(Ragged Paged Attention, PAPERS.md): KV lives in ONE statically-shaped
pool of fixed-size pages per layer, a sequence owns whatever pages it
needs right now through a page table, and the ragged attention kernel
(:func:`mxnet_tpu.ops.pallas_kernels.paged_attention`) reads through the
table — so allocation is a host-side free-list operation that never
touches a device shape. Nothing recompiles as sequences come, grow and
go.

What a model's layers keep between tokens is decided HERE and nowhere else:
the model declares it (``layer_state``, :func:`layer_states`),
:func:`make_cache` composes one :class:`DecodeCache` from the declaration —
a group of pools with its allocator (:class:`PagedKVCache`, a sliding
window's :class:`RingKVCache`) a distinct paged kind, the per-slot state of
the layers that are not paged — and the decode engine asks that cache for
everything, whatever it is made of (docs/serving.md, "What a layer keeps
between tokens").

Split of responsibilities inside a group:

* **host side (:class:`PagedKVCache`)** — the free list, per-page
  refcounts, the per-slot page tables and lengths (numpy, static shapes),
  admission
  accounting, the prefix index, and the ``mxnet_kvcache_*`` gauges;
* **device side (pure helpers)** — :func:`write_kv` scatters one step's
  new K/V rows into the pools at host-computed (page, offset) slots;
  traced inside the decode/prefill jit, static shapes throughout.

Page 0 is reserved as the *null page*: page-table padding and inactive
decode slots point at it (the BlockSpec index map must always name a
real page), and masked reads/garbage writes land there harmlessly. The
allocator never hands it out.

Prefix caching (``prefix_cache=True``; the engine knob is
``MXNET_DECODE_PREFIX_CACHE``): pages are REFCOUNTED — a page may be
mapped into several slots' tables at once, and it returns to the free
list only when its last reference drops AND it is not held by the prefix
index. The index keys each *full* page of a prompt by the rolling hash
of its whole token prefix (``key_i = sha1(key_{i-1} || tokens_i)``), so
a lookup that walks the chain and token-verifies every chunk can map a
shared system prompt's pages directly into a new slot — prefilled once
per fleet, not once per request. The first *divergent or partial* page
is shared **copy-on-write**: the matching page is copied into a fresh
page owned by the new sequence (the engine runs the device copy), and
only then written — sharers never observe each other's writes. Pages
whose last slot reference drops but that remain indexed move to a
**cached LRU**: they cost nothing (``pages_in_use`` excludes them), stay
warm for the next hit, and are reclaimed oldest-first the moment a
reservation needs them — eviction never touches a page a live sequence
references.

Knobs (``docs/env_var.md``): ``MXNET_KVCACHE_PAGE_SIZE`` (default 16
tokens/page), ``MXNET_KVCACHE_PAGES`` (0 = auto-size to the slot count x
max sequence length, + the null page).
"""
from __future__ import annotations

import collections
import hashlib
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import telemetry
from ..base import MXNetError, get_env

__all__ = ["PagedKVCache", "RingKVCache", "DecodeCache", "OutOfPagesError",
           "PrefixMatch", "write_kv", "write_rows", "layer_states",
           "place_layers", "make_cache"]

_DEFAULT_PAGE_SIZE = 16

_T_PAGES = telemetry.gauge(
    "mxnet_kvcache_pages_in_use",
    "KV cache pages currently allocated to live sequences",
    labels=("cache",))
_T_CAPACITY = telemetry.gauge(
    "mxnet_kvcache_pages_capacity",
    "allocatable KV cache pages in the pool (excludes the null page)",
    labels=("cache",))
_T_SHARED = telemetry.gauge(
    "mxnet_kvcache_shared_pages",
    "KV pages currently mapped by more than one live sequence "
    "(refcount > 1: the prefix-sharing win, charged to no single tenant)",
    labels=("cache",))
_T_CACHED = telemetry.gauge(
    "mxnet_kvcache_cached_pages",
    "KV pages held only by the prefix index (refcount 0, reclaimable "
    "on demand — warm capacity, not live usage)",
    labels=("cache",))
_T_PREFIX_HITS = telemetry.counter(
    "mxnet_kvcache_prefix_hits_total",
    "admissions that mapped at least one cached prefix page/token",
    labels=("cache",))
_T_PREFIX_MISSES = telemetry.counter(
    "mxnet_kvcache_prefix_misses_total",
    "admissions that found no cached prefix",
    labels=("cache",))
_T_PRESSURE_SHEDS = telemetry.counter(
    "mxnet_kvcache_pressure_sheds_total",
    "cached-LRU (refcount-0) prefix pages proactively returned to the "
    "free list by the HBM pressure governor's yellow-tier ladder rung "
    "(shed_cached) — warm capacity traded for headroom",
    labels=("cache",))


_T_STATE = telemetry.counter(
    "mxnet_decode_state_total",
    "a model with slot-state and latent layers, summed over decode ticks "
    "and prefills: what=slots_live the rows whose recurrence ran, "
    "what=bytes_moved the state bytes it read and wrote (a tick: every live "
    "slot's state once each way; a prefill: its slot's state written), "
    "what=latent_rows_read the latent rows the attention read",
    labels=("server", "what"))
_T_STATE_BYTES = telemetry.gauge(
    "mxnet_decode_state_bytes",
    "bytes of the per-slot state of the model's slot-state layers, every "
    "slot (fixed at construction: it is not paged)",
    labels=("server",))
_T_GROUP_PAGES = telemetry.gauge(
    "mxnet_kvcache_group_pages_in_use",
    "KV pages in use in a further group of layers of a cache whose model "
    "declares layers of several paged kinds (the first group keeps "
    "mxnet_kvcache_pages_in_use)",
    labels=("cache", "group"))
_T_GROUP_CAPACITY = telemetry.gauge(
    "mxnet_kvcache_group_pages_capacity",
    "allocatable KV pages of a further group of layers (excludes its "
    "null page)",
    labels=("cache", "group"))


class OutOfPagesError(MXNetError):
    """The free list (plus every reclaimable cached page) cannot cover
    the requested reservation; the caller (the decode engine's admission
    loop) defers the sequence instead of growing the pool — static
    shapes are the contract."""


def _page_size(page_size: Optional[int]) -> int:
    """Tokens a page (``None``: ``MXNET_KVCACHE_PAGE_SIZE``)."""
    if page_size is None:
        page_size = get_env("MXNET_KVCACHE_PAGE_SIZE", _DEFAULT_PAGE_SIZE,
                            int, cache=False)
    return max(1, int(page_size))


def write_rows(pool, layer: int, new, pages, offsets):
    """Scatter one batch of new rows into ONE pool of a layer: ``pool`` a
    sequence of per-layer ``(P, page_size, KH, Dw)`` arrays (a latent pool:
    ``(P, page_size, Dw)``), ``new`` ``(N, KH, D)`` (``(N, D)``) rows,
    zero-padded here to the width ``Dw >= D`` the pool holds its rows at
    (:func:`pool_row_width`). Returns the pool as a tuple with element
    ``layer`` replaced — every other layer's array is the SAME object, so a
    step donates and returns it untouched."""
    import jax.numpy as jnp

    pool = tuple(pool)
    pad = pool[layer].shape[-1] - new.shape[-1]
    if pad:
        new = jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, pad),))
    return pool[:layer] + (pool[layer].at[pages, offsets].set(new),) \
        + pool[layer + 1:]


def write_kv(k_pool, v_pool, layer: int, k_new, v_new, pages, offsets):
    """Scatter one batch of new K/V rows into the layer's pool pages.

    k_pool/v_pool: sequences of per-layer ``(P, page_size, KH, Dw)`` device
    pools (traced); k_new/v_new: (N, KH, D) rows (:func:`write_rows` pads
    them to the pools' width);
    pages/offsets: (N,) int32 destinations (host-computed by
    :meth:`PagedKVCache.write_slots`). Returns the pools as tuples with
    element ``layer`` replaced. Pure — trace it
    inside the step jit; every shape is static, so membership churn never
    recompiles. Rows whose destination is the null page (inactive slots,
    prompt padding) overwrite garbage with garbage by design.
    """
    return write_rows(k_pool, layer, k_new, pages, offsets), \
        write_rows(v_pool, layer, v_new, pages, offsets)


def pool_row_width(shape, dtype, device) -> int:
    """The width a ``(P, page_size, KH, head_dim)`` pool's rows (a latent
    pool's: ``(P, page_size, width)``) are HELD at on ``device``: ``head_dim`` where the device's own default layout for
    that shape is row-major (every CPU array; a TPU pool whose head_dim
    fills the 128 lanes), else ``head_dim`` rounded up to the lanes of the
    device's tile.

    The paged kernel reads a pool row-major — a page's rows contiguous. A
    TPU lays an array with a minor dimension under 128 out otherwise by
    default (the PAGE axis on the lanes, since head_dim 64 would pad to
    128), and a program handed such a pool converts the whole of it on the
    way in and again on the way out, every tick. A pool whose rows ARE a
    lane tile wide is row-major by default, byte for byte the row-major
    layout of the narrow one (the pad the tile would add, made of zeros the
    products ignore), and needs no layout of its own. (A layout pinned with
    ``jax.experimental.layout`` does not survive the persistent compile
    cache: PERF.md, PR 30.)
    """
    from jax.experimental.layout import Layout

    def default(dims):
        return Layout.from_pjrt_layout(device.client.get_default_layout(
            np.dtype(dtype), tuple(dims), device))

    row_major = tuple(range(len(shape)))
    layout = default(shape)
    if tuple(layout.major_to_minor) == row_major:
        return int(shape[-1])
    lanes = int(layout.tiling[0][-1])
    width = -(-int(shape[-1]) // lanes) * lanes
    if tuple(default(tuple(shape[:-1]) + (width,)).major_to_minor) \
            != row_major:
        raise MXNetError(
            "kvcache: %s holds no %s pool of shape %s row-major, at rows of "
            "%d either" % (device, np.dtype(dtype), tuple(shape), width))
    return width


class _PrefixEntry:
    """One indexed page: the chain key it answers to, its parent key,
    the page id, and the VALID token run stored in it (``page_size``
    tokens for a full page, fewer for a partial — positions beyond
    ``len(tokens)`` hold other sequences' writes and are masked by
    ``seq_lens``, never trusted)."""

    __slots__ = ("key", "parent", "page", "tokens", "full")

    def __init__(self, key: Optional[bytes], parent: bytes, page: int,
                 tokens: np.ndarray, full: bool):
        self.key = key
        self.parent = parent
        self.page = int(page)
        self.tokens = tokens
        self.full = full


class PrefixMatch:
    """Result of :meth:`PagedKVCache.match_prefix`: the run of full
    pages whose whole token prefix matched, plus (optionally) the first
    divergent/partial page and how many of its leading tokens match —
    that page is shared copy-on-write."""

    __slots__ = ("full", "partial", "partial_len", "matched")

    def __init__(self, full: List[_PrefixEntry],
                 partial: Optional[_PrefixEntry], partial_len: int,
                 matched: int):
        self.full = full
        self.partial = partial
        self.partial_len = int(partial_len)
        self.matched = int(matched)


def _chain_key(parent: bytes, chunk: np.ndarray) -> bytes:
    return hashlib.sha1(parent + chunk.astype("<i4").tobytes()).digest()


def _common_prefix_len(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.size, b.size)
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


class PagedKVCache:
    """Fixed-size paged KV pools for ``num_slots`` concurrent sequences: one
    GROUP of a model's layers (:func:`make_cache` composes a
    :class:`DecodeCache` of one such group a paged kind).

    Device state: ``pools``, ``(k layers, v layers)``, each a tuple of
    ``num_layers`` arrays ``(num_pages, page_size, num_kv_heads, row
    width)`` — ONE ARRAY A LAYER, the operand the layer's scatter writes and
    its kernel reads, its rows as wide as the device holds row-major
    (:func:`pool_row_width`: ``head_dim``, or the lane tile above it),
    allocated once and shape-stable for the cache's lifetime. With
    ``num_kv_heads=None`` the layers are LATENT: ``pools`` is one tuple of
    ``(num_pages, page_size, row width)`` arrays, no head axis (a TPU pads
    one head to a sublane tile and the step then converts the pool on its
    way into the kernel, every tick) and NO V pool — a token's row is key
    and value at once. The decode engine threads the pools through its
    jitted step (functional update, donated) and stores the returned arrays
    back (:meth:`DecodeCache.swap_pools`).

    Host state per slot: a fixed-width page-table row (``max_pages``
    entries, unused entries = the null page 0) and a token count. The
    free list is LIFO — a page freed by one sequence is the next page
    another acquires, which the reuse regression test pins. With
    ``prefix_cache=True`` pages carry refcounts, slots may map shared
    read-only pages (charged to no single slot's *exclusive* count), and
    freed-but-indexed pages park in a reclaimable cached-LRU instead of
    the free list.
    """

    #: the name its page table's walk is counted under
    group = "full"

    def __init__(self, num_slots: int, max_seq_len: int, num_layers: int,
                 num_kv_heads: Optional[int], head_dim: int,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, dtype="float32",
                 name: str = "decode", prefix_cache: bool = False):
        import jax.numpy as jnp

        from ..base import np_dtype

        if num_pages is None:
            num_pages = get_env("MXNET_KVCACHE_PAGES", 0, int, cache=False)
        self.page_size = _page_size(page_size)
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len)
        self.max_pages = -(-self.max_seq_len // self.page_size)
        if not num_pages:
            # worst case: every slot holds a max-length sequence; +1 null
            num_pages = self.num_slots * self.max_pages + 1
        if num_pages < 2:
            raise MXNetError("kvcache needs >= 2 pages (null + 1), got %d"
                             % num_pages)
        self.num_pages = int(num_pages)
        self.name = name
        self.num_layers = int(num_layers)
        if num_kv_heads is None:
            self.group = "latent"
            shape = (self.num_pages, self.page_size, int(head_dim))
        else:
            shape = (self.num_pages, self.page_size, int(num_kv_heads),
                     int(head_dim))
        self._pool_dtype = np_dtype(dtype)
        (device,) = jnp.zeros((), self._pool_dtype).devices()
        #: one layer's pool: rows as wide as ``device`` holds row-major
        self._pool_shape = shape[:-1] + (
            pool_row_width(shape, self._pool_dtype, device),)
        self._zero_pools()
        # LIFO free list over pages 1..P-1; page 0 is the null page
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self.page_table = np.zeros((self.num_slots, self.max_pages),
                                   np.int32)
        self.seq_lens = np.zeros((self.num_slots,), np.int32)
        self._owned = [0] * self.num_slots      # pages mapped per slot
        self._exclusive = [0] * self.num_slots  # un-shared pages per slot
        # per-page slot-mapping refcount; a page is live while > 0
        self._ref = np.zeros((self.num_pages,), np.int32)
        # prefix index: chain-key -> full-page entry, parent-key -> the
        # child entries hanging off it (full AND partial — the divergent-
        # page CoW candidates), page -> its entry, and the cached-LRU of
        # refcount-0 indexed pages (reclaim oldest-first)
        self.prefix_cache = bool(prefix_cache)
        self._index: Dict[bytes, _PrefixEntry] = {}
        self._children: Dict[bytes, List[_PrefixEntry]] = {}
        self._page_entry: Dict[int, _PrefixEntry] = {}
        self._cached: "collections.OrderedDict[int, _PrefixEntry]" = \
            collections.OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_matched = 0
        self.pressure_sheds = 0
        # bumped on every table mutation (reserve/free): the decode
        # engine keys its cached DEVICE copy of the page table on it, so
        # steady decode ticks skip the host->device put entirely
        self.version = 0
        # MXNET_KVCACHE_AUDIT=1: every mutation (and every engine tick)
        # re-proves the refcount invariant — the runtime twin of the
        # static resource-lifecycle pass
        self.audit = bool(get_env("MXNET_KVCACHE_AUDIT", 0, int,
                                  cache=False))
        self._publish()

    # -- accounting --------------------------------------------------------
    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_cached(self) -> int:
        """Refcount-0 pages parked in the prefix index — reclaimable."""
        return len(self._cached)

    @property
    def pages_available(self) -> int:
        """Pages a reservation can draw on right now: the free list plus
        every reclaimable cached page."""
        return len(self._free) + len(self._cached)

    @property
    def pages_in_use(self) -> int:
        """Pages mapped by at least one live sequence (cached-LRU pages
        are warm capacity, not usage — they reclaim on demand)."""
        return self.num_pages - 1 - len(self._free) - len(self._cached)

    @property
    def shared_pages(self) -> int:
        """Pages currently mapped by more than one live sequence — the
        refcount>1 set the ``shared`` pseudo-tenant answers for."""
        return int(np.count_nonzero(self._ref > 1))

    def pages_for(self, n_tokens: int) -> int:
        """Pages a sequence of ``n_tokens`` occupies."""
        return -(-int(n_tokens) // self.page_size)

    def pages_owned(self, slot: int) -> int:
        """Pages currently mapped by ``slot`` (0 after :meth:`free`),
        shared mappings included."""
        return self._owned[int(slot)]

    def exclusive_pages(self, slot: int) -> int:
        """Pages ``slot`` owns EXCLUSIVELY (fresh reservations + its CoW
        copies) — the count charged to the owning tenant's page budget;
        shared prefix pages belong to the ``shared`` pseudo-tenant and
        charge nobody twice."""
        return self._exclusive[int(slot)]

    def reserved_tokens(self, slot: int) -> int:
        """Token capacity of ``slot``'s reserved page run — the hard
        ceiling :meth:`write_slots` enforces. The speculative decode
        step clamps its per-tick draft depth so that all k+1 verify
        rows land below this bound: admission reserved the worst case
        (prompt + max_new) up front, so a speculating sequence can
        never grow pages mid-tick and never exceeds the tenant page
        budget it was charged at admission."""
        return self._owned[int(slot)] * self.page_size

    def can_admit(self, n_tokens: int) -> bool:
        """Whether a full reservation for ``n_tokens`` fits right now."""
        return self.pages_for(n_tokens) <= self.pages_available

    def can_admit_prefix(self, n_tokens: int,
                         match: Optional[PrefixMatch]) -> bool:
        """Whether ``n_tokens`` fits given a prefix ``match``: matched
        full pages are mapped (not allocated), the CoW page and the tail
        come from the free list / reclaimable cached pages — minus the
        match's own pages, which must not be reclaimed out from under
        the mapping."""
        need = self.pages_for(n_tokens)
        pinned = 0
        if match is not None:
            need -= len(match.full)
            for e in match.full:
                if e.page in self._cached:
                    pinned += 1
            if match.partial is not None and \
                    match.partial.page in self._cached:
                pinned += 1
        return need <= self.pages_available - pinned

    # -- allocation --------------------------------------------------------
    def _take_page(self, pin=()) -> int:
        """One page off the free list, or — when it's dry — reclaimed
        from the oldest cached (refcount-0, indexed) page not in
        ``pin``. Raises :class:`OutOfPagesError` when neither has one."""
        if self._free:
            return self._free.pop()
        for page in self._cached:
            if page in pin:
                continue
            entry = self._cached.pop(page)
            self._index_remove(entry)
            return page
        raise OutOfPagesError(
            "kvcache %r: pool exhausted (%d pages, 0 free, %d cached all "
            "pinned)" % (self.name, self.num_pages - 1, len(self._cached)))

    def reserve(self, slot: int, n_tokens: int, _pin=()) -> None:
        """Grow ``slot``'s page run to cover ``n_tokens`` total tokens.

        The decode engine reserves a sequence's WORST CASE (prompt +
        max_new_tokens) at admission, so a sequence admitted can always
        finish — no mid-flight eviction for lack of pages. That same
        admission-time worst case also bounds speculative decoding: a
        tick that writes up to k+1 tokens still lands every row at a
        position < prompt + max_new, i.e. inside this reservation (the
        engine clamps the draft depth by :meth:`reserved_tokens`), so
        pages-per-tick growth is ZERO after admission and a tenant's
        page budget can't be exceeded mid-tick. Shared pages
        already mapped by :meth:`admit_prefix` count toward the cover,
        so only the non-shared tail is allocated. Raises
        :class:`OutOfPagesError` (leaving the slot unchanged) when the
        free list plus reclaimable cached pages can't cover it.
        """
        if n_tokens > self.max_seq_len:
            raise MXNetError(
                "sequence of %d tokens exceeds max_seq_len %d"
                % (n_tokens, self.max_seq_len))
        need = self.pages_for(n_tokens) - self._owned[slot]
        if need <= 0:
            return
        usable = self.pages_available - sum(1 for p in _pin
                                            if p in self._cached)
        if need > usable:
            raise OutOfPagesError(
                "kvcache %r: need %d pages, %d free + %d cached (pool %d)"
                % (self.name, need, len(self._free), len(self._cached),
                   self.num_pages - 1))
        for _ in range(need):
            page = self._take_page(pin=_pin)
            self.page_table[slot, self._owned[slot]] = page
            self._owned[slot] += 1
            self._exclusive[slot] += 1
            self._ref[page] = 1
        self.version += 1
        self._publish()

    def free(self, slot: int) -> None:
        """Drop every page mapping ``slot`` holds and reset its table
        row to the null page. A page returns to the free list only when
        its LAST reference drops — other sequences sharing it are
        untouched; an indexed page with no references parks in the
        cached-LRU instead (warm for the next prefix hit, reclaimed on
        demand). Idempotent."""
        for i in range(self._owned[slot]):
            page = int(self.page_table[slot, i])
            if page == 0 or self._ref[page] <= 0:
                # double-free: this mapping's page already dropped its
                # last reference. Decref once only — decrementing past
                # zero used to clamp AND re-append the page, planting a
                # duplicate free-list entry that hands one page to two
                # slots (silent KV corruption). Audit mode makes the
                # re-entrant release loud instead of absorbing it.
                if self.audit:
                    raise MXNetError(
                        "kvcache %r audit: double-free of page %d via "
                        "slot %d (refcount already 0) — a release path "
                        "ran twice over one mapping" % (self.name, page,
                                                        slot))
                continue
            self._ref[page] -= 1
            if self._ref[page] == 0:
                entry = self._page_entry.get(page)
                if entry is not None:
                    self._cached[page] = entry
                else:
                    self._free.append(page)
        self.page_table[slot, :] = 0
        self.seq_lens[slot] = 0
        self._owned[slot] = 0
        self._exclusive[slot] = 0
        self.version += 1
        self._publish()

    # -- prefix index ------------------------------------------------------
    def match_prefix(self, prompt) -> Optional[PrefixMatch]:
        """Walk the index for ``prompt``: the longest run of full pages
        whose rolling-hash chain matches (every chunk token-verified, so
        a hit is exact by construction, not by hash luck), then the best
        divergent/partial child of the last matched key — the CoW page.
        Read-only; returns None when nothing matched (or the index is
        disabled)."""
        if not self.prefix_cache:
            return None
        prompt = np.asarray(prompt, np.int32).ravel()
        ps = self.page_size
        p = int(prompt.size)
        full: List[_PrefixEntry] = []
        parent = b""
        for i in range(p // ps):
            chunk = prompt[i * ps:(i + 1) * ps]
            key = _chain_key(parent, chunk)
            e = self._index.get(key)
            if e is None or not np.array_equal(e.tokens, chunk):
                break
            full.append(e)
            parent = key
        matched = len(full) * ps
        nxt = prompt[matched:matched + ps]
        best, best_n = None, 0
        if nxt.size:
            for e in self._children.get(parent, ()):
                n = _common_prefix_len(e.tokens, nxt)
                if n > best_n:
                    best, best_n = e, n
        matched += best_n
        if matched == 0:
            return None
        return PrefixMatch(full, best, best_n, matched)

    def admit_prefix(self, slot: int, total_tokens: int,
                     match: Optional[PrefixMatch]):
        """Admission in one atomic host step: map the match's full pages
        into ``slot`` (refcount++, read-only sharing), allocate a fresh
        page for the divergent/partial page (the engine device-copies
        the source into it — copy-on-write, charged to the writer), then
        :meth:`reserve` the remaining worst-case tail. Returns
        ``(matched_tokens, cow_src_page_or_None, cow_dst_page_or_None)``.
        Counts the hit/miss. Every failure raises BEFORE any mutation —
        :class:`OutOfPagesError` when the tail cannot be covered,
        :class:`~mxnet_tpu.base.MXNetError` past ``max_seq_len`` — so
        the slot is never left half-mapped."""
        if total_tokens > self.max_seq_len:
            raise MXNetError(
                "sequence of %d tokens exceeds max_seq_len %d"
                % (total_tokens, self.max_seq_len))
        if not self.can_admit_prefix(total_tokens, match):
            raise OutOfPagesError(
                "kvcache %r: prefix admission needs more pages than the "
                "%d free + %d cached available"
                % (self.name, len(self._free), len(self._cached)))
        if match is None or match.matched == 0:
            self.prefix_misses += 1
            _T_PREFIX_MISSES.inc(cache=self.name)
            self.reserve(slot, total_tokens)
            return 0, None, None
        pin = set()
        for e in match.full:
            self._cached.pop(e.page, None)  # adopted: no longer idle
            self.page_table[slot, self._owned[slot]] = e.page
            self._owned[slot] += 1
            self._ref[e.page] += 1
        cow_src = cow_dst = None
        if match.partial is not None:
            # the divergent/partial page is never mapped read-only: the
            # sequence WILL write into it (its remaining tail and/or its
            # first generated tokens), so it gets a private copy now —
            # pinned so the tail reservation can't reclaim the source
            # before the device copy runs
            cow_src = match.partial.page
            pin.add(cow_src)
            cow_dst = self._take_page(pin=pin)
            self.page_table[slot, self._owned[slot]] = cow_dst
            self._owned[slot] += 1
            self._exclusive[slot] += 1
            self._ref[cow_dst] = 1
        self.version += 1
        self.reserve(slot, total_tokens, _pin=pin)
        self.prefix_hits += 1
        self.prefix_tokens_matched += match.matched
        _T_PREFIX_HITS.inc(cache=self.name)
        self._publish()
        return match.matched, cow_src, cow_dst

    def insert_prefix(self, slot: int, prompt) -> None:
        """Index ``slot``'s freshly-prefilled prompt pages: one full
        entry per page-aligned chunk (chain-keyed), plus a partial entry
        for the tail — the future divergent-page CoW donor. Pages that
        are already indexed (mapped FROM the index, or a concurrent
        duplicate) are skipped; generated tokens are never indexed (the
        partial entry's ``tokens`` stop at the prompt)."""
        if not self.prefix_cache:
            return
        prompt = np.asarray(prompt, np.int32).ravel()
        ps = self.page_size
        p = int(prompt.size)
        parent = b""
        for i in range(p // ps):
            chunk = prompt[i * ps:(i + 1) * ps]
            key = _chain_key(parent, chunk)
            page = int(self.page_table[slot, i])
            # page 0 = the slot was freed under us (a close() racing the
            # last chunk): never index the null page
            if page and key not in self._index \
                    and page not in self._page_entry:
                e = _PrefixEntry(key, parent, page, chunk.copy(), True)
                self._index[key] = e
                self._children.setdefault(parent, []).append(e)
                self._page_entry[page] = e
            parent = key
        tail = prompt[(p // ps) * ps:]
        if tail.size:
            page = int(self.page_table[slot, p // ps])
            covered = any(
                e.tokens.size >= tail.size
                and np.array_equal(e.tokens[:tail.size], tail)
                for e in self._children.get(parent, ()))
            if page and page not in self._page_entry and not covered:
                e = _PrefixEntry(None, parent, page, tail.copy(), False)
                self._children.setdefault(parent, []).append(e)
                self._page_entry[page] = e

    def _index_remove(self, entry: _PrefixEntry) -> None:
        if entry.key is not None:
            self._index.pop(entry.key, None)
        kids = self._children.get(entry.parent)
        if kids is not None:
            try:
                kids.remove(entry)
            except ValueError:
                pass
            if not kids:
                del self._children[entry.parent]
        self._page_entry.pop(entry.page, None)

    def shed_cached(self, n: Optional[int] = None) -> int:
        """Proactively reclaim up to ``n`` (``None`` = all) cached-LRU
        refcount-0 pages to the free list, oldest-first — the governor's
        *yellow*-tier ladder rung. Distinct from demand reclaim inside
        ``_take_page`` (which takes cached pages only when a reservation
        needs them): shedding trades warm prefix capacity for free-list
        headroom *before* anything asks, so an admission under pressure
        never has to choose between deferring and evicting. Touches only
        pages no live sequence references — sequences in flight are
        unaffected. Returns the number of pages shed and counts them in
        ``mxnet_kvcache_pressure_sheds_total{cache=}``."""
        shed = 0
        while self._cached and (n is None or shed < n):
            page, entry = self._cached.popitem(last=False)
            self._index_remove(entry)
            self._free.append(page)
            shed += 1
        if shed:
            self.pressure_sheds += shed
            _T_PRESSURE_SHEDS.inc(shed, cache=self.name)
            self._publish()
        return shed

    def clear_prefix_index(self) -> None:
        """Drop EVERY index entry and return cached (refcount-0) pages
        to the free list. Called when pool *content* stops being
        trustworthy — a weight swap (KV computed under old params must
        not match new-params prompts) or a pool re-zero after eviction.
        Pages still mapped by live slots keep their refcounts and free
        normally later."""
        for page in self._cached:
            self._free.append(page)
        self._cached.clear()
        self._index.clear()
        self._children.clear()
        self._page_entry.clear()
        self._publish()

    # -- write-slot computation (host) -------------------------------------
    def write_slots(self, slot: int, start: int,
                    n_tokens: int) -> Tuple[np.ndarray, np.ndarray]:
        """(pages, offsets) int32 arrays addressing token positions
        ``start .. start+n_tokens`` of ``slot`` — the destinations
        :func:`write_kv` scatters into. Positions must be covered by a
        prior :meth:`reserve`."""
        pos = np.arange(start, start + n_tokens)
        if n_tokens and pos[-1] >= self._owned[slot] * self.page_size:
            raise MXNetError(
                "write past slot %d's reservation (pos %d, %d pages)"
                % (slot, int(pos[-1]), self._owned[slot]))
        pages = self.page_table[slot, pos // self.page_size]
        offsets = (pos % self.page_size).astype(np.int32)
        return pages.astype(np.int32), offsets

    def null_write_slots(self, n_tokens: int) -> Tuple[np.ndarray,
                                                       np.ndarray]:
        """Destinations for rows that must go NOWHERE (inactive decode
        slots, prompt padding, already-cached positions a chunk only
        recomputes): the null page, offset cycling through the page so
        scatter indices stay in range."""
        pos = np.arange(n_tokens)
        return (np.zeros(n_tokens, np.int32),
                (pos % self.page_size).astype(np.int32))

    def page_at(self, slot, pos):
        """The page that holds position ``pos`` of ``slot`` (the decode
        tick's one write a row)."""
        return self.page_table[slot, pos // self.page_size]

    @property
    def paged_bytes(self) -> int:
        """Bytes of what :attr:`num_pages` pages hold, over the layers (a
        page's share of it is what a reservation takes)."""
        import jax

        return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(
            self.pools)))

    def span_args(self, live) -> dict:
        """What a prefill's or a decode tick's span says of this group
        beyond the page walk (``live``: tokens each sequence of the run
        holds): nothing for pages a sequence keeps all of."""
        return {}

    def reset_pools(self) -> None:
        """Fresh zeroed pools (same shapes). The eviction path calls this
        after a failed step: with donation on, the old buffers may have
        been consumed by the failed execution, and every future sequence
        rewrites its pages through prefill before reading them anyway.
        The prefix index dies with the content it described."""
        self._zero_pools()
        self.clear_prefix_index()

    def _zero_pools(self) -> None:
        import jax.numpy as jnp

        def layers():
            return tuple(jnp.zeros(self._pool_shape, self._pool_dtype)
                         for _ in range(self.num_layers))

        self.pools = layers() if self.group == "latent" \
            else (layers(), layers())

    def _publish(self) -> None:
        _T_CAPACITY.set(self.num_pages - 1, cache=self.name)
        _T_PAGES.set(self.pages_in_use, cache=self.name)
        _T_CACHED.set(self.pages_cached, cache=self.name)
        _T_SHARED.set(self.shared_pages, cache=self.name)
        if self.audit:
            self.audit_check()

    def audit_check(self) -> None:
        """``MXNET_KVCACHE_AUDIT=1``: re-prove the refcount invariant —
        the runtime counterpart of tpulint's ``resource-lifecycle`` pass.
        Runs after every mutation (via :meth:`_publish`) and once per
        decode tick from the engine. Raises :class:`MXNetError` on the
        first violated invariant:

        - ``pages_in_use`` equals the number of pages with a live ref;
        - ``sum(ref)`` equals the number of live page-table mappings
          (the first ``owned`` entries of every slot row);
        - the free list holds no duplicates, no null page, no referenced
          page, and is disjoint from the cached-LRU;
        - cached pages all carry refcount 0.
        """
        live_refs = int(np.count_nonzero(self._ref > 0))
        if self.pages_in_use != live_refs:
            raise MXNetError(
                "kvcache %r audit: pages_in_use %d != pages with live "
                "refs %d (free=%d cached=%d) — a release path leaked or "
                "double-counted" % (self.name, self.pages_in_use,
                                    live_refs, len(self._free),
                                    len(self._cached)))
        mappings = sum(self._owned)
        total_ref = int(self._ref.sum())
        if total_ref != mappings:
            raise MXNetError(
                "kvcache %r audit: sum of page refcounts %d != live "
                "page-table mappings %d — refcounts and table rows "
                "disagree" % (self.name, total_ref, mappings))
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise MXNetError(
                "kvcache %r audit: duplicate entries on the free list — "
                "one page would be handed to two slots"
                % (self.name,))
        if 0 in free_set:
            raise MXNetError(
                "kvcache %r audit: null page 0 on the free list"
                % (self.name,))
        if free_set & set(self._cached):
            raise MXNetError(
                "kvcache %r audit: page(s) %s on the free list AND in "
                "the cached-LRU" % (self.name,
                                    sorted(free_set & set(self._cached))))
        bad = [p for p in self._free if self._ref[p] > 0]
        if bad:
            raise MXNetError(
                "kvcache %r audit: referenced page(s) %s on the free "
                "list" % (self.name, bad))
        bad = [p for p in self._cached if self._ref[p] != 0]
        if bad:
            raise MXNetError(
                "kvcache %r audit: cached-LRU page(s) %s carry a live "
                "refcount" % (self.name, bad))
        for s in range(self.num_slots):
            if self._exclusive[s] > self._owned[s]:
                raise MXNetError(
                    "kvcache %r audit: slot %d exclusive count %d > "
                    "owned %d" % (self.name, s, self._exclusive[s],
                                  self._owned[s]))

    def stats(self) -> dict:
        out = {
            "pages_in_use": self.pages_in_use,
            "pages_free": self.pages_free,
            "pages_capacity": self.num_pages - 1,
            "page_size": self.page_size,
            "max_pages_per_seq": self.max_pages,
        }
        if self.prefix_cache:
            total = self.prefix_hits + self.prefix_misses
            out.update({
                "prefix_cache": True,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_ratio": (self.prefix_hits / total
                                     if total else 0.0),
                "prefix_tokens_matched": self.prefix_tokens_matched,
                "pages_cached": self.pages_cached,
                "shared_pages": self.shared_pages,
                "index_entries": len(self._page_entry),
            })
        return out


class RingKVCache(PagedKVCache):
    """The pools of a model's SLIDING-WINDOW layers: a slot's page-table row
    is a ring.

    A window layer attends to the last ``window_tokens`` positions only, so
    a sequence never needs more than ``window_tokens / page_size + 1`` pages
    in it however long it grows (the ``+ 1``: a window that does not start
    on a page boundary touches one page more). The row has exactly that
    many columns and position ``p`` lives in column ``(p // page_size) %
    columns``: once the sequence is longer than the ring, a new page-sized
    block overwrites the column of the block that just left every window.
    Every shape stays static; nothing is allocated or freed mid-sequence.

    ``reserve(slot, n)`` takes ``min(n, ring)`` tokens' worth of pages at
    admission (the engine's worst-case discipline: an admitted sequence can
    always finish). A prompt longer than the ring writes only its last
    ``columns`` blocks (:meth:`write_slots` sends the earlier rows to the
    null page — two rows of one scatter may not name one cell). No prefix
    sharing: a ring page is rewritten in place.
    """

    group = "window"

    def __init__(self, num_slots: int, max_seq_len: int, num_layers: int,
                 num_kv_heads: int, head_dim: int, window_tokens: int,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, dtype="float32",
                 name: str = "decode"):
        page_size = _page_size(page_size)
        if window_tokens < 1 or window_tokens % page_size:
            raise MXNetError("window of %d tokens is not a whole number of "
                             "%d-token pages" % (window_tokens, page_size))
        ring = window_tokens + page_size
        # the base class sizes a row (and the default pool) from the
        # longest sequence: here that is the ring
        super().__init__(num_slots, min(int(max_seq_len), ring), num_layers,
                         num_kv_heads, head_dim, page_size=page_size,
                         num_pages=num_pages or 0, dtype=dtype, name=name)
        self.window_tokens = int(window_tokens)
        self.ring_tokens = self.max_pages * self.page_size
        self.max_seq_len = int(max_seq_len)
        if self.num_pages - 1 < self.max_pages:
            raise MXNetError(
                "kvcache %r: the window group needs at least one whole "
                "ring (%d pages + the null page), got %d pages"
                % (name, self.max_pages, self.num_pages))

    def pages_for(self, n_tokens: int) -> int:
        """Pages a sequence of ``n_tokens`` holds in this group: the ring's
        at the most (what ``can_admit`` counts and ``reserve`` takes)."""
        return super().pages_for(min(int(n_tokens), self.ring_tokens))

    def reserved_tokens(self, slot: int) -> int:
        owned = self._owned[int(slot)]
        return self.max_seq_len if owned == self.max_pages \
            else owned * self.page_size

    def write_slots(self, slot: int, start: int,
                    n_tokens: int) -> Tuple[np.ndarray, np.ndarray]:
        pos = np.arange(start, start + n_tokens)
        offsets = (pos % self.page_size).astype(np.int32)
        if not n_tokens:
            return np.zeros(0, np.int32), offsets
        if pos[-1] >= self.reserved_tokens(slot):
            raise MXNetError(
                "write past slot %d's reservation (pos %d, %d pages)"
                % (slot, int(pos[-1]), self._owned[slot]))
        block = pos // self.page_size
        pages = self.page_table[slot, block % self.max_pages]
        # of the blocks that share a column only the last one is written
        keep = block > block[-1] - self.max_pages
        return np.where(keep, pages, 0).astype(np.int32), offsets

    def page_at(self, slot, pos):
        return self.page_table[slot, (pos // self.page_size) % self.max_pages]

    def span_args(self, live) -> dict:
        """The rows a window layer reads beside those a full layer does,
        and the ring pages taken."""
        return dict(
            kv_rows_full=int(sum(live)),
            kv_rows_window=int(sum(min(n, self.window_tokens)
                                   for n in live)),
            kv_window_pages=self.pages_in_use,
            kv_window_capacity=self.num_pages - 1)

    def _publish(self) -> None:
        _T_GROUP_CAPACITY.set(self.num_pages - 1, cache=self.name,
                              group=self.group)
        _T_GROUP_PAGES.set(self.pages_in_use, cache=self.name,
                           group=self.group)
        if self.audit:
            self.audit_check()

    def stats(self) -> dict:
        out = super().stats()
        out["window_tokens"] = self.window_tokens
        return out


#: what a layer may keep between tokens; the paged kinds in the order of a
#: cache's groups, with the name each group's page walk is counted under
STATE_KINDS = ("paged", "ring", "latent", "slot")
_GROUP_OF = {"paged": "full", "ring": "window", "latent": "latent"}
#: the mixes of kinds a model is served with today (one has each)
_SERVED = ({"paged"}, {"paged", "ring"}, {"latent", "slot"})


def layer_states(model) -> list:
    """What each layer of ``model`` keeps between tokens, one entry a layer:

    ``("paged",)``
        K and V rows a token in pages of the layer's own pools;
    ``("ring", window_tokens)``
        the same, in a ring of pages a slot (a sliding window);
    ``("latent", width)``
        ONE row a token of ``width`` floats, paged, no head axis, no V pool;
    ``("slot", (shape, ...))``
        float32 arrays of fixed shapes a slot, not paged.

    A model says so as ``layer_state``; one that declares nothing keeps
    paged K/V in every layer."""
    declared = getattr(model, "layer_state", None)
    if declared is None:
        return [("paged",)] * model.num_layers
    states = [tuple(st) for st in declared]
    bad = sorted({st[0] for st in states} - set(STATE_KINDS))
    if bad or len(states) != model.num_layers:
        raise MXNetError(
            "layer_state: %d entries for %d layers, unknown kinds %s "
            "(known: %s)" % (len(states), model.num_layers, bad,
                             list(STATE_KINDS)))
    return states


def _paged_entries(states) -> list:
    """The distinct paged entries of ``states`` in the order of a cache's
    groups (:data:`STATE_KINDS`: full before window)."""
    return sorted({st for st in states if st[0] != "slot"},
                  key=lambda st: (STATE_KINDS.index(st[0]), st[1:]))


def place_layers(states) -> list:
    """Where a model finds each layer's arrays in the operands its cache
    hands it, one ``(group, index)`` a layer of ``states`` (a model's
    ``layer_state``): a paged layer's pools are layer ``index`` of group
    ``group`` of ``pools``; a slot layer's state is entry ``index`` of
    ``state`` (``group`` None)."""
    states = [tuple(st) for st in states]
    entries = _paged_entries(states)
    seen = collections.Counter()
    places = []
    for st in states:
        group = None if st[0] == "slot" else entries.index(st)
        places.append((group, seen[group]))
        seen[group] += 1
    return places


class DecodeCache:
    """Everything a model's layers keep between tokens, composed entry by
    entry from its :func:`layer_states` (:func:`make_cache`): what
    :class:`~mxnet_tpu.serving.DecodeEngine` asks of its cache it asks of
    this one, whatever the kinds.

    ``groups``
        one allocator with its pools (:class:`PagedKVCache`, a ring:
        :class:`RingKVCache`) a distinct paged kind, over that kind's
        layers: one slot numbering, a page table, a free list and a
        reservation each. What spans them is answered here: a reservation
        takes pages in every group or in none, ``free`` drops them in every
        group, ``write_slots`` and :meth:`page_lookups` give one row of
        pages (one lookup) a group, ``tables`` one table a group. Everything else — page
        counts without a group's name (``pages_in_use``, ``num_pages``,
        ``pages_for``, a tenant's page budget), the prefix index, ``audit``
        — is the FIRST group's: the one that grows with the sequence and
        gates admission first. A further group reports under
        ``stats()[its name]`` and its own gauges.
    ``state``
        a tuple a ``slot`` layer of arrays ``(num_slots,) + shape``, float32
        zeros, which a prefill overwrites for its slot and a decode tick
        updates in place for the rows that hold a token; ``()`` for a model
        with no such layer. It is not paged, so nothing of it is reserved,
        shared or freed: a slot's next prefill overwrites it whole.

    The two :attr:`operands` the engine threads through its programs and
    donates are ``(pools, state)``; a model is handed ``pools``,
    ``page_tables`` and ``write_pages`` as ONE group's bare or as a tuple a
    group (:meth:`per_group`)."""

    def __init__(self, groups, state_shapes=(), name: str = "decode"):
        self.groups = tuple(groups)
        self.num_groups = len(self.groups)
        self.name = name
        self._state_shapes = tuple(
            tuple(tuple(int(n) for n in shape) for shape in layer)
            for layer in state_shapes)
        #: bytes of the slot state (float32), every layer and slot
        self.state_bytes = 4 * self.num_slots * sum(
            math.prod(shape) for layer in self._state_shapes
            for shape in layer)
        #: the groups whose rows a latent attention reads (`latent_*` below)
        self._latent = [g for g in self.groups if g.group == "latent"]
        #: slots live, state bytes moved, latent rows read: summed over the
        #: ticks and prefills :meth:`span_args` was asked about
        self._moved = (0, 0, 0)
        self._zero_state()
        if self.state:
            _T_STATE_BYTES.set(self.state_bytes, server=name)

    def __getattr__(self, name):
        # what has no group's name is the first group's (see the class)
        if name == "groups" or name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.groups[0], name)

    # -- the operands ------------------------------------------------------
    def per_group(self, items):
        """What a model is handed for ``items``, one a group: the one
        group's own, or the tuple of several groups'."""
        return items[0] if self.num_groups == 1 else tuple(items)

    @property
    def operands(self):
        """``(pools, state)``: what every program of the engine is handed,
        donates and returns (:meth:`swap_pools` stores them back)."""
        return self.per_group([g.pools for g in self.groups]), self.state

    def swap_pools(self, pools, state) -> None:
        """Store what a jitted step returned in the operands' places
        (functional update discipline; with donation the old buffers are
        already dead)."""
        for group, its in zip(self.groups, (pools,) if self.num_groups == 1
                              else pools):
            group.pools = its
        self.state = state

    @property
    def paged_bytes(self) -> int:
        return sum(g.paged_bytes for g in self.groups)

    def _zero_state(self) -> None:
        import jax.numpy as jnp

        self.state = tuple(
            tuple(jnp.zeros((self.num_slots,) + shape, jnp.float32)
                  for shape in layer) for layer in self._state_shapes)

    def reset_pools(self) -> None:
        for group in self.groups:
            group.reset_pools()
        self._zero_state()

    # -- every group -------------------------------------------------------
    @property
    def tables(self):
        """``((version, host table), ...)``, one a group."""
        return tuple((g.version, g.page_table) for g in self.groups)

    def walk_groups(self):
        """``((group, table columns, layers), ...)``: the page tables a
        decode tick's attention walks."""
        return tuple((g.group, g.max_pages, g.num_layers)
                     for g in self.groups)

    def can_admit_prefix(self, n_tokens: int, match=None) -> bool:
        first, *further = self.groups
        return first.can_admit_prefix(n_tokens, match) \
            and all(g.can_admit(n_tokens) for g in further)

    def reserve(self, slot: int, n_tokens: int) -> None:
        for g in self.groups:
            if g.pages_for(n_tokens) - g.pages_owned(slot) \
                    > g.pages_available:
                raise OutOfPagesError(
                    "kvcache %r: the %s group cannot cover %d tokens (%d "
                    "pages free)" % (self.name, g.group, n_tokens,
                                     g.pages_available))
        for g in self.groups:   # the first raises before any mutation
            g.reserve(slot, n_tokens)

    def free(self, slot: int) -> None:
        for group in self.groups:
            group.free(slot)

    def write_slots(self, slot: int, start: int, n_tokens: int):
        """``(pages (groups, n), offsets (n,))``: one row of destination
        pages a group; a page's offsets are the same in all (one page
        size)."""
        rows = [g.write_slots(slot, start, n_tokens) for g in self.groups]
        return np.stack([pages for pages, _ in rows]), rows[0][1]

    def null_write_slots(self, n_tokens: int):
        pages, offsets = self.groups[0].null_write_slots(n_tokens)
        return np.stack([pages] * self.num_groups), offsets

    def page_lookups(self):
        """One ``page_at(slot, pos)`` a group — the page of that group that
        holds position ``pos`` of ``slot``: a decode tick's one write a row,
        resolved once a tick and asked once a row."""
        return tuple(g.page_at for g in self.groups)

    def audit_check(self) -> None:
        for group in self.groups:
            group.audit_check()

    # -- what it tells -----------------------------------------------------
    def span_args(self, live, prefill: bool = False) -> dict:
        """What a prefill's or a decode tick's span says of this cache
        beyond the page walk (``live``: tokens each sequence of the run
        holds): what each group says and, of a cache that holds slot state,
        what moved. A tick's recurrence reads and writes each live slot's
        state once; a prefill writes its slot's. A latent attention reads a
        row a token a latent layer (a prefill: before the pool). Counted
        here too (``mxnet_decode_state_total``, :meth:`stats`)."""
        args = {}
        for group in self.groups:
            args.update(group.span_args(live))
        if not self.state:
            return args
        a_slot = self.state_bytes // self.num_slots
        moved = (len(live), len(live) * a_slot * (1 if prefill else 2),
                 int(sum(live)) * sum(g.num_layers for g in self._latent))
        for what, n in zip(("slots_live", "bytes_moved",
                            "latent_rows_read"), moved):
            _T_STATE.inc(n, server=self.name, what=what)
        # one new tuple: stats() reads it from caller threads
        self._moved = tuple(a + b for a, b in zip(self._moved, moved))
        args.update(state_slots_live=moved[0], state_bytes_moved=moved[1],
                    latent_rows_read=moved[2])
        return args

    def stats(self) -> dict:
        first, *further = self.groups
        out = first.stats()
        for group in further:
            out[group.group] = group.stats()
        if self.state:
            out["state"] = dict(
                zip(("state_slots_live", "state_bytes_moved",
                     "latent_rows_read"), self._moved),
                state_bytes=self.state_bytes,
                latent_pages=sum(g.pages_in_use for g in self._latent),
                latent_capacity=sum(g.num_pages - 1 for g in self._latent))
        return out


def make_cache(model, num_slots: int, max_seq_len: int, page_size=None,
               num_pages=None, dtype="float32", name: str = "decode",
               prefix_cache: bool = False) -> DecodeCache:
    """The cache :func:`layer_states` of ``model`` asks for, composed entry
    by entry: one group of pools with its allocator a distinct paged kind
    (``paged``: ``full``; ``ring``: ``window``; ``latent``), over that
    kind's layers and no other, and the ``slot`` layers' state.
    ``num_pages``: the first group's, or ``{group: pages}``. A mix of kinds
    that no served model has is refused by name."""
    states = layer_states(model)
    kinds = {st[0] for st in states}
    entries = _paged_entries(states)
    if kinds not in _SERVED or len(entries) != len(kinds - {"slot"}):
        raise MXNetError(
            "no served model keeps layers of %s together (served: %s): "
            "serving a further mix wants its reference and its cell beside "
            "this condition"
            % (" + ".join(" ".join(map(str, e)) for e in entries
                          + [("slot",)] * ("slot" in kinds)),
               "; ".join(" + ".join(sorted(mix)) for mix in _SERVED)))
    if prefix_cache and kinds != {"paged"}:
        raise MXNetError("no prefix index over %s layers" % sorted(kinds))
    page_size = _page_size(page_size)
    pages = dict(num_pages) if isinstance(num_pages, dict) \
        else {_GROUP_OF[entries[0][0]]: num_pages}
    groups = []
    for entry in entries:
        kind, layers = entry[0], states.count(entry)
        common = dict(page_size=page_size, dtype=dtype, name=name,
                      num_pages=pages.get(_GROUP_OF[kind]))
        if kind == "ring":
            groups.append(RingKVCache(
                num_slots, max_seq_len, layers, model.num_kv_heads,
                model.head_dim, entry[1], **common))
        elif kind == "latent":
            # (like a ring's, its pages are never MXNET_KVCACHE_PAGES')
            common["num_pages"] = common["num_pages"] or 0
            groups.append(PagedKVCache(
                num_slots, max_seq_len, layers, None, entry[1], **common))
        else:
            groups.append(PagedKVCache(
                num_slots, max_seq_len, layers, model.num_kv_heads,
                model.head_dim, prefix_cache=prefix_cache, **common))
    return DecodeCache(
        groups, [st[1] for st in states if st[0] == "slot"], name=name)
