"""TPU-truth accounting: the two silent performance killers, quantified.

On this stack the throughput cliffs that hurt in production are invisible
to the chrome trace unless you know to look: an XLA **recompile** (a new
shape reaching a jit cache) costs seconds and, recurring in steady state,
caps throughput at compile speed; a **device->host transfer** (``asnumpy``
and friends) serializes dispatch per call. tpulint flags the static
patterns; this module measures what actually happened at runtime:

* :func:`jit_call` wraps a jitted callable per *call site* and counts jit
  cache growth (``mxnet_recompiles_total{site=}``) plus the wall time of
  calls that compiled (``mxnet_compile_seconds_total{site=}``);
* :func:`record_transfer` accumulates transfer count and bytes per *path*
  (``fetch_host``, ``asnumpy``) — wired into ``base.fetch_host`` and the
  NDArray host-conversion methods;
* :func:`set_steady_state_recompiles` is the serving-facing gauge: after
  ``Server.warmup()`` it must stay 0 (the benchmark's
  ``decode_recompiles``).
"""
from __future__ import annotations

import logging
import re
import time

from . import registry as _registry

_LOG = logging.getLogger(__name__)

__all__ = ["RECOMPILES", "COMPILE_SECONDS", "STEADY_STATE_RECOMPILES",
           "TRANSFERS", "TRANSFER_BYTES", "PROFILER_COUNTER",
           "OPT_DISPATCHES", "STEP_DISPATCHES",
           "COMPILE_CACHE_HITS", "COMPILE_CACHE_MISSES",
           "HBM_BYTES_IN_USE", "HBM_BYTES_PEAK",
           "CKPT_SAVE_MS", "CKPT_RESTORE_MS", "CKPT_BYTES",
           "PREEMPTIONS", "CKPT_CORRUPTION", "ELASTIC_GOODPUT",
           "ELASTIC_RESTARTS",
           "PROGRAM_PARTS", "PROGRAM_PARTS_VERSION", "program_parts",
           "jit_call", "jit_cache_size", "note_recompile",
           "record_transfer", "sample_hbm", "hbm_watermark",
           "set_steady_state_recompiles"]

RECOMPILES = _registry.counter(
    "mxnet_recompiles_total",
    "XLA (re)compilations observed per jit call site",
    labels=("site",))

COMPILE_SECONDS = _registry.counter(
    "mxnet_compile_seconds_total",
    "cumulative wall seconds of jit calls that triggered a compile",
    labels=("site",))

STEADY_STATE_RECOMPILES = _registry.gauge(
    "mxnet_steady_state_recompiles",
    "recompiles after warmup at a site that promised compile-once "
    "(serving asserts 0)",
    labels=("site",))

TRANSFERS = _registry.counter(
    "mxnet_host_transfers_total",
    "device->host transfer operations per path",
    labels=("path",))

TRANSFER_BYTES = _registry.counter(
    "mxnet_host_transfer_bytes_total",
    "bytes moved device->host per path",
    labels=("path",))

OPT_DISPATCHES = _registry.counter(
    "mxnet_optimizer_update_dispatches_total",
    "optimizer-update device dispatches by path: perparam = one jitted "
    "call per parameter (the pre-fastpath regime), fused = one call per "
    "whole (params, grads, states) tree, ingraph accounted by the step jit",
    labels=("path",))

STEP_DISPATCHES = _registry.counter(
    "mxnet_trainstep_dispatches_total",
    "training-plane step executions by plane: graph = ONE whole-step jit "
    "(fwd+loss+bwd+allreduce+update in a single dispatch), eager = the "
    "per-phase fallback path (forward/backward/update each dispatch "
    "separately); graph steps with a zero optimizer-dispatch delta prove "
    "dispatches_per_step == 1",
    labels=("plane",))

COMPILE_CACHE_HITS = _registry.counter(
    "mxnet_compile_cache_hits_total",
    "XLA executables served from the persistent compilation cache "
    "(fastpath.cache) instead of recompiled")

COMPILE_CACHE_MISSES = _registry.counter(
    "mxnet_compile_cache_misses_total",
    "compilations the persistent cache could not serve (first-ever trace "
    "of that program on this machine)")

HBM_BYTES_IN_USE = _registry.gauge(
    "mxnet_hbm_bytes_in_use",
    "device memory currently allocated, per device, as reported by the "
    "PJRT memory stats (sample_hbm; absent where the backend has no "
    "stats, e.g. CPU)",
    labels=("device",))

HBM_BYTES_PEAK = _registry.gauge(
    "mxnet_hbm_bytes_peak",
    "peak device memory allocated since process start, per device "
    "(sample_hbm; absent where the backend has no stats)",
    labels=("device",))

# -- elastic/checkpoint accounting (published by mxnet_tpu.elastic) --------
# A preemptible fleet is managed by exactly these numbers: how long saves
# stall or overlap steps, how many bytes the checkpoint plane moves, how
# often preemptions fire, whether restores ever hit corrupt shards, and
# what fraction of wall time across restarts was productive training.

CKPT_SAVE_MS = _registry.histogram(
    "mxnet_ckpt_save_duration_ms",
    "wall duration of one training checkpoint save; mode=sync covers the "
    "whole commit, mode=async only the caller-visible snapshot (writes "
    "overlap subsequent steps)",
    labels=("mode",))

CKPT_RESTORE_MS = _registry.histogram(
    "mxnet_ckpt_restore_duration_ms",
    "wall duration of one training checkpoint restore (params + state + "
    "iterator/rng), including any corruption-fallback walk")

CKPT_BYTES = _registry.counter(
    "mxnet_ckpt_bytes_total",
    "bytes committed to checkpoint storage by kind: params, states "
    "(materialized optimizer state), shard (per-dp-rank ZeRO state), "
    "repl (replicated slots of a sharded save), meta, train (iterator/"
    "rng cursors), manifest",
    labels=("kind",))

PREEMPTIONS = _registry.counter(
    "mxnet_preemptions_total",
    "preemption notices honored (SIGTERM / MXNET_PREEMPTION_FILE): a "
    "best-effort checkpoint-now followed by a clean Preempted exit")

CKPT_CORRUPTION = _registry.counter(
    "mxnet_ckpt_corruption_total",
    "committed checkpoints rejected at restore (missing shard/param file "
    "or content-hash mismatch) — each one fell back to an older epoch")

ELASTIC_GOODPUT = _registry.gauge(
    "mxnet_elastic_goodput_ratio",
    "productive train time over wall time across an elastic run's "
    "restarts (attempts that advanced the committed epoch count as "
    "productive; crash-and-replay time does not)")

ELASTIC_RESTARTS = _registry.counter(
    "mxnet_elastic_restarts_total",
    "run_elastic restarts by reason (exception = train_fn raised, "
    "stall = no step progress within MXNET_ELASTIC_STALL_SECS)",
    labels=("reason",))

PROFILER_COUNTER = _registry.gauge(
    "mxnet_profiler_counter",
    "latest value of each profiler.Counter (chrome-trace counter lanes, "
    "bridged)",
    labels=("domain", "counter"))


def jit_cache_size(jitted) -> int:
    """Compiled-entry count of a ``jax.jit`` callable; -1 when the backend
    can't tell (same probe contract as ``serving.engine``)."""
    probe = getattr(jitted, "_cache_size", None)
    if probe is None:
        return -1
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 - a probe must never break the call
        return -1


_CHAOS = None


def _chaos():
    """The chaos module, resolved lazily: telemetry loads before resilience
    in the package import sequence. One module-global check thereafter."""
    global _CHAOS
    if _CHAOS is None:
        from ..resilience import chaos as _c

        _CHAOS = _c
    return _CHAOS


def jit_call(site: str, jitted, *args, **kwargs):
    """Invoke ``jitted(*args, **kwargs)`` recording recompiles at ``site``.

    Cache growth across the call means this invocation traced+compiled —
    count it and attribute the call's wall time as compile cost (dispatch
    time is noise next to an XLA compile). Repeated same-shape calls grow
    nothing and record nothing, so a steady-state loop through here is
    probe-only overhead (two int reads on the jit cache).

    Every wrapped invocation is also the ``jit.compile`` chaos injection
    site: under an ``MXNET_CHAOS`` schedule matching it, the synthetic
    fault surfaces to the caller's retry policy (serving engines retry it;
    an uncovered call site propagates it like a real compile failure).
    """
    c = _chaos()
    if c.ENABLED:
        c.maybe_fail("jit.compile")
    if not _registry.ENABLED:
        return jitted(*args, **kwargs)
    before = jit_cache_size(jitted)
    t0 = time.perf_counter()
    out = jitted(*args, **kwargs)
    if before >= 0:
        after = jit_cache_size(jitted)
        if after > before:
            RECOMPILES.inc(after - before, site=site)
            COMPILE_SECONDS.inc(time.perf_counter() - t0, site=site)
            # black box: a steady-state recompile at a serving site is a
            # rollback trigger — the dump must show it happened, when
            from . import flightrec

            flightrec.record("recompile", site=site,
                             count=after - before,
                             seconds=round(time.perf_counter() - t0, 4))
    return out


#: The closed vocabulary of ``jax.named_scope`` names the served models and
#: the decode engine's programs put their operations under (what each
#: covers: docs/observability.md "Parts of a program").
PROGRAM_PARTS = frozenset((
    "mx_embed", "mx_qkv", "mx_kv_write", "mx_attn", "mx_attn_out", "mx_mlp",
    "mx_moe_route", "mx_moe_experts", "mx_moe_shared", "mx_moe_combine",
    "mx_kda_proj", "mx_kda_state", "mx_mla_proj", "mx_head"))

#: Which layout of those scopes a program was traced with. jax's persistent
#: compile cache keys a program WITHOUT its ``op_name`` metadata, so two
#: programs that differ in their scopes alone share an entry and the second
#: reads back the first's names (PERF.md section 6, PR 39: the parent's
#: scope-less prefill served to this tree, its map empty). The engine's
#: programs carry this value as an XLA frontend attribute
#: (``jax.experimental.xla_metadata``), which the key does include: BUMP IT
#: whenever a scope moves or the vocabulary changes. Forgetting is a wrong
#: attribution where a cache is shared, not a silent one (the instruction
#: names still match), so ``tests/test_program_parts.py`` pins a digest of
#: the programs' scope paths beside this value: a scope cannot move without
#: that test asking for the bump.
PROGRAM_PARTS_VERSION = "3"

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9_\-]*)\(")
_HLO_NAME = re.compile(r"%([\w.\-]+)")
#: a computation named so is the inside of ONE instruction (a fusion's body,
#: a reduction's or a sort's region): its instructions are no device events
_HLO_INSIDE = re.compile(
    r"\b(?:calls|to_apply|select|scatter|called_computations)=\{?%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: instructions that are a name for a value, not work: no device event
_HLO_NO_WORK = frozenset(("parameter", "constant", "tuple", "bitcast",
                          "get-tuple-element"))


def _part_of(op_name):
    """The innermost vocabulary name on a ``metadata={op_name=...}`` path."""
    for scope in reversed(op_name.split("/")):
        if scope in PROGRAM_PARTS:
            return scope
    return None


def _first(parts):
    """The first of ``parts`` that is one, else ``None``."""
    return next((p for p in parts if p is not None), None)


def program_parts(text: str) -> dict:
    """Which part of the program each instruction of a compiled program
    belongs to, from ``compiled.as_text()``.

    A top-level ``fusion`` carries the ``op_name`` of its root, so an
    instruction's part is the innermost :data:`PROGRAM_PARTS` name on its
    own ``op_name``. What the compiler made itself carries no ``op_name``
    (on a TPU: the ``slice-start`` / ``slice-done`` / ``copy-start`` pairs
    that fetch a weight ahead of its product, layout copies, the pieces of a
    split reduction) and takes the part of the first instruction that reads
    it — a weight's fetch belongs to the product it was fetched for — or
    else of the first operand that has one, or else of the first other
    reader of its operands (the fetch of a weight for the program's NEXT run
    has no reader in this one).

    Returns ``{"program", "parts", "unnamed", "mixed"}``: the module's name
    (an ``XLA Modules`` event starts with it); ``{instruction: part}`` over
    the instructions that do work (no parameter, constant, tuple or bitcast)
    of every computation that is not the inside of one instruction (a
    fusion's body, a reduction's region); those among them that have no
    part; and how many fusions hold instructions of more than one part — a
    fusion XLA built across two scopes goes to its root's part whole, and
    ``mixed`` says how often that happened. A text without scopes gives
    empty ``parts``."""
    module = re.match(r"HloModule ([\w.\-]+)", text)
    comps, inside, rows = {}, set(), None
    for line in text.splitlines():
        if not line.startswith(" "):    # a computation opens or closes
            head = _HLO_COMPUTATION.match(line)
            rows = comps.setdefault(head.group(1), []) if head else None
            continue
        inst = _HLO_INSTRUCTION.match(line) if rows is not None else None
        if not inst:
            continue
        rest = line[inst.end():]
        opcode = _HLO_OPCODE.search(" " + rest)
        opcode = opcode.group(1) if opcode else ""
        if opcode != "call":
            inside.update(_HLO_INSIDE.findall(rest))
        op_name = _HLO_OP_NAME.search(rest)
        rows.append([inst.group(1), opcode,
                     _part_of(op_name.group(1)) if op_name else None,
                     _HLO_NAME.findall(rest.split(", metadata=")[0])])
    parts, unnamed = {}, []
    for name, rows in comps.items():
        if name in inside:
            continue
        part = {inst: p for inst, _o, p, _r in rows}
        users = {}
        for inst, _o, _p, refs in rows:
            for ref in refs:
                users.setdefault(ref, []).append(inst)
        # the text is in schedule order, a definition before its uses: back
        # to front every user is settled first, front to back every operand
        # (by what made it, not by who else reads it)
        made = dict(part)
        for inst, _o, _p, _r in reversed(rows):
            if part[inst] is None:
                part[inst] = _first(part.get(u) for u in users.get(inst, ()))
        for inst, _o, _p, refs in rows:
            if part[inst] is None:
                part[inst] = made[inst] = _first(made.get(r) for r in refs)
            if part[inst] is None:      # ... or by who else reads the same
                part[inst] = _first(part.get(r) for r in refs)
        for inst, opcode, _p, _r in rows:
            if opcode in _HLO_NO_WORK:
                continue
            if part[inst] is not None:
                parts[inst] = part[inst]
            else:
                unnamed.append(inst)
    mixed = sum(
        1 for name in inside
        if len({p for _i, _o, p, _r in comps.get(name, ()) if p}) > 1)
    return {"program": module.group(1) if module else "", "parts": parts,
            "unnamed": unnamed, "mixed": mixed}


def note_recompile(site: str, count: int = 1, seconds: float = 0.0):
    """Manual recompile report for backends without a countable cache."""
    if not _registry.ENABLED or count <= 0:
        return
    RECOMPILES.inc(count, site=site)
    if seconds > 0:
        COMPILE_SECONDS.inc(seconds, site=site)


def set_steady_state_recompiles(site: str, count: int):
    """Publish the post-warmup recompile count for ``site``."""
    if not _registry.ENABLED:
        return
    STEADY_STATE_RECOMPILES.set(count, site=site)


def sample_hbm(devices=None):
    """Sample per-device memory stats into the ``mxnet_hbm_bytes_*``
    gauges and return ``{device_id: (in_use, peak)}``. HBM — not compute
    — is what the ZeRO state plane trades for collectives, so the
    training planes publish this per step. Guarded no-op where the
    backend exposes no memory stats (CPU devices return ``None``): the
    gauges stay unset rather than lying a zero."""
    if not _registry.ENABLED:
        return {}
    import jax

    out = {}
    for d in (devices if devices is not None else jax.local_devices()):
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - a stats probe must never break a step
            stats = None
        if not stats:
            continue
        used = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use", used)
        if used is None:
            continue
        HBM_BYTES_IN_USE.set(int(used), device=str(d.id))
        HBM_BYTES_PEAK.set(int(peak), device=str(d.id))
        out[d.id] = (int(used), int(peak))
    return out


def hbm_watermark(source: str = "emitter"):
    """One :func:`sample_hbm` into the gauges AND the flight-recorder
    ring, so a dump carries a device-memory timeline, and into the HBM
    pressure governor. Guarded no-op on stat-less backends (CPU) and on
    any probe failure — a watermark must never break the thread taking
    it (the Emitter daemon calls this)."""
    try:
        stats = sample_hbm()
    except Exception:  # noqa: BLE001 - never break the sampling thread
        return {}
    if stats:
        from . import flightrec

        flightrec.record(
            "hbm.watermark", source=source,
            devices={str(d): {"in_use": u, "peak": p}
                     for d, (u, p) in stats.items()})
        # feed the pressure governor: real device usage joins the
        # plane-registered bounds in its tier computation (lazy import —
        # telemetry loads before resilience)
        try:
            from ..resilience import hbm as _hbm

            _hbm.governor().observe_device(stats, source=source)
        except Exception:  # noqa: BLE001 - never break the sampler
            _LOG.debug("hbm governor feed failed", exc_info=True)
    return stats


def record_transfer(path: str, arrays):
    """Account one device->host transfer of ``arrays`` (any objects with
    ``nbytes``; others count as 0 bytes) under the given ``path`` label."""
    if not _registry.ENABLED:
        return
    nbytes = 0
    for a in arrays:
        n = getattr(a, "nbytes", 0)
        nbytes += n
    TRANSFERS.inc(1, path=path)
    TRANSFER_BYTES.inc(nbytes, path=path)
