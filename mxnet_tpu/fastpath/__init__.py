"""mxnet_tpu.fastpath — the dispatch-bound-regime killer.

The update path issued one jitted call *per parameter per step* (~160
dispatches/step on ResNet-50), no jit boundary donated its buffers, and
every process restart recompiled the world. What a host-bound step costs
on the chip: 393.5 img/s at 73.8 % device idle while 483 scalar puts a
step held the host, 1,341 img/s device-bound without them
(PERF_LEDGER.jsonl, PR 24 and PR 26). This package is the hot-path rework
(TVM's whole-graph-fusion lesson, arxiv 1802.04799, applied to the
update/exchange plane; Axe, arxiv 2601.19092, motivates the
device-resident parameter layout):

====================  =====================================================
piece                 what it gives you
====================  =====================================================
:mod:`.fused`         tree-level fused optimizer apply: ONE jit over the
                      whole (params, grads, states) pytree per optimizer —
                      every optimizer that implements the pure
                      ``_leaf_step`` kernel gets it for free; buffer
                      donation + the stale-handle guard live here
:mod:`.bucketing`     DDP-style gradient coalescing: small grads ride flat
                      contiguous per-dtype buckets through the kvstore
                      aggregate phase (``MXNET_KVSTORE_BUCKET_MB``)
:mod:`.cache`         persistent XLA compilation cache
                      (``JAX_COMPILATION_CACHE_DIR``) with hit/miss counters
                      feeding the PR-3 recompile accounting
:mod:`.zero`          ZeRO-1/2 sharded state plane (``MXNET_ZERO``):
                      optimizer state (and fp32 masters at level 2) lives
                      partitioned over the dp axis in padded flat buckets;
                      the step swaps all-reduce for reduce-scatter →
                      shard-local kernel → weight all-gather
====================  =====================================================

Consumers: ``gluon.Trainer.step``, ``model._update_params[_on_kvstore]``,
``module.Module.update`` and the kvstore updater path all route through
:func:`apply_updater`; ``MXNET_FASTPATH=0`` restores the legacy
per-parameter loop everywhere (the escape hatch).
"""
from __future__ import annotations

import jax

from ..base import get_env
from .fused import FusedApplyError, apply_updater, fused_apply, tree_kernel
from . import bucketing, cache, zero  # noqa: F401  - cache wires itself at import

__all__ = ["enabled", "donation_enabled", "donation_argnums_ok", "supports",
           "fused_apply", "apply_updater", "FusedApplyError", "tree_kernel",
           "bucketing", "cache", "zero"]


def enabled() -> bool:
    """Whether the fused tree-apply routes are active (``MXNET_FASTPATH``,
    default on; re-read per call so tests and operators can flip it on a
    live process)."""
    return bool(get_env("MXNET_FASTPATH", 1, int, cache=False))


def donation_enabled() -> bool:
    """Whether fused applies donate the param/state buffers and invalidate
    the stale handles. ``MXNET_FASTPATH_DONATE``: ``1`` force on, ``0``
    off, unset = on only where PJRT implements donation (tpu/gpu) — on cpu
    the donate_argnums would be ignored with a warning per compile."""
    raw = get_env("MXNET_FASTPATH_DONATE", None, int, cache=False)
    if raw is None:
        return jax.default_backend() in ("tpu", "gpu")
    return bool(raw)


def donation_argnums_ok() -> bool:
    """Whether ``donate_argnums`` should actually be attached to a jit:
    donation is on AND the backend's PJRT implements it (cpu ignores the
    annotation with a warning per compile). The ONE home of this predicate
    — fused apply, executor backward, and serving engines all ask here."""
    return donation_enabled() and jax.default_backend() in ("tpu", "gpu")


def supports(optimizer, n_positions: int = 1) -> bool:
    """Whether ``optimizer`` can be folded into one tree-level jit for a
    caller holding ``n_positions`` device positions (contexts / executor
    replicas). Optimizers whose host prologue is order-sensitive only fuse
    for a single position: the fused path groups position-outer/
    param-inner, which would reorder those calls vs the legacy param-outer
    loop and break the ``MXNET_FASTPATH=0`` bitwise-equivalence guarantee.
    Order-sensitive means ``_host_scalars_stateful`` (Nadam's
    ``m_schedule``, SGLD's rng stream) or an ``lr_scheduler`` (it reads the
    optimizer-global ``num_update``, whose mid-step value depends on the
    iteration order whenever one index updates once per position)."""
    if not getattr(optimizer, "fastpath_capable", False):
        return False
    if n_positions <= 1:
        return True
    return not (getattr(optimizer, "_host_scalars_stateful", False)
                or getattr(optimizer, "lr_scheduler", None) is not None)
