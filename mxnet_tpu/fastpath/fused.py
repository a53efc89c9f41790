"""Tree-level fused optimizer apply + buffer donation.

The pre-fastpath update plane dispatched one jitted kernel *per parameter
per step* (``Optimizer.update`` via ``Updater.__call__`` in a python loop —
~160 dispatches/step on ResNet-50). Here the SAME pure per-parameter
kernel (``Optimizer._leaf_step``, shared with the per-param path so the
two cannot drift numerically) is composed over the whole ``(params, grads, states)`` pytree and compiled as
ONE jit per optimizer: XLA sees every parameter's rescale → clip → wd →
momentum → assign chain in a single module and the python loop disappears
from the hot path.

Buffer donation: the params and optimizer states are dead the moment the
fused apply returns — donating them lets XLA update weights in place in
HBM (halves peak parameter memory, removes the copy kernels). PJRT only
implements donation on tpu/gpu, so ``donate_argnums`` is attached there;
the *semantics* — a stale ``NDArray`` handle over a donated buffer must
raise instead of reading garbage — are enforced on every backend by
explicitly deleting the consumed buffers after the call
(:func:`_invalidate`). ``jax.Array.delete`` is idempotent, so this is a
no-op where the runtime already reclaimed the buffer via donation.
"""
from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError

__all__ = ["FusedApplyError", "fused_apply", "apply_updater", "tree_kernel"]


class FusedApplyError(MXNetError):
    """Misuse of the fused tree apply (incapable optimizer, ragged input)."""


def _f32(x):
    return jnp.asarray(x, dtype=jnp.float32)


def _is_mp_state(optimizer, index, weight, state):
    """Whether ``state`` is a (fp32 master, base_state) multi-precision
    pair for this weight (created by ``create_state_multi_precision``)."""
    from ..optimizer import _is_mp_dtype, _is_mp_pair

    return (optimizer.multi_precision and _is_mp_dtype(weight.dtype)
            and _is_mp_pair(optimizer, index, weight, state))


def tree_kernel(optimizer, mp_flags: Tuple[bool, ...]):
    """Pure traced update over parallel per-parameter lists:
    ``(ws, gs, sts, ts, lrs, wds, extras) -> (new_ws, new_sts)``.

    The ONE composition of ``Optimizer._leaf_step`` over a parameter tree,
    consumed by two compilers: :func:`_tree_fn` jits it standalone (the
    fused update plane), and ``mxnet_tpu.trainplane`` inlines it into the
    whole-step jit behind ``MXNET_TRAINSTEP`` (the fused *step* plane).
    Because both trace this same function with the same host-prologue
    scalars, the update math of the two planes cannot drift apart — the
    PR-5 bit-identity discipline extended one level up."""

    def tree_step(ws, gs, sts, ts, lrs, wds, extras):
        new_ws: List[Any] = []
        new_sts: List[Any] = []
        for w, g, s, t, lr, wd, ex, mp in zip(
                ws, gs, sts, ts, lrs, wds, extras, mp_flags):
            if mp:
                # fp16/bf16 weight: step the fp32 master, cast back — the
                # traced twin of Optimizer.update_multi_precision
                master, base = s
                nm, nb = optimizer._leaf_step(
                    master, g.astype(jnp.float32), base, t, lr, wd, *ex)
                new_ws.append(nm.astype(w.dtype))
                new_sts.append((nm, nb))
            else:
                nw, ns = optimizer._leaf_step(w, g, s, t, lr, wd, *ex)
                new_ws.append(nw)
                new_sts.append(ns)
        return new_ws, new_sts

    return tree_step


def _tree_fn(optimizer, mp_flags: Tuple[bool, ...], donate_argnums: bool):
    # the jit variants live ON the optimizer (like its _step_cache) so they
    # die with it — an external map would keep every optimizer alive via
    # the tree_step closure below. Keys carry everything the closure reads
    # from the optimizer at trace time (rescale/clip) plus the per-leaf mp
    # layout and the donation mode; Optimizer.__getstate__ drops the cache.
    key = (mp_flags, optimizer.rescale_grad, optimizer.clip_gradient,
           donate_argnums)
    per_opt = optimizer.__dict__.setdefault("_tree_cache", {})
    fn = per_opt.get(key)
    if fn is not None:
        return fn

    fn = jax.jit(tree_kernel(optimizer, mp_flags),
                 donate_argnums=(0, 2) if donate_argnums else ())
    per_opt[key] = fn
    return fn


def _leaf_buffers(tree) -> List[Any]:
    return [l for l in jax.tree_util.tree_leaves(tree)
            if hasattr(l, "delete")]


def _buf_ptr(b):
    """Set of device buffer addresses behind an array (one per shard —
    a dp-sharded ZeRO state bucket has one buffer per mesh device), or
    None when unprobeable (already deleted, backend without the probe).
    Identity must be judged by buffer, not python object: XLA can alias
    two identical jit outputs onto one buffer behind distinct jax.Array
    objects."""
    try:
        return frozenset((b.unsafe_buffer_pointer(),))
    except Exception:  # noqa: BLE001  # tpulint: disable=swallowed-error - fall through to the sharded probe below
        pass
    try:
        return frozenset(s.data.unsafe_buffer_pointer()
                         for s in b.addressable_shards)
    except Exception:  # noqa: BLE001 - probe failure => caller plays safe
        return None


def _invalidate(buffers: Sequence[Any], keep_ptrs) -> None:
    """Delete consumed device buffers so any stale handle raises a clear
    'Array has been deleted' instead of reading reused memory. Idempotent
    with real donation (the runtime already invalidated them)."""
    for b in buffers:
        ptrs = _buf_ptr(b)
        if ptrs is not None and ptrs & keep_ptrs:
            continue  # (a shard of) this buffer is live in an output
        try:
            b.delete()
        except RuntimeError:
            # already reclaimed by real donation — exactly the goal
            continue


def donation_prep(*trees):
    """``(argnums_ok, consumed)`` — the ONE donation-eligibility probe for
    the fused update and whole-step jits. ``consumed`` is the flat list of
    device buffers behind ``trees`` (the args about to be donated), empty
    when donation is off or a buffer appears twice / can't be probed: a
    duplicated buffer cannot be donated twice, and an unprobeable one
    disables donation conservatively."""
    from . import donation_argnums_ok, donation_enabled

    if not donation_enabled():
        return False, []
    consumed: List[Any] = []
    for t in trees:
        consumed += _leaf_buffers(t)
    ptr_sets = [_buf_ptr(b) for b in consumed]
    flat: List[Any] = []
    for p in ptr_sets:
        if p is not None:
            flat.extend(p)
    # any shared shard buffer across two consumed arrays is a duplicate
    duplicated = None in ptr_sets or len(set(flat)) != len(flat)
    return (not duplicated and donation_argnums_ok(),
            [] if duplicated else consumed)


def invalidate_consumed(consumed, live_trees) -> None:
    """Delete every consumed buffer that did not come back alive in
    ``live_trees`` (stale-handle-raises discipline; idempotent with real
    donation, explicit delete() on backends without it)."""
    if not consumed:
        return
    keep = set()
    for t in live_trees:
        for p in map(_buf_ptr, _leaf_buffers(t)):
            if p is not None:
                keep.update(p)
    _invalidate(consumed, keep)


def fused_apply(optimizer, indices, grads, weights, states):
    """Apply ``optimizer`` to every parameter in ONE device dispatch.

    Parameters
    ----------
    indices : per-parameter optimizer indices (lr/wd multiplier keys)
    grads / weights : NDArrays, parallel to ``indices``
    states : per-parameter optimizer state pytrees (entries from
        ``create_state_multi_precision``; mp pairs are handled in-trace)

    Returns the list of new states; weights are updated in place. The
    host-side prologue (update counting, lr/wd multipliers, schedule
    scalars) runs exactly as the per-parameter loop would — ``_leaf_step``
    composed over the tree is the only thing that moved into one jit.
    """
    n = len(indices)
    if not (n == len(grads) == len(weights) == len(states)):
        raise FusedApplyError("fused_apply: ragged inputs")
    if n == 0:
        return []
    if not getattr(optimizer, "fastpath_capable", False):
        raise FusedApplyError(
            "%s has no pure _leaf_step kernel; use the per-parameter path"
            % type(optimizer).__name__)

    ts, lrs, wds, extras, mp_flags = [], [], [], [], []
    for i, w, s in zip(indices, weights, states):
        optimizer._update_count(i)
        lr, wd, ex = optimizer._host_scalars(i)
        ts.append(_f32(optimizer._index_update_count[i]))
        lrs.append(_f32(lr))
        wds.append(_f32(wd))
        extras.append(tuple(ex))
        mp_flags.append(_is_mp_state(optimizer, i, w, s))

    ws = [w._data for w in weights]
    gs = [g._data for g in grads]

    # grads are NOT donated, but a consumed buffer can alias one (e.g.
    # DCASGD's `prev` state starts as the weight itself), so gs rides in
    # the live set below
    argnums, consumed = donation_prep(ws, states)

    fn = _tree_fn(optimizer, tuple(mp_flags), argnums)
    telemetry.OPT_DISPATCHES.inc(path="fused")
    new_ws, new_sts = telemetry.jit_call(
        "fastpath.fused_apply", fn, ws, gs, list(states), ts, lrs, wds,
        extras)

    for w, nw in zip(weights, new_ws):
        w._data = nw
    invalidate_consumed(consumed, (new_ws, new_sts, gs))
    return new_sts


def apply_updater(updater, triples, positions: int = 1):
    """Run an ``optimizer.Updater`` over many ``(index, grad, weight)``
    triples in one fused dispatch — the drop-in replacement for the
    ``for ...: updater(i, g, w)`` loop in Trainer/model/module. Creates
    missing states exactly as ``Updater.__call__`` would.

    ``positions`` is the caller's device-position count (contexts /
    executor replicas): under ``MXNET_ZERO`` the sharded state plane
    (:mod:`.zero`) takes the update first — single-position callers
    only, everything else falls back to the replicated path here with a
    ``mxnet_zero_fallbacks_total`` reason."""
    if not triples:
        return
    from ..optimizer import ensure_mp_state
    from . import zero

    opt = updater.optimizer
    for index, _grad, weight in triples:
        if index not in updater.states:
            updater.states[index] = opt.create_state_multi_precision(
                index, weight)
            updater.states_synced[index] = True
        elif not zero.is_sharded(updater.states[index]):
            # restored states may predate the fp32-master layout for this
            # weight dtype — migrate exactly as update_multi_precision does
            # (a sharded handle was adopted in-layout; acquire_plane runs
            # the same migration whenever the plane rebuilds)
            updater.states[index] = ensure_mp_state(
                opt, index, weight, updater.states[index])
    if zero.level() and zero.apply(updater, triples, positions):
        return
    indices = [t[0] for t in triples]
    # a declined zero call (or the knob flipped off) leaves plain states;
    # formerly-sharded ones may still predate an mp flip — migrate them.
    # None = lost to a failed donated sharded step: recreate fresh
    states = zero.ensure_materialized(updater, indices)
    states = [ensure_mp_state(opt, i, w, s) if s is not None
              else opt.create_state_multi_precision(i, w)
              for (i, _g, w), s in zip(triples, states)]
    for i, s in zip(indices, states):
        updater.states[i] = s
    new_states = fused_apply(
        opt, indices, [t[1] for t in triples], [t[2] for t in triples],
        states)
    for i, ns in zip(indices, new_states):
        updater.states[i] = ns
