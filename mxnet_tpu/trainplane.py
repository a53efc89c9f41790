"""The training plane: ONE compiled SPMD step behind the high-level APIs.

PR 5 collapsed the *update* plane to one fused jit, but the
forward/backward still ran outside ``parallel.TrainStep``. This module
turns the fused update plane into a fused *step* plane: the whole training
step — forward + loss + backward + data-parallel all-reduce + optimizer
update — compiles into ONE XLA module (the reference framework's single
scheduled graph per step: GraphExecutor fwd+bwd + kvstore reduce + fused
optimizer ops; the same end-to-end-compilation argument TVM makes,
PAPERS.md), and the high-level training APIs route through it:

* ``TrainPlane`` — drives a ``gluon.Trainer``-owned model. ``plane.step``
  replaces the canonical record/forward/backward/``Trainer.step`` loop
  body; :func:`fit` is the epoch-loop convenience on top.
* ``module_plane`` — the same plane for ``Module.fit`` (and therefore
  ``model.fit``/``FeedForward.fit``), built over the Symbol graph.

Bit-identity discipline (PR-5, one level up): the in-graph step consumes
the SAME host scalar prologue (``Optimizer._update_count`` +
``_host_scalars``) and traces the SAME per-parameter kernel
(``fastpath.tree_kernel`` over ``Optimizer._leaf_step``) as the eager
fused apply, and seeds the backward with the same all-ones cotangents
``loss.backward()`` would — so fp32 training through the graph plane is
bit-identical to the eager fastpath (asserted in tests/test_trainplane.py).
The optimizer's ``num_update``/per-index counters stay the single source
of truth, so eager and in-graph steps can interleave without lr-schedule
drift.

Knobs (docs/env_var.md):

* ``MXNET_TRAINSTEP`` — ``auto`` (default: compile when traceable, fall
  back silently), ``1`` (compile, warn on fallback), ``0`` (eager always).
  Non-traceable models — plain ``Block``s, host-dependent control flow —
  fall back to the eager path automatically; never a crash.
* ``MXNET_TRAIN_DTYPE`` — ``bf16`` casts the model to bfloat16 at plane
  activation and turns on the fp32 master-weight multi-precision path in
  the optimizer (states are kept f32; the MXU-rate training mode).
* ``MXNET_SHARDED_FEED`` — default on: :func:`fit` stages batches through
  ``io.DevicePrefetchIter`` pre-laid-out over the mesh's ``dp`` axis, so
  the step's own shard check is a no-op instead of a dispatch-serializing
  ``device_put``.

Multi-chip: the default mesh spans every local device whose count divides
the batch; under a launcher (``MXNET_COORDINATOR_*``) construction joins
the multi-process jax runtime via ``kvstore.init_distributed`` and the
same step spans the slice (GSPMD inserts the ICI collectives).
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import autograd, telemetry
from . import optimizer as opt_mod
from .base import get_env
from .context import cpu
from .ndarray.ndarray import NDArray

__all__ = ["TrainPlane", "fit", "module_plane", "mode", "train_dtype",
           "sharded_feed"]

_LOG = logging.getLogger(__name__)

#: category of the planes' ``telemetry.span`` regions (``mx.train.*`` in a
#: ``jax.profiler`` trace; docs/observability.md lists them)
_SPAN_CAT = "trainplane"

#: why planes fell back to eager, by coarse reason — the operator-visible
#: record that MXNET_TRAINSTEP=auto quietly declined to compile something
FALLBACKS = telemetry.counter(
    "mxnet_trainplane_fallbacks_total",
    "training-plane graph compilations declined, by reason",
    labels=("reason",))


def mode() -> str:
    """``MXNET_TRAINSTEP``: ``auto`` | ``1`` | ``0`` (re-read per call)."""
    raw = str(get_env("MXNET_TRAINSTEP", "auto", str, cache=False)).lower()
    return raw if raw in ("auto", "1", "0") else "auto"


def train_dtype() -> str:
    """``MXNET_TRAIN_DTYPE``: ``fp32`` (default) | ``bf16``."""
    raw = str(get_env("MXNET_TRAIN_DTYPE", "fp32", str, cache=False)).lower()
    return "bf16" if raw in ("bf16", "bfloat16") else "fp32"


def sharded_feed() -> bool:
    """Whether :func:`fit` pre-shards batches over the mesh
    (``MXNET_SHARDED_FEED``, default on)."""
    return bool(get_env("MXNET_SHARDED_FEED", 1, int, cache=False))


def _default_mesh(batch_size: int):
    """Mesh over all local devices, shrunk to the largest count that
    divides the batch (a batch XLA cannot split evenly would otherwise
    fail to shard)."""
    from . import parallel

    devices = jax.devices()
    n = len(devices)
    while n > 1 and batch_size % n:
        n -= 1
    return parallel.device_mesh(n)


def _laid_out(x, target) -> bool:
    """Whether ``x`` already IS a ``target``-laid-out array of the target's
    mesh. Equivalence of placement is not enough: jax >= 0.9 carries the
    mesh in an array's TYPE, so a single-device array "equivalent" to a
    1-device-mesh ``target`` still keys a different trace than the
    mesh-typed outputs the step hands back — a first step fed such arrays
    compiled the whole step twice (once per type)."""
    sh = getattr(x, "sharding", None)
    return isinstance(sh, NamedSharding) and sh.mesh == target.mesh \
        and sh.is_equivalent_to(target, x.ndim)


def _aval(x):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)) \
        if not hasattr(x, "dtype") else jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype)


class _Ineligible(Exception):
    """Internal: the graph plane cannot serve this model/config."""


def _backend_refusal(plane: str, exc: BaseException) -> bool:
    """Whether ``exc`` must propagate instead of demoting a plane to the
    eager path. The auto-fallback contract covers TRACE ineligibility (a
    non-traceable model must train, not crash); an XLA/Mosaic compile or
    runtime error is the device saying no, and an eager plane that quietly
    trains anyway would hide it. The one exception is an OOM, which the
    HBM governor records (``hbm.oom_survival``) before the demotion."""
    from .resilience import hbm as hbm_mod

    return isinstance(exc, jax.errors.JaxRuntimeError) \
        and not hbm_mod.oom_survival(plane, exc, dump=True)


def _unpack_scalars(scalars):
    """In-trace inverse of :meth:`_PlaneBase._host_prologue`'s packing: the
    per-row ``ts, lrs, wds`` lists ``fastpath.tree_kernel`` takes, each
    element a float32 scalar sliced by STATIC index out of the ``(3, n)``
    operand — the same bits the eager plane's per-row scalars carry."""
    return tuple([row[k] for k in range(scalars.shape[1])]
                 for row in scalars)


class _PlaneBase(object):
    """Shared jit plumbing of the gluon and Module planes: host prologue,
    donation bookkeeping, dispatch accounting.

    The per-row optimizer scalars (update count, learning rate, weight
    decay) reach the step program as ONE host ``numpy`` array of shape
    ``(3, rows)``, an ordinary operand of the jit call: the dispatch
    transfers it (and, on a mesh, replicates it) once, where one device
    scalar per value was three host->device puts a row — 483 a step for
    ResNet-50, most of the step's host time on the chip. The values change
    every step and the operand's shape never does, so a schedule never
    retraces. ``extras`` (Nadam's schedule scalars, SGLD's key) stay
    outside the pack: they are the optimizer's own device values, of the
    optimizer's own dtypes."""

    @staticmethod
    def _probe_optimizer(opt):
        """Throwaway copy for the trace probe: ``_update_count`` /
        ``_host_scalars`` mutate schedule state (Nadam's m_schedule, rng
        draws), and a failed probe must leave the real optimizer
        untouched. ``param_dict`` holds live Parameters (device arrays) —
        shared by reference, it is only read for lr/wd multipliers."""
        import copy

        pd, opt.param_dict = opt.param_dict, {}
        try:
            probe = copy.deepcopy(opt)
        finally:
            opt.param_dict = pd
        probe.param_dict = pd
        return probe

    def _host_prologue(self, optimizer, indices):
        """Per-index counting + scalar prologue — EXACTLY the sequence the
        eager ``fastpath.fused_apply`` runs, in the same order
        (``_update_count(i)`` then ``_host_scalars(i)``, index by index:
        Nadam's ``m_schedule`` recurrence and SGLD's key draws depend on
        it), so the in-graph update consumes bit-identical scalars (Adam's
        host f64 bias correction included).

        Returns ``(scalars, extras)``. ``scalars`` is one host
        ``numpy.ndarray`` of shape ``(3, len(indices))``, float32, rows
        ``t, lr, wd``: Python numbers rounded to float32 exactly as the
        eager plane's ``jnp.asarray(x, float32)`` rounds them, and no
        device put — the array travels as an operand of the step's jit
        call and :func:`_unpack_scalars` slices it in-trace (the ZeRO
        branch reads its rows on the host). ``extras`` is the per-index
        list of the optimizer's own extra operands, untouched: empty for
        SGD, Adam, Adamax, ...; Nadam's four device scalars and SGLD's key
        are made by the optimizer (its own puts) and are not folded in."""
        ts, lrs, wds, extras = [], [], [], []
        for i in indices:
            optimizer._update_count(i)
            lr, wd, ex = optimizer._host_scalars(i)
            ts.append(optimizer._index_update_count[i])
            lrs.append(lr)
            wds.append(wd)
            extras.append(tuple(ex))
        return np.array([ts, lrs, wds], dtype=np.float32), extras

    @staticmethod
    def _prologue_puts(extras):
        """Host->device transfers the prologue issued: none for the packed
        scalars, one per device value the optimizer put in a row's
        ``extras`` (the ``puts`` argument of ``mx.train.prologue``)."""
        return sum(isinstance(x, jax.Array) for ex in extras for x in ex)

    def _donation(self, diff_vals, states):
        """(argnums_ok, consumed) — the shared ``fastpath.fused`` donation
        discipline, single-sourced."""
        from .fastpath.fused import donation_prep

        return donation_prep(diff_vals, states)

    def _invalidate_consumed(self, consumed, live):
        from .fastpath.fused import invalidate_consumed

        invalidate_consumed(consumed, (live,))


# ---------------------------------------------------------------------------
# gluon plane
# ---------------------------------------------------------------------------


class TrainPlane(_PlaneBase):
    """One training step through whichever plane the model supports.

    ``plane = TrainPlane(net, loss_fn, trainer)`` then
    ``loss = plane.step(data, label)`` replaces the canonical eager loop
    body::

        with autograd.record():
            loss = loss_fn(net(data), label)
        loss.backward()
        trainer.step(batch_size)

    With ``MXNET_TRAINSTEP`` at ``auto``/``1`` and a traceable
    (hybridizable) net, the step runs as ONE compiled SPMD module —
    forward, loss, backward, dp all-reduce over the mesh and the optimizer
    update — with the batch sharded over the mesh's ``dp`` axis and
    parameters/optimizer state replicated. Otherwise the exact eager loop
    above runs, so the call site never changes.

    The trainer stays the owner of the optimizer and its state
    (``trainer._updaters[0].states``): checkpoints via
    ``Trainer.save_states`` keep working, and eager/in-graph steps can be
    mixed freely (one step counter, no schedule drift).

    Parameters
    ----------
    net : Block — trained model (HybridBlock for the compiled plane)
    loss_fn : gluon Loss (or callable ``(out, label) -> loss`` NDArray)
    trainer : gluon.Trainer over ``net.collect_params()``
    mesh : optional jax Mesh; default spans all local devices whose count
        divides the batch size
    batch_axis : batch axis of data/label
    """

    def __init__(self, net, loss_fn, trainer, mesh=None, batch_axis=0):
        from . import kvstore as kvs_mod

        self._net = net
        self._loss = loss_fn
        self._trainer = trainer
        self._mesh = mesh
        self._batch_axis = batch_axis
        self._plane: Optional[str] = None  # 'graph' | 'eager'
        self._why_eager: Optional[str] = None
        self._cast = None                  # jnp.bfloat16 under bf16 mode
        self._rows = None                  # [(trainer idx, Parameter)]
        self._const_names = None
        self._zero_broken = None           # sticky zero-trace failure
        self._jits: Dict[Any, Any] = {}
        self.step_count = 0
        # multi-host: join the distributed runtime when a launcher planted
        # MXNET_COORDINATOR_*; no-op (False) in single-process mode
        kvs_mod.init_distributed()

    # -- plane selection -----------------------------------------------
    @property
    def plane(self) -> str:
        return self._plane or "undecided"

    def _demote(self, reason: str):
        FALLBACKS.inc(reason=reason)
        # black box: a plane demotion changes the performance regime —
        # post-mortems must see it next to whatever broke afterwards
        from .telemetry import flightrec

        flightrec.record("trainplane.fallback", reason=reason)
        self._plane = "eager"
        self._why_eager = reason
        if mode() == "1":
            _LOG.warning(
                "MXNET_TRAINSTEP=1 but the graph plane is unavailable "
                "(%s); training continues on the eager path", reason)

    def _ineligible_reason(self, data_nd) -> Optional[str]:
        from . import fastpath

        tr = self._trainer
        if not fastpath.enabled():
            # the legacy escape hatch must reach ALL the way down: with
            # MXNET_FASTPATH=0 an operator is ruling out the fused kernels,
            # and the graph plane is built on the same tree_kernel
            return "MXNET_FASTPATH=0 (legacy escape hatch)"
        if not hasattr(self._net, "_base_fn"):
            return "net is not a HybridBlock (no traceable base_fn)"
        if len(tr._contexts) != 1:
            return "multi-context trainer (eager split_and_load path)"
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._update_on_kvstore:
            return "update_on_kvstore"
        opt = tr._optimizer
        if not getattr(opt, "fastpath_capable", False):
            return "optimizer has no pure _leaf_step kernel"
        params = self._net.collect_params()
        for name, p in params.items():
            if p.grad_req not in ("null", "write"):
                return "grad_req %r on %s" % (p.grad_req, name)
            if p.grad_req != "null" and name not in tr._param2idx:
                return "net parameter %s not owned by the trainer" % name
        return None

    def _activate(self, data_nd, label_nd, batch_size):
        # bf16-by-default training mode: cast the model once, keep fp32
        # master weights in the optimizer state (multi-precision) — a
        # dtype knob, not a plane knob: applies on BOTH planes (including
        # the MXNET_TRAINSTEP=0 eager path)
        if train_dtype() == "bf16":
            self._cast = jnp.bfloat16
            self._materialize(data_nd)
            ctx = self._trainer._contexts[0]
            anyp = next(iter(self._net.collect_params().values()), None)
            if anyp is not None and \
                    anyp.data(ctx)._data.dtype != jnp.bfloat16:
                self._net.cast("bfloat16")
            self._trainer._optimizer.multi_precision = True
        if mode() == "0":
            self._plane = "eager"
            self._why_eager = "MXNET_TRAINSTEP=0"
            return
        reason = self._ineligible_reason(data_nd)
        if reason is not None:
            self._demote(reason)
            return
        try:
            self._prepare_graph(data_nd, label_nd, batch_size)
            self._plane = "graph"
        except Exception as exc:  # noqa: BLE001 - auto-fallback contract:
            # a non-traceable model (host-sync in hybrid_forward, shape-
            # dependent python control flow, ...) must train, not crash
            if _backend_refusal("trainplane.prepare", exc):
                raise
            self._demote("trace: %s" % type(exc).__name__)

    # -- graph plane ----------------------------------------------------
    def _materialize(self, data_nd):
        """Finish deferred init so every parameter has a value."""
        params = self._net.collect_params()
        try:
            for p in params.values():
                p.data(self._trainer._contexts[0])
        except Exception:  # DeferredInitializationError
            if hasattr(self._net, "infer_shape"):
                # abstract: no forward runs (or compiles) just to learn
                # the parameter shapes
                self._net.infer_shape(data_nd)
            else:
                with autograd.pause():
                    self._net(data_nd)

    def _prepare_graph(self, data_nd, label_nd, batch_size):
        """Resolve rows/mesh and PROBE the whole-step trace (eval_shape:
        no FLOPs, no device buffers, no counter mutation) before the plane
        commits to compiling."""
        tr = self._trainer
        opt = tr._optimizer
        self._materialize(data_nd)
        if self._mesh is None:
            self._mesh = _default_mesh(int(data_nd.shape[self._batch_axis]))
        params = self._net.collect_params()
        rows = []
        for i, p in enumerate(tr._params):
            if p.grad_req != "null":
                rows.append((i, p))
        if not rows:
            raise _Ineligible("no trainable parameters")
        self._rows = rows
        diff_names = {p.name for _, p in rows}
        self._const_names = tuple(n for n in params if n not in diff_names)

        # states must exist for the probe; created EXACTLY as the eager
        # Updater would (same layout, same mp pairs), so a later eager step
        # adopts them unchanged
        updater = tr._updaters[0]
        ctx = tr._contexts[0]
        for i, p in rows:
            w = p.data(ctx)
            if i not in updater.states:
                updater.states[i] = opt.create_state_multi_precision(i, w)
                updater.states_synced[i] = True
            else:
                updater.states[i] = opt_mod.ensure_mp_state(
                    opt, i, w, updater.states[i])

        probe_opt = self._probe_optimizer(opt)
        probe_opt.rescale_grad = tr._scale / batch_size
        scalars, extras = self._host_prologue(
            probe_opt, [i for i, _ in rows])
        step_fn = self._build_step(probe_opt, tuple(
            self._mp_flags(probe_opt, updater)))
        # probe on the CURRENT values' avals, NOT on _gather's output: a
        # failed probe must leave params un-replicated, or the eager
        # fallback would mix mesh-committed params with single-device
        # batches (replication happens in _graph_step, after the plane
        # commits)
        raw_diff = [p.data(ctx)._data for _, p in rows]
        raw_const = {n: params[n].data(ctx)._data
                     for n in self._const_names}
        raw_states = [updater.states[i] for i, _ in rows]
        d = data_nd._data if isinstance(data_nd, NDArray) \
            else jnp.asarray(data_nd)
        l = label_nd._data if isinstance(label_nd, NDArray) \
            else jnp.asarray(label_nd)
        avals = jax.tree_util.tree_map(
            _aval, (raw_diff, raw_const, raw_states,
                    scalars, extras, d, l, _global_key()))
        jax.eval_shape(step_fn, *avals)

    def _mp_flags(self, optimizer, updater):
        from .fastpath.fused import _is_mp_state

        ctx = self._trainer._contexts[0]
        return [_is_mp_state(optimizer, i, p.data(ctx), updater.states[i])
                for i, p in self._rows]

    def _gather(self, updater, with_states=True):
        """Current param/state values as jax arrays, replicated over the
        mesh (fresh buffer on first touch — later steps' outputs come back
        replicated and skip the put). With the ZeRO plane active the
        optimizer state lives dp-sharded in the plane's buckets and is
        NOT gathered here (``with_states=False``) — replicating it would
        silently undo the sharding."""
        from . import parallel

        ctx = self._trainer._contexts[0]
        params = self._net.collect_params()
        repl = NamedSharding(self._mesh, P())

        def repl_val(nd):
            v = nd._data
            if not _laid_out(v, repl):
                v = parallel.fresh_replicate(v, self._mesh)
                nd._data = v
            return v

        diff = [repl_val(p.data(ctx)) for _, p in self._rows]
        const = {n: repl_val(params[n].data(ctx)) for n in self._const_names}
        out = {"diff": diff, "const": const}
        if with_states:
            states = [jax.tree_util.tree_map(
                lambda x: x if _laid_out(x, repl)
                else parallel.fresh_replicate(x, self._mesh),
                updater.states[i]) for i, _ in self._rows]
            for (i, _), s in zip(self._rows, states):
                updater.states[i] = s
            out["states"] = states
        return out

    def _build_step(self, optimizer, mp_flags):
        """The whole-step function: fwd + loss + bwd (+ GSPMD-inserted dp
        all-reduce) + the fastpath tree kernel, traced as ONE program."""
        from . import fastpath

        base_fn = self._net._base_fn([0], train=True)
        kernel = fastpath.tree_kernel(optimizer, mp_flags)
        diff_names = tuple(p.name for _, p in self._rows)
        loss_fn = self._loss
        cast = self._cast

        def mx_train_step(diff_vals, const_vals, states, scalars, extras,
                          data, label, rng):
            if cast is not None and jnp.issubdtype(data.dtype, jnp.floating):
                data = data.astype(cast)

            def f(dv):
                pv = dict(const_vals)
                pv.update(zip(diff_names, dv))
                outs, aux = base_fn(pv, rng, data)
                out0 = outs[0] if isinstance(outs, tuple) else outs
                with autograd._RecordingStateScope(False, None):
                    l_nd = loss_fn(NDArray(out0, cpu()),
                                   NDArray(label, cpu()))
                return l_nd._data, aux

            loss, vjp_fn, aux = jax.vjp(f, list(diff_vals), has_aux=True)
            # the same all-ones cotangent loss.backward() seeds eagerly
            (grads,) = vjp_fn(jnp.ones(loss.shape, loss.dtype))
            ts, lrs, wds = _unpack_scalars(scalars)
            new_ws, new_sts = kernel(
                list(diff_vals), grads, states, ts, lrs, wds, extras)
            return loss, new_ws, new_sts, aux

        return mx_train_step

    # -- ZeRO: the sharded state plane inside the step jit ---------------
    def _zero_acquire(self, opt, updater):
        """The updater's ZeroPlane for this step, or None for the
        replicated layout — decided per call so a flipped ``MXNET_ZERO``
        takes effect (and materializes) without re-activation. Every
        decline lands in ``mxnet_zero_fallbacks_total``."""
        from .fastpath import zero

        lv = zero.level()
        if lv == 0 or self._zero_broken is not None:
            if zero.plane_of(updater) is not None:
                zero.materialize_updater(updater)
            return None
        reason = zero.eligible_reason(opt, len(self._mesh.devices.flat))
        if reason is not None:
            zero.note_fallback(reason)
            if zero.plane_of(updater) is not None:
                zero.materialize_updater(updater)
            return None
        ctx = self._trainer._contexts[0]
        weights = [p.data(ctx) for _, p in self._rows]
        try:
            return zero.acquire_plane(updater, opt, self._mesh, lv,
                                      [i for i, _ in self._rows], weights)
        except Exception as exc:  # noqa: BLE001 - never-a-crash: a failed
            # adopt falls back to the replicated layout, counted
            zero.note_fallback("adopt: %s" % type(exc).__name__)
            zero.materialize_updater(updater)
            return None

    def _build_zero_step(self, optimizer, zp):
        """The whole-step function over the SHARDED state plane: fwd +
        loss + bwd, then ``fastpath.zero.traced_update`` — the packed
        gradients constrained to the dp shards (GSPMD lowers the pending
        batch-axis reduction to a reduce-scatter), the shard-local bucket
        kernel, and an all-gather of ONLY the updated weights — traced
        as ONE program."""
        base_fn = self._net._base_fn([0], train=True)
        diff_names = tuple(p.name for _, p in self._rows)
        loss_fn = self._loss
        cast = self._cast

        def mx_train_step(diff_vals, const_vals, buckets, tvs, lrvs, wdvs,
                          data, label, rng):
            if cast is not None and jnp.issubdtype(data.dtype, jnp.floating):
                data = data.astype(cast)

            def f(dv):
                pv = dict(const_vals)
                pv.update(zip(diff_names, dv))
                outs, aux = base_fn(pv, rng, data)
                out0 = outs[0] if isinstance(outs, tuple) else outs
                with autograd._RecordingStateScope(False, None):
                    l_nd = loss_fn(NDArray(out0, cpu()),
                                   NDArray(label, cpu()))
                return l_nd._data, aux

            loss, vjp_fn, aux = jax.vjp(f, list(diff_vals), has_aux=True)
            (grads,) = vjp_fn(jnp.ones(loss.shape, loss.dtype))
            new_ws, new_buckets = zp.traced_update(
                optimizer, list(diff_vals), grads, buckets,
                tvs, lrvs, wdvs)
            return loss, new_ws, new_buckets, aux

        return mx_train_step

    def _zero_graph_call(self, zp, opt, updater, scalars, d, l, rng):
        """Dispatch one sharded whole-step jit and commit its outputs:
        weights replicated back onto the params, state buckets staying in
        their dp shards (``updater.states`` keeps the handles)."""
        ctx = self._trainer._contexts[0]
        with telemetry.span("train.gather", _SPAN_CAT):
            args = self._gather(updater, with_states=False)
            tvs, lrvs, wdvs = zp.expand_scalars(*scalars)
            argnums, consumed = self._donation(args["diff"], zp.buckets)
            # zp.sig carries indices/plan/level/mesh/mp — the sharded twin
            # of the replicated key's mp_flags: a row added after
            # activation (or any relayout) must miss here, not reuse a jit
            # whose closure holds the OLD plane's diff names and bucket
            # layout
            key = ("zero", zp.sig, tuple(d.shape), str(d.dtype),
                   tuple(l.shape), str(l.dtype), opt.rescale_grad,
                   opt.clip_gradient, argnums)
            fn = self._jits.get(key)
            if fn is None:
                repl = NamedSharding(self._mesh, P())
                fn = jax.jit(
                    self._build_zero_step(opt, zp),
                    out_shardings=(repl, [repl] * len(self._rows),
                                   zp.sharding_tree(), repl),
                    donate_argnums=(0, 2) if argnums else ())
                self._jits[key] = fn
        with telemetry.span("train.dispatch", _SPAN_CAT):
            loss, new_ws, new_buckets, aux = telemetry.jit_call(
                "trainplane.step", fn, args["diff"], args["const"],
                zp.buckets, tvs, lrvs, wdvs, d, l, rng)
        with telemetry.span("train.commit", _SPAN_CAT):
            zp.buckets = new_buckets
            params = self._net.collect_params()
            for (_i, p), nw in zip(self._rows, new_ws):
                p.data(ctx)._data = nw
            for name, val in aux.items():
                params[name].data(ctx)._data = val
            self._invalidate_consumed(consumed, (new_ws, new_buckets))
            telemetry.STEP_DISPATCHES.inc(plane="graph")
        with telemetry.span("train.hbm_sample", _SPAN_CAT):
            telemetry.sample_hbm()
        return NDArray(loss, ctx)

    def _graph_step(self, data_nd, label_nd, batch_size):
        tr = self._trainer
        opt = tr._optimizer
        updater = tr._updaters[0]
        ctx = tr._contexts[0]
        from . import parallel
        from .fastpath import zero as zero_mod

        with telemetry.span("train.shard", _SPAN_CAT):
            d = parallel.shard_to_mesh(data_nd, self._mesh, self._batch_axis)
            l = parallel.shard_to_mesh(label_nd, self._mesh,
                                       self._batch_axis)
        with telemetry.span("train.prologue", _SPAN_CAT) as prologue:
            opt.rescale_grad = tr._scale / batch_size  # Trainer.step parity
            for i, p in self._rows:  # states for rows added after activation
                if i not in updater.states:
                    updater.states[i] = opt.create_state_multi_precision(
                        i, p.data(ctx))
                    updater.states_synced[i] = True
            rng = _global_key()
            zp = self._zero_acquire(opt, updater)
            scalars, extras = self._host_prologue(
                opt, [i for i, _ in self._rows])
            prologue.set_args(puts=self._prologue_puts(extras))
        if zp is not None:
            try:
                return self._zero_graph_call(zp, opt, updater, scalars,
                                             d, l, rng)
            except Exception as exc:  # noqa: BLE001 - never-a-crash: the
                # sharded trace failing must not kill training; the
                # replicated step below reuses the SAME prologue values
                # (counters already advanced — no double count)
                from .resilience import hbm as hbm_mod

                # a ZeRO-step OOM commits the structured HBM diagnostic
                # (bucket-bytes bound included) to a flightrec dump and
                # latches the governor BEFORE the fallback — no-op for
                # every non-OOM trace failure
                hbm_mod.oom_survival("fastpath.zero", exc, dump=True)
                zero_mod.note_fallback("trace: %s" % type(exc).__name__)
                zero_mod.materialize_updater(updater)
                self._zero_broken = type(exc).__name__
                # a state lost to a failed DONATED execution cannot be
                # materialized — recreate it fresh so the replicated step
                # below still runs (momenta reset beats a dead run)
                for i, p in self._rows:
                    if i not in updater.states:
                        updater.states[i] = \
                            opt.create_state_multi_precision(i, p.data(ctx))
                        updater.states_synced[i] = True
        with telemetry.span("train.gather", _SPAN_CAT):
            mp_flags = tuple(self._mp_flags(opt, updater))
            args = self._gather(updater)
            argnums, consumed = self._donation(args["diff"], args["states"])
            key = (tuple(d.shape), str(d.dtype), tuple(l.shape),
                   str(l.dtype), opt.rescale_grad, opt.clip_gradient,
                   mp_flags, argnums, tuple(len(e) for e in extras))
            fn = self._jits.get(key)
            if fn is None:
                repl = NamedSharding(self._mesh, P())
                fn = jax.jit(self._build_step(opt, mp_flags),
                             out_shardings=(repl, repl, repl, repl),
                             donate_argnums=(0, 2) if argnums else ())
                self._jits[key] = fn
        with telemetry.span("train.dispatch", _SPAN_CAT):
            loss, new_ws, new_sts, aux = telemetry.jit_call(
                "trainplane.step", fn, args["diff"], args["const"],
                args["states"], scalars, extras, d, l, rng)
        with telemetry.span("train.commit", _SPAN_CAT):
            params = self._net.collect_params()
            for (i, p), nw, ns in zip(self._rows, new_ws, new_sts):
                p.data(ctx)._data = nw
                updater.states[i] = ns
            for name, val in aux.items():
                params[name].data(ctx)._data = val
            self._invalidate_consumed(consumed, (new_ws, new_sts))
            telemetry.STEP_DISPATCHES.inc(plane="graph")
        with telemetry.span("train.hbm_sample", _SPAN_CAT):
            telemetry.sample_hbm()
        return NDArray(loss, ctx)

    # -- eager plane ----------------------------------------------------
    def _eager_step(self, data_nd, label_nd, batch_size):
        if self._cast is not None and \
                jnp.issubdtype(data_nd._data.dtype, jnp.floating):
            data_nd = NDArray(data_nd._data.astype(self._cast),
                              data_nd.context)
        with autograd.record():
            out = self._net(data_nd)
            loss = self._loss(out, label_nd)
        loss.backward()
        self._trainer.step(batch_size)
        telemetry.STEP_DISPATCHES.inc(plane="eager")
        return loss

    # -- entry ----------------------------------------------------------
    def step(self, data, label, batch_size=None):
        """Run one training step; returns the (per-sample) loss NDArray."""
        data_nd = data if isinstance(data, NDArray) else NDArray(
            jnp.asarray(data), cpu())
        label_nd = label if isinstance(label, NDArray) else NDArray(
            jnp.asarray(label), cpu())
        if batch_size is None:
            batch_size = int(data_nd.shape[self._batch_axis])
        if self._plane is None:
            self._activate(data_nd, label_nd, batch_size)
        self.step_count += 1
        with telemetry.span("train.step", _SPAN_CAT):
            if self._plane != "graph":
                return self._eager_step(data_nd, label_nd, batch_size)
            return self._graph_step_guarded(data_nd, label_nd, batch_size)

    def _graph_step_guarded(self, data_nd, label_nd, batch_size):
        """Never-a-crash at the graph plane's own dispatch: a step
        failure that classifies as OOM (real ``RESOURCE_EXHAUSTED`` or
        chaos ``action=oom``) first lands the structured HBM diagnostic
        — per-plane registered bounds + watermark history — in a
        flight-recorder dump (``hbm.oom_survival``), then demotes to the
        eager plane and runs the step there: training continues, the
        post-mortem is on disk. Anything non-OOM still propagates —
        a programming error must fail fast, not hide behind a fallback.
        Best-effort caveat: a real OOM *mid-execution* may have consumed
        donated param buffers (nothing can resurrect those); the
        injected-OOM path raises before dispatch and always survives."""
        from .resilience import hbm as hbm_mod

        try:
            return self._graph_step(data_nd, label_nd, batch_size)
        except Exception as exc:  # noqa: BLE001 - OOM-only survival
            if not hbm_mod.oom_survival("trainplane.step", exc,
                                        dump=True):
                raise
            self._demote("oom: %s" % type(exc).__name__)
            return self._eager_step(data_nd, label_nd, batch_size)

    @property
    def mesh(self):
        return self._mesh

    def feed_sharding(self, ndim: int):
        """The NamedSharding batches should arrive in (pre-sharded feed)."""
        from . import parallel

        if self._mesh is None:
            return None
        return parallel.batch_sharding(self._mesh, ndim, self._batch_axis)


def _global_key():
    from . import _global

    return _global.next_key()


# ---------------------------------------------------------------------------
# epoch-loop convenience
# ---------------------------------------------------------------------------


def fit(net, loss_fn, trainer, train_data, epochs=1, batch_axis=0,
        mesh=None, batch_end_callback=None, checkpoint=None,
        checkpoint_every=1, resume=True):
    """Train ``net`` over ``train_data`` through the active plane.

    ``train_data`` yields ``io.DataBatch``es (any ``DataIter``) or
    ``(data, label)`` pairs. With the graph plane active and
    ``MXNET_SHARDED_FEED`` on, batches are staged ahead of the step by a
    ``DevicePrefetchIter`` laid out over the mesh's ``dp`` axis, so the
    step never pays a dispatch-serializing ``device_put``. Returns the
    :class:`TrainPlane` (inspect ``plane.plane`` for which path ran).

    ``checkpoint`` (an ``elastic.CheckpointManager``) makes the loop
    preemption-aware: it resumes net/trainer/iterator/RNG from the
    latest committed epoch (``resume=True`` — mid-epoch preemption saves
    resume mid-epoch, replaying nothing), calls
    ``elastic.step_boundary`` before every batch (the stall heartbeat,
    the kill-at-step chaos site, and the SIGTERM/preemption-file
    checkpoint-now), and commits an async sharded-aware checkpoint every
    ``checkpoint_every`` epochs. Wrap the whole call in
    ``elastic.run_elastic`` for supervised restarts.
    """
    from . import io as io_mod

    plane = TrainPlane(net, loss_fn, trainer, mesh=mesh,
                       batch_axis=batch_axis)
    feed = train_data
    if sharded_feed() and mode() != "0" and \
            isinstance(train_data, io_mod.DataIter) and \
            not isinstance(train_data, io_mod.DevicePrefetchIter) and \
            getattr(train_data, "provide_data", None):
        bs = train_data.provide_data[0].shape[batch_axis]
        if plane._mesh is None:
            plane._mesh = _default_mesh(int(bs))
        feed = io_mod.DevicePrefetchIter(
            train_data, sharding=plane.feed_sharding)

    start, mid = 0, False
    if checkpoint is not None and resume:
        from . import elastic

        restored = checkpoint.restore_training(net=net, trainer=trainer,
                                               train_iter=feed)
        if restored >= 0:
            extra = checkpoint.last_restored_extra or {}
            mid = bool(extra.get("mid_epoch"))
            start = restored if mid else restored + 1

    first_pass = True
    for epoch in range(start, epochs):
        # reset before every epoch except the very first pass when the
        # iterator is fresh — or carries a restored mid-epoch cursor
        if hasattr(feed, "reset") and (not first_pass
                                       or (epoch and not mid)):
            feed.reset()
        first_pass = False
        nbatch = 0
        feed_iter = iter(feed)
        while True:
            if checkpoint is not None:
                from . import elastic

                # BEFORE the fetch: a preemption save here records an
                # iterator cursor where every consumed batch was trained
                elastic.step_boundary(
                    manager=checkpoint,
                    save_fn=lambda: checkpoint.save_training(
                        epoch, net=net, trainer=trainer, train_iter=feed,
                        extra={"mid_epoch": True}))
            try:
                batch = next(feed_iter)
            except StopIteration:
                break
            if isinstance(batch, io_mod.DataBatch):
                data, label = batch.data[0], batch.label[0]
            else:
                data, label = batch
            loss = plane.step(data, label)
            nbatch += 1
            if batch_end_callback is not None:
                batch_end_callback(epoch, nbatch, loss)
        if checkpoint is not None and (
                (epoch + 1) % max(1, checkpoint_every) == 0
                or epoch == epochs - 1):
            checkpoint.save_training(epoch, net=net, trainer=trainer,
                                     train_iter=feed,
                                     extra={"mid_epoch": False},
                                     async_save=True)
    if checkpoint is not None:
        checkpoint.wait()
    return plane


# ---------------------------------------------------------------------------
# Module plane (Module.fit / model.fit / FeedForward.fit)
# ---------------------------------------------------------------------------


class _ModulePlane(_PlaneBase):
    """Whole-step jit over a bound ``Module``: the Symbol graph's forward,
    the all-ones-seeded backward and the fastpath update kernel in one
    compiled module per batch signature. Single-context modules only (the
    multi-context Module path stays on the eager executor group); the step
    still collapses forward/backward/update into ONE dispatch."""

    def __init__(self, module):
        self._m = module
        self._exec = module._exec_group.execs[0]
        self._ctx = module._context[0]
        exec_ = self._exec
        param_names = [n for n in module._symbol.list_arguments()
                       if n in module._param_names]
        self._entries = []
        for idx, name in enumerate(param_names):
            req = exec_.grad_req.get(name, "null")
            if name in exec_.grad_dict and req == "write":
                self._entries.append((idx, name))
            elif req not in ("null", "write"):
                # 'add' (and anything else) accumulates across calls — a
                # host-visible side effect the compiled step can't honor.
                # Demote rather than silently freezing the param as a jit
                # constant while the eager path would keep training it.
                raise _Ineligible("grad_req %r on %s" % (req, name))
        if not self._entries:
            raise _Ineligible("no trainable parameters")
        self._diff_names = tuple(n for _, n in self._entries)
        self._jits: Dict[Any, Any] = {}
        self._sig = None        # cached const-signature for the jit key —
        self._sig_batch = None  # only the batch arrays ever change shape
        self._probe()

    def _probe(self):
        m = self._m
        exec_ = self._exec
        opt = self._probe_optimizer(m._optimizer)
        updater = m._updater
        for idx, name in self._entries:
            if idx not in updater.states:
                updater.states[idx] = m._optimizer \
                    .create_state_multi_precision(idx, exec_.arg_dict[name])
                updater.states_synced[idx] = True
        scalars, extras = self._host_prologue(
            opt, [i for i, _ in self._entries])
        step_fn = self._build_step(opt, tuple(self._mp_flags(opt)))
        args = self._args()
        avals = jax.tree_util.tree_map(
            _aval, (args["diff"], args["const"], args["aux"],
                    args["states"], scalars, extras, _global_key()))
        jax.eval_shape(step_fn, *avals)

    def _mp_flags(self, optimizer):
        from .fastpath.fused import _is_mp_state

        updater = self._m._updater
        return [_is_mp_state(optimizer, i, self._exec.arg_dict[n],
                             updater.states[i]) for i, n in self._entries]

    def _args(self):
        exec_ = self._exec
        updater = self._m._updater
        diff = [exec_.arg_dict[n]._data for _, n in self._entries]
        const = {n: a._data for n, a in exec_.arg_dict.items()
                 if n not in self._diff_names}
        aux = {n: a._data for n, a in exec_.aux_dict.items()}
        states = [updater.states[i] for i, _ in self._entries]
        return {"diff": diff, "const": const, "aux": aux, "states": states}

    def _build_step(self, optimizer, mp_flags):
        from . import _global, fastpath

        sym = self._m._symbol
        kernel = fastpath.tree_kernel(optimizer, mp_flags)
        diff_names = self._diff_names

        def run_graph(arg_vals, aux_vals, rng):
            prev = _global.set_train(True)
            _global.push_rng_key(rng)
            try:
                vm = dict(arg_vals)
                vm.update(aux_vals)
                aux_updates = {}
                outs = sym.eval_jax(vm, aux_updates=aux_updates)
            finally:
                _global.pop_rng_key()
                _global.set_train(prev)
            return tuple(outs), aux_updates

        def mx_train_step(diff_vals, const_vals, aux_vals, states, scalars,
                          extras, rng):
            def f(dv):
                av = dict(const_vals)
                av.update(zip(diff_names, dv))
                return run_graph(av, aux_vals, rng)

            outs, vjp_fn, aux_updates = jax.vjp(
                f, list(diff_vals), has_aux=True)
            # backward(out_grads=None) parity: all-ones head gradients
            (grads,) = vjp_fn(tuple(
                jnp.ones(o.shape, o.dtype) for o in outs))
            ts, lrs, wds = _unpack_scalars(scalars)
            new_ws, new_sts = kernel(
                list(diff_vals), grads, states, ts, lrs, wds, extras)
            return outs, aux_updates, new_ws, new_sts

        return mx_train_step

    def step(self, batch):
        """One whole-graph training step for a DataBatch; fills the
        executor's outputs so ``update_metric`` reads them as usual."""
        with telemetry.span("train.step", _SPAN_CAT):
            return self._step(batch)

    def _step(self, batch):
        m = self._m
        exec_ = self._exec
        opt = m._optimizer
        updater = m._updater
        group = m._exec_group
        with telemetry.span("train.shard", _SPAN_CAT):
            # stage the batch into the (traced-operand) arg values
            for name, arr in zip(group.data_names, batch.data):
                exec_.arg_dict[name]._data = arr._data
            if group.label_names and batch.label:
                for name, arr in zip(group.label_names, batch.label):
                    exec_.arg_dict[name]._data = arr._data
        with telemetry.span("train.prologue", _SPAN_CAT) as prologue:
            for idx, name in self._entries:
                if idx not in updater.states:
                    updater.states[idx] = opt.create_state_multi_precision(
                        idx, exec_.arg_dict[name])
                    updater.states_synced[idx] = True
            scalars, extras = self._host_prologue(
                opt, [i for i, _ in self._entries])
            prologue.set_args(puts=self._prologue_puts(extras))
            rng = _global_key()
        with telemetry.span("train.gather", _SPAN_CAT):
            mp_flags = tuple(self._mp_flags(opt))
            args = self._args()
            argnums, consumed = self._donation(args["diff"], args["states"])
            # const = fixed params + the staged batch; only the batch
            # arrays can change shape between steps, so the sorted
            # full-signature walk (O(n log n) host work on the
            # one-dispatch hot path) is rebuilt only when the batch
            # signature does
            batch_sig = tuple((tuple(a.shape), str(a.dtype))
                              for b in (batch.data, batch.label or ())
                              for a in b)
            if batch_sig != self._sig_batch:
                self._sig = tuple(sorted(
                    (n, tuple(v.shape), str(v.dtype))
                    for n, v in args["const"].items()))
                self._sig_batch = batch_sig
            key = (self._sig, opt.rescale_grad, opt.clip_gradient, mp_flags,
                   argnums, tuple(len(e) for e in extras))
            fn = self._jits.get(key)
            if fn is None:
                fn = jax.jit(self._build_step(opt, mp_flags),
                             donate_argnums=(0, 3) if argnums else ())
                self._jits[key] = fn
        with telemetry.span("train.dispatch", _SPAN_CAT):
            outs, aux_updates, new_ws, new_sts = telemetry.jit_call(
                "trainplane.module_step", fn, args["diff"], args["const"],
                args["aux"], args["states"], scalars, extras, rng)
        with telemetry.span("train.commit", _SPAN_CAT):
            for (i, n), nw, ns in zip(self._entries, new_ws, new_sts):
                exec_.arg_dict[n]._data = nw
                updater.states[i] = ns
            for name, val in aux_updates.items():
                if name in exec_.aux_dict:
                    exec_.aux_dict[name]._data = val
            exec_.outputs = [NDArray(o, self._ctx) for o in outs]
            exec_._output_shapes = [o.shape for o in outs]
            exec_._residuals = None
            m._params_dirty = True
            self._invalidate_consumed(consumed, (new_ws, new_sts))
            telemetry.STEP_DISPATCHES.inc(plane="graph")
        return exec_.outputs


def module_plane(module):
    """Build the whole-step graph plane for a bound, optimizer-initialized
    ``Module`` — or return ``None`` when the eager executor path must run
    (``MXNET_TRAINSTEP=0``, multi-context, kvstore exchange, custom
    grad_req, non-traceable graph, ...). ``BaseModule.fit`` calls this once
    per fit and falls back silently: routing must never break training —
    except on a backend compile/runtime error, which propagates
    (:func:`_backend_refusal`)."""
    if mode() == "0":
        return None
    try:
        from .module.module import Module
    except ImportError:
        return None
    if type(module) is not Module:
        return None
    from . import fastpath

    try:
        if not fastpath.enabled() \
                or len(module._context) != 1 or module._kvstore is not None \
                or module._update_on_kvstore \
                or not isinstance(module._updater, opt_mod.Updater) \
                or not getattr(module._optimizer, "fastpath_capable", False) \
                or module._exec_group is None \
                or len(module._exec_group.execs) != 1 \
                or module._exec_group.state_names \
                or module.inputs_need_grad:
            FALLBACKS.inc(reason="module-config")
            return None
        return _ModulePlane(module)
    except Exception as exc:  # noqa: BLE001 - auto-fallback contract
        if _backend_refusal("trainplane.module", exc):
            raise
        FALLBACKS.inc(reason="module-trace: %s" % type(exc).__name__)
        if mode() == "1":
            _LOG.warning(
                "MXNET_TRAINSTEP=1 but Module.fit cannot use the graph "
                "plane (%s); the eager executor path runs instead", exc)
        return None
