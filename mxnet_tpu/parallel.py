"""Multi-device / multi-host execution: meshes, collectives, SPMD training.

TPU-native replacement for the reference's entire distribution stack
(SURVEY §5.8): the CPU/GPU reduce trees (``src/kvstore/comm.h:43``,
``comm_tree.h:50``), NCCL backend (``kvstore_nccl.h:62``) and the ps-lite
parameter server (``kvstore_dist.h:44``, ``kvstore_dist_server.h``) all
collapse onto two primitives:

* ``all_reduce`` — an eager cross-device allreduce over per-device gradient
  copies, lowered to one XLA collective riding ICI (DCN across hosts). This
  backs ``kvstore=tpu`` push/pull, keeping the imperative KVStore API.
* ``TrainStep`` — the in-graph path: ONE jitted SPMD module per step
  containing forward, loss, backward, gradient allreduce, and the optimizer
  update. Parameters and optimizer state are replicated over the mesh; the
  batch is sharded along ``dp``; XLA's GSPMD partitioner inserts the
  collectives (the scaling-book recipe: pick a mesh, annotate shardings,
  let XLA do the rest). Because reductions over the sharded batch axis are
  global, every BatchNorm inside a TrainStep is a cross-device SyncBatchNorm
  (reference ``src/operator/contrib/sync_batch_norm-inl.h``) for free.

Multi-host: under ``jax.distributed`` the same code spans processes —
``jax.devices()`` is the global device set, each process feeds its local
shards, and the collectives ride ICI within a slice / DCN across slices.
The PS server process of the reference disappears: weights stay resident
in HBM (SURVEY §5.8 north star).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import _global, autograd
from .base import MXNetError
from .context import Context, cpu
from .ndarray.ndarray import NDArray

__all__ = ["device_mesh", "all_reduce", "all_reduce_multi",
           "broadcast_to_devices", "TrainStep", "InferStep",
           "pipeline_apply", "shard_to_mesh", "batch_sharding",
           "fresh_replicate"]


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def device_mesh(n_devices: Optional[int] = None, axis_names=("dp",),
                shape: Optional[Sequence[int]] = None, devices=None) -> Mesh:
    """Build a ``jax.sharding.Mesh``.

    One axis (``dp``) by default — the reference's parity scope is data
    parallelism (SURVEY §2.5). Pass ``shape``/``axis_names`` for 2-D+
    meshes (e.g. ``shape=(4, 2), axis_names=('dp', 'mp')``).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    devices = np.asarray(devices)
    if shape is not None:
        devices = devices.reshape(tuple(shape))
        if len(axis_names) != devices.ndim:
            raise MXNetError("axis_names must match mesh shape rank")
    return Mesh(devices, tuple(axis_names))


# ---------------------------------------------------------------------------
# eager collectives (kvstore=tpu backend)
# ---------------------------------------------------------------------------

_REDUCE_JITS: Dict[Any, Any] = {}


def _reduce_fn(mesh: Mesh, op: str):
    key = (tuple(d.id for d in mesh.devices.flat), op)
    fn = _REDUCE_JITS.get(key)
    if fn is None:
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
               "mean": jnp.mean}[op]
        fn = jax.jit(lambda x: red(x, axis=0),
                     out_shardings=NamedSharding(mesh, P()))
        _REDUCE_JITS[key] = fn
    return fn


def _acc_reduce(datas, op):
    """Sequential on-device accumulation of copies for sum/mean/max/min."""
    acc = datas[0]
    for d in datas[1:]:
        if op in ("sum", "mean"):
            acc = acc + d
        elif op == "max":
            acc = jnp.maximum(acc, d)
        elif op == "min":
            acc = jnp.minimum(acc, d)
        else:
            raise MXNetError("unsupported all_reduce op %r" % (op,))
    return acc


def all_reduce(arrays: List[Any], op: str = "sum"):
    """Allreduce per-device copies into one replicated jax.Array.

    ``arrays`` is one array per participating device (jax arrays or
    NDArrays). The copies are assembled zero-copy into a single array
    sharded over a device axis and reduced with the output replicated on
    every participating device — one fused XLA allreduce over ICI instead
    of the reference's tree/P2P/NCCL reduce hierarchy (comm.h:103,451,
    comm_tree.h:50, kvstore_nccl.h:285).

    Across processes (``jax.distributed``), every process passes its local
    copies and the reduction spans the global device set.
    """
    datas = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
             for a in arrays]
    if len(datas) == 1 and jax.process_count() == 1:
        return datas[0]
    devs = []
    for d in datas:
        ds = list(d.devices())
        devs.append(ds[0] if len(ds) == 1 else None)
    distinct = None not in devs and len(set(devs)) == len(devs)
    if jax.process_count() == 1 and not distinct:
        # single process, copies not on distinct devices: plain on-device
        # reduce (multi-process must NOT take this shortcut — the local
        # arrangement is irrelevant, the cross-process reduce still runs)
        acc = _acc_reduce(datas, op)
        if op == "mean":
            acc = acc / len(datas)
        return acc
    mean_unpack = None  # (shape, dtype) when mean rides a sum (see below)
    if jax.process_count() > 1:
        # SPMD contract: branch selection must agree across processes, so
        # either EVERY process passes exactly one copy per local device
        # (fast path: one collective over the global device mesh) or none
        # does (pre-reduce path). Mixed arrangements are a caller error and
        # would run mismatched collectives.
        local = jax.local_devices()
        if len(datas) == len(local) and distinct:
            mesh = Mesh(np.asarray(jax.devices()), ("dev",))
        else:
            # arbitrary number of local copies: pre-reduce them on-device,
            # then reduce the partials across processes on a one-device-per-
            # process mesh (every process computes the same global ordering)
            acc = _acc_reduce(datas, op)
            if op == "mean":
                # mean = global sum / global copy count. The local copy
                # count rides along as one extra element through the SAME
                # cross-process sum, so per-process copy counts may differ
                # (within this branch — see the SPMD contract above).
                mean_unpack = (acc.shape, acc.dtype)
                pack_dtype = jnp.result_type(acc.dtype, jnp.float32)
                acc = jnp.concatenate(
                    [acc.reshape(-1).astype(pack_dtype),
                     jnp.asarray([float(len(datas))], pack_dtype)])
                op = "sum"
            by_proc: Dict[int, Any] = {}
            for d in jax.devices():
                if d.process_index not in by_proc or d.id < by_proc[d.process_index].id:
                    by_proc[d.process_index] = d
            datas = [jax.device_put(acc, by_proc[jax.process_index()])]
            mesh_devs = [by_proc[p] for p in sorted(by_proc)]
            mesh = Mesh(np.asarray(mesh_devs), ("dev",))
    else:
        mesh = Mesh(np.asarray(devs), ("dev",))
    shape = (len(mesh.devices.flat),) + datas[0].shape
    sharding = NamedSharding(mesh, P("dev"))
    shards = [d.reshape((1,) + d.shape) for d in datas]  # leading shard axis
    stacked = jax.make_array_from_single_device_arrays(shape, sharding, shards)
    reduced = _reduce_fn(mesh, op)(stacked)
    if jax.process_count() > 1:
        # The jit output is replicated over the GLOBAL mesh; a global jax.Array
        # is not addressable (asnumpy would raise) outside collectives, so hand
        # back this process's fully-replicated local shard as a plain array.
        reduced = reduced.addressable_shards[0].data
    if mean_unpack is not None:
        out_shape, out_dtype = mean_unpack
        # match the other mean paths' dtype promotion (acc / count, the
        # true-divide result type) — NOT a cast back to the input dtype,
        # which would truncate integer means
        div_dtype = jnp.result_type(out_dtype, jnp.float32) \
            if not jnp.issubdtype(out_dtype, jnp.floating) else out_dtype
        reduced = (reduced[:-1] / reduced[-1]).reshape(out_shape) \
            .astype(div_dtype)
    return reduced


_MULTI_REDUCE_JITS: Dict[Any, Any] = {}


def _multi_reduce_fn(mesh: Mesh, op: str):
    key = (tuple(d.id for d in mesh.devices.flat), op)
    fn = _MULTI_REDUCE_JITS.get(key)
    if fn is None:
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
               "mean": jnp.mean}[op]
        fn = jax.jit(lambda xs: [red(x, axis=0) for x in xs],
                     out_shardings=NamedSharding(mesh, P()))
        _MULTI_REDUCE_JITS[key] = fn
    return fn


def all_reduce_multi(groups: List[List[Any]], op: str = "sum"):
    """Allreduce MANY tensors in ONE compiled XLA module.

    ``groups[k]`` is one per-device copy list for tensor ``k``; every group
    must span the same device set. All reductions compile into a single
    module so XLA can schedule/fuse the collectives together — the
    TPU-native analogue of the reference NCCL store's batched key grouping
    (kvstore_nccl.h:285) and the tree store's multi-tree reduce
    (comm_tree.h:50). Returns one replicated array per group.
    """
    if not groups:
        return []
    datas = [[a._data if isinstance(a, NDArray) else jnp.asarray(a)
              for a in g] for g in groups]
    devs = []
    for d in datas[0]:
        ds = list(d.devices())
        devs.append(ds[0] if len(ds) == 1 else None)
    uniform = None not in devs and len(set(devs)) == len(devs) and all(
        len(g) == len(devs) for g in datas)
    if not uniform or len(devs) == 1:
        return [all_reduce(g, op) for g in groups]
    mesh = Mesh(np.asarray(devs), ("dev",))
    sharding = NamedSharding(mesh, P("dev"))
    stacked = []
    for g in datas:
        by_dev = {next(iter(d.devices())): d for d in g}
        if len(by_dev) != len(devs) or any(dv not in by_dev for dv in devs):
            return [all_reduce(gg, op) for gg in groups]
        shape = (len(devs),) + g[0].shape
        shards = [by_dev[dv].reshape((1,) + by_dev[dv].shape) for dv in devs]
        stacked.append(jax.make_array_from_single_device_arrays(
            shape, sharding, shards))
    return _multi_reduce_fn(mesh, op)(stacked)


def pipeline_apply(stage_fn, stage_params, microbatches, mesh,
                   axis: str = "pp"):
    """GPipe-style pipeline parallelism over a mesh axis.

    Beyond the reference's scope (SURVEY §2.5: MXNet 1.3 has no true
    pipeline parallelism — its overlap is async-engine scheduling), but
    first-class on TPU: stages are laid out along ``axis``, activations
    hop stage-to-stage over ICI via ``lax.ppermute``, and microbatches
    keep every stage busy after the fill phase (the GPipe schedule:
    M + S - 1 ticks for M microbatches over S stages).

    Parameters
    ----------
    stage_fn : callable(params_s, x) -> y — one stage's computation;
        activations must keep one shape across stages.
    stage_params : pytree whose leaves have a leading stage axis (S, ...)
        — sharded over ``axis``, one stage per device.
    microbatches : (M, B, ...) array, replicated.
    mesh : Mesh containing ``axis`` with S devices.

    Returns (M, B, ...) outputs (the last stage's results, in microbatch
    order), fully replicated.
    """
    from jax import shard_map

    n_stage = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    bad = [l.shape for l in jax.tree_util.tree_leaves(stage_params)
           if l.shape[0] != n_stage]
    if bad:
        raise MXNetError(
            "pipeline_apply: every stage_params leaf needs leading dim %d "
            "(one stage per '%s' device); got %s" % (n_stage, axis, bad))
    ticks = n_micro + n_stage - 1
    ring = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def per_device(params_blk, x_all):
        # params_blk leaves: (1, ...) — this device's stage
        my_params = jax.tree_util.tree_map(lambda a: a[0], params_blk)
        stage = jax.lax.axis_index(axis)

        def tick(act_in, t):
            # stage 0 feeds itself from the microbatch stream; later
            # stages consume what the previous stage sent last tick
            my_in = jnp.where(stage == 0,
                              x_all[jnp.clip(t, 0, n_micro - 1)], act_in)
            out = stage_fn(my_params, my_in)
            act_next = jax.lax.ppermute(out, axis, ring)
            return act_next, out

        # the carry crosses ppermute, which makes it device-varying along
        # the pp axis; the initial zeros must carry the same varying type
        zero = jax.lax.pcast(jnp.zeros_like(x_all[0]), (axis,), to="varying")
        _, outs = jax.lax.scan(tick, zero, jnp.arange(ticks))
        return outs[None]  # (1, ticks, B, ...) — stacked over axis

    spec_p = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(spec_p, P()), out_specs=P(axis))
    outs = fn(stage_params, microbatches)  # (S, ticks, B, ...)
    # microbatch m leaves the last stage at tick (S-1) + m
    return outs[n_stage - 1, n_stage - 1:n_stage - 1 + n_micro]


def shard_for_device(array, device):
    """Extract the replica of a replicated array that lives on ``device``
    (zero-copy)."""
    for s in array.addressable_shards:
        if s.device == device:
            return s.data
    return jax.device_put(array, device)


def broadcast_to_devices(array, devices):
    """Replicate a host/single-device array onto each device; returns a list
    of per-device arrays (reference comm.h Broadcast)."""
    data = array._data if isinstance(array, NDArray) else jnp.asarray(array)
    return [jax.device_put(data, d) for d in devices]


# ---------------------------------------------------------------------------
# sharding helpers shared by the step executors and the input plane
# ---------------------------------------------------------------------------


def batch_sharding(mesh: Mesh, ndim: int, batch_axis: int = 0,
                   dp_axis: Optional[str] = None) -> NamedSharding:
    """The NamedSharding a training batch should arrive in: sharded over the
    mesh's data-parallel axis at ``batch_axis``, replicated elsewhere. The
    input plane (``io.DevicePrefetchIter``/``gluon.data.DataLoader``) uses
    this as its device-put target so batches land pre-sharded and the step's
    own ``shard_to_mesh`` degenerates to an equivalence check."""
    spec = [None] * ndim
    spec[batch_axis] = dp_axis or mesh.axis_names[0]
    return NamedSharding(mesh, P(*spec))


def resolve_sharding(sharding, ndim: int):
    """Resolve an input-plane sharding spec — a concrete ``Sharding`` or an
    ``ndim -> Sharding`` callable (how ``batch_sharding`` is usually
    curried) — to the target for one array, or ``None`` when no target is
    configured."""
    if sharding is None:
        return None
    return sharding(ndim) if callable(sharding) else sharding


def _evenly_shardable(target, shape) -> bool:
    """Whether ``target`` can lay an array of ``shape`` out without ragged
    shards (``device_put`` raises on a partitioned dim the mesh axis does
    not divide)."""
    mesh = getattr(target, "mesh", None)
    spec = getattr(target, "spec", None)
    if mesh is None or spec is None:
        return True
    for dim, names in enumerate(spec):
        if names is None:
            continue
        parts = 1
        for axis in (names if isinstance(names, tuple) else (names,)):
            parts *= mesh.shape[axis]
        if dim >= len(shape) or shape[dim] % parts:
            return False
    return True


def put_sharded(data, target):
    """THE home of the skip-put discipline: ``device_put`` a jax array onto
    ``target`` unless it is already laid out equivalently — re-putting
    issues a copy that serializes dispatch with the device queue (a
    wasted device-to-device copy on an attached chip). Returns ``data`` itself on skip, so callers
    can ``is``-check whether a put happened. Shared by ``shard_to_mesh``,
    the ``io.DevicePrefetchIter`` worker and the gluon ``DataLoader``
    feed.

    A batch the target cannot split evenly — the ragged final batch of an
    epoch on a multi-device mesh — degrades to replication over the same
    mesh instead of raising: the training plane's never-a-crash contract
    reaches the input plane too (GSPMD still partitions the step; the odd
    shape pays one extra compile, which it would anyway)."""
    sh = getattr(data, "sharding", None)
    if sh is not None and sh.is_equivalent_to(target, data.ndim):
        return data
    if not _evenly_shardable(target, data.shape):
        target = NamedSharding(target.mesh, P())
    return jax.device_put(data, target)


def shard_to_mesh(data, mesh: Mesh, batch_axis: int = 0,
                  dp_axis: Optional[str] = None):
    """Lay a batch out over the mesh's dp axis via ``put_sharded`` (a batch
    already laid out equivalently — always true for device-resident data on
    a 1-device mesh, and for the pre-sharded feed path — is returned
    as-is)."""
    data = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    return put_sharded(
        data, batch_sharding(mesh, data.ndim, batch_axis, dp_axis))


_REPL_JITS: Dict[Any, Any] = {}


def _identity_copy_fn(mesh: Mesh, target=None):
    if target is None:
        target = NamedSharding(mesh, P())
    key = (tuple(d.id for d in mesh.devices.flat),
           str(getattr(target, "spec", target)))
    fn = _REPL_JITS.get(key)
    if fn is None:
        fn = jax.jit(lambda a: a, out_shardings=target)
        _REPL_JITS[key] = fn
    return fn


def _buffer_ptrs(a):
    """Set of device-buffer addresses behind an array, or None when
    unprobeable."""
    try:
        return {s.data.unsafe_buffer_pointer() for s in a.addressable_shards}
    except Exception:  # noqa: BLE001 - probe failure => caller plays safe
        return None


def fresh_replicate(x, mesh: Mesh, target=None):
    """Lay ``x`` out over ``mesh`` into FRESH buffers, without the eager
    ``jnp.copy`` intermediate the old TrainStep init paid (a transient
    second full copy of every parameter — the 2x-HBM init spike): the
    result must not alias the source, because the step jit donates its
    param inputs and donation would otherwise delete a buffer the caller
    still references.

    ``target`` is the destination ``Sharding`` (or an ``ndim ->
    Sharding`` callable, resolved through :func:`resolve_sharding`);
    default fully replicated. The alias guard is layout-aware: a source
    already laid out as ``target`` — INCLUDING a dp-sharded ZeRO state
    bucket re-initialized in place — takes one compiled identity copy
    UNDER THAT LAYOUT instead of being silently re-replicated (the
    pre-ZeRO guard only knew the replicated case, so re-initializing a
    sharded tree would have quietly undone its sharding and N-tupled its
    per-device bytes).

    * host (numpy) source: ``device_put`` allocates fresh device buffers
      by construction — one copy, done;
    * device source in another layout: ``device_put`` to ``target``, then
      an isolation pass ONLY if a source buffer leaked into the result (a
      runtime may reuse the source as a co-located shard);
    * already-in-layout source (the alias-guaranteed case ``device_put``
      would no-op on): one compiled identity copy — jit outputs never
      alias non-donated inputs.
    """
    target = resolve_sharding(target, getattr(x, "ndim", 0))
    if target is None:
        target = NamedSharding(mesh, P())
    sh = getattr(x, "sharding", None)
    if sh is None:
        return jax.device_put(x, target)
    if sh.is_equivalent_to(target, x.ndim):
        return _identity_copy_fn(mesh, target)(x)
    src = _buffer_ptrs(x)
    moved = jax.device_put(x, target)
    dst = _buffer_ptrs(moved)
    if src is None or dst is None or (src & dst):
        moved = _identity_copy_fn(mesh, target)(moved)
    return moved


# ---------------------------------------------------------------------------
# in-graph SPMD training step
# ---------------------------------------------------------------------------


class TrainStep(object):
    """One fully-fused SPMD training step over a device mesh.

    ``step = TrainStep(net, loss_fn, optimizer, mesh)`` then
    ``loss = step(data, label)`` runs forward + loss + backward + gradient
    reduction + optimizer update as ONE compiled XLA module per shape
    signature. Parameters/optimizer state live replicated on the mesh; the
    batch is sharded over the ``dp`` axis; GSPMD inserts the ICI
    collectives. This is the TPU-native equivalent of the reference's
    whole training stack for data parallelism: GraphExecutor fwd+bwd
    (graph_executor.cc:231-295) + kvstore reduce (comm.h:43) + fused
    optimizer ops (optimizer_op.cc) — in a single HloModule.

    Parameters
    ----------
    net : HybridBlock — initialized (or deferred-init) model
    loss_fn : gluon Loss block, or callable (out_nd, label_nd) -> loss NDArray
    optimizer : str or Optimizer with ``pure_step``
    mesh : jax Mesh from ``device_mesh()``; defaults to all devices
    batch_axis : int — which axis of data/label to shard over ``dp``
    """

    def __init__(self, net, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 optimizer_params=None, batch_axis: int = 0,
                 remat: bool = False):
        from . import optimizer as opt_mod

        #: recompute activations in backward (jax.checkpoint) — trades FLOPs
        #: for HBM, the reference's MXNET_BACKWARD_DO_MIRROR policy
        self._remat = remat
        self._net = net
        self._loss = loss_fn
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self._optimizer = optimizer
        self._mesh = mesh if mesh is not None else device_mesh()
        self._batch_axis = batch_axis
        self._dp_axis = self._mesh.axis_names[0]
        self._pvals = None          # name -> replicated jax array
        self._opt_states = None     # name -> state pytree
        self._grad_reqs = None
        self._mults = None          # name -> (lr_mult, wd_mult)
        self._t = 0
        self._step_jits: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    def _repl(self, x):
        # fresh buffer (jit outputs never alias non-donated inputs): the
        # step jit donates its param inputs, and an alias would let that
        # donation delete a buffer the caller still references. No eager
        # copy intermediate — peak init memory stays ~1x model size.
        return fresh_replicate(x, self._mesh)

    def _shard_batch(self, x, extra_lead_axes=0):
        return shard_to_mesh(x, self._mesh,
                             self._batch_axis + extra_lead_axes,
                             self._dp_axis)

    def _ensure_init(self, data_nd):
        if self._pvals is not None:
            return
        params = self._net.collect_params()
        try:
            pvals = {n: p.data()._data for n, p in params.items()}
        except Exception:
            with autograd.pause():
                self._net(data_nd)  # finish deferred init
            pvals = {n: p.data()._data for n, p in params.items()}
        self._grad_reqs = {n: p.grad_req for n, p in params.items()}
        self._mults = {n: (p.lr_mult, p.wd_mult) for n, p in params.items()}
        self._pvals = {n: self._repl(v) for n, v in pvals.items()}
        self._opt_states = {}
        def _repl_state(x):
            # master optimizer state stays f32 regardless of param dtype
            # (the reference's multi-precision mp_sgd keeps an f32 master,
            # optimizer_op.cc mp_sgd_update); also required for lax.scan
            # carry stability in multi_call — pure_step math runs in f32,
            # so a bf16-created state would change dtype across steps
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating) and \
                    x.dtype != jnp.float32:
                x = x.astype(jnp.float32)
            return self._repl(x)

        for n, p in params.items():
            if self._grad_reqs[n] != "null":
                st = self._optimizer.create_state(n, p.data())
                self._opt_states[n] = jax.tree_util.tree_map(_repl_state, st) \
                    if st is not None else None

    # ------------------------------------------------------------------
    def _core_step(self, in_fmt):
        """The single-step function ``(pvals, opt_states, t, lr, data,
        label, rng) -> (loss, new_pvals, new_opt_states)`` shared by the
        per-call jit and the multi-step ``lax.scan`` executor."""
        # in_fmt is the gluon.block._flatten format of the net's inputs
        base_fn = self._net._base_fn(in_fmt, train=True)
        diff_names = tuple(n for n, r in self._grad_reqs.items() if r != "null")
        const_names = tuple(n for n in self._pvals if n not in diff_names)
        loss_fn = self._loss
        optimizer = self._optimizer
        mults = self._mults

        def step(pvals, opt_states, t, lr, data, label, rng):
            const = {n: pvals[n] for n in const_names}

            def loss_f(dp):
                pv = dict(const)
                pv.update(dp)
                outs, aux = base_fn(pv, rng, data)
                out0 = outs[0] if isinstance(outs, tuple) else outs
                with autograd._RecordingStateScope(False, None):
                    l_nd = loss_fn(NDArray(out0, cpu()), NDArray(label, cpu()))
                loss = jnp.mean(l_nd._data)
                return loss, aux

            diff = {n: pvals[n] for n in diff_names}
            lf = jax.checkpoint(loss_f) if self._remat else loss_f
            (loss, aux), grads = jax.value_and_grad(
                lf, has_aux=True)(diff)

            new_p = dict(const)
            new_states = {}
            for n in diff_names:
                lm, wm = mults[n]
                w, s = optimizer.pure_step(
                    pvals[n], grads[n], opt_states[n], t,
                    lr * lm, optimizer.wd * wm)
                # bf16 params: f32 grads/states would silently upcast the
                # weight each step (multi-precision keeps math in f32, the
                # stored weight stays in the model's dtype)
                new_p[n] = w.astype(pvals[n].dtype)
                new_states[n] = s
            new_p.update(aux)  # BN moving stats et al.
            return loss, new_p, new_states

        return step

    def _build_step(self, in_fmt):
        repl = NamedSharding(self._mesh, P())
        return jax.jit(
            self._core_step(in_fmt),
            out_shardings=(repl, repl, repl),
            donate_argnums=(0, 1),
        )

    def _build_multi(self, in_fmt, k):
        """K training steps fused into ONE XLA module via ``lax.scan``.

        Parameters and optimizer state live in the scan carry, so the
        per-parameter input/output layout copies a single-step module pays
        on every invocation happen once per K steps, and per-execute
        dispatch overhead is amortized K-fold. This is the standard JAX
        scan-over-steps training loop; the reference's analogue is engine
        op bulking (``MXNET_EXEC_BULK_EXEC_TRAIN``,
        src/engine/threaded_engine.cc:289) which batches engine ops to cut
        per-op dispatch cost the same way."""
        core = self._core_step(in_fmt)

        def multi(pvals, opt_states, t, lr, datas, labels, rng):
            keys = jax.random.split(rng, k)

            def body(carry, xs):
                pv, st, tt = carry
                d, l, kk = xs
                loss, new_p, new_s = core(pv, st, tt, lr, d, l, kk)
                return (new_p, new_s, tt + 1.0), loss

            (pvals, opt_states, t), losses = jax.lax.scan(
                body, (pvals, opt_states, t), (datas, labels, keys))
            return losses, pvals, opt_states

        repl = NamedSharding(self._mesh, P())
        return jax.jit(
            multi,
            out_shardings=(repl, repl, repl),
            donate_argnums=(0, 1),
        )

    # ------------------------------------------------------------------
    def __call__(self, data, label):
        data_nd = data if isinstance(data, NDArray) else NDArray(
            jnp.asarray(data), cpu())
        self._ensure_init(data_nd)
        # the step counter has ONE source of truth shared with the eager
        # Updater path (optimizer.num_update): a run that interleaves this
        # in-graph step with eager Trainer.step calls (warmup/eval) must
        # not replay or skip schedule steps on either side
        self._t = max(self._t, self._optimizer.num_update) + 1
        self._optimizer.sync_num_update(self._t)

        d = self._shard_batch(data)
        l = self._shard_batch(label)
        rng = _global.next_key()
        lr = jnp.float32(self._optimizer.learning_rate)
        t = jnp.float32(self._t)

        key = (tuple(d.shape), str(d.dtype), tuple(l.shape), str(l.dtype))
        if key not in self._step_jits:
            self._step_jits[key] = self._build_step([0])
        # avals only (no live buffers): memory_analysis() must not pin a
        # batch or donated-dead params on device
        def _aval(a):
            return jax.ShapeDtypeStruct(jnp.shape(a), a.dtype)
        self._last_call = (key, self._step_jits[key], jax.tree_util.tree_map(
            _aval, (self._pvals, self._opt_states, t, lr, d, l, rng)))
        loss, self._pvals, self._opt_states = self._step_jits[key](
            self._pvals, self._opt_states, t, lr, d, l, rng)
        return NDArray(loss, cpu())

    def memory_analysis(self):
        """XLA's compiled-buffer accounting for the last single-step
        executor (CompiledMemoryStats: ``temp_size_in_bytes`` is the
        stored-activation workspace — see example/memcost for where
        ``remat`` does and does not shrink it). Call the step at least
        once first; stats are cached per input signature."""
        if getattr(self, "_last_call", None) is None:
            raise MXNetError("memory_analysis: run the step once first")
        key, jit_fn, avals = self._last_call
        cache = getattr(self, "_mem_stats", None)
        if cache is None:
            cache = self._mem_stats = {}
        if key not in cache:
            cache[key] = jit_fn.lower(*avals).compile().memory_analysis()
        return cache[key]

    # ------------------------------------------------------------------
    def multi_call(self, datas, labels):
        """Run K fused training steps in ONE device call.

        ``datas``/``labels`` carry a leading steps axis: shape
        ``(K, batch, ...)`` — one slice per step. Returns the per-step
        losses as an NDArray of shape ``(K,)``. The learning rate is
        sampled once per call, so LR schedules advance at call
        granularity. Use this for steady-state training throughput —
        per-call dispatch and parameter-I/O cost is paid once per K steps
        (see ``_build_multi``).
        """
        datas_nd = datas if isinstance(datas, NDArray) else NDArray(
            jnp.asarray(datas), cpu())
        labels_nd = labels if isinstance(labels, NDArray) else NDArray(
            jnp.asarray(labels), cpu())
        self._ensure_init(NDArray(datas_nd._data[0], cpu()))
        k = int(datas_nd._data.shape[0])
        # counter coherence with eager interleaves — see __call__
        self._t = max(self._t, self._optimizer.num_update) + k
        self._optimizer.sync_num_update(self._t)

        d = self._shard_batch(datas_nd, extra_lead_axes=1)
        l = self._shard_batch(labels_nd, extra_lead_axes=1)
        rng = _global.next_key()
        lr = jnp.float32(self._optimizer.learning_rate)
        # first fused step must see the same 1-based counter __call__ uses
        # (t=0 would e.g. zero Adam's bias correction -> NaN weights)
        t = jnp.float32(self._t - k + 1)

        key = ("multi", k, tuple(d.shape), str(d.dtype), tuple(l.shape),
               str(l.dtype))
        if key not in self._step_jits:
            self._step_jits[key] = self._build_multi([0], k)
        losses, self._pvals, self._opt_states = self._step_jits[key](
            self._pvals, self._opt_states, t, lr, d, l, rng)
        return NDArray(losses, cpu())

    # ------------------------------------------------------------------
    def copy_to_net(self):
        """Write the trained replicated parameters back into the net's
        Parameter buffers (so save_parameters/export see the result)."""
        params = self._net.collect_params()
        for n, v in self._pvals.items():
            # fresh buffer: the next step() donates (deletes) self._pvals
            params[n].data()._data = jnp.copy(v)
        return self._net

    @property
    def params(self):
        return self._pvals


class InferStep(object):
    """Batched SPMD inference executor over a device mesh.

    ``infer = InferStep(net, mesh)`` then ``out = infer(x)`` runs one
    forward in predict mode; ``outs = infer.multi_call(xs)`` runs K
    forwards (leading steps axis on ``xs``) fused into ONE XLA module via
    ``lax.scan``, paying parameter input copies and per-call dispatch once
    per K batches. The scan analogue of the reference's inference-side
    engine bulking (``MXNET_EXEC_BULK_EXEC_INFERENCE``,
    docs/faq/env_var.md:74-80); the per-batch path matches
    ``benchmark_score.py``'s protocol.

    Parameters are snapshot on first use (deployment semantics, like the
    reference's ``HybridBlock.export`` artifact). If the net's weights
    change afterwards (training, ``load_parameters``), call
    ``refresh_params()`` to re-snapshot.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, batch_axis: int = 0):
        self._net = net
        self._mesh = mesh if mesh is not None else device_mesh()
        self._batch_axis = batch_axis
        self._dp_axis = self._mesh.axis_names[0]
        self._pvals = None
        self._jits: Dict[Any, Any] = {}

    _shard_batch = TrainStep._shard_batch

    def _ensure_init(self, data_nd):
        if self._pvals is not None:
            return
        params = self._net.collect_params()
        try:
            pvals = {n: p.data()._data for n, p in params.items()}
        except Exception:
            with autograd.pause():
                self._net(data_nd)
            pvals = {n: p.data()._data for n, p in params.items()}
        repl = NamedSharding(self._mesh, P())
        self._pvals = {n: jax.device_put(v, repl) for n, v in pvals.items()}

    def refresh_params(self):
        """Re-snapshot the net's current parameter values (compiled
        executables are kept — only the param buffers are replaced)."""
        self._pvals = None

    def _build(self, k):
        base_fn = self._net._base_fn([0], train=False)

        def single(pvals, data, rng):
            outs, _aux = base_fn(pvals, rng, data)
            return outs[0] if isinstance(outs, tuple) else outs

        if k is None:
            return jax.jit(single)

        def multi(pvals, datas, rng):
            keys = jax.random.split(rng, k)  # independent randomness per
            # scanned batch (predict-mode stochastic layers)

            def body(carry, xs):
                d, kk = xs
                return carry, single(pvals, d, kk)

            _, ys = jax.lax.scan(body, None, (datas, keys))
            return ys

        return jax.jit(multi)

    def __call__(self, data):
        data_nd = data if isinstance(data, NDArray) else NDArray(
            jnp.asarray(data), cpu())
        self._ensure_init(data_nd)
        d = self._shard_batch(data_nd)
        key = (None, tuple(d.shape), str(d.dtype))
        if key not in self._jits:
            self._jits[key] = self._build(None)
        return NDArray(self._jits[key](self._pvals, d, _global.next_key()),
                       cpu())

    def multi_call(self, datas):
        datas_nd = datas if isinstance(datas, NDArray) else NDArray(
            jnp.asarray(datas), cpu())
        self._ensure_init(NDArray(datas_nd._data[0], cpu()))
        k = int(datas_nd._data.shape[0])
        d = self._shard_batch(datas_nd, extra_lead_axes=1)
        key = (k, tuple(d.shape), str(d.dtype))
        if key not in self._jits:
            self._jits[key] = self._build(k)
        return NDArray(self._jits[key](self._pvals, d, _global.next_key()),
                       cpu())
