"""Driver of training cells: a gluon net through ``gluon.Trainer`` and
``trainplane.TrainPlane``, fed as a user's loop feeds it.

The configuration names the net by import path and keyword arguments and the
reference by file; the traffic file gives the batch per chip and the rotation.
Set-up builds ONE plane, drives it through its first three steps (which
compile and warm it) on the rotation's first three batches through the same
feed and call the window uses, and hands that same plane to the window.
"""
from __future__ import annotations

import importlib
import json
import os
import statistics
import time

import numpy as np

import compare
import traffic as traffic_mod
import weights as weights_mod

CHECK_STEPS = 3
IN_FLIGHT = 2


def _resolve(path):
    mod, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def run(cell):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel, telemetry, trainplane

    cfg, model = cell.config, cell.config["model"]
    hyper = cfg["optimizer"]
    if cell.control:
        # the program's own lower-precision path, in the program's place
        os.environ["MXNET_TRAIN_DTYPE"] = "bf16"
    devs = jax.devices()
    ctx = mx.cpu(0) if cell.rehearse else mx.tpu(0)
    mesh = None
    if len(devs) > cell.chips:
        mesh = parallel.device_mesh(devices=devs[:cell.chips])
    batches = traffic_mod.train_feed(cell.traffic, cell.seed, cell.chips,
                                     model["image"], model["classes"])
    batch = batches[0][0].shape[0]

    # -- the program: net, trainer, plane; weights from the seed ---------
    specs = cell.reference.param_specs(model)
    w0 = weights_mod.make(specs, cell.seed)
    net = _resolve(cfg["factory"])(**cfg["factory_kwargs"])
    params = net.collect_params()
    names = [n[len(params.prefix):] for n in params.keys()]
    if names != list(specs):
        raise SystemExit("benchmark: the reference's parameter names are "
                         "not the program's")
    for n, p in zip(names, params.values()):
        p._load_init(nd.NDArray(w0[n], ctx), ctx)
    net.hybridize()
    trainer = gluon.Trainer(params, cfg["optimizer_name"], dict(hyper))
    loss_fn = _resolve(cfg["loss"])()
    plane = trainplane.TrainPlane(net, loss_fn, trainer, mesh=mesh)

    def feed(i):
        x, y = batches[i % len(batches)]
        with cell.span("bench.feed"):
            return nd.array(x, ctx=ctx), nd.array(y, ctx=ctx)

    def step(i):
        x, y = feed(i)
        with cell.span("bench.step"):
            return plane.step(x, y)

    # -- first steps: warm-up AND what the reference is compared with ----
    rows = [(i, p) for i, p in enumerate(trainer._params)
            if p.grad_req != "null"]
    row_names = [p.name[len(params.prefix):] for _i, p in rows]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for v in leaves])
    diffs = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(a, b)])

    def state_leaves(idx):
        return jax.tree_util.tree_leaves(trainer._updaters[0].states[idx])

    def weight_now(idx, p):
        st = state_leaves(idx)       # (fp32 master, momentum) under bf16
        return st[0] if len(st) > 1 else p.data(ctx)._data

    losses, mom_norms = [], None
    for i in range(CHECK_STEPS):
        loss = step(i)
        losses.append(loss)
        if i == 0:
            mom_norms = norms([state_leaves(idx)[-1] for idx, _p in rows])
    delta_norms = diffs([weight_now(idx, p) for idx, p in rows],
                        [w0[n] for n in row_names])
    lr = float(hyper["learning_rate"])
    got = {
        "losses": [float(np.asarray(l.asnumpy(), np.float32).mean())
                   for l in losses],
        "grad_norms": {n: float(v) / lr
                       for n, v in zip(row_names, mom_norms)},
        "delta_norms": {n: float(v) for n, v in zip(row_names, delta_norms)},
    }
    del w0, losses, mom_norms, delta_norms
    if plane.plane != "graph":
        raise SystemExit("benchmark: TrainPlane fell to the %r plane"
                         % plane.plane)
    recompiles0 = telemetry.RECOMPILES.value(site="trainplane.step")
    dispatches0 = telemetry.STEP_DISPATCHES.value(plane="graph")

    # -- the window: steps back to back, one wait at the end -------------
    t0 = cell.setup_done()
    warm = {k: len(v) for k, v in cell.spans.items()}
    pending, steps, starts = [], 0, []
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= cell.seconds:
            break
        starts.append(elapsed)
        cell.trace_tick(elapsed)
        pending.append(step(CHECK_STEPS + steps)._data)
        steps += 1
        if len(pending) > IN_FLIGHT:
            with cell.span("bench.wait"):
                pending.pop(0).block_until_ready()
    with cell.span("bench.wait"):
        last = pending[-1]
        last.block_until_ready()
    window = time.perf_counter() - t0
    cell.trace_stop()
    # a host that stalls shows as a few step intervals far over the median
    gaps = sorted(((b - a, a) for a, b in zip(starts, starts[1:] + [window])),
                  reverse=True)
    last_loss = float(np.asarray(last, np.float32).mean())
    peak = cell.memory_peak(devs)
    counters = {
        "steps": steps,
        "batch": batch,
        "window_s": window,
        "step_interval_p50_s": statistics.median(g for g, _at in gaps),
        "step_intervals_longest_s_at_s": [[round(g, 4), round(at, 2)]
                                          for g, at in gaps[:3]],
        "span_longest_s": {k: round(max(v[warm.get(k, 0):]), 4)
                           for k, v in cell.spans.items()},
        "train_recompiles": telemetry.RECOMPILES.value(
            site="trainplane.step") - recompiles0,
        "graph_dispatches": telemetry.STEP_DISPATCHES.value(plane="graph")
        - dispatches0,
        "mesh_devices": int(plane.mesh.devices.size),
    }

    # -- free the program's state, then follow the same steps plainly ----
    shard = replicated = None
    if cell.chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        plane_mesh = plane.mesh
        replicated = NamedSharding(plane_mesh, P())

        def shard(a):
            return jax.device_put(a, NamedSharding(plane_mesh, P("dp")))
    trainer._updaters[0].states.clear()
    for p in params.values():
        p._data = None
        p._grad = None
    del pending, last, plane, trainer, net, params
    t_ref = time.perf_counter()
    ref_w0 = weights_mod.make(specs, cell.seed, sharding=replicated)
    want = cell.reference.first_steps(
        model, ref_w0,
        [(jnp.asarray(x), jnp.asarray(y)) for x, y in batches[:CHECK_STEPS]],
        lr, float(hyper["momentum"]), shard=shard)
    compared = compare.train_rows(got, want, cfg["limits"])
    print(json.dumps({"phase": "leaf_gap_summary", **{
        k: compare.leaf_gap_summary(got[k], want[k])
        for k in ("grad_norms", "delta_norms")}}), flush=True)
    compared.append({"what": "last_loss_finite", "value": float(
        np.isfinite(last_loss)), "limit": 1.0,
        "ok": bool(np.isfinite(last_loss))})
    compared.append({"what": "train_recompiles",
                     "value": counters["train_recompiles"], "limit": 0,
                     "ok": counters["train_recompiles"] == 0})
    compared.append({"what": "one_graph_dispatch_per_step",
                     "value": counters["graph_dispatches"], "limit": steps,
                     "ok": counters["graph_dispatches"] == steps})
    counters["reference_s"] = time.perf_counter() - t_ref
    return {
        "end_to_end": {"train_img_per_s": steps * batch / window},
        "attempted": steps, "failed": 0,
        "correct": all(r["ok"] for r in compared),
        "compared": compared, "counters": counters,
        "memory_peak_bytes": peak,
        "span_medians_ms": {k: 1e3 * statistics.median(v)
                            for k, v in cell.spans.items()},
    }
