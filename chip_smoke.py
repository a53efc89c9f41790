#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two planes users enter through, once, on the attached TPU, through
the entry points a user calls, and checks what comes out:

* **train** — ``gluon.model_zoo.vision.resnet50_v1`` at full width (224x224,
  batch 32, 1000 classes), ``gluon.Trainer`` SGD+momentum,
  ``trainplane.TrainPlane.step`` on one repeated seeded batch, fp32 then
  ``MXNET_TRAIN_DTYPE=bf16``: graph plane, one dispatch per step, no
  fallback, no recompile after the first step, falling finite loss,
  parameters and outputs resident on the TPU.
* **serve** — ``serving.DecodeEngine`` over the bench's full-size
  ``TinyDecoder`` (512 wide, 4 layers, vocab 1024, 16 slots, max_seq_len
  1152) with ``head_dim=128`` so the Pallas paged-attention kernel is on the
  path, without and with speculation: every request completes, tokens equal
  ``model.reference_generate`` on the same device, no steady-state
  recompile, all KV pages returned, ``tpu_custom_call`` in the decode step.

``--chips 4`` runs ONLY the multi-chip path and what it is compared with:
the same ResNet-50 steps on the default 4-device ``dp`` mesh and on a
1-device mesh from the same seed, losses compared step by step.

One process touches jax; no child needs the chip. Any failed check raises —
the script exits non-zero at once and prints no result line. It fails the
same way when jax finds no TPU (``JAX_PLATFORMS=cpu``) and, by import error,
in a directory that holds nothing else of the repo. The LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile
import time

TRAIN = dict(batch=32, image=224, classes=1000, steps=6, lr=0.02)
SERVE = dict(vocab=1024, layers=4, heads=4, head_dim=128, slots=16,
             max_seq_len=1152, prefill_buckets=(8, 16, 32), spec_k=3)
#: |loss_4dev - loss_1dev| <= MESH_RTOL * max(1, |loss_1dev|) at every step.
#: The two programs are the same math (GSPMD all-reduces the batch-axis
#: sums, BatchNorm statistics included), but at the chip's default matmul
#: precision (one bf16 pass) the compiler rounds in different places in
#: each: on 4 x TPU v5 lite the gap read 1.5e-3 at step 1 and at most
#: 1.7e-2 over six steps on the repeated batch (3.1e-7 at step 1 under
#: ``highest`` precision — PERF.md, PR 22). The bound was fixed first.
MESH_RTOL = 2e-2


def say(**fields):
    print(json.dumps(fields, sort_keys=True), flush=True)


def check(cond, what, **detail):
    if not cond:
        raise SystemExit("chip_smoke: FAILED %s %s"
                         % (what, json.dumps(detail, sort_keys=True,
                                             default=str)))


def _only_device(arr):
    devs = arr.devices()
    check(len(devs) == 1, "array on exactly one device", devices=devs)
    return next(iter(devs))


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _fallbacks():
    from mxnet_tpu import trainplane

    return sum(s["value"] for s in trainplane.FALLBACKS.series())


def _mean_loss(loss):
    import numpy as np

    return float(np.asarray(loss.asnumpy(), dtype=np.float32).mean())


def _seeded_batch(seed, size):
    import numpy as np

    rng = np.random.RandomState(seed)
    x = rng.randn(size["batch"], 3, size["image"],
                  size["image"]).astype(np.float32)
    y = rng.randint(0, size["classes"], size["batch"]).astype(np.float32)
    return x, y


def train_steps(mx, ctx, seed, size, tag, mesh=None, make_net=None):
    """A handful of TrainPlane steps on one repeated seeded batch; returns
    (plane, net, per-step mean losses). Asserts the plane contract."""
    from mxnet_tpu import gluon, nd, telemetry, trainplane
    from mxnet_tpu.fastpath import cache

    if make_net is None:
        make_net = gluon.model_zoo.vision.resnet50_v1
    mx.random.seed(seed)
    net = make_net(classes=size["classes"])
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": size["lr"], "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    plane = trainplane.TrainPlane(net, loss_fn, trainer, mesh=mesh)
    xs, ys = _seeded_batch(seed, size)
    x, y = nd.array(xs, ctx=ctx), nd.array(ys, ctx=ctx)

    fallbacks = _fallbacks()
    graph0 = telemetry.STEP_DISPATCHES.value(plane="graph")
    eager0 = telemetry.STEP_DISPATCHES.value(plane="eager")
    compiles0 = telemetry.RECOMPILES.value(site="trainplane.step")
    compile_s0 = telemetry.COMPILE_SECONDS.value(site="trainplane.step")
    hits0, misses0 = cache.cache_counts()
    losses, walls, compiles_after_first = [], [], None
    loss = None
    for _ in range(size["steps"]):
        t0 = time.perf_counter()
        loss = plane.step(x, y)
        loss._data.block_until_ready()
        walls.append(round(time.perf_counter() - t0, 4))
        losses.append(_mean_loss(loss))
        if compiles_after_first is None:
            compiles_after_first = telemetry.RECOMPILES.value(
                site="trainplane.step")
    recompiles = telemetry.RECOMPILES.value(site="trainplane.step") \
        - compiles_after_first
    hits1, misses1 = cache.cache_counts()
    say(phase=tag, plane=plane.plane, mesh_devices=plane.mesh.devices.size,
        first_loss=losses[0], last_loss=losses[-1], losses=losses,
        step_wall_s=walls,
        compile_s=round(telemetry.COMPILE_SECONDS.value(
            site="trainplane.step") - compile_s0, 2),
        compiles=compiles_after_first - compiles0,
        recompiles_after_first_step=recompiles,
        compile_cache_hits=hits1 - hits0,
        compile_cache_misses=misses1 - misses0)

    check(plane.plane == "graph", "%s: graph plane" % tag, plane=plane.plane)
    check(_fallbacks() == fallbacks, "%s: no trainplane fallback" % tag,
          fallbacks=trainplane.FALLBACKS.series())
    check(telemetry.STEP_DISPATCHES.value(plane="graph") - graph0
          == size["steps"]
          and telemetry.STEP_DISPATCHES.value(plane="eager") == eager0,
          "%s: one graph dispatch per step" % tag)
    check(recompiles == 0, "%s: zero recompiles after the first step" % tag,
          recompiles=recompiles)
    check(all(l == l and abs(l) != float("inf") for l in losses),
          "%s: finite loss" % tag, losses=losses)
    check(losses[-1] < losses[0], "%s: loss falls" % tag, losses=losses)
    return plane, net, losses, loss


def train_phase(mx, ctx, device, seed, size, make_net=None):
    """Full-width train plane, fp32 then bf16, on one chip."""
    for dtype in ("fp32", "bf16"):
        os.environ["MXNET_TRAIN_DTYPE"] = dtype
        try:
            plane, net, _losses, loss = train_steps(
                mx, ctx, seed, size, "train_" + dtype, make_net=make_net)
        finally:
            os.environ.pop("MXNET_TRAIN_DTYPE", None)
        leaf = next(iter(net.collect_params().values())).data(ctx)._data
        check(_only_device(leaf) == device and _only_device(loss._data)
              == device, "train_%s: parameters and step output resident on "
              "%s" % (dtype, device), param=leaf.devices(),
              loss=loss._data.devices())
        check(str(leaf.dtype) == {"fp32": "float32",
                                  "bf16": "bfloat16"}[dtype],
              "train_%s: parameter dtype" % dtype, dtype=str(leaf.dtype))


def default_ctx_probe(mx, device, seed):
    """What the same steps do when the user passes no ``ctx`` at all:
    reported, and the compute device asserted (a step that quietly ran on
    the host CPU would be a trainplane bug)."""
    from mxnet_tpu import gluon, nd, trainplane

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(10))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  trainer)
    xs, ys = _seeded_batch(seed, dict(batch=32, image=8, classes=10))
    x, y = nd.array(xs.reshape(32, -1)), nd.array(ys)
    param = next(iter(net.collect_params().values()))
    before = sorted(str(d) for d in x._data.devices())
    loss = None
    for _ in range(3):
        loss = plane.step(x, y)
    after = sorted(str(d) for d in x._data.devices())
    leaf = param.data()._data
    say(phase="default_ctx", plane=plane.plane,
        batch_devices_before=before, batch_devices_after=after,
        param_devices_after=sorted(str(d) for d in leaf.devices()),
        loss_devices=sorted(str(d) for d in loss._data.devices()),
        batch_copied_host_to_device_every_step=after != [str(device)],
        params_moved_once_then_resident=leaf.devices() == {device})
    check(plane.plane == "graph" and _only_device(loss._data) == device
          and leaf.devices() == {device},
          "default ctx: the step computes on %s" % device,
          loss=loss._data.devices(), param=leaf.devices())


def serve_phase(device, seed, size):
    """Decode engine with the Pallas kernel on the path, without and with
    speculation, token-exact against the dense no-cache oracle."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.fastpath import cache

    model = serving.TinyDecoder(vocab_size=size["vocab"],
                                num_layers=size["layers"],
                                num_heads=size["heads"],
                                head_dim=size["head_dim"])
    params = model.init_params(seed)
    check(_only_device(params["embed"]) == device,
          "serve: weights resident on %s" % device)
    # repetitive prompts (a motif repeated): random ones barely match the
    # prompt-lookup draft's n-grams, so speculation would never accept
    rng = np.random.RandomState(seed)
    reqs = []
    # (every distinct total length costs the no-cache oracle one compile,
    # so the mix stays under ~45 tokens a sequence)
    for plen, new in ((5, 8), (8, 12), (8, 16), (12, 24), (16, 8),
                      (16, 16), (20, 12), (24, 20)):
        motif = rng.randint(1, size["vocab"], 3 + len(reqs) % 3)
        prompt = np.tile(motif, plen // len(motif) + 1)[:plen]
        reqs.append((prompt.astype(np.int32), new))
    t0 = time.perf_counter()
    want = [model.reference_generate(params, p, n) for p, n in reqs]
    say(phase="serve_reference", requests=len(reqs),
        tokens=int(sum(len(w) for w in want)),
        wall_s=round(time.perf_counter() - t0, 2))

    for spec_k in (0, size["spec_k"]):
        tag = "serve_spec%d" % spec_k
        hits0, misses0 = cache.cache_counts()
        eng = serving.DecodeEngine(
            model, params, num_slots=size["slots"],
            max_seq_len=size["max_seq_len"],
            prefill_buckets=size["prefill_buckets"], name=tag, timeout_ms=0,
            spec_k=spec_k,
            spec_draft="prompt_lookup" if spec_k else None)
        try:
            t0 = time.perf_counter()
            compiles = eng.warmup()
            warm_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            futs = [eng.submit(p, n) for p, n in reqs]
            got = [f.result(timeout=600) for f in futs]
            wall = time.perf_counter() - t0
            stats = eng.stats()
            packed = np.zeros(
                (eng._packed_rows, size["slots"] * (spec_k + 1)), np.int32)
            step_text = eng._step.lower(
                eng._params, packed, eng._no_prev, *eng._cache.operands,
                eng._cache.page_table).as_text()
        finally:
            eng.close()
        exact = [bool(np.array_equal(g, w)) for g, w in zip(got, want)]
        hits1, misses1 = cache.cache_counts()
        spec = stats.get("speculative") or {}
        say(phase=tag, requests=len(reqs),
            tokens_generated=int(sum(len(g) for g in got)),
            token_exact_requests=sum(exact), warmup_compiles=compiles,
            warmup_s=round(warm_s, 2), serve_wall_s=round(wall, 3),
            steady_state_recompiles=stats.get("steady_state_recompiles"),
            pages_in_use=stats["kvcache"]["pages_in_use"],
            accepted_per_tick=spec.get("accepted_per_tick"),
            ticks=stats["ticks"], steps_overlapped=stats["steps_overlapped"],
            tpu_custom_call_in_decode_step="tpu_custom_call" in step_text,
            compile_cache_hits=hits1 - hits0,
            compile_cache_misses=misses1 - misses0)
        check(all(exact), "%s: tokens equal reference_generate" % tag,
              exact=exact)
        check(stats.get("steady_state_recompiles") == 0,
              "%s: zero steady-state recompiles" % tag,
              got=stats.get("steady_state_recompiles"))
        check(stats["kvcache"]["pages_in_use"] == 0,
              "%s: all KV pages returned" % tag, kvcache=stats["kvcache"])
        # (off-TPU — a CPU rehearsal of this function — the dispatcher
        # takes the dense reference by design)
        check("tpu_custom_call" in step_text or device.platform != "tpu",
              "%s: Pallas kernel in the lowered decode step" % tag)
        # one step in flight without a draft; with one, a step is fetched
        # before the next is packed
        check((stats["steps_overlapped"] == 0) if spec_k
              else (stats["steps_overlapped"] > stats["ticks"] // 2),
              "%s: steps overlapped as the engine's order says" % tag,
              ticks=stats["ticks"], overlapped=stats["steps_overlapped"])
        if spec_k:
            check(spec.get("accepted_per_tick", 0) > 1.0,
                  "%s: speculation accepts drafts" % tag, speculative=spec)
    say(phase="serve_memory", peak_bytes_in_use=_peak_bytes(device))


def mesh_phase(mx, ctx, seed, size, dump_dir, n, make_net=None):
    """The multi-chip path: the same ResNet-50 steps on the default mesh
    over every local device and on a 1-device mesh, same seed."""
    import jax

    from mxnet_tpu import parallel

    plane_n, net_n, losses_n, _ = train_steps(
        mx, ctx, seed, size, "mesh_%ddev" % n, make_net=make_net)
    devices = list(plane_n.mesh.devices.flat)
    check(len(set(devices)) == n and plane_n.mesh.axis_names == ("dp",),
          "default mesh spans %d distinct devices over dp" % n,
          mesh=str(plane_n.mesh))
    xs, _ys = _seeded_batch(seed, size)
    fed = parallel.shard_to_mesh(mx.nd.array(xs, ctx=ctx), plane_n.mesh)
    shards = {s.device: s.data.shape for s in fed.addressable_shards}
    check(len(shards) == n and set(shards.values())
          == {(size["batch"] // n,) + xs.shape[1:]}
          and fed.sharding.spec[0] == "dp",
          "batch sharded over dp across %d distinct devices" % n,
          shards={str(d): s for d, s in shards.items()},
          spec=str(fed.sharding.spec))
    leaf = next(iter(net_n.collect_params().values())).data(ctx)._data
    check(leaf.devices() == set(devices),
          "parameters live on all %d devices" % n, devices=leaf.devices())
    dumped = [f for f in glob.glob(os.path.join(dump_dir, "*"))
              if "step" in os.path.basename(f) and f.endswith(".txt")]
    with_allreduce = [f for f in dumped if "all-reduce" in open(f).read()]
    say(phase="mesh_compiled_step", dumped_step_modules=len(dumped),
        modules_with_all_reduce=len(with_allreduce))
    check(with_allreduce, "compiled %d-device step contains an all-reduce"
          % n, dumped=[os.path.basename(f) for f in dumped])

    _p1, _net1, losses_1, _ = train_steps(
        mx, ctx, seed, size, "mesh_1dev", make_net=make_net,
        mesh=parallel.device_mesh(devices=jax.devices()[:1]))
    gaps = [abs(a - b) / max(1.0, abs(b))
            for a, b in zip(losses_n, losses_1)]
    say(phase="mesh_compare", losses_ndev=losses_n, losses_1dev=losses_1,
        rel_gap_per_step=gaps, tolerance=MESH_RTOL,
        peak_bytes_in_use_device0=_peak_bytes(jax.devices()[0]))
    check(max(gaps) <= MESH_RTOL,
          "%d-device and 1-device losses agree step by step" % n,
          gaps=gaps, tolerance=MESH_RTOL)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-device data-parallel path and "
                    "its 1-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dump_dir = None
    if args.chips > 1:
        # the only way to read the COMPILED step (where GSPMD's all-reduce
        # lives) without reaching into the plane: have XLA dump it. Only a
        # real compile dumps, so this mode keeps the compile cache off.
        dump_dir = tempfile.mkdtemp(prefix="chip_smoke_xla_")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_dump_to=%s "
            "--xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*step.*"
            % dump_dir).strip()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU chip — jax.devices() reports %d %r "
            "device(s) (JAX_PLATFORMS=%r); this script only passes on the "
            "chip" % (len(devices), devices[0].platform,
                      os.environ.get("JAX_PLATFORMS")))
    if len(devices) < args.chips:
        raise SystemExit("chip_smoke: --chips %d but jax reports %d TPU "
                         "device(s)" % (args.chips, len(devices)))

    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.fastpath import cache

    if args.chips > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    else:
        cache_dir = cache.configure()
    # never lean on a git-ignored binary that happens to be on disk: the
    # native host library is rebuilt from the committed src/*.cc
    native = _native.build_lib(force=True)
    import jaxlib

    say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        device_kind=devices[0].device_kind, devices=len(devices),
        compile_cache_dir=cache_dir,
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        libmxtpu="built in this run from src/*.cc" if native
        else "unavailable (pure-Python host paths)")
    check(_native.native_available() == bool(native),
          "native library loads when it builds")

    ctx = mx.tpu(0)
    check(ctx.jax_device() == devices[0], "mx.tpu(0) is jax.devices()[0]")
    t0 = time.perf_counter()
    if args.chips > 1:
        check(len(devices) == args.chips, "exactly %d devices" % args.chips,
              devices=len(devices))
        mesh_phase(mx, ctx, args.seed, TRAIN, dump_dir, args.chips)
    else:
        train_phase(mx, ctx, devices[0], args.seed, TRAIN)
        say(phase="train_memory", peak_bytes_in_use=_peak_bytes(devices[0]))
        default_ctx_probe(mx, devices[0], args.seed)
        serve_phase(devices[0], args.seed, SERVE)
    say(phase="done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
