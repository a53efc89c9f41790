"""Profiling a training workload (reference ``example/profiler``).

Drives the reference profiler workflow end to end: ``set_config`` →
``set_state('run')`` → train → ``pause``/``resume`` around excluded work
→ ``dumps()`` aggregate table → ``dump()`` Chrome-trace JSON, and prints
where the time actually went (operator vs executor categories).

TPU-idiomatic notes: per-op host timings here measure *dispatch* (op
submission + any blocking fetch), not device kernels — under whole-graph
XLA the per-op device story lives in the ``jax.profiler`` xplane trace,
which `profiler.set_config(jax_trace_dir=...)` captures alongside
(``python3 benchmark/run.py --workload <cell> --trace 1`` records one on
the chip; tpu_profile_r05/ has a live chip's). Both views ship: MXNet-style aggregates for API parity, xplane
for kernel truth.

Run:  python example/profiler/profiler_demo.py
"""
import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, nd, profiler  # noqa: E402
from mxnet_tpu.gluon import Trainer, loss as gloss, nn  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=32)
    args = ap.parse_args()

    out_json = str(Path(tempfile.mkdtemp(prefix="mxtpu_prof_")) /
                   "profile.json")
    profiler.set_config(filename=out_json, profile_all=True)

    mx.random.seed(7)
    rs = np.random.RandomState(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"),
            nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize(mx.initializer.Xavier())
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})

    x = nd.array(rs.rand(args.batch_size, 784).astype(np.float32))
    y = nd.array(rs.randint(0, 10, args.batch_size).astype(np.float32))

    # warmup OUTSIDE the profiled window (compile time would swamp it)
    with autograd.record():
        loss = lossfn(net(x), y)
    loss.backward()
    trainer.step(args.batch_size)

    profiler.set_state("run")
    t0 = time.time()
    for step in range(args.steps):
        if step == args.steps // 2:
            profiler.pause()        # excluded section (e.g. eval/io)
            _ = net(x).asnumpy()
            profiler.resume()
        with autograd.record():
            loss = lossfn(net(x), y)
        loss.backward()
        trainer.step(args.batch_size)
    loss.asnumpy()
    wall = time.time() - t0
    profiler.set_state("stop")

    table = profiler.dumps(reset=False)
    print(table[:1500])
    profiler.dump()

    with open(out_json) as f:
        events = json.load(f)["traceEvents"]
    op_events = [e for e in events if e.get("ph") == "X"]
    cats = {}
    for e in op_events:
        c = e.get("cat", "?")
        cats.setdefault(c, [0, 0.0])
        cats[c][0] += 1
        cats[c][1] += e.get("dur", 0) / 1e6
    print("profiled %.2fs wall; chrome trace at %s" % (wall, out_json))
    for c, (n, secs) in sorted(cats.items(), key=lambda kv: -kv[1][1]):
        print("  %-12s %5d events %7.3fs" % (c, n, secs))

    ok = bool(op_events) and "FullyConnected" in table
    print("profiler %s" % ("CAPTURED" if ok else "missed"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
