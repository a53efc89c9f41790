#!/usr/bin/env python3
"""Find the knee of an open-loop traffic mix, once, on the chip.

    python3 benchmark/sweep.py --workload <name> --rates 1.5,2,2.5,3,3.5 --seconds 40

One process, one engine: for each rate the mix's schedule is offered for
``--seconds``, then the engine drains completely before the next rate. The
knee is the highest rate at which the queue at the window's end is no deeper
than at its middle. The table goes into ``PERF.md`` and the traffic file's
``why``; a stated share of the knee goes into the traffic file as
``rate_per_s`` (0.5 x since PR 44). Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="override the configuration's request-trace rate")
    args = ap.parse_args(argv)

    import run as harness
    import stats as stats_mod
    import traffic as traffic_mod

    manifest = harness.load_json("BENCHMARK.json")
    workload = harness.find(manifest["workloads"], args.workload, "workload")
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: no TPU")
    from mxnet_tpu.fastpath import cache

    cache.configure(os.path.join(harness.ROOT, ".jax_cache"))
    args.trace, args.rehearse, args.control = 0, False, False
    cell = harness.Cell(manifest, workload, args)
    if args.trace_sample is not None:
        cell.config["trace_sample"] = args.trace_sample
    driver = harness.load_module("drivers", cell.config["driver"])
    eng, _params, _n = driver.setup_engine(cell)
    vocab = cell.config["model"]["vocab_size"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        reqs = traffic_mod.open_loop(mix, args.seed + i, args.seconds, vocab)
        win = driver.offer(cell, eng, reqs, args.seconds)
        done = [r for r in reqs if r["tokens"] is not None
                and r["t_done"] < win["end"]]
        driver.wait_for(reqs, win["end"] + 600.0)
        total = [1e3 * (r["t_done"] - (win["t0"] + r["due_s"]))
                 for r in reqs if r["tokens"] is not None]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "finished": len(total),
            "tok_per_s_in_window": sum(len(r["tokens"]) for r in done)
            / args.seconds,
            "queue_mid": win["queue_mid"], "queue_end": win["queue_end"],
            "request_p50_ms": stats_mod.median(total),
            "request_p90_ms": stats_mod.percentile(total, 90.0),
            "kv_pages_peak": win["pages_peak"],
            "slot_occupancy_cumulative": win["stats"]["slot_occupancy"],
            "tpot_p50_ms": win["stats"].get("tpot_p50_ms"),
            "drain_s": time.perf_counter() - win["end"],
            "trace_sample": cell.config.get("trace_sample", 1.0)}),
            flush=True)
    print(json.dumps({"memory_peak_bytes": cell.memory_peak(jax.devices())}),
          flush=True)
    eng.close(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
