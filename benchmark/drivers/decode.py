"""Driver of serving cells: a paged-decode model through
``serving.DecodeEngine``, offered an open-loop schedule from one thread.

The configuration names the model by import path and keyword arguments, the
engine's keyword arguments and the reference by file; the traffic file gives
the arrival rate and the length distributions. Weights are made on the device
from the seed, in the type they are served in. Latencies are taken from when a
request was DUE, not from when it was submitted.
"""
from __future__ import annotations

import functools
import gc
import json
import importlib
import time

import numpy as np

import compare
import stats as stats_mod
import traffic as traffic_mod
import weights as weights_mod

DRAIN_SECONDS = 15.0   # unless the traffic file says `drain_s`
CHECK_REQUESTS = 8
POLL_SECONDS = 0.25


def _resolve(path):
    mod, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def _read_request_traces(tracing, telemetry, n_skip, reqs):
    """``first_token`` instants and ``tick`` events of the window's requests
    from their request traces: the i-th trace minted after the warm-up is
    the i-th submit."""
    ids = tracing.trace_ids()[n_skip:]
    submitted = [r for r in reqs if r["submitted"]]
    if len(ids) != len(submitted):
        raise SystemExit("benchmark: %d request traces for %d submits — "
                         "tracing.set_sample(1.0) did not hold"
                         % (len(ids), len(submitted)))
    for tid, r in zip(ids, submitted):
        evs = telemetry.get_trace(tid)["events"]
        sub = next(e for e in evs if e["kind"] == "submit")
        if sub["prompt_tokens"] != r["prompt"].size \
                or sub["max_new"] != r["max_new"]:
            raise SystemExit("benchmark: request traces out of order")
        first = [e["t"] for e in evs if e["kind"] == "first_token"]
        r["t_first"] = first[0] if first else None
        r["ticks"] = [(e["t"], e["token_index"]) for e in evs
                      if e["kind"] == "tick"]


def setup_engine(cell):
    """Weights from the seed, the model, the engine, warmed up; returns
    ``(engine, params, warm-up compile count)``."""
    from mxnet_tpu.telemetry import tracing

    cfg = cell.config
    specs = cell.reference.param_specs(cfg["model"])
    params = weights_mod.make(specs, cell.seed)
    model = _resolve(cfg["factory"])(**cfg["factory_kwargs"])
    tracing.set_sample(float(cfg.get("trace_sample", 1.0)))
    eng = _resolve(cfg["engine_factory"])(model, params, name="bench",
                                          **cfg["engine"])
    warm_compiles = eng.warmup()
    # two short requests through the live path before any clock starts
    prompt = np.arange(1, 9, dtype=np.int32)
    for fut in [eng.submit(prompt, 4) for _ in range(2)]:
        fut.result(timeout=600)
    return eng, params, warm_compiles


def _on_done(r, fut):
    r["t_done"] = time.perf_counter()
    err = fut.exception()
    if err is None:
        r["tokens"] = fut.result()
    else:
        r["error"] = repr(err)


def _submit(cell, eng, r, now):
    r["t_submit"] = now
    try:
        with cell.span("bench.submit"):
            fut = eng.submit(r["prompt"], r["max_new"])
    except Exception as exc:  # noqa: BLE001 - a refusal is a failed
        # request, counted; the run goes on
        r["error"] = repr(exc)
        return
    r["submitted"] = True
    fut.add_done_callback(functools.partial(_on_done, r))


def offer(cell, eng, reqs, seconds):
    """Offer ``reqs`` on their schedule for ``seconds`` from one thread;
    returns the window's clock and what was sampled inside it. Requests are
    marked in place (``t_submit``, ``submitted``, and by the engine's
    callback ``t_done``, ``tokens`` or ``error``). EVERY request of the
    schedule is submitted: what a stall of this loop left over when the
    window closed goes in late (``gen_late_p90_ms`` shows it), never
    skipped. A traced run's span is the window's last seconds
    (``trace_s`` of the traffic file, else the harness's default) and the
    profiler is stopped here, after the loop: ``t_free`` is the instant that
    stop returned, which the drain counts from."""
    for r in reqs:
        r.update(submitted=False, t_done=None, tokens=None, error=None)
    pages_peak, queue_mid, nxt = 0, None, 0
    trace_s = cell.traffic.get("trace_s")
    t0 = cell.setup_done()
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        cell.trace_tick(now - t0, at_end=True, span=trace_s)
        if queue_mid is None and now - t0 >= seconds / 2.0:
            queue_mid = eng.queue_depth()
        if nxt < len(reqs) and now >= t0 + reqs[nxt]["due_s"]:
            _submit(cell, eng, reqs[nxt], now)
            nxt += 1
            continue
        pages_peak = max(pages_peak, eng.kvcache_stats()["pages_in_use"])
        wake = min(end, now + POLL_SECONDS)
        if nxt < len(reqs):
            wake = min(wake, t0 + reqs[nxt]["due_s"])
        with cell.span("bench.wait"):
            time.sleep(max(0.0, wake - time.perf_counter()))
    for r in reqs[nxt:]:
        _submit(cell, eng, r, time.perf_counter())
    win = {"t0": t0, "end": end, "pages_peak": pages_peak,
           "queue_mid": queue_mid, "queue_end": eng.queue_depth(),
           "stats": eng.stats()}
    cell.trace_stop()
    win["t_free"] = time.perf_counter()
    return win


def wait_for(reqs, deadline):
    """Sleep until every submitted request has resolved or ``deadline``."""
    while time.perf_counter() < deadline and any(
            r["submitted"] and r["t_done"] is None for r in reqs):
        time.sleep(0.05)


def run(cell):
    import jax

    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import tracing

    cfg, model_cfg = cell.config, cell.config["model"]
    devs = jax.devices()
    eng, params, warm_compiles = setup_engine(cell)
    reqs = traffic_mod.open_loop(cell.traffic, cell.seed, cell.seconds,
                                 model_cfg["vocab_size"])
    n_skip = len(tracing.trace_ids())
    pool_pages = eng.kvcache_stats()["pages_capacity"]

    win = offer(cell, eng, reqs, cell.seconds)
    t0, end, window_stats = win["t0"], win["end"], win["stats"]
    pages_peak, queue_end = win["pages_peak"], win["queue_end"]
    done_in_window = [r for r in reqs if r["tokens"] is not None
                      and stats_mod.in_window(r["t_done"], t0, cell.seconds)]
    tokens_in_window = sum(len(r["tokens"]) for r in done_in_window)

    # -- drain: requests still in flight get DRAIN_SECONDS more, counted
    # from when the profiler's stop (a traced run's) gave the thread back
    wait_for(reqs, win["t_free"]
             + float(cell.traffic.get("drain_s", DRAIN_SECONDS)))
    t_cap = time.perf_counter()
    peak = cell.memory_peak(devs)
    unfinished = [r for r in reqs if r["tokens"] is None]
    _read_request_traces(tracing, telemetry, n_skip, reqs)
    eng.close(drain=False)
    end_stats = eng.stats()
    pages_left = end_stats["kvcache"]["pages_in_use"]

    def since_due(r, t):
        return 1e3 * ((t if t is not None else t_cap) - (t0 + r["due_s"]))

    ttft = [since_due(r, r.get("t_first") if r["tokens"] is not None
                      else None) for r in reqs]
    total = [since_due(r, r["t_done"] if r["tokens"] is not None else None)
             for r in reqs]
    late = [1e3 * (r["t_submit"] - (t0 + r["due_s"])) for r in reqs
            if "t_submit" in r]
    n = len(reqs)
    tail = stats_mod.supported_tail(n)
    if not cell.rehearse:
        print(json.dumps({
            "phase": "latency", "requests": n, "supported_tail": tail,
            "samples_beyond_p90": stats_mod.samples_beyond(n, 90.0),
            "ttft_median_ms": stats_mod.median(ttft),
            "request_median_ms": stats_mod.median(total),
            "ttft_tail_ms": stats_mod.percentile(ttft, tail),
            "request_tail_ms": stats_mod.percentile(total, tail),
            "queued_at_mid": win["queue_mid"], "queued_at_end": queue_end}),
            flush=True)
    end_to_end = {
        "decode_tok_per_s": tokens_in_window / cell.seconds,
        "ttft_p50_ms": stats_mod.percentile(ttft, 50.0),
        "request_p50_ms": stats_mod.percentile(total, 50.0),
        "ttft_p90_ms": stats_mod.percentile(ttft, 90.0),
        "request_p90_ms": stats_mod.percentile(total, 90.0),
    }
    counters = {
        "requests": n,
        "completed_in_window": len(done_in_window),
        "tokens_in_window": tokens_in_window,
        "queued_at_window_end": queue_end,
        "warmup_compiles": warm_compiles,
        "decode_recompiles": end_stats.get("steady_state_recompiles"),
        "kv_pages_peak": pages_peak,
        "kv_pages_pool": pool_pages,
        "slot_occupancy": window_stats["slot_occupancy"],
        "tpot_p50_ms": window_stats.get("tpot_p50_ms"),
        "gen_late_p90_ms": stats_mod.percentile(late, 90.0),
        "errors": [r["error"] for r in unfinished][:5],
    }
    if cell.trace_span is not None:
        # K/V rows the decode ticks inside the traced span had to read: at a
        # tick a sequence holds its prompt and the tokens produced so far
        lo, hi = cell.trace_span
        in_span = [r["prompt"].size + idx for r in reqs
                   for t, idx in r.get("ticks", ()) if lo <= t <= hi]
        counters["traced_kv_token_reads"] = sum(in_span)
        counters["traced_slot_ticks"] = len(in_span)

    # -- free the engine, then one plain pass per sampled request --------
    finished = [r for r in reqs if r["tokens"] is not None]
    del eng
    gc.collect()
    compared, check = [], {}
    if finished:
        check = served_check(cell, model_cfg, params, finished)
        compared = compare.served_rows(check["gaps"], cfg["limits"],
                                       check["tokens"])
        if cell.control:
            row = compare.served_rows(check["control_gaps"], cfg["limits"],
                                      check["tokens"])[0]
            row["what"] = "CONTROL_" + row["what"]
            compared.append(row)
    compared.append({"what": "requests_finished", "value": len(finished),
                     "limit": n, "ok": len(finished) == n})
    compared.append({"what": "decode_recompiles",
                     "value": counters["decode_recompiles"], "limit": 0,
                     "ok": counters["decode_recompiles"] == 0})
    compared.append({"what": "kv_pages_in_use_at_end", "value": pages_left,
                     "limit": 0, "ok": pages_left == 0 or bool(unfinished)})
    counters["reference_s"] = check.get("seconds")
    return {
        "end_to_end": end_to_end, "attempted": n, "failed": len(unfinished),
        "correct": all(r["ok"] for r in compared),
        "compared": compared, "counters": counters,
        "memory_peak_bytes": peak,
    }


def served_check(cell, model_cfg, params, finished):
    """A seeded sample of finished requests, the longest among them; one
    teacher-forced reference pass over each prompt + served tokens."""
    import jax
    import jax.numpy as jnp

    t_start = time.perf_counter()
    rng = np.random.default_rng([cell.seed % 2 ** 32, 7])
    longest = max(range(len(finished)), key=lambda i: (
        finished[i]["prompt"].size + len(finished[i]["tokens"])))
    others = [i for i in range(len(finished)) if i != longest]
    picks = [longest] + list(rng.permutation(others)[:CHECK_REQUESTS - 1])
    rows = traffic_mod.max_length(cell.traffic["output_len"])
    pad = -(-(traffic_mod.max_length(cell.traffic["prompt_len"]) + rows)
            // 128) * 128
    ref = jax.jit(functools.partial(cell.reference.served_gaps, model_cfg))
    low = jax.jit(functools.partial(cell.reference.served_gaps, model_cfg,
                                    dtype=jnp.bfloat16))
    gaps, control_gaps, tokens = [], [], 0
    for i in picks:
        r = finished[int(i)]
        out = np.asarray(r["tokens"], np.int32)
        seq = np.zeros(pad, np.int32)
        full = np.concatenate([r["prompt"], out[:-1]])
        seq[:full.size] = full
        served = np.zeros(rows, np.int32)
        served[:out.size] = out
        start = r["prompt"].size - 1
        gap, _best = ref(params, jnp.asarray(seq), start, jnp.asarray(served))
        gaps.extend(np.asarray(gap)[:out.size].tolist())
        tokens += out.size
        if cell.control:
            # the control: what bfloat16 activations put first, read on the
            # float32 reference's logits at the same positions
            _g, low_best = low(params, jnp.asarray(seq), start,
                               jnp.asarray(served))
            gap, _best = ref(params, jnp.asarray(seq), start, low_best)
            control_gaps.extend(np.asarray(gap)[:out.size].tolist())
    return {"gaps": gaps, "control_gaps": control_gaps, "tokens": tokens,
            "seconds": time.perf_counter() - t_start}
