#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json`` at the root
of the checkout (see ``benchmark/README.md``): the workload's ``config`` ->
``benchmark/configs/<config>.json`` (whose ``driver`` names
``benchmark/drivers/<driver>.py`` and whose ``reference`` names
``benchmark/reference/<file>.py``), its ``traffic`` ->
``benchmark/traffic/<traffic>.json``, and each per-layer metric ->
``benchmark/layer_metrics/<name>.py``. No configuration, traffic mix or
per-layer metric is written into this file or into a driver.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``.
Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result. ``--rehearse`` drives the cell's whole control
flow at the tiny preset of its files on the CPU and prints counts and
``correct`` only — never a time or a rate under a metric's name.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 3.0


def say(**fields):
    print(json.dumps(fields, sort_keys=True, default=str), flush=True)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: no %s" % os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find(rows, name, what):
    for row in rows:
        if row["name"] == name:
            return row
    raise SystemExit("benchmark: unknown %s %r" % (what, name))


def reported_in(metric, workload):
    cells = metric.get("workloads")
    return cells is None or workload in cells


class Cell:
    """What a driver is handed: the cell's files, the run's arguments, host
    spans and the profiler window."""

    def __init__(self, manifest, workload, args):
        import traffic as traffic_mod

        self.manifest = manifest
        self.workload = workload
        self.name = workload["name"]
        self.chips = int(workload["chips"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.control = bool(args.control)
        self.root = ROOT
        cfg_row = find(manifest["configs"], workload["config"], "config")
        with open(os.path.join(ROOT, cfg_row["file"])) as f:
            self.config = json.load(f)
        tiny = self.config.pop("rehearse", None)
        if self.rehearse and tiny:
            for key, val in tiny.items():
                if isinstance(val, dict) and isinstance(
                        self.config.get(key), dict):
                    self.config[key].update(val)
                else:
                    self.config[key] = val
        self.traffic = traffic_mod.load(os.path.join(
            HERE, "traffic", workload["traffic"] + ".json"), self.rehearse)
        self.reference = load_module("reference",
                                     self.config["reference"][:-3])
        self.peaks = None          # the device kind's row of peaks.json
        self.spans = {}
        self.trace_dir = os.path.join(ROOT, ".bench_trace", self.name)
        self._tracing = False
        self.traced = False
        self.trace_span = None     # (start, stop) on perf_counter
        self.stop_trace_s = None   # what jax.profiler.stop_trace() took
        self.t_setup_done = None

    # -- host spans -----------------------------------------------------
    @contextlib.contextmanager
    def span(self, name):
        """Time a host span (always) and write it into the profiler's trace
        (while one is being taken)."""
        import jax

        t0 = time.perf_counter()
        if self._tracing:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def setup_done(self):
        """Called by the driver at the first timed step or request."""
        from mxnet_tpu.fastpath import cache

        self.cache_at_setup = cache.cache_counts()
        self.t_setup_done = time.perf_counter()
        return self.t_setup_done

    def memory_peak(self, devices):
        """Peak bytes on the fullest chip, read when the window has closed
        and before the reference runs. This runtime keeps two pools: live
        buffers (``peak_bytes_in_use``) and what compiled programs reserve
        as scratch (``peak_bytes_reserved``); the chip holds both at once."""
        peak = 0
        for d in devices[:self.chips]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                       + int(st.get("peak_bytes_reserved", 0)))
            say(phase="memory_at_window_end", device=d.id, **{
                k: st.get(k) for k in ("peak_bytes_in_use",
                                       "peak_bytes_reserved", "bytes_limit")})
        return peak

    # -- profiler window ------------------------------------------------
    def trace_tick(self, elapsed, at_end=False, span=None):
        """Call from the measuring loop with the seconds since the window
        opened; starts the profiler where the traced span (``span`` seconds,
        ``TRACE_SECONDS`` unless the driver says otherwise) opens. By default
        the span is the window's middle and is closed here. With ``at_end``
        it is the window's last ``span`` seconds and the driver closes it
        with ``trace_stop()`` once its loop has ended: ``stop_trace`` writes
        the file before it returns (seconds per hundred traced steps), and
        nothing that is due in the window may wait for that."""
        if not self.trace or self.traced:
            return
        import jax

        span = min(float(span or TRACE_SECONDS), self.seconds / 2.0)
        lo = self.seconds - span if at_end else (self.seconds - span) / 2.0
        if not self._tracing and elapsed >= lo:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
            self.trace_span = (time.perf_counter(), None)
        elif self._tracing and not at_end and elapsed >= lo + span:
            self.trace_stop()

    def trace_stop(self):
        """Close the traced span: its end is the instant BEFORE the profiler
        is told to stop, so what runs while the file is written is not
        counted among the span's ticks; ``stop_trace_s`` is what the stop
        took."""
        if self._tracing:
            import jax

            t_end = time.perf_counter()
            jax.profiler.stop_trace()
            self._tracing = False
            self.traced = True
            self.trace_span = (self.trace_span[0], t_end)
            self.stop_trace_s = time.perf_counter() - t_end


def layer_metrics(manifest, run):
    """``{name: value}`` of the cell's per-layer metrics, each from its own
    reader; a reader that finds nothing to read gives ``None``."""
    return {m["name"]: load_module("layer_metrics", m["name"]).read(run)
            for m in manifest["per_layer"]
            if reported_in(m, run["cell"].name)}


def device_info(jax, run):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(run.get("memory_peak_bytes", 0))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU: control flow only")
    ap.add_argument("--control", action="store_true",
                    help="put the lower-precision control in the program's "
                    "place (the driver never does): `correct` should then "
                    "come out false")
    args = ap.parse_args(argv)

    manifest = load_json("BENCHMARK.json")
    workload = find(manifest["workloads"], args.workload, "workload")
    if args.seconds is None:
        args.seconds = 2.0 if args.rehearse else manifest["run_seconds"]
    chips = int(workload["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=%d" % chips).strip()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, ROOT]

    import jax

    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        raise SystemExit(
            "benchmark: no TPU — jax reports %d %r device(s); a cell is only "
            "measured on the chip (use --rehearse for the CPU dry run)"
            % (len(devs), devs[0].platform))
    if len(devs) < chips:
        raise SystemExit("benchmark: cell %s needs %d chip(s), jax reports %d"
                         % (args.workload, chips, len(devs)))
    peaks_table = load_json("benchmark", "peaks.json")
    if args.rehearse:
        peaks = None
    elif devs[0].device_kind not in peaks_table:
        raise SystemExit("benchmark: device kind %r is not in benchmark/"
                         "peaks.json — add it with its source, never a default"
                         % devs[0].device_kind)
    else:
        peaks = peaks_table[devs[0].device_kind]

    from mxnet_tpu.fastpath import cache

    hits0, misses0 = cache.cache_counts()
    cache_dir = None
    if not args.rehearse:
        # the program's own switch for jax's persistent cache: the machine's
        # JAX_COMPILATION_CACHE_DIR when that is set, else this fixed
        # directory inside the checkout
        cache_dir = cache.configure(os.path.join(ROOT, ".jax_cache"))
    cell = Cell(manifest, workload, args)
    cell.peaks = peaks
    driver = load_module("drivers", cell.config["driver"])
    say(phase="start", workload=cell.name, seed=cell.seed,
        seconds=cell.seconds, trace=cell.trace, rehearse=cell.rehearse,
        compile_cache_dir=cache_dir, jax=jax.__version__,
        device_kind=devs[0].device_kind, devices=len(devs))

    run = driver.run(cell)
    cell.trace_stop()

    run["cell"] = cell
    run["setup_s"] = cell.t_setup_done - T_PROCESS_START
    run["counters"] = dict(
        run.get("counters", {}),
        compile_cache_hits_setup=cell.cache_at_setup[0] - hits0,
        compile_cache_misses_setup=cell.cache_at_setup[1] - misses0)
    if cell.traced:
        run["counters"].update(
            stop_trace_s=cell.stop_trace_s,
            trace_span_s=cell.trace_span[1] - cell.trace_span[0],
            trace_span_starts_at_s=cell.trace_span[0] - cell.t_setup_done)
    device = device_info(jax, run)

    unit_of = {m["name"]: m["unit"]
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    end_to_end = dict(run["end_to_end"], setup_s=run["setup_s"])
    wanted = [m["name"] for m in manifest["end_to_end"]
              if reported_in(m, cell.name)]
    missing = [n for n in wanted if n not in end_to_end]
    if missing:
        raise SystemExit("benchmark: driver reported no %s" % missing)
    if not args.rehearse:
        say(phase="counters", **run["counters"])
    for row in run["compared"]:
        say(phase="compared", **row)
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}

    if args.rehearse:
        # counts and the verdict only: a CPU time is never a metric
        run["trace"] = None
        say(phase="rehearsed", end_to_end_reported=sorted(end_to_end),
            layer_metrics_readable=[[n, v is not None] for n, v in
                                    layer_metrics(manifest, run).items()])
        result["metrics"] = {}
        result["device"] = device
        print(json.dumps(result), flush=True)
        return 0 if result["correct"] else 1

    if not cell.trace:
        say(phase="end_to_end", **end_to_end)
        result["metrics"] = {n: {"value": end_to_end[n], "unit": unit_of[n]}
                             for n in wanted}
        result["device"] = device
        print(json.dumps(result), flush=True)
        return 0

    import trace_reduce

    say(phase="end_to_end_traced_run", **end_to_end)
    reduced = trace_reduce.reduce(trace_reduce.find_xplane(cell.trace_dir))
    if reduced["busy_s"] <= 0:
        raise SystemExit("benchmark: no operation ran on the device in the "
                         "traced window")
    run["trace"] = reduced
    full = {}
    for name, s_ns, e_ns in reduced["events"][reduced["lead_device"]]:
        full[name] = full.get(name, 0) + (e_ns - s_ns)
    say(phase="trace_ops", module=trace_reduce.dominant_module(reduced),
        top=[[k[:200], v / 1e9] for k, v in sorted(
            full.items(), key=lambda kv: -kv[1])[:6]],
        custom_calls=[[k[:300], v / 1e9] for k, v in sorted(
            full.items(), key=lambda kv: -kv[1]) if "custom" in k][:4])
    metrics = {n: {"value": v, "unit": unit_of[n]}
               for n, v in layer_metrics(manifest, run).items()
               if v is not None}
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    result["metrics"] = metrics
    result["device"] = device
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}
    shutil.rmtree(cell.trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
