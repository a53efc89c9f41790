"""``benchmark/program_parts.py`` and ``benchmark/engine_idle.py`` on a trace
of the tiny ``TinyDecoder`` engine made here on the CPU — the engine's own
``mx.decode.programs`` / ``.idle`` / ``.prefill`` spans, with device events
laid under them by hand (a CPU trace has no device plane) — and the new
readers' contract: ``None`` on a rehearsal and for a program without the
spans, a number from such a trace."""
import glob
import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

import engine_idle
import program_parts
import program_spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ["step_attn_proj_ms_per_tick", "step_mlp_ms_per_tick",
       "step_route_ms_per_tick", "step_head_ms_per_tick", "step_unnamed_pct",
       "prefill_device_ms_p50", "prefill_attn_share_pct",
       "prefill_moe_share_pct", "prefill_unnamed_pct", "engine_empty_pct",
       "device_idle_engaged_pct.serve", "prefill_stall_ms_per_token",
       "steps_overlapped_pct", "kv_cols_live_pct"]
OP_NS = 1000        # every mapped instruction 'runs' this long
STRAY_NS = 500      # ... and two a run that no map knows


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A burst on the tiny engine, then an empty stretch, under one trace."""
    import jax

    from mxnet_tpu import serving

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    rng = np.random.RandomState(3)
    burst = [rng.randint(1, 32, 6).astype(np.int32) for _ in range(5)]
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=3, max_seq_len=48,
            prefill_buckets=(8, 16), prefix_cache=False, timeout_ms=0,
            name="bench_parts") as eng:
        eng.warmup()
        while len(eng.stats()["program_parts"]) < 4:    # the idle worker
            time.sleep(0.02)                # reads a rung's map at a time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            for f in [eng.submit(p, 8) for p in burst]:
                f.result(timeout=120)
            time.sleep(0.3)             # the engine is empty
        finally:
            jax.profiler.stop_trace()
        stats = eng.stats()
    assert glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return trace_dir, stats


def _run_over(trace_dir):
    """A ``run`` as the readers get it. The 'device' ran one
    ``jit_mx_decode_step`` behind every ``mx.decode.dispatch`` and one
    ``jit_mx_prefill`` inside every ``mx.decode.prefill``: every instruction
    of the program's map for ``OP_NS``, back to back, the first three inside
    a ``%while.99`` that no map knows, and two stray operations."""
    spans = program_spans.read_spans(
        program_spans.trace_reduce.find_xplane(trace_dir))
    maps = program_parts.maps_of(spans)
    ops, modules = [], []

    def lay(program, fingerprint, start, parts):
        t = start
        ops.append(("%while.99 = (s32[]) while(s32[] %x), body=%b", t,
                    t + 3 * OP_NS))
        for inst in parts:
            ops.append(("%%%s = f32[8]{0:T(128)} fusion(f32[8] %%p), "
                        "kind=kLoop" % inst, t, t + OP_NS))
            t += OP_NS
        for i in range(2):
            ops.append(("%%stray.%d = f32[] copy(f32[] %%q)" % i, t,
                        t + STRAY_NS))
            t += STRAY_NS
        modules.append(("%s(%d)" % (program, fingerprint), start, t))

    for s in spans:
        if s.name == "mx.decode.dispatch":
            lay(program_parts.STEP, 11, s.end,
                maps[program_parts.STEP][None]["parts"])
        elif s.name == "mx.decode.prefill":
            lay(program_parts.PREFILL, 22, s.start + 10,
                maps[program_parts.PREFILL][s.args["rung"]]["parts"])
    ops.sort(key=lambda ev: ev[1])
    cfg = {"engine": {"num_slots": 3}}
    return {"trace": {"events": {0: ops}, "lead_device": 0,
                      "modules": modules, "window_s": 1.0, "busy_s": 0.5},
            "cell": types.SimpleNamespace(trace_dir=trace_dir, config=cfg,
                                          t_setup_done=0.0),
            "counters": {}}, spans, maps


def test_join_is_a_partition_of_the_runs_operations(cpu_trace, capsys):
    trace_dir, stats = cpu_trace
    run, spans, maps = _run_over(trace_dir)
    got = program_parts.load(run)
    assert program_parts.load(run) is got            # cached on the run
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["phase"] for ln in lines] == ["program_spans",
                                            "program_parts"]
    assert set(got) == {program_parts.STEP, program_parts.PREFILL}
    step = got[program_parts.STEP]
    parts = maps[program_parts.STEP][None]["parts"]
    assert step["runs"] == stats["ticks"] == len(
        [s for s in spans if s.name == "mx.decode.dispatch"])
    # every mapped instruction under its part, the strays and what the
    # `while` does not hand to its three children (nothing) under none
    for part in set(parts.values()):
        n = sum(1 for p in parts.values() if p == part)
        assert step["by_part_ns"][part] == n * OP_NS * step["runs"]
    assert step["unnamed_ns"] == 2 * STRAY_NS * step["runs"]
    assert step["ops_ns"] == step["unnamed_ns"] + sum(
        step["by_part_ns"].values())
    assert step["ops_ns"] == sum(step["run_ns"])      # back to back
    assert [inst for inst, _ns in step["top_unnamed"]] == [
        "stray.0", "stray.1", "while.99"]
    line = lines[1]["programs"][program_parts.STEP]
    assert line["runs"] == step["runs"]
    assert sum(line["ms_a_run_by_part"].values()) + line["unnamed_ms_a_run"] \
        == pytest.approx(line["ops_ms_a_run"])
    assert line["map_s"] > 0 and line["map_unnamed"] == 0
    # a prefill run takes the map of ITS rung (by the span that covers it)
    pre = got[program_parts.PREFILL]
    assert pre["runs"] == 5 == stats["prefills"]
    assert pre["unnamed_ns"] == 2 * STRAY_NS * pre["runs"]


def test_readers_give_numbers_from_such_a_trace(cpu_trace):
    trace_dir, stats = cpu_trace
    run, spans, maps = _run_over(trace_dir)
    parts = maps[program_parts.STEP][None]["parts"]

    def ms(*names):
        return sum(1 for p in parts.values() if p in names) * OP_NS / 1e6

    assert _reader("step_attn_proj_ms_per_tick")(run) == pytest.approx(
        ms("mx_qkv", "mx_attn_out"))
    assert _reader("step_mlp_ms_per_tick")(run) == pytest.approx(
        ms("mx_mlp"))
    assert _reader("step_head_ms_per_tick")(run) == pytest.approx(
        ms("mx_head", "mx_embed"))
    assert _reader("step_route_ms_per_tick")(run) == 0.0   # no expert layer
    assert _reader("step_unnamed_pct")(run) == pytest.approx(
        100.0 * 2 * STRAY_NS / (len(parts) * OP_NS + 2 * STRAY_NS))
    rung8 = maps[program_parts.PREFILL][8]["parts"]
    assert _reader("prefill_device_ms_p50")(run) == pytest.approx(
        (len(rung8) * OP_NS + 2 * STRAY_NS) / 1e6)
    attn = sum(1 for p in rung8.values() if p == "mx_attn")
    assert _reader("prefill_attn_share_pct")(run) == pytest.approx(
        100.0 * attn * OP_NS / (len(rung8) * OP_NS + 2 * STRAY_NS))
    assert _reader("prefill_moe_share_pct")(run) == 0.0
    assert 0.0 < _reader("prefill_unnamed_pct")(run) < 10.0
    # the engine's own spans: most steps dispatched over the one before,
    # a share of the tables' columns walked, tokens that waited for the
    # burst's later prefills
    assert _reader("steps_overlapped_pct")(run) == pytest.approx(
        100.0 * stats["steps_overlapped"] / stats["ticks"])
    assert _reader("kv_cols_live_pct")(run) == pytest.approx(
        100.0 * stats["kv_cols_live"] / stats["kv_cols_grid"])
    held = sum((s.end - s.start) / 1e6 * s.args["held"] for s in spans
               if s.name == "mx.decode.prefill")
    assert held > 0
    assert _reader("prefill_stall_ms_per_token")(run) == pytest.approx(
        held / stats["slot_ticks"])
    assert stats["prefill_held_slot_ms"] == pytest.approx(held, rel=0.3,
                                                          abs=0.5)


def test_window_splits_into_empty_busy_and_engaged_idle(cpu_trace):
    """The last 0.3 s of the window the engine is empty and the 'device'
    idle: ``device_idle_pct.serve`` reads that stretch, the engaged idle
    share does not."""
    trace_dir, _stats = cpu_trace
    run, spans, _maps = _run_over(trace_dir)
    ops = run["trace"]["events"][0]
    last_idle = max(s.end for s in spans if s.name == "mx.decode.idle")
    ops.append(("%end.1 = f32[] copy(f32[] %q)", last_idle - 10, last_idle))
    got = engine_idle.account(run)
    lo, hi = ops[0][1], last_idle
    assert got["window_ns"] == hi - lo
    assert got["empty_ns"] >= 0.25e9
    # the device did nothing while the engine was empty (but that last op)
    assert got["idle_empty_ns"] == pytest.approx(got["empty_ns"], abs=20)
    assert got["idle_ns"] >= got["idle_empty_ns"]
    empty = _reader("engine_empty_pct")(run)
    engaged = _reader("device_idle_engaged_pct.serve")(run)
    assert empty == pytest.approx(100.0 * got["empty_ns"] / (hi - lo))
    assert engaged == pytest.approx(
        100.0 * (got["idle_ns"] - got["idle_empty_ns"])
        / (hi - lo - got["empty_ns"]))
    busy = sum(ns for _n, _s, ns in program_parts.self_times(ops))
    # empty + busy + engaged idle (as shares of the window) make it up
    assert got["empty_ns"] + busy + got["idle_ns"] - got["idle_empty_ns"] \
        == pytest.approx(hi - lo, rel=1e-6)
    assert 100.0 * got["idle_ns"] / (hi - lo) > empty > 10.0


def _parent_run(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    return {"trace": {"events": {0: [("%op = f32[] fusion()", 0, 10),
                                     ("%op = f32[] fusion()", 50, 60)]},
                      "lead_device": 0,
                      "modules": [("jit_mx_decode_step(1)", 0, 60)]},
            "cell": types.SimpleNamespace(
                trace_dir=str(tmp_path), t_setup_done=time.perf_counter(),
                config={"engine": {"num_slots": 8}}),
            "counters": {}}


def test_a_program_without_the_spans_gives_nothing(tmp_path):
    """The parent: a trace with no ``mx.decode.programs`` / ``.idle`` span,
    no ``held``, ``overlapped`` or ``kv_cols_*`` argument. Every reader
    returns ``None`` and none raises."""
    run = _parent_run(tmp_path)
    assert program_parts.load(run) is None
    assert engine_idle.account(run) is None
    for name in NEW:
        assert _reader(name)(run) is None, name


def test_a_map_that_names_nothing_gives_nothing():
    """An executable read back from a compile cache that a program without
    the scopes filled: the span is there, its map empty."""
    spans = program_spans.nest([
        (1, 0, 0, program_parts.SPAN,
         {"program": program_parts.STEP, "parts": "{}", "mixed": 0,
          "unnamed": 7, "map_us": 5}),
        (1, 5, 9, "mx.decode.idle", {"why": "empty"})])
    reduced = {"events": {0: [("%op = f32[] fusion()", 0, 10)]},
               "lead_device": 0,
               "modules": [("jit_mx_decode_step(1)", 0, 10)]}
    assert program_parts.join(reduced, spans) == {}


def test_self_times_take_nested_operations_out():
    got = program_parts.self_times([
        ("%while.1 = ...", 0, 100), ("%a = ...", 10, 30),
        ("%b = ...", 30, 60), ("%c = ...", 200, 250)])
    assert got == [("%while.1 = ...", 0, 50), ("%a = ...", 10, 20),
                   ("%b = ...", 30, 30), ("%c = ...", 200, 50)]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_on_a_rehearsal(name):
    run = {"trace": None, "counters": {}, "end_to_end": {},
           "cell": types.SimpleNamespace(trace_dir="/nonexistent",
                                         t_setup_done=0.0, config={})}
    assert _reader(name)(run) is None
