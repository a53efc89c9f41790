"""``benchmark/program_spans.py`` on a trace made here on the CPU (nested
spans on two threads, a known idle interval), and the new readers' contract:
``None`` on a rehearsal, a number from such a trace."""
import glob
import importlib.util
import json
import os
import threading
import time
import types

import pytest

import program_spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ["train_shard_ms", "train_prologue_ms", "train_gather_ms",
       "train_dispatch_ms", "train_commit_ms", "train_step_self_ms",
       "idle_attributed_pct.train", "tick_host_ms", "tick_prefill_share_pct",
       "slot_occupancy_window_pct", "queue_wait_p50_ms",
       "paged_attn_ms_per_tick", "idle_attributed_pct.serve"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(thread, start, end, name, **args):
    return (thread, start, end, name, args)


def test_nesting_self_time_and_medians():
    spans = program_spans.nest([
        _span(1, 0, 100, "mx.train.step"),
        _span(1, 10, 30, "mx.train.shard"),
        _span(1, 30, 90, "mx.train.dispatch"),
        _span(1, 200, 320, "mx.train.step"),
        _span(1, 210, 250, "mx.train.shard"),
        _span(1, 250, 300, "mx.train.dispatch"),
        _span(2, 20, 80, "mx.kvstore.push"),      # another thread: no parent
    ])
    by = {(s.name, s.start): s for s in spans}
    assert by["mx.train.shard", 10].parent is not None
    assert spans[by["mx.train.shard", 10].parent].name == "mx.train.step"
    assert by["mx.kvstore.push", 20].parent is None
    assert by["mx.train.step", 0].self_ns == 20
    assert by["mx.train.step", 200].self_ns == 30
    table = program_spans.table(spans)
    assert table["mx.train.step"]["count"] == 2
    assert table["mx.train.step"]["median_ms"] == pytest.approx(110e-6)
    assert table["mx.train.step"]["self_median_ms"] == pytest.approx(25e-6)
    assert table["mx.train.step"]["self_total_ms"] == pytest.approx(50e-6)
    assert table["mx.train.shard"]["total_ms"] == pytest.approx(60e-6)
    assert table["mx.train.shard"]["longest_ms"] == pytest.approx(40e-6)
    assert "self_median_ms" not in table["mx.train.shard"]


def test_idle_goes_to_the_innermost_span_and_adds_up():
    spans = program_spans.nest([
        _span(1, 0, 100, "mx.train.step"),
        _span(1, 10, 30, "mx.train.shard"),
        _span(1, 30, 90, "mx.train.dispatch"),
        _span(2, 80, 140, "mx.other"),            # overlaps thread 1's end
    ])
    idle = [(5, 40), (85, 120), (150, 160)]
    got = program_spans.idle_by_span(spans, idle)
    # 5-10 step, 10-30 shard, 30-40 dispatch; 85-90 dispatch (the stretch
    # that started first keeps what two threads cover), 90-120 mx.other
    # (its stretch started before the step's tail did); 150-160 nobody's
    assert got == pytest.approx({
        "mx.train.step": 5e-9, "mx.train.shard": 20e-9,
        "mx.train.dispatch": 15e-9, "mx.other": 30e-9,
        program_spans.UNCOVERED: 10e-9})
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in idle) / 1e9)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A real xplane: two train steps with children on this thread, decode
    ticks with arguments on a worker thread."""
    import jax

    from mxnet_tpu import telemetry

    trace_dir = str(tmp_path_factory.mktemp("trace"))

    def step():
        with telemetry.span("train.step", "trainplane"):
            with telemetry.span("train.shard", "trainplane"):
                time.sleep(0.002)
            with telemetry.span("train.dispatch", "trainplane"):
                time.sleep(0.006)
            time.sleep(0.001)

    def worker():
        for active in (2, 0, 4):
            with telemetry.span("decode.tick", "serving") as tick:
                tick.set_args(active=active, prefilling=0, queued=1)
                if not active:
                    continue
                with telemetry.span("decode.prefill", "serving", rung=8):
                    time.sleep(0.001)
                with telemetry.span("decode.fetch", "serving"):
                    time.sleep(0.004)
                time.sleep(0.002)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        step()
        step()
        th = threading.Thread(target=worker)  # after the steps: what two
        th.start()              # threads cover at once is shared out
        th.join(timeout=30)     # (test_idle_goes_to_the_innermost_span...)
        assert not th.is_alive()
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return trace_dir


def _run_over(trace_dir, spans):
    """A ``run`` as the readers get it: the device 'ran' two operations
    around the first train step's dispatch, so the idle interval between
    them is known."""
    disp = next(s for s in spans if s.name == "mx.train.dispatch")
    ops = [("%op.1 = f32[] fusion()", disp.start - 1000, disp.start),
           ("%mx_paged_attn.3 = f32[] custom-call()", disp.end,
            disp.end + 5000)]
    cfg = {"engine": {"num_slots": 8}}
    return {"trace": {"events": {0: ops}, "lead_device": 0,
                      "modules": [("jit_mx_decode_step(1)", 0, 1)] * 2},
            "cell": types.SimpleNamespace(trace_dir=trace_dir, config=cfg,
                                          t_setup_done=0.0),
            "counters": {}}, disp


def test_load_reads_the_trace_once_and_attributes_the_idle(cpu_trace,
                                                           capsys):
    spans = program_spans.read_spans(
        program_spans.trace_reduce.find_xplane(cpu_trace))
    run, disp = _run_over(cpu_trace, spans)
    got = program_spans.load(run)
    assert program_spans.load(run) is got            # cached on the run
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["phase"] for ln in lines] == ["program_spans"]   # printed once
    assert lines[0]["spans"]["mx.train.step"]["count"] == 2
    # the one idle interval is exactly the first dispatch span
    assert got["idle_s"] == pytest.approx((disp.end - disp.start) / 1e9)
    assert got["idle_by_span_s"]["mx.train.dispatch"] == pytest.approx(
        got["idle_s"], rel=1e-6)
    by = got["by_name"]
    assert by["mx.train.shard"]["count"] == 2
    assert 2.0 <= by["mx.train.shard"]["median_ms"] < 6.0
    assert 6.0 <= by["mx.train.dispatch"]["median_ms"] < 12.0
    assert 1.0 <= by["mx.train.step"]["self_median_ms"] < 5.0
    ticks = [s for s in got["spans"] if s.name == "mx.decode.tick"]
    assert [t.args["active"] for t in ticks] == [2, 0, 4]
    assert {s.thread for s in ticks} != {
        s.thread for s in got["spans"] if s.name == "mx.train.step"}


def test_readers_give_numbers_from_such_a_trace(cpu_trace):
    spans = program_spans.read_spans(
        program_spans.trace_reduce.find_xplane(cpu_trace))
    run, _disp = _run_over(cpu_trace, spans)
    assert 2.0 <= _reader("train_shard_ms")(run) < 6.0
    assert 6.0 <= _reader("train_dispatch_ms")(run) < 12.0
    assert 1.0 <= _reader("train_step_self_ms")(run) < 5.0
    assert _reader("train_prologue_ms")(run) is None    # no such span here
    assert _reader("train_commit_ms")(run) is None
    assert _reader("idle_attributed_pct.train")(run) == pytest.approx(100.0)
    # under mx.train.dispatch, not under an mx.decode.* span of the worker
    assert _reader("idle_attributed_pct.serve")(run) == pytest.approx(0.0)
    # two stepping ticks: 1 + 4 + 2 ms, of which the fetch is 4
    assert 3.0 <= _reader("tick_host_ms")(run) < 7.0
    assert 5.0 < _reader("tick_prefill_share_pct")(run) < 30.0
    assert _reader("slot_occupancy_window_pct")(run) == pytest.approx(
        100.0 * (2 + 4) / (2 * 8))
    # 5000 ns of the kernel over two runs of the step program
    assert _reader("paged_attn_ms_per_tick")(run) == pytest.approx(2.5e-3)


def test_a_program_without_spans_gives_nothing(tmp_path):
    """The parent of the PR that brought the spans: a trace with no
    ``mx.*`` event. Every reader returns ``None`` and none raises."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    run = {"trace": {"events": {0: [("%op = f32[] fusion()", 0, 10),
                                    ("%op = f32[] fusion()", 50, 60)]},
                     "lead_device": 0, "modules": []},
           "cell": types.SimpleNamespace(
               trace_dir=str(tmp_path), t_setup_done=time.perf_counter(),
               config={"engine": {"num_slots": 8}}),
           "counters": {}}
    assert program_spans.load(run)["spans"] == []
    for name in NEW:
        assert _reader(name)(run) is None, name
    run["cell"].trace_dir = str(tmp_path / "gone")
    run.pop(program_spans._KEY)
    assert program_spans.load(run) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_on_a_rehearsal(name):
    run = {"trace": None, "counters": {}, "end_to_end": {},
           "cell": types.SimpleNamespace(trace_dir="/nonexistent",
                                         t_setup_done=0.0, config={})}
    assert _reader(name)(run) is None
