"""``drivers/decode.offer`` against a fake engine and a fake clock: every
request of the schedule is submitted, also when the loop is stalled past the
window's end; the profiler of a traced run is started for the window's LAST
seconds and stopped once, after the loop, and the drain counts from there."""
import contextlib
import types

import pytest

import traffic
from drivers import decode


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def sleep(self, s):
        self.now += max(s, 1e-4)


class FakeFuture:
    def add_done_callback(self, fn):
        self.fn = fn


class FakeEngine:
    def __init__(self, clock, stall_at=None, stall_s=0.0):
        self.clock, self.stall_at, self.stall_s = clock, stall_at, stall_s
        self.submits = []

    def submit(self, prompt, max_new):
        self.submits.append((prompt.size, max_new, self.clock.now))
        if self.stall_at is not None and len(self.submits) == self.stall_at:
            self.clock.now += self.stall_s      # the thread is held here
        return FakeFuture()

    def queue_depth(self):
        return 0

    def kvcache_stats(self):
        return {"pages_in_use": 0}

    def stats(self):
        return {"slot_occupancy": 0.0}


class FakeCell:
    def __init__(self, clock, seconds, trace=False, stop_s=0.0, trace_s=None):
        self.clock, self.seconds, self.trace = clock, seconds, trace
        self.traffic = {} if trace_s is None else {"trace_s": trace_s}
        self.stop_s, self.ticks, self.stops = stop_s, [], []
        self.t0 = None

    def setup_done(self):
        self.t0 = self.clock.now
        return self.t0

    def span(self, _name):
        return contextlib.nullcontext()

    def trace_tick(self, elapsed, at_end=False, span=None):
        self.ticks.append((elapsed, at_end, span))

    def trace_stop(self):
        self.stops.append(self.clock.now)
        self.clock.now += self.stop_s


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(decode, "time", types.SimpleNamespace(
        perf_counter=c.perf_counter, sleep=c.sleep))
    return c


def _schedule(seconds=10.0):
    mix = {"rate_per_s": 4, "arrival_cv": 1,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
           "output_len": {"dist": "uniform", "min": 2, "max": 9}}
    return traffic.open_loop(mix, 5, seconds, 1000)


def test_an_unstalled_offer_submits_each_request_when_it_is_due(clock):
    reqs = _schedule()
    eng, cell = FakeEngine(clock), FakeCell(clock, 10.0)
    win = decode.offer(cell, eng, reqs, 10.0)
    assert len(eng.submits) == len(reqs) == 40
    assert all(r["submitted"] for r in reqs)
    late = [r["t_submit"] - (win["t0"] + r["due_s"]) for r in reqs]
    assert 0 <= min(late) and max(late) < 1e-3
    assert [s[:2] for s in eng.submits] == [
        (r["prompt"].size, r["max_new"]) for r in reqs]
    assert win["end"] == win["t0"] + 10.0 and win["t_free"] >= win["end"]


@pytest.mark.parametrize("stall_s", [3.0, 30.0])
def test_a_stalled_offer_submits_the_whole_schedule_late(clock, stall_s):
    """A submit that holds the thread (as the profiler's stop did in the
    middle of the window): what fell due meanwhile, and what was still to
    come when the window closed, is submitted all the same."""
    reqs = _schedule()
    eng = FakeEngine(clock, stall_at=20, stall_s=stall_s)
    win = decode.offer(FakeCell(clock, 10.0), eng, reqs, 10.0)
    assert len(eng.submits) == len(reqs)
    assert all(r["submitted"] and "t_submit" in r for r in reqs)
    late = [r["t_submit"] - (win["t0"] + r["due_s"]) for r in reqs]
    assert max(late) > 1.0 and min(late) >= 0
    order = [s[2] for s in eng.submits]
    assert order == sorted(order)


def test_the_traced_span_is_the_windows_end_and_is_stopped_after_the_loop(
        clock):
    reqs = _schedule()
    eng = FakeEngine(clock)
    cell = FakeCell(clock, 10.0, trace=True, stop_s=25.0, trace_s=1.5)
    win = decode.offer(cell, eng, reqs, 10.0)
    assert cell.ticks and all(at_end and span == 1.5
                              for _e, at_end, span in cell.ticks)
    assert len(cell.stops) == 1 and cell.stops[0] >= win["end"]
    assert all(s[2] <= win["end"] for s in eng.submits)
    assert win["t_free"] == pytest.approx(cell.stops[0] + 25.0)


def test_cell_places_the_span_where_the_driver_asks(monkeypatch):
    """``Cell.trace_tick``: the middle of the window by default (training),
    the last ``span`` seconds with ``at_end``, closed only by
    ``trace_stop`` — whose span ends before the stop."""
    import run as harness

    calls = []
    fake = types.SimpleNamespace(profiler=types.SimpleNamespace(
        ProfileOptions=lambda: types.SimpleNamespace(),
        start_trace=lambda d, profiler_options=None: calls.append("start"),
        stop_trace=lambda: calls.append("stop")))
    monkeypatch.setitem(__import__("sys").modules, "jax", fake)

    def cell():
        c = harness.Cell.__new__(harness.Cell)
        c.trace, c.traced, c._tracing = True, False, False
        c.seconds, c.trace_dir = 50.0, "/nonexistent/trace"
        c.trace_span = c.stop_trace_s = None
        return c

    mid = cell()
    for t, want in ((23.4, []), (23.5, ["start"]), (26.4, ["start"]),
                    (26.5, ["start", "stop"]), (49.0, ["start", "stop"])):
        mid.trace_tick(t)
        assert calls == want, t
    assert mid.traced and mid.stop_trace_s >= 0
    del calls[:]
    end = cell()
    for t, want in ((26.5, []), (48.9, []), (49.0, ["start"]),
                    (49.99, ["start"])):
        end.trace_tick(t, at_end=True, span=1.0)
        assert calls == want, t
    assert not end.traced
    end.trace_stop()
    assert calls == ["start", "stop"] and end.traced
    lo, hi = end.trace_span
    assert lo <= hi and end.stop_trace_s >= 0
    end.trace_stop()
    assert calls == ["start", "stop"]


def test_an_empty_trace_is_reported_not_raised(monkeypatch):
    import trace_reduce

    monkeypatch.setattr(trace_reduce, "load", lambda path: {
        "devices": {0: []}, "modules": {}, "host_spans": [("bench.wait", 0, 9)]})
    red = trace_reduce.reduce("whatever.xplane.pb")
    assert red["busy_s"] == 0 and red["window_s"] == 0
    assert red["events"] == {} and red["device_ops"] == []
