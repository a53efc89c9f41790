"""``telemetry.span``'s third sink: the ``jax.profiler`` trace.

A span under a live trace comes back from the xplane as ``mx.<name>`` with
its arguments, from any thread; the train planes and the decode worker write
the spans docs/observability.md lists, nested as it says; with telemetry
off and no trace a span still makes no clock call. CPU only: the host plane
is the same on every backend.
"""
import glob
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, serving, telemetry, trainplane
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import spans as spans_mod

B = 8
TRAIN_CHILDREN = ["mx.train.shard", "mx.train.prologue", "mx.train.gather",
                  "mx.train.dispatch", "mx.train.commit",
                  "mx.train.hbm_sample"]


def _traced(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; the ``mx.*`` events of the
    host planes as ``[(name, start_ns, end_ns, args, thread)]`` by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out, thread = [], 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                if ev.name.startswith(spans_mod.TRACE_PREFIX):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats), thread))
    return sorted(out, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2] \
        and child[4] == parent[4]


def _assert_children_tile(events, parent_name, child_names, steps):
    """Each child once per parent, inside it, in order, none overlapping."""
    parents = _named(events, parent_name)
    assert len(parents) == steps
    for parent in parents:
        kids = [e for e in events if e[0] != parent_name
                and _inside(e, parent)]
        assert [k[0] for k in kids] == child_names
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a, b)


# ---------------------------------------------------------------------------
# the sink itself
# ---------------------------------------------------------------------------


def test_span_comes_back_from_the_trace_with_its_arguments(tmp_path):
    def work():
        with telemetry.span("t_trace_region", "t_cat", rung=128):
            pass

    (ev,) = _named(_traced(tmp_path, work), "mx.t_trace_region")
    assert ev[3] == {"rung": 128}
    assert ev[2] >= ev[1]


def test_span_from_a_worker_thread_lands_on_its_own_line(tmp_path):
    def work():
        def worker():
            with telemetry.span("t_trace_worker", slot=3):
                pass

        with telemetry.span("t_trace_main"):
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()

    events = _traced(tmp_path, work)
    (main,) = _named(events, "mx.t_trace_main")
    (worker,) = _named(events, "mx.t_trace_worker")
    assert worker[3] == {"slot": 3}
    assert worker[4] != main[4]


def test_span_set_args_adds_what_is_known_inside_the_region(tmp_path):
    def work():
        with telemetry.span("t_trace_late", queued=1) as sp:
            sp.set_args(active=5)

    (ev,) = _named(_traced(tmp_path, work), "mx.t_trace_late")
    assert ev[3] == {"queued": 1, "active": 5}
    with telemetry.span("t_trace_late") as sp:  # no trace: dropped, no error
        sp.set_args(active=5)


def test_span_decorator_and_traced_annotate_with_the_registry_off(tmp_path):
    @telemetry.span("t_trace_deco", "t_cat", k=2)
    def deco():
        return 1

    @telemetry.traced("t_cat", lambda x: "t_trace_dyn_%d" % x)
    def dyn(x):
        return x

    def work():
        telemetry.set_enabled(False)
        try:
            assert deco() == 1 and dyn(7) == 7
        finally:
            telemetry.set_enabled(True)

    events = _traced(tmp_path, work)
    assert _named(events, "mx.t_trace_deco")[0][3] == {"k": 2}
    assert len(_named(events, "mx.t_trace_dyn_7")) == 1
    assert spans_mod.SPAN_MS.count(category="t_cat",
                                   span="t_trace_deco") == 0


def test_span_with_everything_off_makes_no_clock_call(monkeypatch):
    def no_clock():
        raise AssertionError("a disabled span read the clock")

    @telemetry.span("t_off_deco")
    def deco():
        return 1

    telemetry.set_enabled(False)
    try:
        monkeypatch.setattr(spans_mod.time, "perf_counter", no_clock)
        with telemetry.span("t_off_region", rung=1) as sp:
            sp.set_args(active=1)
        assert deco() == 1
    finally:
        monkeypatch.undo()
        telemetry.set_enabled(True)
    assert spans_mod.SPAN_MS.count(category="span",
                                   span="t_off_region") == 0


# ---------------------------------------------------------------------------
# the train planes
# ---------------------------------------------------------------------------


def _gluon_plane(prefix, hybridize=True, opt="sgd", opt_params=None):
    rs = np.random.RandomState(11)
    xs = rs.rand(4 * B, 6).astype(np.float32)
    ys = rs.randint(0, 8, (4 * B,))
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(8))
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs[:B]))
    if hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), opt, opt_params or
                            {"learning_rate": 0.1, "momentum": 0.9})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  trainer)

    def step(i):
        lo = (i % 4) * B
        return plane.step(nd.array(xs[lo:lo + B]), nd.array(ys[lo:lo + B]))

    return plane, step


def test_trainplane_children_tile_each_step(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    plane, step = _gluon_plane("spg_")
    step(0)  # activation and the compile stay outside the trace
    assert plane.plane == "graph"
    events = _traced(tmp_path, lambda: [step(i) for i in range(1, 4)])
    _assert_children_tile(events, "mx.train.step", TRAIN_CHILDREN, steps=3)


@pytest.mark.parametrize("opt,opt_params,puts_per_row", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 0),
    # Nadam's four schedule scalars a row are the optimizer's own puts
    ("nadam", {"learning_rate": 0.01}, 4),
])
def test_train_prologue_span_carries_its_puts(tmp_path, monkeypatch, opt,
                                              opt_params, puts_per_row):
    """``puts``: the host->device transfers the prologue issued — none for
    the packed t/lr/wd operand, one per device value in ``extras``."""
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    plane, step = _gluon_plane("spp_%s_" % opt, opt=opt,
                               opt_params=opt_params)
    step(0)
    assert plane.plane == "graph"
    events = _traced(tmp_path, lambda: [step(i) for i in range(1, 4)])
    prologues = _named(events, "mx.train.prologue")
    assert len(prologues) == 3
    for ev in prologues:
        assert ev[3] == {"puts": puts_per_row * len(plane._rows)}
    # no other child of the step carries an argument
    for name in TRAIN_CHILDREN:
        if name != "mx.train.prologue":
            assert all(not e[3] for e in _named(events, name))


def test_eager_plane_gets_the_step_span_alone(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRAINSTEP", "0")
    plane, step = _gluon_plane("spe_")
    step(0)
    assert plane.plane == "eager"
    events = _traced(tmp_path, lambda: [step(i) for i in range(1, 3)])
    assert len(_named(events, "mx.train.step")) == 2
    assert not [e for e in events if e[0] in TRAIN_CHILDREN]


def test_module_plane_children_tile_each_step(tmp_path, monkeypatch):
    from mxnet_tpu import io as io_mod
    from mxnet_tpu.module import Module

    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    rs = np.random.RandomState(13)
    xs = rs.rand(4 * B, 6).astype(np.float32)
    ys = rs.randint(0, 4, (4 * B,)).astype(np.float32)
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    it = io_mod.NDArrayIter(xs, ys, batch_size=B)
    mod = Module(mx.sym.SoftmaxOutput(fc2, name="softmax"),
                 context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    plane = trainplane.module_plane(mod)
    assert plane is not None
    batches = list(it)
    plane.step(batches[0])  # the compile stays outside the trace
    events = _traced(tmp_path, lambda: [plane.step(b) for b in batches[1:]])
    # the module plane stages the batch where the gluon plane shards it,
    # and samples no HBM
    _assert_children_tile(events, "mx.train.step", TRAIN_CHILDREN[:-1],
                          steps=3)
    assert [e[3] for e in _named(events, "mx.train.prologue")] == \
        [{"puts": 0}] * 3


# ---------------------------------------------------------------------------
# the decode worker
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    return model, model.init_params(0)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("prefix_cache", False)
    return serving.DecodeEngine(model, params, **kw)


def _prompts(n, seed=5):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, 32, int(rng.randint(2, 12))).astype(np.int32),
             int(rng.randint(3, 9))) for _ in range(n)]


def test_decode_tick_spans_carry_the_slots_in_use(tiny, tmp_path):
    with _engine(tiny, name="spans_tick") as eng:
        eng.warmup()

        def work():
            futs = [eng.submit(p, m) for p, m in _prompts(7)]
            for f in futs:
                f.result(timeout=120)
            # the pass that resolved the last future is still open: join
            # the worker before the trace stops, or that pass's children
            # are in the trace and the pass itself is not
            eng.close()

        events = _traced(tmp_path, work)
        stats = eng.stats()
    ticks = _named(events, "mx.decode.tick")
    stepped = [t for t in ticks if t[3].get("active")]
    assert stepped and len(stepped) <= stats["ticks"]
    for t in ticks:
        kids = [e for e in events if e is not t and _inside(e, t)]
        names = [k[0] for k in kids]
        assert names[0] == "mx.decode.housekeep"
        if "active" not in t[3]:
            continue
        assert set(t[3]) == {"active", "prefilling", "queued"}
        assert 0 <= t[3]["active"] <= eng.num_slots
        # a pass dispatches its step (pack, dispatch), THEN fetches and
        # commits the step the pass before left in flight
        step = ["mx.decode.pack", "mx.decode.dispatch", "mx.decode.fetch",
                "mx.decode.commit"]
        ran = [n for n in names if n in step]
        assert ran in (step, step[:2], step[2:], []), ran
        assert (ran[:2] == step[:2]) == bool(t[3]["active"])
        assert "mx.decode.admit" in names
    # every step is dispatched once, fetched once and committed once; most
    # are dispatched with the step before them un-fetched, and say so
    dispatches = _named(events, "mx.decode.dispatch")
    assert all(set(d[3]) == {"overlapped"} for d in dispatches)
    assert len(dispatches) == stats["ticks"] \
        == len(_named(events, "mx.decode.fetch")) \
        == len(_named(events, "mx.decode.commit"))
    assert sum(d[3]["overlapped"] for d in dispatches) \
        == stats["steps_overlapped"] > 0
    # ... and an overlapped dispatch ENDS before the fetch of its pass (of
    # the step before) starts
    for t in ticks:
        kids = {e[0]: e for e in events if e is not t and _inside(e, t)}
        d, f = kids.get("mx.decode.dispatch"), kids.get("mx.decode.fetch")
        if d is not None and f is not None:
            assert d[3]["overlapped"] == 1 and d[2] <= f[1]
    # every span of the worker lies inside one of its passes, but its wait
    # for work between two of them
    for e in events:
        if e[0] == "mx.decode.idle":
            assert e[3] == {"why": "empty"}
            assert not any(_inside(e, t) for t in ticks), e
        elif e[0] == "mx.decode.programs":
            # zero-length, by the first look that finds the trace live: a
            # pass, or the waiting worker between two slices
            assert e[2] <= dispatches[0][1]
        elif e[0].startswith("mx.decode.") and e[0] != "mx.decode.tick":
            assert any(_inside(e, t) for t in ticks), e
    # monolithic prefill runs inside the admission pass, under its rung
    prefills = _named(events, "mx.decode.prefill")
    assert len(prefills) == 7
    assert {p[3]["rung"] for p in prefills} <= {8, 16}
    admits = _named(events, "mx.decode.admit")
    assert all(any(_inside(p, a) for a in admits) for p in prefills)
    # `active` is what the step decoded: summed over the traced passes it
    # is the engine's own slot_ticks (the whole soak ran under the trace)
    assert sum(t[3]["active"] for t in stepped) == stats["slot_ticks"]


def _walk_model(kind):
    """(model, engine keywords, the (columns, layers) of each table a tick's
    attention walks): TinyDecoder's one table, an afmoe model's full table
    and its window group's ring."""
    if kind == "plain":
        model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                    head_dim=8, num_kv_heads=2)
        return model, dict(max_seq_len=48, page_size=8), [(6, 2)]
    model = serving.AfmoeDecoder(
        vocab_size=96, hidden_size=48, num_attention_heads=12,
        num_key_value_heads=2, head_dim=8, intermediate_size=96,
        moe_intermediate_size=32,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
        sliding_window=32, held_experts=[4, 4], route_scale=2.448,
        mup_enabled=True)
    return model, dict(max_seq_len=128, page_size=8, prefill_chunk=0), \
        [(16, 1), (5, 4)]


@pytest.mark.parametrize("kind", ["plain", "grouped"])
def test_decode_commit_span_carries_the_page_walk(kind, tmp_path):
    """``kv_cols_live`` / ``kv_cols_grid`` / ``kv_cols_walked`` on every
    tick of every model: the table columns the paged-attention walk ran
    (what ``pallas_kernels.live_columns`` hands the kernel) of the tables'
    columns x slots, over the layers, and the grid steps the launches took
    (the extent of ``pallas_kernels.walk_schedule``: the idle slot is not
    walked); the three counters and ``stats()`` add up to the spans."""
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.serving import decode as decode_mod

    model, kw, tables = _walk_model(kind)
    name = "spans_walk_%s" % kind
    with _engine((model, model.init_params(0)), name=name, num_slots=2,
                 prefill_buckets=(16, 64), **kw) as eng:
        eng.warmup()
        before = eng.stats()
        prompt = np.arange(1, 41, dtype=np.int32)   # wraps the 40-token ring

        def work():
            eng.generate(prompt, 6, timeout=300)
            eng.close()

        events = _traced(tmp_path, work)
        stats = eng.stats()
    commits = [e[3] for e in _named(events, "mx.decode.commit")]
    assert len(commits) == 5
    grid = sum(2 * cols * layers for cols, layers in tables)
    for i, args in enumerate(commits):
        # one sequence of 41 + i tokens, the other slot idle
        lens = np.asarray([[41 + i], [0]], np.int32)
        want = walked = 0
        for (cols, layers), ring in zip(tables, (False, True)):
            live = pk.live_columns(lens, None, cols, 8, ring=ring)
            want += layers * int(np.diff(np.asarray(live)).sum())
            walked += layers * int(pk.walk_schedule(
                live, jnp.zeros((2, cols), jnp.int32))[2][-1])
        assert args["kv_cols_live"] == want
        assert args["kv_cols_grid"] == grid
        assert args["kv_cols_walked"] == walked
        assert 0 < args["kv_cols_live"] <= args["kv_cols_walked"] \
            <= args["kv_cols_grid"]
        # the arrays the step was handed as pools: K and V, one a layer
        assert args["kv_pool_leaves"] == 2 * sum(n for _c, n in tables)
    for key, counter in (("kv_cols_live", decode_mod._T_KV_COLS_LIVE),
                         ("kv_cols_grid", decode_mod._T_KV_COLS_GRID),
                         ("kv_cols_walked", decode_mod._T_KV_COLS_WALKED)):
        total = sum(a[key] for a in commits)
        assert stats[key] - before[key] == total
        assert sum(counter.value(server=name, group=g)
                   for g in ("full", "window")[:len(tables)]) == stats[key]
    assert "mxnet_decode_kv_cols_live_total" in telemetry.render_prometheus()
    assert "mxnet_decode_kv_cols_walked_total" in \
        telemetry.render_prometheus()
    assert stats["kv_pool_leaves"] == commits[0]["kv_pool_leaves"]
    # warmup() read the compiled step's temporaries (what they must stay
    # under at real widths: tests/test_chip_compile.py)
    assert stats["decode_step_temp_bytes"] > 0
    assert decode_mod._T_STEP_TEMP.value(server=name) \
        == stats["decode_step_temp_bytes"]
    assert before["decode_step_temp_bytes"] \
        == stats["decode_step_temp_bytes"]


def test_decode_chunked_prefill_span_carries_the_chunk(tiny, tmp_path):
    with _engine(tiny, name="spans_chunk", prefill_chunk=8) as eng:
        eng.warmup()
        prompt = np.arange(1, 20, dtype=np.int32)
        events = _traced(
            tmp_path, lambda: eng.submit(prompt, 3).result(timeout=120))
    prefills = _named(events, "mx.decode.prefill")
    assert len(prefills) == 3  # 19 tokens, 8 a chunk, one chunk a pass
    # (`held`: nobody was decoding while the one request prefilled)
    assert all(p[3] == {"chunk": 8, "held": 0} for p in prefills)
    assert any(t[3].get("prefilling") == 1
               for t in _named(events, "mx.decode.tick"))


def _afmoe():
    return _walk_model("grouped")[0]


def _parts_by_program(events):
    """``{(program, rung): {instruction: part}}`` of a trace's
    ``mx.decode.programs`` spans."""
    import json

    out = {}
    for ev in _named(events, "mx.decode.programs"):
        key = (ev[3]["program"], ev[3].get("rung"))
        assert key not in out, "written twice in one trace: %r" % (key,)
        out[key] = {inst: part
                    for part, insts in json.loads(ev[3]["parts"]).items()
                    for inst in insts}
    return out


def test_programs_span_is_written_once_a_trace_and_never_without(tiny,
                                                                 tmp_path):
    """One zero-length ``mx.decode.programs`` a program in every trace (the
    maps of ``warmup()``, whole), by the worker's first look that finds the
    trace live, ahead of the trace's first program; no trace, no span."""
    import time

    from mxnet_tpu.serving import decode as decode_mod

    def soak(eng):
        for f in [eng.submit(p, m) for p, m in _prompts(4)]:
            f.result(timeout=120)

    def count():
        return spans_mod.SPAN_MS.count(category="serving",
                                       span="decode.programs")

    with _engine(tiny, name="spans_programs") as eng:
        eng.warmup()
        before = count()
        soak(eng)                       # telemetry on, no trace
        assert count() == before
        for i in range(2):
            # the second trace finds the engine idle since the first; the
            # waiting worker looks once a slice whether a trace is live, and
            # has to see none in between to take the next for a new one
            time.sleep(3 * decode_mod._IDLE_SLICE_S)
            events = _traced(tmp_path / str(i), lambda: soak(eng))
            got = _parts_by_program(events)
            assert sorted(got, key=str) == sorted(
                [("jit_mx_decode_step", None)]
                + [("jit_mx_prefill", r) for r in (8, 16, 48)], key=str)
            for row in eng._programs:
                assert got[(row["program"], row.get("rung"))] == row["parts"]
            spans = _named(events, "mx.decode.programs")
            assert all(s[1] == s[2] or s[2] - s[1] < 1e6 for s in spans)
            assert max(s[2] for s in spans) <= min(
                e[1] for e in _named(events, "mx.decode.prefill")
                + _named(events, "mx.decode.dispatch"))
            step = [s for s in spans if "rung" not in s[3]][0][3]
            assert step["step_temp_bytes"] == \
                eng.stats()["decode_step_temp_bytes"]
            assert step["mixed"] >= 0 and step["unnamed"] == \
                eng.stats()["program_parts"]["jit_mx_decode_step"]["unnamed"]
        assert count() == before + 2 * 4


def test_a_trace_that_is_live_before_warmup_still_gets_every_program(
        tiny, tmp_path):
    """``warmup()`` hands the worker every program's map at once; a trace
    that was live before (the worker had looked and found nothing to
    write) gets each program once, from the next look on."""
    with _engine(tiny, name="spans_late") as eng:
        def work():
            eng.generate(np.arange(1, 7, dtype=np.int32), 4, timeout=120)
            assert eng.stats()["program_parts"] == {}
            eng.warmup()
            eng.generate(np.arange(1, 7, dtype=np.int32), 4, timeout=120)

        events = _traced(tmp_path, work)
        stats = eng.stats()
    got = _parts_by_program(events)     # (asserts: none written twice)
    assert len(got) == len(stats["program_parts"]) == 4
    assert all(got[key] for key in got)
    assert stats["compile_count"] == 4


def test_empty_engine_waits_under_idle_spans_a_late_trace_still_sees(
        tiny, tmp_path):
    """The worker of an engine with nothing to do waits in slices, each an
    ``mx.decode.idle{why=empty}``: a trace that starts in the middle of the
    wait loses one slice at most, and no pass runs."""
    import time

    from mxnet_tpu.serving import decode as decode_mod

    with _engine(tiny, name="spans_idle") as eng:
        eng.warmup()
        time.sleep(2 * decode_mod._IDLE_SLICE_S)    # deep in the wait

        def work():
            with telemetry.span("t_idle_marker"):
                pass
            time.sleep(0.6)

        events = _traced(tmp_path, work)
    (marker,) = _named(events, "mx.t_idle_marker")
    idle = _named(events, "mx.decode.idle")
    assert not _named(events, "mx.decode.tick")
    assert len(idle) >= 4 and all(e[3] == {"why": "empty"} for e in idle)
    slice_ns = decode_mod._IDLE_SLICE_S * 1e9
    # the slice the trace started in is lost, the next one is there
    assert idle[0][1] - marker[1] < 3 * slice_ns
    for a, b in zip(idle, idle[1:]):
        assert a[2] <= b[1] and b[1] - a[2] < slice_ns   # back to back
    assert sum(e[2] - e[1] for e in idle) > 0.5 * (idle[-1][2] - idle[0][1])


def test_deferred_pass_sleeps_under_an_idle_span(tiny, tmp_path):
    """Queued work that admission keeps deferring (here: a tenant without a
    token budget) with nothing in flight: the pass yields under
    ``mx.decode.idle{why=deferred}``, inside its ``mx.decode.tick``."""
    with _engine(tiny, name="spans_deferred", timeout_ms=400,
                 tenants="slow,rate=1,burst=12") as eng:
        eng.warmup()
        # the first request spends the budget, the second waits for it
        eng.submit(np.arange(1, 6, dtype=np.int32), 4,
                   tenant="slow").result(timeout=120)
        fut = eng.submit(np.arange(1, 6, dtype=np.int32), 4, tenant="slow")

        def work():
            try:
                fut.result(timeout=120)
            except Exception:  # noqa: BLE001 - it may time out queued
                pass

        events = _traced(tmp_path, work)
    idle = [e for e in _named(events, "mx.decode.idle")
            if e[3] == {"why": "deferred"}]
    ticks = _named(events, "mx.decode.tick")
    assert idle and all(any(_inside(e, t) for t in ticks) for e in idle)


@pytest.mark.parametrize("chunk", [0, 8])
def test_prefill_span_carries_the_slots_it_holds_under_a_burst(tiny, tmp_path,
                                                              chunk):
    """``held`` on ``mx.decode.prefill``: the slots decoding as the prefill
    is launched. A pass's ``active`` is taken after its monolithic prefills
    (admission runs them), and a finished prefill's own slot joins the
    decoding ones: the last prefill of a pass holds ``active`` - 1 (every
    request here outlives its first token), and within a pass each prefill
    holds one slot more than the one before. A pass's one chunk runs after
    ``active`` is taken and holds just those. The counter and ``stats()``
    carry duration x held."""
    from mxnet_tpu.serving import decode as decode_mod

    name = "spans_held_%d" % chunk
    rng = np.random.RandomState(3)
    burst = [(rng.randint(1, 32, 6).astype(np.int32), 8) for _ in range(6)]
    with _engine(tiny, name=name, prefill_chunk=chunk) as eng:
        eng.warmup()
        assert eng.stats()["prefill_held_slot_ms"] == 0.0

        def work():
            for f in [eng.submit(p, m) for p, m in burst]:
                f.result(timeout=120)
            eng.close()

        events = _traced(tmp_path, work)
        stats = eng.stats()
    prefills = _named(events, "mx.decode.prefill")
    assert len(prefills) == 6
    assert all(0 <= p[3]["held"] < eng.num_slots for p in prefills)
    assert max(p[3]["held"] for p in prefills) >= 1
    for t in _named(events, "mx.decode.tick"):
        mine = [p for p in prefills if _inside(p, t)]
        if mine:
            assert mine[-1][3]["held"] == t[3]["active"] - (0 if chunk else 1)
            assert [p[3]["held"] for p in mine] == list(range(
                mine[0][3]["held"], mine[0][3]["held"] + len(mine)))
    lost_ms = sum(p[3]["held"] * (p[2] - p[1]) / 1e6 for p in prefills)
    assert stats["prefill_held_slot_ms"] == pytest.approx(lost_ms, rel=0.3,
                                                          abs=0.5)
    assert decode_mod._T_PREFILL_HELD.value(server=name) == pytest.approx(
        stats["prefill_held_slot_ms"])
    assert "mxnet_decode_prefill_held_slot_ms_total" \
        in telemetry.render_prometheus()


def test_held_is_not_computed_with_nobody_reading(tiny, monkeypatch):
    """No trace and the registry off: a prefill counts no slot."""
    with _engine(tiny, name="spans_held_off") as eng:
        eng.warmup()
        monkeypatch.setattr(
            eng, "_decoding", lambda: pytest.fail("held was computed"),
            raising=True)
        telemetry.set_enabled(False)
        try:
            # (the pass itself asks once a pass, after admission: only the
            # prefill's own count is under test, so admit and stop there)
            with eng._prefill_span(rung=8) as span:
                span.set_args(x=1)
        finally:
            telemetry.set_enabled(True)
        monkeypatch.undo()
        assert eng.stats()["prefill_held_slot_ms"] == 0.0


@pytest.mark.parametrize("kind", ["plain", "grouped"])
def test_tracing_the_parts_changes_nothing_served(kind, tmp_path):
    """The same tokens, compile count, overlap and recompiles with the
    programs' maps built and a trace live as the engine's contract gives
    without: one program a rung and the step, most steps dispatched over the
    one before, nothing compiled after ``warmup()``."""
    model, kw, _tables = _walk_model(kind)
    params = model.init_params(0)
    prompts = [np.arange(1 + i, 8 + 3 * i, dtype=np.int32) for i in range(4)]
    with _engine((model, params), name="spans_same_%s" % kind, num_slots=2,
                 prefill_buckets=(16, 64), **kw) as eng:
        assert eng.warmup() == 1 + len(eng.stats()["prefill_buckets"])
        served = []

        def work():
            futs = [eng.submit(p, 6) for p in prompts]
            served.extend(f.result(timeout=300) for f in futs)

        events = _traced(tmp_path, work)
        stats = eng.stats()
    if kind == "plain":
        for p, got in zip(prompts, served):
            assert list(got) == list(model.reference_generate(params, p, 6))
    else:
        from mxnet_tpu.serving import afmoe_reference

        for p, got in zip(prompts, served):
            seq = list(p)
            for tok in got:
                logits = afmoe_reference.forward_logits(
                    model.cfg, params, np.asarray(seq, np.int32))
                assert int(np.argmax(logits[-1])) == int(tok)
                seq.append(int(tok))
    assert stats["steady_state_recompiles"] == 0
    assert stats["compile_count"] == 1 + len(stats["prefill_buckets"])
    assert stats["steps_overlapped"] >= stats["ticks"] - len(prompts) - 1
    assert len(_named(events, "mx.decode.programs")) == stats["compile_count"]


def test_stats_ticks_and_slot_ticks_give_the_occupancy(tiny):
    with _engine(tiny, name="spans_stats") as eng:
        eng.warmup()
        seen = [(0, 0)]
        for wave in range(3):
            futs = [eng.submit(p, m) for p, m in _prompts(4, seed=wave)]
            for f in futs:
                f.result(timeout=120)
            st = eng.stats()
            seen.append((st["ticks"], st["slot_ticks"]))
            assert st["slot_occupancy"] == pytest.approx(
                st["slot_ticks"] / float(st["ticks"] * eng.num_slots),
                abs=1e-12)
            assert 0.0 < st["slot_occupancy"] <= 1.0
    for (t0, s0), (t1, s1) in zip(seen, seen[1:]):
        assert t1 > t0 and s1 > s0        # monotonic: a window is a delta
        assert s1 - s0 <= (t1 - t0) * eng.num_slots


def test_device_programs_carry_stable_names(tiny):
    with _engine(tiny, name="spans_names") as eng:
        names = [fn.__name__ for fn in (eng._step, eng._prefill_jit,
                                        eng._chunk_jit, eng._cow_jit)]
    assert names == ["mx_decode_step", "mx_prefill", "mx_prefill_chunk",
                     "mx_kv_cow"]
    plane, step = _gluon_plane("spn_")
    step(0)
    if plane.plane == "graph":
        assert [fn.__name__ for fn in plane._jits.values()] == \
            ["mx_train_step"]
