"""The soak gates: each condition a long offered-load run was held to, as a
test at a size that runs in seconds on the CPU mesh.

What is asserted here is a count, an order or a token-exact comparison —
never a time or a rate (a CPU run yields none that means anything on the
chip; speed is ``benchmark/run.py``'s business). One flow per gate that
no other tier-1 test holds:

* continuous batching against restart-per-batch at the same slot count:
  the same tokens, fewer ticks, zero steady-state recompiles, with and
  without speculation;
* speculation against the speculation-off run: the same tokens, and an
  accept-all draft commits more than one token per speculating slot-tick;
* K system prompts shared by N requests, served with caching off, with
  the prefix cache and with prefix cache + chunked prefill;
* a hot tenant at ten times the offered load with a page budget, two
  background tenants, a live weight swap mid-run;
* the batch plane under client threads: every request answered, nothing
  compiled after warm-up;
* the bf16 train plane over a batch sweep: the graph plane, exactly one
  dispatch a step;
* ZeRO 0/1/2 through ``gluon.Trainer``: compile once, per-device
  optimizer bytes shrink;
* kill-at-step preemptions with a checkpoint every step: every batch
  trained exactly once;
* behind the router: one replica killed mid-run and every request
  completed exactly once; a rolling weight swap under load that drops
  nothing and compiles nothing; one scale-up on a queue-depth burn.
"""
import threading

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import elastic, gluon, nd, parallel, serving, telemetry
from mxnet_tpu import trainplane
from mxnet_tpu.fastpath import zero
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving.fleet import FleetRouter
from mxnet_tpu.telemetry import slo


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.disable()
    yield
    chaos.disable()


@pytest.fixture(scope="module")
def tiny():
    model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    return model, model.init_params(0)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("name", "sg%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


def _assert_oracle_exact(tiny, reqs, outs):
    model, params = tiny
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))


# ---------------------------------------------------------------------------
# continuous batching against restart-per-batch
# ---------------------------------------------------------------------------

def _long_tail_requests(n=16):
    # mostly short answers, a few long ones: the mix where draining a
    # wave before refilling strands the most slot-time
    rng = np.random.RandomState(0)
    out_mix = [3, 3, 3, 6, 6, 12, 20, 28]
    return [(rng.randint(1, 32, int(rng.randint(2, 12))).astype(np.int32),
             out_mix[i % len(out_mix)]) for i in range(n)]


@pytest.mark.parametrize("spec_k", [0, 3])
def test_continuous_batching_matches_restart_per_batch(tiny, spec_k):
    reqs = _long_tail_requests()
    slots = 4

    def run(waves):
        with _engine(tiny, num_slots=slots, spec_k=spec_k,
                     spec_draft="prompt_lookup") as eng:
            eng.warmup()
            outs = []
            for lo in range(0, len(reqs), slots if waves else len(reqs)):
                hi = lo + (slots if waves else len(reqs))
                futs = [eng.submit(p, m) for p, m in reqs[lo:hi]]
                outs += [f.result(timeout=120) for f in futs]
            return outs, eng.stats()

    cont, cont_stats = run(waves=False)
    wave, wave_stats = run(waves=True)
    _assert_oracle_exact(tiny, reqs, cont)
    for a, b in zip(cont, wave):
        np.testing.assert_array_equal(a, b)
    for stats in (cont_stats, wave_stats):
        assert stats["completed"] == len(reqs) and stats["errors"] == 0
        assert stats["steady_state_recompiles"] == 0
        assert stats["kvcache"]["pages_in_use"] == 0
    # a freed slot re-admits on the same tick: the same tokens take
    # fewer decode steps, each over fuller slots
    assert cont_stats["ticks"] < wave_stats["ticks"]
    assert cont_stats["slot_occupancy"] > wave_stats["slot_occupancy"]
    assert slo.audit() == []


# ---------------------------------------------------------------------------
# speculation against the speculation-off run
# ---------------------------------------------------------------------------

def _motif_requests(n=8, out=12):
    # repetitive-motif prompts: what prompt lookup is built for
    rng = np.random.RandomState(2)
    reqs = []
    for _ in range(n):
        motif = rng.randint(1, 32, 4).astype(np.int32)
        reqs.append((np.concatenate([motif, motif, motif[:2]]), out))
    return reqs


@pytest.fixture(scope="module")
def spec_off_tokens(tiny):
    reqs = _motif_requests()
    with _engine(tiny, spec_k=0) as eng:
        eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        return [f.result(timeout=120) for f in futs]


@pytest.mark.parametrize("draft", ["model", "prompt_lookup"])
def test_speculation_emits_the_spec_off_tokens(tiny, spec_off_tokens, draft):
    reqs = _motif_requests()
    with _engine(tiny, spec_k=3, spec_draft=draft) as eng:
        eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for a, b in zip(spec_off_tokens, outs):
        np.testing.assert_array_equal(a, b)
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    spec = stats["speculative"]
    assert spec["proposed_tokens"] > 0
    if draft == "model":
        # the served model drafting for itself is the accept-all bound:
        # the widened tick must beat one token per dispatch
        assert spec["accepted_per_tick"] > 1.0
        assert spec["acceptance_rate"] > 0.9
    else:
        assert spec["accepted_per_tick"] >= 1.0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# K shared system prompts, three ways to serve them
# ---------------------------------------------------------------------------

def _shared_prefix_requests(n_sys=3, sys_len=20, n=12, out=5):
    rng = np.random.RandomState(1)
    sys_prompts = [rng.randint(1, 32, sys_len).astype(np.int32)
                   for _ in range(n_sys)]
    reqs = []
    for i in range(n):
        tail = rng.randint(1, 32, int(rng.randint(2, 6))).astype(np.int32)
        reqs.append((np.concatenate([sys_prompts[i % n_sys], tail]), out))
    return reqs


@pytest.mark.parametrize("mode,prefix_cache,chunk", [
    ("cache_off", False, 0),
    ("cache_on", True, 0),
    ("cache_on_chunked", True, 8),
])
def test_shared_system_prompts_served_three_ways(tiny, mode, prefix_cache,
                                                 chunk):
    reqs = _shared_prefix_requests()
    with _engine(tiny, num_slots=3, page_size=8, prefill_buckets=(16, 32),
                 prefix_cache=prefix_cache, prefill_chunk=chunk) as eng:
        warm = eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    _assert_oracle_exact(tiny, reqs, outs)
    assert stats["completed"] == len(reqs) and stats["errors"] == 0
    assert stats["compile_count"] == warm
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    if prefix_cache:
        # three prompts, twelve requests: at most the first of each misses
        assert stats["prefix_hit_ratio"] > 0
        assert stats["kvcache"]["prefix_hits"] >= len(reqs) - 2 * 3
    else:
        assert stats.get("prefix_hit_ratio", 0.0) == 0.0
    # a hit's unshared tail runs through the chunk program, chunked or not
    assert (stats["prefill_chunks"] > 0) == prefix_cache


# ---------------------------------------------------------------------------
# a hot tenant, two background tenants, a live weight swap
# ---------------------------------------------------------------------------

def test_hot_tenant_soak_across_a_live_swap(tiny):
    model, params = tiny
    params_b = model.init_params(1)
    events, lock = [], threading.Lock()

    def note(kind, tenant, rid):
        with lock:
            events.append((kind, tenant, rid))

    # hot's budget covers ONE sequence (prompt + max_new = 7 tokens on a
    # page of 8), so the quota binds on every tick of the run
    with _engine(tiny, num_slots=2, max_seq_len=32, page_size=8,
                 prefill_buckets=(8,), prefix_cache=False,
                 tenants="hot,weight=1,pages=1;bg1,weight=1;bg2,weight=1"
                 ) as eng:
        eng.register_variant("rollout", params_b)
        eng.warmup()
        futs = []

        def submit(tenant, rid):
            note("submit", tenant, rid)
            f = eng.submit([1 + rid % 30], 6, tenant=tenant)
            f.add_done_callback(lambda _f: note("done", tenant, rid))
            futs.append(f)

        rid = 0
        for wave in range(4):
            for _ in range(10):          # ten hot requests ...
                submit("hot", rid)
                rid += 1
            for bg in ("bg1", "bg2"):    # ... to one of each background
                submit(bg, rid)
                rid += 1
            if wave == 1:                # a rollout lands under load
                eng.use_variant("rollout", timeout=120)
        for f in futs:
            assert len(f.result(timeout=180)) == 6
        stats = eng.stats()
    assert stats["errors"] == 0 and stats["completed"] == len(futs)
    assert stats["weight_swaps"] == 1
    assert stats["active_variant"] == "rollout"
    assert stats["steady_state_recompiles"] == 0
    hot = stats["tenants"]["hot"]
    assert hot["page_budget"] == 1 and hot["pages_in_use_max"] <= 1
    assert hot["deferred_pages"] > 0
    assert stats["kvcache"]["pages_in_use"] == 0
    # no starvation: a background request submitted behind ten queued hot
    # ones completes after a handful of them, not after the backlog
    overtaken = []
    for i, (kind, tenant, rid) in enumerate(events):
        if kind != "submit" or tenant == "hot":
            continue
        done_at = events.index(("done", tenant, rid))
        hot_done = sum(1 for k, t, _r in events[i:done_at]
                       if k == "done" and t == "hot")
        hot_waiting = sum(1 for k, t, _r in events[:i]
                          if k == "submit" and t == "hot") \
            - sum(1 for k, t, _r in events[:i]
                  if k == "done" and t == "hot")
        overtaken.append((hot_done, hot_waiting))
    assert len(overtaken) == 8
    assert all(done <= 6 for done, _w in overtaken), overtaken
    assert max(w for _d, w in overtaken) >= 10, overtaken
    assert slo.audit() == []


# ---------------------------------------------------------------------------
# the batch plane under client threads
# ---------------------------------------------------------------------------

def test_offered_load_from_client_threads_compiles_nothing():
    net = nn.Sequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize()
    net(nd.array(np.zeros((1, 16), np.float32)))  # materialize params
    engine = serving.BlockEngine(net)
    rng = np.random.RandomState(0)
    reqs = rng.rand(32, 16).astype(np.float32)
    want = net(nd.array(reqs)).asnumpy()
    srv = serving.Server(engine, (16,), buckets=(1, 4, 16), max_delay_ms=2.0,
                         queue_depth=4096, timeout_ms=0,
                         name="sg%d" % np.random.randint(1 << 30))
    srv.warmup()
    warm = engine.compile_count
    clients, per_client = 4, 25
    errors, answered = [], []

    def client(cid):
        try:
            futs = [(k % 32, srv.submit(reqs[k % 32]))
                    for k in range(cid, cid + per_client * clients, clients)]
            for row, f in futs:
                np.testing.assert_allclose(f.result(timeout=120), want[row],
                                           rtol=1e-5, atol=1e-6)
                answered.append(row)
        except Exception as e:  # noqa: BLE001 - asserted empty below
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = srv.stats()
    srv.close()
    assert errors == []
    assert stats["completed"] == clients * per_client == len(answered)
    assert stats["shed"] == 0 and stats["timeouts"] == 0
    assert engine.compile_count == warm   # every bucket was warmed


# ---------------------------------------------------------------------------
# the bf16 train plane over a batch sweep
# ---------------------------------------------------------------------------

def _opt_dispatches():
    return (telemetry.OPT_DISPATCHES.value(path="perparam")
            + telemetry.OPT_DISPATCHES.value(path="fused"))


@pytest.mark.parametrize("batch", [4, 8])
def test_bf16_plane_is_one_graph_dispatch_a_step(monkeypatch, batch):
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    monkeypatch.setenv("MXNET_TRAIN_DTYPE", "bf16")
    net = _mlp("sgb%d_" % batch)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore="device")
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(1))
    rs = np.random.RandomState(5)
    x = nd.array(rs.rand(batch, 6).astype(np.float32))
    y = nd.array(rs.randint(0, 8, (batch,)))
    plane.step(x, y)                      # activate + compile
    g0 = telemetry.STEP_DISPATCHES.value(plane="graph")
    o0 = _opt_dispatches()
    r0 = telemetry.RECOMPILES.value(site="trainplane.step")
    for _ in range(4):
        plane.step(x, y)
    # an eager-fallback step ALSO totals one dispatch (one fused update,
    # zero graph steps): the plane has to be checked, not only the count
    assert plane.plane == "graph"
    assert telemetry.STEP_DISPATCHES.value(plane="graph") - g0 == 4
    assert _opt_dispatches() - o0 == 0
    assert telemetry.RECOMPILES.value(site="trainplane.step") - r0 == 0


# ---------------------------------------------------------------------------
# ZeRO 0/1/2 through gluon.Trainer
# ---------------------------------------------------------------------------

B = 8


def _mlp(prefix, hidden=16):
    mx.random.seed(7)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"), nn.Dense(8))
    net.initialize()
    with mx.autograd.pause():
        net(nd.ones((B, 6)))
    net.hybridize()
    return net


_OPTS = {"sgd": {"learning_rate": 0.05, "momentum": 0.9},
         "adam": {"learning_rate": 0.01}}


def _eager_zero_run(prefix, opt, steps=5):
    """`steps` eager Trainer steps; (state bytes on device 0, compiles of
    the sharded update after the first step, the plane it ran on)."""
    rs = np.random.RandomState(3)
    xs = rs.rand(steps * B, 6).astype(np.float32)
    ys = rs.randint(0, 8, (steps * B,))
    net = _mlp(prefix)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), opt, dict(_OPTS[opt]),
                       kvstore="device")
    after_first = None
    for s in range(steps):
        with mx.autograd.record():
            loss = loss_fn(net(nd.array(xs[s * B:(s + 1) * B])),
                           nd.array(ys[s * B:(s + 1) * B]))
        loss.backward()
        tr.step(B)
        if after_first is None:
            after_first = telemetry.RECOMPILES.value(
                site="fastpath.zero_apply")
    upd = tr._updaters[0]
    recompiles = telemetry.RECOMPILES.value(
        site="fastpath.zero_apply") - after_first
    return (zero.state_bytes_on(jax.devices()[0], upd), recompiles,
            zero.plane_of(upd))


def _zero_fallbacks():
    fam = telemetry.REGISTRY.get("mxnet_zero_fallbacks_total")
    return sum(s["value"] for s in fam.series()) if fam else 0


@pytest.mark.parametrize("opt", sorted(_OPTS))
@pytest.mark.parametrize("level", [0, 1, 2])
def test_zero_sweep_compiles_once_and_shrinks_state(monkeypatch, level, opt):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setenv("MXNET_ZERO_DEVICES", "2")
    monkeypatch.setenv("MXNET_ZERO", "0")
    full, _r, plane0 = _eager_zero_run("sgz%s%d0_" % (opt, level), opt)
    assert plane0 is None and full > 0
    if level == 0:
        return
    fell_back = _zero_fallbacks()
    monkeypatch.setenv("MXNET_ZERO", str(level))
    sharded, recompiles, plane = _eager_zero_run(
        "sgz%s%d1_" % (opt, level), opt)
    # the sharded plane adopted the updater (no silent fallback); its
    # update program compiled once although Adam's step count and bias
    # correction change every step; device 0 holds half the state
    assert plane is not None and plane.level == level and plane.dp == 2
    assert _zero_fallbacks() == fell_back
    assert recompiles == 0
    assert sharded <= full * 0.5 + 64


# ---------------------------------------------------------------------------
# kill-at-step preemptions, a checkpoint every step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("zero_level", [0, 1])
@pytest.mark.parametrize("async_save", [True, False])
def test_kill_resume_trains_every_batch_exactly_once(tmp_path, monkeypatch,
                                                     async_save, zero_level):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setenv("MXNET_ZERO", str(zero_level))
    monkeypatch.setenv("MXNET_ZERO_DEVICES", "2")
    steps = 12
    rs = np.random.RandomState(0)
    X = rs.rand(steps * B, 6).astype(np.float32)
    Y = rs.randint(0, 8, (steps * B,)).astype(np.float32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    consumed, attempts = [], []
    cm = elastic.CheckpointManager(str(tmp_path))

    def train_fn(start, manager):
        net = _mlp("kr%d%d%d_" % (async_save, zero_level, len(attempts)))
        attempts.append(start)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        plane = trainplane.TrainPlane(net, loss_fn, tr,
                                      mesh=parallel.device_mesh(2))
        it = mx.io.NDArrayIter(X, Y, batch_size=B)
        last = manager.restore_training(net=net, trainer=tr, train_iter=it)
        for step in range(last + 1, steps):
            elastic.step_boundary(manager=manager)
            batch = it.next()
            # which rows the iterator really handed out, not the loop's
            # idea of them: the cursor has to round-trip
            consumed.append(int(np.flatnonzero(
                (X == batch.data[0].asnumpy()[0]).all(axis=1))[0]) // B)
            plane.step(batch.data[0], batch.label[0])
            manager.save_training(step, net=net, trainer=tr, train_iter=it,
                                  async_save=async_save)
        manager.wait()
        return "done"

    # both kills are reachable: the run makes steps + 2 boundary calls
    with chaos.active("site=elastic.step,at=4:10,action=kill"):
        assert elastic.run_elastic(train_fn, cm, max_restarts=4,
                                   restart_delay=0) == "done"
    assert len(attempts) == 3            # two kills, two resumes
    assert consumed == list(range(steps))  # no replay, no skip


# ---------------------------------------------------------------------------
# one replica killed mid-run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("restart", [True, False])
def test_replica_kill_mid_run_completes_every_request_once(tiny, restart):
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(1, 32, 10).astype(np.int32), 6) for _ in range(10)]
    done = []
    name = "sgf%d" % np.random.randint(1 << 30)
    with FleetRouter(_fleet_engine(tiny), replicas=2, name=name) as fl:
        futs = []
        for p, m in reqs:
            f = fl.submit(p, m)
            f.add_done_callback(lambda _f: done.append(1))
            futs.append(f)
        victim = max(fl.debug_state()["replicas"].items(),
                     key=lambda kv: kv[1]["inflight"])[0]
        fl.kill_replica(victim, restart=restart)
        outs = [f.result(timeout=180) for f in futs]
        router = fl.stats()["router"]
        state = fl.debug_state()["replicas"][victim]
    _assert_oracle_exact(tiny, reqs, outs)
    # nothing lost, nothing answered twice: the router's count, the
    # callbacks' count and the number submitted are one number
    assert router["submitted"] == len(reqs)
    assert router["completed"] == len(reqs) == len(done)
    assert router["resubmitted"] >= 1
    assert state["deaths"] == 1


def _fleet_engine(tiny):
    model, params = tiny

    def make(name):
        return serving.DecodeEngine(
            model, params, name=name, num_slots=2, max_seq_len=48,
            prefill_buckets=(16,), page_size=8, prefix_cache=True,
            timeout_ms=0)

    return make


def test_rolling_swap_under_load_drops_nothing_and_compiles_nothing(tiny):
    model, params = tiny
    params_b = model.init_params(1)
    rng = np.random.RandomState(23)
    reqs = [(rng.randint(1, 32, 10).astype(np.int32), 5) for _ in range(6)]
    with FleetRouter(_fleet_engine(tiny), replicas=2,
                     name="sgf%d" % np.random.randint(1 << 30)) as fl:
        fl.warmup()
        fl.register_variant("rollout", params_b)
        futs = [fl.submit(p, m) for p, m in reqs]   # in flight across it
        assert fl.rolling_swap(variant="rollout", timeout=120) == 2
        assert all(len(f.result(timeout=120)) == 5 for f in futs)
        p = rng.randint(1, 32, 9).astype(np.int32)
        np.testing.assert_array_equal(    # traffic after it runs the rollout
            fl.generate(p, 4, timeout=120),
            model.reference_generate(params_b, p, 4))
        stats = fl.stats()
    assert stats["router"]["completed"] == len(reqs) + 1
    assert stats["steady_state_recompiles"] == 0
    assert {row["active_variant"] for row in stats["replicas"].values()} \
        == {"rollout"}


def test_queue_depth_burn_scales_the_fleet_up_once(tiny):
    slo.reset()
    try:
        with FleetRouter(_fleet_engine(tiny), replicas=1, max_replicas=2,
                         name="sgf%d" % np.random.randint(1 << 30)) as fl:
            rep = next(iter(fl.debug_state()["replicas"]))
            slo.note_bound("queue_depth", rep, 10)
            depth = telemetry.gauge("mxnet_serving_queue_depth",
                                    labels=("server",))
            depth.set(9.5, server=rep)       # mean depth / bound > 0.9
            event = fl.autoscale_tick()
            depth.set(0.0, server=rep)
            assert event is not None and event["action"] == "up"
            assert fl.stats()["replicas_live"] == 2
            assert fl.autoscale_tick() is None   # the cooldown holds
            # the replica it built serves
            model, params = tiny
            p = np.asarray([3, 1, 4, 1, 5], np.int32)
            for _ in range(3):
                np.testing.assert_array_equal(
                    fl.generate(p, 3, timeout=120),
                    model.reference_generate(params, p, 3))
    finally:
        slo.reset()
