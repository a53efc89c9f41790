"""HBM pressure governor + OOM survival plane (ISSUE-19 acceptance).

Tier-1, CPU, deterministic: the chaos ``action=oom`` schedules are
seeded, so every "5% OOM" soak here either always passes or always
fails. Covers the governor's hysteresis ladder and red latch, OOM
classification (injected/host/device), the retry policy's refuse-to-
retry-OOM guard, the kvcache shed/reclaim accounting behind the yellow
rung, the orange rung's defer-batch-never-interactive contract, the
decode OOM-survival soak (every request oracle-exact or cleanly
errored, worker alive, red latched + green recovered, zero steady-state
recompiles), the /healthz 503 + ``pressure`` field, the ``hbm``
/debug/state view, and the trainplane OOM path (structured diagnostic
in a flight-recorder dump BEFORE the controlled eager fallback).
"""
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, serving, telemetry, trainplane
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import FaultInjected, RetryPolicy, chaos, hbm
from mxnet_tpu.serving.kvcache import PagedKVCache
from mxnet_tpu.telemetry import accounting, exporters, flightrec


@pytest.fixture(autouse=True)
def _clean_state():
    """Chaos off, fresh governor, fresh metrics + flight ring per test."""
    chaos.disable()
    hbm.reset()
    telemetry.REGISTRY.clear_data()
    flightrec.clear()
    yield
    chaos.disable()
    hbm.reset()
    telemetry.REGISTRY.clear_data()
    flightrec.clear()


# ---------------------------------------------------------------------------
# the governor: ladder, hysteresis, latch
# ---------------------------------------------------------------------------

def _gov(**kw):
    kw.setdefault("capacity_bytes", 100)
    kw.setdefault("yellow", 0.70)
    kw.setdefault("orange", 0.85)
    kw.setdefault("red", 0.95)
    kw.setdefault("hysteresis", 0.05)
    kw.setdefault("red_hold", 2)
    return hbm.PressureGovernor(**kw)


def test_ladder_tiers_up_and_hysteresis_down():
    gov = _gov()
    load = {"b": 0}
    gov.register_bound("plane", lambda: load["b"])
    assert gov.observe() == "green"
    load["b"] = 75
    assert gov.observe() == "yellow"
    load["b"] = 90
    assert gov.observe() == "orange"
    load["b"] = 96
    assert gov.observe() == "red"
    # 0.92 is below red's entry (0.95) but not by the hysteresis margin:
    # a ratio oscillating on the boundary must not flap the tier
    load["b"] = 92
    assert gov.observe() == "red"
    # clears 0.95 - 0.05: releases exactly ONE tier per observation
    load["b"] = 0
    assert gov.observe() == "orange"
    assert gov.observe() == "yellow"
    assert gov.observe() == "green"
    assert gov.tiers_seen() == ["yellow", "orange", "red",
                                "orange", "yellow", "green"]


def test_pressure_is_max_of_device_and_bounds():
    gov = _gov()
    gov.register_bound("kv", 40)
    gov.register_bound("zero", 35)
    assert gov.observe() == "yellow"          # bounds sum to 75
    gov.observe_device({0: (96, 96)})          # device watermark wins
    assert gov.tier() == "red"


def test_hbm_watermark_records_flightrec(monkeypatch):
    monkeypatch.setattr(accounting, "sample_hbm",
                        lambda devices=None: {0: (1024, 4096)})
    stats = accounting.hbm_watermark("test")
    assert stats == {0: (1024, 4096)}
    evs = [e for e in flightrec.tail(0) if e["kind"] == "hbm.watermark"]
    assert evs and evs[-1]["source"] == "test"
    assert evs[-1]["devices"]["0"] == {"in_use": 1024, "peak": 4096}
    # ... and the process governor received the same sample
    assert hbm.governor().oom_report()["device_used_bytes"] == 1024


def test_hbm_watermark_survives_probe_failure(monkeypatch):
    def boom(devices=None):
        raise RuntimeError("no stats on this backend")

    monkeypatch.setattr(accounting, "sample_hbm", boom)
    assert accounting.hbm_watermark("test") == {}


def test_emitter_rides_hbm_watermark(tmp_path, monkeypatch):
    monkeypatch.setattr(accounting, "sample_hbm",
                        lambda devices=None: {0: (7, 9)})
    path = str(tmp_path / "emit.jsonl")
    em = exporters.Emitter(60.0, path)
    assert em.emit_once()
    lines = open(path).read().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["metrics"] is not None
    evs = [e for e in flightrec.tail(0) if e["kind"] == "hbm.watermark"]
    assert evs and evs[-1]["source"] == "emitter"


@pytest.mark.parametrize("used,tier", [(75, "yellow"), (90, "orange"),
                                       (96, "red")])
def test_emitter_watermark_walks_the_process_governor(tmp_path, monkeypatch,
                                                      used, tier):
    """The whole safety path from its new home: the Emitter's tick takes
    the device sample, and that sample alone — no plane has registered a
    bound — puts the process governor on the ladder; a low sample lets it
    down again one tier an observation."""
    gov = hbm.governor()
    gov.set_capacity(100)
    sample = {"used": used}
    monkeypatch.setattr(accounting, "sample_hbm",
                        lambda devices=None: {0: (sample["used"], 100)})
    em = exporters.Emitter(60.0, str(tmp_path / "emit.jsonl"))
    assert gov.tier() == "green"
    assert em.emit_once()
    assert gov.tier() == tier
    assert gov.oom_report()["watermarks"][-1]["source"] == "emitter"
    sample["used"] = 10
    for _ in range(4):
        assert em.emit_once()
    assert gov.tier() == "green"


def test_unknown_capacity_means_no_tier_pressure():
    gov = _gov(capacity_bytes=0)
    gov.register_bound("kv", 1 << 40)
    assert gov.observe() == "green"            # only classified OOMs act


def test_red_latch_outranks_pressure_then_releases():
    gov = _gov(capacity_bytes=0, red_hold=2)
    assert gov.latch_red("oom:test") == "green"
    assert gov.tier() == "red" and gov.latched
    # the hold: pressure (0.0 — stat-less backend) may not speak yet
    assert gov.observe() == "red"
    # hold expired, pressure 0.0 -> green: the CPU CI recovery path
    assert gov.observe() == "green"
    assert not gov.latched
    assert gov.healthz_view()["latch_reason"] is None


def test_broken_callable_bound_reads_zero():
    gov = _gov()

    def boom():
        raise RuntimeError("probe died")

    gov.register_bound("bad", boom)
    gov.register_bound("good", 75)
    assert gov.observe() == "yellow"           # bad bound isolated to 0
    assert gov.oom_report()["bounds_bytes"] == {"bad": 0, "good": 75}


def test_oom_report_and_debug_view_are_json():
    gov = _gov()
    gov.register_bound("kv", lambda: 90)
    gov.observe(source="test")
    gov.latch_red("oom:test")
    gov.note_shed(3, "decode")
    rep = gov.oom_report()
    assert rep["tier"] == "red" and rep["latched"]
    assert rep["capacity_bytes"] == 100
    assert rep["watermarks"][-1]["source"] in ("test", "latch")
    view = gov.debug_view()
    assert view["transitions"][-1]["to"] == "red"
    assert view["last_shed"]["pages"] == 3
    assert view["thresholds"]["red"] == 0.95
    json.dumps(rep)
    json.dumps(view)


def test_governed_admit_default_and_knob(monkeypatch):
    gov = _gov()
    assert gov.governed_admit(8) == 4          # half the in-flight count
    assert gov.governed_admit(1) == 1          # floor 1
    monkeypatch.setenv("MXNET_HBM_RED_ADMIT", "3")
    assert gov.governed_admit(8) == 3


# ---------------------------------------------------------------------------
# classification + the chaos action and retry guard
# ---------------------------------------------------------------------------

def test_classify_kinds():
    assert hbm.classify(MemoryError("host heap")) == "host"
    assert hbm.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes")) == "device"
    assert hbm.classify(RuntimeError("failed to allocate request")) \
        == "device"
    assert hbm.classify(RuntimeError("device OOM during fusion")) \
        == "device"
    assert hbm.classify(ValueError("shape mismatch")) is None
    # the bare acronym matches as a whole word only: an unrelated
    # message containing "zoom"/"room" must not read as an OOM
    assert hbm.classify(ValueError("zoom level out of range")) is None
    assert hbm.classify(None) is None


def test_chaos_action_oom_injects_classifiable_oom():
    chaos.configure("seed=1,site=x.alloc,p=1.0,max=1,action=oom")
    with pytest.raises(chaos.OOMInjected) as ei:
        chaos.maybe_fail("x.alloc")
    exc = ei.value
    # the issue contract: a FaultInjected by inheritance, carrying the
    # literal status text a real XLA OOM would
    assert isinstance(exc, FaultInjected)
    assert "RESOURCE_EXHAUSTED" in str(exc)
    assert hbm.classify(exc) == "injected"
    chaos.maybe_fail("x.alloc")                # max=1: fires exactly once


def test_retry_policy_refuses_to_retry_oom():
    calls = {"n": 0}

    def alloc():
        calls["n"] += 1
        raise chaos.OOMInjected("t.site", calls["n"])

    p = RetryPolicy(max_attempts=5, base_delay_ms=0.0, jitter=0.0)
    with pytest.raises(chaos.OOMInjected):
        p.call(alloc, site="t.site")
    assert calls["n"] == 1                     # surfaced immediately
    from mxnet_tpu.resilience.policies import retries_counter

    assert retries_counter().value(site="t.site", outcome="oom") == 1


def test_oom_survival_ignores_non_oom():
    assert not hbm.oom_survival("any.plane",
                                ValueError("not a memory failure"))
    assert hbm.governor().tier() == "green"


def test_oom_survival_latches_counts_and_records():
    gov = hbm.governor()
    gov.register_bound("kv", 123)
    assert hbm.oom_survival("test.plane",
                            MemoryError("boom"), dump=False)
    assert gov.tier() == "red" and gov.latched
    events = [e for e in flightrec.tail() if e["kind"] == "hbm.oom"]
    assert events and events[-1]["plane"] == "test.plane"
    assert events[-1]["oom_kind"] == "host"
    assert events[-1]["report"]["bounds_bytes"]["kv"] == 123
    assert hbm._T_OOMS.value(plane="test.plane") == 1


# ---------------------------------------------------------------------------
# kvcache: reclaimable accounting + the yellow shed rung
# ---------------------------------------------------------------------------

def _cached_cache():
    """A pool with 5 usable pages, 2 of them parked in the cached-LRU."""
    c = PagedKVCache(num_slots=2, max_seq_len=32, num_layers=1,
                     num_kv_heads=1, head_dim=4, page_size=4, num_pages=6,
                     prefix_cache=True, name="shed%d" % np.random.randint(
                         1 << 30))
    c.reserve(0, 8)
    c.insert_prefix(0, np.arange(1, 9, dtype=np.int32))
    c.free(0)                                  # 2 indexed pages -> cached
    assert c.pages_free == 3 and c.pages_cached == 2
    return c


def test_admission_counts_reclaimable_cached_pages():
    c = _cached_cache()
    # the regression: 5 pages needed, only 3 on the free list — the
    # admission check must count the 2 reclaimable cached pages or every
    # warm cache reads as pressure and admission deadlocks at the head
    assert c.pages_available == 5
    assert c.can_admit(5 * 4)
    c.reserve(1, 5 * 4)                        # demand-reclaims the LRU
    assert c.pages_cached == 0 and c.pages_free == 0
    c.free(1)


def test_shed_cached_reclaims_ref0_only():
    c = _cached_cache()
    c.reserve(1, 4)                            # 1 live page, untouchable
    shed = c.shed_cached()
    assert shed == 2 and c.pages_cached == 0
    assert c.pages_in_use == 1                 # the live mapping survived
    assert c.pressure_sheds == 2
    assert c.shed_cached() == 0                # idempotent when drained
    c.free(1)
    from mxnet_tpu.serving.kvcache import _T_PRESSURE_SHEDS

    assert _T_PRESSURE_SHEDS.value(cache=c.name) == 2


# ---------------------------------------------------------------------------
# decode plane: ladder rungs + the OOM-survival acceptance soak
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
    kw.setdefault("name", "h%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


def test_orange_defers_batch_never_interactive(tiny):
    """The defer-vs-shed boundary: under orange, a batch-class head is
    DEFERRED (stays queued, admits when the tier recedes) while
    interactive heads keep flowing — degradation never inverts
    priority, and deferral is not a shed."""
    gov = hbm.governor()
    bound = 1 << 20
    gov.register_bound("test.synthetic", bound)
    with _engine(tiny) as eng:
        gold = eng.tenants.register(
            "gold", priority=serving.PRIORITY_CLASSES["interactive"])
        bulk = eng.tenants.register(
            "bulk", priority=serving.PRIORITY_CLASSES["batch"])
        eng.warmup()
        gov.set_capacity(int(bound / 0.87))    # pressure ~0.87: orange
        bulk_futs = [eng.submit([1, 2, 3], 4, tenant="bulk")
                     for _ in range(2)]
        gold_futs = [eng.submit([4, 5, 6], 4, tenant="gold")
                     for _ in range(2)]
        for f in gold_futs:                    # interactive flows
            f.result(timeout=120)
        deadline = time.time() + 60
        while not bulk.stats.snapshot()["deferred_pressure"] \
                and time.time() < deadline:
            time.sleep(0.01)
        assert bulk.stats.snapshot()["deferred_pressure"] > 0
        assert gold.stats.snapshot()["deferred_pressure"] == 0
        # not a shed: recede to green and the deferred heads admit
        gov.set_capacity(bound * 4)
        for f in bulk_futs:
            f.result(timeout=120)
        assert bulk.stats.snapshot()["shed"] == 0
    assert "orange" in gov.tiers_seen()


def test_decode_oom_survival_soak(tiny):
    """ISSUE-19 acceptance: chaos action=oom at p=0.05 on BOTH the
    decode step and prefill sites. Every request is oracle-exact or
    cleanly errored, the worker survives every injection, the governor
    latches red and recovers green once chaos stops, and governed
    re-admission never changes slot shapes (zero steady-state
    recompiles)."""
    model, params = tiny
    gov = hbm.governor()
    chaos.configure("seed=5,site=serving.decode,p=0.05,action=oom;"
                    "seed=5,site=serving.decode.prefill,p=0.05,"
                    "action=oom")
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(1, 32, int(rng.randint(2, 12))).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(18)]
    with _engine(tiny) as eng:
        eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        errored = 0
        for (p, m), f in zip(reqs, futs):
            try:
                got = f.result(timeout=180)
            except Exception:  # noqa: BLE001 - a surfaced error IS the
                errored += 1   # clean outcome under injected OOM
                continue
            np.testing.assert_array_equal(
                got, model.reference_generate(params, p, m))
        # the schedule must actually have fired (else the soak proved
        # nothing) — and an injection means the governor latched red
        assert "red" in gov.tiers_seen()
        stats = eng.stats()
        assert stats["hbm"]["oom_count"] > 0
        # recovery: chaos off, the latch releases within red_hold
        # admission passes (stat-less backend -> pressure 0.0) and a
        # second wave completes oracle-exact
        chaos.disable()
        futs2 = [eng.submit(p, m) for p, m in reqs[:6]]
        for (p, m), f in zip(reqs[:6], futs2):
            np.testing.assert_array_equal(
                f.result(timeout=180),
                model.reference_generate(params, p, m))
        assert eng._thread.is_alive()          # zero worker deaths
        stats = eng.stats()
    assert gov.tier() == "green" and not gov.latched
    assert stats["steady_state_recompiles"] == 0
    assert stats["hbm"]["governed_limit"] is None  # cleared on green
    text = telemetry.render_prometheus()
    assert "mxnet_hbm_oom_total" in text


def test_decode_oom_mid_prefill_isolated_and_governed(tiny):
    """A single deterministic prefill OOM: the victim request errors (or
    restarts clean), the survival path arms governed re-admission, and
    the engine keeps serving afterwards."""
    model, params = tiny
    with _engine(tiny) as eng:
        eng.warmup()
        chaos.configure(
            "seed=2,site=serving.decode.prefill,p=1.0,max=1,action=oom")
        victim = eng.submit([1, 2, 3], 4)
        with pytest.raises(Exception):
            victim.result(timeout=120)
        assert hbm.governor().latched or \
            "red" in hbm.governor().tiers_seen()
        # engine alive and exact after the full eviction + re-admission
        out = eng.submit([7, 8, 9], 5).result(timeout=120)
        np.testing.assert_array_equal(
            out, model.reference_generate(
                params, np.asarray([7, 8, 9], np.int32), 5))
        events = [e for e in flightrec.tail() if e["kind"] == "hbm.oom"]
        assert events and events[-1]["plane"] == "serving.decode.prefill"


# ---------------------------------------------------------------------------
# /healthz 503 + the hbm debug view
# ---------------------------------------------------------------------------

def test_healthz_degrades_while_red():
    from mxnet_tpu.telemetry.httpd import _Handler

    doc = _Handler._healthz()
    assert doc["status"] == "ok"
    assert doc["pressure"]["tier"] == "green"
    hbm.governor().latch_red("oom:test")
    doc = _Handler._healthz()
    assert doc["status"] == "degraded"
    assert doc["pressure"]["tier"] == "red"
    assert doc["pressure"]["latched"]
    assert doc["pressure"]["latch_reason"] == "oom:test"


def test_healthz_503_over_http():
    from urllib.error import HTTPError
    from urllib.request import urlopen

    from mxnet_tpu.telemetry import httpd as _httpd

    hbm.governor().latch_red("oom:test")
    srv = _httpd.start_httpd(port=0)
    try:
        host, port = srv.server_address[:2]
        with pytest.raises(HTTPError) as ei:
            urlopen("http://%s:%d/healthz" % (host, port), timeout=10)
        assert ei.value.code == 503
        doc = json.loads(ei.value.read().decode())
        assert doc["status"] == "degraded"
        assert doc["pressure"]["latched"]
    finally:
        _httpd.stop_httpd()


def test_debug_state_grows_hbm_view():
    from mxnet_tpu.telemetry import httpd as _httpd

    gov = hbm.governor()                       # registration side effect
    gov.register_bound("kv", 42)
    views = _httpd._debug_views()
    assert "hbm" in views
    view = views["hbm"]
    assert view["tier"] in hbm.TIERS
    assert view["bounds_bytes"]["kv"] == 42
    assert "transitions" in view and "thresholds" in view


def test_decode_stats_carry_hbm_view(tiny):
    with _engine(tiny) as eng:
        hv = eng.stats()["hbm"]
    assert hv["tier"] in hbm.TIERS
    assert "governed_limit" in hv and "pressure_sheds" in hv


# ---------------------------------------------------------------------------
# trainplane: structured diagnostic BEFORE the controlled fallback
# ---------------------------------------------------------------------------

B = 8


def _mlp_plane(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    monkeypatch.setenv("MXNET_FLIGHTREC_PATH", str(tmp_path / "box.json"))
    rs = np.random.RandomState(3)
    xs = rs.rand(4 * B, 6).astype(np.float32)
    ys = rs.randint(0, 8, (4 * B,))
    net = nn.HybridSequential(prefix="hbmoom_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(8))
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs[:B]))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
        mesh=parallel.device_mesh(1))
    return plane, xs, ys


def test_trainplane_oom_dumps_diagnostic_then_falls_back(monkeypatch,
                                                         tmp_path):
    plane, xs, ys = _mlp_plane(monkeypatch, tmp_path)
    loss = plane.step(nd.array(xs[:B]), nd.array(ys[:B]))
    assert plane.plane == "graph"
    assert np.isfinite(float(np.asarray(loss._data).mean()))
    # one injected OOM at the step's jit dispatch: the step must still
    # RETURN (eager fallback), with the post-mortem already on disk
    chaos.configure("seed=1,site=jit.compile,p=1.0,max=1,action=oom")
    loss = plane.step(nd.array(xs[B:2 * B]), nd.array(ys[B:2 * B]))
    assert np.isfinite(float(np.asarray(loss._data).mean()))
    assert plane.plane == "eager"              # controlled demotion
    assert hbm.governor().latched              # red latched
    path = flightrec.last_dump_path()
    assert path == str(tmp_path / "box.json") and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"].startswith("hbm oom at trainplane.step")
    ooms = [e for e in doc["events"] if e["kind"] == "hbm.oom"]
    assert ooms
    ev = ooms[-1]
    assert ev["plane"] == "trainplane.step"
    assert ev["oom_kind"] == "injected"
    # the structured diagnostic: per-plane breakdown + watermark history
    assert "bounds_bytes" in ev["report"]
    assert "watermarks" in ev["report"]
    assert ev["report"]["latched"] or ev["report"]["oom_count"] >= 1
    # training continues on the eager plane after the survival
    chaos.disable()
    loss = plane.step(nd.array(xs[2 * B:3 * B]), nd.array(ys[2 * B:3 * B]))
    assert np.isfinite(float(np.asarray(loss._data).mean()))


def test_trainplane_non_oom_still_propagates(monkeypatch, tmp_path):
    plane, xs, ys = _mlp_plane(monkeypatch, tmp_path)
    plane.step(nd.array(xs[:B]), nd.array(ys[:B]))
    assert plane.plane == "graph"
    # a plain injected fault is NOT an OOM: no hidden fallback — the
    # never-a-crash discipline is scoped to classified OOMs only
    chaos.configure("seed=1,site=jit.compile,p=1.0,max=1,action=fault")
    with pytest.raises(FaultInjected):
        plane.step(nd.array(xs[B:2 * B]), nd.array(ys[B:2 * B]))
    assert plane.plane == "graph"
    assert not hbm.governor().latched
