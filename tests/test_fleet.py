"""mxnet_tpu.serving.fleet — FleetRouter behind the single-engine surface
(CPU; split across the tier-1 and slow tiers, see below).

Covers the PR-17 acceptance surface: oracle parity through the router,
prefix-affinity placement (fleet hit ratio vs a single replica),
rendezvous + spillover routing, replica drain/rolling-swap with zero
drops, failure containment (kill + chaos site → exactly-once re-routing,
breaker isolation, index tombstones, restart), SLO-driven autoscaling up
and down, the /debug/state fleet view, and the fleet-wide tenant
snapshot merge.

Tiering: every multi-replica warmup costs ~10 jit compiles on a 1-core
CI box, so the soak-shaped tests ride the ``slow`` tier (the tier-1
budget is already nearly spent by the rest of the suite); tier-1 keeps
the surface smoke (oracle parity through a cold 2-replica fleet),
submit validation, the pure snapshot-merge unit, and a seconds-sized
twin of the slow kill soak (tests/test_soak_gates.py)."""
import time

import numpy as np
import pytest

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving.fleet import FleetRouter, fleet_debug_state
from mxnet_tpu.serving.tenancy import aggregate_snapshots
from mxnet_tpu.telemetry import httpd as _httpd
from mxnet_tpu.telemetry import slo as _slo
from mxnet_tpu.telemetry import tracing as _tracing


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


def _factory(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefix_cache", True)

    def make(name):
        return serving.DecodeEngine(model, params, name=name, **kw)

    return make


def _fname():
    return "fl%d" % np.random.randint(1 << 30)


def _routed(fl):
    fam = telemetry.REGISTRY.get("mxnet_fleet_routed_total")
    return {d: fam.value(fleet=fl.name, decision=d)
            for d in ("affine", "rendezvous", "spill")}


# ---------------------------------------------------------------------------
# single-engine surface: oracle parity, stats, close
# ---------------------------------------------------------------------------

def test_fleet_matches_oracle_through_router(tiny):
    # tier-1 smoke: a cold fleet (no warmup — lazy compiles, ONE prefill
    # rung) still answers oracle-exact through the router; the
    # zero-recompile contract is proven by the slow rolling-swap test,
    # which does pay for warmup
    model, params = tiny
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(1, 32, int(rng.randint(9, 14))).astype(np.int32),
             int(rng.randint(1, 5))) for _ in range(9)]
    with FleetRouter(_factory(tiny, prefill_buckets=(16,), max_seq_len=32),
                     replicas=2, name=_fname()) as fl:
        futs = [fl.submit(p, m) for p, m in reqs]
        for f, (p, m) in zip(futs, reqs):
            np.testing.assert_array_equal(
                f.result(timeout=120), model.reference_generate(params, p, m))
        s = fl.stats()
        assert s["replicas_live"] == 2
        assert s["router"]["submitted"] == 9
        assert s["router"]["completed"] == 9
        assert s["router"]["failed"] == 0
        assert len(s["replicas"]) == 2
        # the two replicas split the traffic (router-side bookkeeping)
        assert sum(s["replicas"][r]["completed"]
                   for r in s["replicas"]) == 9
        assert "default" in s["tenants"]
        assert s["tenants"]["default"]["completed"] == 9
    assert fl.closed
    assert fl.close() == 0  # idempotent
    with pytest.raises(serving.ServerClosedError):
        fl.submit([1, 2, 3], 2)


def test_fleet_submit_validation_propagates(tiny):
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        with pytest.raises(MXNetError):
            fl.submit([], 4)
        with pytest.raises(MXNetError):
            fl.submit([1] * 40, 40)  # exceeds max_seq_len on EVERY replica
        assert fl.stats()["router"]["failed"] == 1  # door-reject, no spin


# ---------------------------------------------------------------------------
# placement: affinity, rendezvous, spillover
# ---------------------------------------------------------------------------

def _prefix_workload(rng, n, prefix_len=16, tail=4, max_new=4):
    prefix = rng.randint(1, 32, prefix_len).astype(np.int32)
    return [(np.concatenate([prefix, rng.randint(1, 32, tail)
                             .astype(np.int32)]), max_new)
            for _ in range(n)]


@pytest.mark.slow
def test_prefix_affinity_pins_shared_prefix_to_one_replica(tiny):
    model, params = tiny
    rng = np.random.RandomState(3)
    reqs = _prefix_workload(rng, 8)
    with FleetRouter(_factory(tiny), replicas=3, name=_fname()) as fl:
        fl.warmup()
        for p, m in reqs:
            np.testing.assert_array_equal(
                fl.generate(p, m, timeout=120),
                model.reference_generate(params, p, m))
        counts = [row["routed"]
                  for row in fl.debug_state()["replicas"].values()]
        # every request shares the 2-page prefix: after the first lands,
        # the index pins the rest to the same replica
        assert max(counts) == len(reqs)
        routed = _routed(fl)
        assert routed["affine"] == len(reqs) - 1
        assert fl.stats()["prefix_hit_ratio"] > 0.5


@pytest.mark.slow
def test_fleet_hit_ratio_matches_single_replica(tiny):
    # the acceptance metric: a fleet of 3 keeps >= 0.9x the prefix-hit
    # ratio of a single replica on a shared-prefix workload
    rng = np.random.RandomState(11)
    reqs = _prefix_workload(rng, 10)
    ratios = []
    for n in (1, 3):
        with FleetRouter(_factory(tiny), replicas=n, name=_fname()) as fl:
            fl.warmup()
            for p, m in reqs:
                fl.generate(p, m, timeout=120)
            ratios.append(fl.stats()["prefix_hit_ratio"])
    single, fleet = ratios
    assert single > 0
    assert fleet >= 0.9 * single


@pytest.mark.slow
def test_cold_placement_is_rendezvous_then_affine(tiny):
    rng = np.random.RandomState(5)
    p = rng.randint(1, 32, 12).astype(np.int32)
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        fl.generate(p, 3, timeout=120)
        first = _routed(fl)
        assert first["rendezvous"] == 1 and first["affine"] == 0
        fl.generate(p, 3, timeout=120)
        second = _routed(fl)
        assert second["affine"] == 1  # the index remembers the placement


@pytest.mark.slow
def test_spillover_when_affine_replica_is_loaded(tiny):
    model, params = tiny
    rng = np.random.RandomState(9)
    # every request shares a prefix -> all affine to ONE replica; with
    # 1 slot and a deep backlog the router must spill past it once the
    # affine target carries >= MXNET_FLEET_SPILL_DEPTH in flight
    reqs = _prefix_workload(rng, 8, max_new=6)
    with FleetRouter(_factory(tiny, num_slots=1, queue_depth=16),
                     replicas=2, name=_fname()) as fl:
        fl.warmup()
        futs = [fl.submit(p, m) for p, m in reqs]
        for f, (p, m) in zip(futs, reqs):
            np.testing.assert_array_equal(
                f.result(timeout=120), model.reference_generate(params, p, m))
        counts = [row["routed"]
                  for row in fl.debug_state()["replicas"].values()]
        assert min(counts) > 0, "spillover never engaged: %r" % counts
        assert _routed(fl)["spill"] > 0


@pytest.mark.slow
def test_spillover_on_door_reject(tiny, monkeypatch):
    # disarm the proactive spill so the exception path carries: the
    # affine replica sheds at its door (queue full) and the router walks
    # to the next live replica instead of failing the caller
    monkeypatch.setenv("MXNET_FLEET_SPILL_DEPTH", "1000")
    model, params = tiny
    rng = np.random.RandomState(13)
    reqs = _prefix_workload(rng, 4, max_new=8)
    with FleetRouter(_factory(tiny, num_slots=1, queue_depth=2),
                     replicas=2, name=_fname()) as fl:
        fl.warmup()
        futs = [fl.submit(p, m, tenant="gold" if i % 2 else "bronze")
                for i, (p, m) in enumerate(reqs)]
        for f, (p, m) in zip(futs, reqs):
            np.testing.assert_array_equal(
                f.result(timeout=120), model.reference_generate(params, p, m))
        counts = [row["routed"]
                  for row in fl.debug_state()["replicas"].values()]
        assert min(counts) > 0, "door-reject spill never engaged: %r" % counts
        # fleet-wide tenant merge sees both tenants' traffic
        tens = fl.stats()["tenants"]
        assert tens["gold"]["completed"] == 2
        assert tens["bronze"]["completed"] == 2


# ---------------------------------------------------------------------------
# lifecycle: drain, add, rolling swap
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_drain_replica_zero_drop_and_counted(tiny):
    model, params = tiny
    rng = np.random.RandomState(17)
    reqs = _prefix_workload(rng, 5, max_new=5)  # all pin to one replica
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        futs = [fl.submit(p, m) for p, m in reqs]
        target = max(fl.debug_state()["replicas"].items(),
                     key=lambda kv: kv[1]["routed"])[0]
        drained = fl.drain_replica(target)
        for f, (p, m) in zip(futs, reqs):
            np.testing.assert_array_equal(
                f.result(timeout=120), model.reference_generate(params, p, m))
        assert fl.stats()["replicas_live"] == 1
        assert target not in fl.debug_state()["replicas"]
        # the return value IS the metric (the zero-drop receipt)
        fam = telemetry.REGISTRY.get("mxnet_serving_drain_completed_total")
        assert fam.value(server=target) == drained
        # nothing lost: every request completed exactly once somewhere
        assert fl.stats()["router"]["completed"] == len(reqs)


@pytest.mark.slow
def test_add_replica_takes_traffic(tiny):
    model, params = tiny
    rng = np.random.RandomState(19)
    with FleetRouter(_factory(tiny), replicas=1, name=_fname()) as fl:
        fl.warmup()
        added = fl.add_replica()
        assert fl.stats()["replicas_live"] == 2
        assert added in fl.debug_state()["replicas"]
        # cold prompts rendezvous over BOTH replicas now
        seen = set()
        for i in range(12):
            p = rng.randint(1, 32, 12).astype(np.int32)
            fl.generate(p, 2, timeout=120)
            for name, row in fl.debug_state()["replicas"].items():
                if row["routed"]:
                    seen.add(name)
        assert len(seen) == 2


@pytest.mark.slow
def test_rolling_swap_zero_drop_zero_recompiles(tiny):
    model, params = tiny
    params_b = model.init_params(1)
    rng = np.random.RandomState(23)
    reqs = [(rng.randint(1, 32, 10).astype(np.int32), 5) for _ in range(6)]
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        fl.register_variant("v2", params_b)
        futs = [fl.submit(p, m) for p, m in reqs]  # in flight across swap
        assert fl.rolling_swap(variant="v2", timeout=60) == 2
        for f in futs:
            assert f.result(timeout=120) is not None  # zero dropped
        p = rng.randint(1, 32, 9).astype(np.int32)
        np.testing.assert_array_equal(  # post-swap traffic runs v2
            fl.generate(p, 4, timeout=120),
            model.reference_generate(params_b, p, 4))
        s = fl.stats()
        assert s["steady_state_recompiles"] == 0
        for row in s["replicas"].values():
            assert row["active_variant"] == "v2"


# ---------------------------------------------------------------------------
# failure containment: kill, chaos, exactly-once
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_kill_replica_reroutes_exactly_once(tiny, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    model, params = tiny
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(1, 32, 10).astype(np.int32), 6) for _ in range(12)]
    with FleetRouter(_factory(tiny), replicas=3, name=_fname()) as fl:
        fl.warmup()
        futs = [fl.submit(p, m) for p, m in reqs]
        victim = fl.debug_state()["replicas"]  # kill the busiest
        victim = max(victim.items(), key=lambda kv: kv[1]["inflight"])[0]
        fl.kill_replica(victim)
        for f, (p, m) in zip(futs, reqs):
            np.testing.assert_array_equal(
                f.result(timeout=120), model.reference_generate(params, p, m))
        s = fl.stats()["router"]
        assert s["resubmitted"] >= 1
        assert s["completed"] == len(reqs)
        # exactly-once, proven on the trace terminal contract: every
        # fleet trace carries AT MOST one terminal hop
        terminals = ("complete", "error", "shed", "timeout", "rejected")
        fleet_traces = 0
        for tid in _tracing.trace_ids():
            tr = _tracing.get_trace(tid)
            if not tr or tr.get("plane") != "fleet":
                continue
            fleet_traces += 1
            terms = [e for e in tr["events"] if e["kind"] in terminals]
            assert len(terms) <= 1, (tid, terms)
        assert fleet_traces >= len(reqs)
        # the dead replica restarts and rejoins (daemon rebuild)
        for _ in range(300):
            if fl.debug_state()["replicas"][victim]["state"] == "live":
                break
            time.sleep(0.05)
        row = fl.debug_state()["replicas"][victim]
        assert row["state"] == "live" and row["deaths"] == 1
        assert row["breaker"] == "closed"  # restart probe closed it
        p = rng.randint(1, 32, 8).astype(np.int32)
        np.testing.assert_array_equal(  # the rebuilt replica serves
            fl.generate(p, 3, timeout=120),
            model.reference_generate(params, p, 3))


@pytest.mark.slow
def test_kill_without_restart_isolates_via_breaker(tiny):
    model, params = tiny
    rng = np.random.RandomState(29)
    reqs = _prefix_workload(rng, 4)
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        for p, m in reqs[:2]:
            fl.generate(p, m, timeout=120)
        victim = max(fl.debug_state()["replicas"].items(),
                     key=lambda kv: kv[1]["routed"])[0]
        before = fl.debug_state()["replicas"][victim]["routed"]
        fl.kill_replica(victim, restart=False)
        row = fl.debug_state()["replicas"][victim]
        assert row["state"] == "dead" and row["breaker"] == "open"
        assert fl.stats()["router"]["index_entries"] == 0  # tombstoned
        for p, m in reqs[2:]:  # same prefix now re-routes elsewhere
            np.testing.assert_array_equal(
                fl.generate(p, m, timeout=120),
                model.reference_generate(params, p, m))
        assert fl.debug_state()["replicas"][victim]["routed"] == before


@pytest.mark.slow
def test_chaos_site_kills_replica_at_routing(tiny):
    model, params = tiny
    rng = np.random.RandomState(31)
    p = rng.randint(1, 32, 10).astype(np.int32)
    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        # probe where this prompt lands, then arm the fault at exactly
        # that replica: the affine re-submit MUST walk into it
        fl.generate(p, 3, timeout=120)
        victim = max(fl.debug_state()["replicas"].items(),
                     key=lambda kv: kv[1]["routed"])[0]
        idx = int(victim.rsplit(".r", 1)[1])
        with chaos.active("seed=1,site=serving.fleet.replica.%d,at=1" % idx):
            # the route hits the fault: the router contains the death
            # and re-routes before the caller ever sees it
            np.testing.assert_array_equal(
                fl.generate(p, 3, timeout=120),
                model.reference_generate(params, p, 3))
        assert chaos.injected_counts() == {}  # disabled again outside
        assert fl.debug_state()["replicas"][victim]["deaths"] == 1
        assert fl.stats()["router"]["completed"] == 2


# ---------------------------------------------------------------------------
# autoscaling
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_autoscaler_scales_up_on_queue_depth_burn(tiny):
    _slo.reset()
    with FleetRouter(_factory(tiny), replicas=1, name=_fname(),
                     max_replicas=2) as fl:
        fl.warmup()
        rep = next(iter(fl.debug_state()["replicas"]))
        # synthetic QueueDepthBurn on the replica: mean depth/bound > 0.9
        _slo.note_bound("queue_depth", rep, 10)
        g = telemetry.gauge("mxnet_serving_queue_depth", labels=("server",))
        g.set(9.5, server=rep)
        event = fl.autoscale_tick()
        assert event is not None and event["action"] == "up"
        assert event["reason"] == "QueueDepthBurn"
        assert fl.stats()["replicas_live"] == 2
        assert fl.stats()["router"]["last_scale"]["action"] == "up"
        fam = telemetry.REGISTRY.get("mxnet_fleet_scale_events_total")
        assert fam.value(fleet=fl.name, action="up") == 1
        # cooldown gates the next decision
        assert fl.autoscale_tick() is None
        # the cap holds: even under burn, never past max_replicas
        g.set(9.5, server=rep)
        assert fl.autoscale_tick(now=time.monotonic() + 3600) is None \
            or fl.stats()["replicas_live"] <= 2
        g.set(0.0, server=rep)
    _slo.reset()


@pytest.mark.slow
def test_autoscaler_drains_coldest_on_occupancy_collapse(tiny):
    _slo.reset()
    with FleetRouter(_factory(tiny), replicas=2, name=_fname(),
                     min_replicas=1) as fl:
        fl.warmup()
        g = telemetry.gauge("mxnet_decode_slot_occupancy",
                            labels=("server",))
        for rep in fl.debug_state()["replicas"]:
            g.set(0.0, server=rep)
        event = fl.autoscale_tick()
        assert event is not None and event["action"] == "down"
        assert event["reason"] == "occupancy_collapse"
        assert fl.stats()["replicas_live"] == 1
        # never below min_replicas
        assert fl.autoscale_tick(now=time.monotonic() + 3600) is None
        assert fl.stats()["replicas_live"] == 1
    _slo.reset()


# ---------------------------------------------------------------------------
# observation: /debug/state fleet view, snapshot merge
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_debug_state_view_over_httpd(tiny):
    import json
    from urllib.request import urlopen

    with FleetRouter(_factory(tiny), replicas=2, name=_fname()) as fl:
        fl.warmup()
        fl.generate([1, 2, 3, 4], 2, timeout=120)
        view = fleet_debug_state()
        assert fl.name in view
        row = view[fl.name]
        assert set(row["replicas"]) == set(fl.debug_state()["replicas"])
        for rep in row["replicas"].values():
            assert {"state", "breaker", "inflight", "routed",
                    "deaths"} <= set(rep)
        srv = _httpd.start_httpd(port=0)
        try:
            host, port = srv.server_address[:2]
            with urlopen("http://%s:%d/debug/state" % (host, port),
                         timeout=10) as resp:
                doc = json.loads(resp.read())
            assert fl.name in doc["fleet"]
            rep0 = next(iter(doc["fleet"][fl.name]["replicas"].values()))
            assert rep0["state"] == "live"
            assert "queue_depth" in rep0 and "pages_in_use" in rep0
        finally:
            _httpd.stop_httpd()


def test_aggregate_snapshots_merges_per_tenant():
    a = {"gold": {"submitted": 3, "completed": 2, "queue_ms_p99_ms": 5.0,
                  "queue_ms_count": 2, "breaker": "closed",
                  "weight": 3.0},
         "bronze": {"submitted": 1, "completed": 1, "breaker": "open"}}
    b = {"gold": {"submitted": 4, "completed": 4, "queue_ms_p99_ms": 9.0,
                  "queue_ms_count": 4, "breaker": "half_open",
                  "weight": 3.0}}
    out = aggregate_snapshots([a, b])
    assert out["gold"]["submitted"] == 7
    assert out["gold"]["completed"] == 6
    assert out["gold"]["queue_ms_count"] == 6
    assert out["gold"]["queue_ms_p99_ms"] == 9.0  # worst replica wins
    assert out["gold"]["breaker"] == "half_open"  # severity order
    assert out["gold"]["weight"] == 3.0
    assert out["bronze"]["breaker"] == "open"
    assert aggregate_snapshots([]) == {}
