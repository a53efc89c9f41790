"""mxnet_tpu.serving.tenancy — the multi-tenant serving control plane
(tier-1, CPU).

Covers the ISSUE-13 acceptance surface: weighted-fair admission (DRR
ratios, priority classes, guard deferral without head-of-line blocking),
per-tenant bounded sub-queues shedding before the global queue, KV page
quotas (budget never exceeded at any tick) and token-rate budgets,
sliding-window tenant breakers, the chaos tenant-isolation proof (faults
scheduled against tenant A open only A's breaker; B/C answered
oracle-exact with p99 within tolerance of the fault-free run), deadline
eviction at tick boundaries, and the live weight swap (zero dropped
requests, zero steady-state recompiles) on both serving planes."""
import time

import numpy as np
import pytest

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving import tenancy
from mxnet_tpu.serving.tenancy import (TenantBreaker, TenantRegistry,
                                       TenantUnavailableError,
                                       WeightedFairQueue, parse_tenants)


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.disable()
    yield
    chaos.disable()


def _uname(prefix="tn"):
    return "%s%d" % (prefix, np.random.randint(1 << 30))


# ---------------------------------------------------------------------------
# spec DSL + registry
# ---------------------------------------------------------------------------

def test_parse_tenants_spec():
    cfgs = parse_tenants(
        "gold,weight=4,priority=interactive,pages=64,rate=500,burst=900;"
        "id=bronze,weight=1,priority=batch,depth=32")
    assert cfgs[0] == {"tenant_id": "gold", "weight": 4.0, "priority": 0,
                       "page_budget": 64, "rate": 500.0, "burst": 900.0}
    assert cfgs[1] == {"tenant_id": "bronze", "weight": 1.0, "priority": 2,
                       "queue_depth": 32}
    assert parse_tenants("") == []
    with pytest.raises(MXNetError, match="unknown key"):
        parse_tenants("a,wieght=2")
    with pytest.raises(MXNetError, match="bad value"):
        parse_tenants("a,weight=fast")
    with pytest.raises(MXNetError, match="names no tenant id"):
        parse_tenants("weight=2")


def test_registry_defaults_resolve_and_order():
    reg = TenantRegistry(server=_uname("reg"), spec="a,weight=2;b",
                         max_cost=8.0)
    assert [t.tenant_id for t in reg] == ["a", "b"]
    # untagged -> default tenant, unknown ids auto-register
    d = reg.resolve(None)
    assert d.tenant_id == tenancy.DEFAULT_TENANT
    x = reg.resolve("newcomer")
    assert x.weight == 1.0 and x.page_budget is None
    assert [t.tenant_id for t in reg] == ["a", "b", "default", "newcomer"]
    # get-or-create: re-register returns the existing tenant unchanged
    assert reg.register("a", weight=99).weight == 2.0


# ---------------------------------------------------------------------------
# weighted-fair queue (unit, no engine)
# ---------------------------------------------------------------------------

class _FakeReq:
    def __init__(self, cost=1.0, deadline=None):
        self.cost = float(cost)
        self.t_submit = time.perf_counter()
        self.deadline = deadline


def _wfq(spec, max_cost=1.0):
    reg = TenantRegistry(server=_uname("wfq"), spec=spec, max_cost=max_cost)
    return reg, WeightedFairQueue(reg, cost_fn=lambda r: r.cost)


def test_wfq_drr_ratio_follows_weights():
    reg, q = _wfq("a,weight=3;b,weight=1")
    a, b = reg.get("a"), reg.get("b")
    for _ in range(40):
        q.push(a, _FakeReq())
        q.push(b, _FakeReq())
    picks = [q.pop()[0].tenant_id for _ in range(32)]
    assert picks.count("a") == 24 and picks.count("b") == 8
    # a's service comes in weight-sized runs, not one giant burst
    assert max(len(run) for run in "".join(picks).split("b") if run) <= 3


def test_wfq_priority_classes_are_strict():
    reg, q = _wfq("fg,priority=interactive;bg,priority=batch,weight=100")
    fg, bg = reg.get("fg"), reg.get("bg")
    for _ in range(3):
        q.push(bg, _FakeReq())
        q.push(fg, _FakeReq())
    picks = [q.pop()[0].tenant_id for _ in range(6)]
    # weight 100 does not matter across classes: interactive first, always
    assert picks == ["fg", "fg", "fg", "bg", "bg", "bg"]


def test_wfq_guard_defers_one_tenant_without_blocking():
    reg, q = _wfq("a;b")
    a, b = reg.get("a"), reg.get("b")
    for _ in range(2):
        q.push(a, _FakeReq())
        q.push(b, _FakeReq())
    vetoed = {"a"}
    guard = lambda t, r: t.tenant_id not in vetoed  # noqa: E731
    assert [q.pop(guard)[0].tenant_id for _ in range(2)] == ["b", "b"]
    # a was deferred, not dropped: un-vetoing serves its queued work
    assert q.pop(guard) is None and q.total_queued() == 2
    vetoed.clear()
    assert [q.pop(guard)[0].tenant_id for _ in range(2)] == ["a", "a"]
    assert q.total_queued() == 0


def test_wfq_expire_and_drain():
    reg, q = _wfq("a;b")
    a, b = reg.get("a"), reg.get("b")
    q.push(a, _FakeReq(deadline=time.perf_counter() - 1.0))
    q.push(a, _FakeReq())
    q.push(b, _FakeReq())
    expired = q.expire(time.perf_counter())
    assert len(expired) == 1 and expired[0][0].tenant_id == "a"
    assert q.total_queued() == 2
    assert len(q.drain(a)) == 1 and q.total_queued() == 1
    assert len(q.drain()) == 1 and q.total_queued() == 0


# ---------------------------------------------------------------------------
# tenant breaker (unit)
# ---------------------------------------------------------------------------

def test_tenant_breaker_windowed_trip_and_recovery():
    br = TenantBreaker(_uname("srv"), "t", failure_threshold=2,
                       window_s=10.0, reset_timeout_s=0.05)
    assert br.state == "closed" and br.allow()
    br.on_failure()
    # interleaved successes do NOT reset the window count — the whole
    # point: a bad tenant's failures hide between other traffic
    br.on_success()
    assert br.state == "closed"
    br.on_failure()
    assert br.state == "open" and not br.allow()
    time.sleep(0.06)
    assert br.state == "half_open"
    assert br.allow()       # the probe
    assert not br.allow()   # only one probe
    br.on_success()
    assert br.state == "closed" and br.allow()


def test_token_refund_restores_budget():
    # an admission vetoed AFTER the bucket was debited (breaker veto in
    # the guard) refunds: the tenant is not charged for work never run
    reg = TenantRegistry(server=_uname("reg"), spec="r,rate=10,burst=10",
                         max_cost=10.0)
    t = reg.get("r")
    assert t.take_tokens(8)
    assert not t.take_tokens(8)  # drained
    t.refund_tokens(8)
    assert t.take_tokens(8)      # restored
    t.refund_tokens(1000)        # capped at burst, never overflows
    assert t.take_tokens(10) and not t.take_tokens(10)


def test_tenant_breaker_probe_lease_expires():
    # a consumed half-open probe whose request never reports an outcome
    # (deferred after allow(), expired at batch assembly) must not wedge
    # the breaker: the lease times out and a fresh probe is admitted
    br = TenantBreaker(_uname("srv"), "t", failure_threshold=1,
                       window_s=10.0, reset_timeout_s=0.05)
    br.on_failure()
    time.sleep(0.06)
    assert br.allow()        # the probe
    assert not br.allow()    # exhausted while the probe is in flight
    time.sleep(0.06)         # ...which never reported
    assert br.allow()        # lease expired: probe re-issued, no wedge


def test_tenant_breaker_window_forgets_old_failures():
    br = TenantBreaker(_uname("srv"), "t", failure_threshold=2,
                       window_s=0.05, reset_timeout_s=10.0)
    br.on_failure()
    time.sleep(0.08)  # first failure ages out of the window
    br.on_failure()
    assert br.state == "closed"


# ---------------------------------------------------------------------------
# decode engine: fairness, quotas, sheds
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model = serving.TinyDecoder(vocab_size=32, num_layers=1, num_heads=2,
                                head_dim=8)
    return model, model.init_params(0)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("name", _uname())
    return serving.DecodeEngine(model, params, **kw)


def test_hot_tenant_cannot_starve_background(tiny):
    # the fairness proof: one slot, a hot tenant floods 12 requests in
    # before a background tenant's 3 arrive — DRR interleaves admission,
    # so bg completes long before the hot backlog drains (pure FIFO
    # would finish bg dead last)
    with _engine(tiny, num_slots=1, max_seq_len=32,
                 tenants="hot,weight=1;bg,weight=1") as eng:
        eng.warmup()
        order = []
        futs = []
        for i in range(12):
            f = eng.submit([1 + i % 8], 3, tenant="hot")
            f.add_done_callback(lambda _f: order.append("hot"))
            futs.append(f)
        for i in range(3):
            f = eng.submit([20 + i], 3, tenant="bg")
            f.add_done_callback(lambda _f: order.append("bg"))
            futs.append(f)
        for f in futs:
            f.result(timeout=120)
        stats = eng.stats()
    assert stats["tenants"]["bg"]["completed"] == 3
    last_bg = max(i for i, t in enumerate(order) if t == "bg")
    # all bg done before the last ~3 hot requests even start finishing
    assert last_bg < len(order) - 1
    assert stats["steady_state_recompiles"] == 0


def test_page_quota_defers_without_exceeding_budget(tiny):
    # A's budget covers ONE worst-case sequence; its second request
    # defers until the first completes, while B is admitted meanwhile —
    # and A's pages-in-use high-water mark never tops its budget
    with _engine(tiny, num_slots=2, max_seq_len=32, page_size=8,
                 tenants="A,pages=2;B") as eng:
        eng.warmup()
        futs = [eng.submit([1], 10, tenant="A"),
                eng.submit([2], 10, tenant="A"),
                eng.submit([3], 10, tenant="B")]
        for f in futs:
            f.result(timeout=120)
        stats = eng.stats()
    a = stats["tenants"]["A"]
    assert a["completed"] == 2
    assert a["deferred_pages"] >= 1          # the second request waited
    assert a["pages_in_use_max"] <= 2        # budget held at EVERY tick
    assert stats["tenants"]["B"]["completed"] == 1
    assert stats["kvcache"]["pages_in_use"] == 0


def test_rate_limit_defers_only_that_tenant(tiny):
    # A has a tiny token budget (fits one request, then must refill at
    # 1 token/s); B is unlimited and keeps flowing while A waits
    with _engine(tiny, num_slots=2, max_seq_len=32,
                 tenants="A,rate=1,burst=6;B") as eng:
        eng.warmup()
        fa = eng.submit([1, 2], 4, tenant="A")  # cost 6 = the whole burst
        t0 = time.perf_counter()
        fb = [eng.submit([3 + i], 4, tenant="B") for i in range(4)]
        fa.result(timeout=120)
        for f in fb:
            f.result(timeout=120)
        b_done = time.perf_counter() - t0
        # cost 5 against a drained bucket refilling at 1 token/s: don't
        # wait the ~5s out — just assert it DEFERS while B still flows
        fa2 = eng.submit([9], 4, tenant="A")
        time.sleep(0.1)
        fb2 = eng.submit([10], 4, tenant="B")
        fb2.result(timeout=120)
        stats = eng.stats()
        assert not fa2.done() or not isinstance(fa2.exception(), Exception)
        eng.close(drain=False)
    assert stats["tenants"]["A"]["deferred_rate"] >= 1
    assert stats["tenants"]["B"]["completed"] == 5
    assert b_done < 60  # B was never blocked behind A's rate wait


def test_submit_rejects_unadmittable_tenant_requests(tiny):
    with _engine(tiny, max_seq_len=64, page_size=8,
                 tenants="A,pages=2;R,rate=10,burst=16") as eng:
        with pytest.raises(MXNetError, match="page budget"):
            eng.submit([1] * 10, 20, tenant="A")  # 30 tokens = 4 pages > 2
        with pytest.raises(MXNetError, match="burst"):
            eng.submit([1] * 10, 20, tenant="R")  # 30 tokens > burst 16
        # within budget still serves
        assert len(eng.generate([1], 4, tenant="A")) == 4


def test_per_tenant_queue_sheds_before_global(tiny):
    # tenant A's sub-queue bound (2) trips while the global queue (256)
    # is nowhere near full — and B can still submit
    with _engine(tiny, num_slots=1, max_seq_len=64,
                 tenants="A,depth=2;B") as eng:
        eng.warmup()
        blocker = eng.submit([1, 2], 40, tenant="B")  # occupies the slot
        futs = [eng.submit([3 + i], 30, tenant="A") for i in range(2)]
        with pytest.raises(serving.QueueFullError, match="tenant 'A'"):
            for _ in range(3):  # the worker may admit one meanwhile
                futs.append(eng.submit([9], 30, tenant="A"))
        assert eng.submit([7], 4, tenant="B") is not None
        stats = eng.stats()
        assert stats["tenants"]["A"]["shed"] >= 1
        eng.close(drain=False)


# ---------------------------------------------------------------------------
# chaos tenant isolation: the acceptance proof
# ---------------------------------------------------------------------------

def _isolation_traffic(eng, model, params, n_waves=8):
    """Interleaved A/B/C waves; returns per-tenant outcome lists."""
    out = {"A": [], "B": [], "C": []}
    for w in range(n_waves):
        futs = []
        for tid, k in (("A", 2), ("B", 1), ("C", 1)):
            for j in range(k):
                prompt = [1 + (w + j) % 8, 2 + w % 5]
                try:
                    futs.append((tid, prompt,
                                 eng.submit(prompt, 3, tenant=tid)))
                except TenantUnavailableError as e:
                    out[tid].append(("shed", e))
        for tid, prompt, f in futs:
            try:
                out[tid].append(("ok", prompt, f.result(timeout=120)))
            except chaos.FaultInjected as e:
                out[tid].append(("fault", e))
            except TenantUnavailableError as e:
                out[tid].append(("shed", e))
    return out


def test_chaos_tenant_isolation_suite(tiny):
    """Faults scheduled against tenant A's requests (p=0.3, seeded) stay
    inside A's breaker: A opens and is shed, the ENGINE breaker never
    trips, B/C get every request answered oracle-exact, and B/C p99 stays
    within tolerance of the fault-free run."""
    model, params = tiny

    def run(spec):
        eng = _engine(tiny, num_slots=2, max_seq_len=32)
        eng.tenants.register("A", breaker_threshold=3,
                             breaker_window_s=60.0, breaker_reset_s=60.0)
        eng.tenants.register("B")
        eng.tenants.register("C")
        eng.warmup()
        try:
            if spec:
                with chaos.active(spec):
                    out = _isolation_traffic(eng, model, params)
            else:
                out = _isolation_traffic(eng, model, params)
            return out, eng.stats(), eng._breaker.state
        finally:
            eng.close(drain=False)

    base_out, base_stats, _ = run(None)
    assert all(k[0] == "ok" for v in base_out.values() for k in v)

    spec = "seed=11,site=serving.decode.tenant.A,p=0.3"
    out, stats, engine_breaker = run(spec)

    # A: faulted, its breaker opened, and later traffic was shed — alone
    a_kinds = [o[0] for o in out["A"]]
    assert a_kinds.count("fault") >= 3
    assert a_kinds.count("shed") >= 1
    assert stats["tenants"]["A"]["breaker"] in ("open", "half_open")
    assert stats["tenants"]["A"]["shed_breaker"] >= 1

    # the engine-level breaker never saw any of it
    assert engine_breaker == "closed"
    assert stats["breaker"] == "closed"
    assert stats["evictions"] == 0
    assert stats["steady_state_recompiles"] == 0

    # B and C: every request answered, oracle-exact
    for tid in ("B", "C"):
        assert all(o[0] == "ok" for o in out[tid]), out[tid]
        for _kind, prompt, got in out[tid]:
            np.testing.assert_array_equal(
                got, model.reference_generate(params, prompt, 3))
        assert stats["tenants"][tid]["breaker"] == "closed"
        # p99 within tolerance of the fault-free run (generous bound:
        # CI timing noise dwarfs any real coupling)
        base_p99 = base_stats["tenants"][tid]["latency_p99_ms"]
        assert stats["tenants"][tid]["latency_p99_ms"] <= \
            max(10.0 * base_p99, base_p99 + 250.0)

    # and the per-tenant breaker gauge is scrape-visible
    text = telemetry.render_prometheus()
    assert 'mxnet_tenant_breaker_state{' in text


# ---------------------------------------------------------------------------
# deadline propagation into decode ticks
# ---------------------------------------------------------------------------

def test_deadline_expiring_mid_decode_evicts_at_tick_boundary(tiny):
    model, params = tiny
    with _engine(tiny, num_slots=1, max_seq_len=128) as eng:
        eng.warmup()
        # pin the race: 5ms/tick makes a 100-token generation take
        # >= 500ms, so a 100ms deadline MUST expire mid-decode (warm
        # prefill admits in a few ms — far inside the deadline)
        orig_step = eng._dispatch_step

        def slow_step(active):
            time.sleep(0.005)
            return orig_step(active)

        eng._dispatch_step = slow_step
        fut = eng.submit([1, 2], 100, timeout_ms=100)
        with pytest.raises(serving.RequestTimeoutError, match="mid-decode"):
            fut.result(timeout=120)
        eng._dispatch_step = orig_step
        stats = eng.stats()
        assert stats["deadline_evictions"] == 1  # evicted, not queue-aged
        assert stats["kvcache"]["pages_in_use"] == 0  # pages freed
        # the engine keeps serving, oracle-exact, without recompiling
        np.testing.assert_array_equal(
            eng.generate([5], 4),
            model.reference_generate(params, [5], 4))
        assert eng.stats()["steady_state_recompiles"] == 0


# ---------------------------------------------------------------------------
# live weight swap
# ---------------------------------------------------------------------------

def test_live_swap_zero_drop_zero_recompile(tiny):
    model, params = tiny
    params_b = model.init_params(1)
    with _engine(tiny, num_slots=2, max_seq_len=64) as eng:
        eng.warmup()
        # in-flight load across the swap: nothing may drop
        futs = [eng.submit([1 + i], 12) for i in range(6)]
        eng.register_variant("B", params_b)
        eng.use_variant("B", timeout=60)   # applied at a tick boundary
        assert eng.active_variant == "B"
        for f in futs:
            assert len(f.result(timeout=120)) == 12  # zero dropped
        # requests submitted after the swap serve the NEW weights
        np.testing.assert_array_equal(
            eng.generate([3, 1, 4], 5),
            model.reference_generate(params_b, [3, 1, 4], 5))
        stats = eng.stats()
    assert stats["weight_swaps"] == 1
    assert stats["completed"] == 7 and stats["errors"] == 0
    # the PR-3 gauge: a swap is data movement, never a retrace
    assert stats["steady_state_recompiles"] == 0


def test_swap_applies_while_idle_and_ab_flips_back(tiny):
    model, params = tiny
    params_b = model.init_params(2)
    with _engine(tiny, num_slots=1, max_seq_len=64) as eng:
        eng.warmup()
        eng.swap_params(params_b, timeout=60)  # idle engine: still applies
        np.testing.assert_array_equal(
            eng.generate([7], 4),
            model.reference_generate(params_b, [7], 4))
        eng.swap_params(params, timeout=60)    # A/B flip back
        np.testing.assert_array_equal(
            eng.generate([7], 4),
            model.reference_generate(params, [7], 4))
        assert eng.stats()["weight_swaps"] == 2


def test_swap_rejects_mismatched_signature(tiny):
    _model, _params = tiny
    other = serving.TinyDecoder(vocab_size=32, num_layers=1, num_heads=2,
                                head_dim=16)  # different head_dim
    with _engine(tiny) as eng:
        with pytest.raises(MXNetError, match="signature differs"):
            eng.swap_params(other.init_params(0))
        with pytest.raises(MXNetError, match="signature differs"):
            eng.register_variant("bad", other.init_params(0))
        with pytest.raises(MXNetError, match="unknown variant"):
            eng.use_variant("never-registered")


# ---------------------------------------------------------------------------
# batch server plane
# ---------------------------------------------------------------------------

class _PoisonEngine(serving.Engine):
    """Doubles rows; raises on any 'poisoned' row (value > 100)."""

    kind = "poison"

    def run(self, batch):
        if (batch > 100.0).any():
            raise RuntimeError("poisoned row in batch")
        return batch * 2.0

    @property
    def compile_count(self):
        return 0


def test_server_tenant_breaker_sheds_poison_tenant_alone():
    srv = serving.Server(_PoisonEngine(), (4,), buckets=[1, 4],
                         max_delay_ms=1.0, timeout_ms=0,
                         name=_uname("srv"), breaker_threshold=100)
    srv.tenants.register("evil", breaker_threshold=3,
                         breaker_window_s=60.0, breaker_reset_s=60.0)
    srv.tenants.register("good")
    try:
        poison = np.full((4,), 200.0, np.float32)
        ok = np.ones((4,), np.float32)
        failures = 0
        shed = 0
        for i in range(8):
            try:
                f = srv.submit(poison, tenant="evil")
                with pytest.raises(RuntimeError):
                    f.result(timeout=30)
                failures += 1
            except TenantUnavailableError:
                shed += 1
            out = srv.submit(ok, tenant="good").result(timeout=30)
            np.testing.assert_allclose(out, ok * 2.0)
        stats = srv.stats()
        assert failures >= 3 and shed >= 1  # opened after 3, then shed
        assert stats["tenants"]["evil"]["breaker"] in ("open", "half_open")
        assert stats["tenants"]["good"]["completed"] == 8
        assert stats["tenants"]["good"]["breaker"] == "closed"
        # the ENGINE breaker survived: good traffic kept resetting it
        assert stats["breakers"]["primary"] == "closed"
    finally:
        srv.close(timeout=10)


class _SwappableEngine(serving.Engine):
    kind = "swappable"

    def __init__(self):
        self.source = {"scale": 2.0}
        self._scale = 2.0

    def refresh_params(self):
        self._scale = self.source["scale"]

    def run(self, batch):
        return batch * self._scale

    @property
    def compile_count(self):
        return 0


def test_server_refresh_params_is_a_live_swap():
    srv = serving.Server(_SwappableEngine(), (2,), buckets=[1, 4],
                         max_delay_ms=1.0, timeout_ms=0,
                         name=_uname("srv"))
    try:
        x = np.asarray([1.0, 2.0], np.float32)
        np.testing.assert_allclose(srv.submit(x).result(timeout=30), x * 2)
        srv._engine.source["scale"] = 3.0
        assert srv.refresh_params() == 1  # one engine in the chain swapped
        np.testing.assert_allclose(srv.submit(x).result(timeout=30), x * 3)
        assert srv.stats()["errors"] == 0
    finally:
        srv.close(timeout=10)


def test_server_weighted_fair_batch_fill():
    # hot floods 12, bg queues 3 — WFQ batch assembly interleaves, so bg
    # completes inside the first couple of batches, not dead last
    class _Slow(serving.Engine):
        kind = "slow"

        def run(self, batch):
            time.sleep(0.01)
            return batch * 2.0

        @property
        def compile_count(self):
            return 0

    srv = serving.Server(_Slow(), (2,), buckets=[2], max_delay_ms=1.0,
                         timeout_ms=0, name=_uname("srv"),
                         tenants="hot;bg")
    try:
        order = []
        futs = []
        x = np.ones((2,), np.float32)
        for i in range(12):
            f = srv.submit(x * i, tenant="hot")
            f.add_done_callback(lambda _f: order.append("hot"))
            futs.append(f)
        for i in range(3):
            f = srv.submit(x, tenant="bg")
            f.add_done_callback(lambda _f: order.append("bg"))
            futs.append(f)
        for f in futs:
            f.result(timeout=60)
        stats = srv.stats()
        assert stats["tenants"]["bg"]["completed"] == 3
        assert max(i for i, t in enumerate(order) if t == "bg") < 14
    finally:
        srv.close(timeout=10)


# ---------------------------------------------------------------------------
# telemetry rows
# ---------------------------------------------------------------------------

def test_tenant_metric_families_render(tiny):
    name = "tel-tenant-test"
    with _engine(tiny, name=name, tenants="alpha,weight=2") as eng:
        eng.warmup()
        eng.generate([1, 2], 4, tenant="alpha")
        stats = eng.stats()
    snap = stats["tenants"]["alpha"]
    assert snap["completed"] == 1 and snap["ttft_count"] == 1
    assert snap["tpot_count"] == 3
    text = telemetry.render_prometheus()
    for fam in ("mxnet_tenant_requests_total", "mxnet_tenant_queue_depth",
                "mxnet_tenant_pages_in_use", "mxnet_tenant_ttft_ms",
                "mxnet_tenant_tpot_ms", "mxnet_tenant_breaker_state"):
        assert '%s{server="%s",tenant="alpha"' % (fam, name) in text \
            or '%s_count{server="%s",tenant="alpha"' % (fam, name) in text \
            or fam in text


# ---------------------------------------------------------------------------
# prefix caching x tenancy: shared pages charge no tenant twice (ISSUE 14)
# ---------------------------------------------------------------------------

def test_shared_tenant_id_is_reserved():
    reg = TenantRegistry(server=_uname("shr"))
    with pytest.raises(MXNetError, match="reserved"):
        reg.register("shared")
    with pytest.raises(MXNetError, match="reserved"):
        parse_tenants("shared,weight=2") and reg.register(
            **parse_tenants("shared,weight=2")[0])


def test_shared_pages_not_double_charged_two_tenant_soak(tiny):
    # the budget-invariant soak: A and B share one 16-token system
    # prompt; each budget (3 pages of 8) covers exactly ONE cold
    # worst-case request (2 prompt pages + 1 generation page). Only
    # tail-only charging lets BOTH run concurrently: a sharer pays 1
    # page, not 3 — double-charging would defer every concurrent pair.
    model, params = tiny
    sysp = list(np.random.RandomState(11).randint(1, 30, 16))
    with _engine(tiny, num_slots=2, max_seq_len=32, page_size=8,
                 prefix_cache=True, tenants="A,pages=3;B,pages=3") as eng:
        eng.warmup()
        # cold lap: A prefills the shared prompt once (charged 3)
        p0 = np.asarray(sysp, np.int32)
        np.testing.assert_array_equal(
            eng.generate(p0, 8, tenant="A"),
            model.reference_generate(params, p0, 8))
        # warm soak: both tenants ride the shared prefix concurrently
        futs = []
        for i in range(6):
            futs.append((p0, eng.submit(p0, 8, tenant="A" if i % 2 else "B")))
        for p, f in futs:
            np.testing.assert_array_equal(
                f.result(timeout=120),
                model.reference_generate(params, p, 8))
        # poll a moment where both sharers were live at once
        stats = eng.stats()
    a, b = stats["tenants"]["A"], stats["tenants"]["B"]
    assert a["completed"] + b["completed"] == 7
    # the invariant: per-tenant high-water marks under tail-only charge
    assert a["pages_in_use_max"] <= 3
    assert b["pages_in_use_max"] <= 3
    # both warm sequences fit at once ONLY because shared pages charge
    # the pseudo-tenant: no deferral needed in the warm soak
    assert a["deferred_pages"] == 0 and b["deferred_pages"] == 0
    assert stats["tenants"]["shared"]["pseudo"] is True
    assert stats["kvcache"]["prefix_hits"] >= 6
    assert stats["kvcache"]["pages_in_use"] == 0
    assert stats["steady_state_recompiles"] == 0


def test_shared_pseudo_tenant_counts_refcounted_pages(tiny):
    # while two sequences share prefix pages, the `shared` pseudo row
    # reports refcount>1 pages; once everyone frees, it reads 0
    model, params = tiny
    sysp = np.asarray(list(range(1, 17)), np.int32)
    with _engine(tiny, num_slots=2, max_seq_len=64, page_size=8,
                 prefix_cache=True) as eng:
        eng.warmup()
        eng.generate(sysp, 2, tenant="A")  # seed the index
        fa = eng.submit(sysp, 30, tenant="A")
        fb = eng.submit(sysp, 30, tenant="B")
        seen_shared = 0
        deadline = time.time() + 60
        while time.time() < deadline:
            snap = eng.stats()
            seen_shared = max(
                seen_shared,
                snap["tenants"]["shared"]["pages_in_use_now"])
            if fa.done() and fb.done():
                break
            time.sleep(0.005)
        fa.result(timeout=120)
        fb.result(timeout=120)
        stats = eng.stats()
    assert seen_shared >= 2  # both mapped the 2 full prompt pages
    assert stats["tenants"]["shared"]["pages_in_use_now"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
