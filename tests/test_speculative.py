"""Speculative decoding: prompt-lookup/model drafts, the widened
K+1-row verify tick, greedy rejection, and the rollback/budget
invariants.

The correctness bar is the same one every other decode test holds: the
engine's output token ids are BITWISE equal to the no-cache dense
oracle (`TinyDecoder.reference_generate`), whatever the draft proposed
— accept-all, reject-all and mixed schedules all reduce to the model's
own argmax chain. The accepted-per-tick > 1.0 bar of an accept-all
draft is held by tests/test_soak_gates.py; here we assert the accounting
that proves it.
"""
import contextlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import serving, telemetry  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402
from mxnet_tpu.resilience import RetryPolicy, chaos  # noqa: E402
from mxnet_tpu.serving import speculative  # noqa: E402


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.disable()
    yield
    chaos.disable()


@pytest.fixture(scope="module")
def tiny():
    # 1 layer keeps every per-test engine compile cheap; GQA (4 q heads
    # over 2 kv heads) still exercises the grouped kernel path
    model = serving.TinyDecoder(vocab_size=32, num_layers=1, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    return model, model.init_params(0)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("name", "spec%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


class _RejectAllDraft(speculative.DraftProposer):
    """Proposes (true_next + 1) % vocab: the first draft row is always
    wrong, so greedy verification accepts ZERO drafts every tick — the
    worst case the rollback path must survive bit-exactly."""

    name = "reject_all"

    def __init__(self, model, params):
        self._model = model
        self._params = params

    def propose(self, history, k):
        nxt = self._model.reference_generate(self._params, history, int(k))
        return (np.asarray(nxt, np.int64) + 1) % self._model.vocab_size


# Engine compiles dominate this file's wall-clock, so the engine-level
# tests share three module-scoped engines and assert stats DELTAS
# instead of absolute counters. eng4 keeps its native accept-all model
# draft for life; eng2 is the draft-swap rig (reject-all / prompt-lookup
# batches replace its draft while the worker is parked between batches);
# engt carries the tenant registry with the kvcache audit armed.

@pytest.fixture(scope="module")
def eng4(tiny):
    with _engine(tiny, spec_k=4, spec_draft="model") as eng:
        eng.warmup()
        yield eng


@pytest.fixture(scope="module")
def eng2(tiny):
    with _engine(tiny, spec_k=2, spec_draft="model") as eng:
        eng.warmup()
        yield eng


@pytest.fixture(scope="module")
def engt(tiny):
    # audit armed at CONSTRUCTION (the cache latches the env var), so
    # every test on this engine runs under the per-tick no-alloc /
    # no-overdraft invariants of the bugfix satellite
    old = os.environ.get("MXNET_KVCACHE_AUDIT")
    os.environ["MXNET_KVCACHE_AUDIT"] = "1"
    try:
        eng = _engine(tiny, spec_k=3, spec_draft="model",
                      tenants="slow,spec_k=0;fast,pages=12;beta,pages=12")
    finally:
        if old is None:
            os.environ.pop("MXNET_KVCACHE_AUDIT", None)
        else:
            os.environ["MXNET_KVCACHE_AUDIT"] = old
    with eng:
        eng.warmup()
        yield eng


@contextlib.contextmanager
def _swapped_draft(eng, draft):
    # safe between batches: with every future resolved no slot is
    # active, so the worker is parked and never mid-propose
    if draft is None:
        yield
        return
    prev = eng._draft
    eng._draft = draft
    try:
        yield
    finally:
        eng._draft = prev


# ---------------------------------------------------------------------------
# the multi-query kernel: interpret-mode parity vs the dense oracle
# ---------------------------------------------------------------------------

def test_spec_kernel_parity_interpret():
    rng = np.random.RandomState(0)
    s, w, h, kh, d = 3, 3, 4, 2, 8
    pages, page_size, max_pages = 16, 8, 4
    q = jnp.asarray(rng.randn(s, w, h, d).astype(np.float32))
    kp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(1, pages, (s, max_pages)).astype(np.int32))
    # ragged per-ROW lens: slot 0 mid-speculation, slot 1 inactive,
    # slot 2 speculating with its last row padded out
    sl = jnp.asarray(np.array([5, 6, 7, 0, 0, 0, 12, 13, 0], np.int32))
    got = pk.ragged_spec_attention(q, kp, vp, pt, sl, interpret=True)
    ref = pk.paged_spec_attention_reference(
        q.reshape(s * w, h, d), kp, vp, pt, sl).reshape(s, w, h, d)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    # inactive slot rows emit exact zeros (the seen-gate), and so does
    # slot 2's padded third row
    assert np.abs(np.asarray(got[1])).sum() == 0
    assert np.abs(np.asarray(got[2, 2])).sum() == 0


def test_spec_kernel_width1_matches_single_query_kernel():
    # W=1 is the degenerate case: the spec kernel must agree with the
    # classic kernel bit-for-bit in math (same dtype, same masks)
    rng = np.random.RandomState(1)
    s, h, kh, d = 4, 4, 2, 8
    pages, page_size, max_pages = 8, 8, 3
    q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
    kp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(1, pages, (s, max_pages)).astype(np.int32))
    sl = jnp.asarray(np.array([3, 0, 17, 24], np.int32))
    spec = pk.ragged_spec_attention(q[:, None], kp, vp, pt, sl,
                                    interpret=True)[:, 0]
    classic = pk.ragged_paged_attention(q, kp, vp, pt, sl, interpret=True)
    np.testing.assert_allclose(spec, classic, atol=2e-5, rtol=2e-5)


def test_spec_dispatcher_derives_width_from_shapes():
    rng = np.random.RandomState(2)
    s, w, h, d = 2, 3, 2, 8
    pages, page_size, max_pages = 8, 8, 2
    q = jnp.asarray(rng.randn(s * w, h, d).astype(np.float32))
    kp = jnp.asarray(rng.randn(pages, page_size, h, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, page_size, h, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(1, pages, (s, max_pages)).astype(np.int32))
    sl = jnp.asarray(np.array([4, 5, 6, 9, 10, 0], np.int32))
    out = pk.paged_spec_attention(q, kp, vp, pt, sl)
    assert out.shape == (s * w, h, d)
    ref = pk.paged_spec_attention_reference(q, kp, vp, pt, sl)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the drafts
# ---------------------------------------------------------------------------

def test_prompt_lookup_finds_most_recent_ngram_continuation():
    d = speculative.PromptLookupDraft(ngram_max=3)
    #          0  1  2  3  4  5  6  7  8
    hist = [7, 1, 2, 3, 9, 1, 2, 3, 4, 1, 2, 3]
    out = d.propose(np.asarray(hist, np.int32), 4)
    # suffix (1,2,3) recurs at i=1 and i=5 — the MOST RECENT (i=5) wins,
    # proposing its continuation (4, then 1, 2, 3)
    np.testing.assert_array_equal(out, [4, 1, 2, 3])


def test_prompt_lookup_falls_back_to_shorter_ngrams():
    d = speculative.PromptLookupDraft(ngram_max=3)
    # no 3- or 2-gram recurrence of the tail, but token 5 recurs
    out = d.propose(np.asarray([5, 8, 9, 5], np.int32), 2)
    np.testing.assert_array_equal(out, [8, 9])


def test_prompt_lookup_no_match_proposes_nothing():
    d = speculative.PromptLookupDraft(ngram_max=3)
    assert d.propose(np.asarray([1, 2, 3, 4], np.int32), 4).size == 0
    assert d.propose(np.asarray([1], np.int32), 4).size == 0
    assert d.propose(np.asarray([1, 1, 1], np.int32), 0).size == 0


def test_draft_registry_and_sanitize():
    assert "prompt_lookup" in speculative.available_drafts()
    assert "model" in speculative.available_drafts()
    with pytest.raises(MXNetError):
        speculative.make_draft("no_such_draft")
    # sanitize truncates at the first out-of-vocab id and caps at k
    out = speculative.sanitize([3, 5, 99, 4], k=4, vocab_size=32)
    np.testing.assert_array_equal(out, [3, 5])
    assert speculative.sanitize([1, 2, 3], k=2, vocab_size=32).size == 2
    assert speculative.sanitize([-1], k=4, vocab_size=32).size == 0


# ---------------------------------------------------------------------------
# engine == oracle BITWISE under churn, across schedules and k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["accept_all", "reject_all", "mixed"])
def test_engine_oracle_exact_under_churn(tiny, eng4, eng2, schedule):
    # accept_all rides the k=4 engine's native model draft; reject_all
    # and mixed swap theirs into the k=2 rig; k=0 has its own engine in
    # the test below (and the tenant spec_k=0 cap proves the per-slot
    # k=0 clamp on a speculating engine).
    model, params = tiny
    if schedule == "accept_all":
        eng, draft = eng4, None
    elif schedule == "reject_all":
        eng, draft = eng2, _RejectAllDraft(model, params)
    else:
        eng, draft = eng2, speculative.make_draft("prompt_lookup")
    k = eng.stats()["speculative"]["k"]
    rng = np.random.RandomState(100 + k)
    # more requests than slots: admission churn while speculating
    prompts = [rng.randint(1, 32, rng.randint(2, 10)).astype(np.int32)
               for _ in range(6)]
    maxes = [int(rng.randint(3, 14)) for _ in prompts]
    before = eng.stats()["speculative"]
    ticks0, new0 = eng._spec_slot_ticks, eng._spec_new
    with _swapped_draft(eng, draft):
        futs = [eng.submit(p, m) for p, m in zip(prompts, maxes)]
        outs = [f.result(timeout=180) for f in futs]
    stats = eng.stats()
    for p, m, got in zip(prompts, maxes, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    spec = stats["speculative"]
    proposed = spec["proposed_tokens"] - before["proposed_tokens"]
    accepted = spec["accepted_tokens"] - before["accepted_tokens"]
    ticks = eng._spec_slot_ticks - ticks0
    committed = eng._spec_new - new0
    if schedule == "accept_all":
        assert proposed > 0 and ticks > 0
        assert accepted == proposed
        assert committed / ticks > 1.0
    elif schedule == "reject_all":
        # first draft row always wrong: zero accepted, exactly one
        # committed token per speculating tick — pure rollback traffic
        assert proposed > 0 and ticks > 0
        assert accepted == 0
        assert committed == ticks


def test_spec_k_zero_is_classic_engine(tiny):
    # k=0 through the public knob: the engine runs the classic width-1
    # step, never consults a draft, and stays oracle-exact
    model, params = tiny
    with _engine(tiny, spec_k=0) as eng:
        eng.warmup()
        for p, m in (([5, 6, 7], 6), ([1, 9], 4)):
            np.testing.assert_array_equal(
                eng.submit(p, m).result(timeout=120),
                model.reference_generate(params, p, m))
        stats = eng.stats()
    assert stats["speculative"]["k"] == 0
    assert stats["speculative"]["proposed_tokens"] == 0
    assert stats["steady_state_recompiles"] == 0


def test_eos_respected_mid_acceptance(tiny, eng4):
    # a tick that would commit k+1 tokens stops at EOS exactly where
    # the oracle does — the acceptance loop re-checks finish per token
    model, params = tiny
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 32, 4).astype(np.int32) for _ in range(2)]
    for p in prompts:
        want = model.reference_generate(params, p, 12, eos_id=3)
        got = eng4.submit(p, 12, eos_id=3).result(timeout=120)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# chaos: rejection rollback never leaks pages or evicts bystanders
# ---------------------------------------------------------------------------

def test_chaos_spec_fault_evicts_only_in_flight_no_page_leak(tiny):
    model, params = tiny
    with _engine(tiny, num_slots=2, spec_k=3, spec_draft="prompt_lookup",
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=3"):
            futs = [eng.submit([20 + i, 5, 20 + i, 5], 8)
                    for i in range(2)]
            evicted = 0
            for f in futs:
                try:
                    f.result(timeout=120)
                except chaos.FaultInjected:
                    evicted += 1
        assert evicted == 2  # both in flight on the faulted tick
        mid = eng.stats()
        assert mid["evictions"] == 2
        assert mid["kvcache"]["pages_in_use"] == 0  # rollback leaks nothing
        # the engine keeps speculating — and stays oracle-exact
        after = [eng.submit([30 + i, 7, 30 + i, 7], 6) for i in range(2)]
        for i, f in enumerate(after):
            np.testing.assert_array_equal(
                f.result(timeout=120),
                model.reference_generate(params, [30 + i, 7, 30 + i, 7], 6))
        assert eng.stats()["evictions"] == 2  # no bystanders joined them


def test_chaos_spec_fault_recovers_via_retry(tiny, eng2):
    model, params = tiny
    before = eng2.stats()
    with chaos.active("seed=1,site=serving.decode,at=2"):
        futs = [eng2.submit([40 + i], 5) for i in range(3)]
        outs = [f.result(timeout=120) for f in futs]
    stats = eng2.stats()
    for i, got in enumerate(outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, [40 + i], 5))
    assert stats["evictions"] == before["evictions"]
    assert stats["completed"] == before["completed"] + 3


# ---------------------------------------------------------------------------
# the reservation clamp: speculation can never outgrow admission
# ---------------------------------------------------------------------------

def test_spec_tick_never_allocates_pages_audit_on(tiny, engt):
    # engt's cache was built with MXNET_KVCACHE_AUDIT armed: the
    # per-tick invariants the bugfix satellite demands — pages_in_use
    # may never GROW across a decode tick, and no tenant may stand over
    # its page budget after one. Any violation raises out of the worker
    # and evicts everything, which the oracle-exact completions below
    # prove never happened.
    model, params = tiny
    before_ev = engt.stats()["evictions"]
    ticks0, new0 = engt._spec_slot_ticks, engt._spec_new
    futs = [engt.submit([10 + i, 3], 10,
                        tenant="fast" if i % 2 else "beta")
            for i in range(6)]
    outs = [f.result(timeout=180) for f in futs]
    stats = engt.stats()
    for i, got in enumerate(outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, [10 + i, 3], 10))
    assert stats["evictions"] == before_ev
    assert (engt._spec_new - new0) / (engt._spec_slot_ticks - ticks0) > 1.0
    assert stats["kvcache"]["pages_in_use"] == 0


def test_propose_clamps_to_reservation_and_max_new(tiny):
    # ONE engine whose max_seq_len barely covers prompt+max_new probes
    # both clamps. First, max_new=2: after the first committed token at
    # most ONE more may be committed, so k_eff <= 0 — drafts must be
    # suppressed entirely even though engine k is 4 (a k+1 commit would
    # over-generate). Then max_new=10 against the 16-token reservation:
    # every verify row must stay inside the reserved run (write_slots
    # would hard-fault past it — completion proves no row escaped).
    model, params = tiny
    with _engine(tiny, spec_k=4, spec_draft="model", max_seq_len=16,
                 prefill_buckets=(8,)) as eng:
        eng.warmup()
        got = eng.submit([7, 8, 9], 2).result(timeout=120)
        np.testing.assert_array_equal(
            got, model.reference_generate(params, [7, 8, 9], 2))
        assert eng.stats()["speculative"]["proposed_tokens"] == 0
        got = eng.submit([1, 2, 3, 4, 5, 6], 10).result(timeout=120)
        np.testing.assert_array_equal(
            got, model.reference_generate(params, [1, 2, 3, 4, 5, 6], 10))


def test_kvcache_reserved_tokens():
    cache = serving.PagedKVCache(2, 64, 1, 2, 8, page_size=8,
                                 name="rsv%d" % np.random.randint(1 << 30))
    assert cache.reserved_tokens(0) == 0
    cache.reserve(0, 12)  # 2 pages
    assert cache.reserved_tokens(0) == 16
    cache.free(0)
    assert cache.reserved_tokens(0) == 0


# ---------------------------------------------------------------------------
# per-tenant knobs: registry, DSL, engine clamp, fleet forwarding
# ---------------------------------------------------------------------------

def test_tenant_spec_k_parse_and_snapshot():
    from mxnet_tpu.serving.tenancy import TenantRegistry, parse_tenants

    cfgs = parse_tenants("acme,weight=2,spec_k=1;beta")
    assert cfgs[0]["spec_k"] == 1 and "spec_k" not in cfgs[1]
    reg = TenantRegistry(server="spk%d" % np.random.randint(1 << 30),
                        spec="acme,spec_k=1;beta")
    assert reg.get("acme").spec_k == 1
    assert reg.get("beta").spec_k is None  # inherit the engine k
    snap = reg.snapshot()
    assert snap["acme"]["spec_k"] == 1 and snap["beta"]["spec_k"] is None


def test_tenant_spec_k_caps_draft_depth(tiny, engt):
    # tenant 'slow' capped at spec_k=0: its slots never speculate while
    # 'fast' rides the engine k — both stay oracle-exact, and the
    # per-tenant acceptance accounting splits accordingly ('slow' never
    # runs anywhere else on this engine, so its counter stays 0)
    model, params = tiny
    futs = [(t, p, engt.submit(p, 6, tenant=t))
            for i in range(2)
            for t, p in [("slow" if i % 2 else "fast",
                          np.asarray([15 + i, 2], np.int32))]]
    for t, p, f in futs:
        np.testing.assert_array_equal(
            f.result(timeout=120),
            model.reference_generate(params, p, 6))
    snap = engt.stats()["tenants"]
    assert snap["slow"]["spec_proposed_tokens"] == 0
    assert snap["fast"]["spec_proposed_tokens"] > 0
    assert snap["fast"]["spec_acceptance_rate"] == 1.0


def test_engine_set_tenant_spec_k_runtime(tiny):
    with _engine(tiny, spec_k=2, spec_draft="model") as eng:
        eng.set_tenant_spec_k("acme", 1)
        assert eng._tenants.get("acme").spec_k == 1
        eng.set_tenant_spec_k("acme", None)
        assert eng._tenants.get("acme").spec_k is None


def test_fleet_forwards_spec_caps_to_replicas(tiny):
    model, params = tiny
    name = "flspec%d" % np.random.randint(1 << 30)

    def factory(rname):
        return serving.DecodeEngine(
            model, params, num_slots=2, max_seq_len=32,
            prefill_buckets=(8,), timeout_ms=0, name=rname,
            spec_k=2, spec_draft="model")

    with serving.FleetRouter(factory, replicas=2, name=name) as fleet:
        fleet.configure_speculation("acme", 0)
        for rep in fleet._replicas:
            assert rep.engine._tenants.get("acme").spec_k == 0
        # a scale-up replica inherits the stored override
        fleet.add_replica(warmup=False)
        for rep in fleet._replicas:
            assert rep.engine._tenants.get("acme").spec_k == 0
        fleet.configure_speculation("acme", None)
        for rep in fleet._replicas:
            assert rep.engine._tenants.get("acme").spec_k is None


# ---------------------------------------------------------------------------
# observability: counters, gauges
# ---------------------------------------------------------------------------

def test_spec_counters_and_acceptance_gauge(tiny, eng4):
    name = eng4._name
    before = eng4.stats()
    eng4.submit([9, 9, 9], 8).result(timeout=120)
    stats = eng4.stats()
    text = telemetry.render_prometheus()
    assert ('mxnet_spec_proposed_tokens_total{server="%s"}' % name) in text
    assert ('mxnet_spec_accepted_tokens_total{server="%s"}' % name) in text
    assert ('mxnet_spec_acceptance_rate{server="%s",tenant="_engine"}'
            % name) in text
    spec, spec0 = stats["speculative"], before["speculative"]
    proposed = spec["proposed_tokens"] - spec0["proposed_tokens"]
    accepted = spec["accepted_tokens"] - spec0["accepted_tokens"]
    assert proposed == accepted > 0
    assert stats["spec_proposed_tokens"] == spec["proposed_tokens"]
    # the flat mirror tracks the cumulative ratio (EOS truncation on
    # earlier eng4 requests may hold it just under 1.0)
    assert stats["spec_acceptance_rate"] == pytest.approx(
        spec["accepted_tokens"] / spec["proposed_tokens"])
    assert stats["spec_acceptance_rate"] > 0.9
    # tokens_generated counts COMMITTED tokens (8 per request), not
    # verify rows
    assert stats["tokens_generated"] == before["tokens_generated"] + 8


def test_tokens_total_counts_accepted_not_proposed(tiny, eng2):
    model, params = tiny
    before = eng2.stats()
    with _swapped_draft(eng2, _RejectAllDraft(model, params)):
        out = eng2.submit([11, 12], 6).result(timeout=120)
    stats = eng2.stats()
    assert len(out) == 6
    # reject-all: every tick proposed and committed exactly 1 — the
    # token counter must show 6, not 6 + proposals
    assert stats["tokens_generated"] == before["tokens_generated"] + 6
    assert (stats["speculative"]["accepted_tokens"]
            == before["speculative"]["accepted_tokens"])
    assert (stats["speculative"]["proposed_tokens"]
            > before["speculative"]["proposed_tokens"])
