"""mxnet_tpu.serving.decode — token-level continuous batching + paged KV
cache + ragged paged-attention kernel (tier-1, CPU).

Covers the ISSUE-7 acceptance surface: interpret-mode kernel parity vs a
dense jnp reference (causal + non-causal, ragged lengths, page-boundary
cases, GQA, inactive slots), the page allocator (reserve/free accounting,
LIFO reuse, never-grows regression), engine correctness vs the no-cache
oracle under slot churn, zero steady-state recompiles, the PR-2 policy
surface (shed/timeout/close), TTFT/TPOT stats, and the PR-4 chaos wiring
(prefill isolation, decode-step eviction soak, breaker shed)."""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.resilience import RetryPolicy, chaos
from mxnet_tpu.serving.kvcache import OutOfPagesError, PagedKVCache, write_kv


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.disable()
    yield
    chaos.disable()


# ---------------------------------------------------------------------------
# ragged paged-attention kernel: interpret-mode parity vs the dense oracle
# ---------------------------------------------------------------------------

def _rand_pool(rng, s, h, kh, d, pages, page_size, max_pages):
    q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
    kp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, page_size, kh, d).astype(np.float32))
    pt = jnp.asarray(rng.randint(1, pages, (s, max_pages)).astype(np.int32))
    return q, kp, vp, pt


def _assert_parity(q, kp, vp, pt, sl, q_pos=None):
    ref = pk.paged_attention_reference(q, kp, vp, pt, sl, q_pos=q_pos)
    ker = pk.ragged_paged_attention(q, kp, vp, pt, sl, q_pos=q_pos,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_parity_ragged_noncausal():
    rng = np.random.RandomState(0)
    q, kp, vp, pt = _rand_pool(rng, 4, 8, 8, 16, 9, 8, 3)
    sl = jnp.asarray(np.array([1, 7, 13, 24], np.int32))
    _assert_parity(q, kp, vp, pt, sl)


def test_kernel_parity_causal_q_pos():
    rng = np.random.RandomState(1)
    q, kp, vp, pt = _rand_pool(rng, 4, 4, 4, 8, 7, 8, 3)
    sl = jnp.asarray(np.array([5, 9, 16, 24], np.int32))
    # q_pos < seq_len - 1: future positions masked even though live
    qpos = jnp.asarray(np.array([0, 3, 8, 20], np.int32))
    _assert_parity(q, kp, vp, pt, sl, q_pos=qpos)


def test_kernel_parity_page_boundaries():
    # lengths straddling page edges: k*page_size - 1, k*page_size,
    # k*page_size + 1 — the off-by-one surface of the ragged mask
    rng = np.random.RandomState(2)
    q, kp, vp, pt = _rand_pool(rng, 4, 4, 4, 8, 11, 8, 4)
    sl = jnp.asarray(np.array([7, 8, 9, 32], np.int32))
    _assert_parity(q, kp, vp, pt, sl)


def test_kernel_parity_gqa():
    # 8 query heads over 2 kv heads: head h reads kv head h // 4
    rng = np.random.RandomState(3)
    q, kp, vp, pt = _rand_pool(rng, 3, 8, 2, 16, 6, 8, 2)
    sl = jnp.asarray(np.array([3, 10, 16], np.int32))
    _assert_parity(q, kp, vp, pt, sl)


def test_kernel_inactive_slot_is_zeros():
    rng = np.random.RandomState(4)
    q, kp, vp, pt = _rand_pool(rng, 3, 4, 4, 8, 5, 8, 2)
    sl = jnp.asarray(np.array([0, 5, 0], np.int32))
    ker = np.asarray(pk.ragged_paged_attention(q, kp, vp, pt, sl,
                                               interpret=True))
    assert (ker[0] == 0).all() and (ker[2] == 0).all()
    assert np.abs(ker[1]).sum() > 0


def test_kernel_rejects_indivisible_gqa():
    rng = np.random.RandomState(5)
    q, kp, vp, pt = _rand_pool(rng, 2, 6, 4, 8, 4, 8, 1)
    with pytest.raises(ValueError, match="not divisible"):
        pk.ragged_paged_attention(q, kp, vp, pt,
                                  jnp.asarray(np.array([4, 4], np.int32)),
                                  interpret=True)


def test_dispatcher_uses_reference_off_tpu():
    # on the CPU test mesh paged_attention routes to the jnp reference —
    # same numbers, traceable inside the decode jit
    rng = np.random.RandomState(6)
    q, kp, vp, pt = _rand_pool(rng, 2, 4, 4, 8, 4, 8, 2)
    sl = jnp.asarray(np.array([5, 12], np.int32))
    got = pk.paged_attention(q, kp, vp, pt, sl)
    ref = pk.paged_attention_reference(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# paged KV cache: the host allocator
# ---------------------------------------------------------------------------

def _cache(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("num_layers", 1)
    kw.setdefault("num_kv_heads", 1)
    kw.setdefault("head_dim", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("name", "t-%d" % np.random.randint(1 << 30))
    return PagedKVCache(**kw)


def test_kvcache_reserve_accounting():
    c = _cache()
    assert c.pages_in_use == 0
    c.reserve(0, 17)  # 3 pages of 8
    assert c.pages_in_use == 3 and c._owned[0] == 3
    c.reserve(0, 20)  # still 3 pages — idempotent growth
    assert c.pages_in_use == 3
    c.reserve(0, 25)  # 4th page
    assert c.pages_in_use == 4


def test_kvcache_null_page_never_allocated():
    c = _cache()
    seen = set()
    c.reserve(0, c.max_seq_len)
    c.reserve(1, c.max_seq_len)
    for s in range(c.num_slots):
        seen.update(int(p) for p in c.page_table[s, :c._owned[s]])
    assert 0 not in seen
    assert len(seen) == c.pages_in_use


def test_kvcache_out_of_pages_leaves_slot_unchanged():
    c = _cache(num_pages=4)  # 3 allocatable
    c.reserve(0, 16)  # 2 pages
    with pytest.raises(OutOfPagesError):
        c.reserve(1, 17)  # needs 3, only 1 free
    assert c._owned[1] == 0 and c.pages_in_use == 2
    assert not c.can_admit(17) and c.can_admit(8)


def test_kvcache_free_lifo_reuse():
    c = _cache()
    c.reserve(0, 16)
    freed = [int(p) for p in c.page_table[0, :2]]
    c.free(0)
    assert c.pages_in_use == 0
    assert (c.page_table[0] == 0).all() and c.seq_lens[0] == 0
    c.free(0)  # idempotent
    c.reserve(1, 16)
    got = [int(p) for p in c.page_table[1, :2]]
    # LIFO: the pages just freed are the next handed out
    assert got == freed[::-1]


def test_kvcache_never_grows_under_churn():
    # the reuse regression of the issue: admit/free cycles far exceeding
    # pool capacity must recycle pages, never exhaust or grow the pool
    c = _cache(num_slots=2, max_seq_len=32, page_size=8)
    cap = c.num_pages
    rng = np.random.RandomState(0)
    for i in range(200):
        slot = i % 2
        c.free(slot)
        c.reserve(slot, int(rng.randint(1, 33)))
    assert c.num_pages == cap
    assert c.pages_in_use <= cap - 1
    c.free(0)
    c.free(1)
    assert c.pages_in_use == 0 and c.pages_free == cap - 1


def test_kvcache_write_slots_page_boundary():
    c = _cache()
    c.reserve(0, 24)
    pages, offs = c.write_slots(0, 6, 4)  # tokens 6..9 straddle page 0/1
    own = [int(p) for p in c.page_table[0, :2]]
    assert [int(p) for p in pages] == [own[0], own[0], own[1], own[1]]
    assert [int(o) for o in offs] == [6, 7, 0, 1]
    with pytest.raises(MXNetError, match="past slot"):
        c.write_slots(0, 22, 4)  # token 25 needs a 4th page


def test_kvcache_null_write_slots_target_null_page():
    c = _cache()
    pages, offs = c.null_write_slots(10)
    assert (pages == 0).all()
    assert offs.max() < c.page_size


def test_kvcache_reserve_beyond_max_seq_len():
    c = _cache(max_seq_len=32)
    with pytest.raises(MXNetError, match="max_seq_len"):
        c.reserve(0, 33)


def test_kvcache_gauge_tracks_pages():
    from mxnet_tpu.serving import kvcache as kvc

    name = "gauge-test"
    c = _cache(name=name)
    c.reserve(0, 16)
    assert kvc._T_PAGES.value(cache=name) == 2
    c.free(0)
    assert kvc._T_PAGES.value(cache=name) == 0


def test_write_kv_scatters_rows():
    c = _cache(num_slots=1, num_layers=2)
    c.reserve(0, 10)
    rows = jnp.asarray(np.arange(2 * 1 * 4, dtype=np.float32)
                       .reshape(2, 1, 4))
    pages, offs = c.write_slots(0, 7, 2)  # straddles the page edge
    kp, vp = write_kv(*c.pools, 1, rows, rows * 2.0,
                      jnp.asarray(pages), jnp.asarray(offs))
    got_k = np.asarray(kp[1][np.asarray(pages), np.asarray(offs)])
    np.testing.assert_array_equal(got_k, np.asarray(rows))
    assert np.abs(np.asarray(kp[0])).sum() == 0  # other layer untouched


# ---------------------------------------------------------------------------
# DecodeEngine: continuous batching vs the no-cache oracle
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
    kw.setdefault("name", "t%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


def test_engine_matches_oracle_under_churn(tiny):
    # more requests than slots with mixed prompt/output lengths: every
    # completion re-admits on the same tick, and every output must equal
    # the no-cache dense oracle exactly (greedy argmax, same params)
    model, params = tiny
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(1, 32, int(rng.randint(1, 14))).astype(np.int32),
             int(rng.randint(1, 9))) for _ in range(9)]
    with _engine(tiny) as eng:
        eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for (p, m), got in zip(reqs, outs):
        ref = model.reference_generate(params, p, m)
        np.testing.assert_array_equal(got, ref)
    assert stats["completed"] == len(reqs)
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0  # all freed


def test_engine_zero_recompiles_and_occupancy(tiny):
    with _engine(tiny, num_slots=2) as eng:
        warm = eng.warmup()
        assert warm > 0
        futs = [eng.submit([1 + i, 2, 3], 6) for i in range(6)]
        for f in futs:
            f.result(timeout=120)
        stats = eng.stats()
    assert stats["steady_state_recompiles"] == 0
    assert stats["compile_count"] == warm
    assert 0.0 < stats["slot_occupancy"] <= 1.0
    assert stats["tokens_generated"] == 6 * 6


def test_engine_eos_frees_slot_early(tiny):
    model, params = tiny
    prompt = np.asarray([3, 5, 7], np.int32)
    ref = model.reference_generate(params, prompt, 16)
    eos = int(ref[2])  # force a stop at the 3rd generated token
    with _engine(tiny) as eng:
        out = eng.generate(prompt, 16, eos_id=eos)
        stats = eng.stats()
    np.testing.assert_array_equal(out, ref[:3])
    assert stats["kvcache"]["pages_in_use"] == 0


def test_engine_ttft_tpot_stats_and_prometheus(tiny):
    name = "ttft-test"
    with _engine(tiny, name=name) as eng:
        eng.warmup()
        for f in [eng.submit([1, 2, 3], 4) for _ in range(3)]:
            f.result(timeout=120)
        stats = eng.stats()
    assert stats["ttft_count"] == 3
    assert stats["tpot_count"] == 9  # 3 seqs x 3 post-first tokens
    assert stats["ttft_p50_ms"] > 0 and stats["tpot_p99_ms"] > 0
    text = telemetry.render_prometheus()
    assert 'mxnet_serving_ttft_ms_count{server="%s"}' % name in text
    assert 'mxnet_serving_tpot_ms' in text


def test_engine_submit_validation(tiny):
    with _engine(tiny) as eng:
        with pytest.raises(MXNetError, match=">= 1 prompt token"):
            eng.submit([], 4)
        with pytest.raises(MXNetError, match="max_new_tokens"):
            eng.submit([1], 0)
        with pytest.raises(MXNetError, match="exceeds max_seq_len"):
            eng.submit([1] * 40, 16)  # 40 + 16 > 48


def test_engine_rejects_unadmittable_reservation(tiny):
    # a worst-case reservation larger than the whole (undersized) pool
    # could never be admitted — FIFO head-of-line would starve everything
    # behind it forever, so submit() rejects it at the door
    with _engine(tiny, num_slots=2, max_seq_len=32, page_size=8,
                 num_pages=3) as eng:  # 2 allocatable pages
        with pytest.raises(MXNetError, match="KV pages"):
            eng.submit([1, 2], 20)  # needs 3 pages, pool has 2
        # a request that fits still serves
        assert len(eng.generate([1], 8)) == 8


def test_engine_survives_fetch_fault(tiny, monkeypatch):
    # a wedged device->host transfer mid-tick must evict the in-flight
    # sequences like a failed step — NOT kill the engine thread and hang
    # every later future (the PR-2 batcher survival discipline)
    import mxnet_tpu.serving.decode as dec

    model, params = tiny
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        real = dec.fetch_host
        calls = {"n": 0}

        def flaky(arrays):
            calls["n"] += 1
            if calls["n"] == 2:  # call 1 = prefill first token, 2 = tick
                raise RuntimeError("transfer wedged")
            return real(arrays)

        monkeypatch.setattr(dec, "fetch_host", flaky)
        doomed = eng.submit([7, 8], 6)
        with pytest.raises(RuntimeError, match="wedged"):
            doomed.result(timeout=120)
        assert eng.stats()["evictions"] == 1
        # the worker is alive and the engine keeps answering
        monkeypatch.setattr(dec, "fetch_host", real)
        np.testing.assert_array_equal(
            eng.generate([9], 4),
            model.reference_generate(params, [9], 4))


def test_engine_worker_survives_unexpected_exception(tiny):
    # belt-and-braces: an exception ANYWHERE in the tick loop (here a
    # poisoned _admit) evicts what was in flight and the thread lives on
    model, params = tiny
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        orig = eng._admit
        state = {"armed": True}

        def poisoned():
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("unexpected admit failure")
            orig()

        eng._admit = poisoned
        np.testing.assert_array_equal(
            eng.generate([11], 3),
            model.reference_generate(params, [11], 3))
        assert eng._thread.is_alive()


def test_engine_queue_shed(tiny):
    # a 1-deep queue with a 1-slot engine saturated by a long request:
    # the next submits shed with QueueFullError
    with _engine(tiny, num_slots=1, queue_depth=1) as eng:
        eng.warmup()
        futs = [eng.submit([1, 2], 24)]
        shed = 0
        for _ in range(30):
            try:
                futs.append(eng.submit([3], 24))
            except serving.QueueFullError:
                shed += 1
        assert shed > 0
        for f in futs:
            f.result(timeout=120)
        assert eng.stats()["shed"] == shed


def test_engine_close_drain_reports_completions(tiny):
    # close(drain=True) returns how many requests finished DURING the
    # drain — the number a zero-drop replica drain / rolling upgrade
    # asserts against — and publishes it as the drain counter
    eng = _engine(tiny, name="drain%d" % np.random.randint(1 << 30))
    name = eng.name
    futs = [eng.submit([1 + i], 5) for i in range(3)]
    drained = eng.close(drain=True)
    for f in futs:
        assert len(f.result(timeout=5)) == 5
    # everything not already finished at close() completed in the drain
    assert 0 <= drained <= 3
    assert eng.stats()["completed"] == 3
    fam = telemetry.REGISTRY.get("mxnet_serving_drain_completed_total")
    assert fam.value(server=name) == drained
    assert eng.close() == 0  # repeat closes report nothing

    eng2 = _engine(tiny)
    assert eng2.close(drain=False) == 0  # fail-fast close drains nothing


def test_engine_queue_deadline_expires(tiny):
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        blocker = eng.submit([1, 2], 30)
        doomed = eng.submit([3], 4, timeout_ms=1.0)
        with pytest.raises(serving.RequestTimeoutError):
            doomed.result(timeout=120)
        np.testing.assert_array_equal(
            blocker.result(timeout=120),
            eng._model.reference_generate(eng._params, [1, 2], 30))
        assert eng.stats()["timeouts"] == 1


def test_engine_close_semantics(tiny):
    eng = _engine(tiny)
    fut = eng.submit([1, 2, 3], 4)
    eng.close()  # drain=True finishes in-flight work
    assert len(fut.result(timeout=5)) == 4
    with pytest.raises(serving.ServerClosedError):
        eng.submit([1], 2)
    eng.close()  # idempotent

    eng2 = _engine(tiny, num_slots=1)
    futs = [eng2.submit([1], 20) for _ in range(3)]
    eng2.close(drain=False)
    failed = 0
    for f in futs:
        try:
            f.result(timeout=5)
        except serving.ServerClosedError:
            failed += 1
    assert failed >= 1  # queued (and any admitted) work fails fast
    assert eng2._cache.pages_in_use == 0


def test_engine_admission_defers_on_page_pressure(tiny):
    # pool sized for ~1.5 worst-case sequences: admission must wait for
    # pages, never evict mid-flight, and everyone completes eventually
    model, params = tiny
    with _engine(tiny, num_slots=2, max_seq_len=32, page_size=8,
                 num_pages=5) as eng:
        eng.warmup()
        reqs = [(np.asarray([1 + i], np.int32), 20) for i in range(4)]
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))
    assert stats["completed"] == 4
    assert stats["kvcache"]["pages_in_use"] == 0


def test_engine_concurrent_submitters(tiny):
    model, params = tiny
    with _engine(tiny) as eng:
        eng.warmup()
        results = {}

        def client(i):
            p = np.asarray([i + 1, i + 2], np.int32)
            results[i] = (p, eng.generate(p, 5))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    for p, got in results.values():
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, 5))


# ---------------------------------------------------------------------------
# chaos wiring: per-request isolation, eviction soak, breaker shed
# ---------------------------------------------------------------------------

def test_chaos_prefill_fault_isolates_one_request(tiny):
    # the 2nd prefill attempt faults with retries off: exactly one future
    # fails, every other request completes with oracle-exact output
    model, params = tiny
    with _engine(tiny, num_slots=1,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode.prefill,at=2"):
            futs = [eng.submit([10 + i], 3) for i in range(4)]
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(("ok", f.result(timeout=120)))
                except chaos.FaultInjected as e:
                    outcomes.append(("fault", e))
        stats = eng.stats()
    kinds = [k for k, _ in outcomes]
    assert kinds.count("fault") == 1
    assert kinds.count("ok") == 3
    for i, (kind, val) in enumerate(outcomes):
        if kind == "ok":
            np.testing.assert_array_equal(
                val, model.reference_generate(params, [10 + i], 3))
    assert stats["errors"] == 1
    assert stats["kvcache"]["pages_in_use"] == 0  # failed slot freed


def test_chaos_decode_fault_evicts_only_in_flight(tiny):
    # the decode-step eviction soak of the issue: a mid-stream fault
    # (retries exhausted) fails exactly the sequences in flight, frees
    # their pages, and the engine answers later traffic on fresh pools
    model, params = tiny
    with _engine(tiny, num_slots=2,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=3"):
            futs = [eng.submit([20 + i, 5], 6) for i in range(2)]
            evicted = 0
            for f in futs:
                try:
                    f.result(timeout=120)
                except chaos.FaultInjected:
                    evicted += 1
        assert evicted == 2  # both were in flight on the faulted tick
        mid = eng.stats()
        assert mid["evictions"] == 2
        assert mid["kvcache"]["pages_in_use"] == 0
        # the engine keeps answering — and stays oracle-exact
        after = [eng.submit([30 + i], 4) for i in range(4)]
        for i, f in enumerate(after):
            np.testing.assert_array_equal(
                f.result(timeout=120),
                model.reference_generate(params, [30 + i], 4))
        stats = eng.stats()
    assert stats["completed"] == 4
    assert stats["steady_state_recompiles"] == 0  # eviction never retraces


def test_chaos_decode_fault_recovers_via_retry(tiny):
    # with the default policy a single injected fault is retried in place:
    # nothing evicted, every output still oracle-exact
    model, params = tiny
    with _engine(tiny, num_slots=2) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=2"):
            futs = [eng.submit([40 + i], 5) for i in range(3)]
            outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for i, got in enumerate(outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, [40 + i], 5))
    assert stats["evictions"] == 0 and stats["completed"] == 3


def test_chaos_breaker_opens_sheds_and_recovers(tiny):
    # a step failure trips the engine breaker (threshold 1): queued work
    # is shed with EngineUnavailableError instead of hanging, and the
    # half-open probe recovers the engine once the schedule ends
    model, params = tiny
    with _engine(tiny, num_slots=1,
                 retry_policy=RetryPolicy(max_attempts=1),
                 breaker_threshold=1, breaker_reset_s=0.2) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=1"):
            futs = [eng.submit([50 + i], 6) for i in range(4)]
            collect = []
            for f in futs:
                try:
                    f.result(timeout=120)
                    collect.append("ok")
                except chaos.FaultInjected:
                    collect.append("fault")
                except serving.EngineUnavailableError:
                    collect.append("shed")
        assert collect[0] == "fault"  # the faulted tick's eviction
        assert "shed" in collect and "ok" not in collect
        # past the reset window the half-open probe serves (the schedule
        # is spent), closing the breaker — oracle-exact again
        time.sleep(0.25)
        np.testing.assert_array_equal(
            eng.generate([60], 3),
            model.reference_generate(params, [60], 3))
        assert eng._breaker.state == "closed"
        assert eng.stats()["steady_state_recompiles"] == 0


# ---------------------------------------------------------------------------
# prefill routing
# ---------------------------------------------------------------------------

def test_prefill_ladder_capped_by_max_seq_len(tiny):
    with _engine(tiny, prefill_buckets=(8, 16, 999), max_seq_len=48) as eng:
        assert eng.stats()["prefill_buckets"] == [8, 16, 48]


def test_prefill_ladder_rejects_garbage(tiny):
    model, params = tiny
    with pytest.raises(MXNetError, match="empty prefill bucket"):
        serving.DecodeEngine(model, params, prefill_buckets=(0, -3),
                             name="bad")


def test_ring_prefill_path_matches_oracle(tiny):
    # ring_prefill_len=1 routes EVERY prompt through the long-context
    # path; on a 1-device CPU mesh it degrades to the dense in-graph
    # attention, so outputs must stay oracle-exact (the multi-device
    # sharded case is covered by tests/test_sequence_parallel.py)
    model, params = tiny
    with _engine(tiny, ring_prefill_len=1) as eng:
        out = eng.generate([3, 1, 4, 1, 5], 4)
    np.testing.assert_array_equal(
        out, model.reference_generate(params, [3, 1, 4, 1, 5], 4))


# ---------------------------------------------------------------------------
# prefix caching: refcounted allocator, CoW, index walk (ISSUE 14)
# ---------------------------------------------------------------------------

def _pcache(**kw):
    kw.setdefault("prefix_cache", True)
    return _cache(**kw)


def test_kvcache_share_never_frees_referenced_page():
    # donor prefixes 16 tokens (2 full pages), indexed; a sharer maps
    # them; freeing the donor must NOT return the shared pages to the
    # free list — the sharer still reads them
    c = _pcache(num_slots=2)
    prompt = np.arange(1, 17, dtype=np.int32)
    c.reserve(0, 16)
    c.insert_prefix(0, prompt)
    m = c.match_prefix(prompt)
    assert m is not None and len(m.full) == 2 and m.partial is None
    assert m.matched == 16
    matched, cow_src, cow_dst = c.admit_prefix(1, 24, m)
    assert matched == 16 and cow_src is None
    shared = [int(p) for p in c.page_table[0, :2]]
    assert [int(p) for p in c.page_table[1, :2]] == shared
    assert c.shared_pages == 2
    c.free(0)
    # pages live on for the sharer: not free, not cached
    assert all(p not in c._free and p not in c._cached for p in shared)
    c.free(1)
    # last ref dropped, still indexed -> parked in the cached-LRU
    assert all(p in c._cached for p in shared)
    assert c.pages_in_use == 0 and c.shared_pages == 0


def test_kvcache_cow_at_divergent_partial_page():
    # donor prompt 12 tokens (1 full + partial fill 4); a prompt
    # diverging INSIDE the partial page shares up to the divergence and
    # gets a fresh CoW page mapped in the partial's position
    c = _pcache(num_slots=2)
    donor = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12], np.int32)
    c.reserve(0, 12)
    c.insert_prefix(0, donor)
    probe = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 99, 98], np.int32)
    m = c.match_prefix(probe)
    assert m is not None and len(m.full) == 1
    assert m.partial is not None and m.partial_len == 2  # [9, 10] match
    assert m.matched == 10
    matched, cow_src, cow_dst = c.admit_prefix(1, 20, m)
    assert matched == 10
    assert cow_src == int(c.page_table[0, 1])   # the donor's partial page
    assert cow_dst == int(c.page_table[1, 1])   # the sharer's private copy
    assert cow_dst != cow_src
    assert c.exclusive_pages(1) == 2  # CoW page + 1 tail page (20 tokens)
    # the full page is shared read-only, the partial was copied
    assert int(c.page_table[1, 0]) == int(c.page_table[0, 0])
    assert c.shared_pages == 1


def test_kvcache_match_verifies_tokens_not_just_hashes():
    c = _pcache(num_slots=2)
    donor = np.arange(1, 13, dtype=np.int32)
    c.reserve(0, 12)
    c.insert_prefix(0, donor)
    # diverges at token 0: nothing shareable
    assert c.match_prefix(np.asarray([9, 9, 9], np.int32)) is None
    # diverges inside the FIRST full page: partial CoW candidate only
    probe = np.arange(1, 13, dtype=np.int32)
    probe[5] = 77
    m = c.match_prefix(probe)
    assert m is not None and len(m.full) == 0
    assert m.partial is not None and m.partial_len == 5


def test_kvcache_reclaims_cached_pages_under_pressure():
    # pool of 4 allocatable pages, all parked in the index (ref 0): a
    # fresh reservation must reclaim them oldest-first instead of
    # raising OutOfPagesError
    c = _pcache(num_slots=2, max_seq_len=32, num_pages=5)
    c.reserve(0, 32)  # all 4 pages
    c.insert_prefix(0, np.arange(1, 25, dtype=np.int32))  # 3 indexed
    c.free(0)
    assert c.pages_cached == 3 and c.pages_free == 1
    assert c.pages_available == 4
    c.reserve(1, 32)  # needs 4: 1 free + 3 reclaimed
    assert c._owned[1] == 4
    assert c.pages_cached == 0
    # index entries for the reclaimed pages are gone: no stale hits
    assert c.match_prefix(np.arange(1, 25, dtype=np.int32)) is None


def test_kvcache_churn_no_growth_with_sharing():
    # the 200-cycle regression with the index ON and shared prefixes:
    # pages recycle through free-list <-> cached-LRU <-> slots, the pool
    # never grows and reservations never fail
    c = _pcache(num_slots=2, max_seq_len=32, page_size=8)
    cap = c.num_pages
    rng = np.random.RandomState(0)
    base = rng.randint(1, 100, 24).astype(np.int32)
    for i in range(200):
        slot = i % 2
        c.free(slot)
        n = int(rng.randint(1, 25))
        prompt = base[:n].copy()
        if rng.rand() < 0.3:
            prompt[rng.randint(0, prompt.size)] = 101 + i % 7  # divergent
        m = c.match_prefix(prompt)
        try:
            c.admit_prefix(slot, min(32, n + 8), m)
        except OutOfPagesError:
            # legitimate deferral under pressure (pinned matched pages
            # can't double as fresh tail pages): the engine would wait
            # for a completion — emulate it, then admission MUST succeed
            c.free(1 - slot)
            m = c.match_prefix(prompt)
            c.admit_prefix(slot, min(32, n + 8), m)
        c.seq_lens[slot] = n
        c.insert_prefix(slot, prompt)
    assert c.num_pages == cap
    c.free(0)
    c.free(1)
    assert c.pages_in_use == 0
    assert c.pages_free + c.pages_cached == cap - 1


def test_kvcache_clear_index_returns_cached_pages():
    c = _pcache()
    c.reserve(0, 16)
    c.insert_prefix(0, np.arange(1, 17, dtype=np.int32))
    c.free(0)
    assert c.pages_cached == 2
    c.clear_prefix_index()
    assert c.pages_cached == 0
    assert c.pages_free == c.num_pages - 1
    assert c.match_prefix(np.arange(1, 17, dtype=np.int32)) is None


def test_kvcache_shared_pages_gauge():
    from mxnet_tpu.serving import kvcache as kvc

    name = "shared-gauge-test"
    c = _pcache(num_slots=2, name=name)
    prompt = np.arange(1, 17, dtype=np.int32)
    c.reserve(0, 16)
    c.insert_prefix(0, prompt)
    c.admit_prefix(1, 16, c.match_prefix(prompt))
    assert kvc._T_SHARED.value(cache=name) == 2
    c.free(1)
    assert kvc._T_SHARED.value(cache=name) == 0
    assert kvc._T_PREFIX_HITS.value(cache=name) == 1


# ---------------------------------------------------------------------------
# DecodeEngine: prefix caching + chunked prefill vs the no-cache oracle
# ---------------------------------------------------------------------------

def test_engine_prefix_cache_exact_and_compiles_nothing(tiny):
    # the shared-prefix oracle-exactness acceptance + the warmup
    # regression: after warmup, a COLD first shared-prefix request (and
    # every hit after it — tail chunks, CoW copies included) compiles
    # nothing
    model, params = tiny
    sysp = [5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 11, 13]  # 12 tokens, ps 8
    reqs = [(np.asarray(sysp + [20 + i], np.int32), 5) for i in range(6)]
    with _engine(tiny, num_slots=2, page_size=8, prefix_cache=True) as eng:
        warm = eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))
    assert stats["kvcache"]["prefix_hits"] >= 4
    assert stats["prefix_hit_ratio"] > 0
    assert stats["cow_copies"] >= 1  # prompts diverge inside page 2
    assert stats["compile_count"] == warm  # cold shared path: 0 compiles
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    assert stats["tenants"]["shared"]["pseudo"] is True


def test_engine_full_prompt_hit_recomputes_last_token(tiny):
    # identical prompt resubmitted: the whole prompt is covered by the
    # index, only the last position is recomputed (no KV rewritten) and
    # the output must stay oracle-exact
    model, params = tiny
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], np.int32)
    ref = model.reference_generate(params, prompt, 6)
    with _engine(tiny, page_size=8, prefix_cache=True) as eng:
        eng.warmup()
        np.testing.assert_array_equal(eng.generate(prompt, 6), ref)
        np.testing.assert_array_equal(eng.generate(prompt, 6), ref)
        stats = eng.stats()
    assert stats["kvcache"]["prefix_hits"] == 1
    assert stats["kvcache"]["prefix_tokens_matched"] == 10
    assert stats["steady_state_recompiles"] == 0


def test_engine_chunked_prefill_exact(tiny):
    # chunked prefill alone (cache off): every prompt runs through the
    # one chunk rung, outputs oracle-exact, chunk count = sum of
    # ceil(p / C), zero steady-state recompiles
    model, params = tiny
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(1, 32, int(rng.randint(1, 14))).astype(np.int32),
             int(rng.randint(1, 7))) for _ in range(7)]
    with _engine(tiny, num_slots=2, prefix_cache=False,
                 prefill_chunk=4) as eng:
        warm = eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))
    want_chunks = sum(-(-p.size // 4) for p, _m in reqs)
    assert stats["prefill_chunks"] == want_chunks
    assert stats["compile_count"] == warm
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0


def test_engine_chunked_plus_cache_exact(tiny):
    # both optimisations composed: shared prefixes + chunk interleaving
    model, params = tiny
    sysp = [7, 3, 7, 3, 1, 1, 2, 2, 9]
    reqs = [(np.asarray(sysp + [15 + i, 14 - i], np.int32), 5)
            for i in range(5)]
    with _engine(tiny, num_slots=2, page_size=8, prefix_cache=True,
                 prefill_chunk=4) as eng:
        warm = eng.warmup()
        futs = [eng.submit(p, m) for p, m in reqs]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, model.reference_generate(params, p, m))
    assert stats["kvcache"]["prefix_hits"] >= 3
    assert stats["prefill_chunks"] > 0
    assert stats["compile_count"] == warm
    assert stats["steady_state_recompiles"] == 0


def test_engine_chunked_short_prompt_not_blocked_by_long(tiny):
    # the TTFT-decoupling property, functionally: a short request
    # submitted alongside a LONG prompt (many chunks) completes while
    # the long one is still prefilling — chunks yield the tick
    with _engine(tiny, num_slots=2, max_seq_len=48, prefix_cache=False,
                 prefill_chunk=4) as eng:
        eng.warmup()
        order = []
        f_long = eng.submit(np.arange(1, 33, dtype=np.int32), 4)  # 8 chunks
        f_short = eng.submit([2, 4], 2)                           # 1 chunk
        f_long.add_done_callback(lambda _f: order.append("long"))
        f_short.add_done_callback(lambda _f: order.append("short"))
        f_long.result(timeout=120)
        f_short.result(timeout=120)
        stats = eng.stats()
    assert order[0] == "short"
    assert stats["prefill_chunks"] == 9
    assert stats["steady_state_recompiles"] == 0


def test_engine_cow_shared_eviction_leaves_sharers_intact(tiny):
    # chaos: the sharer's tail prefill faults AFTER its pages were
    # mapped/CoW'd — exactly its future fails and its mappings release,
    # while the donor (mid-decode on the shared pages) finishes
    # oracle-exact. at=2 targets the second prefill-site call: the
    # donor's monolithic prefill is call 1, the sharer's tail chunk is
    # call 2.
    model, params = tiny
    prompt = np.asarray([6, 2, 6, 2, 1, 5, 1, 5, 3, 9], np.int32)
    with _engine(tiny, num_slots=2, page_size=8, prefix_cache=True,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode.prefill,at=2"):
            donor = eng.submit(prompt, 16)
            time.sleep(0.05)  # let the donor prefill + start decoding
            doomed = eng.submit(prompt, 16)
            with pytest.raises(chaos.FaultInjected):
                doomed.result(timeout=120)
            out = donor.result(timeout=120)
        stats = eng.stats()
    np.testing.assert_array_equal(
        out, model.reference_generate(params, prompt, 16))
    assert stats["errors"] == 1
    assert stats["evictions"] == 0  # request-level failure, no eviction
    assert stats["kvcache"]["pages_in_use"] == 0
    # the engine still answers shared-prefix traffic afterwards
    with _engine(tiny, page_size=8, prefix_cache=True) as eng2:
        eng2.warmup()
        np.testing.assert_array_equal(
            eng2.generate(prompt, 4),
            model.reference_generate(params, prompt, 4))


def test_engine_weight_swap_flushes_prefix_index(tiny):
    # cached KV was computed under the old weights: after swap_params
    # the same prompt must match NOTHING and the output must equal the
    # new-params oracle (a stale hit would poison it)
    model, params = tiny
    params_b = model.init_params(1)
    prompt = np.asarray([8, 6, 7, 5, 3, 0 + 1, 9, 4, 2, 12], np.int32)
    with _engine(tiny, page_size=8, prefix_cache=True) as eng:
        eng.warmup()
        np.testing.assert_array_equal(
            eng.generate(prompt, 5),
            model.reference_generate(params, prompt, 5))
        eng.swap_params(params_b, timeout=120)
        np.testing.assert_array_equal(
            eng.generate(prompt, 5),
            model.reference_generate(params_b, prompt, 5))
        stats = eng.stats()
    assert stats["kvcache"]["prefix_hits"] == 0  # flush: no stale hit
    assert stats["steady_state_recompiles"] == 0


def test_engine_eviction_clears_prefix_index(tiny):
    # a tick-level eviction re-zeroes the pools: stale index entries
    # pointing at zeroed pages must die with them, and later shared
    # traffic stays oracle-exact
    model, params = tiny
    prompt = np.asarray([4, 4, 2, 2, 8, 8, 1, 1, 6, 6], np.int32)
    with _engine(tiny, num_slots=1, page_size=8, prefix_cache=True,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=2"):
            f1 = eng.submit(prompt, 6)
            with pytest.raises(chaos.FaultInjected):
                f1.result(timeout=120)
        # the future fails before the worker's reset_pools finishes:
        # poll for the flush instead of racing it
        deadline = time.time() + 10
        while eng.stats()["kvcache"]["pages_cached"] and \
                time.time() < deadline:
            time.sleep(0.01)
        assert eng.stats()["kvcache"]["pages_cached"] == 0  # index flushed
        np.testing.assert_array_equal(
            eng.generate(prompt, 6),
            model.reference_generate(params, prompt, 6))


def test_prefix_and_chunk_metrics_render_prometheus(tiny):
    name = "prefix-prom-test"
    prompt = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], np.int32)
    with _engine(tiny, name=name, page_size=8, prefix_cache=True,
                 prefill_chunk=4) as eng:
        eng.warmup()
        eng.generate(prompt, 3)
        eng.generate(prompt, 3)
    text = telemetry.render_prometheus()
    assert 'mxnet_kvcache_prefix_hits_total{cache="%s"}' % name in text
    assert 'mxnet_kvcache_prefix_misses_total{cache="%s"}' % name in text
    assert 'mxnet_kvcache_shared_pages' in text
    assert 'mxnet_decode_prefill_chunks_total{server="%s"}' % name in text


def test_kvcache_admit_prefix_rejects_before_mutating():
    # review regression: a total past max_seq_len must raise BEFORE any
    # mapping — no half-admitted slot with live refcounts
    c = _pcache(num_slots=2, max_seq_len=32)
    donor = np.arange(1, 17, dtype=np.int32)
    c.reserve(0, 16)
    c.insert_prefix(0, donor)
    m = c.match_prefix(donor)
    before = c._ref.copy()
    with pytest.raises(MXNetError, match="max_seq_len"):
        c.admit_prefix(1, 40, m)
    assert c._owned[1] == 0 and c.exclusive_pages(1) == 0
    np.testing.assert_array_equal(c._ref, before)
    assert c.prefix_hits == 0  # nothing was admitted


def test_engine_swap_mid_chunked_prefill_never_reindexes_stale_kv(tiny):
    # review regression: a weight swap landing BETWEEN chunks of an
    # in-flight prefill flushes the index; the straddling sequence's
    # pages hold old-weight KV and must NOT be re-indexed at completion
    # — later identical prompts must match the NEW-params oracle
    model, params = tiny
    params_b = model.init_params(1)
    prompt = np.arange(1, 33, dtype=np.int32)  # 16 chunks of 2
    with _engine(tiny, num_slots=1, max_seq_len=48, page_size=8,
                 prefix_cache=True, prefill_chunk=2) as eng:
        eng.warmup()
        f = eng.submit(prompt, 2)
        time.sleep(0.01)  # let some chunks land under the old weights
        eng.swap_params(params_b, timeout=120)
        f.result(timeout=120)  # mixed-weight output: the documented
        #                        in-flight rollout semantic — not checked
        # the invariant: whatever the race, the next identical prompt is
        # exact under the NEW weights (a stale re-index would poison it)
        np.testing.assert_array_equal(
            eng.generate(prompt, 5),
            model.reference_generate(params_b, prompt, 5))
        assert eng.stats()["steady_state_recompiles"] == 0


# ---------------------------------------------------------------------------
# MXNET_KVCACHE_AUDIT: the runtime twin of the static resource-lifecycle
# pass — re-proves the refcount invariant on every mutation and tick
# ---------------------------------------------------------------------------

def test_kvcache_double_free_decrefs_once_silently_when_audit_off(
        monkeypatch):
    # a release path running twice over one mapping used to clamp the
    # refcount AND re-append the page — a duplicate free-list entry that
    # hands one page to two slots. The guard decrefs once and keeps the
    # free list duplicate-free. (Pinned audit-off: the suite may run
    # under MXNET_KVCACHE_AUDIT=1, where this same shape raises.)
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "0")
    c = _pcache(num_slots=2)
    c.reserve(0, 16)  # 2 exclusive pages
    row = [int(p) for p in c.page_table[0, :2]]
    c.free(0)
    assert len(set(c._free)) == len(c._free)
    # simulate the stale mapping a re-entrant release would observe
    c.page_table[0, :2] = row
    c._owned[0] = 2
    free_before = list(c._free)
    c.free(0)  # absorbed: no decref past zero, no duplicate entry
    assert list(c._free) == free_before
    assert len(set(c._free)) == len(c._free)
    c.reserve(1, 16)  # the pool still hands out distinct pages
    got = [int(p) for p in c.page_table[1, :2]]
    assert len(set(got)) == 2


def test_kvcache_double_free_raises_under_audit(monkeypatch):
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    c = _pcache(num_slots=2)
    assert c.audit
    c.reserve(0, 16)
    row = [int(p) for p in c.page_table[0, :2]]
    c.free(0)
    c.page_table[0, :2] = row
    c._owned[0] = 2
    with pytest.raises(MXNetError, match="double-free"):
        c.free(0)


def test_kvcache_audit_check_passes_through_cow_sharing(monkeypatch):
    # the full CoW lifecycle — donor indexes, sharer maps + CoW page,
    # donor freed, sharer freed — keeps every audit invariant green
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    c = _pcache(num_slots=2)
    donor = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12], np.int32)
    c.reserve(0, 12)
    c.insert_prefix(0, donor)
    probe = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 99, 98], np.int32)
    c.admit_prefix(1, 20, c.match_prefix(probe))
    c.audit_check()
    c.free(0)
    c.free(1)
    c.audit_check()
    assert c.pages_in_use == 0


def test_engine_audit_shared_prefix_chaos_eviction(tiny, monkeypatch):
    # two slots decode on CoW-shared prefix pages; a chaos decode fault
    # (retries off) evicts them mid-tick. Each eviction must decref the
    # shared pages exactly once — the per-tick audit turns any re-entrant
    # release into a hard failure instead of silent KV corruption — and
    # the engine must answer shared-prefix traffic afterwards.
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    model, params = tiny
    prompt = np.asarray([6, 2, 6, 2, 1, 5, 1, 5, 3, 9], np.int32)
    with _engine(tiny, num_slots=2, page_size=8, prefix_cache=True,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        assert eng._cache.audit
        eng.warmup()
        # donor populates the prefix index, then completes (pages parked)
        np.testing.assert_array_equal(
            eng.generate(prompt, 2),
            model.reference_generate(params, prompt, 2))
        with chaos.active("seed=1,site=serving.decode,at=3"):
            futs = [eng.submit(prompt, 12) for _ in range(2)]
            evicted = 0
            for f in futs:
                try:
                    f.result(timeout=120)
                except chaos.FaultInjected:
                    evicted += 1
        assert evicted >= 1  # at least one sharer died on the faulted tick
        mid = eng.stats()
        assert mid["kvcache"]["pages_in_use"] == 0
        # the audited engine keeps serving the shared prefix, exactly
        np.testing.assert_array_equal(
            eng.generate(prompt, 4),
            model.reference_generate(params, prompt, 4))
        eng._cache.audit_check()


# ---------------------------------------------------------------------------
# window layers: the ring table in decode, the band in prefill (PR 27)
# ---------------------------------------------------------------------------

def test_ring_blocks_names_the_block_each_column_holds():
    got = np.asarray(pk.ring_blocks(jnp.asarray([0, 5, 33, 100]), 5, 8))
    assert got.tolist() == [[-5, -4, -3, -2, -1], [0, -4, -3, -2, -1],
                            [0, 1, 2, 3, 4], [10, 11, 12, 8, 9]]


@pytest.mark.parametrize("h,kh", [(4, 4), (12, 2)])
def test_window_kernel_over_a_ring_table_matches_dense_oracle(h, kh):
    """Interpret mode: an idle slot, a sequence inside the window, one past
    it and one that wrapped the ring twice, each against the dense form."""
    rng = np.random.RandomState(7)
    s, d, ps, cols, window = 4, 16, 8, 5, 32
    pool = 1 + s * cols
    k_pool = jnp.asarray(rng.randn(pool, ps, kh, d).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(pool, ps, kh, d).astype(np.float32))
    pt = jnp.asarray(1 + rng.permutation(s * cols).reshape(s, cols),
                     jnp.int32)
    q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
    lens = jnp.asarray([0, 5, 37, 100], jnp.int32)
    got = pk.ragged_window_attention(q, k_pool, v_pool, pt, lens, window,
                                     interpret=True)
    want = pk.paged_window_attention_reference(q, k_pool, v_pool, pt, lens,
                                               window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got)[0].any()
    # the dense form itself, for the wrapped sequence: keys 68..99 and no
    # other, each read from column (p // 8) % 5
    pos = np.arange(100 - window, 100)
    rows_k = np.asarray(k_pool)[np.asarray(pt)[3][(pos // ps) % cols],
                                pos % ps]
    rows_v = np.asarray(v_pool)[np.asarray(pt)[3][(pos // ps) % cols],
                                pos % ps]
    g = h // kh
    sc = np.einsum("hd,thd->ht", np.asarray(q)[3],
                   np.repeat(rows_k, g, 1)) / np.sqrt(d)
    p = np.exp(sc - sc.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    np.testing.assert_allclose(
        np.einsum("ht,thd->hd", p, np.repeat(rows_v, g, 1)),
        np.asarray(want)[3], atol=2e-5)


def test_paged_kernel_masks_a_window_over_an_ordinary_table():
    rng = np.random.RandomState(8)
    s, h, kh, d, ps, mp, window = 3, 4, 2, 16, 8, 6, 16
    q, k_pool, v_pool, pt = _rand_pool(rng, s, h, kh, d, 1 + s * mp, ps, mp)
    lens = jnp.asarray([3, 20, 48], jnp.int32)
    got = pk._paged_call(q[:, None], k_pool, v_pool, pt, lens, None, None,
                         True, "test", window=window)[:, 0]
    # the dense oracle over the last `window` keys: gather them to the front
    want = []
    for i, n in enumerate(np.asarray(lens)):
        lo = max(0, n - window)
        pos = np.arange(lo, n)
        kk = np.asarray(k_pool)[np.asarray(pt)[i][pos // ps], pos % ps]
        vv = np.asarray(v_pool)[np.asarray(pt)[i][pos // ps], pos % ps]
        sc = np.einsum("hd,thd->ht", np.asarray(q)[i],
                       np.repeat(kk, h // kh, 1)) / np.sqrt(d)
        p = np.exp(sc - sc.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        want.append(np.einsum("ht,thd->hd", p, np.repeat(vv, h // kh, 1)))
    np.testing.assert_allclose(np.asarray(got), np.stack(want), atol=2e-5)


# ---------------------------------------------------------------------------
# the bounded page walk: live_columns, and the kernel under it (PR 28)
# ---------------------------------------------------------------------------

_WALK_PS = 8
_WALK_LAUNCHES = ["decode", "verify", "chunk", "ring", "window"]
_WALK_LENGTHS = [0, 1, _WALK_PS, _WALK_PS + 1, "full"]


def _walk_launch(kind, n):
    """One of ``_paged_call``'s five launches at tiny sizes, slot 0 (the
    chunk: the whole launch) holding ``n`` tokens (``"full"``: as many as
    its table holds): ``run(pt)`` the kernel in interpret mode, ``ref(pt)``
    its dense oracle, the table ``pt``, per-row lengths ``sl`` and query
    positions ``qp`` ``(S, W)`` and the static ``cols/window/ring``."""
    rng = np.random.RandomState(28)
    ps, h, kh, d = _WALK_PS, 4, 2, 8
    cols, window, ring = (5, 32, True) if kind == "ring" else \
        (6, 16 if kind == "window" else 0, False)
    if n == "full":
        n = cols * ps
    qp = None
    if kind == "verify":        # W = 4: three rows in a chain, one padded
        sl = np.asarray([[max(n - 2, 0), max(n - 1, 0), n, 0],
                         [19, 20, 21, 22], [0, 0, 0, 0]], np.int32)
    elif kind == "chunk":       # 4 chunk rows of ONE sequence of n tokens,
        # each a slot of its own over one table row, the last one padding
        start = max(n - 3, 0)
        qp = (start + np.arange(4, dtype=np.int32))[:, None]
        sl = np.where(np.arange(4)[:, None] < min(n, 3), n, 0).astype(
            np.int32)
    elif kind == "ring":        # beside it: inside the window, wrapped twice
        sl = np.asarray([[n], [37], [100]], np.int32)
    else:
        sl = np.asarray([[n], [20], [48]], np.int32)
    s, w = sl.shape
    pool = 2 + s * cols         # page 0 the null page, the last one NaN
    q = jnp.asarray(rng.randn(s, w, h, d).astype(np.float32))
    kp = rng.randn(pool, ps, kh, d).astype(np.float32)
    vp = rng.randn(pool, ps, kh, d).astype(np.float32)
    kp[-1] = vp[-1] = np.nan
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    pt = 1 + rng.permutation(s * cols).reshape(s, cols).astype(np.int32)
    if kind == "chunk":
        pt[:] = pt[0]
    flat = jnp.asarray(sl.ravel())
    qflat = None if qp is None else jnp.asarray(qp.ravel())

    def run(table):
        return pk._paged_call(q, kp, vp, jnp.asarray(table), flat, qflat,
                              None, True, "test", window=window, ring=ring)

    def ref(table):
        table = jnp.asarray(table)
        if kind == "verify":
            out = pk.paged_spec_attention_reference(
                q.reshape(s * w, h, d), kp, vp, table, flat)
        elif kind == "ring":
            out = pk.paged_window_attention_reference(
                q[:, 0], kp, vp, table, flat, window)
        elif kind == "window":
            pos = np.arange(cols * ps)[None, :]
            out = pk._dense_paged(
                q[:, 0], kp, vp, table,
                jnp.asarray((pos < sl) & (pos >= sl - window)), None)
        else:
            out = pk.paged_attention_reference(q[:, 0], kp, vp, table, flat,
                                               q_pos=qflat)
        return out.reshape(s, w, h, d)

    return dict(run=run, ref=ref, pt=pt, sl=sl, qp=qp, cols=cols, ps=ps,
                window=window, ring=ring, nan_page=pool - 1)


def _walk_live(launch):
    """``live_columns`` of a launch, as the kernel is handed it."""
    return np.asarray(pk.live_columns(
        jnp.asarray(launch["sl"]),
        None if launch["qp"] is None else jnp.asarray(launch["qp"]),
        launch["cols"], launch["ps"], window=launch["window"],
        ring=launch["ring"]))


def _walk_mask(launch):
    """The kernel's own ``valid`` mask by brute force: ``(S, cols)`` bool,
    whether ANY row of the slot sees ANY position of the column."""
    sl, qp, ps, cols = (launch[k] for k in ("sl", "qp", "ps", "cols"))
    first = np.broadcast_to(np.arange(cols), (sl.shape[0], cols))
    if launch["ring"]:
        first = np.asarray(pk.ring_blocks(jnp.asarray(sl[:, 0]), cols, ps))
    # (S, W, cols, ps)
    pos = first[:, None, :, None] * ps + np.arange(ps)
    rows = sl[:, :, None, None]
    valid = pos < rows
    query = rows - 1
    if qp is not None:
        query = qp[:, :, None, None]
        valid &= pos <= query
    if launch["window"]:
        valid &= pos >= np.maximum(query - launch["window"] + 1, 0)
    if launch["ring"]:
        valid &= pos >= 0
    return valid.any(axis=(1, 3))


@pytest.mark.parametrize("n", _WALK_LENGTHS)
@pytest.mark.parametrize("kind", _WALK_LAUNCHES)
def test_live_columns_cover_the_kernels_mask_to_one_column(kind, n):
    launch = _walk_launch(kind, n)
    seen = _walk_mask(launch)
    for (c0, c1), cols in zip(_walk_live(launch), seen):
        assert 0 <= c0 <= c1 <= launch["cols"] and c0 < launch["cols"]
        live = np.flatnonzero(cols)
        if not live.size:
            assert c0 == c1 == 0
            continue
        assert c0 <= live[0] and live[-1] < c1           # never narrower
        if launch["ring"]:      # a prefix; wrapped, all but at most one
            assert c0 == 0 and (c1 - c0) - live.size <= 1
        else:
            assert (c0, c1) == (live[0], live[-1] + 1)


@pytest.mark.parametrize("n", _WALK_LENGTHS)
@pytest.mark.parametrize("kind", _WALK_LAUNCHES)
def test_bounded_walk_matches_the_dense_oracle(kind, n):
    launch = _walk_launch(kind, n)
    got = np.asarray(launch["run"](launch["pt"]))
    np.testing.assert_allclose(got, np.asarray(launch["ref"](launch["pt"])),
                               atol=2e-5, rtol=2e-5)
    assert not got[~(launch["sl"] > 0)].any()    # a row that sees nothing


@pytest.mark.parametrize("n", _WALK_LENGTHS)
@pytest.mark.parametrize("kind", _WALK_LAUNCHES)
def test_bounded_walk_never_reads_a_dead_column(kind, n):
    """Every column outside ``[c0, c1)`` points at a page of NaN: the kernel
    neither fetches nor multiplies it (a masked product would read ``0 *
    NaN``), so its output is finite and equals the oracle's over the table
    whose dead columns hold the null page."""
    launch = _walk_launch(kind, n)
    col = np.arange(launch["cols"])[None, :]
    live = _walk_live(launch)
    dead = (col < live[:, :1]) | (col >= live[:, 1:])
    assert dead.any() or n == "full"
    got = np.asarray(launch["run"](
        np.where(dead, launch["nan_page"], launch["pt"])))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(launch["ref"](np.where(dead, 0, launch["pt"]))),
        atol=2e-5, rtol=2e-5)


def _band_operands(t, h, kh, d=16, seed=9):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(t, n, d).astype(np.float32))
                 for n in (h, kh, kh))


@pytest.mark.parametrize("window", [0, 32, 40])
@pytest.mark.parametrize("h,kh", [(4, 4), (12, 2)])
def test_band_attention_kernel_matches_dense_oracle(window, h, kh):
    """Interpret mode, 16-row blocks over 100 rows (padded to 112): causal,
    a window of whole blocks and one that cuts a block."""
    q, k, v = _band_operands(100, h, kh)
    got = pk.band_attention(q, k, v, window=window, block=16,
                            interpret=True)
    want = pk.band_attention_reference(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("length", [5, 48, 49, 100])
@pytest.mark.parametrize("window", [0, 32, 40])
@pytest.mark.parametrize("h,kh", [(12, 2), (6, 1)])
def test_band_attention_kernel_stops_at_the_prompts_last_block(
        window, h, kh, length):
    """``length`` (traced) inside the first block, on a block's boundary,
    one past it and the whole sequence: the prompt's rows are the dense
    oracle's and, bit for bit, those of the call without ``length``; the
    query blocks behind the prompt's last are not launched and come back
    zeros; the padding rows between are finite."""
    t, block = 100, 16
    q, k, v = _band_operands(t, h, kh)
    got = np.asarray(jax.jit(lambda n: pk.band_attention(
        q, k, v, window=window, block=block, interpret=True, length=n))(
            jnp.asarray(length, jnp.int32)))
    whole = np.asarray(pk.band_attention(q, k, v, window=window,
                                         block=block, interpret=True))
    want = np.asarray(pk.band_attention_reference(q, k, v, window=window))
    np.testing.assert_allclose(got[:length], want[:length], atol=2e-5)
    np.testing.assert_array_equal(got[:length], whole[:length])
    behind = -(-length // block) * block
    assert np.isfinite(got[length:behind]).all()
    assert not got[behind:].any()


@pytest.mark.parametrize("length", [None, 150, 17])
@pytest.mark.parametrize("window", [0, 72])
def test_band_attention_kernel_at_its_own_block_size(window, length):
    """The kept block (256 rows a query block, 256 columns a kv block) over
    600 rows: three blocks, a window that cuts one. Keys and values behind
    the prompt's last block are NaN: a block the kernel fetched and
    multiplied for a live row would show (``0 * NaN``)."""
    t, block = 600, pk._BAND_BLOCK
    assert t > 2 * block
    q, k, v = _band_operands(t, 12, 2)
    n = t if length is None else length
    dead = jnp.arange(t)[:, None, None] >= -(-n // block) * block
    got = np.asarray(pk.band_attention(
        q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
        window=window, interpret=True,
        length=None if length is None else jnp.asarray(length, jnp.int32)))
    want = np.asarray(pk.band_attention_reference(q, k, v, window=window))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:n], want[:n], atol=2e-5)


def test_a_model_that_declares_nothing_keeps_its_one_group_and_its_operands(
        tiny):
    """TinyDecoder declares neither ``layer_state`` nor ``moe_counters``: one
    group of paged K/V, a (6, S) step operand (five rows and ``from_prev``),
    a (3, rung) prefill operand, no counters behind the tokens, no new key
    in its stats."""
    from mxnet_tpu.serving.kvcache import PagedKVCache

    with _engine(tiny, prefix_cache=False) as eng:
        (group,) = eng._cache.groups
        assert type(group) is PagedKVCache and eng._cache.state == ()
        assert eng._packed_rows == 6 and eng._moe_rows is None
        eng.warmup()
        shapes = []
        real = eng._jnp.asarray

        class Spy:
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def asarray(x, *a, **k):
                shapes.append(np.shape(x))
                return real(x, *a, **k)

        eng._jnp = Spy()
        out = eng.generate(np.arange(1, 7, dtype=np.int32), 5, timeout=120)
        stats = eng.stats()
    assert out.size == 5
    assert (3, 8) in shapes and shapes.count((6, 3)) == 4
    assert "moe" not in stats and "window" not in stats["kvcache"]
