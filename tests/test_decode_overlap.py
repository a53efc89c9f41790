"""One decode step in flight (tier-1, CPU): ``DecodeEngine`` dispatches step
N+1 before it fetches step N — N+1 reads N's sampled tokens on the device —
and what that must not break.

A pipelined tick can fail ``correct`` four ways (``benchmark/drivers/
decode.py``): a wrong token, a request that never finishes, a recompile, a
page left in use. Each case below holds one of them on a path where a step is
in flight while something else happens: a burst of admissions into re-used
slots, EOS on the token before the one in flight, a timeout, an injected step
fault, a failed fetch, both kinds of ``close``, a weight swap. ``plain`` is
``TinyDecoder`` over a ``PagedKVCache``; ``grouped`` the tiny ``AfmoeDecoder``
of ``tests/test_afmoe_decoder.py`` (window ring + full group, expert counters
behind the tokens), judged like there: a served token may lie ``GAP_TOL`` row
standard deviations below the reference's best.
"""
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.resilience import RetryPolicy, chaos
from mxnet_tpu.serving import afmoe_reference as ref
from mxnet_tpu.serving import decode as decode_mod

GAP_TOL = 1e-3
AFMOE = dict(vocab_size=96, hidden_size=48, num_attention_heads=12,
             num_key_value_heads=2, head_dim=8, intermediate_size=96,
             moe_intermediate_size=32,
             layer_types=["sliding_attention"] * 4 + ["full_attention"],
             num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
             sliding_window=32, held_experts=[4, 4], route_scale=2.448,
             mup_enabled=True)


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.disable()
    yield
    chaos.disable()


@pytest.fixture(scope="module")
def models():
    plain = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    grouped = serving.AfmoeDecoder(**AFMOE)
    return {"plain": (plain, plain.init_params(0)),
            "grouped": (grouped, grouped.init_params(0))}


def _engine(models, kind, **kw):
    model, params = models[kind]
    kw.setdefault("num_slots", 4)
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("name", "ov%d" % np.random.randint(1 << 30))
    if kind == "grouped":
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("page_size", 8)
        kw.setdefault("prefill_buckets", (16, 64))
        kw.setdefault("prefill_chunk", 0)
    else:
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("prefill_buckets", (8, 16))
    return serving.DecodeEngine(model, params, **kw)


def _prompt(kind, n, seed):
    vocab = 96 if kind == "grouped" else 32
    return np.random.RandomState(seed).randint(1, vocab, n).astype(np.int32)


def _assert_served(models, kind, prompt, out, max_new, eos_id=None):
    """``out`` is what the reference generates: bit for bit (plain), or
    token by token the reference's best to a near-tie (grouped)."""
    model, params = models[kind]
    if kind == "plain":
        np.testing.assert_array_equal(
            out, model.reference_generate(params, prompt, max_new,
                                          eos_id=eos_id))
        return
    assert 1 <= out.size <= max_new
    assert (out.size == max_new) if eos_id is None \
        else (out[-1] == eos_id or out.size == max_new)
    seq = np.concatenate([prompt, out[:-1]])
    rows = np.asarray(ref.forward_logits(model.cfg, params, seq))
    rows = rows[prompt.size - 1:]
    got = rows[np.arange(out.size), out]
    assert float(((rows.max(-1) - got) / rows.std(-1)).max()) <= GAP_TOL


def _slow_fetch(monkeypatch, seconds):
    """Every device->host fetch of the engine takes ``seconds`` longer: the
    worker spends its time with a step in flight, and a caller's thread gets
    its turn while one is."""
    real = decode_mod.fetch_host

    def fetch(xs, *a, **k):
        time.sleep(seconds)
        return real(xs, *a, **k)

    monkeypatch.setattr(decode_mod, "fetch_host", fetch)


def _wait_overlapped(eng, more=2, timeout=60.0):
    """Block until ``more`` further steps were dispatched over an un-fetched
    one: a step is in flight at this instant or the next."""
    start = eng.stats()["steps_overlapped"]
    deadline = time.time() + timeout
    while eng.stats()["steps_overlapped"] < start + more:
        assert time.time() < deadline, "no step was ever overlapped"
        time.sleep(0.001)


def _idle(eng):
    """The worker at rest: nothing queued, slotted or in flight."""
    deadline = time.time() + 30.0
    while eng._inflight is not None or eng._any_active():
        assert time.time() < deadline
        time.sleep(0.001)
    assert eng.kvcache_stats()["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# (a) a burst while a step is in flight, slots re-used
# ---------------------------------------------------------------------------
BURSTS = {
    # twelve and more requests of mixed lengths into four slots; max_new 1
    # and 2 are the edges: done at the prefill, and "the token in flight is
    # the last" on the very first step
    "plain": dict(kind="plain"),
    "plain_prefix_cache": dict(kind="plain", prefix_cache=True, shared=6),
    "plain_chunked_prefill": dict(kind="plain", prefill_chunk=4),
    "grouped": dict(kind="grouped"),
}


@pytest.mark.parametrize("case", sorted(BURSTS))
def test_burst_admitted_while_a_step_is_in_flight_is_token_exact(
        models, monkeypatch, case):
    cfg = dict(BURSTS[case])
    kind, shared = cfg.pop("kind"), cfg.pop("shared", 0)
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    _slow_fetch(monkeypatch, 0.002)
    rng = np.random.RandomState(11)
    if kind == "grouped":
        sizes = [(5, 20), (40, 24), (70, 30), (100, 28), (12, 1), (33, 2),
                 (64, 8), (90, 12), (7, 3), (20, 16), (50, 5), (36, 9),
                 (9, 2), (75, 7)]
    else:
        sizes = [(int(rng.randint(1, 14)), int(rng.randint(1, 10)))
                 for _ in range(14)]
        sizes[3], sizes[8] = (sizes[3][0], 1), (sizes[8][0], 2)
    head = _prompt(kind, shared, 1000)
    prompts = [np.concatenate([head, _prompt(kind, n, i)])
               for i, (n, _m) in enumerate(sizes)]
    with _engine(models, kind, **cfg) as eng:
        warm = eng.warmup()
        first = [eng.submit(prompts[i], sizes[i][1] + 12) for i in range(2)]
        _wait_overlapped(eng, 3)
        futs = [eng.submit(p, m) for p, (_n, m) in
                zip(prompts[2:], sizes[2:])]
        outs = [f.result(timeout=300) for f in first + futs]
        _idle(eng)
        stats = eng.stats()
    want = [m + 12 for _n, m in sizes[:2]] + [m for _n, m in sizes[2:]]
    for prompt, out, m in zip(prompts, outs, want):
        _assert_served(models, kind, prompt, out, m)
    assert stats["completed"] == len(sizes) and stats["errors"] == 0
    assert stats["compile_count"] == warm
    assert stats["steady_state_recompiles"] == 0
    assert 0 < stats["steps_overlapped"] < stats["ticks"]
    # every token but each request's first (its prefill's) came from a step
    assert stats["slot_ticks"] == stats["tokens_generated"] - len(sizes)


# ---------------------------------------------------------------------------
# (b) EOS on the token before the one in flight
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["plain", "grouped"])
def test_eos_while_the_next_step_is_in_flight(models, monkeypatch, kind):
    """The step after an EOS token is already on the device when the host
    sees the EOS: the request completes there and then, the stale row is
    dropped, the pages come back, and the slot's next request is exact."""
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    nxt = _prompt(kind, 6, 4)
    with _engine(models, kind, num_slots=1) as eng:
        eng.warmup()
        # a prompt whose run holds a token (from the third on, not the last
        # few) that did not occur before it: that token is the EOS
        for seed in range(40):
            prompt = _prompt(kind, 9, seed)
            free_run = eng.generate(prompt, 16, timeout=300)
            k = next((i for i in range(2, 12)
                      if free_run[i] not in free_run[:i]), None)
            if k is not None:
                break
        eos = int(free_run[k])
        dropped = []
        retire = eng._retire_step

        def spy(rec):
            dropped.extend(r for _s, r in rec.active if r.future.done())
            return retire(rec)

        eng._retire_step = spy
        a = eng.submit(prompt, 16, eos_id=eos)
        b = eng.submit(nxt, 7)                # waits for the one slot
        got, after = a.result(timeout=300), b.result(timeout=300)
        _idle(eng)
        stats = eng.stats()
    np.testing.assert_array_equal(got, free_run[:k + 1])
    _assert_served(models, kind, prompt, got, 16, eos_id=eos)
    _assert_served(models, kind, nxt, after, 7)
    assert len(dropped) == 1      # the row dispatched past the EOS token
    assert stats["kvcache"]["pages_in_use"] == 0
    assert stats["steady_state_recompiles"] == 0 and stats["errors"] == 0


# ---------------------------------------------------------------------------
# (c) timeout, step fault, fetch fault: with a step in flight
# ---------------------------------------------------------------------------
def test_deadline_eviction_with_a_step_in_flight(models, monkeypatch):
    _slow_fetch(monkeypatch, 0.004)
    kind = "plain"
    pa, pb, pc = (_prompt(kind, 5, s) for s in (21, 22, 23))
    with _engine(models, kind, num_slots=2, max_seq_len=64) as eng:
        eng.warmup()
        doomed = eng.submit(pa, 50, timeout_ms=60)    # >= 200 ms of steps
        kept = eng.submit(pb, 20)
        with pytest.raises(serving.RequestTimeoutError, match="mid-decode"):
            doomed.result(timeout=120)
        late = eng.submit(pc, 6)                      # takes the freed slot
        _assert_served(models, kind, pb, kept.result(timeout=120), 20)
        _assert_served(models, kind, pc, late.result(timeout=120), 6)
        _idle(eng)
        stats = eng.stats()
    assert stats["deadline_evictions"] == 1 and stats["evictions"] == 0
    assert stats["steady_state_recompiles"] == 0
    assert stats["steps_overlapped"] > 0


def test_injected_step_fault_with_a_step_in_flight(models, monkeypatch):
    """The 4th dispatch fails with the 3rd un-fetched: exactly the sequences
    in flight fail, the un-fetched step is forgotten (none of its tokens
    reaches a later request of the same slot), the engine serves on."""
    _slow_fetch(monkeypatch, 0.002)
    kind = "plain"
    with _engine(models, kind, num_slots=2,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        eng.warmup()
        with chaos.active("seed=1,site=serving.decode,at=4"):
            futs = [eng.submit(_prompt(kind, 4, 30 + i), 12)
                    for i in range(2)]
            for f in futs:
                with pytest.raises(chaos.FaultInjected):
                    f.result(timeout=120)
        _idle(eng)
        mid = eng.stats()
        assert mid["evictions"] == 2 and mid["ticks"] == 2
        assert mid["kvcache"]["pages_in_use"] == 0
        after = [(_prompt(kind, 3, 40 + i), 5 + i) for i in range(4)]
        for (p, m), f in zip(after, [eng.submit(p, m) for p, m in after]):
            _assert_served(models, kind, p, f.result(timeout=120), m)
        _idle(eng)
        stats = eng.stats()
    assert stats["completed"] == 4 and stats["evictions"] == 2
    assert stats["steady_state_recompiles"] == 0


def test_failed_fetch_with_a_step_in_flight(models, monkeypatch):
    """The fetch of step 3 fails while step 4 is on the device: both are
    given up, the sequences in flight fail, nothing else does."""
    kind = "plain"
    real = decode_mod.fetch_host
    steps = []

    def fetch(xs, *a, **k):
        if np.shape(xs[0]) == (2,):       # a step's tokens, not a prefill's
            steps.append(1)
            if len(steps) == 3:
                raise RuntimeError("wedged transfer")
        return real(xs, *a, **k)

    monkeypatch.setattr(decode_mod, "fetch_host", fetch)
    with _engine(models, kind, num_slots=2) as eng:
        eng.warmup()
        futs = [eng.submit(_prompt(kind, 4, 50 + i), 12) for i in range(2)]
        for f in futs:
            with pytest.raises(RuntimeError, match="wedged"):
                f.result(timeout=120)
        _idle(eng)
        assert eng.stats()["evictions"] == 2
        p = _prompt(kind, 7, 60)
        _assert_served(models, kind, p, eng.generate(p, 9, timeout=120), 9)
        stats = eng.stats()
    assert stats["completed"] == 1
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# (d) close, with a step in flight
# ---------------------------------------------------------------------------
def test_close_drain_true_retires_the_step_in_flight(models, monkeypatch):
    _slow_fetch(monkeypatch, 0.002)
    kind = "plain"
    reqs = [(_prompt(kind, 3 + i, 70 + i), 6 + 2 * i) for i in range(6)]
    eng = _engine(models, kind, num_slots=2)
    eng.warmup()
    futs = [eng.submit(p, m) for p, m in reqs]
    _wait_overlapped(eng, 2)
    assert eng.close(drain=True, timeout=120) == len(reqs)
    assert not eng._thread.is_alive() and eng._inflight is None
    for (p, m), f in zip(reqs, futs):
        _assert_served(models, kind, p, f.result(timeout=0), m)
    assert eng.kvcache_stats()["pages_in_use"] == 0


def test_close_drain_false_gives_up_the_step_in_flight(models, monkeypatch):
    _slow_fetch(monkeypatch, 0.002)
    kind = "plain"
    eng = _engine(models, kind, num_slots=2, max_seq_len=64)
    eng.warmup()
    futs = [eng.submit(_prompt(kind, 4, 80 + i), 50) for i in range(3)]
    _wait_overlapped(eng, 2)
    assert eng.close(drain=False, timeout=120) == 0
    assert not eng._thread.is_alive() and eng._inflight is None
    for f in futs:
        with pytest.raises(serving.ServerClosedError):
            f.result(timeout=0)
    assert eng.kvcache_stats()["pages_in_use"] == 0
    assert eng.stats()["steady_state_recompiles"] == 0


# ---------------------------------------------------------------------------
# (e) one signature: host-fed, device-fed, mixed, after an eviction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["plain", "grouped"])
def test_one_step_executable_for_every_mix_of_rows(models, monkeypatch,
                                                   kind):
    _slow_fetch(monkeypatch, 0.002)
    mixes = []
    with _engine(models, kind, num_slots=2,
                 retry_policy=RetryPolicy(max_attempts=1)) as eng:
        warm = eng.warmup()
        pack = eng._pack_step

        def spy(active):
            packed, drafts = pack(active)
            fed = {int(packed[-1, slot]) for slot, _r in active}
            mixes.append("mixed" if len(fed) == 2
                         else "device" if fed == {1} else "host")
            assert packed[-1].sum() == sum(
                int(packed[-1, slot]) for slot, _r in active)
            return packed, drafts

        eng._pack_step = spy
        pa, pb = _prompt(kind, 6, 90), _prompt(kind, 11, 91)
        a = eng.submit(pa, 14)
        _wait_overlapped(eng, 2)              # a alone: host-fed, device-fed
        assert eng.compile_count == warm
        b = eng.submit(pb, 6)                 # b's first step beside a's nth
        _assert_served(models, kind, pa, a.result(timeout=300), 14)
        _assert_served(models, kind, pb, b.result(timeout=300), 6)
        assert eng.compile_count == warm
        with chaos.active("seed=1,site=serving.decode,at=3"):
            c = eng.submit(pa, 10)
            with pytest.raises(chaos.FaultInjected):
                c.result(timeout=300)
        _idle(eng)
        # the first step after an eviction and reset_pools()
        _assert_served(models, kind, pb, eng.generate(pb, 5, timeout=300), 5)
        stats = eng.stats()
    assert {"host", "device", "mixed"} <= set(mixes)
    assert stats["compile_count"] == warm
    assert stats["steady_state_recompiles"] == 0


def _placed(params, placement):
    """The weights where a caller put them: committed to one device that is
    not the default, or sharded over two (the first leaf is a matrix: its
    spec fits no vector)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if placement == "one_device":
        return jax.device_put(params, jax.devices()[3])
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    placed = jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, NamedSharding(
            mesh, P(None, "x") if leaf.ndim == 2 else P())), params)
    assert jax.tree_util.tree_leaves(placed)[0].ndim == 2
    return placed


@pytest.mark.parametrize("placement", ["one_device", "sharded"])
def test_stand_in_for_no_previous_step_follows_the_weights(models,
                                                           monkeypatch,
                                                           placement):
    """The "no step before" operand is placed as the step places its
    output, whatever the weights' sharding: host-fed and device-fed steps
    run the executable the first step ran."""
    _slow_fetch(monkeypatch, 0.002)
    model, params = models["plain"]
    placed = _placed(params, placement)
    with serving.DecodeEngine(
            model, placed, num_slots=2, max_seq_len=48, timeout_ms=0,
            prefill_buckets=(8, 16), prefix_cache=False,
            name="ov-" + placement) as eng:
        warm = eng.warmup()
        pa, pb = _prompt("plain", 6, 95), _prompt("plain", 11, 96)
        _assert_served(models, "plain", pa,
                       eng.generate(pa, 8, timeout=300), 8)
        served = eng.compile_count
        a = eng.submit(pa, 14)
        _wait_overlapped(eng, 2)
        b = eng.submit(pb, 6)                 # host-fed beside device-fed
        _assert_served(models, "plain", pa, a.result(timeout=300), 14)
        _assert_served(models, "plain", pb, b.result(timeout=300), 6)
        _idle(eng)
        assert eng.compile_count == served
        if placement == "sharded":
            # warmup() asked the step: nothing compiles after it
            assert served == warm


# ---------------------------------------------------------------------------
# (f) with a draft in play: today's order through the same halves
# ---------------------------------------------------------------------------
def test_speculation_retires_each_step_before_the_next_dispatch(models):
    kind = "plain"
    model, params = models[kind]
    rng = np.random.RandomState(5)
    reqs = []
    for i in range(6):
        motif = rng.randint(1, 32, 3 + i % 3)
        reqs.append((np.tile(motif, 6)[:10 + i].astype(np.int32), 12))
    order = []
    with _engine(models, kind, num_slots=3, spec_k=3,
                 spec_draft="prompt_lookup") as eng:
        eng.warmup()
        dispatch, retire = eng._dispatch_step, eng._retire_step
        eng._dispatch_step = lambda act: order.append("d") or dispatch(act)
        eng._retire_step = lambda rec: order.append("r") or retire(rec)
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, m) for p, m in reqs]]
        _idle(eng)
        stats = eng.stats()
    for (p, m), out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, model.reference_generate(params, p, m))
    assert order and order == ["d", "r"] * (len(order) // 2)
    assert stats["steps_overlapped"] == 0
    assert stats["speculative"]["accepted_per_tick"] > 1.0
    assert stats["steady_state_recompiles"] == 0


# ---------------------------------------------------------------------------
# (g) the order itself
# ---------------------------------------------------------------------------
def test_step_n_plus_1_is_dispatched_before_step_n_is_fetched(models,
                                                             monkeypatch):
    kind = "plain"
    log, lock = [], threading.Lock()
    real = decode_mod.fetch_host

    def fetch(xs, *a, **k):
        with lock:
            log.append(("fetch", id(xs[0]), time.perf_counter()))
        time.sleep(0.003)
        return real(xs, *a, **k)

    monkeypatch.setattr(decode_mod, "fetch_host", fetch)
    p = _prompt(kind, 5, 7)
    with _engine(models, kind, num_slots=2) as eng:
        eng.warmup()
        dispatch = eng._dispatch_step

        def spy(active):
            rec = dispatch(active)
            with lock:
                log.append(("dispatched", id(rec.out), time.perf_counter()))
            return rec

        eng._dispatch_step = spy
        out = eng.generate(p, 10, timeout=120)
        _idle(eng)
        stats = eng.stats()
    _assert_served(models, kind, p, out, 10)
    steps = [i for kind_, i, _t in log if kind_ == "dispatched"]
    at = {(kind_, i): t for kind_, i, t in log}
    assert len(steps) == 9 == stats["ticks"]      # the prefill gave one
    # step n+1 was on the device before the host began to fetch step n
    for n, nxt in zip(steps, steps[1:]):
        assert at[("dispatched", nxt)] < at[("fetch", n)]
    assert stats["steps_overlapped"] == len(steps) - 1
    assert decode_mod._T_OVERLAPPED.value(server=eng.name) == len(steps) - 1


# ---------------------------------------------------------------------------
# (h) a weight swap between two overlapped steps
# ---------------------------------------------------------------------------
def test_swap_params_between_two_overlapped_steps(models, monkeypatch):
    import jax

    _slow_fetch(monkeypatch, 0.002)
    kind = "plain"
    model, params = models[kind]
    same = jax.tree_util.tree_map(lambda x: x + 0, params)   # new arrays
    other = model.init_params(3)
    pa, pb = _prompt(kind, 6, 101), _prompt(kind, 4, 102)
    with _engine(models, kind, num_slots=2, max_seq_len=64) as eng:
        eng.warmup()
        a = eng.submit(pa, 30)
        _wait_overlapped(eng, 3)
        eng.swap_params(same, timeout=60)     # lands with a step in flight
        assert not a.done()
        _wait_overlapped(eng, 2)
        _assert_served(models, kind, pa, a.result(timeout=120), 30)
        b = eng.submit(pa, 30)
        _wait_overlapped(eng, 3)
        eng.swap_params(other, timeout=60)
        assert len(b.result(timeout=120)) == 30       # nothing dropped
        np.testing.assert_array_equal(
            eng.generate(pb, 8, timeout=120),
            model.reference_generate(other, pb, 8))
        _idle(eng)
        stats = eng.stats()
    assert stats["weight_swaps"] == 2 and stats["errors"] == 0
    assert stats["steady_state_recompiles"] == 0
