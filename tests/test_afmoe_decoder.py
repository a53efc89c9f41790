"""``serving.AfmoeDecoder`` through ``DecodeEngine`` and both cache groups
against the repo's plain reference (``serving/afmoe_reference.py``, float32
``highest``), at a tiny size that keeps the shape of the thing: 12 query / 2
kv heads, window 32 with sequences to 100, 8-token pages (a ring of 5 pages
= 40 tokens, so a 100-token sequence wraps it twice), 16 experts top-4 of
which 4 are held, 5 layers ``s | s, s, s, f``.

Tolerances. ``LOGIT_TOL`` 2e-4 on logits of standard deviation about 1: both
sides are float32; the program reads its K/V back through pages, sums a
token's picks in another order and (prefill) pads to a rung — rounding
differences of order 1e-6 a layer. A run whose activations are rounded to
bfloat16 at every norm misses it by two orders of magnitude (asserted). The
engine's greedy tokens are compared by where the reference puts them: a
served token's reference logit may lie at most ``GAP_TOL`` = 1e-3 row standard
deviations below the reference's best (random weights put near-ties in a
96-way argmax; a flipped near-tie is rounding, a wrong cache row is not).
"""
import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving import afmoe_reference as ref
from mxnet_tpu.serving import decode as decode_mod
from mxnet_tpu.serving.kvcache import (OutOfPagesError, RingKVCache,
                                       make_cache)

LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
WINDOW, PAGE, VOCAB = 32, 8, 96
TINY = dict(vocab_size=VOCAB, hidden_size=48, num_attention_heads=12,
            num_key_value_heads=2, head_dim=8, intermediate_size=96,
            moe_intermediate_size=32,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
            sliding_window=WINDOW, held_experts=[4, 4], route_scale=2.448,
            mup_enabled=True)


@pytest.fixture(scope="module")
def tiny():
    model = serving.AfmoeDecoder(**TINY)
    return model, model.init_params(0)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_buckets", (16, 64))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("prefill_chunk", 0)
    kw.setdefault("name", "af%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, n).astype(np.int32)


def _direct(model, params, prompt, steps, rung=64):
    """Prefill then ``steps`` teacher-forced decode steps through a
    the model's cache, as the engine drives them; the logits of every
    position from the prompt's last on."""
    cache = make_cache(model, 2, 128, page_size=PAGE)
    slot, p = 1, prompt.size
    seq = np.concatenate([prompt, _prompt(steps, 99)])
    cache.reserve(slot, p + steps)
    pages, offs = cache.write_slots(slot, 0, p)
    pad = rung - p
    tokens = np.concatenate([prompt, np.zeros(pad, np.int32)])
    wp = tuple(jnp.asarray(np.concatenate([g, np.zeros(pad, np.int32)]))
               for g in pages)
    wo = jnp.asarray(np.concatenate([offs, np.zeros(pad, np.int32)]))
    out = model.prefill(params, jnp.asarray(tokens), jnp.asarray(p),
                        *cache.operands, wp, wo)
    logits = [np.asarray(out[0])]
    pools, state = out[1], out[2]
    tables = tuple(jnp.asarray(t) for _v, t in cache.tables)
    for i in range(steps):
        pos = p + i
        rows = np.zeros((2,), np.int32)
        toks, poss, lens = rows.copy(), rows.copy(), rows.copy()
        toks[slot], poss[slot], lens[slot] = seq[pos], pos, pos + 1
        pages, offs = cache.write_slots(slot, pos, 1)
        wp = tuple(jnp.asarray(np.where(np.arange(2) == slot, g[0], 0)
                               .astype(np.int32)) for g in pages)
        wo = jnp.asarray(np.where(np.arange(2) == slot, offs[0], 0)
                         .astype(np.int32))
        out = model.decode(params, jnp.asarray(toks), jnp.asarray(poss),
                           pools, state, tables, jnp.asarray(lens), wp, wo)
        logits.append(np.asarray(out[0])[slot])
        pools, state = out[1], out[2]
    return seq, np.stack(logits)


@pytest.mark.parametrize("p,steps", [(5, 30), (30, 12), (47, 60)])
def test_prefill_then_decode_logits_equal_the_reference(tiny, p, steps):
    """Across the window edge (position 32) and the ring's wrap (position
    40, and again at 80): logits, not tokens."""
    model, params = tiny
    seq, got = _direct(model, params, _prompt(p, p), steps)
    want = np.asarray(ref.forward_logits(model.cfg, params, seq))[p - 1:]
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want[:got.shape[0]], atol=LOGIT_TOL)


def test_bfloat16_activations_miss_the_logit_tolerance(tiny, monkeypatch):
    model, params = tiny
    rms = serving.AfmoeDecoder._rms
    monkeypatch.setattr(
        serving.AfmoeDecoder, "_rms", lambda self, x, g:
        rms(self, x, g).astype(jnp.bfloat16).astype(jnp.float32))
    seq, got = _direct(model, params, _prompt(30, 30), 12)
    want = np.asarray(ref.forward_logits(model.cfg, params, seq))[29:]
    assert np.abs(got - want[:got.shape[0]]).max() > 50 * LOGIT_TOL


def _gap_sd(model, params, prompt, out):
    seq = np.concatenate([prompt, out[:-1]])
    rows = np.asarray(ref.forward_logits(model.cfg, params, seq))
    rows = rows[prompt.size - 1:]
    got = rows[np.arange(out.size), out]
    return float(((rows.max(-1) - got) / rows.std(-1)).max())


def test_engine_serves_what_the_reference_puts_first_under_churn(
        tiny, monkeypatch):
    """More requests than slots, prompts on both sides of the window and
    past the ring, pages audited at every mutation and every tick: every
    served token is the reference's best (to a near-tie), the window group
    never holds more than its ring a slot, both groups free to zero, and
    nothing compiles after warm-up."""
    monkeypatch.setenv("MXNET_KVCACHE_AUDIT", "1")
    model, params = tiny
    sizes = [(5, 20), (40, 24), (70, 30), (100, 28), (12, 9), (33, 40),
             (64, 8), (90, 12)]
    ring_pages = WINDOW // PAGE + 1
    with _engine(tiny) as eng:
        assert all(group.audit for group in eng._cache.groups)
        assert eng.warmup() == 4      # the step and three prefill rungs
        prompts = [_prompt(n, i) for i, (n, _m) in enumerate(sizes)]
        futs = [eng.submit(p, m) for p, (_n, m) in zip(prompts, sizes)]
        peak = 0
        while not all(f.done() for f in futs):
            _full, win = eng._cache.groups
            assert max(win.pages_owned(s) for s in range(3)) <= ring_pages
            peak = max(peak, eng.kvcache_stats()["window"]["pages_in_use"])
            time.sleep(0.01)
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
        eng._cache.audit_check()
    assert 0 < peak <= 3 * ring_pages
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    assert stats["kvcache"]["window"]["pages_in_use"] == 0
    assert stats["kvcache"]["window"]["pages_capacity"] == 3 * ring_pages
    for prompt, out, (_n, m) in zip(prompts, outs, sizes):
        assert out.size == m
        assert _gap_sd(model, params, prompt, out) <= GAP_TOL
    # the expert-load counters: every (token, pick) row of every expert
    # layer is either held here or absent
    tokens = sum(n + m - 1 for n, m in sizes)
    moe = stats["moe"]
    assert moe["expert_layers"] == 4 and moe["experts_held"] == 4
    assert moe["rows_held"] + moe["rows_absent"] == tokens * 4 * 4
    assert moe["rows_held"] == sum(map(sum, moe["rows_by_expert"]))
    assert moe["load_max_over_mean"] >= 1.0
    assert "mxnet_moe_expert_load_max_over_mean" in \
        telemetry.render_prometheus()
    assert decode_mod._T_MOE_ROWS.value(server=eng.name, where="held") \
        == moe["rows_held"]
    assert decode_mod._T_MOE_ROWS.value(server=eng.name, where="absent") \
        == moe["rows_absent"]


def test_one_put_and_one_fetch_a_steady_tick(tiny, monkeypatch):
    """The counters ride the tick's one fetch and both groups' write pages
    its one packed operand."""
    fetches, puts = [], []
    real_fetch = decode_mod.fetch_host
    monkeypatch.setattr(decode_mod, "fetch_host",
                        lambda xs: fetches.append(len(xs)) or real_fetch(xs))
    with _engine(tiny, num_slots=2) as eng:
        eng.warmup()
        real_asarray = eng._jnp.asarray

        class Counting:
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def asarray(x, *a, **k):
                puts.append(np.shape(x))
                return real_asarray(x, *a, **k)

        eng._jnp = Counting()
        out = eng.generate(_prompt(20, 3), 16, timeout=300)
        stats = eng.stats()
    assert out.size == 16 and stats["ticks"] == 15
    assert fetches == [1] * 16          # one prefill, fifteen ticks
    # a tick puts its (7, slots) operand (`from_prev` last) and nothing
    # else: the tables were put once each after the admission, the prefill
    # put two operands
    assert puts.count((7, 2)) == 15
    assert len(puts) == 15 + 2 + 2


def test_ring_cache_reserves_a_ring_and_writes_the_last_window():
    ring = RingKVCache(2, 128, 4, 2, 8, WINDOW, page_size=PAGE)
    assert ring.max_pages == 5 and ring.ring_tokens == 40
    assert ring.num_pages == 2 * 5 + 1
    ring.reserve(0, 20)
    assert ring.pages_owned(0) == 3 and ring.reserved_tokens(0) == 24
    with pytest.raises(MXNetError):
        ring.write_slots(0, 20, 8)            # past a short reservation
    ring.reserve(0, 128)                      # grows to the ring, no more
    assert ring.pages_owned(0) == 5 and ring.reserved_tokens(0) == 128
    with pytest.raises(MXNetError):
        ring.reserve(0, 129)
    row = ring.page_table[0].copy()
    assert len(set(row)) == 5 and 0 not in row
    # a 100-token prompt: blocks 0..12, the last 5 (8..12) are written,
    # each into column block % 5; earlier rows go to the null page
    pages, offs = ring.write_slots(0, 0, 100)
    block = np.arange(100) // PAGE
    assert not pages[block <= 7].any()
    assert np.array_equal(pages[block > 7], row[block[block > 7] % 5])
    assert np.array_equal(offs, np.arange(100) % PAGE)
    assert ring.page_at(0, 100) == row[(100 // PAGE) % 5]
    ring.reserve(1, 40)
    small = RingKVCache(3, 128, 4, 2, 8, WINDOW, page_size=PAGE, num_pages=8)
    small.reserve(0, 128)                     # 5 of its 7 pages
    assert small.can_admit(16) and not small.can_admit(17)
    with pytest.raises(OutOfPagesError):
        small.reserve(1, 24)
    assert small.pages_owned(1) == 0
    ring.free(0)
    ring.free(1)
    assert ring.pages_in_use == 0
    ring.audit_check()
    with pytest.raises(MXNetError):
        RingKVCache(2, 128, 4, 2, 8, 30, page_size=PAGE)   # 30 % 8
    with pytest.raises(MXNetError):
        RingKVCache(2, 128, 4, 2, 8, WINDOW, page_size=PAGE, num_pages=4)


def test_grouped_cache_takes_pages_in_both_groups_or_in_neither(tiny):
    model, _params = tiny
    cache = make_cache(model, 3, 128, page_size=PAGE,
                       num_pages={"full": 40, "window": 8})
    full, window = cache.groups
    (full_k, full_v), (window_k, window_v) = cache.operands[0]
    assert [x.shape for x in full_k] == [(40, PAGE, 2, 8)]
    assert [x.shape for x in window_v] == [(8, PAGE, 2, 8)] * 4
    assert cache.operands[1] == ()
    cache.reserve(0, 100)
    assert full.pages_owned(0) == 13
    assert window.pages_owned(0) == 5
    assert cache.can_admit_prefix(16) and not cache.can_admit_prefix(24)
    with pytest.raises(OutOfPagesError, match="window"):
        cache.reserve(1, 24)          # 3 window pages, 2 free
    assert full.pages_owned(1) == 0 and cache.pages_in_use == 13
    cache.reserve(1, 16)
    stats = cache.stats()
    assert stats["pages_in_use"] == 15 and stats["pages_capacity"] == 39
    assert stats["window"] == dict(stats["window"], pages_in_use=7,
                                   pages_capacity=7, window_tokens=WINDOW)
    cache.free(0)
    cache.free(1)
    cache.audit_check()
    assert cache.pages_in_use == 0 and window.pages_in_use == 0
    pages, offs = cache.null_write_slots(9)
    assert pages.shape == (2, 9) and not pages.any() and offs.max() == 7
    # a tick's write pages, one lookup a group: the ring's column wraps
    cache.reserve(2, 100)
    full_at, window_at = cache.page_lookups()
    assert [full_at(2, 3), full_at(2, 99)] \
        == [full.page_table[2, 0], full.page_table[2, 12]]
    assert [window_at(2, 3), window_at(2, 99)] \
        == [window.page_table[2, 0], window.page_table[2, 12 % 5]]


def test_engine_admission_waits_for_pages_of_the_window_group(tiny):
    """A window pool of two rings under three slots: the third long
    request is admitted only when a ring frees."""
    with _engine(tiny, num_pages={"full": 49, "window": 11}) as eng:
        eng.warmup()
        futs = [eng.submit(_prompt(60, i), 24) for i in range(3)]
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    assert all(o.size == 24 for o in outs)
    assert stats["kvcache"]["window"]["pages_capacity"] == 10
    assert stats["kvcache"]["window"]["pages_in_use"] == 0
    assert stats["steady_state_recompiles"] == 0


def test_a_grouped_model_is_refused_the_prefix_cache_chunks_and_drafts(tiny):
    model, params = tiny
    for kw in ({"prefix_cache": True}, {"prefill_chunk": 8}, {"spec_k": 2}):
        with pytest.raises(MXNetError, match=r"ring layers \(layer_state\)"):
            _engine(tiny, **kw)
    with pytest.raises(MXNetError, match="chunked prefill"):
        model.prefill_chunk(params, None, 0, 1, None, (), None, None, None)
    with pytest.raises(MXNetError):
        serving.AfmoeDecoder(**dict(TINY, layer_types=["full_attention"]))
    with pytest.raises(MXNetError):
        serving.AfmoeDecoder(**dict(TINY, held_experts=[14, 4]))


def _traced_events(tmp_path, work):
    """(name, arguments) of the ``mx.decode.*`` spans ``work()`` writes into
    a ``jax.profiler`` trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return [(ev.name, dict(ev.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("mx.decode.")]


def test_spans_of_a_grouped_model_carry_the_layers_arguments(tiny, tmp_path):
    """``mx.decode.commit`` and ``mx.decode.prefill`` carry what the
    benchmark's per-layer readers read, on the trace's own clock — on the
    overlapped path too: a step's commit runs a pass after its dispatch
    and still describes that step."""
    keys = {"moe_rows_held", "moe_experts_hit", "moe_load_max",
            "kv_rows_full", "kv_rows_window", "kv_window_pages",
            "kv_window_capacity"}
    walk = {"kv_cols_live", "kv_cols_grid",     # a decode tick's alone
            "kv_cols_walked", "kv_pool_leaves"}
    with _engine(tiny, num_slots=2) as eng:
        eng.warmup()
        events = _traced_events(tmp_path, lambda: (
            eng.generate(_prompt(50, 5), 6, timeout=300), eng.close()))
        stats = eng.stats()
    prefill = [a for n, a in events if n == "mx.decode.prefill"]
    commits = [a for n, a in events if n == "mx.decode.commit"]
    assert len(prefill) == 1 and len(commits) == 5
    # the worker's other spans, by the names the benchmark reads them by:
    # five steps, the first host-fed, four dispatched over an un-fetched one
    for name in ("mx.decode.pack", "mx.decode.dispatch", "mx.decode.fetch"):
        assert sum(n == name for n, _a in events) == 5, name
    assert [a for n, a in events if n == "mx.decode.dispatch"] \
        == [{"overlapped": 0}] + [{"overlapped": 1}] * 4
    assert stats["steps_overlapped"] == 4 and stats["ticks"] == 5
    assert all(set(a) == {"active", "prefilling", "queued"}
               for n, a in events if n == "mx.decode.tick" and a)
    assert set(prefill[0]) == keys | {"rung", "held", "attn_blocks_rung",
                                      "attn_blocks_live", "rows_rung",
                                      "rows_computed"}
    assert prefill[0]["held"] == 0          # nobody was decoding yet
    # ... and one block of rows for the row-wise passes, computed whole
    assert (prefill[0]["rows_rung"], prefill[0]["rows_computed"]) == (64, 64)
    # 50 tokens on the rung of 64: one block of 64 rows a layer, all live
    assert prefill[0]["attn_blocks_rung"] == 5
    assert prefill[0]["attn_blocks_live"] == 5
    assert prefill[0]["kv_rows_full"] == 50
    assert prefill[0]["kv_rows_window"] == WINDOW
    for i, args in enumerate(commits):
        assert set(args) == keys | walk
        assert args["kv_rows_full"] == 51 + i
        assert args["kv_rows_window"] == WINDOW
        assert args["kv_window_pages"] == 5
        assert args["kv_window_capacity"] == 10
        assert args["kv_pool_leaves"] == 2 * len(TINY["layer_types"])
        assert 0 <= args["moe_load_max"] <= args["moe_rows_held"] <= 16


# ---------------------------------------------------------------------------
# the prefill's blocked attention: what it multiplies, and what is counted
# ---------------------------------------------------------------------------
def _band_live_pairs(t, window, length, block=pk._BAND_BLOCK):
    """(query block, kv block) pairs a kv head's launch of the band kernel
    multiplies (interpret mode), counted from outside: with kv block ``kb``'s
    values NaN, the query blocks that come back NaN are those that
    multiplied it (a masked column still reads ``0 * NaN``); a block that
    was not launched comes back zeros."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(t, n, 8).astype(np.float32))
               for n in (6, 1, 1))
    block = min(block, t)
    run = jax.jit(lambda vv, n: pk.band_attention(
        q, k, vv, window=window, block=block, interpret=True, length=n))
    pairs = 0
    for kb in range(-(-t // block)):
        rows = jnp.arange(t)[:, None, None] // block == kb
        out = np.asarray(run(jnp.where(rows, jnp.nan, v),
                             jnp.asarray(length, jnp.int32)))
        out = np.pad(out, ((0, -t % block), (0, 0), (0, 0)))
        pairs += int(np.isnan(out.reshape(-1, block * 6 * 8)).any(1).sum())
    return pairs


@pytest.mark.parametrize("length", [200, 130, 65, 17])
@pytest.mark.parametrize("window", [0, 72])
def test_band_blocks_counts_the_kernels_live_steps(window, length):
    """``band_blocks`` — the arithmetic behind ``attn_blocks_live`` — against
    the kernel itself, 200 rows in thirteen blocks of 16, a window that cuts
    one."""
    assert _band_live_pairs(200, window, length, block=16) \
        == pk.band_blocks(length, 200, window, block=16)
    assert pk.band_blocks(200, 200, window, block=16) \
        == pk.band_blocks(200, window=window, block=16) \
        >= pk.band_blocks(length, 200, window, block=16)


def test_the_spans_block_pairs_are_the_kernels_live_steps(tiny, tmp_path):
    """A prompt of 200 tokens on the rung of 512: ``attn_blocks_live`` of
    its ``mx.decode.prefill`` span is the live steps of the five launches
    (four over the window, one over every key), ``attn_blocks_rung`` what
    the whole rung takes; ``stats()`` and the Prometheus counter carry the
    same pair, summed over the engine's prefills since it started."""
    model, _params = tiny
    with _engine(tiny, num_slots=2, max_seq_len=640,
                 prefill_buckets=(64, 512)) as eng:
        eng.warmup()
        before = eng.stats()
        events = _traced_events(tmp_path, lambda: (
            eng.generate(_prompt(200, 1), 3, timeout=300),
            eng.generate(_prompt(40, 2), 3, timeout=300), eng.close()))
        stats = eng.stats()
    live = 4 * _band_live_pairs(512, WINDOW, 200) \
        + _band_live_pairs(512, 0, 200)
    rung = 4 * _band_live_pairs(512, WINDOW, 512) \
        + _band_live_pairs(512, 0, 512)
    assert (live, rung) == (5, 15)
    assert [(a["rung"], a["attn_blocks_live"], a["attn_blocks_rung"])
            for n, a in events if n == "mx.decode.prefill"] \
        == [(512, live, rung), (64, 5, 5)]
    assert model.prefill_attn_blocks(200, 512) == live
    for kind, n in (("live", live + 5), ("rung", rung + 5)):
        key = "attn_blocks_" + kind
        assert stats[key] - before[key] == n
        assert decode_mod._T_PREFILL_ATTN_BLOCKS.value(
            server=eng.name, kind=kind) == stats[key]
    assert "mxnet_decode_prefill_attn_blocks_total" \
        in telemetry.render_prometheus()
    # the row-wise passes: 200 tokens reach one block of 256 rows of the
    # rung of 512, 40 tokens the one block the rung of 64 is
    assert [(a["rows_rung"], a["rows_computed"])
            for n, a in events if n == "mx.decode.prefill"] \
        == [(512, model.prefill_rows(200, 512)), (64, 64)] \
        == [(512, 256), (64, 64)]
    for kind, n in (("rung", 512 + 64), ("computed", 256 + 64)):
        key = "prefill_rows_" + kind
        assert stats[key] - before[key] == n
        assert decode_mod._T_PREFILL_ROWS.value(
            server=eng.name, kind=kind) == stats[key]
    assert "mxnet_decode_prefill_rows_total" in telemetry.render_prometheus()


def test_engine_serves_the_same_tokens_with_the_band_kernel_on_the_path(
        tiny, monkeypatch):
    """Off the TPU ``band_attention`` is its dense reference; with the
    kernel itself (interpret mode, the prompt's traced ``length``, the
    padding's query blocks zeros) under ``AfmoeDecoder.prefill`` the engine
    serves the tokens it serves without, each the reference's best."""
    model, params = tiny
    sizes = [(5, 6), (40, 8), (70, 8), (100, 6)]
    prompts = [_prompt(n, i) for i, (n, _m) in enumerate(sizes)]

    def serve():
        with _engine(tiny) as eng:
            eng.warmup()
            return [eng.generate(p, m, timeout=300)
                    for p, (_n, m) in zip(prompts, sizes)]

    plain = serve()
    calls = []
    real = pk.band_attention

    def through_the_kernel(q, k, v, **kw):
        calls.append(kw["length"])
        return real(q, k, v, block=16, interpret=True, **kw)

    monkeypatch.setattr(pk, "band_attention", through_the_kernel)
    served = serve()
    assert calls and all(n is not None for n in calls)
    for prompt, got, want in zip(prompts, served, plain):
        assert list(got) == list(want)
        assert _gap_sd(model, params, prompt, got) <= GAP_TOL
