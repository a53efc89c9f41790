"""``serving.LingDecoder`` through ``DecodeEngine``, its latent pool and its
per-slot state against the benchmark's plain reference
(``benchmark/reference/ling_share.py``: float32 ``highest``, a serial scan,
expanded latent attention), at a tiny size that keeps the shape of the thing:
4 heads of 8, a latent row of 16 + 4, 8-token pages, 16 experts in 4 groups
(2 groups and 4 experts a token) of which 8 are held, 4 layers ``kda | kda,
kda, mla``. The recurrence's chunk is 32 tokens (``ops.kda.CHUNK``), so the
prompts below end inside a chunk, on its edge and behind it, and inside and
on the edge of a page.

Tolerances as in ``tests/test_afmoe_decoder.py``: ``LOGIT_TOL`` 2e-4 on logits
of standard deviation about 1 (both sides float32; the program solves a
chunk as a triangular system, reads its latent rows back through pages and
absorbs the latent expansion into the query), ``GAP_TOL`` 1e-3 row standard
deviations on where the reference puts a served token.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import kda, moe
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving import decode as decode_mod
from mxnet_tpu.serving import kvcache

LOGIT_TOL = 2e-4
GAP_TOL = 1e-3
PAGE, VOCAB = 8, 96
TINY = dict(vocab_size=VOCAB, hidden_size=48, num_attention_heads=4,
            head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, intermediate_size=96,
            moe_intermediate_size=32,
            layer_types=["kda", "kda", "kda", "mla"], num_dense_layers=1,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            held_experts=[0, 8], routed_scaling_factor=2.5, rope_theta=6e6)
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def ref():
    """``benchmark/reference/ling_share.py`` (it imports ``weights`` from
    ``benchmark/``, as under ``benchmark/run.py``)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "ling_share_under_test",
            os.path.join(BENCH, "reference", "ling_share.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    return mod


@pytest.fixture(scope="module")
def tiny():
    model = serving.LingDecoder(**TINY)
    return model, model.init_params(0)


def _cfg(model):
    return dict(model.cfg, param_dtype="float32")


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, n).astype(np.int32)


_REF_JITS = {}
REF_LEN, REF_ROWS = 160, 16     # one compile: sequences padded at the end


def _ref_logits(ref, model, params, seq, start, rows):
    """The reference's logits and near-ties at ``rows`` positions from
    ``start`` of the causal pass over ``seq`` (zeros behind it change
    nothing in front)."""
    assert rows <= REF_ROWS and start + REF_ROWS <= REF_LEN
    if id(model) not in _REF_JITS:
        cfg = _cfg(model)

        def fn(params, seq, start):
            with jax.default_matmul_precision("highest"):
                return ref.rows_logits(cfg, params, seq, start, REF_ROWS)

        _REF_JITS[id(model)] = jax.jit(fn)
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    logits, near = _REF_JITS[id(model)](params, jnp.asarray(padded), start)
    return np.asarray(logits)[:rows], np.asarray(near)[:rows]


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_buckets", (16, 64))
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("prefill_chunk", 0)
    kw.setdefault("name", "ling%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, params, **kw)


def _cache(model, slots=2):
    return kvcache.make_cache(model, slots, 128, page_size=PAGE)


def _prefill(model, params, cache, slot, prompt, rung, total):
    """One prompt through ``model.prefill`` as the engine drives it."""
    p = prompt.size
    cache.reserve(slot, total)
    pages, offs = cache.write_slots(slot, 0, p)
    tokens = np.zeros(rung, np.int32)
    tokens[:p] = prompt
    wp = np.zeros(rung, np.int32)
    (wp[:p],) = pages      # one group: one row of pages
    wo = np.concatenate([offs, cache.null_write_slots(rung - p)[1]])
    out = model.prefill(params, jnp.asarray(tokens), jnp.asarray(p, jnp.int32),
                        *cache.operands, jnp.asarray(wp),
                        jnp.asarray(wo), slot=jnp.asarray(slot, jnp.int32))
    cache.swap_pools(out[1], out[2])
    cache.seq_lens[slot] = p
    return np.asarray(out[0])


def _decode(model, params, cache, rows):
    """One decode tick: ``rows`` = {slot: (token, position)}; the other
    slots carry ``seq_len`` 0 and the null write page."""
    s = cache.num_slots
    tok = np.zeros(s, np.int32)
    pos = np.zeros(s, np.int32)
    lens = np.zeros(s, np.int32)
    wp = np.zeros(s, np.int32)
    wo = (np.arange(s) % PAGE).astype(np.int32)
    for slot, (t, at) in rows.items():
        tok[slot], pos[slot], lens[slot] = t, at, at + 1
        wp[slot] = cache.page_table[slot, at // PAGE]
        wo[slot] = at % PAGE
    out = model.decode(params, jnp.asarray(tok), jnp.asarray(pos),
                       *cache.operands,
                       jnp.asarray(cache.page_table), jnp.asarray(lens),
                       jnp.asarray(wp), jnp.asarray(wo))
    cache.swap_pools(out[1], out[2])
    return np.asarray(out[0])


# -- the recurrence ------------------------------------------------------

def _kda_inputs(t, heads=3, dk=16, dv=8, seed=0):
    rng = np.random.RandomState(seed)

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = l2(rng.randn(t, heads, dk)).astype(np.float32) * dk ** -0.5
    k = l2(rng.randn(t, heads, dk)).astype(np.float32)
    v = rng.randn(t, heads, dv).astype(np.float32)
    # decays over the whole of (-5, 0): 64 steps at -5 leave exp(-320)
    a = (-5.0 / (1.0 + np.exp(-3.0 * rng.randn(t, heads, dk)))
         ).astype(np.float32)
    b = (1.0 / (1.0 + np.exp(-rng.randn(t, heads)))).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, a, b))


@pytest.mark.parametrize("tokens,chunk", [(150, 16), (150, 32), (64, 64),
                                          (5, 32)])
def test_chunked_scan_is_the_serial_scan(tokens, chunk):
    args = _kda_inputs(tokens)
    want_o, want_s = kda.serial_scan(*args)
    got_o, got_s = kda.chunked_scan(*args, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


@pytest.mark.parametrize("length", [1, 31, 32, 100])
def test_a_rungs_padding_moves_neither_state_nor_output(length):
    """Rows with no decay and no update behind ``length``: the state is the
    state after ``length`` tokens, whatever the padding holds."""
    q, k, v, a, b = _kda_inputs(128, seed=1)
    live = jnp.arange(128) < length
    _o, got = kda.chunked_scan(q, k, v, jnp.where(live[:, None, None], a, 0),
                               jnp.where(live[:, None], b, 0))
    _o, want = kda.serial_scan(q[:length], k[:length], v[:length],
                               a[:length], b[:length])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_row_that_is_no_token_keeps_state_and_tail_bit_for_bit():
    q, k, v, a, b = _kda_inputs(4, seed=2)
    rng = np.random.RandomState(3)
    state = jnp.asarray(rng.randn(4, 3, 16, 8).astype(np.float32))
    valid = jnp.asarray([True, False, True, False])
    _o, new = kda.step(q, k, v, a, b, state, valid)
    new, state = np.asarray(new), np.asarray(state)
    assert np.array_equal(new[1], state[1]) and np.array_equal(new[3],
                                                               state[3])
    assert not np.array_equal(new[0], state[0])
    tail = jnp.asarray(rng.randn(4, 3, 5).astype(np.float32))
    x = jnp.asarray(rng.randn(4, 5).astype(np.float32))
    w = jnp.asarray(rng.randn(4, 5).astype(np.float32))
    _y, new_tail = kda.short_conv_step(x, tail, w, valid)
    assert np.array_equal(np.asarray(new_tail)[1], np.asarray(tail)[1])
    assert np.array_equal(np.asarray(new_tail)[0, -1], np.asarray(x)[0])


LIVE = {"none": [], "first": [0], "last": [4], "scattered": [1, 3],
        "all": [0, 1, 2, 3, 4]}


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("heads,dk,dv", [(3, 16, 8), (2, 128, 128)])
def test_state_kernel_in_interpret_mode_is_step_over_the_live_slots(
        heads, dk, dv, live):
    """``mx_kda_state`` against ``kda.step``: live rows' outputs and states
    to 1e-5, and a slot that holds no token — every slot of a tick with none
    — bit for bit what it was (the kernel never visits it)."""
    slots = 5
    q, k, v, a, b = _kda_inputs(slots, heads, dk, dv, seed=5)
    state = jnp.asarray(np.random.RandomState(6).randn(slots, heads, dk, dv)
                        .astype(np.float32))
    valid = np.zeros(slots, bool)
    valid[LIVE[live]] = True
    want_o, want_s = kda.step(q, k, v, a, b, state, jnp.asarray(valid))
    got_o, got_s = jax.jit(lambda *xs: pk.kda_state_step(
        *xs, interpret=True))(q, k, v, a, b, state, jnp.asarray(valid))
    got_o, got_s, state = (np.asarray(x) for x in (got_o, got_s, state))
    np.testing.assert_allclose(got_o[valid], np.asarray(want_o)[valid],
                               atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    assert np.array_equal(got_s[~valid], state[~valid])
    assert not got_o[~valid].any()
    if valid.any():
        assert not np.array_equal(got_s[valid], state[valid])
    # the walk: the live slots packed to the front, the last one repeated
    slot_of, count = (np.asarray(x) for x in pk.live_slots(
        jnp.asarray(valid)))
    assert count.tolist() == [valid.sum()] and slot_of.shape == (slots + 1,)
    assert slot_of[:valid.sum()].tolist() == LIVE[live]
    assert (slot_of[valid.sum():] == (LIVE[live] or [0])[-1]).all()


def test_short_conv_step_continues_short_conv():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(9, 6).astype(np.float32))
    w = jnp.asarray(rng.randn(4, 6).astype(np.float32))
    whole = kda.short_conv(x, w)
    for length in (1, 2, 5, 8):
        tail = kda.conv_tail(x, length, 4)
        y, _tail = kda.short_conv_step(x[length][None], tail[None], w)
        np.testing.assert_allclose(y[0], whole[length], atol=1e-6)


# -- the router and the shares ---------------------------------------------

def test_route_without_groups_is_bit_for_bit_todays():
    rng = np.random.RandomState(5)
    h = jnp.asarray(rng.randn(40, 48).astype(np.float32))
    wr = jnp.asarray(rng.randn(48, 16).astype(np.float32) * 48 ** -0.5)
    bias = jnp.asarray(rng.randn(16).astype(np.float32) * 0.01)

    def before(h, wr, expert_bias, top_k, route_norm=True, route_scale=1.0):
        # ops/moe.py route() as the parent commit has it
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), wr.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(scores + expert_bias.astype(jnp.float32),
                               top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        if route_norm:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * route_scale

    for fn in (lambda f: f, jax.jit):
        sel, w = fn(lambda *a: moe.route(*a, 4, True, 2.448))(h, wr, bias)
        sel0, w0 = fn(lambda *a: before(*a, 4, True, 2.448))(h, wr, bias)
        assert np.array_equal(sel, sel0) and np.array_equal(w, w0)
        sel, w = fn(lambda *a: moe.route(*a, 4, True, 2.448, n_group=1,
                                         topk_group=1))(h, wr, bias)
        assert np.array_equal(sel, sel0) and np.array_equal(w, w0)


def test_group_limited_route_picks_inside_the_kept_groups(ref, tiny):
    model, params = tiny
    layer = params["layers"][1]
    h = jnp.asarray(np.random.RandomState(6).randn(64, 48).astype(np.float32))
    sel, w = moe.route(h, layer["router"], layer["expert_bias"], 4, True,
                       2.5, n_group=4, topk_group=2)
    sel = np.asarray(sel)
    assert (np.array([len(set(r // 4)) for r in sel]) <= 2).all()
    want_sel, want_w, _near, _tight = ref._route(_cfg(model), layer, h)
    assert np.array_equal(sel, np.asarray(want_sel))
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-5)


def test_reference_leaves_out_what_a_tight_tie_carries(ref, tiny, monkeypatch):
    """A selection decided by less than ``TIGHT_TIE`` at one position: that
    position and the ``CARRY`` behind it are not compared (the recurrence
    hands a flipped token on), no other."""
    model, params = tiny
    seq = jnp.asarray(_prompt(60, 50))
    route = ref._route

    def tight_at_10(model, layer, hx, near_tie=ref.NEAR_TIE):
        sel, w, near, tight = route(model, layer, hx)
        at = jnp.arange(hx.shape[0]) == 10
        return sel, w, near & False, at

    monkeypatch.setattr(ref, "_route", tight_at_10)
    _x, left_out = ref.forward(_cfg(model), params, seq)
    assert np.nonzero(np.asarray(left_out))[0].tolist() == list(
        range(10, 10 + ref.CARRY + 1))
    assert ref.CARRY >= 8 and ref.TIGHT_TIE <= ref.NEAR_TIE

    # ... and only behind an expert layer that FEEDS a recurrence: the tiny
    # model's last kda layer is layer 2, so the expert layers of layers 2
    # and 3 (a latent layer and the head behind them) carry nothing
    calls = []

    def tight_in_the_last_two(model, layer, hx, near_tie=ref.NEAR_TIE):
        sel, w, near, tight = route(model, layer, hx)
        calls.append(len(calls))
        at = (jnp.arange(hx.shape[0]) == 10) & (len(calls) > 1)
        return sel, w, near & False, at

    monkeypatch.setattr(ref, "_route", tight_in_the_last_two)
    _x, left_out = ref.forward(_cfg(model), params, seq)
    assert len(calls) == 3 and not np.asarray(left_out).any()

    # the expert layer behind the latent attention takes the wider near-tie
    widths = []

    # of a row diluted among the n a position sees, from the sequence's
    # first tight tie on (here: position 10, the first expert layer)
    def spy(model, layer, hx, near_tie=ref.NEAR_TIE):
        widths.append(np.broadcast_to(np.asarray(near_tie), hx.shape[:1]))
        sel, w, near, tight = route(model, layer, hx, near_tie)
        at = (jnp.arange(hx.shape[0]) == 10) & (len(widths) == 1)
        return sel, w, near, at

    monkeypatch.setattr(ref, "_route", spy)
    ref.forward(_cfg(model), params, seq)
    assert all((w == ref.NEAR_TIE).all() for w in widths[:2])
    np.testing.assert_allclose(widths[2], np.where(
        np.arange(60) < 10, ref.NEAR_TIE, np.maximum(
            ref.NEAR_TIE, ref.DILUTE / np.arange(1, 61))), rtol=1e-6)


def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, tiny):
    """Each share: the experts it holds plus the shared expert. The shared
    expert counted once, the four parts are the uncut reference's layer."""
    model, _params = tiny
    whole = serving.LingDecoder(**dict(TINY, held_experts=[0, 16]))
    layer = whole.init_params(7)["layers"][1]
    h = jnp.asarray(np.random.RandomState(8).randn(24, 48).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want, _near = ref._mlp(_cfg(whole), layer, h, jnp.float32)
        picks = moe.route(h, layer["router"], layer["expert_bias"], 4, True,
                          2.5, n_group=4, topk_group=2)
        alike = ref._swiglu(h, layer["shared"]["w1"], layer["shared"]["w3"],
                            layer["shared"]["w2"], jnp.float32)
        total, rows = alike, 0
        for first in (0, 4, 8, 12):
            held = {k: v[first:first + 4] for k, v in
                    layer["experts"].items()}
            out, n = moe.expert_layer(h, picks, held, (first, 4),
                                      shared=layer["shared"])
            total = total + (out - alike)
            rows += int(n[:4].sum())
            assert int(n.sum()) == 24 * 4
    assert rows == 24 * 4               # every pick is held by one share
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- the latent pool ----------------------------------------------------

@pytest.mark.parametrize("lens", [(1, 8, 9, 0), (40, 17, 64, 33)])
def test_latent_kernel_in_interpret_mode_is_its_reference(lens):
    rng = np.random.RandomState(9)
    slots, heads, rank, width, held = 4, 4, 16, 20, 24
    pool = jnp.asarray(rng.randn(33, PAGE, held).astype(np.float32)
                       ).at[..., width:].set(0.0)
    table = jnp.asarray(rng.permutation(32)[:slots * 8].reshape(slots, 8)
                        .astype(np.int32) + 1)
    q = jnp.asarray(rng.randn(slots, heads, width).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)
    want = pk.paged_latent_attention_reference(q, pool, table, lens, rank,
                                               0.3)
    got = pk.paged_latent_attention(q, pool, table, lens, rank, 0.3,
                                    interpret=True)
    assert got.shape == (slots, heads, rank)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(got)[np.asarray(lens) == 0].any()


def test_cache_holds_pools_for_the_layers_that_own_some(tiny):
    model, _params = tiny
    assert kvcache.layer_states(model) == [
        ("slot", ((4, 8, 8), (3, 96)))] * 3 + [("latent", 20)]
    cache = kvcache.make_cache(model, 3, 128, page_size=PAGE)
    pools, state = cache.operands
    # one latent pool, no head axis, no V pool; three layers of slot state
    assert [x.shape for x in pools] == [(3 * 16 + 1, PAGE, 20)]
    assert [[x.shape for x in layer] for layer in state] == \
        [[(3, 4, 8, 8), (3, 3, 96)]] * 3
    st = cache.stats()["state"]
    assert st["state_bytes"] == 3 * 3 * (4 * 8 * 8 + 3 * 96) * 4
    assert st["latent_capacity"] == 48 and st["latent_pages"] == 0
    cache.reserve(1, 20)
    assert cache.stats()["state"]["latent_pages"] == 3
    # what the engine asks of it by declaration, not by its class
    assert cache.walk_groups() == (("latent", 128 // PAGE, 1),)
    assert cache.paged_bytes == (3 * 16 + 1) * PAGE * 20 * 4
    assert cache.state_bytes == st["state_bytes"]
    ((_version, table),) = cache.tables
    assert table.shape == (3, 128 // PAGE)
    with pytest.raises(MXNetError, match="no prefix index"):
        kvcache.make_cache(model, 3, 128, prefix_cache=True)


def test_declarations_of_the_other_models_give_the_caches_they_had():
    tiny = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                               head_dim=8, num_kv_heads=2)
    assert kvcache.layer_states(tiny) == [("paged",)] * 2
    paged = kvcache.make_cache(tiny, 2, 64, page_size=8)
    (group,) = paged.groups
    assert type(group) is kvcache.PagedKVCache
    # what the engine asks a cache by declaration: one group of K/V pages,
    # no state, nothing more on a span
    assert paged.span_args([5, 9]) == {} and paged.state_bytes == 0
    assert paged.walk_groups() == (("full", 8, 2),)
    (k_pool, v_pool), state = paged.operands
    assert state == () and len(k_pool) == len(v_pool) == 2
    assert paged.paged_bytes == sum(x.nbytes for x in k_pool + v_pool)
    afmoe = serving.AfmoeDecoder(
        vocab_size=96, hidden_size=48, num_attention_heads=12,
        num_key_value_heads=2, head_dim=8, intermediate_size=96,
        moe_intermediate_size=32,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
        sliding_window=32, held_experts=[4, 4])
    assert kvcache.layer_states(afmoe) == [("ring", 32)] * 4 + [("paged",)]
    cache = kvcache.make_cache(afmoe, 2, 128, page_size=8)
    full, window = cache.groups
    assert (type(full), type(window)) == (kvcache.PagedKVCache,
                                          kvcache.RingKVCache)
    assert (full.num_layers, window.num_layers) == (1, 4)
    assert cache.walk_groups() == (("full", 16, 1), ("window", 5, 4))
    assert cache.paged_bytes == full.paged_bytes + window.paged_bytes
    assert cache.span_args([40, 9]) == dict(
        kv_rows_full=49, kv_rows_window=41, kv_window_pages=0,
        kv_window_capacity=window.num_pages - 1)

    class Mixed:
        num_layers = 2
        layer_state = [("paged",), ("slot", ((2, 2),))]

    with pytest.raises(MXNetError, match=r"layers of paged \+ slot together"):
        kvcache.make_cache(Mixed(), 2, 64)


# -- prefill and decode through the caches ------------------------------

@pytest.mark.parametrize("tokens", [5, 8, 17, 32, 33, 63, 64, 100])
def test_prefill_then_decode_give_the_references_logits(ref, tiny, tokens):
    """Logits of the prompt's last position (prefill: chunked scan, expanded
    attention, padded to a rung) and of six teacher-forced positions behind
    it (decode: one-token update, absorbed attention over the pool) against
    the reference's full forward pass. The prompts end inside a page (5, 17,
    33, 63, 100) and on its edge (8, 32, 64), inside the recurrence's first
    chunk (5 .. 17), on a chunk's edge (32, 64), one token behind it (33)
    and inside a later chunk (63, 100)."""
    model, params = tiny
    steps = 6
    seq = np.concatenate([_prompt(tokens, tokens), _prompt(steps, 99)])
    cache = _cache(model)
    got = [_prefill(model, params, cache, 1, seq[:tokens], 128,
                    tokens + steps)]
    for i in range(steps):
        at = tokens + i
        got.append(_decode(model, params, cache, {1: (seq[at], at)})[1])
    want, _near = _ref_logits(ref, model, params, seq, tokens - 1, steps + 1)
    assert 0.5 < want.std() < 2.0
    np.testing.assert_allclose(np.stack(got), want, atol=LOGIT_TOL)


def test_absorbed_decode_is_expanded_prefill(tiny):
    """The same position through both forms of the latent layer: the last
    logits of a 41-token prefill, and of 40 tokens prefilled + 1 decoded."""
    model, params = tiny
    seq = _prompt(41, 12)
    whole = _prefill(model, params, _cache(model), 0, seq, 64, 41)
    cache = _cache(model)
    _prefill(model, params, cache, 0, seq[:40], 64, 41)
    one = _decode(model, params, cache, {0: (seq[40], 40)})[0]
    np.testing.assert_allclose(one, whole, atol=LOGIT_TOL)


def test_prefill_writes_its_slots_state_whole(tiny):
    """A slot's previous owner leaves nothing behind: garbage (NaN) in the
    slot's state and tail is gone after the next prefill, and the other
    slots' state is untouched bit for bit."""
    model, params = tiny
    seq = _prompt(30, 13)
    clean = _cache(model)
    want = _prefill(model, params, clean, 1, seq, 64, 40)
    dirty = _cache(model)
    marked = tuple(tuple(x.at[1].set(jnp.nan).at[0].set(7.0) for x in layer)
                   for layer in dirty.state)
    dirty.swap_pools(dirty.operands[0], marked)
    got = _prefill(model, params, dirty, 1, seq, 64, 40)
    assert np.array_equal(got, want)
    for mine, theirs in zip(jax.tree_util.tree_leaves(dirty.state),
                            jax.tree_util.tree_leaves(clean.state)):
        assert np.array_equal(np.asarray(mine)[1], np.asarray(theirs)[1])
        assert (np.asarray(mine)[0] == 7.0).all()
    a = _decode(model, params, dirty, {1: (3, 30)})[1]
    b = _decode(model, params, clean, {1: (3, 30)})[1]
    assert np.isfinite(a).all() and np.array_equal(a, b)


def test_a_seq_len_0_row_leaves_its_slots_state_bit_for_bit(tiny):
    model, params = tiny
    cache = _cache(model, slots=3)
    _prefill(model, params, cache, 0, _prompt(20, 14), 64, 30)
    _prefill(model, params, cache, 2, _prompt(9, 15), 64, 30)
    before = [np.asarray(x) for x in jax.tree_util.tree_leaves(cache.state)]
    _decode(model, params, cache, {0: (5, 20)})      # slots 1, 2: seq_len 0
    after = [np.asarray(x) for x in jax.tree_util.tree_leaves(cache.state)]
    for old, new in zip(before, after):
        assert np.array_equal(old[1:], new[1:])
    assert any(not np.array_equal(old[0], new[0])
               for old, new in zip(before, after))


def test_decode_through_the_state_kernel_is_decode_through_step(
        tiny, monkeypatch):
    """``LingDecoder.decode`` with ``mx_kda_state`` in interpret mode (one
    walk handed to every kda layer) against the same tick through
    ``kda.step``: logits of the live rows, every layer's state, and the
    empty slot's state bit for bit."""
    import functools

    model, params = tiny
    ticks = []
    for kernel in (False, True):
        if kernel:
            for name in ("kda_state_walk", "kda_state_step"):
                monkeypatch.setattr(pk, name, functools.partial(
                    getattr(pk, name), interpret=True))
        cache = _cache(model, slots=3)
        _prefill(model, params, cache, 0, _prompt(20, 14), 64, 30)
        _prefill(model, params, cache, 2, _prompt(9, 15), 64, 30)
        before = np.asarray(cache.state[0][0])
        logits = _decode(model, params, cache, {0: (5, 20), 2: (7, 9)})
        ticks.append((logits, [np.asarray(s) for s, _t in cache.state]))
        assert np.array_equal(ticks[-1][1][0][1], before[1])
    (want, want_s), (got, got_s) = ticks
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=LOGIT_TOL)
    for a, b in zip(got_s, want_s):
        np.testing.assert_allclose(a, b, atol=1e-5)


# -- through the engine -------------------------------------------------

def _gaps(ref, model, params, prompt, out):
    seq = np.concatenate([prompt, out[:-1]])
    logits, near = _ref_logits(ref, model, params, seq, prompt.size - 1,
                               out.size)
    gap = (logits.max(-1) - logits[np.arange(out.size), out]) \
        / logits.std(-1)
    return np.where(near, 0.0, gap)


def test_engine_serves_the_references_tokens_and_reuses_slots(ref, tiny):
    """Nine requests over three slots (every slot changes hands twice),
    prompts that cross pages, chunks and rungs."""
    model, params = tiny
    prompts = [_prompt(n, 20 + n) for n in (5, 40, 70, 17, 64, 9, 100, 33,
                                            65)]
    with _engine(tiny) as eng:
        eng.warmup()
        futs = [eng.submit(p, 12) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    for p, out in zip(prompts, outs):
        assert out.size == 12
        assert _gaps(ref, model, params, p, out).max() < GAP_TOL
    assert stats["steady_state_recompiles"] == 0
    assert stats["kvcache"]["pages_in_use"] == 0
    assert stats["steps_overlapped"] > 0
    state = stats["state"]
    a_slot = state["state_bytes"] // 3
    assert state["latent_capacity"] == 48 and state["latent_pages"] == 0
    assert state["state_slots_live"] == stats["slot_ticks"] + 9
    assert state["state_bytes_moved"] == a_slot * (
        2 * stats["slot_ticks"] + 9)
    assert state["latent_rows_read"] > sum(p.size for p in prompts)
    assert stats["moe"]["rows_held"] > 0
    assert stats["kv_cols_live"] > 0


def test_a_row_dropped_at_retire_leaves_no_trace_in_the_next_owner(
        ref, tiny, monkeypatch):
    """One slot. Request A ends on EOS at step N while step N + 1, already
    dispatched, updates the slot's state once more with a token nobody
    asked for; B then takes the slot. B's tokens are those of an engine B
    had to itself: its prefill overwrote the state whole, and nothing read
    it in between (the slot's rows carry ``seq_len`` 0 until then)."""
    model, params = tiny
    a, b = _prompt(21, 30), _prompt(34, 31)
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        alone_a = eng.submit(a, 10).result(timeout=300)
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        alone_b = eng.submit(b, 10).result(timeout=300)
    eos = int(alone_a[4])
    assert eos not in alone_a[:4]
    real_fetch = decode_mod.fetch_host

    def slow_fetch(arrays):     # the host lags: a step is always in flight
        import time
        time.sleep(0.002)
        return real_fetch(arrays)

    monkeypatch.setattr(decode_mod, "fetch_host", slow_fetch)
    with _engine(tiny, num_slots=1) as eng:
        eng.warmup()
        fa = eng.submit(a, 10, eos_id=eos)
        fb = eng.submit(b, 10)
        got_a, got_b = fa.result(timeout=300), fb.result(timeout=300)
        stats = eng.stats()
    assert np.array_equal(got_a, alone_a[:5])
    assert np.array_equal(got_b, alone_b)
    assert stats["steps_overlapped"] > 0
    # the step that ran on behind A's EOS: one more row than tokens kept
    assert stats["state"]["state_slots_live"] > got_a.size + got_b.size
    assert _gaps(ref, model, params, b, got_b).max() < GAP_TOL


@pytest.mark.parametrize("kw", [dict(prefix_cache=True),
                                dict(prefill_chunk=16), dict(spec_k=2)])
def test_engine_refuses_sharing_chunks_and_drafts(tiny, kw):
    with pytest.raises(MXNetError, match="declares latent and slot layers "
                       ".* is served with prefix_cache=False, "
                       "prefill_chunk=0, spec_k=0 .* a slot's state is no "
                       "page to share"):
        _engine(tiny, **kw)


def test_spans_carry_what_the_readers_read(tiny):
    """``state_slots_live``, ``state_bytes_moved`` and ``latent_rows_read``
    on ``mx.decode.commit`` and ``mx.decode.prefill`` (the arguments
    ``_layer_args`` hands the spans from the cache's ``span_args``), beside
    the expert layer's."""
    model, params = tiny
    seen = []
    with _engine(tiny, num_slots=2) as eng:
        eng.warmup()
        real = eng._layer_args

        def spy(counters, live, prefill=False):
            args = real(counters, live, prefill=prefill)
            seen.append((prefill, list(live), args))
            return args

        eng._layer_args = spy
        eng.submit(_prompt(12, 40), 5).result(timeout=300)
    prefills = [args for pre, _live, args in seen if pre]
    ticks = [args for pre, _live, args in seen if not pre]
    a_slot = (4 * 8 * 8 + 3 * 96) * 4 * 3
    assert prefills[-1]["state_slots_live"] == 1
    assert prefills[-1]["state_bytes_moved"] == a_slot
    assert prefills[-1]["latent_rows_read"] == 12
    assert ticks and all(t["state_bytes_moved"] == 2 * a_slot
                         * t["state_slots_live"] for t in ticks)
    assert [t["latent_rows_read"] for t in ticks if t["state_slots_live"]
            ][:2] == [13, 14]
    assert all("moe_rows_held" in t for t in ticks)
    text = telemetry.render_prometheus()
    for name in ("mxnet_decode_state_total", "mxnet_decode_state_bytes"):
        assert name in text
