"""A prefill's row-wise passes over the row blocks that hold work
(``mxnet_tpu.ops.row_blocks``): the helper against the plain function, the two
served models' prefills with a count against the same call computed whole, the
expert layer and the chunked scan with one, the decode programs without any,
and ``prefill_rows`` against the blocks the helper visits. CPU, tiny widths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.ops import kda, moe
from mxnet_tpu.ops import row_blocks as rb
from mxnet_tpu.serving import kvcache

BLOCK, ROWS = 8, 40


def _fn(w):
    """A row-independent function with two results of different shape and
    type (and a table it closes over whole)."""
    return lambda x, i: (jnp.tanh(x @ w) + 1.0, (i * 3 + 1)[:, None])


def _operands():
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(ROWS, 6).astype(np.float32)),
            jnp.arange(ROWS, dtype=jnp.int32)), \
        jnp.asarray(rng.randn(6, 5).astype(np.float32))


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------
@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 rows (the models' 256 are a constant of the module)."""
    monkeypatch.setattr(rb, "_BLOCK", BLOCK)


@pytest.mark.parametrize("n", [1, BLOCK - 1, BLOCK, BLOCK + 1, ROWS])
def test_helper_is_the_plain_function_over_the_first_rows(n, small_blocks):
    """Rows ``0 .. n - 1`` are the plain function's to the bit (the same
    row-wise arithmetic on a block as on the whole extent), every row from
    ``n`` on exactly zero — also when the operands hold NaN there."""
    operands, w = _operands()
    plain = _fn(w)(*operands)
    dirty = (operands[0].at[n:].set(jnp.nan), operands[1])
    got = jax.jit(lambda ops, n: rb.row_blocks(_fn(w), ops, n))(
        dirty, jnp.asarray(n, jnp.int32))
    for want, have in zip(plain, got):
        assert have.shape == want.shape and have.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(have[:n]),
                                      np.asarray(want[:n]))
        assert not np.asarray(have[n:]).any()


def test_helper_without_a_count_is_the_plain_call():
    operands, w = _operands()
    jaxpr = jax.make_jaxpr(lambda ops: rb.row_blocks(_fn(w), ops))(operands)
    assert "while" not in str(jaxpr)
    for want, have in zip(_fn(w)(*operands), rb.row_blocks(_fn(w), operands)):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


def test_a_block_that_does_not_divide_the_rows(small_blocks):
    """37 rows in blocks of 8: the last block starts at row 29 and computes
    rows 29-31 a second time, to the same values."""
    operands, w = _operands()
    operands = tuple(x[:37] for x in operands)
    got = rb.row_blocks(_fn(w), operands, jnp.asarray(37))
    for want, have in zip(_fn(w)(*operands), got):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


def _blocks_visited(n, rows):
    """The starts of the blocks the loop visits, seen from inside it."""
    seen = []

    def fn(i):
        jax.debug.callback(lambda at: seen.append(int(at)), i[0],
                           ordered=True)
        return i

    jax.block_until_ready(rb.row_blocks(
        fn, (jnp.arange(rows, dtype=jnp.int32),), jnp.asarray(n, jnp.int32)))
    return seen


@pytest.mark.parametrize("tokens", [0, 1, 255, 256, 257, 700, 1024])
def test_prefill_rows_is_the_rows_of_the_blocks_the_helper_visits(tokens):
    """``prefill_rows(tokens, rung)`` — the arithmetic behind the span's
    ``rows_computed`` — against the loop's own trips, at the models' block
    (``row_block``: 256 rows of a rung of 1024), as ``band_blocks`` is pinned
    against the kernel's live steps."""
    rung = 1024
    block = rb.row_block(rung)
    assert block == 256
    seen = _blocks_visited(tokens, rung)
    assert seen == list(range(0, len(seen) * block, block))
    assert len(seen) * block == rb.rows_visited(tokens, rung)
    for model in (_afmoe(), _ling()):
        assert model.prefill_rows(tokens, rung) == len(seen) * block
        assert model.prefill_rows(rung, rung) == rung
    # a rung under a block is one block
    assert rb.rows_visited(3, 64) == 64 and rb.row_block(64) == 64


# ---------------------------------------------------------------------------
# the expert layer and the scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["all held", "none held", "one expert"])
def test_expert_layer_with_a_row_count_is_the_layer_without(case,
                                                            small_blocks):
    """The first 21 of 32 rows are tokens. With ``length`` the passes visit
    the row blocks (of 8) that hold work — the held (token, pick) rows are a
    prefix of the sorted rows — and give what the straight-line layer
    gives, the rows behind the prompt zero."""
    moe.expert_layer.clear_cache()      # traced with the block of its day
    rng = np.random.RandomState(1)
    t, e, m, n_experts, top_k, length = 32, 16, 24, 8, 2, 21
    held = {"all held": (0, 8), "none held": (6, 2),
            "one expert": (0, 8)}[case]
    sel = rng.randint(0, 6 if case == "none held" else n_experts,
                      size=(t, top_k))
    if case == "one expert":
        sel[:] = 3
    sel = jnp.asarray(sel, jnp.int32)
    weights = jnp.asarray(rng.rand(t, top_k).astype(np.float32))
    h = jnp.asarray(rng.randn(t, e).astype(np.float32))

    def swiglu(*lead):
        return {k: jnp.asarray(rng.randn(*lead, *shape).astype(np.float32)
                               * 0.3)
                for k, shape in (("w1", (e, m)), ("w3", (e, m)),
                                 ("w2", (m, e)))}

    experts, shared = swiglu(held[1]), swiglu()
    valid = jnp.arange(t) < length
    want, want_rows = moe.expert_layer(h, (sel, weights), experts, held,
                                       shared=shared, valid=valid)
    got, got_rows = moe.expert_layer(h, (sel, weights), experts, held,
                                     shared=shared, valid=valid,
                                     length=jnp.asarray(length, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got_rows),
                                  np.asarray(want_rows))
    assert int(got_rows[:held[1]].sum()) == {
        "all held": length * top_k, "none held": 0,
        "one expert": length * top_k}[case]
    np.testing.assert_allclose(np.asarray(got[:length]),
                               np.asarray(want[:length]), rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got[length:]).any()
    text = str(jax.make_jaxpr(lambda n: moe.expert_layer(
        h, (sel, weights), experts, held, shared=shared, valid=valid,
        length=n))(jnp.asarray(length, jnp.int32)))
    # the gather, the combine, and an activation and a result a SwiGLU
    assert text.count("while[") == 6
    moe.expert_layer.clear_cache()


@pytest.mark.parametrize("length", [5, 32, 33, 70, 96])
def test_chunked_scan_with_a_length_is_the_serial_scan_of_the_prompt(length):
    """96 rows in chunks of 32, rows from ``length`` on padding that holds
    anything: the scan visits the chunks that hold the prompt, masks the
    last one's padding itself, and returns the serial scan's outputs and
    state of the first ``length`` rows; the chunks behind come back zero."""
    rng = np.random.RandomState(2)
    t, heads, dk, dv = 96, 2, 8, 4
    q, k, a = (jnp.asarray(rng.randn(t, heads, dk).astype(np.float32))
               for _ in range(3))
    v = jnp.asarray(rng.randn(t, heads, dv).astype(np.float32))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_decay = -jax.nn.softplus(a)
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(t, heads)
                                      .astype(np.float32)))
    want, want_state = kda.serial_scan(q[:length], k[:length], v[:length],
                                       log_decay[:length], beta[:length])
    got, got_state = jax.jit(
        lambda n: kda.chunked_scan(q, k, v, log_decay, beta, length=n))(
        jnp.asarray(length, jnp.int32))
    np.testing.assert_allclose(np.asarray(got[:length]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state),
                               np.asarray(want_state), rtol=2e-4, atol=2e-5)
    assert not np.asarray(got[-(-length // 32) * 32:]).any()


# ---------------------------------------------------------------------------
# the served models
# ---------------------------------------------------------------------------
def _afmoe():
    return serving.AfmoeDecoder(
        vocab_size=96, hidden_size=48, num_attention_heads=12,
        num_key_value_heads=2, head_dim=8, intermediate_size=96,
        moe_intermediate_size=32,
        layer_types=["sliding_attention"] * 2 + ["full_attention"],
        num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
        sliding_window=16, held_experts=[4, 4], route_scale=2.448,
        mup_enabled=True)


def _ling():
    return serving.LingDecoder(
        vocab_size=96, hidden_size=48, num_attention_heads=4, head_dim=8,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=96, moe_intermediate_size=32,
        layer_types=["kda", "kda", "mla"], num_dense_layers=1,
        num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
        held_experts=[0, 8], routed_scaling_factor=2.5)


RUNG, SLOTS, PAGE = 32, 2, 8


def _prefill(model, length):
    """``(logits, pools or state ...)`` of ``model.prefill`` over a prompt
    of ``length`` tokens on the rung of 32, written into slot 1's pages."""
    params = model.init_params(0)
    cache = kvcache.make_cache(model, num_slots=SLOTS, max_seq_len=64,
                               page_size=PAGE)
    cache.reserve(1, length)
    rng = np.random.RandomState(7)
    tokens = np.zeros((RUNG,), np.int32)
    tokens[:length] = rng.randint(1, 96, size=length)
    pages, offs = cache.write_slots(1, 0, length)

    def padded(x):
        out = np.zeros((RUNG,), np.int32)
        out[:length] = x
        return jnp.asarray(out)

    # a row of pages a cache group, as the cache hands a model its groups
    pages = cache.per_group([padded(p) for p in pages])
    more = {"slot": jnp.asarray(1, jnp.int32)} if cache.state else {}
    k, v = cache.operands
    out = jax.jit(lambda n: model.prefill(
        params, jnp.asarray(tokens), n, k, v, pages, padded(offs), **more))(
        jnp.asarray(length, jnp.int32))
    return out


def _whole(monkeypatch):
    """The same prefill computed whole: every pass over every row of the
    rung, the scan over every chunk with its padding masked by the caller
    (what the models did before they handed a count on)."""
    def every_row(fn, operands, n=None):
        return fn(*operands)

    def every_chunk(q, k, v, log_decay, beta, chunk=kda.CHUNK, length=None):
        token = jnp.arange(q.shape[0]) < length
        return scan(q, k, v, jnp.where(token[:, None, None], log_decay, 0.0),
                    jnp.where(token[:, None], beta, 0.0), chunk)

    scan = kda.chunked_scan
    monkeypatch.setattr(rb, "row_blocks", every_row)
    monkeypatch.setattr(moe, "row_blocks", every_row)
    monkeypatch.setattr(kda, "chunked_scan", every_chunk)


@pytest.mark.parametrize("length", [BLOCK - 1, BLOCK, BLOCK + 1, 21, RUNG])
@pytest.mark.parametrize("kind", ["afmoe", "ling"])
def test_prefill_with_the_count_is_the_prefill_computed_whole(
        kind, length, small_blocks, monkeypatch):
    """Blocks of 8 rows on the rung of 32, prompts on both sides of a block
    edge and at the rung: the logits, the rows routed to each expert and
    every pool and state leaf the prompt wrote — every page but the null
    page 0, where the padding's rows go — equal to rounding."""
    # `expert_layer` is a jit of its own: a trace of it made with another
    # block, or with another `row_blocks`, must not be found again
    moe.expert_layer.clear_cache()
    model = _afmoe() if kind == "afmoe" else _ling()
    got = _prefill(model, length)
    with monkeypatch.context() as patch:
        _whole(patch)
        moe.expert_layer.clear_cache()
        want = _prefill(model, length)
    moe.expert_layer.clear_cache()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    have = jax.tree_util.tree_leaves(got[1:3])
    for a, b in zip(have, jax.tree_util.tree_leaves(want[1:3])):
        a, b = np.asarray(a), np.asarray(b)
        paged = a.ndim >= 3 and a.shape[1] == PAGE
        np.testing.assert_allclose(a[1:] if paged else a,
                                   b[1:] if paged else b, rtol=2e-4,
                                   atol=2e-5)
    assert any(np.asarray(x)[1:].any() for x in have)


@pytest.mark.parametrize("kind", ["afmoe", "ling"])
def test_decode_program_holds_no_loop_of_the_helper(kind):
    """A decode tick hands no count: its rows are straight-line code, and
    its jaxpr holds no loop at all, where the prefill's holds one a pass."""
    model = _afmoe() if kind == "afmoe" else _ling()
    params = model.init_params(0)
    cache = kvcache.make_cache(model, num_slots=SLOTS, max_seq_len=64,
                               page_size=PAGE)
    k, v = cache.operands
    ints = jnp.zeros((SLOTS,), jnp.int32)
    if kind == "afmoe":
        tables = tuple(jnp.asarray(t) for _v, t in cache.tables)
        pages = (ints, ints)
    else:
        tables, pages = jnp.asarray(cache.page_table), ints
    step = str(jax.make_jaxpr(lambda: model.decode(
        params, ints, ints, k, v, tables, ints + 1, pages, ints))())
    assert "while[" not in step and "dynamic_update_slice" not in step
    more = {} if kind == "afmoe" else {"slot": jnp.asarray(0, jnp.int32)}
    rung = jnp.zeros((RUNG,), jnp.int32)
    wp = (rung, rung) if kind == "afmoe" else rung
    prefill = str(jax.make_jaxpr(lambda n: model.prefill(
        params, rung, n, k, v, wp, rung, **more))(jnp.asarray(3, jnp.int32)))
    assert prefill.count("while[") >= 2 * model.num_layers
