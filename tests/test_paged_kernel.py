"""``_paged_kernel`` in Pallas interpret mode against the dense references:
the body that multiplies a page ONCE for all its kv heads (PR 34) — one
query matrix of every head's rows, one key matrix ``(page_size * n_kv, D)``,
a score masked where its row reads another kv head than its column holds.

No chip and no libtpu: every launch is called with ``interpret=True``. What
Mosaic makes of the same body is ``tests/test_chip_compile.py``'s.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_kernels as pk

PAGE, DIM = 8, 16
# (n_kv, groups): Trinity's 48 / 8, OPT's 32 / 32, a multiple of the sublane
# tile with one head a group, and a count the tile does not divide
HEADS = [(8, 6), (32, 1), (8, 1), (2, 4)]
LAUNCHES = ["decode", "window", "spec", "chunk"]


def _pools(rng, pages, n_kv, dim=DIM):
    return (jnp.asarray(rng.randn(pages, PAGE, n_kv, dim).astype(np.float32)),
            jnp.asarray(rng.randn(pages, PAGE, n_kv, dim).astype(np.float32)))


def _table(rng, slots, cols):
    """Distinct pages 1.. for every (slot, column); page 0 is nobody's."""
    return jnp.asarray(1 + rng.permutation(slots * cols)
                       .reshape(slots, cols).astype(np.int32))


def _launch(launch, rng, n_kv, groups, k_pool, v_pool, pt, dim=DIM,
            **kernel):
    """(kernel's result, reference's result) of one launch over ``pt``
    (4 slots): an idle slot beside live ones in every launch. The reference
    reads the first ``dim`` lanes of the pools' rows."""
    h = n_kv * groups
    cols = pt.shape[1]
    narrow = (k_pool[..., :dim], v_pool[..., :dim])
    if launch == "decode":
        q = jnp.asarray(rng.randn(4, h, dim).astype(np.float32))
        lens = jnp.asarray([0, 5, PAGE * 2, PAGE * cols - 3], jnp.int32)
        return (pk.ragged_paged_attention(q, k_pool, v_pool, pt, lens,
                                          interpret=True, **kernel),
                pk.paged_attention_reference(q, *narrow, pt, lens))
    if launch == "window":
        # a ring of `cols` columns under a window of (cols - 1) pages: inside
        # the window, past it, and wrapped more than twice
        window = PAGE * (cols - 1)
        q = jnp.asarray(rng.randn(4, h, dim).astype(np.float32))
        lens = jnp.asarray([0, 5, window + 5, 2 * PAGE * cols + 11],
                           jnp.int32)
        return (pk.ragged_window_attention(q, k_pool, v_pool, pt, lens,
                                           window, interpret=True, **kernel),
                pk.paged_window_attention_reference(q, *narrow, pt, lens,
                                                    window))
    if launch == "spec":
        # width 3: each row sees the prefix and the draft rows below it; an
        # idle slot, and a slot whose last row is padded out
        q = jnp.asarray(rng.randn(4, 3, h, dim).astype(np.float32))
        lens = jnp.asarray([0, 0, 0, 5, 6, 7, PAGE * 2 - 1, PAGE * 2, 0,
                            PAGE * cols - 2, PAGE * cols - 1, PAGE * cols],
                           jnp.int32)
        want = pk.paged_spec_attention_reference(
            q.reshape(12, h, dim), *narrow, pt, lens).reshape(q.shape)
        return (pk.ragged_spec_attention(q, k_pool, v_pool, pt, lens,
                                         interpret=True, **kernel), want)
    # the chunk program's launch: one row a chunk token, every row the same
    # table row, causal by q_pos; the last row is padding
    start, n = PAGE + 3, 4
    q = jnp.asarray(rng.randn(n, h, dim).astype(np.float32))
    q_pos = start + jnp.arange(n, dtype=jnp.int32)
    lens = jnp.where(jnp.arange(n) < n - 1, q_pos + 1, 0).astype(jnp.int32)
    rows = jnp.broadcast_to(pt[3][None], (n, cols))
    return (pk.ragged_paged_attention(q, k_pool, v_pool, rows, lens,
                                      q_pos=q_pos, interpret=True, **kernel),
            pk.paged_attention_reference(q, *narrow, rows, lens,
                                         q_pos=q_pos))


@pytest.mark.parametrize("launch", LAUNCHES)
@pytest.mark.parametrize("n_kv,groups", HEADS)
def test_one_product_a_page_matches_the_dense_reference(n_kv, groups, launch):
    rng = np.random.RandomState(n_kv * 7 + groups)
    cols = 5
    k_pool, v_pool = _pools(rng, 1 + 4 * cols, n_kv)
    got, want = _launch(launch, rng, n_kv, groups, k_pool, v_pool,
                        _table(rng, 4, cols))
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert np.abs(np.asarray(want)).sum() > 0


@pytest.mark.parametrize("n_kv,groups", HEADS)
def test_a_slot_with_no_token_beside_live_ones_is_zeros(n_kv, groups):
    rng = np.random.RandomState(11)
    k_pool, v_pool = _pools(rng, 13, n_kv)
    q = jnp.asarray(rng.randn(3, n_kv * groups, DIM).astype(np.float32))
    lens = jnp.asarray([PAGE + 1, 0, 3 * PAGE], jnp.int32)
    got = np.asarray(pk.ragged_paged_attention(
        q, k_pool, v_pool, _table(rng, 3, 4), lens, interpret=True))
    assert not got[1].any()
    assert np.abs(got[0]).sum() > 0 and np.abs(got[2]).sum() > 0


@pytest.mark.parametrize("n_kv,groups", HEADS)
def test_other_kv_heads_of_the_page_do_not_leak_into_a_head(n_kv, groups):
    """The cross-head mask: kv head 1's rows read the same numbers whether
    the page's other kv heads hold ordinary values or 1e4 (a masked score
    is exp'd to exactly 0, and 0 x 1e4 adds nothing to a row's sum)."""
    rng = np.random.RandomState(13)
    k_pool, v_pool = _pools(rng, 13, n_kv)
    others = (jnp.arange(n_kv) != 1)[None, None, :, None]
    loud = [jnp.where(others, 1e4, pool) for pool in (k_pool, v_pool)]
    q = jnp.asarray(rng.randn(3, n_kv * groups, DIM).astype(np.float32))
    pt = _table(rng, 3, 4)
    lens = jnp.asarray([5, 2 * PAGE, 4 * PAGE - 1], jnp.int32)
    quiet = np.asarray(pk.ragged_paged_attention(q, k_pool, v_pool, pt, lens,
                                                 interpret=True))
    got = np.asarray(pk.ragged_paged_attention(q, *loud, pt, lens,
                                               interpret=True))
    mine = slice(groups, 2 * groups)        # the query heads of kv head 1
    np.testing.assert_allclose(got[:, mine], quiet[:, mine], atol=1e-6,
                               rtol=0)
    assert np.abs(got[:, mine]).max() < 10.0


@pytest.mark.parametrize("launch", LAUNCHES)
def test_pools_with_rows_wider_than_head_dim(launch):
    """Trinity's heads over pools whose rows carry zero lanes beyond
    ``head_dim`` (``kvcache.pool_row_width``): the query matrix grows to the
    rows, the result is cut back."""
    rng = np.random.RandomState(17)
    n_kv, groups, cols = 8, 6, 5
    k_pool, v_pool = _pools(rng, 1 + 4 * cols, n_kv)
    wide = [jnp.pad(x, ((0, 0),) * 3 + ((0, DIM),)) for x in (k_pool, v_pool)]
    got, want = _launch(launch, rng, n_kv, groups, *wide,
                        _table(rng, 4, cols))
    assert got.shape == want.shape and got.shape[-1] == DIM
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _dot_precisions(fn, *operands):
    """``precision`` of every ``dot_general`` in the kernel body of the one
    ``pallas_call`` that ``fn`` traces."""
    calls = [e for e in jax.make_jaxpr(fn)(*operands).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(eqn.params["precision"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(calls[0].params["jaxpr"])
    return found


@pytest.mark.parametrize("launch", ["decode", "window"])
@pytest.mark.parametrize("precise", [True, False])
def test_precise_asks_float32_products_of_both_products(launch, precise):
    """``precise=True`` is ``lax.Precision.HIGHEST`` on the score product
    AND the value product, two products a page; without it neither states a
    precision (the compiler's default)."""
    rng = np.random.RandomState(19)
    k_pool, v_pool = _pools(rng, 9, 8)
    pt = _table(rng, 2, 4)
    q = jnp.asarray(rng.randn(2, 48, DIM).astype(np.float32))
    lens = jnp.asarray([5, 30], jnp.int32)
    if launch == "decode":
        def fn(*a):
            return pk.ragged_paged_attention(*a, interpret=True,
                                             precise=precise)
    else:
        def fn(*a):
            return pk.ragged_window_attention(*a, 24, interpret=True,
                                              precise=precise)
    got = _dot_precisions(fn, q, k_pool, v_pool, pt, lens)
    assert len(got) == 2, got
    for p in got:
        if precise:
            assert p is not None and set(
                p if isinstance(p, tuple) else (p,)) == {
                    jax.lax.Precision.HIGHEST}, got
        else:
            assert p is None, got


@pytest.mark.parametrize("precise,tol", [(True, 1e-6), (False, 2e-2)])
def test_precise_agrees_with_the_float32_reference(precise, tol):
    """Against the reference at ``highest``: float32 products agree to 1e-6,
    the default to bfloat16 tolerance at the least (off the chip the default
    is float32 too — the chip's own reading is PERF.md's)."""
    rng = np.random.RandomState(23)
    k_pool, v_pool = _pools(rng, 21, 8)
    pt = _table(rng, 4, 5)
    q = jnp.asarray(rng.randn(4, 48, DIM).astype(np.float32))
    lens = jnp.asarray([0, 5, 2 * PAGE, 5 * PAGE - 3], jnp.int32)
    got = pk.ragged_paged_attention(q, k_pool, v_pool, pt, lens,
                                    interpret=True, precise=precise)
    with jax.default_matmul_precision("highest"):
        want = pk.paged_attention_reference(q, k_pool, v_pool, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=0)
