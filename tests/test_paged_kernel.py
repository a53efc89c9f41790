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


# ---------------------------------------------------------------------------
# the flat walk: one grid step a live (slot, column) pair (PR 40)
# ---------------------------------------------------------------------------

FLAT_KINDS = ["decode", "verify", "chunk", "ring", "window"]
# tokens a slot; "full": as many as the table holds (a ring: wrapped twice)
FLAT_FILLS = {
    "empty_first": [0, 5, 20, 37, 48],
    "empty_middle": [5, 20, 0, 37, 48],
    "empty_last": [5, 20, 37, 48, 0],
    "all_empty": [0, 0, 0, 0, 0],
    "one_full_beside_empties": [0, 0, "full", 0, 0],
}


def _flat_launch(kind, tokens):
    """One of ``_paged_call``'s five launches over 5 slots holding
    ``tokens`` (the chunk: 5 rows of ONE sequence, a row real where its
    entry is not 0): ``run(pt)`` the kernel in interpret mode, ``ref(pt)``
    its dense oracle, and what the launch hands ``live_columns``."""
    rng = np.random.RandomState(40)
    ps, h, kh, d = PAGE, 4, 2, 8
    cols, window, ring = (5, 32, True) if kind == "ring" else \
        (6, 16 if kind == "window" else 0, False)
    full = 2 * cols * ps + 11 if ring else cols * ps
    n = np.asarray([full if t == "full" else t for t in tokens], np.int32)
    s = n.size
    qp = None
    if kind == "verify":        # W = 3: a chain of rows, the last padded
        sl = np.stack([np.maximum(n - 1, 0), n, np.zeros_like(n)], axis=1)
    elif kind == "chunk":
        start = cols * ps - s if "full" in tokens else ps + 3
        qp = (start + np.arange(s, dtype=np.int32))[:, None]
        sl = np.where(n[:, None] > 0, qp + 1, 0).astype(np.int32)
    else:
        sl = n[:, None]
    w = sl.shape[1]
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

    live = np.array(pk.live_columns(
        jnp.asarray(sl), None if qp is None else jnp.asarray(qp), cols, ps,
        window=window, ring=ring))
    return dict(run=run, ref=ref, pt=pt, sl=sl, live=live, cols=cols,
                nan_page=pool - 1)


def _check_schedule(live, pt):
    """``walk_schedule`` against the walk written out by hand: every live
    (slot, column) once, slot-major and ascending."""
    s, cols = pt.shape
    page_of, cell_of, starts = (np.asarray(x) for x in pk.walk_schedule(
        jnp.asarray(live), jnp.asarray(pt)))
    want = [(slot, col) for slot in range(s)
            for col in range(live[slot, 0], live[slot, 1])]
    extent = int(starts[-1])
    assert extent == len(want)
    assert page_of.shape == cell_of.shape == (s * cols + 1,)
    got = list(zip(cell_of[:extent] >> pk._CELL_BITS,
                   cell_of[:extent] & pk._CELL_MASK))
    assert got == want
    assert [pt[c] for c in want] == list(page_of[:extent])
    assert list(starts) == [sum(1 for c in want if c[0] < slot)
                            for slot in range(s + 1)]
    # past the extent every entry repeats the last step's (no new copy);
    # a walk of nothing names a cell and a page of the table all the same
    last = max(extent - 1, 0)
    assert (cell_of[last:] == cell_of[last]).all()
    assert (page_of[last:] == page_of[last]).all()
    assert 0 <= cell_of[last] >> pk._CELL_BITS < s
    assert 0 <= cell_of[last] & pk._CELL_MASK < cols
    assert page_of[last] in pt
    return extent


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", FLAT_KINDS)
def test_walk_schedule_is_the_live_pairs_slot_major_and_ascending(kind,
                                                                  seed):
    """Seeded ragged lengths, an empty slot among them: the schedule is the
    brute-force walk, and its extent is what the engine counts as
    ``kv_cols_walked`` a layer (each slot's own columns up to its longest
    row; where no window cuts columns off the front)."""
    rng = np.random.RandomState(400 + seed)
    tokens = [int(t) for t in rng.randint(1, 110 if kind == "ring" else 49,
                                          5)]
    tokens[rng.randint(5)] = 0
    launch = _flat_launch(kind, tokens)
    extent = _check_schedule(launch["live"], launch["pt"])
    if kind in ("decode", "verify", "ring"):
        longest = launch["sl"].max(axis=1)
        assert extent == sum(min(-(-int(n) // PAGE), launch["cols"])
                             for n in longest)


@pytest.mark.parametrize("kind", FLAT_KINDS)
def test_walk_schedule_with_every_slot_full_stays_in_bounds(kind):
    """Every column of every slot live: the walk is ``S * max_pages`` steps
    and the entry behind its last one — whose index maps the pipeline
    evaluates — exists and names the last step's block again."""
    launch = _flat_launch(kind, ["full"] * 5)
    if kind == "window":        # a window cuts columns off the front
        launch["live"][:, 0] = 0
    elif kind == "chunk":       # rows of one sequence: the last sees it all
        launch["live"][:] = [0, launch["cols"]]
    extent = _check_schedule(launch["live"], launch["pt"])
    assert extent == launch["pt"].size


@pytest.mark.parametrize("fill", sorted(FLAT_FILLS))
@pytest.mark.parametrize("kind", FLAT_KINDS)
def test_flat_walk_matches_the_dense_oracle_around_empty_slots(kind, fill):
    launch = _flat_launch(kind, FLAT_FILLS[fill])
    _check_schedule(launch["live"], launch["pt"])
    got = np.asarray(launch["run"](launch["pt"]))
    np.testing.assert_allclose(got, np.asarray(launch["ref"](launch["pt"])),
                               atol=2e-5, rtol=2e-5)
    assert not got[~(launch["sl"] > 0)].any()    # a row that sees nothing
    if fill != "all_empty":
        assert np.abs(got).sum() > 0


@pytest.mark.parametrize("fill", sorted(FLAT_FILLS))
@pytest.mark.parametrize("kind", FLAT_KINDS)
def test_flat_walk_never_reads_a_column_it_does_not_walk(kind, fill):
    """Every column outside a slot's ``[c0, c1)`` — every column of an
    empty slot — points at a page of NaN: no step fetches or multiplies it
    (a masked product would read ``0 * NaN``), so the output is finite and
    equals the oracle's over the table whose un-walked columns hold the null
    page (PR 28's test, for the walk that visits live pairs alone)."""
    launch = _flat_launch(kind, FLAT_FILLS[fill])
    col = np.arange(launch["cols"])[None, :]
    dead = (col < launch["live"][:, :1]) | (col >= launch["live"][:, 1:])
    assert dead.any()
    got = np.asarray(launch["run"](
        np.where(dead, launch["nan_page"], launch["pt"])))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(launch["ref"](np.where(dead, 0, launch["pt"]))),
        atol=2e-5, rtol=2e-5)
