"""The KV pools as the decode step holds them (tier-1, CPU): one array a
layer, in a tuple the cache, the engine's four jits and ``write_kv`` thread
whole — for a :class:`PagedKVCache` and for both groups of a
:class:`GroupedKVCache`. What the pools cost on the chip (no whole-pool copy,
no per-layer slice) is ``tests/test_chip_compile.py``'s to hold.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.resilience import hbm
from mxnet_tpu.serving import kvcache
from mxnet_tpu.serving.kvcache import GroupedKVCache, PagedKVCache, write_kv

PAGE, WINDOW = 8, 32
AFMOE = dict(vocab_size=96, hidden_size=48, num_attention_heads=12,
             num_key_value_heads=2, head_dim=8, intermediate_size=96,
             moe_intermediate_size=32,
             layer_types=["sliding_attention"] * 4 + ["full_attention"],
             num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
             sliding_window=WINDOW, held_experts=[4, 4], route_scale=2.448,
             mup_enabled=True)
KINDS = ["paged", "grouped"]


def _cache(kind):
    """``(cache, [(layers, pages) of each group])``."""
    name = "pools-%d" % np.random.randint(1 << 30)
    if kind == "paged":
        return PagedKVCache(3, 64, 3, 2, 8, page_size=PAGE, num_pages=20,
                            name=name), [(3, 20)]
    groups = {"full": [4], "window": [0, 1, 2, 3], "window_tokens": WINDOW}
    return GroupedKVCache(3, 128, groups, 2, 8, page_size=PAGE,
                          num_pages={"full": 40, "window": 12},
                          name=name), [(1, 40), (4, 12)]


def _groups(cache, pool):
    """The per-group tuples of ``pool`` (a plain cache has one)."""
    return pool if isinstance(cache, GroupedKVCache) else (pool,)


def _engine(kind, **kw):
    if kind == "paged":
        model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                    head_dim=8, num_kv_heads=2)
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("prefill_buckets", (8, 16))
    else:
        model = serving.AfmoeDecoder(**AFMOE)
        kw.update(max_seq_len=128, page_size=PAGE, prefill_buckets=(16, 64),
                  prefix_cache=False, prefill_chunk=0)
    kw.setdefault("num_slots", 3)
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("name", "pools%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, model.init_params(0), **kw)


@pytest.mark.parametrize("kind", KINDS)
def test_a_pool_is_one_array_a_layer(kind):
    cache, groups = _cache(kind)
    for pool in (cache.k_pool, cache.v_pool):
        for leaves, (layers, pages) in zip(_groups(cache, pool), groups):
            assert isinstance(leaves, tuple)
            assert [x.shape for x in leaves] == [(pages, PAGE, 2, 8)] * layers
            assert len({id(x) for x in leaves}) == layers


@pytest.mark.parametrize("kind", KINDS)
def test_write_kv_replaces_one_layers_array_and_no_other(kind):
    cache, groups = _cache(kind)
    rows = jnp.asarray(np.arange(3 * 2 * 8, dtype=np.float32)
                       .reshape(3, 2, 8) + 1.0)
    pages = jnp.asarray([1, 1, 2], jnp.int32)
    offs = jnp.asarray([6, 7, 0], jnp.int32)
    for k_old, v_old, (layers, _p) in zip(_groups(cache, cache.k_pool),
                                          _groups(cache, cache.v_pool),
                                          groups):
        layer = layers - 1
        k_new, v_new = write_kv(k_old, v_old, layer, rows, rows * 2.0,
                                pages, offs)
        assert isinstance(k_new, tuple) and len(k_new) == layers
        for li in range(layers):
            if li != layer:      # the SAME object: donated and returned
                assert k_new[li] is k_old[li] and v_new[li] is v_old[li]
        np.testing.assert_array_equal(
            np.asarray(k_new[layer])[[1, 1, 2], [6, 7, 0]], np.asarray(rows))
        np.testing.assert_array_equal(
            np.asarray(v_new[layer])[[1, 1, 2], [6, 7, 0]],
            np.asarray(rows) * 2.0)
        assert float(jnp.abs(k_old[layer]).sum()) == 0.0   # functional


@pytest.mark.parametrize("kind", KINDS)
def test_swap_and_reset_pools_take_the_pytree(kind):
    cache, groups = _cache(kind)
    ones = jax.tree_util.tree_map(lambda x: x + 1.0, cache.k_pool)
    twos = jax.tree_util.tree_map(lambda x: x + 2.0, cache.v_pool)
    cache.swap_pools(ones, twos)
    assert jax.tree_util.tree_structure(cache.k_pool) \
        == jax.tree_util.tree_structure(ones)
    for held, given in zip(jax.tree_util.tree_leaves(
            (cache.k_pool, cache.v_pool)),
            jax.tree_util.tree_leaves((ones, twos))):
        assert held is given
    cache.reset_pools()
    leaves = jax.tree_util.tree_leaves((cache.k_pool, cache.v_pool))
    assert len(leaves) == 2 * sum(layers for layers, _p in groups)
    assert all(float(jnp.abs(x).sum()) == 0.0 for x in leaves)
    assert [x.shape for x in jax.tree_util.tree_leaves(cache.k_pool)] \
        == [x.shape for x in jax.tree_util.tree_leaves(ones)]


@pytest.mark.parametrize("kind", KINDS)
def test_cow_copies_the_page_in_every_layers_array(kind):
    with _engine(kind) as eng:
        k_pool = jax.tree_util.tree_map(
            lambda x: x.at[3].set(7.0), eng._cache.k_pool)
        kp, vp = eng._cow_jit(k_pool, eng._cache.v_pool,
                              jnp.asarray(3, jnp.int32),
                              jnp.asarray(5, jnp.int32))
        assert jax.tree_util.tree_structure(kp) \
            == jax.tree_util.tree_structure(eng._cache.k_pool)
        for leaf in jax.tree_util.tree_leaves(kp):
            got = np.asarray(leaf)
            assert (got[5] == 7.0).all() and (got[3] == 7.0).all()
            assert (got[4] == 0.0).all()
        assert all(float(jnp.abs(x).sum()) == 0.0
                   for x in jax.tree_util.tree_leaves(vp))


@pytest.mark.parametrize("kind", KINDS)
def test_pools_dead_reads_one_leaf(kind):
    with _engine(kind) as eng:
        assert eng._pools_dead() is False
        pool = eng._cache.k_pool
        first = (pool[0] if kind == "grouped" else pool)[0]
        first.delete()      # what a failed step does to a donated buffer
        assert eng._pools_dead() is True
        eng._cache.reset_pools()
        assert eng._pools_dead() is False


@pytest.mark.parametrize("kind", KINDS)
def test_governor_bound_and_stats_count_every_leaf(kind):
    with _engine(kind) as eng:
        leaves = jax.tree_util.tree_leaves(
            (eng._cache.k_pool, eng._cache.v_pool))
        bound = hbm.governor().oom_report()["bounds_bytes"][
            "serving.%s.kv_pool" % eng._name]
        assert bound == sum(x.nbytes for x in leaves) > 0
        stats = eng.stats()
        assert stats["kv_pool_leaves"] == len(leaves)
        assert stats["decode_step_temp_bytes"] is None      # no warm-up yet
        eng.warmup()
        assert eng.stats()["decode_step_temp_bytes"] > 0
        assert eng.stats()["steady_state_recompiles"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_served_tokens_survive_a_pool_reset(kind):
    """Tokens through tuple pools equal the oracle's, before and after the
    eviction path's ``reset_pools``."""
    with _engine(kind) as eng:
        eng.warmup()
        prompt = np.arange(1, 12, dtype=np.int32)
        first = eng.generate(prompt, 6, timeout=300)
        eng._cache.reset_pools()
        again = eng.generate(prompt, 6, timeout=300)
        np.testing.assert_array_equal(first, again)
        assert eng.stats()["steady_state_recompiles"] == 0
        assert eng.kvcache_stats()["pages_in_use"] == 0


def test_row_width_is_head_dim_where_the_device_is_row_major():
    """Every CPU array is: rows stay ``head_dim`` wide (what a TPU answers
    at 32 x 64 and 8 x 128: tests/test_chip_compile.py)."""
    (device,) = jnp.zeros(()).devices()
    for dim in (4, 8, 64, 128):
        assert kvcache.pool_row_width((20, 8, 2, dim), "float32",
                                      device) == dim


@pytest.fixture
def wide_rows(monkeypatch):
    """Pools held at twice the head_dim, as a TPU holds rows under 128."""
    monkeypatch.setattr(kvcache, "pool_row_width",
                        lambda shape, dtype, device: 2 * shape[-1])


@pytest.mark.parametrize("kind", KINDS)
def test_pools_with_wider_rows_serve_the_same_tokens(kind, wide_rows):
    """``write_kv`` zero-pads the rows it is handed, the paged attention
    reads ``head_dim`` of them: nothing but the pools' shape changes."""
    prompts = [np.arange(1, 12, dtype=np.int32),
               np.arange(5, 45, dtype=np.int32)]
    with _engine(kind) as eng:
        leaf = jax.tree_util.tree_leaves(eng._cache.k_pool)[0]
        assert leaf.shape[-1] == 16
        eng.warmup()
        got = [eng.generate(p, 6, timeout=300) for p in prompts]
        assert eng.stats()["steady_state_recompiles"] == 0
        model, params = eng._model, eng._params
    if kind == "paged":
        want = [model.reference_generate(params, p, 6) for p in prompts]
    else:
        from mxnet_tpu.serving import afmoe_reference as ref

        want = []
        for p in prompts:
            seq = list(p)
            for _ in range(6):
                logits = ref.forward_logits(model.cfg, params,
                                            np.asarray(seq, np.int32))
                seq.append(int(np.argmax(np.asarray(logits)[-1])))
            want.append(np.asarray(seq[len(p):], np.int32))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_write_kv_pads_rows_to_the_pools_width(wide_rows):
    cache, _groups_ = _cache("paged")
    rows = jnp.ones((2, 2, 8), jnp.float32)
    k_new, _v = write_kv(cache.k_pool, cache.v_pool, 1, rows, rows,
                         jnp.asarray([1, 2], jnp.int32),
                         jnp.asarray([0, 3], jnp.int32))
    got = np.asarray(k_new[1])
    assert got.shape == (20, PAGE, 2, 16)
    assert (got[1, 0, :, :8] == 1.0).all() and (got[1, 0, :, 8:] == 0.0).all()
    assert got.sum() == 2 * 2 * 8


@pytest.mark.parametrize("launch", ["decode", "spec", "window", "chunk"])
def test_kernel_reads_head_dim_of_wider_rows(launch):
    """Every launch of ``_paged_kernel`` (interpret mode) over pools whose
    rows carry zero lanes beyond ``head_dim`` equals the reference over the
    narrow pools: the query grows to the rows, the result is cut back."""
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(7)
    s, h, kh, d, pages, cols = 3, 4, 2, 8, 13, 4
    kp = jnp.asarray(rng.randn(pages, PAGE, kh, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pages, PAGE, kh, d).astype(np.float32))
    wide = [jnp.pad(x, ((0, 0),) * 3 + ((0, d),)) for x in (kp, vp)]
    pt = jnp.asarray(rng.permutation(np.arange(1, pages))[:s * cols]
                     .reshape(s, cols).astype(np.int32))
    lens = jnp.asarray([5, 0, 27], jnp.int32)
    if launch == "decode":
        q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
        got = pk.ragged_paged_attention(q, *wide, pt, lens, interpret=True)
        want = pk.paged_attention_reference(q, kp, vp, pt, lens)
    elif launch == "spec":
        q = jnp.asarray(rng.randn(s, 2, h, d).astype(np.float32))
        rows = jnp.asarray([4, 5, 0, 0, 26, 27], jnp.int32)
        got = pk.ragged_spec_attention(q, *wide, pt, rows, interpret=True)
        want = pk.paged_spec_attention_reference(
            q.reshape(s * 2, h, d), kp, vp, pt, rows).reshape(q.shape)
    elif launch == "window":
        q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
        lens = jnp.asarray([5, 0, 60], jnp.int32)       # wraps the ring
        got = pk.ragged_window_attention(q, *wide, pt, lens, 24,
                                         interpret=True)
        want = pk.paged_window_attention_reference(q, kp, vp, pt, lens, 24)
    else:
        q = jnp.asarray(rng.randn(6, h, d).astype(np.float32))
        args = (pt[2], jnp.asarray(20, jnp.int32), jnp.asarray(5, jnp.int32))
        got = pk.ragged_paged_attention(
            q, *wide, jnp.broadcast_to(pt[2][None], (6, cols)),
            jnp.where(jnp.arange(6) < 5, 21 + jnp.arange(6), 0),
            q_pos=20 + jnp.arange(6), interpret=True)
        want = pk.paged_prefill_attention(q, kp, vp, *args)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the dense reference over the wide pools reads the same rows
    if launch == "decode":
        np.testing.assert_allclose(
            np.asarray(pk.paged_attention_reference(q, *wide, pt, lens)),
            np.asarray(want), rtol=1e-6, atol=1e-6)
