"""The KV pools as the decode step holds them (tier-1, CPU): one array a
layer, in the pytree the cache, the engine's four jits and ``write_kv``
thread whole — for the one group of a model that declares nothing, for both
groups of a window model and for the latent pools and the slot state of a
recurrent one, each built by ``kvcache.make_cache``. What the pools cost on
the chip (no whole-pool copy, no per-layer slice) is
``tests/test_chip_compile.py``'s to hold.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.resilience import hbm
from mxnet_tpu.serving import kvcache
from mxnet_tpu.serving.kvcache import make_cache, write_kv

PAGE, WINDOW = 8, 32
AFMOE = dict(vocab_size=96, hidden_size=48, num_attention_heads=12,
             num_key_value_heads=2, head_dim=8, intermediate_size=96,
             moe_intermediate_size=32,
             layer_types=["sliding_attention"] * 4 + ["full_attention"],
             num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
             sliding_window=WINDOW, held_experts=[4, 4], route_scale=2.448,
             mup_enabled=True)
LING = dict(vocab_size=96, hidden_size=48, num_attention_heads=4, head_dim=8,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=96, moe_intermediate_size=32,
            layer_types=["kda", "kda", "kda", "mla"], num_dense_layers=1,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            held_experts=[0, 8], routed_scaling_factor=2.5)
KINDS = ["paged", "grouped"]        # K/V groups
ALL_KINDS = KINDS + ["latent"]


def _model(kind):
    if kind == "paged":
        return serving.TinyDecoder(vocab_size=32, num_layers=3, num_heads=4,
                                   head_dim=8, num_kv_heads=2)
    if kind == "grouped":
        return serving.AfmoeDecoder(**AFMOE)
    return serving.LingDecoder(**LING)


def _cache(kind):
    """``(cache, [(layers, pages) of each group])``."""
    name = "pools-%d" % np.random.randint(1 << 30)
    if kind == "paged":
        return make_cache(_model(kind), 3, 64, page_size=PAGE, num_pages=20,
                          name=name), [(3, 20)]
    if kind == "latent":
        return make_cache(_model(kind), 3, 64, page_size=PAGE, num_pages=20,
                          name=name), [(1, 20)]
    return make_cache(_model(kind), 3, 128, page_size=PAGE,
                      num_pages={"full": 40, "window": 12},
                      name=name), [(1, 40), (4, 12)]


def _engine(kind, **kw):
    model = _model(kind)
    if kind == "paged":
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("prefill_buckets", (8, 16))
    else:
        kw.update(max_seq_len=128, page_size=PAGE, prefill_buckets=(16, 64),
                  prefix_cache=False, prefill_chunk=0)
    kw.setdefault("num_slots", 3)
    kw.setdefault("timeout_ms", 0)
    kw.setdefault("name", "pools%d" % np.random.randint(1 << 30))
    return serving.DecodeEngine(model, model.init_params(0), **kw)


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_a_pool_is_one_array_a_layer(kind):
    cache, groups = _cache(kind)
    for group, (layers, pages) in zip(cache.groups, groups):
        # a latent group: the layers themselves, no head axis, no V pool
        pools, row = ((group.pools,), (20,)) if kind == "latent" \
            else (group.pools, (2, 8))
        assert len(pools) == (1 if kind == "latent" else 2)
        for leaves in pools:
            assert isinstance(leaves, tuple)
            assert [x.shape for x in leaves] \
                == [(pages, PAGE) + row] * layers
            assert len({id(x) for x in leaves}) == layers
    # what a model is handed: one group's pools bare, several as a tuple
    pools, state = cache.operands
    assert pools is cache.groups[0].pools if len(groups) == 1 \
        else pools == tuple(g.pools for g in cache.groups)
    assert (state == ()) == (kind != "latent")


@pytest.mark.parametrize("kind", KINDS)
def test_write_kv_replaces_one_layers_array_and_no_other(kind):
    cache, groups = _cache(kind)
    rows = jnp.asarray(np.arange(3 * 2 * 8, dtype=np.float32)
                       .reshape(3, 2, 8) + 1.0)
    pages = jnp.asarray([1, 1, 2], jnp.int32)
    offs = jnp.asarray([6, 7, 0], jnp.int32)
    for group, (layers, _p) in zip(cache.groups, groups):
        k_old, v_old = group.pools
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


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_swap_and_reset_pools_take_the_pytree(kind):
    cache, groups = _cache(kind)
    given = jax.tree_util.tree_map(lambda x: x + 1.0, cache.operands)
    cache.swap_pools(*given)
    assert jax.tree_util.tree_structure(cache.operands) \
        == jax.tree_util.tree_structure(given)
    for held, its in zip(jax.tree_util.tree_leaves(cache.operands),
                         jax.tree_util.tree_leaves(given)):
        assert held is its
    cache.reset_pools()
    pools, state = cache.operands
    assert len(jax.tree_util.tree_leaves(pools)) \
        == (1 if kind == "latent" else 2) * sum(n for n, _p in groups)
    assert all(float(jnp.abs(x).sum()) == 0.0
               for x in jax.tree_util.tree_leaves((pools, state)))
    assert [x.shape for x in jax.tree_util.tree_leaves(cache.operands)] \
        == [x.shape for x in jax.tree_util.tree_leaves(given)]
    assert cache.paged_bytes == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(pools))
    assert cache.state_bytes == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state))


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_cow_copies_the_page_in_every_layers_array_and_no_state(kind):
    with _engine(kind) as eng:
        pools, state = eng._cache.operands
        k_marked = jax.tree_util.tree_map(lambda x: x.at[3].set(7.0), pools)
        copied, kept = eng._cow_jit(k_marked, state,
                                    jnp.asarray(3, jnp.int32),
                                    jnp.asarray(5, jnp.int32))
        assert jax.tree_util.tree_structure(copied) \
            == jax.tree_util.tree_structure(pools)
        for leaf in jax.tree_util.tree_leaves(copied):
            got = np.asarray(leaf)
            assert (got[5] == 7.0).all() and (got[3] == 7.0).all()
            assert (got[4] == 0.0).all()
        # what is not paged is not a page to copy
        assert jax.tree_util.tree_structure(kept) \
            == jax.tree_util.tree_structure(state)
        assert all(float(jnp.abs(x).sum()) == 0.0
                   for x in jax.tree_util.tree_leaves(kept))


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_pools_dead_reads_one_leaf(kind):
    with _engine(kind) as eng:
        assert eng._pools_dead() is False
        first = jax.tree_util.tree_leaves(eng._cache.operands)[0]
        first.delete()      # what a failed step does to a donated buffer
        assert eng._pools_dead() is True
        eng._cache.reset_pools()
        assert eng._pools_dead() is False


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_governor_bound_and_stats_count_every_leaf(kind):
    with _engine(kind) as eng:
        leaves = jax.tree_util.tree_leaves(eng._cache.operands)
        bound = hbm.governor().oom_report()["bounds_bytes"][
            "serving.%s.kv_pool" % eng._name]
        assert bound == sum(x.nbytes for x in leaves) > 0
        stats = eng.stats()
        assert stats["kv_pool_leaves"] == len(leaves)
        assert stats["decode_step_temp_bytes"] is None      # no warm-up yet
        eng.warmup()
        assert eng.stats()["decode_step_temp_bytes"] > 0
        assert eng.stats()["steady_state_recompiles"] == 0


@pytest.mark.parametrize("kind", ALL_KINDS)
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


@pytest.mark.parametrize("kind,groups,leaves", [
    ("paged", 1, 6), ("grouped", 2, 10), ("latent", 1, 7)])
def test_the_seam_between_the_cache_the_engine_and_the_model(kind, groups,
                                                            leaves):
    """What the three served models are handed, by what they declare and by
    nothing else: the step's packed operand has ``4 + groups + 1`` rows, a
    prefill's ``2 + groups`` and one for the slot where a layer keeps slot
    state; ``pools``, ``page_tables`` and ``write_pages`` have one entry a
    group, one group's bare; ``state`` is ``()`` exactly where no layer is
    ``slot``; and ``stats()`` counts the leaves it counted."""
    with _engine(kind) as eng:
        cache, model = eng._cache, eng._model
        slot_layers = [st for st in kvcache.layer_states(model)
                       if st[0] == "slot"]
        assert cache.num_groups == groups == len(cache.tables) \
            == len(cache.walk_groups())
        packed, _drafts = eng._pack_step([])
        assert packed.shape == (4 + groups + 1, eng.num_slots) \
            and eng._packed_rows == 4 + groups + 1
        assert eng._prefill_rows == 2 + groups + bool(slot_layers)
        pools, state = cache.operands
        tables = eng._device_page_table()
        cache.reserve(0, 40)
        pages, offs = cache.write_slots(0, 0, 40)
        at = [page_at(0, 39) for page_at in cache.page_lookups()]
        cache.free(0)
        assert pages.shape == (groups, 40) and offs.shape == (40,)
        assert at == list(pages[:, 39])
        if groups == 1:     # bare: the group's own, no tuple around it
            assert pools is cache.groups[0].pools
            assert tables.shape == cache.groups[0].page_table.shape
            assert cache.per_group(list(pages)).shape == (40,)
        else:
            assert isinstance(pools, tuple) and len(pools) == groups
            assert [t.shape for t in tables] \
                == [g.page_table.shape for g in cache.groups]
            assert len(cache.per_group(list(pages))) == groups
        assert (state == ()) == (not slot_layers)
        assert len(state) == len(slot_layers)
        # the programs take exactly these operands, and hand them back
        out = jax.eval_shape(eng._step, eng._params, jnp.asarray(packed),
                             eng._no_prev, pools, state, tables)
        assert jax.tree_util.tree_structure(out[1:]) \
            == jax.tree_util.tree_structure((pools, state))
        assert eng.stats()["kv_pool_leaves"] == leaves == len(
            jax.tree_util.tree_leaves((pools, state)))


@pytest.mark.parametrize("states,said", [
    ([("paged",), ("latent", 20)], "paged + latent 20"),
    ([("paged",), ("slot", ((2, 2),))], "paged + slot"),
    ([("latent", 20), ("latent", 20)], "latent 20"),
    ([("ring", 16), ("ring", 32), ("paged",)], "paged + ring 16 + ring 32"),
    ([("ring", 16), ("latent", 20), ("slot", ((2, 2),))],
     "ring 16 + latent 20 + slot")])
def test_a_mix_of_kinds_no_served_model_has_is_refused_by_name(states, said):
    class Mixed:
        num_layers = len(states)
        num_kv_heads, head_dim = 2, 8
        layer_state = states

    assert kvcache.layer_states(Mixed()) == states
    with pytest.raises(kvcache.MXNetError, match=said.replace("+", r"\+")
                       + " together"):
        make_cache(Mixed(), 2, 64, page_size=PAGE)


def test_layers_are_placed_in_the_order_of_the_caches_groups():
    """``place_layers`` is where a model learns its layers' places: full
    before window whatever the layers' order, a slot layer in ``state``."""
    assert kvcache.place_layers(
        [("ring", 32), ("paged",), ("ring", 32), ("paged",)]) \
        == [(1, 0), (0, 0), (1, 1), (0, 1)]
    assert kvcache.place_layers(
        [("slot", ((2, 2),)), ("latent", 20), ("slot", ((2, 2),))]) \
        == [(None, 0), (0, 0), (None, 1)]
    assert kvcache.place_layers([("paged",)] * 2) == [(0, 0), (0, 1)]


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
        leaf = jax.tree_util.tree_leaves(eng._cache.operands)[0]
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
    k_new, _v = write_kv(*cache.operands[0], 1, rows, rows,
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
