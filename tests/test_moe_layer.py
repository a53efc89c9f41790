"""``mxnet_tpu.ops.moe``: the router, the grouped product and the expert
layer of one chip's share of an expert-parallel model.

The reference is :mod:`mxnet_tpu.serving.afmoe_reference` (every expert over
every row, masked). Tolerance 2e-5 absolute on values of order one: both
sides compute in float32, the program sums a token's picks in another order
than the reference's loop over experts, and nothing else differs; bfloat16
operands miss it by three orders of magnitude (asserted below).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import moe
from mxnet_tpu.serving import afmoe_reference as ref

TOL = 2e-5
E, M, N, K = 48, 32, 16, 4     # hidden, expert width, experts, top-k


def _layer(seed=0):
    """A reference expert layer with ALL ``N`` experts' weights."""
    cfg = {"hidden_size": E, "head_dim": 8, "num_attention_heads": 12,
           "num_key_value_heads": 2, "intermediate_size": 96,
           "moe_intermediate_size": M, "layer_types": ["full_attention"],
           "num_dense_layers": 0, "num_experts": N,
           "num_experts_per_tok": K, "held_experts": [0, N],
           "vocab_size": 8, "route_norm": True, "route_scale": 2.448}
    layer = ref.init_params(cfg, seed)["layers"][0]
    return cfg, layer


def _rows(t, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(t, E), jnp.float32)


def _program(cfg, layer, hx, held, shared=True, valid=None):
    picks = moe.route(hx, layer["router"], layer["expert_bias"], K,
                      cfg["route_norm"], cfg["route_scale"])
    first, count = held
    experts = {k: v[first:first + count]
               for k, v in layer["experts"].items()}
    return moe.expert_layer(hx, picks, experts, held,
                            shared=layer["shared"] if shared else None,
                            valid=valid)


def test_route_is_sigmoid_bias_corrected_topk_normalised_and_scaled():
    cfg, layer = _layer()
    hx = _rows(9)
    sel, w = moe.route(hx, layer["router"], layer["expert_bias"], K, True,
                       2.448)
    s = 1.0 / (1.0 + np.exp(-np.asarray(hx, np.float64)
                            @ np.asarray(layer["router"], np.float64)))
    want = np.argsort(-(s + np.asarray(layer["expert_bias"])), axis=1)[:, :K]
    assert np.array_equal(np.sort(np.asarray(sel), 1), np.sort(want, 1))
    picked = np.take_along_axis(s, np.asarray(sel), 1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(1, keepdims=True) * 2.448,
        rtol=1e-5)
    # the bias moves the selection and never the weight
    _sel, w0 = moe.route(hx, layer["router"], layer["expert_bias"] * 0, K,
                         False, 1.0)
    assert float(w0.max()) <= 1.0


@pytest.mark.parametrize("sizes", [
    [40, 0, 100, 3, 57], [0, 0, 0, 0, 0], [256, 0, 0, 0, 0],
    [0, 0, 0, 0, 256], [1, 1, 1, 1, 1], [128, 128, 0, 0, 0]])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    """Interpret mode, two row tiles: groups that share a tile, empty
    groups, every row in one group, no row at all; rows past the last group
    come back zero."""
    rng = np.random.RandomState(len(sizes) + sum(sizes))
    lhs = jnp.asarray(rng.randn(256, 32), jnp.float32)
    rhs = jnp.asarray(rng.randn(len(sizes), 32, 256), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe.grouped_matmul(lhs, rhs, gs, interpret=True)
    want = moe.grouped_matmul_reference(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert not np.asarray(got)[sum(sizes):].any()


def test_expert_layer_matches_the_reference_and_counts_its_rows():
    cfg, layer = _layer()
    hx = _rows(23)
    out, rows = _program(cfg, layer, hx, (4, 6))
    ex = {k: v[4:10] for k, v in layer["experts"].items()}
    want = ref._mlp(dict(cfg, held_experts=[4, 6]),
                    dict(layer, experts=ex), hx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=TOL)
    sel, _w = ref.route(cfg, layer, hx)
    sel = np.asarray(sel)
    assert np.asarray(rows).tolist() == \
        [int((sel == e).sum()) for e in range(4, 10)] \
        + [int(((sel < 4) | (sel >= 10)).sum())]
    assert int(rows.sum()) == 23 * K


def test_bfloat16_operands_miss_the_tolerance():
    cfg, layer = _layer()
    hx = _rows(23)
    want = ref._mlp(cfg, layer, hx)
    low = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
        {"experts": layer["experts"], "shared": layer["shared"]})
    out, _rows_ = _program(cfg, dict(layer, **low),
                           hx.astype(jnp.bfloat16).astype(jnp.float32),
                           (0, N))
    assert float(jnp.abs(out - want).max()) > 100 * TOL


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test: each of 8 chips holds 2 of the 16 experts and
    computes its experts' part; the parts, with the shared expert (which
    every chip computes alike) counted once, are the uncut layer."""
    cfg, layer = _layer(seed=3)
    hx = _rows(31, seed=4)
    whole = ref._mlp(cfg, layer, hx)                  # all 16 experts
    parts, routed = 0.0, 0
    for chip in range(8):
        out, rows = _program(cfg, layer, hx, (2 * chip, 2), shared=False)
        parts = parts + out
        routed += int(rows[:2].sum())
    shared = ref.expert_mlp(hx, **layer["shared"])
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), atol=TOL)
    assert routed == 31 * K        # every pick was held by exactly one chip


def test_every_row_to_one_expert_drops_nothing():
    """No capacity: a bias that sends every token to experts 1, 8, 9, 10
    gives held expert 1 all 40 rows (its neighbours none) and the result is
    still the reference's."""
    cfg, layer = _layer(seed=5)
    bias = np.zeros(N, np.float32)
    bias[[1, 8, 9, 10]] = 10.0
    layer = dict(layer, expert_bias=jnp.asarray(bias))
    hx = _rows(40, seed=6)
    out, rows = _program(cfg, layer, hx, (0, 4))
    assert np.asarray(rows).tolist() == [0, 40, 0, 0, 120]
    ex = {k: v[:4] for k, v in layer["experts"].items()}
    want = ref._mlp(dict(cfg, held_experts=[0, 4]),
                    dict(layer, experts=ex), hx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=TOL)
    # and every pick held here: 160 rows through four experts
    bias[:] = 0.0
    bias[:4] = 10.0
    out, rows = _program(cfg, dict(layer, expert_bias=jnp.asarray(bias)),
                         hx, (0, 4))
    assert np.asarray(rows).tolist() == [40, 40, 40, 40, 0]


def test_rows_that_are_no_token_are_routed_and_counted_nowhere():
    cfg, layer = _layer(seed=7)
    hx = _rows(12, seed=8)
    valid = jnp.arange(12) < 7
    out, rows = _program(cfg, layer, hx, (0, 8), valid=valid)
    part, part_rows = _program(cfg, layer, hx[:7], (0, 8))
    assert np.array_equal(np.asarray(rows), np.asarray(part_rows))
    np.testing.assert_allclose(np.asarray(out[:7]), np.asarray(part),
                               atol=TOL)
    # an invalid row keeps the shared expert's part and no routed part
    shared = ref.expert_mlp(hx[7:], **layer["shared"])
    np.testing.assert_allclose(np.asarray(out[7:]), np.asarray(shared),
                               atol=TOL)


def test_expert_layer_has_static_shapes_under_any_routing():
    """One trace serves every routing: rows per expert are data."""
    cfg, layer = _layer(seed=9)
    traces = []

    @jax.jit
    def run(hx, bias):
        traces.append(1)
        return _program(cfg, dict(layer, expert_bias=bias), hx, (0, 4))

    for seed in range(3):
        bias = jnp.asarray(np.random.RandomState(seed).randn(N) * 5,
                           jnp.float32)
        run(_rows(16, seed), bias)
    assert len(traces) == 1


def test_float32_rows_meet_bfloat16_weights_as_two_terms():
    """``moe.matmul``: the float32 product to rounding (2**-16 and better),
    where one bfloat16 pass is off by 2**-9; stacked for few rows, a product
    a term for many — the same numbers; float32 weights pass through."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(512, 256) * 0.05, jnp.float32)
    low = w.astype(jnp.bfloat16)
    for rows in (40, 300):
        x = jnp.asarray(rng.randn(rows, 512), jnp.float32)
        exact = np.asarray(x, np.float64) @ np.asarray(
            low.astype(jnp.float32), np.float64)
        scale = np.abs(exact).max()
        assert np.abs(np.asarray(moe.matmul(x, low)) - exact).max() \
            < 2e-5 * scale
        one = jnp.dot(x.astype(jnp.bfloat16), low,
                      preferred_element_type=jnp.float32)
        assert np.abs(np.asarray(one) - exact).max() > 5e-4 * scale
        np.testing.assert_allclose(np.asarray(moe.matmul(x, w)),
                                   np.asarray(x @ w), rtol=1e-6, atol=1e-6)
    hi, lo = moe.split_terms(x, jnp.bfloat16)
    assert hi.dtype == lo.dtype == jnp.bfloat16
    assert float(jnp.abs(hi.astype(jnp.float32) + lo.astype(jnp.float32)
                         - x).max()) < 2.0 ** -15 * float(jnp.abs(x).max())
    assert len(moe.split_terms(x, jnp.float32)) == 1


def test_grouped_matmul_kernel_keeps_float32_rows_against_bfloat16_weights():
    rng = np.random.RandomState(1)
    lhs = jnp.asarray(rng.randn(64, 512), jnp.float32)
    rhs = jnp.asarray(rng.randn(5, 512, 256) * 0.05,
                      jnp.float32).astype(jnp.bfloat16)
    gs = jnp.asarray([10, 0, 20, 3, 7], jnp.int32)
    got = moe.grouped_matmul(lhs, rhs, gs, interpret=True)
    want = moe.grouped_matmul_reference(lhs, rhs, gs)
    assert float(jnp.abs(got - want).max()) < 1e-4     # one pass: 2e-2
