"""Mixture-of-experts layer for one chip's share of an expert-parallel model.

Two pure functions, traced inside a jitted step with static shapes:

:func:`route`
    the router over ALL experts of the model: scores, bias-corrected top-k
    selection, normalised and scaled weights. Float32 at ``highest``
    precision whatever the activations' type (a ``hidden x num_experts``
    product; a pick that flips is then upstream rounding, not the gate's).
:func:`expert_layer`
    what the experts HELD HERE (``held=(first, count)``: experts ``first ..
    first+count-1`` of the model) add to each token, plus the shared expert
    every chip computes alike. Picks of experts held elsewhere add nothing —
    what the absent experts would have added is left out, by contract: the
    other chips of the deployment own that part (docs/serving.md "Expert
    layer"). No capacity factor and no dropped token: the ``T * top_k``
    (token, pick) rows are sorted by held expert and multiplied as ONE
    grouped product whose rows per expert are data, not shape, so any
    imbalance — every row to one expert included — is computed in full.

Device names a trace can be searched for: the grouped product and the shared
expert run the Pallas kernel :func:`grouped_matmul` under the names
``mx_moe_gmm`` and ``mx_moe_shared`` (the custom call ``%mx_moe_gmm.<n>``);
every operation of the layer sits under one of four ``jax.named_scope``s,
parts of the program (``telemetry.PROGRAM_PARTS``): ``mx_moe_route`` (scores,
top-k, the sort into groups), ``mx_moe_experts``, ``mx_moe_shared`` and
``mx_moe_combine`` — in the compiled program's ``op_name`` metadata, from
which ``telemetry.program_parts`` maps XLA's own instruction names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _pad_up
from .row_blocks import row_blocks

__all__ = ["route", "expert_layer", "grouped_matmul",
           "grouped_matmul_reference", "matmul", "split_terms"]

_STACK_ROWS = 256  # up to here a product is bound by reading its weights
_ROW_TILE = 128   # rows of one grouped-product tile
_COL_TILE = 256   # output columns of one tile


def route(h, wr, expert_bias, top_k, route_norm=True, route_scale=1.0,
          n_group=1, topk_group=1):
    """Router over all ``N`` experts. ``h``: ``(T, E)``; ``wr``: ``(E, N)``;
    ``expert_bias``: ``(N,)`` — added to the scores for SELECTION only.
    Returns ``(sel (T, top_k) int32, weights (T, top_k) float32)``:
    ``s = sigmoid(h wr)``, ``sel = top_k(s + bias)``, ``w = s[sel]``,
    normalised to sum 1 (``route_norm``) and scaled by ``route_scale``.

    ``n_group`` > 1: group-limited selection (DeepSeek-V3's): the experts are
    ``n_group`` runs of ``N / n_group`` consecutive ones, a group's score is
    the sum of its two largest ``s + bias``, and the top-k is taken over the
    experts of the ``topk_group`` best groups only."""
    with jax.named_scope("mx_moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), wr.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        biased = scores + expert_bias.astype(jnp.float32)
        if n_group > 1:
            t, n = biased.shape
            best2, _ = lax.top_k(biased.reshape(t, n_group, n // n_group), 2)
            _, groups = lax.top_k(best2.sum(axis=-1), topk_group)
            kept = (groups[:, :, None] == jnp.arange(n_group)[None, None]
                    ).any(axis=1)
            biased = jnp.where(jnp.repeat(kept, n // n_group, axis=1),
                               biased, -jnp.inf)
        _, sel = lax.top_k(biased, top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        if route_norm:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * route_scale


# ---------------------------------------------------------------------------
# float32 activations against bfloat16 weights
# ---------------------------------------------------------------------------

def split_terms(x, dtype, in_kernel=False):
    """``x`` as the terms that meet weights of ``dtype`` on the MXU. Float32
    activations against bfloat16 weights enter as TWO bfloat16 terms, ``hi =
    bf16(x)`` and ``lo = bf16(x - hi)`` (``x = hi + lo`` to 2**-17): both
    products are exact in the float32 accumulator, so the result is the
    float32 product to rounding, at two MXU passes over weights that are
    read once. One pass (the chip's default for a float32 operand) rounds
    the activations to 8 bits of mantissa at every product, which is all
    that tells this path from one that keeps its activations in bfloat16 —
    and enough to flip the router's near-ties (PERF.md section 6, PR 27)."""
    if x.dtype == dtype or dtype != jnp.bfloat16:
        return (x.astype(dtype),)
    if in_kernel:   # Mosaic lowers no reduce_precision, and drops no cast
        hi = x.astype(dtype)
        return hi, (x - hi.astype(x.dtype)).astype(dtype)
    # reduce_precision, not a cast there and back: XLA is free to drop a
    # float32 -> bfloat16 -> float32 round trip (excess precision), which
    # would leave lo = 0 and one rounded term
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(dtype), (x - hi).astype(dtype)


def matmul(x, w):
    """``x (T, K) @ w (K, N)`` in float32, ``x`` entering by
    :func:`split_terms`. Few rows (a decode tick, bound by reading ``w``):
    the terms are stacked into one product, so ``w`` is read once; many rows
    (a prefill, bound by arithmetic): a product a term."""
    terms = split_terms(x, w.dtype)
    if len(terms) == 1:
        return jnp.dot(terms[0], w, preferred_element_type=jnp.float32)
    t = x.shape[0]
    if t <= _STACK_ROWS:
        both = jnp.dot(jnp.concatenate(terms), w,
                       preferred_element_type=jnp.float32)
        return both[:t] + both[t:]
    return sum(jnp.dot(term, w, preferred_element_type=jnp.float32)
               for term in terms)


# ---------------------------------------------------------------------------
# grouped matrix product
# ---------------------------------------------------------------------------

def _work_items(group_sizes, rows, tm):
    """The (row tile, group) pairs a grouped product has to visit, in row
    order: ``(group_starts (G+1,), item_group (W,), item_tile (W,),
    n_items)`` with ``W = rows // tm + G`` the static upper bound. A tile
    that two groups share is visited once for each; a group with no row is
    never visited."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = jnp.cumsum(n_tiles)
    w = jnp.arange(rows // tm + g, dtype=jnp.int32)
    grp = jnp.minimum(jnp.searchsorted(item_ends, w, side="right"),
                      g - 1).astype(jnp.int32)
    tile = first[grp] + (w - (item_ends - n_tiles)[grp])
    tile = jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32)
    bounds = jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32)
    return bounds, grp, tile, item_ends[-1].astype(jnp.int32)


def _gmm_kernel(bounds_ref, grp_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                *, tm):
    """One (column tile, work item) cell: the item's row tile times its
    group's weights, stored into the rows of the tile that belong to the
    group; the tile's other rows keep what an earlier item of the same tile
    stored (consecutive items of one tile share the output block)."""
    w = pl.program_id(1)
    grp = grp_ref[w]
    lo = bounds_ref[grp]
    hi = bounds_ref[grp + 1]
    prod = None
    for term in split_terms(lhs_ref[...], rhs_ref.dtype, in_kernel=True):
        # DEFAULT precision said out loud: under a caller's
        # `default_matmul_precision("highest")` Mosaic is asked for an fp32
        # contraction of bfloat16 operands and refuses it
        part = lax.dot_general(
            term, rhs_ref[...], (((1,), (0,)), ((), ())),
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        prod = part if prod is None else prod + part
    row = tile_ref[w] * tm + lax.broadcasted_iota(jnp.int32, prod.shape, 0)
    mine = jnp.logical_and(row >= lo, row < hi)
    out_ref[...] = jnp.where(mine, prod,
                             out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def grouped_matmul(lhs, rhs, group_sizes, name="mx_moe_gmm", interpret=None,
                   zero=True):
    """``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``:
    ``lhs`` ``(M, K)`` with its rows sorted by group, ``rhs`` ``(G, K, N)``,
    ``group_sizes`` ``(G,)`` int32 (sum <= M). Rows past the last group come
    back ZERO. A Pallas kernel on a TPU: the operands meet in the weights'
    type on the MXU (float32 rows against bfloat16 weights as two terms:
    :func:`split_terms`), float32 accumulation, float32 out; only tiles that hold
    a row are visited and only the weights of groups that have one are
    read. ``zero=False`` leaves the rows past the last group as the buffer
    held them (tiles no item visited are never written): for a caller whose
    next pass over the rows zeroes them itself
    (:func:`~mxnet_tpu.ops.row_blocks.row_blocks`). Off the TPU
    :func:`grouped_matmul_reference`, unless ``interpret`` asks for the
    kernel."""
    if interpret is None:
        if _interpret():
            return grouped_matmul_reference(lhs, rhs, group_sizes)
        interpret = False
    m, k = lhs.shape
    g, _, n = rhs.shape
    tm = min(_ROW_TILE, _pad_up(m, 8))
    mp = _pad_up(m, tm)
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    tn = _COL_TILE if n % _COL_TILE == 0 else n
    group_sizes = group_sizes.astype(jnp.int32)
    bounds, grp, tile, n_items = _work_items(group_sizes, mp, tm)
    # at least one item, so that the grid is never empty: with no row at
    # all, item 0 is group 0 with no row of its own and stores nothing
    n_items = jnp.maximum(n_items, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, n_items),
        in_specs=[
            pl.BlockSpec((tm, k), lambda ni, w, b, gr, ti: (ti[w], 0)),
            pl.BlockSpec((None, k, tn),
                         lambda ni, w, b, gr, ti: (gr[w], 0, ni)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda ni, w, b, gr, ti: (ti[w], ni)),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(bounds, grp, tile, lhs, rhs)
    if not zero:
        return out[:m]
    # tiles no item visited, and rows of a visited tile past the last
    # group, hold whatever the buffer held
    live = jnp.arange(mp, dtype=jnp.int32)[:, None] < bounds[-1]
    return jnp.where(live, out, 0.0)[:m]


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """:func:`grouped_matmul` as ``jax.lax.ragged_dot`` (the CPU path and
    the kernel's parity oracle), rows past the last group zeroed."""
    out = lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                         group_sizes.astype(jnp.int32))
    live = jnp.arange(lhs.shape[0], dtype=jnp.int32)[:, None] \
        < group_sizes.sum()
    return jnp.where(live, out, 0.0)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _swiglu(x, weights, group_sizes, name, rows=None):
    """``(silu(x w1) * (x w3)) w2`` of each row under its group's weights
    (``weights``: ``w1``/``w3`` ``(G, E, M)``, ``w2`` ``(G, M, E)``), zero
    in the rows past the last group. ``rows``: the groups' sum as a traced
    count (a prefill's), or ``None`` — with it the activation and the result
    are passes over the row blocks that hold a row
    (:func:`~mxnet_tpu.ops.row_blocks.row_blocks`), and it is they that zero
    what the products' tiles did not write."""
    zero = rows is None
    gate = grouped_matmul(x, weights["w1"], group_sizes, name=name, zero=zero)
    up = grouped_matmul(x, weights["w3"], group_sizes, name=name, zero=zero)
    act = row_blocks(lambda g, u: jax.nn.silu(g) * u, (gate, up), rows)
    out = grouped_matmul(act, weights["w2"], group_sizes, name=name,
                         zero=zero)
    return row_blocks(lambda o: o, (out,), rows)


# a jit of its own: a model's expert layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("held",))
def expert_layer(h, route, experts, held, shared=None, valid=None,
                 length=None):
    """What this chip's experts add to each token, plus the shared expert.

    ``h``: ``(T, E)``; ``route``: ``(sel, weights)`` of :func:`route` over
    the model's ``N`` experts; ``experts``: ``{"w1", "w3": (count, E, M),
    "w2": (count, M, E)}`` — the weights of experts ``first .. first +
    count - 1`` (``held = (first, count)``); ``shared``: ``{"w1", "w3": (E,
    M), "w2": (M, E)}`` or ``None``; ``valid``: ``(T,)`` bool — rows that
    are real tokens (padding of a prefill rung and idle decode slots are
    routed nowhere and counted nowhere). ``length``: a traced int32 count,
    or ``None`` — where the real tokens are the first ``length`` rows (a
    prompt on its rung), the row-wise passes visit only the row blocks that
    hold work (:func:`~mxnet_tpu.ops.row_blocks.row_blocks`): the shared
    expert and the combine those of the first ``length`` tokens, the gather
    and the activation of the sorted (token, pick) rows those of the rows
    routed to experts held here, which the sort puts first. A decode tick
    hands none and runs straight-line code over every row.

    Returns ``(out (T, E) float32, rows (count + 1,) int32)``: ``out =
    shared(h) + sum over a token's picks that are held here of w_e *
    expert_e(h)`` (with ``length``: zero for the tokens of the row blocks
    behind it); ``rows[e]`` the (token, pick) rows expert ``first + e``
    received and ``rows[count]`` those routed to experts held elsewhere.
    """
    sel, weights = route
    first, count = held
    t, top_k = sel.shape
    n_rows = t * top_k
    with jax.named_scope("mx_moe_route"):
        local = sel.reshape(n_rows) - first
        here = jnp.logical_and(local >= 0, local < count)
        # sort key: a held expert's own index, then the absent picks, then
        # the rows that are no token at all
        key = jnp.where(here, local, count)
        if valid is not None:
            key = jnp.where(jnp.repeat(valid, top_k), key, count + 1)
        rows = jnp.zeros((count + 2,), jnp.int32).at[key].add(1)[:count + 1]
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = rows[:count]
        n_held = None if length is None else group_sizes.sum()
        x = row_blocks(lambda o: h[o // top_k], (order,), n_held)
    with jax.named_scope("mx_moe_experts"):
        y = _swiglu(x, experts, group_sizes, "mx_moe_gmm", n_held)
    with jax.named_scope("mx_moe_combine"):
        # back to (token, pick) order; rows past the held groups are zero
        back = jnp.zeros((n_rows,), jnp.int32).at[order].set(
            jnp.arange(n_rows, dtype=jnp.int32))
        out = row_blocks(
            lambda w, at: jnp.einsum("tk,tke->te", w.astype(jnp.float32),
                                     y[at.reshape(-1)].reshape(at.shape
                                                               + (-1,))),
            (weights, back.reshape(t, top_k)), length)
    if shared is not None:
        with jax.named_scope("mx_moe_shared"):
            whole = jnp.asarray([t], jnp.int32) if length is None \
                else jnp.reshape(length, (1,)).astype(jnp.int32)
            alike = _swiglu(h, {k: v[None] for k, v in shared.items()},
                            whole, "mx_moe_shared", length)
        with jax.named_scope("mx_moe_combine"):
            out = out + alike
    return out, rows
