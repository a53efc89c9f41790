"""A row-wise pass over the row blocks that hold work.

A prefill pads its prompt to a rung of the ladder, and most of what it
computes is a function of ONE ROW: a norm, a projection, the router's scores,
an activation, a gather. :func:`row_blocks` applies such a function to the
first ``ceil(n / block)`` blocks of its operands' rows, ``n`` a traced count:
a ``lax.fori_loop`` with a traced bound over ``dynamic_slice`` /
``dynamic_update_slice`` of the rows, the results' buffers carried (and
updated in place) from zeros — so the blocks behind come back ZERO unvisited,
as :func:`~mxnet_tpu.ops.pallas_kernels.band_attention` returns the query
blocks it does not launch, and the last block's rows from ``n`` on are zeroed
as it is stored: whatever rows ``n ..`` of the operands hold, uninitialised
memory included, none of it comes through. The idea of the kernels' traced
grid extents (``_band_kernel``, ``_paged_kernel``, ``kda_state_step``) in
plain XLA.

Who hands a count in decides what runs: a prefill passes its prompt's
``length`` (and the expert layer the rows routed to experts held here); a
decode tick passes ``None`` and gets ``fn(*operands)`` as straight-line code,
the program it always was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["row_blocks", "row_block", "rows_visited"]

#: rows of a block. On the chip one Trinity expert layer's row-wise passes over
#: a rung of 4096 took 9.43 ms whole and, in blocks of 256 / 512, 5.39 / 5.78
#: with 2252 rows real, 6.86 / 6.71 with 2990, 8.80 / 8.62 with all 4096; max(256,
#: rung / 16) is one of the two at every rung (PERF.md section 6, PR 48)
_BLOCK = 256


def row_block(rows: int) -> int:
    """Rows of one block of a pass over ``rows`` rows (a shape, never data)."""
    return min(_BLOCK, int(rows))


def rows_visited(n: int, rows: int) -> int:
    """Rows of the blocks :func:`row_blocks` visits for a count of ``n`` of
    ``rows`` — host arithmetic (the engine's ``rows_computed`` span
    argument; pinned against the loop's own trip count in
    tests/test_row_blocks.py)."""
    block = row_block(rows)
    return min(-(-int(n) // block) * block, int(rows))


def row_blocks(fn, operands, n=None):
    """``fn(*operands)`` over the row blocks that hold the first ``n`` rows.

    ``operands``: arrays that share their leading extent ``T``; ``fn`` maps a
    block of their rows (the same rows of each) to an array, or a tuple of
    arrays, with as many rows, each row a function of its own row alone —
    whatever else it needs (weights, a table it gathers from) it closes over
    whole. ``n``: a traced int32 count, or ``None``: every row, no loop.
    Returns what ``fn(*operands)`` would in rows ``0 .. n - 1`` and ZEROS
    behind them, having computed the rows of ``ceil(n / block)`` blocks of
    :func:`row_block` rows. Where the block does not divide ``T`` the last
    one starts at ``T - block`` and computes some rows twice, to the same
    values."""
    if n is None:
        return fn(*operands)
    t = operands[0].shape[0]
    block = row_block(t)
    n = jnp.asarray(n, jnp.int32)
    fn = jax.jit(fn)    # traced once, for its shapes and for the loop's body
    shapes = jax.eval_shape(
        fn, *(jax.ShapeDtypeStruct((block,) + x.shape[1:], x.dtype)
              for x in operands))

    def one(i, outs):
        at = jnp.minimum(i * block, t - block)
        real = at + jnp.arange(block, dtype=jnp.int32) < n

        def store(out, y):
            y = jnp.where(real.reshape((block,) + (1,) * (y.ndim - 1)), y,
                          jnp.zeros((), y.dtype))
            return lax.dynamic_update_slice_in_dim(out, y, at, 0)

        return jax.tree_util.tree_map(store, outs, fn(*(
            lax.dynamic_slice_in_dim(x, at, block) for x in operands)))

    return lax.fori_loop(
        0, jnp.clip(-(-n // block), 0, -(-t // block)), one,
        jax.tree_util.tree_map(
            lambda s: jnp.zeros((t,) + s.shape[1:], s.dtype), shapes))
