"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference (MXNet 1.3) predates long-context training; its sequence
story is bucketing + fused RNNs (SURVEY §5.7). For the TPU build, sequence
scaling is a first-class NEW capability expressed through sharding: the
sequence axis of activations is sharded over a mesh axis, and attention —
the one op whose reduction spans the full sequence — is computed with
collectives instead of materializing any (S, S) block on one chip:

- :func:`ring_attention` — blockwise flash-style attention with K/V blocks
  rotating around the ring via ``ppermute`` while queries stay resident;
  per-step compute overlaps the neighbor exchange on ICI. Online-softmax
  (running max/denominator) accumulation keeps the math exact, so the
  result is bit-comparable (up to fp tolerance) to single-device softmax
  attention at ANY sequence length. Memory per chip: O(S/n · S/n) per
  step instead of O(S²).
- :func:`ulysses_attention` — the all-to-all alternative: resharding flips
  (seq-sharded → head-sharded) so each chip runs ordinary full attention
  on a subset of heads, then flips back. One collective each way; best
  when heads ≥ devices and S/n blocks fit in HBM.

Both run under ``jax.shard_map`` over a named mesh axis, compose with the
``dp`` data-parallel axis of :mod:`mxnet_tpu.parallel`, and are reverse-
mode differentiable (shard_map-of-collectives has well-defined vjps).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = ["ring_attention", "ulysses_attention", "sequence_mesh"]


def sequence_mesh(n_devices: Optional[int] = None, devices=None,
                  axis_name: str = "sp") -> Mesh:
    """A 1-D mesh over the sequence axis."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def _online_update(m, l, o, scores, v_blk):
    """Flash-attention accumulator update for one K/V block.

    m: (..., Sq, 1) running max; l: (..., Sq, 1) running denominator;
    o: (..., Sq, D) running numerator; scores: (..., Sq, Skv).
    """
    m_blk = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # rescale previous accumulators to the new max
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum("...qk,...kd->...qd", p, v_blk)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, axis_name, causal, scale, seq_len_local):
    """Per-device body: rotate K/V around the ring, accumulate online
    softmax. q/k/v: (B, H, Sl, D) local blocks."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    neg = jnp.asarray(-jnp.inf, q.dtype)

    row_pos = my_idx * seq_len_local + jnp.arange(sl)  # global query rows

    def step(i, carry):
        k_blk, v_blk, m, l, o = carry
        # the block we hold at step i originated on device (my_idx - i) % n
        src = (my_idx - i) % n
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            col_pos = src * seq_len_local + jnp.arange(sl)
            mask = row_pos[:, None] >= col_pos[None, :]
            scores = jnp.where(mask[None, None], scores, neg)
        m, l, o = _online_update(m, l, o, scores, v_blk)
        # rotate: send our current block to the next rank (overlaps with the
        # next step's compute under XLA's async collectives)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    # derive accumulators from q so their varying-axes (shard_map vma) match
    # the loop-carried K/V blocks — fresh jnp.zeros would be "replicated"
    # typed and reject the carry
    m0 = q[..., :1] * 0 + neg
    l0 = q[..., :1] * 0
    o0 = q * 0
    _, _, m, l, o = lax.fori_loop(0, n, step, (k, v, m0, l0, o0))
    # fully-masked rows (can't happen with causal self-attention, but keep
    # the math safe): l == 0 -> output 0
    return jnp.where(l > 0, o / jnp.maximum(l, 1e-30), 0.0)


def ring_attention(q, k, v, mesh: Optional[Mesh] = None, causal: bool = False,
                   axis_name: str = "sp", scale: Optional[float] = None):
    """Exact softmax attention with the sequence axis sharded over a ring.

    Parameters
    ----------
    q, k, v : (B, H, S, D) NDArrays or jax arrays; S must divide evenly by
        the mesh size. Inputs may be unsharded (they are scattered) or
        already sharded over ``axis_name``.
    mesh : 1-D Mesh over the sequence axis (default: all devices).
    causal : apply the autoregressive mask on GLOBAL positions.

    Returns an array sharded like ``q`` (sequence axis over the mesh).
    """
    qd = q._data if isinstance(q, NDArray) else jnp.asarray(q)
    kd = k._data if isinstance(k, NDArray) else jnp.asarray(k)
    vd = v._data if isinstance(v, NDArray) else jnp.asarray(v)
    if mesh is None:
        mesh = sequence_mesh(axis_name=axis_name)
    n = mesh.devices.size
    b_, h_, s, d = qd.shape
    if s % n != 0:
        raise MXNetError("ring_attention: seq len %d not divisible by %d "
                         "devices" % (s, n))
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    spec = P(None, None, axis_name, None)
    fn = _shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale, seq_len_local=s // n),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(jax.device_put(qd, NamedSharding(mesh, spec)),
             jax.device_put(kd, NamedSharding(mesh, spec)),
             jax.device_put(vd, NamedSharding(mesh, spec)))
    if isinstance(q, NDArray):
        return NDArray(out, q.context)
    return out


def _ulysses_local(q, k, v, *, axis_name, causal, scale):
    """Per-device body: all-to-all seq->heads, full local attention over
    the complete sequence for this device's head subset, all-to-all back.
    Enters with local blocks (B, H, S/n, D); H must divide n devices."""
    n = lax.psum(1, axis_name)

    def seq_to_heads(x):
        # (B, H, Sl, D) -> gather seq, scatter heads -> (B, H/n, S, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        s = scores.shape[-1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores,
                           jnp.asarray(-jnp.inf, scores.dtype))
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh: Optional[Mesh] = None,
                      causal: bool = False, axis_name: str = "sp",
                      scale: Optional[float] = None):
    """All-to-all (DeepSpeed-Ulysses style) sequence-parallel attention:
    reshard seq→heads, ordinary attention per head subset, reshard back.
    Requires ``H % n_devices == 0``."""
    qd = q._data if isinstance(q, NDArray) else jnp.asarray(q)
    kd = k._data if isinstance(k, NDArray) else jnp.asarray(k)
    vd = v._data if isinstance(v, NDArray) else jnp.asarray(v)
    if mesh is None:
        mesh = sequence_mesh(axis_name=axis_name)
    n = mesh.devices.size
    b_, h, s, d = qd.shape
    if s % n != 0 or h % n != 0:
        raise MXNetError("ulysses_attention: seq %d and heads %d must both "
                         "divide by %d devices" % (s, h, n))
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    spec = P(None, None, axis_name, None)
    fn = _shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(jax.device_put(qd, NamedSharding(mesh, spec)),
             jax.device_put(kd, NamedSharding(mesh, spec)),
             jax.device_put(vd, NamedSharding(mesh, spec)))
    if isinstance(q, NDArray):
        return NDArray(out, q.context)
    return out
