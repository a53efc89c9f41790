"""The gated delta rule with a decay a channel (Kimi Delta Attention,
arXiv:2510.26692): one state matrix ``S (d_k, d_v)`` a head, per token

    S' = diag(exp(a_t)) S_{t-1}                   # a_t <= 0, a channel of d_k
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T      # the delta rule
    o_t = S_t^T q_t

Three pure functions, plain XLA, float32 throughout (every product of two
activations at ``highest`` precision: the state carries what it rounds):

:func:`chunked_scan`
    a whole prompt, ``chunk`` tokens at a time (a loop over the chunks that
    hold the prompt, its trip count traced from the prompt's length; inside
    one the recurrence is solved as a triangular system);
:func:`step`
    one token a slot, EVERY slot's state read twice and written once
    whatever holds a token: the reference of the tick and its path off the
    chip. On a TPU a decode step goes through
    :func:`mxnet_tpu.ops.pallas_kernels.kda_state_step` instead (the kernel
    ``mx_kda_state``: the same operations in the same order over the live
    slots only, each one's state read once and written once, in place),
    which is ``step`` itself everywhere else;
:func:`serial_scan`
    the recurrence as written, a token at a time through :func:`step`: the
    oracle of the tests.

Inside a chunk the decay between two of its tokens is ``exp(G_i - G_j)``, ``i
>= j``, from the cumulative log-decays ``G``: an argument that is never
positive. The form that divides by a cumulative decay (``exp(G_i) *
exp(-G_j)``) overflows float32 after 18 tokens at ``a = -5``.

:func:`short_conv` / :func:`short_conv_step` are the causal depthwise
convolution in front of it (a weight a channel and a tap) over a prompt and
over one token with the last ``taps - 1`` inputs of its slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["chunked_scan", "step", "serial_scan", "short_conv",
           "short_conv_step", "conv_tail"]

# tokens of a chunk: on the chip 2048 tokens of 32 heads x 128 took 97.2 / 5.3
# / 8.1 / 14.0 ms at 16 / 32 / 64 / 128 (PERF.md section 6, PR 46)
CHUNK = 32
_HIGHEST = lax.Precision.HIGHEST


def serial_scan(q, k, v, log_decay, beta):
    """The recurrence from an empty state, a token at a time. ``q``, ``k``,
    ``log_decay``: ``(T, H, d_k)``; ``v``: ``(T, H, d_v)``; ``beta``: ``(T,
    H)``. Returns ``(o (T, H, d_v), state (H, d_k, d_v))``."""
    state = jnp.zeros(k.shape[1:] + v.shape[2:], jnp.float32)

    def one(s, xs):
        qt, kt, vt, at, bt = xs
        o, s = step(qt[None], kt[None], vt[None], at[None], bt[None],
                    s[None])
        return s[0], o[0]

    state, out = lax.scan(one, state, (q, k, v, log_decay, beta))
    return out, state


def step(q, k, v, log_decay, beta, state, valid=None):
    """One token a row. ``q``, ``k``, ``log_decay``: ``(B, H, d_k)``; ``v``:
    ``(B, H, d_v)``; ``beta``: ``(B, H)``; ``state``: ``(B, H, d_k, d_v)``;
    ``valid``: ``(B,)`` bool or None — a row that is no token leaves its
    state bit for bit as it was (its output is meaningless). Returns ``(o (B,
    H, d_v), state)``. Two passes over the state: ``k`` and ``q`` against the
    decayed state in one (``o = S'^T q + (q . k) u``), the update in the
    other."""
    decayed = jnp.exp(log_decay)[..., None] * state
    seen_k = (k[..., None] * decayed).sum(axis=-2)          # S'^T k
    seen_q = (q[..., None] * decayed).sum(axis=-2)          # S'^T q
    u = beta[..., None] * (v - seen_k)
    out = seen_q + (q * k).sum(axis=-1, keepdims=True) * u
    new = decayed + k[..., None] * u[..., None, :]
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, state)
    return out, new


def chunked_scan(q, k, v, log_decay, beta, chunk=CHUNK, length=None):
    """:func:`serial_scan` a ``chunk`` of tokens at a time (same arguments
    and result). A row with ``log_decay`` 0 and ``beta`` 0 moves nothing:
    that is how a caller masks the padding of a rung. ``T`` is padded to a
    whole number of chunks with such rows. ``length`` (a traced int32 count,
    or None: every row is a token): the rows from ``length`` on are padding
    whatever they hold — they neither decay nor update — and the chunks
    behind the one that holds row ``length - 1`` are not visited: their
    outputs come back ZERO.

    A chunk, all heads at once, with ``S`` the state in front of it, ``G``
    the cumulative log-decays inside it, ``D_ij = exp(G_i - G_j)`` (a
    channel)::

        A_ij = sum_d k_i k_j D_ij (j < i);  B_ij = sum_d q_i k_j D_ij (j <= i)
        (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S)
        O = (Q * exp(G)) S + B U
        S' = diag(exp(G_C)) S + (K * exp(G_C - G))^T U
    """
    t, n_heads, d_k = k.shape
    d_v = v.shape[-1]
    chunk = min(int(chunk), max(t, 1))
    n = -(-t // chunk)
    pad = n * chunk - t
    state = jnp.zeros((n_heads, d_k, d_v), jnp.float32)

    def chunks(x):
        if pad:     # zeros: no decay, no update
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, chunk) + x.shape[1:])

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def dot(a, b, spec):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    xs = tuple(chunks(x) for x in (q, k, v, log_decay, beta))

    def one(i, carry):
        s, outs = carry
        qc, kc, vc, ac, bc = (x[i] for x in xs)     # (C, H, ...)
        if length is not None:
            token = i * chunk + jnp.arange(chunk) < length
            ac = jnp.where(token[:, None, None], ac, 0.0)
            bc = jnp.where(token[:, None], bc, 0.0)
        g = jnp.cumsum(ac, axis=0)              # (C, H, d_k)
        # (C, C, H, d_k): the decay from token j to token i, 0 above the
        # diagonal (where the argument would be positive)
        pair = jnp.where(lower[:, :, None, None],
                         jnp.exp(jnp.minimum(g[:, None] - g[None, :], 0.0)),
                         0.0)
        a_kk = (kc[:, None] * pair * kc[None, :]).sum(axis=-1)   # (C, C, H)
        b_qk = (qc[:, None] * pair * kc[None, :]).sum(axis=-1)
        a_kk = jnp.where(strict[:, :, None], a_kk, 0.0)
        into = jnp.exp(g)
        rhs = bc[..., None] * (vc - dot(kc * into, s, "chd,hde->che"))
        system = eye[None] + (bc[:, None, :] * a_kk).transpose(2, 0, 1)
        u = jax.scipy.linalg.solve_triangular(
            system, rhs.transpose(1, 0, 2), lower=True, unit_diagonal=True)
        out = dot(qc * into, s, "chd,hde->che") \
            + dot(b_qk, u, "ijh,hje->ihe")
        to_end = jnp.exp(g[-1][None] - g)
        s = into[-1][..., None] * s + dot(kc * to_end, u, "jhd,hje->hde")
        return s, outs.at[i].set(out)

    n_live = n if length is None else jnp.clip(
        -(-jnp.asarray(length, jnp.int32) // chunk), 0, n)
    state, out = lax.fori_loop(0, n_live, one, (
        state, jnp.zeros((n, chunk, n_heads, d_v), jnp.float32)))
    return out.reshape((n * chunk, n_heads, d_v))[:t], state


def short_conv(x, weight):
    """Causal depthwise convolution over time: ``y_t = sum_j weight[j] *
    x_{t - taps + 1 + j}`` with zeros in front of the sequence. ``x``: ``(T,
    C)``; ``weight``: ``(taps, C)``."""
    taps = weight.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    t = x.shape[0]
    return sum(weight[j][None] * lax.dynamic_slice_in_dim(padded, j, t)
               for j in range(taps))


def conv_tail(x, length, taps):
    """The last ``taps - 1`` rows of ``x (T, C)`` in front of row ``length``
    (a traced int32; zeros where the sequence is shorter): what
    :func:`short_conv_step` needs of a prompt of ``length`` tokens."""
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return lax.dynamic_slice_in_dim(padded, length, taps - 1)


def short_conv_step(x, tail, weight, valid=None):
    """One token a row: ``x (B, C)`` behind its slot's last inputs ``tail (B,
    taps - 1, C)``. Returns ``(y (B, C), tail)``; a row that is not ``valid``
    keeps its tail bit for bit."""
    window = jnp.concatenate([tail, x[:, None]], axis=1)    # (B, taps, C)
    y = (window * weight[None]).sum(axis=1)
    new = window[:, 1:]
    if valid is not None:
        new = jnp.where(valid[:, None, None], new, tail)
    return y, new
