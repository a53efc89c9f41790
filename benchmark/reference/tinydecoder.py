"""Plain reference of the pre-norm decoder ``serving.TinyDecoder`` serves.

Straight ``jax.numpy``: no cache, no kernel, no batching, one sequence at a
time, float32 under ``highest`` matmul precision. The equations, per layer:
``h = rms(x, ln1)``; ``q, k, v = h wq, h wk, h wv``; causal softmax attention
with scale ``head_dim ** -0.5``; ``x += att wo``; ``x += relu(rms(x, ln2) w1)
w2``. Input ``embed[token] + sinusoid(position)``; output ``rms(x, lnf)
unembed``. ``rms(x, g) = x * g / sqrt(mean(x ** 2) + 1e-6)``.

Departures from OPT-1.3B, whose sizes the benchmark's configuration takes:
RMSNorm without bias for LayerNorm, sinusoidal for learned positions, no
linear biases, untied output head. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import Spec  # benchmark/ is on sys.path (see run.py)


def param_specs(model):
    """The parameter tree for ``model`` (``vocab_size``, ``num_layers``,
    ``num_heads``, ``head_dim``, ``mlp_ratio``, ``param_dtype``): matrices
    normal with std ``1 / sqrt(fan_in)``, norm scales ones."""
    e = model["num_heads"] * model["head_dim"]
    m = e * model["mlp_ratio"]
    v = model["vocab_size"]
    dt = model["param_dtype"]

    def w(rows, cols):
        return Spec((rows, cols), dt, "normal", rows ** -0.5)

    def ones():
        return Spec((e,), "float32", "ones")

    layers = [{"ln1": ones(), "wq": w(e, e), "wk": w(e, e), "wv": w(e, e),
               "wo": w(e, e), "ln2": ones(), "w1": w(e, m), "w2": w(m, e)}
              for _ in range(model["num_layers"])]
    return {"embed": w(v, e), "layers": layers, "lnf": ones(),
            "unembed": w(e, v)}


def _rms(x, g):
    return x * g.astype(x.dtype) / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _sinusoid(positions, e):
    half = e // 2
    freq = 1.0 / (10000.0 ** (jnp.arange(half) / float(half)))
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rows_logits(model, params, seq, start, rows, dtype=jnp.float32):
    """Float32 logits ``(rows, vocab)`` at positions ``start .. start + rows
    - 1`` of the causal forward over the whole of ``seq`` (int32, any
    padding at the END — a causal model never looks ahead). ``dtype`` is the
    type activations and matmul operands are held in: float32 for the
    reference, bfloat16 for the control."""
    h, d = model["num_heads"], model["head_dim"]
    t = seq.shape[0]
    x = (params["embed"][seq].astype(jnp.float32)
         + _sinusoid(jnp.arange(t), h * d)).astype(dtype)
    mask = jnp.tril(jnp.ones((t, t), bool))[None]
    for layer in params["layers"]:
        hx = _rms(x, layer["ln1"])
        q = (hx @ layer["wq"].astype(dtype)).reshape(t, h, d)
        k = (hx @ layer["wk"].astype(dtype)).reshape(t, h, d)
        v = (hx @ layer["wv"].astype(dtype)).reshape(t, h, d)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", p.astype(dtype), v)
        x = x + att.reshape(t, h * d) @ layer["wo"].astype(dtype)
        hx = _rms(x, layer["ln2"])
        x = x + jax.nn.relu(hx @ layer["w1"].astype(dtype)) \
            @ layer["w2"].astype(dtype)
    tail = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    out = _rms(tail, params["lnf"]) @ params["unembed"].astype(dtype)
    return out.astype(jnp.float32)


def served_gaps(model, params, seq, start, served, dtype=jnp.float32):
    """For each of the ``served.shape[0]`` positions from ``start``: how far
    the served token's reference logit lies below the reference's best, in
    units of that row's logit standard deviation; and the reference's own
    greedy token. Runs under ``highest`` precision when ``dtype`` is
    float32."""
    rows = served.shape[0]
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        logits = rows_logits(model, params, seq, start, rows, dtype)
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return (best - got) / logits.std(axis=-1), logits.argmax(axis=-1)
