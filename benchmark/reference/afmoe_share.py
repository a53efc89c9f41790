"""Plain reference of one chip's share of the ``afmoe`` decoder (Arcee
Trinity) that ``serving.AfmoeDecoder`` serves.

Straight ``jax.numpy``: no cache, no kernel, no batching, one sequence at a
time, float32 under ``highest`` matmul precision. To fit a 7,552-token pass
beside 8.64 GB of weights, attention is computed a block of 128 queries at a
time (each against every key, masked) and the experts one at a time, each
over every row, masked by the router's picks. ``rms(x, g) = x * g /
sqrt(mean(x ** 2) + eps)``. Input ``x = embed[token] * sqrt(hidden)``. Per
layer, of kind ``sliding_attention`` or ``full_attention``::

    h  = rms(x, ln_in)
    q  = rms_head(h wq -> (T, H, D), q_norm); k = rms_head(h wk -> (T, KH, D),
         k_norm); v = h wv -> (T, KH, D)
    sliding: q, k = rope(q, k, position, theta, rotate-half over all D dims)
             (a full layer sees no positions at all)
    a  = softmax(q k^T / sqrt(D) + mask) v      # grouped queries; mask: causal,
                                                # sliding: key > query - window
    a  = a * sigmoid(h wg)                      # the output gate, elementwise
    x  = x + rms(a wo, ln_post_attn)
    h  = rms(x, ln_pre_mlp)
    m  = dense layer:  (silu(h w1) * (h w3)) w2
         expert layer: s = sigmoid(h router) in float32; sel = top_k(s +
                       expert_bias); w = s[sel] / (sum + 1e-20) * route_scale
                       m = shared(h) + sum over picks held here of
                           w_e * expert_e(h)    # each a SwiGLU
    x  = x + rms(m, ln_post_mlp)

Output ``rms(x, ln_f) head`` (untied).

Departures from the published Trinity-Large-Preview, each also in the
program (``benchmark/configs/trinity_large_preview_ep8.json`` lists them
under ``reduced`` and ``assumed``):

* ``held_experts = [first, count]``: the experts of a layer that live on
  this chip of the expert-parallel deployment. The router keeps all
  ``num_experts`` outputs and its top-k; what a pick of an absent expert
  would add is LEFT OUT, and that partial sum goes on to the next layer.
* ``vocab_size`` is a slice of the published vocabulary: embedding rows,
  logits and argmax are over the slice.
* ``layer_types`` lists the layers held here (the cut in depth); the first
  ``num_dense_layers`` of them have the dense MLP.
* the embedding factor ``sqrt(hidden)`` (the config gives only the flag
  ``mup_enabled``); ``expert_bias`` drawn normal, std 0.01 (zero in a fresh
  model, non-zero in a trained one); norm scales are parameters (ones), where
  the published model scales its sandwich norms with depth; random weights.

Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import Spec  # benchmark/ is on sys.path (see run.py)

Q_BLOCK = 128
NEAR_TIE = 1e-4   # in bias-corrected score; how it was set: PERF.md section 2


def param_specs(model):
    """The parameter tree for ``model`` (the keys of the configuration's
    ``model`` block): matrices normal with std ``fan_in ** -0.5`` in
    ``param_dtype`` (embedding rows ``hidden ** -0.5``: unit RMS after the
    mup factor), router float32, ``expert_bias`` normal std 0.01, norm
    scales ones."""
    e, d = model["hidden_size"], model["head_dim"]
    h, kh = model["num_attention_heads"], model["num_key_value_heads"]
    held = model["held_experts"][1]
    dt = model["param_dtype"]

    def w(*shape, dtype=dt, fan_in=None):
        return Spec(tuple(shape), dtype, "normal",
                    (fan_in or shape[-2]) ** -0.5)

    def ones(n):
        return Spec((n,), "float32", "ones")

    def swiglu(width, *lead):
        return {"w1": w(*lead, e, width), "w3": w(*lead, e, width),
                "w2": w(*lead, width, e)}

    layers = []
    for li, _kind in enumerate(model["layer_types"]):
        layer = {"ln_in": ones(e), "ln_post_attn": ones(e),
                 "ln_pre_mlp": ones(e), "ln_post_mlp": ones(e),
                 "q_norm": ones(d), "k_norm": ones(d),
                 "wq": w(e, h * d), "wk": w(e, kh * d), "wv": w(e, kh * d),
                 "wg": w(e, h * d), "wo": w(h * d, e)}
        if li < model["num_dense_layers"]:
            layer.update(swiglu(model["intermediate_size"]))
        else:
            layer["router"] = w(e, model["num_experts"], dtype="float32")
            layer["expert_bias"] = Spec((model["num_experts"],), "float32",
                                        "normal", 0.01)
            layer["experts"] = swiglu(model["moe_intermediate_size"], held)
            layer["shared"] = swiglu(model["moe_intermediate_size"])
        layers.append(layer)
    return {"embed": w(model["vocab_size"], e, fan_in=e), "layers": layers,
            "ln_f": ones(e), "head": w(e, model["vocab_size"])}


def _rms(x, g, eps):
    return x * g.astype(x.dtype) / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return (x * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def _attention(model, layer, x, kind, dtype):
    t = x.shape[0]
    h, kh, d = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    eps = model["rms_norm_eps"]
    hx = _rms(x, layer["ln_in"], eps)
    q = _rms((hx @ layer["wq"].astype(dtype)).reshape(t, h, d),
             layer["q_norm"], eps)
    k = _rms((hx @ layer["wk"].astype(dtype)).reshape(t, kh, d),
             layer["k_norm"], eps)
    v = (hx @ layer["wv"].astype(dtype)).reshape(t, kh, d)
    window = 0
    if kind == "sliding_attention":
        q = _rope(q, jnp.arange(t), model["rope_theta"])
        k = _rope(k, jnp.arange(t), model["rope_theta"])
        window = model["sliding_window"]
    k = jnp.repeat(k, h // kh, axis=1)
    v = jnp.repeat(v, h // kh, axis=1)
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    cols = jnp.arange(t)[None, :]

    def one_block(args):
        qb, first = args                      # (block, H, D), its first row
        rows = first + jnp.arange(block)[:, None]
        mask = cols <= rows
        if window:
            mask = mask & (cols > rows - window)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], scores.astype(jnp.float32),
                                     -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(dtype), v)

    att = jax.lax.map(one_block, (q.reshape(t // block, block, h, d),
                                  jnp.arange(0, t, block)))
    att = att.reshape(t, h * d) * jax.nn.sigmoid(hx @ layer["wg"].astype(dtype))
    return x + _rms(att @ layer["wo"].astype(dtype), layer["ln_post_attn"],
                    eps)


def _swiglu(x, w1, w3, w2, dtype):
    return (jax.nn.silu(x @ w1.astype(dtype)) * (x @ w3.astype(dtype))) \
        @ w2.astype(dtype)


def _route(model, layer, hx):
    """``(sel (T, k), weights (T, k), near (T,))`` of the router over all
    ``num_experts``. ``near``: the pick is a NEAR-TIE that matters here —
    the last expert chosen and the first one left out lie within
    ``NEAR_TIE`` of each other in bias-corrected score and one of the two
    is held on this chip, so which of them is picked is decided by
    rounding upstream and changes this chip's part of the result."""
    k = model["num_experts_per_tok"]
    # the gate stays in float32 whatever the activations' type, as the
    # published code keeps it
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(hx.astype(jnp.float32) @ layer["router"])
    top, order = jax.lax.top_k(s + layer["expert_bias"], k + 1)
    sel = order[:, :k]
    w = jnp.take_along_axis(s, sel, axis=-1)
    if model["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    first, count = model["held_experts"]
    edge = order[:, k - 1:]                   # last in, first out
    held = ((edge >= first) & (edge < first + count)).any(axis=-1)
    near = held & (top[:, k - 1] - top[:, k] < NEAR_TIE)
    return sel, w * model["route_scale"], near


def _mlp(model, layer, hx, dtype):
    """``(mlp(hx), near (T,) or None)``."""
    if "router" not in layer:
        return _swiglu(hx, layer["w1"], layer["w3"], layer["w2"],
                       dtype), None
    sel, w, near = _route(model, layer, hx)
    first, count = model["held_experts"]
    ex = layer["experts"]

    def one_expert(e, out):   # every row through expert e, masked
        w_e = jnp.where(sel == first + e, w, 0.0).sum(axis=-1)
        return out + w_e[:, None].astype(dtype) * _swiglu(
            hx, ex["w1"][e], ex["w3"][e], ex["w2"][e], dtype)

    shared = layer["shared"]
    out = _swiglu(hx, shared["w1"], shared["w3"], shared["w2"], dtype)
    return jax.lax.fori_loop(0, count, one_expert, out), near


def rows_logits(model, params, seq, start, rows, dtype=jnp.float32):
    """Float32 logits ``(rows, vocab)`` at positions ``start .. start + rows
    - 1`` of the causal forward over the whole of ``seq`` (int32, any
    padding at the END), and for each of those positions whether some
    expert layer routed it at a near-tie (:func:`_route`). ``dtype``: the
    type activations and matmul operands are held in — float32 for the
    reference, bfloat16 for the control."""
    eps = model["rms_norm_eps"]
    x = params["embed"][seq].astype(jnp.float32)
    if model.get("mup_enabled"):
        x = x * model["hidden_size"] ** 0.5
    x = x.astype(dtype)
    near = jnp.zeros(seq.shape, bool)
    for layer, kind in zip(params["layers"], model["layer_types"]):
        x = _attention(model, layer, x, kind, dtype)
        m, tie = _mlp(model, layer, _rms(x, layer["ln_pre_mlp"], eps), dtype)
        if tie is not None:
            near = near | tie
        x = x + _rms(m, layer["ln_post_mlp"], eps)
    tail = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    out = _rms(tail, params["ln_f"], eps) @ params["head"].astype(dtype)
    return out.astype(jnp.float32), \
        jax.lax.dynamic_slice_in_dim(near, start, rows, axis=0)


def served_gaps(model, params, seq, start, served, dtype=jnp.float32):
    """For each of the ``served.shape[0]`` positions from ``start``: how far
    the served token's reference logit lies below the reference's best, in
    units of that row's logit standard deviation; and the reference's own
    greedy token. ``highest`` precision when ``dtype`` is float32."""
    rows = served.shape[0]
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        logits, near = rows_logits(model, params, seq, start, rows, dtype)
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    gap = (best - got) / logits.std(axis=-1)
    return jnp.where(near, 0.0, gap), logits.argmax(axis=-1)
