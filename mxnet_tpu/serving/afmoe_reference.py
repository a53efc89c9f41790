"""Plain reference of the ``afmoe`` decoder (Arcee Trinity) that
:class:`mxnet_tpu.serving.AfmoeDecoder` serves.

Straight ``jax.numpy``: no cache, no kernel, no batching, one sequence,
float32 under ``highest`` matmul precision, the whole ``(T, T)`` score
matrix. ``rms(x, g) = x * g / sqrt(mean(x ** 2) + eps)``. Input ``x =
embed[token] * sqrt(hidden)`` (``mup_enabled``). Per layer, of kind
``sliding_attention`` or ``full_attention``::

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
         expert layer: s = sigmoid(h router); sel = top_k(s + expert_bias);
                       w = s[sel] / (sum + 1e-20) * route_scale
                       m = shared(h) + sum over picks held here of
                           w_e * expert_e(h)    # each a SwiGLU
    x  = x + rms(m, ln_post_mlp)

Output ``rms(x, ln_f) head`` (untied).

Departures from the published model, each also in the program:

* ``held_experts = [first, count]``: the experts of a layer that live on
  this chip of an expert-parallel deployment. The router keeps all
  ``num_experts`` outputs and its top-k; what a pick of an absent expert
  would add is LEFT OUT and that partial sum goes on to the next layer.
* ``vocab_size`` may be a slice of the published vocabulary: embedding rows,
  logits and argmax are over the slice.
* ``layer_types`` lists the layers held here (a cut in depth); the first
  ``num_dense_layers`` of them have the dense MLP.
* the embedding factor ``sqrt(hidden)`` (the config gives only the flag
  ``mup_enabled``) and a non-zero ``expert_bias`` are assumptions.
* the published model scales its sandwich norms with depth; the norm scales
  are parameters here (ones in a fresh tree).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["init_params", "forward_logits", "expert_mlp", "route",
           "attention"]


def init_params(cfg, seed=0, dtype="float32"):
    """A seeded parameter tree for ``cfg`` (the tree
    :meth:`AfmoeDecoder.init_params` builds): matrices normal with std
    ``fan_in ** -0.5`` in ``dtype`` (embedding rows ``hidden ** -0.5``), norm
    scales ones, ``expert_bias`` normal std 0.01, router float32."""
    rng = np.random.RandomState(seed)
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held = cfg["held_experts"][1]

    def w(*shape, dt=dtype, fan_in=None):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)
                           * (fan_in or shape[-2]) ** -0.5, dt)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def swiglu(width, *lead):
        return {"w1": w(*lead, e, width), "w3": w(*lead, e, width),
                "w2": w(*lead, width, e)}

    layers = []
    for li, _kind in enumerate(cfg["layer_types"]):
        layer = {"ln_in": ones(e), "ln_post_attn": ones(e),
                 "ln_pre_mlp": ones(e), "ln_post_mlp": ones(e),
                 "q_norm": ones(d), "k_norm": ones(d),
                 "wq": w(e, h * d), "wk": w(e, kh * d), "wv": w(e, kh * d),
                 "wg": w(e, h * d), "wo": w(h * d, e)}
        if li < cfg["num_dense_layers"]:
            layer.update(swiglu(cfg["intermediate_size"]))
        else:
            layer["router"] = w(e, cfg["num_experts"], dt="float32")
            layer["expert_bias"] = jnp.asarray(
                rng.randn(cfg["num_experts"]).astype(np.float32) * 0.01)
            layer["experts"] = swiglu(cfg["moe_intermediate_size"], held)
            layer["shared"] = swiglu(cfg["moe_intermediate_size"])
        layers.append(layer)
    # embedding rows of std hidden ** -0.5: unit RMS after the mup factor
    return {"embed": w(cfg["vocab_size"], e, fan_in=e), "layers": layers,
            "ln_f": ones(e), "head": w(e, cfg["vocab_size"])}


def _rms(x, g, eps):
    return x * g.astype(x.dtype) / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """Rotate-half over all of the last axis. x: (T, heads, D)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _f32(w):
    return w.astype(jnp.float32)


def attention(cfg, layer, x, kind):
    """The attention half of a layer: ``x + rms(gated attention, ln)``."""
    t = x.shape[0]
    h, kh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    hx = _rms(x, layer["ln_in"], eps)
    q = _rms((hx @ _f32(layer["wq"])).reshape(t, h, d), layer["q_norm"], eps)
    k = _rms((hx @ _f32(layer["wk"])).reshape(t, kh, d), layer["k_norm"],
             eps)
    v = (hx @ _f32(layer["wv"])).reshape(t, kh, d)
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    mask = cols <= rows
    if kind == "sliding_attention":
        q = _rope(q, jnp.arange(t), cfg["rope_theta"])
        k = _rope(k, jnp.arange(t), cfg["rope_theta"])
        mask = mask & (cols > rows - cfg["sliding_window"])
    k = jnp.repeat(k, h // kh, axis=1)
    v = jnp.repeat(v, h // kh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * d)
    att = att * jax.nn.sigmoid(hx @ _f32(layer["wg"]))
    return x + _rms(att @ _f32(layer["wo"]), layer["ln_post_attn"], eps)


def expert_mlp(x, w1, w3, w2):
    """One SwiGLU: ``(silu(x w1) * (x w3)) w2``."""
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def route(cfg, layer, hx):
    """``(sel (T, k), weights (T, k))`` over all ``num_experts``."""
    s = jax.nn.sigmoid(hx @ _f32(layer["router"]))
    _, sel = jax.lax.top_k(s + layer["expert_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return sel, w * cfg["route_scale"]


def _mlp(cfg, layer, hx):
    if "router" not in layer:
        return expert_mlp(hx, layer["w1"], layer["w3"], layer["w2"])
    sel, w = route(cfg, layer, hx)
    out = expert_mlp(hx, **layer["shared"])
    first, count = cfg["held_experts"]
    ex = layer["experts"]
    for e in range(count):   # one expert at a time, every row, masked
        w_e = jnp.where(sel == first + e, w, 0.0).sum(axis=-1)
        out = out + w_e[:, None] * expert_mlp(hx, ex["w1"][e], ex["w3"][e],
                                              ex["w2"][e])
    return out


def forward_logits(cfg, params, tokens):
    """Float32 logits ``(T, vocab)`` of the causal forward over ``tokens``
    (int32 ``(T,)``)."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(tokens)]
        if cfg.get("mup_enabled"):
            x = x * cfg["hidden_size"] ** 0.5
        for layer, kind in zip(params["layers"], cfg["layer_types"]):
            x = attention(cfg, layer, x, kind)
            m = _mlp(cfg, layer, _rms(x, layer["ln_pre_mlp"], eps))
            x = x + _rms(m, layer["ln_post_mlp"], eps)
        return _rms(x, params["ln_f"], eps) @ _f32(params["head"])
