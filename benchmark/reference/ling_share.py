"""Plain reference of one chip's share of Ling-3.0-flash's language model
that ``serving.LingDecoder`` serves.

Straight ``jax.numpy``: no cache, no state array, no kernel, no batching,
one sequence at a time, float32 under ``highest`` matmul precision. The
recurrence is a serial ``lax.scan`` over the tokens, latent attention is
EXPANDED (every head's keys and values from the latent row) over the whole
sequence a block of 128 queries at a time, and the experts are computed one
at a time, each over every row, masked by the router's picks. ``rms(x, g) =
x * g / sqrt(mean(x ** 2) + eps)``. Pre-norm blocks::

    x = embed[token]
    x = x + attn_l(rms(x, ln_in));  x = x + mlp_l(rms(x, ln_mlp))
    logits = rms(x, ln_f) head                                  (untied)

``kda`` layer (Kimi Delta Attention, arXiv:2510.26692; H heads of D)::

    [q~; k~; v~] = h [wq; wk; wv]                 # each E -> H D
    [q'; k'; v'] = silu(conv(.))       # causal, depthwise: sum over taps j
                                       # of conv[j] * x_{t - taps + 1 + j}
    q = l2(q'_h) * D ** -0.5; k = l2(k'_h); v = v'_h
                                       # l2: x / sqrt(sum x^2 + 1e-6)
    a = kda_lower_bound * sigmoid(exp(a_log_h) * (h wf + dt_bias))
                                       # a channel
    beta = sigmoid(h wb)                          # a head
    S' = diag(exp(a_t)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
    y = concat_h(rms(o_h, o_norm) * sigmoid(h wg)_h) wo

``mla`` layer (latent attention, DeepSeek-V2, arXiv:2405.04434)::

    [q_nope_h (nope); q_rope_h (rope)] = h wq;  [c (rank); k_r (rope)] = h wkva
    c = rms(c, kv_norm); q_rope_h, k_r = rope(., position, theta, rotate-half)
    [k_nope_h (nope); v_h (dv)] = c wkvb
    p = softmax_causal((q_nope_h . k_nope_h + q_rope_h . k_r)
                       / sqrt(nope + rope))
    y = concat_h((sum_s p_s v_h,s) * sigmoid(h wg)_h) wo

MLP: dense SwiGLU in the first ``num_dense_layers`` layers, then the expert
layer: ``s = sigmoid(h router)`` in float32; selection on ``s +
expert_bias``: ``n_group`` groups of consecutive experts, a group's score
the sum of its two largest, the ``topk_group`` best groups kept, top-k over
their experts; ``w = s[sel] / (sum + 1e-20) * routed_scaling_factor``; ``m =
shared(h) + sum over picks held here of w_e * expert_e(h)``.

Departures from the published model, each also in the program (the
configuration file lists them under ``reduced`` and ``assumed``):
``held_experts = [first, count]`` (what a pick of an absent expert would add
is LEFT OUT, and that partial sum goes on to the next layer); ``vocab_size``
is a slice of the vocabulary; ``layer_types`` lists the layers held here;
random weights, ``expert_bias`` drawn normal std 0.01.

NEAR-TIES. Which group or expert wins a selection whose two sides lie within
``NEAR_TIE`` of each other is decided by the last bit of rounding in any
arithmetic; such a position is not compared (its gap reads 0) where the flip
changes what THIS chip adds: the last expert in against the first one out
with one of the two held here (as ``afmoe_share.py``), and the last group in
against the first group out, within ``2 * NEAR_TIE`` (a group's score is
the sum of two scores) — whichever groups they are: another group's experts
compete for the same top-k, so a flipped group moves held picks out or in
and rescales the weights of those that stay.

A flipped pick reaches LATER tokens too, by two paths (PERF.md section 6,
PR 46; on the chip one selection in about 4,000 flips, float32 rounding
against float32 rounding: twelve seen in 48,000 tokens, at margins of 0 to
2.7e-6 and one of 6.6e-6):

* a RECURRENCE carries it. The token that was routed otherwise writes
  another key and value into the state of every kda layer behind that expert
  layer and the next tokens read it back: the positions behind such a flip
  read gaps of 1.25 down to 0.15 over twelve positions, falling by about half
  a position. So the ``CARRY`` positions behind a selection decided by less
  than ``TIGHT_TIE`` (seven times the widest margin at which a flip with a
  wake was seen) are not compared — where the expert layer HAS a kda layer
  behind it: a flip in the last kda layer's expert layer, or behind the
  latent layer, changes no state.
* the LATENT POOL keeps it. The flipped token's latent row stays in the
  pool and every later token attends to it, one row among the ``n`` it sees:
  no decay, a weight of about ``1 / n``. That moves a later token's hidden
  state behind the latent attention by about that much, which is a thousand
  times rounding — and the expert layer behind the latent attention flips
  its own pick where its margin is inside that (seen once: an isolated gap of
  0.081, its neighbours 0, at a margin of 2.5e-4 with 409 tokens in the pool,
  17 positions behind the 6.6e-6). So an expert layer with a latent layer at
  or in front of it takes ``max(NEAR_TIE, DILUTE / n)`` for its near-ties
  (0.25 / 409 = 6.1e-4: 2.4 times that margin) from the first tight tie of
  the sequence on. Such a flip has no wake: no recurrence lies behind that
  layer.

``served_gaps`` prints how many positions of a pass it skipped, of any kind
(about a third of a run's positions: 8 % near-ties, 1.4 % tight ones in the
four layers that carry x 25 positions, 1 % the pool's).

Imports nothing of the program.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from weights import Spec  # benchmark/ is on sys.path (see run.py)

Q_BLOCK = 128
NEAR_TIE = 1e-4   # as reference/afmoe_share.py: PERF.md section 2
TIGHT_TIE = 2e-5  # a selection this close may flip in float32 itself ...
CARRY = 24        # ... and a recurrence behind it carries the flip this far
DILUTE = 0.25     # ... and the latent pool keeps it, one row among n: 0.25 / n


def param_specs(model):
    """The parameter tree for ``model`` (the keys of the configuration's
    ``model`` block): matrices normal with std ``fan_in ** -0.5`` in
    ``param_dtype`` (embedding rows ``hidden ** -0.5``), the router, the
    convolution (std ``taps ** -0.5``), ``a_log`` and ``dt_bias`` (std 0.5)
    float32, ``expert_bias`` normal std 0.01, norm scales ones."""
    e, d, h = (model["hidden_size"], model["head_dim"],
               model["num_attention_heads"])
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    taps = model["short_conv_kernel_size"]
    held = model["held_experts"][1]
    dt = model["param_dtype"]

    def w(*shape, dtype=dt, fan_in=None, std=None):
        return Spec(tuple(shape), dtype, "normal",
                    (fan_in or shape[-2]) ** -0.5 if std is None else std)

    def ones(n):
        return Spec((n,), "float32", "ones")

    def swiglu(width, *lead):
        return {"w1": w(*lead, e, width), "w3": w(*lead, e, width),
                "w2": w(*lead, width, e)}

    layers = []
    for li, kind in enumerate(model["layer_types"]):
        layer = {"ln_in": ones(e), "ln_mlp": ones(e), "wg": w(e, h)}
        if kind == "kda":
            layer.update(
                wq=w(e, h * d), wk=w(e, h * d), wv=w(e, h * d),
                conv=w(taps, 3 * h * d, dtype="float32", std=taps ** -0.5),
                wf=w(e, h * d), a_log=w(h, dtype="float32", std=0.5),
                dt_bias=w(h * d, dtype="float32", std=0.5),
                wb=w(e, h), o_norm=ones(d), wo=w(h * d, e))
        else:
            layer.update(
                wq=w(e, h * (nope + rope)), wkva=w(e, rank + rope),
                kv_norm=ones(rank), wkvb=w(rank, h * (nope + dv)),
                wo=w(h * dv, e))
        if li < model["num_dense_layers"]:
            layer.update(swiglu(model["intermediate_size"]))
        else:
            layer["router"] = w(e, model["num_experts"], dtype="float32")
            layer["expert_bias"] = w(model["num_experts"], dtype="float32",
                                     std=0.01)
            layer["experts"] = swiglu(model["moe_intermediate_size"], held)
            layer["shared"] = swiglu(model["moe_intermediate_size"])
        layers.append(layer)
    return {"embed": w(model["vocab_size"], e, fan_in=e), "layers": layers,
            "ln_f": ones(e), "head": w(e, model["vocab_size"])}


def _rms(x, g, eps):
    return x * g.astype(x.dtype) / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """Rotate-half over the last axis; ``x``: ``(T, ..., d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return (x * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def _kda(model, layer, hx, dtype):
    """A kda layer's ``y`` (before the residual); ``hx`` the normed input."""
    t = hx.shape[0]
    h, d = model["num_attention_heads"], model["head_dim"]
    taps = model["short_conv_kernel_size"]
    qkv = jnp.concatenate([hx @ layer[n].astype(dtype)
                           for n in ("wq", "wk", "wv")], axis=-1)
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    mixed = sum(layer["conv"][j].astype(dtype)[None] * padded[j:j + t]
                for j in range(taps))
    q, k, v = (x.reshape(t, h, d)
               for x in jnp.split(jax.nn.silu(mixed), 3, axis=-1))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                            + 1e-6)

    q, k = l2(q) * d ** -0.5, l2(k)
    gate = (hx @ layer["wf"].astype(dtype)
            + layer["dt_bias"].astype(dtype)).reshape(t, h, d)
    decay = jnp.exp(model["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(layer["a_log"]).astype(dtype)[None, :, None] * gate))
    beta = jax.nn.sigmoid(hx @ layer["wb"].astype(dtype))

    def one(s, xs):                 # s: (H, D, D), float32 whatever dtype
        qt, kt, vt, dec, bt = (x.astype(jnp.float32) for x in xs)
        s = dec[..., None] * s
        u = bt[:, None] * (vt - jnp.einsum("hd,hde->he", kt, s))
        s = s + kt[..., None] * u[:, None, :]
        return s, jnp.einsum("hd,hde->he", qt, s).astype(dtype)

    _s, out = jax.lax.scan(one, jnp.zeros((h, d, d), jnp.float32),
                           (q, k, v, decay, beta))
    out = _rms(out, layer["o_norm"], model["rms_norm_eps"]) \
        * jax.nn.sigmoid(hx @ layer["wg"].astype(dtype))[..., None]
    return out.reshape(t, h * d) @ layer["wo"].astype(dtype)


def _mla(model, layer, hx, dtype):
    """A latent layer's ``y``, expanded over the whole sequence."""
    t = hx.shape[0]
    h = model["num_attention_heads"]
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    pos = jnp.arange(t)
    q = (hx @ layer["wq"].astype(dtype)).reshape(t, h, nope + rope)
    kva = hx @ layer["wkva"].astype(dtype)
    c = _rms(kva[:, :rank], layer["kv_norm"], model["rms_norm_eps"])
    k_r = _rope(kva[:, rank:], pos, model["rope_theta"])
    kv = (c @ layer["wkvb"].astype(dtype)).reshape(t, h, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos, model["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (t, h, rope))], -1)
    v = kv[..., nope:]
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    cols = jnp.arange(t)[None, :]

    def one_block(args):
        qb, first = args
        rows = first + jnp.arange(block)[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where((cols <= rows)[None],
                                     scores.astype(jnp.float32), -1e30),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(dtype), v)

    att = jax.lax.map(one_block, (q.reshape(t // block, block, h, -1),
                                  jnp.arange(0, t, block)))
    att = att.reshape(t, h, dv) \
        * jax.nn.sigmoid(hx @ layer["wg"].astype(dtype))[..., None]
    return att.reshape(t, h * dv) @ layer["wo"].astype(dtype)


def _swiglu(x, w1, w3, w2, dtype):
    return (jax.nn.silu(x @ w1.astype(dtype)) * (x @ w3.astype(dtype))) \
        @ w2.astype(dtype)


def _route(model, layer, hx, near_tie=NEAR_TIE):
    """``(sel (T, k), weights (T, k), near (T,), tight (T,))`` of the router
    over all ``num_experts``; ``near``: a near-tie that matters here, decided
    by less than ``near_tie`` (a number, or one a position), ``tight``: one
    decided by less than ``TIGHT_TIE`` (the module's head)."""
    k = model["num_experts_per_tok"]
    n_group, topk_group = model["n_group"], model["topk_group"]
    first, count = model["held_experts"]
    # the gate stays in float32 whatever the activations' type
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(hx.astype(jnp.float32) @ layer["router"])
    biased = s + layer["expert_bias"]
    near = tight = jnp.zeros(hx.shape[:1], bool)
    if n_group > 1:
        t, n = biased.shape
        best2, _ = jax.lax.top_k(biased.reshape(t, n_group, n // n_group), 2)
        top, groups = jax.lax.top_k(best2.sum(axis=-1),
                                    min(topk_group + 1, n_group))
        kept = (groups[:, :topk_group, None]
                == jnp.arange(n_group)[None, None]).any(axis=1)
        biased = jnp.where(jnp.repeat(kept, n // n_group, axis=1), biased,
                           -jnp.inf)
        if topk_group < n_group:
            # (a group's score is the sum of two scores)
            margin = top[:, topk_group - 1] - top[:, topk_group]
            near, tight = margin < 2 * near_tie, margin < 2 * TIGHT_TIE
    top, order = jax.lax.top_k(biased, k + 1)
    sel = order[:, :k]
    w = jnp.take_along_axis(s, sel, axis=-1)
    if model["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    edge = order[:, k - 1:]                   # last in, first out
    held = ((edge >= first) & (edge < first + count)).any(axis=-1)
    margin = top[:, k - 1] - top[:, k]
    near = near | (held & (margin < near_tie))
    tight = tight | (held & (margin < TIGHT_TIE))
    return sel, w * model["routed_scaling_factor"], near, tight


def _mlp(model, layer, hx, dtype, near_tie=NEAR_TIE):
    """``(mlp(hx), (near, tight) (T,) each, or None)``."""
    if "router" not in layer:
        return _swiglu(hx, layer["w1"], layer["w3"], layer["w2"],
                       dtype), None
    sel, w, near, tight = _route(model, layer, hx, near_tie)
    first, count = model["held_experts"]
    ex = layer["experts"]

    def one_expert(e, out):   # every row through expert e, masked
        w_e = jnp.where(sel == first + e, w, 0.0).sum(axis=-1)
        return out + w_e[:, None].astype(dtype) * _swiglu(
            hx, ex["w1"][e], ex["w3"][e], ex["w2"][e], dtype)

    shared = layer["shared"]
    out = _swiglu(hx, shared["w1"], shared["w3"], shared["w2"], dtype)
    return jax.lax.fori_loop(0, count, one_expert, out), (near, tight)


def forward(model, params, seq, dtype=jnp.float32):
    """The hidden states ``(T, E)`` behind the last layer of the causal
    forward over ``seq`` (int32, any padding at the END), and for each
    position whether it is left out of the comparison: some expert layer
    routed it at a near-tie (a wider one behind a latent layer), or an
    expert layer with a recurrence behind it routed one of the ``CARRY``
    positions in front of it at a tight one (the module's head)."""
    eps = model["rms_norm_eps"]
    x = params["embed"][seq].astype(dtype)
    near = tight = suspect = jnp.zeros(seq.shape, bool)
    kinds = model["layer_types"]
    # what one flipped token's row weighs among the n a position attends to
    diluted = jnp.maximum(NEAR_TIE, DILUTE / (1.0 + jnp.arange(seq.shape[0])))
    for li, (layer, kind) in enumerate(zip(params["layers"], kinds)):
        attn = _kda if kind == "kda" else _mla
        x = x + attn(model, layer, _rms(x, layer["ln_in"], eps), dtype)
        # behind a latent layer, from the first tight tie of the sequence on
        width = jnp.where(jnp.cumsum(suspect) > 0, diluted, NEAR_TIE) \
            if "mla" in kinds[:li + 1] else NEAR_TIE
        m, ties = _mlp(model, layer, _rms(x, layer["ln_mlp"], eps), dtype,
                       width)
        if ties is not None:
            near, suspect = near | ties[0], suspect | ties[1]
            if "kda" in kinds[li + 1:]:   # a recurrence behind it carries
                tight = tight | ties[1]
        x = x + m
    # tight ties among positions s - CARRY .. s, by a running count
    count = jnp.cumsum(tight.astype(jnp.int32))
    before = jnp.pad(count, (CARRY + 1, 0))[:count.shape[0]]
    return x, near | (count - before > 0)


def rows_logits(model, params, seq, start, rows, dtype=jnp.float32):
    """Float32 logits ``(rows, vocab)`` at positions ``start .. start + rows
    - 1`` of :func:`forward`, and their near-ties. ``dtype``: the type
    activations and matmul operands are held in — float32 for the
    reference, bfloat16 for the control."""
    x, near = forward(model, params, seq, dtype)
    tail = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    out = _rms(tail, params["ln_f"], model["rms_norm_eps"]) \
        @ params["head"].astype(dtype)
    return out.astype(jnp.float32), \
        jax.lax.dynamic_slice_in_dim(near, start, rows, axis=0)


def _say_skipped(skipped, positions):
    print(json.dumps({"phase": "near_ties", "skipped": int(skipped),
                      "positions": int(positions)}), flush=True)


def served_gaps(model, params, seq, start, served, dtype=jnp.float32):
    """For each of the ``served.shape[0]`` positions from ``start``: how far
    the served token's reference logit lies below the reference's best, in
    units of that row's logit standard deviation; and the reference's own
    greedy token. ``highest`` precision when ``dtype`` is float32. Prints
    the positions it did not compare (near-ties) of those it was handed."""
    rows = served.shape[0]
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        logits, near = rows_logits(model, params, seq, start, rows, dtype)
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    gap = (best - got) / logits.std(axis=-1)
    if dtype == jnp.float32:
        jax.debug.callback(_say_skipped, near.sum(), rows)
    return jnp.where(near, 0.0, gap), logits.argmax(axis=-1)
