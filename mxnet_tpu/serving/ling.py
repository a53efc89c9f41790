"""Ling-3.0-flash's language model through the paged-decode contract: one
chip's share of an expert-parallel deployment.

Pre-norm blocks, ``x += attn(rms(x)); x += mlp(rms(x))``. A layer's attention
is one of two kinds (``layer_types``):

``kda``
    Kimi Delta Attention (arXiv:2510.26692; :mod:`mxnet_tpu.ops.kda`): q, k
    and v through a causal depthwise convolution of ``short_conv_kernel_size``
    taps and SiLU, q and k L2-normalised a head, a decay a channel ``a =
    kda_lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias))`` in
    ``(kda_lower_bound, 0)``, ``beta = sigmoid(x W_beta)`` a head, the delta
    rule over ONE STATE MATRIX ``(head_dim, head_dim)`` A HEAD, the output
    RMS-normalised a head and gated a head by ``sigmoid(x W_g)``. What it
    keeps between tokens is fixed in size: the state and the convolution's
    last ``taps - 1`` inputs, a slot. A prompt runs the chunked scan; a
    decode tick updates the state of the slots that hold a token, in place
    (:func:`~mxnet_tpu.ops.pallas_kernels.kda_state_step`).
``mla``
    latent attention (DeepSeek-V2, arXiv:2405.04434): a token is kept as ONE
    row ``[c (kv_lora_rank); k_r (qk_rope_head_dim)]``, ``c`` RMS-normalised,
    ``k_r`` rotated and shared by the heads. Prefill expands ``c`` into each
    head's keys and values (:func:`~mxnet_tpu.ops.pallas_kernels
    .band_attention`); decode ABSORBS the expansion into the query and the
    output (``q^ = W_kvb^K^T q_nope`` scores against ``c`` itself, the
    weighted sum of ``c`` goes through ``W_kvb^V``) and walks the latent
    pool (:func:`~mxnet_tpu.ops.pallas_kernels.paged_latent_attention`).
    The same head-wise gate.

The MLP is a dense SwiGLU in the first ``num_dense_layers`` layers and an
expert layer after them (:mod:`mxnet_tpu.ops.moe`: a sigmoid router over all
``num_experts`` with group-limited selection, the grouped product over the
``held_experts`` that live here, one shared expert).

What it declares to :class:`~mxnet_tpu.serving.DecodeEngine`:
``layer_state`` (a ``slot`` entry a kda layer, a ``latent`` entry a mla
layer: of the two operands every model is handed, ``pools`` is its one
group's, the latent layers' pools bare, and ``state`` a pair of arrays a kda
layer), ``moe_counters`` and ``prefill_rows`` (the rows of a rung a
prefill's row-wise passes compute for a prompt: those of the row blocks the
prompt reaches, :mod:`mxnet_tpu.ops.row_blocks`; the scan visits the chunks
that hold the prompt).
Activations are float32 for real (:func:`mxnet_tpu.ops.moe.matmul` against
bfloat16 weights, float32 products in the recurrence and the attention): a
router amplifies rounding and a recurrence carries it. No chunked prefill,
no prefix sharing, no drafts: the engine refuses them for such a model.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from ..base import MXNetError
from .decode import PagedDecodeModel
from .kvcache import place_layers, write_rows

__all__ = ["LingDecoder"]

KINDS = ("kda", "mla")


def _mm(x, w):
    from ..ops import moe

    return moe.matmul(x, w)


def _mm_heads(x, w, spec):
    """``einsum(spec, x, w)`` of float32 ``x`` against ``w`` in its served
    type, a head at a time: ``x`` enters as the terms of
    :func:`mxnet_tpu.ops.moe.split_terms`, float32 accumulation."""
    import jax.numpy as jnp

    from ..ops import moe

    return sum(jnp.einsum(spec, term, w, preferred_element_type=jnp.float32)
               for term in moe.split_terms(x, w.dtype))


class LingDecoder(PagedDecodeModel):
    """See the module. Arguments are the keys of the model's ``config.json``
    (``layer_types`` lists the layers held here, ``kda`` or ``mla``) plus
    ``held_experts = (first, count)``, the experts of each layer that live
    on this chip."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_attention_heads: int, head_dim: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 layer_types: Sequence[str], num_dense_layers: int,
                 num_experts: int, num_experts_per_tok: int,
                 n_group: int = 1, topk_group: int = 1, held_experts=None,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True,
                 short_conv_kernel_size: int = 4,
                 kda_lower_bound: float = -5.0,
                 rope_theta: float = 10000.0, rms_norm_eps: float = 1e-6):
        bad = sorted(set(layer_types) - set(KINDS))
        if bad or "kda" not in layer_types or "mla" not in layer_types:
            raise MXNetError("LingDecoder needs layers of both kinds %s, got "
                             "%s" % (list(KINDS), list(layer_types)))
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise MXNetError("%d experts in %d groups, %d of them a token"
                             % (num_experts, n_group, topk_group))
        held = (0, num_experts) if held_experts is None \
            else tuple(int(x) for x in held_experts)
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > num_experts:
            raise MXNetError("held_experts %s outside 0..%d"
                             % (held, num_experts))
        self.cfg = {
            "vocab_size": int(vocab_size), "hidden_size": int(hidden_size),
            "num_attention_heads": int(num_attention_heads),
            "head_dim": int(head_dim), "kv_lora_rank": int(kv_lora_rank),
            "qk_nope_head_dim": int(qk_nope_head_dim),
            "qk_rope_head_dim": int(qk_rope_head_dim),
            "v_head_dim": int(v_head_dim),
            "intermediate_size": int(intermediate_size),
            "moe_intermediate_size": int(moe_intermediate_size),
            "layer_types": list(layer_types),
            "num_dense_layers": int(num_dense_layers),
            "num_experts": int(num_experts),
            "num_experts_per_tok": int(num_experts_per_tok),
            "n_group": int(n_group), "topk_group": int(topk_group),
            "held_experts": list(held),
            "routed_scaling_factor": float(routed_scaling_factor),
            "norm_topk_prob": bool(norm_topk_prob),
            "short_conv_kernel_size": int(short_conv_kernel_size),
            "kda_lower_bound": float(kda_lower_bound),
            "rope_theta": float(rope_theta),
            "rms_norm_eps": float(rms_norm_eps),
        }
        self.vocab_size = int(vocab_size)
        self.num_layers = len(layer_types)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = 1       # a latent row has no head axis
        self.head_dim = int(head_dim)
        #: a latent row: ``[c; k_r]``
        self.latent_width = int(kv_lora_rank) + int(qk_rope_head_dim)
        self.scale = float(qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        h, d = self.num_heads, self.head_dim
        state = ((h, d, d), (int(short_conv_kernel_size) - 1, 3 * h * d))
        self.layer_state = [("slot", state) if kind == "kda"
                            else ("latent", self.latent_width)
                            for kind in layer_types]
        # layer -> its index among the latent pools, or in the slot state
        self._place = [at for _group, at in place_layers(self.layer_state)]
        n_expert_layers = self.num_layers - int(num_dense_layers)
        self.moe_counters = (n_expert_layers, held[1] + 1) \
            if n_expert_layers > 0 else None

    def init_params(self, seed: int = 0, dtype="float32"):
        """A seeded parameter tree (the tree of
        ``benchmark/reference/ling_share.py``'s ``param_specs``): matrices
        normal with std ``fan_in ** -0.5`` in ``dtype``, the router, the
        convolution, ``A_log``, ``dt_bias`` and the norm scales float32,
        ``expert_bias`` normal std 0.01."""
        import jax.numpy as jnp

        cfg = self.cfg
        rng = np.random.RandomState(seed)
        e, h, d = cfg["hidden_size"], self.num_heads, self.head_dim
        rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        taps = cfg["short_conv_kernel_size"]

        def w(*shape, dt=dtype, fan_in=None, std=None):
            std = (fan_in or shape[-2]) ** -0.5 if std is None else std
            return jnp.asarray(rng.randn(*shape).astype(np.float32) * std,
                               dt)

        def ones(n):
            return jnp.ones((n,), jnp.float32)

        def swiglu(width, *lead):
            return {"w1": w(*lead, e, width), "w3": w(*lead, e, width),
                    "w2": w(*lead, width, e)}

        layers = []
        for li, kind in enumerate(cfg["layer_types"]):
            layer = {"ln_in": ones(e), "ln_mlp": ones(e), "wg": w(e, h)}
            if kind == "kda":
                layer.update(
                    wq=w(e, h * d), wk=w(e, h * d), wv=w(e, h * d),
                    conv=w(taps, 3 * h * d, dt="float32", std=taps ** -0.5),
                    wf=w(e, h * d),
                    a_log=w(h, dt="float32", std=0.5),
                    dt_bias=w(h * d, dt="float32", std=0.5),
                    wb=w(e, h), o_norm=ones(d), wo=w(h * d, e))
            else:
                layer.update(
                    wq=w(e, h * (nope + rope)), wkva=w(e, rank + rope),
                    kv_norm=ones(rank), wkvb=w(rank, h * (nope + dv)),
                    wo=w(h * dv, e))
            if li < cfg["num_dense_layers"]:
                layer.update(swiglu(cfg["intermediate_size"]))
            else:
                layer["router"] = w(e, cfg["num_experts"], dt="float32")
                layer["expert_bias"] = w(cfg["num_experts"], dt="float32",
                                         std=0.01)
                layer["experts"] = swiglu(cfg["moe_intermediate_size"],
                                          cfg["held_experts"][1])
                layer["shared"] = swiglu(cfg["moe_intermediate_size"])
            layers.append(layer)
        return {"embed": w(cfg["vocab_size"], e, fan_in=e), "layers": layers,
                "ln_f": ones(e), "head": w(e, cfg["vocab_size"])}

    # -- shared pieces --------------------------------------------------
    def _rms(self, x, g):
        import jax.numpy as jnp

        return x * g / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                         keepdims=True)
                                + self.cfg["rms_norm_eps"])

    def _rope(self, x, positions):
        """Rotate-half over the last axis of ``x (N, ..., rope dims)``."""
        import jax.numpy as jnp

        d = x.shape[-1]
        inv = self.cfg["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
        ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def _kda_inputs(self, layer, x):
        """A kda layer's rows from the residual stream: ``(normed row (N,
        E), qkv (N, 3 H D) before the convolution, log decay (N, H, D), beta
        (N, H))``."""
        import jax
        import jax.numpy as jnp

        n = x.shape[0]
        h, d = self.num_heads, self.head_dim
        hx = self._rms(x, layer["ln_in"])
        qkv = jnp.concatenate([_mm(hx, layer["wq"]), _mm(hx, layer["wk"]),
                               _mm(hx, layer["wv"])], axis=-1)
        gate = (_mm(hx, layer["wf"]) + layer["dt_bias"]).reshape(n, h, d)
        decay = self.cfg["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(layer["a_log"])[None, :, None] * gate)
        return hx, qkv, decay, jax.nn.sigmoid(_mm(hx, layer["wb"]))

    def _kda_heads(self, mixed):
        """The convolution's output ``(N, 3 H D)`` as the recurrence's ``q,
        k, v``: SiLU, q and k L2-normalised a head, q scaled."""
        import jax
        import jax.numpy as jnp

        n = mixed.shape[0]
        h, d = self.num_heads, self.head_dim
        q, k, v = (x.reshape(n, h, d) for x in jnp.split(
            jax.nn.silu(mixed), 3, axis=-1))

        def l2(x):
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        return l2(q) * d ** -0.5, l2(k), v

    def _kda_out(self, layer, hx, out):
        """The recurrence's output, RMS-normalised and gated a head, as the
        rows ``(N, H D)`` that ``W_o`` takes."""
        import jax

        gated = self._rms(out, layer["o_norm"]) \
            * jax.nn.sigmoid(_mm(hx, layer["wg"]))[..., None]
        return gated.reshape(out.shape[0], -1)

    def _mla_rows(self, layer, x, positions):
        """A latent layer's rows from the residual stream: ``(normed row (N,
        E), q_nope (N, H, nope), q_rope (N, H, rope) rotated, row (N, rank +
        rope))`` — its queries and the row it keeps a token."""
        import jax.numpy as jnp

        cfg = self.cfg
        n = x.shape[0]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        rank = cfg["kv_lora_rank"]
        hx = self._rms(x, layer["ln_in"])
        q = _mm(hx, layer["wq"]).reshape(n, self.num_heads, nope + rope)
        kva = _mm(hx, layer["wkva"])
        row = jnp.concatenate(
            [self._rms(kva[:, :rank], layer["kv_norm"]),
             self._rope(kva[:, rank:], positions)], axis=-1)
        return hx, q[..., :nope], self._rope(q[..., nope:], positions), row

    def _behind_attention(self, layer, kind, x, hx, att):
        """A row from its attention's output (the recurrence's ``(N, H,
        D)``, the latent attention's ``(N, H, dv)``) to where its MLP
        begins. A dense layer: the whole rest of the layer, ``(x,)``; an
        expert layer: ``(x, normed row)``, what its router and its experts
        take."""
        import jax

        part = jax.named_scope
        if kind == "kda":
            with part("mx_kda_state"):
                att = self._kda_out(layer, hx, att)
            with part("mx_kda_proj"):
                x = x + _mm(att, layer["wo"])
        else:
            with part("mx_mla_proj"):
                att = att * jax.nn.sigmoid(_mm(hx, layer["wg"]))[..., None]
                x = x + _mm(att.reshape(att.shape[0], -1), layer["wo"])
        # an expert layer's norm in front goes with its router, the
        # residual behind with its combine
        if "router" in layer:
            with part("mx_moe_route"):
                return x, self._rms(x, layer["ln_mlp"])
        with part("mx_mlp"):
            hm = self._rms(x, layer["ln_mlp"])
            return (x + _mm(jax.nn.silu(_mm(hm, layer["w1"]))
                            * _mm(hm, layer["w3"]), layer["w2"]),)

    def _forward(self, params, tokens, positions, latent, state, write_pages,
                 write_offsets, valid, kda, mla, length=None):
        """The layers over ``tokens`` rows, each piece under its part of the
        program (``telemetry.PROGRAM_PARTS``). ``kda(layer, qkv, decay,
        beta, state of the layer) -> (out (N, H, D), state)`` and
        ``mla(layer, q_nope, q_rope, row, pool of the layer) -> out (N, H,
        dv)`` are what prefill and decode do differently, and ``length``: a
        prefill hands the count of rows that hold its prompt, and what a row
        computes alone then runs over the row blocks under it
        (:func:`~mxnet_tpu.ops.row_blocks.row_blocks`; the rows behind them
        come back zero, and nothing reads them); a decode tick hands none
        and runs every row as straight-line code."""
        import jax
        import jax.numpy as jnp

        from ..ops import moe
        from ..ops.row_blocks import row_blocks

        part = jax.named_scope
        latent, state = tuple(latent), list(state)
        with part("mx_embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        rows = []
        for li, layer in enumerate(params["layers"]):
            at = self._place[li]
            kind = self.cfg["layer_types"][li]
            if kind == "kda":
                with part("mx_kda_proj"):
                    hx, qkv, decay, beta = row_blocks(
                        lambda x: self._kda_inputs(layer, x), (x,), length)
                att, state[at] = kda(layer, qkv, decay, beta, state[at])
            else:
                with part("mx_mla_proj"):
                    hx, q_nope, q_rope, row = row_blocks(
                        lambda x, at: self._mla_rows(layer, x, at),
                        (x, positions), length)
                with part("mx_kv_write"):
                    latent = write_rows(latent, at, row, write_pages,
                                        write_offsets)
                att = mla(layer, q_nope, q_rope, row, latent[at])
            with part("mx_kda_proj" if kind == "kda" else "mx_mla_proj"):
                x, *routed = row_blocks(
                    lambda *row: self._behind_attention(layer, kind, *row),
                    (x, hx, att), length)
            if not routed:
                continue
            # the router stays whole: a top-k over a block of rows lowers to
            # a full sort, 2.2 ms a layer at rung 4096 (PERF.md section 6,
            # PR 48)
            (hm,), cfg = routed, self.cfg
            picks = moe.route(hm, layer["router"], layer["expert_bias"],
                              cfg["num_experts_per_tok"],
                              cfg["norm_topk_prob"],
                              cfg["routed_scaling_factor"],
                              n_group=cfg["n_group"],
                              topk_group=cfg["topk_group"])
            m, n_rows = moe.expert_layer(
                hm, picks, layer["experts"], tuple(cfg["held_experts"]),
                shared=layer["shared"], valid=valid, length=length)
            rows.append(n_rows)
            with part("mx_moe_combine"):
                x = x + m
        with part("mx_head"):
            counters = (jnp.stack(rows),) if rows else ()
        return x, latent, tuple(state), counters

    # -- contract -------------------------------------------------------
    def prefill(self, params, tokens, length, latent, state, write_pages,
                write_offsets, attn=None, slot=None):
        import jax
        import jax.numpy as jnp

        from ..ops import kda as kda_ops
        from ..ops import pallas_kernels
        from ..ops.pallas_kernels import LANES
        from ..ops.row_blocks import row_blocks

        if attn is not None or slot is None:
            raise MXNetError("LingDecoder prefills one slot's state: no ring "
                             "attention, and `slot` is not optional")
        cfg = self.cfg
        t = tokens.shape[0]
        taps = cfg["short_conv_kernel_size"]
        nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        wide = -(-(nope + cfg["qk_rope_head_dim"]) // LANES) * LANES
        with jax.named_scope("mx_embed"):
            positions = jnp.arange(t, dtype=jnp.int32)
            valid = positions < length

        def kda(layer, qkv, decay, beta, held):
            # the slot's state is written where it is made, `soon`: left to
            # itself the scheduler writes every layer's at the program's end
            # and keeps what it is made from until then (1.2 GB of `qkv` at
            # rung 4096; compile, PR 48)
            soon = jax.lax.optimization_barrier
            with jax.named_scope("mx_kda_proj"):
                # the convolution reaches across rows: it stays whole
                qkv_heads = self._kda_heads(
                    kda_ops.short_conv(qkv, layer["conv"]))
                tails = held[1].at[slot].set(
                    kda_ops.conv_tail(qkv, length, taps))
                (q, k, v), tails = soon((qkv_heads, tails))
            with jax.named_scope("mx_kda_state"):
                out, s_new = kda_ops.chunked_scan(q, k, v, decay, beta,
                                                  length=length)
                out, states = soon((out, held[0].at[slot].set(s_new)))
            return out, (states, tails)

        def mla(layer, q_nope, q_rope, row, _pool):
            def expand(q_nope, q_rope, row):
                """Every head's keys and values from the row a token
                keeps."""
                n = row.shape[0]
                kv = _mm(row[:, :cfg["kv_lora_rank"]], layer["wkvb"]
                         ).reshape(n, self.num_heads, nope + dv)
                k_rope = jnp.broadcast_to(
                    row[:, None, cfg["kv_lora_rank"]:],
                    (n, self.num_heads, q_rope.shape[-1]))
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
                # one head size for the three operands: the lane tile above
                # the keys' (the values' zeros are cut off again)
                return tuple(
                    jnp.pad(x, ((0, 0), (0, 0), (0, wide - x.shape[-1])))
                    for x in (q, k, kv[..., nope:]))

            with jax.named_scope("mx_mla_proj"):
                q, k, v = row_blocks(expand, (q_nope, q_rope, row), length)
            with jax.named_scope("mx_attn"):
                out = pallas_kernels.band_attention(
                    q, k, v, scale=self.scale, precise=True, length=length)
            return out[..., :dv]

        x, latent, state, counters = self._forward(
            params, tokens, positions, latent, state, write_pages,
            write_offsets, valid, kda, mla, length=length)
        with jax.named_scope("mx_head"):
            last = _mm(self._rms(x[length - 1], params["ln_f"])[None],
                       params["head"])[0]
        return (last, latent, state) + counters

    def prefill_rows(self, tokens: int, rung: int) -> int:
        """Rows of ``rung`` that :meth:`prefill`'s row-wise passes compute
        for a prompt of ``tokens``: those of the row blocks it reaches."""
        from ..ops import row_blocks

        return row_blocks.rows_visited(tokens, rung)

    def prefill_chunk(self, params, tokens, start, length, latent, state,
                      page_table_row, write_pages, write_offsets):
        raise MXNetError(
            "LingDecoder offers no chunked prefill: serve it with "
            "prefix_cache=False, prefill_chunk=0")

    def decode(self, params, tokens, positions, latent, state, page_tables,
               seq_lens, write_pages, write_offsets):
        import jax
        import jax.numpy as jnp

        from ..ops import kda as kda_ops
        from ..ops import pallas_kernels

        if tokens.shape[0] != page_tables.shape[0]:
            raise MXNetError("LingDecoder decodes one token a slot "
                             "(spec_k=0)")
        cfg = self.cfg
        rank = cfg["kv_lora_rank"]
        nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        with jax.named_scope("mx_embed"):
            valid = seq_lens > 0
        with jax.named_scope("mx_kda_state"):
            # the live slots, once for every kda layer's update
            walk = pallas_kernels.kda_state_walk(valid)

        def kda(layer, qkv, decay, beta, held):
            with jax.named_scope("mx_kda_proj"):
                mixed, tail = kda_ops.short_conv_step(
                    qkv, held[1], layer["conv"], valid)
                q, k, v = self._kda_heads(mixed)
            with jax.named_scope("mx_kda_state"):
                out, s_new = pallas_kernels.kda_state_step(
                    q, k, v, decay, beta, held[0], valid, walk=walk)
            return out, (s_new, tail)

        def mla(layer, q_nope, q_rope, _row, pool):
            with jax.named_scope("mx_mla_proj"):
                # absorbed: the keys' expansion goes into the query ...
                wkvb = layer["wkvb"].reshape(rank, self.num_heads, nope + dv)
                q = jnp.concatenate(
                    [_mm_heads(q_nope, wkvb[..., :nope], "shd,chd->shc"),
                     q_rope], axis=-1)
            with jax.named_scope("mx_attn"):
                seen = pallas_kernels.paged_latent_attention(
                    q, pool, page_tables, seq_lens, rank, self.scale)
            with jax.named_scope("mx_mla_proj"):
                # ... and the values' behind the weighted sum of the rows
                return _mm_heads(seen, wkvb[..., nope:], "shc,chd->shd")

        x, latent, state, counters = self._forward(
            params, tokens, positions, latent, state, write_pages,
            write_offsets, valid, kda, mla)
        with jax.named_scope("mx_head"):
            logits = _mm(self._rms(x, params["ln_f"]), params["head"])
        return (logits, latent, state) + counters
