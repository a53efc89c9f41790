"""``afmoe`` (Arcee Trinity) through the paged-decode contract: one chip's
share of an expert-parallel deployment.

Per layer (the equations, with every departure from the published model, are
at the head of :mod:`mxnet_tpu.serving.afmoe_reference`): sandwich RMS norms,
grouped-query attention with per-head QK-norm and a sigmoid output gate;
``sliding_attention`` layers rotate q and k (RoPE) and see the last
``sliding_window`` positions, ``full_attention`` layers see every position
and no position signal at all; the MLP is a dense SwiGLU in the first
``num_dense_layers`` layers and an expert layer after them
(:mod:`mxnet_tpu.ops.moe`: a router over all ``num_experts``, the grouped
product over the ``held_experts`` that live here, one shared expert).

What it declares to :class:`~mxnet_tpu.serving.DecodeEngine`:

``layer_state``
    ``("paged",)`` for a full layer, which keeps every page, ``("ring",
    sliding_window)`` for a window layer: a ring of ``sliding_window /
    page_size + 1`` pages a slot. Two groups of pools
    (:func:`~mxnet_tpu.serving.kvcache.make_cache`), so ``pools``, page
    tables and write pages arrive as ``(full, window)`` pairs, a group's
    pools as ``(k layers, v layers)``; ``state`` is ``()``;
``moe_counters``
    ``decode`` and ``prefill`` return the rows routed to each held expert of
    each expert layer (last column: to experts held elsewhere) behind the
    logits;
``prefill_attn_blocks``
    the block pairs :func:`~mxnet_tpu.ops.pallas_kernels.band_attention`
    multiplies for a prompt, over the layers: the padding of a rung behind
    the prompt's last block is not computed;
``prefill_rows``
    the rows of a rung a prefill's row-wise passes (norms, projections,
    router, shared expert, combine) compute for a prompt: those of the row
    blocks the prompt reaches (:mod:`mxnet_tpu.ops.row_blocks`).

Decode attends through :func:`~mxnet_tpu.ops.pallas_kernels.paged_attention`
(full) and :func:`~mxnet_tpu.ops.pallas_kernels.paged_window_attention`
(window, its own ring table); prefill through
:func:`~mxnet_tpu.ops.pallas_kernels.band_attention` (blocked, causal +
window, never an ``(H, T, T)`` tensor). Activations are float32 for real:
every product against bfloat16 weights takes them as two bfloat16 terms
(:func:`mxnet_tpu.ops.moe.matmul`) and the attention kernels multiply at
float32 precision (``precise=True``), because the router amplifies rounding:
with one bfloat16 pass a product, 3 % of the tokens pick another expert than
the float32 reference does (PERF.md section 6, PR 27). ``prefill_chunk`` is not offered: a
chunk would have to read earlier chunks back through a ring that the prompt's
own tail is overwriting, so the engine serves this model with
``prefix_cache=False, prefill_chunk=0``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from ..base import MXNetError
from .decode import PagedDecodeModel
from .kvcache import place_layers, write_kv

__all__ = ["AfmoeDecoder"]

KINDS = ("sliding_attention", "full_attention")


def _mm(x, w):
    """Float32 activations times weights in their served type
    (:func:`mxnet_tpu.ops.moe.matmul`: two bfloat16 terms against bfloat16
    weights, so the activations really are float32)."""
    from ..ops import moe

    return moe.matmul(x, w)


class AfmoeDecoder(PagedDecodeModel):
    """See the module. Arguments are the keys of the model's ``config.json``
    (``layer_types`` lists the layers held here) plus ``held_experts =
    (first, count)``, the experts of each layer that live on this chip."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: int, intermediate_size: int,
                 moe_intermediate_size: int, layer_types: Sequence[str],
                 num_dense_layers: int, num_experts: int,
                 num_experts_per_tok: int, sliding_window: int,
                 held_experts=None, num_shared_experts: int = 1,
                 rope_theta: float = 10000.0, rms_norm_eps: float = 1e-5,
                 route_norm: bool = True, route_scale: float = 1.0,
                 mup_enabled: bool = False):
        if num_attention_heads % num_key_value_heads:
            raise MXNetError("num_attention_heads %d %% num_key_value_heads "
                             "%d != 0" % (num_attention_heads,
                                          num_key_value_heads))
        bad = sorted(set(layer_types) - set(KINDS))
        if bad:
            raise MXNetError("unknown layer_types %s (known: %s)"
                             % (bad, list(KINDS)))
        if num_shared_experts != 1:
            raise MXNetError("afmoe has one shared expert, got %d"
                             % num_shared_experts)
        held = (0, num_experts) if held_experts is None \
            else tuple(int(x) for x in held_experts)
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > num_experts:
            raise MXNetError("held_experts %s outside 0..%d"
                             % (held, num_experts))
        self.cfg = {
            "vocab_size": int(vocab_size), "hidden_size": int(hidden_size),
            "num_attention_heads": int(num_attention_heads),
            "num_key_value_heads": int(num_key_value_heads),
            "head_dim": int(head_dim),
            "intermediate_size": int(intermediate_size),
            "moe_intermediate_size": int(moe_intermediate_size),
            "layer_types": list(layer_types),
            "num_dense_layers": int(num_dense_layers),
            "num_experts": int(num_experts),
            "num_experts_per_tok": int(num_experts_per_tok),
            "held_experts": list(held),
            "sliding_window": int(sliding_window),
            "rope_theta": float(rope_theta),
            "rms_norm_eps": float(rms_norm_eps),
            "route_norm": bool(route_norm),
            "route_scale": float(route_scale),
            "mup_enabled": bool(mup_enabled),
        }
        self.vocab_size = int(vocab_size)
        self.num_layers = len(layer_types)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.scale = float(head_dim) ** -0.5
        if len(set(layer_types)) != 2:
            raise MXNetError("AfmoeDecoder needs layers of both kinds, got "
                             "%s" % list(layer_types))
        self.layer_state = [("ring", int(sliding_window))
                            if kind == "sliding_attention" else ("paged",)
                            for kind in layer_types]
        # layer -> (group, index inside the group's pools)
        self._place = place_layers(self.layer_state)
        n_expert_layers = self.num_layers - int(num_dense_layers)
        self.moe_counters = (n_expert_layers, held[1] + 1) \
            if n_expert_layers > 0 else None

    def init_params(self, seed: int = 0, dtype="float32"):
        from . import afmoe_reference

        return afmoe_reference.init_params(self.cfg, seed, dtype)

    # -- shared pieces --------------------------------------------------
    def _rms(self, x, g):
        import jax.numpy as jnp

        return x * g / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                         keepdims=True)
                                + self.cfg["rms_norm_eps"])

    def _rope(self, x, positions):
        import jax.numpy as jnp

        d = self.head_dim
        inv = self.cfg["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def _embed(self, params, tokens):
        import jax.numpy as jnp

        x = params["embed"][tokens].astype(jnp.float32)
        if self.cfg["mup_enabled"]:
            x = x * self.cfg["hidden_size"] ** 0.5
        return x

    def _qkv(self, layer, hx, positions, sliding):
        n = hx.shape[0]
        h, kh, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = self._rms(_mm(hx, layer["wq"]).reshape(n, h, d), layer["q_norm"])
        k = self._rms(_mm(hx, layer["wk"]).reshape(n, kh, d),
                      layer["k_norm"])
        v = _mm(hx, layer["wv"]).reshape(n, kh, d)
        if sliding:
            q, k = self._rope(q, positions), self._rope(k, positions)
        return q, k, v

    def _behind_attention(self, layer, x, hx, att):
        """A row from its attention's output to where its MLP begins. A
        dense layer: the whole rest of the layer, ``(x,)``; an expert layer:
        ``(x, normed row, picks, weights)`` — the router is the last thing a
        row decides alone."""
        import jax

        from ..ops import moe

        part = jax.named_scope
        cfg = self.cfg
        with part("mx_attn_out"):
            att = att.reshape(att.shape[0], -1) \
                * jax.nn.sigmoid(_mm(hx, layer["wg"]))
            x = x + self._rms(_mm(att, layer["wo"]), layer["ln_post_attn"])
        # an expert layer's norm in front goes with its router, the norm
        # behind and the residual with its combine
        if "router" in layer:
            with part("mx_moe_route"):
                hm = self._rms(x, layer["ln_pre_mlp"])
            return (x, hm) + moe.route(
                hm, layer["router"], layer["expert_bias"],
                cfg["num_experts_per_tok"], cfg["route_norm"],
                cfg["route_scale"])
        with part("mx_mlp"):
            hm = self._rms(x, layer["ln_pre_mlp"])
            m = _mm(jax.nn.silu(_mm(hm, layer["w1"])) * _mm(hm, layer["w3"]),
                    layer["w2"])
            return (x + self._rms(m, layer["ln_post_mlp"]),)

    def _forward(self, params, tokens, positions, pools, state,
                 write_pages, write_offsets, valid, attend, length=None):
        """The layers over ``tokens`` rows, each piece under its part of the
        program (``telemetry.PROGRAM_PARTS``); ``attend(sliding, q, k, v,
        the layer's k pool, its v pool)`` is one thing prefill and decode do
        differently. The other
        is ``length``: a prefill hands the count of rows that hold its
        prompt, and what a row computes alone then runs over the row blocks
        under it (:func:`~mxnet_tpu.ops.row_blocks.row_blocks`; the rows
        behind them come back zero, and nothing reads them); a decode tick
        hands none and runs every row as straight-line code."""
        import jax
        import jax.numpy as jnp

        from ..ops import moe
        from ..ops.row_blocks import row_blocks

        part = jax.named_scope
        pools = list(pools)     # a (k layers, v layers) a group
        with part("mx_embed"):
            x = self._embed(params, tokens)
        rows = []
        for li, layer in enumerate(params["layers"]):
            sliding = self.cfg["layer_types"][li] == "sliding_attention"
            grp, gi = self._place[li]

            def qkv(x, positions):
                hx = self._rms(x, layer["ln_in"])
                return (hx,) + self._qkv(layer, hx, positions, sliding)

            with part("mx_qkv"):
                hx, q, k, v = row_blocks(qkv, (x, positions), length)
            with part("mx_kv_write"):
                pools[grp] = k_pool, v_pool = write_kv(
                    *pools[grp], gi, k, v, write_pages[grp], write_offsets)
            with part("mx_attn"):
                att = attend(sliding, q, k, v, k_pool[gi], v_pool[gi])
            with part("mx_attn_out"):
                x, *routed = row_blocks(
                    lambda *row: self._behind_attention(layer, *row),
                    (x, hx, att), length)
            if not routed:
                continue
            hm, sel, weights = routed
            m, n_rows = moe.expert_layer(
                hm, (sel, weights), layer["experts"],
                tuple(self.cfg["held_experts"]), shared=layer["shared"],
                valid=valid, length=length)
            rows.append(n_rows)
            with part("mx_moe_combine"):
                x = x + self._rms(m, layer["ln_post_mlp"])
        with part("mx_head"):
            counters = (jnp.stack(rows),) if rows else ()
        return x, tuple(pools), state, counters

    # -- contract -------------------------------------------------------
    def prefill(self, params, tokens, length, pools, state, write_pages,
                write_offsets, attn=None):
        import jax
        import jax.numpy as jnp

        from ..ops import pallas_kernels

        if attn is not None:
            raise MXNetError("AfmoeDecoder has no ring-attention prefill")
        t = tokens.shape[0]
        with jax.named_scope("mx_embed"):
            positions = jnp.arange(t, dtype=jnp.int32)
            valid = positions < length
        window = self.cfg["sliding_window"]

        def attend(sliding, q, k, v, kp, vp):
            # the rows are in the pools before the attention reads them:
            # left to itself the scheduler writes every layer's at the
            # program's end and keeps them all until then (336 MB at rung
            # 8192; compile, PR 48)
            q, k, v, _, _ = jax.lax.optimization_barrier((q, k, v, kp, vp))
            return pallas_kernels.band_attention(
                q, k, v, scale=self.scale, window=window if sliding else 0,
                precise=True, length=length)

        x, pools, state, counters = self._forward(
            params, tokens, positions, pools, state, write_pages,
            write_offsets, valid, attend, length=length)
        with jax.named_scope("mx_head"):
            last = _mm(self._rms(x[length - 1], params["ln_f"])[None],
                       params["head"])[0]
        return (last, pools, state) + counters

    def prefill_attn_blocks(self, tokens: int, rung: int) -> int:
        """Block pairs :meth:`prefill`'s attention launches multiply for a
        prompt of ``tokens`` on ``rung``, a kv head, over the layers."""
        from ..ops import pallas_kernels

        n_window = self.cfg["layer_types"].count("sliding_attention")
        return n_window * pallas_kernels.band_blocks(
            tokens, rung, self.cfg["sliding_window"]) \
            + (self.num_layers - n_window) * pallas_kernels.band_blocks(
                tokens, rung)

    def prefill_rows(self, tokens: int, rung: int) -> int:
        """Rows of ``rung`` that :meth:`prefill`'s row-wise passes compute
        for a prompt of ``tokens``: those of the row blocks it reaches."""
        from ..ops import row_blocks

        return row_blocks.rows_visited(tokens, rung)

    def prefill_chunk(self, params, tokens, start, length, pools, state,
                      page_table_row, write_pages, write_offsets):
        raise MXNetError(
            "AfmoeDecoder offers no chunked prefill: serve it with "
            "prefix_cache=False, prefill_chunk=0")

    def decode(self, params, tokens, positions, pools, state, page_tables,
               seq_lens, write_pages, write_offsets):
        import jax

        from ..ops import pallas_kernels

        if tokens.shape[0] != page_tables[0].shape[0]:
            raise MXNetError("AfmoeDecoder decodes one token a slot "
                             "(spec_k=0)")
        window = self.cfg["sliding_window"]

        def attend(sliding, q, _k, _v, kp, vp):
            if sliding:
                return pallas_kernels.paged_window_attention(
                    q, kp, vp, page_tables[1], seq_lens, window,
                    scale=self.scale, precise=True)
            return pallas_kernels.paged_attention(
                q, kp, vp, page_tables[0], seq_lens, scale=self.scale,
                precise=True)

        with jax.named_scope("mx_embed"):
            valid = seq_lens > 0
        x, pools, state, counters = self._forward(
            params, tokens, positions, pools, state, write_pages,
            write_offsets, valid, attend)
        with jax.named_scope("mx_head"):
            logits = _mm(self._rms(x, params["ln_f"]), params["head"])
        return (logits, pools, state) + counters
