"""Bytes the delta-rule recurrence and the latent attention of a decode tick
have to move, counted from what the program's spans say a tick worked on
(state bytes of its live slots, latent rows of its live tokens). Both are
bound by memory at a token a slot: the recurrence reads and writes a ``(d_k,
d_v)`` float32 matrix a head for some ``4 d_k d_v`` operations on it, the
latent attention reads a row of ``width`` floats for ``2 x 2 x heads x
width`` operations. As in ``flops.py``, a share over 100 % raises
(``flops.share_of_peak``).
"""
from __future__ import annotations


def state_least_seconds(state_bytes_moved, peaks):
    """Least time of a tick's recurrence: the state bytes its live slots'
    updates read and wrote (``state_bytes_moved`` of ``mx.decode.commit``:
    each live slot's state once each way, over the state layers) over the
    peak memory rate."""
    return state_bytes_moved / peaks["hbm_bytes_per_s"]


def latent_row_width(model):
    """Floats of a latent row ``[c; k_r]``: what the algorithm has to read a
    token, whatever width the pool holds it at."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def latent_least_seconds(latent_rows_read, model, itemsize, peaks):
    """Least time of a tick's latent attention: every live token's row in
    every latent layer (``latent_rows_read`` of ``mx.decode.commit``), once,
    over the peak memory rate. The row is key and value at once: it is read
    ONCE (the launch fetches it twice, as its K and as its V operand)."""
    return latent_rows_read * latent_row_width(model) * itemsize \
        / peaks["hbm_bytes_per_s"]


def latent_prefill_flops(latent_rows_read, model):
    """Operations of a prefill's latent attention in expanded form:
    ``latent_rows_read`` of ``mx.decode.prefill`` is the prompt's tokens a
    latent layer, summed over the latent layers; a layer's causal band has
    ``t (t + 1) / 2`` pairs, each a product over ``qk_nope_head_dim +
    qk_rope_head_dim`` for the score and one over ``v_head_dim`` for the
    value, every head."""
    layers = sum(k == "mla" for k in model["layer_types"])
    tokens = latent_rows_read // layers
    return layers * 2 * (tokens * (tokens + 1) // 2) * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"]) * model["num_attention_heads"]
