"""Operations and bytes of the expert layer and of window/full attention,
counted from shapes and from what the program's spans say a program run
worked on (rows routed, experts hit, live K/V rows). As in ``flops.py``, one
multiply-add is two operations and a share over 100 % raises
(``flops.share_of_peak``).
"""
from __future__ import annotations


def grouped_swiglu_least_seconds(rows, experts_hit, hidden, width, peaks,
                                 weight_itemsize=2, act_itemsize=4):
    """Least time of the three grouped products of a SwiGLU expert layer
    (``x w1``, ``x w3``, ``(..) w2``) over ``rows`` (token, pick) rows that
    reach ``experts_hit`` distinct experts: the larger of its operations
    over the peak arithmetic rate and its bytes over the peak memory rate.
    Bytes: each hit expert's three ``hidden x width`` matrices once, and for
    each product every row read and written once. Returns ``(seconds,
    "flops" or "bytes")``."""
    ops = 2 * 3 * hidden * width * rows
    weights = experts_hit * 3 * hidden * width * weight_itemsize
    acts = 3 * rows * act_itemsize * (hidden + width)
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = (weights + acts) / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("flops" if by_ops >= by_bytes
                                   else "bytes")


def grouped_decode_kv_bytes(rows_full, rows_window, full_layers,
                            window_layers, num_kv_heads, head_dim, itemsize):
    """Bytes of K and V one decode tick has to read when the layers are of
    two kinds: every live token's row in each full layer, at most the
    window's rows in each window layer."""
    rows = rows_full * full_layers + rows_window * window_layers
    return 2 * rows * num_kv_heads * head_dim * itemsize


def band_pairs(tokens, window=0):
    """(query, key) pairs of causal attention over ``tokens`` positions,
    each query seeing at most its last ``window`` keys (0: all)."""
    if not window or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def band_attention_flops(tokens, num_heads, head_dim, window=0):
    """Operations of one layer's prefill attention: ``q k^T`` and ``p v``
    over the band's pairs, every query head."""
    return 2 * 2 * band_pairs(tokens, window) * head_dim * num_heads


def layer_kinds(model):
    """``(full layers, window layers, expert layers)`` of a configuration's
    ``model`` block."""
    kinds = model["layer_types"]
    full = sum(k == "full_attention" for k in kinds)
    return full, len(kinds) - full, len(kinds) - model["num_dense_layers"]
