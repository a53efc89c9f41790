"""First-party Pallas TPU kernels.

The detection tail is where XLA's stock ops stop being enough: NMS is a
sequential, data-dependent suppression loop the reference implements as a
custom CUDA kernel (``src/operator/contrib/bounding_box.cu``). Here it is a
Pallas TPU kernel: boxes live in VMEM as (8, N) lane-major rows, the
suppression loop is a ``fori_loop`` whose body is pure VPU work (8x128
vector compare/select — no scalar gather), and N is padded to the 128-lane
boundary. On non-TPU backends (the CPU test mesh) the same kernel runs in
Pallas interpret mode, so correctness is tested everywhere while the TPU
path compiles to a real kernel.

Layout notes (see /opt/skills/guides/pallas_guide.md):
- float32 min tile is (8, 128): inputs are packed into an (8, Np) matrix —
  rows x1,y1,x2,y2,class,keep and two zero rows of padding.
- iota must be >=2D on TPU: all row vectors are kept (1, Np).
- scalar extraction from a lane vector uses a masked sum instead of a
  dynamic gather (VPU-friendly, no SMEM round-trip).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_ROW_X1, _ROW_Y1, _ROW_X2, _ROW_Y2, _ROW_CLS, _ROW_KEEP = range(6)
_PACK_ROWS = 8  # float32 sublane tile


def _interpret() -> bool:
    """The ONE platform decision of this module: Mosaic-compiled kernels on
    a TPU backend, Pallas interpret mode (or, for the paged dispatchers, the
    dense reference) everywhere else. Nothing else — no shape gate, no
    try/except — picks a path, so on a TPU a kernel either runs or the call
    fails with the compiler's message."""
    return jax.default_backend() != "tpu"


def _pad_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _nms_kernel(packed_ref, out_ref, *, n_boxes, overlap_thresh,
                force_suppress):
    """Greedy NMS over score-sorted boxes.

    packed_ref: (8, Np) f32 — rows x1,y1,x2,y2,class,keep(1/0 valid).
    out_ref:    (8, Np) f32 — row 0 is the final keep mask.
    """
    x1 = packed_ref[_ROW_X1:_ROW_X1 + 1, :]
    y1 = packed_ref[_ROW_Y1:_ROW_Y1 + 1, :]
    x2 = packed_ref[_ROW_X2:_ROW_X2 + 1, :]
    y2 = packed_ref[_ROW_Y2:_ROW_Y2 + 1, :]
    cls = packed_ref[_ROW_CLS:_ROW_CLS + 1, :]
    keep0 = packed_ref[_ROW_KEEP:_ROW_KEEP + 1, :]
    np_ = x1.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (1, np_), 1)
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)

    def sel(vec, i):
        # masked-sum scalar extraction: one VPU pass, no dynamic gather
        return jnp.sum(jnp.where(lane == i, vec, 0.0))

    def body(i, keep):
        keep_i = sel(keep, i)
        xi1, yi1 = sel(x1, i), sel(y1, i)
        xi2, yi2 = sel(x2, i), sel(y2, i)
        ci = sel(cls, i)
        ai = jnp.maximum(xi2 - xi1, 0.0) * jnp.maximum(yi2 - yi1, 0.0)
        iw = jnp.maximum(jnp.minimum(x2, xi2) - jnp.maximum(x1, xi1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, yi2) - jnp.maximum(y1, yi1), 0.0)
        inter = iw * ih
        iou = inter / jnp.maximum(area + ai - inter, 1e-12)
        same = jnp.logical_or(bool(force_suppress), cls == ci)
        suppress = jnp.logical_and(
            jnp.logical_and(keep_i > 0.5, lane > i),
            jnp.logical_and(same, iou > overlap_thresh))
        return jnp.where(suppress, 0.0, keep)

    keep = lax.fori_loop(0, n_boxes, body, keep0)
    out_ref[:, :] = jnp.broadcast_to(keep, out_ref.shape)


def nms_keep(boxes, cls_ids, valid, overlap_thresh, force_suppress):
    """Keep mask for greedy NMS over boxes ALREADY sorted by score desc.

    boxes: (N, 4) corner-format f32; cls_ids: (N,) f32 (-1 = no class);
    valid: (N,) bool. Returns (N,) bool.
    """
    n = boxes.shape[0]
    np_ = _pad_up(max(n, LANES), LANES)
    pad = np_ - n

    packed = jnp.zeros((_PACK_ROWS, np_), jnp.float32)
    for row, col in ((_ROW_X1, 0), (_ROW_Y1, 1), (_ROW_X2, 2), (_ROW_Y2, 3)):
        packed = packed.at[row, :n].set(boxes[:, col].astype(jnp.float32))
    packed = packed.at[_ROW_CLS, :n].set(cls_ids.astype(jnp.float32))
    packed = packed.at[_ROW_CLS, n:].set(-2.0)  # padding matches no class
    packed = packed.at[_ROW_KEEP, :n].set(valid.astype(jnp.float32))

    kernel = functools.partial(
        _nms_kernel, n_boxes=n, overlap_thresh=float(overlap_thresh),
        force_suppress=bool(force_suppress))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((_PACK_ROWS, np_), jnp.float32),
        interpret=_interpret(),
    )(packed)
    return out[0, :n] > 0.5


# ---------------------------------------------------------------------------
# Flash attention (TPU fused attention kernel)
# ---------------------------------------------------------------------------
#
# The MXU-resident attention kernel: one pallas_call computes
# softmax(q k^T / sqrt(d)) v without materializing the (S, S) score matrix
# in HBM. Grid (batch*heads, q-blocks, kv-blocks); the kv axis is the
# innermost ("arbitrary") dimension and carries the online-softmax state
# (running max m, normalizer l, weighted accumulator acc) in VMEM scratch.
# Interpret mode runs the same kernel on the CPU test mesh.

_NEG_BIG = -1e30  # -inf would turn exp(m_prev - m_new) into nan on an
#                   all-masked first block; a large-negative sentinel keeps
#                   the online-softmax algebra finite


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale, causal, bq, bk, n_kv, seq_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)          # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = cols < seq_len                    # sequence-padding mask
    if causal:
        valid = valid & (cols <= rows)
    s = jnp.where(valid, s, _NEG_BIG)

    m_prev = m_scr[:, :1]                     # (bq, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _flash_forward(q, k, v, scale, causal, block_q=128, block_k=128):
    """q/k/v: (B, H, S, D) -> (B, H, S, D)."""
    b, h, s_len, d = q.shape
    bq = min(block_q, _pad_up(s_len, 8))
    bk = min(block_k, _pad_up(s_len, 128))
    # pad to a common multiple of BOTH block sizes — padding to only the
    # larger one truncates the other axis's grid and silently drops tail
    # blocks when custom block sizes don't divide it
    sp = _pad_up(s_len, math.lcm(bq, bk))
    pad = ((0, 0), (0, 0), (0, sp - s_len), (0, 0))
    qp = jnp.pad(q, pad).reshape(b * h, sp, d)
    kp = jnp.pad(k, pad).reshape(b * h, sp, d)
    vp = jnp.pad(v, pad).reshape(b * h, sp, d)
    n_q, n_kv = sp // bq, sp // bk

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_kv=n_kv, seq_len=s_len)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="mx_flash_attn",  # what a device trace is searched for
    )(qp, kp, vp)
    return out.reshape(b, h, sp, d)[:, :, :s_len]


def _attention_reference(q, k, v, scale, causal):
    """Pure-jnp attention — the backward recompute path."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        n = s.shape[-1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, scale=None, causal=False):
    """Fused multi-head attention, (B, H, S, D) layout.

    Forward runs the Pallas kernel (flash/online-softmax: O(S) memory, MXU
    matmuls, no (S, S) HBM tensor). Backward differentiates a dense jnp
    recompute, which DOES materialize the (S, S) score matrix — O(S^2)
    memory. The flash memory bound therefore holds for inference and for
    forward-only use; long-sequence TRAINING should shard S first (ring /
    Ulysses in sequence_parallel.py) so each device's S is modest.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_forward(q, k, v, scale, causal)


def _fa_fwd(q, k, v, scale, causal):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_forward(q, k, v, scale, causal), (q, k, v)


def _fa_bwd(scale, causal, res, g):
    q, k, v = res
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    _, vjp = jax.vjp(lambda a, b, c:
                     _attention_reference(a, b, c, scale, causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Ragged paged-attention decode (TPU LLM serving kernel)
# ---------------------------------------------------------------------------
#
# The decode-plane attention of mxnet_tpu.serving.decode: each of S decode
# slots holds W new query tokens (1 on a classic tick or a chunked-prefill
# row, K+1 on a speculative verify tick — ONE kernel, W is a trace-time
# constant) that must attend to that sequence's whole KV history, which
# lives scattered across fixed-size pages of a static device pool
# (serving.kvcache). Shapes are static in (S, max_pages,
# page_size) regardless of how many sequences are live or how long each
# one is — membership churn and ragged lengths never retrace (the Ragged
# Paged Attention argument, PAPERS.md).
#
# Kernel layout: ONE flat grid axis over the LIVE (slot, column) pairs,
# slot-major and columns ascending — its extent a traced scalar, the sum
# over slots of their live columns ``[c0, c1)`` (:func:`live_columns`: the
# table columns that hold a key some query row of the slot may see). A slot
# pays for its own columns and no other's; a slot with none is not visited
# at all (the launch zeroes its rows behind the call). The axis is
# "arbitrary" and carries online-softmax state (running max m, normalizer
# l, accumulator acc) in VMEM scratch, exactly the flash-kernel idiom
# above: a slot's first step resets it, its last one writes the slot's
# rows. The walk's SCHEDULE (:func:`walk_schedule`, built in XLA once a
# table: the launches of a step that share a table and lengths share it by
# common-subexpression elimination) rides in as scalar-prefetch operands
# (PrefetchScalarGridSpec) beside the per-row lengths: for a flat step its
# pool page, its (slot, column) cell, and each slot's first step. So the
# K/V BlockSpec index_map names the pool page directly — it is DMA'd
# straight into VMEM with no gather op in the kernel body — and every grid
# step fetches a page and multiplies it; the ragged mask works position by
# position.
#
# A grid step multiplies its page ONCE for all the page's kv heads: the
# block (page_size, KH, D) is read as one key matrix (page_size * KH, D) —
# with KH a multiple of the sublane tile the same tiles in the same order,
# elsewhere a relayout in the kernel — and the slot's query rows are one
# matrix (R, D) of every head, R = pad_up(KH * W * groups, 8). Two products
# a live page, (R, D) x (page_size * KH, D)^T and (R, page_size * KH) x
# (page_size * KH, D), whatever KH; a score of a row against another kv
# head's key is masked like a position out of the row's sight, so its
# weight is exactly 0. Scratch: m, l (R, LANES), acc (R, D).
# Interpret mode runs the same kernel on the CPU test mesh;
# `paged_attention` (the dispatcher the decode engine calls) uses the dense
# jnp reference off-TPU instead, which is faster than interpreting and
# bit-comparable within fp tolerance.


# a flat step's cell: its slot above _CELL_BITS, its table column below
_CELL_BITS = 16
_CELL_MASK = (1 << _CELL_BITS) - 1


def _paged_kernel(pg_ref, cell_ref, st_ref, sl_ref, qp_ref, *rest, page_size,
                  max_pages, groups, width, scale, causal, window=0,
                  ring=False, precision=None):
    """One live (slot, column) pair of ragged paged attention, ``width``
    query tokens per slot (1 = classic decode tick / chunked-prefill row,
    K+1 = speculative verify tick). The grid is the flat walk of
    :func:`walk_schedule`: step ``t`` is column ``cell_ref[t] & _CELL_MASK``
    of slot ``cell_ref[t] >> _CELL_BITS``, the slot's steps are
    ``[st_ref[slot], st_ref[slot + 1])`` and ``pg_ref[t]`` (the K/V index
    map's) is the pool page the column names.

    q_ref/o_ref: (1, R, D) — ALL the slot's query rows as one matrix, those
    of a kv head contiguous: row ``r = (kh*width + w)*groups + g`` is query
    token ``w``, head ``kh*groups + g``; R pads ``n_kv*width*groups`` to the
    sublane tile. k_ref/v_ref: (1, page_size, KH, D) — the step's page, read
    as ONE key matrix ``(page_size*KH, D)`` for all its kv heads: key row
    ``c = t*KH + kh`` is token ``t`` of the page, kv head ``kh``. Scratch
    m/l: (R, LANES), acc: (R, D).
    sl_ref/qp_ref are (S*width,): PER-QUERY-TOKEN seq_len and (when
    ``causal``) query position.

    A step costs two products, ``(R, D) x (page_size*KH, D)^T`` and
    ``(R, page_size*KH) x (page_size*KH, D)``: a score whose row reads
    another kv head than its column holds is masked like a position the row
    may not see, ``exp`` makes it exactly 0, and the zeros pick each row's
    own head out of the values.

    ``window`` > 0 (static) also masks keys at or below ``query - window``
    (the query is the row's ``q_pos`` when causal, else its last token).
    ``ring`` (static): the table's columns are a ring and a sixth
    scalar-prefetch operand ``blk_ref`` ``(S * max_pages,)`` names the
    page-sized block of the sequence each column holds (-1: none yet).
    ``precision``: of the two products (None: the compiler's default, one
    bfloat16 pass over float32 operands; ``HIGHEST``: float32 products).
    """
    del pg_ref                      # the index maps' own
    if ring:
        blk_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(0)
    cell = cell_ref[t]
    s = cell >> _CELL_BITS
    j = cell & _CELL_MASK

    @pl.when(t == st_ref[s])
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    r_pad, d = acc_scr.shape
    # (a latent pool's block has no head axis: (1, page_size, D))
    n_kv = k_ref.shape[2] if len(k_ref.shape) == 4 else 1
    rows = width * groups           # the query rows of one kv head
    keys = page_size * n_kv

    # the mask, per query row and key row: the key's kv head is the row's,
    # and its token position lies under the row's length (and its query
    # position when causal). The w of a row is an unrolled select over the
    # width scalar-prefetch entries; a pad row reads kv head KH, which no key
    # holds, and comes out as zeros.
    row = lax.broadcasted_iota(jnp.int32, (r_pad, 1), 0)
    row_kh = row // rows
    row_w = (row - row_kh * rows) // groups
    sl_rows = jnp.zeros((r_pad, 1), jnp.int32)
    qp_rows = jnp.zeros((r_pad, 1), jnp.int32)
    for w in range(width):
        sl_rows = jnp.where(row_w == w, sl_ref[s * width + w], sl_rows)
        if causal:
            qp_rows = jnp.where(row_w == w, qp_ref[s * width + w], qp_rows)
    col = lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    first = blk_ref[s * max_pages + j] if ring else j
    pos = first * page_size + col // n_kv
    valid = jnp.logical_and(col % n_kv == row_kh, pos < sl_rows)
    if causal:
        valid = jnp.logical_and(valid, pos <= qp_rows)
    if window or ring:
        # a ring column that holds no block yet has first = -1: pos < 0
        low = (qp_rows if causal else sl_rows - 1) - window + 1 \
            if window else 0
        valid = jnp.logical_and(valid, pos >= jnp.maximum(low, 0))

    q = q_ref[0].astype(jnp.float32)                        # (R, D)
    k = k_ref[0].astype(jnp.float32).reshape(keys, d)
    v = v_ref[0].astype(jnp.float32).reshape(keys, d)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid, scores, _NEG_BIG)
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(t == st_ref[s + 1] - 1)
    def _finish():
        # a fully-masked row (padded draft row, seq_len 0 beside a live row
        # of its slot) never raises the running max off the sentinel: gate
        # on the max and emit zeros (the row IS the slot's output, there is
        # no padding to drop as in the flash kernel, where such a row's p =
        # exp(NEG_BIG - NEG_BIG) = 1 accumulates garbage)
        seen = m_scr[:, :1] > _NEG_BIG * 0.5
        o = jnp.where(seen,
                      acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30),
                      0.0)
        o_ref[0] = o.astype(o_ref.dtype)


def ring_blocks(seq_lens, columns, page_size):
    """Which page-sized block of its sequence each column of a RING page
    table holds: ``(S, columns)`` int32, -1 where the column holds none yet.
    Position ``p`` lives in column ``(p // page_size) % columns`` (the
    contract of ``serving.kvcache.RingKVCache``), so with the last token in
    block ``b`` column ``j`` holds the latest block at or below ``b`` that
    is congruent to ``j``."""
    last = (seq_lens.astype(jnp.int32) - 1) // page_size      # -1: empty
    col = jnp.arange(columns, dtype=jnp.int32)[None, :]
    return last[:, None] - (last[:, None] - col + columns) % columns


def live_columns(seq_lens, q_pos, columns, page_size, window=0, ring=False):
    """The columns ``[c0, c1)`` of each slot's page table that hold a key
    some query row of the slot may see — what :func:`_paged_kernel` walks.
    seq_lens (and q_pos, or None): ``(S, W)`` per query row; ``columns``,
    ``page_size``, ``window``, ``ring`` static. Returns ``(S, 2)`` int32.

    A row sees positions ``[lo, hi)``: ``hi`` its length (cut at its query
    position when causal), ``lo`` the window's lower edge (0 without one).
    Ordinary table: from the column of the lowest ``lo`` to the column of
    the highest ``hi`` over the rows that see anything. Ring table: before
    it wraps the live columns are the prefix that holds a block; after it
    every column does, and at most one of them lies below the window. A
    slot whose rows see nothing: ``c0 = c1 = 0``. ``c0 < columns`` always."""
    sl = seq_lens.astype(jnp.int32)
    query = sl - 1 if q_pos is None else q_pos.astype(jnp.int32)
    hi = jnp.minimum(sl, query + 1)
    lo = jnp.zeros_like(hi)
    if window and not ring:
        lo = jnp.maximum(query - window + 1, 0)
    sees = hi > lo
    c1 = jnp.where(sees, -(-hi // page_size), 0).max(axis=1)
    c1 = jnp.minimum(c1, columns)
    c0 = jnp.where(sees, lo // page_size, columns).min(axis=1)
    return jnp.stack([jnp.minimum(c0, jnp.maximum(c1 - 1, 0)), c1], axis=1)


def walk_schedule(live, page_table):
    """The flat walk of :func:`_paged_kernel` over the live (slot, column)
    pairs of a launch, slot-major and columns ascending. live: ``(S, 2)``
    int32 :func:`live_columns`; page_table: ``(S, max_pages)`` int32.
    Returns ``(page_of, cell_of, starts)``:

    - ``starts`` ``(S + 1,)``: slot ``s`` owns the steps ``[starts[s],
      starts[s + 1])``, as many as it has live columns — a slot with none
      owns no step; ``starts[S]`` is the walk's extent;
    - ``cell_of`` ``(S * max_pages + 1,)``: step ``t`` is column
      ``cell_of[t] & _CELL_MASK`` of slot ``cell_of[t] >> _CELL_BITS``;
    - ``page_of``, as long: the pool page that column names.

    The two are as long as a walk can be and one more (the pipeline
    evaluates the index maps of the step AFTER the one it runs); every
    entry past the extent repeats the last step's, which asks for no new
    copy. Compare-and-sum over the slots' boundaries and one gather of the
    table: no loop, nothing a device trace would show beside the kernel."""
    s_slots, max_pages = page_table.shape
    count = live[:, 1] - live[:, 0]
    slot_ids = jnp.arange(s_slots, dtype=jnp.int32)
    starts = jnp.where(
        jnp.arange(s_slots + 1, dtype=jnp.int32)[:, None] > slot_ids[None],
        count[None], 0).sum(axis=1)
    t = jnp.minimum(jnp.arange(s_slots * max_pages + 1, dtype=jnp.int32),
                    jnp.maximum(starts[-1] - 1, 0))
    # the slots whose first step lies at or before t: all up to t's own
    # (the boundaries ascend), so its slot is their count and what its
    # column lies off t is the telescoped sum of their differences
    begun = t[:, None] >= starts[None, 1:-1]
    slot = begun.sum(axis=1, dtype=jnp.int32)
    off = starts[:-1] - live[:, 0]
    col = t - off[0] - jnp.where(begun, (off[1:] - off[:-1])[None],
                                 0).sum(axis=1)
    page_of = page_table.ravel()[slot * max_pages + col]
    return page_of, (slot << _CELL_BITS) | col, starts


def _paged_call(q, k_pool, v_pool, page_table, seq_lens, q_pos, scale,
                interpret, who, window=0, ring=False, precise=False,
                name="mx_paged_attn"):
    """Shared launch of :func:`_paged_kernel`. q: (S, W, H, D); seq_lens
    (and q_pos, when not None): (S*W,) per query token. ``window`` (static)
    masks keys at or below ``query - window``; ``ring`` (static) reads
    ``page_table`` as a ring of columns (one query token a slot);
    ``precise`` (static) asks for float32 products where the compiler's
    default is one bfloat16 pass. The pools may hold their rows wider than
    D (zero lanes: ``serving.kvcache.pool_row_width``): the query grows to
    them with zeros and the result is cut back. A pool of THREE axes ``(P,
    page_size, D)`` is a latent pool: one row a token, no head axis, read by
    every query head. ``name``: the custom call's, what a device trace is
    searched for. Returns (S, W, H, D)."""
    s_slots, width, n_heads, d_q = q.shape
    page_size, d = k_pool.shape[1], k_pool.shape[-1]
    n_kv = k_pool.shape[2] if k_pool.ndim == 4 else 1
    if scale is None:
        scale = 1.0 / (d_q ** 0.5)
    if d != d_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d - d_q)))
    if n_heads % n_kv:
        raise ValueError("%s: %d heads not divisible by %d kv heads"
                         % (who, n_heads, n_kv))
    if seq_lens.shape[0] != s_slots * width:
        raise ValueError("%s: seq_lens %s != S*W = %d"
                         % (who, seq_lens.shape, s_slots * width))
    if ring and width != 1:
        raise ValueError("%s: a ring table serves one query token a slot, "
                         "got %d" % (who, width))
    groups = n_heads // n_kv
    max_pages = page_table.shape[1]
    if max_pages > _CELL_MASK:
        raise ValueError("%s: a page table of %d columns (a walk's cell "
                         "holds %d)" % (who, max_pages, _CELL_MASK))
    causal = q_pos is not None
    if interpret is None:
        interpret = _interpret()

    # (S, W, KH, G, D) -> (S, KH*W*G, D): one matrix of every query row,
    # those of a kv head contiguous, padded as a whole to the f32 sublane
    # tile. Pad rows read no kv head (see the kernel's mask), each row's
    # softmax state is independent, and they are sliced off on return —
    # layout, not math.
    rows = n_kv * width * groups
    r_pad = _pad_up(rows, _PACK_ROWS)
    qk = q.reshape(s_slots, width, n_kv, groups, d).transpose(0, 2, 1, 3, 4)
    qk = jnp.pad(qk.reshape(s_slots, rows, d),
                 ((0, 0), (0, r_pad - rows), (0, 0)))
    more = {}
    if window or ring:
        more = {"window": int(window), "ring": bool(ring)}
    if precise:
        more["precision"] = lax.Precision.HIGHEST
    kernel = functools.partial(
        _paged_kernel, page_size=page_size, max_pages=max_pages,
        groups=groups, width=width, scale=float(scale), causal=causal,
        **more)
    sl = seq_lens.astype(jnp.int32)
    qpos = q_pos.astype(jnp.int32) if causal else jnp.zeros_like(sl)
    live = live_columns(
        sl.reshape(s_slots, width),
        qpos.reshape(s_slots, width) if causal else None,
        max_pages, page_size, window=window, ring=ring)
    # the walk's schedule, lengths, query positions (+ the ring's block
    # numbers): traced data all, so no length ever retraces
    page_of, cell_of, starts = walk_schedule(
        live, page_table.astype(jnp.int32))
    scalars = (page_of, cell_of, starts, sl, qpos)
    if ring:
        scalars += (ring_blocks(sl, max_pages, page_size).ravel(),)

    def q_map(t, page_of, cell_of, *_):
        return (cell_of[t] >> _CELL_BITS, 0, 0)

    def page_map(t, page_of, *_):
        return (page_of[t],) + (0,) * (k_pool.ndim - 1)

    page_block = (1,) + k_pool.shape[1:]

    spec = dict(
        # one step a live (slot, column) pair (a launch with none still
        # runs one step: nothing it leaves behind is kept, see below)
        grid=(jnp.maximum(starts[-1], 1),),
        in_specs=[
            pl.BlockSpec((1, r_pad, d), q_map),
            pl.BlockSpec(page_block, page_map),
            pl.BlockSpec(page_block, page_map),
        ],
        out_specs=pl.BlockSpec((1, r_pad, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((r_pad, LANES), jnp.float32),
            pltpu.VMEM((r_pad, LANES), jnp.float32),
            pltpu.VMEM((r_pad, d), jnp.float32),
        ],
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=6, **spec) \
        if ring else pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=5,
                                                  **spec)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s_slots, r_pad, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, qk, k_pool, v_pool)
    # a slot with no live column was never visited: its rows are whatever
    # the buffer held (one step an empty slot, writing zeros, was timed
    # beside this on the chip: 10.6 us a launch more at OPT's widths with
    # fifteen slots empty, 11.1 at Trinity's — PERF.md §6, PR 40)
    out = jnp.where((live[:, 1] > live[:, 0])[:, None, None], out, 0)
    out = out[:, :rows, :d_q].reshape(s_slots, n_kv, width, groups, d_q)
    return out.transpose(0, 2, 1, 3, 4).reshape(s_slots, width, n_heads,
                                                d_q)


def ragged_paged_attention(q, k_pool, v_pool, page_table, seq_lens,
                           q_pos=None, scale=None, interpret=None,
                           precise=False):
    """Ragged paged-attention for decode: one query token per slot.

    q: (S, H, D); k_pool/v_pool: (P, page_size, KH, D) static pools;
    page_table: (S, max_pages) int32 page ids (the walk visits a
    sequence's live pages, so later entries are not read — but a launch
    with no live page at all still names one entry: keep every entry a
    valid page id);
    seq_lens: (S,) int32 tokens live per slot (0 = inactive slot, output
    row is zeros).
    q_pos: optional (S,) int32 — when given, the causal bound: positions
    > q_pos[s] are masked even if < seq_lens[s] (decode passes None: the
    new token sits at seq_len - 1 and sees the whole prefix).
    H % KH == 0 (grouped-query attention: head h reads kv head h // g).

    Static in every shape — membership churn, ragged lengths and page
    reassignment never recompile. Returns (S, H, D).
    """
    return _paged_call(q[:, None], k_pool, v_pool, page_table, seq_lens,
                       q_pos, scale, interpret, "ragged_paged_attention",
                       precise=precise)[:, 0]


def _dense_paged(q, k_pool, v_pool, page_table, valid, scale):
    """Dense attention of one query a slot over the keys its table's pages
    hold, ``valid`` (S, columns * page_size) saying which of them count; a
    slot with none comes back zeros. The body of both paged references."""
    s_slots, n_heads, d = q.shape
    _, page_size, n_kv, _ = k_pool.shape
    groups = n_heads // n_kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    t = page_table.shape[1] * page_size
    # (S, columns, page_size, KH, D) -> (S, T, KH, D); a pool may hold its
    # rows wider than D (zero lanes)
    k = k_pool[page_table][..., :d].reshape(s_slots, t, n_kv, d)
    v = v_pool[page_table][..., :d].reshape(s_slots, t, n_kv, d)
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(valid[:, None, :], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("sht,sthd->shd", p, v.astype(jnp.float32))
    return jnp.where(valid.any(axis=1)[:, None, None], out,
                     0.0).astype(q.dtype)


def paged_attention_reference(q, k_pool, v_pool, page_table, seq_lens,
                              q_pos=None, scale=None):
    """Dense jnp ragged paged attention — the kernel's parity oracle and
    the decode path on non-TPU backends (faster than interpret mode;
    gathers (S, max_pages*page_size) KV views, so it trades the kernel's
    O(page) VMEM residency for plain XLA gathers)."""
    t = page_table.shape[1] * k_pool.shape[1]
    pos = jnp.arange(t, dtype=jnp.int32)
    valid = pos[None, :] < seq_lens.astype(jnp.int32)[:, None]
    if q_pos is not None:
        valid = valid & (pos[None, :] <= q_pos.astype(jnp.int32)[:, None])
    return _dense_paged(q, k_pool, v_pool, page_table, valid, scale)


def paged_prefill_attention(q, k_pool, v_pool, page_row, start, length,
                            scale=None):
    """Chunked-prefill attention: C chunk queries of ONE sequence attend
    over that sequence's pages (the prior prefix written by earlier
    chunks/shared prefix pages AND the chunk's own rows, which the model
    scatters into the pool before calling this).

    q: (C, H, D) — the chunk's queries at absolute positions
    ``start .. start+C-1``; page_row: (max_pages,) int32, the sequence's
    page-table row; start/length: traced int32 scalars — ``length`` is
    the chunk's real token count (padding rows beyond it come back
    zeroed). Reuses the decode kernel by treating each chunk token as
    its own grid row sharing one page table — every shape is static in
    (C, max_pages, page_size), so one compile serves every chunk of a
    rung no matter where it starts. Returns (C, H, D).
    """
    c = q.shape[0]
    idx = jnp.arange(c, dtype=jnp.int32)
    q_pos = start.astype(jnp.int32) + idx
    # query i sees positions <= start+i (causal), padding rows see nothing
    seq_lens = jnp.where(idx < length, q_pos + 1, 0).astype(jnp.int32)
    pt = jnp.broadcast_to(page_row.astype(jnp.int32)[None, :],
                          (c, page_row.shape[0]))
    return paged_attention(q, k_pool, v_pool, pt, seq_lens, q_pos=q_pos,
                           scale=scale)


def paged_attention(q, k_pool, v_pool, page_table, seq_lens, q_pos=None,
                    scale=None, precise=False):
    """Dispatcher the decode engine traces: the Pallas kernel on a TPU
    backend, the jnp reference elsewhere — same math, tested for parity in
    interpret mode. The platform is the ONLY gate: there is no shape gate
    (the kernel's blocks span the pool's full (KH, D) minor dims, so any
    page_size/head_dim lowers — tests/test_chip_compile.py), and a
    compiler refusal on a TPU propagates instead of demoting."""
    if not _interpret():
        return ragged_paged_attention(q, k_pool, v_pool, page_table,
                                      seq_lens, q_pos=q_pos, scale=scale,
                                      interpret=False, precise=precise)
    return paged_attention_reference(q, k_pool, v_pool, page_table,
                                     seq_lens, q_pos=q_pos, scale=scale)


def paged_latent_attention_reference(q, pool, page_table, seq_lens, rank,
                                     scale):
    """Dense jnp form of :func:`paged_latent_attention` (the CPU path and
    the kernel's parity oracle)."""
    pool = pool[:, :, None]         # one "kv head"
    return paged_attention_reference(q, pool, pool, page_table, seq_lens,
                                     scale=scale)[..., :rank]


def paged_latent_attention(q, pool, page_table, seq_lens, rank, scale,
                           interpret=None):
    """Decode attention over a LATENT pool: one row a token and no head axis,
    ``[c (rank); k_r]`` — every head's key is the whole row and every head's
    value its first ``rank`` columns (latent attention in its absorbed form:
    DeepSeek-V2, arXiv:2405.04434).

    q: (S, H, W) absorbed queries ``[W_kvb^K^T q_nope; q_rope]``, ``W`` the
    width of the row's content; pool: (P, page_size, Dw) — rows held ``Dw
    >= W`` wide (zero lanes); page_table: (S, max_pages);
    seq_lens: (S,). Returns ``(S, H, rank)``: ``sum_s softmax_s(q . row_s *
    scale) row_s[:rank]``. The launch is :func:`_paged_kernel`'s own, the
    SAME array as its K and its V operand and float32 products, under the
    name ``mx_mla_attn``; what it computes past ``rank`` is dropped."""
    if interpret is None:
        if _interpret():
            return paged_latent_attention_reference(
                q, pool, page_table, seq_lens, rank, scale)
        interpret = False
    out = _paged_call(q[:, None], pool, pool, page_table, seq_lens, None,
                      scale, interpret, "paged_latent_attention",
                      precise=True, name="mx_mla_attn")
    return out[:, 0, :, :rank]


def ragged_spec_attention(q, k_pool, v_pool, page_table, seq_lens,
                          scale=None, interpret=None):
    """Multi-query ragged paged attention — the speculative verify step.

    q: (S, W, H, D) — W = K+1 query rows per slot (committed token +
    drafts, in position order); page_table: (S, max_pages) — ONE row per
    slot, shared by its W queries (speculation widens queries, not KV
    residency); seq_lens: (S*W,) int32, PER ROW: row w of slot s has
    seq_len = its absolute position + 1, so each draft row attends the
    committed prefix plus the earlier draft rows already written below
    it (the ragged mask alone encodes causality between draft rows — no
    q_pos operand), and a padded/inactive row carries 0 and returns
    zeros. The same kernel as :func:`ragged_paged_attention`, which is
    its W = 1 case.

    Shapes are static in (S, W, max_pages, page_size): speculation depth
    and per-slot acceptance vary the seq_lens DATA only — membership
    churn, rejection, ragged drafts never recompile. Returns (S, W, H, D).
    """
    return _paged_call(q, k_pool, v_pool, page_table, seq_lens, None,
                       scale, interpret, "ragged_spec_attention")


def paged_spec_attention_reference(q, k_pool, v_pool, page_table, seq_lens,
                                   scale=None):
    """Dense oracle for the multi-query verify step: each of a slot's W
    query rows is treated as its own single-query slot sharing the slot's
    page-table row — the chunked-prefill broadcast-row trick, with the
    per-row seq_lens carrying causality. q: (S*W, H, D), page_table:
    (S, max_pages), seq_lens: (S*W,). Returns (S*W, H, D)."""
    s_rows = q.shape[0]
    width = s_rows // page_table.shape[0]
    pt = jnp.repeat(page_table.astype(jnp.int32), width, axis=0)
    return paged_attention_reference(q, k_pool, v_pool, pt, seq_lens,
                                     scale=scale)


def paged_spec_attention(q, k_pool, v_pool, page_table, seq_lens,
                         scale=None):
    """Dispatcher for the widened (speculative) decode step: q is the
    flattened (S*W, H, D) query block — W derived from the page-table row
    count at trace time, so the engine's model code needs no signature
    change. Pallas kernel on a TPU backend (platform-only gate, as
    `paged_attention`), dense reference elsewhere."""
    s_slots = page_table.shape[0]
    width = q.shape[0] // s_slots
    if not _interpret():
        out = ragged_spec_attention(
            q.reshape(s_slots, width, q.shape[1], q.shape[2]),
            k_pool, v_pool, page_table, seq_lens, scale=scale,
            interpret=False)
        return out.reshape(q.shape)
    return paged_spec_attention_reference(q, k_pool, v_pool, page_table,
                                          seq_lens, scale=scale)


# ---------------------------------------------------------------------------
# Window layers: decode through a ring table, prefill over the band
# ---------------------------------------------------------------------------
#
# A sliding-window layer attends to the last ``window`` positions only, so
# its cache holds at most ``window`` tokens (+ a page of slack) a sequence:
# the slot's page-table row is a RING of ``window / page_size + 1`` columns
# (serving.kvcache.RingKVCache) and the decode kernel walks those columns
# and no more. Prefill computes the causal band blockwise (the flash idiom
# above) for all the query heads of a kv head at once, and never visits a
# block that lies wholly outside the band.


def ragged_window_attention(q, k_pool, v_pool, page_table, seq_lens, window,
                            scale=None, interpret=None, precise=False):
    """Decode attention of a sliding-window layer: one query token a slot
    over a RING page table. q: (S, H, D); page_table: (S, columns) — column
    ``(p // page_size) % columns`` holds position ``p``; seq_lens: (S,)
    tokens live (the query is the last); keys at or below ``query -
    window`` are masked. Returns (S, H, D)."""
    return _paged_call(q[:, None], k_pool, v_pool, page_table, seq_lens,
                       None, scale, interpret, "ragged_window_attention",
                       window=window, ring=True, precise=precise)[:, 0]


def paged_window_attention_reference(q, k_pool, v_pool, page_table,
                                     seq_lens, window, scale=None):
    """Dense jnp form of :func:`ragged_window_attention`: the kernel's
    parity oracle and the path off the TPU."""
    s_slots, columns = page_table.shape
    page_size = k_pool.shape[1]
    sl = seq_lens.astype(jnp.int32)
    pos = (ring_blocks(sl, columns, page_size)[:, :, None] * page_size
           + jnp.arange(page_size, dtype=jnp.int32)
           ).reshape(s_slots, columns * page_size)
    valid = (pos < sl[:, None]) \
        & (pos >= jnp.maximum(sl - window, 0)[:, None])
    return _dense_paged(q, k_pool, v_pool, page_table, valid, scale)


def paged_window_attention(q, k_pool, v_pool, page_table, seq_lens, window,
                           scale=None, precise=False):
    """Dispatcher (as :func:`paged_attention`): the kernel on a TPU, the
    dense reference elsewhere."""
    if not _interpret():
        return ragged_window_attention(q, k_pool, v_pool, page_table,
                                       seq_lens, window, scale=scale,
                                       interpret=False, precise=precise)
    return paged_window_attention_reference(q, k_pool, v_pool, page_table,
                                            seq_lens, window, scale=scale)


# rows of a query block and columns of a kv block of the band kernel: ONE
# constant for the chip, found there (tools/kernel_time.py --kernel band;
# PERF.md section 6, PR 43: 128 / 256 rows x 128 / 256 / 512 columns timed)
_BAND_BLOCK = 256


def _band_first(qi, block, window, maximum=jnp.maximum):
    """The first kv block query block ``qi`` sees — the one that holds its
    first row's oldest key; the last is its own, the diagonal. ``qi`` a
    traced int, or a Python one with ``maximum=max``."""
    if not window:
        return 0 * qi
    return maximum(qi * block - window + 1, 0) // block


def band_blocks(tokens, rows=None, window=0, block=_BAND_BLOCK):
    """(query block, kv block) pairs ONE kv head's launch of
    :func:`band_attention` multiplies when ``tokens`` of its ``rows``
    (default: all) are real — host arithmetic over the kernel's own block
    size (the engine's ``attn_blocks_*`` span arguments; pinned against the
    kernel's live steps in tests/test_afmoe_decoder.py)."""
    block = min(block, _pad_up(tokens if rows is None else rows, 8))
    return sum(qi - _band_first(qi, block, window, max) + 1
               for qi in range(max(-(-tokens // block), 1)))


def _band_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, window, block, n_band, precision):
    """One (kv head, query block, band step) cell of causal (+ window)
    prefill attention. q_ref/o_ref: (1, G, B, D) — the G query heads of the
    kv head, multiplied as ONE (G x B, D) matrix; k_ref/v_ref: (1, B, D) —
    kv block ``first + j`` of the band (:func:`_band_first`); steps past
    the diagonal are not computed (their index map names the diagonal block
    again: no new copy). m/l/acc scratch: (G x B, ...), a row a query."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    kb = _band_first(qi, block, window) + j
    groups, _, d = q_ref.shape[1:]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= qi)
    def _block():
        q = q_ref[0].astype(jnp.float32).reshape(groups * block, d)
        k = k_ref[0].astype(jnp.float32)          # (B, D)
        v = v_ref[0].astype(jnp.float32)
        # a row's position depends on its place in its head's block only:
        # one (B, B) mask for the G heads
        rows = qi * block + lax.broadcasted_iota(jnp.int32,
                                                 (block, block), 0)
        cols = kb * block + lax.broadcasted_iota(jnp.int32,
                                                 (block, block), 1)
        valid = cols <= rows
        if window:
            valid = jnp.logical_and(valid, cols > rows - window)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None], s.reshape(groups, block, block),
                      _NEG_BIG).reshape(groups * block, block)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_band - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                    ).reshape(groups, block, d).astype(o_ref.dtype)


def band_attention(q, k, v, scale=None, window=0, block=_BAND_BLOCK,
                   interpret=None, precise=False, length=None):
    """Causal prefill attention of ONE sequence with grouped queries and an
    optional sliding window, never an ``(H, T, T)`` tensor.

    q: (T, H, D); k/v: (T, KH, D), ``H % KH == 0``; ``window`` (static) > 0
    masks keys at or below ``query - window``. ``length`` (a traced int32
    scalar, or None: every row is real): the rows that hold the prompt's
    tokens. Query blocks of padding behind them are not launched and come
    back ZEROS; the padding rows of the last live block come back finite
    and meaningless (a causal row never sees what follows it). Grid (KH,
    live query blocks, band): a query block of ``block`` rows visits the
    kv blocks of as many columns that its band crosses (all up to the
    diagonal without a window) — blocks outside are not visited — and
    multiplies each once for the ``H / KH`` query heads of the kv head.
    ``precise`` (static): float32 products where the compiler's default is
    one bfloat16 pass over float32 operands. Returns (T, H, D). On a TPU
    the kernel ``mx_prefill_attn``; elsewhere
    :func:`band_attention_reference` unless ``interpret`` asks for the
    kernel."""
    if interpret is None:
        if _interpret():
            return band_attention_reference(q, k, v, scale=scale,
                                            window=window)
        interpret = False
    t, n_heads, d = q.shape
    n_kv = k.shape[1]
    groups = n_heads // n_kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block = min(block, _pad_up(t, 8))
    tp = _pad_up(t, block)
    n_q = tp // block
    n_band = max(qi - _band_first(qi, block, window, max) + 1
                 for qi in range(n_q))
    n_live = n_q if length is None else jnp.clip(
        -(-jnp.asarray(length, jnp.int32) // block), 1, n_q)
    pad = ((0, tp - t), (0, 0), (0, 0))
    # (T, KH, G, D) -> (KH, G, T, D); k/v -> (KH, T, D)
    qg = jnp.pad(q, pad).reshape(tp, n_kv, groups, d).transpose(1, 2, 0, 3)
    kk = jnp.pad(k, pad).transpose(1, 0, 2)
    vv = jnp.pad(v, pad).transpose(1, 0, 2)

    def q_map(h, qi, j):
        return (h, 0, qi, 0)

    def kv_map(h, qi, j):
        return (h, jnp.minimum(_band_first(qi, block, window) + j, qi), 0)

    out = pl.pallas_call(
        functools.partial(_band_kernel, scale=float(scale),
                          window=int(window), block=block, n_band=n_band,
                          precision=lax.Precision.HIGHEST if precise
                          else None),
        # the query blocks behind the prompt's last are not launched (a
        # traced extent; launched and skipped they cost 0.9-1.5 ms a long
        # prefill more: PERF.md section 6, PR 43)
        grid=(n_kv, n_live, n_band),
        in_specs=[pl.BlockSpec((1, groups, block, d), q_map),
                  pl.BlockSpec((1, block, d), kv_map),
                  pl.BlockSpec((1, block, d), kv_map)],
        out_specs=pl.BlockSpec((1, groups, block, d), q_map),
        out_shape=jax.ShapeDtypeStruct((n_kv, groups, tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((groups * block, LANES), jnp.float32),
                        pltpu.VMEM((groups * block, LANES), jnp.float32),
                        pltpu.VMEM((groups * block, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="mx_prefill_attn",  # what a device trace is searched for
    )(qg, kk, vv)
    if length is not None:
        # a query block that was not launched is whatever the buffer held
        live = lax.broadcasted_iota(jnp.int32, (1, 1, tp, 1), 2) \
            < n_live * block
        out = jnp.where(live, out, 0)
    return out.transpose(2, 0, 1, 3).reshape(tp, n_heads, d)[:t]


def band_attention_reference(q, k, v, scale=None, window=0):
    """Dense jnp form of :func:`band_attention` (materialises the scores:
    tests and the CPU path only)."""
    t, n_heads, d = q.shape
    groups = n_heads // k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    valid = cols <= rows
    if window:
        valid = valid & (cols > rows - window)
    p = jax.nn.softmax(jnp.where(valid[None], scores, _NEG_BIG), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# The delta rule's one-token update over the live slots (ops/kda.py `step`)
# ---------------------------------------------------------------------------
#
# A decode tick of a delta-rule layer reads and writes ONE STATE MATRIX ``(d_k,
# d_v)`` a head and slot: 2 MB a slot at 32 heads of 128 x 128 float32, far
# more than everything else the layer touches. :func:`mxnet_tpu.ops.kda.step`
# (plain XLA, the reference and the path off the chip) reads every slot's
# state twice and writes it once whatever is live; here one grid step holds
# ONE LIVE SLOT's state in VMEM for both passes and writes it back once, and
# a slot that holds no token is not visited at all. The state operand is
# aliased to the state result, so what is not visited keeps its bits because
# nothing touches it. The walk (:func:`live_slots`: the live slots packed to
# the front and their count, scalar-prefetch operands built in XLA once a
# step for all its layers) is :func:`walk_schedule`'s idea again: the state
# block's index map names the slot, so it is DMA'd straight into VMEM.
#
# The arithmetic is `kda.step`'s in its order, float32 on the vector unit. The
# state lies ``d_k`` on the sublanes and ``d_v`` on the lanes, so ``k``, ``q``
# and ``exp(a)`` scale it as COLUMNS: their ``(heads, d_k)`` tiles are
# transposed once a grid step (three small transposes a slot) and a head's
# column is a lane of the result, broadcast along the lanes: never a relayout
# of a state tile.


def live_slots(valid):
    """The walk of :func:`kda_state_step` over the slots that hold a token.
    valid: ``(S,)`` bool. Returns ``(slot_of (S + 1,), count (1,))`` int32:
    the live slots in ascending order packed to the front, every entry past
    ``count`` repeating the last live slot (slot 0 with none live) — the
    list is one entry longer than a walk can be because the pipeline
    evaluates the index maps of the step AFTER the one it runs, and a
    repeated entry asks for no new copy (as :func:`walk_schedule`'s).
    Compare-and-sum, no loop, nothing a device trace would show beside the
    kernels."""
    n = valid.shape[0]
    live = valid.astype(jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)
    # a slot's place among the live: the live slots below it
    place = jnp.where(ids[None, :] < ids[:, None], live[None, :], 0).sum(
        axis=1)
    count = live.sum()
    t = jnp.minimum(jnp.arange(n + 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    mine = jnp.logical_and(valid[None, :], place[None, :] == t[:, None])
    return jnp.where(mine, ids[None, :], 0).sum(axis=1), count.reshape(1)


def _kda_state_kernel(slot_ref, n_ref, q_ref, k_ref, a_ref, v_ref, b_ref,
                      s_ref, o_ref, s_out_ref):
    """One live slot of the delta rule's one-token update, every head.
    Grid step ``t`` is slot ``slot_ref[t]`` (the index maps' own) of
    ``n_ref[0]`` live ones. q_ref/k_ref/a_ref: ``(1, H, d_k)`` (``a`` the log
    decay); v_ref/o_ref: ``(1, H, d_v)``; b_ref: ``(1, 1, H)`` (beta);
    s_ref/s_out_ref: ``(1, H, d_k, d_v)`` — ONE buffer in HBM. A head::

        S' = exp(a) S;  u = beta (v - S'^T k)
        o = S'^T q + (q . k) u;  S = S' + k u^T

    A launch with no live slot still runs step 0: it copies slot 0's state
    through, bit for bit (the block is written back whatever the body did)."""
    del slot_ref
    t = pl.program_id(0)
    n = n_ref[0]
    heads = s_ref.shape[1]

    @pl.when(t < n)
    def _live():
        # (d_k, H): a head's k, q and decay are columns of these
        qt = q_ref[0].T
        kt = k_ref[0].T
        et = jnp.exp(a_ref[0]).T
        qk = (qt * kt).sum(axis=0, keepdims=True)            # (1, H)
        beta = b_ref[0]                                      # (1, H)
        for h in range(heads):
            kc = kt[:, h:h + 1]
            decayed = et[:, h:h + 1] * s_ref[0, h]
            seen_k = (kc * decayed).sum(axis=0, keepdims=True)  # S'^T k
            seen_q = (qt[:, h:h + 1] * decayed).sum(axis=0, keepdims=True)
            u = beta[:, h:h + 1] * (v_ref[0, h:h + 1, :] - seen_k)
            o_ref[0, h:h + 1, :] = seen_q + qk[:, h:h + 1] * u
            s_out_ref[0, h] = decayed + kc * u

    @pl.when(jnp.logical_and(n == 0, t == 0))
    def _none_live():
        s_out_ref[...] = s_ref[...]


def kda_state_walk(valid, interpret=None):
    """What :func:`kda_state_step` walks, built ONCE a step for all its
    layers: :func:`live_slots` where the kernel runs (a TPU, or ``interpret``
    given), ``None`` where the reference does."""
    if interpret is None and _interpret():
        return None
    return live_slots(valid)


def kda_state_step(q, k, v, log_decay, beta, state, valid=None,
                   interpret=None, walk=None):
    """The delta rule's one-token update (:func:`mxnet_tpu.ops.kda.step`:
    same arguments, same result ``(o (B, H, d_v), state)``) over the LIVE
    slots only, in place. Off a TPU it IS ``kda.step`` (the reference, and
    the oracle of the tests); on a TPU, or with ``interpret`` given, the
    kernel ``mx_kda_state``: one grid step a live slot (``valid``), its
    ``(H, d_k, d_v)`` state read once, held in VMEM for both passes and
    written once into the buffer it came from (the state operand is aliased
    to the state result: donate it). A slot that holds no token is neither
    read nor written and keeps its bits; its output row comes back zeros. A
    launch with no live slot changes nothing.

    ``walk``: :func:`kda_state_walk` of ``valid`` where the caller has built
    it (a step hands ONE to all its layers), else built here. The platform is
    the only gate: any ``(H, d_k, d_v)`` goes through the kernel by its
    shapes — the blocks span the operands' minor dims whole, so a head of 16
    x 8 lowers as one of 128 x 128 does (tests/test_chip_compile.py) — and
    a compiler's refusal on a TPU propagates."""
    if interpret is None:
        if _interpret():
            from . import kda

            return kda.step(q, k, v, log_decay, beta, state, valid)
        interpret = False
    if valid is None:
        valid = jnp.ones(k.shape[:1], bool)
    slot_of, count = live_slots(valid) if walk is None else walk
    out, state = _kda_state_call(slot_of, count, q, k, log_decay, v,
                                 beta[:, None, :], state,
                                 interpret=bool(interpret))
    # a slot that was not visited is whatever the buffer held
    return jnp.where(valid[:, None, None], out, 0), state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_state_call(slot_of, count, q, k, log_decay, v, beta, state, *,
                    interpret):
    """The launch of :func:`_kda_state_kernel`, a jitted function of its own
    so that the layers of a step share ONE trace and ONE lowering of the
    kernel (its loop over the heads is unrolled: lowered a layer, six of
    them cost a step program 1.9 s of set-up)."""
    _, heads, d_k = k.shape
    d_v = v.shape[-1]

    def block(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda t, slot_of, count: (slot_of[t],)
                            + (0,) * len(shape))

    return pl.pallas_call(
        _kda_state_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # one step a live slot (none live: one step that changes
            # nothing)
            grid=(jnp.maximum(count[0], 1),),
            in_specs=[block(heads, d_k), block(heads, d_k),
                      block(heads, d_k), block(heads, d_v), block(1, heads),
                      block(heads, d_k, d_v)],
            out_specs=[block(heads, d_v), block(heads, d_k, d_v)]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (operand 7, the scalars counted) IS the state result
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mx_kda_state",    # what a device trace is searched for
    )(slot_of, count, q, k, log_decay, v, beta, state)


def _register_flash_attention_op():
    """Expose the kernel through the op registry:
    ``_contrib_flash_attention(query, key, value)`` on (B, H, S, D)."""
    from .registry import register

    @register("_contrib_flash_attention",
              params={"scale": (float, None), "causal": (bool, False)},
              inputs=("query", "key", "value"),
              aliases=("flash_attention",))
    def _op(attrs, q, k, v):
        return flash_attention(q, k, v, attrs.scale, attrs.causal)


_register_flash_attention_op()
