"""Operations and bytes the algorithms need, counted from shapes.

Nothing here asks the program anything: a later PR cannot move these counts.
One multiply-add is two operations. A training step is counted as three
forward passes (forward, gradient w.r.t. inputs, gradient w.r.t. weights) of
every convolution and dense layer — the usual model-FLOPs convention;
BatchNorm, ReLU, pooling, the loss and the optimizer update are left out, so
the utilization this gives is a lower bound on the device's arithmetic.
"""
from __future__ import annotations


def conv_macs(out_ch, in_ch, kernel, out_hw):
    """Multiply-adds of one image through one square conv layer."""
    return out_ch * in_ch * kernel * kernel * out_hw * out_hw


def resnet_v1_bottleneck_layers(image, classes, stage_blocks, stage_channels,
                                stem_channels):
    """Every conv/dense layer of a bottleneck ResNet v1 (MXNet model zoo
    layout: 7x7/2 stem, 3x3/2 max-pool, stride on the FIRST 1x1 of a
    stage's first block, 1x1 projection shortcut there) as rows
    ``(name, out_ch, in_ch, kernel, out_hw)``."""
    rows = []
    hw = (image + 2 * 3 - 7) // 2 + 1
    rows.append(("stem", stem_channels, 3, 7, hw))
    hw = (hw + 2 * 1 - 3) // 2 + 1
    in_ch = stem_channels
    for si, (blocks, ch) in enumerate(zip(stage_blocks, stage_channels)):
        mid = ch // 4
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            out_hw = (hw - 1) // stride + 1
            tag = "stage%d.block%d" % (si + 1, bi)
            rows.append((tag + ".conv1x1a", mid, in_ch, 1, out_hw))
            rows.append((tag + ".conv3x3", mid, mid, 3, out_hw))
            rows.append((tag + ".conv1x1b", ch, mid, 1, out_hw))
            if bi == 0 and ch != in_ch:
                rows.append((tag + ".shortcut", ch, in_ch, 1, out_hw))
            in_ch, hw = ch, out_hw
    rows.append(("dense", classes, in_ch, 1, 1))
    return rows


def resnet_v1_train_flops_per_image(model):
    """FLOPs of one image through one training step of the configuration's
    ``model`` block (``image``, ``classes``, ``stage_blocks``,
    ``stage_channels``, ``stem_channels``)."""
    rows = resnet_v1_bottleneck_layers(
        model["image"], model["classes"], model["stage_blocks"],
        model["stage_channels"], model["stem_channels"])
    macs = sum(conv_macs(o, i, k, hw) for _n, o, i, k, hw in rows)
    return 2 * macs * 3


def paged_decode_kv_bytes(live_tokens, num_layers, num_kv_heads, head_dim,
                          itemsize):
    """Bytes of K and V one decode tick has to read: every live token's K
    and V row in every layer, once."""
    return 2 * live_tokens * num_layers * num_kv_heads * head_dim * itemsize


def share_of_peak(achieved, peak, what):
    """``achieved / peak`` in per cent; a share over 100 is a counting fault
    (operations or bytes too high, or time that leaves out work) and raises
    instead of printing."""
    if peak <= 0:
        raise ValueError("%s: non-positive peak %r" % (what, peak))
    pct = 100.0 * achieved / peak
    if pct > 100.0:
        raise ValueError(
            "%s reads %.2f %% of peak: the count is too high or the time "
            "leaves out part of the work" % (what, pct))
    return pct
