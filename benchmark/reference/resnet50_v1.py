"""Plain reference of ResNet v1 with bottleneck blocks, and of its training.

Straight ``jax.numpy`` / ``jax.lax`` in float32 under ``highest`` matmul
precision: forward with batch-statistics BatchNorm (biased variance, eps
1e-5), softmax cross-entropy, ``jax.grad`` of the batch-mean loss, SGD with
momentum (``m = mu m - lr g; w += m``, no weight decay). The layout is the
MXNet model zoo's ``resnet50_v1``: 7x7/2 stem conv (no bias), BN, ReLU, 3x3/2
max-pool (pad 1); per stage a first block with stride (on its FIRST 1x1 conv)
and a 1x1 projection shortcut with BN, then identity blocks; the two 1x1
convs of a block carry a bias, the 3x3 and the shortcut do not; global
average pool; dense. Parameter names are the zoo's, without the net's prefix,
in creation order — the benchmark checks them against the program's. Each
block is rematerialised (``jax.checkpoint``) so that batch 128 fits beside
nothing else on a 16 GB chip; that changes memory, not one number.
Imports nothing of the program.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from weights import Spec  # benchmark/ is on sys.path (see run.py)

BN_EPS = 1e-5


def _blocks(model):
    """(stage, block, stride, has_shortcut, in_ch, ch) rows."""
    in_ch = model["stem_channels"]
    for si, (n, ch) in enumerate(zip(model["stage_blocks"],
                                     model["stage_channels"]), 1):
        for bi in range(n):
            yield si, bi, (2 if bi == 0 and si > 1 else 1), \
                (bi == 0 and ch != in_ch), in_ch, ch
            in_ch = ch


def param_specs(model):
    """Ordered ``name -> Spec``: conv and dense weights normal with std
    ``sqrt(2 / fan_in)`` / ``sqrt(1 / fan_in)``, BN scale and running
    variance ones, everything else zeros."""
    out = collections.OrderedDict()

    def conv(name, o, i, k, bias=False):
        out[name + "_weight"] = Spec((o, i, k, k), "float32", "normal",
                                     (2.0 / (i * k * k)) ** 0.5)
        if bias:
            out[name + "_bias"] = Spec((o,), "float32", "zeros")

    def bn(name, c):
        out[name + "_gamma"] = Spec((c,), "float32", "ones")
        out[name + "_beta"] = Spec((c,), "float32", "zeros")
        out[name + "_running_mean"] = Spec((c,), "float32", "zeros")
        out[name + "_running_var"] = Spec((c,), "float32", "ones")

    conv("conv0", model["stem_channels"], 3, 7)
    bn("batchnorm0", model["stem_channels"])
    counter = {}
    for si, _bi, _stride, shortcut, in_ch, ch in _blocks(model):
        c = counter.setdefault(si, 0)
        pre = "stage%d_" % si
        mid = ch // 4
        rows = [(mid, in_ch, 1, True), (mid, mid, 3, False),
                (ch, mid, 1, True)] + ([(ch, in_ch, 1, False)]
                                       if shortcut else [])
        for j, (o, i, k, bias) in enumerate(rows):
            conv("%sconv%d" % (pre, c + j), o, i, k, bias)
            bn("%sbatchnorm%d" % (pre, c + j), o)
        counter[si] = c + len(rows)
    out["dense0_weight"] = Spec((model["classes"], model["stage_channels"][-1]),
                                "float32", "normal",
                                model["stage_channels"][-1] ** -0.5)
    out["dense0_bias"] = Spec((model["classes"],), "float32", "zeros")
    return out


def trainable(name):
    return not name.endswith(("_running_mean", "_running_var"))


def _conv(x, p, name, stride, pad):
    y = jax.lax.conv_general_dilated(
        x, p[name + "_weight"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    bias = p.get(name + "_bias")
    return y if bias is None else y + bias[None, :, None, None]


def _bn(x, p, name):
    mean = x.mean(axis=(0, 2, 3), keepdims=True)
    var = jnp.square(x - mean).mean(axis=(0, 2, 3), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) \
        * p[name + "_gamma"][None, :, None, None] \
        + p[name + "_beta"][None, :, None, None]


def forward(model, p, x):
    """Logits ``(B, classes)`` of the training-mode forward pass."""
    x = jax.nn.relu(_bn(_conv(x, p, "conv0", 2, 3), p, "batchnorm0"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    counter = {}
    for si, _bi, stride, shortcut, _in_ch, _ch in _blocks(model):
        c = counter.setdefault(si, 0)
        pre = "stage%d_" % si

        def block(x, c=c, pre=pre, stride=stride, shortcut=shortcut):
            def cb(x, j, s, pad):
                return _bn(_conv(x, p, "%sconv%d" % (pre, c + j), s, pad),
                           p, "%sbatchnorm%d" % (pre, c + j))

            y = jax.nn.relu(cb(x, 0, stride, 0))
            y = jax.nn.relu(cb(y, 1, 1, 1))
            y = cb(y, 2, 1, 0)
            skip = cb(x, 3, stride, 0) if shortcut else x
            return jax.nn.relu(y + skip)

        x = jax.checkpoint(block)(x)
        counter[si] = c + (4 if shortcut else 3)
    x = x.mean(axis=(2, 3))
    return x @ p["dense0_weight"].T + p["dense0_bias"]


def mean_loss(model, p, x, y):
    logp = jax.nn.log_softmax(forward(model, p, x), axis=-1)
    return -jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None],
                                axis=-1).mean()


def train_step(model, p, mom, x, y, lr, momentum):
    """One SGD-momentum step: ``(loss, grads, new_p, new_mom)``; only the
    trainable leaves have gradients and momenta."""
    with jax.default_matmul_precision("highest"):
        w = {k: v for k, v in p.items() if trainable(k)}
        rest = {k: v for k, v in p.items() if not trainable(k)}
        loss, g = jax.value_and_grad(
            lambda w: mean_loss(model, {**w, **rest}, x, y))(w)
    new_mom = {k: momentum * mom[k] - lr * g[k] for k in w}
    new_p = {**rest, **{k: w[k] + new_mom[k] for k in w}}
    return loss, g, new_p, new_mom


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def first_steps(model, p0, batches, lr, momentum, shard=None):
    """Follow the first ``len(batches)`` training steps from ``p0``. Returns
    host floats: ``losses`` (one per step), ``grad_norms`` (per trainable
    leaf, of the FIRST step's gradient of the batch-mean loss) and
    ``delta_norms`` (per trainable leaf, of ``p_after - p0``). ``shard``,
    when given, places each batch (the 4-chip cell shards rows over chips).
    """
    step = jax.jit(lambda p, m, x, y: train_step(model, p, m, x, y, lr,
                                                 momentum))
    norms = jax.jit(leaf_norms)
    p = p0
    mom = {k: jnp.zeros_like(v) for k, v in p0.items() if trainable(k)}
    losses, grad_norms = [], None
    for x, y in batches:
        if shard is not None:
            x, y = shard(x), shard(y)
        loss, g, p, mom = step(p, mom, x, y)
        if grad_norms is None:
            grad_norms = norms(g)
        del g
        losses.append(loss)
    delta = norms(jax.jit(lambda a, b: {k: a[k] - b[k] for k in a
                                        if trainable(k)})(p, p0))
    return {"losses": [float(v) for v in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
