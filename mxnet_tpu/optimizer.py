"""Optimizers.

API parity with reference ``python/mxnet/optimizer.py`` (Optimizer registry,
SGD/NAG/Signum/FTML/Adam/AdaGrad/RMSProp/AdaDelta/Ftrl/Adamax/Nadam/SGLD/
DCASGD/LBSGD, lr/wd multipliers, ``num_update`` bookkeeping, ``Updater`` with
state (de)serialization).

TPU-native design: the reference accelerates updates with hand-fused CUDA ops
(reference ``src/operator/optimizer_op.cc`` — sgd_mom_update, adam_update, …).
Here every optimizer is split into two pieces:

* a host-side scalar prologue :meth:`Optimizer._host_scalars` — per-index
  lr/wd multipliers plus any schedule transform computed in python (Adam's
  bias correction, Nadam's momentum schedule);
* a pure per-parameter kernel :meth:`Optimizer._leaf_step`
  ``(w, g, state, t, lr, wd, *extras) -> (new_w, new_state)`` on jax arrays
  only.

The generic :meth:`Optimizer.update` jits the kernel once per optimizer
(lr/wd/t enter as traced scalars, so LR schedules never retrace) — XLA fuses
the whole rescale → clip → wd → momentum → assign chain into one kernel, the
direct equivalent of the reference's fused ops. The SAME kernel is what
``mxnet_tpu.fastpath`` composes over the whole parameter tree (ONE jit per
step instead of one per parameter) and — where the math permits — what
``parallel.TrainStep`` traces in-graph, so the three update paths cannot
drift apart numerically: they are one function traced in three places.
"""
from __future__ import annotations

import math
import pickle
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry as _telemetry
from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = [
    "Optimizer", "register", "create", "get_updater", "Updater",
    "SGD", "NAG", "Signum", "SignSGD", "FTML", "DCASGD", "SGLD", "LBSGD",
    "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam",
    "Test",
]


def _as_jax(x):
    return x._data if isinstance(x, NDArray) else x


def _f32(x):
    return jnp.asarray(x, dtype=jnp.float32)


def _is_mp_dtype(dtype):
    """Dtypes that keep an fp32 master copy under ``multi_precision``:
    float16 (reference mp_sgd_update) and bfloat16 (the TPU-native low
    precision — same master-weight rationale, MXU-rate storage)."""
    return dtype == np.float16 or dtype == jnp.bfloat16


def _base_state_structure(optimizer, index, weight):
    """Pytree structure of ``create_state`` for this weight, without
    allocating (eval_shape); cached per (shape, dtype) on the instance."""
    cache = optimizer.__dict__.setdefault("_state_struct_cache", {})
    key = (tuple(weight.shape), str(_as_jax(weight).dtype))
    if key not in cache:
        cache[key] = jax.tree_util.tree_structure(jax.eval_shape(
            lambda: optimizer.create_state(index, weight)))
    return cache[key]


def _is_mp_pair(optimizer, index, weight, state):
    """Whether ``state`` is an ``(fp32 master, base_state)`` pair for this
    weight — the layout ``create_state_multi_precision`` produces.

    A structural dtype/shape test alone is ambiguous: Adam-family plain
    states are ALSO 2-tuples of fp32 weight-shaped arrays, and treating a
    resumed ``(m, v)`` as ``(master, base)`` would silently install the
    first moment as the weight. Disambiguation: in a true pair the SECOND
    element has ``create_state``'s pytree structure while the whole state
    does not."""
    if not (isinstance(state, tuple) and len(state) == 2
            and getattr(state[0], "dtype", None) == jnp.float32
            and getattr(state[0], "shape", None) == tuple(weight.shape)):
        return False
    expected = _base_state_structure(optimizer, index, weight)
    whole = jax.tree_util.tree_structure(state)
    second = jax.tree_util.tree_structure(state[1])
    if whole == expected and second != expected:
        return False  # the state IS a plain create_state tuple (Adam (m,v))
    return second == expected


def ensure_mp_state(optimizer, index, weight, state):
    """Adopt the fp32-master layout for a low-precision weight whose state
    predates it (e.g. a bf16 optimizer checkpoint saved before
    ``multi_precision`` covered bfloat16, when bf16 silently took the
    non-master branch, or an fp32 run resumed onto bf16-cast weights): the
    current weight becomes the master, the loaded state stays as the base.
    No-op when mp doesn't apply or the state is already a pair."""
    if not (optimizer.multi_precision and _is_mp_dtype(weight.dtype)):
        return state
    if _is_mp_pair(optimizer, index, weight, state):
        return state
    return (jnp.asarray(_as_jax(weight), dtype=jnp.float32), state)


class Optimizer(object):
    """Base optimizer (reference optimizer.py:35).

    Subclasses implement :meth:`create_state` and the pure
    :meth:`_leaf_step` kernel (plus :meth:`_host_scalars` when the update
    needs host-computed schedule scalars); the base class handles registry,
    per-index lr/wd multipliers, update counting, jit caching and the
    generic :meth:`update` dispatch.
    """

    opt_registry: Dict[str, type] = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise MXNetError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        if not isinstance(param_idx2name, dict):
            raise MXNetError("param_idx2name should be a dict of param indexes to names.")
        self.idx2name = param_idx2name.copy()
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None else ()
        self.param_dict = param_dict if param_dict else {}
        self._step_cache: Dict[Any, Any] = {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def create_state(self, index, weight):
        """Return optimizer state for one parameter (None / array / tuple)."""
        return None

    def create_state_multi_precision(self, index, weight):
        """fp16/bf16 weights get an fp32 master copy (reference
        create_state_multi_precision; mp_sgd_update parity)."""
        weight_master_copy = None
        if self.multi_precision and _is_mp_dtype(weight.dtype):
            weight_master_copy = jnp.asarray(_as_jax(weight), dtype=jnp.float32)
            # the base state is built from the MASTER (reference
            # optimizer.py does the same): the kernel returns fp32 state,
            # so a state born in the weight's dtype changed type after the
            # first update and compiled every jit over it a second time
            return (weight_master_copy,
                    self.create_state(index, weight_master_copy))
        return self.create_state(index, weight)

    # ------------------------------------------------------------------
    # the update protocol: host scalar prologue + pure per-leaf kernel
    # ------------------------------------------------------------------
    def _host_scalars(self, index):
        """Host-side scalar prologue for one parameter's update, run AFTER
        :meth:`_update_count`: returns ``(lr, wd, extras)``. ``lr`` carries
        any host-computed schedule transform (Adam's bias correction,
        Adamax's warmup divisor); ``extras`` are additional traced operands
        :meth:`_leaf_step` consumes (Nadam's momentum schedule, SGLD's rng
        key). Shared verbatim by the per-parameter path and the fastpath
        fused tree-apply, so the two stay bit-identical."""
        return self._get_lr(index), self._get_wd(index), ()

    def _leaf_step(self, w, g, state, t, lr, wd, *extras):
        """Pure per-parameter kernel on jax arrays:
        ``(new_weight, new_state)``. ``t`` is the traced 1-based update
        count of this index; ``lr``/``wd`` come from :meth:`_host_scalars`.
        Traced by :meth:`update` (one jit per parameter), by
        ``fastpath.fused_apply`` (one jit per tree) and — via
        :meth:`pure_step` where aliased — by the in-graph SPMD step."""
        raise NotImplementedError(
            "%s does not implement _leaf_step" % self.__class__.__name__)

    #: True when :meth:`_host_scalars` mutates optimizer state or consumes
    #: a host stream (Nadam's ``m_schedule`` recurrence, SGLD's rng keys):
    #: its call ORDER is then observable, so the fused path must preserve
    #: the legacy param-outer/device-inner ordering — with multiple device
    #: positions it cannot, and ``fastpath.supports`` falls back.
    _host_scalars_stateful = False

    #: True when :meth:`_leaf_step` is element-wise over the weight — no
    #: cross-element math (LBSGD's layer-wise norms), no shape-dependent
    #: randomness (SGLD's noise draw). The ZeRO plane (``fastpath.zero``)
    #: may then run the kernel over a flattened 1/N dp-shard of the
    #: concatenated parameter buckets and get bit-identical per-element
    #: results; subclasses with cross-element math MUST set this False or
    #: sharded updates would silently change the math.
    _leaf_step_pointwise = True

    @property
    def fastpath_capable(self):
        """Whether ``fastpath.fused_apply`` can fold this optimizer's whole
        update into one tree-level jit."""
        return type(self)._leaf_step is not Optimizer._leaf_step

    def update(self, index, weight, grad, state):
        """Apply one parameter's update (reference optimizer.py:update).

        Generic over the protocol above: bookkeeping + host scalars, then
        ONE jitted fused kernel per optimizer class (cached across
        parameters and steps; lr/wd/t are traced operands)."""
        if not self.fastpath_capable:
            raise NotImplementedError()
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd, extras = self._host_scalars(index)

        def step(w, g, s, t, lr, wd, *ex):
            return self._leaf_step(w, g, s, t, lr, wd, *ex)

        _telemetry.OPT_DISPATCHES.inc(path="perparam")
        new_w, new_state = self._fused(type(self).__name__, step)(
            _as_jax(weight), _as_jax(grad), state, _f32(t), _f32(lr),
            _f32(wd), *extras)
        weight._data = new_w
        return new_state

    def pure_step(self, w, g, state, t, lr, wd):
        """Pure functional update used by the in-graph SPMD training step
        (``mxnet_tpu.parallel.TrainStep``): returns ``(new_w, new_state)``
        from jax arrays only. ``t`` is the traced 1-based update count so
        bias-corrected optimizers (Adam family) compile once and stay
        correct on every step. Optimizers whose kernel needs no host-side
        schedule work alias this to :meth:`_leaf_step`; the Adam family
        overrides it with the bias correction traced on-device."""
        raise MXNetError(
            "%s does not implement pure_step; it cannot be fused into an "
            "SPMD train step — use Trainer/Updater instead"
            % self.__class__.__name__)

    def update_multi_precision(self, index, weight, grad, state):
        """fp16/bf16 weights: run the update on the fp32 master copy, then
        cast back (reference mp_sgd_update semantics). Returns the new
        state."""
        if self.multi_precision and _is_mp_dtype(weight.dtype):
            state = ensure_mp_state(self, index, weight, state)
            master, base_state = state
            g32 = NDArray(jnp.asarray(_as_jax(grad), jnp.float32), weight._ctx)
            w32 = NDArray(master, weight._ctx)
            new_base = self.update(index, w32, g32, base_state)
            weight._data = jnp.asarray(w32._data, dtype=_as_jax(weight).dtype)
            return (w32._data, new_base if new_base is not None else base_state)
        new_state = self.update(index, weight, grad, state)
        return new_state if new_state is not None else state

    # ------------------------------------------------------------------
    # lr / wd plumbing (reference optimizer.py:200-320)
    # ------------------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("LRScheduler of the optimizer has already been defined.")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def sync_num_update(self, t):
        """Single source of truth for the step counter when an in-graph
        step plane (``parallel.TrainStep`` / ``trainplane``) interleaves
        with eager ``Trainer.step``/``Updater`` updates (warmup or eval
        phases mixed into a compiled run): advance ``num_update`` to ``t``
        AND align every per-index count, so the next eager update continues
        at ``t + 1`` instead of replaying the eager-only count — without
        this, an ``lr_scheduler`` reading ``num_update`` would see the two
        paths drift apart (regression-tested in tests/test_trainplane.py).
        """
        t = int(t)
        self.num_update = max(self.num_update, t)
        # begin_num_update seeds indices _update_count has not seen yet
        # (graph-only steps never touch _index_update_count): without
        # advancing it, a param first updated eagerly AFTER t graph steps
        # would restart its per-index count — and e.g. Adam's bias
        # correction — at 1 instead of t + 1.
        self.begin_num_update = max(self.begin_num_update, t)
        for idx in self._index_update_count:
            self._index_update_count[idx] = max(
                self._index_update_count[idx], t)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # ------------------------------------------------------------------
    # jit-fused step dispatch
    # ------------------------------------------------------------------
    def _preprocess(self, grad, weight, wd):
        """Shared rescale → clip → weight-decay prologue, traced into the
        fused kernel (the reference bakes the same sequence into each
        optimizer_op.cc kernel)."""
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = jnp.clip(grad, -self.clip_gradient, self.clip_gradient)
        return grad + wd * weight

    def _preprocess_wd_in_clip(self, grad, weight, wd):
        """rescale → +wd·weight → clip: the adam/ftml/rmsprop/adamax/nadam
        family folds weight decay into the gradient BEFORE clipping
        (reference optimizer.py Adam :1037 ``clip(grad*rescale + wd*weight)``,
        optimizer_op-inl.h AdamUpdate/FTMLKernel/RMSProp kernels), unlike the
        sgd family which clips the bare gradient (``_preprocess``)."""
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = jnp.clip(grad, -self.clip_gradient, self.clip_gradient)
        return grad

    def _preprocess_no_wd(self, grad):
        """rescale → clip, weight decay applied separately at the weight
        update (reference AdaGrad :1105-1108, AdaDelta :1271-1284, DCASGD
        :909-920 — wd never enters the gradient statistics)."""
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = jnp.clip(grad, -self.clip_gradient, self.clip_gradient)
        return grad

    def _fused(self, key, fn):
        """jit-compile ``fn`` once per (variant, rescale_grad, clip) key.

        rescale_grad/clip_gradient are read by the step closures at trace
        time, so they are part of the cache key: Trainer.step() mutates
        rescale_grad per batch size, and a changed value must retrace rather
        than silently reuse the first-traced constant. (State-structure
        variants — momentum on/off, centered RMSProp — need no key of their
        own: jax.jit retraces per input pytree structure.)"""
        key = (key, self.rescale_grad, self.clip_gradient)
        if key not in self._step_cache:
            self._step_cache[key] = jax.jit(fn)
        return self._step_cache[key]

    def __getstate__(self):
        st = self.__dict__.copy()
        st["_step_cache"] = {}
        st.pop("_tree_cache", None)  # fastpath jit variants (fused.py)
        st.pop("_state_struct_cache", None)
        return st


register = Optimizer.register
create = Optimizer.create_optimizer


@register
class Test(Optimizer):
    """Trivial debug optimizer: w -= lr * grad, state keeps a weight copy
    (reference optimizer.py:Test)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        return w - lr * g * self.rescale_grad, state

    pure_step = _leaf_step


@register
class SGD(Optimizer):
    """SGD with momentum and multi-precision (reference optimizer.py:445;
    fused-op parity: sgd_update/sgd_mom_update/mp_sgd_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        g = self._preprocess(g, w, wd)
        if state is None:
            return w - lr * g, None
        m = self.momentum * state - lr * g
        return w + m, m

    pure_step = _leaf_step


@register
class ccSGD(SGD):  # noqa: N801 - reference name (optimizer.py:ccSGD)
    """Deprecated alias of SGD kept for reference-code compatibility."""


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference optimizer.py:NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        g = self._preprocess(g, w, wd)
        if state is None:
            return w - lr * g, None
        m = self.momentum * state + g
        return w - lr * (self.momentum * m + g), m

    pure_step = _leaf_step


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference optimizer.py:SGLD).
    The injected-noise key is drawn on the host per update (one
    ``_global.next_key()`` per parameter per step, the same stream the
    per-parameter path always consumed) and enters the kernel as a traced
    extra."""

    _host_scalars_stateful = True  # consumes the host rng stream in order
    _leaf_step_pointwise = False   # noise draw depends on the weight SHAPE

    def _host_scalars(self, index):
        from . import _global

        return (self._get_lr(index), self._get_wd(index),
                (_global.next_key(),))

    def _leaf_step(self, w, g, state, t, lr, wd, key):
        g = self._preprocess(g, w, wd)
        noise = jax.random.normal(key, w.shape, dtype=w.dtype) * jnp.sqrt(lr)
        return w - lr / 2 * g + noise, state


@register
class SignSGD(Optimizer):
    """Take the sign of the gradient (reference optimizer.py:Signum family)."""

    def _leaf_step(self, w, g, state, t, lr, wd):
        g = g * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return w - lr * (jnp.sign(g) + wd * w), state

    pure_step = _leaf_step


@register
class Signum(Optimizer):
    """Sign of momentum SGD (reference optimizer.py:550)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        if state is None:
            g = g * self.rescale_grad
            if self.clip_gradient is not None:
                g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
            return w - lr * (jnp.sign(g) + wd * w), None
        g = self._preprocess(g, w, wd)
        m = self.momentum * state - (1 - self.momentum) * g
        return w + lr * jnp.sign(m) - lr * self.wd_lh * w, m

    pure_step = _leaf_step


@register
class FTML(Optimizer):
    """Follow the Moving Leader (reference optimizer.py:616)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w), jnp.zeros_like(w))

    def _leaf_step(self, w, g, state, t, lr, wd):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        d, v, z = state
        g = self._preprocess_wd_in_clip(g, w, wd)
        v = b2 * v + (1 - b2) * g * g
        bc1 = 1 - jnp.power(b1, t)
        bc2 = 1 - jnp.power(b2, t)
        d_t = bc1 / lr * (jnp.sqrt(v / bc2) + eps)
        sigma = d_t - b1 * d
        z = b1 * z + (1 - b1) * g - sigma * w
        return -z / d_t, (d_t, v, z)

    pure_step = _leaf_step


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py:DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        w = _as_jax(weight)
        if self.momentum == 0.0:
            return (None, jnp.asarray(w))
        return (jnp.zeros_like(w), jnp.asarray(w))

    def _leaf_step(self, w, g, state, t, lr, wd):
        mom, prev = state
        g = self._preprocess_no_wd(g)
        if mom is None:
            upd = -lr * (g + wd * w + self.lamda * g * g * (w - prev))
            return w + upd, (None, w)
        m = self.momentum * mom - lr * (
            g + wd * w + self.lamda * g * g * (w - prev))
        return w + m, (m, w)

    pure_step = _leaf_step


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (reference optimizer.py:672, simplified to the lars core)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum

    _leaf_step_pointwise = False  # layer-wise w/g norms are cross-element

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        g = self._preprocess(g, w, wd)
        wnorm = jnp.linalg.norm(w.ravel())
        gnorm = jnp.linalg.norm(g.ravel())
        lars = jnp.where(
            (wnorm > 0) & (gnorm > 0), wnorm / (gnorm + 1e-9), 1.0)
        eff_lr = lr * lars
        if state is None:
            return w - eff_lr * g, None
        m = self.momentum * state - eff_lr * g
        return w + m, m

    pure_step = _leaf_step


@register
class Adam(Optimizer):
    """Adam (reference optimizer.py:1014; fused-op parity adam_update).

    The bias correction is a host-side scalar transform of the learning
    rate (:meth:`_host_scalars`, reference optimizer.py:1037) so the kernel
    itself stays schedule-free; the in-graph :meth:`pure_step` traces the
    same correction on-device from the scanned ``t``."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def _host_scalars(self, index):
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        lr = lr * math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        return lr, self._get_wd(index), ()

    def _leaf_step(self, w, g, state, t, lr, wd):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        m, v = state
        g = self._preprocess_wd_in_clip(g, w, wd)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        return w - lr * m / (jnp.sqrt(v) + eps), (m, v)

    def pure_step(self, w, g, state, t, lr, wd):
        b1, b2 = self.beta1, self.beta2
        lr = lr * jnp.sqrt(1.0 - jnp.power(b2, t)) / (1.0 - jnp.power(b1, t))
        return self._leaf_step(w, g, state, t, lr, wd)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference optimizer.py:AdaGrad; sparse lazy path collapses to
    dense — XLA has no sparse, SURVEY §7.3)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return jnp.zeros_like(_as_jax(weight))

    def _leaf_step(self, w, g, state, t, lr, wd):
        g = self._preprocess_no_wd(g)
        h = state + g * g
        return w - lr * (g / jnp.sqrt(h + self.float_stable_eps) + wd * w), h

    pure_step = _leaf_step


@register
class RMSProp(Optimizer):
    """RMSProp, centered (Graves) and plain (reference optimizer.py:1155)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        w = _as_jax(weight)
        if self.centered:
            return (jnp.zeros_like(w), jnp.zeros_like(w), jnp.zeros_like(w))
        return (jnp.zeros_like(w),)

    def _leaf_step(self, w, g, state, t, lr, wd):
        g1, g2, eps = self.gamma1, self.gamma2, self.epsilon
        g = self._preprocess_wd_in_clip(g, w, wd)
        if len(state) == 1:
            (n,) = state
            n = (1 - g1) * g * g + g1 * n
            w = w - lr * g / jnp.sqrt(n + eps)
            if self.clip_weights:
                w = jnp.clip(w, -self.clip_weights, self.clip_weights)
            return w, (n,)
        n, mg, delta = state
        n = (1 - g1) * g * g + g1 * n
        mg = (1 - g1) * g + g1 * mg
        delta = g2 * delta - lr * g / jnp.sqrt(n - mg * mg + eps)
        w = w + delta
        if self.clip_weights:
            w = jnp.clip(w, -self.clip_weights, self.clip_weights)
        return w, (n, mg, delta)

    pure_step = _leaf_step


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference optimizer.py:AdaDelta)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def _leaf_step(self, w, g, state, t, lr, wd):
        rho, eps = self.rho, self.epsilon
        g = self._preprocess_no_wd(g)
        acc_g, acc_d = state
        acc_g = rho * acc_g + (1 - rho) * g * g
        delta = jnp.sqrt(acc_d + eps) / jnp.sqrt(acc_g + eps) * g
        acc_d = rho * acc_d + (1 - rho) * delta * delta
        return w - (delta + wd * w), (acc_g, acc_d)

    pure_step = _leaf_step


@register
class Ftrl(Optimizer):
    """FTRL-proximal (reference optimizer.py:Ftrl; fused ftrl_update parity)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w))  # (z, n)

    def _leaf_step(self, w, g, state, t, lr, wd):
        l1, beta = self.lamda1, self.beta
        g = g * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        z, n = state
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr
        z = z + g - sigma * w
        n = n + g * g
        w = jnp.where(
            jnp.abs(z) > l1,
            -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / lr + wd),
            0.0,
        ).astype(w.dtype)
        return w, (z, n)

    pure_step = _leaf_step


@register
class Adamax(Optimizer):
    """AdaMax (reference optimizer.py:Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def _host_scalars(self, index):
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        return lr, self._get_wd(index), ()

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def _leaf_step(self, w, g, state, t, lr, wd):
        b1, b2 = self.beta1, self.beta2
        g = self._preprocess_wd_in_clip(g, w, wd)
        m, u = state
        m = b1 * m + (1 - b1) * g
        u = jnp.maximum(b2 * u, jnp.abs(g))
        return w - lr * m / (u + 1e-8), (m, u)

    def pure_step(self, w, g, state, t, lr, wd):
        lr = lr / (1.0 - jnp.power(self.beta1, t))
        return self._leaf_step(w, g, state, t, lr, wd)


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference optimizer.py:Nadam). The momentum schedule
    is a host-side recurrence (``m_schedule`` multiplies up across updates),
    so its scalars enter the kernel as traced extras via
    :meth:`_host_scalars` — time-varying values never retrace."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    _host_scalars_stateful = True  # m_schedule multiplies up per call

    def create_state(self, index, weight):
        w = _as_jax(weight)
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def _host_scalars(self, index):
        t = self._index_update_count[index]
        momentum_t = self.beta1 * (1.0 - 0.5 * (0.96 ** (t * self.schedule_decay)))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * (0.96 ** ((t + 1) * self.schedule_decay)))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        return (self._get_lr(index), self._get_wd(index),
                (_f32(momentum_t), _f32(momentum_t_1), _f32(self.m_schedule),
                 _f32(m_schedule_next)))

    def _leaf_step(self, w, g, state, t, lr, wd, mt, mt1, ms, msn):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        m, v = state
        g = self._preprocess_wd_in_clip(g, w, wd)
        g_prime = g / (1.0 - ms)
        m = b1 * m + (1.0 - b1) * g
        m_prime = m / (1.0 - msn)
        v = b2 * v + (1.0 - b2) * g * g
        v_prime = v / (1.0 - jnp.power(b2, t))
        m_bar = (1.0 - mt) * g_prime + mt1 * m_prime
        return w - lr * m_bar / (jnp.sqrt(v_prime) + eps), (m, v)


# ---------------------------------------------------------------------------
# Updater (reference optimizer.py:1506)
# ---------------------------------------------------------------------------


class Updater(object):
    """Applies an optimizer to (index, grad, weight) triples, owning the
    per-index state dict — reference optimizer.py:Updater (get_updater)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        elif getattr(self.states[index], "_is_zero_shard", False):
            # an eager per-param update interleaving with the ZeRO plane
            # must see the plain per-parameter layout — materialize the
            # whole plane (the next sharded step re-adopts)
            from .fastpath import zero

            zero.materialize_updater(self)
            if index not in self.states:  # lost to a failed donated step
                self.states[index] = \
                    self.optimizer.create_state_multi_precision(index,
                                                                weight)
                self.states_synced[index] = True
        self.states[index] = self.optimizer.update_multi_precision(
            index, weight, grad, self.states[index])

    def sync_state_context(self, state, context):
        return state

    def set_states(self, states):
        """Restore states from :meth:`get_states` bytes."""
        # a restore replaces the whole layout: drop any attached ZeRO
        # plane rather than letting a stale handle alias the old shards
        self._zero_plane = None
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        # stored as numpy; rehydrate to jax on first use
        self.states = {
            k: jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) if isinstance(a, np.ndarray) else a, v)
            for k, v in self.states.items()
        }
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def adopt_states(self, states: Dict, optimizer=None):
        """Install plain per-index ``states`` directly (no pickle round
        trip) — the sharded-checkpoint restore path: ``elastic`` rebuilds
        per-parameter trees from shard files and hands them here. Any
        attached ZeRO plane is dropped (its handles would alias a layout
        that no longer owns the state; the next sharded step re-adopts
        onto the live mesh), numpy leaves rehydrate to jax arrays, and
        ``optimizer`` — when given — replaces the owned optimizer so the
        restored step counters (``num_update``, per-index counts) become
        the live ones."""
        self._zero_plane = None
        if optimizer is not None:
            self.optimizer = optimizer
        self.states = {
            k: jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) if isinstance(a, np.ndarray) else a,
                v)
            for k, v in states.items()
        }
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        """Serialize states (optionally with the optimizer) to bytes.
        Sharded (ZeRO) states are materialized back to the plain
        per-parameter layout first — a checkpoint must never depend on
        the mesh it was trained on."""
        from .fastpath import zero

        zero.materialize_updater(self)
        host_states = {
            k: jax.tree_util.tree_map(
                lambda a: np.asarray(a) if isinstance(a, jnp.ndarray) else a, v)
            for k, v in self.states.items()
        }
        return pickle.dumps((host_states, self.optimizer) if dump_optimizer else host_states)


def get_updater(optimizer):
    return Updater(optimizer)
