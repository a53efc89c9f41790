"""Gluon Block / HybridBlock.

API parity with reference ``python/mxnet/gluon/block.py`` (Block :126,
HybridBlock :672, SymbolBlock :953, name scoping, ``save_parameters`` /
``load_parameters``, ``export``).

TPU-native CachedOp: the reference's ``hybridize()`` traces hybrid_forward
into an nnvm graph interpreted node-by-node (``_build_cache`` →
``CachedOp::Forward``, reference block.py:749-786, src/imperative/cached_op.cc).
Here ``hybridize()`` wraps the same eager forward in ``jax.jit``: the whole
block — children included — lowers to ONE fused XLA HloModule per
(input-shapes, train-mode) key, which is strictly stronger than the
reference's static_alloc/static_shape fast path. Autograd over the compiled
block records a single tape node whose vjp is the XLA-transposed module.
"""
from __future__ import annotations

import copy
import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from .. import _fused, _global, autograd, telemetry
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import ndarray as nd_mod
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope(object):
    """Name scoping for Blocks (reference gluon/block.py:_BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from .. import name as _name

                prefix = _name.NameManager._current_counted(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


def _flatten(args, fmt_name):
    """Flatten nested lists/tuples of NDArrays; returns (flat, fmt)."""
    if isinstance(args, NDArray):
        return [args], int(0)
    if args is None:
        return [None], int(-1)
    if isinstance(args, (list, tuple)):
        flat, fmts = [], []
        for i in args:
            arg, fmt = _flatten(i, fmt_name)
            flat.extend(arg)
            fmts.append(fmt)
        return flat, fmts
    raise MXNetError(
        "When hybridized, the input of HybridBlock {} must be (nested) list of "
        "NDArray, but got {} of type {}".format(fmt_name, str(args), str(type(args))))


def _regroup(args, fmt):
    if isinstance(fmt, int):
        if fmt == -1:
            return None, args
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    ret = []
    for i in fmt:
        res, args = _regroup(args, i)
        ret.append(res)
    return ret, args


class Block(object):
    """Base building block (reference gluon/block.py:126)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            "  ({key}): {block}".format(key=key, block=_indent(str(block), 2))
            for key, block in self.__dict__.items()
            if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
                raise TypeError(
                    "Changing attribute type for {name} from {type1} to {type2} "
                    "is not allowed.".format(
                        name=name, type1=type(existing), type2=type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        """All Parameters of this block and children (reference block.py:collect_params)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _check_container_with_block(self):
        children = set(self._children.values())
        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and k not in ("_children",):
                items = v.values() if isinstance(v, dict) else v
                for item in items:
                    if isinstance(item, Block) and item not in children:
                        import warnings

                        warnings.warn(
                            '"{}" is an unregistered container with Blocks. '
                            "Register it with register_child().".format(k))

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- (de)serialization ---------------------------------------------------
    def save_parameters(self, filename):
        """Save parameters keyed by attribute chain (reference
        block.py:save_parameters format — loadable without network structure)."""
        from ..ndarray import io_utils

        params = self._collect_params_with_prefix()
        arg_dict = {key: val._reduce() if hasattr(val, "_reduce") else val.data()
                    for key, val in params.items()}
        io_utils.save(filename, arg_dict)

    save_params = save_parameters

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        from ..ndarray import io_utils

        loaded = io_utils.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()):
            # legacy format: full param names
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise MXNetError(
                        "Parameter '%s' is missing in file '%s'." % (name, filename))
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        "Parameter '%s' loaded from file '%s' is not present in this "
                        "block." % (name, filename))
                continue
            params[name]._load_init(loaded[name], ctx)

    load_params = load_parameters

    # -- children / hooks ----------------------------------------------------
    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle._id] = hook
        return handle

    def apply(self, fn):
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        if init is None:
            from .. import initializer

            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary given example inputs (reference
        block.py:summary)."""
        summary = OrderedDict()
        hooks = []

        def _register(block, prefix):
            def hook(blk, inp, out):
                name = prefix + blk.__class__.__name__
                outs = out if isinstance(out, (list, tuple)) else [out]
                shape = [tuple(o.shape) for o in outs if isinstance(o, NDArray)]
                n_params = sum(
                    int(np.prod(p.shape)) for p in blk._reg_params.values()
                    if p.shape is not None)
                summary[name] = (shape, n_params)

            hooks.append(block.register_forward_hook(hook))
            for cname, child in block._children.items():
                _register(child, prefix + cname + ".")

        _register(self, "")
        try:
            self(*inputs)
        finally:
            for h in hooks:
                h.detach()
        lines = ["%-40s %-24s %12s" % ("Layer", "Output Shape", "Params"),
                 "=" * 78]
        total = 0
        for name, (shape, n) in summary.items():
            lines.append("%-40s %-24s %12d" % (name, str(shape), n))
            total += n
        lines.append("=" * 78)
        lines.append("Total params (leaf blocks): %d" % total)
        print("\n".join(lines))


class _HookHandle(object):
    _next_id = [0]

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        self._id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1

    def detach(self):
        self._hooks_dict.pop(self._id, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    if len(lines) == 1:
        return s_
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


class _TrainPair(object):
    """One compiled forward module + one compiled backward module.

    ``forward`` runs a jitted function that computes outputs, aux updates,
    and the vjp residuals (via jax.closure_convert, which hoists the vjp
    closure's captured intermediates into explicit arrays). ``backward``
    runs the hoisted, jitted transpose on (residuals, cotangents). Both are
    traced exactly once per shape signature — the TPU counterpart of the
    reference building forward+backward as one nnvm graph up front
    (src/executor/graph_executor.cc:231-295) instead of re-deriving the
    backward every iteration.
    """

    def __init__(self, base_fn, diff_pnames, diff_arg_idx):
        self._diff_pnames = list(diff_pnames)
        self._diff_arg_idx = list(diff_arg_idx)
        self._cell = {}
        cell = self._cell

        def fwd(diff_pvals, const_pvals, rng, arg_datas):
            def f(dp_list, da_list):
                pv = dict(const_pvals)
                pv.update(zip(diff_pnames, dp_list))
                full = list(arg_datas)
                for i, a in zip(diff_arg_idx, da_list):
                    full[i] = a
                return base_fn(pv, rng, *full)

            da_list = [arg_datas[i] for i in diff_arg_idx]
            outs, vjp_fn, aux = jax.vjp(f, list(diff_pvals), da_list,
                                        has_aux=True)
            flat_outs, out_tree = jax.tree_util.tree_flatten(outs)

            def vjp_flat(*cts_flat):
                return vjp_fn(jax.tree_util.tree_unflatten(
                    out_tree, list(cts_flat)))

            examples = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                        for o in flat_outs]
            vjp_pure, res = _fused.convert_closure(vjp_flat, *examples)
            cell["bwd"] = vjp_pure
            cell["single"] = not isinstance(outs, (tuple, list))
            return outs, aux, res

        self._fwd_jit = jax.jit(fwd)

    def forward(self, diff_pvals, const_pvals, rng, arg_datas):
        outs, aux, res = telemetry.jit_call(
            "gluon.hybrid_forward", self._fwd_jit, diff_pvals, const_pvals,
            rng, list(arg_datas))
        single = self._cell["single"]
        outs_t = (outs,) if single else tuple(outs)
        return outs_t, aux, res, single

    def backward(self, res, cts_flat):
        if "bwd_jit" not in self._cell:
            bwd = self._cell["bwd"]
            self._cell["bwd_jit"] = jax.jit(
                lambda res, cts: bwd(res, *cts))
        return self._cell["bwd_jit"](list(res), list(cts_flat))


class HybridBlock(Block):
    """Block that can compile its forward (reference gluon/block.py:672).

    Subclasses implement ``hybrid_forward(self, F, x, *args, **params)``
    where ``F`` is the ``nd`` namespace and params arrive as keyword
    NDArrays, exactly like the reference. ``hybridize()`` activates the
    jitted whole-graph path.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._jit_cache = {}
        self._out_fmt = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def _clear_cached_op(self):
        self._jit_cache = {}
        self._out_fmt = None

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Activate compiled execution. static_alloc/static_shape accepted for
        API parity; jit always gives static planning on XLA."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Infer (and finish deferred init of) params by running an abstract
        forward with jax.eval_shape — no FLOPs spent."""
        self._deferred_infer(args)

    def _deferred_infer(self, args):
        """Finish the deferred init of every parameter under this block
        WITHOUT running it: the eager forward is traced under
        ``jax.eval_shape``, so each layer's ``shape_hint`` sees real input
        shapes and its initializer runs (on concrete values, for real)
        while the layer math costs nothing. Running the forward for real
        instead compiled every child block on its own — 191 compiles,
        ~460 s, before ResNet-50's first step on a v5e chip (PR 22). A
        forward that cannot be traced (host sync, value-dependent python
        control flow) falls back to the real eager pass."""
        x, rest = args[0], list(args[1:])
        flat_args, in_fmt = _flatten([x] + rest, "input")
        ctx = x.context

        def trace(*datas):
            # a non-empty key stack makes nested hybridized blocks inline
            # into this trace instead of jit-compiling themselves
            _global.push_rng_key(jax.random.PRNGKey(0))
            try:
                nds = [NDArray(d, ctx) if d is not None else None
                       for d in datas]
                grouped, _rest = _regroup(nds, in_fmt)
                with autograd.pause():
                    self._eager_forward(*grouped)
            finally:
                _global.pop_rng_key()

        try:
            jax.eval_shape(trace, *[a._data if a is not None else None
                                    for a in flat_args])
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerBoolConversionError,
                jax.errors.TracerIntegerConversionError):
            with autograd.pause():
                self._eager_forward(*args)

    # -- eager path ----------------------------------------------------------
    def _eager_forward(self, x, *args):
        from .. import ndarray as F

        try:
            params = {i: j.data(x.context) for i, j in self._reg_params.items()}
        except DeferredInitializationError:
            self._finish_deferred(x, *args)
            params = {i: j.data(x.context) for i, j in self._reg_params.items()}
        return self.hybrid_forward(F, x, *args, **params)

    def _finish_deferred(self, x, *args):
        """Resolve deferred shapes, then init (reference
        block.py:_deferred_infer_shape → infer_shape)."""
        self.shape_hint(x, *args)
        # initializers run for real even when the forward around them is
        # only being traced (_deferred_infer): without this, jax stages
        # their ops into the trace and the parameter becomes a tracer
        with jax.ensure_compile_time_eval():
            for p in self._reg_params.values():
                p._finish_deferred_init()

    def shape_hint(self, x, *args):
        """Layers override to resolve 0-dims in param shapes from the input."""

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            if self._active:
                return self._call_cached(x, *args)
            return self._eager_forward(x, *args)
        from .. import symbol as sym_mod

        if isinstance(x, sym_mod.Symbol):
            # symbolic trace (reference block.py:_build_cache / export path):
            # params enter as Symbol variables; children recurse through the
            # same dispatch since their __call__ receives Symbols
            params = {name: p.var() for name, p in self._reg_params.items()}
            for name, p in self._reg_params.items():
                if p.grad_req == "null":
                    params[name]._outputs[0][0]._forced_aux = True
            return self.hybrid_forward(sym_mod, x, *args, **params)
        raise MXNetError(
            "HybridBlock requires NDArray or Symbol inputs, got %s" % type(x))

    # -- compiled path (CachedOp equivalent) --------------------------------
    def _call_cached(self, x, *args):
        # nested compiled blocks inline into the enclosing trace: one fused
        # HloModule for the outermost hybridized block
        if _global._state().key_stack:
            return self._eager_forward(x, *args)

        flat_args, in_fmt = _flatten([x] + list(args), "input")
        arg_datas = [a._data if a is not None else None for a in flat_args]

        # collect ALL params (children included); finish deferred init first
        params = self.collect_params()
        try:
            pvals = {name: p.data(x.context)._data for name, p in params.items()
                     if p._data is not None or p._deferred_init}
        except DeferredInitializationError:
            self._deferred_infer((x,) + tuple(args))
            pvals = {name: p.data(x.context)._data for name, p in params.items()
                     if p._data is not None}

        train = bool(_global.is_train())
        rng = _global.next_key()
        record = autograd.is_recording() and (
            any(a is not None and a._in_graph for a in flat_args)
            or any(p.grad_req != "null" for p in params.values()))

        param_nds = {name: params[name].data(x.context) for name in pvals}

        if not record:
            key = (train,)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._build_jit_fn(in_fmt, train)
            out_datas, aux_out = self._jit_cache[key](pvals, rng, *arg_datas)
            self._apply_aux(params, aux_out, x.context)
            return self._wrap_outputs(out_datas, x.context)

        # fused fwd+bwd: one compiled forward module (outputs + residuals)
        # and one compiled backward module — the counterpart of the
        # reference GraphExecutor building fwd+bwd as a single graph
        # (graph_executor.cc:231-295). No retracing on later steps: the
        # pair is cached per (shapes, dtypes) signature.
        diff_pnames = tuple(n for n in pvals if params[n].grad_req != "null")
        const_pvals = {n: v for n, v in pvals.items() if n not in diff_pnames}
        diff_arg_idx = tuple(i for i, a in enumerate(flat_args) if a is not None)
        shape_sig = tuple((a.shape, str(a.dtype)) for a in arg_datas
                          if a is not None)
        key = ("fb", train, diff_pnames, diff_arg_idx, shape_sig)
        if key not in self._jit_cache:
            self._jit_cache[key] = _TrainPair(
                self._base_fn(in_fmt, train), diff_pnames, diff_arg_idx)
        pair = self._jit_cache[key]

        outs_t, aux_out, res, single = pair.forward(
            [pvals[n] for n in diff_pnames], const_pvals, rng, arg_datas)
        self._apply_aux(params, aux_out, x.context)

        node_inputs = [param_nds[n] for n in diff_pnames] + \
                      [flat_args[i] for i in diff_arg_idx]

        def vjp_wrapper(gs, _pair=pair, _res=res, _single=single):
            p_grads, a_grads = _pair.backward(
                _res, (gs,) if _single else tuple(gs))
            return tuple(p_grads) + tuple(a_grads)

        node = autograd._TapeNode(
            vjp_fn=vjp_wrapper,
            inputs=node_inputs,
            out_shapes=[(o.shape, o.dtype) for o in outs_t],
            single=single,
            op_name="_CachedOp(%s)" % self._alias(),
        )
        nd_outs = []
        for idx, o in enumerate(outs_t):
            nd = NDArray(o, x.context)
            nd._entry = (node, idx)
            nd_outs.append(nd)
        return self._wrap_tree(nd_outs, single)

    @staticmethod
    def _apply_aux(params, aux_out, ctx):
        """Write back aux-state updates (BatchNorm moving stats) computed
        inside the compiled module — the counterpart of the reference's
        mutable-input handling in CachedOp (cached_op.h:33-50)."""
        for name, val in aux_out.items():
            params[name].data(ctx)._data = val

    def _build_jit_fn(self, in_fmt, train):
        """Jitted whole-block forward for the non-recording path."""
        return jax.jit(self._base_fn(in_fmt, train))

    def _base_fn(self, in_fmt, train):
        """Build the traceable whole-block function. Parameters enter as a
        dict pytree; the RNG key is traced so dropout/rrelu resample per
        call; returns (outputs, aux_updates) where aux_updates carries new
        values of non-differentiable state (BN moving stats)."""
        block = self

        def fn(pvals, rng, *arg_datas):
            prev_train = _global.set_train(train)
            _global.push_rng_key(rng)
            try:
                params = block.collect_params()
                saved = {}
                wrapped_nds = {}
                for name, val in pvals.items():
                    p = params[name]
                    saved[name] = p._data
                    wrapped = NDArray(val, cpu())
                    wrapped_nds[name] = wrapped
                    p._data = OrderedDict([(cpu(), wrapped)])
                try:
                    flat_nd = [NDArray(a, cpu()) if a is not None else None
                               for a in arg_datas]
                    grouped, _rest = _regroup(flat_nd, in_fmt)
                    # pause recording but keep train mode: the train flag is
                    # part of the jit cache key and governs BN/dropout here
                    with autograd._RecordingStateScope(False, None):
                        out = block._eager_forward(*grouped)
                    # aux params whose buffer was rebound during the trace
                    # (e.g. BN moving stats) surface as extra outputs
                    aux = {
                        name: wrapped_nds[name]._data
                        for name in pvals
                        if params[name].grad_req == "null"
                        and wrapped_nds[name]._data is not pvals[name]
                    }
                finally:
                    for name, d in saved.items():
                        params[name]._data = d
            finally:
                _global.pop_rng_key()
                _global.set_train(prev_train)
            if isinstance(out, (list, tuple)):
                flat_out, out_fmt = _flatten(out, "output")
                block._out_fmt = out_fmt
                return tuple(o._data for o in flat_out), aux
            block._out_fmt = 0
            return out._data, aux

        return fn

    def _wrap_outputs(self, out_datas, ctx):
        if isinstance(out_datas, tuple):
            nds = [NDArray(o, ctx) for o in out_datas]
            return self._wrap_tree(nds, False)
        return NDArray(out_datas, ctx)

    def _wrap_tree(self, nd_list, single):
        if single:
            return nd_list[0]
        if self._out_fmt is not None and not isinstance(self._out_fmt, int):
            grouped, _ = _regroup(nd_list, self._out_fmt)
            return grouped
        return list(nd_list)

    def export(self, path, epoch=0):
        """Export compiled model as symbol JSON + params (reference
        block.py:export two-artifact contract)."""
        from .. import symbol as sym_mod
        from ..ndarray import io_utils

        sym = self._as_symbol()
        sym.save("%s-symbol.json" % path)
        arg_names = set(sym.list_arguments())
        aux_names = set(sym.list_auxiliary_states())
        arg_dict = {}
        for name, param in self.collect_params().items():
            if name in aux_names:
                arg_dict["aux:%s" % name] = param.data()
            elif name in arg_names:
                arg_dict["arg:%s" % name] = param.data()
        io_utils.save("%s-%04d.params" % (path, epoch), arg_dict)

    def _as_symbol(self):
        """Trace this block (children included) with Symbol inputs to produce
        a graph (reference _build_cache's symbolic trace)."""
        from .. import symbol as sym_mod

        out = self(sym_mod.var("data"))
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        return out

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


class SymbolBlock(HybridBlock):
    """Build a Block from a Symbol + inputs (reference gluon/block.py:953);
    used to import exported models."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        # param names come straight from the symbol graph: empty prefix
        # (reference block.py SymbolBlock.__init__)
        self._prefix = ""
        self._params = ParameterDict("", params)
        from .. import symbol as sym_mod

        if isinstance(inputs, sym_mod.Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(outputs)
        self._cached_graph_sym = outputs
        self._in_names = [i.name for i in inputs]
        arg_names = set(outputs.list_arguments()) - set(self._in_names)
        for name in outputs.list_arguments():
            if name not in self._in_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self.params.get(name, allow_deferred_init=True, grad_req="null")

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod

        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx, allow_missing=False,
                                      ignore_extra=True)
        return ret

    def forward(self, x, *args):
        from .. import symbol as sym_mod

        arg_dict = {self._in_names[0]: x}
        for name, a in zip(self._in_names[1:], args):
            arg_dict[name] = a
        for pname, p in self.collect_params().items():
            arg_dict[pname] = p.data(x.context)
        return self._cached_graph_sym.eval_nd(arg_dict)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
