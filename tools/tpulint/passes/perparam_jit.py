"""perparam-jit: jitted-call dispatch inside a per-parameter loop.

The dispatch-bound regime (on the chip, ResNet-50 at 393.5 img/s with the
device 73.8 % idle against 1,341 img/s device-bound: PERF_LEDGER.jsonl,
PR 24 and PR 26) comes from one shape of code: a python ``for`` loop over
parameters (or kvstore keys) issuing one compiled-call dispatch per element —
``updater(i, g, w)`` per parameter, ``self._fused(...)(...)`` per weight,
``kv.push(i, ...)`` per key. Each iteration pays a full host→device
dispatch for micro-sized work while the accelerator idles between kernels.
The fastpath layer removes the pattern (one fused jit over the whole tree,
one batched pushpull over all keys); this pass keeps it from growing back.

Flagged inside a loop:

- invoking a jitted callable obtained *in the same expression*:
  ``jax.jit(f)(x)``, ``self._fused(...)(...)``, or a subscripted jit cache
  (``self._step_cache[k](...)``, ``_JITS[key](...)``);
- calling a name bound from ``jax.jit(...)`` in the same function;
- the per-parameter optimizer dispatch: ``optimizer.update(...)`` /
  ``.update_multi_precision(...)``, or calling an ``updater``/``upd``
  variable;
- the per-key kvstore exchange: ``.push(...)`` / ``.pull(...)`` on a
  kvstore-named receiver.

Legacy escape hatches (the ``MXNET_FASTPATH=0`` loops) stay baselined, not
fixed — the gate only stops NEW per-parameter dispatch loops.
"""
from __future__ import annotations

import ast
from typing import Iterator

from ..core import (FileContext, Finding, Pass, dotted_name, in_loop,
                    register)

_JIT_FACTORIES = {"jax.jit", "jit", "pjit", "jax.pjit"}
_JIT_CACHE_SUFFIXES = ("_jit", "_jits", "_step_cache", "_fwd_cache")
_UPDATER_NAMES = {"updater", "upd", "self._updater"}
_OPT_METHODS = {"update", "update_multi_precision"}
_KV_METHODS = {"push", "pull"}


def _callee_text(node: ast.AST) -> str:
    name = dotted_name(node)
    if name:
        return name
    try:
        return ast.unparse(node)
    except Exception:  # noqa: BLE001 - display only
        return "<call>"


def _is_jit_cache_subscript(node: ast.AST) -> bool:
    if not isinstance(node, ast.Subscript):
        return False
    base = dotted_name(node.value) or ""
    tail = base.rsplit(".", 1)[-1]
    return tail.endswith(_JIT_CACHE_SUFFIXES) or tail.isupper() and "JIT" in tail


def _jit_bound_names(func_node: ast.AST) -> set:
    """Names assigned from ``jax.jit(...)`` within this function body."""
    names = set()
    for node in ast.walk(func_node):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and dotted_name(node.value.func) in _JIT_FACTORIES:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
    return names


@register
class PerParamJitPass(Pass):
    name = "perparam-jit"
    description = ("jitted-call / optimizer / kvstore dispatch inside a "
                   "per-parameter loop (fuse over the tree instead)")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("mxnet_tpu/")

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        from ..core import enclosing_function

        jit_names_cache = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not in_loop(node):
                continue
            f = node.func

            # jax.jit(...)(...) / self._fused(...)(...) in one expression
            if isinstance(f, ast.Call):
                inner = dotted_name(f.func) or ""
                if inner in _JIT_FACTORIES or inner.endswith("._fused"):
                    yield ctx.finding(
                        node, self.name,
                        "`%s(...)(...)` dispatches one compiled call per "
                        "loop iteration" % inner)
                    continue

            # jit-cache subscript invocation: self._step_cache[k](...)
            if _is_jit_cache_subscript(f):
                yield ctx.finding(
                    node, self.name,
                    "jit-cache dispatch `%s(...)` inside a loop"
                    % _callee_text(f))
                continue

            # name bound from jax.jit(...) in the same function
            if isinstance(f, ast.Name):
                fn = enclosing_function(node)
                if fn is not None:
                    if fn not in jit_names_cache:
                        jit_names_cache[fn] = _jit_bound_names(fn)
                    if f.id in jit_names_cache[fn]:
                        yield ctx.finding(
                            node, self.name,
                            "`%s(...)` (bound from jax.jit) dispatches one "
                            "compiled call per loop iteration" % f.id)
                        continue

            name = dotted_name(f) or ""
            recv, _, attr = name.rpartition(".")
            recv_tail = recv.rsplit(".", 1)[-1].lower()

            # per-parameter optimizer dispatch; receiver must literally be
            # optimizer-named — short names like `opt`/`o` collide with
            # ordinary dict.update() merges and would red-flag valid code
            if (attr in _OPT_METHODS and "optimizer" in recv_tail) \
                    or name in _UPDATER_NAMES:
                yield ctx.finding(
                    node, self.name,
                    "per-parameter optimizer dispatch `%s(...)` in a loop — "
                    "route through fastpath.fused_apply" % name)
                continue

            # per-key kvstore exchange
            if attr in _KV_METHODS and "kv" in recv_tail:
                yield ctx.finding(
                    node, self.name,
                    "per-key kvstore `%s(...)` in a loop — batch through "
                    "pushpull_multi" % name)
