"""eager-step: an eager forward/backward training step inside a loop.

The training plane behind ``MXNET_TRAINSTEP`` (``mxnet_tpu.trainplane``)
compiles the whole step — forward + loss + backward + allreduce + update —
into ONE XLA module; an eager loop body that records a forward, runs
``.backward()`` and applies an optimizer step dispatches dozens of
compiled calls per iteration instead. This pass flags
the shape of code that bypasses the step plane inside ``mxnet_tpu/`` so
framework-owned training loops route through ``trainplane``/``TrainStep``
(or get explicitly baselined as the eager fallback they are).

Flagged — a ``for``/``while`` loop whose body contains a full eager
training step, i.e. either:

- a ``.forward_backward(...)`` call together with an ``.update(...)``
  dispatch (the Module idiom), or
- an ``autograd.record()`` with-block AND a ``.backward(...)`` call AND a
  trainer/optimizer step (``.step(...)`` / ``.update(...)``) — the gluon
  idiom.

One finding per loop. The legit eager sites — the documented fallback
loops the graph plane demotes to — stay baselined, not fixed; the gate
only stops NEW eager training loops from growing into the framework.
"""
from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, Pass, dotted_name, register

_LOOPS = (ast.For, ast.AsyncFor, ast.While)


def _is_record_with(node: ast.With) -> bool:
    for item in node.items:
        expr = item.context_expr
        if isinstance(expr, ast.Call):
            name = dotted_name(expr.func) or ""
            if name.endswith("record") or name.endswith("train_mode"):
                return True
    return False


def _scan_loop(loop: ast.AST):
    """(has_record, has_backward, has_step, has_fwd_bwd, has_update) over
    the loop body, not descending into nested function definitions."""
    has = {"record": False, "backward": False, "step": False,
           "fwd_bwd": False, "update": False}

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.With) and _is_record_with(child):
                has["record"] = True
            if isinstance(child, ast.Call):
                name = dotted_name(child.func) or ""
                attr = name.rsplit(".", 1)[-1]
                if attr == "forward_backward":
                    has["fwd_bwd"] = True
                elif attr == "backward":
                    has["backward"] = True
                elif attr == "step":
                    has["step"] = True
                elif attr == "update":
                    # metric.update(label, pred) is bookkeeping, not an
                    # optimizer step — `eval_metric.update` next to
                    # record/backward must not read as a training loop
                    recv = name.rsplit(".", 1)[0] if "." in name else ""
                    if "metric" not in recv.lower():
                        has["update"] = True
            walk(child)

    walk(loop)
    return has


@register
class EagerStepPass(Pass):
    name = "eager-step"
    description = ("eager forward/backward training step inside a loop — "
                   "route through trainplane/TrainStep (one whole-step jit)")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("mxnet_tpu/")

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, _LOOPS):
                continue
            has = _scan_loop(node)
            if has["fwd_bwd"] and has["update"]:
                yield ctx.finding(
                    node, self.name,
                    "eager forward_backward()+update() training loop — "
                    "route through the MXNET_TRAINSTEP graph plane")
            elif has["record"] and has["backward"] and (
                    has["step"] or has["update"]):
                yield ctx.finding(
                    node, self.name,
                    "eager record/backward/step training loop — route "
                    "through trainplane.TrainPlane (one whole-step jit)")
