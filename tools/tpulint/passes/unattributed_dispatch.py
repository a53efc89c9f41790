"""unattributed-dispatch: jit dispatch sites invisible to the perf plane.

The telemetry stack attributes everything that flows through
``telemetry.jit_call``: recompiles + compile seconds per site (PR 3)
and chaos injection (PR 4). A jit/pallas dispatch that bypasses the
wrapper gets NONE of that: its recompiles surface only as unexplained
latency.

This pass reuses the recompile-risk interpreter's dispatch-site finder
(:class:`tools.tpulint.shapes.DispatchSite` — the same resolution that
sees direct calls of ``jax.jit`` values, jit-valued ``self._step``-style
attributes and ``@jit``-decorated functions called by name) and flags
every site in ``mxnet_tpu/`` not routed through ``telemetry.jit_call``.
A bare ``resilience.call`` around a jitted fn counts as UNattributed:
it retries the dispatch but accounts nothing.

Legitimate bypasses exist — one-shot AOT warmup dispatches, the
optimizer's fused-update plumbing where the wrapper would sit inside a
scan, engine warmup laps whose recompiles are the *point* — and live in
the baseline with justifications, same as every other pass.
"""
from __future__ import annotations

from typing import Iterator

from ..core import FileContext, Finding, Pass, register
from .. import shapes


@register
class UnattributedDispatchPass(Pass):
    name = "unattributed-dispatch"
    description = ("jit/pallas dispatch sites not routed through "
                   "telemetry.jit_call — invisible to recompile "
                   "accounting and chaos injection")
    project = True

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("mxnet_tpu/")

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        graph = ctx.project
        if graph is None:
            return
        ana = shapes.analyze(graph)
        for site in ana.dispatch_sites.get(ctx.relpath, ()):
            if site.wrapped:
                continue
            how = {"resilience.call": "dispatches through a bare "
                                      "resilience.call, which retries but "
                                      "does not attribute",
                   "decorated": "calls a @jit-decorated function directly",
                   }.get(site.via, "dispatches a jit-compiled callable "
                                   "directly")
            yield ctx.finding(
                site.node, self.name,
                "jit dispatch `%s` %s — its recompiles are invisible to "
                "the accounting plane and chaos cannot reach it; "
                "route it as telemetry.jit_call(\"<site>\", fn, ...) or "
                "baseline it with the reason it must stay bare"
                % (site.fn_label, how))
