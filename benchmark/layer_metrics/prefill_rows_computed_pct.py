"""Share of the prefills' rows that their row-wise passes (norms,
projections, router, shared expert) compute: summed `rows_computed` over
summed `rows_rung` of the traced `mx.decode.prefill` spans — the rows of the
row blocks the prompts reach, of the padded rungs' rows. A program whose
spans carry no `rows_rung` (the parent of the PR that brought the argument:
it computes every row of the rung) and a window without a prefill are left
out."""
import trace_within


def read(run):
    rows = [r for r in trace_within.span_args(
        run, ("mx.decode.prefill",)) or () if r.get("rows_rung")]
    if not rows:
        return None
    return 100.0 * sum(r["rows_computed"] for r in rows) \
        / sum(r["rows_rung"] for r in rows)
