"""Share of the prefill attention's (query block, kv block) pairs that hold
a prompt's real tokens: summed `attn_blocks_live` over summed
`attn_blocks_rung` of the traced `mx.decode.prefill` spans — what
`mx_prefill_attn` multiplies of what the band holds over the padded rungs.
A program whose spans carry no `attn_blocks_rung` (the parent of the PR that
brought the argument) and a window without a prefill are left out."""
import trace_within


def read(run):
    rows = [r for r in trace_within.span_args(
        run, ("mx.decode.prefill",)) or () if r.get("attn_blocks_rung")]
    if not rows:
        return None
    return 100.0 * sum(r["attn_blocks_live"] for r in rows) \
        / sum(r["attn_blocks_rung"] for r in rows)
