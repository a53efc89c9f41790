"""Share of the page tables' columns that the traced ticks' paged-attention
walks ran: summed `kv_cols_live` over summed `kv_cols_grid` (columns x slots
x layers) of the `mx.decode.commit` spans. The rest of a launch is grid steps
that fetch and multiply nothing."""
import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    rows = [s.args for s in got["spans"] if s.name == "mx.decode.commit"
            and s.args.get("kv_cols_grid")]
    if not rows:
        return None
    return 100.0 * sum(r["kv_cols_live"] for r in rows) \
        / sum(r["kv_cols_grid"] for r in rows)
