"""Share of the grid steps the traced ticks' paged-attention launches ran that
fetched and multiplied nothing: summed `kv_cols_walked` less summed
`kv_cols_live`, over summed `kv_cols_walked`, of the `mx.decode.commit`
spans. A program whose spans carry no `kv_cols_walked` (the parent of the PR
that brought the argument) is left out."""
import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    rows = [s.args for s in got["spans"] if s.name == "mx.decode.commit"
            and s.args.get("kv_cols_walked")]
    if not rows:
        return None
    walked = sum(r["kv_cols_walked"] for r in rows)
    return 100.0 * (walked - sum(r["kv_cols_live"] for r in rows)) / walked
