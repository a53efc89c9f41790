"""The comparison that decides ``correct``.

Each function returns rows ``{"what", "value", "limit", "ok", ...}``: one
number compared beside its limit; a run prints every row. The limits are data
(the configuration file's ``limits``), set from readings ``PERF.md`` records.
"""
from __future__ import annotations

import statistics


def _row(what, value, limit, **more):
    return dict(what=what, value=float(value), limit=float(limit),
                ok=bool(value <= limit), **more)


def worst_leaf_gap(got, want):
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some gradients are all but
    zero). Returns ``(gap, leaf)``."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for name, ref in want.items():
        gap = abs(got[name] - ref) / max(ref, floor)
        if where is None or gap > worst:
            worst, where = gap, name
    return worst, where


def leaf_gap_summary(got, want):
    """Other views of the same per-leaf norms, printed beside the compared
    rows so that a limit can be re-derived from any run's output."""
    floor = statistics.median(want.values())
    gaps = sorted(abs(got[k] - v) / max(v, floor) for k, v in want.items())
    tot_g = sum(v * v for v in got.values()) ** 0.5
    tot_w = sum(v * v for v in want.values()) ** 0.5
    return {"median_leaf": gaps[len(gaps) // 2],
            "p90_leaf": gaps[(len(gaps) * 9) // 10],
            "worst_leaf": gaps[-1],
            "global_norm": abs(tot_g - tot_w) / tot_w}


def train_rows(got, want, limits):
    """Training: each of the first steps' losses, the first gradient's norm
    and the parameters' change over those steps, both by the worst leaf."""
    rows = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        rows.append(_row("loss_step%d_rel_gap" % i, abs(a - b) / abs(b),
                         limits["loss_rel"], program=a, reference=b))
    gap, leaf = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    rows.append(_row("first_grad_norm_worst_leaf_rel_gap", gap,
                     limits["grad_norm_rel"], leaf=leaf,
                     program=got["grad_norms"][leaf],
                     reference=want["grad_norms"][leaf]))
    gap, leaf = worst_leaf_gap(got["delta_norms"], want["delta_norms"])
    rows.append(_row("param_change_norm_worst_leaf_rel_gap", gap,
                     limits["delta_norm_rel"], leaf=leaf,
                     program=got["delta_norms"][leaf],
                     reference=want["delta_norms"][leaf]))
    return rows


def served_rows(gaps, limits, tokens):
    """Serving: the widest gap by which a served token's reference logit
    lies below the reference's best (in that row's logit standard
    deviations), over all compared tokens."""
    return [_row("served_token_widest_logit_gap_sd", max(gaps),
                 limits["served_logit_gap_sd"], tokens=int(tokens))]
