"""tpulint command line: ``python -m tools.tpulint [paths...]``.

Exit codes: 0 clean (or baseline written), 1 new findings, 2 usage error.
"""
from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

from .cache import DEFAULT_CACHE_PATH, LintCache, baseline_sig
from .core import (DEFAULT_BASELINE, DEFAULT_ROOTS, REPO_ROOT, Finding,
                   all_passes, apply_baseline, baseline_counts, collect_files,
                   key_scope, lint_files, load_baseline, load_justifications,
                   relpath_of, write_baseline_counts)
from .reporters import render_json, render_stats, render_text


def changed_files(root: Path = REPO_ROOT) -> Optional[List[str]]:
    """Paths (repo-relative) touched in the working tree vs HEAD, plus
    untracked files — the quick local pre-push scope. None when git fails:
    a broken git must fail the gate loudly, not pass it as 'no changes'."""
    out: List[str] = []
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            proc = subprocess.run(args, cwd=str(root), capture_output=True,
                                  text=True, timeout=30, check=True)
        except (OSError, subprocess.SubprocessError):
            return None
        out.extend(line.strip() for line in proc.stdout.splitlines() if line.strip())
    return sorted(set(out))


def filter_to_scope(changed: Sequence[str], scope: Sequence[Path],
                    root: Path = REPO_ROOT) -> List[Path]:
    """Intersect changed paths with the already-collected lint scope."""
    wanted = {str((root / c).resolve()) for c in changed if c.endswith(".py")}
    return [p for p in scope if str(p.resolve()) in wanted]


def lint_paths(paths: Sequence[str], baseline_path: Optional[Path] = DEFAULT_BASELINE,
               passes: Optional[Sequence[str]] = None, cache: bool = True,
               ) -> Tuple[List[Finding], List[Finding]]:
    """Lint `paths`; returns ``(new_findings, all_findings)`` where *new*
    means not covered by the baseline (all of them when ``baseline_path``
    is None). ``cache=True`` (default) shares the CLI's incremental
    cache — keyed by the baseline content like every other entry point —
    so programmatic callers (the tier-1 gate test) pay ~20ms warm instead
    of a cold whole-program run."""
    files = collect_files(paths)
    lc = LintCache(DEFAULT_CACHE_PATH,
                   extra_sig=baseline_sig(baseline_path)) if cache else None
    findings = lint_files(files, passes=passes, cache=lc)
    baseline = load_baseline(baseline_path) if baseline_path else {}
    return apply_baseline(findings, baseline), findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.tpulint",
        description="AST-based TPU-correctness linter for mxnet_tpu.")
    parser.add_argument("paths", nargs="*", default=list(DEFAULT_ROOTS),
                        help="files or directories to lint (default: %s)"
                             % " ".join(DEFAULT_ROOTS))
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--baseline", type=Path, default=DEFAULT_BASELINE,
                        help="baseline file (default: tools/tpulint/baseline.json)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding, ignoring the baseline")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write all current findings to the baseline and exit 0")
    parser.add_argument("--changed-only", action="store_true",
                        help="lint only files changed vs HEAD (git diff + untracked)")
    parser.add_argument("--select", default=None, metavar="RULES",
                        help="comma-separated subset of rules to run")
    parser.add_argument("--list-rules", action="store_true",
                        help="list available rules and exit")
    parser.add_argument("--stats", action="store_true",
                        help="print per-pass timing and cache hit rate")
    parser.add_argument("--cache", type=Path, default=DEFAULT_CACHE_PATH,
                        metavar="PATH",
                        help="incremental cache file (default: "
                             ".tpulint-cache.json at the repo root)")
    parser.add_argument("--no-cache", action="store_true",
                        help="run every pass from scratch, don't touch the cache")
    args = parser.parse_args(argv)

    registry = all_passes()
    if args.list_rules:
        for name in sorted(registry):
            print("%-14s %s" % (name, registry[name].description))
        return 0

    passes = None
    if args.select:
        passes = [r.strip() for r in args.select.split(",") if r.strip()]
        unknown = [r for r in passes if r not in registry]
        if unknown:
            print("tpulint: unknown rule(s): %s (try --list-rules)"
                  % ", ".join(unknown), file=sys.stderr)
            return 2

    # an explicit path that matches nothing is a usage error, not a clean run
    missing = [p for p in args.paths
               if not (Path(p) if Path(p).is_absolute() else REPO_ROOT / p).exists()]
    if missing:
        print("tpulint: path(s) do not exist: %s" % ", ".join(missing),
              file=sys.stderr)
        return 2

    files = collect_files(args.paths)
    project_scope = None
    if args.changed_only:
        changed = changed_files()
        if changed is None:
            print("tpulint: --changed-only requires a working `git diff`; "
                  "run on explicit paths instead", file=sys.stderr)
            return 2
        # report only on changed files, but keep the WHOLE collected
        # scope as graph context: a traced/thread seed in an unchanged
        # file must still reach a hazard in a changed one
        project_scope = files
        files = filter_to_scope(changed, files)
        if not files:
            print("tpulint: no changed files in scope")
            return 0

    import time

    t0 = time.perf_counter()
    # the cache is keyed by the baseline CONTENT: editing the baseline
    # invalidates cached pass results, so a warm run re-runs and
    # re-reports instead of serving results computed in the old world
    cache = None if args.no_cache else LintCache(
        args.cache, extra_sig=baseline_sig(
            None if args.no_baseline else args.baseline))
    stats: dict = {}
    findings = lint_files(files, passes=passes, cache=cache, stats=stats,
                          project_scope=project_scope)
    stats["total_ms"] = round((time.perf_counter() - t0) * 1000, 1)

    def emit_stats():
        if args.stats:
            # stderr: --format json consumers must keep a parseable stdout
            print(render_stats(stats), file=sys.stderr)

    counts = baseline_counts(findings)
    # Scope actually covered by this run: baseline keys outside it (files
    # not linted, rules not selected) carry no evidence either way.
    linted = {relpath_of(p) for p in files}
    ran_rules = set(passes) if passes is not None else set(registry)

    def in_scope(key: str) -> bool:
        path, rule = key_scope(key)
        return path in linted and rule in ran_rules

    if args.write_baseline:
        merged = dict(counts)
        for k, v in load_baseline(args.baseline).items():
            if not in_scope(k):  # narrowed run must not drop other entries
                merged[k] = v
        # keep each surviving entry's one-line justification
        write_baseline_counts(merged, args.baseline,
                              justifications=load_justifications(args.baseline))
        if cache is not None:
            # the cache on disk is keyed by the PRE-write baseline: re-key
            # to the baseline just written so the next run starts warm
            cache.rekey(baseline_sig(args.baseline))
            cache.save(root=REPO_ROOT)
        print("tpulint: wrote %d finding(s) to %s (%d kept from outside this "
              "run's scope)" % (sum(merged.values()), args.baseline,
                               sum(merged.values()) - len(findings)))
        emit_stats()
        return 0

    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    new = apply_baseline(findings, baseline)
    stale = [k for k in baseline if in_scope(k) and counts.get(k, 0) < baseline[k]]

    render = render_json if args.format == "json" else render_text
    print(render(new, len(findings), len(findings) - len(new), stale))
    emit_stats()
    return 1 if new else 0


if __name__ == "__main__":  # pragma: no cover - exercised via __main__.py
    sys.exit(main())
