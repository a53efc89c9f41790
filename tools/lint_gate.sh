#!/usr/bin/env bash
# tools/lint_gate.sh — the pre-commit/CI tpulint gate.
#
# Runs the whole-program linter twice (a cold-or-warm pass that fills the
# incremental cache, then a fully-warm pass), enforces the tier-1 time
# contract on each (cold < LINT_GATE_COLD_S, warm < LINT_GATE_WARM_S),
# and checks the JSON output for non-baselined findings. The scope runs
# every registered pass, including the v4 concurrency/lifecycle set
# (lock-order-cycle, blocking-under-lock, cv-protocol,
# resource-lifecycle) — their shared LockAnalysis dominates the cold
# run (~19s measured vs the 30s gate); warm runs stay cache-only
# (~0.2s). Exit codes:
#   0  clean and inside the time gates
#   1  new (non-baselined) findings — fix, suppress, or --write-baseline
#   2  usage/environment error (python or repo missing)
#   3  time gate exceeded (the cache or a pass regressed)
#
# Wire into pre-commit with:
#   ln -s ../../tools/lint_gate.sh .git/hooks/pre-commit
set -u -o pipefail

# resolve symlinks (the documented `ln -s .../lint_gate.sh
# .git/hooks/pre-commit` wiring) before deriving the repo root, or the
# hook would root itself inside .git/ and fail every commit
SELF="$(readlink -f "${BASH_SOURCE[0]}" 2>/dev/null || echo "${BASH_SOURCE[0]}")"
REPO_ROOT="$(cd "$(dirname "$SELF")/.." && pwd -P)"
PY="${PYTHON:-python3}"
COLD_GATE="${LINT_GATE_COLD_S:-30}"
WARM_GATE="${LINT_GATE_WARM_S:-5}"
SCOPE=("mxnet_tpu" "tools")
OUT="$(mktemp)"
trap 'rm -f "$OUT" "$OUT.stats"' EXIT

command -v "$PY" >/dev/null 2>&1 || { echo "lint_gate: no $PY" >&2; exit 2; }
cd "$REPO_ROOT" || exit 2

ELAPSED=""
run_lint() { # $1 = phase label; sets $ELAPSED (seconds). NOT called in a
             # subshell — a broken run must exit the GATE with rc 2, and
             # `exit` inside $(...) would only kill the substitution.
    local t0 t1 rc
    t0=$(date +%s.%N)
    "$PY" -m tools.tpulint "${SCOPE[@]}" --format json --stats >"$OUT" 2>"$OUT.stats"
    rc=$?
    t1=$(date +%s.%N)
    # rc 1 = findings (checked from the JSON below); rc >= 2 = broken run
    if [ "$rc" -ge 2 ]; then
        echo "lint_gate: $1 run failed (rc=$rc)" >&2
        cat "$OUT" "$OUT.stats" >&2
        exit 2
    fi
    ELAPSED=$(echo "$t0 $t1" | awk '{printf "%.1f", $2 - $1}')
}

check_findings() { # $1 = phase label; rc 0 clean, 1 findings, 2 bad output
    "$PY" - "$OUT" "$1" <<'PYEOF'
import json, sys
try:
    payload = json.load(open(sys.argv[1]))
except (OSError, ValueError) as exc:
    # polluted/unparseable linter stdout is a BROKEN TOOL, not findings
    print("lint_gate: unparseable linter output (%s run): %s"
          % (sys.argv[2], exc), file=sys.stderr)
    sys.exit(2)
new = payload.get("new", [])
if new:
    print("lint_gate: %d new finding(s) [%s run]:" % (len(new), sys.argv[2]),
          file=sys.stderr)
    for f in new:
        print("  %s:%s: [%s] %s" % (f["path"], f["line"], f["rule"],
                                    f["message"]), file=sys.stderr)
    sys.exit(1)
PYEOF
}

check_time() { # $1 = elapsed, $2 = gate, $3 = label
    awk -v t="$1" -v g="$2" 'BEGIN { exit !(t < g) }' || {
        echo "lint_gate: $3 run took ${1}s (gate: <${2}s) — the incremental" \
             "cache or a pass regressed" >&2
        exit 3
    }
}

gate_phase() { # $1 = label, $2 = time gate
    run_lint "$1"
    local elapsed="$ELAPSED" rc=0
    check_findings "$1" || rc=$?
    [ "$rc" -eq 1 ] && exit 1
    [ "$rc" -ge 2 ] && exit 2
    check_time "$elapsed" "$2" "$1"
    LAST_ELAPSED="$elapsed"
}

gate_phase cold "$COLD_GATE"
cold_s="$LAST_ELAPSED"
gate_phase warm "$WARM_GATE"
warm_s="$LAST_ELAPSED"

echo "lint_gate: clean (cold ${cold_s}s < ${COLD_GATE}s, warm ${warm_s}s < ${WARM_GATE}s)"
exit 0
