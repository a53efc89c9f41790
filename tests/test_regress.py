"""regress + benchwatch — the bench-regression sentinel.

Covers the ISSUE-18 sentinel surface: config signatures keep apples
with apples, direction inference, the median+MAD verdict math (noise
absorption, the zero-MAD relative floor, warm-up exclusion, dead-round
``no_value``), history ingestion across all three committed file
shapes, the acceptance replay (a seeded slowdown is flagged; an
unchanged rerun of the committed history produces zero false
verdicts), stamp_line/recent_verdicts, and the benchwatch CLI's exit
codes.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.telemetry import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    regress.reset()
    yield
    regress.reset()


def _line(value, metric="decode tokens/s", unit="tok/s", extra=None,
          error=None):
    doc = {"metric": metric, "value": value, "unit": unit}
    if extra is not None:
        doc["extra"] = extra
    if error is not None:
        doc["error"] = error
    return doc


def _seed(store, values, **kw):
    for v in values:
        store.add(_line(v, **kw))


# ---------------------------------------------------------------------------
# keys and direction
# ---------------------------------------------------------------------------

def test_config_signature_ignores_measurements_keeps_config():
    a = _line(100.0, extra={"batch": 8, "infer_img_s": 52.9})
    b = _line(900.0, extra={"batch": 8, "infer_img_s": 11.1})
    assert regress.config_signature(a) == regress.config_signature(b)
    c = _line(100.0, extra={"batch": 16, "infer_img_s": 52.9})
    assert regress.config_signature(a) != regress.config_signature(c)
    # unit/metric are part of the key too
    assert regress.config_signature(_line(1, unit="ms")) != \
        regress.config_signature(_line(1, unit="tok/s"))


def test_direction_inference():
    assert regress.direction(_line(1, unit="tok/s")) == "higher"
    assert regress.direction(_line(1, unit="img/s")) == "higher"
    assert regress.direction(_line(1, unit="ms")) == "lower"
    assert regress.direction(_line(1, unit="seconds")) == "lower"
    assert regress.direction(
        _line(1, metric="decode p99 latency", unit="x")) == "lower"
    assert regress.direction(
        _line(1, metric="devprof overhead", unit="frac")) == "lower"


# ---------------------------------------------------------------------------
# verdict math
# ---------------------------------------------------------------------------

def test_insufficient_history_never_confirms():
    store = regress.TrajectoryStore()
    v = store.verdict(_line(100.0))
    assert v["verdict"] == "no_history" and not v["confirmed"]
    _seed(store, [100.0, 101.0])
    v = store.verdict(_line(1.0))  # a 99% drop — but only 2 points
    assert v["verdict"] == "insufficient_history" and not v["confirmed"]


def test_regression_beyond_noise_confirms():
    store = regress.TrajectoryStore()
    _seed(store, [100.0, 102.0, 98.0, 101.0, 99.0])
    v = store.verdict(_line(80.0))  # 20% down, noise is ~1.5
    assert v["verdict"] == "regression" and v["confirmed"]
    assert v["direction"] == "higher" and v["delta"] < 0
    # same magnitude UP is an improvement, not a regression
    v = store.verdict(_line(120.0))
    assert v["verdict"] == "improvement" and not v["confirmed"]


def test_latency_regresses_upward():
    store = regress.TrajectoryStore()
    _seed(store, [10.0, 10.2, 9.8, 10.1], metric="decode p50", unit="ms")
    v = store.verdict(_line(14.0, metric="decode p50", unit="ms"))
    assert v["verdict"] == "regression" and v["confirmed"]
    v = store.verdict(_line(7.0, metric="decode p50", unit="ms"))
    assert v["verdict"] == "improvement"


def test_zero_mad_history_uses_relative_floor():
    # identical repeated values: MAD = 0, so the sigma term is 0 — the
    # 5% relative floor must keep a 1% wobble from flagging
    store = regress.TrajectoryStore()
    _seed(store, [100.0, 100.0, 100.0, 100.0])
    assert store.verdict(_line(99.0))["verdict"] == "ok"
    assert store.verdict(_line(94.0))["verdict"] == "regression"


def test_noise_absorption_within_sigma():
    store = regress.TrajectoryStore()
    _seed(store, [100.0, 110.0, 90.0, 105.0, 95.0])  # MAD 5 -> sigma ~7.4
    assert store.verdict(_line(85.0))["verdict"] == "ok"  # within 4 sigma


def test_warmup_points_are_not_history():
    store = regress.TrajectoryStore()
    for _ in range(5):
        store.add(_line(10.0, extra={"warmup": True}))
    key = store.key(_line(10.0, extra={"warmup": True}))
    assert store.history(key) == []
    # explicit flag works too
    store.add(_line(10.0), warmup=True)
    assert store.history(store.key(_line(10.0))) == []


def test_dead_round_is_no_value_with_error():
    store = regress.TrajectoryStore()
    _seed(store, [100.0, 101.0, 99.0])
    v = store.verdict(_line(None, error="backend init timed out"))
    assert v["verdict"] == "no_value" and not v["confirmed"]
    assert "backend init" in v["error"]
    # and the null point never pollutes history
    store.add(_line(None, error="backend init timed out"))
    assert store.history(store.key(_line(1.0))) == [100.0, 101.0, 99.0]


def test_history_is_bounded():
    store = regress.TrajectoryStore(max_points=4)
    _seed(store, [float(i) for i in range(10)])
    assert store.history(store.key(_line(1.0))) == [6.0, 7.0, 8.0, 9.0]


# ---------------------------------------------------------------------------
# ingestion: the three committed shapes
# ---------------------------------------------------------------------------

def test_iter_bench_lines_raw_wrapper_jsonl(tmp_path):
    raw = tmp_path / "raw.json"
    raw.write_text(json.dumps(_line(15.31, metric="resnet quick")))
    wrapper = tmp_path / "wrap.json"
    wrapper.write_text(json.dumps(
        {"n": 4, "rc": 1, "parsed": _line(52.63, metric="resnet train"),
         "tail": "noise"}))
    dead = tmp_path / "dead.json"
    dead.write_text(json.dumps(
        {"n": 5, "rc": 1, "parsed": None,
         "tail": "Traceback...\n" + json.dumps(_line(9.9, metric="embedded"))
         + "\nmore noise"}))
    jsonl = tmp_path / "emit.jsonl"
    jsonl.write_text(json.dumps(_line(1.0, metric="a")) + "\n"
                     + "not json\n"
                     + json.dumps(_line(2.0, metric="b")) + "\n")
    got = {m["metric"]: m for p in (raw, wrapper, dead, jsonl)
           for m in regress.iter_bench_lines(str(p))}
    assert set(got) == {"resnet quick", "resnet train", "embedded",
                        "a", "b"}


def test_iter_bench_lines_snapshot_rows(tmp_path):
    snap = {"ts": 1.0, "enabled": True, "metrics": {
        "mxnet_device_time_ms": {"type": "histogram", "series": [
            {"labels": {"site": "serving.decode_step"},
             "p50": 1.25, "p99": 3.0, "sum": 10.0, "count": 8}]},
        "mxnet_tokens_per_device_second": {"type": "gauge", "series": [
            {"labels": {"server": "srv"}, "value": 5000.0}]}}}
    p = tmp_path / "telemetry.jsonl"
    p.write_text(json.dumps(snap) + "\n")
    rows = list(regress.iter_bench_lines(str(p)))
    mets = {r["metric"]: r["value"] for r in rows}
    assert mets["devprof p50 device ms [serving.decode_step]"] == 1.25
    assert mets["devprof tokens/device-s [srv]"] == 5000.0


def test_iter_bench_lines_never_raises(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{truncated")
    assert list(regress.iter_bench_lines(str(bad))) == []
    assert list(regress.iter_bench_lines(str(tmp_path / "missing"))) == []


def test_default_paths_round_order(tmp_path, monkeypatch):
    for name in ("BENCH_r02.json", "BENCH_r01.json", "BENCH_CPU.json",
                 "BENCH_r10.json"):
        (tmp_path / name).write_text("{}")
    monkeypatch.delenv("MXNET_TELEMETRY_EMIT_PATH", raising=False)
    got = [os.path.basename(p) for p in regress.default_paths(str(tmp_path))]
    assert got == ["BENCH_CPU.json", "BENCH_r01.json", "BENCH_r02.json",
                   "BENCH_r10.json"]


# ---------------------------------------------------------------------------
# the acceptance replay: seeded slowdown flagged, unchanged rerun clean
# ---------------------------------------------------------------------------

def _committed_history(tmp_path, values, seed_last=None):
    """A BENCH_r* sequence shaped like the repo's committed files."""
    paths = []
    vals = list(values) + ([seed_last] if seed_last is not None else [])
    for i, v in enumerate(vals, 1):
        p = tmp_path / ("BENCH_r%02d.json" % i)
        p.write_text(json.dumps(_line(v, extra={"batch": 8})))
        paths.append(str(p))
    return paths


def test_replay_flags_seeded_slowdown_only(tmp_path):
    clean = [5400.0, 5450.0, 5380.0, 5420.0]
    paths = _committed_history(tmp_path, clean, seed_last=4000.0)
    store = regress.TrajectoryStore()
    verdicts = []
    for p in paths:
        for line in regress.iter_bench_lines(p):
            verdicts.append(store.verdict(line))
            store.add(line, source=os.path.basename(p))
    # exactly ONE confirmed verdict: the seeded 26% slowdown at the end
    confirmed = [v for v in verdicts if v["confirmed"]]
    assert len(confirmed) == 1
    assert confirmed[0] is verdicts[-1]
    assert confirmed[0]["verdict"] == "regression"


def test_replay_unchanged_rerun_zero_false_positives(tmp_path):
    paths = _committed_history(tmp_path, [5400.0, 5450.0, 5380.0, 5420.0])
    store = regress.build_store(paths)
    # rerunning the same workload at the same speed: always ok
    for v in (5400.0, 5450.0, 5380.0, 5420.0):
        verdict = store.verdict(_line(v, extra={"batch": 8}))
        assert verdict["verdict"] == "ok", verdict
        assert not verdict["confirmed"]


def test_repo_shaped_history_replays_with_zero_false_verdicts(tmp_path):
    # a trail shaped like the repo's own early rounds, written here (the
    # committed dead rounds left the tree in PR 22): an empty driver
    # wrapper, a wrapper whose tail carries a dead bench line, a dead raw
    # line, then ONE live point. Dead rounds are no_value (their error is
    # the signal), nothing is ever a confirmed regression.
    metric = "resnet50_v1 train img/s (bs=32 fp32, fused step, 1 chip)"
    dead = _line(None, metric=metric, unit="img/s",
                 error="backend-init failure (infrastructure): timed out")
    docs = [
        {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
         "parsed": None},
        {"n": 2, "cmd": "python bench.py", "rc": 1, "parsed": None,
         "tail": "WARNING: noise\n" + json.dumps(dead) + "\n"},
        dead,
        _line(52.63, metric=metric, unit="img/s", extra={"batch": 32}),
    ]
    for i, doc in enumerate(docs, 1):
        (tmp_path / ("BENCH_r%02d.json" % i)).write_text(json.dumps(doc))
    paths = regress.default_paths(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == \
        ["BENCH_r%02d.json" % i for i in range(1, 5)]
    store = regress.TrajectoryStore()
    verdicts = []
    for path in paths:
        for line in regress.iter_bench_lines(path):
            v = store.verdict(line)
            assert not v["confirmed"], (path, v)
            verdicts.append(v["verdict"])
            store.add(line, source=os.path.basename(path))
    assert verdicts == ["no_value", "no_value", "no_history"]
    assert store.keys(), "history produced no trajectories"


def test_config_change_starts_new_trajectory_not_regression():
    store = regress.TrajectoryStore()
    _seed(store, [100.0, 101.0, 99.0], extra={"batch": 32})
    # same metric at batch 4 is 10x slower — a different config, not a
    # regression of the batch-32 trajectory
    v = store.verdict(_line(10.0, extra={"batch": 4}))
    assert v["verdict"] == "no_history" and not v["confirmed"]


# ---------------------------------------------------------------------------
# stamp_line / recent verdicts
# ---------------------------------------------------------------------------

def test_stamp_line_verdicts_then_absorbs():
    store = regress.TrajectoryStore()
    for v in (100.0, 101.0, 99.0):
        regress.stamp_line(_line(v), store=store)
    verdict = regress.stamp_line(_line(50.0), store=store)
    assert verdict["confirmed"] and verdict["verdict"] == "regression"
    recents = regress.recent_verdicts()
    assert len(recents) == 4
    assert recents[-1] is verdict
    # the regressed point is IN history now (next identical run is ok
    # against the median, not double-flagged forever)
    assert 50.0 in store.history(store.key(_line(50.0)))


# ---------------------------------------------------------------------------
# benchwatch CLI
# ---------------------------------------------------------------------------

def _benchwatch(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "benchwatch.py")]
        + list(argv), capture_output=True, text=True, cwd=cwd, timeout=120)


def test_benchwatch_committed_history_is_clean():
    res = _benchwatch()
    assert res.returncode == 0, res.stdout + res.stderr
    assert "no confirmed regressions at head" in res.stdout


def test_benchwatch_flags_seeded_slowdown(tmp_path):
    paths = _committed_history(
        tmp_path, [5400.0, 5450.0, 5380.0, 5420.0], seed_last=4000.0)
    res = _benchwatch(*paths)
    assert res.returncode == 9, res.stdout + res.stderr
    assert "CONFIRMED REGRESSION" in res.stdout
    res = _benchwatch("--json", *paths)
    assert res.returncode == 9
    doc = json.loads(res.stdout)
    assert doc["rc"] == 9 and len(doc["regressions_at_head"]) == 1


def test_benchwatch_recovered_head_is_clean(tmp_path):
    # a mid-history regression that later recovered: the rc gate judges
    # only the trajectory head, so the tree is clean today
    paths = _committed_history(
        tmp_path, [5400.0, 5450.0, 5380.0, 5420.0, 4000.0, 5410.0])
    res = _benchwatch(*paths)
    assert res.returncode == 0, res.stdout + res.stderr


def test_benchwatch_line_judged_against_history(tmp_path):
    hist = _committed_history(tmp_path, [5400.0, 5450.0, 5380.0, 5420.0])
    cand = tmp_path / "candidate.json"
    cand.write_text(json.dumps(_line(4000.0, extra={"batch": 8})))
    res = _benchwatch(*hist, "--line", str(cand), "--json")
    assert res.returncode == 9
    doc = json.loads(res.stdout)
    assert doc["verdicts"][-1]["source"] == "candidate.json"
    assert doc["verdicts"][-1]["confirmed"]


def test_benchwatch_usage_error_on_missing_file():
    res = _benchwatch("/nonexistent/history.json")
    assert res.returncode == 2
