"""What the driver touches must never rot: ``python3 benchmark/run.py``
(``BENCHMARK.json``'s command, one JSON object as its last line),
``__graft_entry__.entry()`` (jittable forward) and ``dryrun_multichip``
(full SPMD step over a virtual mesh), each in a subprocess the way it is
invoked — and, in tier-1, what keeps the documents beside them true: the
environment-variable table against the code, the span histograms a live
server reads on ``/metrics``, and nothing pointing at a measuring system
that is gone."""
import functools
import json
import re
import subprocess
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

from tests.conftest import subprocess_env as _env


def _manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["resnet50_b128_train_1chip",
                                  "decoder_opt1p3b_chat"])
def test_benchmark_rehearses_a_cell(cell):
    """The benchmark's whole control flow for one train and one decode
    cell at the tiny preset on the CPU: it runs to its end, the exit code
    says what `correct` says, and the last line parses and names the
    cell's end-to-end metrics."""
    out = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"),
         "--workload", cell, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=_env(),
        cwd=str(REPO))
    tail = out.stdout[-2000:] + out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    assert lines, tail
    result = lines[-1]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result, result
    assert out.returncode == (0 if result["correct"] else 1), tail
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    # never a rate or a time under a metric's name from a CPU run
    assert result["metrics"] == {}
    want = {m["name"] for m in _manifest()["end_to_end"]
            if cell in m.get("workloads", [cell])}
    (rehearsed,) = [l for l in lines if l.get("phase") == "rehearsed"]
    assert want <= set(rehearsed["end_to_end_reported"])
    compared = {l["what"]: l["ok"] for l in lines
                if l.get("phase") == "compared"}
    if "train" in cell:
        # the tiny preset's third loss misses its limit, at the parent of
        # PR 29 too (ROADMAP W2 has it); what the program owes holds
        assert compared["train_recompiles"]
        assert compared["one_graph_dispatch_per_step"]
    else:
        assert result["correct"], tail


@pytest.mark.slow
def test_graft_entry_compiles():
    src = ("import __graft_entry__ as g, jax; fn, args = g.entry(); "
           "out = jax.jit(fn)(*args); jax.block_until_ready(out); "
           "print('ENTRY_OK', out.shape)")
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=600, env=_env(), cwd=str(REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ENTRY_OK" in out.stdout


@pytest.mark.slow
def test_dryrun_multichip_eight_devices():
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=900,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=8"),
        cwd=str(REPO))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for marker in ("all_reduce OK", "TrainStep parity OK",
                   "kvstore=tpu push/pull OK", "ring-attention(sp) OK",
                   "tp(mp-sharded matmul) OK", "pp(GPipe ppermute) OK",
                   "ep(expert-sharded einsum) OK"):
        assert marker in out.stdout, out.stdout[-1500:]


# ---------------------------------------------------------------------------
# tier-1: the documents beside the benchmark stay true
# ---------------------------------------------------------------------------

_ENV_SOURCES = ("mxnet_tpu", "tools", "benchmark", "src")
_ENV_SUFFIXES = (".py", ".sh", ".cc", ".h")


def _documented_env_rows():
    doc = (REPO / "docs" / "env_var.md").read_text()
    return re.findall(r"^\| `([A-Z][A-Z0-9_]+)`", doc, re.M)


def _source_text(roots):
    files = [p for d in roots for p in sorted((REPO / d).rglob("*"))
             if p.suffix in _ENV_SUFFIXES]
    return "\n".join(p.read_text(errors="ignore") for p in files)


@pytest.mark.parametrize("direction", ["documented_is_read",
                                       "read_is_documented"])
def test_env_var_table_matches_the_code(direction):
    rows = _documented_env_rows()
    assert len(rows) == len(set(rows)), "a variable has two rows"
    if direction == "documented_is_read":
        text = _source_text(_ENV_SOURCES) \
            + (REPO / "chip_smoke.py").read_text()
        # read (get_env / getenv / environ / a shell expansion) or handed
        # to a child's environment by a tool
        forms = (r'get_env\(\s*["\']%s["\']', r'getenv\(\s*"%s"',
                 r'environ[^\n]*["\']%s["\']', r"\$\{?%s\b", r"\b%s=")
        unread = [r for r in rows
                  if not any(re.search(f % r, text) for f in forms)]
        assert unread == [], "docs/env_var.md rows nothing reads: %s" % unread
    else:
        code = _source_text(("mxnet_tpu",))
        read = set(re.findall(r'get_env\(\s*["\'](MXNET_[A-Z0-9_]+)["\']',
                              code))
        missing = sorted(read - set(rows))
        assert missing == [], "get_env names with no row: %s" % missing


_GONE = {
    "the bench script and its modes": r"(?<![A-Za-z_])bench\.py|BENCH_[A-Z]|"
                                      r"MXNET_BENCH_",
    "the regression sentinel": r"benchwatch|telemetry\.regress|"
                               r"MXNET_REGRESS_|perf_verdict",
    "the sampling plane": r"devprof|_DEVPROF_HOOK|MXNET_DEVPROF_|"
                          r"/debug/perf",
    "records from before the chip": r"BENCH_r0\d|BENCH_TPU_PARTIAL|"
                                    r"MULTICHIP_r0\d",
}
# the records of what was done: they name what went, by design
_HISTORY = {"CHANGES.md", "ISSUE.md", "PERF.md", "ROADMAP.md",
            "PERF_LEDGER.jsonl", "PROGRESS.jsonl", "SURVEY.md", "PAPER.md",
            "PAPERS.md", "SNIPPETS.md", "BASELINE.md", "REVIEW.md"}


@functools.lru_cache(maxsize=None)
def _tracked_documents_and_sources():
    """(path, lines) of every text file git would commit that is not a
    record of what was done. Outside a git checkout: every such file
    that is not under a directory `.gitignore` names."""
    listed = subprocess.run(["git", "ls-files"], capture_output=True,
                            text=True, cwd=str(REPO))
    if listed.returncode == 0 and listed.stdout.strip():
        rels = listed.stdout.splitlines()
    else:
        ignored = {".git"} | {
            l.strip().rstrip("/")
            for l in (REPO / ".gitignore").read_text().splitlines()
            if l.strip().endswith("/")}
        rels = [str(p.relative_to(REPO)) for p in sorted(REPO.rglob("*"))
                if not ignored & set(p.relative_to(REPO).parts)]
    out = []
    for rel in rels:
        path = REPO / rel
        if (rel in _HISTORY or rel == "tests/test_driver_artifacts.py"
                or path.suffix not in (".py", ".md", ".sh", ".json", ".ini")
                or not path.is_file()):
            continue
        out.append((rel, path.read_text(errors="ignore").splitlines()))
    return out


@pytest.mark.parametrize("what", sorted(_GONE))
def test_nothing_points_at_a_measuring_system_that_is_gone(what):
    """One benchmark (benchmark/run.py) and one clock (the trace + mx.*
    spans): no source file, document or script still sends a reader to
    what was deleted."""
    pat = re.compile(_GONE[what])
    hits = ["%s:%d: %s" % (rel, n, line.strip()[:80])
            for rel, lines in _tracked_documents_and_sources()
            for n, line in enumerate(lines, 1) if pat.search(line)]
    assert hits == [], "\n".join(hits[:20])


def _gluon_plane(monkeypatch, hybridize):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, trainplane
    from mxnet_tpu.gluon import nn

    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    rs = np.random.RandomState(11)
    xs = rs.rand(8, 6).astype(np.float32)
    ys = rs.randint(0, 8, (8,))
    net = (nn.HybridSequential if hybridize else nn.Sequential)(
        prefix="da%d_" % hybridize)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(8))
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs))
    if hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  trainer)
    for _ in range(3):
        plane.step(nd.array(xs), nd.array(ys))
    return plane


def _train_plane(monkeypatch):
    assert _gluon_plane(monkeypatch, hybridize=True).plane == "graph"
    return "trainplane", ["train.step", "train.dispatch", "train.prologue",
                          "train.commit"]


def _eager_plane(monkeypatch):
    # a plain Block cannot be traced into one program: the plane steps it
    # eagerly and the step span is all there is
    assert _gluon_plane(monkeypatch, hybridize=False).plane == "eager"
    return "trainplane", ["train.step"]


def _module_plane(monkeypatch):
    import mxnet_tpu as mx
    from mxnet_tpu import io as io_mod
    from mxnet_tpu import trainplane
    from mxnet_tpu.module import Module

    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    rs = np.random.RandomState(13)
    xs = rs.rand(24, 6).astype(np.float32)
    ys = rs.randint(0, 4, (24,)).astype(np.float32)
    fc = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4, name="fc")
    it = io_mod.NDArrayIter(xs, ys, batch_size=8)
    mod = Module(mx.sym.SoftmaxOutput(fc, name="softmax"), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    plane = trainplane.module_plane(mod)
    for batch in it:
        plane.step(batch)
    return "trainplane", ["train.step", "train.dispatch", "train.shard"]


def _decode_plane(monkeypatch):
    from mxnet_tpu import serving

    model = serving.TinyDecoder(vocab_size=32, num_layers=1, num_heads=2,
                                head_dim=8)
    with serving.DecodeEngine(model, model.init_params(0), num_slots=2,
                              max_seq_len=32, prefill_buckets=(8,),
                              timeout_ms=0, prefix_cache=False,
                              name="da_spans") as eng:
        for f in [eng.submit([1 + i, 2, 3], 4) for i in range(3)]:
            f.result(timeout=120)
    return "serving", ["decode.tick", "decode.dispatch", "decode.fetch",
                       "decode.prefill"]


@pytest.mark.parametrize("drive", [_train_plane, _eager_plane,
                                   _module_plane, _decode_plane],
                         ids=["trainplane", "eager", "module", "decode"])
def test_span_histograms_are_on_metrics_with_no_trace_live(drive,
                                                           monkeypatch):
    """What a live server reads where no profiler trace is running: after
    a few steps or ticks the plane's spans are series of
    ``mxnet_span_duration_ms`` on ``/metrics``."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import spans as spans_mod

    assert not spans_mod._trace_live()
    category, names = drive(monkeypatch)
    server = telemetry.start_httpd(port=0)
    try:
        url = "http://127.0.0.1:%d/metrics" % server.server_address[1]
        text = urllib.request.urlopen(url, timeout=30).read().decode()
    finally:
        telemetry.stop_httpd()
    for name in names:
        series = 'mxnet_span_duration_ms_count{category="%s",span="%s"}' \
            % (category, name)
        (line,) = [l for l in text.splitlines() if l.startswith(series)]
        assert float(line.split()[-1]) >= 1, line


def test_debug_endpoint_lists_the_views_that_are_there():
    """``/debug/<view>`` serves what an upper layer registered; the
    sampling plane's ``perf`` view went with it, and an unknown name is a
    404 that says which views exist."""
    import urllib.error

    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import httpd

    httpd.register_debug_view("da_view", lambda: {"answer": 42})
    server = telemetry.start_httpd(port=0)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    try:
        doc = json.loads(urllib.request.urlopen(
            base + "/debug/da_view", timeout=30).read())
        assert doc == {"answer": 42}
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + "/debug/perf", timeout=30)
        assert err.value.code == 404
        body = json.loads(err.value.read())
        assert "da_view" in body["views"] and "perf" not in body["views"]
    finally:
        telemetry.stop_httpd()
        with httpd._VIEWS_LOCK:
            httpd._DEBUG_VIEWS.pop("da_view", None)
