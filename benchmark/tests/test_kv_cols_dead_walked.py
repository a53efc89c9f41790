"""``benchmark/layer_metrics/kv_cols_dead_walked_pct.py``: ``None`` on a
rehearsal, without a trace file and for a program whose ``mx.decode.commit``
spans carry no ``kv_cols_walked`` (the parent); a share from spans that do;
the engine's own spans on a CPU trace; its place in the manifest."""
import importlib.util
import json
import os
import types

import numpy as np
import pytest

import program_spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "kv_cols_dead_walked_pct"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "lm_" + NAME, os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(commits, other=()):
    """A traced run whose spans are already read: ``mx.decode.commit`` with
    each of ``commits`` as its arguments, and ``other`` (name, args)."""
    raw = [(1, 10 * i, 10 * i + 5, "mx.decode.commit", dict(args))
           for i, args in enumerate(commits)]
    raw += [(2, 0, 1, name, dict(args)) for name, args in other]
    return {"trace": {"events": {}}, program_spans._KEY: {
        "spans": program_spans.nest(raw), "by_name": {}, "idle_s": 0.0,
        "idle_by_span_s": {}}}


@pytest.mark.parametrize("run", [
    {"trace": None},
    {"trace": {"events": {}},
     "cell": types.SimpleNamespace(trace_dir="/nonexistent")},
], ids=["rehearsal", "no_trace_file"])
def test_none_without_a_trace(run):
    assert _read(run) is None


@pytest.mark.parametrize("commits,other", [
    ([], []),
    # the parent's spans: live and grid, no walked
    ([{"kv_cols_live": 19, "kv_cols_grid": 2048}] * 3, []),
    # the argument on another span does not count
    ([{"kv_cols_live": 19, "kv_cols_grid": 2048}],
     [("mx.decode.tick", {"kv_cols_walked": 40})]),
], ids=["no_commit", "parent", "other_span"])
def test_none_where_no_commit_carries_the_argument(commits, other):
    assert _read(_run(commits, other)) is None


@pytest.mark.parametrize("commits,want", [
    # the rectangle: 16 slots x the longest slot's 19 columns, one live
    ([{"kv_cols_live": 19, "kv_cols_walked": 304}], 100.0 * 285 / 304),
    # one step an empty slot
    ([{"kv_cols_live": 19, "kv_cols_walked": 34}] * 4, 100.0 * 15 / 34),
    # live pairs only
    ([{"kv_cols_live": 264, "kv_cols_walked": 264},
      {"kv_cols_live": 270, "kv_cols_walked": 270}], 0.0),
    # sums over the spans, not a mean of shares; a commit of the parent's
    # form among them is left out
    ([{"kv_cols_live": 10, "kv_cols_walked": 20},
      {"kv_cols_live": 90, "kv_cols_walked": 180},
      {"kv_cols_live": 7, "kv_cols_grid": 99}], 50.0),
], ids=["rectangle", "one_step_an_empty_slot", "live_pairs", "sums"])
def test_share_of_the_walked_steps_that_were_dead(commits, want):
    assert _read(_run(commits)) == pytest.approx(want)


def test_reads_the_engines_own_spans(tmp_path):
    """A burst on the tiny engine under a CPU trace: the reader's share is
    what ``stats()`` counted."""
    import jax

    from mxnet_tpu import serving

    model = serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                head_dim=8, num_kv_heads=2)
    rng = np.random.RandomState(5)
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=3, max_seq_len=48,
            prefill_buckets=(8, 16), prefix_cache=False, timeout_ms=0,
            name="bench_dead_walked") as eng:
        eng.warmup()
        before = eng.stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for f in [eng.submit(rng.randint(1, 32, 6).astype(np.int32), 8)
                      for _ in range(4)]:
                f.result(timeout=120)
        finally:
            jax.profiler.stop_trace()
        stats = eng.stats()
    walked = stats["kv_cols_walked"] - before["kv_cols_walked"]
    live = stats["kv_cols_live"] - before["kv_cols_live"]
    assert 0 < live <= walked
    # a CPU trace has no device plane: one operation laid there by hand
    run = {"trace": {"events": {0: [("%op = f32[] copy(f32[] %p)", 0, 10)]},
                     "lead_device": 0},
           "cell": types.SimpleNamespace(trace_dir=str(tmp_path))}
    assert _read(run) == pytest.approx(100.0 * (walked - live) / walked)


def test_manifest_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (row,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    decode = next(m["workloads"] for m in manifest["end_to_end"]
                  if m["name"] == "request_p50_ms")
    assert row == {"name": NAME, "unit": "%", "better": "lower",
                   "source": "program_span", "layer": "kernels",
                   "moves": "request_p50_ms", "workloads": decode}
