"""The ``trinity_large_preview_ep8`` configuration and its cell, on the CPU.

* the benchmark's plain reference (``reference/afmoe_share.py``, which
  imports nothing of the program) and the repo's own
  (``mxnet_tpu/serving/afmoe_reference.py``) give equal logits;
* the configuration file holds every number of the catalog row's ``config``
  that it does not list under ``reduced``, and 4.322 B parameters;
* ``--rehearse`` of the cell comes out ``correct``, its bfloat16 control not;
* the counts of ``flops_moe`` and the trace helpers, on made-up inputs.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_run_paths import _compared, _run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "trinity_ep8_short_long"
TINY = dict(vocab_size=96, hidden_size=48, num_attention_heads=12,
            num_key_value_heads=2, head_dim=8, intermediate_size=96,
            moe_intermediate_size=32,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
            sliding_window=32, held_experts=[4, 4], rope_theta=10000.0,
            rms_norm_eps=1e-5, route_norm=True, route_scale=2.448,
            mup_enabled=True, param_dtype="float32")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "trinity_large_preview_ep8.json")) as f:
        return json.load(f)


def test_both_references_give_equal_logits():
    import weights
    from mxnet_tpu.serving import afmoe_reference as repo_ref
    from reference import afmoe_share as bench_ref

    params = weights.make(bench_ref.param_specs(TINY), 5)
    seq = np.random.RandomState(0).randint(1, 96, 128).astype(np.int32)
    want = np.asarray(repo_ref.forward_logits(TINY, params, seq))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(bench_ref.rows_logits(TINY, params,
                                               jnp.asarray(seq), 0, 128)[0])
    # float32 both: blocks of queries and a loop over experts reorder sums
    np.testing.assert_allclose(got, want, atol=2e-4)
    gap, best = bench_ref.served_gaps(TINY, params, jnp.asarray(seq), 40,
                                      jnp.asarray(want[40:72].argmax(-1)))
    assert float(gap.max()) < 1e-3
    assert np.array_equal(np.asarray(best), want[40:72].argmax(-1))


def test_configuration_keeps_every_published_number_it_does_not_reduce(
        config):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "trinity_large_preview_ep8")
    assert entry["source"].startswith(row["source_url"])
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers",
                       "num_experts", "vocab_size", "layer_types"}
    for key, val in row["config"].items():
        if key not in reduced:
            assert config[key] == val, key
    model = config["model"]
    assert config["num_experts"] == model["held_experts"][1] == 32
    assert model["num_experts"] == config["published"]["num_experts"] == 256
    assert config["vocab_size"] == model["vocab_size"] == 200192 // 8
    assert config["layer_types"] == model["layer_types"] == [
        row["config"]["layer_types"][i] for i in (0, 8, 9, 10, 11)]
    assert config["factory_kwargs"] == {
        k: v for k, v in model.items() if k != "param_dtype"}


def test_the_share_is_4_322_billion_parameters(config):
    from reference import afmoe_share

    import weights

    specs = jax.tree_util.tree_leaves(
        afmoe_share.param_specs(config["model"]),
        is_leaf=lambda x: isinstance(x, weights.Spec))
    count = sum(int(np.prod(s.shape)) for s in specs)
    assert abs(count - 4.322e9) < 0.005e9
    assert sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in specs) < 8.66e9


def test_cell_rehearses_correct_and_its_control_does_not():
    proc, lines = _run(["--workload", CELL, "--seed", "21", "--rehearse",
                        "--control"])
    assert lines, proc.stderr[-2000:]
    assert _compared(lines, "served_token_widest_logit_gap_sd")["ok"]
    assert _compared(lines, "requests_finished")["ok"]
    assert _compared(lines, "decode_recompiles")["ok"]
    assert _compared(lines, "kv_pages_in_use_at_end")["ok"]
    control = _compared(lines, "CONTROL_served_token_widest_logit_gap_sd")
    assert not control["ok"] and lines[-1]["correct"] is False
    proc, lines = _run(["--workload", CELL, "--seed", "22", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0


def test_counts_of_the_expert_layer_and_the_band():
    import flops_moe

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a decode tick: 8 rows to 8 experts is bound by the experts' weights
    sec, by = flops_moe.grouped_swiglu_least_seconds(8, 8, 3072, 3072, peaks)
    assert by == "bytes"
    assert sec == pytest.approx((8 * 3 * 3072 * 3072 * 2
                                 + 3 * 8 * 4 * 6144) / 819e9)
    # 512 rows an expert (241 and more) are bound by arithmetic
    sec, by = flops_moe.grouped_swiglu_least_seconds(16384, 32, 3072, 3072,
                                                     peaks)
    assert by == "flops"
    assert sec == pytest.approx(2 * 3 * 3072 * 3072 * 16384 / 197e12)
    assert flops_moe.band_pairs(5) == 15
    assert flops_moe.band_pairs(5, 3) == sum(min(q + 1, 3)
                                             for q in range(5))
    assert flops_moe.band_pairs(8192, 4096) == sum(
        min(q + 1, 4096) for q in range(8192))
    assert flops_moe.band_attention_flops(4, 2, 8) == 4 * 10 * 8 * 2
    assert flops_moe.grouped_decode_kv_bytes(100, 60, 1, 4, 8, 128, 4) \
        == 2 * (100 + 240) * 8 * 128 * 4
    assert flops_moe.layer_kinds(TINY) == (1, 4, 4)


def test_time_within_splits_a_kernel_by_the_program_that_ran_it():
    import trace_within

    reduced = {"lead_device": 0, "modules": [
        ("jit_mx_decode_step(1)", 0, 100), ("jit_mx_prefill(2)", 100, 300),
        ("jit_mx_decode_step(1)", 300, 400)], "events": {0: [
            ("%mx_moe_gmm.1 = f32[] custom-call()", 10, 20),
            ("%mx_moe_shared.1 = f32[] custom-call()", 20, 25),
            ("%mx_moe_gmm.7 = f32[] custom-call()", 150, 250),
            ("%fusion.3 = f32[] fusion()", 310, 320),
            ("%mx_moe_gmm.1 = f32[] custom-call()", 350, 360)]}}
    sec, count, runs = trace_within.time_within(
        reduced, r"^%?mx_moe_\w+\b", "mx_decode_step")
    assert (round(sec * 1e9), count, runs) == (25, 3, 2)
    sec, count, runs = trace_within.time_within(
        reduced, r"^%?mx_moe_gmm\b", "mx_prefill")
    assert (round(sec * 1e9), count, runs) == (100, 1, 1)


def test_layer_metric_readers_on_real_spans_and_made_up_device_events(
        tmp_path, config):
    """The six readers end to end: the program's own spans from a CPU trace
    of a tiny engine (the host plane is the same on every backend), laid
    beside device events made up under the names the chip's trace has."""
    import types

    import run as harness
    import trace_reduce
    from mxnet_tpu import serving

    model = serving.AfmoeDecoder(**{k: v for k, v in TINY.items()
                                    if k != "param_dtype"})
    eng = serving.DecodeEngine(
        model, model.init_params(0), num_slots=2, max_seq_len=128,
        page_size=8, prefill_buckets=(16, 64), prefix_cache=False,
        prefill_chunk=0, timeout_ms=0, name="readers")
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        eng.generate(np.arange(1, 51, dtype=np.int32), 6, timeout=300)
        eng.close()
    finally:
        jax.profiler.stop_trace()
    spans = __import__("program_spans").read_spans(
        trace_reduce.find_xplane(trace_dir))
    commits = [s for s in spans if s.name == "mx.decode.commit"]
    prefill = [s for s in spans if s.name == "mx.decode.prefill"]
    assert len(commits) == 5 and len(prefill) == 1
    # device events: one prefill program, then one step program a commit,
    # each ending where its span ends
    events, modules = [], []
    p = prefill[0]
    modules.append(("jit_mx_prefill(1)", p.start, p.end))
    events += [("%mx_prefill_attn.1 = f32[] custom-call()", p.start,
                p.start + 40_000),
               ("%mx_moe_gmm.9 = f32[] custom-call()", p.start + 40_000,
                p.start + 50_000)]
    for c in commits:
        lo = c.start - 100_000
        modules.append(("jit_mx_decode_step(2)", lo, c.start))
        events += [("%mx_paged_attn.1 = f32[] custom-call()", lo,
                    lo + 30_000),
                   ("%mx_moe_gmm.1 = f32[] custom-call()", lo + 30_000,
                    lo + 50_000),
                   ("%mx_moe_shared.1 = f32[] custom-call()", lo + 50_000,
                    lo + 60_000),
                   ("%fusion.7 = f32[] fusion()", lo + 60_000, c.start)]
    reduced = {"lead_device": 0, "events": {0: events}, "modules": modules,
               "busy_s": 1.0, "window_s": 1.0}
    cell = types.SimpleNamespace(
        name=CELL, trace_dir=trace_dir, peaks={
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        config=dict(config, model=dict(TINY)))
    full = sum(c.args["kv_rows_full"] for c in commits)
    run = {"cell": cell, "trace": reduced,
           "counters": {"traced_kv_token_reads": full + 5}}
    names = ["moe_ms_per_tick", "moe_gmm_roofline",
             "paged_attn_window_roofline", "moe_load_max_over_mean",
             "kv_window_pages_peak_pct", "prefill_attn_roofline"]
    got = {n: harness.load_module("layer_metrics", n).read(run)
           for n in names}
    assert got["moe_ms_per_tick"] == pytest.approx(0.03)   # 30 us a step
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["kv_window_pages_peak_pct"] == pytest.approx(50.0)
    assert got["moe_load_max_over_mean"] >= 1.0
    for n in ("moe_gmm_roofline", "paged_attn_window_roofline",
              "prefill_attn_roofline"):
        assert 0 < got[n] <= 100.0, (n, got[n])
    # spans that claim more rows than the driver's request traces saw
    run2 = dict(run, counters={"traced_kv_token_reads": int(full / 1.05)})
    run2.pop("_program_spans", None)
    assert harness.load_module(
        "layer_metrics", "paged_attn_window_roofline").read(run2) is None
    # no prefill in the traced span reads nothing: a share is never 0
    reduced3 = dict(reduced, events={0: [e for e in events
                                         if "prefill_attn" not in e[0]]})
    run3 = dict(run, trace=reduced3)
    assert harness.load_module(
        "layer_metrics", "prefill_attn_roofline").read(run3) is None
    # a program without the spans (the parent): nothing, and no raise
    parent = dict(run, trace=dict(reduced))
    parent["_program_spans"] = {"spans": [], "by_name": {}, "idle_s": 0.0,
                                "idle_by_span_s": {}}
    for n in names[1:]:
        assert harness.load_module("layer_metrics", n).read(parent) is None
