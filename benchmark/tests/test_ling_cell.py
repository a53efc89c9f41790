"""The ``ling_3_flash_vl_ep4`` configuration and its cell, on the CPU.

* the configuration file holds every number of the catalog row's ``config``
  that it does not list under ``reduced``, and 5,169,366,976 parameters;
* ``--rehearse`` of the cell comes out ``correct``; its bfloat16 control and a
  timed path broken underneath (a prefill that leaves its slot's state as
  the previous owner left it) do not;
* the counts of ``flops_ling``, and every new reader on the program's own
  spans beside device events made up under the names the chip's trace has.
"""
import json
import os
import types

import numpy as np
import pytest

import jax

from test_run_paths import _compared, _run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ling3_flash_ep4_long_output"
NEW_READERS = ["kda_state_ms_per_tick", "kda_state_roofline",
               "mla_attn_roofline", "step_kda_proj_ms_per_tick",
               "step_mla_proj_ms_per_tick", "prefill_kda_share_pct",
               "latent_pages_peak_pct", "prefill_mla_attn_roofline"]

# a prefill that ADDS its state to what the slot held: right on a fresh
# engine's first use of a slot (zeros), wrong on every reuse
BROKEN_SLOT_REUSE = """
import jax
from mxnet_tpu import serving
_prefill = serving.LingDecoder.prefill
def prefill(self, params, tokens, length, k_pool, v_pool, *a, **k):
    out = _prefill(self, params, tokens, length, k_pool, v_pool, *a, **k)
    kept = jax.tree_util.tree_map(lambda new, old: new + old, out[2],
                                  tuple(v_pool))
    return (out[0], out[1], kept) + tuple(out[3:])
serving.LingDecoder.prefill = prefill
"""


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "ling_3_flash_vl_ep4.json")) as f:
        return json.load(f)


def test_configuration_keeps_every_published_number_it_does_not_reduce(
        config):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ling_3_flash_vl_ep4")
    assert entry["source"].startswith(row["source_url"])
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "num_experts", "vocab_size"}
    for key, val in row["config"].items():
        if key not in reduced:
            assert config[key] == val, key
    model, published = config["model"], config["published"]
    assert {k: published[k] for k in reduced} == {
        k: row["config"][k] for k in reduced}
    assert config["num_experts"] == model["held_experts"][1] == 512 // 4
    assert model["num_experts"] == published["num_experts"] == 512
    assert config["vocab_size"] == model["vocab_size"] == 157184 // 4
    # one whole period behind one dense layer: layer l is latent where
    # (l + 1) % layer_group_size == 0
    held = [0, 6, 7, 8, 9, 10, 11]
    assert config["layer_types"] == model["layer_types"] == [
        "mla" if (l + 1) % config["layer_group_size"] == 0 else "kda"
        for l in held]
    assert config["num_hidden_layers"] == len(held)
    assert config["first_k_dense_replace"] == model["num_dense_layers"] == 1
    assert all(config[k][l] == 0 for l in held for k in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"))
    assert config["factory_kwargs"] == {
        k: v for k, v in model.items() if k != "param_dtype"}
    for key in ("n_group", "topk_group", "num_experts_per_tok",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "routed_scaling_factor",
                "short_conv_kernel_size", "kda_lower_bound", "rope_theta",
                "rms_norm_eps", "num_attention_heads", "norm_topk_prob"):
        assert model[key] == row["config"][key], key


def test_the_share_is_5_169_366_976_parameters(config):
    from reference import ling_share

    import weights

    specs = jax.tree_util.tree_leaves(
        ling_share.param_specs(config["model"]),
        is_leaf=lambda x: isinstance(x, weights.Spec))
    assert sum(int(np.prod(s.shape)) for s in specs) == 5_169_366_976
    assert sum(int(np.prod(s.shape)) * np.dtype(
        "float32" if s.dtype == "float32" else "uint16").itemsize
        for s in specs) == 10_355_187_456


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "ling_share.py")) as f:
        text = f.read()
    assert "mxnet_tpu" not in text.split('"""', 2)[2]


def test_cell_rehearses_correct_and_its_control_does_not():
    proc, lines = _run(["--workload", CELL, "--seed", "31", "--rehearse",
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
    skipped = [ln for ln in lines if ln.get("phase") == "near_ties"]
    assert skipped and all(0 <= ln["skipped"] < ln["positions"]
                           for ln in skipped)
    readable = dict(next(ln for ln in lines if ln.get("phase")
                         == "rehearsed")["layer_metrics_readable"])
    assert set(NEW_READERS) <= set(readable)


def test_state_not_reset_on_slot_reuse_is_not_correct():
    proc, lines = _run(["--workload", CELL, "--seed", "23", "--rehearse"],
                       patch=BROKEN_SLOT_REUSE)
    assert lines, proc.stderr[-2000:]
    assert lines[-1]["attempted"] > 4       # ... so slots changed hands
    assert not _compared(lines, "served_token_widest_logit_gap_sd")["ok"]
    assert lines[-1]["correct"] is False and proc.returncode != 0


def test_counts_of_the_recurrence_and_the_latent_rows():
    import flops_ling

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    model = {"kv_lora_rank": 512, "qk_rope_head_dim": 64}
    # 32 live slots, six layers of (32, 128, 128) + 3 x 12288 floats, each
    # way once
    a_slot = 6 * (32 * 128 * 128 + 3 * 12288) * 4
    assert flops_ling.state_least_seconds(2 * 32 * a_slot, peaks) \
        == pytest.approx(2 * 32 * a_slot / 819e9)
    assert flops_ling.latent_row_width(model) == 576
    assert flops_ling.latent_least_seconds(48000, model, 4, peaks) \
        == pytest.approx(48000 * 576 * 4 / 819e9)


def test_new_readers_on_real_spans_and_made_up_device_events(tmp_path,
                                                             config):
    """The eight readers end to end: the program's own spans from a CPU
    trace of a tiny engine (the host plane is the same on every backend),
    laid beside device events made up under the names the chip's trace has:
    the step's instructions by the parts the engine's own map gives them."""
    import run as harness
    import trace_reduce
    from mxnet_tpu import serving

    tiny = dict(config["factory_kwargs"], **config["rehearse"]["model"])
    model = serving.LingDecoder(**tiny)
    eng = serving.DecodeEngine(
        model, model.init_params(0), num_slots=2, max_seq_len=128,
        page_size=8, prefill_buckets=(16, 64), prefix_cache=False,
        prefill_chunk=0, timeout_ms=0, name="ling_readers")
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
    programs = {(s.args["program"], s.args.get("rung")): json.loads(
        s.args["parts"]) for s in spans if s.name == "mx.decode.programs"}
    assert len(commits) == 5 and len(prefill) == 1
    assert all(c.args["state_slots_live"] == 1 for c in commits)
    assert [c.args["latent_rows_read"] for c in commits] == [51, 52, 53, 54,
                                                             55]
    step = programs[("jit_mx_decode_step", None)]
    rung = programs[("jit_mx_prefill", 64)]
    for part in ("mx_kda_state", "mx_kda_proj", "mx_mla_proj"):
        assert step[part] and rung[part], part

    def named(parts, part, lo, ns):
        return ("%%%s = f32[] fusion()" % parts[part][0], lo, lo + ns)

    events, modules = [], []
    p = prefill[0]
    modules.append(("jit_mx_prefill(1)", p.start, p.end))
    events += [named(rung, "mx_kda_state", p.start, 30_000),
               named(rung, "mx_kda_proj", p.start + 30_000, 10_000),
               named(rung, "mx_moe_route", p.start + 40_000, 60_000),
               # (outside the module's run: the shares above stay whole)
               ("%mx_prefill_attn.1 = f32[] custom-call()", p.start - 90_000,
                p.start - 40_000)]
    for c in commits:
        lo = c.start - 100_000
        modules.append(("jit_mx_decode_step(2)", lo, c.start))
        events += [("%mx_mla_attn.1 = f32[] custom-call()", lo, lo + 20_000),
                   named(step, "mx_kda_state", lo + 20_000, 40_000),
                   named(step, "mx_kda_proj", lo + 60_000, 10_000),
                   named(step, "mx_mla_proj", lo + 70_000, 5_000)]
    reduced = {"lead_device": 0, "events": {0: events}, "modules": modules,
               "busy_s": 1.0, "window_s": 1.0}
    cell = types.SimpleNamespace(
        name=CELL, trace_dir=trace_dir, peaks={
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        config=dict(config, model=dict(tiny)))
    run = {"cell": cell, "trace": reduced,
           "counters": {"kv_pages_peak": 7, "kv_pages_pool": 32}}
    got = {n: harness.load_module("layer_metrics", n).read(run)
           for n in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    assert got["kda_state_ms_per_tick"] == pytest.approx(0.04)
    assert got["step_kda_proj_ms_per_tick"] == pytest.approx(0.01)
    assert got["step_mla_proj_ms_per_tick"] == pytest.approx(0.005)
    assert got["prefill_kda_share_pct"] == pytest.approx(40.0)
    a_slot = commits[0].args["state_bytes_moved"]
    assert got["kda_state_roofline"] == pytest.approx(
        100.0 * 5 * a_slot / 819e9 / (5 * 40e-6))
    assert got["mla_attn_roofline"] == pytest.approx(
        100.0 * sum(range(51, 56)) * 20 * 4 / 819e9 / (5 * 20e-6))
    assert got["latent_pages_peak_pct"] == pytest.approx(100.0 * 7 / 32)
    # one latent layer, a prompt of 50 tokens: 1275 pairs, 4 heads, keys of
    # 8 + 4 and values of 8
    assert prefill[0].args["latent_rows_read"] == 50
    assert got["prefill_mla_attn_roofline"] == pytest.approx(
        100.0 * 2 * 1275 * 20 * 4 / 197e12 / 50e-6)
    # a share over 100 % is a counting fault and raises
    fast = dict(run, trace=dict(reduced, events={0: [
        (n, s, s + 1) if "mla_attn" in n else (n, s, e)
        for n, s, e in events]}))
    fast.pop("_program_parts", None)
    with pytest.raises(ValueError, match="mla_attn_roofline"):
        harness.load_module("layer_metrics", "mla_attn_roofline").read(fast)
    # a program without the spans or the parts (the parent): nothing, and
    # no raise
    parent = dict(run, trace=dict(reduced), counters={})
    parent.pop("_program_parts", None)
    parent["_program_spans"] = {"spans": [], "by_name": {}, "idle_s": 0.0,
                                "idle_by_span_s": {}}
    for n in NEW_READERS:
        assert harness.load_module("layer_metrics", n).read(parent) is None
