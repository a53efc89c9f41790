"""``telemetry.program_parts``: from a compiled program's text to the part of
the program each of its instructions belongs to.

The served models and the decode engine's programs put every operation under
one of a closed vocabulary of ``jax.named_scope`` names
(``telemetry.PROGRAM_PARTS``); XLA names its fusions itself, so a device
trace is joined to the parts through this map (docs/observability.md "Parts
of a program"). CPU only: the CPU's compiled text has the same grammar as the
chip's.
"""
import collections
import hashlib
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry

TINY_PARTS = {"mx_embed", "mx_qkv", "mx_kv_write", "mx_attn", "mx_attn_out",
              "mx_mlp", "mx_head"}
MOE_PARTS = {"mx_moe_route", "mx_moe_experts", "mx_moe_shared",
             "mx_moe_combine"}
LING_PARTS = {"mx_kda_proj", "mx_kda_state", "mx_mla_proj"}


def _model(kind):
    if kind == "tiny":
        return serving.TinyDecoder(vocab_size=32, num_layers=2, num_heads=4,
                                   head_dim=8, num_kv_heads=2), \
            dict(max_seq_len=48), TINY_PARTS
    if kind == "ling":
        # no mx_qkv / mx_attn_out: its two kinds of attention have parts of
        # their own around the one launch under mx_attn
        model = serving.LingDecoder(
            vocab_size=96, hidden_size=48, num_attention_heads=4, head_dim=8,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=96, moe_intermediate_size=32,
            layer_types=["kda", "kda", "mla"], num_dense_layers=1,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            held_experts=[0, 8], routed_scaling_factor=2.5)
        return model, dict(max_seq_len=128, prefill_chunk=0), \
            (TINY_PARTS - {"mx_qkv", "mx_attn_out"}) | MOE_PARTS | LING_PARTS
    model = serving.AfmoeDecoder(
        vocab_size=96, hidden_size=48, num_attention_heads=12,
        num_key_value_heads=2, head_dim=8, intermediate_size=96,
        moe_intermediate_size=32,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
        sliding_window=32, held_experts=[4, 4], route_scale=2.448,
        mup_enabled=True)
    return model, dict(max_seq_len=128, prefill_chunk=0), \
        TINY_PARTS | MOE_PARTS


@pytest.fixture(scope="module", params=["tiny", "afmoe", "ling"])
def warmed(request):
    """``(stats()["program_parts"], the engine's rows, the parts the model
    has)`` of a warmed-up engine."""
    model, kw, parts = _model(request.param)
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=2, page_size=8,
            prefill_buckets=(16, 64), prefix_cache=False, timeout_ms=0,
            name="parts_%s" % request.param, **kw) as eng:
        eng.warmup()
        return eng.stats()["program_parts"], list(eng._programs), parts


def test_vocabulary_is_closed():
    assert telemetry.PROGRAM_PARTS == TINY_PARTS | MOE_PARTS | LING_PARTS


@pytest.mark.parametrize("program", ["step", "first rung", "last rung"])
def test_map_names_every_part_the_model_has(warmed, program):
    """Step and prefill rungs alike: every part of the vocabulary the model
    has holds instructions, and under a tenth of the instructions that do
    work are left with no part."""
    by_program, rows, parts = warmed
    names = list(by_program)
    assert names[0] == "jit_mx_decode_step" and names[1] == "jit_mx_prefill/16"
    name = names[{"step": 0, "first rung": 1, "last rung": -1}[program]]
    assert name == "jit_mx_prefill/%d" % rows[-1]["rung"] or program != \
        "last rung"
    counts = dict(by_program[name])
    unnamed, mixed = counts.pop("unnamed"), counts.pop("mixed")
    assert set(counts) == parts
    assert all(n > 0 for n in counts.values())
    assert unnamed < 0.1 * (unnamed + sum(counts.values()))
    assert mixed >= 0


def test_engine_keeps_one_row_a_program_with_what_the_map_cost(warmed):
    by_program, rows, _parts = warmed
    assert [(r["program"], r.get("rung")) for r in rows][0] == \
        ("jit_mx_decode_step", None)
    assert [r["rung"] for r in rows[1:]][:1] == [16] and len(rows) >= 3
    assert len(by_program) == len(rows)
    for row in rows:
        assert set(row["parts"].values()) <= telemetry.PROGRAM_PARTS
        assert not set(row["parts"]) & set(row["unnamed"])
        assert 0.0 < row["seconds"] < 5.0


def test_warmup_lowers_and_compiles_every_program_once(caplog):
    """The maps are read from the objects ``warmup()`` compiles ahead of its
    calls, and a call finds that lowering and that executable again: no
    program is lowered twice, with no persistent cache to help (jax logs
    one ``Compiling jit(<name>)`` a lowering)."""
    model, kw, _parts = _model("tiny")
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=2, page_size=8,
            prefill_buckets=(16, 32), prefix_cache=True, timeout_ms=0,
            name="parts_once", **kw) as eng:
        with jax.log_compiles(), caplog.at_level(logging.WARNING, "jax"):
            eng.warmup()
        lowered = collections.Counter(
            m.group(1) for m in (re.match(r"Compiling jit\((mx_\w+)\)",
                                          r.getMessage())
                                 for r in caplog.records) if m)
        rungs, chunks = len(eng._ladder), len(eng._chunk_rungs)
        assert rungs == 3 and chunks >= 1
        assert lowered == {"mx_decode_step": 1, "mx_prefill": rungs,
                           "mx_prefill_chunk": chunks, "mx_kv_cow": 1}
        assert eng.compile_count == 2 + rungs + chunks
        # ... and every model program has its map as warmup() returns
        assert len(eng.stats()["program_parts"]) == 1 + rungs + chunks


def test_chunk_rungs_are_mapped_like_the_others():
    model, kw, parts = _model("tiny")
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=2, page_size=8,
            prefill_buckets=(16,), prefix_cache=True, prefill_chunk=8,
            timeout_ms=0, name="parts_chunk", **kw) as eng:
        eng.warmup()
        by_program = eng.stats()["program_parts"]
    assert list(by_program) == ["jit_mx_decode_step", "jit_mx_prefill_chunk/8"]
    counts = dict(by_program["jit_mx_prefill_chunk/8"])
    unnamed, _mixed = counts.pop("unnamed"), counts.pop("mixed")
    assert set(counts) == parts
    assert unnamed < 0.1 * (unnamed + sum(counts.values()))


#: what ``telemetry.PROGRAM_PARTS_VERSION`` stands for: a digest of the scope
#: paths (``jit(mx_prefill)/mx_qkv/dot_general``, each with the number of
#: source lines that put an operation there) of the tiny models' programs as
#: jax lowers them, before any cache is asked ("2": with the ling model's;
#: "3": the prefills' row-wise passes as loops over row blocks, PR 48)
SCOPES_PINNED = {"1": "9a331db489e43cc8", "2": "39e65f87636dace6",
                 "3": "2e9814b4465f6f1b"}


def _scope_paths(lowered):
    # (`moe.expert_layer` is a jit of its own: inside it a path starts at
    # the part's name)
    return re.findall(r'loc\("((?:jit\(mx_|mx_)[^"]*)"',
                      lowered.as_text(debug_info=True))


def test_the_version_tag_is_pinned_to_the_layout_of_the_scopes():
    """A persistent compile cache serves a program its names from whichever
    tree filled the entry unless ``PROGRAM_PARTS_VERSION`` differs (below),
    and the instruction names of the two still match: a moved scope without
    a bump is a WRONG attribution, not a silent one. So a scope cannot move
    without this digest moving."""
    paths = collections.Counter()
    for kind in ("tiny", "afmoe", "ling"):
        model, kw, _parts = _model(kind)
        kw.pop("prefill_chunk", None)
        with serving.DecodeEngine(
                model, model.init_params(0), num_slots=2, page_size=8,
                prefill_buckets=(16,), prefix_cache=False, timeout_ms=0,
                name="parts_pin_%s" % kind, **kw) as eng:
            k, v = eng._cache.operands
            one = jnp.asarray(1, jnp.int32)
            paths.update(_scope_paths(eng._step.lower(
                eng._params, jnp.zeros((eng._packed_rows, 2), jnp.int32),
                eng._no_prev, k, v, eng._device_page_table())))
            paths.update(_scope_paths(eng._prefill_jit.lower(
                eng._params,
                jnp.zeros((eng._prefill_rows, 16), jnp.int32), one, k, v)))
            if kind == "tiny":
                paths.update(_scope_paths(eng._chunk_jit.lower(
                    eng._params, jnp.zeros((3, 8), jnp.int32), one, one,
                    jnp.zeros((eng._cache.max_pages,), jnp.int32), k, v)))
    assert {scope for p in paths for scope in p.split("/")} \
        >= telemetry.PROGRAM_PARTS
    digest = hashlib.sha256("\n".join(
        "%d %s" % (n, p) for p, n in sorted(paths.items())).encode()) \
        .hexdigest()[:16]
    assert SCOPES_PINNED.get(telemetry.PROGRAM_PARTS_VERSION) == digest, (
        "the scopes of the engine's programs moved (digest %s): bump "
        "telemetry.PROGRAM_PARTS_VERSION and pin the new pair in "
        "SCOPES_PINNED" % digest)


def test_programs_carry_the_layout_of_their_parts_where_the_cache_keys_it():
    """jax's persistent compile cache keys a program without its ``op_name``
    metadata: a program traced with other scopes would be served this one's
    executable, names and all. The engine's programs carry
    ``telemetry.PROGRAM_PARTS_VERSION`` as a frontend attribute, which the
    key holds."""
    model, kw, _parts = _model("tiny")
    with serving.DecodeEngine(
            model, model.init_params(0), num_slots=2, page_size=8,
            prefill_buckets=(16,), prefix_cache=True, prefill_chunk=8,
            timeout_ms=0, name="parts_tag", **kw) as eng:
        s = eng.num_slots
        k, v = eng._cache.operands
        packed = jnp.zeros((eng._packed_rows, s), jnp.int32)
        one = jnp.asarray(1, jnp.int32)
        lowered = {
            "step": eng._step.lower(eng._params, packed, eng._no_prev, k, v,
                                    eng._device_page_table()),
            "prefill": eng._prefill_jit.lower(
                eng._params, jnp.zeros((3, 16), jnp.int32), one, k, v),
            "chunk": eng._chunk_jit.lower(
                eng._params, jnp.zeros((3, 8), jnp.int32), one, one,
                jnp.zeros((eng._cache.max_pages,), jnp.int32), k, v)}
    tag = 'mx_parts = "%s"' % telemetry.PROGRAM_PARTS_VERSION
    for name, low in lowered.items():
        assert tag in low.as_text(), name
        assert 'mx_parts="%s"' % telemetry.PROGRAM_PARTS_VERSION \
            in low.compile().as_text(), name


def test_text_without_scopes_gives_an_empty_map():
    def plain(x, w):
        return jnp.tanh(x @ w).sum(axis=-1)

    text = jax.jit(plain).lower(jnp.ones((4, 8)), jnp.ones((8, 8))) \
        .compile().as_text()
    row = telemetry.program_parts(text)
    assert row["program"] == "jit_plain"
    assert row["parts"] == {} and row["mixed"] == 0
    assert row["unnamed"]           # ... and says what it could not place


def test_scoped_function_maps_through_the_compiler(tmp_path):
    """What the issue checked by hand on this jax: a top-level fusion
    carries the ``op_name`` of its root, so the join works; the innermost
    vocabulary name wins over an outer one and over names that are no
    part."""
    def f(x, w):
        with jax.named_scope("mx_mlp"):
            h = jnp.tanh(x @ w)
            with jax.named_scope("mx_prefill_8"):
                with jax.named_scope("mx_moe_route"):
                    top = jax.lax.top_k(h, 2)[0]
        with jax.named_scope("mx_head"):
            return top.sum(axis=-1)

    text = jax.jit(f).lower(jnp.ones((4, 8)), jnp.ones((8, 8))) \
        .compile().as_text()
    row = telemetry.program_parts(text)
    assert set(row["parts"].values()) == {"mx_mlp", "mx_moe_route",
                                          "mx_head"}
    assert not row["unnamed"]


HLO = """HloModule jit_toy, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(toy)/mx_head/reduce_sum"}
}

%fused_computation (param_0: f32[8,8]) -> f32[8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%param_0), metadata={op_name="jit(toy)/mx_mlp/exp"}
  %constant.2 = f32[] constant(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%exp.1, %constant.2), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(toy)/mx_head/reduce_sum"}
}

%fused_computation.1 (param_0.1: f32[8,8], param_1.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %param_1.1 = f32[8,8]{1,0} parameter(1)
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%param_0.1, %param_1.1), metadata={op_name="jit(toy)/mx_prefill_8/mx_mlp/mul"}
}

ENTRY %main.5 (x.1: f32[8,8], w.1: f32[16,8], y.1: f32[8,8]) -> (f32[8], f32[8,8]) {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %w.1 = f32[16,8]{1,0:T(8,128)} parameter(1), metadata={op_name="w"}
  %y.1 = f32[8,8]{1,0} parameter(2), metadata={op_name="y"}
  %slice-start = ((f32[16,8]{1,0:T(8,128)}), f32[8,8]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%w.1), slice={[0:8], [0:8]}
  %slice-done = f32[8,8]{1,0:T(8,128)S(1)} slice-done(%slice-start)
  %bitcast.3 = f32[8,8]{1,0} bitcast(%slice-done)
  %scale_fusion = f32[8,8]{1,0} fusion(%x.1, %bitcast.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(toy)/mx_prefill_8/mx_mlp/mul"}
  %mx_moe_gmm.7 = f32[8,8]{1,0} custom-call(%scale_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/mx_moe_experts/mx_moe_gmm"}
  %exp_reduce_fusion = f32[8]{0} fusion(%mx_moe_gmm.7), kind=kInput, calls=%fused_computation, metadata={op_name="jit(toy)/mx_head/reduce_sum"}
  %copy.4 = f32[8,8]{0,1} copy(%mx_moe_gmm.7)
  %copy-start.1 = (f32[16,8]{1,0:T(8,128)S(1)}, f32[16,8]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%w.1)
  %copy-done.1 = f32[16,8]{1,0:T(8,128)S(1)} copy-done(%copy-start.1)
  %all-gather.1 = f32[8,8]{1,0} all-gather(%y.1), dimensions={0}
  ROOT %tuple.6 = (f32[8]{0}, f32[8,8]{0,1}) tuple(%exp_reduce_fusion, %copy.4)
}
"""


def test_fusion_goes_to_its_roots_part_and_mixed_counts_it():
    row = telemetry.program_parts(HLO)
    assert row["program"] == "jit_toy"
    # the fusion holds an `mx_mlp` exponential under an `mx_head` root
    assert row["parts"]["exp_reduce_fusion"] == "mx_head"
    assert row["mixed"] == 1
    # the kernel keeps its own instruction name, under its scope's part
    assert row["parts"]["mx_moe_gmm.7"] == "mx_moe_experts"
    assert row["parts"]["scale_fusion"] == "mx_mlp"     # not mx_prefill_8


def test_what_the_compiler_made_takes_its_readers_part_or_its_operands():
    row = telemetry.program_parts(HLO)
    # a weight's fetch, through the bitcast, belongs to the product that
    # reads it; a copy on the way out to what made its operand
    assert row["parts"]["slice-start"] == "mx_mlp"
    assert row["parts"]["slice-done"] == "mx_mlp"
    assert row["parts"]["copy.4"] == "mx_moe_experts"
    # the weight fetched again for the program's next run has no reader
    # here: it goes where the weight's other reader is
    assert row["parts"]["copy-start.1"] == "mx_mlp"
    assert row["parts"]["copy-done.1"] == "mx_mlp"
    # nothing to inherit from: unnamed, and said so
    assert row["unnamed"] == ["all-gather.1"]


def test_map_holds_the_instructions_that_are_device_events_only():
    row = telemetry.program_parts(HLO)
    # no parameter, bitcast or tuple; nothing from inside a fusion or from
    # a reduction's region
    assert sorted(row["parts"]) == sorted([
        "slice-start", "slice-done", "scale_fusion", "mx_moe_gmm.7",
        "exp_reduce_fusion", "copy.4", "copy-start.1", "copy-done.1"])
