"""Compile the Pallas kernels of the main path for the REAL chip, without
the chip: the TPU compiler is installed here and compiles for a device that
is described, not attached (``jax.experimental.topologies``). Interpret-mode
parity tests prove the math; only this proves Mosaic accepts the kernels —
both decode kernels passed every interpret test while being refused at
lowering (``dynamic_slice`` on values) until PR 22.

This is the ONLY file that describes the chip. The topology is asked for
inside a module-scoped fixture, never at import / in ``skipif`` / in
``parametrize``: libtpu belongs to one process, the suite runs under several
xdist workers that each import every test file, and only the worker handed
this file may load it. Nothing runs — a passing compile is not a chip run.
"""
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_kernels as pk

# chip_smoke.py's serve shapes (SERVE there): 16 slots, 4 heads x 128, the
# engine's default 16-token pages, max_seq_len 1152 -> 72 pages per slot,
# pool of slots * pages + the null page; speculation width spec_k + 1.
SLOTS, HEADS, HEAD_DIM, PAGE, MAX_PAGES = 16, 4, 128, 16, 72
POOL_PAGES = SLOTS * MAX_PAGES + 1
SPEC_W = 4
CHUNK = 32          # the largest prefill-bucket rung below max_seq_len


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_cache_off():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again): keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _paged_operands(spec, dtype, rows, width=1):
    q_shape = (rows, HEADS, HEAD_DIM) if width == 1 \
        else (rows, width, HEADS, HEAD_DIM)
    pool = spec((POOL_PAGES, PAGE, HEADS, HEAD_DIM), dtype)
    return (spec(q_shape, dtype), pool, pool,
            spec((rows, MAX_PAGES), jnp.int32),
            spec((rows * width,), jnp.int32))


def _case(name, spec, dtype):
    """(function, abstract operands) for one kernel at chip_smoke's shapes;
    every kernel is called with ``interpret=False`` (flash/NMS through the
    test's ``_interpret`` patch) — the dispatchers ask
    ``jax.default_backend()``, which is the CPU here."""
    if name == "paged_decode":
        return (lambda *a: pk.ragged_paged_attention(*a, interpret=False),
                _paged_operands(spec, dtype, SLOTS))
    if name == "paged_prefill_chunk":
        # what paged_prefill_attention feeds the kernel: one row per chunk
        # token, a broadcast page-table row, causal q_pos
        ops = _paged_operands(spec, dtype, CHUNK)
        return (lambda q, k, v, pt, sl, qp: pk.ragged_paged_attention(
            q, k, v, pt, sl, q_pos=qp, interpret=False),
            ops + (spec((CHUNK,), jnp.int32),))
    if name == "paged_spec_verify":
        return (lambda *a: pk.ragged_spec_attention(*a, interpret=False),
                _paged_operands(spec, dtype, SLOTS, SPEC_W))
    if name == "flash_attention":
        qkv = spec((1, HEADS, 1152, HEAD_DIM), dtype)
        return (lambda q, k, v: pk._flash_forward(q, k, v, 0.088, True),
                (qkv, qkv, qkv))
    # Trinity-Large-Preview's attention (48 query / 8 kv heads x 128, window
    # 4096, 16-token pages -> a ring of 257 pages a slot) and expert widths
    # (``_trinity``: with float32 products, as afmoe.py launches it)
    if name in ("paged_window_decode", "paged_window_decode_trinity"):
        ring = 4096 // PAGE + 1
        pool = spec((SLOTS * ring + 1, PAGE, 8, 128), dtype)
        return (lambda q, k, v, pt, sl: pk.ragged_window_attention(
            q, k, v, pt, sl, 4096, interpret=False,
            precise=name.endswith("trinity")),
            (spec((SLOTS, 48, 128), dtype), pool, pool,
             spec((SLOTS, ring), jnp.int32), spec((SLOTS,), jnp.int32)))
    # the two benchmark cells' own tables: the widest scalar-prefetch
    # operands (table, lengths, live columns) the decode step hands SMEM
    if name == "paged_decode_opt1p3b":      # 16 slots x 128 columns, 32 x 64
        pool = spec((769, PAGE, 32, 64), dtype)
        return (lambda *a: pk.ragged_paged_attention(*a, interpret=False),
                (spec((SLOTS, 32, 64), dtype), pool, pool,
                 spec((SLOTS, 128), jnp.int32), spec((SLOTS,), jnp.int32)))
    if name == "paged_decode_trinity_full":  # 512 columns, 48 / 8 x 128
        pool = spec((4097, PAGE, 8, 128), dtype)
        return (lambda *a: pk.ragged_paged_attention(
            *a, interpret=False, precise=True),
            (spec((SLOTS, 48, 128), dtype), pool, pool,
             spec((SLOTS, 512), jnp.int32), spec((SLOTS,), jnp.int32)))
    # the body that multiplies a page once for all its kv heads (PR 34), at
    # the cells' own shapes. OPT as the engine holds it on the chip: 32 kv
    # heads x 64 in rows 128 wide (the key matrix of a page is 512 x 128),
    # the decode tick, the verify tick (width 4) and the chunk program
    if name.startswith("opt1p3b_wide_"):
        pool = spec((769, PAGE, 32, 128), dtype)
        table = spec((SLOTS, 128), jnp.int32)
        if name.endswith("decode"):
            return (lambda *a: pk.ragged_paged_attention(*a, interpret=False),
                    (spec((SLOTS, 32, 64), dtype), pool, pool, table,
                     spec((SLOTS,), jnp.int32)))
        if name.endswith("spec"):
            return (lambda *a: pk.ragged_spec_attention(*a, interpret=False),
                    (spec((SLOTS, SPEC_W, 32, 64), dtype), pool, pool, table,
                     spec((SLOTS * SPEC_W,), jnp.int32)))
        return (lambda q, k, v, pt, sl, qp: pk.ragged_paged_attention(
            q, k, v, pt, sl, q_pos=qp, interpret=False),
            (spec((CHUNK, 32, 64), dtype), pool, pool,
             spec((CHUNK, 128), jnp.int32), spec((CHUNK,), jnp.int32),
             spec((CHUNK,), jnp.int32)))
    # Trinity's prefill (48 / 8 heads x 128, float32 products) with the
    # prompt's traced length: the largest rung over every key and over the
    # window, the smallest rung, and a prompt of ONE token
    if name.startswith("band_prefill_"):
        rung = 512 if "512" in name else 8192
        window = 4096 if name.endswith("window") else 0
        kv = spec((rung, 8, 128), dtype)
        operands = (spec((rung, 48, 128), dtype), kv, kv)
        if name.endswith("length_1"):
            return (lambda q, k, v: pk.band_attention(
                q, k, v, window=window, interpret=False, precise=True,
                length=jnp.asarray(1, jnp.int32)), operands)
        return (lambda q, k, v, n: pk.band_attention(
            q, k, v, window=window, interpret=False, precise=True, length=n),
            operands + (spec((), jnp.int32),))
    if name in ("moe_gmm_decode", "moe_gmm_prefill"):
        rows = 64 if name.endswith("decode") else 8192 * 4
        return (lambda x, w, g: moe.grouped_matmul(x, w, g, interpret=False),
                (spec((rows, 3072), dtype),
                 spec((32, 3072, 3072), jnp.bfloat16),
                 spec((32,), jnp.int32)))
    if name == "latent_decode_ling":
        # the Ling cell's latent layer: 32 slots x 32 heads on rows of 512 +
        # 64 held 640 wide, 352 columns a slot, a pool with no head axis
        return (lambda q, pool, table, lens: pk.paged_latent_attention(
            q, pool, table, lens, 512, 192 ** -0.5, interpret=False),
            (spec((32, 32, 576), dtype), spec((32 * 352 + 1, PAGE, 640),
                                              dtype),
             spec((32, 352), jnp.int32), spec((32,), jnp.int32)))
    if name == "band_prefill_4096_ling":
        # its prefill attention, expanded: 32 heads of 192 (keys) and 128
        # (values), all three operands at the lane tile above, 256
        operands = (spec((4096, 32, 256), dtype),) * 3
        return (lambda q, k, v, n: pk.band_attention(
            q, k, v, scale=192 ** -0.5, interpret=False, precise=True,
            length=n), operands + (spec((), jnp.int32),))
    if name in ("kda_state_ling", "kda_state_tiny"):
        # the Ling cell's recurrence: 32 slots x 32 heads of 128 x 128 (a
        # slot's 2 MB a grid step), and tests/test_ling_decoder.py's 3 heads
        # of 16 x 8, which the kernel takes by its shapes (always float32:
        # the state carries what it rounds)
        slots, heads, dk, dv = (32, 32, 128, 128) if name.endswith("ling") \
            else (3, 3, 16, 8)
        cols = spec((slots, heads, dk), jnp.float32)
        return (lambda *a: pk.kda_state_step(*a, interpret=False),
                (cols, cols, spec((slots, heads, dv), jnp.float32), cols,
                 spec((slots, heads), jnp.float32),
                 spec((slots, heads, dk, dv), jnp.float32),
                 spec((slots,), jnp.bool_)))
    if name == "nms":
        n = 1000
        return (lambda b, c, v: pk.nms_keep(b, c, v, 0.5, False),
                (spec((n, 4), dtype), spec((n,), dtype),
                 spec((n,), jnp.bool_)))
    raise AssertionError(name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["paged_decode", "paged_prefill_chunk",
                                  "paged_spec_verify", "flash_attention",
                                  "nms", "paged_window_decode",
                                  "paged_decode_opt1p3b",
                                  "paged_decode_trinity_full",
                                  "opt1p3b_wide_decode",
                                  "opt1p3b_wide_spec",
                                  "opt1p3b_wide_chunk",
                                  "paged_window_decode_trinity",
                                  "band_prefill_8192",
                                  "band_prefill_8192_window",
                                  "band_prefill_512",
                                  "band_prefill_8192_length_1",
                                  "moe_gmm_decode", "moe_gmm_prefill",
                                  "latent_decode_ling",
                                  "band_prefill_4096_ling",
                                  "kda_state_ling", "kda_state_tiny"])
def test_kernel_lowers_for_v5e(name, dtype, one_chip, compile_cache_off,
                               monkeypatch):
    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn, operands = _case(name, spec, jnp.dtype(dtype))
    compiled = jax.jit(fn).lower(*operands).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,kernel", [
    ("paged_decode", "mx_paged_attn"),
    ("paged_spec_verify", "mx_paged_attn"),
    ("flash_attention", "mx_flash_attn"),
    ("paged_window_decode", "mx_paged_attn"),
    ("paged_prefill_chunk", "mx_paged_attn"),
    ("paged_decode_opt1p3b", "mx_paged_attn"),
    ("band_prefill_8192_window", "mx_prefill_attn"),
    ("band_prefill_512", "mx_prefill_attn"),
    ("moe_gmm_decode", "mx_moe_gmm"),
    ("latent_decode_ling", "mx_mla_attn"),
    ("kda_state_ling", "mx_kda_state"),
])
def test_kernel_keeps_its_name_in_the_compiled_program(
        name, kernel, one_chip, compile_cache_off, monkeypatch):
    """The custom call of a named ``pallas_call`` is the instruction
    ``%<name>.<n>``: what a device trace's events are named by, and what
    ``benchmark/layer_metrics/paged_attn_ms_per_tick.py`` looks for."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn, operands = _case(name, spec, jnp.dtype("float32"))
    text = jax.jit(fn).lower(*operands).compile().as_text()
    calls = [ln.split(" = ", 1)[0].split("%")[-1] for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls and all(c.startswith(kernel) for c in calls), calls


# ---------------------------------------------------------------------------
# the decode step's KV pools: updated in place, never copied or converted
# ---------------------------------------------------------------------------
LAYERS = 4


def _pool_step_case(name, one_chip):
    """A 4-layer decode step as the models build it — ``write_kv`` into the
    layer's pool, the paged kernel on that pool — over per-layer pools with
    rows as wide as the cache would hold them on the described chip
    (``kvcache.pool_row_width``): ``(step, abstract operands, donated
    operands, elements of the smallest pool)``."""
    from mxnet_tpu.serving import kvcache

    (device,) = one_chip.device_set

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def pools(layers, shape):
        width = kvcache.pool_row_width(shape, jnp.float32, device)
        assert width == 128, width      # 64 grows to the lanes, 128 stays
        return tuple(spec(shape[:-1] + (width,)) for _ in range(layers))

    # pools (a (k layers, v layers) a group), tables and (group, layer in
    # it) by cache group, as a model whose ``layer_state`` has several paged
    # kinds receives them; group 1 is a window's ring
    if name == "opt1p3b_32x64":         # the OPT cell (769 pages), 32 x 64
        heads, kv_heads, dim = 32, 32, 64
        pool = (pools(LAYERS, (769, PAGE, kv_heads, dim)),)
        tables = (spec((SLOTS, 128), jnp.int32),)
        place = [(0, li) for li in range(LAYERS)]
        smallest = 769 * PAGE * kv_heads * 128
    else:                               # Trinity's: 8 x 128, a window group
        heads, kv_heads, dim = 48, 8, 128
        ring = 4096 // PAGE + 1
        # (a pool small enough for the chip's 128 MiB of VMEM is prefetched
        # there whole by the compiler: the cells' pools are not, nor this)
        pool = (pools(1, (4097, PAGE, kv_heads, dim)),
                pools(LAYERS - 1, (SLOTS * ring + 1, PAGE, kv_heads, dim)))
        tables = (spec((SLOTS, 512), jnp.int32),
                  spec((SLOTS, ring), jnp.int32))
        place = [(1, 0), (1, 1), (1, 2), (0, 0)]
        smallest = 4097 * PAGE * kv_heads * dim

    def step(q, k_new, v_new, pools, state, tables, lens, pages, offs):
        pools = list(pools)
        for grp, li in place:
            pools[grp] = k_pool, v_pool = kvcache.write_kv(
                *pools[grp], li, k_new, v_new, pages, offs)
            operands = (q, k_pool[li], v_pool[li], tables[grp], lens)
            q = q + (pk.ragged_window_attention(*operands, 4096,
                                                interpret=False) if grp
                     else pk.ragged_paged_attention(*operands,
                                                    interpret=False))
            k_new, v_new = k_new + 1.0, v_new + 1.0
        return q, tuple(pools), state

    rows = spec((SLOTS, kv_heads, dim))
    ints = spec((SLOTS,), jnp.int32)
    return (step, (spec((SLOTS, heads, dim)), rows, rows,
                   tuple((group, group) for group in pool), (), tables,
                   ints, ints, ints), (3, 4), smallest)


@pytest.mark.parametrize("name", ["opt1p3b_32x64", "trinity_8x128_window"])
def test_decode_step_updates_its_kv_pools_in_place(name, one_chip,
                                                   compile_cache_off):
    """One array a layer, its rows as wide as the chip holds row-major: the
    compiled step holds no copy of a pool (``%copy.*``: a pool converted to
    the kernel's layout and back), no slice of one
    (``%slice_bitcast_fusion.*``: a layer cut out of a stacked pool) and
    next to no temporaries. Stacked ``(L, P, page, KH, D)`` pools read 1.214
    GB of temporaries here at 32 x 64 (PR 30)."""
    step, operands, donate, smallest = _pool_step_case(name, one_chip)
    compiled = jax.jit(step, donate_argnums=donate).lower(
        *operands).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    big = {}
    for line in entry.splitlines():
        m = re.match(r"\s+(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
                     line)
        if m and np.prod([int(d) for d in m.group(2).split(",")]) >= smallest:
            big.setdefault(m.group(3), []).append(m.group(1))
    # a layer's K and V scatter, each a fusion that writes its operand
    fusions = big.pop("fusion")
    assert len(fusions) == 2 * LAYERS and not any(
        "slice" in n for n in fusions), fusions
    big.pop("parameter")
    big.pop("bitcast", None)            # a view, not a buffer
    assert not big, "pool-sized outputs: %r" % big
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    # in and out in the one layout the kernel reads: row-major
    for leaf in jax.tree_util.tree_leaves(
            (compiled.input_formats[0][3:5], compiled.output_formats[1:])):
        assert tuple(leaf.layout.major_to_minor) == (0, 1, 2, 3)


def test_ling_step_updates_its_latent_pool_and_slot_state_in_place(
        one_chip, compile_cache_off):
    """The Ling cell's shapes: a row into the latent pool, the latent launch
    on that pool (the same array as its K and its V operand), the one-token
    update of two layers' per-slot state through ``kda_state_step``'s kernel,
    one walk for both. The compiled step holds no copy of the pool (a pool
    with a head axis of 1 was converted whole on its way into the kernel, 461
    MB a tick: PERF.md section 6, PR 46) and no second state: the kernel's
    state result IS its state operand, which is what keeps a slot that holds
    no token untouched."""
    from mxnet_tpu.ops import kda
    from mxnet_tpu.serving import kvcache

    slots, heads, dim = 32, 32, 128

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, row, pool, state, table, lens, pages, offs, x):
        (pool,) = kvcache.write_rows((pool,), 0, row, pages, offs)
        seen = pk.paged_latent_attention(q, pool, table, lens, 512,
                                         192 ** -0.5, interpret=False)
        live, new = lens > 0, []
        walk = pk.kda_state_walk(live, interpret=False)
        for s_l, tail in state:
            mixed, tail = kda.short_conv_step(x, tail, jnp.ones((4, 3 * heads
                                                                 * dim)),
                                              live)
            qk = mixed[:, :heads * dim].reshape(slots, heads, dim)
            out, s_l = pk.kda_state_step(qk, qk, qk, -qk * qk, qk[..., 0],
                                         s_l, live, interpret=False,
                                         walk=walk)
            new.append((s_l, tail))
            x = x + out.sum()
        return seen, x, pool, tuple(new)

    state = tuple((spec((slots, heads, dim, dim)),
                   spec((slots, 3, 3 * heads * dim))) for _ in range(2))
    ints = spec((slots,), jnp.int32)
    pool = spec((slots * 352 + 1, PAGE, 640))
    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        spec((slots, heads, 576)), spec((slots, 576)), pool, state,
        spec((slots, 352), jnp.int32), ints, ints, ints,
        spec((slots, 3 * heads * dim))).compile()
    text = compiled.as_text()
    assert "mx_mla_attn" in text
    assert len(re.findall(r"%mx_kda_state[.\d]* = [^\n]*tpu_custom_call",
                          text)) == 2
    pool_bytes = (slots * 352 + 1) * PAGE * 640 * 4
    state_bytes = slots * heads * dim * dim * 4
    # under one layer's state, far under the pool: nothing is held twice
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes \
        < pool_bytes
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes \
        + 2 * state_bytes


# ---------------------------------------------------------------------------
# the served models' prefills: their row-wise passes loop over the row
# blocks a prompt reaches, and the loops' carries cost no memory
# ---------------------------------------------------------------------------
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _cell(config, reference):
    """``(model, abstract parameters, the configuration's engine block)`` of
    a benchmark configuration at its published widths: the tree its plain
    reference states (``param_specs``), as shapes."""
    from mxnet_tpu import serving

    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            reference + "_for_shapes",
            os.path.join(BENCH, "reference", reference + ".py"))
        ref = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ref)
    finally:
        sys.path.remove(BENCH)
    model = getattr(serving, cfg["factory"].rsplit(".", 1)[1])(
        **cfg["factory_kwargs"])
    specs = ref.param_specs(dict(model.cfg, param_dtype="bfloat16"))
    return model, specs, cfg["engine"]


@pytest.mark.parametrize("name,rung,most", [
    ("trinity", 8192, 1.58e9), ("ling", 4096, 0.87e9)],
    ids=["trinity_8192", "ling_4096"])
def test_prefill_of_the_largest_rung_takes_no_more_temporaries(
        name, rung, most, one_chip, compile_cache_off, monkeypatch):
    """Trinity's rung 8192 and Ling's rung 4096 at the cells' widths and
    pools, donated: the programs compile for the chip, hold the loops of the
    row-wise passes (``mxnet_tpu.ops.row_blocks``) and the band kernel, and
    take no more temporaries than before the passes followed the prompt's
    length (1.58 GB and 0.86 GB; PERF.md section 6, PR 48: the loops'
    carries are updated in place, and the pools' and the state's writes stay
    where they are made — written at the program's end they kept 0.3 and
    2.4 GB more alive)."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    moe.expert_layer.clear_cache()      # no trace made off the chip

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)

    model, specs, engine = _cell(*{
        "trinity": ("trinity_large_preview_ep8", "afmoe_share"),
        "ling": ("ling_3_flash_vl_ep4", "ling_share")}[name])
    params = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), specs,
        is_leaf=lambda x: hasattr(x, "init"))
    slots, page = engine["num_slots"], engine["page_size"]
    rows, count = spec((rung,), jnp.int32), spec((), jnp.int32)
    if name == "trinity":
        def group(name, kind):
            layers = tuple(spec((engine["num_pages"][name], page, 8, 128))
                           for st in model.layer_state if st[0] == kind)
            return layers, layers       # its K layers, its V layers

        cache = ((group("full", "paged"), group("window", "ring")), ())

        def prefill(p, tokens, n, pools, state, full, window, offs):
            return model.prefill(p, tokens, n, pools, state, (full, window),
                                 offs)

        operands = (rows, count) + cache + (rows, rows, rows)
    else:
        pages = slots * (engine["max_seq_len"] // page) + 1
        cache = ((spec((pages, page, 640)),),
                 tuple((spec((slots, 32, 128, 128)),
                        spec((slots, 3, 12288)))
                       for kind in model.cfg["layer_types"] if kind == "kda"))

        def prefill(p, tokens, n, latent, state, pages, offs, slot):
            return model.prefill(p, tokens, n, latent, state, pages, offs,
                                 slot=slot)

        operands = (rows, count) + cache + (rows, rows, count)
    try:
        compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
            params, *operands).compile()
    finally:
        moe.expert_layer.clear_cache()
    text = compiled.as_text()
    assert "mx_prefill_attn" in text and "mx_moe_gmm" in text
    assert text.count(" while(") >= 6 * model.num_layers
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < most
    # the pools (and the slots' state) are updated in place
    assert memory.alias_size_in_bytes >= sum(
        int(np.prod(x.shape)) * 4 for x in jax.tree_util.tree_leaves(cache))
