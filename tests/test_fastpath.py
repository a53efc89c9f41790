"""mxnet_tpu.fastpath tests — ISSUE-5 acceptance.

Covers: bit-identical parity of the fused tree-apply vs the per-parameter
loop (fp32 + fp16/bf16 master-weight multi-precision), the ≥10× dispatch
reduction, the donation-safety guard (stale NDArray raises), gradient
bucketing (plan shapes, pack/unpack round-trip, pushpull parity incl.
odd sizes / mixed dtypes / chaos), the batched Trainer exchange, the
``update_on_kvstore`` fused path, ``ignore_stale_grad`` semantics, the
``MXNET_FASTPATH=0`` escape hatch, and the persistent compile cache
hitting on a second process.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, fastpath, gluon, nd, telemetry
from mxnet_tpu import optimizer as opt
from mxnet_tpu.fastpath import bucketing
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.resilience import chaos

from conftest import subprocess_env

SHAPES = [(4, 3), (7,), (2, 2, 2), (5, 1), (3,)]


def _param_bytes(arrs):
    return [np.asarray(a._data).tobytes() for a in arrs]


def _run_updates(path, name, dtype=jnp.float32, steps=5, shapes=SHAPES,
                 **kw):
    """Drive one optimizer over several parameters via the per-param loop
    or the fused tree-apply; returns (weight bytes, states)."""
    mx.random.seed(7)
    rs = np.random.RandomState(0)
    wvals = [rs.randn(*s).astype(np.float32) for s in shapes]
    gvals = [[rs.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(steps)]
    o = opt.create(name, learning_rate=0.05, wd=0.01, **kw)
    upd = opt.get_updater(o)
    ws = [NDArray(jnp.asarray(wvals[i], dtype), mx.cpu())
          for i in range(len(shapes))]
    for s in range(steps):
        gs = [NDArray(jnp.asarray(gvals[s][i], dtype), mx.cpu())
              for i in range(len(shapes))]
        if path == "fused":
            fastpath.apply_updater(
                upd, [(i, gs[i], ws[i]) for i in range(len(ws))])
        else:
            for i in range(len(ws)):
                upd(i, gs[i], ws[i])
    return _param_bytes(ws), upd.states


# ---------------------------------------------------------------------------
# fused tree-apply: bit-identical parity (the tentpole guarantee)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kw", [
    ("sgd", {}),
    ("sgd", {"momentum": 0.9}),
    ("adam", {}),
])
def test_fused_apply_bit_identical_fp32(name, kw):
    a, _ = _run_updates("perparam", name, **kw)
    b, _ = _run_updates("fused", name, **kw)
    assert a == b, "fused tree-apply diverged from the per-param loop"


@pytest.mark.parametrize("name,kw", [
    ("nag", {"momentum": 0.9}), ("rmsprop", {"centered": True}),
    ("rmsprop", {}), ("ftrl", {}), ("adadelta", {}), ("adagrad", {}),
    ("adamax", {}), ("ftml", {}), ("nadam", {}), ("sgld", {}),
    ("signum", {"momentum": 0.9}), ("signsgd", {}),
    ("dcasgd", {"momentum": 0.9}), ("lbsgd", {"momentum": 0.9}),
    ("test", {}),
])
def test_fused_apply_bit_identical_all_optimizers(name, kw):
    """Every registered optimizer rides the fused path for free — the
    kernel protocol makes divergence structurally impossible, this pins
    it."""
    a, _ = _run_updates("perparam", name, **kw)
    b, _ = _run_updates("fused", name, **kw)
    assert a == b, name


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("name,kw", [("sgd", {"momentum": 0.9}),
                                     ("adam", {})])
def test_fused_apply_bit_identical_master_weight(name, kw, dtype):
    """fp16/bf16 weights with multi_precision: fused in-trace master-weight
    handling matches update_multi_precision bit for bit."""
    a, _ = _run_updates("perparam", name, dtype=dtype,
                        multi_precision=True, **kw)
    b, _ = _run_updates("fused", name, dtype=dtype,
                        multi_precision=True, **kw)
    assert a == b


@pytest.mark.parametrize("path", ["fused", "perparam"])
def test_multi_precision_migrates_pre_master_states(path):
    """A bf16 optimizer state saved BEFORE multi_precision covered bfloat16
    is a plain (m, v) tuple; restoring it must adopt an fp32 master instead
    of mis-unpacking the moments as (master, base)."""
    o = opt.create("adam", learning_rate=0.01, multi_precision=True)
    upd = opt.get_updater(o)
    w = NDArray(jnp.asarray(np.ones((4, 3), np.float32), jnp.bfloat16),
                mx.cpu())
    g = NDArray(jnp.asarray(np.full((4, 3), 0.5, np.float32), jnp.bfloat16),
                mx.cpu())
    # pre-migration layout: create_state on the raw weight (no master pair)
    upd.states[0] = o.create_state(0, w)
    upd.states_synced[0] = True
    if path == "fused":
        fastpath.apply_updater(upd, [(0, g, w)])
    else:
        upd(0, g, w)
    master, base = upd.states[0]  # migrated to the pair layout
    assert master.dtype == jnp.float32 and master.shape == w.shape
    assert len(base) == 2  # adam (m, v) kept as the base state
    assert np.all(np.asarray(w.asnumpy(), np.float32) < 1.0)  # stepped


def test_multi_precision_does_not_mistake_fp32_moments_for_master():
    """An fp32 Adam run's (m, v) state resumed onto bf16-cast weights is
    structurally a 2-tuple of fp32 weight-shaped arrays — it must be
    wrapped as the BASE of a fresh master pair, never unpacked as
    (master, base) with the first moment installed as the weight."""
    from mxnet_tpu.optimizer import ensure_mp_state

    o = opt.create("adam", learning_rate=0.01, multi_precision=True)
    w = NDArray(jnp.asarray(np.full((4, 3), 0.75, np.float32),
                            jnp.bfloat16), mx.cpu())
    m = jnp.full((4, 3), 1e-8, jnp.float32)
    v = jnp.full((4, 3), 1e-8, jnp.float32)
    state = ensure_mp_state(o, 0, w, (m, v))
    master, base = state
    # the master is the WEIGHT, not the near-zero first moment
    np.testing.assert_allclose(np.asarray(master), 0.75, rtol=1e-2)
    assert base is not None and len(base) == 2
    # and a genuine pair passes through untouched
    assert ensure_mp_state(o, 0, w, state) is state


def test_fused_apply_rejects_incapable_optimizer():
    class NoKernel(opt.Optimizer):
        pass

    o = NoKernel()
    w = nd.array(np.ones((2, 2), np.float32))
    g = nd.array(np.ones((2, 2), np.float32))
    with pytest.raises(fastpath.FusedApplyError):
        fastpath.fused_apply(o, [0], [g], [w], [None])


# ---------------------------------------------------------------------------
# dispatch accounting: >= 10x fewer update dispatches per step
# ---------------------------------------------------------------------------

def _mlp(n_layers=6):
    net = gluon.nn.Sequential()
    for _ in range(n_layers - 1):
        net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize()
    net(nd.array(np.zeros((2, 8), np.float32)))
    return net


def _train_mlp(steps=3):
    mx.random.seed(0)  # identical init across the legacy/fused runs
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    loss_fn = gluon.loss.L2Loss()
    rs = np.random.RandomState(1)
    for s in range(steps):
        x = nd.array(rs.rand(2, 8).astype(np.float32))
        y = nd.array(rs.rand(2, 4).astype(np.float32))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(2)
    # positional keys: the global block-name counter differs across nets
    return [p.data().asnumpy().tobytes()
            for p in net.collect_params().values()]


def test_dispatches_per_step_10x_reduction(monkeypatch):
    """ISSUE-5 acceptance: ≥10× fewer optimizer-update dispatches per step
    on an MLP with ≥10 parameters (12 here: 6 layers × weight+bias)."""
    steps = 3
    monkeypatch.setenv("MXNET_FASTPATH", "0")
    pp0 = telemetry.OPT_DISPATCHES.value(path="perparam")
    _train_mlp(steps)
    perparam = telemetry.OPT_DISPATCHES.value(path="perparam") - pp0
    monkeypatch.setenv("MXNET_FASTPATH", "1")
    f0 = telemetry.OPT_DISPATCHES.value(path="fused")
    _train_mlp(steps)
    fused = telemetry.OPT_DISPATCHES.value(path="fused") - f0
    assert fused == steps  # ONE dispatch per step
    assert perparam / fused >= 10, (perparam, fused)


def test_trainer_fastpath_matches_legacy_bitwise(monkeypatch):
    """MXNET_FASTPATH=0 escape hatch and the fused route train to the SAME
    bits."""
    monkeypatch.setenv("MXNET_FASTPATH", "0")
    legacy = _train_mlp()
    monkeypatch.setenv("MXNET_FASTPATH", "1")
    fused = _train_mlp()
    assert legacy == fused


# ---------------------------------------------------------------------------
# ignore_stale_grad semantics (regression: previously silently ignored)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fast", ["1", "0"])
def test_trainer_ignore_stale_grad(monkeypatch, fast):
    monkeypatch.setenv("MXNET_FASTPATH", fast)
    net = _mlp(2)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    loss_fn = gluon.loss.L2Loss()
    x = nd.array(np.ones((2, 8), np.float32))
    y = nd.array(np.ones((2, 4), np.float32))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(2)
    # no new backward: every grad is stale now
    with pytest.raises(UserWarning):
        trainer.step(2)
    before = {k: p.data().asnumpy().tobytes()
              for k, p in net.collect_params().items()}
    trainer.step(2, ignore_stale_grad=True)  # skips, doesn't corrupt
    after = {k: p.data().asnumpy().tobytes()
             for k, p in net.collect_params().items()}
    assert before == after


# ---------------------------------------------------------------------------
# donation-safety guard
# ---------------------------------------------------------------------------

def test_donation_invalidates_stale_handles(monkeypatch):
    """With donation forced on, an NDArray still wrapping the pre-step
    buffer raises on use instead of reading garbage."""
    monkeypatch.setenv("MXNET_FASTPATH_DONATE", "1")
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    upd = opt.get_updater(o)
    w = nd.array(np.ones((4, 4), np.float32))
    g = nd.array(np.ones((4, 4), np.float32))
    stale = NDArray(w._data, w.context)  # aliases the pre-step buffer
    fastpath.apply_updater(upd, [(0, g, w)])
    np.asarray(w.asnumpy())  # the live handle moved to the new buffer
    with pytest.raises(Exception, match="[Dd]eleted"):
        stale.asnumpy()


def test_no_donation_keeps_old_buffers(monkeypatch):
    monkeypatch.setenv("MXNET_FASTPATH_DONATE", "0")
    o = opt.create("sgd", learning_rate=0.1)
    upd = opt.get_updater(o)
    w = nd.array(np.ones((4, 4), np.float32))
    g = nd.array(np.ones((4, 4), np.float32))
    stale = NDArray(w._data, w.context)
    fastpath.apply_updater(upd, [(0, g, w)])
    np.testing.assert_allclose(stale.asnumpy(), 1.0)  # untouched


def test_donation_skipped_for_duplicated_buffers(monkeypatch):
    """DCASGD's `prev` state starts as the weight buffer itself — duplicate
    donation must be detected and skipped, not crash."""
    monkeypatch.setenv("MXNET_FASTPATH_DONATE", "1")
    o = opt.create("dcasgd", learning_rate=0.1, momentum=0.9)
    upd = opt.get_updater(o)
    w = nd.array(np.ones((3, 3), np.float32))
    g = nd.array(np.ones((3, 3), np.float32))
    fastpath.apply_updater(upd, [(0, g, w)])
    w.asnumpy()  # live handle fine; no duplicate-donation error raised


# ---------------------------------------------------------------------------
# gradient bucketing
# ---------------------------------------------------------------------------

def test_bucket_plan_shapes_mixed_dtypes_and_solo():
    cap = 64  # bytes, tiny so the layout is forced
    leaves = [jnp.ones((4,), jnp.float32),     # 16 B
              jnp.ones((3,), jnp.float32),     # 12 B
              jnp.ones((5,), jnp.float16),     # 10 B
              jnp.ones((100,), jnp.float32),   # 400 B >= cap: solo
              jnp.ones((7,), jnp.float16),     # 14 B
              jnp.ones((2, 3), jnp.float32)]   # 24 B
    plan = bucketing.plan_for(leaves, cap)
    assert plan is not None
    flat = [i for b in plan.buckets for i in b]
    assert sorted(flat + plan.solo) == list(range(len(leaves)))
    assert 3 in plan.solo  # over-cap leaf rides alone
    for b in plan.buckets:
        dts = {str(leaves[i].dtype) for i in b}
        assert len(dts) == 1  # buckets never mix dtypes
        assert sum(leaves[i].nbytes for i in b) <= cap

    packed = plan.pack(leaves)
    assert len(packed) == plan.n_out
    out = plan.unpack(packed)
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_bucket_plan_disabled_or_degenerate():
    assert bucketing.plan_for([jnp.ones((4,))], 1024) is None  # one leaf
    assert bucketing.plan_for([jnp.ones((4,)), jnp.ones((4,))], 0) is None
    # nothing coalesces: every dtype has one small leaf
    assert bucketing.plan_for([jnp.ones((4,), jnp.float32),
                               jnp.ones((4,), jnp.float16)], 1024) is None


def _two_copy_values(rs, shapes_dtypes):
    """Per-key 2-device copy lists + expected elementwise sums."""
    devs = jax.devices()[:2]
    values, expect = [], []
    for shape, dt in shapes_dtypes:
        copies = [rs.rand(*shape).astype(dt) for _ in devs]
        expect.append(sum(c.astype(np.float64) for c in copies))
        values.append([NDArray(jax.device_put(jnp.asarray(c), d), mx.cpu())
                       for c, d in zip(copies, devs)])
    return values, expect


@pytest.mark.parametrize("bucket_mb", ["0", "1"])
def test_pushpull_multi_bucketing_parity(monkeypatch, bucket_mb):
    """Bucketed and unbucketed fused pushpull produce identical sums over
    odd sizes and mixed dtypes (bit-identical: sums are elementwise)."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", bucket_mb)
    rs = np.random.RandomState(3)
    shapes_dtypes = [((7,), np.float32), ((3, 5), np.float32),
                     ((2, 2, 2), np.float32), ((11,), np.float16),
                     ((1,), np.float32), ((5,), np.float16)]
    values, expect = _two_copy_values(rs, shapes_dtypes)
    kv = mx.kv.create("tpu")
    keys = list(range(len(values)))
    for k, v in zip(keys, values):
        kv.init(k, nd.zeros(v[0].shape, dtype=v[0].dtype))
    packs = []
    orig_pack = bucketing.Plan.pack
    monkeypatch.setattr(bucketing.Plan, "pack",
                        lambda self, leaves: packs.append(1)
                        or orig_pack(self, leaves))
    outs = [[nd.zeros(v[0].shape, dtype=v[0].dtype) for _ in v]
            for v in values]
    kv.pushpull_multi(keys, values, outs)
    if bucket_mb != "0":
        assert packs, "bucketing did not engage on the multi-copy exchange"
    else:
        assert not packs
    for o_list, exp, (shape, dt) in zip(outs, expect, shapes_dtypes):
        for o in o_list:
            np.testing.assert_allclose(
                o.asnumpy().astype(np.float64), exp,
                rtol=1e-2 if dt == np.float16 else 1e-6)


def test_pushpull_multi_bucketed_chaos_bit_identical(monkeypatch):
    """ISSUE-5 acceptance: the retried aggregate stays bit-identical under
    injected faults WITH bucketing enabled (pack/reduce/unpack all inside
    the pure phase, commit outside)."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "1")
    rs = np.random.RandomState(11)
    shapes_dtypes = [((7,), np.float32), ((3, 5), np.float32),
                     ((9,), np.float32)]

    def exchange():
        values, _ = _two_copy_values(np.random.RandomState(11),
                                     shapes_dtypes)
        kv = mx.kv.create("tpu")
        keys = list(range(len(values)))
        for k, v in zip(keys, values):
            kv.init(k, nd.zeros(v[0].shape, dtype=v[0].dtype))
        outs = [[nd.zeros(v[0].shape, dtype=v[0].dtype)] for v in values]
        for _ in range(6):
            kv.pushpull_multi(keys, values, outs)
        return [o[0].asnumpy().tobytes() for o in outs]

    clean = exchange()
    with chaos.active("seed=5,site=kvstore.*,p=0.3"):
        faulted = exchange()
        injected = chaos.injected_counts()
    assert any(s.startswith("kvstore.") for s in injected), injected
    assert clean == faulted


def test_chaos_training_bit_identical_with_bucketing(monkeypatch):
    """The PR-4 end-to-end chaos training acceptance, re-run with the
    bucketing knob enabled."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "1")
    from test_resilience import test_chaos_training_bit_identical

    test_chaos_training_bit_identical()


# ---------------------------------------------------------------------------
# batched gradient exchange (Trainer / base store / update_on_kvstore)
# ---------------------------------------------------------------------------

def test_trainer_allreduce_grads_single_pushpull():
    """allreduce_grads batches EVERY gradient through one pushpull_multi
    call instead of per-param push/pull."""
    from mxnet_tpu.kvstore import _T_OPS

    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="tpu")
    loss_fn = gluon.loss.L2Loss()
    x = nd.array(np.ones((2, 8), np.float32))
    y = nd.array(np.ones((2, 4), np.float32))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    p0 = _T_OPS.value(op="push")
    m0 = _T_OPS.value(op="pushpull_multi")
    trainer.allreduce_grads()
    assert _T_OPS.value(op="push") == p0  # zero per-key pushes
    assert _T_OPS.value(op="pushpull_multi") == m0 + 1  # ONE batched call


def test_escape_hatch_gates_the_exchange_plane(monkeypatch):
    """MXNET_FASTPATH=0 restores per-key push/pull too — an operator
    bisecting an exchange bug must be able to rule out the batched path."""
    from mxnet_tpu.kvstore import _T_OPS

    kv = mx.kv.create("tpu")
    monkeypatch.setenv("MXNET_FASTPATH", "0")
    assert not kv._can_fuse_pushpull()
    net = _mlp(2)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="tpu")
    loss_fn = gluon.loss.L2Loss()
    with autograd.record():
        loss = loss_fn(net(nd.array(np.ones((2, 8), np.float32))),
                       nd.array(np.ones((2, 4), np.float32)))
    loss.backward()
    m0 = _T_OPS.value(op="pushpull_multi")
    p0 = _T_OPS.value(op="push")
    trainer.step(2)
    assert _T_OPS.value(op="pushpull_multi") == m0  # batched path off
    assert _T_OPS.value(op="push") > p0             # legacy per-key on


def test_base_store_pushpull_multi_matches_push_pull():
    """The host ('local') store's batched exchange equals its per-key
    push+pull sequence."""
    rs = np.random.RandomState(5)
    shapes = [(4,), (2, 3), (5,)]
    vals = [rs.rand(*s).astype(np.float32) for s in shapes]

    def drive(batched):
        kv = mx.kv.create("local")
        outs = []
        for i, (s, v) in enumerate(zip(shapes, vals)):
            kv.init(i, nd.zeros(s))
            outs.append(nd.zeros(s))
        if batched:
            kv.pushpull_multi(list(range(len(shapes))),
                              [nd.array(v) for v in vals], outs)
        else:
            for i, v in enumerate(vals):
                kv.push(i, nd.array(v))
                kv.pull(i, out=outs[i])
        return [o.asnumpy().tobytes() for o in outs]

    assert drive(True) == drive(False)


def test_update_params_on_kvstore_paths_agree(monkeypatch):
    """model._update_params_on_kvstore: the fused pushpull_update_multi
    exchange and the legacy per-key push/pull produce the same weights."""
    from mxnet_tpu import model as model_mod

    rs = np.random.RandomState(9)
    shapes = [(4, 3), (7,), (2, 5)]
    wvals = [rs.randn(*s).astype(np.float32) for s in shapes]
    gvals = [[rs.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(3)]

    def drive(fast):
        monkeypatch.setenv("MXNET_FASTPATH", fast)
        kv = mx.kv.create("local")
        params = [nd.array(w) for w in wvals]
        for i, p in enumerate(params):
            kv.init(i, p)
        kv.set_optimizer(opt.create("sgd", learning_rate=0.05,
                                    momentum=0.9))
        for step in range(3):
            grads = [nd.array(g) for g in gvals[step]]
            model_mod._update_params_on_kvstore(
                [[p] for p in params], [[g] for g in grads], kv,
                ["p%d" % i for i in range(len(params))])
        return [p.asnumpy().tobytes() for p in params]

    assert drive("1") == drive("0")


def test_multi_position_lr_scheduler_falls_back(monkeypatch):
    """lr_scheduler reads the optimizer-global num_update, which is
    iteration-order-sensitive across device positions — with >1 positions
    the fused grouping must fall back so MXNET_FASTPATH=1 stays
    bitwise-equal to =0."""
    from mxnet_tpu import lr_scheduler
    from mxnet_tpu import model as model_mod

    sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
    assert not fastpath.supports(
        opt.create("sgd", learning_rate=0.1, lr_scheduler=sched),
        n_positions=2)

    rs = np.random.RandomState(21)
    wvals = [[rs.randn(4, 3).astype(np.float32) for _ in range(2)]
             for _ in range(2)]
    gvals = [[[rs.randn(4, 3).astype(np.float32) for _ in range(2)]
              for _ in range(2)] for _ in range(4)]

    def drive(fast):
        monkeypatch.setenv("MXNET_FASTPATH", fast)
        params = [[nd.array(c) for c in w] for w in wvals]
        sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
        updater = opt.get_updater(opt.create(
            "sgd", learning_rate=0.1, momentum=0.9, lr_scheduler=sched))
        for step in range(4):
            grads = [[nd.array(c) for c in g] for g in gvals[step]]
            model_mod._update_params(params, grads, updater, 2)
        return [c.asnumpy().tobytes() for p in params for c in p]

    assert drive("1") == drive("0")


@pytest.mark.parametrize("name", ["nadam", "sgld", "adam"])
def test_update_params_multi_device_paths_agree(monkeypatch, name):
    """num_device > 1: optimizers with an order-sensitive host prologue
    (Nadam's m_schedule, SGLD's rng stream) must fall back to the legacy
    ordering so MXNET_FASTPATH=1 stays bitwise-equal to =0; order-free
    optimizers (adam) keep the fused path."""
    from mxnet_tpu import model as model_mod

    rs = np.random.RandomState(13)
    shapes = [(4, 3), (7,)]
    wvals = [[rs.randn(*s).astype(np.float32) for _ in range(2)]
             for s in shapes]
    gvals = [[[rs.randn(*s).astype(np.float32) for _ in range(2)]
              for s in shapes] for _ in range(3)]

    def drive(fast):
        mx.random.seed(3)  # sgld noise stream must restart identically
        monkeypatch.setenv("MXNET_FASTPATH", fast)
        params = [[nd.array(c) for c in w] for w in wvals]
        updater = opt.get_updater(opt.create(name, learning_rate=0.01))
        for step in range(3):
            grads = [[nd.array(c) for c in g] for g in gvals[step]]
            model_mod._update_params(params, grads, updater, 2)
        return [c.asnumpy().tobytes() for p in params for c in p]

    assert drive("1") == drive("0")


def test_update_params_host_updater_paths_agree(monkeypatch):
    """model._update_params (host-side updater): fused vs legacy bitwise."""
    from mxnet_tpu import model as model_mod

    rs = np.random.RandomState(4)
    shapes = [(4, 3), (7,), (2, 5)]
    wvals = [rs.randn(*s).astype(np.float32) for s in shapes]
    gvals = [[rs.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(3)]

    def drive(fast):
        monkeypatch.setenv("MXNET_FASTPATH", fast)
        params = [nd.array(w) for w in wvals]
        updater = opt.get_updater(opt.create("adam", learning_rate=0.01))
        for step in range(3):
            grads = [nd.array(g) for g in gvals[step]]
            model_mod._update_params([[p] for p in params],
                                     [[g] for g in grads], updater, 1)
        return [p.asnumpy().tobytes() for p in params]

    assert drive("1") == drive("0")


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

_CACHE_PROBE = r"""
import json, sys
import mxnet_tpu as mx
import jax, jax.numpy as jnp
from mxnet_tpu.fastpath import cache
f = jax.jit(lambda x: x * 3 + 1)
f(jnp.ones((16, 16))).block_until_ready()
hits, misses = cache.cache_counts()
print(json.dumps({"hits": hits, "misses": misses,
                  "configured": cache.configured()}))
"""


_CACHE_DIR_PROBE = r"""
import json
import mxnet_tpu as mx
import jax
from mxnet_tpu.fastpath import cache
at_import = jax.config.jax_compilation_cache_dir
wired_at_import = cache.configured()
print(json.dumps({"at_import": at_import, "wired_at_import": wired_at_import,
                  "configure": cache.configure(),
                  "after": jax.config.jax_compilation_cache_dir,
                  "explicit": cache.configure("/explicit/path"),
                  "after_explicit": jax.config.jax_compilation_cache_dir,
                  "default_dir": cache.DEFAULT_DIR}))
"""


@pytest.mark.parametrize("env_dir", ["/x", None])
def test_compile_cache_directory_is_placed_from_outside(env_dir):
    """The cache-path rule: with JAX_COMPILATION_CACHE_DIR set no code path
    sets another directory (not at import, not configure(), not
    configure(path)); unset, import wires nothing and configure() uses the
    one fixed in-checkout path."""
    env = subprocess_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _CACHE_DIR_PROBE],
                         capture_output=True, text=True, env=env,
                         timeout=300, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    if env_dir:
        assert {got[k] for k in ("at_import", "wired_at_import", "configure",
                                 "after", "explicit", "after_explicit")} \
            == {env_dir}
    else:
        assert got["at_import"] is None and got["wired_at_import"] is None
        assert got["configure"] == got["after"] == got["default_dir"] \
            == os.path.join(repo, ".jax_cache")
        assert got["explicit"] == got["after_explicit"] == "/explicit/path"


@pytest.mark.slow
def test_compile_cache_hits_on_second_process(tmp_path):
    """ISSUE-5 acceptance: a restarted process deserializes executables
    from JAX_COMPILATION_CACHE_DIR instead of recompiling."""
    env = subprocess_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path))

    def probe():
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                             capture_output=True, text=True, env=env,
                             timeout=300, cwd=os.path.dirname(
                                 os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = probe()
    assert first["configured"] == str(tmp_path)
    if first["misses"] == 0 and first["hits"] == 0:
        pytest.skip("backend does not report compilation-cache events")
    assert first["misses"] > 0
    entries = list(tmp_path.iterdir())
    assert entries, "first process wrote no cache entries"
    second = probe()
    assert second["hits"] > 0, second
